"""Reduce a profiler trace (``.xplane.pb``) to what the benchmark reports.

* ``busy_s``: the union of the intervals in which an operation ran on the
  device, inside the traced window, averaged over the devices used;
* ``window_s``: the traced window, the host span named ``WINDOW`` that the
  harness puts around its measured loop;
* ``device_ops``: device time by operation, longest first, each named by
  its HLO instruction and result shape (``%fusion.206 = f32[1401,16,8,128]``);
  loops and calls, which contain other operations, are left out of it;
* ``idle_gaps``: idle device time by what the host was doing meanwhile, as
  the harness's own spans (``bench.*``) name it;
* ``collective_s``: the union of the intervals in which a collective
  operation ran (all-reduce, all-gather, reduce-scatter, collective-permute,
  all-to-all, or their ``-start``/``-done`` halves, by instruction name or
  opcode), inside the window, averaged over the devices used.

``device_ops`` and ``idle_gaps`` are what a run prints as its breakdown.
Three more keys are for the per-layer readers (``bench/metrics``), and grow
with the trace, so a run never prints them:

* ``modules``: for each XLA module (``jit_decode_multi``, named without
  the ``(<id>)`` suffix), ``seconds``, its events' device time inside the
  window, and ``count``, its events that overlap the window, both averaged
  over the devices used;
* ``spans``: for each program span (``engine.*``) that overlaps the window,
  ``count``, ``seconds`` inside the window, and ``args``, the sum of each
  numeric argument over its events (``k`` of ``engine.decode_dispatch``:
  the decode steps dispatched);
* ``ops``: device time of every operation that ``device_ops`` ranks, not
  only the longest, averaged over the devices used.  A custom call (a
  Pallas or Mosaic kernel) also counts under its kernel's name
  (``kernel_name`` of its HLO text where it carries one, else its
  instruction's name without ``%`` and the ``.<n>`` suffix, which Pallas
  sets to the kernel's name), so its time shows twice in the sum over all
  keys.

Device planes are those named ``/device:TPU:<n>``; on each, operations are
the events of the line ``XLA Ops`` and programs those of ``XLA Modules``.
Host spans are events of any line of the plane ``/host:CPU``.  ProfileData
puts every plane's events on one clock, so device and host intervals
compare directly.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import warnings
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
HOST_PREFIX = "bench."
SPAN_PREFIX = "engine."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
IDLE = "(no bench span)"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVE = re.compile(r"(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")
KERNEL_NAME = re.compile(r'kernel_name="([^"]+)"')

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """``%fusion.206 = f32[1401,16,8,128]{...} fusion(...)`` ->
    ``%fusion.206 = f32[1401,16,8,128]``."""
    return hlo.split("{", 1)[0].split(" fusion(", 1)[0].strip()


def module_name(name: str) -> str:
    """``jit_decode_multi(15611547856741370565)`` -> ``jit_decode_multi``."""
    return name.split("(", 1)[0]


def kernel_name(hlo: str) -> Optional[str]:
    """The kernel a custom call runs, by the name its HLO text carries;
    None for any other operation, and for a custom call that carries none
    (an instruction named ``custom-call``, as XLA's own are)."""
    if " custom-call(" not in hlo:
        return None
    named = KERNEL_NAME.search(hlo)
    if named:
        return named.group(1)
    name = re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].strip().lstrip("%"))
    return None if name == "custom-call" else name


def _numeric(ev) -> Dict[str, float]:
    """An event's numeric arguments (its profiler stats)."""
    with warnings.catch_warnings():
        # the first read of an event's stats warns about their builtin type
        warnings.simplefilter("ignore", DeprecationWarning)
        stats = list(ev.stats)
    return {k: v for k, v in stats
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def _container(hlo: str) -> bool:
    rhs = hlo.split(" = ", 1)[-1]
    return any(f" {c}(" in rhs or rhs.startswith(f"{c}(") for c in CONTAINERS)


def _collective(hlo: str) -> bool:
    """Whether the instruction's name or opcode (the text before its
    operands) names a collective."""
    return bool(COLLECTIVE.search(hlo.split("(", 1)[0]))


def _union(iv: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class _Spans:
    """The harness's host spans, sorted, for finding the one that overlaps
    a gap most.  They follow one another without overlapping, so the spans
    a gap meets end after its start and begin before its end."""

    def __init__(self, spans: Sequence[Tuple[float, float, str]]):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]

    def label(self, gap: Interval) -> str:
        best, best_len = IDLE, 0.0
        i = bisect.bisect_left(self.starts, gap[1]) - 1
        while i >= 0 and self.spans[i][1] > gap[0]:
            s, e, name = self.spans[i]
            ov = min(e, gap[1]) - max(s, gap[0])
            if ov > best_len:
                best, best_len = name, ov
            i -= 1
        return best


def reduce(pd, n_devices: int = 1, top: int = 10) -> Optional[Dict]:
    """Reduce a ``jax.profiler.ProfileData``; None where no device
    operation or no window span is found."""
    window: Optional[Interval] = None
    spans: List[Tuple[float, float, str]] = []
    program: List[Tuple[float, float, str, Dict[str, float]]] = []
    devices: List[List[Tuple[float, float, str]]] = []
    modules: List[List[Tuple[float, float, str]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith(HOST_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
                    elif ev.name.startswith(SPAN_PREFIX):
                        program.append((ev.start_ns, ev.end_ns, ev.name,
                                        _numeric(ev)))
        elif plane.name.startswith("/device:TPU:"):
            ops = [(ev.start_ns, ev.end_ns, ev.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append(ops)
                modules.append([(ev.start_ns, ev.end_ns, ev.name)
                                for line in plane.lines
                                if line.name == MODULES_LINE
                                for ev in line.events])
    if window is None or not devices:
        return None
    devices = devices[:n_devices]
    lo, hi = window
    by_op: Dict[str, float] = defaultdict(float)
    by_kernel: Dict[str, float] = defaultdict(float)
    by_host: Dict[str, float] = defaultdict(float)
    host = _Spans(spans)
    busy_ns = coll_ns = 0.0
    for ops in devices:
        coll = []
        for s, e, name in ops:
            if e > lo and s < hi and not _container(name):
                by_op[op_name(name)] += min(e, hi) - max(s, lo)
                kernel = kernel_name(name)
                if kernel:
                    by_kernel[kernel] += min(e, hi) - max(s, lo)
                if _collective(name):
                    coll.append((s, e))
        busy = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        coll_ns += sum(e - s for s, e in _union(_clip(coll, lo, hi)))
        for g in _gaps(busy, lo, hi):
            by_host[host.label(g)] += g[1] - g[0]
    nd = len(devices)
    if busy_ns <= 0:
        return None

    def ranked(d: Dict[str, float]) -> List[List]:
        return [[k, v / nd / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_ns / nd / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": ranked(by_op), "idle_gaps": ranked(by_host),
            "collective_s": coll_ns / nd / 1e9,
            "modules": _modules(modules[:nd], lo, hi),
            "spans": _program_spans(program, lo, hi),
            "ops": {k: v / nd / 1e9 for d in (by_op, by_kernel)
                    for k, v in d.items()}}


def _modules(planes, lo: float, hi: float) -> Dict[str, Dict[str, float]]:
    """Device seconds and events of each module inside ``[lo, hi)``,
    averaged over the planes."""
    ns: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    for events in planes:
        for s, e, name in events:
            if e > lo and s < hi:
                ns[module_name(name)] += min(e, hi) - max(s, lo)
                count[module_name(name)] += 1
    n = len(planes)
    return {k: {"seconds": v / n / 1e9, "count": count[k] / n}
            for k, v in ns.items()}


def _program_spans(program, lo: float, hi: float) -> Dict[str, Dict]:
    """Count, seconds inside ``[lo, hi)`` and summed numeric arguments of
    each program span that overlaps it."""
    out: Dict[str, Dict] = {}
    for s, e, name, args in program:
        if e > lo and s < hi:
            sp = out.setdefault(name, {"count": 0, "seconds": 0.0,
                                       "args": defaultdict(float)})
            sp["count"] += 1
            sp["seconds"] += (min(e, hi) - max(s, lo)) / 1e9
            for k, v in args.items():
                sp["args"][k] += v
    for sp in out.values():
        sp["args"] = dict(sp["args"])
    return out


def reduce_dir(trace_dir: str, n_devices: int = 1) -> Optional[Dict]:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(trace_dir)), n_devices)

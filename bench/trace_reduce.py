"""Reduce a profiler trace (``.xplane.pb``) to what the benchmark reports.

* ``busy_s``: the union of the intervals in which an operation ran on the
  device, inside the traced window, averaged over the devices used;
* ``window_s``: the traced window, the host span named ``WINDOW`` that the
  harness puts around its measured loop;
* ``device_ops``: device time by operation, longest first, each named by
  its HLO instruction and result shape (``%fusion.206 = f32[1401,16,8,128]``);
  loops and calls, which contain other operations, are left out of it;
* ``idle_gaps``: idle device time by what the host was doing meanwhile, as
  the harness's own spans (``bench.*``) name it.

Device planes are those named ``/device:TPU:<n>``; on each, operations are
the events of the line ``XLA Ops``.  Host spans are events of any line of
the plane ``/host:CPU``.  ProfileData puts every plane's events on one
clock, so device and host intervals compare directly.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
IDLE = "(no bench span)"
CONTAINERS = ("while", "conditional", "call")

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


def _container(hlo: str) -> bool:
    rhs = hlo.split(" = ", 1)[-1]
    return any(f" {c}(" in rhs or rhs.startswith(f"{c}(") for c in CONTAINERS)


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
    devices: List[List[Tuple[float, float, str]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith(HOST_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:TPU:"):
            ops = [(ev.start_ns, ev.end_ns, ev.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append(ops)
    if window is None or not devices:
        return None
    devices = devices[:n_devices]
    lo, hi = window
    by_op: Dict[str, float] = defaultdict(float)
    by_host: Dict[str, float] = defaultdict(float)
    host = _Spans(spans)
    busy_ns = 0.0
    for ops in devices:
        for s, e, name in ops:
            if e > lo and s < hi and not _container(name):
                by_op[op_name(name)] += min(e, hi) - max(s, lo)
        busy = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for g in _gaps(busy, lo, hi):
            by_host[host.label(g)] += g[1] - g[0]
    nd = len(devices)
    if busy_ns <= 0:
        return None

    def ranked(d: Dict[str, float]) -> List[List]:
        return [[k, v / nd / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_ns / nd / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": ranked(by_op), "idle_gaps": ranked(by_host)}


def reduce_dir(trace_dir: str, n_devices: int = 1) -> Optional[Dict]:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(trace_dir)), n_devices)

#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``) in ``BENCHMARK.json``; each metric
the cell reports is read by ``bench/metrics/<metric>.py``.  Nothing here
names a cell, a model or a metric.

A run: set-up (start JAX with the compile cache in
``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``.jax_cache/`` of the
checkout; draw the weights from the seed, configure the paged engine, which
compiles or loads every bucket it can dispatch, and generate the traffic),
then ``--seconds`` of serving, then a drain of the requests still open, then
the correctness check against the plain float32 reference
(``bench/models/<family>.py``).  A configuration whose engine names a
``mesh`` (``{"data": 2, "model": 2}``) is served by one engine sharded over
that many chips, as many as its cell asks for.  With ``--trace 1`` the
profiler records the window and the run reports the per-layer metrics; with
``--trace 0`` the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``, each number compared with its limit.
The same comparisons are the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, the run prints no result and
exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import traffic_gen as TG  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
DRAIN_S = 90.0          # how long answers due at the close may take
SAMPLE_TOKENS = 400     # served tokens the correctness sample aims for
SAMPLE_MAX = 8          # requests it takes at most
WINDOW = "bench.window"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# --- finding things by name ---------------------------------------------------
def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_of(bench: Dict, workload: str) -> Dict:
    """The cell's configuration, traffic and metric specs, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    if chips_of(cfg) != cell["chips"]:
        raise SystemExit(f"{workload} asks for {cell['chips']} chip(s); its "
                         f"engine spans {chips_of(cfg)}")

    def applies(m):
        return workload in m.get("workloads", [workload])
    return {"cell": cell,
            "cfg": cfg,
            "traffic": load_json(os.path.join(BENCH, "traffic",
                                              cell["traffic"] + ".json")),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def chips_of(cfg: Dict) -> int:
    """The chips a configuration's engine spans: its mesh's, else one."""
    mesh = cfg["engine"].get("mesh")
    return int(np.prod(list(mesh.values()))) if mesh else 1


# The program's model options that a configuration may set where the
# program's registry departs from the published value.  Only constants of the
# arithmetic: a width, a norm, a bias or the rotary layout stays the
# registry's, so that ``check_program`` refuses any departure there.
PROGRAM_OPTIONS = ("norm_eps",)


def program_model(cfg: Dict, pcfg=None):
    """The program's model for a configuration (``pcfg`` in its place, in
    tests), with the ``PROGRAM_OPTIONS`` the file sets in
    ``program_options``; any other key is refused."""
    from repro.configs import get_config
    opts = cfg.get("program_options", {})
    other = sorted(set(opts) - set(PROGRAM_OPTIONS))
    if other:
        raise RuntimeError(f"program_options may set only {PROGRAM_OPTIONS}; "
                           f"{cfg['name']} sets {other}")
    pcfg = pcfg or get_config(cfg["program_model"])
    return pcfg.with_(**opts)


def family(cfg: Dict):
    return load_module(os.path.join(BENCH, "models", cfg["family"] + ".py"),
                       "bench_family_" + cfg["family"])


def reader(name: str):
    """The metric's reader, ``bench/metrics/<name>.py``.  A quantity split
    by the end-to-end metric it moves (``<base>.<suffix>``) is read by
    ``<base>.py`` where it has no file of its own."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    if not os.path.isfile(path) and "." in name:
        path = os.path.join(BENCH, "metrics", name.split(".")[0] + ".py")
    return load_module(path, "bench_metric_" + name.replace(".", "_"))


# --- the program under test ---------------------------------------------------
class CompileCounter:
    """Counts lowerings (fresh compiles and persistent-cache loads alike)."""

    def __init__(self):
        from jax import monitoring
        self.n = 0

        def on(event, _secs, **_kw):
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.n += 1
        monitoring.register_event_duration_secs_listener(on)


class Harness:
    """One configuration's engine, on one chip or sharded over the mesh its
    engine names, served window by window."""

    def __init__(self, cfg: Dict, pcfg=None):
        import jax
        self.jax = jax
        self.cfg = cfg
        self.fam = family(cfg)
        self.m = self.fam.dims(cfg)
        self.pcfg = program_model(cfg, pcfg)
        self.fam.check_program(self.pcfg, self.m)
        self.chips = chips_of(cfg)
        self.mesh = None
        if "mesh" in cfg["engine"]:
            from repro.launch.mesh import make_mesh_for
            self.mesh = make_mesh_for(
                self.chips, model_parallel=cfg["engine"]["mesh"]["model"])
        self.ev = None
        self.eng = None
        self.weights = None
        self.compiles = CompileCounter()

    def load(self, seed: int) -> None:
        """Weights from the seed, handed to a fresh paged engine that
        configures (and so compiles or loads) every bucket it dispatches.
        On a mesh the weights are drawn sharded as the program's parameters,
        after the last seed's are let go."""
        from repro.core.catalog import Variant
        from repro.core.config_graph import ConfigGraph
        from repro.models import registry as R
        from repro.serving.engine import EngineVariant, RealEngine
        jax = self.jax
        self.free()
        want = jax.eval_shape(
            lambda: R.init_params(jax.random.PRNGKey(0), self.pcfg))
        if self.mesh is None:
            self.weights = self.fam.make_weights(self.cfg, seed)
        else:
            from repro.sharding.rules import param_shardings
            # two sets of weights would not fit: the last seed's go first,
            # with the variant that holds the engine's placed copy of them
            # (a fresh variant loads its programs from the compile cache)
            self.weights = self.ev = None
            gc.collect()
            self.weights = self.fam.make_weights(
                self.cfg, seed, param_shardings(want, self.pcfg, self.mesh))
        params = self.fam.to_program(self.weights, self.cfg)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
            raise RuntimeError("weights do not fit the program's parameters")
        jax.block_until_ready(params)
        if self.ev is None:
            n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
            v = Variant(family=self.pcfg.name, name="x1", quality=1,
                        accuracy=1.0, flops_g=2 * n / 1e9, params_m=n / 1e6,
                        mem_gb=n * 2 / 2**30)
            self.ev = EngineVariant(v, self.pcfg, params)
        else:
            self.ev.params = params     # same jitted entry points, new weights
        e = self.cfg["engine"]
        self.eng = RealEngine(
            [self.ev], kv_layout=e["kv_layout"], max_len=e["max_len"],
            block_size=e["block_size"], n_blocks=e["n_blocks"],
            max_seqs=e["max_seqs"], chunk_blocks=e["chunk_blocks"],
            prefix_caching=e["prefix_caching"], policy=e["policy"],
            fused_steps=e["fused_steps"], mesh=self.mesh)
        self.graph = ConfigGraph.from_dict(self.pcfg.name,
                                           {("x1", self.chips): 1})
        self.eng.configure(self.graph)
        self._warm_eager()

    def _warm_eager(self) -> None:
        """The engine runs a few small operations outside its jitted steps
        (the first token's argmax over a prefill chunk's logits, its scatter
        into a row bucket's next-token buffer).  Run each once per shape so
        that none compiles inside the window."""
        jnp = self.jax.numpy
        e = self.cfg["engine"]
        lg = jnp.zeros((1, e["chunk_blocks"] * e["block_size"], self.m["Vpad"]),
                       self.m["dtype"])
        tok = jnp.argmax(lg[0, 0]).astype(jnp.int32)
        b = 1
        while True:
            jnp.zeros((min(b, e["max_seqs"]), 1), jnp.int32).at[0, 0].set(
                tok).block_until_ready()
            if b >= e["max_seqs"]:
                break
            b *= 2

    def reset(self) -> None:
        """Fresh arena map and prefix cache, same compiled programs."""
        self.eng.configure(self.graph)

    def free(self) -> None:
        """Drop the engine and its arena (the weights stay)."""
        if self.eng is not None:
            self.eng = None
            gc.collect()

    # --- serving --------------------------------------------------------------
    def window(self, traffic: Dict, seed: int, seconds: float,
               drain_s: float = DRAIN_S) -> Dict:
        """Serve ``traffic`` for ``seconds``, then drain; the run record."""
        import jax
        from repro.serving.api import InferenceRequest
        V = self.m["V"]
        closed = not TG.is_open(traffic)
        src = TG.ClosedSource(traffic, seed, V) if closed else None
        pending = ([] if closed else
                   TG.open_requests(traffic, seed, seconds, V))[::-1]
        reqs: List[Dict] = []
        late: List[float] = []
        clock = {"open": 0.0, "base": None}
        eng = self.eng
        out = {"n": 0}

        def on_token(rid, tok):
            r = reqs[rid]
            r["times"].append(time.perf_counter() - clock["open"])
            r["tokens"].append(int(tok))

        def send(r: TG.Request, now: float) -> None:
            reqs.append({"rid": r.rid, "t_s": r.t_s, "prompt": r.prompt,
                         "prompt_len": len(r.prompt), "n_out": r.n_out,
                         "times": [], "tokens": [], "done": False})
            late.append(now - r.t_s)
            first = clock["base"] is None
            if first:
                clock["base"] = r.t_s
            with jax.profiler.TraceAnnotation("bench.submit"):
                eng.submit(InferenceRequest(
                    rid=r.rid, prompt=r.prompt, max_new_tokens=r.n_out,
                    slo=r.slo, arrival_s=r.t_s - clock["base"],
                    on_token=on_token))
            if first:
                # the engine's clock starts inside the first submit: later
                # arrivals are stated on it, early by at most that submit
                clock["base"] = time.perf_counter() - clock["open"]
            out["n"] += 1

        def finished(resps, now: float, more: bool) -> None:
            for resp in resps:
                reqs[resp.rid]["done"] = True
                out["n"] -= 1
                if more:
                    send(src.next(now), now)

        c0 = self.compiles.n
        with jax.profiler.TraceAnnotation(WINDOW):
            clock["open"] = t_open = time.perf_counter()
            if closed:
                for _ in range(src.clients):
                    send(src.next(0.0), 0.0)
            while True:
                now = time.perf_counter() - t_open
                if now >= seconds:
                    break
                while pending and pending[-1].t_s <= now:
                    send(pending.pop(), now)
                if out["n"]:
                    with jax.profiler.TraceAnnotation("bench.step"):
                        resps = eng.step()
                    finished(resps, time.perf_counter() - t_open, closed)
                else:
                    nxt = pending[-1].t_s if pending else seconds
                    with jax.profiler.TraceAnnotation("bench.sleep"):
                        time.sleep(max(min(nxt, seconds) - now, 0.0))
        compiles = self.compiles.n - c0
        t_close = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.drain"):
            while out["n"] and time.perf_counter() - t_close < drain_s:
                finished(eng.step(), 0.0, False)
        stats, responses = None, []
        if not out["n"]:
            responses = [dict(rid=r.rid, queue_delay_s=r.queue_delay_s)
                         for r in eng.drain()]
            stats = eng.stats()
        closed_s = time.perf_counter() - t_open
        return {"cfg": self.cfg, "m": self.m, "family": self.fam,
                "chips": self.chips, "seconds": seconds,
                "requests": reqs, "responses": responses, "stats": stats,
                "closed_s": closed_s, "lateness": late,
                "compiles_in_window": compiles, "trace": None}

    # --- correctness ------------------------------------------------------------
    def sample(self, rec: Dict, seed: int) -> List[Dict]:
        """The longest completed request, then others drawn from the seed,
        until the sample holds SAMPLE_TOKENS served tokens."""
        done = [r for r in rec["requests"] if r["done"]]
        if not done:
            return []
        done.sort(key=lambda r: (-(r["prompt_len"] + r["n_out"]), r["rid"]))
        rest = TG.rng_for(seed, 20).permutation(len(done) - 1) + 1
        out, n = [done[0]], len(done[0]["tokens"])
        for i in rest:
            if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
                break
            out.append(done[i])
            n += len(done[i]["tokens"])
        return out

    def gaps(self, sample: List[Dict], control: bool = False) -> List[float]:
        """Per sampled request, the widest gap of a served token's reference
        logit below the reference's best (``control``: of the token the
        lower-precision control puts first, at the same positions)."""
        jax, jnp, m, fam = self.jax, self.jax.numpy, self.m, self.fam
        S = self.cfg["engine"]["max_len"]
        key = "control" if control else "served"
        if not hasattr(self, "_gap_fns"):
            self._gap_fns = {
                "served": jax.jit(lambda w, t, g: fam.served_gaps(w, t, g, m)),
                "control": jax.jit(lambda w, t: fam.control_gaps(w, t, m))}
        out = []
        with jax.default_matmul_precision("highest"):
            for r in sample:
                seq = np.concatenate([r["prompt"], np.asarray(r["tokens"],
                                                              np.int32)])
                toks = fam.pad_to(seq[:-1], S)
                p, n = r["prompt_len"], len(r["tokens"])
                if control:
                    g = self._gap_fns[key](self.weights, jnp.asarray(toks))
                else:
                    g = self._gap_fns[key](self.weights, jnp.asarray(toks),
                                           jnp.asarray(fam.pad_to(seq[1:], S)))
                out.append(float(np.asarray(g)[p - 1:p - 1 + n].max()))
        return out

    def check(self, rec: Dict, seed: int,
              control: bool = False) -> Dict[str, Dict]:
        """Every number compared, with its limit (``value <= limit``).
        ``control``: the lower-precision control's gaps in the program's
        place, which ``passed`` has to refuse."""
        V = self.m["V"]
        done = [r for r in rec["requests"] if r["done"]]
        wrong = sum(1 for r in done if len(r["tokens"]) != r["n_out"])
        bad = sum(1 for r in done for t in r["tokens"] if not 0 <= t < V)
        sample = self.sample(rec, seed)
        gaps = self.gaps(sample, control) if sample else []
        return {
            "wrong_lengths": {"value": wrong, "limit": 0},
            "ids_outside_vocab": {"value": bad, "limit": 0},
            "compared_tokens": {"value": sum(len(r["tokens"]) for r in sample),
                                "limit": None},
            "max_logit_gap": {"value": max(gaps) if gaps else None,
                              "limit": self.cfg["limits"]["max_logit_gap"]},
        }


def passed(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] is not None and (c["limit"] is None
                                           or c["value"] <= c["limit"])
               for c in checks.values())


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def peaks_of(kind: str) -> Dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise RuntimeError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# --- one run ----------------------------------------------------------------------
def run_cell(spec: Dict, seed: int, seconds: float, trace: bool,
             devices, t_start: float = T_START, pcfg=None) -> Dict:
    """One run of a cell on ``devices``; the result line as a dict.
    ``pcfg`` stands in for the program's model (smaller, in tests)."""
    import jax
    cfg, traffic = spec["cfg"], spec["traffic"]
    dev = devices[0]
    peaks = peaks_of(dev.device_kind) if dev.platform == "tpu" else None
    h = Harness(cfg, pcfg)
    h.load(seed)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the harness's spans are enough
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    rec = h.window(traffic, seed, seconds)
    rec.update(setup_s=setup_s, peaks=peaks)
    if trace:
        jax.profiler.stop_trace()
        import trace_reduce
        rec["trace"] = trace_reduce.reduce_dir(TRACE_DIR, len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    mem = memory_peak(devices)
    log("memory_stats: " + json.dumps([d.memory_stats() for d in devices]))
    h.free()
    checks = h.check(rec, seed)
    lat = rec["lateness"]
    log(f"generator lateness: max {max(lat):.6f} s, p99 "
        f"{np.percentile(lat, 99):.6f} s over {len(lat)} sends; compiles in "
        f"window {rec['compiles_in_window']}")
    print(json.dumps({"lateness_max_s": max(lat), "sends": len(lat),
                      "compiles_in_window": rec["compiles_in_window"],
                      "stats": rec["stats"]}), flush=True)
    specs = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for ms in specs:
        v = reader(ms["name"]).read(rec)
        if v is not None:
            metrics[ms["name"]] = {"value": v, "unit": ms["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    attempted = len(rec["requests"])
    out = {"correct": passed(checks), "attempted": attempted,
           "failed": attempted - sum(r["done"] for r in rec["requests"]),
           "metrics": metrics, "device": device}
    if trace and rec["trace"]:
        device.update(busy_s=rec["trace"]["busy_s"],
                      window_s=rec["trace"]["window_s"])
        out["breakdown"] = {k: rec["trace"][k]
                            for k in ("device_ops", "idle_gaps")}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = cell_of(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                   args.workload)
    # JAX_COMPILATION_CACHE_DIR where it is set, else .jax_cache/ in the
    # checkout, a fixed path
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
        return 2
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                   devices[:chips])
    log(f"correct: {out['correct']}; the numbers compared, each with its "
        f"limit:")
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

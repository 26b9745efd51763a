"""Benchmark harness — one function per paper table/figure + roofline tables.

Prints the ``name,us_per_call,derived`` CSV contract (one line per benchmark)
and writes the full per-figure CSVs to benchmarks/out/.

Usage:  PYTHONPATH=src python -m benchmarks.run [--only fig09] [--fast]
"""
from __future__ import annotations

import argparse
import csv
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")


def _benchmarks(fast: bool):
    from benchmarks import paper_figs as F
    if fast:
        F.TRACE_HOURS = 6.0
    items = [
        ("fig02_mixed_quality", F.fig02_mixed_quality),
        ("fig03_partitioning", F.fig03_partitioning),
        ("fig08_traces", F.fig08_traces),
        ("fig09_effectiveness", F.fig09_effectiveness),
        ("fig10_schemes", F.fig10_schemes),
        ("fig11_objective_timeline", F.fig11_objective_timeline),
        ("fig12_overhead", F.fig12_overhead),
        ("fig13_trajectory", F.fig13_trajectory),
        ("fig14_lambda", F.fig14_lambda),
        ("fig15_consolidation", F.fig15_consolidation),
        ("fig16_geo", F.fig16_geo),
        ("table_chatgpt", F.table_chatgpt_estimate),
        ("table_lm_serving", F.table_lm_serving),
        ("roofline_baseline", _roofline_bench),
        ("carbon_policy_serving", _carbon_policy_bench),
        ("observability_telemetry", _observability_bench),
        ("decode_hotpath", _decode_hotpath_bench),
        ("mixed_quality_serving", _mixed_quality_bench),
        ("disagg_serving", _disagg_bench),
    ]
    return items


def _roofline_bench():
    """Roofline terms for every compiled dry-run cell (single-pod mesh)."""
    from repro.launch import roofline as RL
    path = os.path.join(os.path.dirname(__file__), "..", "dryrun_results.json")
    if not os.path.exists(path):
        return {"skipped": "run repro.launch.dryrun first"}, [("missing",)]
    rows = RL.analyze_file(path, mesh="16x16")
    csv_rows = [("arch", "shape", "t_compute_s", "t_memory_s", "t_coll_s",
                 "dominant", "useful_ratio", "roofline_frac", "mem_gib")]
    for r in rows:
        csv_rows.append((r["arch"], r["shape"], r["t_compute_s"],
                         r["t_memory_s"], r["t_collective_s"], r["dominant"],
                         round(r["useful_flops_ratio"], 3),
                         round(r["roofline_fraction"], 4),
                         round(r["mem_footprint_gib"], 2)))
    dom = {}
    for r in rows:
        dom[r["dominant"]] = dom.get(r["dominant"], 0) + 1
    derived = {"cells": len(rows), "dominant_counts": dom,
               "median_roofline_frac": round(
                   sorted(x["roofline_fraction"] for x in rows)[len(rows) // 2], 4)}
    return derived, csv_rows


def _carbon_policy_bench():
    """Forecast-driven carbon scheduling vs the raw-trace threshold policy,
    plus partial swap-in page savings — the PR-5 control-plane numbers.

    Stage 1 (DES, diurnal trace): deferrable work arriving on the morning
    CI decline under (a) ``CarbonAwarePolicy`` with a raw trace lookup and
    threshold release and (b) ``CarbonForecastPolicy`` scheduling for the
    forecast valley inside the deadline runway (``fleet.forecast`` ensemble
    through ``ForecastCIFn``).  Both must meet every deadline and hold the
    interactive SLA; the forecast policy must come back with lower
    gCO2/request.

    Stage 2 (real paged engine): an overcommitted arena forces decode-time
    preemption with a shared prompt preamble in the radix tree; partial
    swap-in must restore strictly fewer pages than a full restore while
    emitting token-identical greedy outputs vs a never-preempted reference.
    """
    import numpy as np

    from repro.core import carbon as CB
    from repro.core import catalog as CAT
    from repro.core import config_graph as CG
    from repro.fleet.forecast import EnsembleForecaster, ForecastCIFn
    from repro.serving import queue as Q
    from repro.serving.api import DEFERRABLE, INTERACTIVE, InferenceRequest, \
        serve_workload
    from repro.serving.policies import CarbonAwarePolicy, CarbonForecastPolicy

    # --- stage 1: forecast valley vs raw threshold (DES, diurnal) -----------
    trace = CB.make_trace("CISO-March", hours=72, seed=3)
    t0 = 36 * 3600.0
    ts = np.arange(t0, t0 + 24 * 3600.0, 600.0)
    t_valley = float(ts[int(np.argmin([trace.at(float(t)) for t in ts]))])
    arrival = t_valley - 9 * 3600.0
    deadline = t_valley + 4 * 3600.0
    threshold = trace.mean()     # the raw policy's natural operating point
    # deferrable entries model BATCH jobs (the fleet's jobs carry ~1e5
    # requests each): max_new_tokens scales DES service time, so one entry
    # is ~60 s of busy drain — enough busy joules that the policy's choice
    # of serving window is visible over the session's idle floor
    n_defer, n_inter = 48, 12
    defer_tokens = 80_000
    inter_gap = (deadline - arrival) / n_inter

    def reqs():
        out = [InferenceRequest(rid=i, prompt=[1],
                                max_new_tokens=defer_tokens,
                                arrival_s=arrival, slo=DEFERRABLE,
                                deadline_s=deadline) for i in range(n_defer)]
        out += [InferenceRequest(rid=n_defer + i, prompt=[1],
                                 max_new_tokens=8,
                                 arrival_s=arrival + inter_gap * i,
                                 slo=INTERACTIVE) for i in range(n_inter)]
        return out

    # two instances: one absorbs the interactive stream while the other
    # drains released batch work, as the fleet's spare capacity would
    des_g = CG.ConfigGraph.from_dict("efficientnet", {("B3", 1): 2})
    variants = CAT.get_family("efficientnet")
    est_svc = 0.006 * defer_tokens / 8.0
    policies = {
        "carbon_raw": CarbonAwarePolicy(lambda now: trace.at(now or 0.0),
                                        ci_threshold=threshold,
                                        est_service_s=est_svc,
                                        deadline_margin_s=1800.0),
        "carbon_forecast": CarbonForecastPolicy(
            ForecastCIFn(EnsembleForecaster(trace)),
            horizon_s=8 * 3600.0, step_s=1800.0,
            est_service_s=est_svc, deadline_margin_s=1800.0),
    }
    rows = [("stage", "metric", "value")]
    stats = {}
    for name, pol in policies.items():
        des = Q.DESBackend(des_g, variants, Q.DESConfig(jitter_sigma=0.0),
                           policy=pol, ci_g_per_kwh=trace.at,
                           hold_retry_s=300.0)
        responses = serve_workload(des, reqs())
        m = des.stats()
        inter_worst = max(r.latency_s for r in responses
                          if r.slo == INTERACTIVE)
        m["interactive_worst_s"] = inter_worst
        stats[name] = m
        rows += [("des", f"{name}_carbon_g_per_req",
                  round(m["carbon_g_per_req"], 4)),
                 ("des", f"{name}_deadline_misses", m["deadline_misses"]),
                 ("des", f"{name}_interactive_worst_s",
                  round(inter_worst, 3))]
    saving = (1.0 - stats["carbon_forecast"]["carbon_g_per_req"]
              / max(stats["carbon_raw"]["carbon_g_per_req"], 1e-12)) * 100
    # equal SLA attainment: zero deadline misses under both, and the
    # interactive stream's worst case stayed in the same band
    sla_equal = int(stats["carbon_raw"]["deadline_misses"] == 0
                    and stats["carbon_forecast"]["deadline_misses"] == 0
                    and stats["carbon_forecast"]["interactive_worst_s"]
                    <= stats["carbon_raw"]["interactive_worst_s"] + est_svc)

    # --- stage 2: partial swap-in pages saved (real paged engine) -----------
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.serving import engine as ENG
    base = get_smoke_config("qwen3-1.7b").with_(n_layers=2,
                                                dtype=jnp.float32)
    family = ENG.build_engine_family(base, fracs=(1.0,))
    g = CG.ConfigGraph.from_dict(base.name, {("x1", 16): 1})
    rng = np.random.default_rng(5)
    pre = rng.integers(0, base.vocab_size, size=16).astype(np.int32)
    prompts = [np.concatenate([pre, rng.integers(0, base.vocab_size, size=6)
                               .astype(np.int32)]) for _ in range(4)]
    ref = ENG.RealEngine(family, n_slots=2, max_len=64, kv_layout="paged",
                         block_size=8, max_seqs=4, n_blocks=41)
    ref.configure(g)
    ref._serve_prompts(prompts, n_new=16)
    eng = ENG.RealEngine(family, n_slots=2, max_len=64, kv_layout="paged",
                         block_size=8, max_seqs=4, n_blocks=14,
                         preemption=True)
    eng.configure(g)
    m_swap = eng._serve_prompts(prompts, n_new=16)
    parity = int(all(
        np.array_equal(ref.last_outputs[rid], eng.last_outputs[rid])
        for rid in ref.last_outputs))
    full_pages = (m_swap["swapin_pages_copied"]
                  + m_swap["partial_swapin_pages_saved"])
    # the scenario must keep its teeth: if a retuned arena stops preempting
    # (or parity breaks) this benchmark must FAIL, not record zeros
    if m_swap["preemptions"] < 1 or full_pages < 1 or not parity:
        raise RuntimeError(
            f"partial swap-in scenario degenerated: preemptions="
            f"{m_swap['preemptions']}, restore pages={full_pages}, "
            f"parity={parity}")
    rows += [("engine", "preemptions", m_swap["preemptions"]),
             ("engine", "swapin_pages_full_restore", full_pages),
             ("engine", "swapin_pages_copied", m_swap["swapin_pages_copied"]),
             ("engine", "partial_swapin_pages_saved",
              m_swap["partial_swapin_pages_saved"]),
             ("engine", "swapin_token_parity", parity)]
    derived = {
        "carbon_g_per_req_raw": round(
            stats["carbon_raw"]["carbon_g_per_req"], 4),
        "carbon_g_per_req_forecast": round(
            stats["carbon_forecast"]["carbon_g_per_req"], 4),
        "forecast_saving_pct": round(saving, 2),
        "sla_equal_deadlines_met": sla_equal,
        "preemptions": int(m_swap["preemptions"]),
        "partial_swapin_pages_saved": int(
            m_swap["partial_swapin_pages_saved"]),
        "swapin_pages_copied": int(m_swap["swapin_pages_copied"]),
        "swapin_token_parity": parity,
    }
    return derived, rows


def _observability_bench():
    """Unified-telemetry acceptance numbers (PR-6 observability layer).

    Stage 1 (shared workload, three backends): one camel-shaped request
    stream runs through the DES backend, the fluid backend, and the real
    paged engine, each with the full telemetry bundle.  All three must
    expose the *identical* metric-name set (the shared CATALOG), and each
    trace must pass the conservation validator — every span closed,
    span-attributed joules equal to the backend's session energy total.
    The engine trace is exported to ``benchmarks/out/trace_engine.json``
    (Perfetto-loadable) and schema-checked.

    Stage 2 (overhead gate): the same compiled paged engine serves the same
    prompts with telemetry detached vs attached (best of ``reps`` runs
    each); tracing + metrics may cost at most ``OVERHEAD_GATE_PCT`` of
    tokens/s, else this benchmark FAILS.

    Stage 3 (layout regression gate): slotted vs paged at equal batch
    (n_slots == max_seqs == 4, identical prompts/compiled family).  Paged
    tokens/s below ``PAGED_GATE_FRAC`` × slotted fails the run — the gate
    that catches a paged-attention throughput regression riding in on an
    unrelated change.  Both gate values land in BENCH_engine.json via
    ``--json``.

    Stage 4 (fleet-plane gates, PR-8 observability plane): (a) the three
    stage-1 registries plus a fleet rollup over them must expose the
    IDENTICAL OpenMetrics family-name set, each exposition round-tripping
    exactly; (b) the TOTAL plane cost — phase profiling (telemetry
    attached), exposition + parse-validation, and a fleet-rollup merge per
    session — may cost at most ``OVERHEAD_GATE_PCT`` of tokens/s vs the
    bare engine (``plane_overhead_pct`` in BENCH_engine.json).
    """
    import numpy as np

    from repro.core import catalog as CAT
    from repro.core import config_graph as CG
    from repro.fleet.workload import shaped_request_stream
    from repro.obs import CATALOG, CarbonFeed, FleetRollup, Telemetry, \
        TraceRecorder, parse_openmetrics, to_openmetrics, \
        validate_chrome_events, validate_trace
    from repro.obs.export import render_families
    from repro.serving import queue as Q
    from repro.serving.api import serve_workload
    from repro.serving.backends import FluidBackend

    OVERHEAD_GATE_PCT = 5.0
    # the pipelined device-resident decode loop (fused dispatches, async
    # readback, event-bound uploads) lifted the measured equal-batch ratio
    # from 0.70-0.98 (synchronous loop) to >= 1.0 on the CPU smoke config;
    # the gate sits under the new noise floor so a regression in either the
    # paged kernel or the hot path trips it
    PAGED_GATE_FRAC = 0.85

    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.serving import engine as ENG
    base = get_smoke_config("qwen3-1.7b").with_(n_layers=2,
                                                dtype=jnp.float32)
    ci = 220.0

    def workload():
        return shaped_request_stream(16, 1.0, vocab_size=base.vocab_size,
                                     shape="camel", prompt_lens=(6, 10),
                                     n_new=8, seed=11)

    def bundle(backend):
        return Telemetry(tracer=TraceRecorder(backend),
                         feed=CarbonFeed(lambda t: ci, interval_s=30.0,
                                         region=backend),
                         backend=backend)

    # --- stage 1: shared workload, three backends, one metric namespace ----
    variants = CAT.get_family("efficientnet")
    des_g = CG.ConfigGraph.from_dict("efficientnet", {("B3", 1): 1})
    tel_des = bundle("des")
    des = Q.DESBackend(des_g, variants, Q.DESConfig(jitter_sigma=0.0),
                       ci_g_per_kwh=ci, telemetry=tel_des)
    serve_workload(des, workload())
    m_des = des.stats()
    validate_trace(tel_des.tracer, expect_energy_j=m_des["energy_j"],
                   expect_requests=int(m_des["served"]))

    tel_fluid = bundle("fluid")
    fluid = FluidBackend(des_g, variants, sla_target_s=2.0, window_s=0.25,
                         ci_g_per_kwh=ci, telemetry=tel_fluid)
    serve_workload(fluid, workload())
    m_fluid = fluid.stats()
    validate_trace(tel_fluid.tracer, expect_energy_j=m_fluid["energy_j"],
                   expect_requests=int(m_fluid["served"]))

    family = ENG.build_engine_family(base, fracs=(1.0,))
    g = CG.ConfigGraph.from_dict(base.name, {("x1", 16): 1})
    tel_real = bundle("real-paged")
    eng = ENG.RealEngine(family, n_slots=4, max_len=48, kv_layout="paged",
                         block_size=8, max_seqs=4, n_blocks=28,
                         ci_g_per_kwh=ci, telemetry=tel_real)
    eng.configure(g)
    serve_workload(eng, workload())
    m_eng = eng.stats()
    validate_trace(tel_real.tracer, expect_energy_j=m_eng["energy_j"],
                   expect_requests=int(m_eng["served"]))
    trace_path = os.path.join(OUT_DIR, "trace_engine.json")
    tel_real.tracer.to_chrome_trace(trace_path)
    with open(trace_path) as f:
        n_events = validate_chrome_events(json.load(f)["traceEvents"])

    name_sets = [des.registry.names(), fluid.registry.names(),
                 eng.last_registry.names()]
    if not all(s == set(CATALOG) for s in name_sets):
        raise RuntimeError(f"metric namespaces diverged: "
                           f"{[sorted(s ^ set(CATALOG)) for s in name_sets]}")
    tol = 1e-6 * m_eng["energy_j"]
    if abs(tel_real.feed.energy_j_total + tel_real.feed.pending_energy_j
           - m_eng["energy_j"]) > tol:
        raise RuntimeError("carbon feed diverged from engine energy total")

    # --- stage 2: telemetry + full-plane overhead on the warm engine -------
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, base.vocab_size, size=6).astype(np.int32)
               for _ in range(24)]

    def best_tps(e, reps=3):
        best = 0.0
        gc.collect()
        gc.disable()
        try:
            for _ in range(reps):
                best = max(best, e._serve_prompts(prompts, n_new=32)
                           ["tokens_per_s"])
        finally:
            gc.enable()
        return best

    def plane_scrape(e):
        """One full scrape against the session registry — OpenMetrics
        export + parse validation + fleet-rollup merge — returning its
        wall seconds, charged against the session it scraped."""
        t0 = time.perf_counter()
        text = to_openmetrics(e.last_registry)
        parse_openmetrics(text)
        roll = FleetRollup()
        roll.add(e.last_registry, region="bench")
        roll.merged()
        return time.perf_counter() - t0

    # The three modes are INTERLEAVED rep by rep: sessions here are short
    # (~0.1 s), and machine drift between unpaired best-of runs taken
    # minutes apart swamps the ~1 ms scrape cost.  Back-to-back sessions
    # see the same machine state, so best-of per mode compares cleanly.
    # GC stays off inside the loop — collector pauses are the dominant
    # session-to-session jitter at this wall length.  One re-measure on a
    # gate miss rejects one-off machine hiccups without loosening the gate.
    def measure_modes(reps=5):
        best = {"off": 0.0, "on": 0.0, "plane": 0.0}
        gc.disable()
        try:
            for _ in range(reps):
                # collect before EVERY session (outside the timed wall):
                # with gc off, garbage accumulates, and without the
                # per-session collect the later modes in each rep would
                # systematically run on a fatter heap than the first.
                gc.collect()
                eng.telemetry = None
                best["off"] = max(
                    best["off"],
                    eng._serve_prompts(prompts, n_new=32)["tokens_per_s"])
                gc.collect()
                eng.telemetry = tel_real           # phase profiling live
                best["on"] = max(
                    best["on"],
                    eng._serve_prompts(prompts, n_new=32)["tokens_per_s"])
                gc.collect()
                m = eng._serve_prompts(prompts, n_new=32)
                plane_s = plane_scrape(eng)
                best["plane"] = max(
                    best["plane"], m["tokens"] / (m["wall_s"] + plane_s))
        finally:
            gc.enable()
        return best

    eng._serve_prompts(prompts, n_new=32)          # warm all shapes
    for attempt in range(2):
        best_mode = measure_modes()
        tps_paged = best_mode["off"]               # doubles as the gate run
        tps_on = best_mode["on"]
        tps_plane = best_mode["plane"]
        overhead_pct = (1.0 - tps_on / tps_paged) * 100.0
        if (overhead_pct <= OVERHEAD_GATE_PCT
                and (1.0 - tps_plane / tps_paged) * 100.0
                <= OVERHEAD_GATE_PCT):
            break
    if overhead_pct > OVERHEAD_GATE_PCT:
        raise RuntimeError(f"telemetry overhead {overhead_pct:.1f}% exceeds "
                           f"{OVERHEAD_GATE_PCT}% gate "
                           f"({tps_on:.0f} vs {tps_paged:.0f} tokens/s)")

    # --- stage 3: equal-batch paged vs slotted regression gate -------------
    slot = ENG.RealEngine(family, n_slots=4, max_len=48, ci_g_per_kwh=ci)
    slot.configure(g)
    slot._serve_prompts(prompts, n_new=32)         # warm
    tps_slot = best_tps(slot)
    ratio = tps_paged / max(tps_slot, 1e-9)
    if ratio < PAGED_GATE_FRAC:
        raise RuntimeError(
            f"paged layout regressed: {tps_paged:.0f} tokens/s is "
            f"{ratio:.3f}× slotted ({tps_slot:.0f}) at equal batch — "
            f"gate {PAGED_GATE_FRAC}")

    # --- stage 4a: exporter family parity across backends + fleet ----------
    regs = {"des": des.registry, "fluid": fluid.registry,
            "real-paged": eng.last_registry}
    rollup = FleetRollup()
    for rname, reg in regs.items():
        rollup.add(reg, region=rname)
    family_sets = {}
    for rname, reg in {**regs, "fleet": rollup}.items():
        text = to_openmetrics(reg)
        fams = parse_openmetrics(text)
        if render_families(fams) != text:
            raise RuntimeError(f"{rname}: OpenMetrics round-trip diverged")
        family_sets[rname] = frozenset(fams)
    if len(set(family_sets.values())) != 1:
        raise RuntimeError(
            f"exporter family sets diverged across backends/fleet: "
            f"{ {a: sorted(family_sets[a] ^ family_sets['fleet']) for a in family_sets} }")
    n_families = len(family_sets["fleet"])

    # --- stage 4b: TOTAL plane overhead gate (measured in the stage-2
    # interleaved loop: telemetry attached + one full scrape per session) --
    plane_overhead_pct = (1.0 - tps_plane / tps_paged) * 100.0
    if plane_overhead_pct > OVERHEAD_GATE_PCT:
        raise RuntimeError(
            f"observability plane overhead {plane_overhead_pct:.1f}% "
            f"exceeds {OVERHEAD_GATE_PCT}% gate "
            f"({tps_plane:.0f} vs {tps_paged:.0f} tokens/s)")
    phase_samples = sum(
        m.count for _, _, m in eng.last_registry.labeled_series(
            "phase_latency_s"))
    if phase_samples <= 0:
        raise RuntimeError("phase profiler recorded no samples with "
                           "telemetry attached")

    rows = [("stage", "metric", "value"),
            ("shared", "backends_conserving", 3),
            ("shared", "metric_names", len(CATALOG)),
            ("shared", "chrome_events", n_events),
            ("shared", "des_energy_j", round(m_des["energy_j"], 3)),
            ("shared", "fluid_energy_j", round(m_fluid["energy_j"], 3)),
            ("shared", "engine_energy_j", round(m_eng["energy_j"], 3)),
            ("overhead", "tokens_per_s_telemetry_off", round(tps_paged, 1)),
            ("overhead", "tokens_per_s_telemetry_on", round(tps_on, 1)),
            ("overhead", "overhead_pct", round(overhead_pct, 2)),
            ("layout_gate", "paged_tokens_per_s", round(tps_paged, 1)),
            ("layout_gate", "slotted_tokens_per_s", round(tps_slot, 1)),
            ("layout_gate", "paged_vs_slotted_ratio", round(ratio, 3)),
            ("layout_gate", "gate_frac", PAGED_GATE_FRAC),
            ("fleet_plane", "openmetrics_families", n_families),
            ("fleet_plane", "exporter_family_parity", 1),
            ("fleet_plane", "tokens_per_s_full_plane", round(tps_plane, 1)),
            ("fleet_plane", "plane_overhead_pct",
             round(plane_overhead_pct, 2)),
            ("fleet_plane", "phase_samples", int(phase_samples))]
    derived = {
        "metric_names_match": 1,
        "conservation_backends": 3,
        "chrome_events": int(n_events),
        "telemetry_overhead_pct": round(overhead_pct, 2),
        "overhead_gate_pct": OVERHEAD_GATE_PCT,
        "paged_tokens_per_s": round(tps_paged, 1),
        "slotted_tokens_per_s": round(tps_slot, 1),
        "paged_vs_slotted_ratio": round(ratio, 3),
        "paged_gate_frac": PAGED_GATE_FRAC,
        "openmetrics_families": int(n_families),
        "exporter_family_parity": 1,
        "plane_overhead_pct": round(plane_overhead_pct, 2),
        "phase_samples": int(phase_samples),
    }
    return derived, rows


def _decode_hotpath_bench():
    """Device-resident decode hot-path breakdown (pipelined paged loop).

    Three engines serve the SAME equal-batch closed-loop workload (4 rows
    × 32 new tokens, fully reserved tables, preemption off): the slotted
    baseline, the pipelined paged engine (device-resident loop state,
    fused multi-step dispatches, async token readback), and the
    synchronous paged reference (``decode_pipeline=False``) — the
    pre-pipelining loop kept as the greedy-parity oracle.  Emits the
    per-tick dispatch breakdown (landed steps per jitted dispatch, H2D
    uploads and blocking host round-trips per step) plus the tokens/s of
    all three loops; ``--json`` lands it in BENCH_engine.json.

    Deterministic gates (counter-based, immune to timing noise): the run
    FAILS unless (a) pipelined greedy outputs are token-identical to the
    synchronous reference, (b) fused dispatch engaged (strictly fewer
    dispatches than landed steps), and (c) pipelined steady-state decode
    kept uploads event-bound — zero per-tick H2D traffic, i.e. far under
    the reference loop's fixed 4-upload-per-step rate.
    """
    import numpy as np

    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.core import config_graph as CG
    from repro.serving import engine as ENG

    base = get_smoke_config("qwen3-1.7b").with_(n_layers=2,
                                                dtype=jnp.float32)
    family = ENG.build_engine_family(base, fracs=(1.0,))
    g = CG.ConfigGraph.from_dict(base.name, {("x1", 16): 1})
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, base.vocab_size, size=6).astype(np.int32)
               for _ in range(4)]
    n_new = 32

    def build(**kw):
        e = ENG.RealEngine(family, n_slots=4, max_len=48, block_size=8,
                           max_seqs=4, n_blocks=28, **kw)
        e.configure(g)
        e._serve_prompts(prompts, n_new=n_new)       # warm every shape
        return e

    def best(e, reps=3):
        m_best = None
        for _ in range(reps):
            m = e._serve_prompts(prompts, n_new=n_new)
            if m_best is None or m["tokens_per_s"] > m_best["tokens_per_s"]:
                m_best = m
        return m_best

    pipe = build(kv_layout="paged")
    m_pipe = best(pipe)
    out_pipe = {r: t.copy() for r, t in pipe.last_outputs.items()}
    sync = build(kv_layout="paged", decode_pipeline=False)
    m_sync = best(sync)
    parity = int(len(out_pipe) == len(sync.last_outputs) and all(
        np.array_equal(out_pipe[r], sync.last_outputs[r]) for r in out_pipe))
    slot = build()
    m_slot = best(slot)

    steps = max(int(m_pipe["decode_steps"]), 1)
    steps_sync = max(int(m_sync["decode_steps"]), 1)
    spd = round(steps / max(m_pipe["decode_dispatches"], 1), 2)
    h2d_pipe = round(m_pipe["h2d_transfers"] / steps, 3)
    h2d_sync = round(m_sync["h2d_transfers"] / steps_sync, 3)
    syncs_pipe = round(m_pipe["host_syncs"] / steps, 3)
    syncs_sync = round(m_sync["host_syncs"] / steps_sync, 3)
    if not parity:
        raise RuntimeError("pipelined decode diverged from the synchronous "
                           "reference loop (greedy parity broken)")
    if m_pipe["decode_dispatches"] >= m_pipe["decode_steps"]:
        raise RuntimeError(
            f"fused dispatch never engaged: {m_pipe['decode_dispatches']} "
            f"dispatches for {m_pipe['decode_steps']} steps")
    if h2d_pipe >= 1.0:
        raise RuntimeError(
            f"steady-state decode is re-uploading loop state: "
            f"{h2d_pipe} H2D transfers/step (reference loop: {h2d_sync})")
    rows = [("stage", "metric", "value"),
            ("dispatch", "decode_steps", int(m_pipe["decode_steps"])),
            ("dispatch", "decode_dispatches",
             int(m_pipe["decode_dispatches"])),
            ("dispatch", "steps_per_dispatch", spd),
            ("traffic", "h2d_per_step_pipelined", h2d_pipe),
            ("traffic", "h2d_per_step_sync", h2d_sync),
            ("traffic", "host_syncs_per_step_pipelined", syncs_pipe),
            ("traffic", "host_syncs_per_step_sync", syncs_sync),
            ("throughput", "tokens_per_s_pipelined",
             round(m_pipe["tokens_per_s"], 1)),
            ("throughput", "tokens_per_s_sync_reference",
             round(m_sync["tokens_per_s"], 1)),
            ("throughput", "tokens_per_s_slotted",
             round(m_slot["tokens_per_s"], 1)),
            ("throughput", "greedy_parity_vs_reference", parity)]
    derived = {
        "steps_per_dispatch": spd,
        "h2d_per_step_pipelined": h2d_pipe,
        "h2d_per_step_sync": h2d_sync,
        "host_syncs_per_step_pipelined": syncs_pipe,
        "host_syncs_per_step_sync": syncs_sync,
        "tokens_per_s_pipelined": round(m_pipe["tokens_per_s"], 1),
        "tokens_per_s_sync_reference": round(m_sync["tokens_per_s"], 1),
        "tokens_per_s_slotted": round(m_slot["tokens_per_s"], 1),
        "pipelined_vs_sync_speedup": round(
            m_pipe["tokens_per_s"] / max(m_sync["tokens_per_s"], 1e-9), 3),
        "pipelined_vs_slotted_ratio": round(
            m_pipe["tokens_per_s"] / max(m_slot["tokens_per_s"], 1e-9), 3),
        "greedy_parity_vs_reference": parity,
    }
    return derived, rows


def _mixed_quality_bench():
    """Carbon/accuracy Pareto sweep of the mixed-quality request path
    (PR-9 quality selectors, ``serving.quality``).

    One diurnal-trace DES workload (deferrable batch entries + an
    interactive stream spread over 24 h, fifo policy so per-request
    quality is the ONLY lever) runs under four operating points:

      * ``off``      — no selector, an all-best pool (``B3 × 2``): today's
        deployment, the accuracy ceiling and the carbon worst case;
      * ``static``   — per-class pinning (deferrable → B1) on a mixed
        ``B1 + B3`` pool of the same total chips;
      * ``greedy``   — dirty-grid downshifter: deferrable ride B1 whenever
        the nowcast CI is above the trace mean, B3 when the grid is clean;
      * ``governed`` — the greedy downshifter behind the accuracy-floor
        governor (deferrable windowed mean ≥ 0.80, between B1's 0.791 and
        B3's 0.816 — the floor genuinely binds).

    Emits one (gCO2/request, mean served accuracy) Pareto point per mode.
    Deterministic gates: every mode meets every deadline at equal
    interactive attainment, the governed point beats ``off`` on
    gCO2/request, its per-class windowed accuracy holds the floor, and at
    least one governed decision actually downshifted (the scenario keeps
    its teeth).  ``--json`` lands the sweep in BENCH_engine.json, where
    the trajectory guard watches the governed point.
    """
    from repro.core import carbon as CB
    from repro.core import catalog as CAT
    from repro.core import config_graph as CG
    from repro.serving import queue as Q
    from repro.serving.api import DEFERRABLE, INTERACTIVE, InferenceRequest, \
        serve_workload
    from repro.serving.quality import make_selector

    trace = CB.make_trace("CISO-March", hours=72, seed=3)
    t0 = 24 * 3600.0                   # skip the trace's warm-up day
    span = 24 * 3600.0
    dirty = trace.mean()               # the downshifters' threshold
    variants = CAT.get_family("efficientnet")
    n_defer, n_inter = 48, 24
    defer_tokens = 80_000              # ~60 s of B3 busy drain per entry

    def reqs():
        gap_d, gap_i = span / n_defer, span / n_inter
        out = [InferenceRequest(rid=i, prompt=[1],
                                max_new_tokens=defer_tokens,
                                arrival_s=t0 + gap_d * i, slo=DEFERRABLE,
                                deadline_s=t0 + gap_d * i + 4 * 3600.0)
               for i in range(n_defer)]
        out += [InferenceRequest(rid=n_defer + i, prompt=[1],
                                 max_new_tokens=8,
                                 arrival_s=t0 + gap_i * i, slo=INTERACTIVE)
                for i in range(n_inter)]
        return out

    floor = 0.80
    pool_off = CG.ConfigGraph.from_dict("efficientnet", {("B3", 1): 2})
    pool_mix = CG.ConfigGraph.from_dict("efficientnet",
                                        {("B1", 1): 1, ("B3", 1): 1})
    modes = {
        "off": (pool_off, None),
        "static": (pool_mix, make_selector(
            "static", pins={DEFERRABLE: "B1"})),
        "greedy": (pool_mix, make_selector(
            "greedy", ci_fn=trace.at, dirty_threshold_g=dirty)),
        "governed": (pool_mix, make_selector(
            "governed", ci_fn=trace.at, dirty_threshold_g=dirty,
            floors={DEFERRABLE: floor})),
    }
    inter_target_s = 180.0             # generous: attainment must be equal,
                                       # not tight — quality is the lever
    rows = [("mode", "carbon_g_per_req", "mean_accuracy",
             "deferrable_accuracy", "interactive_accuracy",
             "interactive_attainment", "deadline_misses")]
    point = {}
    for mode, (g, sel) in modes.items():
        des = Q.DESBackend(g, variants, Q.DESConfig(jitter_sigma=0.0),
                           policy="fifo", ci_g_per_kwh=trace.at,
                           quality_selector=sel)
        responses = serve_workload(des, reqs())
        m = des.stats()
        by = {}
        for r in responses:
            by.setdefault(r.slo, []).append(r.accuracy)
        acc = {slo: sum(a) / len(a) for slo, a in by.items()}
        inter = [r.latency_s for r in responses if r.slo == INTERACTIVE]
        attain = sum(1 for l in inter if l <= inter_target_s) / len(inter)
        point[mode] = {
            "carbon_g_per_req": m["carbon_g_per_req"],
            "mean_accuracy": m["mean_accuracy"],
            "deferrable_accuracy": acc[DEFERRABLE],
            "interactive_accuracy": acc[INTERACTIVE],
            "interactive_attainment": attain,
            "deadline_misses": int(m["deadline_misses"]),
            "downshifts": (sum(1 for _, _, why in sel.decision_sequence()
                               if why in ("downshift", "pressure"))
                           if sel is not None else 0),
        }
        rows.append((mode, round(m["carbon_g_per_req"], 4),
                     round(m["mean_accuracy"], 4),
                     round(acc[DEFERRABLE], 4), round(acc[INTERACTIVE], 4),
                     round(attain, 4), int(m["deadline_misses"])))
    gov, off = point["governed"], point["off"]
    # the gates that keep the sweep honest
    misses = {m: p["deadline_misses"] for m, p in point.items()}
    if any(misses.values()):
        raise RuntimeError(
            f"deadline misses under the mixed-quality sweep: {misses}")
    if any(p["interactive_attainment"] < off["interactive_attainment"]
           for p in point.values()):
        raise RuntimeError("a selector mode lost interactive attainment vs "
                           "the no-selector baseline")
    if gov["carbon_g_per_req"] >= off["carbon_g_per_req"]:
        raise RuntimeError(
            f"governed selector failed to cut gCO2/request: "
            f"{gov['carbon_g_per_req']:.4f} vs off "
            f"{off['carbon_g_per_req']:.4f}")
    if gov["deferrable_accuracy"] < floor \
            or gov["interactive_accuracy"] < floor:
        raise RuntimeError(f"governed accuracy broke the {floor} floor: "
                           f"{gov}")
    if gov["downshifts"] < 1:
        raise RuntimeError("governed scenario degenerated: no downshift "
                           "ever happened (the grid never looked dirty)")
    derived = {f"{mode}_{k}": round(v, 4)
               for mode, p in point.items() for k, v in p.items()}
    derived.update({
        "pareto_points": len(point),
        "accuracy_floor": floor,
        "governed_vs_off_saving_pct": round(
            (1.0 - gov["carbon_g_per_req"] / off["carbon_g_per_req"]) * 100,
            2),
    })
    return derived, rows


def _disagg_bench():
    """Multi-device sharded serving with prefill/decode disaggregation
    (serving.disagg on a ("data","model") mesh, PR 10).

    The measurement needs 8 host devices, so the body runs in a subprocess
    (``benchmarks/disagg_serving.py`` sets XLA_FLAGS before jax imports —
    this harness stays at 1 device) and prints its numbers as one JSON
    line.  The subprocess enforces the hard gates itself (token parity of
    disagg vs monolithic on the sharded mesh, exact per-role joules/carbon
    conservation, prefill-pool throughput ≥ the monolithic engine's at
    equal chips); a gate failure is a nonzero exit surfaced here."""
    import subprocess

    script = os.path.join(os.path.dirname(__file__), "disagg_serving.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")) \
        + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"       # virtual host devices by design
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, timeout=1800, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"disagg bench failed:\n{out.stdout[-2000:]}\n"
                           f"{out.stderr[-2000:]}")
    derived = json.loads(out.stdout.strip().splitlines()[-1])
    rows = [("metric", "value")] + sorted(derived.items())
    return derived, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true",
                    help="6 h traces instead of 48 h")
    ap.add_argument("--json", action="store_true",
                    help="also merge each benchmark's derived dict into the "
                         "root-level BENCH_engine.json (via _bench_json), "
                         "keyed by benchmark name — the cross-PR perf "
                         "trajectory file")
    ap.add_argument("--fail-on-regress", action="store_true",
                    help="turn >10%% bench-trajectory regressions vs the "
                         "previous BENCH_engine.json (tokens/s, paged/"
                         "slotted ratio, overheads) from warnings into "
                         "failures")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    os.makedirs(OUT_DIR, exist_ok=True)
    try:                                           # python -m benchmarks.run
        from benchmarks import _bench_json as BJ
    except ImportError:                            # python benchmarks/run.py
        import _bench_json as BJ
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in _benchmarks(args.fast):
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            derived, rows = fn()
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name},ERROR,{e!r}", flush=True)
            continue
        us = (time.perf_counter() - t0) * 1e6
        with open(os.path.join(OUT_DIR, f"{name}.csv"), "w", newline="") as f:
            csv.writer(f).writerows(rows)
        # trajectory guard BEFORE the snapshot overwrites the previous run:
        # warn (or fail) on >10% regressions of the guarded keys, and append
        # this run's numbers to the history JSONL either way
        regressions = BJ.check_trajectory(name, derived)
        for msg in regressions:
            print(f"{name},REGRESSION,\"{msg}\"", flush=True)
        if regressions and args.fail_on_regress:
            failures += 1
        BJ.append_history(name, {**derived, "us_per_call": round(us)})
        if args.json:
            BJ.update_bench_json(name, {**derived, "us_per_call": round(us)})
        print(f"{name},{us:.0f},\"{json.dumps(derived)}\"", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

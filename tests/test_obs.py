"""Unified telemetry layer: the shared metrics registry (one CATALOG across
real/DES/fluid backends, nearest-rank percentiles identical to the legacy
scheduler path), request-lifecycle tracing with the conservation invariant
(every span closes; span-attributed joules equal the session total, incl.
preemption + partial swap-in), the streaming carbon feed (accountant-exact
totals, controller consumption), policy-hold accounting on responses, and
the shaped load generators."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import carbon as CB
from repro.core import catalog as CAT
from repro.core import config_graph as CG
from repro.fleet.workload import WORKLOAD_SHAPES, shaped_arrival_times, \
    shaped_request_stream
from repro.obs import CATALOG, CarbonFeed, MetricsRegistry, Telemetry, \
    TraceRecorder, validate_chrome_events, validate_trace
from repro.obs.metrics import nearest_rank_percentile
from repro.serving import engine as ENG
from repro.serving import queue as Q
from repro.serving.api import DEFERRABLE, INTERACTIVE, InferenceRequest, \
    serve_workload
from repro.serving.policies import CarbonAwarePolicy
from repro.serving.scheduler import latency_percentile

CFG = get_smoke_config("qwen3-1.7b").with_(n_layers=2, dtype=jnp.float32)
VARIANTS = CAT.get_family("efficientnet")
DES_G = CG.ConfigGraph.from_dict("efficientnet", {("B3", 1): 1})


@pytest.fixture(scope="module")
def family():
    return ENG.build_engine_family(CFG, fracs=(1.0,))


def _graph():
    return CG.ConfigGraph.from_dict(CFG.name, {("x1", 16): 1})


def _bundle(backend):
    return Telemetry(tracer=TraceRecorder(backend),
                     feed=CarbonFeed(300.0, interval_s=1e9, region=backend),
                     backend=backend)


# =============================================================================
# metrics registry
# =============================================================================
def test_percentiles_match_legacy_scheduler_exactly():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100):
        vals = rng.exponential(1.0, size=n).tolist()
        for q in (0.0, 50.0, 90.0, 95.0, 99.0, 100.0):
            assert nearest_rank_percentile(vals, q) == \
                latency_percentile(vals, q), (n, q)
    assert nearest_rank_percentile([], 95.0) == 0.0


def test_registry_standard_catalog_and_kind_safety():
    reg = MetricsRegistry.standard("x")
    assert reg.names() == set(CATALOG)
    reg.counter("requests_served").inc(3)
    assert reg.value("requests_served") == 3
    with pytest.raises(AssertionError):
        reg.histogram("requests_served")      # kind mismatch
    with pytest.raises(AssertionError):
        reg.counter("energy_j").inc(-1.0)     # counters are monotonic
    g = reg.gauge("blocks_in_use")
    g.set(5.0), g.set(2.0)
    assert g.value == 2.0 and g.peak == 5.0
    h = reg.histogram("latency_s")
    for v in (3.0, 1.0, 2.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["latency_s_count"] == 3 and snap["latency_s_mean"] == 2.0
    assert snap["latency_s_p50"] == 2.0 and snap["blocks_in_use_peak"] == 5.0


# =============================================================================
# trace recorder + validators
# =============================================================================
def test_tracer_lifecycle_export_and_conservation_checks(tmp_path):
    tr = TraceRecorder("unit")
    sid = tr.open_span("request", 0.0, rid=0)
    tr.instant("admit", 0.1, rid=0)
    tr.counter("blocks_in_use", 0.2, 4)
    tr.close_span(sid, 1.0)
    tr.annotate(sid, energy_j=2.5, carbon_g=0.1)
    tr.span("request", 0.5, 2.0, rid=1, energy_j=1.5)   # retroactive
    s = validate_trace(tr, expect_energy_j=4.0, expect_requests=2)
    assert s["requests"] == 2 and s["energy_j"] == 4.0

    with pytest.raises(AssertionError):     # a joule went missing
        validate_trace(tr, expect_energy_j=5.0)
    dangling = tr.open_span("preempted", 2.5, rid=1)
    with pytest.raises(AssertionError):     # unclosed span
        validate_trace(tr)
    tr.close_span(dangling, 3.0, pages=2)

    jl = tmp_path / "t.jsonl"
    ct = tmp_path / "t.json"
    tr.to_jsonl(str(jl))
    assert len(jl.read_text().splitlines()) == len(tr.records)
    tr.to_chrome_trace(str(ct))
    doc = json.loads(ct.read_text())
    n = validate_chrome_events(doc["traceEvents"])
    assert n == len(tr.records)             # every record became an event
    # rid tracks are tid = rid + 1; the counter lands on the engine track 0
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["tid"] for e in x} == {1, 2}
    assert all(e["dur"] >= 0 for e in x)
    with pytest.raises(AssertionError):
        validate_chrome_events([{"ph": "X", "name": "no_ts"}])


# =============================================================================
# shaped load generators
# =============================================================================
def test_shaped_arrivals_follow_their_density():
    D, n = 100.0, 4000
    for shape in WORKLOAD_SHAPES:
        t = shaped_arrival_times(n, D, shape, seed=1)
        assert len(t) == n and np.all(np.diff(t) >= 0)
        assert t.min() >= 0.0 and t.max() <= D
    lin = shaped_arrival_times(n, D, "linear", seed=1)
    assert lin.mean() > 0.55 * D            # mass shifts late on the ramp
    peak = shaped_arrival_times(n, D, "peak", seed=1)
    assert abs(peak.mean() - 0.5 * D) < 0.05 * D
    assert peak.std() < 0.25 * D            # tighter than uniform (0.29 D)
    camel = shaped_arrival_times(n, D, "camel", seed=1)

    def frac(t, lo, hi):
        return float(np.mean((t >= lo * D) & (t < hi * D)))
    # bimodal: the humps carry more mass than the saddle between them
    assert frac(camel, 0.15, 0.35) > 1.5 * frac(camel, 0.45, 0.55) * 2.0
    with pytest.raises(ValueError):
        shaped_arrival_times(10, D, "sawtooth")


def test_shaped_request_stream_carries_deadlines():
    reqs = shaped_request_stream(12, 60.0, vocab_size=100, shape="camel",
                                 slo=DEFERRABLE, priority=0,
                                 deadline_slack_s=300.0, seed=4)
    assert [r.rid for r in reqs] == list(range(12))
    for r in reqs:
        assert r.slo == DEFERRABLE and r.priority == 0
        assert r.deadline_s == pytest.approx(r.arrival_s + 300.0)
    assert all(r.deadline_s is None for r in
               shaped_request_stream(3, 60.0, vocab_size=100))


# =============================================================================
# carbon feed
# =============================================================================
def test_feed_totals_equal_accountant_exactly():
    trace = CB.make_trace("CISO-March", hours=6.0)
    feed = CarbonFeed(trace.at, interval_s=600.0, region="r",
                      pue=CB.PUE_DEFAULT)
    acct = CB.CarbonAccountant(trace, feed=feed)
    seen = []
    feed.subscribe(seen.append)
    rng = np.random.default_rng(0)
    t = 0.0
    for _ in range(40):
        dt = float(rng.uniform(30.0, 400.0))
        acct.add(t, dt, power_w=float(rng.uniform(100.0, 5000.0)))
        t += dt
    feed.flush(t, sla_ok_frac=0.97)
    # conservation by construction: bit-identical totals, not approx
    assert feed.energy_j_total == acct.energy_j
    assert feed.carbon_g_total == acct.carbon_g
    assert feed.pending_energy_j == 0.0
    assert feed.snapshots and seen == feed.snapshots
    assert feed.latest().sla_ok_frac == 0.97
    assert sum(s.energy_j for s in feed.snapshots) == feed.energy_j_total
    for s in feed.snapshots[:-1]:
        assert s.window_s >= 600.0          # emitted on the measure interval


def test_feed_sampler_integrates_power():
    feed = CarbonFeed(500.0, interval_s=1e9, pue=1.0)
    feed.sample(0.0, 200.0)                 # anchors the clock only
    feed.sample(10.0, 200.0)
    feed.sample(20.0, 100.0)
    snap = feed.flush(20.0)
    assert snap.energy_j == pytest.approx(200.0 * 10 + 100.0 * 10)
    assert snap.carbon_g == pytest.approx(snap.energy_j / 3.6e6 * 500.0)


def test_controller_consumes_feed_snapshots():
    from repro.core import controller as CTRL
    from repro.core import schemes as SCH
    from repro.serving import simulator as SIM
    ctx, _ = SIM.make_context("efficientnet", SIM.SimConfig(n_blocks=1))
    c = CTRL.Controller(SCH.make_scheme("CLOVER"), ctx)
    c.start(0.0, 300.0)
    with pytest.raises(AssertionError):     # no ci AND no feed: refuse
        c.maybe_reoptimize(600.0)
    feed = CarbonFeed(120.0, interval_s=60.0, region="r")
    c.feed = feed
    feed.record_segment(540.0, 60.0, 1000.0)
    feed.flush(600.0)
    n0 = len(c.invocations)
    cfg, outcome = c.maybe_reoptimize(600.0)        # ci read from the feed
    assert len(c.invocations) == n0 + 1 and outcome is not None
    assert c.invocations[-1].ci == pytest.approx(120.0)
    # explicit ci still wins over the feed
    assert c.maybe_reoptimize(1200.0, 120.0)[1] is None


# =============================================================================
# DES backend: hold accounting + trace conservation + catalog parity
# =============================================================================
def test_des_holds_carry_reason_and_trace_conserves():
    pol = CarbonAwarePolicy(lambda now: 500.0 if (now or 0) < 90.0 else 50.0,
                            ci_threshold=200.0, est_service_s=1.0)
    tel = _bundle("des")
    des = Q.DESBackend(DES_G, VARIANTS, Q.DESConfig(jitter_sigma=0.0),
                       policy=pol, ci_g_per_kwh=300.0, hold_retry_s=10.0,
                       telemetry=tel)
    reqs = [InferenceRequest(rid=0, prompt=[1], arrival_s=0.0,
                             slo=DEFERRABLE, deadline_s=10_000.0),
            InferenceRequest(rid=1, prompt=[1], arrival_s=1.0,
                             slo=INTERACTIVE),
            InferenceRequest(rid=2, prompt=[1], arrival_s=2.0,
                             slo=DEFERRABLE, deadline_s=10_000.0)]
    responses = {r.rid: r for r in serve_workload(des, reqs)}
    m = des.stats()

    for rid in (0, 2):                      # held through the dirty spell
        r = responses[rid]
        assert r.release_reason == "threshold"
        assert r.held_s > 0.0
        assert r.held_s <= r.queue_delay_s + 1e-9
        assert r.t_finish >= 90.0
    assert responses[1].release_reason is None      # interactive never held
    assert responses[1].held_s == 0.0

    assert des.registry.names() == set(CATALOG)
    assert des.registry.value("holds_released") == 2
    assert des.registry.histogram("held_s").count == 2
    validate_trace(tel.tracer, expect_energy_j=m["energy_j"],
                   expect_requests=3)
    holds = tel.tracer.spans("hold")
    assert len(holds) == 2
    assert all(h["args"]["reason"] == "threshold" for h in holds)
    assert len(tel.tracer.spans("service")) == 3
    tel.feed.flush(m["wall_s"])
    assert tel.feed.energy_j_total == pytest.approx(m["energy_j"],
                                                    rel=1e-12)


def test_validate_cli_runs_clean():
    from repro.obs import validate as V
    assert V.main() == 0


# =============================================================================
# three backends, one metric namespace (shared workload)
# =============================================================================
def test_metric_name_parity_across_real_des_fluid(family):
    from repro.serving.backends import FluidBackend

    def workload():
        return shaped_request_stream(6, 0.3, vocab_size=CFG.vocab_size,
                                     shape="peak", prompt_lens=(6, 10),
                                     n_new=4, seed=2)

    eng = ENG.RealEngine(family, n_slots=2, max_len=32, ci_g_per_kwh=300.0)
    eng.configure(_graph())
    serve_workload(eng, workload())
    des = Q.DESBackend(DES_G, VARIANTS, Q.DESConfig(jitter_sigma=0.0),
                       ci_g_per_kwh=300.0)
    serve_workload(des, workload())
    fluid = FluidBackend(DES_G, VARIANTS, sla_target_s=2.0, window_s=0.25,
                         ci_g_per_kwh=300.0)
    serve_workload(fluid, workload())

    regs = {"real": eng.last_registry, "des": des.registry,
            "fluid": fluid.registry}
    for name, reg in regs.items():
        assert reg.names() == set(CATALOG), name
        assert reg.value("requests_served") == 6, name
        assert reg.value("energy_j") > 0.0, name
        assert reg.histogram("latency_s").count == 6, name
        assert reg.gauge("wall_s").value > 0.0, name
    # same nearest-rank arithmetic everywhere: the stats views agree with
    # their registries bit-for-bit
    assert eng.stats()["p95_s"] == \
        eng.last_registry.histogram("latency_s").percentile(95.0)
    assert des.stats()["p95_s"] == \
        des.registry.histogram("latency_s").percentile(95.0)


# =============================================================================
# real engine: conservation through preemption + partial swap-in
# =============================================================================
def test_engine_trace_conserves_through_preemption_and_swapin(family):
    rng = np.random.default_rng(5)
    pre = rng.integers(0, CFG.vocab_size, size=16).astype(np.int32)
    prompts = [np.concatenate([pre, rng.integers(0, CFG.vocab_size, size=6)
                               .astype(np.int32)]) for _ in range(4)]
    tel = _bundle("real-paged")
    eng = ENG.RealEngine(family, n_slots=2, max_len=64, kv_layout="paged",
                         block_size=8, max_seqs=4, n_blocks=14,
                         preemption=True, ci_g_per_kwh=300.0, telemetry=tel)
    eng.configure(_graph())
    m = eng._serve_prompts(prompts, n_new=16)
    assert m["preemptions"] >= 1, "arena did not force preemption"
    assert m["partial_swapin_pages_saved"] >= 1

    s = validate_trace(tel.tracer, expect_energy_j=m["energy_j"],
                       expect_requests=4)
    assert s["carbon_g"] == pytest.approx(m["carbon_g"], rel=1e-9)
    tr = tel.tracer
    pre_spans = tr.spans("preempted")       # opened at swap-out, closed at
    assert len(pre_spans) == m["preemptions"]          # partial swap-in
    assert all(p["t1"] > p["t0"] and "pages" in p["args"]
               for p in pre_spans)
    assert len(tr.instants("swap_out")) == m["preemptions"]
    assert len(tr.instants("swap_in")) == m["preemptions"]
    assert len(tr.spans("prefill_chunk")) == m["prefill_chunks"]
    # one decode span per landed dispatch, from launch to landing: the
    # steps they carry sum to the session's decode_steps
    decodes = tr.spans("decode")
    assert len(decodes) == m["decode_dispatches"]
    assert sum(d["args"]["k"] for d in decodes) == m["decode_steps"]
    assert all(d["t1"] >= d["t0"] for d in decodes)
    occupants = [d["args"]["rids"] for d in decodes]
    assert any(len(o) > 1 for o in occupants)   # batched ticks, one event

    reg = eng.last_registry
    assert reg.names() == set(CATALOG)
    assert reg.value("preemptions") == m["preemptions"]
    assert reg.value("swapin_pages_saved") == m["partial_swapin_pages_saved"]
    assert reg.gauge("blocks_in_use").peak == m["blocks_peak"]
    tel.feed.flush(m["wall_s"])
    assert tel.feed.energy_j_total == pytest.approx(m["energy_j"],
                                                    rel=1e-12)


def test_engine_compile_retrace_counter(family):
    eng = ENG.RealEngine(family, n_slots=2, max_len=48, ci_g_per_kwh=300.0)
    eng.configure(_graph())
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, CFG.vocab_size, size=L).astype(np.int32)
               for L in (4, 10, 24)]
    m = eng._serve_prompts(prompts, n_new=4)
    # warmup compiled every serve bucket: in-bucket traffic never retraces
    assert m["compile_retraces"] == 0
    assert eng.last_registry.value("compile_retraces") == 0

    class _Inst:                            # the counter itself, unit-level
        pass
    d = _Inst()
    d._shapes, d.retraces = {("decode",)}, 0
    ENG._note_shape(d, ("decode",))         # known shape: no retrace
    assert d.retraces == 0
    ENG._note_shape(d, ("prefill", 64))     # novel shape: counted once
    ENG._note_shape(d, ("prefill", 64))
    assert d.retraces == 1


# =============================================================================
# engine phase profiling + per-tick counter tracks + exporter parity
# =============================================================================
def test_engine_phase_profiling_and_counter_tracks(family, tmp_path):
    tel = _bundle("real-paged")
    eng = ENG.RealEngine(family, n_slots=2, max_len=48, kv_layout="paged",
                         block_size=8, max_seqs=4, n_blocks=20,
                         ci_g_per_kwh=300.0, telemetry=tel)
    eng.configure(_graph())
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab_size, size=6).astype(np.int32)
               for _ in range(4)]
    eng._serve_prompts(prompts, n_new=8)
    reg = eng.last_registry
    assert reg.labels.get("kv_layout") == "paged"
    phases = {d["phase"]: h for _, d, h in
              reg.labeled_series("phase_latency_s")}
    assert {"prefill_chunk", "decode_dispatch", "decode_land"} <= set(phases)
    assert all(h.count > 0 and h.sum >= 0.0 for h in phases.values())
    # per-request slo_class children recorded alongside the parents
    assert any(d.get("slo_class") for _, d, _ in
               reg.labeled_series("latency_s"))
    # the chrome export carries the per-tick counter tracks
    ct = tmp_path / "t.json"
    tel.tracer.to_chrome_trace(str(ct))
    doc = json.loads(ct.read_text())
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    by_name = {}
    for e in counters:
        by_name.setdefault(e["name"], []).append(e)
    assert {"blocks_in_use", "occupied_rows", "power_w"} <= set(by_name)
    # one sample per engine tick on every track, power always > 0 (the
    # idle floor), and occupancy actually moved during the session
    n_ticks = {n: len(v) for n, v in by_name.items()
               if n in ("blocks_in_use", "occupied_rows", "power_w")}
    assert len(set(n_ticks.values())) == 1
    assert all(next(iter(e["args"].values())) > 0.0
               for e in by_name["power_w"])
    occ = [next(iter(e["args"].values())) for e in by_name["occupied_rows"]]
    assert max(occ) > 0.0


def test_engine_detached_profiler_records_nothing(family):
    eng = ENG.RealEngine(family, n_slots=2, max_len=48, kv_layout="paged",
                         block_size=8, max_seqs=4, n_blocks=20,
                         ci_g_per_kwh=300.0)          # no telemetry bundle
    eng.configure(_graph())
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab_size, size=6).astype(np.int32)
               for _ in range(2)]
    eng._serve_prompts(prompts, n_new=4)
    assert eng.profiler.registry is None
    assert list(eng.last_registry.labeled_series("phase_latency_s")) == []


def test_exporter_family_parity_includes_real_engine(family):
    from repro.obs import FleetRollup, parse_openmetrics, to_openmetrics
    from repro.serving.backends import FluidBackend

    def workload():
        return shaped_request_stream(6, 0.3, vocab_size=CFG.vocab_size,
                                     shape="peak", prompt_lens=(6, 10),
                                     n_new=4, seed=2)

    eng = ENG.RealEngine(family, n_slots=2, max_len=32, ci_g_per_kwh=300.0)
    eng.configure(_graph())
    serve_workload(eng, workload())
    des = Q.DESBackend(DES_G, VARIANTS, Q.DESConfig(jitter_sigma=0.0),
                       ci_g_per_kwh=300.0)
    serve_workload(des, workload())
    fluid = FluidBackend(DES_G, VARIANTS, sla_target_s=2.0, window_s=0.25,
                         ci_g_per_kwh=300.0)
    serve_workload(fluid, workload())

    regs = {"real": eng.last_registry, "des": des.registry,
            "fluid": fluid.registry}
    rollup = FleetRollup()
    for rname, reg in regs.items():
        rollup.add(reg, region=rname)
    sets = {rname: frozenset(parse_openmetrics(to_openmetrics(reg)))
            for rname, reg in {**regs, "fleet": rollup}.items()}
    assert len(set(sets.values())) == 1, \
        {a: sorted(sets[a] ^ sets["fleet"]) for a in sets}
    rollup.conservation(("energy_j", "carbon_g", "requests_served"))


# =============================================================================
# fleet: per-region feeds stream accountant-exact totals
# =============================================================================
def test_fleet_region_feeds_match_accounting():
    from repro.fleet import fleet_sim as FS
    traces = {r: CB.make_trace(r, hours=30.0, seed=2)
              for r in ("CISO-March", "ESO-March")}
    cfg = FS.FleetConfig(warmup_s=24 * 3600.0, n_jobs=2,
                         min_slack_s=2 * 3600.0, max_slack_s=4 * 3600.0,
                         plan_horizon_s=6 * 3600.0)
    rep = FS.run_fleet("efficientnet", traces, cfg)
    for name, r in rep.regions.items():
        assert r.feed_snapshots >= 1, name
        assert r.feed_energy_j == pytest.approx(r.energy_j, rel=1e-9), name
        assert r.feed_carbon_g == pytest.approx(r.carbon_g, rel=1e-9), name
    # the report ships a fleet rollup whose totals conserve bit-exactly
    # over the per-region registries and match the region reports
    assert rep.rollup is not None
    totals = rep.rollup.conservation(("energy_j", "carbon_g"))
    assert set(rep.rollup.regions) == set(traces)
    assert totals["energy_j"] == pytest.approx(
        sum(r.energy_j for r in rep.regions.values()), rel=1e-12)
    fleet = rep.rollup.merged()
    regions_seen = {d["region"] for _, d, _ in fleet.labeled_series()
                    if "region" in d}
    assert regions_seen == set(traces)

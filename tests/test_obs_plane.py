"""Fleet-scope observability plane (jax-free): labeled series on the shared
catalog, bounded-memory streaming histograms with exact small-n parity,
bit-exact fleet rollup conservation, OpenMetrics round-trip identity,
snapshot-writer cadence, deterministic SLO/carbon burn-rate alerting, and
the controller consuming a firing alert as a forced re-optimization."""
import json
import math

import numpy as np
import pytest

from repro.core import catalog as CAT
from repro.core import config_graph as CG
from repro.obs import CATALOG, FleetRollup, LABEL_KEYS, MetricsRegistry, \
    PHASES, PhaseProfiler, SnapshotWriter, StreamingHistogram, \
    parse_openmetrics, to_openmetrics
from repro.obs.export import render_families
from repro.obs.metrics import Histogram
from repro.obs.slo import BurnRatePolicy, CarbonBudget, LatencyObjective, \
    SLOEvaluator, default_rules
from repro.serving import queue as Q
from repro.serving.api import DEFERRABLE, INTERACTIVE, serve_workload
from repro.fleet.workload import shaped_request_stream

VARIANTS = CAT.get_family("efficientnet")
DES_G = CG.ConfigGraph.from_dict("efficientnet", {("B3", 1): 1})


# =============================================================================
# streaming histogram: exact below max_raw, bounded sketch above
# =============================================================================
def test_streaming_small_n_parity_is_bit_exact():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(0.0, 2.0, size=200)
    exact = Histogram("latency_s")
    sh = StreamingHistogram("latency_s", max_raw=4096)
    for v in vals:
        exact.observe(float(v))
        sh.observe(float(v))
    assert not sh.spilled and sh.samples == exact.samples
    assert sh.count == exact.count and sh.sum == exact.sum
    assert sh.mean == exact.mean
    for q in (0.0, 50.0, 90.0, 95.0, 99.0, 100.0):
        assert sh.percentile(q) == exact.percentile(q), q


def test_streaming_spill_accuracy_and_memory_bound():
    rng = np.random.default_rng(1)
    vals = rng.lognormal(0.0, 2.0, size=1_000_000)
    sh = StreamingHistogram("latency_s", max_raw=4096, alpha=0.01)
    sh.observe_many(vals)
    # memory bound: a million samples became a few hundred int buckets
    assert sh.spilled and sh.samples == []
    assert sh.n_buckets < 4096
    # count/sum stay exact even after the spill
    assert sh.count == 1_000_000
    assert sh.sum == float(vals.sum())
    # quantiles within the sketch's relative-accuracy contract (α = 1%,
    # doubled for the nearest-rank-vs-bucket-midpoint discretization)
    for q in (50.0, 95.0, 99.0):
        ref = float(np.quantile(vals, q / 100.0))
        assert abs(sh.percentile(q) - ref) <= 2.5e-2 * ref, q


def test_streaming_observe_many_matches_scalar_path():
    rng = np.random.default_rng(2)
    vals = rng.normal(0.0, 3.0, size=5000)   # negatives + positives
    a = StreamingHistogram("h", max_raw=64)
    b = StreamingHistogram("h", max_raw=64)
    a.observe_many(vals)
    for v in vals:
        b.observe(float(v))
    assert a.count == b.count and a._buckets == b._buckets


def test_streaming_merge_exact_and_spilled():
    rng = np.random.default_rng(3)
    small = StreamingHistogram("h", max_raw=4096)
    small.observe_many(rng.exponential(1.0, size=100))
    big = StreamingHistogram("h", max_raw=256)
    big_vals = rng.exponential(1.0, size=10_000)
    big.observe_many(big_vals)
    exact = Histogram("h")
    for v in (0.5, 1.5, 2.5):
        exact.observe(v)

    tgt = StreamingHistogram("h", max_raw=4096)
    tgt.merge(small)                    # raw ⊕ raw: still exact
    assert not tgt.spilled and tgt.count == 100
    tgt.merge(exact)                    # exact Histogram folds in too
    assert tgt.count == 103 and not tgt.spilled
    tgt.merge(big)                      # spilled side forces the sketch
    assert tgt.spilled
    assert tgt.count == small.count + exact.count + big.count
    assert tgt.sum == small.sum + exact.sum + big.sum
    with pytest.raises(AssertionError):
        tgt.merge(StreamingHistogram("h", alpha=0.05))   # α mismatch


# =============================================================================
# labeled series on the shared catalog
# =============================================================================
def test_registry_labels_do_not_change_catalog_parity():
    reg = MetricsRegistry.standard("r", labels={"region": "east"})
    reg.labeled("requests_served", slo_class="interactive").inc(3)
    reg.labeled("requests_served", slo_class="deferrable").inc(1)
    reg.labeled("latency_s", slo_class="interactive").observe(0.2)
    reg.labeled("phase_latency_s", phase="decode_dispatch").observe(1e-4)
    # the NAME set is still exactly the catalog — labels are children
    assert reg.names() == set(CATALOG)
    series = list(reg.labeled_series())
    assert len(series) == 4
    assert ("requests_served", {"slo_class": "interactive"}) in \
        [(n, d) for n, d, _ in series]
    # same (name, labels) key returns the same child
    again = reg.labeled("requests_served", slo_class="interactive")
    assert again.value == 3
    # kind follows the parent; label keys outside the schema are rejected
    assert reg.labeled("latency_s", slo_class="x").kind == "histogram"
    with pytest.raises(AssertionError):
        reg.labeled("latency_s", datacenter="x")
    assert "datacenter" not in LABEL_KEYS


def test_registry_streaming_mode_swaps_histogram_class():
    reg = MetricsRegistry.standard("r", streaming=True, max_raw_samples=8)
    h = reg.histogram("latency_s")
    assert isinstance(h, StreamingHistogram)
    for v in range(20):
        h.observe(float(v))
    assert h.spilled and h.count == 20
    assert isinstance(reg.labeled("latency_s", slo_class="interactive"),
                      StreamingHistogram)


# =============================================================================
# fleet rollup: bit-exact conservation + per-region breakdown
# =============================================================================
def test_rollup_conservation_is_bit_exact():
    rng = np.random.default_rng(4)
    rollup = FleetRollup()
    expect_e = expect_c = 0.0
    for name in ("east", "west", "north"):
        reg = MetricsRegistry.standard(name, labels={"region": name})
        e, c = float(rng.uniform(1e3, 1e5)), float(rng.uniform(0.1, 50.0))
        reg.counter("energy_j").inc(e)
        reg.counter("carbon_g").inc(c)
        reg.counter("requests_served").inc(int(rng.integers(1, 100)))
        reg.gauge("blocks_in_use").set(float(rng.integers(1, 30)))
        for _ in range(50):
            reg.histogram("latency_s").observe(float(rng.exponential(1.0)))
        reg.labeled("requests_served", slo_class="interactive").inc(2)
        rollup.add(reg)
        expect_e += e
        expect_c += c
    totals = rollup.conservation(("energy_j", "carbon_g"))
    assert totals["energy_j"] == expect_e       # ==, not approx
    assert totals["carbon_g"] == expect_c
    fleet = rollup.merged()
    assert fleet.names() == set(CATALOG)
    # gauges sum across regions; histograms keep exact count/sum
    assert fleet.gauge("blocks_in_use").value == sum(
        r.gauge("blocks_in_use").value for r in rollup.regions.values())
    assert fleet.histogram("latency_s").count == 150
    # per-region counters survive as region-labeled children
    by_label = {(n, tuple(sorted(d.items()))): m
                for n, d, m in fleet.labeled_series()}
    for name, reg in rollup.regions.items():
        child = by_label[("energy_j", (("region", name),))]
        assert child.value == reg.counter("energy_j").value
    # regions' own labeled children got re-labeled with their region
    assert ("requests_served",
            (("region", "east"), ("slo_class", "interactive"))) in by_label
    with pytest.raises(AssertionError):         # duplicate region
        rollup.add(MetricsRegistry.standard("east"))


def test_rollup_conservation_catches_tampering():
    rollup = FleetRollup()
    for name, e in (("a", 10.0), ("b", 20.0)):
        reg = MetricsRegistry.standard(name)
        reg.counter("energy_j").inc(e)
        rollup.add(reg, region=name)
    rollup.conservation(("energy_j",))
    rollup.merged().counter("energy_j").inc(1e-9)   # a joule goes missing
    with pytest.raises(AssertionError):
        rollup.conservation(("energy_j",))


# =============================================================================
# OpenMetrics exposition: round-trip identity, float exactness
# =============================================================================
def test_openmetrics_round_trip_identity_and_exact_floats():
    reg = MetricsRegistry.standard("r", labels={"region": "east"})
    odd = 0.1 + 0.2                             # classic non-decimal float
    reg.counter("energy_j").inc(odd)
    reg.gauge("blocks_in_use").set(7.0)
    reg.histogram("latency_s").observe(odd)
    reg.labeled("latency_s", slo_class="interactive").observe(1.5)
    text = to_openmetrics(reg)
    fams = parse_openmetrics(text)
    assert render_families(fams) == text        # identity, byte for byte
    assert text.endswith("# EOF\n")
    e = [v for n, _, v in fams["repro_energy_j"]["samples"]
         if n == "repro_energy_j_total"]
    assert [float(v) for v in e] == [odd]       # repr() round-trips exactly
    # constant labels ride on every sample; children add their own
    lat = fams["repro_latency_s"]["samples"]
    assert all(("region", "east") in lbl for _, lbl, _ in lat)
    assert any(("slo_class", "interactive") in lbl for _, lbl, _ in lat)
    assert fams["repro_blocks_in_use"]["type"] == "gauge"
    assert "repro_blocks_in_use_peak" in fams   # peak is its own family
    with pytest.raises(AssertionError):
        parse_openmetrics("no_help_line 1.0\n# EOF\n")
    with pytest.raises(AssertionError):
        parse_openmetrics("# HELP x y\n# TYPE x counter\nx 1.0\n")  # no EOF


def test_exporter_family_parity_des_vs_fluid():
    from repro.serving.backends import FluidBackend

    def workload():
        return shaped_request_stream(6, 0.5, vocab_size=64, shape="peak",
                                     prompt_lens=(4, 8), n_new=4, seed=9)

    des = Q.DESBackend(DES_G, VARIANTS, Q.DESConfig(jitter_sigma=0.0),
                       ci_g_per_kwh=300.0)
    serve_workload(des, workload())
    fluid = FluidBackend(DES_G, VARIANTS, sla_target_s=2.0, window_s=0.25,
                         ci_g_per_kwh=300.0)
    serve_workload(fluid, workload())
    sets = [frozenset(parse_openmetrics(to_openmetrics(b.registry)))
            for b in (des, fluid)]
    assert sets[0] == sets[1]
    # both recorded slo_class-labeled children from the live workload
    for b in (des, fluid):
        assert any(d.get("slo_class") for _, d, _ in
                   b.registry.labeled_series("latency_s"))


# =============================================================================
# snapshot writer cadence
# =============================================================================
def test_snapshot_writer_interval_gating(tmp_path):
    reg = MetricsRegistry.standard("r")
    reg.counter("requests_served").inc(1)
    path = tmp_path / "snap.jsonl"
    w = SnapshotWriter(str(path), interval_s=60.0)
    assert w.maybe_write(0.0, reg)              # first write always lands
    assert not w.maybe_write(30.0, reg)         # inside the interval
    assert not w.maybe_write(59.9, reg)
    assert w.maybe_write(60.0, reg)
    w.write(70.0, reg)                          # forced (e.g. at drain)
    assert w.writes == 3
    recs = [json.loads(x) for x in path.read_text().splitlines()]
    assert [r["t"] for r in recs] == [0.0, 60.0, 70.0]
    assert all(r["metrics"]["requests_served"] == 1 for r in recs)


# =============================================================================
# SLO / carbon burn-rate alerting: exact fire/clear ticks
# =============================================================================
POLICY = BurnRatePolicy(short_s=60.0, long_s=300.0,
                        fire_burn=2.0, clear_burn=1.0)


def test_latency_burn_rate_fire_and_clear_ticks_exact():
    # one request per second; all bad (ttft 1.0 > 0.5) during t ∈ [101, 160].
    # error budget 1 − 0.9 = 0.1, so burn = 10 × bad_fraction.
    #   fire: first eval with BOTH windows ≥ 2 — short trips at t=120
    #   (20/60 bad) but long (20/120) lags; both pass at t=130 (30/60,
    #   30/130 → 5.0 and 2.31).
    #   clear: short is clean from t=220; long needs the bad run to age
    #   out of (t−300, t] — at t=430 it is 30/300 → burn exactly 1.0 (not
    #   < 1), at t=440 it is 20/300 → 0.67.  Clear tick: 440.
    ev = SLOEvaluator([LatencyObjective("ttft", threshold_s=0.5,
                                        target=0.9)], POLICY)
    for t in range(1, 501):
        bad = 101 <= t <= 160
        ev.record_request(float(t), INTERACTIVE,
                          ttft_s=1.0 if bad else 0.1)
        if t % 10 == 0:
            ev.evaluate(float(t))
    st = ev.states["ttft"]
    assert st.transitions == [(130.0, "fire"), (440.0, "clear")]
    assert st.fire_count == 1 and not st.firing
    assert st.t_fired == 130.0 and st.t_cleared == 440.0
    assert ev.total_fires == 1 and ev.firing() == []


def test_carbon_burn_rate_fire_and_clear_ticks_exact():
    # 0.125 g (exact binary) per second for t ∈ [1, 100] against a 60 g/h
    # budget: allowance is 1 g per short window, 5 g per long window.
    #   fire at t=80: short (20,80] holds 7.5 g → 7.5×; long (…,80] holds
    #   10 g → exactly 2.0× (t=70 long is 8.75/5 = 1.75).
    #   clear when the long window drains below 5 g: at t=360 it still
    #   holds exactly 5 g (burn 1.0), at t=370 → 3.75 g (0.75).
    ev = SLOEvaluator([CarbonBudget("cb", budget_g=60.0, window_s=3600.0)],
                      POLICY)
    for t in range(1, 401):
        if t <= 100:
            ev.record_carbon(float(t), 0.125)
        if t % 10 == 0:
            ev.evaluate(float(t))
    st = ev.states["cb"]
    assert st.transitions == [(80.0, "fire"), (370.0, "clear")]
    assert st.fire_count == 1 and not st.firing


def test_evaluator_memory_is_bounded_by_the_long_window():
    ev = SLOEvaluator(default_rules(), POLICY)
    for t in range(100_000):
        ev.record_request(float(t), INTERACTIVE, ttft_s=0.1, latency_s=1.0)
        ev.record_carbon(float(t), 1e-6)
        if t % 1000 == 0:
            ev.evaluate(float(t))
    ev.evaluate(99_999.0)
    # deques hold only the long window (300 s of 1/s events), not the run
    assert all(len(dq) <= POLICY.long_s + 1 for dq in ev._lat.values())
    assert len(ev._carbon) <= POLICY.long_s + 1


def test_evaluator_rule_validation():
    with pytest.raises(AssertionError):         # duplicate rule name
        SLOEvaluator([CarbonBudget("x", 1.0), CarbonBudget("x", 2.0)])
    with pytest.raises(AssertionError):         # unknown metric
        LatencyObjective("y", threshold_s=1.0, metric="p99_s")
    with pytest.raises(AssertionError):         # degenerate policy
        BurnRatePolicy(short_s=600.0, long_s=60.0)
    names = [r.name for r in default_rules()]
    assert names == ["interactive-ttft", "deferrable-latency",
                     "hourly-carbon"]


# =============================================================================
# controller: a firing alert forces re-optimization
# =============================================================================
def test_controller_consumes_burn_alert_as_forced_reopt():
    from repro.core import controller as CTRL
    from repro.core import schemes as SCH
    from repro.serving import simulator as SIM
    ctx, _ = SIM.make_context("efficientnet", SIM.SimConfig(n_blocks=1))
    c = CTRL.Controller(SCH.make_scheme("CLOVER"), ctx)
    c.start(0.0, 300.0)
    # same CI, no alerts attached: the drift trigger stays quiet
    assert c.maybe_reoptimize(600.0, 300.0)[1] is None
    n0 = len(c.invocations)

    ev = SLOEvaluator([LatencyObjective("ttft", threshold_s=0.5,
                                        target=0.9)], POLICY)
    for ts in range(1150, 1200):                # 50 straight SLO misses
        ev.record_request(float(ts), INTERACTIVE, ttft_s=1.0)
    c.alerts = ev
    cfg, outcome = c.maybe_reoptimize(1200.0, 300.0)   # CI still flat
    assert outcome is not None and len(c.invocations) == n0 + 1
    inv = c.invocations[-1]
    assert inv.alert and not inv.predictive     # alert, not forecast
    assert c.last_alerts[0].firing
    assert ev.states["ttft"].t_fired == 1200.0
    # the SAME (still-firing) alert does not re-force every tick
    assert c.maybe_reoptimize(1210.0, 300.0)[1] is None
    assert len(c.invocations) == n0 + 1
    assert c.last_alerts[0].firing              # state still visible


# =============================================================================
# phase profiler plumbing (engine-free)
# =============================================================================
def test_phase_profiler_routes_and_detaches():
    prof = PhaseProfiler()                      # detached: every call no-ops
    prof.observe("decode_dispatch", 1.0)
    reg = MetricsRegistry.standard("r")
    prof.registry = reg
    prof.observe("decode_dispatch", 2e-3)
    with prof.span("swap_d2h"):
        math.sqrt(2.0)
    with pytest.raises(AssertionError):
        prof.observe("warmup", 1.0)             # not a canonical phase
    series = {d["phase"]: m for _, d, m in
              reg.labeled_series("phase_latency_s")}
    assert set(series) == {"decode_dispatch", "swap_d2h"}
    assert series["decode_dispatch"].count == 1
    assert series["swap_d2h"].samples[0] >= 0.0
    assert set(PHASES) == {"step", "admit", "tick", "prefill_chunk",
                           "upload", "decode_dispatch", "decode_land",
                           "emit", "finish", "swap_d2h", "swap_h2d"}
    prof.registry = None                        # detach again: silent
    prof.observe("decode_land", 1.0)
    assert reg.names() == set(CATALOG)


def test_phase_span_annotates_stamps_and_observes():
    opened = []

    class Ann:                                  # stands in for jax's
        def __init__(self, name, **meta):       # TraceAnnotation
            self.rec = {"name": name, "meta": meta, "open": False}
            opened.append(self.rec)

        def __enter__(self):
            self.rec["open"] = True

        def __exit__(self, *exc):
            self.rec["open"] = False

    prof = PhaseProfiler(annotate=Ann)          # annotates, observes nothing
    with prof.span("decode_dispatch", k=8, rows=32) as sp:
        assert opened[-1]["open"]
    assert opened == [{"name": "engine.decode_dispatch",
                       "meta": {"k": 8, "rows": 32}, "open": False}]
    assert sp.t1 >= sp.t0 and sp.seconds == sp.t1 - sp.t0
    reg = MetricsRegistry.standard("r")
    prof.registry = reg
    with pytest.raises(ValueError):
        with prof.span("tick"):                 # closed and observed even
            raise ValueError                    # when the block raises
    assert not opened[-1]["open"]
    series = {d["phase"]: m for _, d, m in
              reg.labeled_series("phase_latency_s")}
    assert set(series) == {"tick"} and series["tick"].count == 1
    with pytest.raises(AssertionError):
        prof.span("warmup")                     # not a canonical phase


def test_obs_imports_without_jax():
    import os
    import subprocess
    import sys
    import repro
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    code = ("import sys; sys.modules['jax'] = None; import repro.obs; "
            "p = repro.obs.PhaseProfiler(); "
            "s = p.span('step'); s.__enter__(); s.__exit__(None, None, None)")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=src))
    assert p.returncode == 0, p.stderr[-2000:]

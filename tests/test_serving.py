"""Serving stack: DES queue (hedging, failures), fluid simulator, schemes,
controller, carbon accounting."""
import random

import numpy as np
import pytest

from repro.core import carbon as CB
from repro.core import catalog as CAT
from repro.core import config_graph as CG
from repro.core import objective as OBJ
from repro.core import perf_model as PM
from repro.serving import queue as Q
from repro.serving import simulator as SIM

VARIANTS = CAT.get_family("efficientnet")


def test_carbon_trace_properties():
    for region in ("CISO-March", "CISO-September", "ESO-March"):
        tr = CB.make_trace(region, hours=48)
        assert tr.duration_s == pytest.approx(48 * 3600, rel=0.01)
        assert tr.intensity.min() >= 40.0
        # paper: >200 gCO2/kWh swings within half a day
        half_day = int(12 * 3600 / (tr.times_s[1] - tr.times_s[0]))
        swings = [np.ptp(tr.intensity[i:i + half_day])
                  for i in range(0, len(tr.intensity) - half_day, half_day)]
        assert max(swings) > 150.0, region


def test_carbon_accounting_identity():
    tr = CB.CarbonTrace("const", np.array([0.0, 3600.0]), np.array([360.0, 360.0]))
    acct = CB.CarbonAccountant(tr, pue=1.5)
    g = acct.add(0.0, 3600.0, 1000.0)      # 1 kW for 1 h = 1 kWh
    assert g == pytest.approx(1.0 * 360.0 * 1.5)


def test_des_matches_analytic_capacity():
    g = CG.ConfigGraph.uniform("efficientnet", "B3", 4, 1)
    res_an = OBJ.evaluate(g, VARIANTS, 1e-9)
    arrival = res_an.capacity_rps * 0.5
    des = Q.run_des(g, VARIANTS, arrival, horizon_s=60.0,
                    des=Q.DESConfig(jitter_sigma=0.01, seed=1))
    assert des.served > 0.9 * arrival * 55
    # p95 within 3x of the nominal service latency at moderate load
    nominal = PM.cached_point(VARIANTS[1], 4).latency_s
    assert des.p95() < 3.0 * nominal


def test_des_hedging_tames_stragglers():
    g = CG.ConfigGraph.uniform("efficientnet", "B3", 4, 1)
    arrival = OBJ.evaluate(g, VARIANTS, 1e-9).capacity_rps * 0.3
    cfg_no = Q.DESConfig(straggler_prob=0.03, straggler_mult=20.0, seed=2)
    cfg_hedge = Q.DESConfig(straggler_prob=0.03, straggler_mult=20.0,
                            hedge=True, hedge_factor=3.0, seed=2)
    r_no = Q.run_des(g, VARIANTS, arrival, 120.0, cfg_no)
    r_h = Q.run_des(g, VARIANTS, arrival, 120.0, cfg_hedge)
    assert r_h.hedges > 0
    assert r_h.p95() < r_no.p95(), "hedging must cut the straggler tail"


def test_des_failures_requeue_no_loss():
    g = CG.ConfigGraph.uniform("efficientnet", "B1", 1, 1)   # 16 instances
    arrival = OBJ.evaluate(g, VARIANTS, 1e-9).capacity_rps * 0.2
    des = Q.DESConfig(fail_rate_per_instance_hz=1 / 30.0, repair_time_s=5.0,
                      seed=3)
    r = Q.run_des(g, VARIANTS, arrival, 60.0, des)
    assert r.failures > 0
    assert r.served > 0.85 * arrival * 50, "failures must not lose requests"


def test_simulator_scheme_ordering():
    """Paper Figs. 9/10 structure on a short trace: CO2OPT saves the most
    carbon with the worst accuracy; CLOVER beats BLOVER on f; ORACLE ≥ CLOVER;
    all schemes meet the SLA on average."""
    tr = CB.make_trace("CISO-March", hours=4)
    rep = SIM.compare_schemes("efficientnet", tr,
                              schemes=("BASE", "CO2OPT", "BLOVER", "CLOVER",
                                       "ORACLE"),
                              sim=SIM.SimConfig(n_blocks=2))
    sv = SIM.savings_vs_base(rep)
    lam = 0.1

    def f(name):
        return (lam * sv[name]["carbon_saving_pct"]
                + (1 - lam) * sv[name]["accuracy_delta_pct"])

    assert sv["CO2OPT"]["carbon_saving_pct"] >= sv["CLOVER"]["carbon_saving_pct"]
    assert rep["CO2OPT"].accuracy < rep["CLOVER"].accuracy
    assert f("CLOVER") > f("BLOVER"), "graph optimizer must beat random search"
    assert f("ORACLE") >= f("CLOVER") - 0.3
    assert f("CLOVER") >= 0.75 * f("ORACLE"), "Clover should approach Oracle"
    assert sv["CLOVER"]["carbon_saving_pct"] > 30.0
    assert rep["CLOVER"].accuracy > 0.98 * rep["BASE"].accuracy
    assert rep["CLOVER"].p95_latency_s <= rep["CLOVER"].sla_target_s * 1.05
    assert rep["CLOVER"].opt_time_frac < 0.05


def test_controller_reinvocation_threshold():
    import random as _r
    from repro.core import annealing as SA
    from repro.core import controller as CTRL
    from repro.core import schemes as SCH
    ctx, _ = SIM.make_context("efficientnet", SIM.SimConfig(n_blocks=1))
    c = CTRL.Controller(SCH.make_scheme("CLOVER"), ctx)
    c.start(0.0, 300.0)
    n0 = len(c.invocations)
    assert not c.should_reoptimize(305.0)     # 1.7 % change: below threshold
    assert c.should_reoptimize(330.0)         # 10 % change: re-invoke
    c.maybe_reoptimize(60.0, 330.0)
    assert len(c.invocations) == n0 + 1


def test_controller_elastic_scaling():
    ctx, _ = SIM.make_context("efficientnet", SIM.SimConfig(n_blocks=2))
    from repro.core import controller as CTRL
    from repro.core import schemes as SCH
    c = CTRL.Controller(SCH.make_scheme("BASE"), ctx)
    g0 = c.start(0.0, 300.0)
    chips0 = g0.total_chips
    g1 = c.scale_blocks(+2)
    assert g1.total_chips == chips0 * 2
    g2 = c.scale_blocks(-2)
    assert g2.total_chips == chips0


def test_engine_real_generation_quality_ladder():
    """Real-execution engine: deeper variants are measurably slower."""
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.serving import engine as ENG
    base = get_smoke_config("qwen3-1.7b").with_(n_layers=8, dtype=jnp.float32)
    fam = ENG.build_engine_family(base, fracs=(1.0, 0.25))
    eng = ENG.RealEngine(fam)
    g = CG.ConfigGraph.from_dict(base.name, {("x0.25", 8): 1, ("x1", 8): 1})
    eng.configure(g)
    prompts = [np.array([[1, 2, 3, 4]], dtype=np.int32) for _ in range(4)]
    m = eng._serve_prompts(prompts, n_new=4)
    assert m["served"] == 4 and m["p95_s"] > 0 and m["energy_j"] > 0
    # depth ladder: measure each variant directly.  The one-pass engine is
    # fast enough that fixed dispatch overhead hides depth on tiny decodes,
    # so time a longer generation, best-of-3 after a jit warmup run.  The
    # two variants alternate, so a burst of load from other processes
    # lands on both rather than on one variant's every run.
    i_small = ENG.Instance(fam[0], 8, max_len=64)
    i_big = ENG.Instance(fam[1], 8, max_len=64)
    n_new = 32
    i_small.generate(prompts[0], n_new)            # warm: jit compile
    i_big.generate(prompts[0], n_new)
    runs = [(i_small.generate(prompts[0], n_new)[1],
             i_big.generate(prompts[0], n_new)[1]) for _ in range(3)]
    t_small = min(s for s, _ in runs)
    t_big = min(b for _, b in runs)
    assert t_big > t_small, (t_big, t_small)


def test_lm_ladders_all_archs():
    """Every assigned architecture yields a usable Clover quality ladder
    (DESIGN.md §Arch-applicability: no arch is inapplicable)."""
    from repro.configs import ARCHS
    for arch in ARCHS:
        vs = CAT.get_family(arch)
        assert len(vs) >= 3, arch
        accs = [v.accuracy for v in sorted(vs, key=lambda v: v.quality)]
        assert accs == sorted(accs), f"{arch}: ladder accuracy not monotone"
        assert all(CAT.feasible_slices(v) for v in vs), f"{arch}: OOM on all slices"
        flops = [v.flops_g for v in sorted(vs, key=lambda v: v.quality)]
        assert flops == sorted(flops), f"{arch}: ladder flops not monotone"


def test_perf_model_monotonicity():
    """Latency decreases (to a floor) with slice size for big models and
    energy/request increases with slice size at full load."""
    vs = CAT.get_family("efficientnet")
    big = vs[-1]
    lat = [PM.service_point(big, c).latency_s for c in (1, 4, 16)]
    assert lat[0] > lat[1] > lat[2] * 0.99, lat   # B7 keeps speeding up
    small = vs[0]
    e = [PM.service_point(small, c).energy_per_req_j for c in (1, 4, 16)]
    assert e[0] < e[1] < e[2], e                  # fine slices win on energy


def test_block_failure_recovery():
    """Serving-layer fault tolerance: losing a block removes exactly one
    block's worth of chips; re-optimization restores SLA for the reduced
    fleet (examples/elastic_failure.py, compact)."""
    ctx, arrival = SIM.make_context("efficientnet", SIM.SimConfig(n_blocks=2))
    from repro.core import controller as CTRL
    from repro.core import schemes as SCH
    ctrl = CTRL.Controller(SCH.make_scheme("CLOVER"), ctx)
    ctrl.start(0.0, 300.0)
    chips0 = ctrl.config.total_chips
    ctrl.scale_blocks(-1)
    assert ctrl.config.total_chips == chips0 - 16
    ctrl.last_opt_ci = None
    cfg, outcome = ctrl.maybe_reoptimize(100.0, 300.0)
    res = OBJ.evaluate(cfg, ctx.variants, arrival)
    assert res.p95_latency_s <= ctx.obj_cfg.l_tail_s * 1.05, "SLA must recover"
    ctrl.scale_blocks(+1)
    assert ctrl.config.total_chips == chips0

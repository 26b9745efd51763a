"""Disaggregated-serving bench body (subprocess of benchmarks/run.py).

Runs on 8 forced host devices (XLA_FLAGS set below, BEFORE jax imports —
the parent harness stays at 1 device) and prints one JSON dict on the last
stdout line.  Three measurements on a ("data","model")=(4,2) mesh:

  1. PARITY — a mixed-length shared-prefix workload through a sharded
     monolithic paged engine and a sharded prefill/decode DisaggEngine:
     greedy outputs must be token-identical, every sequence must hand off
     exactly once, and the per-role joules split (session stats AND every
     response's ``energy_by_role``) must conserve exactly;
  2. ATTRIBUTION — with a fixed carbon intensity, the role energy split
     exposes per-phase carbon (prefill/decode/handoff gCO2 summing to the
     session total) — the number CI-aware pool placement acts on;
  3. PREFILL THROUGHPUT — a prompt-heavy (max_new_tokens=1) workload runs
     entirely on the prefill pool; its prompt-tokens/s must not fall below
     the monolithic engine's on the same workload at equal chips per
     worker (best-of-3 warm sessions each; the prefill-role tick skips the
     decode dispatch machinery, so the split must not cost prefill
     throughput).
"""
import json
import os
import sys

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

CI_G_PER_KWH = 300.0


def main() -> int:
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.core import config_graph as CG
    from repro.launch.mesh import make_mesh_for
    from repro.obs.validate import check_disagg_conservation
    from repro.serving import engine as ENG
    from repro.serving.api import InferenceRequest, serve_workload

    cfg = get_smoke_config("qwen3-1.7b").with_(n_layers=2, dtype=jnp.float32)
    fam = ENG.build_engine_family(cfg, fracs=(1.0,))
    graph = CG.ConfigGraph.from_dict(cfg.name, {("x1", 16): 1})
    mesh = make_mesh_for(8, model_parallel=2)

    rng = np.random.default_rng(0)
    pre = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
    prompts = [np.concatenate(
        [pre, rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (6, 14, 9, 22, 6, 11)]
    n_new = 8

    def build(**kw):
        eng = ENG.RealEngine(fam, n_slots=4, max_len=64, kv_layout="paged",
                             block_size=8, max_seqs=4, mesh=mesh,
                             ci_g_per_kwh=CI_G_PER_KWH, **kw)
        eng.configure(graph)
        return eng

    def reqs():
        return [InferenceRequest(rid=i, prompt=p, max_new_tokens=n_new)
                for i, p in enumerate(prompts)]

    # --- 1+2: parity + per-role attribution ------------------------------
    mono = build()
    rm = {r.rid: r for r in serve_workload(mono, reqs())}
    sm = mono.stats()
    dis = build(roles={"prefill": 1, "decode": 1})
    rd = {r.rid: r for r in serve_workload(dis, reqs())}
    sd = dis.stats()

    parity = set(rm) == set(rd) and all(
        np.array_equal(rm[rid].tokens, rd[rid].tokens) for rid in rm)
    if not parity:
        raise RuntimeError("disagg outputs diverged from the monolithic "
                           "engine (token parity broken)")
    if sd["handoffs"] != len(prompts):
        raise RuntimeError(f"expected {len(prompts)} handoffs, got "
                           f"{sd['handoffs']}")
    check_disagg_conservation(sd)
    check_disagg_conservation(sm)
    for r in rd.values():
        if abs(sum(r.energy_by_role.values()) - r.energy_j) \
                > 1e-9 * max(r.energy_j, 1e-12):
            raise RuntimeError(f"rid {r.rid}: energy_by_role does not sum "
                               f"to energy_j")
    # per-phase carbon: role joules × the serving window's intensity
    carbon = {role: sd[f"{role}_energy_j"] / 3.6e6 * CI_G_PER_KWH
              for role in ("prefill", "decode", "handoff")}
    if abs(sum(carbon.values()) - sd["carbon_g"]) \
            > 1e-9 * max(sd["carbon_g"], 1e-12):
        raise RuntimeError("per-phase carbon does not sum to the session "
                           "total")

    # --- 3: prefill-pool throughput vs monolithic ------------------------
    pf_prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
                  for n in (24, 40, 32, 24, 40, 32, 24, 32)]
    pf_tokens = sum(len(p) for p in pf_prompts)

    def prefill_tps(eng):
        best = 0.0
        for _ in range(3):
            m = eng._serve_prompts(pf_prompts, n_new=1)
            assert m["served"] == len(pf_prompts)
            best = max(best, pf_tokens / max(m["wall_s"], 1e-9))
        return best

    tps_mono_pf = prefill_tps(mono)
    tps_dis_pf = prefill_tps(dis)     # n_new=1 → runs on the prefill pool
    ratio = tps_dis_pf / max(tps_mono_pf, 1e-9)
    if ratio < 0.95:
        raise RuntimeError(
            f"prefill pool lost throughput vs monolithic at equal chips: "
            f"{tps_dis_pf:.1f} vs {tps_mono_pf:.1f} tok/s (ratio "
            f"{ratio:.3f})")

    import jax
    print(json.dumps({
        # the numbers below are host-device (CPU) figures, never chip ones
        "platform": jax.devices()[0].platform,
        "token_parity": int(parity),
        "handoffs": int(sd["handoffs"]),
        "handoff_pages": int(sd["handoff_pages"]),
        "tokens_per_s_disagg": round(sd["tokens_per_s"], 1),
        "tokens_per_s_monolithic": round(sm["tokens_per_s"], 1),
        "prefill_tokens_per_s_disagg": round(tps_dis_pf, 1),
        "prefill_tokens_per_s_monolithic": round(tps_mono_pf, 1),
        "prefill_throughput_ratio": round(ratio, 3),
        "prefill_energy_j": round(sd["prefill_energy_j"], 4),
        "decode_energy_j": round(sd["decode_energy_j"], 4),
        "handoff_energy_j": round(sd["handoff_energy_j"], 4),
        "prefill_carbon_g": carbon["prefill"],
        "decode_carbon_g": carbon["decode"],
        "handoff_carbon_g": carbon["handoff"],
        "carbon_g_total": sd["carbon_g"],
        "role_conservation": 1,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

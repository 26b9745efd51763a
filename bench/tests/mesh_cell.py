"""Rehearse a cell whose engine is sharded over a mesh, at smoke size, on
virtual CPU devices.  Run as a child process, since the device count is
fixed when JAX starts:

    python3 bench/tests/mesh_cell.py run <workload> <seed> <seconds> [--alter]
    python3 bench/tests/mesh_cell.py draw <workload>

``run``: one ``run_cell`` through the harness as ``run.py`` drives it, past
its look for a chip; ``--alter`` adds 1 to every token where the fused
decode step produces it.  ``draw``: the weights of one seed, loaded into the
engine, and how they lie on the devices.  The last line of standard output
is one JSON object.
"""
import json
import os
import sys

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_smoke import RUN, CopyingUploads, smoke  # noqa: E402


def altering(load):
    """``Harness.load`` with the fused decode's tokens altered."""
    def broken_load(self, seed):
        load(self, seed)
        decode = self.ev.fns["decode_multi"]

        def altered(*a, **kw):
            toks, arena, nxt, ln = decode(*a, **kw)
            return (toks + 1) % self.m["V"], arena, nxt, ln
        self.ev.fns["decode_multi"] = altered
    return broken_load


def placement(spec, pc) -> dict:
    """Whether each drawn weight has its program parameter's sharding, and
    the engine serves those same buffers; the bytes on each device."""
    import jax
    from repro.models import registry as R
    from repro.sharding.rules import param_shardings
    h = RUN.Harness(spec["cfg"], pc)
    h.load(7)
    want = param_shardings(jax.eval_shape(
        lambda: R.init_params(jax.random.PRNGKey(0), h.pcfg)), h.pcfg, h.mesh)
    params = h.ev.params

    def ptrs(a):
        return [s.data.unsafe_buffer_pointer() for s in a.addressable_shards]
    same = jax.tree.map(lambda a, s: a.sharding.is_equivalent_to(s, a.ndim),
                        params, want)
    shared = jax.tree.map(lambda a, b: ptrs(a) == ptrs(b), params,
                          h.eng.instances[0].params)
    per = {d.id: 0 for d in h.mesh.devices.flat}
    for a in jax.tree.leaves(h.weights):
        for s in a.addressable_shards:
            per[s.device.id] += s.data.nbytes
    return {"sharded_as_program": all(jax.tree.leaves(same)),
            "engine_shares_buffers": all(jax.tree.leaves(shared)),
            "device_bytes": list(per.values()),
            "total_bytes": sum(a.nbytes for a in jax.tree.leaves(h.weights))}


def main(argv):
    import jax
    from repro.serving import engine
    what, workload = argv[0], argv[1]
    engine.jnp = CopyingUploads(engine.jnp)
    spec, pc = smoke(workload)
    if what == "draw":
        print(json.dumps(placement(spec, pc)), flush=True)
        return 0
    seed, seconds = int(argv[2]), float(argv[3])
    if "--alter" in argv:
        RUN.Harness.load = altering(RUN.Harness.load)
    devices = jax.devices()[:spec["cell"]["chips"]]
    assert len(devices) == spec["cell"]["chips"] > 1, devices
    out = RUN.run_cell(spec, seed, seconds, False, devices, pcfg=pc)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

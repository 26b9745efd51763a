"""Loop-aware HLO analyzer validation against hand-computable programs.

Runs in a subprocess where multiple host devices are needed (collective test);
the matmul trip-count test runs inline on 1 device.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as HA


def test_scan_matmul_flops_exact():
    def f(x, w):
        def body(x, _):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    m = n = k = 64
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((m, k), jnp.float32),
                         jax.ShapeDtypeStruct((k, n), jnp.float32)).compile()
    st = HA.analyze(c.as_text(), 1)
    assert st.dot_flops == pytest.approx(7 * 2 * m * n * k, rel=1e-6)


def test_nested_scan_flops_exact():
    def f(x, w):
        def outer(x, _):
            def inner(x, _):
                return x @ w, None
            y, _ = jax.lax.scan(inner, x, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((32, 32), jnp.float32),
                         jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    st = HA.analyze(c.as_text(), 1)
    assert st.dot_flops == pytest.approx(15 * 2 * 32 ** 3, rel=1e-6)


def test_no_loop_dot():
    c = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((16, 8), jnp.float32),
        jax.ShapeDtypeStruct((8, 4), jnp.float32)).compile()
    st = HA.analyze(c.as_text(), 1)
    assert st.dot_flops == pytest.approx(2 * 16 * 8 * 4, rel=1e-6)


def test_collectives_in_scan_counted_with_trip(tmp_path):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch import hlo_analysis as HA
        mesh = jax.make_mesh((8,), ("d",))
        def g(x):
            def inner(x):
                def body(c, _):
                    s = jax.lax.psum(c, "d")
                    return c + 0 * s, s
                y, ys = jax.lax.scan(body, x, None, length=5)
                return y + ys.sum(0)
            return jax.shard_map(inner, mesh=mesh, in_specs=P("d"),
                                 out_specs=P("d"))(x)
        c2 = jax.jit(g).lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
        st2 = HA.analyze(c2.as_text(), 8)
        expect = 5 * 2 * (7 / 8) * 128 * 4
        assert abs(st2.coll_bytes["all-reduce"] - expect) < 1e-6, st2.coll_bytes
        assert st2.coll_counts["all-reduce"] == 5
        print("SCENARIO OK")
    """)
    p = tmp_path / "coll.py"
    p.write_text(script)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")) + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"       # virtual host devices, never a chip
    out = subprocess.run([sys.executable, str(p)], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr
    assert "SCENARIO OK" in out.stdout

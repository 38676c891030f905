"""The roofline's cost analyzer is measurement infrastructure — test it.

XLA's cost_analysis counts while bodies once; the jaxpr walker must multiply
scan lengths, count dot FLOPs exactly, and account collectives with ring
factors.
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.jaxpr_analysis import analyze_fn, analyze_jaxpr


class _FakeMesh:
    shape = {"x": 4}


def _analyze(fn, *args, mesh_shape=None):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return analyze_jaxpr(jaxpr.jaxpr, mesh_shape or {}, total_devices=1)


def test_scan_multiplies_trip_count():
    def f(x):
        return jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=8)[0]
    c = _analyze(f, jnp.ones((64, 64)))
    assert np.isclose(c.dot_flops, 8 * 2 * 64 ** 3)


def test_nested_scans_multiply():
    def f(x):
        def outer(c, _):
            def inner(ci, _):
                return ci @ ci, None
            return jax.lax.scan(inner, c, None, length=3)[0], None
        return jax.lax.scan(outer, x, None, length=5)[0]
    c = _analyze(f, jnp.ones((32, 32)))
    assert np.isclose(c.dot_flops, 5 * 3 * 2 * 32 ** 3)


def test_dot_general_flops_batched():
    def f(a, b):
        return jnp.einsum("bik,bkj->bij", a, b)
    c = _analyze(f, jnp.ones((4, 8, 16)), jnp.ones((4, 16, 32)))
    assert np.isclose(c.dot_flops, 2 * 4 * 8 * 16 * 32)


def test_cond_expected_value():
    def f(x, p):
        return jax.lax.cond(p, lambda y: y @ y, lambda y: y, x)
    c = _analyze(f, jnp.ones((32, 32)), jnp.bool_(True))
    # mean over branches: 0.5 * matmul
    assert np.isclose(c.dot_flops, 0.5 * 2 * 32 ** 3)


def test_psum_ring_factor():
    from repro.launch.mesh import make_mesh_auto
    mesh = make_mesh_auto((1,), ("x",))

    def f(x):
        return jax.shard_map(lambda a: jax.lax.psum(a, "x"), mesh=mesh,
                             in_specs=jax.sharding.PartitionSpec(None),
                             out_specs=jax.sharding.PartitionSpec(None),
                             check_vma=False)(x)
    jaxpr = jax.make_jaxpr(f)(jnp.ones((128,), jnp.float32))
    c = analyze_jaxpr(jaxpr.jaxpr, {"x": 4}, total_devices=4)
    # ring all-reduce: 2*(n-1)/n * payload = 1.5 * 512B
    assert np.isclose(c.collective_bytes["psum"], 1.5 * 512)


def test_dus_counts_update_not_operand():
    def f(big, small):
        return jax.lax.dynamic_update_slice(big, small, (0, 0))
    c = _analyze(f, jnp.ones((1024, 1024)), jnp.ones((2, 2)))
    assert c.bytes_upper <= 2 * 2 * 2 * 4 + 1  # ~2x the 2x2 update

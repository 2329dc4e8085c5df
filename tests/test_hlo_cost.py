"""Trip-count-aware HLO cost analyzer (the roofline's measurement layer)."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze_hlo, parse_hlo


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_scan_trip_count_multiplied():
    """XLA cost_analysis counts a while body once; ours multiplies."""
    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    n_steps, m = 8, 128
    hlo = _compile(scanned, jax.ShapeDtypeStruct((m, m), jnp.float32),
                   jax.ShapeDtypeStruct((n_steps, m, m), jnp.float32))
    r = analyze_hlo(hlo)
    expected = n_steps * 2 * m ** 3
    assert r["flops"] == pytest.approx(expected, rel=0.01)


def test_plain_matmul_flops_convention():
    m, k, n = 64, 128, 256
    hlo = _compile(lambda a, b: a @ b,
                   jax.ShapeDtypeStruct((m, k), jnp.float32),
                   jax.ShapeDtypeStruct((k, n), jnp.float32))
    r = analyze_hlo(hlo)
    assert r["flops"] == pytest.approx(2 * m * k * n, rel=0.01)


def test_scan_cache_update_charges_slice_not_buffer():
    """A per-step dynamic-update-slice into a big carried buffer must be
    charged the update region x trips, not the whole buffer x trips."""
    S, D, steps = 1024, 64, 16

    def fn(buf, xs):
        def body(b, x):
            i = jnp.sum(x[:0].astype(jnp.int32))  # 0, traced
            return jax.lax.dynamic_update_slice(b, x[None], (i, 0)), None
        out, _ = jax.lax.scan(body, buf, xs)
        return out

    hlo = _compile(fn, jax.ShapeDtypeStruct((S, D), jnp.float32),
                   jax.ShapeDtypeStruct((steps, D), jnp.float32))
    r = analyze_hlo(hlo)
    buffer_bytes = S * D * 4
    # far below steps x full-buffer traffic
    assert r["bytes"] < 0.5 * steps * buffer_bytes


def test_collectives_counted_with_trips():
    mesh = jax.make_mesh((1,), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    def fn(xs):
        def body(c, x):
            return c + jax.lax.psum(x, "d"), None
        y, _ = jax.lax.scan(body, jnp.zeros((64,)), xs)
        return y

    with mesh:
        sm = jax.shard_map(fn, mesh=mesh,
                           in_specs=jax.sharding.PartitionSpec(None, None),
                           out_specs=jax.sharding.PartitionSpec(None))
        hlo = jax.jit(sm).lower(
            jax.ShapeDtypeStruct((4, 64), jnp.float32)).compile().as_text()
    r = analyze_hlo(hlo)
    # 4 trips x 64 floats each (all-reduce may lower to copy on 1 device —
    # accept either zero or the multiplied count, but never a single trip)
    if r["collective_bytes"]:
        assert r["collective_bytes"] >= 4 * 64 * 4


def test_parse_hlo_structure():
    hlo = _compile(lambda a: jnp.tanh(a) @ a,
                   jax.ShapeDtypeStruct((32, 32), jnp.float32))
    comps = parse_hlo(hlo)
    assert any(c.instrs for c in comps.values())
    entry = [l for l in hlo.splitlines() if l.startswith("ENTRY")]
    assert entry

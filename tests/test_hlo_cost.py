"""While-aware HLO cost parser: validated against known-math probes.

XLA's cost_analysis counts while bodies once; these tests pin down that the
parser recovers exact trip-count-weighted dot FLOPs on flat, nested and
sharded scans (the §Roofline methodology).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import parse_hlo_costs
from repro.launch.mesh import make_mesh

X = jax.ShapeDtypeStruct((64, 128), jnp.float32)
W = jax.ShapeDtypeStruct((128, 128), jnp.float32)
FLOPS_1 = 2 * 64 * 128 * 128


def test_flat_scan_trip_weighting():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    hc = parse_hlo_costs(jax.jit(f).lower(X, W).compile().as_text())
    assert hc.dot_flops == pytest.approx(7 * FLOPS_1, rel=1e-6)
    assert 7 in hc.trip_counts


def test_nested_scan_trip_weighting():
    def g(x, w):
        def outer(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), None
            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y
    hc = parse_hlo_costs(jax.jit(g).lower(X, W).compile().as_text())
    assert hc.dot_flops == pytest.approx(15 * FLOPS_1, rel=1e-6)
    assert sorted(hc.trip_counts) == [3, 5]


def test_unrolled_matches_scan():
    def f_scan(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, None, length=6)[0]

    def f_unroll(x, w):
        for _ in range(6):
            x = jnp.tanh(x @ w)
        return x
    h1 = parse_hlo_costs(jax.jit(f_scan).lower(X, W).compile().as_text())
    h2 = parse_hlo_costs(jax.jit(f_unroll).lower(X, W).compile().as_text())
    assert h1.dot_flops == pytest.approx(h2.dot_flops, rel=1e-6)


def test_scan_io_bytes_not_trip_inflated():
    """Scan-input slicing / output stacking must cost slice bytes per trip,
    not full-buffer bytes (the DUS-fusion rule)."""
    S = 512

    def f(x, w, seq):
        def body(c, s):
            return jnp.tanh(c @ w + s), c.sum()
        y, outs = jax.lax.scan(body, x, seq)
        return y, outs
    seq = jax.ShapeDtypeStruct((S, 64, 128), jnp.float32)
    hc = parse_hlo_costs(jax.jit(f).lower(X, W, seq).compile().as_text())
    # full-buffer-per-trip accounting would give ≥ S * |seq| = 512·16MB ≈ 8GB
    full_per_trip = S * (S * 64 * 128 * 4)
    assert hc.hbm_bytes < full_per_trip / 20


def test_collective_bytes_sharded():
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    with mesh:
        def h(x, w):
            return (x @ w).sum()
        c = jax.jit(h, in_shardings=(NamedSharding(mesh, P("data", None)),
                                     NamedSharding(mesh, P()))).lower(
            X, W).compile()
        hc = parse_hlo_costs(c.as_text())
    assert hc.dot_flops == pytest.approx(FLOPS_1, rel=1e-6)

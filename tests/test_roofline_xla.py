"""Cross-check the HLO roofline parser against XLA's own cost analysis
on a real compiled module (single device, no collectives).

Pins the empirical fact the §Roofline methodology rests on: XLA's
``cost_analysis()`` counts a ``while`` (scan) body ONCE, while the parser
re-weights by the trip count.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import roofline as rl

L, M, K = 6, 32, 64


def _compiled():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), 0
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((M, K), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, K, K), jnp.float32)
    return jax.jit(f).lower(x, ws).compile()


def test_parser_reweights_scan_bodies():
    compiled = _compiled()
    ca = compiled.cost_analysis()
    rep = rl.analyze_compiled(compiled, n_devices=1)

    per_iter = 2 * M * K * K
    # XLA counts the body once...
    assert abs(ca["flops"] - per_iter) / per_iter < 0.05, ca["flops"]
    # ...the parser counts it L times
    assert rep.while_trip_counts == [L]
    np.testing.assert_allclose(rep.flops, per_iter * L, rtol=0.05)
    assert rep.dot_count == L


def test_parser_hbm_within_sane_bounds():
    """HBM estimate covers at least the unavoidable traffic (weights read
    once, activations per step) and is within a small factor of it."""
    compiled = _compiled()
    rep = rl.analyze_compiled(compiled, n_devices=1)
    lower = 4 * (L * K * K + L * M * K)      # weights + per-iter x in/out
    assert rep.hbm_bytes >= lower
    assert rep.hbm_bytes < 20 * lower

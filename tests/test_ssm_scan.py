"""The selective-scan kernel (ops/ssm_scan.py, interpret mode here)
against `lax.scan` over the positions, on random `delta`, `u`, `B`, `C`,
a decay and an initial state: the same outputs and final state, at the
lengths a tick, a ragged chunk and whole lane tiles of positions give,
and a state kept in bfloat16 through both alike."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorchdistributed_tpu.ops import ssm_scan


def operands(bt, steps, d, n, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    delta = jax.nn.softplus(jax.random.normal(k[0], (bt, steps, d)) - 3.0)
    h = jax.random.normal(k[1], (bt, steps, d))
    b, c = (jax.random.normal(kk, (bt, steps, n)) for kk in k[2:4])
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None],
                          (n, d))
    state = jax.random.normal(k[4], (bt, n, d))
    return delta, delta * h, b, c, a, state


@pytest.mark.parametrize("bt,steps,d,n", [
    (2, 1, 128, 16),       # a tick's one position
    (1, 7, 256, 4),        # a ragged chunk
    (2, 64, 128, 16),      # positions under a lane tile
    (1, 256, 1024, 16),    # two tiles of positions, two blocks of channels
])
def test_the_kernel_is_the_scan(bt, steps, d, n):
    args = operands(bt, steps, d, n)
    y0, s0 = ssm_scan.scan_reference(*args)
    y1, s1 = ssm_scan.kernel_scan(*args, interpret=True)
    assert y1.shape == (bt, steps, d) and s1.shape == (bt, n, d)
    scale = float(jnp.abs(y0).max())
    np.testing.assert_allclose(y1, y0, atol=1e-6 * scale)
    np.testing.assert_allclose(s1, s0, atol=1e-6 * float(jnp.abs(s0).max()))


def test_a_step_with_no_delta_leaves_the_state_as_it_was():
    """What a padded position or a free slot is: `delta` and `u`
    nought."""
    delta, u, b, c, a, state = operands(1, 8, 128, 16, seed=1)
    zero = jnp.zeros_like(delta)
    for fn in (ssm_scan.scan_reference, lambda *x: ssm_scan.kernel_scan(
            *x, interpret=True)):
        _, s = fn(zero, zero, b, c, a, state)
        np.testing.assert_array_equal(s, state)


def test_a_bf16_state_rounds_alike_and_moves_the_result(monkeypatch):
    """`STATE_DTYPE` as the faults plant it: both paths round the state
    alike at every position, and away from the float32 one."""
    args = operands(1, 64, 128, 16, seed=2)
    exact, _ = ssm_scan.scan_reference(*args)
    monkeypatch.setattr(ssm_scan, "STATE_DTYPE", jnp.bfloat16)
    y0, _ = ssm_scan.scan_reference(*args)
    y1, _ = ssm_scan.kernel_scan(*args, interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-6 * float(jnp.abs(y0).max()))
    assert float(jnp.abs(exact - y0).max()) > 1e-4 * float(
        jnp.abs(exact).max())

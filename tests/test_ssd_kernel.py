"""Mamba-2 SSD matmul-form Pallas kernel vs the sequential-scan oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd_scan import ssd_scan
from repro.models.ssm import mamba2_scan


@pytest.mark.parametrize("B,S,H,G,P,N,chunk", [
    (1, 32, 2, 1, 16, 8, 8),
    (2, 64, 4, 2, 32, 16, 16),  # two groups of two heads (zamba2's layout)
    (1, 50, 3, 1, 8, 4, 16),    # padding (50 % 16 != 0)
    (2, 16, 1, 1, 64, 32, 16),  # single head, wide state
    (1, 1, 4, 2, 64, 64, 128),  # one decode step, grouped
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_matches_sequential(B, S, H, G, P, N, chunk, dtype):
    """Kernel (interpret mode) vs the jnp scan; head h reads B/C group
    h // (H / G)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, H))).astype(dtype)
    Bc = jax.random.normal(ks[1], (B, S, G, N), dtype)
    Cc = jax.random.normal(ks[2], (B, S, G, N), dtype)
    x = jax.random.normal(ks[3], (B, S, H, P), dtype)
    A = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
    y1, h1 = ssd_scan(dt, Bc, Cc, x, A, chunk=chunk)
    y2, h2 = mamba2_scan(dt, Bc, Cc, x, A, chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=tol, rtol=tol)


def test_ssd_state_continuation():
    B, S, H, G, P, N = 1, 32, 2, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, H)))
    Bc = jax.random.normal(ks[1], (B, S, G, N))
    Cc = jax.random.normal(ks[2], (B, S, G, N))
    x = jax.random.normal(ks[3], (B, S, H, P))
    A = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
    y_full, h_full = ssd_scan(dt, Bc, Cc, x, A, chunk=8)
    h, outs = None, []
    for sl in (slice(0, 16), slice(16, 32)):
        y, h = ssd_scan(dt[:, sl], Bc[:, sl], Cc[:, sl], x[:, sl], A,
                        h0=h, chunk=8)
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(y_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_full), atol=1e-5)


def test_grouped_scan_matches_per_head_groups():
    """The jnp scan with G groups equals the one-group scan run per head
    on that head's own group: the grouping is the head -> group map."""
    B, S, H, G, P, N = 1, 12, 4, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, H)))
    Bc = jax.random.normal(ks[1], (B, S, G, N))
    Cc = jax.random.normal(ks[2], (B, S, G, N))
    x = jax.random.normal(ks[3], (B, S, H, P))
    A = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
    y, h = mamba2_scan(dt, Bc, Cc, x, A, chunk=4)
    for hh in range(H):
        g = hh // (H // G)
        y1, h1 = mamba2_scan(dt[:, :, hh:hh + 1], Bc[:, :, g:g + 1],
                             Cc[:, :, g:g + 1], x[:, :, hh:hh + 1],
                             A[hh:hh + 1], chunk=4)
        np.testing.assert_allclose(np.asarray(y[:, :, hh]),
                                   np.asarray(y1[:, :, 0]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(h[:, hh]),
                                   np.asarray(h1[:, 0]), atol=1e-5)

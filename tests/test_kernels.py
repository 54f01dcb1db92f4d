"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gru_scan.kernel import gru_scan
from repro.kernels.gru_scan.ops import gru_sequence
from repro.kernels.gru_scan.ref import gru_scan_ref
from repro.kernels.ssd.ops import ssd_full
from repro.kernels.ssd.ref import ssd_ref

RNG = np.random.default_rng(42)


# --------------------------------------------------------------------------
# GRU scan
# --------------------------------------------------------------------------

GRU_SHAPES = [
    (1, 1, 8),
    (3, 24, 32),     # the paper's shape (N=32, T=24h)
    (128, 24, 32),
    (130, 24, 32),   # ragged batch vs b_tile
    (16, 7, 16),
    (5, 50, 64),
]


@pytest.mark.parametrize("b,t,n", GRU_SHAPES)
def test_gru_scan_matches_ref(b, t, n):
    xg = jnp.asarray(RNG.normal(size=(b, t, 3 * n)), jnp.float32)
    whh = jnp.asarray(RNG.normal(size=(n, 3 * n)) * 0.3, jnp.float32)
    bhh = jnp.asarray(RNG.normal(size=(3 * n,)) * 0.1, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(gru_scan(xg, whh, bhh)),
        np.asarray(gru_scan_ref(xg, whh, bhh)),
        atol=1e-5, rtol=1e-5,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gru_scan_dtypes(dtype):
    b, t, n = 4, 12, 16
    xg = jnp.asarray(RNG.normal(size=(b, t, 3 * n)), dtype)
    whh = jnp.asarray(RNG.normal(size=(n, 3 * n)) * 0.3, dtype)
    bhh = jnp.asarray(RNG.normal(size=(3 * n,)) * 0.1, dtype)
    out = gru_scan(xg, whh, bhh)
    ref = gru_scan_ref(xg, whh, bhh)
    assert out.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_gru_sequence_full_layer():
    """ops.py wrapper: hoisted input projection + kernel == direct math."""
    b, t, f, n = 6, 24, 38, 32
    x = jnp.asarray(RNG.normal(size=(b, t, f)), jnp.float32)
    w_ih = jnp.asarray(RNG.normal(size=(f, 3 * n)) * 0.2, jnp.float32)
    w_hh = jnp.asarray(RNG.normal(size=(n, 3 * n)) * 0.2, jnp.float32)
    b_ih = jnp.zeros(3 * n)
    b_hh = jnp.zeros(3 * n)
    out = gru_sequence(x, w_ih, w_hh, b_ih, b_hh)
    ref = gru_scan_ref(x @ w_ih + b_ih, w_hh, b_hh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# --------------------------------------------------------------------------
# gradients: Pallas ops vs oracle under jax.grad, across dtypes + odd lengths
# --------------------------------------------------------------------------

GRAD_DTYPES = [jnp.float32, jnp.bfloat16]


def assert_grads_close(got, ref, tol: float) -> None:
    """Scale-aware gradient comparison: max |got - ref| within ``tol`` of the
    reference's own magnitude.  Elementwise rtol is meaningless for bf16
    gradients whose cotangents span orders of magnitude."""
    for g, r in zip(got, ref):
        assert g.dtype == r.dtype
        g32 = np.asarray(g, np.float32)
        r32 = np.asarray(r, np.float32)
        assert np.all(np.isfinite(g32))
        scale = max(1.0, float(np.max(np.abs(r32))))
        np.testing.assert_array_less(np.max(np.abs(g32 - r32)), tol * scale)


GRU_GRAD_SHAPES = [
    (3, 7, 16),      # odd T, not a multiple of any tile
    (2, 13, 32),     # odd T at the paper's hidden size
]


@pytest.mark.parametrize("dtype", GRAD_DTYPES)
@pytest.mark.parametrize("b,t,n", GRU_GRAD_SHAPES)
def test_gru_scan_grad_matches_ref(dtype, b, t, n):
    """d(loss)/d(inputs, weights, bias) through the Pallas op equals the
    oracle's gradients — the custom_vjp must not just "flow", it must be
    *correct* for every argument, dtype, and ragged sequence length."""
    from repro.kernels.gru_scan.ops import gru_scan_op

    xg = jnp.asarray(RNG.normal(size=(b, t, 3 * n)), dtype)
    whh = jnp.asarray(RNG.normal(size=(n, 3 * n)) * 0.3, dtype)
    bhh = jnp.asarray(RNG.normal(size=(3 * n,)) * 0.1, dtype)

    def loss(fn):
        return lambda x, w, bb: jnp.sum(fn(x, w, bb).astype(jnp.float32) ** 2)

    g = jax.grad(loss(gru_scan_op), argnums=(0, 1, 2))(xg, whh, bhh)
    g_ref = jax.grad(loss(gru_scan_ref), argnums=(0, 1, 2))(xg, whh, bhh)
    assert_grads_close(g, g_ref, tol=1e-5 if dtype == jnp.float32 else 3e-2)


SSD_GRAD_CASES = [
    # (s, chunk): odd lengths rag against the chunking
    (23, 8),
    (37, 16),
]


@pytest.mark.parametrize("dtype", GRAD_DTYPES)
@pytest.mark.parametrize("s,chunk", SSD_GRAD_CASES)
def test_ssd_grad_matches_ref(dtype, s, chunk):
    """SSD kernel gradients wrt activations AND dt/B/C match the oracle
    across dtypes and sequence lengths that do not divide the chunk."""
    b, h, p, n = 1, 2, 8, 8
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), dtype)
    dt = jax.nn.softplus(jnp.asarray(RNG.normal(size=(b, s, h)), dtype))
    a = -jnp.exp(jnp.asarray(RNG.normal(size=(h,)) * 0.3, jnp.float32))
    bm = jnp.asarray(RNG.normal(size=(b, s, n)), dtype)
    cm = jnp.asarray(RNG.normal(size=(b, s, n)), dtype)

    def loss(fn):
        return lambda xx, dd, bb, cc: jnp.sum(
            fn(xx, dd, a.astype(dtype), bb, cc).astype(jnp.float32) ** 2
        )

    kernel = lambda xx, dd, aa, bb, cc: ssd_full(xx, dd, aa, bb, cc, chunk=chunk)
    g = jax.grad(loss(kernel), argnums=(0, 1, 2, 3))(x, dt, bm, cm)
    g_ref = jax.grad(loss(ssd_ref), argnums=(0, 1, 2, 3))(x, dt, bm, cm)
    assert_grads_close(g, g_ref, tol=1e-4 if dtype == jnp.float32 else 5e-2)


def test_gru_scan_grads_flow():
    """The op must be differentiable (custom_vjp through the oracle) and the
    gradient must equal the oracle's gradient."""
    from repro.kernels.gru_scan.ops import gru_scan_op

    b, t, n = 3, 8, 16
    xg = jnp.asarray(RNG.normal(size=(b, t, 3 * n)), jnp.float32)
    whh = jnp.asarray(RNG.normal(size=(n, 3 * n)) * 0.3, jnp.float32)
    bhh = jnp.zeros(3 * n)
    g = jax.grad(lambda w: jnp.sum(gru_scan_op(xg, w, bhh) ** 2))(whh)
    g_ref = jax.grad(lambda w: jnp.sum(gru_scan_ref(xg, w, bhh) ** 2))(whh)
    assert bool(jnp.all(jnp.isfinite(g)))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4, rtol=1e-4)


def test_ssd_op_grads_flow():
    from repro.kernels.ssd.ops import ssd_full

    b, s, h, p, n = 1, 24, 2, 8, 8
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(RNG.normal(size=(b, s, h)), jnp.float32))
    a = -jnp.exp(jnp.asarray(RNG.normal(size=(h,)) * 0.3, jnp.float32))
    bm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    cm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    g = jax.grad(lambda xx: jnp.sum(ssd_full(xx, dt, a, bm, cm, chunk=8) ** 2))(x)
    g_ref = jax.grad(lambda xx: jnp.sum(ssd_ref(xx, dt, a, bm, cm) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=5e-3, rtol=5e-3)


# --------------------------------------------------------------------------
# SSD chunk scan
# --------------------------------------------------------------------------

SSD_SHAPES = [
    # (b, s, h, p, n, chunk)
    (1, 16, 1, 8, 8, 8),
    (2, 64, 4, 16, 32, 16),
    (1, 37, 2, 8, 16, 16),    # ragged seq vs chunk
    (3, 128, 8, 32, 64, 32),
    (2, 96, 3, 16, 16, 32),   # h not a multiple of 8 -> one tile of all heads
    (2, 48, 16, 8, 16, 16),   # two 8-head tiles
]


@pytest.mark.parametrize("b,s,h,p,n,chunk", SSD_SHAPES)
def test_ssd_matches_naive_recurrence(b, s, h, p, n, chunk):
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(RNG.normal(size=(b, s, h)), jnp.float32))
    a = -jnp.exp(jnp.asarray(RNG.normal(size=(h,)) * 0.5, jnp.float32))
    bm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    cm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    out = ssd_full(x, dt, a, bm, cm, chunk=chunk)
    ref = ssd_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-4, rtol=1e-4)


def test_ssd_strong_decay_localizes():
    """With very fast decay the SSD output reduces to the diagonal term
    dt * C.B * x — a physics sanity check on the state recurrence."""
    b, s, h, p, n = 1, 12, 2, 4, 8
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.full((b, s, h), 1.0)
    a = jnp.full((h,), -50.0)  # state dies between steps
    bm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    cm = jnp.asarray(RNG.normal(size=(b, s, n)), jnp.float32)
    out = ssd_full(x, dt, a, bm, cm, chunk=4)
    diag = jnp.einsum("bsn,bsn->bs", cm, bm)[:, :, None, None] * x * 1.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(diag), atol=1e-3, rtol=1e-3)

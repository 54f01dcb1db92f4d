"""Jitted public wrapper: full GRU layer = hoisted MXU matmul + Pallas scan.

Backend selection lives in ``repro.kernels.backend``: the kernels compile
on TPU and run in interpret mode elsewhere, and off-TPU
``REPRO_PALLAS_INTERPRET=1`` also routes the backward pass through the
interpret-mode backward kernel.

``pallas_call`` has no reverse-mode rule, so the op carries a
``custom_vjp``.  The forward stashes its own output (the hidden-state
sequence) as the residual; the backward is then a *single* reverse-time
pass — the hand-written Pallas kernel on TPU, the pure-jnp
``gru_scan_bwd_ref`` reverse scan elsewhere.  Neither reruns the forward,
unlike the previous oracle-recompute pairing (``jax.vjp(gru_scan_ref)``),
which is kept below as ``gru_scan_oracle`` purely for benchmarking.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.gru_scan.kernel import gru_scan, gru_scan_bwd
from repro.kernels.gru_scan.ref import gru_scan_bwd_ref, gru_scan_ref


@jax.custom_vjp
def gru_scan_op(x_gates: jnp.ndarray, w_hh: jnp.ndarray, b_hh: jnp.ndarray) -> jnp.ndarray:
    return gru_scan(x_gates, w_hh, b_hh)


def _fwd(x_gates, w_hh, b_hh):
    h_seq = gru_scan(x_gates, w_hh, b_hh)
    return h_seq, (x_gates, w_hh, b_hh, h_seq)


def _bwd(residuals, cotangent):
    x_gates, w_hh, b_hh, h_seq = residuals
    if backend.pallas_backward():
        return gru_scan_bwd(x_gates, w_hh, b_hh, h_seq, cotangent)
    return gru_scan_bwd_ref(x_gates, w_hh, b_hh, h_seq, cotangent)


gru_scan_op.defvjp(_fwd, _bwd)


@jax.custom_vjp
def gru_scan_oracle(x_gates: jnp.ndarray, w_hh: jnp.ndarray, b_hh: jnp.ndarray) -> jnp.ndarray:
    """The pre-residual pairing (benchmark baseline only): Pallas forward,
    backward recomputes the whole forward through the jnp oracle and
    transposes it."""
    return gru_scan(x_gates, w_hh, b_hh)


def _oracle_fwd(x_gates, w_hh, b_hh):
    return gru_scan_oracle(x_gates, w_hh, b_hh), (x_gates, w_hh, b_hh)


def _oracle_bwd(residuals, cotangent):
    _, vjp = jax.vjp(gru_scan_ref, *residuals)
    return vjp(cotangent)


gru_scan_oracle.defvjp(_oracle_fwd, _oracle_bwd)


def gru_sequence(
    x: jnp.ndarray,       # (B, T, F)
    w_ih: jnp.ndarray,    # (F, 3N)
    w_hh: jnp.ndarray,    # (N, 3N)
    b_ih: jnp.ndarray,    # (3N,)
    b_hh: jnp.ndarray,    # (3N,)
) -> jnp.ndarray:
    """Hidden sequence (B, T, N) for one GRU layer."""
    x_gates = x @ w_ih + b_ih  # one large MXU matmul over all timesteps
    return gru_scan_op(x_gates, w_hh, b_hh)

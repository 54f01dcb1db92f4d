"""Jitted public wrapper for the SSD Pallas kernel.

Accepts the chunked layout produced by ``repro.models.mamba2``; backend
selection (interpret mode off-TPU, backward routing,
``REPRO_PALLAS_INTERPRET``) lives in ``repro.kernels.backend``.
``ssd_full`` is the convenience entry point taking an unchunked sequence
(used by tests to sweep shapes against the oracle).

``pallas_call`` has no reverse-mode rule, so the op carries a
``custom_vjp``.  The forward stashes the chunk-entry states S_k as the
residual; the backward is then one reverse pass over chunks — the
hand-written Pallas kernel on TPU, the pure-jnp ``ssd_chunk_scan_bwd_ref``
reverse scan elsewhere.  The previous oracle-recompute pairing is kept as
``ssd_chunk_scan_oracle`` purely for benchmarking.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import backend
from repro.kernels.ssd.kernel import ssd_chunk_scan as _kernel
from repro.kernels.ssd.kernel import ssd_chunk_scan_bwd as _kernel_bwd
from repro.kernels.ssd.ref import ssd_chunk_scan_bwd_ref, ssd_chunk_scan_ref


@jax.custom_vjp
def ssd_chunk_scan(xc, dtc, cum, bc, cc):
    """Chunked inputs (B, NC, L, ...) -> y (B, NC, L, H, P)."""
    return _kernel(xc, dtc, cum, bc, cc)


def _fwd(xc, dtc, cum, bc, cc):
    y, states = _kernel(xc, dtc, cum, bc, cc, return_states=True)
    return y, (xc, dtc, cum, bc, cc, states)


def _bwd(residuals, cotangent):
    xc, dtc, cum, bc, cc, states = residuals
    if backend.pallas_backward():
        return _kernel_bwd(xc, dtc, cum, bc, cc, states, cotangent)
    return ssd_chunk_scan_bwd_ref(xc, dtc, cum, bc, cc, states, cotangent)


ssd_chunk_scan.defvjp(_fwd, _bwd)


@jax.custom_vjp
def ssd_chunk_scan_oracle(xc, dtc, cum, bc, cc):
    """The pre-residual pairing (benchmark baseline only): Pallas forward,
    backward recomputes the whole forward through the jnp oracle."""
    return _kernel(xc, dtc, cum, bc, cc)


def _oracle_fwd(xc, dtc, cum, bc, cc):
    return ssd_chunk_scan_oracle(xc, dtc, cum, bc, cc), (xc, dtc, cum, bc, cc)


def _oracle_bwd(residuals, cotangent):
    _, vjp = jax.vjp(ssd_chunk_scan_ref, *residuals)
    return vjp(cotangent)


ssd_chunk_scan_oracle.defvjp(_oracle_fwd, _oracle_bwd)


def ssd_full(
    x: jnp.ndarray,      # (B, S, H, P)
    dt: jnp.ndarray,     # (B, S, H)
    a: jnp.ndarray,      # (H,)
    b_mat: jnp.ndarray,  # (B, S, N)
    c_mat: jnp.ndarray,  # (B, S, N)
    chunk: int = 64,
) -> jnp.ndarray:
    """Unchunked convenience wrapper: pads, chunks, runs the kernel."""
    b, s, h, p = x.shape
    n = b_mat.shape[-1]
    nc = -(-s // chunk)
    pad = nc * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    bc = b_mat.reshape(b, nc, chunk, n)
    cc = c_mat.reshape(b, nc, chunk, n)
    dac = dtc * a[None, None, None, :]
    cum = jnp.cumsum(dac, axis=2)
    y = ssd_chunk_scan(xc, dtc, cum, bc, cc)
    return y.reshape(b, nc * chunk, h, p)[:, :s]

"""Fused GRU recurrence — Pallas TPU kernel.

TPU adaptation of the paper's compute hot spot (the 2-layer GRU runs over
every ICU stay at every local client step).  The input projections
``x_t @ W_ih + b_ih`` for ALL timesteps are hoisted out of the recurrence as
one large MXU matmul (done in ops.py); the kernel then keeps the hidden
state ``h`` and the recurrent weights ``W_hh`` resident in VMEM and walks
the T timesteps with a ``fori_loop`` — the sequential part never round-trips
through HBM, which is what makes a recurrence bandwidth-hostile when
implemented naively.

Grid: batch tiles.  Per program instance the VMEM working set is
``(B_TILE, T, 3N) + (N, 3N) + (B_TILE, N)`` — for the paper's N=32 this is
a few hundred KB; B_TILE=128 keeps the per-step ``(B_TILE, N) @ (N, 3N)``
matmul MXU-shaped on the batch dimension.

The bias travels as a ``(1, 3N)`` row.  The cohort engine vmaps the kernel
over clients, which adds a leading client axis to every block; a 1-D
``(3N,)`` block would then sit over a ``(C, 3N)`` array with a partial
second-to-last dimension, which Mosaic's tiling rule refuses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret as _interpret


def _gru_kernel(xg_ref, w_hh_ref, b_hh_ref, out_ref):
    """xg: (B_TILE, T, 3N) precomputed input gates; out: (B_TILE, T, N)."""
    b_tile, t_len, three_n = xg_ref.shape
    n = three_n // 3
    w_hh = w_hh_ref[...].astype(jnp.float32)        # (N, 3N) resident in VMEM
    b_hh = b_hh_ref[...].astype(jnp.float32)        # (1, 3N)

    def step(t, h):
        gx = xg_ref[:, t, :].astype(jnp.float32)    # (B_TILE, 3N)
        gh = h @ w_hh + b_hh
        xr, xz, xn = gx[:, :n], gx[:, n : 2 * n], gx[:, 2 * n :]
        hr, hz, hn = gh[:, :n], gh[:, n : 2 * n], gh[:, 2 * n :]
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        cand = jnp.tanh(xn + r * hn)
        h_new = (1.0 - z) * cand + z * h
        out_ref[:, t, :] = h_new.astype(out_ref.dtype)
        return h_new

    h0 = jnp.zeros((b_tile, n), dtype=jnp.float32)
    jax.lax.fori_loop(0, t_len, step, h0)


def gru_scan(
    x_gates: jnp.ndarray,   # (B, T, 3N) = x @ W_ih + b_ih
    w_hh: jnp.ndarray,      # (N, 3N)
    b_hh: jnp.ndarray,      # (3N,)
    *,
    b_tile: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Hidden-state sequence (B, T, N).

    ``interpret=None`` takes ``backend.interpret()``: compiled on a TPU,
    interpreted elsewhere.
    """
    b, t, three_n = x_gates.shape
    n = three_n // 3
    b_tile = min(b_tile, b)
    num_tiles = -(-b // b_tile)
    pad = num_tiles * b_tile - b
    if pad:
        x_gates = jnp.pad(x_gates, ((0, pad), (0, 0), (0, 0)))

    out = pl.pallas_call(
        _gru_kernel,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((b_tile, t, three_n), lambda i: (i, 0, 0)),
            pl.BlockSpec((n, three_n), lambda i: (0, 0)),
            pl.BlockSpec((1, three_n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((b_tile, t, n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_tiles * b_tile, t, n), x_gates.dtype),
        interpret=_interpret(interpret),
    )(x_gates, w_hh, b_hh.reshape(1, three_n))
    return out[:b]


def _gru_bwd_kernel(xg_ref, w_hh_ref, b_hh_ref, h_ref, dy_ref, dxg_ref, dw_ref, db_ref):
    """Reverse-time backward over one batch tile.

    Gates are rebuilt from the stashed hidden states (one (B_TILE, N) @
    (N, 3N) matmul per step — the forward's own cost) instead of rerunning
    the forward scan.  Weight cotangents use the grid-reduction pattern:
    the dw/db output blocks ignore the tile index, so revisits are
    consecutive; tile 0 zero-initialises, every tile accumulates.
    """
    b_tile, t_len, three_n = xg_ref.shape
    n = three_n // 3
    tile = pl.program_id(0)
    w_hh = w_hh_ref[...].astype(jnp.float32)
    b_hh = b_hh_ref[...].astype(jnp.float32)

    @pl.when(tile == 0)
    def _zero_accumulators():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    def step(k, carry):
        dh, dw, db = carry
        t = t_len - 1 - k
        tm1 = jnp.maximum(t - 1, 0)
        gx = xg_ref[:, t, :].astype(jnp.float32)                      # (B, 3N)
        h_prev = jnp.where(t > 0, h_ref[:, tm1, :].astype(jnp.float32), 0.0)
        dy_t = dy_ref[:, t, :].astype(jnp.float32)
        gh = h_prev @ w_hh + b_hh
        xr, xz, xn = gx[:, :n], gx[:, n : 2 * n], gx[:, 2 * n :]
        hr, hz, hn = gh[:, :n], gh[:, n : 2 * n], gh[:, 2 * n :]
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        cand = jnp.tanh(xn + r * hn)

        dh_total = dy_t + dh
        dz = dh_total * (h_prev - cand)
        da_n = dh_total * (1.0 - z) * (1.0 - cand * cand)
        da_r = da_n * hn * r * (1.0 - r)
        da_z = dz * z * (1.0 - z)
        d_gx = jnp.concatenate([da_r, da_z, da_n], axis=-1)           # (B, 3N)
        d_gh = jnp.concatenate([da_r, da_z, da_n * r], axis=-1)       # (B, 3N)
        dxg_ref[:, t, :] = d_gx.astype(dxg_ref.dtype)

        dh_new = dh_total * z + d_gh @ w_hh.T
        return dh_new, dw + h_prev.T @ d_gh, db + d_gh.sum(axis=0, keepdims=True)

    carry0 = (
        jnp.zeros((b_tile, n), dtype=jnp.float32),
        jnp.zeros((n, three_n), dtype=jnp.float32),
        jnp.zeros((1, three_n), dtype=jnp.float32),
    )
    _, dw_tile, db_tile = jax.lax.fori_loop(0, t_len, step, carry0)
    dw_ref[...] += dw_tile.astype(dw_ref.dtype)
    db_ref[...] += db_tile.astype(db_ref.dtype)


def gru_scan_bwd(
    x_gates: jnp.ndarray,   # (B, T, 3N)
    w_hh: jnp.ndarray,      # (N, 3N)
    b_hh: jnp.ndarray,      # (3N,)
    h_seq: jnp.ndarray,     # (B, T, N)  forward output (residual)
    dy: jnp.ndarray,        # (B, T, N)  output cotangent
    *,
    b_tile: int = 128,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-pass Pallas backward: ``(dx_gates, dw_hh, db_hh)``."""
    b, t, three_n = x_gates.shape
    n = three_n // 3
    b_tile = min(b_tile, b)
    num_tiles = -(-b // b_tile)
    pad = num_tiles * b_tile - b
    if pad:
        # Zero-padded rows contribute zero to every cotangent.
        x_gates = jnp.pad(x_gates, ((0, pad), (0, 0), (0, 0)))
        h_seq = jnp.pad(h_seq, ((0, pad), (0, 0), (0, 0)))
        dy = jnp.pad(dy, ((0, pad), (0, 0), (0, 0)))

    dxg, dw_hh, db_hh = pl.pallas_call(
        _gru_bwd_kernel,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((b_tile, t, three_n), lambda i: (i, 0, 0)),
            pl.BlockSpec((n, three_n), lambda i: (0, 0)),
            pl.BlockSpec((1, three_n), lambda i: (0, 0)),
            pl.BlockSpec((b_tile, t, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((b_tile, t, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b_tile, t, three_n), lambda i: (i, 0, 0)),
            pl.BlockSpec((n, three_n), lambda i: (0, 0)),
            pl.BlockSpec((1, three_n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_tiles * b_tile, t, three_n), x_gates.dtype),
            jax.ShapeDtypeStruct((n, three_n), jnp.float32),
            jax.ShapeDtypeStruct((1, three_n), jnp.float32),
        ],
        interpret=_interpret(interpret),
    )(x_gates, w_hh, b_hh.reshape(1, three_n), h_seq, dy)
    return dxg[:b], dw_hh.astype(w_hh.dtype), db_hh.reshape(three_n).astype(b_hh.dtype)

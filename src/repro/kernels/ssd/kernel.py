"""Mamba2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060, sec. 6): within a
chunk of length L the dual quadratic form ``(C B^T ⊙ decay)`` runs on the
MXU; the inter-chunk state ``S (H, P, N)`` is carried in a VMEM *scratch*
buffer across sequential grid steps — the TPU grid executes in order, so the
innermost grid axis (chunks) implements the recurrence without HBM
round-trips of the state.

Layout.  The public wrappers take the model's ``(B, NC, L, H, ...)``
layout and hand the kernels a heads-major one: ``x``/``y`` as
``(B, NC, H, L, P)`` and ``dt``/``cum`` as ``(B, NC, H, L)``.  Every block
then ends in a full ``(L, P)`` or ``(h_tile, L)`` pair, which Mosaic's
``(8, 128)`` tiling rule accepts when ``h_tile`` is a multiple of 8 or the
whole head count, and each head is a 2-D problem: ``(L, L)`` and
``(L, P)`` matmuls, row vectors ``(1, L)`` and their transposes.  The
kernels loop over the heads of a tile, so only one head's ``(L, L)``
temporaries are live at a time.

Grid: ``(batch, head_tiles, chunks)`` with chunks innermost.  At
mamba2-130m widths (H=24, P=64, N=128, L=256) a tile of 8 heads keeps the
double-buffered blocks and one head's temporaries inside the scoped VMEM
limit; ``tests/test_tpu_compile.py`` compiles both kernels there for v5e.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret as _interpret

_F32 = jnp.float32


def _pick_h_tile(h: int) -> int:
    """8 heads per tile where they divide, else all of them (Mosaic needs
    the ``(h_tile, L)`` block's first dim to be a multiple of 8 or whole)."""
    return 8 if h % 8 == 0 else h


def _dot_t(a, b, contract: int):
    """``a^T @ b`` (contract=0) or ``a @ b^T`` (contract=1), f32 accumulate."""
    dims = (((contract,), (contract,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _head_vectors(dt_ref, cum_ref, h: int):
    """Head ``h``'s dt and cum as rows (1, L) and columns (L, 1)."""
    dt_row = dt_ref[0, 0, h : h + 1, :].astype(_F32)
    cum_row = cum_ref[0, 0, h : h + 1, :].astype(_F32)
    return dt_row, cum_row, dt_row.T, cum_row.T


def _last(row):
    """The last entry of a (1, L) row as (1, 1), by a masked lane reduction
    (a lane slice at offset L-1 gives a layout Mosaic cannot broadcast)."""
    l_len = row.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lanes == l_len - 1, row, 0.0), axis=1, keepdims=True)


def _decay(cum_row, cum_col):
    """decay[l, m] = exp(cum[l] - cum[m]) for l >= m, else 0.  The mask sits
    inside the exponent: the l < m differences are large and positive."""
    l_len = cum_row.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (l_len, l_len), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (l_len, l_len), 1)
    return jnp.exp(jnp.where(rows >= cols, cum_col - cum_row, -1e30))


def _ssd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, *rest):
    """Blocks (leading (1, 1) grid dims indexed away):

    x/out: (Ht, L, P); dt/cum: (Ht, L); b/c: (L, N), shared across heads;
    entry (optional): (Ht, P, N) chunk-entry states; state scratch:
    (Ht, P, N) fp32, persists across chunks.
    """
    out_ref, *entry, state_ref = rest
    chunk_idx = pl.program_id(2)

    @pl.when(chunk_idx == 0)
    def _reset():
        state_ref[...] = jnp.zeros_like(state_ref)

    b_mat = b_ref[0, 0].astype(_F32)             # (L, N)
    c_mat = c_ref[0, 0].astype(_F32)             # (L, N)
    cb = _dot_t(c_mat, b_mat, 1)                 # (L, L) = C B^T, MXU
    for h in range(x_ref.shape[2]):
        x = x_ref[0, 0, h].astype(_F32)          # (L, P)
        dt_row, cum_row, dt_col, cum_col = _head_vectors(dt_ref, cum_ref, h)
        state = state_ref[h]                     # (P, N)
        if entry:
            entry[0][0, 0, h] = state.astype(entry[0].dtype)

        # intra-chunk quadratic ("attention") form + carried-state term
        w = cb * _decay(cum_row, cum_col) * dt_row                    # (L, L)
        y_inter = _dot_t(c_mat, state, 1) * jnp.exp(cum_col)          # (L, P)
        out_ref[0, 0, h] = (_dot(w, x) + y_inter).astype(out_ref.dtype)

        # state update for the next chunk
        cum_last = _last(cum_row)                            # (1, 1)
        in_decay = jnp.exp(cum_last - cum_col) * dt_col               # (L, 1)
        state_ref[h] = state * jnp.exp(cum_last) + _dot_t(x * in_decay, b_mat, 0)


def _heads_major(x, dt, cum):
    """(B, NC, L, H, P) / (B, NC, L, H) -> (B, NC, H, L, P) / (B, NC, H, L)."""
    return (
        jnp.swapaxes(x, 2, 3),
        jnp.swapaxes(dt, 2, 3),
        jnp.swapaxes(cum, 2, 3),
    )


def ssd_chunk_scan(
    x: jnp.ndarray,      # (B, NC, L, H, P) fp32
    dt: jnp.ndarray,     # (B, NC, L, H)
    cum: jnp.ndarray,    # (B, NC, L, H)  within-chunk cumulative log-decay
    b_mat: jnp.ndarray,  # (B, NC, L, N)
    c_mat: jnp.ndarray,  # (B, NC, L, N)
    *,
    interpret: bool | None = None,
    return_states: bool = False,
):
    """Returns y (B, NC, L, H, P); with ``return_states`` also the fp32
    chunk-entry states (B, NC, H, P, N).  ``interpret=None`` takes
    ``backend.interpret()``."""
    batch, nc, l_len, h, p = x.shape
    n = b_mat.shape[-1]
    h_tile = _pick_h_tile(h)
    xh, dth, cumh = _heads_major(x, dt, cum)

    y_spec = pl.BlockSpec((1, 1, h_tile, l_len, p), lambda b, hh, c: (b, c, hh, 0, 0))
    vec_spec = pl.BlockSpec((1, 1, h_tile, l_len), lambda b, hh, c: (b, c, hh, 0))
    mat_spec = pl.BlockSpec((1, 1, l_len, n), lambda b, hh, c: (b, c, 0, 0))
    y_shape = jax.ShapeDtypeStruct(xh.shape, x.dtype)
    out_specs, out_shape = y_spec, y_shape
    if return_states:
        out_specs = [
            y_spec,
            pl.BlockSpec((1, 1, h_tile, p, n), lambda b, hh, c: (b, c, hh, 0, 0)),
        ]
        out_shape = [y_shape, jax.ShapeDtypeStruct((batch, nc, h, p, n), _F32)]

    out = pl.pallas_call(
        _ssd_kernel,
        grid=(batch, h // h_tile, nc),            # chunks innermost: sequential state
        in_specs=[y_spec, vec_spec, vec_spec, mat_spec, mat_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h_tile, p, n), _F32)],
        interpret=_interpret(interpret),
    )(xh, dth, cumh, b_mat, c_mat)
    if return_states:
        return jnp.swapaxes(out[0], 2, 3), out[1]
    return jnp.swapaxes(out, 2, 3)


def _ssd_bwd_kernel(
    x_ref, dt_ref, cum_ref, b_ref, c_ref, s_ref, dy_ref,
    dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref, ds_ref,
):
    """Reverse-chunk backward for one (batch, head tile) pair.

    The grid walks chunks last-to-first (index maps flip the chunk axis), so
    the dS carry lives in VMEM scratch exactly like the forward's state.
    dB/dC are shared across heads: each head tile writes its own partial
    ``(L, N)`` block, summed over tiles by the wrapper, so every output
    block is written exactly once.
    """
    chunk_idx = pl.program_id(2)

    @pl.when(chunk_idx == 0)
    def _reset():  # first visit = last chunk: final state has no cotangent
        ds_ref[...] = jnp.zeros_like(ds_ref)

    b_mat = b_ref[0, 0].astype(_F32)             # (L, N)
    c_mat = c_ref[0, 0].astype(_F32)             # (L, N)
    cb = _dot_t(c_mat, b_mat, 1)                 # (L, L)
    l_len, n = b_mat.shape
    last_row = jax.lax.broadcasted_iota(jnp.int32, (l_len, 1), 0) == l_len - 1
    dcb = jnp.zeros((l_len, l_len), _F32)        # sum over the tile's heads
    dc = jnp.zeros((l_len, n), _F32)
    db = jnp.zeros((l_len, n), _F32)
    for h in range(x_ref.shape[2]):
        x = x_ref[0, 0, h].astype(_F32)          # (L, P)
        dy = dy_ref[0, 0, h].astype(_F32)        # (L, P)
        s_k = s_ref[0, 0, h].astype(_F32)        # (P, N) chunk-entry state
        ds = ds_ref[h]                           # (P, N) carry
        dt_row, cum_row, dt_col, cum_col = _head_vectors(dt_ref, cum_ref, h)
        decay = _decay(cum_row, cum_col)

        # intra-chunk quadratic form transpose
        w = cb * decay * dt_row
        dw = _dot_t(dy, x, 1)                    # (L, L) = dy x^T
        dx = _dot_t(w, dy, 0)                    # (L, P) = w^T dy
        dcb = dcb + dw * decay * dt_row
        t = dw * cb * decay
        ddt_row = jnp.sum(t, axis=0, keepdims=True)                   # (1, L)
        term = t * dt_row
        dcum_col = jnp.sum(term, axis=1, keepdims=True)               # (L, 1)
        dcum_row = -jnp.sum(term, axis=0, keepdims=True)              # (1, L)

        # inter-chunk carried-state contribution
        sd = jnp.exp(cum_col)                                         # (L, 1)
        d_cs = dy * sd
        dc = dc + _dot(d_cs, s_k)
        ds_from_y = _dot_t(d_cs, c_mat, 0)                            # (P, N)
        y_inter = _dot_t(c_mat, s_k, 1) * sd                          # (L, P)
        dcum_col = dcum_col + jnp.sum(dy * y_inter, axis=1, keepdims=True)

        # state-update transpose
        cum_last = _last(cum_row)                            # (1, 1)
        cd = jnp.exp(cum_last)
        e_col = jnp.exp(cum_last - cum_col)                           # (L, 1)
        indec = e_col * dt_col
        ds_ref[h] = ds * cd + ds_from_y
        bds = _dot_t(b_mat, ds, 1)                                    # (L, P) = B dS^T
        g = jnp.sum(x * bds, axis=1, keepdims=True)                   # (L, 1)
        db = db + _dot(x * indec, ds)
        dx = dx + bds * indec
        ddt_col = g * e_col
        dcum_col = dcum_col - g * indec
        s_dot = jnp.sum(jnp.sum(ds * s_k, axis=1, keepdims=True), axis=0, keepdims=True)
        last = s_dot * cd + jnp.sum(g * indec, axis=0, keepdims=True)  # (1, 1)
        dcum_col = dcum_col + jnp.where(last_row, last, 0.0)

        dx_ref[0, 0, h] = dx.astype(dx_ref.dtype)
        ddt_ref[0, 0, h : h + 1, :] = (ddt_row + ddt_col.T).astype(ddt_ref.dtype)
        dcum_ref[0, 0, h : h + 1, :] = (dcum_row + dcum_col.T).astype(dcum_ref.dtype)
    dc_ref[0, 0, 0] = (dc + _dot(dcb, b_mat)).astype(dc_ref.dtype)
    db_ref[0, 0, 0] = (db + _dot_t(dcb, c_mat, 0)).astype(db_ref.dtype)


def ssd_chunk_scan_bwd(
    x: jnp.ndarray,       # (B, NC, L, H, P)
    dt: jnp.ndarray,      # (B, NC, L, H)
    cum: jnp.ndarray,     # (B, NC, L, H)
    b_mat: jnp.ndarray,   # (B, NC, L, N)
    c_mat: jnp.ndarray,   # (B, NC, L, N)
    states: jnp.ndarray,  # (B, NC, H, P, N) fp32 chunk-entry states
    dy: jnp.ndarray,      # (B, NC, L, H, P)
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, ...]:
    """Single-pass Pallas backward: ``(dx, ddt, dcum, db, dc)``."""
    batch, nc, l_len, h, p = x.shape
    n = b_mat.shape[-1]
    h_tile = _pick_h_tile(h)
    tiles = h // h_tile
    xh, dth, cumh = _heads_major(x, dt, cum)
    dyh = jnp.swapaxes(dy, 2, 3)
    rev = lambda c: nc - 1 - c

    head_spec = pl.BlockSpec((1, 1, h_tile, l_len, p), lambda b, hh, c: (b, rev(c), hh, 0, 0))
    vec_spec = pl.BlockSpec((1, 1, h_tile, l_len), lambda b, hh, c: (b, rev(c), hh, 0))
    mat_spec = pl.BlockSpec((1, 1, l_len, n), lambda b, hh, c: (b, rev(c), 0, 0))
    part_spec = pl.BlockSpec((1, 1, 1, l_len, n), lambda b, hh, c: (b, rev(c), hh, 0, 0))
    dxh, ddth, dcumh, db_parts, dc_parts = pl.pallas_call(
        _ssd_bwd_kernel,
        grid=(batch, tiles, nc),                  # chunks innermost, reversed
        in_specs=[
            head_spec, vec_spec, vec_spec, mat_spec, mat_spec,
            pl.BlockSpec((1, 1, h_tile, p, n), lambda b, hh, c: (b, rev(c), hh, 0, 0)),
            head_spec,
        ],
        out_specs=[head_spec, vec_spec, vec_spec, part_spec, part_spec],
        out_shape=[
            jax.ShapeDtypeStruct(xh.shape, x.dtype),
            jax.ShapeDtypeStruct(dth.shape, dt.dtype),
            jax.ShapeDtypeStruct(cumh.shape, cum.dtype),
            jax.ShapeDtypeStruct((batch, nc, tiles, l_len, n), _F32),
            jax.ShapeDtypeStruct((batch, nc, tiles, l_len, n), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((h_tile, p, n), _F32)],
        interpret=_interpret(interpret),
    )(xh, dth, cumh, b_mat, c_mat, states, dyh)
    dx, ddt, dcum = _heads_major(dxh, ddth, dcumh)
    return (
        dx,
        ddt,
        dcum,
        db_parts.sum(axis=2).astype(b_mat.dtype),
        dc_parts.sum(axis=2).astype(c_mat.dtype),
    )

"""Pallas TPU kernel: block-diagonal matmul (one Monarch stage).

Computes  out[t, j, :] = x[t, j, :] @ W[j].T  for W: (k, q, p) block-diagonal
factors — the paper's SparseMap operand without the zero padding: each grid
cell (j, t-tile) streams one block and one token tile into VMEM, so no MXU
cycle is spent on the off-diagonal zeros that waste 80 % of the crossbar in
the naive mapping (paper Fig. 6b).

The kernel works on the block-major layout ``(k, T, p)``: a grid cell's
token tile is the ``(bT, p)`` slab ``x[j, t*bT:(t+1)*bT]``, whose last two
dims are a sublane-aligned row range and the full block width — the shape
Mosaic tiles directly, for any ``p``.  ``bdmm``/``bdmm_q`` keep the
token-major ``(T, k, p)`` interface and transpose around the call; the
staged Monarch path in ``kernels.ops`` chains the block-major entry points
so the stride permutation between the stages is one transpose.

Grid: (k, T // bT).  VMEM working set per cell: bT*p + q*p + bT*q elements
(each double-buffered by the pipeline) — bounded regardless of T and k.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_TILE_T = 256


def _dequant_block(w, scale, p: int):
    """int8 (q, p) or nibble-packed int4 (q, p//2) -> fp32 (q, p) in VMEM:
    ``core.quant.dequantize_factor`` verbatim (plain jnp ops, VMEM-safe), so
    the kernels, the einsum path and the oracles share ONE rounding chain —
    the kernel-side analogue of applying the block's ADC full-scale range
    (cim/spec.py)."""
    from repro.core.quant import dequantize_factor

    return dequantize_factor(w, scale, unpacked_dim=p)


def _block_dot(x, w):
    """(bT, p) x (q, p) -> (bT, q) fp32: a 2-D contraction over the shared
    minor dim, the one form the MXU lowering takes for any block shape."""
    return jax.lax.dot_general(
        x, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bdmm_kernel(x_ref, w_ref, o_ref):
    # x: (1, bT, p), w: (1, q, p), o: (1, bT, q)
    o_ref[0] = _block_dot(x_ref[0], w_ref[0]).astype(o_ref.dtype)


def _bdmm_q_kernel(x_ref, w_ref, s_ref, o_ref, *, p: int):
    # x: (1, bT, p); w: (1, q, p[/2]) int8; s: (1, 1, 1) fp32 block scale
    x = x_ref[0]
    w = _dequant_block(w_ref[0], s_ref[0], p).astype(x.dtype)
    o_ref[0] = _block_dot(x, w).astype(o_ref.dtype)


def _tile(T: int, tile_t: int) -> tuple[int, int]:
    """(bT, padded T): one full-T block when T fits a tile, else tiles of
    ``tile_t`` rows (a multiple of the 16-row bf16 sublane tile)."""
    if T <= tile_t:
        return T, T
    bT = max(16, tile_t - tile_t % 16)
    return bT, T + (-T) % bT


def bdmm_kmajor(x: jax.Array, w: jax.Array, *, tile_t: int = DEFAULT_TILE_T,
                interpret: bool = False) -> jax.Array:
    """Block-major block-diagonal matmul: x (k, T, p), w (k, q, p) ->
    (k, T, q) in ``x.dtype``."""
    k, T, p = x.shape
    k2, q, p2 = w.shape
    assert (k2, p2) == (k, p), (x.shape, w.shape)
    bT, Tp = _tile(T, tile_t)
    if Tp != T:
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
    out = pl.pallas_call(
        _bdmm_kernel,
        grid=(k, Tp // bT),
        in_specs=[
            pl.BlockSpec((1, bT, p), lambda j, t: (j, t, 0)),
            pl.BlockSpec((1, q, p), lambda j, t: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bT, q), lambda j, t: (j, t, 0)),
        out_shape=jax.ShapeDtypeStruct((k, Tp, q), x.dtype),
        interpret=interpret,
    )(x, w)
    return out[:, :T] if Tp != T else out


def bdmm_q_kmajor(x: jax.Array, wq: jax.Array, scale: jax.Array, *,
                  tile_t: int = DEFAULT_TILE_T,
                  interpret: bool = False) -> jax.Array:
    """Block-major quantized block-diagonal matmul: x (k, T, p); wq (k, q,
    p[/2]) int8; scale (k, 1, 1) fp32 -> (k, T, q) in ``x.dtype``."""
    k, T, p = x.shape
    k2, q, pp = wq.shape
    assert k2 == k and pp in (p, p // 2), (x.shape, wq.shape)
    assert scale.shape == (k, 1, 1), scale.shape
    bT, Tp = _tile(T, tile_t)
    if Tp != T:
        x = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_bdmm_q_kernel, p=p),
        grid=(k, Tp // bT),
        in_specs=[
            pl.BlockSpec((1, bT, p), lambda j, t: (j, t, 0)),
            pl.BlockSpec((1, q, pp), lambda j, t: (j, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda j, t: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bT, q), lambda j, t: (j, t, 0)),
        out_shape=jax.ShapeDtypeStruct((k, Tp, q), x.dtype),
        interpret=interpret,
    )(x, wq, scale)
    return out[:, :T] if Tp != T else out


@functools.partial(jax.jit, static_argnames=("tile_t", "interpret"))
def bdmm(x: jax.Array, w: jax.Array, *, tile_t: int = DEFAULT_TILE_T,
         interpret: bool = False) -> jax.Array:
    """x: (T, k, p), w: (k, q, p) -> (T, k, q)."""
    out = bdmm_kmajor(jnp.swapaxes(x, 0, 1), w, tile_t=tile_t,
                      interpret=interpret)
    return jnp.swapaxes(out, 0, 1)


@functools.partial(jax.jit, static_argnames=("tile_t", "interpret"))
def bdmm_q(x: jax.Array, wq: jax.Array, scale: jax.Array, *,
           tile_t: int = DEFAULT_TILE_T, interpret: bool = False) -> jax.Array:
    """Quantized block-diagonal matmul with in-kernel dequantization.

    x: (T, k, p); wq: (k, q, p) int8 or (k, q, p//2) nibble-packed int4;
    scale: (k, 1, 1) fp32 per-block -> (T, k, q).  The int8/int4 weights are
    what streams HBM -> VMEM (the memory-bound decode bytes); dequantization
    happens in VMEM and the MXU accumulates in fp32.
    """
    out = bdmm_q_kmajor(jnp.swapaxes(x, 0, 1), wq, scale, tile_t=tile_t,
                        interpret=interpret)
    return jnp.swapaxes(out, 0, 1)


__all__ = ["bdmm", "bdmm_q", "bdmm_kmajor", "bdmm_q_kmajor"]

"""Pallas TPU kernel: fused two-stage Monarch matmul.

y = reshape( R-stage( P( L-stage( reshape(x) ) ) ) )

This is the TPU-native analogue of the paper's capacity-optimized DenseMap
(DESIGN.md Sec. 3): both block-diagonal stages execute per token tile with
the intermediate **resident in VMEM** — it never round-trips HBM (the
paper's "weights stay in the array; outputs stream into the next stage's
DACs", Sec. III-B3) — and the stride permutation P is folded into how
stage 2 addresses the intermediate.

The kernel runs feature-major: tokens sit on the lane axis, so every stage
is a plain 2-D matmul over one diagonal block, and P becomes a strided
sublane read.  Per token tile of ``bT`` columns:

  stage 1, block j:  u[j*q:(j+1)*q, :] = L[j] (q, p) @ x[j] (p, bT)
  stage 2, block i:  y[i] = R[i] (s, k) @ u[i::q, :] (k, bT)

``u`` is a (k*q, bT) fp32 VMEM scratch; ``u[i::q]`` gathers row i of every
stage-1 block — exactly the folded permutation.  The wrappers transpose the
activations to ``(k, p, T)`` and back from ``(q, s, T)`` in XLA.

Grid: (T // bT,).  VMEM working set: the resident factors plus the x/out
tiles and the scratch (``fused_working_set``); ``ops.monarch_mm`` falls back
to two ``bdmm`` calls when the factors exceed the VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# token tile on the lane axis: the stage-1 scratch must be exactly one lane
# tile wide for the strided (permuting) read, so tiles are 128 tokens
TILE_T = 128
VMEM_BUDGET_BYTES = 10 * 2**20  # per-core VMEM the resident factors may take
# scoped VMEM the fused kernels ask the compiler for: the factor budget,
# double-buffered, plus the token tiles and the stage-1 scratch
# (``fused_working_set``); v5e has 128 MiB of VMEM per core
VMEM_LIMIT_BYTES = 48 * 2**20


def _stages(x_ref, o_ref, u_ref, l_block, r_block):
    """Both stages over one token tile.  ``l_block(j)`` / ``r_block(i)``
    return the (q, p) / (s, k) factor blocks in the activation dtype."""
    k, _, _ = x_ref.shape
    q = u_ref.shape[0] // k
    dt = x_ref.dtype

    def stage1(j, carry):
        row = pl.multiple_of(j * q, q)
        u_ref[pl.ds(row, q), :] = jnp.dot(
            l_block(j), x_ref[j], preferred_element_type=jnp.float32)
        return carry

    def stage2(i, carry):
        ut = u_ref[pl.ds(i, k, stride=q), :].astype(dt)      # (k, bT)
        o_ref[i] = jnp.dot(r_block(i), ut, preferred_element_type=jnp.float32
                           ).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, k, stage1, 0)
    jax.lax.fori_loop(0, q, stage2, 0)


def _monarch_kernel(x_ref, l_ref, r_ref, o_ref, u_ref):
    # x: (k, p, bT); L: (k, q, p); R: (q, s, k); o: (q, s, bT)
    _stages(x_ref, o_ref, u_ref, lambda j: l_ref[j], lambda i: r_ref[i])


def _monarch_q_kernel(x_ref, l_ref, ls_ref, r_ref, rs_ref, o_ref, u_ref,
                      *, p: int, k: int):
    from repro.kernels.bdmm import _dequant_block

    # int8/int4 factors + per-block scales dequantize in VMEM one block at a
    # time; both stages and the folded permutation then run exactly as the
    # float kernel, with fp32 MXU accumulation.  Bytes moved HBM->VMEM per
    # weight: 1 (int8) or 0.5 (int4) instead of 2/4.
    dt = x_ref.dtype
    _stages(x_ref, o_ref, u_ref,
            lambda j: _dequant_block(l_ref[j], ls_ref[j], p).astype(dt),
            lambda i: _dequant_block(r_ref[i], rs_ref[i], k).astype(dt))


def _fused_call(kernel, x, factors, k, p, q, s, interpret):
    """Shared wrapper: (T, k*p) token-major in, (T, q*s) token-major out;
    T is padded to whole 128-token tiles."""
    T, _ = x.shape
    bT = TILE_T
    Tp = T + (-T) % bT
    xt = jnp.transpose(x.reshape(T, k, p), (1, 2, 0))        # (k, p, T)
    if Tp != T:
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, Tp - T)))
    whole = [pl.BlockSpec(f.shape, lambda t, n=f.ndim: (0,) * n)
             for f in factors]
    out = pl.pallas_call(
        kernel,
        grid=(Tp // bT,),
        in_specs=[pl.BlockSpec((k, p, bT), lambda t: (0, 0, t))] + whole,
        out_specs=pl.BlockSpec((q, s, bT), lambda t: (0, 0, t)),
        out_shape=jax.ShapeDtypeStruct((q, s, Tp), x.dtype),
        scratch_shapes=[pltpu.VMEM((k * q, bT), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xt, *factors)
    return jnp.transpose(out.reshape(q * s, Tp)[:, :T])


@functools.partial(jax.jit, static_argnames=("interpret",))
def monarch_fused(x: jax.Array, L: jax.Array, R: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """x: (T, din) -> (T, dout) with din = k*p, dout = q*s."""
    T, din = x.shape
    k, q, p = L.shape
    q2, s, k2 = R.shape
    assert (q2, k2) == (q, k) and k * p == din, (x.shape, L.shape, R.shape)
    return _fused_call(_monarch_kernel, x, (L.astype(x.dtype),
                                            R.astype(x.dtype)),
                       k, p, q, s, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def monarch_fused_q(x: jax.Array, Lq: jax.Array, Ls: jax.Array,
                    Rq: jax.Array, Rs: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Fused two-stage Monarch matmul over quantized factors.

    x: (T, din); Lq: (k, q, p[/2]) int8; Ls: (k, 1, 1) fp32;
    Rq: (q, s, k[/2]) int8; Rs: (q, 1, 1) fp32 -> (T, q*s).
    """
    T, din = x.shape
    k = Ls.shape[0]
    q = Rs.shape[0]
    p = din // k
    s = Rq.shape[1]
    assert k * p == din and Lq.shape[:2] == (k, q), (x.shape, Lq.shape)
    assert Lq.shape[2] in (p, p // 2) and Rq.shape[2] in (k, k // 2), (
        Lq.shape, Rq.shape)
    return _fused_call(functools.partial(_monarch_q_kernel, p=p, k=k), x,
                       (Lq, Ls, Rq, Rs), k, p, q, s, interpret)


def fused_fits(L_shape, R_shape, dtype_bytes: float = 4,
               scale_bytes: int = 0, dequant_bytes: float = 0) -> bool:
    """Do both factors fit the per-core VMEM weight budget?

    ``dtype_bytes`` is the **stored weight** width (4 fp32, 2 bf16, 1 int8,
    0.5 packed int4) — what the BlockSpecs actually pin in VMEM — so fusion
    kicks in for e.g. bf16-stored models whose fp32 factors would spill.
    For the quantized kernels, ``dequant_bytes`` counts fp32 dequant
    temporaries per weight on top of the pinned int8/int4 blocks, and
    ``scale_bytes`` the per-block scale vectors.  ``ops._dispatch`` fuses
    only when this holds AND the kernel's whole working set
    (``fused_working_set``) is under ``VMEM_LIMIT_BYTES``.
    """
    k, q, p = L_shape
    _, s, _ = R_shape
    weights = (k * q * p + q * s * k) * (dtype_bytes + dequant_bytes)
    return weights + scale_bytes <= VMEM_BUDGET_BYTES


def fused_working_set(L_shape, R_shape, dtype_bytes: float,
                      act_bytes: int) -> int:
    """VMEM bytes one grid step of the fused kernel holds: both factors and
    the x/out token tiles double-buffered by the pipeline, plus the fp32
    stage-1 scratch."""
    k, q, p = L_shape
    _, s, _ = R_shape
    bT = TILE_T
    weights = (k * q * p + q * s * k) * dtype_bytes
    tiles = (k * p + q * s) * bT * act_bytes
    return int(2 * (weights + tiles) + 4 * k * q * bT)


__all__ = ["monarch_fused", "monarch_fused_q", "fused_fits",
           "fused_working_set", "TILE_T", "VMEM_BUDGET_BYTES",
           "VMEM_LIMIT_BYTES"]

"""Unified linear layer: dense or Monarch, selected per-matmul by config.

Every parameterized matmul in the model zoo routes through this module, which
is what makes the paper's technique a first-class, globally-togglable feature
(``ModelConfig.monarch``): the same model code runs dense (the paper's
*Linear* baseline) or Monarch-sparse (*SparseMap*/*DenseMap* operand), and the
CIM mapper / dry-run / roofline all consume the same layer metadata.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import monarch as mn
from repro.core import quant as qn


@dataclasses.dataclass(frozen=True)
class MonarchSpec:
    """How to Monarch-factorize the parameterized matmuls of a model."""

    enable: bool = False
    policy: str = "paper"          # "paper" (b ~ sqrt(n)) | "mxu128" (TPU co-design)
    nblocks: Optional[int] = None  # explicit override
    backend: str = "einsum"        # "einsum" | "pallas" (fused kernel)
    min_dim: int = 256             # don't factorize tiny matmuls (routers etc.)

    def applies(self, din: int, dout: int) -> bool:
        return self.enable and min(din, dout) >= self.min_dim


def linear_init(
    key: jax.Array,
    din: int,
    dout: int,
    spec: Optional[MonarchSpec] = None,
    use_bias: bool = False,
    dtype: Any = jnp.float32,
    w_init_scale: float = 1.0,
) -> dict[str, Any]:
    """Initialize a linear layer; Monarch-factorized when spec.applies()."""
    if spec is not None and spec.applies(din, dout):
        dims = mn.make_dims(din, dout, policy=spec.policy, nblocks=spec.nblocks)
        params = mn.init_monarch(key, dims, dtype=dtype, scale=w_init_scale)
    else:
        std = w_init_scale * (1.0 / jnp.sqrt(din))
        params = {"w": (jax.random.normal(key, (din, dout)) * std).astype(dtype)}
    if use_bias:
        params["b"] = jnp.zeros((dout,), dtype=dtype)
    return params


def is_monarch(params: dict[str, Any]) -> bool:
    return "L" in params and "R" in params


def is_quantized(params: dict[str, Any]) -> bool:
    """Quantized Monarch container (core.quant): int8/int4 factors +
    per-block scales."""
    return qn.is_quantized(params)


def linear_apply(
    params: dict[str, Any],
    x: jax.Array,
    precision=None,
    backend: str = "einsum",
) -> jax.Array:
    """y = x @ W (+ b).  Dispatches on the parameter structure (including
    D2S-converted dense layers, where ``w`` becomes an {L, R} dict).

    The product is computed in ``x.dtype`` whatever the weights are stored
    in: weights are cast to the activation dtype (a no-op when they already
    match), so a bf16 residual stream stays bf16 over fp32 master weights
    and the MXU runs at activation precision.  Its operations carry the
    ``linear`` scope (``serving/tracing.py`` lists the scopes)."""
    if "w" in params and isinstance(params["w"], dict):
        inner = dict(params["w"])
        if "b" in params:
            inner["b"] = params["b"]
        return linear_apply(inner, x, precision=precision, backend=backend)
    with jax.named_scope("linear"):
        return _linear(params, x, precision, backend)


def _linear(params, x, precision, backend):
    dt = x.dtype
    if qn.is_quantized(params):
        if backend == "pallas":
            from repro.kernels import ops as kops  # lazy: avoid cycle

            y = kops.monarch_mm_q(x, params["Lq"], params["Ls"],
                                  params["Rq"], params["Rs"])
        else:
            k = params["Ls"].shape[-3]
            deq = qn.dequantize_monarch(params, k, x.shape[-1] // k)
            y = mn.monarch_multiply(x, deq["L"].astype(dt),
                                    deq["R"].astype(dt), precision=precision)
    elif is_monarch(params):
        L, R = params["L"].astype(dt), params["R"].astype(dt)
        if backend == "pallas":
            from repro.kernels import ops as kops  # lazy: avoid cycle

            y = kops.monarch_mm(x, L, R)
        else:
            y = mn.monarch_multiply(x, L, R, precision=precision)
    else:
        y = jnp.einsum("...d,df->...f", x, params["w"].astype(dt),
                       precision=precision)
    if "b" in params:
        y = y + params["b"].astype(dt)
    return y


def linear_out_dim(params: dict[str, Any]) -> int:
    if qn.is_quantized(params):
        return qn.quantized_out_dim(params)
    if is_monarch(params):
        q, s, _ = params["R"].shape
        return q * s
    return params["w"].shape[1]


def linear_param_count(params: dict[str, Any]) -> int:
    return sum(int(jnp.size(v)) for v in params.values())


__all__ = [
    "MonarchSpec",
    "linear_init",
    "linear_apply",
    "is_monarch",
    "is_quantized",
    "linear_out_dim",
    "linear_param_count",
]

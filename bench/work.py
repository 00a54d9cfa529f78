"""What each engine step had to do, counted from shapes: FLOPs and bytes.

The count is of the work the requests need, whatever implements it: the
XLA einsum or the Pallas Monarch path, the dense KV gather or the paged
kernel.  Padding rows and padded span positions are not work; neither is
reading the KV of a slot that is not in use.  So a step's count is a lower
bound of what any implementation moves and computes, and its roofline time
``max(F / peak FLOP/s, B / peak bytes/s)`` a lower bound of its device time.

Per step, over the rows it carried (a row = one sequence's span of ``n``
tokens starting at position ``c``):

* Monarch (or dense) linears: 2 x factor parameters x span tokens;
* attention: 4 x heads x head_dim x (keys each query sees), keys = c + i + 1
  for the i-th token of the span, every layer;
* unembedding: 2 x d_model x vocab for each row that samples;
* bytes: the layer weights and the unembedding once, the embedding rows of
  the span tokens, the KV of the context read (c positions) and of the new
  rows written (n positions), every layer.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from bench.model import is_factorized, linears, monarch_blocks

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS_FILE}")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the count needs, from a configuration's ``model``."""

    layer_params: int      # parameterized-matmul parameters of one layer
    layer_bytes: int       # every weight of one layer, served dtype
    n_layers: int
    d_model: int
    vocab: int             # real vocabulary (padding is not work)
    heads: int
    kv_heads: int
    head_dim: int
    dtype_bytes: int
    final_norm_bytes: int

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.n_layers * self.kv_heads * self.head_dim * self.dtype_bytes


def shape_of(cfg) -> Shape:
    per_layer = 0
    for mats in linears(cfg).values():
        for din, dout, _ in mats.values():
            if is_factorized(cfg, din, dout):
                k, q, p, s = monarch_blocks(din, dout)
                per_layer += k * q * p + q * s * k
            else:
                per_layer += din * dout
    nb = 2 if cfg.dtype == "bfloat16" else 4
    norm = cfg.d_model * (1 if cfg.norm_type == "rmsnorm" else 2)
    return Shape(layer_params=per_layer, layer_bytes=nb * (per_layer + 2 * norm),
                 n_layers=cfg.n_layers, d_model=cfg.d_model, vocab=cfg.vocab,
                 heads=cfg.n_heads, kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
                 dtype_bytes=nb, final_norm_bytes=nb * norm)


def step_work(shape: Shape, rows) -> tuple[float, float]:
    """(FLOPs, bytes) of one step; ``rows`` = [(start, n, samples), ...]."""
    tokens = sum(n for _, n, _ in rows)
    sampled = sum(1 for _, _, s in rows if s)
    keys = sum(n * c + n * (n + 1) // 2 for c, n, _ in rows)
    context = sum(c for c, _, _ in rows)
    s = shape
    flops = (2.0 * s.layer_params * s.n_layers * tokens
             + 4.0 * s.heads * s.head_dim * keys * s.n_layers
             + 2.0 * s.d_model * s.vocab * sampled)
    unembed = s.d_model * s.vocab * s.dtype_bytes if sampled else 0
    nbytes = (s.layer_bytes * s.n_layers + s.final_norm_bytes + unembed
              + tokens * s.d_model * s.dtype_bytes
              + (context + tokens) * s.kv_bytes_per_token)
    return flops, float(nbytes)


def steps_from_log(dispatch_log, requests: dict, lo: int, hi: int) -> dict:
    """Rows of every step with ``lo < step_idx <= hi``, rebuilt from the
    engine's dispatch log ``(step_idx, req_id, kind, n)`` and the requests
    (``req_id`` -> Request).  A request's cursor starts at its prefix hit
    and advances by each span; a prefill after a decode is a resumed
    preemption, which restarts the cursor at the hit."""
    cursor, decoded, out = {}, set(), {}
    for step, rid, kind, n in dispatch_log:
        req = requests.get(rid)
        if req is None:
            continue
        if rid not in cursor or (kind == "prefill" and rid in decoded):
            cursor[rid] = req.num_cached_tokens
            decoded.discard(rid)
        c = cursor[rid]
        if kind == "decode":
            decoded.add(rid)
            samples = True
        else:
            samples = c + n >= len(req.prompt)
        cursor[rid] = c + n
        if lo < step <= hi:
            out.setdefault(step, []).append((c, n, samples))
    return out

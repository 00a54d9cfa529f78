"""A step's count (``bench/work.py``) split into the layers the program
names with its scopes, and each layer's share of its roofline.

Per step, over the rows it carried (``rows`` as ``step_work`` takes them):

* ``linear``: 2 x factor parameters x span tokens, every layer; the
  factors' bytes once, every layer;
* ``attention``: 4 x heads x head_dim x (keys each query sees), every
  layer; the KV of the context read and of the new rows written;
* ``unembed``: 2 x d_model x vocab for each row that samples; the
  unembedding once when any row samples.

The parts' FLOPs add up to ``step_work``'s.  Their bytes leave out the
norms' weights and the embedding rows, which no part's scope holds, so they
add up to at most ``step_work``'s.
"""

from __future__ import annotations

PARTS = ("linear", "attention", "unembed")


def step_parts(shape, rows) -> dict:
    """{part: (FLOPs, bytes)} of one step; ``rows`` = [(start, n, samples)]."""
    s = shape
    tokens = sum(n for _, n, _ in rows)
    sampled = sum(1 for _, _, smp in rows if smp)
    keys = sum(n * c + n * (n + 1) // 2 for c, n, _ in rows)
    context = sum(c for c, _, _ in rows)
    return {
        "linear": (2.0 * s.layer_params * s.n_layers * tokens,
                   float(s.dtype_bytes * s.layer_params * s.n_layers)),
        "attention": (4.0 * s.heads * s.head_dim * keys * s.n_layers,
                      float((context + tokens) * s.kv_bytes_per_token)),
        "unembed": (2.0 * s.d_model * s.vocab * sampled,
                    float(s.d_model * s.vocab * s.dtype_bytes
                          if sampled else 0)),
    }


def roofline_share(ctx, part: str):
    """Percent of its roofline that ``part`` reached over the traced
    stretch: the sum over the stretch's steps of max(F / peak FLOP/s,
    B / peak bytes/s) over the device self time of the part's scope.  None
    when the run carries no scope times (a harness that does not collect
    them) or the scope took no time in the stretch."""
    scope_s = getattr(ctx, "scope_s", None) or {}
    steps = getattr(ctx, "stretch_parts", None)
    if not steps or scope_s.get(part, 0.0) <= 0:
        return None
    p = ctx.peaks
    need = sum(max(f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"])
               for f, b in (step[part] for step in steps))
    return 100.0 * need / scope_s[part]

"""Whether the timed path served the right tokens.

Once the window has closed, a sample of the requests the engine finished is
drawn from the seed, always with the longest of them, until it holds
``sample_tokens`` served tokens (or every finished request; fewer than
``min_tokens`` is not correct).  The plain reference (the ``score`` of the
configuration's family; ``bench/reference.py`` for the dense decoder) runs
once over each prompt followed by its served tokens; at the position of
every served token it gives the reference's best logit and the logit of
the token the engine served.  Per served token the gap is

    best reference logit - reference logit of the token the engine served

which is 0 where the engine chose the reference's argmax and grows with
every step the engine's logits stray from the reference's.  Two numbers are
read: the widest gap and the mean gap over the sample.  Greedy decoding
only: every request of every mix is greedy.

Which numbers a cell compares, and their limits, are in
``bench/limits/<cell>.json``, set from the readings of sound runs and of
the control (PERF.md gives both).
"""

from __future__ import annotations

import numpy as np


def draw_sample(finished: list, seed: int, want_tokens: int) -> list:
    """``finished`` = [(prompt, served tokens)]: the longest (prompt plus
    served) first, then others in an order drawn from the seed, until
    ``want_tokens`` served tokens are in."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rng = np.random.default_rng([int(seed) % 2**64, 0xC0FFEE])
    rest = list(order[1:])
    rng.shuffle(rest)
    out, total = [], 0
    for i in [order[0]] + rest:
        out.append(finished[i])
        total += len(finished[i][1])
        if total >= want_tokens:
            break
    return out


def widest_gap(score, model: dict, weights, sample: list,
               length: int) -> dict:
    """Run the reference ``score`` (a family's) over every sampled request
    (padded to ``length`` positions); the widest gap and what it was read
    over."""
    gaps, agree = [], 0
    for prompt, served in sample:
        r = score(model, weights, prompt, served, length)
        gaps.append(r["best"] - r["served"])
        agree += int((r["argmax"] == np.asarray(served)).sum())
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    empty = float("inf")
    return {"max_logit_gap": float(g.max()) if g.size else empty,
            "mean_logit_gap": float(g.mean()) if g.size else empty,
            "requests": len(sample), "tokens": int(g.size),
            "argmax_agreement": agree / g.size if g.size else 0.0}


def judge(reading: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {value, max or min}}): every number the cell's
    limits file compares (``compare``: name -> its ``max``) within its
    limit, over a sample of at least ``min_tokens`` served tokens (a
    sample short of it means the engine finished too little to judge)."""
    check = {name: {"value": reading[name], "max": lim["max"]}
             for name, lim in limits["compare"].items()}
    check["sampled_tokens"] = {"value": reading["tokens"],
                               "min": limits["min_tokens"]}
    ok = (all(c["value"] <= c["max"] for c in check.values() if "max" in c)
          and reading["tokens"] >= limits["min_tokens"])
    return ok, check

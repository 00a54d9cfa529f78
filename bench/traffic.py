"""The one traffic generator: a mix file (``bench/traffic/<mix>.json``) and
a seed in, requests with due times out.

Every size and every gap is drawn by *stratified blocks*: each block of
``block`` draws holds the same quantiles of the stated distribution, in an
order shuffled by the mix's own ``shape_seed``.  The run's seed draws only
the token ids.  So every seed serves the same sizes at the same due times
(the same work, other tokens): runs with different seeds spread no more
than two runs of one seed, and any stretch of a few blocks carries the
stated mix.

Two loops:

* ``backlog``: ``requests`` requests all due at t = 0 (offline batch);
* ``open``: sessions arrive as a Poisson process at ``session_rate_per_s``
  (exponential gaps); each session has a context of ``context_tokens`` and
  sends ``requests_per_session`` requests at exponential ``gap_s`` gaps;
  each request's prompt is the previous prompt plus ``edit_tokens`` new
  tokens, so all but the edit is a prefix another request committed.

A mix may give ``kinds`` in place of its ``prompt_tokens`` (backlog) or
``context_tokens`` (open) and ``output_tokens``: a list of ``{"share",
"prompt_tokens" | "context_tokens", "output_tokens"}``, one per request
kind (short and long in one queue).  Every block of ``block`` requests (or
sessions) then holds round(share x block) of each kind, in an order
shuffled by ``shape_seed``, and each kind draws its sizes from stratified
blocks of its own count.  A session keeps its kind for all its requests.

Token ids are uniform over the model's vocabulary.  Decoding is greedy.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    due_s: float          # seconds after the traffic starts
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int
    session: int


def load_mix(path) -> dict:
    with open(path) as f:
        return json.load(f)


def quantile(dist: dict, u: float) -> float:
    """The ``u``-quantile of a stated distribution, clipped to its range."""
    kind = dist["dist"]
    if kind == "lognormal":
        z = statistics.NormalDist().inv_cdf(u)
        v = dist["median"] * math.exp(dist["sigma"] * z)
    elif kind == "uniform_int":
        v = dist["min"] + math.floor(u * (dist["max"] - dist["min"] + 1))
    elif kind == "exponential":
        v = -dist["mean"] * math.log1p(-u)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(max(v, dist.get("min", -math.inf)), dist.get("max", math.inf))


class Shuffled:
    """An endless stream of draws: blocks of ``values``, each block
    shuffled by ``rng``."""

    def __init__(self, values: list, rng: np.random.Generator):
        self._vals = list(values)
        self._rng = rng
        self._buf: list = []

    def __call__(self):
        if not self._buf:
            self._buf = [self._vals[i]
                         for i in self._rng.permutation(len(self._vals))]
        return self._buf.pop()


class Stratified(Shuffled):
    """Blocks of the ``block`` mid-quantiles (i + 0.5) / block of ``dist``,
    each block shuffled by ``rng``."""

    def __init__(self, dist: dict, block: int, rng: np.random.Generator,
                 integer: bool = True):
        vals = [quantile(dist, (i + 0.5) / block) for i in range(block)]
        super().__init__([int(round(v)) for v in vals] if integer else vals,
                         rng)


class Kinds:
    """The request kinds of a mix: ``size()`` gives the next request's (or
    session's) kind and its first size, ``output(kind)`` an output length
    of that kind.  A mix without ``kinds`` is one kind of its own keys;
    one kind draws no order, so such a mix draws exactly as before kinds
    existed."""

    def __init__(self, mix: dict, first: str, rng: np.random.Generator):
        block = mix["block"]
        kinds = mix.get("kinds")
        if kinds is None:
            kinds = [{"share": 1.0, first: mix[first],
                      "output_tokens": mix["output_tokens"]}]
        elif first in mix or "output_tokens" in mix:
            raise ValueError(f"a mix with kinds gives {first} and "
                             "output_tokens in each kind, not beside them")
        counts = [round(k["share"] * block) for k in kinds]
        for k, n in zip(kinds, counts):
            if set(k) != {"share", first, "output_tokens"}:
                raise ValueError(f"a kind has share, {first} and "
                                 f"output_tokens, not {sorted(k)}")
            if n < 1:
                raise ValueError(f"share {k['share']} of a block of {block} "
                                 "is no request")
        if sum(counts) != block:
            raise ValueError(f"shares {[k['share'] for k in kinds]} give "
                             f"{sum(counts)} of a block of {block}")
        self._kind = (Shuffled([i for i, n in enumerate(counts)
                                for _ in range(n)], rng)
                      if len(kinds) > 1 else lambda: 0)
        self._first = [Stratified(k[first], n, rng)
                       for k, n in zip(kinds, counts)]
        self._output = [Stratified(k["output_tokens"], n, rng)
                        for k, n in zip(kinds, counts)]

    def size(self) -> tuple[int, int]:
        kind = self._kind()
        return kind, self._first[kind]()

    def output(self, kind: int) -> int:
        return self._output[kind]()


def generate(mix: dict, seed: int, vocab: int, horizon_s: float) -> list[Req]:
    """Requests of ``mix`` due before ``horizon_s``, sorted by due time:
    sizes and due times from the mix's ``shape_seed``, token ids from
    ``seed``."""
    rng = np.random.default_rng(int(seed) % 2**64)
    shapes = np.random.default_rng(mix["shape_seed"])
    if mix["loop"] == "backlog":
        kinds = Kinds(mix, "prompt_tokens", shapes)
        reqs = []
        for i in range(mix["requests"]):
            kind, prompt = kinds.size()
            reqs.append(Req(0.0, rng.integers(0, vocab, prompt,
                                              dtype=np.int32),
                            kinds.output(kind), i))
    elif mix["loop"] == "open":
        reqs = _sessions(mix, rng, shapes, vocab, horizon_s)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    for r in reqs:
        if len(r.prompt) + r.max_new_tokens > mix["max_len"]:
            raise ValueError(f"request of {len(r.prompt)} + "
                             f"{r.max_new_tokens} tokens exceeds max_len "
                             f"{mix['max_len']}")
    return sorted(reqs, key=lambda r: (r.due_s, r.session))


def digest(reqs: list[Req]) -> str:
    """A fingerprint of a request list: every due time, size, session and
    token id, in order."""
    h = hashlib.sha256()
    for r in reqs:
        h.update(repr((r.due_s, r.max_new_tokens, r.session,
                       len(r.prompt))).encode())
        h.update(r.prompt.tobytes())
    return h.hexdigest()


def _sessions(mix, rng, shapes, vocab, horizon_s) -> list[Req]:
    block = mix["block"]
    draw = lambda key, integer=True: Stratified(  # noqa: E731
        mix[key], block, shapes, integer)
    arrival = Stratified({"dist": "exponential",
                          "mean": 1.0 / mix["session_rate_per_s"]},
                         block, shapes, integer=False)
    kinds = Kinds(mix, "context_tokens", shapes)
    n_reqs = draw("requests_per_session")
    gap, edit = draw("gap_s", integer=False), draw("edit_tokens")
    reqs, t, sid = [], 0.0, 0
    while t < horizon_s:
        kind, context = kinds.size()
        prompt = rng.integers(0, vocab, context, dtype=np.int32)
        due = t
        for _ in range(n_reqs()):
            prompt = np.concatenate(
                [prompt, rng.integers(0, vocab, edit(), dtype=np.int32)])
            if due < horizon_s:
                reqs.append(Req(due, prompt, kinds.output(kind), sid))
            due += gap()
        sid += 1
        t += arrival()
    return reqs

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

Token ids are uniform over the model's vocabulary.  Decoding is greedy.
"""

from __future__ import annotations

import dataclasses
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


class Stratified:
    """An endless stream of draws: blocks of the ``block`` mid-quantiles
    (i + 0.5) / block, each block shuffled by ``rng``."""

    def __init__(self, dist: dict, block: int, rng: np.random.Generator,
                 integer: bool = True):
        self._vals = [quantile(dist, (i + 0.5) / block) for i in range(block)]
        if integer:
            self._vals = [int(round(v)) for v in self._vals]
        self._rng = rng
        self._buf: list = []

    def __call__(self):
        if not self._buf:
            self._buf = [self._vals[i]
                         for i in self._rng.permutation(len(self._vals))]
        return self._buf.pop()


def generate(mix: dict, seed: int, vocab: int, horizon_s: float) -> list[Req]:
    """Requests of ``mix`` due before ``horizon_s``, sorted by due time:
    sizes and due times from the mix's ``shape_seed``, token ids from
    ``seed``."""
    rng = np.random.default_rng(int(seed) % 2**64)
    shapes = np.random.default_rng(mix["shape_seed"])
    block = mix["block"]
    draw = lambda key, integer=True: Stratified(  # noqa: E731
        mix[key], block, shapes, integer)
    if mix["loop"] == "backlog":
        prompt, output = draw("prompt_tokens"), draw("output_tokens")
        reqs = [Req(0.0, rng.integers(0, vocab, prompt(), dtype=np.int32),
                    output(), i) for i in range(mix["requests"])]
    elif mix["loop"] == "open":
        reqs = _sessions(mix, rng, shapes, vocab, horizon_s, draw)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    for r in reqs:
        if len(r.prompt) + r.max_new_tokens > mix["max_len"]:
            raise ValueError(f"request of {len(r.prompt)} + "
                             f"{r.max_new_tokens} tokens exceeds max_len "
                             f"{mix['max_len']}")
    return sorted(reqs, key=lambda r: (r.due_s, r.session))


def _sessions(mix, rng, shapes, vocab, horizon_s, draw) -> list[Req]:
    arrival = Stratified({"dist": "exponential",
                          "mean": 1.0 / mix["session_rate_per_s"]},
                         mix["block"], shapes, integer=False)
    context, n_reqs = draw("context_tokens"), draw("requests_per_session")
    gap, edit = draw("gap_s", integer=False), draw("edit_tokens")
    output = draw("output_tokens")
    reqs, t, sid = [], 0.0, 0
    while t < horizon_s:
        prompt = rng.integers(0, vocab, context(), dtype=np.int32)
        due = t
        for _ in range(n_reqs()):
            prompt = np.concatenate(
                [prompt, rng.integers(0, vocab, edit(), dtype=np.int32)])
            if due < horizon_s:
                reqs.append(Req(due, prompt, output(), sid))
            due += gap()
        sid += 1
        t += arrival()
    return reqs

"""The traffic generator: deterministic per seed, the same multiset of sizes
and arrivals for every seed, the stated distributions and due times."""

import collections
import json
import statistics
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"
VOCAB = 92416
# mixes of the cells kept for later (PERF.md, open questions), stated here
# so the generator stays tested on a shorter-prompt backlog and on sessions
LATER = {
    "chat_backlog_1k": {
        "name": "chat_backlog_1k", "loop": "backlog", "requests": 400,
        "shape_seed": 20261013, "block": 16,
        "prompt_tokens": {"dist": "lognormal", "median": 512, "sigma": 0.7,
                          "min": 128, "max": 1024},
        "output_tokens": {"dist": "lognormal", "median": 384, "sigma": 0.5,
                          "min": 128, "max": 1024},
        "max_len": 2048, "warmup_steps": 32},
    "repo_completion": {
        "name": "repo_completion", "loop": "open",
        "session_rate_per_s": 0.088, "shape_seed": 20261013, "block": 4,
        "context_tokens": {"dist": "lognormal", "median": 2048, "sigma": 0.35,
                           "min": 1024, "max": 3072},
        "requests_per_session": {"dist": "uniform_int", "min": 4, "max": 8},
        "gap_s": {"dist": "exponential", "mean": 1.5},
        "edit_tokens": {"dist": "uniform_int", "min": 16, "max": 96},
        "output_tokens": {"dist": "uniform_int", "min": 16, "max": 64},
        "max_len": 4096, "warmup_s": 20},
}


def mix(name):
    if name in LATER:
        return LATER[name]
    return traffic.load_mix(MIXES / f"{name}.json")


def key(reqs):
    return [(r.due_s, r.prompt.tobytes(), r.max_new_tokens, r.session)
            for r in reqs]


@pytest.mark.parametrize("name", ["chat_backlog", "chat_backlog_1k",
                                  "repo_completion"])
def test_same_seed_same_requests(name):
    m = mix(name)
    a = traffic.generate(m, 2**31 + 7, VOCAB, 60.0)
    b = traffic.generate(m, 2**31 + 7, VOCAB, 60.0)
    c = traffic.generate(m, 2**31 + 8, VOCAB, 60.0)
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("name", ["chat_backlog", "chat_backlog_1k"])
def test_backlog_sizes_and_due_times(name):
    m = mix(name)
    reqs = traffic.generate(m, 5, VOCAB, 60.0)
    assert len(reqs) == m["requests"]
    assert all(r.due_s == 0.0 for r in reqs)
    p = m["prompt_tokens"]
    o = m["output_tokens"]
    lens = [len(r.prompt) for r in reqs]
    outs = [r.max_new_tokens for r in reqs]
    assert p["min"] <= min(lens) and max(lens) <= p["max"]
    assert o["min"] <= min(outs) and max(outs) <= o["max"]
    # mid-quantile strata: the medians are the distributions' medians
    assert statistics.median(lens) == pytest.approx(p["median"], rel=0.1)
    assert statistics.median(outs) == pytest.approx(o["median"], rel=0.1)
    # every block of `block` requests holds the same multiset of sizes
    b = m["block"]
    first = collections.Counter(lens[:b])
    for i in range(0, len(lens) - b + 1, b):
        assert collections.Counter(lens[i:i + b]) == first
    assert all(int(t) < VOCAB for r in reqs for t in r.prompt)
    assert len({r.prompt.tobytes() for r in reqs}) == len(reqs)


@pytest.mark.parametrize("name", ["chat_backlog", "repo_completion"])
def test_seeds_change_tokens_not_work(name):
    m = mix(name)
    a = traffic.generate(m, 1, VOCAB, 90.0)
    b = traffic.generate(m, 2**40 + 3, VOCAB, 90.0)
    shape = lambda reqs: [(r.due_s, len(r.prompt), r.max_new_tokens,  # noqa: E731
                           r.session) for r in reqs]
    assert shape(a) == shape(b)
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another shape seed is another realization of the same mix
    c = traffic.generate(dict(m, shape_seed=m["shape_seed"] + 1), 1, VOCAB,
                         90.0)
    assert shape(c) != shape(a)


def test_sessions_grow_prompts_and_arrive_in_order():
    m = mix("repo_completion")
    reqs = traffic.generate(m, 2**33 + 1, VOCAB, 120.0)
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues) and dues[0] == 0.0 and max(dues) < 120.0
    by = collections.defaultdict(list)
    for r in reqs:
        by[r.session].append(r)
    c = m["context_tokens"]
    e = m["edit_tokens"]
    for rs in by.values():
        assert len(rs) <= m["requests_per_session"]["max"]
        first = len(rs[0].prompt)
        assert c["min"] + e["min"] <= first <= c["max"] + e["max"]
        for prev, cur in zip(rs, rs[1:]):
            # everything but the new edit is the previous prompt
            assert np.array_equal(cur.prompt[:len(prev.prompt)], prev.prompt)
            assert e["min"] <= len(cur.prompt) - len(prev.prompt) <= e["max"]
            assert cur.due_s > prev.due_s
        for r in rs:
            assert 16 <= r.max_new_tokens <= 64
            assert len(r.prompt) + r.max_new_tokens <= m["max_len"]
    # session starts: a Poisson process at the stated rate, in strata
    starts = sorted(rs[0].due_s for rs in by.values())
    gaps = np.diff(starts)
    assert np.mean(gaps) == pytest.approx(1 / m["session_rate_per_s"],
                                          rel=0.25)


def test_sessions_same_arrivals_for_every_seed():
    m = mix("repo_completion")
    starts = []
    for seed in (3, 4):
        reqs = traffic.generate(m, seed, VOCAB, 400.0)
        first = {}
        for r in reqs:
            first.setdefault(r.session, r.due_s)
        gaps = np.diff(sorted(first.values()))
        b = m["block"]
        starts.append(sorted(np.round(gaps[:b * 4], 9)))
    assert starts[0] == starts[1]


def test_quantiles_of_the_stated_distributions():
    ln = {"dist": "lognormal", "median": 512, "sigma": 0.7, "min": 128,
          "max": 2048}
    assert traffic.quantile(ln, 0.5) == pytest.approx(512)
    assert traffic.quantile(ln, 1e-9) == 128
    assert traffic.quantile(ln, 1 - 1e-9) == 2048
    ex = {"dist": "exponential", "mean": 1.5}
    assert traffic.quantile(ex, 1 - np.exp(-1)) == pytest.approx(1.5)
    ui = {"dist": "uniform_int", "min": 4, "max": 8}
    assert [traffic.quantile(ui, (i + 0.5) / 5) for i in range(5)] == [
        4, 5, 6, 7, 8]


def test_oversized_request_is_refused():
    m = json.loads((MIXES / "chat_backlog.json").read_text())
    m["max_len"] = 1024
    with pytest.raises(ValueError, match="exceeds max_len"):
        traffic.generate(m, 1, VOCAB, 10.0)


# request digests (``traffic.digest``) of the generator as it stood before
# mixes could hold kinds: a mix without kinds draws the same sizes, order
# and token ids
DIGESTS = {
    ("chat_backlog", 1):
        "cc944c9c2d9f6dee1090813bf0b490153965df6f33ba25949359f56dbe5806d4",
    ("chat_backlog", 4000018201):
        "843c3b2a35e6f067cf4334cd831891e1dcc7eb1b128001e851f23e359904b269",
    ("repo_completion", 1):
        "5e829db0616d7cade44786d02eb98b34efcad183a29c3bcf81826ed02bc4bdb8",
    ("repo_completion", 4000018201):
        "c682d1af2db63d4992d8e1015155a3b4666457032ea2b8493e131573190f2809",
}


@pytest.mark.parametrize("name,seed", list(DIGESTS))
def test_mix_without_kinds_draws_as_before(name, seed):
    reqs = traffic.generate(mix(name), seed, VOCAB, 60.0)
    assert traffic.digest(reqs) == DIGESTS[name, seed]


# short and long requests in one queue (the shape of ROADMAP W5)
SHORT = {"dist": "uniform_int", "min": 100, "max": 199}
LONG = {"dist": "lognormal", "median": 4000, "sigma": 0.3, "min": 2000,
        "max": 8000}
OUT = {"dist": "uniform_int", "min": 16, "max": 64}
KINDS_BACKLOG = {
    "name": "short_and_long", "loop": "backlog", "requests": 96,
    "shape_seed": 11, "block": 8,
    "kinds": [{"share": 0.75, "prompt_tokens": SHORT, "output_tokens": OUT},
              {"share": 0.25, "prompt_tokens": LONG,
               "output_tokens": {"dist": "uniform_int", "min": 500,
                                 "max": 1000}}],
    "max_len": 9216}


def _is_long(r) -> bool:
    return len(r.prompt) >= LONG["min"]


def test_kinds_hold_their_shares_in_every_block():
    reqs = traffic.generate(KINDS_BACKLOG, 2**35 + 1, VOCAB, 60.0)
    assert len(reqs) == 96
    b = KINDS_BACKLOG["block"]
    for i in range(0, len(reqs), b):
        block = reqs[i:i + b]
        longs = [r for r in block if _is_long(r)]
        assert len(longs) == 2
        assert all(500 <= r.max_new_tokens <= 1000 for r in longs)
        assert all(SHORT["min"] <= len(r.prompt) <= SHORT["max"]
                   and OUT["min"] <= r.max_new_tokens <= OUT["max"]
                   for r in block if not _is_long(r))
    # each kind draws stratified blocks of its own count: every 6 short
    # prompts hold the same sizes, every 2 long ones too
    shorts = [len(r.prompt) for r in reqs if not _is_long(r)]
    longs = [len(r.prompt) for r in reqs if _is_long(r)]
    for sizes, n in ((shorts, 6), (longs, 2)):
        first = sorted(sizes[:n])
        assert all(sorted(sizes[i:i + n]) == first
                   for i in range(0, len(sizes), n))
    # where the long ones sit in a block is shuffled by the shape seed
    where = {tuple(j for j, r in enumerate(reqs[i:i + b]) if _is_long(r))
             for i in range(0, len(reqs), b)}
    assert len(where) > 1
    # and the seed changes the tokens, not the work
    other = traffic.generate(KINDS_BACKLOG, 3, VOCAB, 60.0)
    assert [(len(r.prompt), r.max_new_tokens) for r in other] == \
        [(len(r.prompt), r.max_new_tokens) for r in reqs]


def test_kinds_of_sessions():
    m = dict(LATER["repo_completion"], block=4)
    ctx = m.pop("context_tokens")
    out = m.pop("output_tokens")
    m["kinds"] = [
        {"share": 0.5, "context_tokens": ctx, "output_tokens": out},
        {"share": 0.5, "context_tokens": {"dist": "uniform_int",
                                          "min": 64, "max": 128},
         "output_tokens": {"dist": "uniform_int", "min": 256, "max": 512}}]
    reqs = traffic.generate(m, 9, VOCAB, 300.0)
    by = collections.defaultdict(list)
    for r in reqs:
        by[r.session].append(r)
    sessions = [by[s] for s in sorted(by)]
    second = [rs[0].max_new_tokens >= 256 for rs in sessions]
    for rs, s in zip(sessions, second):     # a session keeps its kind
        assert all((r.max_new_tokens >= 256) == s for r in rs)
    assert len(second) >= 8
    for i in range(0, len(second) - 3, 4):
        assert sum(second[i:i + 4]) == 2


@pytest.mark.parametrize("kinds,match", [
    ([{"share": 0.5, "prompt_tokens": SHORT, "output_tokens": OUT},
      {"share": 0.3, "prompt_tokens": LONG, "output_tokens": OUT}],
     "give 6 of a block of 8"),
    ([{"share": 0.99, "prompt_tokens": SHORT, "output_tokens": OUT},
      {"share": 0.01, "prompt_tokens": LONG, "output_tokens": OUT}],
     "is no request"),
    ([{"share": 1.0, "prompt_tokens": SHORT, "output_tokens": OUT,
       "gap_s": OUT}], "a kind has"),
])
def test_kinds_that_do_not_fill_a_block_are_refused(kinds, match):
    with pytest.raises(ValueError, match=match):
        traffic.generate(dict(KINDS_BACKLOG, kinds=kinds), 1, VOCAB, 60.0)
    with pytest.raises(ValueError, match="not beside them"):
        traffic.generate(dict(KINDS_BACKLOG, output_tokens=OUT), 1, VOCAB,
                         60.0)

"""The harness, driven on the CPU past its look for a chip, at the tiny
size of ``bench/tests/tiny.py``: a sound run comes out correct, and the
control and every fault a serving cell can have come out not correct.

* control: the program's own int8 path (int8 Monarch weights and KV
  pages), the step below bf16 that would tempt a later change;
* a token altered where it is produced (the sampled id plus one);
* a step that returns its state unchanged (the KV pool as it came in).

Half a batch left out and the exchange between chips left out are faults
of training and of multi-chip cells; no cell here has them.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from bench import run as R
from bench.tests import tiny


def broken_step(kind: str):
    from repro.serving import engine as E

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def step(params, pool, chunk_tok, tok_dev, use_dev, start, span, pt,
             wstart, sample_mask, temp, keys, *, cfg):
        new_pool, sampled, tok_new, keys_new = E._mixed_step_body(
            params, pool, chunk_tok, tok_dev, use_dev, start, span, pt,
            wstart, sample_mask, temp, keys, cfg)
        if kind == "token_altered":
            sampled = (sampled + 1) % cfg.vocab
            tok_new = jnp.where(sample_mask, sampled, tok_dev)
        elif kind == "state_unchanged":
            new_pool = pool
        return new_pool, sampled, tok_new, keys_new

    return step


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    return R.Layout(tiny.write(tmp_path_factory.mktemp("tiny")))


def run_cell(layout, cell: str, extra=()):
    args = R.parse(["--workload", cell, "--seed", str(2**32 + 5),
                    "--seconds", "1.5", "--trace", "0", *extra])
    return R.run(args, layout, chip=False)


@pytest.mark.parametrize("cell", ["tiny.backlog", "tiny.sessions"])
def test_sound_run_is_correct(layout, cell):
    out = run_cell(layout, cell)
    assert out["correct"] is True
    assert out["check"]["mean_logit_gap"]["value"] <= tiny.LIMITS[
        "compare"]["mean_logit_gap"]["max"]
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("cell", ["tiny.backlog", "tiny.sessions"])
@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_fault_is_not_correct(layout, cell, fault, monkeypatch):
    from repro.serving import engine as E

    monkeypatch.setattr(E, "_mixed_step_jit", broken_step(fault))
    out = run_cell(layout, cell)
    assert out["correct"] is False
    assert out["check"]["mean_logit_gap"]["value"] > tiny.LIMITS[
        "compare"]["mean_logit_gap"]["max"]


@pytest.mark.parametrize("cell", ["tiny.backlog", "tiny.sessions"])
def test_control_is_not_correct(layout, cell):
    out = run_cell(layout, cell, ["--control"])
    assert out["correct"] is False
    assert out["check"]["mean_logit_gap"]["value"] > tiny.LIMITS[
        "compare"]["mean_logit_gap"]["max"]

"""Pool sizing compiles the engine's own largest step: the same program as
the step written out by hand, under the engine's settings, and a page
count read off its footprint under the device's memory limit."""

import jax
import jax.numpy as jnp
import pytest

from bench import model, sizing
from bench.tests import tiny

SETTINGS = tiny.CONFIG["deployment"]
MAX_LEN = 64


@pytest.fixture(scope="module")
def tiny_model():
    cfg = model.model_config(tiny.CONFIG)
    return cfg, model.make_weights(cfg, 3)


def hand_lowered_bytes(cfg, weights, n_pages: int) -> int:
    """The oracle: the mixed step's arguments written out by hand, as the
    harness once listed them, lowered through the program's own jit."""
    from repro.models import transformer as T
    from repro.serving.engine import _mixed_step_jit

    slots, page = SETTINGS["max_slots"], SETTINGS["page_size"]
    span, mp = 64, MAX_LEN // page       # the scheduler's chunk, max_len
    pool = jax.eval_shape(lambda: T.init_paged_pool(cfg, n_pages, page))
    i32 = jnp.int32
    rows = [((slots, span), i32), ((slots,), i32), ((slots,), bool),
            ((slots,), i32), ((slots,), i32), ((slots, mp), i32),
            ((slots,), i32), ((slots,), bool), ((slots,), jnp.float32),
            ((slots, 2), jnp.uint32)]
    args = [jax.ShapeDtypeStruct(s, d) for s, d in rows]
    shapes = jax.eval_shape(lambda w: w, weights)
    return sizing.step_bytes(
        _mixed_step_jit.lower(shapes, pool, *args, cfg=cfg).compile())


@pytest.mark.parametrize("n_pages", [5, 17])
def test_engine_step_is_the_hand_lowered_step(tiny_model, n_pages):
    cfg, weights = tiny_model
    got = sizing.step_bytes(sizing.compile_engine_step(
        cfg, weights, SETTINGS, MAX_LEN, n_pages))
    assert got == hand_lowered_bytes(cfg, weights, n_pages)


def test_engine_step_follows_the_engine_settings(tiny_model):
    cfg, weights = tiny_model
    _, base, engine = sizing.engine_step(cfg, weights, SETTINGS, MAX_LEN, 9)
    assert (engine.max_slots, engine.span_bucket) == (4, 64)
    assert base[2].shape == (4, 64)             # the largest row bucket
    # a setting the harness never names reaches the step: the chunk width
    _, args, engine = sizing.engine_step(
        cfg, weights, dict(SETTINGS, chunk_size=32), MAX_LEN, 9)
    assert engine.span_bucket == 32 and args[2].shape == (4, 32)
    # and the pool is whatever pytree the engine holds: int8 pages add
    # their scale rows
    _, args, _ = sizing.engine_step(
        cfg, weights, dict(SETTINGS, kv_dtype="int8"), MAX_LEN, 9)
    leaves = jax.tree_util.tree_leaves(args[1])
    assert len(leaves) > len(jax.tree_util.tree_leaves(base[1]))
    assert {x.dtype for x in leaves} == {jnp.dtype(jnp.int8),
                                         jnp.dtype(jnp.float32)}


class LimitedDevice:
    """The first device, reporting ``limit`` bytes of memory."""

    def __init__(self, device, limit: int):
        self._device, self._limit = device, limit

    def memory_stats(self):
        return {"bytes_limit": self._limit}

    def __getattr__(self, name):
        return getattr(self._device, name)


def test_size_pool_under_a_memory_limit(tiny_model, monkeypatch, tmp_path):
    cfg, weights = tiny_model

    def footprint(n):
        return sizing.step_bytes(sizing.compile_engine_step(
            cfg, weights, SETTINGS, MAX_LEN, n))

    full = 1 + 4 * (MAX_LEN // 16)
    b2, b3 = footprint(2), footprint(3)
    per_page = b3 - b2
    assert per_page > 0
    # a device whose budget holds the step with 6.5 pages more than 2
    limit = int((b2 + 6.5 * per_page) / sizing.MEMORY_SHARE)
    device = LimitedDevice(jax.devices()[0], limit)
    monkeypatch.setattr(sizing.jax, "devices", lambda *a: [device])
    monkeypatch.setattr(sizing, "CACHE_DIR", tmp_path)
    lines = []
    res = sizing.size_pool(cfg, weights, SETTINGS, MAX_LEN, log=lines.append)
    assert 2 < res["n_pages"] < full
    assert res["n_pages"] == 8
    assert res["step_bytes"] == footprint(8)
    assert res["step_bytes"] <= sizing.MEMORY_SHARE * limit
    assert footprint(9) > sizing.MEMORY_SHARE * limit
    assert res["limit_bytes"] == limit and res["span"] == 64
    assert lines and "pool 8 pages of 16" in lines[0]
    # the second run of a checkout reads the result and compiles nothing
    assert len(list(tmp_path.glob("pool-*.json"))) == 1

    def no_compile(*a, **k):
        raise AssertionError("sized again")

    monkeypatch.setattr(sizing, "compile_engine_step", no_compile)
    assert sizing.size_pool(cfg, weights, SETTINGS, MAX_LEN) == res
    # another deployment is another key
    with pytest.raises(AssertionError, match="sized again"):
        sizing.size_pool(cfg, weights, dict(SETTINGS, chunk_size=32),
                         MAX_LEN)


def test_size_pool_without_memory_stats(tiny_model):
    """A backend that reports no memory (the CPU) gets every slot at
    max_len, read from the engine's own settings."""
    cfg, weights = tiny_model
    res = sizing.size_pool(cfg, weights, dict(SETTINGS, max_slots=3),
                           MAX_LEN)
    assert res["n_pages"] == 1 + 3 * (MAX_LEN // 16)

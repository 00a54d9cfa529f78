"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell comes from ``BENCHMARK.json`` and the files it
names: the configuration (``configs[].file``), its model family
(``bench/families/<family>.py``, the file's ``family`` key, ``decoder``
where it has none: weights, reference, work count and scope names), the
mix (``bench/traffic/<traffic>.json``), the correctness limits
(``bench/limits/<cell>.json``) and each per-layer metric's reader
(``bench/metrics/<metric>.py``, or ``<base>.py`` for ``<base>.<group>``).
A cell, configuration, family, mix or metric is added by adding files and
entries.

One run, one process: the configuration's ``deployment`` settings checked
against the engine's constructor (every key is passed to it by name);
weights made on the device from the seed; the pool sized from the
engine's own compiled step (``bench/sizing.py``); the engine built; every
program the cell dispatches warmed; the mix run unmeasured for its
``warmup_s`` or ``warmup_steps``; then ``--seconds`` measured
(``bench/window.py``).  With
``--trace 1`` the engine's tracer is on and the profiler traces the last
seconds of the window, read under the family's scopes
(``bench/scopes.py``); the line then holds the per-layer metrics.  After
the window: peak device memory, then the engine is freed and the family's
plain reference judges a sample of the served tokens
(``bench/correct.py``).

Exits non-zero, printing no result, when JAX finds no TPU, fewer chips than
the cell asks for, a device kind not in ``bench/peaks.json``, or a Pallas
kernel that would run in interpret mode.
"""

from __future__ import annotations

T_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

# seconds of the window's end the profiler traces in a --trace 1 run
PROFILE_S = 3.0
# engine arguments the harness sets itself (from the mix, the pool sizing,
# --trace; the per-layer readers read the engine's metrics), which a
# configuration's deployment may not set
HARNESS_SETS = ("max_len", "n_pages", "pool_bytes", "trace", "metrics")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------


class Layout:
    """The benchmark's files under ``root``, found by the names in
    ``root/BENCHMARK.json``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: dict = {}

    def cell(self, name: str) -> dict:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        cell = cells[name]
        conf = {c["name"]: c for c in self.spec["configs"]}[cell["config"]]
        config = json.loads((self.root / conf["file"]).read_text())
        return dict(
            cell=cell,
            config=config,
            family=self.family(config.get("family", "decoder")),
            mix=json.loads((self.bench / "traffic"
                            / f"{cell['traffic']}.json").read_text()),
            limits=json.loads((self.bench / "limits"
                               / f"{name}.json").read_text()))

    def metrics(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        out = []
        for m in self.spec[kind]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            if kind == "per_layer" and "workloads" not in m:
                moves = {e["name"]: e for e in self.spec["end_to_end"]}
                e = moves[m["moves"]]
                if "workloads" in e and cell not in e["workloads"]:
                    continue
            out.append(m)
        return out

    def reader(self, metric: str):
        d = self.bench / "metrics"
        for stem in (metric, metric.split(".")[0]):
            if (d / f"{stem}.py").exists():
                return self._module("metrics", stem).read
        raise FileNotFoundError(f"no reader for metric {metric!r} in {d}")

    def family(self, name: str):
        """The model family module ``bench/families/<name>.py``."""
        if not (self.bench / "families" / f"{name}.py").exists():
            raise FileNotFoundError(f"no model family {name!r} in "
                                    f"{self.bench / 'families'}")
        return self._module("families", name)

    def _module(self, kind: str, stem: str):
        """``bench/<kind>/<stem>.py`` loaded by path, once per layout."""
        if (kind, stem) not in self._modules:
            path = self.bench / kind / f"{stem}.py"
            mod = re.sub(r"\W", "_", f"bench_{kind}_{stem}")
            spec = importlib.util.spec_from_file_location(mod, path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            self._modules[kind, stem] = module
        return self._modules[kind, stem]


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def require_chip(chips: int) -> dict:
    """The device, or exit non-zero: TPU only, enough chips, a kind in the
    peaks table, kernels compiled (not interpreted)."""
    import jax

    from bench.work import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
    try:
        pk = peaks(devs[0].device_kind)
    except KeyError as e:
        sys.exit(f"bench: {e}")
    from repro.kernels import ops, paged

    if ops._interpret() or paged._interpret():
        sys.exit("bench: Pallas kernels would run in interpret mode")
    return pk


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says); every program cached.
    The key takes in the programs' op metadata: without it an executable
    compiled from a source whose named scopes differ can load in place of
    the program's own, and a traced run then reads the wrong scopes or
    none.  Traced and untraced runs compile the same programs, so they
    share the entries."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


def peak_memory() -> int:
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


# ---------------------------------------------------------------------------
# the engine's settings
# ---------------------------------------------------------------------------


def engine_settings(deployment: dict) -> dict:
    """A configuration's ``deployment``, each key an argument of the
    engine's constructor, passed to it by name.  Exits naming a key the
    constructor does not take, or one the harness sets itself."""
    import inspect

    from repro.serving import ContinuousBatchingEngine

    params = inspect.signature(ContinuousBatchingEngine).parameters
    for key in deployment:
        p = params.get(key)
        if p is None or p.kind is not p.KEYWORD_ONLY:
            sys.exit(f"bench: deployment setting {key!r} is no argument of "
                     "ContinuousBatchingEngine")
        if key in HARNESS_SETS:
            sys.exit(f"bench: deployment setting {key!r} is set by the "
                     "harness, not by a configuration")
    return dict(deployment)


# ---------------------------------------------------------------------------
# warm-up: every program the cell can dispatch
# ---------------------------------------------------------------------------


def span_buckets(chunk_size: int) -> list[int]:
    """Prompt lengths the warm-up serves: powers of two up to the bucket
    of ``chunk_size``, so one chunk of each length goes through admission,
    the mixed step (one span width, a program per row bucket, all
    compiled at its first prefill) and eviction."""
    top = 1 << (chunk_size - 1).bit_length()
    return [1 << i for i in range(top.bit_length())]


def warm(engine, buckets, vocab: int, shares: bool, rng) -> None:
    """Serve tiny requests through the engine's own entry, one of each
    length in ``buckets``, so the step's programs compile (or load) before
    the window; with prefix-sharing traffic also the copy-on-write batches
    of 1, 2 and 4 forks.  Then the scatters into the engine's slot arrays
    for every number of rows a step can touch (``warm_slot_scatters``)."""
    from repro.serving import SamplingParams

    def serve(prompts):
        for p in prompts:
            engine.add_request(p, SamplingParams(max_new_tokens=2))
        while engine.has_work():
            engine.step()

    for b in buckets:
        if b + 2 <= engine.max_len:
            serve([rng.integers(0, vocab, b, dtype=np.int32)])
    if shares:
        page = engine.page_size
        base = rng.integers(0, vocab, page + page // 2, dtype=np.int32)
        serve([base])
        for k in (1, 2, 4):
            serve([np.concatenate([base, rng.integers(0, vocab, 3,
                                                      dtype=np.int32)])
                   for _ in range(k)])
    warm_slot_scatters(engine)


def warm_slot_scatters(engine) -> None:
    """Between steps the engine scatters host rows into its per-slot
    device arrays for the k slots a step admitted or whose page table
    grew; each k is its own small program.  Run that scatter for k =
    1..slots into every device array the engine holds with a row per slot,
    so none compiles inside the window (the window counts compilations).
    The arrays are found on the engine, not named here: a per-slot array a
    later engine adds is warmed too.  The results are dropped."""
    import jax

    slots = engine.max_slots
    for arr in vars(engine).values():
        if not (isinstance(arr, jax.Array) and arr.ndim
                and arr.shape[0] == slots):
            continue
        for k in range(1, slots + 1):
            rows = np.zeros((k, *arr.shape[1:]), arr.dtype)
            jax.block_until_ready(arr.at[np.arange(k)].set(rows))


# ---------------------------------------------------------------------------
# per-layer context of a traced run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TracedRun:
    trace: dict | None
    stretch_steps: int
    stretch_work: list         # family's step_work of each stretch step
    stretch_parts: list        # family's step_parts of each stretch step
    scope_s: dict              # device self time by scope over the stretch
    peaks: dict
    host_step_ms: list
    prefix_hit_tokens: int
    prompt_tokens_admitted: int
    batch_sum: float
    batch_count: int
    max_slots: int


def host_step_ms(events, lo: int, hi: int) -> list[float]:
    """Per engine step ``lo < step <= hi``: the ``step`` span less the
    ``sync`` spans nested in it (events complete children first)."""
    out, sync = [], 0.0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        if ev["name"] == "sync":
            sync += ev["dur"]
        elif ev["name"] == "step":
            if lo < ev.get("args", {}).get("step", -1) <= hi:
                out.append((ev["dur"] - sync) / 1e3)
            sync = 0.0
    return out


def traced_context(engine, w, family, cfg, pk, trace_summary, max_slots):
    """What the per-layer readers read of a traced run: the window's
    counters and spans, and the traced stretch's device numbers beside its
    steps' work counted by the model ``family``."""
    from bench.work import steps_from_log

    shape = family.shape_of(cfg)
    a, b = w.at_start, w.at_end
    reqs = {rec.req.req_id: rec.req for rec in w.records if rec.req}
    p0, p1 = w.profile["begin"], w.profile["end"]
    rows = steps_from_log(engine.dispatch_log, reqs, p0.step_idx, p1.step_idx)
    admitted = sum(len(rec.req.prompt) for rec in w.records
                   if rec.req is not None
                   and w.start <= rec.req.t_admitted < w.end)
    return TracedRun(
        trace=trace_summary,
        stretch_steps=p1.mixed_steps - p0.mixed_steps,
        stretch_work=[family.step_work(shape, r) for r in rows.values()],
        stretch_parts=[family.step_parts(shape, r) for r in rows.values()],
        scope_s=(trace_summary or {}).get("scope_s", {}),
        peaks=pk,
        host_step_ms=host_step_ms(engine.tracer.events, a.step_idx,
                                  b.step_idx),
        prefix_hit_tokens=b.prefix_hit_tokens - a.prefix_hit_tokens,
        prompt_tokens_admitted=admitted,
        batch_sum=b.batch_sum - a.batch_sum,
        batch_count=b.batch_count - a.batch_count,
        max_slots=max_slots)


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------


def end_to_end(name: str, w, setup_s: float):
    from bench import window as W

    m = re.fullmatch(r"(ttft|itl)_p(\d+)_ms", name)
    if m:
        samples = (W.ttft_samples(w) if m[1] == "ttft" else W.itl_samples(w))
        v = W.percentile(samples, float(m[2]))
        return None if v is None else 1e3 * v
    if name == "output_tok_s":
        return W.tokens_in_window(w) / (w.end - w.start)
    if name == "setup_s":
        return setup_s
    raise KeyError(f"no end-to-end metric {name!r}")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run(args, layout: Layout, chip: bool = True) -> dict:
    import jax

    files = layout.cell(args.workload)
    cell, conf, mix, limits, family = (files["cell"], files["config"],
                                       files["mix"], files["limits"],
                                       files["family"])
    settings = engine_settings(conf["deployment"])
    if chip:
        pk = require_chip(cell["chips"])
    else:
        pk = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    cache = use_compile_cache() if chip else None
    dev = jax.devices()[0]
    log(f"[run] {args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={dev.device_kind} x{len(jax.devices())} "
        f"compile_cache={cache}")
    if args.rate is not None:
        mix = dict(mix, session_rate_per_s=args.rate)

    from bench import correct, model, sizing, traffic
    from bench import window as W
    from repro.serving import ContinuousBatchingEngine

    cfg = model.model_config(conf)
    t = time.perf_counter()
    weights = model.make_weights(cfg, args.seed, family.weight_tree)
    jax.block_until_ready(weights)
    log(f"[weights] {model.weight_bytes(weights) / 1e9:.3f} GB in {cfg.dtype} "
        f"on device in {time.perf_counter() - t:.1f} s")
    size = sizing.size_pool(cfg, weights, settings, mix["max_len"], log=log)
    # the control (not a benchmark run): the program's own lower-precision
    # path, int8 Monarch weights and int8 KV pages, judged like the program
    control = (dict(quantize="int8", kv_dtype="int8") if args.control
               else {})
    engine = ContinuousBatchingEngine(
        cfg, weights, max_len=mix["max_len"], n_pages=size["n_pages"],
        trace=bool(args.trace), **{**settings, **control})
    compiles = W.CompileCounter()
    rng = np.random.default_rng([int(args.seed) % 2**64, 1])
    t = time.perf_counter()
    buckets = span_buckets(engine.scheduler.cfg.chunk_size)
    warm(engine, buckets, cfg.vocab, mix["loop"] == "open", rng)
    log(f"[warm] prompts of {buckets} tokens in "
        f"{time.perf_counter() - t:.1f} s, {compiles.n} backend compiles")
    warmup = {k: mix[k] for k in ("warmup_s", "warmup_steps") if k in mix}
    requests = traffic.generate(mix, args.seed, cfg.vocab,
                                mix.get("warmup_s", 0) + args.seconds)
    log(f"[traffic] {len(requests)} requests, digest "
        f"{traffic.digest(requests)}")

    profiler, logdir = None, None
    if args.trace:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        from bench.trace import profiler_options

        profiler = (lambda: jax.profiler.start_trace(
            logdir, profiler_options=profiler_options()),
            jax.profiler.stop_trace)
    w = W.drive(engine, requests, time.perf_counter(), warmup,
                args.seconds, compiles, profiler=profiler,
                profile_s=min(PROFILE_S, args.seconds / 2))
    compiles.close()
    setup_s = w.start - T_START
    mem = peak_memory()
    late = sorted(w.lateness_s)
    log(f"[setup] {setup_s:.2f} s from process start to the window")
    log(f"[queue] {json.dumps(W.queue_trend(w))}")
    a, b = w.at_start, w.at_end
    log(f"[engine] window steps {a.step_idx}-{b.step_idx}, preemptions "
        f"{b.preemptions - a.preemptions}, prefill tokens "
        f"{b.prefill_tokens - a.prefill_tokens}")
    log(f"[window] {args.seconds} s: steps {w.at_end.step_idx - w.at_start.step_idx}, "
        f"requests added {len(w.records)}, compiles in window {w.compiles}, "
        f"generator lateness p50 {W.percentile(late, 50) or 0:.4f} s "
        f"max {late[-1] if late else 0:.4f} s; pool {size['n_pages']} pages; "
        f"peak device memory {mem / 1e9:.3f} GB")

    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    breakdown = None
    if args.trace:
        from bench import scopes as SC

        summary = SC.reduce(SC.load(logdir, family.SCOPES))
        shutil.rmtree(logdir, ignore_errors=True)
        ctx = traced_context(engine, w, family, cfg, pk, summary,
                             engine.max_slots)
        for m in layout.metrics(args.workload, "per_layer"):
            v = layout.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            device.update(busy_s=summary["busy_s"],
                          window_s=summary["window_s"])
            breakdown = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
    else:
        for m in layout.metrics(args.workload, "end_to_end"):
            v = end_to_end(m["name"], w, setup_s)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted, failed = W.counts(w)

    from repro.serving.request import FinishReason

    finished = [(r.prompt, r.output_tokens) for r in
                (rec.req for rec in w.records if rec.req is not None)
                if r.finish_reason is FinishReason.LENGTH]
    del engine
    gc.collect()
    t = time.perf_counter()
    sample = correct.draw_sample(finished, args.seed, limits["sample_tokens"])
    reading = correct.widest_gap(family.score, conf["model"], weights,
                                 sample, mix["max_len"])
    ok, check = correct.judge(reading, limits)
    log(f"[check] reference over {reading['requests']} requests, "
        f"{reading['tokens']} served tokens in {time.perf_counter() - t:.1f} s; "
        f"reference argmax agreement {reading['argmax_agreement']:.4f}; "
        f"max gap {reading['max_logit_gap']} mean gap "
        f"{reading['mean_logit_gap']}")
    for name, c in check.items():
        bound = (f"max {c['max']}" if "max" in c else f"min {c['min']}")
        log(f"check {name} {c['value']} {bound}")
    out = {"correct": bool(ok), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override an open mix's session rate (knee sweep)")
    ap.add_argument("--control", action="store_true",
                    help="serve through the program's int8 path (the "
                         "correctness control; never a benchmark run)")
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse(argv)
    out = run(args, Layout(ROOT))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

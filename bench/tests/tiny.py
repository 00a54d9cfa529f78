"""A benchmark layout at a size the CPU test runs hold: one tiny float32
configuration of the CodeQwen family, a backlog and a session mix."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

MODEL = {
    "d_model": 128, "n_layers": 2, "n_heads": 4, "n_kv_heads": 4,
    "head_dim": 32, "d_ff": 256, "vocab": 512, "ffn_type": "swiglu",
    "norm_type": "rmsnorm", "rope_theta": 1000000.0, "tie_embeddings": False,
    "pad_vocab_to_multiple": 256, "dtype": "float32",
    "param_dtype": "float32",
    "monarch": {"enable": True, "policy": "paper", "min_dim": 64},
}
CONFIG = {"name": "tiny", "source": "test", "arch": "codeqwen1_5-7b",
          "model": MODEL, "deployment": {"max_slots": 4, "page_size": 16},
          "reduced": ["d_model"], "assumed": {}}
BACKLOG = {"name": "tiny_backlog", "loop": "backlog", "requests": 48,
           "shape_seed": 1, "block": 4,
           "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                             "min": 8, "max": 48},
           "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.3,
                             "min": 6, "max": 16},
           "max_len": 64, "warmup_steps": 8}
SESSIONS = {"name": "tiny_sessions", "loop": "open",
            "session_rate_per_s": 4.0, "shape_seed": 2, "block": 4,
            "context_tokens": {"dist": "lognormal", "median": 40,
                               "sigma": 0.3, "min": 24, "max": 64},
            "requests_per_session": {"dist": "uniform_int", "min": 2,
                                     "max": 3},
            "gap_s": {"dist": "exponential", "mean": 0.3},
            "edit_tokens": {"dist": "uniform_int", "min": 4, "max": 12},
            "output_tokens": {"dist": "uniform_int", "min": 4, "max": 8},
            "max_len": 128, "warmup_s": 0.5}
# float32 at this size: sound runs read a mean gap of 0.0 on 8 seeds of
# each mix (greedy tokens are the reference's argmax, the widest gap is 0.0
# too); on 4 seeds each the int8 control read 0.0037-0.0067 (backlog) and
# 0.0043-0.0103 (sessions), a token altered 5.7-6.4, the pool left
# unchanged 2.5-2.8.  The cells compare the mean gap, and so does this.
LIMITS = {"compare": {"mean_logit_gap": {"max": 0.001}}, "sample_tokens": 96,
          "min_tokens": 48}


def write(root: Path, extra_metrics: dict | None = None) -> Path:
    """A checkout-like tree under ``root``: BENCHMARK.json, the bench
    package's metric readers and model families, and the tiny files, found
    by name."""
    b = root / "bench"
    for sub in ("metrics", "families"):
        shutil.copytree(BENCH / sub, b / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub, name, data in (("configs", "tiny", CONFIG),
                            ("traffic", "tiny_backlog", BACKLOG),
                            ("traffic", "tiny_sessions", SESSIONS),
                            ("limits", "tiny.backlog", LIMITS),
                            ("limits", "tiny.sessions", LIMITS)):
        (b / sub).mkdir(parents=True, exist_ok=True)
        (b / sub / f"{name}.json").write_text(json.dumps(data))
    for name, src in (extra_metrics or {}).items():
        (b / "metrics" / f"{name}.py").write_text(src)
    cells = [("tiny.backlog", "tiny_backlog", "backlog"),
             ("tiny.sessions", "tiny_sessions", "completion")]
    spec = {
        "command": ["python", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": c, "config": "tiny", "traffic": t, "chips": 1,
                       "why": "test"} for c, t, _ in cells],
        "end_to_end": [
            {"name": "ttft_p90_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock",
             "workloads": ["tiny.sessions"]},
            {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "output_tok_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.25, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": f"{m}.{g}", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "test",
             "moves": "output_tok_s", "workloads": [c]}
            for m in ("host_ms_per_step", "batch_occupancy",
                      "prefix_hit_share", *(extra_metrics or {}))
            for c, _, g in cells],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root

import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=512"
)
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: AOT lower+compile every (arch x shape x mesh) cell.

The lines above run before ANY other import (jax locks the device count
at first init): the dry-run — and only the dry-run — sees 512 placeholder
host devices so ``jax.make_mesh`` can build the production meshes
(16x16 single-pod, 2x16x16 multi-pod).  It is pinned to the CPU platform,
and so are the per-cell children it starts: a placeholder-device tool must
never take an accelerator that another process is using.

Per cell this produces:
  * ``compiled.memory_analysis()``  (fits-on-chip proof)
  * ``compiled.cost_analysis()``    (HLO FLOPs / bytes)
  * collective bytes parsed from the optimized HLO text
  * scan-body corrections: cost_analysis counts while bodies once, so the
    cell total = full_step + (trips - 1) x body_step from separate body
    compiles (empirically verified methodology, DESIGN.md Sec. 7)
  * the three roofline terms + bottleneck (EXPERIMENTS.md Sec. Roofline)

Usage:
  python -m repro.launch.dryrun --arch granite-moe-1b-a400m --shape train_4k
  python -m repro.launch.dryrun --all [--resume] [--multi-pod-only]
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _cell_name(arch, shape, multi_pod, variant):
    mesh = "pod2x16x16" if multi_pod else "pod16x16"
    return f"{arch}__{shape}__{mesh}__{variant}"


# ---------------------------------------------------------------------------
# Body (scan-trip) decomposition for cost correction
# ---------------------------------------------------------------------------


def _body_defs(cfg, shape_name, mesh, step_kind):
    """[(name, trips, lower_fn)] — standalone compiles of each scan body."""
    import numpy as np
    from repro.configs.shapes import SHAPES
    from repro.models import layers as L
    from repro.models import transformer as T
    from repro.models.mamba2 import mamba_cache_init
    from repro.sharding import axis_rules, guarded_sharding
    from repro.sharding.params import param_shardings

    cell = SHAPES[shape_name]
    B = cell.global_batch
    S = 1 if step_kind == "decode" else cell.seq_len
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x_sds = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
    with axis_rules(mesh):
        x_sh = guarded_sharding(x_sds.shape, ["batch", None, None], mesh)

    defs = []

    def add(name, trips, init_fn, apply_fn, cache_fn=None):
        p_sds = jax.eval_shape(lambda: init_fn(jax.random.PRNGKey(0)))
        p_sh = param_shardings(p_sds, mesh)

        if step_kind == "train":
            def run(params, x):
                def f(pp, xx):
                    y = apply_fn(pp, xx, None)
                    return jnp.sum(y.astype(jnp.float32))
                g = jax.grad(f, argnums=(0, 1))(params, x)
                return g
            args = (p_sds, x_sds)
            shardings = (p_sh, x_sh)
        elif step_kind == "prefill":
            def run(params, x):
                return apply_fn(params, x, None)
            args = (p_sds, x_sds)
            shardings = (p_sh, x_sh)
        else:  # decode
            cache_sds = jax.eval_shape(cache_fn) if cache_fn else None
            from repro.launch.steps import cache_shardings
            c_sh = (cache_shardings(cfg, mesh, cache_sds)
                    if cache_sds is not None else None)

            def run(params, x, cache):
                return apply_fn(params, x, cache)
            args = (p_sds, x_sds, cache_sds)
            shardings = (p_sh, x_sh, c_sh)

        def lower_fn():
            with axis_rules(mesh):
                with jax.set_mesh(mesh):
                    return jax.jit(run, in_shardings=shardings).lower(*args)

        defs.append((name, trips, lower_fn))

    pos_dummy = jnp.zeros((B,), jnp.int32)

    if cfg.layer_kind in ("attn",):
        def init_fn(k):
            return T.attn_block_init(k, cfg, cross=cfg.encdec)

        def apply_fn(p, x, cache):
            if cache is None:
                y, _, _ = T.attn_block_apply(p, x, cfg, window=None)
            else:
                y, _, _ = T.attn_block_apply(
                    p, x, cfg, window=None, cache=cache, pos=pos_dummy)
            return y

        def cache_fn():
            return {"attn": L.attention_cache_init(cfg, B, cell.seq_len, dt)}

        add("attn_layer", cfg.n_layers, init_fn, apply_fn, cache_fn)
        if cfg.encdec and step_kind != "decode":
            import dataclasses as dc
            enc_cfg = dc.replace(cfg, n_layers=cfg.n_enc_layers, moe=None,
                                 layer_kind="attn")

            def e_init(k):
                return T.attn_block_init(k, enc_cfg)

            def e_apply(p, x, cache):
                y, _, _ = T.attn_block_apply(p, x, enc_cfg, window=None,
                                             bidir=True)
                return y
            add("enc_layer", cfg.n_enc_layers, e_init, e_apply)
    elif cfg.layer_kind == "mamba":
        def init_fn(k):
            return T._mamba_layer_init(k, cfg)

        def apply_fn(p, x, cache):
            y, _ = T._mamba_layer(p, x, cfg, cache)
            return y

        add("mamba_layer", cfg.n_layers, init_fn, apply_fn,
            lambda: mamba_cache_init(cfg, B, dt))
    else:  # hybrid: n_layers mamba bodies + n_groups shared-attn bodies
        g = cfg.shared_attn_every
        n_groups = cfg.n_layers // g

        def m_init(k):
            return T._mamba_layer_init(k, cfg)

        def m_apply(p, x, cache):
            y, _ = T._mamba_layer(p, x, cfg, cache)
            return y

        add("mamba_layer", cfg.n_layers, m_init, m_apply,
            lambda: mamba_cache_init(cfg, B, dt))

        def a_init(k):
            return T.attn_block_init(k, cfg)

        def a_apply(p, x, cache):
            if cache is None:
                y, _, _ = T.attn_block_apply(p, x, cfg, window=None)
            else:
                y, _, _ = T.attn_block_apply(p, x, cfg, window=None,
                                             cache=cache, pos=pos_dummy)
            return y

        def a_cache():
            return {"attn": L.attention_cache_init(cfg, B, cell.seq_len, dt)}

        add("shared_attn", n_groups, a_init, a_apply, a_cache)

    return defs


# ---------------------------------------------------------------------------
# One cell
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool, variant: str,
             overrides: dict | None = None, scheme: str = "psum") -> dict:
    from repro.configs import get_config
    from repro.configs.shapes import (SHAPES, cell_supported,
                                      decode_cache_specs, enc_out_specs,
                                      input_specs)
    from repro.launch import hlo_analysis as H
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import (cache_shardings, make_decode_step,
                                    make_prefill_step, make_train_step,
                                    train_shardings)
    from repro.models import transformer as T
    from repro.sharding import axis_rules, guarded_sharding
    from repro.sharding.params import param_shardings

    t0 = time.time()
    cfg = get_config(f"{arch}:{variant}" if variant != "paper" else arch)
    accum_steps = 1
    if overrides:
        import dataclasses as _dc
        overrides = dict(overrides)
        accum_steps = overrides.pop("accum_steps", 1)
        if overrides:
            cfg = _dc.replace(cfg, **overrides)
    if scheme != "auto":
        from repro.sharding.api import set_monarch_scheme
        set_monarch_scheme(scheme)
    ok, reason = cell_supported(cfg, shape_name)
    rec = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np_prod(mesh.devices.shape))
    cell = SHAPES[shape_name]
    step_kind = cell.step

    with axis_rules(mesh), jax.set_mesh(mesh):
        if step_kind == "train":
            _, train_step = make_train_step(cfg, accum_steps=accum_steps)
            state_sh, batch_sh, state_sds, batch_sds = train_shardings(
                cfg, mesh, shape_name)
            lowered = jax.jit(
                train_step,
                in_shardings=(state_sh, batch_sh),
                donate_argnums=(0,),
            ).lower(state_sds, batch_sds)
        elif step_kind == "prefill":
            prefill_step = make_prefill_step(cfg)
            p_sds = jax.eval_shape(
                lambda: T.init_params(jax.random.PRNGKey(0), cfg))
            p_sh = param_shardings(p_sds, mesh)
            batch_sds = input_specs(cfg, shape_name)
            batch_sh = {
                k: guarded_sharding(
                    v.shape, ["batch"] + [None] * (len(v.shape) - 1), mesh)
                for k, v in batch_sds.items()
            }
            lowered = jax.jit(
                prefill_step, in_shardings=(p_sh, batch_sh)
            ).lower(p_sds, batch_sds)
        else:  # decode
            decode_step = make_decode_step(cfg)
            p_sds = jax.eval_shape(
                lambda: T.init_params(jax.random.PRNGKey(0), cfg))
            p_sh = param_shardings(p_sds, mesh)
            tok_sds = input_specs(cfg, shape_name)["tokens"]
            tok_sh = guarded_sharding(tok_sds.shape, ["batch"], mesh)
            cache_sds = decode_cache_specs(cfg, shape_name)
            shard_kv_seq = shape_name == "long_500k"
            c_sh = cache_shardings(cfg, mesh, cache_sds,
                                   shard_kv_seq=shard_kv_seq)
            args = [p_sds, tok_sds, cache_sds]
            shardings = [p_sh, tok_sh, c_sh]
            if cfg.encdec:
                eo = enc_out_specs(cfg, shape_name)
                eo_sh = guarded_sharding(eo.shape, ["batch", None, None], mesh)
                args.append(eo)
                shardings.append(eo_sh)
            lowered = jax.jit(
                decode_step,
                in_shardings=tuple(shardings),
                donate_argnums=(2,),
            ).lower(*args)

        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

        # --- memory & cost ---
        mem = {}
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes",
                          "generated_code_size_in_bytes"):
                    v = getattr(ma, k, None)
                    if v is not None:
                        mem[k] = int(v)
                print("memory_analysis:", mem)
        except Exception as e:  # CPU backend may not implement it
            mem = {"error": str(e)}
        flops_full, bytes_full = H.cost_terms(compiled)
        print(f"cost_analysis: flops={flops_full:.3e} bytes={bytes_full:.3e}")
        coll_full = H.collective_bytes(compiled.as_text())

        # --- scan-body corrections ---
        bodies = []
        flops_tot, bytes_tot = flops_full, bytes_full
        coll_tot = dict(coll_full)
        for name, trips, lower_fn in _body_defs(cfg, shape_name, mesh,
                                                step_kind):
            try:
                bl = lower_fn()
                bc = bl.compile()
                bf, bb = H.cost_terms(bc)
                bcoll = H.collective_bytes(bc.as_text())
                bodies.append({"name": name, "trips": trips, "flops": bf,
                               "bytes": bb, "coll": bcoll})
                flops_tot += (trips - 1) * bf
                bytes_tot += (trips - 1) * bb
                for k, v in bcoll.items():
                    coll_tot[k] = coll_tot.get(k, 0) + (trips - 1) * v
            except Exception as e:
                bodies.append({"name": name, "trips": trips,
                               "error": f"{type(e).__name__}: {e}"})

    # --- roofline ---
    n_emb = cfg.vocab * cfg.d_model if not cfg.tie_embeddings else 0
    n_eff = cfg.active_param_count() - n_emb
    if step_kind == "train":
        tokens = cell.global_batch * cell.seq_len
        model_flops = 6 * n_eff * tokens
    elif step_kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        model_flops = 2 * n_eff * tokens
    else:
        model_flops = 2 * n_eff * cell.global_batch
    terms = H.RooflineTerms(
        hlo_flops=flops_tot,
        hlo_bytes=bytes_tot,
        coll_bytes=float(sum(coll_tot.values())),
        n_chips=n_chips,
        model_flops=float(model_flops),
    )
    rec.update(
        status="ok",
        step=step_kind,
        n_chips=n_chips,
        time_lower_s=round(t_lower, 2),
        time_compile_s=round(t_compile, 2),
        memory=mem,
        flops_full=flops_full,
        bytes_full=bytes_full,
        coll_full=coll_full,
        bodies=bodies,
        roofline=terms.as_dict(),
    )
    return rec


def np_prod(shape):
    n = 1
    for s in shape:
        n *= s
    return n


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def _run_all(resume: bool, variant: str, multi_pod_only: bool,
             single_pod_only: bool, archs=None, shapes=None):
    from repro.configs import ALL_ARCHS
    from repro.configs.shapes import SHAPES

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    cells = []
    meshes = []
    if not multi_pod_only:
        meshes.append(False)
    if not single_pod_only:
        meshes.append(True)
    for arch in (archs or ALL_ARCHS):
        for shape in (shapes or SHAPES):
            for mp in meshes:
                cells.append((arch, shape, mp))
    done = failed = 0
    for arch, shape, mp in cells:
        out = RESULTS_DIR / f"{_cell_name(arch, shape, mp, variant)}.json"
        if resume and out.exists():
            done += 1
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--variant", variant,
               "--out", str(out)]
        if mp:
            cmd.append("--multi-pod")
        print(f"[dryrun] {out.stem} ...", flush=True)
        t0 = time.time()
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=3600,
                           env={**os.environ, "PYTHONPATH": "src",
                                "JAX_PLATFORMS": "cpu"})
        dt = time.time() - t0
        if r.returncode != 0 and not out.exists():
            failed += 1
            out.write_text(json.dumps({
                "arch": arch, "shape": shape, "variant": variant,
                "mesh": "2x16x16" if mp else "16x16",
                "status": "error",
                "error": r.stderr[-3000:],
            }, indent=1))
            print(f"  FAILED in {dt:.0f}s: {r.stderr.splitlines()[-1] if r.stderr else '?'}",
                  flush=True)
        else:
            done += 1
            print(f"  ok in {dt:.0f}s", flush=True)
    print(f"[dryrun] complete: {done} ok, {failed} failed")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--variant", default="paper",
                    choices=["paper", "dense", "mxu"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override field=value (perf loop), e.g. "
                         "--set attn_chunk=1024 --set remat=dots")
    ap.add_argument("--scheme", default="auto",
                    choices=["auto", "psum", "a2a"],
                    help="monarch TP scheme (DESIGN.md Sec. 5)")
    args = ap.parse_args()

    overrides = {}
    import ast
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    if args.all:
        _run_all(args.resume, args.variant, args.multi_pod_only,
                 args.single_pod_only)
        return

    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod, args.variant,
                       overrides=overrides, scheme=args.scheme)
        rec["overrides"] = overrides
        rec["scheme"] = args.scheme
    except Exception:
        rec = {
            "arch": args.arch, "shape": args.shape, "variant": args.variant,
            "mesh": "2x16x16" if args.multi_pod else "16x16",
            "status": "error", "error": traceback.format_exc()[-4000:],
        }
    out = Path(args.out) if args.out else (
        RESULTS_DIR / f"{_cell_name(args.arch, args.shape, args.multi_pod, args.variant)}.json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1, default=float))
    print(json.dumps({k: rec[k] for k in ("arch", "shape", "mesh", "status")},
                     indent=None))
    if rec["status"] == "error":
        print(rec["error"][-2000:], file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape x
mesh) combination against 512 placeholder host devices — proving the
distribution config is coherent without hardware.

Per cell, TWO lowerings happen:
  1. deployable — scanned layers + chunked attention.  Proves compilation,
     yields memory_analysis() (fits-in-HBM evidence) and the collective
     schedule.
  2. cost-faithful — COST_MODE unrolled variants with 1 and 2 layer-groups;
     FLOPs/bytes/collective-bytes are linearly extrapolated to the full
     depth (exact for homogeneous stacks; XLA cost_analysis counts scan
     bodies once, see models/costmode.py).

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from ..compat import NamedSharding, P, use_mesh
from ..configs.registry import ARCHS, get_config
from ..configs.shapes import SHAPES, applicable
from ..models import encdec, transformer
from ..models.config import ModelConfig
from ..models.costmode import cost_mode
from ..models.steps import (batch_specs_sharding, input_specs,
                            make_decode_step, make_prefill, make_train_step)
from ..train.optimizer import AdamWConfig
from .mesh import make_production_mesh
from .roofline import analyze_compiled, roofline_terms

# Gradient-accumulation microbatches per train step, sized so per-device
# residual activations (n_layers x B_loc/accum x S x d_model bf16, kept by
# per-group remat) fit the 16 GB v5e HBM next to params + optimizer state.
ACCUM_STEPS = {
    "mistral-large-123b": 16,
    "internvl2-76b": 16,
    "qwen2.5-14b": 8,
    "stablelm-3b": 4,
    "recurrentgemma-2b": 4,
    # MoE: the shard_map dispatch (§Perf) removed the dispatch blow-up, so
    # accumulation drops 4->2 — fewer FSDP weight re-gathers per step while
    # the dots_nb live set stays under HBM (olmoe 13.6 GiB measured).
    "olmoe-1b-7b": 2,
    "granite-moe-1b-a400m": 2,
    "qwen1.5-0.5b": 2,
    "mamba2-130m": 1,
    "whisper-tiny": 1,
}

# Per-arch remat policy for the layer-group scan (§Perf): 'dots_nb' saves
# projection outputs but recomputes the batched S^2 attention einsums —
# less recompute traffic than 'full' without the HBM blow-up of 'dots'
# (dots saved the S^2 score matrices: olmoe 51 GiB/device, an OOM).
REMAT_POLICY = {
    "internvl2-76b": "dots_nb",   # bound 60.3->54.9 s; fits (13.0 GiB)
    "olmoe-1b-7b": "dots_nb",
    "granite-moe-1b-a400m": "dots_nb",
    "mamba2-130m": "dots_nb",
    "qwen1.5-0.5b": "dots_nb",
    "whisper-tiny": "dots_nb",
}

# Two-level (sqrt-N) remat for the deep stacks whose flat boundary stash
# (n_groups x |x| per device) exceeds HBM even at accum=16 (§Perf):
# mistral 88 groups x 100 MB = 8.8 GiB, internvl2 80 x ~70 MB.
REMAT_CHUNKS = {
    "mistral-large-123b": 8,     # 8 outer x 11 inner
    "internvl2-76b": 8,          # 8 outer x 10 inner
}


def _model_mod(cfg):
    return encdec if cfg.family == "audio" else transformer


def param_structs_and_specs(cfg: ModelConfig, mesh_axes):
    """Abstract param tree + PartitionSpecs without allocating anything."""
    mod = _model_mod(cfg)
    captured = {}

    def f():
        p, s = mod.init_model(jax.random.PRNGKey(0), cfg, mesh_axes)
        captured["specs"] = s
        return p

    sds = jax.eval_shape(f)
    return sds, captured["specs"]


def _shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# §Perf A/B toggle: set False to lower serving cells with the training
# (FSDP x TP) weight layout instead of serving_weight_rules.
SERVING_RULES_ENABLED = True

# Cross-pod gradient compression ("int8" | None) for multi-pod train cells
# — see train/compression.py.  Default off (the baseline reduction is the
# reference; flip for the §Perf A/B).
GRAD_COMPRESSION = None


def serving_weight_rules(cfg: ModelConfig, mesh, batch: int = 0) -> dict:
    """Inference param-sharding policy (§Perf: 'serving sharding != training
    sharding').  Training uses FSDP ('embed' axis over 'data'), which makes
    every decode step all-gather layer weights — pure overhead when weights
    are read-only.  If the TP-only footprint fits comfortably in HBM *and*
    the request batch actually shards over the data axis, replicate the
    'embed' axis (weights stationary, sharded over 'model' only).

    Measured counter-case (mamba2-130m long_500k, B=1): with the batch
    unsharded every device repeats the same compute, so FSDP's weight
    *split* + gather (9.7 MB/step) beats stationary replicated reads
    (bound 196us vs 267us/step) — keep the 2D layout there.
    """
    tp = mesh.shape.get("model", 1)
    dp = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            dp *= mesh.shape[ax]
    param_bytes = cfg.param_count * 2          # bf16
    if param_bytes / tp <= 6e9 and batch % dp == 0:
        return {"embed": None}
    return {}


def _lower(cfg: ModelConfig, mode: str, B: int, S: int, mesh,
           donate: bool = True, accum_steps: int = 1):
    """Lower + compile one program.  Returns (lowered, compiled)."""
    from ..models.common import rules_override
    mesh_axes = mesh.axis_names
    dp_total = 1
    for ax in ("pod", "data"):
        if ax in mesh_axes:
            dp_total *= mesh.shape[ax]
    rules = {} if B % dp_total == 0 else {"batch": None}
    if mode in ("prefill", "decode") and SERVING_RULES_ENABLED:
        rules.update(serving_weight_rules(cfg, mesh, batch=B))
    with rules_override(**rules):
        return _lower_inner(cfg, mode, B, S, mesh, donate, accum_steps)


def _lower_inner(cfg, mode, B, S, mesh, donate, accum_steps):
    from ..models.common import logical_to_spec as l2s
    mesh_axes = mesh.axis_names
    params_sds, pspecs = param_structs_and_specs(cfg, mesh_axes)
    p_shard = _shardings(mesh, pspecs)
    dp = tuple(a for a in ("pod", "data") if a in mesh_axes)

    with use_mesh(mesh):
        if mode == "train":
            f32sds = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
            state_sds = {"params": params_sds,
                         "opt": {"m": jax.tree.map(f32sds, params_sds),
                                 "v": jax.tree.map(f32sds, params_sds),
                                 "step": jax.ShapeDtypeStruct((),
                                                              jnp.int32)}}
            state_shard = {"params": p_shard,
                           "opt": {"m": p_shard, "v": p_shard,
                                   "step": NamedSharding(mesh, P())}}
            bspecs = batch_specs_sharding(cfg, mesh_axes)
            batch_sds = input_specs(cfg, B, S, "train")
            b_shard = {k: NamedSharding(mesh, bspecs[k]) for k in batch_sds}
            rep = NamedSharding(mesh, P())
            jitted = jax.jit(
                make_train_step(cfg, AdamWConfig(),
                                accum_steps=accum_steps,
                                grad_compression=GRAD_COMPRESSION),
                in_shardings=(state_shard, b_shard),
                out_shardings=(state_shard,
                               {"loss": rep, "grad_norm": rep, "lr": rep}),
                donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(state_sds, batch_sds)
        elif mode == "prefill":
            bspecs = batch_specs_sharding(cfg, mesh_axes)
            batch_sds = input_specs(cfg, B, S, "prefill")
            b_shard = {k: NamedSharding(mesh, bspecs[k]) for k in batch_sds}
            cspecs = (encdec.cache_specs(cfg, mesh_axes)
                      if cfg.family == "audio"
                      else transformer.cache_specs(cfg, mesh_axes))
            out_shard = (NamedSharding(
                mesh, l2s(("batch", None, "act_vocab"),
                          mesh_axes=mesh_axes)),
                         _shardings(mesh, cspecs))
            jitted = jax.jit(make_prefill(cfg),
                             in_shardings=(p_shard, b_shard),
                             out_shardings=out_shard)
            lowered = jitted.lower(params_sds, batch_sds)
        elif mode == "decode":
            if cfg.family == "audio":
                cache_sds = encdec.cache_shape(cfg, B, S)
                cspecs = encdec.cache_specs(cfg, mesh_axes)
            else:
                cache_sds = jax.eval_shape(
                    lambda: transformer.init_cache(cfg, B, S))
                cspecs = transformer.cache_specs(cfg, mesh_axes)
            c_shard = _shardings(mesh, cspecs)
            out_shard = (NamedSharding(
                mesh, l2s(("batch", None, "act_vocab"),
                          mesh_axes=mesh_axes)), c_shard)
            jitted = jax.jit(
                make_decode_step(cfg),
                in_shardings=(p_shard, c_shard,
                              NamedSharding(
                                  mesh, l2s(("batch", "seq"),
                                            mesh_axes=mesh_axes)),
                              NamedSharding(mesh, P())),
                out_shardings=out_shard,
                donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(
                params_sds, cache_sds,
                jax.ShapeDtypeStruct((B, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
        else:
            raise ValueError(mode)
        compiled = lowered.compile()
    return lowered, compiled


def _cost_cfg(cfg: ModelConfig, k: int) -> ModelConfig:
    """Config with k layer-groups (remainder preserved)."""
    u = len(cfg.unit)
    rem = cfg.n_layers % u
    kw = {"n_layers": k * u + rem}
    if cfg.family == "audio":
        kw["enc_layers"] = k          # enc/dec trip counts move together
    return dataclasses.replace(cfg, **kw)


def _extrapolate(c1: dict, c2: dict, g_full: int) -> dict:
    """cost(G) = a + b*G; b = c2 - c1; return cost(g_full)."""
    out = {}
    for key in ("hlo_flops", "hlo_bytes", "hlo_bytes_structural",
                "hlo_bytes_attn_s2"):
        if key not in c1:
            continue
        b = c2[key] - c1[key]
        out[key] = c1[key] + (g_full - 1) * b
    for ckey in ("collectives", "collectives_raw_f32promoted"):
        if ckey not in c1:
            continue
        coll = {}
        for kind in c1[ckey]:
            b = c2[ckey][kind] - c1[ckey][kind]
            coll[kind] = int(c1[ckey][kind] + (g_full - 1) * b)
        out[ckey] = coll
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               cfg: ModelConfig | None = None, extra_tag: str = "",
               skip_cost: bool = False):
    """Lower + compile one cell (deployable + cost passes)."""
    if cfg is None:
        cfg = get_config(arch)
        if arch in REMAT_POLICY and cfg.remat == "full":
            cfg = dataclasses.replace(cfg, remat=REMAT_POLICY[arch])
        if arch in REMAT_CHUNKS and cfg.remat_chunks == 0:
            cfg = dataclasses.replace(cfg, remat_chunks=REMAT_CHUNKS[arch])
    shape = SHAPES[shape_name]
    if not applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name,
                "skipped": "long_500k needs sub-quadratic decode "
                           "(see DESIGN.md §Arch-applicability)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    B, S = shape.global_batch, shape.seq_len
    accum = ACCUM_STEPS.get(arch, 1) if shape.mode == "train" else 1

    t0 = time.time()
    lowered, compiled = _lower(cfg, shape.mode, B, S, mesh,
                               accum_steps=accum)
    t_deploy = time.time() - t0
    rec = analyze_compiled(lowered, compiled, seq_len=S)
    rec["counted_once"] = {"hlo_flops": rec.pop("hlo_flops"),
                           "hlo_bytes": rec.pop("hlo_bytes"),
                           "collectives": rec.pop("collectives")}

    if not skip_cost:
        t0 = time.time()
        with cost_mode():
            _, comp1 = _lower(_cost_cfg(cfg, 1), shape.mode, B, S, mesh,
                              accum_steps=accum)
            c1 = analyze_compiled(None, comp1, seq_len=S)
            _, comp2 = _lower(_cost_cfg(cfg, 2), shape.mode, B, S, mesh,
                              accum_steps=accum)
            c2 = analyze_compiled(None, comp2, seq_len=S)
        g_full = (cfg.n_layers if cfg.family == "audio" else cfg.n_groups)
        ext = _extrapolate(c1, c2, g_full)
        rec.update(ext)
        rec.update(roofline_terms(ext["hlo_flops"], ext["hlo_bytes"],
                                  ext["collectives"]))
        if "hlo_bytes_structural" in ext:
            from .mesh import peaks
            hbm_bw = peaks()["hbm_bw"]
            rec["memory_s_structural"] = (ext["hlo_bytes_structural"]
                                          / hbm_bw)
            rec["memory_s_structural_flash"] = (
                (ext["hlo_bytes_structural"]
                 - ext.get("hlo_bytes_attn_s2", 0.0)) / hbm_bw)
        rec["cost_pass_s"] = round(time.time() - t0, 2)

    rec["accum_steps"] = accum
    rec.update(arch=arch, shape=shape_name, mode=shape.mode,
               mesh="2x16x16" if multi_pod else "16x16",
               seq_len=S, global_batch=B,
               deploy_compile_s=round(t_deploy, 2),
               model_params=cfg.param_count,
               model_params_active=cfg.active_param_count)
    if extra_tag:
        rec["tag"] = extra_tag
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-cost", action="store_true",
                    help="deployable compile only (no roofline extrapolation)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = ([(a, s) for a in ARCHS for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        tag = f"{arch}__{shape}__{'2x16x16' if args.multi_pod else '16x16'}"
        path = outdir / f"{tag}.json"
        if path.exists():
            print(f"[skip cached] {tag}")
            continue
        print(f"[dryrun] {tag} ...", flush=True)
        try:
            rec = lower_cell(arch, shape, multi_pod=args.multi_pod,
                             skip_cost=args.skip_cost)
        # what lower_cell can actually raise: bad arch/shape config keys
        # (KeyError/ValueError), spec/rank mismatches in the model code
        # (TypeError/ValueError), partial-manual shard_map gaps on old JAX
        # (NotImplementedError), and XLA lowering/compile failures
        # (XlaRuntimeError subclasses RuntimeError on all supported
        # versions).  Anything else — MemoryError, KeyboardInterrupt,
        # genuine bugs — should crash the sweep, not be recorded as a
        # per-cell failure (REPRO002).
        except (KeyError, ValueError, TypeError, NotImplementedError,
                RuntimeError):
            failures += 1
            rec = {"arch": arch, "shape": shape,
                   "error": traceback.format_exc()}
            print(rec["error"])
        path.write_text(json.dumps(rec, indent=2, default=str))
        if "error" not in rec and "skipped" not in rec:
            if "compute_s" in rec:
                print(f"  compute={rec['compute_s']:.4f}s "
                      f"memory={rec['memory_s']:.4f}s "
                      f"collective={rec['collective_s']:.4f}s "
                      f"dominant={rec['dominant']}")
            print(f"  memory_analysis: {rec['memory']} "
                  f"(deploy compile {rec['deploy_compile_s']}s)")
        elif "skipped" in rec:
            print(f"  skipped: {rec['skipped']}")
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()

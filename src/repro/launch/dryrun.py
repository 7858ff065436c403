"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds the production mesh, resolves shardings
from the logical-axis rules, lowers the appropriate step function with
ShapeDtypeStruct inputs (no allocation), compiles it, and records
memory_analysis / cost_analysis / the collective schedule for the roofline
table (EXPERIMENTS.md §Dry-run, §Roofline).

Usage:
  python -m repro.launch.dryrun --arch granite-20b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config.base import SHAPES, ArchConfig, all_arch_ids, cell_is_runnable, get_config
from repro.launch.mesh import dp_axes, dp_size, make_production_mesh, model_size
from repro.models.layers.moe import SpmdCtx
from repro.models.model_api import build
from repro.models.perf_flags import PerfFlags, use_flags
from repro.models.param import (
    default_rules,
    tree_abstract,
    tree_pspecs,
    tree_shardings,
)
from repro.optim.optimizers import OptimizerConfig
from repro.optim.specs import opt_state_specs
from repro.roofline import hw
from repro.roofline.analysis import analyze, model_flops_estimate
from repro.roofline.jaxpr_cost import trace_cost
from repro.train.step import make_decode_step, make_prefill_step, make_train_step


def param_dtype(cfg: ArchConfig, kind: str = "train"):
    # adamw archs keep fp32 master params for training; adafactor archs
    # store bf16. Serving always uses the inference dtype.
    if kind != "train":
        return jnp.dtype(cfg.dtype)
    return jnp.float32 if cfg.optimizer == "adamw" else jnp.bfloat16


def make_rules(cfg: ArchConfig, multi_pod: bool,
               fsdp_only: bool = False) -> Dict:
    rules = default_rules(multi_pod)
    rules["batch"] = ("pod", "data") if multi_pod else ("data",)
    rules["kv_seq"] = "model"
    if getattr(make_rules, "_h10", False):
        rules["expert_embed"] = None
    if fsdp_only:
        # H6: ZeRO-3-style sharding — every weight fully sharded on its
        # d_model dim over (data × model); no tensor parallelism, so no
        # per-layer activation psums. Vocab/experts keep the model axis
        # (the logits head and EP still want it).
        fs = ("pod", "data", "model") if multi_pod else ("data", "model")
        rules["embed"] = fs
        for ax in ("heads", "kv_heads", "mlp", "ssm_heads"):
            rules[ax] = None
    return rules


def replicated_like(tree: Any, mesh) -> Tuple[Any, Any]:
    """(abstract tree, replicated shardings) for small concrete-state trees."""
    ab = jax.eval_shape(lambda: tree)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), ab)
    return ab, sh


def spmd_ctx(cfg: ArchConfig, mesh, multi_pod: bool,
             tokens_per_call: int, batch: int) -> SpmdCtx:
    groups = dp_size(mesh)
    if tokens_per_call % groups != 0:
        groups = 1   # e.g. long_500k decode: batch=1 token per step
    n_ep = model_size(mesh)
    if cfg.moe is not None and cfg.moe.num_experts % n_ep != 0:
        n_ep = 1
    axes = ("pod", "data") if multi_pod else ("data",)
    if batch % dp_size(mesh) != 0:
        axes = ()
    return SpmdCtx(num_groups=groups, num_ep_shards=n_ep,
                   batch_axes=axes, model_axis="model")


def build_cell(arch_id: str, shape_name: str, multi_pod: bool,
               fsdp_only: bool = False):
    """Returns (fn, args_abstract, in_shardings, out_shardings, meta)."""
    cfg = get_config(arch_id)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(cfg, multi_pod, fsdp_only=fsdp_only)
    model = build(cfg)
    tokens_per_call = (
        shape.global_batch * shape.seq_len
        if shape.kind in ("train", "prefill") else shape.global_batch
    )
    ctx = spmd_ctx(cfg, mesh, multi_pod, tokens_per_call,
                   shape.global_batch)
    dp = rules["batch"]
    pdt = param_dtype(cfg, shape.kind)

    pspecs = model.specs()
    params_ab = tree_abstract(pspecs, dtype_override=pdt)
    params_sh = tree_shardings(pspecs, mesh, rules)

    B, S = shape.global_batch, shape.seq_len
    # Batch sharding falls back to replication when B is not divisible by
    # the dp extent (long_500k has global_batch=1).
    dp_total = dp_size(mesh)
    batch_spec = dp if B % dp_total == 0 else None
    tok_sh = NamedSharding(mesh, P(batch_spec, None))

    def extra_inputs() -> Tuple[Dict, Dict]:
        ab, sh = {}, {}
        if cfg.family == "encdec":
            ab["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_len, cfg.d_model), jnp.dtype(cfg.dtype)
            )
            sh["frames"] = NamedSharding(mesh, P(batch_spec, None, None))
        if cfg.family == "vlm":
            ab["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.num_patches, cfg.d_model), jnp.dtype(cfg.dtype)
            )
            sh["patches"] = NamedSharding(mesh, P(batch_spec, None, None))
        return ab, sh

    if shape.kind == "train":
        opt_cfg = OptimizerConfig(name=cfg.optimizer)
        fn = make_train_step(
            model, opt_cfg, ctx=ctx,
            param_pspecs=tree_pspecs(pspecs, mesh, rules),
        )
        opt_specs = opt_state_specs(opt_cfg, pspecs)
        state_ab: Dict[str, Any] = {
            "params": params_ab,
            "opt": tree_abstract(opt_specs),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        state_sh: Dict[str, Any] = {
            "params": params_sh,
            "opt": tree_shardings(opt_specs, mesh, rules),
            "step": NamedSharding(mesh, P()),
        }
        dk = model.dyskew_init(ctx)
        if dk is not None:
            dk_ab, dk_sh = replicated_like(dk, mesh)
            state_ab["dyskew"] = dk_ab
            state_sh["dyskew"] = dk_sh
        xab, xsh = extra_inputs()
        batch_ab = dict(
            tokens=jax.ShapeDtypeStruct((B, S), jnp.int32),
            targets=jax.ShapeDtypeStruct((B, S), jnp.int32),
            **xab,
        )
        batch_sh = dict(tokens=tok_sh, targets=tok_sh, **xsh)
        args_ab = (state_ab, batch_ab)
        in_sh = (state_sh, batch_sh)
        out_sh = (state_sh, None)
        tokens = B * S
        mf = model_flops_estimate(cfg.active_param_count(), tokens, "train")

    elif shape.kind == "prefill":
        fn = make_prefill_step(build(cfg), ctx=ctx)
        dstate_specs = build(cfg).decode_state_specs(B, S)
        state_ab = tree_abstract(dstate_specs)
        state_sh = tree_shardings(dstate_specs, mesh, rules)
        xab, xsh = extra_inputs()
        inputs_ab = dict(tokens=jax.ShapeDtypeStruct((B, S), jnp.int32), **xab)
        inputs_sh = dict(tokens=tok_sh, **xsh)
        args_ab = (params_ab, state_ab, inputs_ab)
        in_sh = (params_sh, state_sh, inputs_sh)
        out_sh = (None, state_sh)
        mf = model_flops_estimate(cfg.active_param_count(), B * S, "prefill")

    else:  # decode
        fn = make_decode_step(build(cfg), ctx=ctx)
        dstate_specs = build(cfg).decode_state_specs(B, S)
        state_ab = tree_abstract(dstate_specs)
        state_sh = tree_shardings(dstate_specs, mesh, rules)
        token_ab = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        args_ab = (params_ab, state_ab, token_ab)
        in_sh = (params_sh, state_sh, tok_sh)
        out_sh = (None, state_sh)
        mf = model_flops_estimate(cfg.active_param_count(), B, "decode")

    meta = dict(
        arch=arch_id, shape=shape_name,
        mesh="multi" if multi_pod else "single",
        chips=hw.CHIPS_MULTI_POD if multi_pod else hw.CHIPS_SINGLE_POD,
        kind=shape.kind, model_flops=mf,
        params=model.num_params(),
        active_params=cfg.active_param_count(),
    )
    return fn, args_ab, in_sh, out_sh, mesh, meta


FLAG_MAP = {
    "h1": "causal_skip",
    "h2": "cast_before_gather",
    "h3": "constrain_kv",
    "h5": "constrain_activations",
    "h8": "constrain_grads",
    "h9": "moe_scatter_combine",
    "h11": "constrain_mamba_acts",
    # h7 = disable XLA excess precision (bf16 collectives stay bf16) —
    # handled via compiler options, not PerfFlags.
}


def parse_flags(spec_str: str):
    kw = {}
    h7 = False
    h6 = False
    for tok in spec_str.split(","):
        tok = tok.strip().lower()
        if not tok:
            continue
        if tok == "h7":
            h7 = True
            continue
        if tok == "h6":
            h6 = True
            continue
        if tok == "h10":
            make_rules._h10 = True
            continue
        kw[FLAG_MAP.get(tok, tok)] = True
    return PerfFlags(**kw), h7, h6


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             out_dir: Optional[str] = None, verbose: bool = True,
             flags: PerfFlags = PerfFlags(), tag: str = "",
             no_excess_precision: bool = False,
             fsdp_only: bool = False) -> Dict:
    cfg = get_config(arch_id)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    rec: Dict[str, Any] = dict(arch=arch_id, shape=shape_name, mesh=mesh_name)
    if not ok:
        rec["status"] = why
        if verbose:
            print(f"[dryrun] {arch_id} × {shape_name} × {mesh_name}: {why}")
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(
                out_dir, f"{arch_id}__{shape_name}__{mesh_name}.json"
            ), "w") as f:
                json.dump(rec, f, indent=1)
        return rec

    t0 = time.time()
    try:
        if (flags.constrain_kv or flags.causal_skip) and flags.kv_pspec is None:
            dp = ("pod", "data") if multi_pod else ("data",)
            bspec = dp if SHAPES[shape_name].global_batch % (
                32 if multi_pod else 16) == 0 else None
            flags = dataclasses.replace(
                flags, kv_pspec=P(bspec, "model", None, None)
            )
        if flags.constrain_mamba_acts and flags.act_pspec is None:
            dp = ("pod", "data") if multi_pod else ("data",)
            bspec = dp if SHAPES[shape_name].global_batch % (
                32 if multi_pod else 16) == 0 else None
            flags = dataclasses.replace(
                flags, act_pspec=P(bspec, None, None)
            )
        with use_flags(flags):
            fn, args_ab, in_sh, out_sh, mesh, meta = build_cell(
                arch_id, shape_name, multi_pod, fsdp_only=fsdp_only
            )
            donate = (0,) if meta["kind"] == "train" else (1,)
            with jax.set_mesh(mesh):
                lowered = jax.jit(
                    fn, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=donate,
                ).lower(*args_ab)
                t_lower = time.time() - t0
                copts = (
                    {"xla_allow_excess_precision": False}
                    if no_excess_precision else None
                )
                compiled = lowered.compile(compiler_options=copts)
                t_compile = time.time() - t0 - t_lower
                jc = trace_cost(fn, *args_ab)

            mem = compiled.memory_analysis()
        terms = analyze(compiled, meta["chips"], meta["model_flops"],
                        jaxpr_cost=jc)
        rec.update(meta)
        rec.update(
            status="OK",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=dict(
                argument_bytes=getattr(mem, "argument_size_in_bytes", 0),
                output_bytes=getattr(mem, "output_size_in_bytes", 0),
                # Liveness-aware working-set peak (temp_size is the SUM of
                # all temp allocations, not a peak — misleading).
                peak_bytes=getattr(mem, "peak_memory_in_bytes", 0),
                temp_bytes_sum=getattr(mem, "temp_size_in_bytes", 0),
                generated_code_bytes=getattr(
                    mem, "generated_code_size_in_bytes", 0
                ),
            ),
            roofline=terms.as_dict(),
        )
        # XLA's buffer-assignment peak includes live arguments; donated
        # outputs alias their inputs — peak IS the per-device residency.
        per_dev = rec["memory"]["peak_bytes"]
        rec["memory"]["per_device_total_gb"] = round(per_dev / 1024**3, 3)
        rec["memory"]["fits_hbm"] = bool(per_dev <= hw.HBM_BYTES)
        if verbose:
            r = rec["roofline"]
            print(
                f"[dryrun] {arch_id} × {shape_name} × {mesh_name}: OK "
                f"compile={rec['compile_s']}s "
                f"mem/dev={rec['memory']['per_device_total_gb']}GB "
                f"tc={r['t_compute_s']:.4f} tm={r['t_memory_s']:.4f} "
                f"tcoll={r['t_collective_s']:.4f} → {r['bottleneck']}"
            )
    except Exception as e:  # record failures — they are bugs to fix
        rec["status"] = f"FAIL: {type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()
        if verbose:
            print(f"[dryrun] {arch_id} × {shape_name} × {mesh_name}: "
                  f"{rec['status']}")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        path = os.path.join(
            out_dir, f"{arch_id}__{shape_name}__{mesh_name}{suffix}.json"
        )
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


#: Host devices the production meshes are built from.
_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=512"


def main() -> None:
    # Before the first JAX call: the CPU backend reads XLA_FLAGS once, when
    # it starts.  Appended, so flags the caller set are kept.
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS", ""), _HOST_DEVICES_FLAG) if f
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="")
    ap.add_argument("--shape", type=str, default="")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="experiments/dryrun")
    ap.add_argument("--flags", type=str, default="",
                    help="comma list: h1,h2,h3,h5 (perf hillclimb knobs)")
    ap.add_argument("--tag", type=str, default="")
    args = ap.parse_args()
    flags, h7, h6 = parse_flags(args.flags)

    archs = all_arch_ids() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = (
        [False, True] if args.mesh == "both"
        else [args.mesh == "multi"]
    )
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, out_dir=args.out,
                               flags=flags, tag=args.tag,
                               no_excess_precision=h7, fsdp_only=h6)
                if str(rec.get("status", "")).startswith("FAIL"):
                    failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

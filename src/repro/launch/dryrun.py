"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers, SPMD-
partitions, compiles, and fits — then extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek_67b \
        --shape train_4k --mesh single --optimizer slim

Emits a JSON record (memory analysis, loop-corrected HLO stats, roofline
terms) to benchmarks/results/dryrun/. The 512 placeholder host devices exist
only in this process — tests and benchmarks see the real single CPU device.
The tool is CPU-only: it pins JAX to the CPU platform before JAX starts, so
on a machine with a TPU it never takes the chip (and the sweep's one child
per cell never contends for it).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# Also holds when JAX was imported before this module set the environment.
jax.config.update("jax_platforms", "cpu")

from ..configs import (
    ARCH_IDS,
    SHAPES,
    cell_supported,
    decode_input_specs,
    get_config,
    input_specs,
)
from ..core import rules_as_tree, table3_rules
from ..core.slim_adam import slim_adam
from ..models import transformer
from ..models.attention import KVCache
from ..models.ssm import SSMCache
from ..optim.adam import adamw
from ..sharding.logical import ShardingContext, param_specs, use_sharding
from ..train.step import make_serve_step, make_train_step
from ..sharding.state_shardings import opt_state_specs
from . import hlo_analysis
from .mesh import HBM_PER_CHIP, HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16, make_production_mesh

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


# ---------------------------------------------------------------------------
# Sharding assignment
# ---------------------------------------------------------------------------


def batch_specs(ctx: ShardingContext, batch_abstract: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for k, v in batch_abstract.items():
        names = ["batch"] + [None] * (v.ndim - 1)
        out[k] = ctx.spec_for(names, v.shape)
    return out


def decode_cache_specs(ctx: ShardingContext, cache_abstract) -> Any:
    """KV caches: batch over DP axes, sequence over 'model' (SP); SSM states:
    d_inner over 'model'."""

    def kv(c: KVCache) -> KVCache:
        scale_spec = (ctx.spec_for(("layers", "batch", "seq_kv", None), c.k_scale.shape)
                      if c.k_scale.ndim == 4 else P())
        return KVCache(
            k=ctx.spec_for(("layers", "batch", "seq_kv", None, None), c.k.shape),
            v=ctx.spec_for(("layers", "batch", "seq_kv", None, None), c.v.shape),
            k_scale=scale_spec, v_scale=scale_spec,
            index=P(),
        )

    def ssm(c: SSMCache) -> SSMCache:
        return SSMCache(
            conv=ctx.spec_for(("layers", "batch", None, "d_inner"), c.conv.shape),
            h=ctx.spec_for(("layers", "batch", "d_inner", None), c.h.shape),
        )

    slots = {}
    for key, c in cache_abstract.slots.items():
        if isinstance(c, KVCache) or (hasattr(c, "index") and hasattr(c, "k")):
            slots[key] = kv(c)
        else:
            slots[key] = ssm(c)
    return transformer.DecodeCache(slots=slots, step=P())


def pick_grad_accum(cfg, shape_name: str, mesh) -> int:
    """Choose microbatch count so per-microbatch memory fits HBM.

    Two dominant terms (measured on the compiled HLO, see EXPERIMENTS.md):
      * scan carries saved for backward: n_layers * B_local * S * d_model * 2 B
      * fp32 CE/logits buffers: ~3 live copies of B_local * S * vocab_local * 4 B
    Budget ~9 GiB for these (params/moments/grads/workspace take the rest)."""
    seq, gb, kind = SHAPES[shape_name]
    if kind != "train":
        return 1
    n_dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    n_tp = mesh.shape.get("model", 1)
    # calibrated against measured CPU-backend temp arenas (deepseek-67b:
    # estimate 2.9 GiB @ accum=4 -> measured 11.1 GiB incl. fp32 transients
    # and optimizer temps) — a 4 GiB estimate keeps total under 16 GiB HBM
    budget = 3 * 2**30
    extra = 2.0 if any(s.mixer == "mamba" for s in cfg.pattern) else 1.0
    # sequence parallelism shards the residual carries (and the seq dim of
    # the CE logits) over the TP axis when S divides it
    sp = n_tp if seq % n_tp == 0 else 1
    # mamba layers keep full-S fp32 residuals (the scan is sequential in S, so
    # SP cannot shard them); all mamba slots of one period are live together
    # during the period's backward (measured: jamba 7-slot period ~6x falcon's
    # 1-slot period at equal width)
    mamba_slots = sum(1 for s_ in cfg.pattern if s_.mixer == "mamba")
    d_inner_local = (cfg.ssm_expand * cfg.d_model) // n_tp if (cfg.ssm_expand * cfg.d_model) % n_tp == 0 \
        else cfg.ssm_expand * cfg.d_model
    for accum in (1, 2, 4, 8, 16, 32, 64, 128, 256):
        b_local = max(gb // accum // n_dp, 1)
        carries = cfg.n_layers * b_local * (seq // sp) * cfg.d_model * 2 * extra
        ce = 3 * b_local * (seq // sp) * cfg.vocab_size * 4
        ssm_live = mamba_slots * b_local * seq * d_inner_local * 64
        if carries + ce + ssm_live <= budget and gb % accum == 0 and (gb // accum) >= n_dp:
            return accum
    return 256


# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------


def build_cell(arch: str, shape: str, mesh, *, optimizer: str = "slim", grad_accum: Optional[int] = None,
               variant: str = "default", backend: str = "jnp"):
    """Returns (jitted, abstract_args, ctx, info, cfg). ``backend`` selects the
    Adam/SlimAdam execution path; 'fused' lowers the optimizer step as
    shard_map'd Pallas kernels on the production mesh (mesh + param specs
    are threaded into the transformation), so the dry-run proves the
    shard-aware kernels partition/compile alongside the model."""
    seq, gb, kind = SHAPES[shape]
    if variant == "optimized":
        import importlib
        mod = importlib.import_module(f"repro.configs.{arch}")
        if not hasattr(mod, "optimized"):
            raise ValueError(f"{arch} has no optimized() variant")
        cfg = dataclasses.replace(mod.optimized(), param_dtype=jnp.bfloat16)
    else:
        cfg = get_config(arch, param_dtype=jnp.bfloat16)
    if cfg.pos == "learned" and cfg.max_position < seq + 1:
        # the paper's GPT uses a 1024-position table; the assigned shape cells
        # need longer tables (noted as a deviation only for the extra archs)
        cfg = dataclasses.replace(cfg, max_position=seq + 1)
    ctx = ShardingContext(mesh, rules=dict(cfg.sharding_overrides) or None)
    info: Dict[str, Any] = {"arch": arch, "shape": shape, "kind": kind,
                            "seq": seq, "global_batch": gb,
                            "sharding_overrides": dict(cfg.sharding_overrides)}

    with use_sharding(ctx):
        params_abs, meta = cfg.abstract()
        p_specs = param_specs(meta, params_abs)
        p_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                                   is_leaf=lambda x: isinstance(x, P))

        if kind in ("train", "prefill"):
            batch_abs = input_specs(cfg, shape)
            b_specs = batch_specs(ctx, batch_abs)
            b_shardings = {k: NamedSharding(mesh, s) for k, s in b_specs.items()}
            if kind == "train":
                if optimizer == "slim":
                    rules = table3_rules(meta)
                    dims_tree = rules_as_tree(rules, params_abs, meta)
                    tx = slim_adam(3e-4, dims_tree, backend=backend,
                                   mesh=mesh, param_specs=p_specs)
                    info["optimizer"] = "slim_adam(table3)"
                else:
                    tx = adamw(3e-4, backend=backend, mesh=mesh, param_specs=p_specs)
                    info["optimizer"] = "adamw"
                info["opt_backend"] = backend
                accum = grad_accum or pick_grad_accum(cfg, shape, mesh)
                info["grad_accum"] = accum
                opt_abs = jax.eval_shape(tx.init, params_abs)
                from ..optim.base import resolve_backend
                o_specs = opt_state_specs(
                    opt_abs, params_abs, p_specs,
                    owner_mesh=mesh if resolve_backend(backend) == "fused" else None)
                o_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs,
                                           is_leaf=lambda x: isinstance(x, P))
                step = make_train_step(cfg, tx, grad_accum=accum, grad_shardings=p_shardings)
                jitted = jax.jit(
                    step,
                    in_shardings=(p_shardings, o_shardings, b_shardings),
                    out_shardings=(p_shardings, o_shardings, None),
                    donate_argnums=(0, 1),
                )
                args = (params_abs, opt_abs, batch_abs)
            else:  # prefill: forward only (inference)
                def prefill(params, batch):
                    logits, _ = transformer.forward(cfg, params, batch)
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32)

                jitted = jax.jit(prefill, in_shardings=(p_shardings, b_shardings))
                args = (params_abs, batch_abs)
        else:  # decode
            dspec = decode_input_specs(cfg, shape)
            cache_abs = dspec["cache"]
            c_specs = decode_cache_specs(ctx, cache_abs)
            c_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), c_specs,
                                       is_leaf=lambda x: isinstance(x, P))
            t_sharding = NamedSharding(mesh, ctx.spec_for(("batch", None), dspec["tokens"].shape))
            serve = make_serve_step(cfg)
            jitted = jax.jit(
                serve,
                in_shardings=(p_shardings, c_shardings, t_sharding),
                out_shardings=(NamedSharding(mesh, ctx.spec_for(("batch", None), dspec["tokens"].shape)),
                               None, c_shardings),
                donate_argnums=(1,),
            )
            args = (params_abs, cache_abs, dspec["tokens"])

    info["n_params"] = sum(math.prod(p.shape) for p in jax.tree.leaves(params_abs))
    return jitted, args, ctx, info, cfg


def model_flops_estimate(cfg, info) -> float:
    """MODEL_FLOPS (global): 6*N*D train / 2*N_active*D inference-ish."""
    n = info["n_params"]
    seq, gb, kind = SHAPES[info["shape"]]
    # active params for MoE: experts scaled by top_k / n_experts
    if cfg.n_experts:
        params_abs, meta = cfg.abstract()
        from ..core.labels import flatten_with_names
        total, expert = 0, 0
        for (name, p), (_, m) in zip(flatten_with_names(params_abs)[0], flatten_with_names(meta)[0]):
            sz = math.prod(p.shape)
            total += sz
            if "experts" in m.axes and m.role != "moe_router":
                expert += sz
        n = total - expert + expert * cfg.top_k / cfg.n_experts
    tokens = seq * gb if kind != "decode" else gb  # decode: one token per seq
    if kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens


def run_cell(arch: str, shape: str, mesh_kind: str, *, optimizer: str = "slim",
             grad_accum: Optional[int] = None, out_dir: Path = RESULTS_DIR,
             variant: str = "default", backend: str = "jnp") -> Dict[str, Any]:
    ok, reason = cell_supported(arch, shape)
    record: Dict[str, Any] = {"arch": arch, "shape": shape, "mesh": mesh_kind}
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        return record

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = math.prod(mesh.devices.shape)
    t0 = time.time()
    jitted, args, ctx, info, cfg = build_cell(arch, shape, mesh, optimizer=optimizer,
                                              grad_accum=grad_accum, variant=variant,
                                              backend=backend)
    with use_sharding(ctx):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    record.update(info)
    record["status"] = "ok"
    record["n_chips"] = n_chips
    record["lower_s"] = round(t_lower, 1)
    record["compile_s"] = round(t_compile, 1)

    mem = compiled.memory_analysis()
    if mem is not None:
        for attr in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "temp_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                record[f"mem_{attr}"] = int(v)
        args_b = record.get("mem_argument_size_in_bytes", 0)
        temp_b = record.get("mem_temp_size_in_bytes", 0)
        record["fits_hbm"] = bool(args_b + temp_b <= HBM_PER_CHIP)

    cost = compiled.cost_analysis()
    # Multi-module executables (e.g. shard_map'd pallas_call bodies under the
    # fused backend) report a list of per-module dicts; take the main module.
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if cost:
        record["xla_cost_flops_raw"] = float(cost.get("flops", -1.0))
        record["xla_cost_bytes_raw"] = float(cost.get("bytes accessed", -1.0))

    stats = hlo_analysis.analyze(compiled.as_text())
    record["hlo_dot_flops_per_dev"] = stats.dot_flops
    record["hlo_traffic_bytes_per_dev"] = stats.traffic_bytes
    record["hlo_collective_bytes_per_dev"] = stats.collective_bytes
    record["hlo_collective_counts"] = stats.collective_count
    record["hlo_unresolved_loops"] = stats.unresolved_loops

    # --- roofline terms (seconds per step, per chip)
    compute_t = stats.dot_flops / PEAK_FLOPS_BF16
    memory_t = stats.traffic_bytes / HBM_BW
    collective_t = stats.total_collective_bytes / ICI_BW_PER_LINK
    record["roofline"] = {
        "compute_s": compute_t,
        "memory_s": memory_t,
        "collective_s": collective_t,
        "dominant": max(
            (("compute", compute_t), ("memory", memory_t), ("collective", collective_t)),
            key=lambda kv: kv[1],
        )[0],
    }
    mf = model_flops_estimate(cfg, info)
    record["model_flops_global"] = mf
    record["model_flops_per_dev"] = mf / n_chips
    if stats.dot_flops > 0:
        record["useful_flops_ratio"] = (mf / n_chips) / stats.dot_flops
        bound = max(compute_t, memory_t, collective_t)
        record["roofline_fraction"] = (mf / n_chips / PEAK_FLOPS_BF16) / bound if bound > 0 else 0.0

    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "" if optimizer == "slim" else f"_{optimizer}"
    if variant != "default":
        suffix += f"_{variant}"
    if backend != "jnp":
        suffix += f"_{backend}"
    out_path = out_dir / f"{arch}__{shape}__{mesh_kind}{suffix}.json"
    out_path.write_text(json.dumps(record, indent=2, default=str))
    record["out_path"] = str(out_path)
    return record


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, required=False)
    ap.add_argument("--shape", choices=list(SHAPES), required=False)
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--optimizer", choices=("slim", "adam"), default="slim")
    ap.add_argument("--backend", choices=("jnp", "fused"), default="jnp",
                    help="optimizer execution path; 'fused' lowers shard_map'd "
                         "Pallas optimizer kernels into the cell")
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--variant", default="default")
    ap.add_argument("--list", action="store_true", help="list all runnable cells")
    args = ap.parse_args(argv)

    if args.list:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                ok, reason = cell_supported(arch, shape)
                print(f"{arch:22s} {shape:12s} {'RUN' if ok else 'SKIP: ' + reason}")
        return 0

    rec = run_cell(args.arch, args.shape, args.mesh, optimizer=args.optimizer,
                   grad_accum=args.grad_accum, variant=args.variant,
                   backend=args.backend)
    print(json.dumps(rec, indent=2, default=str))
    return 0 if rec["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())

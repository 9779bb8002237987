#!/usr/bin/env python3
"""On-chip smoke test: the SlimAdam trainer and the paged server on a TPU.

    python chip_smoke.py             # one chip: train + serve phases
    python chip_smoke.py --chips 4   # four chips: sharded train step only

Run from the root of the repository. Everything runs in this one process
(a TPU chip belongs to one process at a time). It refuses to run without a
TPU: there is no CPU fallback.

One chip:

  * **train** — ``Trainer`` on full-width GPT-small (124M, seq 1024) with
    random weights from ``--seed`` and ``ZipfLM`` data, ``backend="fused"``,
    for SlimAdam and then Adam. The batch is the paper's 32 when the
    compiled step fits the device's memory, else the largest that does.
    Checks: finite losses, no optimizer leaf degraded to jnp, a Mosaic
    kernel (``tpu_custom_call``) in the compiled step, and the same steps
    with ``backend="jnp"`` within ``TRAIN_LOSS_TOL``.
  * **serve** — ``Engine`` on full-width SmolLM-135M, 8 slots, 16-token
    pages, 128-token prefill chunks, ``max_seq`` 2048: eight requests with
    100-1000-token prompts and 32 new tokens each. Checks: every request
    completes, no degraded step, no leaked page, and the paged kernel
    matches ``paged_attention_ref`` on the engine's own pool within
    ``ATTN_TOL`` for one decode step and one prefill chunk.

Four chips (``--chips 4``): GPT-small on a (data=2, model=2) mesh through
the launcher's sharded setup, fused SlimAdam, against the same steps on one
device. Checks: losses within ``SHARDED_LOSS_TOL``, no degraded leaf, and
every psum-regime leaf served by the Pallas partial/finalize pair
(``psum_jnp == 0``).

Times printed here are smoke timings of a handful of steps, not benchmark
results. The last line of standard output is one JSON object naming the
device; it is printed only when every check passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SEQ = 1024
TRAIN_BATCHES = (32, 16, 8)       # the paper's micro-batch first
TRAIN_STEPS = 5
TRAIN_LR = 1e-3
# Tolerances sit a few times above the readings on a v5e (PERF.md): fused vs
# jnp losses differed by 1.5e-5 at most, mesh vs one device by 2.1e-3, the
# paged kernel vs the reference by 8.6e-4 (relative, bf16 output).
TRAIN_LOSS_TOL = 1e-4             # |fused - jnp| per step, absolute
SHARDED_BATCH = 8
SHARDED_STEPS = 4
SHARDED_LOSS_TOL = 1e-2           # |2x2 mesh - one device| per step, absolute
ATTN_TOL = 5e-3                   # relative; under one bf16 ULP at 1.0 (7.8e-3)
SERVE_REQUESTS = 8
SERVE_NEW_TOKENS = 32


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


def device_check(want_chips: int):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SmokeFailure(f"no TPU found: JAX's default device is "
                           f"{d0.platform} ({d0.device_kind}); this smoke "
                           f"test runs on a TPU only")
    say(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    check(len(devs) >= want_chips,
          f"--chips {want_chips} needs {want_chips} TPU devices, found {len(devs)}")
    return d0


def program_bytes(ma) -> int:
    """Device bytes one compiled program needs live at once."""
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def device_memory_limit(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("bytes_limit", 16 * 2**30))


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------


def _trainer(cfg, optimizer: str, backend: str, batch: int, seed: int):
    from repro.data import DataConfig, ZipfLM
    from repro.train import Trainer, TrainerConfig

    data = ZipfLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                             global_batch=batch, seed=seed))
    tc = TrainerConfig(total_steps=TRAIN_STEPS, log_every=1, seed=seed,
                       backend=backend)
    return Trainer(cfg, optimizer, TRAIN_LR, data, tc)


def _run_steps(tr, steps: int):
    """Step 1 warms up (its program is already compiled); steps 2..n are
    timed, ended by block_until_ready. Returns (losses, seconds per step)."""
    import jax

    tr.run(1)
    jax.block_until_ready(tr.params)
    t0 = time.perf_counter()
    tr.run(steps)
    jax.block_until_ready((tr.params, tr.opt_state))
    per_step = (time.perf_counter() - t0) / (steps - 1)
    return [m["loss"] for m in tr.metrics_log], per_step


def train_phase(cfg, optimizer: str, seed: int, mem_limit: int) -> None:
    from repro.optim import fused

    tag = f"train[{cfg.name}/{optimizer}]"
    fused.reset_kernel_degradation()
    tr = compiled = None
    for batch in TRAIN_BATCHES:
        tr = _trainer(cfg, optimizer, "fused", batch, seed)
        t0 = time.perf_counter()
        compiled = tr.compile_step()
        compile_s = time.perf_counter() - t0
        need = program_bytes(compiled.memory_analysis())
        say(f"{tag}: batch {batch} x seq {SEQ}: compiled step needs "
            f"{need / 2**30:.2f} GiB of {mem_limit / 2**30:.2f} GiB; "
            f"compile (set-up) {compile_s:.1f} s")
        if need <= mem_limit:
            break
        tr = compiled = None
        gc.collect()
    check(tr is not None, f"{tag}: no batch in {TRAIN_BATCHES} fits the device")
    n_kernels = compiled.as_text().count("tpu_custom_call")
    say(f"{tag}: batch {batch}; tpu_custom_call sites in the compiled step: "
        f"{n_kernels}")
    check(n_kernels > 0, f"{tag}: no Pallas kernel in the compiled train step")
    del compiled

    losses, per_step = _run_steps(tr, TRAIN_STEPS)
    degraded = fused.kernel_degraded_leaves()
    say(f"{tag}: fused losses {losses}")
    say(f"{tag}: smoke timing, not a benchmark: {per_step * 1e3:.1f} ms/step "
        f"over steps 2-{TRAIN_STEPS} (block_until_ready)")
    say(f"{tag}: degraded optimizer leaves: {degraded}")
    check(all(math.isfinite(x) for x in losses), f"{tag}: non-finite loss {losses}")
    check(degraded == 0, f"{tag}: {degraded} optimizer leaves fell back to jnp")
    del tr
    gc.collect()

    ref = _trainer(cfg, optimizer, "jnp", batch, seed)
    ref_losses, _ = _run_steps(ref, TRAIN_STEPS)
    del ref
    gc.collect()
    diff = max(abs(a - b) for a, b in zip(losses, ref_losses))
    say(f"{tag}: jnp losses {ref_losses}; max |fused - jnp| = {diff:.3g} "
        f"(tolerance {TRAIN_LOSS_TOL})")
    check(diff <= TRAIN_LOSS_TOL,
          f"{tag}: fused and jnp losses differ by {diff} > {TRAIN_LOSS_TOL}")


# ---------------------------------------------------------------------------
# Serve
# ---------------------------------------------------------------------------


def _paged_parity(pool, cfg, lens, seed: int, tag: str) -> None:
    """The paged kernel against the dense reference on one layer of the
    engine's own pool: a decode step over every slot and one prefill chunk."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_attention, paged_attention_ref

    n_pages, _, page, _ = pool.shape
    hd = cfg.hd
    slots = len(lens)
    max_pages = (n_pages - 1) // slots
    table = jnp.asarray(1 + np.arange(slots * max_pages, dtype=np.int32)
                        .reshape(slots, max_pages))
    key = jax.random.PRNGKey(seed)
    kernel = jax.jit(paged_attention)
    ref = jax.jit(paged_attention_ref)
    cases = {"decode": (jnp.asarray(lens, jnp.int32), 1, table),
             "prefill": (jnp.asarray(lens[:1], jnp.int32), 128, table[:1])}
    for name, (lengths, chunk, tbl) in cases.items():
        key, sub = jax.random.split(key)
        q = jax.random.normal(sub, (tbl.shape[0], chunk, cfg.n_heads, hd),
                              jnp.bfloat16)
        text = kernel.lower(q, pool, tbl, lengths).compile().as_text()
        check("tpu_custom_call" in text,
              f"{tag}: paged_attention {name} did not compile to the kernel")
        got = np.asarray(kernel(q, pool, tbl, lengths), np.float32)
        want = np.asarray(ref(q, pool, tbl, lengths), np.float32)
        check(bool(np.any(want != 0)), f"{tag}: reference {name} output is all zeros")
        err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
        say(f"{tag}: paged_attention {name} (q {tuple(q.shape)}) vs reference: "
            f"max rel err {err:.3g} (tolerance {ATTN_TOL})")
        check(err <= ATTN_TOL, f"{tag}: paged_attention {name} error {err}")


def serve_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.paged_attention import paged_fits
    from repro.serve import Engine, Rejected, Request, ServeConfig

    cfg = get_config("smollm_135m")
    tag = f"serve[{cfg.name}]"
    sc = ServeConfig(page_size=16, prefill_chunk=128, max_seq=2048,
                     max_slots=8, max_new_tokens=SERVE_NEW_TOKENS)
    for chunk in (1, sc.prefill_chunk):
        check(paged_fits(chunk, cfg.n_heads, cfg.hd, sc.page_size,
                         cfg.n_kv_heads),
              f"{tag}: paged_attention would take the reference at C={chunk}")
    params, _ = cfg.init(jax.random.PRNGKey(seed))
    eng = Engine(cfg, params, sc)
    rng = np.random.default_rng(seed)

    t0 = time.perf_counter()
    eng.submit(Request(prompt=rng.integers(0, cfg.vocab_size, 100),
                       max_new_tokens=2))
    eng.run_until_drained()
    say(f"{tag}: warm-up request (compiles prefill chunk + decode step, "
        f"set-up) {time.perf_counter() - t0:.1f} s")

    lens = rng.integers(100, 1001, SERVE_REQUESTS)
    rids = []
    for n in lens:
        rid = eng.submit(Request(prompt=rng.integers(0, cfg.vocab_size, int(n)),
                                 max_new_tokens=SERVE_NEW_TOKENS))
        check(not isinstance(rid, Rejected), f"{tag}: request rejected: {rid}")
        rids.append(rid)
    t0 = time.perf_counter()
    done = eng.run_until_drained()
    wall = time.perf_counter() - t0
    m = eng.metrics()
    say(f"{tag}: {len(done)}/{len(rids)} requests done, prompt lengths "
        f"{lens.tolist()}, {m.prefill_chunks} prefill chunks, "
        f"{m.decode_steps} decode steps, degraded_steps {m.degraded_steps}")
    check(sorted(done) == sorted(rids), f"{tag}: missing completions")
    for rid in rids:
        c = done[rid]
        check(c.finish_reason == "length" and len(c.tokens) == SERVE_NEW_TOKENS,
              f"{tag}: request {rid} ended {c.finish_reason!r} after "
              f"{len(c.tokens)} tokens")
    check(m.degraded_steps == 0,
          f"{tag}: {m.degraded_steps} steps fell back to the reference path")
    check(m.nan_retired == 0, f"{tag}: {m.nan_retired} requests hit NaN logits")
    check(eng.pool.used_pages == 0
          and eng.pool.alloc_count == eng.pool.free_count,
          f"{tag}: page leak: {eng.pool.used_pages} pages in use, "
          f"{eng.pool.alloc_count} allocated vs {eng.pool.free_count} freed")
    ttft = float(np.mean([done[r].ttft_s for r in rids]))
    tpot = float(np.mean([done[r].tpot_s for r in rids]))
    say(f"{tag}: smoke timing, not a benchmark: TTFT mean {ttft * 1e3:.1f} ms, "
        f"TPOT mean {tpot * 1e3:.2f} ms, drain wall {wall:.2f} s")

    pool = eng._device_pools()["slot_0"][:eng.pool.n_pages]   # layer 0
    _paged_parity(pool, cfg, [int(n) + SERVE_NEW_TOKENS for n in lens], seed,
                  tag)


# ---------------------------------------------------------------------------
# Four chips: the sharded train step
# ---------------------------------------------------------------------------


def sharded_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data import DataConfig, ZipfLM
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_train_setup
    from repro.optim import fused
    from repro.sharding.logical import param_specs, use_sharding
    from repro.sharding.shardspec import regime_counts
    from repro.train.trainer import slim_rule_dims

    cfg = get_config("gpt_small")
    mesh = make_mesh((2, 2), ("data", "model"))
    tag = f"sharded[{cfg.name}/slim, mesh data=2 x model=2]"
    data = ZipfLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=SEQ,
                             global_batch=SHARDED_BATCH, seed=seed))
    batches = [{k: jnp.asarray(v) for k, v in data.batch(s).items()}
               for s in range(SHARDED_STEPS)]

    def run(m):
        setup = build_train_setup(cfg, "slim", TRAIN_LR, backend="fused",
                                  mesh=m, batch=SHARDED_BATCH, seq=SEQ,
                                  seed=seed)
        p, o = setup.params, setup.opt_state
        with use_sharding(setup.ctx):
            t0 = time.perf_counter()
            text = setup.step_fn.lower(p, o, batches[0]).compile().as_text()
            compile_s = time.perf_counter() - t0
            losses = []
            for b in batches:
                p, o, metrics = setup.step_fn(p, o, b)
                losses.append(float(metrics["loss"]))
        return setup.ctx, losses, text, compile_s

    fused.reset_kernel_degradation()
    ctx, losses, text, compile_s = run(mesh)
    n_kernels = text.count("tpu_custom_call")
    say(f"{tag}: batch {SHARDED_BATCH} x seq {SEQ}; compile (set-up) "
        f"{compile_s:.1f} s; tpu_custom_call sites {n_kernels}; "
        f"losses {losses}")
    params_abs, meta = cfg.abstract()
    with use_sharding(ctx):
        specs = param_specs(meta, params_abs)
    leaves, treedef = jax.tree_util.tree_flatten(params_abs)
    dims = slim_rule_dims("slim", params_abs, meta)
    plans = fused.sharded_tree_plans(
        leaves, [tuple(d) for d in treedef.flatten_up_to(dims)],
        treedef.flatten_up_to(specs), mesh)
    counts = regime_counts(plans, degraded=fused.kernel_degraded_leaves())
    say(f"{tag}: regime counts {counts}")
    _, ref_losses, _, _ = run(None)
    diff = max(abs(a - b) for a, b in zip(losses, ref_losses))
    say(f"{tag}: one-device losses {ref_losses}; max |mesh - one device| = "
        f"{diff:.3g} (tolerance {SHARDED_LOSS_TOL})")
    check(n_kernels > 0, f"{tag}: no Pallas kernel in the sharded step")
    check(all(math.isfinite(x) for x in losses), f"{tag}: non-finite loss")
    check(counts["degraded"] == 0, f"{tag}: degraded leaves {counts}")
    check(counts["psum"] > 0 and counts["psum_jnp"] == 0,
          f"{tag}: psum regime not fully kernel-served: {counts}")
    check(diff <= SHARDED_LOSS_TOL,
          f"{tag}: sharded and one-device losses differ by {diff}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train + serve phases on one chip; 4: only the "
                         "sharded train step on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: FAIL: the repro package is not under {SRC}; run "
              f"this script from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    try:
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        dev = device_check(args.chips)
        t0 = time.perf_counter()
        if args.chips == 4:
            sharded_phase(args.seed)
        else:
            from repro.configs import get_config

            gpt = get_config("gpt_small")
            limit = device_memory_limit(dev)
            for optimizer in ("slim", "adam"):
                train_phase(gpt, optimizer, args.seed, limit)
            serve_phase(args.seed)
        say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1

    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

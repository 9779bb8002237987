"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot —
tile-misaligned slices, unsupported gathers, VMEM overruns — at real widths
and without a chip:

  * the whole-tree optimizer megakernels on GPT-small's planned groups:
    ``mega_adam_update`` (dense groups of the Adam and SlimAdam trees),
    ``mega_slim_update_batched`` and the sharded psum pair
    ``mega_slim_partial_stats_batched`` / ``mega_slim_finalize_batched``
    on every compressed group;
  * ``paged_attention`` at SmolLM-135M serving widths, decode (C=1, eight
    slots) and one 128-token prefill chunk, over a 2048-position table, and
    at Command R's wider GQA, where the chunk is the largest that the
    kernel's VMEM fit gate ``paged_fits`` admits;
  * the serving engine's two programs, ``paged_decode_step`` and
    ``paged_prefill_chunk``, for SmolLM-135M at the serving benchmark's
    geometry with the pools donated: no op copies or relayouts a pool or a
    layer's share of one, and the pool argument aliases the pool output.

Each compile must contain a Mosaic kernel (``tpu_custom_call``): a kernel
that silently fell back to plain XLA would compile too. Every call passes
``interpret=False``, since the process itself runs on the CPU backend.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and test collection
must not depend on it.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import rules_as_tree, table3_rules
from repro.kernels import megaplan
from repro.kernels import paged_attention as pa
from repro.kernels.paged_attention import paged_attention, paged_fits, pool_shape
from repro.models import transformer
from repro.kernels.slim_update import PRECOND_BUFS

F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: an
    entry written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_for_tpu(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"


def _gpt_small_plan(optimizer: str) -> megaplan.MegaPlan:
    """The megakernel plan the fused optimizer builds for full-width
    GPT-small (abstract parameters; nothing is materialized)."""
    params, meta = get_config("gpt_small").abstract()
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if optimizer == "slim":
        dims = rules_as_tree(table3_rules(meta), params, meta)
        dims_leaves = [tuple(d) for d in treedef.flatten_up_to(dims)]
    else:
        dims_leaves = [()] * len(leaves)
    return megaplan.plan_megagroups([p.shape for p in leaves],
                                    [p.dtype for p in leaves], dims_leaves,
                                    n_bufs=PRECOND_BUFS)


def _groups(optimizer: str, kind: str):
    groups = [g for g in _gpt_small_plan(optimizer).groups if g.kind == kind]
    assert groups, f"GPT-small {optimizer} plan has no {kind} group"
    return groups


def _view3(group):
    """(batch, rows, cols) of a slim group's super-tensor and its line."""
    b, r, c = group.view if group.kind == "batched" else (1,) + group.view
    return (b, r, c), ((b, r, 1) if group.axis == 1 else (b, 1, c))


@pytest.mark.parametrize("optimizer", ["adam", "slim"])
def test_mega_adam_update(one_chip, optimizer):
    (group,) = _groups(optimizer, "dense")
    full, line = (group.rows, group.cols), (group.rows, 1)
    _compile_for_tpu(
        lambda g, m, v, l1, l2: megaplan.mega_adam_update(
            g, m, v, l1, l2, with_health=True, interpret=False),
        (full, F32), (full, F32), (full, F32), (line, F32), (line, F32),
        sharding=one_chip)


@pytest.mark.parametrize("kind", ["batched", "minor"])
def test_mega_slim_update_batched(one_chip, kind):
    for group in _groups("slim", kind):
        full, line = _view3(group)
        _compile_for_tpu(
            lambda g, m, v, l1, l2: megaplan.mega_slim_update_batched(
                g, m, v, l1, l2, axis=group.axis, with_health=True,
                interpret=False),
            (full, F32), (full, F32), (line, F32), (line, F32), (line, F32),
            sharding=one_chip)


@pytest.mark.parametrize("kind", ["batched", "minor"])
def test_mega_slim_partial_stats_batched(one_chip, kind):
    for group in _groups("slim", kind):
        full, _ = _view3(group)
        _compile_for_tpu(
            lambda g, m: megaplan.mega_slim_partial_stats_batched(
                g, m, axis=group.axis, with_health=True, interpret=False),
            (full, F32), (full, F32), sharding=one_chip)


@pytest.mark.parametrize("form", ["ek", "owner"])
@pytest.mark.parametrize("kind", ["batched", "minor"])
def test_mega_slim_finalize_batched(one_chip, kind, form):
    for group in _groups("slim", kind):
        full, line = _view3(group)
        lines = [(line, F32)] * (4 if form == "ek" else 3)
        _compile_for_tpu(
            lambda m, v, l1, l2, *ek: megaplan.mega_slim_finalize_batched(
                m, v, l1, l2, axis=group.axis, ek=ek[0] if ek else None,
                interpret=False),
            (full, F32), *lines, sharding=one_chip)


def _compile_paged(cfg, slots, chunk, sharding, page=16, max_seq=2048):
    max_pages = max_seq // page
    pool = pool_shape(slots * max_pages + 1, page, cfg.n_kv_heads, cfg.hd)
    _compile_for_tpu(
        lambda q, p, t, n: paged_attention(q, p, t, n, interpret=False),
        ((slots, chunk, cfg.n_heads, cfg.hd), jnp.bfloat16),
        (pool, jnp.bfloat16), ((slots, max_pages), jnp.int32),
        ((slots,), jnp.int32), sharding=sharding)


@pytest.mark.parametrize("slots,chunk", [(8, 1), (1, 128)],
                         ids=["decode", "prefill"])
def test_paged_attention_smollm(one_chip, slots, chunk):
    _compile_paged(get_config("smollm_135m"), slots, chunk, one_chip)


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_paged_attention_large_gqa(one_chip, phase):
    """Command R widths (64 query heads over 8 kv heads, head_dim 128): the
    softmax stats' (8, 128)-tile padding dominates the VMEM working set
    there. Whatever ``paged_fits`` admits must compile to the kernel; the
    prefill case takes the largest admitted chunk up to 128."""
    cfg = get_config("command_r_35b")
    admitted = [c for c in (1, 16, 32, 64, 128)
                if paged_fits(c, cfg.n_heads, cfg.hd, 16, cfg.n_kv_heads)]
    assert admitted[-1] >= 16, admitted
    slots, chunk = (8, 1) if phase == "decode" else (1, admitted[-1])
    _compile_paged(cfg, slots, chunk, one_chip)


# -- the engine's paged programs at the serving cells' geometry --------------

_HLO_OP = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([0-9,]*)\]\S* "
                     r"([\w\-]+)\((.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
# Ops of a pool's size that move no bytes, or the in-place write itself.
_NO_COPY = {"parameter", "get-tuple-element", "tuple", "bitcast", "scatter"}


def _pool_sized_ops(text: str, sizes) -> list:
    """(name, opcode) of each op of a compiled module whose result has one
    of ``sizes`` elements and that may copy it: every op but parameters,
    tuple plumbing, bitcasts and the scatter, a fusion counting as the op
    at its root."""
    roots, ops, comp = {}, [], None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _HLO_OP.match(line)
        if not m:
            continue
        name, dims, opcode, rest = m.groups()
        if line.lstrip().startswith("ROOT "):
            roots[comp] = opcode
        n = math.prod(int(d) for d in dims.split(",") if d)
        if n in sizes:
            called = re.search(r"calls=%([\w.\-]+)", rest)
            ops.append((name, opcode, called.group(1) if called else None))
    return [(name, op) for name, op, called in ops
            if (roots.get(called, op) if op == "fusion" else op) not in _NO_COPY]


def _aliases(text: str) -> set:
    """(output index, parameter number) pairs of ``input_output_alias``."""
    head = text.split("\n", 1)[0]
    return {(int(o), int(p)) for o, p in
            re.findall(r"\{(\d+)\}: \((\d+), \{\}", head)}


def _entry_types(text: str):
    """Parameter types and output types of the entry computation."""
    line = next(ln for ln in text.splitlines() if ln.startswith("ENTRY"))
    params, out = line.split(") -> (", 1)
    return (re.findall(r": (\w+\[[0-9,]*\])", params),
            re.findall(r"\w+\[[0-9,]*\]", out))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_paged_program_writes_the_pool_in_place(one_chip, monkeypatch, program):
    """SmolLM-135M's decode step and prefill chunk as the serving cells run
    them (64 slots, 16-token pages, 2048 positions, 8193 pages, 30 layers),
    with the Mosaic kernel and the pools donated as the engine donates
    them: no op of a whole pool's size or of one layer's share of it may
    copy it (the write is one scatter into the donated pool), and the pool
    argument is the pool output's buffer."""
    monkeypatch.setattr(pa, "default_interpret",
                        lambda i=None: False if i is None else i)
    cfg = get_config("smollm_135m")
    slots, page, n_pages = 64, 16, 8193
    max_pages = 2048 // page
    shape = pool_shape(cfg.n_periods * n_pages, page, cfg.n_kv_heads, cfg.hd)

    def arg(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    params = jax.tree.map(lambda a: arg(a.shape, a.dtype), cfg.abstract()[0])
    pools = {"slot_0": arg(shape, jnp.bfloat16)}
    if program == "decode":
        fn = lambda p, st, t: transformer.paged_decode_step(cfg, p, st, t)
        state = transformer.PagedState(
            pools=pools, table=arg((slots, max_pages), jnp.int32),
            lengths=arg((slots,), jnp.int32), active=arg((slots,), bool))
        args = (params, state, arg((slots, 1), jnp.int32))
    else:
        fn = lambda p, pl_, r, a, n, t: transformer.paged_prefill_chunk(
            cfg, p, pl_, r, a, n, t)
        args = (params, pools, arg((1, max_pages), jnp.int32),
                arg((), jnp.int32), arg((), jnp.int32),
                arg((1, 128), jnp.int32))
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    whole = math.prod(shape)
    assert _pool_sized_ops(text, {whole, whole // cfg.n_periods}) == []
    hlo = f"bf16[{','.join(map(str, shape))}]"
    params_t, outs_t = _entry_types(text)
    assert params_t.count(hlo) == 1 and outs_t.count(hlo) == 1
    assert (outs_t.index(hlo), params_t.index(hlo)) in _aliases(text)

"""What the program records for a profile, and its counters: the engine's
programs are named, the page-pool ops carry the ``kv_pool`` scope and the
optimizer update the ``optimizer`` scope, ``Engine.step`` and
``Trainer.run`` write ``repro.*`` host spans nested by phase, and the
engine counts decode rows and the time each request spent queued."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_reduced
from repro.data import DataConfig, ZipfLM
from repro.models.transformer import PagedState
from repro.serve import Engine, Request, ServeConfig
from repro.train import Trainer, TrainerConfig
from repro.train.guard import GuardConfig

_OP = re.compile(r"^\s*%?([\w.\-]+) = (\w+)\[([0-9,]*)\]\S* ([\w\-]+)\(.*"
                 r'op_name="([^"]*)"')


def _ops(lowered) -> list:
    """(name, dtype, shape, opcode, op_name) of each op of a lowered program
    that has an op_name."""
    out = []
    for line in lowered.as_text(dialect="hlo", debug_info=True).splitlines():
        m = _OP.match(line)
        if m:
            name, dt, dims, opcode, op_name = m.groups()
            shape = tuple(int(d) for d in dims.split(",") if d)
            out.append((name, dt, shape, opcode, op_name))
    return out


def _engine(**sc):
    cfg = get_reduced("smollm_135m")
    params, _ = cfg.init(jax.random.PRNGKey(0))
    kw = dict(max_seq=32, max_slots=2, page_size=4, prefill_chunk=4,
              max_new_tokens=3)
    kw.update(sc)
    return Engine(cfg, params, ServeConfig(**kw))


def _lowered(eng, program):
    pools = eng._device_pools()
    b = eng.sc.max_slots
    if program == "decode":
        state = PagedState(pools=pools, table=jnp.asarray(eng.scheduler.table),
                           lengths=jnp.zeros((b,), jnp.int32),
                           active=jnp.zeros((b,), bool))
        return eng._decode.lower(eng.params, state, jnp.zeros((b, 1), jnp.int32))
    return eng._prefill.lower(eng.params, pools,
                              jnp.asarray(eng.scheduler.table[:1]), np.int32(0),
                              np.int32(1), jnp.zeros((1, eng.sc.prefill_chunk),
                                                     jnp.int32))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_engine_program_is_named(program):
    text = _lowered(_engine(), program).as_text(dialect="hlo")
    assert text.startswith(f"HloModule jit_paged_{program},")


_ANY_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([0-9,]*)\]\S* "
                     r"[\w\-]+\(([^)]*)\)")


def _operands(lowered) -> tuple:
    """Element count of every array op of a lowered program by name, and
    each op's operand names."""
    sizes, args = {}, {}
    for line in lowered.as_text(dialect="hlo").splitlines():
        m = _ANY_OP.match(line)
        if m:
            name, dims, operands = m.groups()
            sizes[name] = int(np.prod([int(d) for d in dims.split(",") if d]))
            args[name] = [a.strip().lstrip("%") for a in operands.split(",")]
    return sizes, args


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_pool_is_written_in_place_under_kv_pool(program):
    """Each program writes a layer's K/V with one scatter into the whole
    stacked pool, under ``kv_pool``; nothing slices a layer's pool out of
    it, writes one back, or copies either. (On the CPU the Pallas
    interpreter slices and stores single pages; those are the kernel's.)"""
    eng = _engine()
    (pool,) = eng._device_pools().values()
    pool_sizes = {pool.size, pool.size // eng.cfg.n_periods}
    low = _lowered(eng, program)
    ops = _ops(low)
    sizes, args = _operands(low)
    writes = [o for o in ops if o[3] == "scatter"]
    assert [o[2] for o in writes] == [pool.shape]
    assert "kv_pool" in writes[0][4].split("/")
    moved = [o for o in ops
             if (o[3] in ("dynamic-slice", "copy")
                 and sizes[o[0]] in pool_sizes)
             or (o[3] == "dynamic-update-slice"
                 and sizes[args[o[0]][1]] in pool_sizes)]
    assert moved == []
    # the attention over the pool is not pool bookkeeping
    dots = [o for o in ops if o[3] == "dot"]
    assert dots and not any("kv_pool" in o[4].split("/") for o in dots)


def _trainer(guard=False, **tc):
    cfg = get_reduced("gpt_small")
    data = ZipfLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                             global_batch=2))
    kw = dict(total_steps=4, log_every=2, seed=0)
    kw.update(tc)
    if guard:
        kw["guard"] = GuardConfig()
    return Trainer(cfg, "slim", 1e-3, data, TrainerConfig(**kw))


@pytest.mark.parametrize("guard", [False, True], ids=["plain", "guarded"])
def test_optimizer_update_is_under_its_scope(guard):
    t = _trainer(guard)
    batch = {k: jnp.asarray(v) for k, v in t.data.batch(0).items()}
    args = (t.params, t.opt_state, batch)
    if guard:
        args += ({"lr_scale": jnp.float32(1.0), "grad_scale": jnp.float32(1.0)},)
    ops = _ops(t._train_step.lower(*args))
    under = [o for o in ops if "optimizer" in o[4].split("/")]
    assert under, "no op of the train step is under the optimizer scope"
    # the update is elementwise: the model's matmuls lie outside the scope
    dots = [o for o in ops if o[3] == "dot"]
    assert dots and not any("optimizer" in o[4].split("/") for o in dots)


def _spans(tmp_path, fn, prefix="repro."):
    """Run ``fn`` under the profiler; (start, end, name, stats) of every
    host span whose name starts with ``prefix``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        out.append((e.start_ns, e.end_ns, e.name, dict(e.stats)))
    return out


def _inside(inner, outers) -> bool:
    return any(o[0] <= inner[0] and inner[1] <= o[1] for o in outers)


def test_engine_step_spans_nest_by_phase(tmp_path):
    eng = _engine()
    eng.submit(Request(prompt=np.arange(1, 7, dtype=np.int32)))   # warm up
    eng.run_until_drained()
    rids = [eng.submit(Request(prompt=np.arange(1, 7, dtype=np.int32)))
            for _ in range(3)]                 # 3 requests, 2 slots: one waits

    spans = _spans(tmp_path, lambda: [eng.step() for _ in range(4)])
    by = {}
    for s in spans:
        by.setdefault(s[2], []).append(s)
    assert len(by["repro.serve.step"]) == 4
    for phase in ("admit", "decode", "readback", "sample"):
        assert by[f"repro.serve.{phase}"], phase
        for s in by[f"repro.serve.{phase}"]:
            assert _inside(s, by["repro.serve.step"]), phase
    prefills = by["repro.serve.prefill"]
    assert sorted(s[3]["rid"] for s in prefills) == rids
    assert all(_inside(s, by["repro.serve.admit"]) for s in prefills)
    # readback follows the decode dispatch it reads
    for r in by["repro.serve.readback"]:
        assert any(d[1] <= r[0] for d in by["repro.serve.decode"])


def test_trainer_run_spans_by_phase(tmp_path):
    t = _trainer(log_every=2, ckpt_every=2, ckpt_dir=str(tmp_path / "ckpt"),
                 measure_snr=True, snr_early_every=2)
    t.run(1)                                   # compile outside the profile
    spans = _spans(tmp_path / "trace", lambda: t.run(4))
    names = [s[2] for s in sorted(spans)]
    assert names.count("repro.train.batch") == 3
    assert names.count("repro.train.step") == 3
    assert {"repro.train.readback", "repro.train.snr",
            "repro.train.checkpoint"} <= set(names)
    # one step's phases follow each other, none inside another
    ordered = sorted(spans)
    for a, b in zip(ordered, ordered[1:]):
        assert a[1] <= b[0], (a[2], b[2])
    assert names.index("repro.train.batch") < names.index("repro.train.step")


def _drive(eng, clock, dt=1.0):
    """Step to the end on a clock that advances ``dt`` per step."""
    eng._now = lambda: clock[0]
    done = {}
    while eng.scheduler.queue or eng.scheduler.active_slots():
        assert eng.step() is None
        clock[0] += dt
        done.update(eng.completions())
    return done


def test_decode_rows_count_the_tokens_decoded():
    eng = _engine(max_slots=2)
    clock = [0.0]
    eng._now = lambda: clock[0]
    rids = [eng.submit(Request(prompt=np.arange(1, 6, dtype=np.int32),
                               max_new_tokens=n)) for n in (2, 5, 3)]
    done = _drive(eng, clock)
    assert sorted(done) == rids and eng.scheduler.preempted == 0
    # each request's first token comes from its prefill, the rest from decode
    decoded = sum(len(c.tokens) - 1 for c in done.values())
    m = eng.metrics()
    assert eng.decode_rows == m.decode_rows == decoded
    assert m.decode_rows_computed == m.decode_steps * 2
    assert m.decode_rows < m.decode_rows_computed


def test_queue_s_grows_with_time_queued():
    eng = _engine(max_slots=1)
    clock = [0.0]
    eng._now = lambda: clock[0]
    rids = [eng.submit(Request(prompt=np.arange(1, 6, dtype=np.int32)))
            for _ in range(3)]
    done = _drive(eng, clock)
    q = [done[r].queue_s for r in rids]
    assert q[0] == 0.0                         # admitted in the first step
    assert 0.0 < q[1] < q[2]
    # the second waited for the first to retire, one step after its last token
    assert q[1] == pytest.approx(done[rids[0]].wall_s + 1.0)


def test_queue_s_counts_the_wait_after_a_preemption():
    eng = _engine(max_seq=16, max_new_tokens=6, max_slots=2, page_size=2,
                  pool_pages=8, prefill_chunk=4)          # capacity 7
    clock = [0.0]
    eng._now = lambda: clock[0]
    r0 = eng.submit(Request(prompt=np.array([1, 2, 3, 4], np.int32)))
    r1 = eng.submit(Request(prompt=np.array([9, 8, 7, 6], np.int32)))
    done = _drive(eng, clock)
    assert done[r1].preemptions >= 1
    assert done[r0].queue_s == 0.0
    assert done[r1].queue_s >= 1.0             # both admitted at once, then requeued

"""Serving fault-tolerance layer (ISSUE 10): shared injection registry,
admission control, deadlines, degradation ladder, livelock diagnosis, and
a seeded chaos test of the scheduler invariants under injected failure.

The clean engine (no injections) is the oracle throughout: an injected run
must either produce the same greedy tokens or retire the affected request
with a meaningful finish_reason — never garbage tokens, never a leak.
"""
import warnings

import jax
import numpy as np
import pytest

from repro import injection
from repro.configs import get_reduced
from repro.serve import (
    Engine,
    LivelockError,
    Rejected,
    Request,
    ServeConfig,
    ServeFaultPlan,
    inject_paged_kernel_failure,
)
from repro.serve.faults import CLOCK_POINT

pytestmark = pytest.mark.filterwarnings("ignore")


def _mk(arch="gpt_small", **sc_kw):
    cfg = get_reduced(arch)
    params, _ = cfg.init(jax.random.PRNGKey(0))
    return cfg, params, ServeConfig(**sc_kw)


def _prompt(n, vocab, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, vocab))


def _invariants(eng):
    """No slot double-use, no page mapped twice, table agrees with pool
    ownership — checked live between scheduler steps."""
    sched = eng.scheduler
    seen = {}
    for slot in range(sched.n_slots):
        rid = sched.slot_rid[slot]
        row = sched.table[slot]
        if rid is None:
            assert not row.any(), f"empty slot {slot} has mapped pages"
            continue
        for pg in row[row != 0]:
            assert pg not in seen, f"page {pg} mapped by slots {seen[pg]},{slot}"
            seen[int(pg)] = slot
            assert eng.pool.owner(int(pg)) == rid


class TestInjectionRegistry:
    def test_fire_without_hook_is_noop(self):
        assert injection.fire("test.nothing", 1, 2) is None

    def test_installed_restores_previous_hook(self):
        with injection.installed("test.point", lambda: "outer"):
            with injection.installed("test.point", lambda: "inner"):
                assert injection.fire("test.point") == "inner"
            assert injection.fire("test.point") == "outer"
        assert injection.get("test.point") is None

    def test_call_counter_fails_on_schedule(self):
        hook, state = injection.call_counter(
            (2,), lambda n: RuntimeError(f"boom #{n}"))
        hook()
        with pytest.raises(RuntimeError, match="boom #2"):
            hook()
        hook()
        assert state == {"calls": 3, "failed": 1}


class TestAdmissionControl:
    def test_queue_full_rejects_without_exception(self):
        cfg, params, sc = _mk(max_seq=32, page_size=4, max_queue=1)
        eng = Engine(cfg, params, sc)
        first = eng.submit(Request(prompt=_prompt(4, cfg.vocab_size)))
        assert isinstance(first, int)
        verdict = eng.submit(Request(prompt=_prompt(4, cfg.vocab_size)))
        assert isinstance(verdict, Rejected)
        assert verdict.reason == "queue_full"
        assert verdict.queue_depth == 1
        m = eng.metrics()
        assert m.rejected_queue == 1 and m.rejected == 1

    def test_pool_pressure_rejects_on_projected_demand(self):
        # capacity 8 pages, watermark 0.5 -> 4 pages; each request projects
        # ceil((8 prompt + 8 new) / 4) = 4 pages, so the second must bounce.
        cfg, params, sc = _mk(max_seq=32, page_size=4, pool_pages=9,
                              max_new_tokens=8, admit_watermark=0.5)
        eng = Engine(cfg, params, sc)
        first = eng.submit(Request(prompt=_prompt(8, cfg.vocab_size)))
        assert isinstance(first, int)
        verdict = eng.submit(Request(prompt=_prompt(8, cfg.vocab_size)))
        assert isinstance(verdict, Rejected)
        assert verdict.reason == "pool_pressure"
        assert verdict.projected_pages == 8 > 0.5 * verdict.pool_capacity
        assert eng.metrics().rejected_pool == 1

    def test_impossible_request_still_raises(self):
        cfg, params, sc = _mk(max_seq=16, page_size=4, pool_pages=3)
        eng = Engine(cfg, params, sc)
        with pytest.raises(ValueError, match="pool"):
            eng.submit(Request(prompt=_prompt(8, cfg.vocab_size)))


class TestDeadlines:
    def test_queued_request_past_deadline_is_dropped(self):
        cfg, params, sc = _mk(max_seq=32, page_size=4, max_slots=1,
                              max_new_tokens=3)
        eng = Engine(cfg, params, sc)
        r0 = eng.submit(Request(prompt=_prompt(4, cfg.vocab_size)))
        r1 = eng.submit(Request(prompt=_prompt(4, cfg.vocab_size),
                                deadline_s=0.0))
        done = eng.run_until_drained()
        assert done[r1].finish_reason == "deadline"
        assert len(done[r1].tokens) == 0
        assert done[r0].finish_reason == "length"
        # r1 never reached a slot or the device
        assert eng.scheduler.admitted == 1
        assert eng.metrics().deadline_expired == 1
        assert eng.pool.used_pages == 0

    def test_active_request_retires_on_stalled_clock(self):
        cfg, params, sc = _mk(max_seq=48, page_size=4, max_new_tokens=8)
        eng = Engine(cfg, params, sc)
        rid = eng.submit(Request(prompt=_prompt(4, cfg.vocab_size),
                                 deadline_s=60.0))
        plan = ServeFaultPlan(stall_steps=(2,), stall_s=120.0)
        with plan.install(eng):
            done = eng.run_until_drained()
        c = done[rid]
        assert c.finish_reason == "deadline"
        assert 0 < len(c.tokens) < 8       # partial progress returned
        m = eng.metrics()
        assert m.deadline_expired == 1 and m.injected_stalls == 1
        assert eng.pool.used_pages == 0


class TestDegradation:
    def test_kernel_failure_degrades_with_token_parity(self):
        cfg, params, sc = _mk(max_seq=48, page_size=4, max_new_tokens=6,
                              prefill_chunk=4)
        prompt = _prompt(6, cfg.vocab_size)
        clean_eng = Engine(cfg, params, sc)
        rid = clean_eng.submit(Request(prompt=prompt))
        clean = clean_eng.run_until_drained()[rid].tokens

        eng = Engine(cfg, params, sc)
        rid = eng.submit(Request(prompt=prompt))
        # dispatch 1 = first prefill chunk, dispatch 4 = a decode step
        with inject_paged_kernel_failure(fail_on=(1, 4)) as state:
            done = eng.run_until_drained()
        assert state["failed"] == 2
        m = eng.metrics()
        assert m.degraded_steps == 2
        assert done[rid].finish_reason == "length"
        np.testing.assert_array_equal(done[rid].tokens, clean)

    def test_lost_pools_recompute_with_token_parity(self):
        """A decode whose returned pools are dropped leaves the engine with
        the donated, deleted ones: the next step makes new pools and every
        active request recomputes its context, with the clean tokens."""
        cfg, params, sc = _mk(max_seq=48, page_size=4, max_new_tokens=6,
                              prefill_chunk=4)
        prompts = [_prompt(6, cfg.vocab_size, seed=s) for s in (1, 2)]
        clean_eng = Engine(cfg, params, sc)
        rids = [clean_eng.submit(Request(prompt=p)) for p in prompts]
        clean = clean_eng.run_until_drained()

        eng = Engine(cfg, params, sc)
        rids = [eng.submit(Request(prompt=p)) for p in prompts]
        decode = eng._decode

        def drop_pools_once(pr, state, tokens):
            logits, ok, new = decode(pr, state, tokens)
            if eng.decode_steps == 1:
                new = new._replace(pools=state.pools)
            return logits, ok, new

        eng._decode = drop_pools_once
        done = eng.run_until_drained()
        m = eng.metrics()
        assert m.degraded_steps == 1 and m.preempted == 2
        for r in rids:
            assert done[r].finish_reason == "length"
            np.testing.assert_array_equal(done[r].tokens, clean[r].tokens)
        assert eng.pool.used_pages == 0

    def test_genuine_nan_logits_retire_not_crash(self):
        cfg, params, sc = _mk(max_seq=32, page_size=4, max_new_tokens=4)
        # Corrupt one embedding row; with tied embeddings every logit row
        # grows a NaN column, so the health tap must fire at prefill.
        bad = dict(params)
        emb = np.array(bad["embed"], np.float32)
        emb[0, :] = np.nan
        bad["embed"] = jax.numpy.asarray(emb)
        eng = Engine(cfg, bad, sc)
        rid = eng.submit(Request(prompt=_prompt(4, cfg.vocab_size)))
        done = eng.run_until_drained()
        assert done[rid].finish_reason == "nan"
        assert len(done[rid].tokens) == 0
        assert eng.metrics().nan_retired == 1
        assert eng.pool.used_pages == 0

    def test_injected_poison_isolates_one_request(self):
        cfg, params, sc = _mk(max_seq=48, page_size=4, max_new_tokens=6)
        prompts = [_prompt(5, cfg.vocab_size, seed=s) for s in (1, 2)]
        clean_eng = Engine(cfg, params, sc)
        crids = [clean_eng.submit(Request(prompt=p)) for p in prompts]
        clean = clean_eng.run_until_drained()

        eng = Engine(cfg, params, sc)
        rids = [eng.submit(Request(prompt=p)) for p in prompts]
        plan = ServeFaultPlan(poison_rids=(rids[1],), poison_after=2)
        with plan.install(eng):
            done = eng.run_until_drained()
        # the clean slot never notices its neighbour's poisoning
        assert done[rids[0]].finish_reason == "length"
        np.testing.assert_array_equal(done[rids[0]].tokens,
                                      clean[crids[0]].tokens)
        poisoned = done[rids[1]]
        assert poisoned.finish_reason == "nan"
        assert len(poisoned.tokens) == 2
        np.testing.assert_array_equal(poisoned.tokens,
                                      clean[crids[1]].tokens[:2])
        m = eng.metrics()
        assert m.nan_retired == 1 and m.injected_poison == 1


class TestLivelock:
    def test_wedged_pool_raises_diagnosable_livelock(self):
        cfg, params, sc = _mk(max_seq=32, page_size=4, pool_pages=5,
                              max_new_tokens=4, livelock_patience=4)
        eng = Engine(cfg, params, sc)
        # Hold every free page permanently: the queued request can never
        # admit, so the drain loop must back off and then diagnose.
        held = eng.pool.reserve(eng.pool.capacity)
        rid = eng.submit(Request(prompt=_prompt(4, cfg.vocab_size)))
        with pytest.raises(LivelockError) as ei:
            eng.run_until_drained()
        err = ei.value
        assert err.queued_rids == (rid,)
        assert err.metrics.livelock_backoffs == 4
        assert err.metrics.free_pages == 0
        for needle in ("free_pages=0", f"queue=[{rid}]", "slot_rids"):
            assert needle in str(err)
        assert isinstance(err, RuntimeError)   # old broad handlers still fire
        eng.pool.unreserve(held)

    def test_transient_pressure_recovers_without_error(self):
        cfg, params, sc = _mk(max_seq=32, page_size=4, pool_pages=5,
                              max_new_tokens=4, livelock_patience=12)
        eng = Engine(cfg, params, sc)
        # Squeeze the whole pool for a few steps, then release: backoff
        # must bridge the window and the request must still complete.
        plan = ServeFaultPlan(squeeze_window=(0, 4),
                              squeeze_pages=eng.pool.capacity)
        rid = eng.submit(Request(prompt=_prompt(4, cfg.vocab_size)))
        with plan.install(eng):
            done = eng.run_until_drained()
        assert done[rid].finish_reason == "length"
        m = eng.metrics()
        assert m.livelock_backoffs >= 1
        assert eng.pool.used_pages == 0


class TestWarnOnce:
    def test_truncation_warns_once_but_counts_every_time(self):
        cfg, params, sc = _mk(max_seq=8, page_size=4, max_new_tokens=32)
        eng = Engine(cfg, params, sc)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            eng.submit(Request(prompt=_prompt(4, cfg.vocab_size)))
            eng.submit(Request(prompt=_prompt(4, cfg.vocab_size)))
        truncs = [x for x in w if "truncating" in str(x.message)]
        assert len(truncs) == 1
        assert eng.counters.truncated_max_new == 2
        assert eng.counters.warned_codes == ("truncate_max_new",)


class TestChaos:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_faults_preserve_scheduler_invariants(self, seed):
        """Seeded random workload + random fault plan on a near-capacity
        pool: every scheduler step upholds the ownership invariants, every
        accepted request completes, and the pool drains to zero."""
        rng = np.random.default_rng(seed)
        cfg, params, sc = _mk(max_seq=32, page_size=4, max_slots=3,
                              pool_pages=11, max_new_tokens=5,
                              prefill_chunk=4)
        eng = Engine(cfg, params, sc)
        n_req = 5
        prompts = [rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(3, 10)))
                   for _ in range(n_req)]
        plan = ServeFaultPlan(
            kernel_fail_steps=tuple(
                int(x) for x in rng.choice(12, size=2, replace=False)),
            prefill_fail_chunks=(int(rng.integers(0, 4)),),
            poison_rids=(int(rng.integers(0, n_req)),),
            poison_after=int(rng.integers(1, 4)),
            squeeze_window=(1, 5),
            squeeze_pages=int(rng.integers(0, 5)),
        )
        with plan.install(eng):
            rids = [eng.submit(Request(prompt=p)) for p in prompts]
            assert all(isinstance(r, int) for r in rids)
            steps = 0
            while eng.scheduler.queue or eng.scheduler.active_slots():
                eng.step()
                _invariants(eng)
                steps += 1
                assert steps < 200, "chaos run failed to drain"
        done = eng.completions()
        assert set(done) == set(rids)
        assert all(c.finish_reason in ("eos", "length", "nan")
                   for c in done.values())
        assert eng.pool.used_pages == 0
        assert eng.pool.alloc_count == eng.pool.free_count
        m = eng.metrics()
        assert m.degraded_steps >= 1       # at least one injection landed

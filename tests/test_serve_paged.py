"""Paged serving fast path (PR 9): kernel parity, engine parity vs the
legacy loop, request API, and scheduler/pool invariants.

The legacy token-by-token batch loop (ServeConfig(paged=False)) is the
oracle throughout: same params, same greedy sampling, dense per-request
caches — the paged path must reproduce its tokens exactly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.kernels.paged_attention import (paged_attention, paged_attention_ref,
                                           paged_fits, pool_shape, pool_write)
from repro.serve import Engine, Request, ServeConfig


def _rand_pool_case(key, *, b, kv, rep, hd, page, max_pages, pool_dtype):
    """Random pool + per-row distinct page tables + ragged lengths
    (one full-page row, one mid-page row, one empty row when b >= 3)."""
    k1, k2 = jax.random.split(key)
    n_pages = b * max_pages + 1
    pool = jax.random.normal(k1, pool_shape(n_pages, page, kv, hd)).astype(pool_dtype)
    table = (1 + np.arange(b * max_pages, dtype=np.int32)).reshape(b, max_pages)
    lengths = np.zeros((b,), np.int32)
    lengths[0] = max_pages * page            # every page full
    if b > 1:
        lengths[1] = page + 1                # ragged: one token into page 1
    # rows >= 2 stay at 0: inactive, must come out all-zero
    return k2, pool, jnp.asarray(table), jnp.asarray(lengths)


class TestPagedKernel:
    @pytest.mark.parametrize("page", [4, 8])
    @pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
    def test_decode_matches_dense_ref(self, page, pool_dtype):
        key = jax.random.PRNGKey(0)
        key, pool, table, lengths = _rand_pool_case(
            key, b=3, kv=2, rep=2, hd=8, page=page, max_pages=3,
            pool_dtype=pool_dtype)
        q = jax.random.normal(key, (3, 1, 4, 8), jnp.float32)
        got = paged_attention(q, pool, table, lengths)
        want = paged_attention_ref(q, pool, table, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
        assert not np.asarray(got[2]).any()   # inactive row is exact zeros

    @pytest.mark.parametrize("page", [4, 8])
    def test_chunk_matches_dense_ref(self, page):
        key = jax.random.PRNGKey(1)
        key, pool, table, lengths = _rand_pool_case(
            key, b=2, kv=2, rep=2, hd=8, page=page, max_pages=3,
            pool_dtype=jnp.float32)
        q = jax.random.normal(key, (2, 4, 4, 8), jnp.float32)
        got = paged_attention(q, pool, table, lengths)
        want = paged_attention_ref(q, pool, table, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)

    def test_chunk_rows_equal_per_token_decode(self):
        """A C-token chunk must produce exactly what C successive one-token
        decode calls at growing lengths produce — the chunked-prefill
        correctness contract."""
        c, page = 4, 4
        key = jax.random.PRNGKey(2)
        key, pool, table, _ = _rand_pool_case(
            key, b=1, kv=2, rep=2, hd=8, page=page, max_pages=3,
            pool_dtype=jnp.float32)
        length = 2 * page + 3                 # ragged final page
        q = jax.random.normal(key, (1, c, 4, 8), jnp.float32)
        chunk = paged_attention(q, pool, table, jnp.asarray([length], jnp.int32))
        for i in range(c):
            li = length - c + 1 + i           # query i sits at position li - 1
            tok = paged_attention(q[:, i:i + 1], pool, table,
                                  jnp.asarray([li], jnp.int32))
            np.testing.assert_allclose(np.asarray(chunk[:, i]),
                                       np.asarray(tok[:, 0]),
                                       atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("layer", [0, 2])
    def test_pool_write_touches_only_its_layer(self, layer):
        """K/V written through a table offset to ``layer`` of a stacked pool
        land in that layer's pages at (page, kv head, slot in page) as K
        then V on the minor dim; nothing else changes but page 0, where the
        dead writes go and which no live table entry of any layer reaches."""
        n_layers, n_pages, page, kv, hd = 3, 7, 4, 2, 8
        pool = jax.random.normal(jax.random.PRNGKey(3),
                                 pool_shape(n_layers * n_pages, page, kv, hd))
        table = np.array([[1, 2, 0], [3, 4, 5]], np.int32)
        rows = np.array([0, 0, 1, 1, 1], np.int32)
        positions = np.array([2, 5, 0, 9, 11], np.int32)
        valid = np.array([True, False, True, True, False])
        k, v = jax.random.normal(jax.random.PRNGKey(4), (2, 5, kv, hd))
        out = np.asarray(pool_write(pool, jnp.asarray(table + layer * n_pages),
                                    jnp.asarray(rows), jnp.asarray(positions),
                                    jnp.asarray(valid), k, v))
        want, written = np.array(pool), set()
        for i in np.flatnonzero(valid):
            pg = layer * n_pages + table[rows[i], positions[i] // page]
            want[pg, :, positions[i] % page] = np.concatenate([k[i], v[i]], -1)
            written.add(pg)
        np.testing.assert_array_equal(out[1:], want[1:])
        changed = set(np.flatnonzero((out != np.asarray(pool)).any(axis=(1, 2, 3))))
        live = {m * n_pages + t for m in range(n_layers) for t in table[table != 0]}
        assert changed == written | {0} and 0 not in live
        assert all(layer * n_pages < pg < (layer + 1) * n_pages for pg in written)

    def test_fit_gate_charges_padded_stats(self):
        """The softmax stats (KV, C * rep, 1) take a full 128-lane tile each
        on the TPU. With 64 query heads over 8 kv heads at head_dim 128 they
        are as large as the q block, so a 128-token chunk no longer fits
        VMEM_BUDGET while a 32-token one does."""
        h, kv, hd, page = 64, 8, 128, 16
        assert paged_fits(32, h, hd, page, kv)
        assert not paged_fits(128, h, hd, page, kv)
        q = jnp.zeros((1, 128, h, hd), jnp.float32)
        pool = jnp.zeros(pool_shape(3, page, kv, hd), jnp.float32)
        table = jnp.asarray([[1, 2]], jnp.int32)
        lengths = jnp.asarray([2 * page], jnp.int32)
        jaxpr = jax.make_jaxpr(paged_attention)(q, pool, table, lengths)
        assert "pallas_call" not in str(jaxpr)    # rejected -> reference


def _mk(arch="gpt_small", **sc_kw):
    cfg = get_reduced(arch)
    params, _ = cfg.init(jax.random.PRNGKey(0))
    return cfg, params, ServeConfig(**sc_kw)


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


class TestEngineParity:
    @pytest.mark.parametrize("arch", ["gpt_small", "smollm_135m"])
    @pytest.mark.parametrize("page_size", [4, 16])
    def test_paged_matches_legacy_greedy(self, arch, page_size):
        cfg, params, _ = _mk(arch)
        kw = dict(max_new_tokens=8, max_seq=32, page_size=page_size)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 5), 0,
                                     cfg.vocab_size)
        paged = Engine(cfg, params, ServeConfig(**kw)).generate(prompts)
        legacy = Engine(cfg, params, ServeConfig(paged=False, **kw)).generate(prompts)
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(legacy))

    def test_step_donates_the_pools(self):
        """Each program writes the pools it is given in place: after one
        engine step (three prefills and a decode) those arrays are deleted,
        and the engine goes on to serve the legacy loop's greedy tokens."""
        cfg, params, _ = _mk("smollm_135m")
        kw = dict(max_new_tokens=8, max_seq=32, page_size=4)
        prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 5),
                                                0, cfg.vocab_size))
        eng = Engine(cfg, params, ServeConfig(**kw))
        rids = [eng.submit(Request(prompt=p)) for p in prompts]
        given = list(eng._device_pools().values())
        eng.step()
        assert eng.prefill_chunks == 3 and eng.decode_steps == 1
        assert all(p.is_deleted() for p in given)
        assert not any(p.is_deleted() for p in eng._device_pools().values())
        done = eng.run_until_drained()
        served = np.stack([np.concatenate([prompts[i], done[r].tokens])
                           for i, r in enumerate(rids)])
        legacy = Engine(cfg, params, ServeConfig(paged=False, **kw)).generate(prompts)
        np.testing.assert_array_equal(served, np.asarray(legacy))

    def test_bf16_pool_matches_bf16_legacy_cache(self):
        cfg = dataclasses.replace(get_reduced("gpt_small"),
                                  dtype=jnp.bfloat16)
        params, _ = cfg.init(jax.random.PRNGKey(0))
        kw = dict(max_new_tokens=6, max_seq=32, page_size=8)
        prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 4), 0,
                                     cfg.vocab_size)
        paged = Engine(cfg, params, ServeConfig(**kw)).generate(prompts)
        legacy = Engine(cfg, params, ServeConfig(paged=False, **kw)).generate(prompts)
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(legacy))

    def test_chunked_prefill_matches_token_by_token(self):
        """prefill_chunk=1 degenerates to token-by-token prefill; larger
        chunks must emit identical tokens in ceil(S/C) prefill steps."""
        cfg, params, _ = _mk("gpt_small")
        prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                                     cfg.vocab_size)
        outs, chunks = [], []
        for c in (1, 4, 8):
            eng = Engine(cfg, params, ServeConfig(
                max_new_tokens=4, max_seq=32, prefill_chunk=c))
            outs.append(np.asarray(eng.generate(prompts)))
            chunks.append(eng.prefill_chunks)
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])
        assert chunks[0] == 2 * 12            # token-by-token
        assert chunks[1] == 2 * 3             # ceil(12/4)
        assert chunks[2] == 2 * 2             # ceil(12/8)


class TestRequestAPI:
    def test_submit_run_until_drained(self):
        cfg, params, sc = _mk(max_seq=32, max_new_tokens=16)
        eng = Engine(cfg, params, sc)
        prompt = np.array([1, 2, 3, 4], np.int32)
        r_short = eng.submit(Request(prompt=prompt, max_new_tokens=2))
        r_long = eng.submit(Request(prompt=prompt, max_new_tokens=5))
        done = eng.run_until_drained()
        assert set(done) == {r_short, r_long}
        assert done[r_short].finish_reason == "length"
        assert len(done[r_short].tokens) == 2
        assert len(done[r_long].tokens) == 5
        # same prompt, same greedy -> the short completion is a prefix
        np.testing.assert_array_equal(done[r_short].tokens,
                                      done[r_long].tokens[:2])
        for c in done.values():
            assert c.ttft_s is not None and 0 <= c.ttft_s <= c.wall_s
            np.testing.assert_array_equal(c.prompt, prompt)

    def test_per_request_seed_reproducible(self):
        cfg, params, sc = _mk(max_seq=32, max_new_tokens=6)
        prompt = np.array([5, 6, 7], np.int32)

        def sample(seed):
            eng = Engine(cfg, params, ServeConfig(max_seq=32))
            rid = eng.submit(Request(prompt=prompt, max_new_tokens=6,
                                     temperature=1.0, seed=seed))
            return eng.run_until_drained()[rid].tokens

        np.testing.assert_array_equal(sample(11), sample(11))

    def test_serveconfig_default_not_shared(self):
        """Engine() used to share one mutable ServeConfig() instance across
        every engine constructed without an explicit config."""
        cfg, params, _ = _mk()
        e1 = Engine(cfg, params)
        e1.sc.max_seq = 7
        assert Engine(cfg, params).sc.max_seq == 512

    def test_request_exceeding_pool_rejected(self):
        cfg, params, _ = _mk()
        eng = Engine(cfg, params, ServeConfig(
            max_seq=64, max_new_tokens=32, page_size=4, pool_pages=4))
        with pytest.raises(ValueError, match="pages"):
            eng.submit(Request(prompt=np.arange(20, dtype=np.int32)))

    def test_request_api_unavailable_on_legacy_arch(self):
        cfg, params, _ = _mk("falcon_mamba_7b")
        eng = Engine(cfg, params, ServeConfig(max_seq=32))
        with pytest.raises(NotImplementedError, match="generate"):
            eng.submit(Request(prompt=np.array([1, 2], np.int32)))


class TestScheduler:
    def test_no_leak_after_drain_with_queueing(self):
        """More requests than slots: everything completes, no page stays
        allocated, invariants hold between steps."""
        cfg, params, _ = _mk()
        eng = Engine(cfg, params, ServeConfig(
            max_seq=32, max_new_tokens=3, max_slots=2, page_size=8))
        prompt = np.array([1, 2, 3], np.int32)
        rids = [eng.submit(Request(prompt=prompt)) for _ in range(5)]
        while eng.scheduler.queue or eng.scheduler.active_slots():
            eng.step()
            _invariants(eng)
        done = eng.completions()
        assert set(done) == set(rids)
        assert eng.pool.used_pages == 0
        assert eng.scheduler.admitted == 5 and eng.scheduler.retired == 5
        base = done[rids[0]].tokens
        for rid in rids[1:]:                  # identical work -> identical tokens
            np.testing.assert_array_equal(done[rid].tokens, base)

    def test_eos_retirement_releases_pages_for_late_admits(self):
        """The pool only holds one request's pages at a time: later requests
        can be admitted *only* because retirement frees pages immediately
        (releasing at batch drain would deadlock this workload)."""
        cfg, params, _ = _mk()
        eng = Engine(cfg, params, ServeConfig(
            max_seq=16, max_new_tokens=4, max_slots=4, page_size=4,
            pool_pages=5))                    # capacity 4 pages
        prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
        rids = [eng.submit(Request(prompt=prompt)) for _ in range(4)]
        # prompt(8) = 2 pages; +4 new tokens -> 3 pages: two requests cannot
        # coexist (2 * 2 prompt pages + headroom > 4), so progress requires
        # mid-batch page recycling
        done = eng.run_until_drained()
        assert set(done) == set(rids)
        assert all(len(c.tokens) == 4 for c in done.values())
        assert eng.pool.used_pages == 0
        assert eng.pool.high_water <= 3
        assert eng.pool.free_count == eng.pool.alloc_count

    def test_preemption_recompute_matches_solo_run(self):
        """Pool exhaustion mid-decode preempts the youngest request; after
        recompute its tokens must match an uncontended solo run exactly."""
        cfg, params, _ = _mk()
        sc = ServeConfig(max_seq=16, max_new_tokens=6, max_slots=2,
                         page_size=2, pool_pages=8)   # capacity 7
        eng = Engine(cfg, params, sc)
        p0 = np.array([1, 2, 3, 4], np.int32)
        p1 = np.array([9, 8, 7, 6], np.int32)
        r0 = eng.submit(Request(prompt=p0))
        r1 = eng.submit(Request(prompt=p1))
        done = eng.run_until_drained()
        assert eng.scheduler.preempted >= 1
        assert done[r1].preemptions >= 1
        assert eng.pool.used_pages == 0
        for rid, prompt in ((r0, p0), (r1, p1)):
            solo = Engine(cfg, params, sc)
            srid = solo.submit(Request(prompt=prompt))
            np.testing.assert_array_equal(
                done[rid].tokens, solo.run_until_drained()[srid].tokens)

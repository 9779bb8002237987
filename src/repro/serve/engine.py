"""Request-level serving engine: paged fast path + legacy fallback loop.

The public surface is request-oriented:

    eng = Engine(cfg, params, ServeConfig(max_seq=256))
    rid = eng.submit(Request(prompt=tokens, max_new_tokens=64, eos_id=2))
    completions = eng.run_until_drained()       # {rid: Completion}

``submit`` enqueues (or returns :class:`~repro.serve.metrics.Rejected`
under admission control); ``step`` runs one scheduler iteration (expire
deadlines, admit queued requests into free slots, chunk-prefill them, one
batched paged decode for every active slot, retire finished ones);
``run_until_drained`` loops step until nothing is queued or active,
backing off deterministically on no-progress before raising a diagnosable
:class:`~repro.serve.metrics.LivelockError`. Per-request sampling
(temperature, seed) and SLOs (deadline_s, priority) live on the
:class:`Request`; :class:`ServeConfig` keeps the engine-wide geometry
(max_seq, page/pool sizing, slot count, wall budget, admission
watermarks).

Fault handling (see ``repro.serve`` package docs for the full ladder): a
failing paged-attention launch degrades that one step to the dense
reference path; a non-finite logit tap retires the poisoned slot with
``reason="nan"`` instead of sampling garbage; every such decision lands in
:class:`~repro.serve.metrics.ServeCounters` (snapshot via
:meth:`Engine.metrics`) rather than a hot-loop warning.

Architectures outside the paged fast path's coverage (SSM/hybrid mixers,
int8 KV) fall back to the legacy batch loop transparently;
:meth:`Engine.generate` is kept as a thin compatibility wrapper over the
request API (deprecated for new code — it hides per-request raggedness by
padding).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import injection
from ..models import transformer
from .faults import CLOCK_POINT, KERNEL_POINT, LOGITS_POINT, STEP_POINT
from .kvpool import KVPool
from .metrics import LivelockError, Rejected, ServeCounters, ServeMetrics
from .scheduler import Scheduler


@dataclasses.dataclass
class ServeConfig:
    """Engine-wide serving geometry. ``temperature``/``seed`` remain only
    as defaults for requests that don't set their own (the pre-request-API
    surface); new code should put sampling on the :class:`Request`."""
    max_new_tokens: int = 32
    max_seq: int = 512
    temperature: float = 0.0   # deprecated default; see Request.temperature
    seed: int = 0              # deprecated default; see Request.seed
    # Per-request wall-clock budget (seconds). A pathological decode loop —
    # a recompile storm, an overloaded host — degrades to a *truncated*
    # response (finish_reason='budget', counted) instead of hanging the
    # caller. None = no cap.
    max_wall_s: Optional[float] = None
    # Paged fast path geometry
    page_size: int = 16        # token positions per KV page
    pool_pages: Optional[int] = None   # None -> max_slots * pages(max_seq) + 1
    max_slots: int = 8         # fixed decode batch width
    prefill_chunk: int = 8     # prompt tokens per chunked-prefill step
    # None -> auto (paged when the arch supports it); False forces the
    # legacy token-by-token loop (the parity oracle in tests)
    paged: Optional[bool] = None
    # --- admission control / backpressure (None = accept everything) ---
    # submit() returns Rejected('queue_full') once this many requests are
    # queued (admitted-and-running requests don't count).
    max_queue: Optional[int] = None
    # submit() returns Rejected('pool_pressure') when the projected page
    # demand of everything queued + active + the new request exceeds this
    # fraction of pool capacity. 1.0 = reject only guaranteed-thrash loads;
    # lower values keep preemption-churn headroom.
    admit_watermark: Optional[float] = None
    # --- livelock handling -------------------------------------------
    # Consecutive no-progress scheduler steps tolerated (with backoff)
    # before run_until_drained raises LivelockError. Must exceed any
    # transient external pressure window (e.g. a chaos-drill squeeze).
    livelock_patience: int = 16
    # Admissions frozen for this many steps at the start of a no-progress
    # burst — stops admit->preempt churn from masking a wedged pool.
    backoff_freeze_steps: int = 2


@dataclasses.dataclass
class Request:
    """One generation request. ``temperature``/``seed`` default to the
    engine's ServeConfig when None. ``deadline_s`` is an SLO relative to
    submission: once exceeded the request retires with
    ``finish_reason='deadline'`` (whatever was generated so far) instead of
    occupying a slot; queued requests past deadline are dropped without
    ever touching the device. Higher ``priority`` admits first (FIFO
    within a level)."""
    prompt: object                       # (S,) int tokens (list/np/jnp)
    max_new_tokens: Optional[int] = None
    eos_id: Optional[int] = None
    temperature: Optional[float] = None
    seed: Optional[int] = None
    deadline_s: Optional[float] = None
    priority: int = 0


@dataclasses.dataclass
class Completion:
    """Result of one request. ``tokens`` holds only the *generated* suffix
    (including the eos token when one was emitted). ``finish_reason``:
    'eos' | 'length' | 'budget' | 'deadline' | 'nan'. ``queue_s`` is the
    time spent queued for a slot, from submission to admission, counting
    again after every preemption."""
    id: int
    prompt: np.ndarray
    tokens: np.ndarray
    finish_reason: str
    ttft_s: Optional[float]              # submit -> first token
    wall_s: float                        # submit -> retirement
    preemptions: int = 0
    tpot_s: Optional[float] = None       # mean time per token after the first
    queue_s: float = 0.0                 # queued for a slot (all spells)


class _ReqState:
    """Host-side decode state for one in-flight request."""

    __slots__ = ("rid", "request", "prompt", "max_new", "generated",
                 "ctx_len", "t_submit", "t_first", "preemptions",
                 "deadline_s", "priority", "t_queued", "queue_s")

    def __init__(self, rid: int, request: Request, prompt: np.ndarray,
                 max_new: int, t_submit: float):
        self.rid = rid
        self.request = request
        self.prompt = prompt
        self.max_new = max_new
        self.generated: List[int] = []
        self.ctx_len = 0          # KV positions written on device
        self.t_submit = t_submit
        self.t_first: Optional[float] = None
        self.preemptions = 0
        self.deadline_s = request.deadline_s
        self.priority = request.priority
        self.t_queued: Optional[float] = t_submit   # None while in a slot
        self.queue_s = 0.0

    def leave_queue(self, now: float) -> None:
        self.queue_s += now - self.t_queued
        self.t_queued = None

    def ctx_tokens(self) -> np.ndarray:
        """Tokens whose KV must exist before decoding can continue — the
        prompt plus everything generated so far (preemption recompute
        prefills this whole extended prompt, losing no sampled token)."""
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])


class Engine:
    def __init__(self, model_cfg, params, sc: Optional[ServeConfig] = None):
        self.cfg = model_cfg
        self.params = params
        self.sc = sc if sc is not None else ServeConfig()
        self._paged = (self.sc.paged if self.sc.paged is not None
                       else transformer.supports_paged(model_cfg))
        self._next_rid = 0
        self._reqs: Dict[int, _ReqState] = {}
        self._done: Dict[int, Completion] = {}
        self.counters = ServeCounters()
        self.decode_steps = 0
        self.decode_rows = 0            # active rows, summed over decode calls
        self.decode_rows_computed = 0   # rows each decode call ran (max_slots)
        self.prefill_chunks = 0
        self.tokens_out = 0
        self.sched_steps = 0        # scheduler iterations, incl. no-progress
        self._completed_total = 0
        self._no_progress = 0       # consecutive no-progress steps
        self._admit_freeze = 0      # steps with admissions suspended
        # Virtual-clock skew (seconds) added to every monotonic read — the
        # deterministic stall injection advances it so deadline logic can be
        # driven without sleeping in CI.
        self._clock_skew = 0.0
        if self._paged:
            p = self.sc.page_size
            max_pages = -(-self.sc.max_seq // p)
            n_pages = (self.sc.pool_pages if self.sc.pool_pages is not None
                       else self.sc.max_slots * max_pages + 1)
            self.pool = KVPool(n_pages, p)
            self.scheduler = Scheduler(self.sc.max_slots, max_pages, self.pool)
            self._pools = None     # device pools, created on first use
            # Named functions, so each program is an XLA module of its own
            # name in a profile (``jit_paged_decode``, ...).
            def paged_decode(pr, st, t):
                return transformer.paged_decode_step(model_cfg, pr, st, t)

            def paged_decode_ref(pr, st, t):
                return transformer.paged_decode_step(model_cfg, pr, st, t,
                                                     attn_impl="ref")

            def paged_prefill(pr, pools, row, pos0, nv, tok):
                return transformer.paged_prefill_chunk(model_cfg, pr, pools,
                                                       row, pos0, nv, tok)

            def paged_prefill_ref(pr, pools, row, pos0, nv, tok):
                return transformer.paged_prefill_chunk(model_cfg, pr, pools,
                                                       row, pos0, nv, tok,
                                                       attn_impl="ref")

            # The pools (argument 1, inside the PagedState for decode) are
            # donated: each call writes them in place and self._pools takes
            # what it returns. A failed launch raises before anything is
            # donated, so the fallback still gets live pools.
            self._decode = jax.jit(paged_decode, donate_argnums=(1,))
            self._decode_fallback = jax.jit(paged_decode_ref,
                                            donate_argnums=(1,))
            self._prefill = jax.jit(paged_prefill, donate_argnums=(1,))
            self._prefill_fallback = jax.jit(paged_prefill_ref,
                                             donate_argnums=(1,))
        else:
            def dense_decode(pr, c, t):
                return transformer.decode_step(model_cfg, pr, c, t)

            self._decode = jax.jit(dense_decode)

    def _now(self) -> float:
        return time.monotonic() + self._clock_skew

    # ------------------------------------------------------------------
    # Request API
    # ------------------------------------------------------------------

    def submit(self, request: Request) -> Union[int, Rejected]:
        """Validate, admission-check and enqueue one request; returns its id
        or a :class:`Rejected` verdict (backpressure — never an exception).
        Raises ValueError only for requests that could never run: a prompt
        that cannot fit ``max_seq``, or a footprint exceeding the whole
        page pool even alone."""
        if not self._paged:
            raise NotImplementedError(
                f"the request API needs the paged fast path, which does not "
                f"cover arch '{self.cfg.name}' — use generate()")
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        n_prompt = prompt.shape[0]
        budget = self.sc.max_seq - n_prompt
        if budget <= 0:
            raise ValueError(
                f"prompt length {n_prompt} leaves no room to generate within "
                f"max_seq={self.sc.max_seq}")
        max_new = (request.max_new_tokens if request.max_new_tokens is not None
                   else self.sc.max_new_tokens)
        if max_new > budget:
            self.counters.truncated_max_new += 1
            self.counters.warn_once(
                "truncate_max_new",
                f"truncating max_new_tokens {max_new} -> {budget}: prompt "
                f"length {n_prompt} + requested tokens would overrun the "
                f"max_seq={self.sc.max_seq} cache (counted in "
                f"ServeMetrics.truncated_max_new; warning not repeated)")
            max_new = budget
        need = self.pool.pages_for(n_prompt + max_new)
        if need > self.pool.capacity:
            raise ValueError(
                f"request needs {need} KV pages but the pool holds only "
                f"{self.pool.capacity} — raise pool_pages or shrink the "
                f"request")
        sched = self.scheduler
        if (self.sc.max_queue is not None
                and len(sched.queue) >= self.sc.max_queue):
            self.counters.rejected_queue += 1
            return Rejected(reason="queue_full",
                            queue_depth=len(sched.queue),
                            projected_pages=need,
                            pool_capacity=self.pool.capacity)
        if self.sc.admit_watermark is not None:
            projected = self.pool.used_pages + self._queued_pages() + need
            if projected > self.sc.admit_watermark * self.pool.capacity:
                self.counters.rejected_pool += 1
                return Rejected(reason="pool_pressure",
                                queue_depth=len(sched.queue),
                                projected_pages=projected,
                                pool_capacity=self.pool.capacity)
        rid = self._next_rid
        self._next_rid += 1
        self._reqs[rid] = _ReqState(rid, request, prompt, max_new,
                                    t_submit=self._now())
        sched.submit(rid, priority=request.priority)
        return rid

    def _queued_pages(self) -> int:
        """Projected lifetime page demand of everything still queued (each
        request's full prompt + generation footprint — recompute extensions
        never exceed it)."""
        return sum(
            self.pool.pages_for(self._reqs[rid].prompt.shape[0]
                                + self._reqs[rid].max_new)
            for rid in self.scheduler.queue)

    def step(self) -> None:
        """One scheduler iteration: expire deadlines, admit + prefill,
        grow/preempt, one batched decode, retire. In a profile the step and
        its phases are ``repro.serve.*`` spans: ``step`` around all of it,
        ``admit`` (``prefill`` inside it, one per admitted request),
        ``decode``, ``readback`` and ``sample``."""
        if not self._paged:
            raise NotImplementedError(
                f"the request API needs the paged fast path, which does not "
                f"cover arch '{self.cfg.name}' — use generate()")
        with jax.profiler.TraceAnnotation("repro.serve.step"):
            step_idx = self.sched_steps
            self.sched_steps += 1
            injection.fire(STEP_POINT, self, step_idx)
            skew = injection.fire(CLOCK_POINT, step_idx)
            if skew:
                self._clock_skew += float(skew)
                self.counters.injected_stalls += 1
            self._expire_deadlines()
            self._recover_lost_pools()
            if self._admit_freeze > 0:
                self._admit_freeze -= 1      # backoff: no admissions this step
            else:
                with jax.profiler.TraceAnnotation("repro.serve.admit"):
                    self._admit_queued()
            self._grow_or_preempt()
            active = self.scheduler.active_slots()
            if not active:
                return
            with jax.profiler.TraceAnnotation("repro.serve.decode"):
                logits, ok_dev = self._decode_active(active)
            with jax.profiler.TraceAnnotation("repro.serve.readback"):
                last = np.asarray(logits[:, -1].astype(jnp.float32))
                ok = np.asarray(ok_dev)
            with jax.profiler.TraceAnnotation("repro.serve.sample"):
                self._sample_active(active, last, ok)

    def _admit_queued(self) -> None:
        """Admit as many queue heads as slots and pages allow, prefilling
        each."""
        sched = self.scheduler
        while sched.queue:
            rid = sched.queue[0]
            st = self._reqs[rid]
            slot = sched.try_admit(rid, len(st.ctx_tokens()))
            if slot is None:
                break
            st.leave_queue(self._now())
            with jax.profiler.TraceAnnotation("repro.serve.prefill", rid=rid):
                self._prefill_into(slot, st)

    def _grow_or_preempt(self) -> None:
        """Make room for every active row's next write position, preempting
        the youngest other row when the pool is exhausted."""
        sched = self.scheduler
        ensured: List[int] = []
        for slot, rid in list(sched.active_slots()):
            if sched.slot_rid[slot] != rid:
                continue               # evicted by an earlier row's preempt
            st = self._reqs[rid]
            while True:
                if sched.ensure_capacity(slot, st.ctx_len):
                    ensured.append(slot)
                    break
                victim = sched.youngest_other(slot, tuple(ensured))
                self._preempt(victim if victim is not None else slot)
                if victim is None:
                    break              # self-preempted; skip decode this step

    def _decode_active(self, active):
        """Dispatch one fixed-shape decode over all ``max_slots`` rows, the
        active ones live. Returns the (logits, ok) device arrays."""
        n = self.sc.max_slots
        tokens = np.zeros((n, 1), np.int32)
        lengths = np.zeros((n,), np.int32)
        mask = np.zeros((n,), bool)
        for slot, rid in active:
            st = self._reqs[rid]
            tokens[slot, 0] = st.generated[-1]
            lengths[slot] = st.ctx_len
            mask[slot] = True
        state = transformer.PagedState(
            pools=self._device_pools(), table=jnp.asarray(self.scheduler.table),
            lengths=jnp.asarray(lengths), active=jnp.asarray(mask))
        logits, ok_dev, new_state = self._decode_call(state, jnp.asarray(tokens))
        self._pools = new_state.pools
        self.decode_steps += 1
        self.decode_rows += len(active)
        self.decode_rows_computed += n
        return logits, ok_dev

    def _sample_active(self, active, last: np.ndarray, ok: np.ndarray) -> None:
        """Sample each active row's next token from its logits row and retire
        the rows that finished (or whose logits were not finite)."""
        now = self._now()
        for slot, rid in active:
            st = self._reqs[rid]
            st.ctx_len += 1        # this step wrote generated[-1]'s KV
            poisoned = bool(injection.fire(
                LOGITS_POINT, rid, len(st.generated)))
            if poisoned:
                self.counters.injected_poison += 1
            if poisoned or not ok[slot]:
                self._retire_nan(slot, st)
                continue
            tok = self._sample_one(st, last[slot])
            st.generated.append(tok)
            self.tokens_out += 1
            eos = st.request.eos_id
            if eos is not None and tok == eos:
                self._retire(slot, st, "eos")
            elif len(st.generated) >= st.max_new:
                self._retire(slot, st, "length")
            elif (self.sc.max_wall_s is not None
                  and now - st.t_submit > self.sc.max_wall_s):
                self.counters.budget_truncated += 1
                self.counters.warn_once(
                    "wall_budget",
                    f"serve request {rid} exceeded wall-clock budget "
                    f"max_wall_s={self.sc.max_wall_s} after "
                    f"{len(st.generated)}/{st.max_new} tokens; returning "
                    f"truncated response (counted in "
                    f"ServeMetrics.budget_truncated; warning not "
                    f"repeated)")
                self._retire(slot, st, "budget")

    def run_until_drained(self) -> Dict[int, Completion]:
        """Step until every admitted request has retired; returns and
        clears the accumulated completions. On a no-progress step the
        engine backs off deterministically (freeze admissions, force-retire
        over-deadline slots); only after ``livelock_patience`` consecutive
        stuck steps does it raise :class:`LivelockError` carrying the full
        scheduler/pool counter snapshot."""
        sched = self.scheduler
        self._no_progress = 0
        while sched.queue or sched.active_slots():
            before = self._progress_sig()
            self.step()
            if self._progress_sig() == before:
                self._no_progress += 1
                self._backoff()
                if self._no_progress >= self.sc.livelock_patience:
                    raise LivelockError(self.metrics(), sched.slot_rid,
                                        tuple(sched.queue))
            else:
                self._no_progress = 0
        done, self._done = self._done, {}
        return done

    def completions(self) -> Dict[int, Completion]:
        """Completions retired so far (without draining the batch)."""
        done, self._done = self._done, {}
        return done

    def metrics(self) -> ServeMetrics:
        """One consistent snapshot of serving health: scheduler/pool gauges
        plus every fault/SLO counter. Cheap — no device sync."""
        c = self.counters
        if self._paged:
            sched, pool = self.scheduler, self.pool
            gauges = dict(queue_depth=len(sched.queue),
                          active_slots=len(sched.active_slots()),
                          free_pages=pool.free_pages,
                          used_pages=pool.used_pages,
                          page_high_water=pool.high_water,
                          pool_capacity=pool.capacity,
                          admitted=sched.admitted, retired=sched.retired,
                          preempted=sched.preempted)
        else:
            gauges = dict(queue_depth=0, active_slots=0, free_pages=0,
                          used_pages=0, page_high_water=0, pool_capacity=0,
                          admitted=0, retired=0, preempted=0)
        return ServeMetrics(
            sched_steps=self.sched_steps, decode_steps=self.decode_steps,
            decode_rows=self.decode_rows,
            decode_rows_computed=self.decode_rows_computed,
            prefill_chunks=self.prefill_chunks, tokens_out=self.tokens_out,
            degraded_steps=c.degraded_steps, nan_retired=c.nan_retired,
            deadline_expired=c.deadline_expired,
            budget_truncated=c.budget_truncated,
            truncated_max_new=c.truncated_max_new,
            rejected_queue=c.rejected_queue, rejected_pool=c.rejected_pool,
            livelock_backoffs=c.livelock_backoffs,
            injected_stalls=c.injected_stalls,
            injected_poison=c.injected_poison, **gauges)

    # ------------------------------------------------------------------
    # Progress / livelock handling
    # ------------------------------------------------------------------

    def _progress_sig(self) -> Tuple[int, ...]:
        sched = self.scheduler
        return (self.tokens_out, sched.admitted, sched.retired,
                sched.preempted, self._completed_total)

    def _backoff(self) -> None:
        """Deterministic no-progress backoff: count the round, force-retire
        anything past its deadline right now, and freeze admissions at the
        start of a burst (stops admit->preempt churn from hiding a wedged
        pool while transient pressure — e.g. a chaos squeeze — drains)."""
        self.counters.livelock_backoffs += 1
        self._expire_deadlines()
        if self._no_progress == 1:
            self._admit_freeze = self.sc.backoff_freeze_steps

    def _expire_deadlines(self) -> None:
        """Retire every request past its deadline: queued ones are dropped
        without touching the device; active ones give up their slot and
        pages immediately, returning whatever they generated."""
        now = self._now()
        sched = self.scheduler

        def expired(st: _ReqState) -> bool:
            return (st.deadline_s is not None
                    and now - st.t_submit > st.deadline_s)

        for rid in [r for r in sched.queue if expired(self._reqs[r])]:
            st = self._reqs[rid]
            sched.drop_queued(rid)
            self._count_deadline(st)
            self._finish(st, "deadline")
        for slot, rid in list(sched.active_slots()):
            st = self._reqs[rid]
            if expired(st):
                self._count_deadline(st)
                self._retire(slot, st, "deadline")

    def _count_deadline(self, st: _ReqState) -> None:
        self.counters.deadline_expired += 1
        self.counters.warn_once(
            "deadline",
            f"serve request {st.rid} exceeded its deadline_s="
            f"{st.deadline_s} after {len(st.generated)}/{st.max_new} "
            f"tokens; retiring with reason='deadline' (counted in "
            f"ServeMetrics.deadline_expired; warning not repeated)")

    # ------------------------------------------------------------------
    # Paged internals
    # ------------------------------------------------------------------

    def _pool_dtype(self):
        return jnp.float32 if self.cfg.dtype == jnp.float32 else jnp.bfloat16

    def _device_pools(self):
        if self._pools is None:
            self._pools = transformer.init_paged_pools(
                self.cfg, self.pool.n_pages, self.pool.page_size,
                self._pool_dtype())
        return self._pools

    def _recover_lost_pools(self) -> None:
        """The programs consume the pools they are given and return them
        updated. Pools found deleted mean a caller dropped what a call
        returned, and with it every active request's K/V: make new pools
        and requeue each active request to recompute its context on
        re-admission, as a preemption does. Counted as a degraded step."""
        if self._pools is None or not any(
                p.is_deleted() for p in self._pools.values()):
            return
        self._pools = None
        self.counters.degraded_steps += 1
        self.counters.warn_once(
            "pools_lost",
            "the paged KV pools were deleted without a replacement; "
            "recomputing every active request into new pools (counted in "
            "ServeMetrics.degraded_steps; warning not repeated)")
        for slot, _ in list(self.scheduler.active_slots()):
            self._preempt(slot)

    def _preempt(self, slot: int) -> None:
        """Requeue ``slot``'s request; it recomputes its context when
        re-admitted."""
        rid = self.scheduler.preempt(slot)
        self._reqs[rid].preemptions += 1
        self._reqs[rid].t_queued = self._now()

    def _decode_call(self, state, tokens):
        """Dispatch one batched decode, degrading to the dense reference
        attention for exactly this step when the kernel launch fails (an
        injected fault or a real trace/compile regression). Mirrors the
        fused optimizer's per-leaf ``_guarded`` ladder at step granularity."""
        try:
            injection.fire(KERNEL_POINT, "decode", self.decode_steps)
            return self._decode(self.params, state, tokens)
        except Exception as e:  # noqa: BLE001 — any kernel failure degrades
            self.counters.degraded_steps += 1
            self.counters.warn_once(
                "kernel_degraded",
                f"paged decode launch failed ({type(e).__name__}: {e}); "
                f"serving this step through the dense reference path "
                f"(counted in ServeMetrics.degraded_steps; warning not "
                f"repeated)")
            return self._decode_fallback(self.params, state, tokens)

    def _prefill_call(self, row, pos0, n_valid, buf):
        """Prefill-chunk dispatch with the same degradation ladder as
        :meth:`_decode_call` (chunks indexed globally across requests)."""
        try:
            injection.fire(KERNEL_POINT, "prefill", self.prefill_chunks)
            return self._prefill(self.params, self._device_pools(), row,
                                 pos0, n_valid, buf)
        except Exception as e:  # noqa: BLE001 — any kernel failure degrades
            self.counters.degraded_steps += 1
            self.counters.warn_once(
                "kernel_degraded",
                f"paged prefill launch failed ({type(e).__name__}: {e}); "
                f"serving this chunk through the dense reference path "
                f"(counted in ServeMetrics.degraded_steps; warning not "
                f"repeated)")
            return self._prefill_fallback(self.params, self._device_pools(),
                                          row, pos0, n_valid, buf)

    def _prefill_into(self, slot: int, st: _ReqState) -> None:
        """Chunk-prefill a freshly admitted request's whole known context
        (prompt + any pre-preemption tokens) and sample its next token."""
        ctx = st.ctx_tokens()
        n_ctx = ctx.shape[0]
        chunk = self.sc.prefill_chunk
        n_chunks = -(-n_ctx // chunk)
        row = jnp.asarray(self.scheduler.table[slot:slot + 1])
        logits = None
        ok_dev = None
        n_valid = chunk
        for k in range(n_chunks):
            lo = k * chunk
            n_valid = min(chunk, n_ctx - lo)
            buf = np.zeros((1, chunk), np.int32)
            buf[0, :n_valid] = ctx[lo:lo + n_valid]
            logits, ok_dev, pools = self._prefill_call(
                row, np.int32(lo), np.int32(n_valid), jnp.asarray(buf))
            self._pools = pools
            self.prefill_chunks += 1
            if (self.sc.max_wall_s is not None
                    and self._now() - st.t_submit > self.sc.max_wall_s):
                self.counters.budget_truncated += 1
                self.counters.warn_once(
                    "wall_budget",
                    f"serve request {st.rid} exceeded wall-clock budget "
                    f"max_wall_s={self.sc.max_wall_s} during prefill "
                    f"({k + 1}/{n_chunks} chunks); returning prompt only "
                    f"(counted in ServeMetrics.budget_truncated; warning "
                    f"not repeated)")
                st.ctx_len = lo + n_valid
                self._retire(slot, st, "budget")
                return
        st.ctx_len = n_ctx
        poisoned = bool(injection.fire(LOGITS_POINT, st.rid,
                                       len(st.generated)))
        if poisoned:
            self.counters.injected_poison += 1
        if poisoned or not bool(np.asarray(ok_dev)):
            self._retire_nan(slot, st)
            return
        row_logits = np.asarray(logits[0, n_valid - 1].astype(jnp.float32))
        tok = self._sample_one(st, row_logits)
        st.generated.append(tok)
        self.tokens_out += 1
        eos = st.request.eos_id
        if eos is not None and tok == eos:
            self._retire(slot, st, "eos")
        elif len(st.generated) >= st.max_new:
            self._retire(slot, st, "length")

    def _sample_one(self, st: _ReqState, logits_row: np.ndarray) -> int:
        if st.t_first is None:
            st.t_first = self._now()
        temp = (st.request.temperature if st.request.temperature is not None
                else self.sc.temperature)
        if temp <= 0.0:
            return int(np.argmax(logits_row))
        seed = st.request.seed if st.request.seed is not None else self.sc.seed
        # fold the token index into the request's key: resampling the same
        # index after a preemption recompute reproduces the same token
        key = jax.random.fold_in(jax.random.PRNGKey(seed), len(st.generated))
        return int(jax.random.categorical(
            key, jnp.asarray(logits_row, jnp.float32) / temp))

    def _retire_nan(self, slot: int, st: _ReqState) -> None:
        """Poisoned slot: skip sampling entirely (no garbage token escapes)
        and retire with whatever was generated before the poison."""
        self.counters.nan_retired += 1
        self.counters.warn_once(
            "nan_logits",
            f"non-finite logits for serve request {st.rid} after "
            f"{len(st.generated)} tokens; skipping sampling and retiring "
            f"with reason='nan' (counted in ServeMetrics.nan_retired; "
            f"warning not repeated)")
        self._retire(slot, st, "nan")

    def _retire(self, slot: int, st: _ReqState, reason: str) -> None:
        self.scheduler.retire(slot)
        self._finish(st, reason)

    def _finish(self, st: _ReqState, reason: str) -> None:
        """Build the Completion (a request dropped from the queue ends its
        queued spell here)."""
        now = self._now()
        if st.t_queued is not None:
            st.leave_queue(now)
        ttft = None if st.t_first is None else st.t_first - st.t_submit
        wall = now - st.t_submit
        tpot = None
        if ttft is not None and len(st.generated) > 1:
            tpot = (wall - ttft) / (len(st.generated) - 1)
        self._done[st.rid] = Completion(
            id=st.rid, prompt=st.prompt,
            tokens=np.asarray(st.generated, np.int32),
            finish_reason=reason, ttft_s=ttft, wall_s=wall,
            preemptions=st.preemptions, tpot_s=tpot, queue_s=st.queue_s)
        del self._reqs[st.rid]
        self._completed_total += 1

    # ------------------------------------------------------------------
    # Compatibility wrapper (pre-request-API surface)
    # ------------------------------------------------------------------

    def generate(self, prompts: jnp.ndarray, *, eos_id: Optional[int] = None) -> jnp.ndarray:
        """prompts: (B, S_prompt) int32 -> (B, S_prompt + new) tokens.

        Deprecated compatibility wrapper: submits one :class:`Request` per
        row and pads the ragged completions back into a rectangle (eos_id —
        or 0 — as filler), which is what the old batch loop produced. New
        code should use submit/step/run_until_drained directly.
        """
        if not self._paged:
            return self._generate_legacy(prompts, eos_id=eos_id)
        prompts = jnp.asarray(prompts)
        b, s_prompt = prompts.shape
        host_prompts = np.asarray(prompts)
        rids = []
        for i in range(b):
            rid = self.submit(Request(prompt=host_prompts[i], eos_id=eos_id))
            if isinstance(rid, Rejected):
                raise RuntimeError(
                    f"generate() row {i} rejected by admission control "
                    f"({rid.reason}) — the batch wrapper cannot shed load; "
                    f"use submit() directly under backpressure")
            rids.append(rid)
        done = self.run_until_drained()
        rows = [np.concatenate([host_prompts[i], done[rid].tokens])
                for i, rid in enumerate(rids)]
        width = max(len(r) for r in rows)
        fill = eos_id if eos_id is not None else 0
        out = np.full((b, width), fill, np.int32)
        for i, r in enumerate(rows):
            out[i, :len(r)] = r
        return jnp.asarray(out)

    # ------------------------------------------------------------------
    # Legacy batch loop (SSM/hybrid archs; paged=False parity oracle)
    # ------------------------------------------------------------------

    def _sample(self, logits: jnp.ndarray, key) -> jnp.ndarray:
        if self.sc.temperature <= 0.0:
            return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        probs = jax.nn.softmax(logits[:, -1].astype(jnp.float32) / self.sc.temperature, axis=-1)
        return jax.random.categorical(key, jnp.log(probs + 1e-9), axis=-1).astype(jnp.int32)[:, None]

    def _generate_legacy(self, prompts: jnp.ndarray, *, eos_id: Optional[int] = None) -> jnp.ndarray:
        """Token-by-token batch loop over the dense per-request caches
        (correct for every arch in the zoo, incl. SSM state builds)."""
        b, s_prompt = prompts.shape
        # The KV/SSM caches hold max_seq positions; dynamic_update_slice
        # *clamps* out-of-range writes, so an unchecked overrun would
        # silently overwrite the last cache slot instead of failing.
        budget = self.sc.max_seq - s_prompt
        if budget <= 0:
            raise ValueError(
                f"prompt length {s_prompt} leaves no room to generate within "
                f"max_seq={self.sc.max_seq}")
        max_new = self.sc.max_new_tokens
        if max_new > budget:
            self.counters.truncated_max_new += 1
            self.counters.warn_once(
                "truncate_max_new",
                f"truncating max_new_tokens {max_new} -> {budget}: prompt "
                f"length {s_prompt} + requested tokens would overrun the "
                f"max_seq={self.sc.max_seq} cache (counted in "
                f"ServeMetrics.truncated_max_new; warning not repeated)")
            max_new = budget
        cache = transformer.init_decode_cache(
            self.cfg, b, self.sc.max_seq,
            dtype=jnp.float32 if self.cfg.dtype == jnp.float32 else jnp.bfloat16)
        key = jax.random.PRNGKey(self.sc.seed)

        t0 = self._now()

        def over_budget() -> bool:
            return (self.sc.max_wall_s is not None
                    and self._now() - t0 > self.sc.max_wall_s)

        tokens = prompts
        logits = None
        for i in range(s_prompt):                      # prefill
            logits, cache = self._decode(self.params, cache, prompts[:, i:i + 1])
            if over_budget():
                # Can't emit anything sensible without a full prefill — the
                # degraded response is the prompt unchanged.
                self.counters.budget_truncated += 1
                self.counters.warn_once(
                    "wall_budget",
                    f"serve batch exceeded wall-clock budget "
                    f"max_wall_s={self.sc.max_wall_s} during prefill "
                    f"({i + 1}/{s_prompt} tokens); returning prompt only "
                    f"(counted in ServeMetrics.budget_truncated; warning "
                    f"not repeated)")
                return prompts
        out: List[jnp.ndarray] = [tokens]
        done = jnp.zeros((b, 1), bool)
        for n in range(max_new):                       # decode
            key, sub = jax.random.split(key)
            nxt = self._sample(logits, sub)
            if eos_id is not None:
                done = done | (nxt == eos_id)
                nxt = jnp.where(done, eos_id, nxt)
            out.append(nxt)
            if eos_id is not None and bool(done.all()):
                break                                  # every row finished
            if over_budget():
                self.counters.budget_truncated += 1
                self.counters.warn_once(
                    "wall_budget",
                    f"serve batch exceeded wall-clock budget "
                    f"max_wall_s={self.sc.max_wall_s} after {n + 1}/"
                    f"{max_new} tokens; returning truncated response "
                    f"(counted in ServeMetrics.budget_truncated; warning "
                    f"not repeated)")
                break
            logits, cache = self._decode(self.params, cache, nxt)
        return jnp.concatenate(out, axis=1)

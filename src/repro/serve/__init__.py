"""Serving: request-level engine, paged KV pool, continuous batching.

Serving fast path
-----------------

The fast path replaces per-request dense ``(B, S_max)`` KV caches with a
shared **page pool** per attention slot:

    pool:  (n_periods * n_pages, kv_heads, page_size, 2 * head_dim)
           one per layer slot, period l on pages l * n_pages + table
    table: (max_slots, max_pages) int32                   page ids per row
    page 0 = reserved null page (padding / inactive-row scatter target)

K and V of each kv head sit side by side on the lanes of one page row (K
on the first ``head_dim``, V on the next), so the ragged Pallas decode
kernel (:mod:`repro.kernels.paged_attention`) streams each page with a
single double-buffered block DMA, walking the row's page table via scalar
prefetch, and the engine's programs update the donated pools in place. Chunked prefill pushes ``prefill_chunk`` prompt tokens through
the same kernel per step — ``ceil(S/chunk)`` launches instead of ``S``.

The scheduler loop (:mod:`repro.serve.scheduler`) keeps the fixed-shape
device state busy: admit queued requests into free slots when their pages
fit, lazily grow one page per crossed boundary, preempt the youngest
request on pool exhaustion (recompute on re-admit; sampled tokens ride
along as prompt extension), retire on eos/length/wall-budget and return
pages to the freelist *immediately* so waiting requests can join mid-batch.

Migrating from ``generate()``
-----------------------------

Old surface (still works, now a thin deprecated wrapper)::

    Engine(cfg, params, ServeConfig(temperature=0.7)).generate(prompts)

New request-level surface — sampling is per-request, completions are
ragged and carry finish reasons + latency::

    eng = Engine(cfg, params, ServeConfig(max_seq=256, page_size=16))
    rid = eng.submit(Request(prompt=toks, max_new_tokens=64,
                             eos_id=2, temperature=0.7, seed=1))
    for c in eng.run_until_drained().values():
        print(c.finish_reason, c.ttft_s, c.tokens)

Architectures the paged path does not cover (SSM/hybrid mixers, int8 KV)
transparently fall back to the legacy token-by-token batch loop; forcing
``ServeConfig(paged=False)`` turns that loop into a parity oracle for the
fast path (tests/test_serve_paged.py).

Failure handling & SLOs
-----------------------

The serving mirror of the train-side fault substrate (PR 6), at request
granularity. Three principles: *degrade before failing*, *poison one
request, not the batch*, and *every decision is a counter*.

**Deadlines.** ``Request.deadline_s`` is seconds-from-submission. The
engine checks it at the top of every scheduler step: a queued request past
deadline is dropped without ever touching the device; an active one gives
up its slot and pages immediately and completes with
``finish_reason='deadline'`` carrying whatever it generated. Higher
``Request.priority`` admits first (FIFO within a level — a preempted
request requeues by its original submission tick, so it cannot starve).

**Admission control.** With ``ServeConfig.max_queue`` /
``admit_watermark`` set, ``Engine.submit`` returns a :class:`Rejected`
verdict — ``'queue_full'`` at the queue-depth watermark,
``'pool_pressure'`` when the projected page demand of everything queued +
active + the new request exceeds the watermark fraction of pool capacity.
Backpressure is the contract, not an exception; callers shed load or
retry. ``ValueError`` remains reserved for requests that could never run.

**Degradation ladder** (most local first):

1. a failing paged-attention launch (decode step or prefill chunk) serves
   exactly that step through the dense ``paged_attention_ref`` path —
   ``degraded_steps`` counts, one warning total; pools found deleted at a
   step's start (a call's returned pools were dropped) are made anew and
   every active request recomputes, as after a preemption — counted there
   too;
2. a non-finite logit row (on-device per-slot health tap, no host vocab
   scan) skips sampling for the poisoned slot only and retires it with
   ``finish_reason='nan'`` — the rest of the batch never notices;
3. wall-budget / deadline overruns truncate that one request
   (``'budget'`` / ``'deadline'``);
4. a no-progress scheduler step triggers deterministic backoff — freeze
   admissions for ``backoff_freeze_steps``, force-retire over-deadline
   slots — and only ``livelock_patience`` consecutive stuck steps raise
   :class:`LivelockError`, which carries the full scheduler/pool counter
   snapshot (queue, per-slot rids, freelist) in its message.

**Metrics.** ``Engine.metrics()`` snapshots a frozen
:class:`ServeMetrics`: gauges (queue depth, active slots, free/used pages,
high-water), scheduler counters (admitted/retired/preempted, step/chunk/
token counts, decode rows live and computed), and every fault counter
above. Latency is per request: ``Completion.ttft_s``/``tpot_s``/``queue_s``.
Hot-loop conditions warn only on first occurrence (see
``ServeCounters.warn_once``); recurrence is what the counters are for.

Chaos drill: :class:`ServeFaultPlan` (``serve/faults.py``) injects kernel
failures, poisoned logits, pool squeezes and clock stalls deterministically
through the shared :mod:`repro.injection` registry;
``benchmarks/serve_drill.py`` gates CI on an injected run draining with
greedy parity on unpoisoned requests and zero page leaks.
"""
from .engine import Completion, Engine, Request, ServeConfig
from .faults import ServeFaultPlan, inject_paged_kernel_failure
from .kvpool import KVPool, PoolExhausted
from .metrics import LivelockError, Rejected, ServeCounters, ServeMetrics
from .scheduler import Scheduler

__all__ = ["Engine", "ServeConfig", "Request", "Completion",
           "KVPool", "PoolExhausted", "Scheduler",
           "ServeMetrics", "ServeCounters", "Rejected", "LivelockError",
           "ServeFaultPlan", "inject_paged_kernel_failure"]

"""Serving SLO metrics, admission results, and diagnosable failures.

The serving counterpart of the train-side guard counters (PR 6): every
fault-handling decision the engine makes — degraded kernel step, NaN
retirement, deadline expiry, admission rejection, livelock backoff — lands
in a counter here instead of a hot-loop ``warnings.warn`` (which Python
dedups to one line per process, hiding recurrence). The engine's
:meth:`~repro.serve.engine.Engine.metrics` snapshots everything into a
frozen :class:`ServeMetrics` so drills and dashboards read one consistent
view.

* :class:`ServeCounters` — the engine's mutable tallies, with
  :meth:`ServeCounters.warn_once` for first-occurrence-only warnings
  (the counter keeps counting after the warning stops).
* :class:`ServeMetrics` — immutable snapshot: counters + scheduler/pool
  gauges. ``to_dict()`` feeds the bench history.
* :class:`Rejected` — ``Engine.submit`` admission-control verdict
  (backpressure instead of unbounded queueing).
* :class:`LivelockError` — raised only after deterministic backoff fails;
  carries the full scheduler/pool counter snapshot so a field failure is
  diagnosable from the exception message alone.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional, Set, Tuple


class ServeCounters:
    """Mutable fault/SLO tallies owned by one Engine instance."""

    __slots__ = ("degraded_steps", "nan_retired", "deadline_expired",
                 "budget_truncated", "truncated_max_new", "rejected_queue",
                 "rejected_pool", "livelock_backoffs", "injected_stalls",
                 "injected_poison", "_warned")

    def __init__(self) -> None:
        self.degraded_steps = 0       # launches degraded to the ref path, pools lost
        self.nan_retired = 0          # slots retired on a non-finite logit tap
        self.deadline_expired = 0     # requests retired/dropped past deadline
        self.budget_truncated = 0     # wall-clock budget truncations
        self.truncated_max_new = 0    # submit-time max_new_tokens clamps
        self.rejected_queue = 0       # admissions rejected: queue watermark
        self.rejected_pool = 0        # admissions rejected: pool projection
        self.livelock_backoffs = 0    # no-progress backoff rounds
        self.injected_stalls = 0      # fault-plan clock skews applied
        self.injected_poison = 0      # fault-plan logit poisonings applied
        self._warned: Set[str] = set()

    def warn_once(self, code: str, message: str) -> None:
        """Warn on the *first* occurrence of ``code`` only; recurrence is
        what the counters are for. (Relying on the warnings module's own
        dedup instead silently swallowed distinct messages that shared a
        format — the old hot-loop behavior this replaces.)"""
        if code not in self._warned:
            self._warned.add(code)
            warnings.warn(message, stacklevel=3)

    @property
    def warned_codes(self) -> Tuple[str, ...]:
        return tuple(sorted(self._warned))


@dataclasses.dataclass(frozen=True)
class ServeMetrics:
    """One consistent snapshot of the engine's serving health. Gauges
    (queue depth, free pages) read the instant of the snapshot; counters
    are monotone since engine construction."""

    # gauges
    queue_depth: int
    active_slots: int
    free_pages: int
    used_pages: int
    page_high_water: int
    pool_capacity: int
    # scheduler counters
    admitted: int
    retired: int
    preempted: int
    sched_steps: int
    decode_steps: int
    decode_rows: int              # active rows, summed over decode calls
    decode_rows_computed: int     # rows the decode calls ran (max_slots each)
    prefill_chunks: int
    tokens_out: int
    # fault / SLO counters (mirrors ServeCounters)
    degraded_steps: int
    nan_retired: int
    deadline_expired: int
    budget_truncated: int
    truncated_max_new: int
    rejected_queue: int
    rejected_pool: int
    livelock_backoffs: int
    injected_stalls: int
    injected_poison: int

    @property
    def preemption_rate(self) -> float:
        """Preemptions per admission — the churn measure the admission
        watermark is meant to bound."""
        return self.preempted / max(self.admitted, 1)

    @property
    def rejected(self) -> int:
        return self.rejected_queue + self.rejected_pool

    def to_dict(self) -> Dict[str, float]:
        d = dataclasses.asdict(self)
        d["preemption_rate"] = round(self.preemption_rate, 4)
        d["rejected"] = self.rejected
        return d


@dataclasses.dataclass(frozen=True)
class Rejected:
    """Admission-control verdict from ``Engine.submit``: the request was
    *not* enqueued. ``reason`` is ``'queue_full'`` (queue depth at
    ``ServeConfig.max_queue``) or ``'pool_pressure'`` (projected page demand
    of everything queued + active + this request past the
    ``admit_watermark`` fraction of pool capacity). Callers shed load or
    retry later — backpressure is the contract, not an exception."""

    reason: str
    queue_depth: int
    projected_pages: int
    pool_capacity: int


class LivelockError(RuntimeError):
    """The scheduler made no progress for a full patience window despite
    backoff (admission freeze + forced retirement of over-deadline slots).
    Subclasses RuntimeError so pre-existing broad handlers still fire.

    Carries the complete state needed to diagnose the wedge from the
    message alone: queue depth, per-slot rids, pool freelist state, and the
    full :class:`ServeMetrics` snapshot at raise time."""

    def __init__(self, metrics: ServeMetrics,
                 slot_rids: List[Optional[int]],
                 queued_rids: Tuple[int, ...]) -> None:
        self.metrics = metrics
        self.slot_rids = list(slot_rids)
        self.queued_rids = tuple(queued_rids)
        counters = ", ".join(
            f"{k}={v}" for k, v in sorted(metrics.to_dict().items()))
        super().__init__(
            f"scheduler made no progress for {metrics.livelock_backoffs} "
            f"backoff rounds — queue={list(queued_rids)} "
            f"(depth {metrics.queue_depth}), slot_rids={self.slot_rids}, "
            f"free_pages={metrics.free_pages}/{metrics.pool_capacity}, "
            f"counters: {counters}")

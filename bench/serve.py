"""Serving cells: the program's ``Engine`` (paged KV, chunked prefill,
continuous batching), driven by one host thread.

``serve_open``: Poisson arrivals at the traffic's fixed rate. Requests due
in the ``warmup_s`` before the window load the engine; the requests due in
the window are measured, and followed to completion after it while arrivals
go on. Latencies count from when a request was due, so they include how
late the generator submitted it.

``serve_closed``: an offline job that keeps ``backlog`` requests queued.
All slots are filled before the window opens; the window counts the output
tokens produced inside it and the requests retired inside it.

Both check a sample of requests, drawn from the seed before they are
submitted with the one that serves most tokens among them, against the
plain float32 reference after the window: the program's logits at every
served position, as the engine sampled from them, and its greedy tokens.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import counts, program, spec, traffic
from .reference.common import PRECISIONS, init_params
from .train import peak_bytes, stop_trace, trace_options

BAD_ENDS = ("nan", "deadline", "budget")


@dataclasses.dataclass
class Req:
    prompt: np.ndarray
    due: float = 0.0
    submit: float = 0.0
    admit: float | None = None       # start of the engine step that admitted it
    admit_step: int | None = None
    done_step: int | None = None
    completion: object = None
    failed: bool = False
    rows: dict | None = None         # token index -> the logits it came from


def pick(out_lens, n: int, seed: int) -> list:
    """Indices of the check's sample: the one with most output tokens (the
    first of them), and ``n - 1`` of the others drawn from the seed."""
    longest = int(np.argmax(out_lens))
    rest = [i for i in range(len(out_lens)) if i != longest]
    draw = traffic.rng(seed, 5).permutation(len(rest))[:n - 1]
    return [longest] + [rest[i] for i in draw]


class EngineSession:
    """The engine with the bookkeeping the metrics need, step by step."""

    def __init__(self, cell, seed: int, model=None, fault=None):
        from repro.serve import Engine, Request, ServeConfig

        self.cell, self.cfg, self.tr, self.seed = cell, cell.config, cell.traffic, seed
        self.family = fam = cell.family
        mc = program.model_config(self.cfg, fam, model)
        key = jax.random.PRNGKey(traffic.seed32(seed))
        shapes = fam.param_shapes(self.cfg)
        self.make = jax.jit(lambda k: init_params(self.cfg, shapes, k))
        self.key = key
        sc = ServeConfig(max_new_tokens=self.tr["output"]["max"],
                         **self.tr["serve"])
        self.eng = Engine(mc, self.make(key), sc)
        self._watch_sampling()
        if fault is not None:
            fault(self.eng)
        self.Request = Request
        self.reqs: dict = {}
        self.queued: set = set()
        self.steps: list = []            # per engine step: inside the window?
        self.in_window = False
        self.moved: dict = {}            # program counters' change in the window
        # Warm-up: one request compiles the prefill chunk, the decode step and
        # the host-side reads; no other shape is used.
        self.eng.submit(Request(prompt=np.arange(2, dtype=np.int32),
                                max_new_tokens=2, temperature=0.0))
        self.eng.run_until_drained()

    def _watch_sampling(self) -> None:
        """Keep a copy of each logits row the engine samples a watched
        request's token from (prefill's last row, then one per decode)."""
        sample = self.eng._sample_one

        def record(st, row):
            r = self.reqs.get(st.rid)
            if r is not None and r.rows is not None:
                r.rows[len(st.generated)] = np.array(row, np.float32)
            return sample(st, row)

        self.eng._sample_one = record

    def submit(self, prompt, out_len: int, due: float, watch: bool = False) -> None:
        with jax.profiler.TraceAnnotation("bench.engine.submit"):
            now = time.monotonic()
            rid = self.eng.submit(self.Request(prompt=prompt, max_new_tokens=out_len,
                                               temperature=0.0))
        r = Req(prompt=prompt, due=due, submit=now, rows={} if watch else None)
        if not isinstance(rid, int):           # Rejected by admission control
            r.failed = True
            rid = ("rejected", len(self.reqs))
        else:
            self.queued.add(rid)
        self.reqs[rid] = r

    def busy(self) -> bool:
        s = self.eng.scheduler
        return bool(s.queue) or bool(s.active_slots())

    def step(self) -> None:
        eng = self.eng
        degraded = eng.counters.degraded_steps
        k = len(self.steps)
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.engine.step"):
            eng.step()
        self.steps.append(self.in_window)
        left = self.queued - set(eng.scheduler.queue)
        for rid in left:
            self.reqs[rid].admit, self.reqs[rid].admit_step = t0, k
        self.queued -= left
        done = eng.completions()
        for rid, c in done.items():
            r = self.reqs[rid]
            r.completion, r.done_step = c, k
            r.failed |= c.finish_reason in BAD_ENDS
        if eng.counters.degraded_steps != degraded:
            live = {rid for rid in eng.scheduler.slot_rid if rid is not None}
            for rid in live | set(done) | left:
                self.reqs[rid].failed = True

    def watched(self) -> list:
        return [r for r in self.reqs.values() if r.rows is not None]

    # -- per-step accounting for the paged-attention and MFU readers -------

    def window_work(self):
        """(paged-attention calls, attended contexts of every token) of the
        steps inside the window. A request's first token comes from its
        prefill in its admission step, and each later token from one decode
        step: the last of them in the step that retired it, or, for a
        request still running, one in every step from its admission on."""
        chunk = self.tr["serve"]["prefill_chunk"]
        inside = self.steps
        calls, contexts = [], []
        for r in self.reqs.values():
            if r.admit_step is None:
                continue
            p = len(r.prompt)
            if inside[r.admit_step]:
                for lo in range(0, p, chunk):
                    q = min(chunk, p - lo)
                    calls.append((q, lo + q))
                contexts.extend(range(1, p + 1))
            if r.completion is not None:
                n = len(r.completion.tokens)
                steps = range(r.done_step - (n - 2), r.done_step + 1)
            else:
                steps = range(r.admit_step, len(self.steps))
            for j, k in enumerate(steps, start=2):
                if 0 <= k < len(inside) and inside[k]:
                    calls.append((1, p + j - 1))
                    contexts.append(p + j - 1)
        return calls, contexts

    def counters(self) -> dict:
        return program.counters(self.eng, self.family.counters)

    def pool_signatures(self) -> list:
        """The type of each of the engine's page pools, as the trace spells
        a result."""
        return sorted({counts.array_signature(p)
                       for p in self.eng._device_pools().values()})

    def kernel_calls(self) -> dict:
        """Kernel name -> the Pallas calls of the cell's kernels in the
        engine's decode and prefill programs, read from their lowering."""
        from repro.models.transformer import PagedState

        eng, sc = self.eng, self.tr["serve"]
        pools = eng._device_pools()
        b = sc["max_slots"]
        state = PagedState(pools=pools, table=jnp.asarray(eng.scheduler.table),
                           lengths=jnp.zeros((b,), jnp.int32),
                           active=jnp.zeros((b,), bool))
        texts = [
            eng._decode.lower(eng.params, state, jnp.zeros((b, 1), jnp.int32)).as_text(),
            eng._prefill.lower(eng.params, pools,
                               jnp.asarray(eng.scheduler.table[:1]), np.int32(0),
                               np.int32(1), jnp.zeros((1, sc["prefill_chunk"]),
                                                      jnp.int32)).as_text(),
        ]
        return counts.calls_by_kernel(texts, spec.kernels(self.cell))

    # -- the check ----------------------------------------------------------

    def check(self, control: bool = False) -> dict:
        """The watched requests that finished, against the float32
        reference run once over each prompt with its served tokens:

        * ``logit_err``: the widest relative gap, over served positions,
          between the logits the engine sampled from and the reference's,
          ``|prog - ref| / |ref|`` over the vocabulary;
        * ``logit_gap``: the widest gap by which a served greedy token's
          reference logit lies below the reference's best.

        With ``control`` also the same two of the fp8 reference at the same
        positions (its logits, and the token it puts first). A watched
        request that never finished, or failed, reads infinite on both."""
        sample = self.watched()
        self.eng = None
        gc.collect()
        if any(r.completion is None or r.failed for r in sample):
            self.served_checked = 0
            return {"logit_err": float("inf"), "logit_gap": float("inf")}
        self.served_checked = sum(len(r.completion.tokens) for r in sample)
        cfg, max_seq = self.cfg, self.tr["serve"]["max_seq"]
        width, forward = self.tr["output"]["max"], self.family.forward
        params = self.make(self.key)

        @jax.jit
        def compare(p, tokens, rows, lo, n):
            logits = forward(cfg, p, tokens[None])[0]
            pos = jnp.arange(max_seq)
            live = (pos >= lo) & (pos < lo + n)
            prog = jnp.zeros_like(logits).at[lo + jnp.arange(width)].set(
                rows, mode="drop")
            best = jnp.max(logits, -1)
            size = jnp.linalg.norm(logits, axis=-1)

            def gap(tok):
                return best - jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]

            def err(x):
                return jnp.linalg.norm(x - logits, axis=-1) / size

            def widest(x):
                return jnp.max(jnp.where(live, x, -jnp.inf))

            out = {"logit_err": widest(err(prog)),
                   "logit_gap": widest(gap(jnp.roll(tokens, -1)))}
            if control:
                low = forward(cfg, p, tokens[None], PRECISIONS["fp8"])[0]
                out["control_logit_err"] = widest(err(low))
                out["control_logit_gap"] = widest(gap(jnp.argmax(low, -1)))
            return out

        worst: dict = {}
        for r in sample:
            seq = np.concatenate([r.prompt, r.completion.tokens]).astype(np.int32)
            buf = np.zeros(max_seq, np.int32)
            buf[:len(seq)] = seq
            rows = np.zeros((width, cfg["vocab_size"]), np.float32)
            for k, row in r.rows.items():
                rows[k] = row
            p, n = len(r.prompt), len(r.completion.tokens)
            res = compare(params, jnp.asarray(buf), jnp.asarray(rows),
                          np.int32(p - 1), np.int32(n))
            for k, v in res.items():
                worst[k] = max(worst.get(k, -np.inf), float(v))
        return worst


def _open_window(d: EngineSession, trace_dir):
    if trace_dir:
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options())
    ann = jax.profiler.TraceAnnotation("bench.window")
    ann.__enter__()
    d.in_window, d.at_open = True, d.counters()
    return ann


def _close_window(d: EngineSession, ann) -> None:
    ann.__exit__(None, None, None)
    d.in_window = False
    d.moved = program.counter_deltas(d.at_open, d.counters())


DEPTH_MARKS = (0.25, 0.5, 1.0)


def run_open(cell, seed: int, seconds: float, trace_dir, clock, *, model=None,
             fault=None, rate=None, control=False) -> dict:
    """One run of an open-loop cell; ``rate`` (requests/s) in place of the
    traffic's is the knee sweep's."""
    tr = cell.traffic
    rate = rate or tr["rate_rps"]
    d = EngineSession(cell, seed, model, fault)
    warm, drain = tr["warmup_s"], tr["drain_limit_s"]
    sched = traffic.open_schedule(tr, rate, warm, seconds, warm + seconds + drain,
                                  cell.config["vocab_size"], seed)
    due = [j for j, x in enumerate(sched) if warm <= x[0] < warm + seconds]
    watch = {due[j] for j in pick([sched[j][2] for j in due],
                                  tr["check_requests"], seed)}
    start = time.monotonic()
    lo, hi = start + warm, start + warm + seconds
    i, ann, t_open, t_close, depth = 0, None, None, None, {}
    while True:
        now = time.monotonic()
        if ann is None and now >= lo:
            ann, t_open = _open_window(d, trace_dir), time.monotonic()
        while i < len(sched) and start + sched[i][0] <= now:
            d.submit(sched[i][1], sched[i][2], due=start + sched[i][0],
                     watch=i in watch)
            i += 1
        for f in DEPTH_MARKS:
            if ann is not None and f not in depth and now >= lo + f * seconds:
                depth[f] = len(d.eng.scheduler.queue)
        if t_close is None and now >= hi:
            _close_window(d, ann)
            t_close = now
            active = len(d.eng.scheduler.active_slots())
        if t_close is not None:
            window = [r for r in d.reqs.values() if lo <= r.due < hi]
            if all(r.completion is not None or r.failed for r in window) \
                    or now > hi + drain:
                break
        if d.busy():
            d.step()
        elif i < len(sched):
            with jax.profiler.TraceAnnotation("bench.loadgen.wait"):
                time.sleep(max(0.0, start + sched[i][0] - time.monotonic()))
    if trace_dir:
        stop_trace()
    setup = t_open - clock
    mem = peak_bytes()
    for r in window:
        r.failed |= r.completion is None
    ok = [r for r in window if not r.failed]
    lost = (time.monotonic() - lo) * 1e3     # a failed request never answered
    ttft = [(r.completion.ttft_s + r.submit - r.due) * 1e3 if not r.failed else lost
            for r in window]
    tpot = [r.completion.tpot_s * 1e3 if not r.failed else lost
            for r in window if r.failed or r.completion.tpot_s is not None]
    wait = [(r.admit - r.due) * 1e3 if r.admit is not None else lost for r in window]
    # queued for a slot, as the program counts it (submit to admission)
    queued = [getattr(r.completion, "queue_s", None) if not r.failed else lost / 1e3
              for r in window]
    calls, contexts = d.window_work()
    out = {
        "setup_s": setup,
        "attempted": len(window), "failed": len(window) - len(ok),
        "end_to_end": {"ttft_p95_ms": traffic.percentile(ttft, 95),
                       "tpot_p95_ms": traffic.percentile(tpot, 95)},
        "memory_peak_bytes": mem,
        "counts": {"window_s": t_close - t_open,
                   "queue_wait_p95_ms": traffic.percentile(wait, 95),
                   "queue_depth": depth, "active_at_close": active,
                   "paged_calls": calls, "contexts": contexts,
                   "kernel_calls": d.kernel_calls() if trace_dir else {},
                   "pool_signatures": d.pool_signatures(), "program": d.moved},
    }
    if None not in queued:
        out["counts"]["admit_wait_p95_ms"] = 1e3 * traffic.percentile(queued, 95)
    out["numbers"] = d.check(control)
    out["counts"]["served_checked"] = d.served_checked
    return out


def run_closed(cell, seed: int, seconds: float, trace_dir, clock, *, model=None,
               fault=None, control=False) -> dict:
    """One run of a closed-loop (offline) cell. The check's sample comes
    from the requests that fill the slots before the window; those still
    running when it closes are stepped to their end after it, without new
    requests."""
    tr = cell.traffic
    d = EngineSession(cell, seed, model, fault)
    backlog = tr["backlog"]
    stream = traffic.Requests(tr, cell.config["vocab_size"], seed, block=backlog)
    slots = tr["serve"]["max_slots"]
    first = [stream.next() for _ in range(slots)]
    watch = set(pick([m for _, m in first], tr["check_requests"], seed))

    def top_up():
        while len(d.eng.scheduler.queue) < backlog:
            d.submit(*stream.next(), due=time.monotonic())

    for j, (prompt, out_len) in enumerate(first):
        d.submit(prompt, out_len, due=time.monotonic(), watch=j in watch)
    top_up()
    while len(d.eng.scheduler.active_slots()) < slots:
        top_up()
        d.step()
    top_up()
    kernel_calls = d.kernel_calls() if trace_dir else {}
    ann = _open_window(d, trace_dir)
    t_open = time.monotonic()
    k0, tok0 = len(d.steps), d.eng.tokens_out
    while True:
        top_up()
        d.step()
        if time.monotonic() - t_open >= seconds:
            break
    _close_window(d, ann)
    t_close = time.monotonic()
    k1, tok1 = len(d.steps), d.eng.tokens_out
    if trace_dir:
        stop_trace()
    while any(r.completion is None and not r.failed for r in d.watched()) \
            and time.monotonic() - t_close < tr["drain_limit_s"]:
        d.step()
    mem = peak_bytes()
    done = [r for r in d.reqs.values()
            if r.done_step is not None and k0 <= r.done_step < k1]
    ok = [r for r in done if not r.failed]
    calls, contexts = d.window_work()
    window = t_close - t_open
    out = {
        "setup_s": t_open - clock,
        "attempted": len(done), "failed": len(done) - len(ok),
        "end_to_end": {"serve_tokens_per_s": (tok1 - tok0) / window},
        "memory_peak_bytes": mem,
        "counts": {"window_s": window,
                   "paged_calls": calls, "contexts": contexts,
                   "kernel_calls": kernel_calls,
                   "pool_signatures": d.pool_signatures(), "program": d.moved},
    }
    out["numbers"] = d.check(control)
    out["counts"]["served_checked"] = d.served_checked
    return out


def sweep(cell, seed: int, seconds: float, rate: float) -> dict:
    """One run of an open-loop cell at ``rate`` requests/s, reduced to
    whether a backlog grows: the queue's depth at a quarter, half and the
    end of the window, the slots busy at its end, and the tails."""
    r = run_open(cell, seed, seconds, None, time.perf_counter(), rate=rate)
    c = r["counts"]
    return {"rate_rps": rate, "seconds": seconds, "due": r["attempted"],
            "failed": r["failed"], "queue_depth": c["queue_depth"],
            "active_at_close": c["active_at_close"],
            "queue_wait_p95_ms": c["queue_wait_p95_ms"], **r["end_to_end"],
            **r["numbers"]}

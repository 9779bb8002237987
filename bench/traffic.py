"""Load generation from ``--seed``: training batches and serving requests.

Every seed gets the same set of sizes: lengths and inter-arrival gaps are the
stratified quantiles ``(i + 0.5) / n`` of their distributions, and the seed
only shuffles them and draws the token ids. So two seeds differ in order and
content, not in the amount of work. A traffic mix with ``"order": "fixed"``
gives every seed the same order too, and the seed draws only the token ids.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_SEED_MASK = (1 << 63) - 1


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one seed (any whole number)."""
    return np.random.default_rng((seed & _SEED_MASK, *stream))


def order_seed(traffic: dict, seed: int) -> int:
    """The seed that orders a mix's lengths and gaps."""
    return 0 if traffic.get("order") == "fixed" else seed


def seed32(seed: int) -> int:
    """A 31-bit value derived from ``seed``, for APIs that take int32 seeds."""
    return int(rng(seed, 0x5EED).integers(0, 2**31 - 1))


class ZipfBatches:
    """Next-token batches from a Zipfian Markov stream, a pure function of
    (seed, step): token frequencies follow ``p(t) ~ 1/(t+1)^alpha``; with
    probability ``mix`` the next token is instead one of ``continuations``
    preferred successors of the current state (``token % n_states``), so the
    stream has learnable bigram structure. Rows hold ``seq + 1`` tokens
    split into ``tokens`` and next-token ``labels``."""

    def __init__(self, vocab: int, seq: int, batch: int, seed: int, *,
                 alpha: float, n_states: int, mix: float, continuations: int):
        self.vocab, self.seq, self.batch_size, self.seed = vocab, seq, batch, seed
        self.mix = mix
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        base = 1.0 / ranks ** alpha
        self.base = base / base.sum()
        self.n_states = min(n_states, vocab)
        self.succ = rng(seed, 1).integers(
            0, vocab, size=(self.n_states, continuations))

    def batch(self, step: int) -> dict:
        import jax  # annotation only; the stream itself is numpy

        with jax.profiler.TraceAnnotation("bench.data.batch"):
            r = rng(self.seed, 2, step)
            n = self.batch_size * (self.seq + 1)
            draw = r.choice(self.vocab, size=n, p=self.base)
            mixed = r.random(n) < self.mix
            pick = r.integers(0, self.succ.shape[1], size=n)
            succ, k = self.succ.tolist(), self.n_states
            state = int(r.integers(0, k))
            out = []
            for d, m, p in zip(draw.tolist(), mixed.tolist(), pick.tolist()):
                t = succ[state][p] if m else d
                out.append(t)
                state = t % k
            rows = np.asarray(out, np.int32).reshape(self.batch_size, -1)
            return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, r: np.random.Generator) -> np.ndarray:
    """n stratified lengths of ``spec`` (``lognormal`` with ``median`` and
    ``sigma``, or ``uniform``), clipped to [min, max], in a seeded order."""
    u = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"]) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return r.permutation(x)


def poisson_gaps(rate: float, n: int, r: np.random.Generator) -> np.ndarray:
    """n stratified exponential inter-arrival gaps at ``rate`` per second."""
    return r.permutation(-np.log1p(-_quantiles(n)) / rate)


class Requests:
    """A seeded stream of (prompt tokens, output length) in blocks of
    ``block``: each block holds the same stratified set of lengths."""

    def __init__(self, traffic: dict, vocab: int, seed: int, block: int,
                 stream: int = 0):
        self.traffic, self.vocab, self.seed, self.block = traffic, vocab, seed, block
        self.stream = stream
        self._buf: list = []
        self._blocks = 0

    def _fill(self) -> None:
        r = rng(self.seed, 3, self.stream, self._blocks)
        ordered = order_seed(self.traffic, self.seed)
        order = r if ordered == self.seed else rng(ordered, 3, self.stream,
                                                   self._blocks)
        self._blocks += 1
        p = lengths(self.traffic["prompt"], self.block, order)
        o = lengths(self.traffic["output"], self.block, order)
        self._buf.extend(
            (r.integers(0, self.vocab, int(n)).astype(np.int32), int(m))
            for n, m in zip(p, o))

    def next(self):
        if not self._buf:
            self._fill()
        return self._buf.pop(0)


def open_schedule(traffic: dict, rate: float, warm: float, seconds: float,
                  horizon: float, vocab: int, seed: int) -> list:
    """Open-loop requests as (due seconds from the start, prompt, output
    length). The warm-up, the window and each later span of ``seconds``
    hold ``round(rate * span)`` requests: a stratified block of lengths, at
    stratified Poisson gaps scaled to fill the span. So every seed's window
    holds the same requests and gaps, in another order unless the mix fixes
    it."""
    spans = [(0.0, warm), (warm, warm + seconds)] if warm > 0 else [(0.0, seconds)]
    while spans[-1][1] < horizon:
        spans.append((spans[-1][1], spans[-1][1] + seconds))
    out = []
    for k, (a, b) in enumerate(spans):
        n = max(1, round(rate * (b - a)))
        gaps = poisson_gaps(rate, n, rng(order_seed(traffic, seed), 4, k))
        due = a + (b - a) * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
        reqs = Requests(traffic, vocab, seed, n, stream=k)
        out.extend((float(t), *reqs.next()) for t in due)
    return out


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) of a non-empty sequence."""
    v = sorted(values)
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])

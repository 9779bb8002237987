"""Reduce a profiler trace of one run to what the per-layer metrics read.

Device time comes from the ``XLA Ops`` line of each ``/device:TPU:n``
plane: busy time is the union of the op intervals inside the window, and
an op's self time is its duration less that of the ops nested in it (a
loop's body, say). The window is the harness's ``bench.window`` span on the
host, on the same clock. Pallas kernels are ``tpu_custom_call`` ops; they
are keyed by their result types, which the lowered program ties to a kernel
name (``counts.custom_calls``). Idle gaps are named after the innermost
``bench.*`` span the host was in at their midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

_LAYOUT = re.compile(r"\{[^{}]*\}")
_TYPE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_HEAD = re.compile(r"^%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
CLOCK_SLACK_NS = 2_000_000


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                 # mean over the devices traced
    n_devices: int
    self_s: dict                  # short op name -> self seconds
    kernels: dict                 # result signature -> [calls, seconds]
    gaps: list                    # (seconds, host span) longest first
    # filled by ``xplane.reduce``: named scope -> self seconds, program ->
    # [calls, seconds], result signature -> self seconds of path-less ops
    scope_s: dict = dataclasses.field(default_factory=dict)
    modules: dict = dataclasses.field(default_factory=dict)
    unscoped_s: dict = dataclasses.field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_time(self, signatures) -> tuple:
        """(calls, seconds) of the Pallas calls with these signatures."""
        hits = [self.kernels[s] for s in set(signatures) if s in self.kernels]
        return sum(h[0] for h in hits), sum(h[1] for h in hits)

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.self_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[span, s] for s, span in self.gaps[:n]]}


def describe(hlo: str):
    """(short name, result signature, is a Pallas call) of one op event."""
    m = _HEAD.match(hlo)
    if not m:
        return hlo[:80], "", False
    inst, result, opcode = m.groups()
    types = _TYPE.findall(_LAYOUT.sub("", result))
    sig = ",".join(f"{dt}[{dims}]" for dt, dims in types)
    pallas = 'custom_call_target="tpu_custom_call"' in hlo
    kind = "tpu_custom_call" if pallas else opcode
    return f"{inst} {sig[:60]} {kind}", sig, pallas


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {files}")
    return files[0]


def reduce_trace(path: str, window_span: str = "bench.window") -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.start_ns, e.end_ns, e.name))
        elif re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append([(e.start_ns, e.end_ns, e.name)
                                    for e in line.events])
    if not devices:
        raise ValueError(f"{path}: no TPU device plane with XLA Ops")
    windows = [(a, b) for a, b, n in spans if n == window_span]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {window_span!r} span, "
                         f"found {len(windows)}")
    lo, hi = windows[0][0] - CLOCK_SLACK_NS, windows[0][1] + CLOCK_SLACK_NS
    self_s = collections.Counter()
    kernels = collections.defaultdict(lambda: [0, 0.0])
    busy, gaps = [], []
    for events in devices:
        ev = sorted((max(a, lo), min(b, hi), n) for a, b, n in events
                    if b > lo and a < hi)
        busy.append(_union((a, b) for a, b, _ in ev))
        stack = []                                   # [end, name, child time]
        for a, b, name in ev + [(hi, hi, None)]:
            while stack and a >= stack[-1][0]:
                end, sname, child, dur = stack.pop()
                self_s[sname] += (dur - child) / 1e9
                if stack:
                    stack[-1][2] += dur
            if name is None:
                break
            short, sig, pallas = describe(name)
            if pallas:
                kernels[sig][0] += 1
                kernels[sig][1] += (b - a) / 1e9
            stack.append([b, short, 0.0, b - a])
        if events is devices[0]:
            end = lo
            for a, b, _ in ev:
                if a > end:
                    gaps.append((end, a))
                end = max(end, b)
            if hi > end:
                gaps.append((end, hi))
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [s for s in spans if s[0] <= mid <= s[1] and s[2] != window_span]
        span = max(inside, key=lambda s: s[0])[2] if inside else "outside any bench span"
        named.append(((b - a) / 1e9, span))
    named.sort(key=lambda g: -g[0])
    n = len(devices)
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n / 1e9,
                   n_devices=n, self_s={k: v / n for k, v in self_s.items()},
                   kernels={k: [c, s / n] for k, (c, s) in kernels.items()},
                   gaps=named)

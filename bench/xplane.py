"""Read what ``jax.profiler.ProfileData`` leaves out of a profile, and reduce
a traced window by the program's own names.

``ProfileData`` gives each device op its HLO text but not the stats of its
event metadata. There the trace keeps ``tf_op``: the JAX op path, with every
``jax.named_scope`` the op was traced under (``jit(train_step)/optimizer/
mul``). The ``XLA Modules`` line of a device plane names the program each
op ran in (``jit_paged_decode``). This module reads both straight from the
``.xplane.pb`` file with ``google.protobuf``, declaring the few fields of
the XPlane schema (``tsl/profiler/protobuf/xplane.proto``) it reads; unread
fields are skipped on parsing.

``reduce`` gives, inside the ``bench.window`` span and in one pass over
the file, what ``trace_reduce.reduce_trace`` reads through ``ProfileData``
(busy time, each op's self time, Pallas calls by result signature), and
device self-seconds per named scope, calls and device seconds per program,
the self-seconds of the ops that have no op path (XLA made them) by result
signature, and the idle gaps named after the innermost ``bench.*`` or
``repro.*`` host span at their midpoint: what the per-layer readers get.

An op is under scope ``s`` when ``s`` is one of the scopes of its path
(``a/s/op`` or ``s/op``). Inside a loop body (a ``lax.scan``) JAX names
ops from the body's own root, so ``kv_pool/dynamic_slice`` with no
``jit(...)/`` before it; a scope opened outside the loop does not reach the
ops inside it.
"""
from __future__ import annotations

import collections
import functools
import heapq
import re

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from .trace_reduce import CLOCK_SLACK_NS, Reduced, _union, describe

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
SPAN_PREFIXES = ("bench.", "repro.")
WINDOW_SPAN = "bench.window"
_MODULE_ID = re.compile(r"\(\d+\)$")

# (message, [(field, number, type, repeated, message type)]); a map field is
# read as the repeated entry message it is on the wire.
_T = descriptor_pb2.FieldDescriptorProto
_SCHEMA = [
    ("XSpace", [("planes", 1, _T.TYPE_MESSAGE, True, "XPlane")]),
    ("XPlane", [("name", 2, _T.TYPE_STRING, False, None),
                ("lines", 3, _T.TYPE_MESSAGE, True, "XLine"),
                ("event_metadata", 4, _T.TYPE_MESSAGE, True, "EventMetadataEntry"),
                ("stat_metadata", 5, _T.TYPE_MESSAGE, True, "StatMetadataEntry")]),
    ("EventMetadataEntry", [("key", 1, _T.TYPE_INT64, False, None),
                            ("value", 2, _T.TYPE_MESSAGE, False, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, _T.TYPE_INT64, False, None),
                           ("value", 2, _T.TYPE_MESSAGE, False, "XStatMetadata")]),
    ("XLine", [("name", 2, _T.TYPE_STRING, False, None),
               ("timestamp_ns", 3, _T.TYPE_INT64, False, None),
               ("events", 4, _T.TYPE_MESSAGE, True, "XEvent")]),
    ("XEvent", [("metadata_id", 1, _T.TYPE_INT64, False, None),
                ("offset_ps", 2, _T.TYPE_INT64, False, None),
                ("duration_ps", 3, _T.TYPE_INT64, False, None)]),
    ("XStat", [("metadata_id", 1, _T.TYPE_INT64, False, None),
               ("str_value", 5, _T.TYPE_STRING, False, None),
               ("ref_value", 7, _T.TYPE_UINT64, False, None)]),
    ("XEventMetadata", [("name", 2, _T.TYPE_STRING, False, None),
                        ("stats", 5, _T.TYPE_MESSAGE, True, "XStat")]),
    ("XStatMetadata", [("name", 2, _T.TYPE_STRING, False, None)]),
]


@functools.lru_cache(maxsize=None)
def _space_class():
    package = "bench_xplane"
    fd = descriptor_pb2.FileDescriptorProto(name=f"{package}.proto",
                                            package=package, syntax="proto3")
    for name, fields in _SCHEMA:
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated, mtype in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=_T.LABEL_REPEATED if repeated else _T.LABEL_OPTIONAL)
            if mtype:
                f.type_name = f".{package}.{mtype}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{package}.XSpace"))


def read_space(path: str):
    """The ``XSpace`` message of an ``.xplane.pb`` file."""
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _events(plane, line_names) -> list:
    """(start ns, end ns, metadata id) of each event on the lines named in
    ``line_names``, in whole nanoseconds as ``ProfileData`` gives them."""
    out = []
    for line in plane.lines:
        if line.name in line_names:
            t = line.timestamp_ns
            for e in line.events:
                a = t + e.offset_ps // 1000
                out.append((a, a + e.duration_ps // 1000, e.metadata_id))
    return out


def _tf_op(md, stat_names: dict) -> str:
    """The op path of one event's metadata, without its ``:type`` suffix;
    empty where the op has none (XLA made it)."""
    for s in md.stats:
        if stat_names.get(s.metadata_id) == "tf_op":
            value = s.str_value or stat_names.get(s.ref_value, "")
            return value.rpartition(":")[0] if ":" in value else value
    return ""


def _device_planes(space) -> list:
    return [p for p in space.planes if DEVICE_PLANE.fullmatch(p.name)]


def device_ops(space) -> list:
    """Per TPU plane, the ``XLA Ops`` events as (start ns, end ns, HLO text,
    op path)."""
    out = []
    for plane in _device_planes(space):
        names = {s.key: s.value.name for s in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        info = {}
        events = []
        for a, b, k in _events(plane, ("XLA Ops",)):
            if k not in info:
                info[k] = (meta[k].name, _tf_op(meta[k], names))
            events.append((a, b) + info[k])
        out.append(events)
    return out


def device_modules(space) -> list:
    """Per TPU plane, the ``XLA Modules`` events as (start ns, end ns,
    program name without its id)."""
    out = []
    for plane in _device_planes(space):
        name = {e.key: _MODULE_ID.sub("", e.value.name) for e in plane.event_metadata}
        out.append([(a, b, name[k]) for a, b, k in _events(plane, ("XLA Modules",))])
    return out


def host_spans(space) -> list:
    """(start ns, end ns, name) of every ``bench.*`` and ``repro.*`` host
    span."""
    out = []
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            spans = {e.key: e.value.name for e in plane.event_metadata
                     if e.value.name.startswith(SPAN_PREFIXES)}
            lines = {line.name for line in plane.lines}
            out.extend((a, b, spans[k]) for a, b, k in _events(plane, lines)
                       if k in spans)
    return out


def name_gaps(gaps, spans) -> list:
    """(seconds, the innermost span other than the window holding the gap's
    midpoint) of each (start ns, end ns) gap, longest first."""
    spans = sorted(s for s in spans if s[2] != WINDOW_SPAN)
    open_, i, named = [], 0, []
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (a + b) / 2
        while i < len(spans) and spans[i][0] <= mid:
            heapq.heappush(open_, (-spans[i][0], spans[i][1], spans[i][2]))
            i += 1
        while open_ and open_[0][1] < mid:     # ended before this midpoint
            heapq.heappop(open_)
        # the latest-starting span still open holds the midpoint (spans that
        # ended earlier but started later are popped once they reach the top)
        named.append(((b - a) / 1e9, open_[0][2] if open_ else "outside any span"))
    named.sort(key=lambda g: -g[0])
    return named


def reduce(path: str, window_span: str = WINDOW_SPAN) -> Reduced:
    """The traced window of ``path`` reduced in one pass: what
    ``trace_reduce.reduce_trace`` gives (busy time, each op's self time,
    Pallas calls by result signature, idle gaps), with the gaps named by
    the innermost ``bench.*`` or ``repro.*`` span, and device self seconds
    under each named scope, calls and device seconds per program, and self
    seconds of path-less ops by result signature."""
    space = read_space(path)
    spans = host_spans(space)
    windows = [(a, b) for a, b, n in spans if n == window_span]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {window_span!r} span, "
                         f"found {len(windows)}")
    ops = device_ops(space)
    if not ops:
        raise ValueError(f"{path}: no TPU device plane with XLA Ops")
    lo, hi = windows[0][0] - CLOCK_SLACK_NS, windows[0][1] + CLOCK_SLACK_NS
    self_s, scope_s, unscoped_s = (collections.Counter() for _ in range(3))
    kernels = collections.defaultdict(lambda: [0, 0.0])
    described, busy, gaps = {}, [], []
    for events in ops:
        ev = sorted((max(a, lo), min(b, hi), hlo, op) for a, b, hlo, op in events
                    if b > lo and a < hi)
        busy.append(_union((a, b) for a, b, *_ in ev))
        stack = []                          # [end, hlo, path, child, duration]
        for a, b, hlo, op in ev + [(hi, hi, None, None)]:
            while stack and a >= stack[-1][0]:
                _, text, opath, child, dur = stack.pop()
                own = (dur - child) / 1e9
                short, sig, _ = described[text]
                self_s[short] += own
                for scope in set(opath.split("/")[:-1]):
                    scope_s[scope] += own
                if not opath:
                    unscoped_s[sig] += own
                if stack:
                    stack[-1][3] += dur
            if hlo is None:
                break
            if hlo not in described:
                described[hlo] = describe(hlo)
            _, sig, pallas = described[hlo]
            if pallas:
                kernels[sig][0] += 1
                kernels[sig][1] += (b - a) / 1e9
            stack.append([b, hlo, op, 0.0, b - a])
        if events is ops[0]:
            end = lo
            for a, b, *_ in ev:
                if a > end:
                    gaps.append((end, a))
                end = max(end, b)
            if hi > end:
                gaps.append((end, hi))
    modules = collections.defaultdict(lambda: [0, 0.0])
    for events in device_modules(space):
        for a, b, name in events:
            if b > lo and a < hi:
                modules[name][0] += 1
                modules[name][1] += (min(b, hi) - max(a, lo)) / 1e9
    n = len(ops)
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n / 1e9,
                   n_devices=n, self_s={k: v / n for k, v in self_s.items()},
                   kernels={k: [c, s / n] for k, (c, s) in kernels.items()},
                   gaps=name_gaps(gaps, spans),
                   scope_s={k: v / n for k, v in scope_s.items()},
                   modules={k: [c // n, s / n] for k, (c, s) in modules.items()},
                   unscoped_s={k: v / n for k, v in unscoped_s.items()})

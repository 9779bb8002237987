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

``reduce_layers`` gives, inside the ``bench.window`` span: device
self-seconds per named scope, calls and device seconds per program, and
the idle gaps
named after the innermost ``bench.*`` or ``repro.*`` host span at their
midpoint. Times are on the same clock as ``trace_reduce``'s.

An op is under scope ``s`` when ``s`` is one of the scopes of its path
(``a/s/op`` or ``s/op``). Inside a loop body (a ``lax.scan``) JAX names
ops from the body's own root, so ``kv_pool/dynamic_slice`` with no
``jit(...)/`` before it; a scope opened outside the loop does not reach the
ops inside it.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import re

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

from .trace_reduce import CLOCK_SLACK_NS

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


def _events(plane, line_name: str) -> list:
    """(start ns, end ns, event metadata) of each event on the named line,
    in whole nanoseconds as ``ProfileData`` gives them."""
    meta = {e.key: e.value for e in plane.event_metadata}
    out = []
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                a = line.timestamp_ns + e.offset_ps // 1000
                out.append((a, a + e.duration_ps // 1000, meta[e.metadata_id]))
    return out


def _tf_op(md, stat_names: dict) -> str:
    """The op path of one event's metadata, without its ``:type`` suffix;
    empty where the op has none (XLA made it)."""
    for s in md.stats:
        if stat_names.get(s.metadata_id) == "tf_op":
            value = s.str_value or stat_names.get(s.ref_value, "")
            return value.rpartition(":")[0] if ":" in value else value
    return ""


def device_ops(space) -> list:
    """Per TPU plane, the ``XLA Ops`` events as (start ns, end ns, HLO text,
    op path)."""
    out = []
    for plane in space.planes:
        if DEVICE_PLANE.fullmatch(plane.name):
            names = {s.key: s.value.name for s in plane.stat_metadata}
            out.append([(a, b, md.name, _tf_op(md, names))
                        for a, b, md in _events(plane, "XLA Ops")])
    return out


def device_modules(space) -> list:
    """Per TPU plane, the ``XLA Modules`` events as (start ns, end ns,
    program name without its id)."""
    return [[(a, b, _MODULE_ID.sub("", md.name))
             for a, b, md in _events(plane, "XLA Modules")]
            for plane in space.planes if DEVICE_PLANE.fullmatch(plane.name)]


def host_spans(space) -> list:
    """(start ns, end ns, name) of every ``bench.*`` and ``repro.*`` host
    span."""
    out = []
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for a, b, md in _events(plane, line.name):
                    if md.name.startswith(SPAN_PREFIXES):
                        out.append((a, b, md.name))
    return out


@dataclasses.dataclass
class Layers:
    window_s: float
    scope_s: dict        # named scope -> device self seconds (device mean)
    modules: dict        # program name -> [calls, device seconds]
    gaps: list           # (seconds, innermost host span) longest first


def reduce_layers(path: str, scopes=()) -> Layers:
    """The traced window of ``path`` reduced by ``scopes``, programs and
    host spans."""
    space = read_space(path)
    spans = host_spans(space)
    windows = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {WINDOW_SPAN!r} span, "
                         f"found {len(windows)}")
    ops = device_ops(space)
    if not ops:
        raise ValueError(f"{path}: no TPU device plane")
    lo, hi = windows[0][0] - CLOCK_SLACK_NS, windows[0][1] + CLOCK_SLACK_NS
    scope_s = collections.Counter()
    gaps = []
    for events in ops:
        ev = sorted((max(a, lo), min(b, hi), op) for a, b, _, op in events
                    if b > lo and a < hi)
        stack = []                                   # [end, path, child, dur]
        for a, b, op in ev + [(hi, hi, None)]:
            while stack and a >= stack[-1][0]:
                _, opath, child, dur = stack.pop()
                for s in set(scopes).intersection(opath.split("/")[:-1]):
                    scope_s[s] += (dur - child) / 1e9
                if stack:
                    stack[-1][2] += dur
            if op is None:
                break
            stack.append([b, op, 0.0, b - a])
        if events is ops[0]:
            end = lo
            for a, b, _ in ev:
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
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        inside = [s for s in spans if s[0] <= mid <= s[1] and s[2] != WINDOW_SPAN]
        span = max(inside, key=lambda s: s[0])[2] if inside else "outside any span"
        named.append(((b - a) / 1e9, span))
    named.sort(key=lambda g: -g[0])
    return Layers(window_s=(hi - lo) / 1e9,
                  scope_s={s: scope_s[s] / n for s in scopes},
                  modules={k: [c // n, s / n] for k, (c, s) in modules.items()},
                  gaps=named)

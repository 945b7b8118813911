"""Read a ``jax.profiler`` trace by the names the program gives its own work.

``trace_reduce`` names device time by XLA's instructions and idle gaps by
whatever host event overlaps them. This module adds the program's names:

``scopes``
    Device self-time per executable and per model scope. A device op's
    ``tf_op`` (the op's framework name, on its event metadata) is a path such
    as ``jit(_decode_fn)/while/body/ternary_proj/dot_general``; the op
    belongs to the innermost component that is one of ``SCOPES``. Ops under
    no scope are listed by their instruction's base name (``copy``,
    ``sort``). Self time is an op's time less that of the ops nested inside
    it, so the layer scan's ``while`` no longer counts its body twice.
``spans``
    Seconds and count of each program span (``serve.*``, ``backlog.*``,
    ``client.*``) inside the window.
``by_span``
    Device seconds of the executables launched inside each span, nested
    spans included: a device run carries a ``run_id``, which the host's
    ``DoEnqueueProgram`` event that launched it carries too; that event is
    tied (through the ``_c`` / ``_p`` flows of the events around it) to the
    Python thread's line, and the spans open there at that moment get the
    run.
``idle_gaps``
    ``trace_reduce``'s ten longest idle gaps, each named by the innermost
    ``serve.*`` span that overlaps it, or as ``trace_reduce`` names it where
    none does.

Everything is clipped to the window ``trace_reduce`` uses. The event
metadata that ``jax.profiler.ProfileData`` does not expose is decoded from
the XPlane protobuf schema, declared here for ``google.protobuf``. The
scope list is the benchmark's own copy: yardstick modules import nothing of
the program.

    python -m chipbench.trace_names <trace.xplane.pb>

prints the reduction as JSON.
"""
from __future__ import annotations

import collections
import functools
import json
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

from chipbench import trace_reduce

#: The program's model scopes (``jax.named_scope`` names).
SCOPES = ("ternary_proj", "lora", "attn", "kv_append", "lm_head", "embed")
#: Prefixes of the program's host spans, one per thread kind.
SPAN_PREFIXES = ("serve.", "backlog.", "client.")
#: The dispatch thread's spans: these name idle gaps.
SERVE = "serve."
#: Host event that launches one device run, carrying its ``run_id``.
ENQUEUE = "DoEnqueueProgram"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


class Event(NamedTuple):
    name: str
    start: int              # ns, on the trace's clock
    end: int
    stats: dict             # the event's own stats over its metadata's


class Line(NamedTuple):
    id: int
    name: str
    events: List[Event]


class Plane(NamedTuple):
    name: str
    lines: List[Line]


# -- the XPlane schema ---------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _xspace_class():
    """Message class of ``tensorflow.profiler.XSpace``, declared from its
    field numbers (tsl/profiler/protobuf/xplane.proto)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, f64 = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    s, b, msg = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench", syntax="proto3")

    def message(name, fields, oneof=None, parent=None):
        m = (parent.nested_type if parent else fd.message_type).add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, typ, label, *rest in fields:
            f = m.field.add(name=fname, number=number, type=typ, label=label)
            if typ == msg:
                f.type_name = ".chipbench." + rest[0]
            elif rest:
                f.oneof_index = 0
        return m

    message("XStat", [("metadata_id", 1, i64, one),
                      ("double_value", 2, f64, one, 0),
                      ("uint64_value", 3, u64, one, 0),
                      ("int64_value", 4, i64, one, 0),
                      ("str_value", 5, s, one, 0),
                      ("bytes_value", 6, b, one, 0),
                      ("ref_value", 7, u64, one, 0)], oneof="value")
    message("XEvent", [("metadata_id", 1, i64, one),
                       ("offset_ps", 2, i64, one, 0),
                       ("num_occurrences", 5, i64, one, 0),
                       ("duration_ps", 3, i64, one),
                       ("stats", 4, msg, rep, "XStat")], oneof="data")
    message("XLine", [("id", 1, i64, one), ("display_id", 10, i64, one),
                      ("name", 2, s, one), ("display_name", 11, s, one),
                      ("timestamp_ns", 3, i64, one),
                      ("duration_ps", 9, i64, one),
                      ("events", 4, msg, rep, "XEvent")])
    message("XEventMetadata", [("id", 1, i64, one), ("name", 2, s, one),
                               ("display_name", 4, s, one),
                               ("metadata", 3, b, one),
                               ("stats", 5, msg, rep, "XStat"),
                               ("child_id", 6, i64, rep)])
    message("XStatMetadata", [("id", 1, i64, one), ("name", 2, s, one),
                              ("description", 3, s, one)])
    plane = message("XPlane", [
        ("id", 1, i64, one), ("name", 2, s, one),
        ("lines", 3, msg, rep, "XLine"),
        ("event_metadata", 4, msg, rep, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, msg, rep, "XPlane.StatMetadataEntry"),
        ("stats", 6, msg, rep, "XStat")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        m = message(entry, [("key", 1, i64, one),
                            ("value", 2, msg, one, value)], parent=plane)
        m.options.map_entry = True
    message("XSpace", [("planes", 1, msg, rep, "XPlane"),
                       ("errors", 2, s, rep), ("warnings", 3, s, rep),
                       ("hostnames", 4, s, rep)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.XSpace"))


def _stats(plane, stats) -> dict:
    out = {}
    for st in stats:
        kind = st.WhichOneof("value")
        if kind is None or kind == "bytes_value":
            continue
        v = getattr(st, kind)
        out[plane.stat_metadata[st.metadata_id].name] = (
            plane.stat_metadata[v].name if kind == "ref_value" else v)
    return out


def load(path: str) -> List[Plane]:
    """The host and device planes of an ``*.xplane.pb``, each event with
    its name, start and end (ns) and stats."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = []
    for p in space.planes:
        if not p.name.startswith(("/host:", "/device:")):
            continue
        meta = {k: (m.name, _stats(p, m.stats))
                for k, m in p.event_metadata.items()}
        lines = []
        for ln in p.lines:
            events = []
            for ev in ln.events:
                name, mstats = meta.get(ev.metadata_id, ("", {}))
                # whole ns, as ``ProfileData`` gives them to trace_reduce
                start = (ln.timestamp_ns * 1000 + ev.offset_ps) // 1000
                events.append(Event(name, start,
                                    start + ev.duration_ps // 1000,
                                    {**mstats, **_stats(p, ev.stats)}))
            lines.append(Line(ln.id, ln.name, events))
        planes.append(Plane(p.name, lines))
    return planes


# -- the reduction ---------------------------------------------------------------


def scope_of(tf_op: str, scopes=SCOPES) -> Optional[str]:
    """The innermost component of an op's framework path that is a scope."""
    for part in reversed(tf_op.split(":", 1)[0].split("/")):
        if part in scopes:
            return part
    return None


def _self_times(events, lo: float, hi: float):
    """(event, seconds) for each event of one line, clipped to [lo, hi),
    less the clipped time of the events nested directly inside it."""
    clip = [max(min(e.end, hi) - max(e.start, lo), 0.0) for e in events]
    own = list(clip)
    stack: List[int] = []
    for i in sorted(range(len(events)),
                    key=lambda i: (events[i].start, -events[i].end)):
        e = events[i]
        while stack and not (events[stack[-1]].start <= e.start
                             and e.end <= events[stack[-1]].end):
            stack.pop()
        if stack:
            own[stack[-1]] -= clip[i]
        stack.append(i)
    return [(e, max(t, 0.0) * 1e-9) for e, t in zip(events, own)]


def _window(host: List[Event], devices) -> Tuple[float, float]:
    marks = [e for e in host if e.name == trace_reduce.WINDOW]
    if marks:
        return marks[0].start, marks[0].end
    spans = [(e.start, e.end) for lines in devices
             for e in lines[trace_reduce.OPS_LINE].events]
    return (min(s for s, _ in spans), max(e for _, e in spans)) if spans \
        else (0.0, 0.0)


def _launchers(host_lines: List[Line]) -> Dict[int, Tuple[int, float]]:
    """run_id → (line, time) of the call that launched the run. The host
    event that enqueued the run may sit on a runtime thread's line; while it
    lies inside an event that a flow (``_c`` from ``_p``) ties to another
    line, follow the flow to where it started. On a TPU host the chain runs
    from the enqueue on a runtime thread, through the dispatching thread's
    PJRT call, to the Python thread's line that holds the program's spans."""
    flows = {e.stats["_p"]: (ln.id, e.start) for ln in host_lines
             for e in ln.events if "_p" in e.stats}
    cross = collections.defaultdict(list)
    for ln in host_lines:
        for e in ln.events:
            src = flows.get(e.stats.get("_c"))
            if src is not None and src[0] != ln.id:
                cross[ln.id].append(e)
    out = {}
    for ln in host_lines:
        for e in ln.events:
            if e.name != ENQUEUE or "run_id" not in e.stats:
                continue
            line, s, t = ln.id, e.start, e.end
            for _ in range(len(host_lines)):
                links = [o for o in cross[line] if o.start <= s and t <= o.end]
                if not links:
                    break
                line, s = flows[min(links, key=lambda o: o.end - o.start)
                                .stats["_c"]]
                t = s
            out[e.stats["run_id"]] = (line, s)
    return out


def reduce_planes(planes, scopes=SCOPES) -> Dict:
    """``scopes``, ``spans``, ``by_span`` and ``idle_gaps`` of parsed
    planes (:func:`load`), in seconds."""
    host_lines = [ln for p in planes if p.name.startswith("/host:")
                  for ln in p.lines]
    host = [e for ln in host_lines for e in ln.events]
    devices = []
    for p in planes:
        if p.name.startswith("/device:"):
            lines = {ln.name: ln for ln in p.lines}
            if trace_reduce.OPS_LINE in lines:
                devices.append(lines)
    lo, hi = _window(host, devices)

    programs = {}
    for lines in devices:
        for e in getattr(lines.get(trace_reduce.MODULES_LINE), "events", ()):
            m = _PROGRAM_ID.search(e.name)
            if m:
                programs[int(m.group(1))] = trace_reduce.base_name(e.name)
    by_scope: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    for lines in devices:
        for e, t in _self_times(lines[trace_reduce.OPS_LINE].events, lo, hi):
            if t <= 0:
                continue
            exe = programs.get(e.stats.get("program_id"), "?")
            name = scope_of(str(e.stats.get("tf_op", "")), scopes) \
                or trace_reduce.base_name(e.name)
            by_scope[exe][name] += t

    spans: Dict[str, List] = {}
    open_spans: Dict[int, List[Event]] = collections.defaultdict(list)
    for ln in host_lines:
        for e in ln.events:
            if not e.name.startswith(SPAN_PREFIXES):
                continue
            open_spans[ln.id].append(e)
            t = min(e.end, hi) - max(e.start, lo)
            if t > 0 or lo <= e.start < hi:
                s, n = spans.get(e.name, (0.0, 0))
                spans[e.name] = [s + max(t, 0.0) * 1e-9, n + 1]

    launched = _launchers(host_lines)
    by_span: Dict[str, float] = collections.defaultdict(float)
    for lines in devices:
        for e in getattr(lines.get(trace_reduce.MODULES_LINE), "events", ()):
            t = min(e.end, hi) - max(e.start, lo)
            at = launched.get(e.stats.get("run_id"))
            if t <= 0 or at is None:
                continue
            line, when = at
            for sp in {s.name for s in open_spans[line]
                       if s.start <= when < s.end}:
                by_span[sp] += t * 1e-9

    gaps: List[Tuple[float, float]] = []
    if devices:
        ops = [(max(e.start, lo), min(e.end, hi))
               for e in devices[0][trace_reduce.OPS_LINE].events
               if min(e.end, hi) > max(e.start, lo)]
        ends = [(lo, lo)] + trace_reduce._union(ops) + [(hi, hi)]
        gaps = sorted(((a[1], b[0]) for a, b in zip(ends, ends[1:])
                       if b[0] > a[1]), key=lambda g: g[0] - g[1])[:10]
    serve = [e for e in host if e.name.startswith(SERVE)]
    others = [(e.name, e.start, e.end) for e in host
              if e.name != trace_reduce.WINDOW]
    return {
        "scopes": {k: dict(v) for k, v in by_scope.items()},
        "spans": spans,
        "by_span": dict(by_span),
        "idle_gaps": [(label(s, e, serve, others), (e - s) * 1e-9)
                      for s, e in gaps],
    }


def label(s: float, e: float, serve: List[Event], host) -> str:
    """The innermost ``serve.*`` span overlapping [s, e) (most overlap,
    then shortest), else ``trace_reduce``'s name for the gap."""
    best, best_key = None, (0.0, 0.0)
    for sp in serve:
        ov = min(e, sp.end) - max(s, sp.start)
        if ov > 0:
            key = (ov, -(sp.end - sp.start))
            if key > best_key:
                best, best_key = sp.name, key
    return best or trace_reduce._label(s, e, host)


def decode_scope(red: Dict, scope: str) -> Tuple[int, float]:
    """Runs of the decode executable in the window, and its device
    self-time under ``scope``; (0, 0.0) where the reduction has no scopes."""
    runs = sum(v for k, v in red.get("module_runs", {}).items()
               if "decode_fn" in k)
    t = sum(v.get(scope, 0.0) for k, v in red.get("scopes", {}).items()
            if "decode_fn" in k)
    return (runs, t) if "scopes" in red else (0, 0.0)


def reduce_file(path: str) -> Dict:
    """``trace_reduce.reduce_file`` with this module's keys added and its
    idle gaps named by the program's spans."""
    red = trace_reduce.reduce_file(path)
    red.update(reduce_planes(load(path)))
    return red


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1, default=str))

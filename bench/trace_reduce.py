"""From a profiler trace (``.xplane.pb``) to device busy time, stage times
and the run's ``breakdown``.

The reader builds the XPlane message types from their field numbers (the
``xplane.proto`` schema of the profiler) with ``google.protobuf`` alone, so
it needs neither TensorFlow nor the program.  Every time is taken on the
trace's own clock: a line's ``timestamp_ns`` plus an event's ``offset_ps``.

What a TPU trace holds (TPU v5 lite, JAX 0.9): a plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per executable run),
``XLA Ops`` (one event per HLO instruction run; a ``while`` spans the ops of
its body) and ``Async XLA Ops`` (copies in flight, not compute).  An op's
event metadata carries ``tf_op``, the JAX name stack of the instruction
(``jit(_execute_core)/jit(grid_knn)/...``); ``while`` instructions may lack
it, and their body ops carry it.  Host threads are the lines of
``/host:CPU``; the benchmark's own ``TraceAnnotation``s are events there.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_SCHEMA = {   # message -> [(field, number, type, label, message type)]
    "XSpace": [("planes", 1, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XPlane")],
    "XPlane": [("id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
               ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
               ("lines", 3, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XLine"),
               ("event_metadata", 4, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
                "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
                "XPlane.StatMetadataEntry")],
    "XLine": [("id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
              ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
              ("timestamp_ns", 3, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
              ("events", 4, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XEvent")],
    "XEvent": [("metadata_id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
               ("offset_ps", 2, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
               ("duration_ps", 3, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None)],
    "XStat": [("metadata_id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
              ("str_value", 5, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None)],
    "XEventMetadata": [("id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
                       ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
                       ("display_name", 4, _F.TYPE_STRING, _F.LABEL_OPTIONAL,
                        None),
                       ("stats", 5, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
                        "XStat")],
    "XStatMetadata": [("id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
                      ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None)],
}
_MAPS = {"XPlane": [("EventMetadataEntry", "XEventMetadata"),
                    ("StatMetadataEntry", "XStatMetadata")]}


def _xspace():
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                            package="benchxplane",
                                            syntax="proto3")

    def add_fields(msg, fields):
        for name, num, typ, label, ref in fields:
            f = msg.field.add(name=name, number=num, type=typ, label=label)
            if ref:
                f.type_name = f".benchxplane.{ref}"

    for name, fields in _SCHEMA.items():
        msg = fd.message_type.add(name=name)
        add_fields(msg, fields)
        for entry, value in _MAPS.get(name, ()):
            sub = msg.nested_type.add(name=entry)
            sub.options.map_entry = True
            add_fields(sub, [("key", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL,
                              None),
                             ("value", 2, _F.TYPE_MESSAGE, _F.LABEL_OPTIONAL,
                              value)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchxplane.XSpace"))


@dataclass
class Event:
    start_ps: int
    end_ps: int
    name: str
    tf_op: str = ""


@dataclass
class Trace:
    device: dict = field(default_factory=dict)   # plane -> [Event] (XLA Ops)
    host: dict = field(default_factory=dict)     # name#id -> [Event]


def read(path) -> Trace:
    """Device op events of every ``/device:*`` plane and host events of
    every ``/host:CPU`` line, on the trace's clock in picoseconds."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = Trace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU")
        if not device and plane.name != "/host:CPU":
            continue
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        for k, md in plane.event_metadata.items():
            tf_op = next((s.str_value for s in md.stats
                          if stat_name.get(s.metadata_id) == "tf_op"), "")
            meta[k] = (md.display_name or md.name, tf_op)
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            t0 = line.timestamp_ns * 1000
            evs = [Event(t0 + e.offset_ps, t0 + e.offset_ps + e.duration_ps,
                         *meta.get(e.metadata_id, ("?", "")))
                   for e in line.events]
            if device:
                out.device.setdefault(plane.name, []).extend(evs)
            else:
                out.host[f"{line.name}#{line.id}"] = evs
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merge overlapping ``(start, end)`` intervals, sorted by start."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    return sum(max(0, min(e, hi) - max(s, lo))
               for s, e in union(intervals))


def window_of(trace: Trace, name: str) -> tuple[int, int]:
    """The span of the host annotation ``name`` (the measured window)."""
    spans = [(e.start_ps, e.end_ps) for evs in trace.host.values()
             for e in evs if e.name == name]
    if not spans:
        raise ValueError(f"no host event {name!r} in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy_ps(trace: Trace, lo: int, hi: int) -> float:
    """Device time in ``[lo, hi]`` in which some op ran, averaged over the
    device planes."""
    planes = list(trace.device.values())
    if not planes:
        return 0.0
    return sum(covered([(e.start_ps, e.end_ps) for e in evs], lo, hi)
               for evs in planes) / len(planes)


def stage_ps(trace: Trace, lo: int, hi: int, scopes) -> float:
    """Device time in ``[lo, hi]`` of the ops whose JAX name stack holds one
    of ``scopes`` (e.g. ``"jit(grid_knn)"``), averaged over device planes."""
    planes = list(trace.device.values())
    if not planes:
        return 0.0
    return sum(covered([(e.start_ps, e.end_ps) for e in evs
                        if any(s in e.tf_op for s in scopes)], lo, hi)
               for evs in planes) / len(planes)


def _short(name: str) -> str:
    """An HLO op event is named by its whole instruction text; keep the
    instruction's name (``%fusion.77 = ...`` -> ``fusion.77``)."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def top_ops(trace: Trace, lo: int, hi: int, n: int = 10) -> list:
    """The ``n`` device ops with the most time in the window, as
    ``[name, seconds]`` summed over their runs (first device plane)."""
    evs = next(iter(trace.device.values()), [])
    total: dict[str, int] = {}
    for e in evs:
        d = max(0, min(e.end_ps, hi) - max(e.start_ps, lo))
        if d:
            key = _short(e.name)
            total[key] = total.get(key, 0) + d
    return [[k, v / 1e12] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, lo: int, hi: int, labels, n: int = 10) -> list:
    """The ``n`` longest stretches of the window with no op on the first
    device, as ``(label, start_ps, end_ps)``.  The label names what the
    host was doing: the benchmark's annotation (names in ``labels``) that
    overlaps the gap most, else the host event that overlaps it most."""
    evs = next(iter(trace.device.values()), [])
    busy = union([(max(e.start_ps, lo), min(e.end_ps, hi)) for e in evs
                  if e.end_ps > lo and e.start_ps < hi])
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [e for line in trace.host.values() for e in line]
    mine = [e for e in host if e.name in labels]
    out = []
    for s, e in gaps:
        label = "idle"
        for pool in (mine, host):
            best = max(pool, default=None,
                       key=lambda h: min(h.end_ps, e) - max(h.start_ps, s))
            if best is not None and min(best.end_ps, e) > max(best.start_ps,
                                                              s):
                label = best.name
                break
        out.append((label, s, e))
    return out

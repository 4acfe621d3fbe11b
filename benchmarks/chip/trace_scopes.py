"""The program's own names in a profiler trace: scope paths of device ops
and the engine's host spans, beside what ``trace_reduce`` reads.

The program marks its layers in two ways (DESIGN.md §18):

- **named scopes**: ``jax.named_scope`` names (``sample``, ``kv_alloc``,
  ``cgmq_stats``, ``cgmq_controller``, ``fake_quant``, ``adam``, the model's
  ``attn`` / ``ffn`` ...) in each device op's ``tf_op`` stat, e.g.
  ``jit(f)/sample/jit(sort)/sort:``. On a TPU v5e trace (jax 0.9.0) that
  stat sits on the op's event *metadata*, which ``ProfileData`` does not
  show (its ``XLA Ops`` events carry only ``device_offset_ps``,
  ``device_duration_ps`` and ``Time Scale Multiplier``);
- **host spans** on ``/host:CPU``: the engine's ``engine.*`` spans, with
  the ids they were opened with, beside JAX's own compile span
  ``backend_compile_and_load`` and the benchmark's spans
  (``trace_reduce.HOST_SPANS``).

``load`` reads the file once and gives ``trace_reduce.load``'s plain data
with each event's kept stats taken from the event and its own metadata
(by metadata id), so ``trace_reduce.reduce`` reads it unchanged. The
functions below it read what that reduction leaves out.
"""

from __future__ import annotations

import functools
import re
from pathlib import Path

import trace_reduce

SCOPE_STAT = "tf_op"
ENGINE_PREFIX = "engine."
COMPILE_SPAN = "backend_compile_and_load"
SPAN_IDS = ("rid", "slot", "tokens", "kind")
KEEP_STATS = trace_reduce.KEEP_STATS + SPAN_IDS
TICK_MODULE = re.compile(r"^jit__tick\b")
TRAIN_MODULE = re.compile(r"^jit_train_step\b")
SAMPLING = ("sample",)
CGMQ = ("cgmq_stats", "cgmq_controller")


@functools.cache
def _xspace():
    """Message class of ``XSpace`` (tsl's ``xplane.proto``): the fields
    this module reads, under their numbers there."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    I64, U64, STR = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING
    f = descriptor_pb2.FileDescriptorProto(name="chipbench_xplane.proto",
                                           package="chipbench")

    def msg(name, *fields):
        """``fields``: ``(name, number, type)``, or ``(name, number,
        message, repeated)`` for a message field."""
        m = f.message_type.add(name=name)
        for fname, num, typ, *rep in fields:
            fd = m.field.add(name=fname, number=num, label=F.LABEL_OPTIONAL)
            if isinstance(typ, str):
                fd.type, fd.type_name = F.TYPE_MESSAGE, ".chipbench." + typ
                if rep[0]:
                    fd.label = F.LABEL_REPEATED
            else:
                fd.type = typ

    msg("XStat", ("metadata_id", 1, I64), ("double_value", 2, F.TYPE_DOUBLE),
        ("uint64_value", 3, U64), ("int64_value", 4, I64),
        ("str_value", 5, STR), ("ref_value", 7, U64))
    msg("XEvent", ("metadata_id", 1, I64), ("offset_ps", 2, I64),
        ("duration_ps", 3, I64), ("stats", 4, "XStat", True))
    msg("XLine", ("name", 2, STR), ("timestamp_ns", 3, I64),
        ("events", 4, "XEvent", True))
    msg("XEventMetadata", ("id", 1, I64), ("name", 2, STR),
        ("stats", 5, "XStat", True))
    msg("XStatMetadata", ("id", 1, I64), ("name", 2, STR))
    # map<int64, ...> fields, read as their repeated entries
    msg("EventEntry", ("key", 1, I64), ("value", 2, "XEventMetadata", False))
    msg("StatEntry", ("key", 1, I64), ("value", 2, "XStatMetadata", False))
    msg("XPlane", ("name", 2, STR), ("lines", 3, "XLine", True),
        ("event_metadata", 4, "EventEntry", True),
        ("stat_metadata", 5, "StatEntry", True))
    msg("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.XSpace"))


def _value(st, names: dict):
    """An ``XStat``'s value; a reference reads the stat name it points
    to."""
    if st.HasField("ref_value"):
        return names.get(st.ref_value, "")
    for field in ("str_value", "int64_value", "uint64_value"):
        if st.HasField(field):
            return getattr(st, field)
    return st.double_value


def _stats(stats, names: dict, out: dict) -> dict:
    for st in stats:
        name = names.get(st.metadata_id)
        if name in KEEP_STATS:
            out[name] = _value(st, names)
    return out


def load(path) -> dict:
    """Plain data of one ``.xplane.pb`` as ``trace_reduce.load`` gives it
    (``[name, start_ns, duration_ns, stats]`` per event), each event's
    stats holding the kept ones of its metadata and then its own."""
    space = _xspace()()
    space.ParseFromString(Path(path).read_bytes())
    planes = []
    for pl in space.planes:
        names = {e.key: e.value.name for e in pl.stat_metadata}
        meta = {e.key: (e.value.name, _stats(e.value.stats, names, {}))
                for e in pl.event_metadata}
        lines = []
        for ln in pl.lines:
            evs = []
            for e in ln.events:
                name, mstats = meta.get(e.metadata_id, ("", {}))
                # whole ns, as ProfileData gives them
                evs.append([name, float(ln.timestamp_ns + e.offset_ps // 1000),
                            float(e.duration_ps // 1000),
                            _stats(e.stats, names, dict(mstats))])
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def scope_path(ev) -> str:
    """The op's scope path (``jit(_tick)/sample/sort:``), or ``""``."""
    return str(ev[3].get(SCOPE_STAT, "")) if len(ev) > 3 else ""


def scope_name(component: str) -> str:
    """A path component without the transformations JAX wraps it in
    (``transpose(jvp(cgmq_stats))`` -> ``cgmq_stats``)."""
    return component.rstrip(")").rsplit("(", 1)[-1]


def under(path: str, scopes) -> bool:
    """Whether any of ``scopes`` names a component of ``path``, also
    inside a transformation (the backward of a scope's ops)."""
    parts = {scope_name(c) for c in path.split("/")}
    return any(s in parts for s in scopes)


def is_program_span(name: str) -> bool:
    return name.startswith(ENGINE_PREFIX) or name == COMPILE_SPAN


def host_events(data: dict) -> list:
    return [e for pl in data["planes"] if pl["name"] == trace_reduce.HOST_PLANE
            for ln in pl["lines"] for e in ln["events"]]


def span_label(ev) -> str:
    """A host span's name, with the sync kind where it has one
    (``engine.sync kind=tick``)."""
    kind = ev[3].get("kind") if len(ev) > 3 else None
    return f"{ev[0]} kind={kind}" if kind else ev[0]


def spans(data: dict, program_only: bool = False) -> list:
    """``(start, end, label)`` of the host spans of ``data``: the program's
    (``engine.*`` and compile) and, unless ``program_only``, the
    benchmark's."""
    return sorted((e[1], e[1] + e[2], span_label(e)) for e in host_events(data)
                  if is_program_span(e[0]) or (
                      not program_only and e[0] in trace_reduce.HOST_SPANS))


def chip0_ops(data: dict) -> list:
    devs = trace_reduce.device_planes(data)
    if 0 not in devs:
        raise ValueError("trace holds no /device:TPU:0 plane")
    return trace_reduce._line(devs[0], trace_reduce.OPS_LINE)


def idle_gaps(data: dict) -> list:
    """``(start, end)`` of chip 0's idle gaps between busy intervals, as
    ``trace_reduce.reduce`` finds them."""
    merged = trace_reduce.merge((o[1], o[1] + o[2]) for o in chip0_ops(data))
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:])]


def innermost(spans_, t: float):
    """The label of the shortest of ``spans_`` covering ``t``, or None."""
    cover = [(b - a, name) for a, b, name in spans_ if a <= t <= b]
    return min(cover)[1] if cover else None


def label_gaps(gaps, spans_) -> list:
    """``[label, seconds]`` per gap, longest first: the innermost of
    ``spans_`` covering the gap's midpoint (``trace_reduce.label_gaps``
    with spans of every kind)."""
    out = [[innermost(spans_, (s + e) / 2) or trace_reduce.UNLABELLED,
            (e - s) * 1e-9] for s, e in gaps]
    return sorted(out, key=lambda g: -g[1])


def engine_idle(gaps, program) -> tuple[float, list]:
    """Idle seconds of chip 0 inside the union of the ``program`` spans,
    and the same seconds by the innermost program span covering each gap's
    midpoint, longest first."""
    covered = trace_reduce.merge((a, b) for a, b, _ in program)
    total, by_span = 0.0, {}
    for s, e in gaps:
        inside = 1e-9 * sum(max(0.0, min(e, b) - max(s, a))
                            for a, b in covered)
        if inside > 0:
            total += inside
            name = innermost(program, (s + e) / 2) or "between program spans"
            by_span[name] = by_span.get(name, 0.0) + inside
    return total, sorted(by_span.items(), key=lambda kv: -kv[1])


def engine_idle_share(data: dict, window_s: float) -> float | None:
    """% of the traced window in which chip 0 ran no op while a program
    span was open; None where the trace holds no engine span."""
    program = spans(data, program_only=True)
    if not any(n.startswith(ENGINE_PREFIX) for _, _, n in program):
        return None
    return 100.0 * engine_idle(idle_gaps(data), program)[0] / window_s


def ops_in(data: dict, module: re.Pattern) -> list:
    """``[short name, start, self_ns, scope path]`` of chip 0's ops inside
    the runs of the programs ``module`` matches (self time as
    ``trace_reduce.self_times`` counts it)."""
    devs = trace_reduce.device_planes(data)
    runs = [m for m in trace_reduce._line(devs[0], trace_reduce.MODULES_LINE)
            if module.match(m[0])]
    evs = sorted(chip0_ops(data), key=lambda e: (e[1], -e[2]))
    ops = [[n, s, d, scope_path(e)] for (n, s, d), e in
           zip(trace_reduce.self_times(evs), evs)]
    return trace_reduce.within(ops, runs)


def scope_shares(data: dict, module: re.Pattern) -> dict:
    """``{scope: % of device self time}`` inside the runs of the programs
    ``module`` matches, by every scope seen (the components of each op's
    path between the program's name and the op's own), with the seconds
    counted under ``"_total_s"``."""
    ops = ops_in(data, module)
    total = sum(o[2] for o in ops)
    shares: dict = {}
    for o in ops:
        for part in set(o[3].split("/")[1:-1]):
            shares[part] = shares.get(part, 0.0) + o[2]
    out = {k: 100.0 * v / total for k, v in shares.items()} if total else {}
    out["_total_s"] = total * 1e-9
    return out


def share(data: dict, module: re.Pattern, scopes) -> float | None:
    """% of device self time inside ``module``'s runs spent in ops under
    any of ``scopes``; None where no op carries them (a program without
    these named scopes: the phases measured here run in every step)."""
    ops = ops_in(data, module)
    if not any(under(o[3], scopes) for o in ops):
        return None
    total = sum(o[2] for o in ops)
    return 100.0 * sum(o[2] for o in ops if under(o[3], scopes)) / total


def idle_per_tick_ms(trace: dict) -> float | None:
    """Chip 0's idle gaps in the traced window per decode-tick run, in ms
    (from ``trace_reduce.reduce``'s own keys, so it reads a program without
    spans too)."""
    ticks = [m for m in trace["modules"] if TICK_MODULE.match(m[0])]
    if not ticks:
        return None
    return 1e3 * sum(g for _, g in trace["idle_by_span"]) / len(ticks)

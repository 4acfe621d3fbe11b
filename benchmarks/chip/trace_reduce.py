"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

``load`` turns the file into plain data: planes, their lines, and events
``[name, start_ns, duration_ns, stats]``. ``reduce`` keeps what the
readers need:

- ``busy_s``: the union of device op intervals, averaged over the chips
  used (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane);
- ``ops``: device op events of chip 0 as ``[short name, start_ns,
  dur_ns, name]``, the last the event's whole name (on a TPU the HLO
  instruction, ``%fusion.12 = f32[...] fusion(...), calls=...``);
  ``op_totals``: self seconds per short name (an op that encloses others,
  such as a ``while``, keeps only its own time), longest first;
- ``modules``: the ``XLA Modules`` line of chip 0 (one event per run of a
  compiled program, named after it);
- ``gaps``: the idle gaps of chip 0 between busy intervals, longest first,
  each labelled with the benchmark's innermost host span (see
  ``HOST_SPANS``) that covers its midpoint.
"""

from __future__ import annotations

import glob
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
# spans the benchmark itself opens around its calls into the program
HOST_SPANS = ("submit", "step", "wait_arrival", "train_step", "stage_batch",
              "sync")
UNLABELLED = "no benchmark span"
KEEP_STATS = ("long_name", "hlo_op", "tf_op", "hlo_module", "program_id")


def load(path) -> dict:
    """Plain data of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            evs = []
            for e in ln.events:
                st = {}
                for k, v in e.stats:
                    if k in KEEP_STATS:
                        st[k] = v if isinstance(v, (int, float)) else str(v)
                evs.append([e.name, float(e.start_ns), float(e.duration_ns),
                            st])
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def merge(intervals) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_name(ev) -> str:
    """Short name of a device op: the HLO instruction's name without the
    ``%`` and the numeric suffix XLA gives each instance (``%fusion.12 =
    f32[...] fusion(...)`` -> ``fusion``; a Pallas kernel's custom call
    keeps its kernel's name, e.g. ``quant_matmul_packed_op``)."""
    head = ev[0].split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def self_times(events) -> list:
    """``[name, start, self_ns]`` per event of one line, where an event
    that encloses others (a ``while`` around its body's ops) keeps only
    the time none of them covers."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[op_name(e), e[1], e[2]] for e in evs]
    stack = []  # indices of open enclosing events
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= e[1]:
            stack.pop()
        if stack:
            out[stack[-1]][2] -= e[2]
        stack.append(i)
    return out


def device_planes(data: dict) -> dict:
    out = {}
    for pl in data["planes"]:
        m = DEVICE_PLANE.match(pl["name"])
        if m:
            out[int(m.group(1))] = pl
    return out


def label_gaps(gaps, host_events) -> list:
    """``[label, seconds]`` per gap: the shortest benchmark span that
    covers the gap's midpoint."""
    spans = sorted((e[1], e[1] + e[2], e[0]) for e in host_events
                   if e[0] in HOST_SPANS)
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(b - a, name) for a, b, name in spans if a <= mid <= b]
        out.append([min(cover)[1] if cover else UNLABELLED, (e - s) * 1e-9])
    return out


def reduce(data: dict, n_devices: int, top: int = 10) -> dict:
    devs = device_planes(data)
    if 0 not in devs:
        raise ValueError("trace holds no /device:TPU:0 plane")
    busy = []
    for i in range(n_devices):
        ops = _line(devs.get(i, {"lines": []}), OPS_LINE)
        busy.append(sum(e - s for s, e in merge(
            (o[1], o[1] + o[2]) for o in ops)) * 1e-9)
    ops0 = _line(devs[0], OPS_LINE)
    merged = merge((o[1], o[1] + o[2]) for o in ops0)
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    host = [e for pl in data["planes"] if pl["name"] == HOST_PLANE
            for ln in pl["lines"] for e in ln["events"]]
    totals: dict = {}
    for k, _, dur in self_times(ops0):
        totals[k] = totals.get(k, 0.0) + dur * 1e-9
    labelled = label_gaps(gaps, host)
    labelled.sort(key=lambda g: -g[1])
    by_label: dict = {}
    for lab, sec in labelled:
        by_label[lab] = by_label.get(lab, 0.0) + sec
    return {
        "busy_s": sum(busy) / len(busy),
        "ops": [[op_name(o), o[1], o[2], o[0]] for o in ops0],
        "op_totals": sorted(totals.items(), key=lambda kv: -kv[1]),
        "modules": [[m[0], m[1], m[2]] for m in _line(devs[0],
                                                      MODULES_LINE)],
        "gaps": labelled[:top],
        "idle_by_span": sorted(by_label.items(), key=lambda kv: -kv[1]),
    }


def reduce_dir(trace_dir, n_devices: int) -> dict:
    files = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return reduce(load(files[0]), n_devices)


def within(events, spans) -> list:
    """The events that lie inside any of ``spans`` (``[name, start, dur]``
    lists), e.g. the device ops of one compiled program's runs."""
    iv = sorted((s[1], s[1] + s[2]) for s in spans)
    out, j = [], 0
    for ev in sorted(events, key=lambda e: e[1]):
        while j < len(iv) and iv[j][1] < ev[1]:
            j += 1
        if j < len(iv) and iv[j][0] <= ev[1] and ev[1] + ev[2] <= iv[j][1]:
            out.append(ev)
    return out

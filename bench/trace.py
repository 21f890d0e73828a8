"""Reduction of a profiler trace to device busy time, exposed
collective time and a breakdown.

Works on plain data, so that tests can feed it a synthetic trace:
``devices`` maps a device name to its op events ``(start_ns, end_ns,
name)``, ``host_spans`` is a list of ``(start_ns, end_ns, name)`` of
the harness's own annotations, and ``window`` is the traced interval.
:func:`from_profile` builds that data from a ``jax.profiler``
``.xplane.pb`` file.
"""
from __future__ import annotations

import re

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|collectivepermute|alltoall",
    re.IGNORECASE)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"        # where a trace has no op line


def union(intervals):
    """Merge (start, end) intervals; returns a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e, *_ in intervals
            if e > lo and s < hi]


def intersect(a, b):
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def op_kind(name: str) -> str:
    """An op's name without its instance number: ``fusion.12`` ->
    ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", name)


def reduce_trace(devices: dict, host_spans: list, window: tuple,
                 top: int = 10) -> dict:
    """Busy and idle time per device, the part of collective time with
    no other op running on that device, the ops that took most time,
    and the longest idle gaps of the first device, each named by the
    host span that covers its middle."""
    lo, hi = window
    span = float(hi - lo)
    if span <= 0:
        raise ValueError(f"empty traced window {window}")
    if not devices:
        return {"window_s": span / 1e9, "busy_s": 0.0, "idle_share": None,
                "exposed_collective_s": None,
                "exposed_collective_share": None, "has_collectives": False,
                "device_ops": [], "idle_gaps": []}
    busy, exposed, by_op = [], [], {}
    for name in sorted(devices):
        ops = devices[name]
        busy.append(length(union(clip(ops, lo, hi))))
        coll = union(clip([o for o in ops if COLLECTIVE.search(o[2])],
                          lo, hi))
        other = union(clip([o for o in ops if not COLLECTIVE.search(o[2])],
                           lo, hi))
        exposed.append(length(coll) - length(intersect(coll, other)))
        for s, e, n in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_op[op_kind(n)] = by_op.get(op_kind(n), 0.0) + d
    n_dev = len(devices)
    first = union(clip(devices[sorted(devices)[0]], lo, hi))
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            mid = (s + e) / 2
            label = next((n for hs, he, n in host_spans if hs <= mid < he),
                         "outside harness spans")
            gaps.append([label, (e - s) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    ops_sorted = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": span / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "idle_share": 1.0 - sum(busy) / n_dev / span,
        "exposed_collective_s": sum(exposed) / n_dev / 1e9,
        "exposed_collective_share": sum(exposed) / n_dev / span,
        "has_collectives": any(COLLECTIVE.search(o[2])
                               for ops in devices.values() for o in ops),
        "device_ops": [[k, v / n_dev / 1e9] for k, v in ops_sorted],
        "idle_gaps": gaps[:top],
    }


def cell_planes(planes: dict, device_ids) -> dict:
    """The device planes of the chips a cell uses, ``/device:TPU:<n>``
    for ``n`` in ``device_ids``, out of every TPU plane of the trace.
    The host's other chips are left out: they would read as idle
    whatever the program does."""
    want = {f"/device:TPU:{i}" for i in device_ids}
    missing = want - set(planes)
    if missing:
        raise ValueError(f"the trace has no plane for {sorted(missing)}; "
                         f"it has {sorted(planes)}")
    return {k: v for k, v in planes.items() if k in want}


def from_profile(path: str, window_span: str, device_ids,
                 host_prefix: str = "bench."):
    """(devices, host_spans, window) from an ``.xplane.pb`` file. Device
    planes are those of :func:`cell_planes`; their ops are the events of
    the ``XLA Ops`` line (of ``XLA Modules`` where there is none). Host
    spans are events whose name starts with ``host_prefix``; the window
    is the span named ``window_span``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes, spans = {}, []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {line.name: line for line in plane.lines}
            line = lines.get(OPS_LINE, lines.get(MODULES_LINE))
            planes[plane.name] = [] if line is None else [
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    wins = [(s, e) for s, e, n in spans if n == window_span]
    if not wins:
        raise ValueError(f"no host span {window_span!r} in {path}")
    spans = [s for s in spans if s[2] != window_span]
    return cell_planes(planes, device_ids), spans, wins[0]

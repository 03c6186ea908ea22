"""Reduction of a profiler trace to device busy time, idle gaps and ops.

A trace is held as plain data: a list of planes, each
``{"name": str, "lines": [{"name": str, "events": [[name, start_ns,
duration_ns], ...]}]}``, read from JAX's ``.xplane.pb`` by :func:`load`
(with ``jax.profiler.ProfileData``) or from a JSON copy by
:func:`load_json`. A device is a plane named ``/device:TPU:<n>``; its
operations are the events of its ``XLA Ops`` line. Every other plane
named ``/host:...`` holds what the host threads were doing. An
operation is known by its HLO instruction name (:func:`op_name`).

:func:`reduce` measures, inside a window (the host event the benchmark
opens around the traced part of a run, ``bench.window``):

- each device's busy time: the union of its operations' intervals;
- each device's collective time: its operations whose names are
  all-to-all, all-gather, all-reduce, reduce-scatter or
  collective-permute (with their ``-start``/``-done`` halves);
- the busiest device's longest idle gaps, each labelled by the innermost
  host event (or extra span given by the caller) that covers its middle;
- the busiest device's operations that took most time, by name.
"""

from __future__ import annotations

import gzip
import json
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_EVENT = "bench.window"
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute")
TOP = 10


def load(path: str) -> list[dict]:
    """Planes of an ``.xplane.pb`` file as plain data."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in ln.events]
            lines.append({"name": ln.name, "events": events})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def load_json(path: str) -> list[dict]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def save_json(planes: list[dict], path: str) -> None:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump(planes, f)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, t0: float, t1: float):
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def op_name(event_name: str) -> str:
    """``fusion.9`` of a TPU op event named by its HLO text
    (``%fusion.9 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _device_ops(planes: list[dict]) -> dict[str, list]:
    devices = {}
    for pl in planes:
        if not DEVICE_PLANE.match(pl["name"]):
            continue
        for ln in pl["lines"]:
            if ln["name"] == OPS_LINE:
                devices[pl["name"]] = [[op_name(n), s, d]
                                       for n, s, d in ln["events"]]
    return devices


def host_events(planes: list[dict]) -> list[tuple[str, float, float]]:
    """``(name, start_ns, end_ns)`` of every host event with a duration."""
    out = []
    for pl in planes:
        if not pl["name"].startswith("/host:"):
            continue
        for ln in pl["lines"]:
            out += [(n, s, s + d) for n, s, d in ln["events"] if d > 0]
    return out


def window(planes: list[dict]) -> tuple[float, float] | None:
    """The ``bench.window`` host event, or ``None`` if there is none."""
    for name, s, e in host_events(planes):
        if name == WINDOW_EVENT:
            return s, e
    return None


def _label(mid: float, spans) -> str:
    best = None
    for name, s, e in spans:
        if name != WINDOW_EVENT and s <= mid <= e and (
                best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no host event"


def reduce(planes: list[dict], extra_spans=()) -> dict | None:
    """Busy, collective and idle time of the devices in the traced window.

    ``extra_spans``: further ``(name, start_ns, end_ns)`` host spans on
    the trace's clock, such as the program's own spans, to label gaps
    with. Returns ``None`` when the trace holds no device operations.
    """
    devices = _device_ops(planes)
    if not any(devices.values()):
        return None
    win = window(planes)
    if win is None:
        starts = [s for evs in devices.values() for _, s, _ in evs]
        ends = [s + d for evs in devices.values() for _, s, d in evs]
        win = (min(starts), max(ends))
    t0, t1 = win
    per_device = {}
    for name, evs in devices.items():
        busy = union(_clip([(s, s + d) for _, s, d in evs], t0, t1))
        coll = union(_clip([(s, s + d) for n, s, d in evs
                            if COLLECTIVE.search(n)], t0, t1))
        per_device[name] = {
            "busy_ns": sum(e - s for s, e in busy),
            "collective_ns": sum(e - s for s, e in coll),
            "intervals": busy,
        }
    busiest = max(per_device, key=lambda n: per_device[n]["busy_ns"])
    busy = per_device[busiest]["intervals"]
    gaps, prev = [], t0
    for s, e in busy + [(t1, t1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    spans = host_events(planes) + list(extra_spans)
    idle_gaps = [[_label(0.5 * (s + e), spans), (e - s) * 1e-9]
                 for s, e in gaps[:TOP]]
    totals: dict[str, float] = {}
    for n, s, d in devices[busiest]:
        if s + d > t0 and s < t1:
            totals[n] = totals.get(n, 0.0) + (min(s + d, t1) - max(s, t0))
    device_ops = sorted(([n, ns * 1e-9] for n, ns in totals.items()),
                        key=lambda x: x[1], reverse=True)[:TOP]
    window_ns = t1 - t0
    return {
        "window_s": window_ns * 1e-9,
        "devices": len(per_device),
        "busiest": busiest,
        "busy_s": {n: v["busy_ns"] * 1e-9 for n, v in per_device.items()},
        "collective_s": {n: v["collective_ns"] * 1e-9
                         for n, v in per_device.items()},
        "busiest_busy_s": per_device[busiest]["busy_ns"] * 1e-9,
        "busiest_collective_s": per_device[busiest]["collective_ns"] * 1e-9,
        "mean_busy_s": sum(v["busy_ns"] for v in per_device.values())
        * 1e-9 / len(per_device),
        "idle_share": 1.0 - per_device[busiest]["busy_ns"] / window_ns,
        "breakdown": {"device_ops": device_ops, "idle_gaps": idle_gaps},
    }

"""The profile and the program's spans on one clock.

A profile's host and device lines are on the profiler's clock; the engine's
spans start at a monotonic stamp plus the process's ONE monotonic → wall
offset (`langstream_tpu/tracing.MONO_TO_WALL_S`, taken once, so two spans'
starts differ by exactly their monotonic stamps). The engine's launch
annotations (`engine.admit_group`, `engine.decode_chunk`) carry the launch's
own monotonic stamp as the stat `t_mono_ns`, the value the dispatch's span
starts at. So every launch a profile holds is one reading of K in

    profiler-ns = monotonic-ns + K

(`fit`: K is their median, the residual the median distance from it: what the
host takes between the stamp and the annotation's start, some microseconds),
and the span of the same dispatch (`seq`) gives the wall offset to the digit.
`to_profile_ns` then lays any span's start on the profile's lines.

`load` reads what that needs and what `readers/trace_idle_by_request.py`
lays against it in one pass over the file: the window `reduce/xplane.py`
measures (every event of every plane), the device's busy intervals (the union
of its "XLA Ops" line, a plane each) and the launch annotations. A profile of
a program without `t_mono_ns` (the parent of the PR that brought it) fits
nothing: `fit` returns None, never a zero.
"""

from __future__ import annotations

import statistics
from pathlib import Path
from typing import Optional

from reduce.xplane import DEVICE_PLANE, HOST_PLANE, OPS_LINE, _union

LAUNCHES = ("engine.admit_group", "engine.decode_chunk", "engine.prefill_segment",
            "engine.verify")


def load(path: Path, *, device_plane: str = DEVICE_PLANE, ops_line: str = OPS_LINE,
         host_plane: str = HOST_PLANE) -> dict:
    """`lo`, `hi`: the profile's window in ns (`reduce_trace`'s `window_s`);
    `busy`: a list a device plane of merged (start, end) intervals in which
    an operation ran; `launches`: the launch annotations in time order, each
    `{"name", "start", "seq", "t_mono_ns"}` (the last two 0 where the
    program did not say)."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    lo, hi = float("inf"), float("-inf")
    busy: list[list[tuple[float, float]]] = []
    launches: list[dict] = []
    for plane in data.planes:
        is_device = plane.name.startswith(device_plane)
        is_host = plane.name.startswith(host_plane)
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            lo = min(lo, min(e.start_ns for e in events))
            hi = max(hi, max(e.start_ns + e.duration_ns for e in events))
            if is_device and line.name.startswith(ops_line):
                busy.append(_union([(e.start_ns, e.start_ns + e.duration_ns) for e in events]))
            elif is_host:
                for e in events:
                    if e.name in LAUNCHES:
                        stats = dict(e.stats)
                        launches.append({
                            "name": e.name, "start": e.start_ns,
                            "seq": int(float(stats.get("seq", 0))),
                            "t_mono_ns": int(float(stats.get("t_mono_ns", 0))),
                        })
    launches.sort(key=lambda a: a["start"])
    return {"lo": lo, "hi": hi, "busy": busy, "launches": launches}


def fit(launches: list[dict], spans: list[dict]) -> Optional[dict]:
    """`{"k_ns", "residual_ns", "launches", "offset_s"}`: K and the median
    distance of the launches' readings from it, how many there were, and the
    spans' wall offset (start − monotonic stamp, s) from the dispatch spans
    whose `seq` a launch carries. None where no launch carries a stamp or no
    span joins one."""
    stamped = [a for a in launches if a["t_mono_ns"] > 0]
    if not stamped:
        return None
    readings = [a["start"] - a["t_mono_ns"] for a in stamped]
    k_ns = statistics.median(readings)
    by_seq = {
        (s["name"], s["attributes"]["seq"]): s["start"] for s in spans
        if s["name"] in LAUNCHES and "seq" in s["attributes"]
    }
    offsets = [
        by_seq[key] - a["t_mono_ns"] / 1e9 for a in stamped
        if (key := (a["name"], a["seq"])) in by_seq
    ]
    if not offsets:
        return None
    return {
        "k_ns": k_ns,
        "residual_ns": statistics.median(abs(r - k_ns) for r in readings),
        "launches": len(stamped),
        "offset_s": statistics.median(offsets),
    }


def to_profile_ns(clock: dict, wall_s: float) -> float:
    """A span's `start` (wall seconds) on the profile's clock."""
    return (wall_s - clock["offset_s"]) * 1e9 + clock["k_ns"]

"""From a profiler trace (`.xplane.pb`) to numbers: device busy time, the
device operations that took most time, and the longest idle gaps by what the
host was doing in them. Read with `jax.profiler.ProfileData`, nothing else.

Busy is the union of the intervals in which an operation ran on a device
(the "XLA Ops" line of its plane), averaged over the device planes; the
window is the span all events cover. A program's whole executions (one event
per dispatch on the plane's "XLA Modules" line, named `jit_<function>(<id>)`)
are kept per program name: the device time of a prefill group or a decode
chunk, whatever the host was doing meanwhile. A gap between two device operations is
named after the shortest host event (any host thread) that covers its
middle: the call the host was inside while the device waited.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MIN_GAP_NS = 20_000  # shorter gaps are launch latency, not the host's doing


# on the chip an operation's name is its whole HLO line
_HLO = re.compile(r"^(%?[\w.\-]+) = \(?([a-z0-9]+\[[\d,]*\])")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_MODULE_ID = re.compile(r"\(\d+\)$")  # `jit_admit_group(1234)`: the id varies by process


def short_name(name: str) -> str:
    """`%fusion.3 = bf16[8,512]{...} fusion(...)` → `%fusion.3 fusion bf16[8,512]`
    (a tuple result is named by its first element)."""
    m, op = _HLO.match(name), _OPCODE.search(name)
    return f"{m.group(1)} {op.group(1)} {m.group(2)}" if m and op else name[:120]


def find_trace(directory: Path) -> Path:
    traces = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if not traces:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return traces[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _self_times(events: list[tuple[float, float, str]]):
    """(name, own time) per event: a control-flow operation (`while`,
    `conditional`) encloses the operations of its body on the same line, and
    only what it does not spend in them is its own."""
    ordered = sorted(events, key=lambda e: (e[0], -e[1]))
    own = [end - start for start, end, _ in ordered]
    stack: list[int] = []
    for i, (start, end, _) in enumerate(ordered):
        while stack and ordered[stack[-1]][1] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= end - start
        stack.append(i)
    return [(e[2], max(0.0, t)) for e, t in zip(ordered, own)]


def _host_cover(host_lines: list, t: float) -> str:
    best, best_len = "no host event", float("inf")
    for events, starts in host_lines:
        # events sorted by start; look back over the few that can cover t
        i = bisect.bisect_right(starts, t)
        for start, end, name in events[max(0, i - 64) : i]:
            if start <= t < end and end - start < best_len:
                best, best_len = name, end - start
    return best


def reduce_trace(path: Path, *, device_plane: str = DEVICE_PLANE,
                 ops_line: str = OPS_LINE, host_plane: str = HOST_PLANE,
                 modules_line: str = MODULES_LINE, top: int = 10) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    lo, hi = float("inf"), float("-inf")
    devices: list[list[tuple[float, float, str]]] = []
    host_lines = []
    module_ns: dict[str, list[float]] = defaultdict(list)
    device_lines: set[str] = set()
    for plane in data.planes:
        is_device = plane.name.startswith(device_plane)
        is_host = plane.name.startswith(host_plane)
        for line in plane.lines:
            events = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events
            ]
            if not events:
                continue
            lo = min(lo, min(e[0] for e in events))
            hi = max(hi, max(e[1] for e in events))
            if is_device:
                device_lines.add(line.name)
            if is_device and line.name.startswith(ops_line):
                devices.append(events)
            elif is_device and line.name.startswith(modules_line):
                for start, end, name in events:
                    module_ns[_MODULE_ID.sub("", name)].append(end - start)
            elif is_host and not (is_device and line.name.startswith(ops_line)):
                timed = sorted(e for e in events if e[1] > e[0])
                if timed:
                    host_lines.append((timed, [e[0] for e in timed]))
    if not devices:
        raise ValueError(
            f"no '{ops_line}' line on a '{device_plane}*' plane in {path}: "
            "no operation ran on the device in the traced window"
        )
    busy_ns, op_ns, gap_ns = 0.0, defaultdict(float), defaultdict(float)
    op_calls: dict[str, int] = defaultdict(int)
    for events in devices:
        merged = _union([(s, e) for s, e, _ in events])
        busy_ns += sum(e - s for s, e in merged)
        for name, self_ns in _self_times(events):
            op_ns[name] += self_ns
            op_calls[name] += 1
        for (_, left), (right, _) in zip(merged, merged[1:]):
            if right - left >= MIN_GAP_NS:
                gap_ns[_host_cover(host_lines, (left + right) / 2)] += right - left
    n = len(devices)

    def ranked(table: dict) -> list:
        return [
            [short_name(name), ns / n / 1e9]
            for name, ns in sorted(table.items(), key=lambda kv: -kv[1])[:top]
        ]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "device_planes": n,
        "device_ops": ranked(op_ns),
        "idle_gaps": ranked(gap_ns),
        "ops": {name: {"seconds": ns / n / 1e9, "calls": op_calls[name] / n}
                for name, ns in op_ns.items()},
        # per execution, not averaged over planes: a program on four chips
        # runs once on each and takes as long as each took
        "modules": {name: {"seconds": sum(ns) / 1e9, "calls": len(ns)}
                    for name, ns in module_ns.items()},
        "device_lines": sorted(device_lines),
    }

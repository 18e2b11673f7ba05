"""A profiler trace read by program execution, scope and dispatch number.

`reduce/xplane.py` sums a trace by operation. This keeps three more things
apart, for the readers that need them (`readers/trace_scope.py`):

- **Executions.** Every event of the device's "XLA Modules" line is one run
  of one compiled program (`jit_<function>(<id>)`); the operations of the
  "XLA Ops" line that start inside it are its own, each with its self time
  (`xplane._self_times`: a `while` does not count its body twice).
- **Scopes.** Where the scope path shows, looked at by hand on a v5e trace
  (PERF.md section 3): NOT in the event's name (on the chip that is the HLO
  line without its `metadata={op_name=...}`) and not in the event's stats
  (`device_offset_ps`, `device_duration_ps` only), but in the `tf_op` stat of
  the event's METADATA entry, e.g. `jit(_paged_decode_chunk)/while/body/
  closed_call/while/body/closed_call/attention/dot_general:`, which
  `jax.profiler.ProfileData` does not expose and `xplane_meta.op_scopes`
  reads from the file. A fusion has one such path, its ROOT instruction's: a
  fusion that spans two scopes (a `kv_pool.read` slice fused into the
  attention's first matmul) counts whole under the root's. Operations the
  compiler made itself (copies, bitcasts) have none and count under no scope.
  A Pallas kernel's `name=` shows twice: as the instruction's name
  (`%ragged_paged_decode_attention.3 = ...`) and as a scope of its own inside
  the caller's (`.../attention/ragged_paged_decode_attention/pallas_call:`).
- **Annotations.** The engine's `jax.profiler.TraceAnnotation`s
  (`engine.decode_chunk` and `engine.admit_group` around a launch,
  `engine.fetch` around the wait for its result, the phases of an iteration)
  are events of the host threads' lines, on the profiler's clock; their
  keyword arguments (`seq`, `steps`) are the event's stats.

`match` joins the two sides of a dispatch; its docstring has the rule.
"""

from __future__ import annotations

import bisect
from pathlib import Path

from reduce.xplane import (DEVICE_PLANE, HOST_PLANE, MODULES_LINE, OPS_LINE, _MODULE_ID,
                           _self_times)
from reduce.xplane_meta import op_scopes

ANNOTATION_PREFIX = "engine."
EDGE_NS = 1_000


def load(path: Path, *, device_plane: str = DEVICE_PLANE, ops_line: str = OPS_LINE,
         modules_line: str = MODULES_LINE, host_plane: str = HOST_PLANE) -> dict:
    """`executions`: program name -> its runs in time order, each
    `{"start", "end"` (ns)`, "ops": {event name: [self seconds, calls]}}`, of
    the FIRST device plane (a program on four chips runs alike on each);
    `scope_of`: event name -> scope path; `annotations`: name -> events in
    time order, each `{"start", "end", **stats}`."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    executions: dict[str, list[dict]] = {}
    annotations: dict[str, list[dict]] = {}
    device_done = False
    for plane in data.planes:
        if plane.name.startswith(device_plane) and not device_done:
            runs, ops = [], []
            for line in plane.lines:
                if line.name.startswith(modules_line):
                    runs += [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                elif line.name.startswith(ops_line):
                    ops += [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
            if not runs:
                continue
            device_done = True
            # the profile cuts what is running when it starts and stops: an
            # execution that touches either end of the plane is short, not
            # whole (seen on a v5e: the last one, 1.5 of its 10.7 ms)
            lo = min(e[0] for e in runs + ops)
            hi = max(e[1] for e in runs + ops)
            runs = sorted(r for r in runs if r[0] > lo + EDGE_NS and r[1] < hi - EDGE_NS)
            starts = [r[0] for r in runs]
            slots = [{"start": s, "end": e, "ops": {}} for s, e, _ in runs]
            for (start, _, _), (name, own_ns) in zip(
                sorted(ops, key=lambda e: (e[0], -e[1])), _self_times(ops)
            ):
                i = bisect.bisect_right(starts, start) - 1
                if i < 0 or start >= runs[i][1]:
                    continue  # between two executions: belongs to neither
                entry = slots[i]["ops"].setdefault(name, [0.0, 0])
                entry[0] += own_ns / 1e9
                entry[1] += 1
            for (_, _, name), slot in zip(runs, slots):
                executions.setdefault(_MODULE_ID.sub("", name), []).append(slot)
        elif plane.name.startswith(host_plane):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        annotations.setdefault(e.name, []).append(
                            {"start": e.start_ns, "end": e.start_ns + e.duration_ns,
                             **{k: v for k, v in e.stats}}
                        )
    for events in annotations.values():
        events.sort(key=lambda a: a["start"])
    return {
        "executions": executions,
        "scope_of": op_scopes(path, device_plane),
        "annotations": annotations,
    }


SKEW_NS = 2_000_000  # the device's clock against the host's, seen: 0.5 ms


def match(fetches: list[dict], executions: list[dict]) -> list[tuple[dict, dict]]:
    """Pairs (fetch annotation, execution) of one program's dispatches.

    The engine's fetch thread waits for each dispatch's result in dispatch
    order inside an `engine.fetch` annotation that carries the dispatch's
    `seq`, and the wait ends when the program's output is on the host: a
    transfer after the execution's end, far less than the next execution
    takes. So a fetch's execution is the LAST one of its program that ended
    by the fetch's end (give or take the clocks' skew) and that no earlier
    fetch took. Launch times cannot decide this: the device runs one to two
    dispatches behind the host, so an execution launched before the profile
    began may start after the profile's first launch.

    What has no partner is dropped. At the trace's start: executions whose
    fetch was already waiting when the profile began (its annotation is not
    in the trace). At its end: fetches still waiting when it stopped. The
    execution the profile cut short at either end is recorded short, not
    left out; `load` drops it before this sees it (it touches the plane's
    edge). Edge error: what remains is that the fetch thread stamps a result
    up to a few ms after the execution ended (2.5–2.9 ms on the recorded
    trace, transfer and skew together), so a program whose executions are
    shorter than that could be paired one late; the engine's take 100 ms and
    more."""
    pairs, floor = [], 0
    ends = [e["end"] for e in executions]
    for f in fetches:
        i = bisect.bisect_right(ends, f["end"] + SKEW_NS) - 1
        if i < floor:
            continue
        pairs.append((f, executions[i]))
        floor = i + 1
    return pairs


def under(scope_of: dict, name: str, scopes: set[str]) -> bool:
    """Whether the operation's scope path has one of `scopes` among its
    components."""
    return bool(scopes.intersection(scope_of.get(name, "").rstrip(":").split("/")))


def scope_seconds(execution: dict, scope_of: dict, scopes: list[str]) -> tuple[float, int]:
    """Self seconds and calls of the execution's operations under `scopes`."""
    wanted, seconds, calls = set(scopes), 0.0, 0
    for name, (own, n) in execution["ops"].items():
        if under(scope_of, name, wanted):
            seconds += own
            calls += n
    return seconds, calls

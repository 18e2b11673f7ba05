"""Device time by scope and dispatch, from the window's profile.

The executions of one compiled `program` (`jit_<program>` on the device's
"XLA Modules" line) are paired with the window's `span`s of that program's
dispatches (`engine.decode_chunk`, `engine.admit_group`) through the
`engine.fetch` annotations, which carry the dispatch's `seq` on the
profiler's clock (`reduce/scoped.match` has the rule and its edge error: only
whole executions whose fetch is inside the traced seconds count). Over the
pairs:

- the self time (`reduce/xplane._self_times`) of the operations under any of
  `scopes` (`jax.named_scope`s or kernel names), over the sum of the paired
  spans' `per` attribute: `steps` of a decode chunk, `computed_tokens` of a
  prefill group (a plain mean per execution would move with the mix of
  widths among the few groups a profile holds);
- `roofline` instead turns a kernel's time into its share of the least time
  the chip could take for the bytes and operations the paired spans say it
  had to move: `cost` names `reduce/<module>.<function>`, `shape` reads the
  call's sizes out of the kernel's HLO line, `span_attr` is the span's work
  count.

A dispatch's whole device time needs no trace: the spans carry it
(`device_ms`, read by `span_ratio` over the whole window). The profile is
parsed once per run (`reduce/scoped.load`, kept on the window). A program
without the spans, the annotations or the scopes (the parent of the PR that
brought them) reads as nothing."""

from __future__ import annotations

import importlib
import re
from typing import Optional

from reduce import costs, scoped
from reduce.xplane import find_trace

CACHE = "_scoped_trace"


def _trace(ctx: dict) -> Optional[dict]:
    if CACHE not in ctx:
        try:
            ctx[CACHE] = scoped.load(find_trace(ctx["trace_dir"]), **ctx.get("trace_planes", {}))
        except FileNotFoundError:
            ctx[CACHE] = None
    return ctx[CACHE]


def pairs_of(definition: dict, ctx: dict) -> list[tuple[dict, dict]]:
    """(span, execution) of the program's dispatches inside the trace."""
    trace = _trace(ctx) if ctx.get("trace_dir") else None
    if not trace:
        return []
    spans = {
        s["attributes"]["seq"]: s for s in ctx["spans"]
        if s["name"] == definition["span"] and "seq" in s["attributes"]
    }
    fetches = [
        f for f in trace["annotations"].get("engine.fetch", [])
        if int(f.get("seq", 0)) in spans
    ]
    executions = trace["executions"].get("jit_" + definition["program"], [])
    return [(spans[int(f["seq"])], e) for f, e in scoped.match(fetches, executions)]


def _roofline(definition: dict, pairs: list, scope_of: dict, peaks: dict) -> Optional[float]:
    spec = definition["roofline"]
    module, function = spec["cost"].split(".")
    cost = getattr(importlib.import_module(f"reduce.{module}"), function)
    shape, wanted = re.compile(spec["shape"]), set(definition["scopes"])
    least = took = 0.0
    for span, execution in pairs:
        steps = span["attributes"]["steps"]
        for name, (seconds, calls) in execution["ops"].items():
            m = shape.search(name)
            if m is None or not scoped.under(scope_of, name, wanted):
                continue
            sizes = {k: int(v) for k, v in m.groupdict().items()}
            if "group" in sizes:  # query heads = kv heads x group
                sizes["n_heads"] = sizes["n_kv_heads"] * sizes.pop("group")
            work = cost(
                span["attributes"][spec["span_attr"]], steps=steps,
                layers=calls / steps, **sizes, **spec.get("sizes", {}),
            )
            least += costs.roofline_seconds(work, peaks)[0]
            took += seconds
    return 100.0 * least / took if took else None


def read(definition: dict, ctx: dict) -> Optional[float]:
    pairs = pairs_of(definition, ctx)
    if not pairs:
        return None
    scope_of = ctx[CACHE]["scope_of"]
    if "roofline" in definition:
        return _roofline(definition, pairs, scope_of, ctx["peaks"])
    seconds = sum(
        scoped.scope_seconds(e, scope_of, definition["scopes"])[0] for _, e in pairs
    )
    if not seconds:
        return None  # the program names no such scope
    count = sum(s["attributes"][definition["per"]] for s, _ in pairs)
    return seconds / count * definition.get("scale", 1.0)

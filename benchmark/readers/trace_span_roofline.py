"""A kernel's share of its roofline where its work is counted by SEVERAL
attributes of its dispatches' spans, over one or several programs.

`readers/trace_scope.py`'s roofline prices each matched event with one span
attribute and the event's own call count. That cannot say what two kernels of
one name were asked for when each read another count (the decode kernel of a
model with window layers: the full layers read `kv_tokens_read`, the window
layers `kv_tokens_read_window`, under one `pallas_call` name), nor add up a
kernel that runs in two programs (the grouped expert product, in the decode
chunk and in the prefill segment). Here, for every (span, execution) pair of
every entry of `dispatches` (`trace_scope.pairs_of` has the pairing), the
events under `scopes` whose line matches `shape` are summed, seconds and
calls, and `cost` (`reduce/<module>.<function>`) is called ONCE a pair with
the span's `span_attrs` by name, its `steps` (1 where the span has none), the
summed `calls` and the definition's `sizes`. The share is the least seconds
over the seconds taken, both summed over all pairs. A span that lacks one of
the attributes, a trace without the kernel, a program without the spans: read
as nothing."""

from __future__ import annotations

import importlib
import re
from typing import Optional

from readers import trace_scope
from reduce import costs, scoped


def read(definition: dict, ctx: dict) -> Optional[float]:
    spec = definition["roofline"]
    module, function = spec["cost"].split(".")
    cost = getattr(importlib.import_module(f"reduce.{module}"), function)
    shape, wanted = re.compile(spec.get("shape", "")), set(definition["scopes"])
    least = took = 0.0
    for dispatch in definition["dispatches"]:
        for span, execution in trace_scope.pairs_of(dispatch, ctx):
            attrs = span["attributes"]
            if any(a not in attrs for a in spec["span_attrs"]):
                continue
            seconds = calls = 0.0
            for name, (own, n) in execution["ops"].items():
                if scoped.under(ctx[trace_scope.CACHE]["scope_of"], name, wanted) and shape.search(name):
                    seconds += own
                    calls += n
            if not calls:
                continue
            work = cost(
                **{a: attrs[a] for a in spec["span_attrs"]}, steps=attrs.get("steps", 1),
                calls=int(calls), **spec.get("sizes", {}),
            )
            least += costs.roofline_seconds(work, ctx["peaks"])[0]
            took += seconds
    return 100.0 * least / took if took else None

"""A kernel's share of its roofline, from the device trace: the least time
the chip could take for the calls it made (the larger of operations over
peak and bytes over peak, from the shapes each event's line carries) over
the device time those events took. `reduce/kernels/<kernel>.json`, the file
the metric's definition names, says how the kernel's events are recognised."""

from __future__ import annotations

import importlib
import re
from typing import Optional

from modelcfg import load_json
from reduce import costs


def read(definition: dict, ctx: dict) -> Optional[float]:
    if not ctx.get("trace"):
        return None
    entry = load_json("reduce/kernels", definition["kernel"])
    module, function = entry["cost"].split(".")
    cost = getattr(importlib.import_module(f"reduce.{module}"), function)
    shape = re.compile(entry["shape"])
    least = took = 0.0
    for name, op in ctx["trace"]["ops"].items():
        m = shape.search(name)
        if m is None:
            continue
        sizes = {k: int(v) for k, v in m.groupdict().items()}
        if "group" in sizes:  # query heads = kv heads x group
            sizes["n_heads"] = sizes["n_kv_heads"] * sizes.pop("group")
        seconds, _ = costs.roofline_seconds(cost(**sizes), ctx["peaks"])
        least += seconds * op["calls"]
        took += op["seconds"]
    return 100.0 * least / took if took else None

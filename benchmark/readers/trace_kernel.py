"""A kernel's share of its roofline, from the device trace: the least time
the chip could take for the calls it made (the larger of operations over
peak and bytes over peak, from the shapes each event's line carries) over
the device time those events took. `reduce/kernel_names.json` says how a
kernel's events are recognised; a kernel it does not list is not read."""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path
from typing import Optional

from reduce import costs

NAMES = Path(__file__).resolve().parents[1] / "reduce" / "kernel_names.json"


def read(definition: dict, ctx: dict) -> Optional[float]:
    entry = json.loads(NAMES.read_text())["kernels"].get(definition["kernel"])
    if entry is None or not ctx.get("trace"):
        return None
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

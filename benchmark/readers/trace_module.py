"""The device time of one of the program's compiled programs, per execution,
from the device trace: the events of the device's "XLA Modules" line whose
name matches `module` (a regular expression over `jit_<function>`), summed
over the traced seconds and divided by their number. What the device took
for a prefill group or a decode chunk, not what the host took to launch it."""

from __future__ import annotations

import re
from typing import Optional


def read(definition: dict, ctx: dict) -> Optional[float]:
    if not ctx.get("trace"):
        return None
    wanted = re.compile(definition["module"])
    seconds = calls = 0.0
    for name, module in ctx["trace"]["modules"].items():
        if wanted.search(name):
            seconds += module["seconds"]
            calls += module["calls"]
    return seconds / calls * definition.get("scale", 1.0) if calls else None

"""Work counted where it happened: over the spans of one name (or of several:
`span` may be a list) that the harness drained during the window, the sum of
one attribute over the sum of another (`numerator` / `denominator`: real
tokens over computed tokens, dropped expert assignments over routed ones, a
chunk's device milliseconds over its steps). Spans that lack either attribute
are left out; nothing left, or a zero denominator, reads as nothing. A
percentile of a span's length is `readers/span.py`'s."""

from __future__ import annotations

from typing import Optional


def read(definition: dict, ctx: dict) -> Optional[float]:
    names = definition["span"]
    names = {names} if isinstance(names, str) else set(names)
    pairs = [
        (n, d) for s in ctx["spans"] if s["name"] in names
        if (n := s["attributes"].get(definition["numerator"])) is not None
        and (d := s["attributes"].get(definition["denominator"])) is not None
    ]
    total = sum(d for _, d in pairs)
    return sum(n for n, _ in pairs) / total * definition.get("scale", 1.0) if total else None

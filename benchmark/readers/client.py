"""A statistic of the client's own records (`metrics.ttft_ms`,
`metrics.tpot_ms` per request): a tail that the window's sample is too small
to hold to a bound stands here, beside the end-to-end mean."""

from __future__ import annotations

from typing import Optional

import metrics


def read(definition: dict, ctx: dict) -> Optional[float]:
    per_request = getattr(metrics, definition["quantity"])
    values = [v for v in map(per_request, ctx["requests"]) if v is not None]
    return metrics.percentile(values, definition["percentile"]) if values else None

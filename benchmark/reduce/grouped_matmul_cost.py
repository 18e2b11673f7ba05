"""What the grouped expert product has to move and compute: the engine counts,
on the dispatch's span, the assignments that fell on held experts
(`moe_local`: the rows that arrived, summed over layers and steps) and the
(layer, step, expert) triples that got at least one row (`moe_touched`). An
expert layer is three products, gate and up [d, f] and down [f, d]: the least
bytes are each touched expert's three int8 matrices once and every row in and
out of each product; the operations are over the rows that arrived, none for
padding, none for experts without rows. A decode step is bound by the weights
it touches, a prefill segment by the products."""

from __future__ import annotations


def grouped_matmul(moe_local: int, moe_touched: int, steps: int, calls: int, d_model: int,
                   d_ff: int, weight_bytes_per_elem: int = 1, bytes_per_elem: int = 2) -> dict:
    weights = moe_touched * 3 * d_model * d_ff * weight_bytes_per_elem
    rows = moe_local * 3 * (d_model + d_ff) * bytes_per_elem  # in and out, three products
    return {"ops": moe_local * 3 * 2 * d_model * d_ff, "bytes": weights + rows}

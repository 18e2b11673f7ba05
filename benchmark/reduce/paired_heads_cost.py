"""What the causal prefill kernel has to do where two KV heads of 64 share a
128-lane row (`ModelConfig.kv_head_pack`): the call's line carries the PACKED
sizes (`n_kv_heads` packed rows of `head_dim` = 2 x 64 lanes, `n_heads` query
heads in all, each laid in its own half of the lanes), and the least work is
the unpacked model's: every query head against its own head's 64 lanes.
`costs.prefill_attention` over the packed sizes would count the zeros of the
other half as operations and read twice the kernel's true share."""

from __future__ import annotations

from reduce import costs

PACK = 2


def prefill_attention(rows: int, width: int, n_heads: int, n_kv_heads: int,
                      head_dim: int, bytes_per_elem: int = 2) -> dict:
    """`n_kv_heads` packed rows of `head_dim` lanes: PACK x as many heads of
    head_dim / PACK; the query heads are what they are."""
    return costs.prefill_attention(
        rows, width, n_heads, n_kv_heads * PACK, head_dim // PACK, bytes_per_elem
    )

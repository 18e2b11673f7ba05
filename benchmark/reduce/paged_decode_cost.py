"""What the paged decode attention of one dispatch has to move.

Decode attention is bound by memory: each generated token's query reads the
whole live prefix of its row once per layer. The engine counts that prefix
where it knows it (`kv_tokens_read` on the `engine.decode_chunk` span: over
the chunk's steps and active rows, the row's live length at that step), so
the least bytes are those tokens' K and V in every layer, plus the queries
read and the outputs written for every row the kernel is called on (free
slots ride along and cost a query and an output, not a prefix). Operations
are the q.k and p.v products over the same tokens."""

from __future__ import annotations


def paged_decode_attention(kv_tokens_read: int, steps: int, layers: float, rows: int,
                           n_heads: int, n_kv_heads: int, head_dim: int,
                           kv_bytes_per_elem: int = 2, bytes_per_elem: int = 2) -> dict:
    k_and_v = kv_tokens_read * 2 * n_kv_heads * head_dim * kv_bytes_per_elem
    q_and_out = 2 * steps * rows * n_heads * head_dim * bytes_per_elem
    ops = 4 * kv_tokens_read * n_heads * head_dim  # a multiply and an add, twice
    return {"ops": ops * layers, "bytes": (k_and_v + q_and_out) * layers}

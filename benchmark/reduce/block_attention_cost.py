"""What the attention of a block pass has to move.

A model that fills blocks runs B queries a row in a pass, and all of them see
the same keys: everything up to the block's end. So a (row, pass) reads the
row's live prefix ONCE per layer, whatever B is, and the engine counts that
prefix where it knows it (`kv_tokens_read` on the `engine.block_chunk` span:
over the chunk's passes and the rows whose request held the slot, the block's
end). The least bytes are those tokens' K and V in every layer, plus the B
queries read and the B outputs written for every row the kernel is called on
(idle slots ride along and cost their queries and outputs, not a prefix).
Operations are the q.k and p.v products of B queries over the same tokens.
`calls` is the kernel's events in the dispatch: one a layer and pass."""

from __future__ import annotations


def block_attention(kv_tokens_read: int, passes: int, steps: int, calls: int, rows: int,
                    block_length: int, n_heads: int, n_kv_heads: int, head_dim: int,
                    kv_bytes_per_elem: int = 2, bytes_per_elem: int = 2) -> dict:
    layers = calls / max(1, passes)
    k_and_v = kv_tokens_read * 2 * n_kv_heads * head_dim * kv_bytes_per_elem
    q_and_out = 2 * passes * rows * block_length * n_heads * head_dim * bytes_per_elem
    ops = 4 * kv_tokens_read * block_length * n_heads * head_dim  # a multiply and an add, twice
    return {"ops": ops * layers, "bytes": (k_and_v + q_and_out) * layers}

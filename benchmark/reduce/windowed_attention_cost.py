"""What attention has to move and compute in a model whose layers are of two
kinds: full layers, where a query reads its row's whole live prefix, and
window layers, where it reads at most the last `sliding_window` tokens. The
engine counts both where it knows them, on the dispatch's span:
`kv_tokens_read` (a full layer's (query, key) pairs) and
`kv_tokens_read_window` (a window layer's, each query capped by the window).
The standing `paged_decode_cost` prices every layer at the full count; for
such a model that is more than the work there is, so it is not used here.

Decode is bound by memory: the pairs' K and V once a layer, plus the queries
read and the outputs written for every row the kernel is called on. A prefill
segment is bound by compute: the q.k and p.v products over the same pairs
(window-bounded work, whatever blocks an implementation visits); its least
bytes are the queries and outputs and each visible key once."""

from __future__ import annotations


def _pairs(kv_tokens_read: int, kv_tokens_read_window: int, full_layers: int,
           window_layers: int) -> int:
    return full_layers * kv_tokens_read + window_layers * kv_tokens_read_window


def windowed_decode_attention(kv_tokens_read: int, kv_tokens_read_window: int, steps: int,
                              calls: int, rows: int, n_heads: int, n_kv_heads: int,
                              head_dim: int, full_layers: int, window_layers: int,
                              bytes_per_elem: int = 2) -> dict:
    """A decode chunk of `steps` steps over `rows` rows (`calls` is the
    kernel's events, one a layer and step: unused, the layers are the
    configuration's)."""
    pairs = _pairs(kv_tokens_read, kv_tokens_read_window, full_layers, window_layers)
    k_and_v = pairs * 2 * n_kv_heads * head_dim * bytes_per_elem
    q_and_out = (
        2 * steps * rows * n_heads * head_dim * bytes_per_elem * (full_layers + window_layers)
    )
    return {"ops": 4 * pairs * n_heads * head_dim, "bytes": k_and_v + q_and_out}


def segment_attention(kv_tokens_read: int, kv_tokens_read_window: int, real_tokens: int,
                      offset: int, steps: int, calls: int, n_heads: int, n_kv_heads: int,
                      head_dim: int, full_layers: int, window_layers: int, window: int,
                      bytes_per_elem: int = 2) -> dict:
    """One prefill segment: `real_tokens` queries at positions `offset` on."""
    pairs = _pairs(kv_tokens_read, kv_tokens_read_window, full_layers, window_layers)
    q_and_out = 2 * real_tokens * n_heads * head_dim * (full_layers + window_layers)
    seen_full = offset + real_tokens
    seen_window = min(seen_full, window + real_tokens - 1)
    k_and_v = 2 * n_kv_heads * head_dim * (full_layers * seen_full + window_layers * seen_window)
    return {
        "ops": 4 * pairs * n_heads * head_dim, "bytes": (q_and_out + k_and_v) * bytes_per_elem,
    }

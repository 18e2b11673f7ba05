"""What attention has to move and compute in a model whose KINDS of layer each
keep a latent of their own geometry (dots3-note-prev): full layers read under a
learned selection, window layers read the last `sliding_window` tokens. The
engine counts both on the dispatch's span, a layer: `kv_tokens_selected` (a
full layer's (query, key) pairs, each query capped by the top-k) and
`kv_tokens_read_window` (a window layer's, each query capped by the window);
`layers` is the kind's count in the configuration. A head's key and value have
widths of their own (192 | 256 against 128), so the expanded form's count is
`2 x n_heads x (qk_head_dim + v_head_dim)` a pair.

- A window layer's decode read: a token's latent ONCE a layer for key and
  value, at the `kept_width` the pool holds it (1,088 values kept at 1,152
  lanes: 2,304 B, what a page's DMA moves), plus the absorbed queries read and
  the mixed latents written; a multiply and an add over `latent_width`
  (scores) and `value_width` (the weighted sum) a pair and head.
- A segment's attention of either kind, in the EXPANDED form whatever form is
  run: the products over the pairs the kind's queries see (the selected ones;
  those inside the window's band); the least bytes are queries in, outputs out
  and the latents of the columns seen once.

`steps` and `calls` are the reader's (`readers/trace_span_roofline.py`)."""

from __future__ import annotations


def window_decode_attention(kv_tokens_read_window: int, active_rows: int, steps: int, calls: int,
                            n_heads: int, latent_width: int, kept_width: int, value_width: int,
                            layers: int, bytes_per_elem: int = 2) -> dict:
    latents = kv_tokens_read_window * kept_width * bytes_per_elem
    q_and_out = steps * active_rows * n_heads * (latent_width + value_width) * bytes_per_elem
    return {
        "ops": 2 * kv_tokens_read_window * n_heads * (latent_width + value_width) * layers,
        "bytes": (latents + q_and_out) * layers,
    }


def _segment(pairs: int, real_tokens: int, seen: int, n_heads: int, qk_head_dim: int,
             v_head_dim: int, latent_width: int, layers: int, bytes_per_elem: int) -> dict:
    q_and_out = real_tokens * n_heads * (qk_head_dim + v_head_dim)
    return {
        "ops": 2 * pairs * n_heads * (qk_head_dim + v_head_dim) * layers,
        "bytes": (q_and_out + latent_width * seen) * bytes_per_elem * layers,
    }


def selected_segment_attention(kv_tokens_selected: int, real_tokens: int, offset: int, steps: int,
                               calls: int, n_heads: int, qk_head_dim: int, v_head_dim: int,
                               latent_width: int, layers: int, bytes_per_elem: int = 2) -> dict:
    return _segment(kv_tokens_selected, real_tokens, offset + real_tokens, n_heads, qk_head_dim,
                    v_head_dim, latent_width, layers, bytes_per_elem)


def window_segment_attention(kv_tokens_read_window: int, real_tokens: int, offset: int,
                             steps: int, calls: int, n_heads: int, qk_head_dim: int,
                             v_head_dim: int, latent_width: int, window: int, layers: int,
                             bytes_per_elem: int = 2) -> dict:
    seen = min(offset + real_tokens, window + real_tokens - 1)
    return _segment(kv_tokens_read_window, real_tokens, seen, n_heads, qk_head_dim, v_head_dim,
                    latent_width, layers, bytes_per_elem)

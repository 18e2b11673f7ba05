"""What the attention of a model that keeps a LATENT and has NO selection has
to move and compute, whatever implements it (the absorbed or the expanded
form, a walk of pages or a gather): every query reads EVERY cached latent
behind it. The engine counts, on the dispatch's span, what the attention read
(`kv_tokens_read`: over live rows and steps, or over a segment's real queries,
the columns each sees, `offset + i + 1` for a segment's i-th), a layer;
`layers` is the configuration's. The widths are the PUBLISHED ones: a lane
the chip pads (576 kept at 640, a 192-wide key at 256 in VMEM) is the
kernel's cost and not the count's.

- A decode step's dense read: a cached token's latent ONCE a layer
  (`latent_width` values: the key of every head and, its first `value_width`
  lanes, their value), plus the absorbed queries read and the mixed latents
  written for every row, step and head; a multiply and an add over
  `latent_width` (scores) and `value_width` (the weighted sum) a (query, key)
  pair and head. At 64 heads that is 121 operations a byte of latent, close to
  a v5e's ridge: the roofline is the larger of the two.
- A segment's causal attention: the q.k and p.v products over the causal
  (query, key) pairs in the EXPANDED form, `2 x n_heads x (qk_head_dim +
  v_head_dim)` operations each (the key and the value have widths of their
  own), whatever form is run; the least bytes are the queries in, the outputs
  out and the row's latents once.

`steps` and `calls` are the reader's (`readers/trace_span_roofline.py`)."""

from __future__ import annotations


def latent_decode_attention(kv_tokens_read: int, active_rows: int, steps: int, calls: int,
                            n_heads: int, latent_width: int, value_width: int, layers: int,
                            bytes_per_elem: int = 2) -> dict:
    latents = kv_tokens_read * latent_width * bytes_per_elem
    q_and_out = steps * active_rows * n_heads * (latent_width + value_width) * bytes_per_elem
    return {
        "ops": 2 * kv_tokens_read * n_heads * (latent_width + value_width) * layers,
        "bytes": (latents + q_and_out) * layers,
    }


def latent_segment_attention(kv_tokens_read: int, real_tokens: int, offset: int, steps: int,
                             calls: int, n_heads: int, qk_head_dim: int, v_head_dim: int,
                             latent_width: int, layers: int, bytes_per_elem: int = 2) -> dict:
    q_and_out = real_tokens * n_heads * (qk_head_dim + v_head_dim)
    latents = latent_width * (offset + real_tokens)
    return {
        "ops": 2 * kv_tokens_read * n_heads * (qk_head_dim + v_head_dim) * layers,
        "bytes": (q_and_out + latents) * bytes_per_elem * layers,
    }

"""What the attention of a model that keeps a LATENT in place of keys and
values has to move and compute, whatever implements it (a walk of every page
under a mask or a gather of the selected rows, the absorbed or the expanded
form). The engine counts, on the dispatch's span, what the attention read
(`kv_tokens_selected`: over live rows and steps, or over a segment's real
queries, the columns each attends to, capped by the top-k), a layer; `layers`
is the configuration's.

- A decode step's selected read: a selected token's latent ONCE a layer
  (`latent_width` values: the key of every head and, its first `value_width`
  lanes, their value), plus the absorbed queries read and the mixed latents
  written for every row, step and head; a multiply and an add over
  `latent_width` (scores) and `value_width` (the weighted sum) a selected
  (query, key) pair and head. At 64 heads that is 121 operations a byte of
  latent, close to a v5e's ridge: the roofline is the larger of the two.
- A segment's attention under the selection: the q.k and p.v products over the
  SELECTED (query, key) pairs in the EXPANDED form, `4 x n_heads x head_dim`
  operations each, whatever form is run (walking the latents with absorbed
  queries costs `2 x (latent_width + value_width)` a pair and head and would
  read over 100% of its own count, so the expanded form's is the yardstick);
  the least bytes are the queries in, the outputs out and the row's latents
  once.

`steps` and `calls` are the reader's (`readers/trace_span_roofline.py`)."""

from __future__ import annotations


def latent_decode_attention(kv_tokens_selected: int, active_rows: int, steps: int, calls: int,
                            n_heads: int, latent_width: int, value_width: int, layers: int,
                            bytes_per_elem: int = 2) -> dict:
    latents = kv_tokens_selected * latent_width * bytes_per_elem
    q_and_out = steps * active_rows * n_heads * (latent_width + value_width) * bytes_per_elem
    return {
        "ops": 2 * kv_tokens_selected * n_heads * (latent_width + value_width) * layers,
        "bytes": (latents + q_and_out) * layers,
    }


def latent_segment_attention(kv_tokens_selected: int, real_tokens: int, offset: int, steps: int,
                             calls: int, n_heads: int, head_dim: int, latent_width: int,
                             layers: int, bytes_per_elem: int = 2) -> dict:
    q_and_out = 2 * real_tokens * n_heads * head_dim
    latents = latent_width * (offset + real_tokens)
    return {
        "ops": 4 * kv_tokens_selected * n_heads * head_dim * layers,
        "bytes": (q_and_out + latents) * bytes_per_elem * layers,
    }

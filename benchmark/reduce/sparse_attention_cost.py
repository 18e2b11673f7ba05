"""What the attention of a model with a learned selection has to move and
compute, whatever implements it. The engine counts, on the dispatch's span,
what the indexer scored (`index_tokens_scored`: over live rows and steps, or
over a segment's real queries, the columns each may see) and what the
attention then read (`kv_tokens_selected`: each capped by the top-k), both a
layer; `layers` is the configuration's.

- The selected read of a decode step is bound by memory: the selected tokens'
  K and V once a layer (a token's `2 x n_kv_heads x head_dim` values), plus
  the queries read and the outputs written for every row and step.
- The indexer's scores read the scored tokens' indexer keys once a layer
  (`index_head_dim` values, 128 B at 64 in bf16) and cost a multiply and an
  add over `index_n_heads x index_head_dim` a (query, key) pair; a decode
  step's are bound by the keys' bytes, a segment's by the products.
- A segment's attention under the selection is bound by compute: the q.k and
  p.v products over the SELECTED (query, key) pairs, `4 x n_heads x head_dim`
  operations each. A walk that visits every key block under a mask does the
  unselected pairs' products too and reads low here, honestly.

`steps` and `calls` are the reader's (`readers/trace_span_roofline.py`); the
events counted are XLA's own where no kernel carries the work, so `calls`
says nothing of the layers."""

from __future__ import annotations


def sparse_decode_attention(kv_tokens_selected: int, active_rows: int, steps: int, calls: int,
                            n_heads: int, n_kv_heads: int, head_dim: int, layers: int,
                            bytes_per_elem: int = 2) -> dict:
    k_and_v = kv_tokens_selected * 2 * n_kv_heads * head_dim * bytes_per_elem
    q_and_out = 2 * steps * active_rows * n_heads * head_dim * bytes_per_elem
    return {
        "ops": 4 * kv_tokens_selected * n_heads * head_dim * layers,
        "bytes": (k_and_v + q_and_out) * layers,
    }


def index_scores(index_tokens_scored: int, steps: int, calls: int, index_n_heads: int,
                 index_head_dim: int, layers: int, bytes_per_elem: int = 2) -> dict:
    keys = index_tokens_scored * index_head_dim * bytes_per_elem
    scores = index_tokens_scored * 4  # one float32 a pair, written
    return {
        "ops": 2 * index_tokens_scored * index_n_heads * index_head_dim * layers,
        "bytes": (keys + scores) * layers,
    }


def sparse_segment_attention(kv_tokens_selected: int, real_tokens: int, offset: int, steps: int,
                             calls: int, n_heads: int, n_kv_heads: int, head_dim: int,
                             layers: int, bytes_per_elem: int = 2) -> dict:
    q_and_out = 2 * real_tokens * n_heads * head_dim
    k_and_v = 2 * n_kv_heads * head_dim * (offset + real_tokens)
    return {
        "ops": 4 * kv_tokens_selected * n_heads * head_dim * layers,
        "bytes": (q_and_out + k_and_v) * bytes_per_elem * layers,
    }

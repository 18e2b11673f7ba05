"""Plain reference of the language model of Keye-VL-2.0-30B-A3B
(`model_type: KeyeVL2`, Kwai-Keye/Keye-VL-2.0-30B-A3B), one sequence at a
time: float32 `jax.numpy` at `jax.default_matmul_precision("highest")`, no
kernel, no cache, no batching. Weights arrive as the served int8 tree
(`{"q": int8, "s": f32}` a matrix, one scale an output channel) and are
dequantised here, so system and reference see the same numbers. `dims` is a
plain dict read from the configuration file. Text only: the catalog's row
holds no key of the vision tower, so its equations cannot be written.

The layer, on `x` [T, 2048], no bias but the indexer's LayerNorm, `eps` 1e-6:

- `u = RMSNorm(x; g_attn)`; `q = u Wq` (32 heads x 128), `k = u Wk`,
  `v = u Wv` (4 heads x 128); `q_h = RMSNorm_128(q_h; g_q)`,
  `k_h = RMSNorm_128(k_h; g_k)`: each head over its 128, one weight vector
  shared by the heads, before the rotary turn (Qwen3-MoE's block, which this
  language model is; `config.json` has no key for it: `assumed`).
- m-rope. A token has a position triple `(p_t, p_h, p_w)`. With
  `f_i = theta^(-i/64)`, `i` = 0..63, `theta` = 1e7, frequency `i` turns by
  `p_t` for `i` < 16, by `p_h` for 16 <= `i` < 40, by `p_w` for `i` >= 40
  (`mrope_section` [16, 24, 24]); pairs `(i, i + 64)`. Text has
  `p_t = p_h = p_w`. `positions` is `[3, T]` (None: 0..T-1 thrice).
- The indexer (`sa_config`: 16 heads of 64, one key head, `topk` 2048).
  `qI = u W_qI` [T, 16, 64]; `kI = LayerNorm_64(u W_kI; g_I, b_I)` [T, 64];
  both turned by the rotary rule over the head's 64 (pairs `(i, i + 32)`,
  `f_i = theta^(-i/32)`, the text position `p_t`); `w = (u W_w) / sqrt(16 * 64)`
  [T, 16]. Score of key `s` for query `t`, `s <= t`:
  `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`. Selection `S_t` = the
  `min(t + 1, 2048)` positions `s <= t` of largest `I[t, s]`, a tie to the
  lower position (`lax.top_k`'s rule). One selection a token and layer,
  shared by all 32 heads.
- `a = softmax(q k^T / sqrt(128) + M) v`, GQA 32/4, `M[t, s] = 0` iff
  `s in S_t`, else -inf. Up to position 2,047 this is causal attention.
  `x = x + a Wo`.
- `u = RMSNorm(x; g_ffn)`; `p = softmax(u Wr)` over all 128 experts in
  float32; the 8 largest; weights `p_e / sum_top8 p` (`norm_topk_prob` true);
  `y = sum_e w_e W_down,e (silu(W_gate,e u) * W_up,e u)`, width 768; no shared
  expert; every layer sparse (`intermediate_size` 6144 is read by no layer).
  `x = x + y`. Final `RMSNorm`, `logits = h W_head` (untied), next-token.

Departures, each noted in the configuration file's `assumed` too:

- the scores `I` are formed one indexer head at a time and summed, and the
  attention one query head at a time (a whole `[16, T, T]` or `[32, T, T]`
  float32 tensor at the check's width does not fit beside the engine):
  `q_chunk_size` and `kv_chunk_size` are tiles of that kind and change no
  result;
- the selection is found as `lax.top_k`'s set without its scatter: the k-th
  largest score of a row (the last of `lax.top_k`'s values), every score above
  it, and of the scores AT it the lowest positions until the row holds
  `min(t + 1, topk)`;
- a score of -0.0 (every head's ReLU shut, negative weights) reads +0.0.

Besides the output a layer reports, per token, the gap between the 8th and
the 9th router logit (where it is inside the program's rounding a different
expert may legitimately be picked: the check counts such tokens as
tie-exposed) and each expert's load; the attention half also reports
`select_gap`, the gap between the topk-th and the next score of each query
(inf where the query keeps all it sees), and, as its `router_gap`, 0 where
that gap is under `dims["eps_select"]` (a key of the file's `check` block;
0 or absent: no query is excused) and inf elsewhere: a selection that bf16
scores may legitimately make otherwise is a tie like a router's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def dequant(w) -> jax.Array:
    if isinstance(w, dict):
        return w["q"].astype(jnp.float32) * w["s"].astype(jnp.float32)
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32)


def layer_norm(x, weight, bias, eps: float):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    normed = xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return normed * weight.astype(jnp.float32) + bias.astype(jnp.float32)


def rope(x, angles):
    """x: [S, H, D]; angles [S, D/2], frequency i's turn of each token;
    pairs (i, i + D/2)."""
    half = x.shape[-1] // 2
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mrope_angles(positions, half: int, theta: float, sections):
    """[S, half]: frequency i times the position stream its section names.
    positions [3, S]."""
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(sections), total_repeat_length=half)
    return positions.astype(jnp.float32)[stream, :].T * inv_freq[None, :]


def index_scores(u, lp, positions, dims):
    """[T, T] float32: I[t, s], one indexer head at a time."""
    s = u.shape[0]
    hi, di, theta = dims["index_n_heads"], dims["index_head_dim"], dims["rope_theta"]
    inv_freq = theta ** (-jnp.arange(di // 2, dtype=jnp.float32) / (di // 2))
    angles = positions[0].astype(jnp.float32)[:, None] * inv_freq[None, :]
    q = rope((u @ dequant(lp["wq_idx"])).reshape(s, hi, di), angles)
    k = layer_norm(u @ dequant(lp["wk_idx"]), lp["idx_norm"], lp["idx_bias"], dims["eps"])
    k = rope(k[:, None, :], angles)[:, 0]
    w = (u @ lp["w_idx"].astype(jnp.float32)) * (hi * di) ** -0.5  # [T, Hi]

    def one_head(acc, xs):
        q_h, w_h = xs  # [T, Di], [T]
        return acc + w_h[:, None] * jax.nn.relu(q_h @ k.T), None

    scores, _ = jax.lax.scan(
        one_head, jnp.zeros((s, s), jnp.float32), (q.transpose(1, 0, 2), w.T)
    )
    return scores + 0.0


def select(scores, topk: int):
    """(S_t as a mask [T, T], the gap between each query's topk-th and next
    score): the `min(t + 1, topk)` positions s <= t of largest score, a tie
    to the lower position."""
    s = scores.shape[0]
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
    if s <= topk:
        return causal, jnp.full((s,), jnp.inf)
    masked = jnp.where(causal, scores, -jnp.inf)
    ranked = jax.lax.top_k(masked, topk + 1)[0]
    kth, nxt = ranked[:, topk - 1], ranked[:, topk]
    above = masked > kth[:, None]
    at = causal & (masked == kth[:, None])
    room = jnp.minimum(jnp.arange(s) + 1, topk) - above.sum(-1)
    chosen = above | (at & (jnp.cumsum(at, axis=-1) <= room[:, None]))
    # a query that sees no more than topk keeps them all
    keeps_all = jnp.arange(s) < topk
    return jnp.where(keeps_all[:, None], causal, chosen), jnp.where(keeps_all, jnp.inf, kth - nxt)


def attention_block(x, lp, dims, positions=None):
    """x: [S, d_model] float32 -> (x + attention(x) under the selection, info)."""
    s = x.shape[0]
    h, hkv, hd, eps = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"], dims["eps"]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (3, s))
    u = rms_norm(x, lp["attn_norm"], eps)
    angles = mrope_angles(positions, hd // 2, dims["rope_theta"], dims["mrope_section"])
    q = rope(rms_norm((u @ dequant(lp["wq"])).reshape(s, h, hd), lp["q_norm"], eps), angles)
    k = rope(rms_norm((u @ dequant(lp["wk"])).reshape(s, hkv, hd), lp["k_norm"], eps), angles)
    v = (u @ dequant(lp["wv"])).reshape(s, hkv, hd)
    seen, gap = select(index_scores(u, lp, positions, dims), dims["index_topk"])
    group = h // hkv

    def one_head(_, xs):  # query head i reads KV head i // group
        q_h, kv = xs  # [S, D], index of the KV head
        scores = (q_h @ k[:, kv].T) * hd**-0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return None, probs @ v[:, kv]

    _, out = jax.lax.scan(one_head, None, (q.transpose(1, 0, 2), jnp.arange(h) // group))
    out = out.transpose(1, 0, 2).reshape(s, h * hd)
    return x + out @ dequant(lp["wo"]), {"select_gap": gap, "selected": seen}


def moe(u, lp, dims):
    """u: [S, d_model] normed hidden state -> (the expert layer's output, info)."""
    k = dims["top_k"]
    logits = u @ lp["router"].astype(jnp.float32)  # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)  # over ALL experts
    top, chosen = jax.lax.top_k(probs, k)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)  # norm_topk_prob
    gate = jnp.zeros_like(logits).at[jnp.arange(u.shape[0])[:, None], chosen].set(weights)

    def one_expert(acc, xs):
        w_gate, w_up, w_down, g = xs
        hidden = jax.nn.silu(u @ dequant(w_gate)) * (u @ dequant(w_up))
        return acc + g[:, None] * (hidden @ dequant(w_down)), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u), (lp["w_gate"], lp["w_up"], lp["w_down"], gate.T)
    )
    ranked = jax.lax.top_k(logits, k + 1)[0]
    info = {
        "router_gap": ranked[:, k - 1] - ranked[:, k],  # 8th minus 9th logit
        "chosen": chosen,
        "expert_load": (gate > 0).sum(axis=0),
    }
    return out, info


def layer(x, lp, dims, positions=None):
    """One layer; or, where `lp` holds the leaves of one HALF only (the
    attention half's `wq` ..., or the expert half's `router` ...), that half:
    `x + attention(x)` or `x + moe(norm(x))`. The check steps through a layer
    half by half (`families/keye_vl2.py`, `system_chain`), so that the router
    here reads the very hidden state the program's router read."""
    info = {"expert_load": jnp.zeros((dims["n_experts"],), jnp.int32)}
    with jax.default_matmul_precision(HIGHEST):
        if "wq" in lp:
            x, picked = attention_block(x, lp, dims, positions)
            # a query whose topk-th and next score lie under `eps_select`
            # apart is tie-exposed, as a token at a router tie is (the check
            # reads `router_gap`; 0: no query is excused)
            tied = picked["select_gap"] < dims.get("eps_select", 0.0)
            info = {**info, "select_gap": picked["select_gap"],
                    "router_gap": jnp.where(tied, 0.0, jnp.inf)}
        if "router" in lp:
            out, routed = moe(rms_norm(x, lp["ffn_norm"], dims["eps"]), lp, dims)
            x, info = x + out, {**info, **routed}
    return x, info


def embed(params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def unembed(params, x, dims):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, params["final_norm"], dims["eps"]) @ dequant(params["lm_head"])


def forward(params, tokens, dims, positions=None):
    """Logits [S, V] of a whole sequence, every layer in turn."""
    x = embed(params, tokens)
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for index in range(n_layers):
        x, _ = layer(x, jax.tree.map(lambda a: a[index], params["layers"]), dims, positions)
    return unembed(params, x, dims)

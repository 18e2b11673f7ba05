"""Plain reference of GLM-5 (`model_type: glm_moe_dsa`, zai-org/GLM-5), one
sequence at a time: float32 `jax.numpy` at
`jax.default_matmul_precision("highest")`, no kernel, no cache, no batching,
and ONLY the expanded form of the latent attention: nothing here absorbs a
projection into a query. Weights arrive as the served int8 tree (`{"q": int8,
"s": f32}` a matrix, one scale an output channel) and are dequantised here, so
system and reference see the same numbers. `dims` is a plain dict read from
the configuration file.

Layer `l` on `x` [T, 6144], no bias but the indexer's LayerNorm, `eps` 1e-5,
rotary theta 1e6:

- `u = RMSNorm(x; g_in)`.
- Query. `c_q = RMSNorm_2048(u W_dq; g_q)`; `q = c_q W_uq` -> [T, 64, 256]; of
  each head the first 192 are `q_nope`, the last 64 `q_rope`, turned by the
  rotary rule in interleaved pairs `(2i, 2i+1)`, `f_i = theta^(-i/32)`,
  `i` = 0..31 (`rope_interleave` true).
- Key-value latent. `[c | r] = u W_dkv` (512 | 64); `c_kv = RMSNorm_512(c;
  g_kv)`; `k_rope = rotary(r)`, ONE 64-wide key for all heads. A token's cache
  is `(c_kv, k_rope)`. `[k_nope | v] = c_kv W_ukv` -> [T, 64, 192 | 256];
  `k_h = [k_nope_h | k_rope]`.
- The indexer (`index_n_heads` 32, `index_head_dim` 128, `index_topk` 2048):
  `qI = c_q W_qI` [T, 32, 128] (from the QUERY LATENT); `kI = LayerNorm_128(u
  W_kI; g_I, b_I)` [T, 128]; of each the FIRST 64 are turned by the rotary rule
  above (`indexer_rope_interleave` true), the other 64 are not; `w = (u W_w) /
  sqrt(32 * 128)` [T, 32] in float32. `I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s])`, accumulated in float32; `S_t` = the `min(t + 1, 2048)` positions
  `s <= t` of largest `I[t, s]`, a tie to the lower position; one selection a
  token and layer for all 64 heads. The dense layer has its indexer like every
  layer.
- `a_h = softmax(q_h k_h^T / sqrt(256) + M) v_h`, `M[t, s] = 0` iff `s in S_t`,
  else -inf (`rope_type` default: no further scale); `x = x + concat_h(a_h)
  W_o` (16,384 -> 6,144).
- `u = RMSNorm(x; g_ffn)`. A leading dense layer: `x = x + W_down (silu(W_gate
  u) * W_up u)`, width 12,288. An expert layer: `s = sigmoid(u W_r)` over all
  256 in float32; chosen `E` = the 8 largest of `s + b` (`b` the layer's
  `e_score_correction_bias` [256], float32; `n_group` 1, `topk_group` 1: no
  groups); weights `g_e = 2.5 * s_e / sum_{e in E} s_e` (the bias chooses and
  does NOT weigh; `norm_topk_prob`, `routed_scaling_factor`); `x = x + sum_{e
  in E} g_e Expert_e(u) + Shared(u)`, experts and the shared one SwiGLU of
  width 2,048. Final RMSNorm, `logits = h W_head` (untied).

A share of the experts (`dims["experts_held"]` = (first, count): the tree
holds those experts' weights alone): the router stays `n_experts` wide, every
token's 8 are chosen among ALL of them, and what an absent expert would add is
left out, as on the chip that holds the others its own part is; the shared
expert is whole in every share.

Departures, each noted in the configuration file's `assumed` too:

- DeepSeek-V3.2's published indexer rotates `qI` and `kI` by a Hadamard
  matrix and rounds them to FP8: the rotation is orthogonal and changes no
  score, the rounding is a kernel's precision and is replaced by the
  `index_key_dtype` the file states. Neither is here;
- the multi-token-prediction module (`num_nextn_predict_layers` 1) is left
  out: the model's own logits do not pass through it;
- the scores `I` are formed one indexer head at a time and the attention one
  query head at a time (a whole [32, T, T] or [64, T, T] float32 tensor at the
  check's width does not fit beside the engine); the experts one at a time;
- the selection is `lax.top_k`'s set found without its scatter, and a score of
  -0.0 reads +0.0 (as `reference/keye_vl2.py`).

Besides its output a layer reports, per token, `router_gap` (the gap between
the 8th and the 9th of `s + b`: under the program's rounding another expert
may legitimately be picked, and the check counts such tokens tie-exposed),
each expert's load and its `chosen`; the attention half `select_gap` (the gap
between a query's topk-th and next score, inf where it keeps all it sees) and
`selected`, and as its `router_gap` 0 where that gap is under
`dims["eps_select"]` (0 or absent: no query is excused), inf elsewhere.
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


def rope_interleaved(x, angles):
    """x: [S, H, D]; angles [S, D/2]; pairs (2i, 2i + 1) turned by angle i."""
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def turn_first(x, angles, width: int):
    """x [S, H, D] with its first `width` lanes turned, the rest as they are."""
    return jnp.concatenate([rope_interleaved(x[..., :width], angles), x[..., width:]], axis=-1)


def index_scores(u, c_q, lp, angles, dims):
    """[T, T] float32: I[t, s], one indexer head at a time."""
    s = u.shape[0]
    hi, di, rope = dims["index_n_heads"], dims["index_head_dim"], dims["qk_rope_head_dim"]
    q_in = c_q if dims.get("index_query_input", "query_latent") == "query_latent" else u
    q = turn_first((q_in @ dequant(lp["wq_idx"])).reshape(s, hi, di), angles, rope)
    k = layer_norm(u @ dequant(lp["wk_idx"]), lp["idx_norm"], lp["idx_bias"], dims["eps"])
    k = turn_first(k[:, None, :], angles, rope)[:, 0]
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
    keeps_all = jnp.arange(s) < topk  # a query that sees no more than topk keeps them all
    return jnp.where(keeps_all[:, None], causal, chosen), jnp.where(keeps_all, jnp.inf, kth - nxt)


def attention_block(x, lp, dims, positions=None):
    """x: [S, d_model] float32 -> (x + attention(x) under the selection, info).
    The EXPANDED form: every position's keys and values of all heads."""
    s = x.shape[0]
    h, eps = dims["n_heads"], dims["eps"]
    kl, nope, rope, vd = (
        dims["kv_lora_rank"], dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
        dims["v_head_dim"],
    )
    if positions is None:
        positions = jnp.arange(s)
    inv_freq = dims["rope_theta"] ** (-jnp.arange(rope // 2, dtype=jnp.float32) / (rope // 2))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    u = rms_norm(x, lp["attn_norm"], eps)
    c_q = rms_norm(u @ dequant(lp["wq_a"]), lp["q_a_norm"], eps)
    q = (c_q @ dequant(lp["wq_b"])).reshape(s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], rope_interleaved(q[..., nope:], angles)], axis=-1)
    down = u @ dequant(lp["wkv_a"])
    c_kv = rms_norm(down[:, :kl], lp["kv_a_norm"], eps)
    k_rope = rope_interleaved(down[:, None, kl:], angles)[:, 0]  # [S, rope]: one key for all heads
    up = (c_kv @ dequant(lp["wkv_b"])).reshape(s, h, nope + vd)
    k_nope, v = up[..., :nope], up[..., nope:]
    seen, gap = select(index_scores(u, c_q, lp, angles, dims), dims["index_topk"])

    def one_head(_, xs):
        q_h, k_h, v_h = xs  # [S, nope + rope], [S, nope], [S, v]
        scores = (q_h[:, :nope] @ k_h.T + q_h[:, nope:] @ k_rope.T) * (nope + rope) ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return None, probs @ v_h

    _, out = jax.lax.scan(
        one_head, None, (q.transpose(1, 0, 2), k_nope.transpose(1, 0, 2), v.transpose(1, 0, 2))
    )
    out = out.transpose(1, 0, 2).reshape(s, h * vd)
    return x + out @ dequant(lp["wo"]), {"select_gap": gap, "selected": seen}


def swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ dequant(w_gate)) * (u @ dequant(w_up))) @ dequant(w_down)


def moe(u, lp, dims):
    """u: [S, d_model] normed hidden state -> (the expert layer's output, info).
    The tree holds experts `experts_held` = (first, count) of `n_experts`."""
    k, e = dims["top_k"], dims["n_experts"]
    first, held = dims.get("experts_held") or (0, e)
    scores = jax.nn.sigmoid(u @ lp["router"].astype(jnp.float32))  # [S, E], ALL experts
    biased = scores + lp["router_bias"].astype(jnp.float32)
    _, chosen = jax.lax.top_k(biased, k)  # the bias chooses ...
    top = jnp.take_along_axis(scores, chosen, axis=-1)  # ... and does not weigh
    weights = dims["routed_scaling"] * top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], chosen].set(weights)

    def one_expert(acc, xs):
        w_gate, w_up, w_down, g = xs
        return acc + g[:, None] * swiglu(u, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (lp["w_gate"], lp["w_up"], lp["w_down"], gate.T[first:first + held]),
    )
    out = out + swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])  # the shared expert, whole
    ranked = jax.lax.top_k(biased, k + 1)[0]
    info = {
        "router_gap": ranked[:, k - 1] - ranked[:, k],
        "chosen": chosen,
        "expert_load": (gate > 0).sum(axis=0),
    }
    return out, info


def layer(x, lp, dims, positions=None):
    """One layer; or, where `lp` holds the leaves of one HALF only (the
    attention half's `wq_a` ..., a dense FFN's `w_gate` without a router, or
    the expert half's `router` ...), that half. The check steps through a
    layer half by half (`families/glm_moe_dsa.py`, `system_chain`), so that
    the router here reads the very hidden state the program's router read.
    `lp` may come under its kind's name, `{kind: leaves}` (`dense` | `sparse`):
    the kind says nothing the leaves do not."""
    if len(lp) == 1 and next(iter(lp)) in ("dense", "sparse"):
        lp = next(iter(lp.values()))
    info = {"expert_load": jnp.zeros((dims["n_experts"],), jnp.int32)}
    with jax.default_matmul_precision(HIGHEST):
        if "wq_a" in lp:
            x, picked = attention_block(x, lp, dims, positions)
            tied = picked["select_gap"] < dims.get("eps_select", 0.0)
            info = {**info, "select_gap": picked["select_gap"], "selected": picked["selected"],
                    "router_gap": jnp.where(tied, 0.0, jnp.inf)}
        if "router" in lp:
            out, routed = moe(rms_norm(x, lp["ffn_norm"], dims["eps"]), lp, dims)
            x, info = x + out, {**info, **routed}
        elif "w_gate" in lp:
            u = rms_norm(x, lp["ffn_norm"], dims["eps"])
            x = x + swiglu(u, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x, info


def embed(params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def unembed(params, x, dims):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, params["final_norm"], dims["eps"]) @ dequant(params["lm_head"])


def forward(params, tokens, dims, positions=None):
    """Logits [S, V] of a whole sequence: the leading dense layers
    (`params["dense_layers"]`), then the expert layers, every one in turn."""
    x = embed(params, tokens)
    for stack in ("dense_layers", "layers"):
        if stack not in params:
            continue
        for index in range(jax.tree.leaves(params[stack])[0].shape[0]):
            x, _ = layer(x, jax.tree.map(lambda a: a[index], params[stack]), dims, positions)
    return unembed(params, x, dims)

"""Plain reference of dots3-note-prev's language model (`model_type:
dots3_note`, dots-studio/dots3-note-prev), one sequence at a time: float32
`jax.numpy` at `jax.default_matmul_precision("highest")`, no kernel, no cache,
no batching, and ONLY the expanded form of the latent attention: nothing here
absorbs a projection into a query, keeps a ring or gathers a band. Weights
arrive as the served int8 tree (`{"q": int8, "s": f32}` a matrix, one scale an
output channel) and are dequantised here, so system and reference see the same
numbers. `dims` is a plain dict read from the configuration file.

TWO kinds of layer, each with a latent of its own geometry (`dims["kinds"]`:
`full` and `window`, each `n_heads` H, `q_lora_rank` r_q, `kv_lora_rank` r_kv,
`qk_nope_head_dim` d_n, `qk_rope_head_dim` d_r, `v_head_dim` d_v,
`rope_theta`). Layer `l` of kind k on `x` [T, 5120], no bias but the indexer's
LayerNorm, `eps` 1e-5:

- `u = RMSNorm(x; g_in)`.
- Query. `c_q = rho_q RMSNorm(u W_dq; g_q)`, `rho_q = sqrt(5120 / r_q)`
  (`apply_mla_qkv_lora_rescale`); `q = c_q W_uq` -> [T, H, d_n + d_r], each
  head's last d_r turned by the rotary rule in interleaved pairs `(2i, 2i+1)`,
  `f_i = theta_k^(-2i/d_r)`.
- Key-value latent. `[c | r] = u W_dkv` (r_kv | d_r); `c_kv = rho_kv
  RMSNorm(c; g_kv)`, `rho_kv = sqrt(5120 / r_kv)`; `k_rope = rotary(r)` at
  theta_k, ONE key for all heads, NOT rescaled. `[k_nope | v] = c_kv W_ukv` ->
  [T, H, d_n | d_v]; `k_h = [k_nope_h | k_rope]`; scores `q.k / sqrt(d_n + d_r)`.
- What query t sees. `window`: keys s with `t - (sliding_window - 1) <= s <=
  t` (513 with itself). `full`: the `min(t + 1, index_topk)` positions `s <= t`
  of largest `I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`, `qI = c_q W_qI`
  [T, 64, 128] (from the RESCALED query latent), `kI = LayerNorm_128(u W_kI)`,
  the first d_r lanes of both turned by the full kind's rotary, `w = (u W_w) /
  sqrt(64 x 128)` in float32, a tie to the lower position
  (`reference/glm_moe_dsa.py`'s indexer to the letter: its functions are
  copied here). The window kind has no indexer.
- `o_h = softmax(scores over what is seen) v_h`; `g = sigmoid(u W_g)` [T, H];
  `o_h <- g_h o_h` (`attention_gate_type` headwise: one scalar a head and
  token, after the softmax's mix, before W_o); `x = x + concat_h(o_h) W_o`.
- `u = RMSNorm(x; g_ffn)`. The leading dense layer: `x = x + W_down
  (silu(W_gate u) * W_up u)`, width 13,824. An expert layer: `s = sigmoid(u
  W_r)` over all 256 in float32; chosen `E` = the 8 largest of `s + b`
  (`noaux_tc`, no groups); weights `g_e = routed_scaling x s_e / sum_{e in E}
  s_e` (scaling 1); `x = x + sum_{e in E} g_e Expert_e(u) + Shared(u)`,
  SwiGLU of width 1,536. Final RMSNorm, `logits = h W_head` (untied).

A share of the experts (`dims["experts_held"]` = (first, count)): as
`reference/glm_moe_dsa.py`.

Departures, each in the configuration file's `assumed` too: the vision tower,
the audio encoder and multi-token prediction are left out; the indexer's
Hadamard rotation and FP8 rounding are left out (as GLM-5's file); scores are
formed one head at a time and experts one at a time.

Besides its output a layer reports `router_gap`, `expert_load`, `chosen`
(expert half) and `select_gap`, `selected` (a full kind's attention half), as
`reference/glm_moe_dsa.py` does and for the same use.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"
KINDS = ("full_dense", "full", "window")  # a layer's kind, as `lp` may be named


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


def index_scores(u, c_q, lp, angles, dims, rope: int):
    """[T, T] float32: I[t, s], one indexer head at a time."""
    s = u.shape[0]
    hi, di = dims["index_n_heads"], dims["index_head_dim"]
    q = turn_first((c_q @ dequant(lp["wq_idx"])).reshape(s, hi, di), angles, rope)
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


def attention_block(x, lp, dims, kind: str, positions=None):
    """x: [S, d_model] float32 -> (x + the kind's attention of x, info): the
    EXPANDED form, every position's keys and values of all heads. `kind`:
    `full` (under the indexer's selection) | `window` (the last
    `sliding_window` positions)."""
    s, d = x.shape
    geo, eps = dims["kinds"][kind], dims["eps"]
    h, ql, kl = geo["n_heads"], geo["q_lora_rank"], geo["kv_lora_rank"]
    nope, rope, vd = geo["qk_nope_head_dim"], geo["qk_rope_head_dim"], geo["v_head_dim"]
    if positions is None:
        positions = jnp.arange(s)
    inv_freq = geo["rope_theta"] ** (-jnp.arange(rope // 2, dtype=jnp.float32) / (rope // 2))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    rho_q, rho_kv = (d / ql) ** 0.5, (d / kl) ** 0.5  # apply_mla_qkv_lora_rescale
    u = rms_norm(x, lp["attn_norm"], eps)
    c_q = rho_q * rms_norm(u @ dequant(lp["wq_a"]), lp["q_a_norm"], eps)
    q = (c_q @ dequant(lp["wq_b"])).reshape(s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], rope_interleaved(q[..., nope:], angles)], axis=-1)
    down = u @ dequant(lp["wkv_a"])
    c_kv = rho_kv * rms_norm(down[:, :kl], lp["kv_a_norm"], eps)
    k_rope = rope_interleaved(down[:, None, kl:], angles)[:, 0]  # [S, rope], not rescaled
    up = (c_kv @ dequant(lp["wkv_b"])).reshape(s, h, nope + vd)
    k_nope, v = up[..., :nope], up[..., nope:]
    info = {}
    if kind == "window":
        at = jnp.arange(s)
        seen = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - dims["sliding_window"])
    else:
        seen, gap = select(index_scores(u, c_q, lp, angles, dims, rope), dims["index_topk"])
        info = {"select_gap": gap, "selected": seen}
    gate = jax.nn.sigmoid(u @ dequant(lp["w_attn_gate"]))  # [S, H]: headwise

    def one_head(_, xs):
        q_h, k_h, v_h, g_h = xs  # [S, nope + rope], [S, nope], [S, v], [S]
        scores = (q_h[:, :nope] @ k_h.T + q_h[:, nope:] @ k_rope.T) * (nope + rope) ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return None, g_h[:, None] * (probs @ v_h)

    _, out = jax.lax.scan(
        one_head, None,
        (q.transpose(1, 0, 2), k_nope.transpose(1, 0, 2), v.transpose(1, 0, 2), gate.T),
    )
    out = out.transpose(1, 0, 2).reshape(s, h * vd)
    return x + out @ dequant(lp["wo"]), info


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
    if dims.get("shared", True):  # (the share test sums parts: the shared expert once)
        out = out + swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    ranked = jax.lax.top_k(biased, k + 1)[0]
    info = {
        "router_gap": ranked[:, k - 1] - ranked[:, k],
        "chosen": chosen,
        "expert_load": (gate > 0).sum(axis=0),
    }
    return out, info


def layer(x, lp, dims, positions=None):
    """One layer of the kind `lp` comes under, `{kind: leaves}` with kind one
    of `KINDS` (`full_dense`: the full kind's attention and the dense FFN;
    `full`, `window`: the kind's attention and the expert layer); or, where
    the leaves are those of one HALF only (the attention half's `wq_a` ...,
    a dense FFN's `w_gate` without a router, the expert half's `router` ...),
    that half: the check steps through a layer half by half
    (`families/dots3_note.py`, `system_chain`)."""
    (kind, lp), = lp.items()
    info = {"expert_load": jnp.zeros((dims["n_experts"],), jnp.int32)}
    with jax.default_matmul_precision(HIGHEST):
        if "wq_a" in lp:
            x, picked = attention_block(
                x, lp, dims, "window" if kind == "window" else "full", positions
            )
            if picked:
                tied = picked["select_gap"] < dims.get("eps_select", 0.0)
                info = {**info, **picked, "router_gap": jnp.where(tied, 0.0, jnp.inf)}
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


def layer_places(dims) -> list:
    """[(stack, the program's kind, the layer's place in that stack, the
    reference's kind)] in the model's order, from `dims["layer_types"]` and
    `dims["n_leading_dense"]`: a kind's leading dense layers are a stack of
    their own, `params["dense_layers"][kind]`."""
    seen, places = {}, []
    for i, kind in enumerate(dims["layer_types"]):
        dense = i < dims["n_leading_dense"]
        stack = "dense_layers" if dense else "layers"
        at = seen.get((stack, kind), 0)
        seen[stack, kind] = at + 1
        name = "window" if kind == "sliding_attention" else "full_dense" if dense else "full"
        places.append((stack, kind, at, name))
    return places


def forward(params, tokens, dims, positions=None):
    """Logits [S, V] of a whole sequence, every layer in the model's order."""
    x = embed(params, tokens)
    for stack, kind, at, name in layer_places(dims):
        lp = jax.tree.map(lambda a: a[at], params[stack][kind])
        x, _ = layer(x, {name: lp}, dims, positions)
    return unembed(params, x, dims)

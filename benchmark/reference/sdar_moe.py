"""Plain reference of the SDAR-MoE block (`model_type: sdar_moe`,
JetLM/SDAR-30B-A3B-Chat), one sequence at a time: float32 `jax.numpy` at
`jax.default_matmul_precision("highest")`, no kernel, no cache, no batching.
Weights arrive as the served int8 tree (`{"q": int8, "s": f32}` a matrix, one
scale an output channel) and are dequantised here, so system and reference
see the same numbers. `dims` is a plain dict read from the configuration file.

The layer, on `x` [T, 2048], no bias anywhere, `eps` 1e-6:

- `u = RMSNorm(x; g_attn)`; `q = u Wq` (32 heads x 128), `k = u Wk`,
  `v = u Wv` (4 heads x 128).
- `q_h = RMSNorm_128(q_h; g_q)`, `k_h = RMSNorm_128(k_h; g_k)`: each head over
  its 128, one weight vector shared by the heads, before the rotary turn.
  (`config.json` has no key for it: `sdar_moe` is Qwen3-MoE's block, which has
  it unconditionally; the configuration file lists it under `assumed`.)
- Rotary on q and k, pairs `(i, i + 64)`, `rope_theta` 1e6, no scaling.
- `a = softmax(q k^T / sqrt(128) + M) v`, GQA 32/4. `M`: key `j` is visible
  to query `i` iff `floor(j / B) <= floor(i / B)`: causal across blocks of
  `B = block_length` positions, two-way inside one. `x = x + a Wo`.
- `u = RMSNorm(x; g_ffn)`; `p = softmax(u Wr)` over all 128 experts in
  float32; the 8 largest; weights `p_e / sum_top8 p` (`norm_topk_prob` true);
  `y = sum_e w_e W_down,e (silu(W_gate,e u) * W_up,e u)`, expert width 768; no
  shared expert; every layer sparse (`decoder_sparse_step` 1,
  `mlp_only_layers` []), so `intermediate_size` 6144 is read by NO layer.
  `x = x + y`. This file computes every expert for every token and keeps the
  chosen ones, one expert dequantised to float32 at a time (a whole layer in
  float32 is 2.4 GB).
- Final `RMSNorm`, `logits = h W_head` (untied). The logits at position `i`
  score the token AT `i` (no shift): a masked position is answered in place.

Generation (the family's published inference script and its defaults, written
from knowledge of it with no network to check against; the catalog marks block
length and schedule `not_given`, so the configuration file lists each item
under `assumed`): block length `B = 4`, `denoising_steps = 4`, mask id 151669,
remasking `low_confidence_dynamic` with `confidence_threshold` 0.9. With `n`
prompt tokens the first `floor(n / B) * B` are prefilled under `M` and yield
no token. Then, block by block: the block is the prompt's tail (if any)
followed by mask ids; a DENOISE pass runs the block's B positions against the
prefix and the block itself, takes at each OPEN position the token (argmax,
or a draw at the request's temperature / top-k / top-p) and its confidence
(the probability of that token under the softmax it was taken from), and
fixes every open position whose confidence exceeds the threshold, and at
least `schedule[step]` of the most confident (`schedule` = `B // steps` each,
the remainder on the first steps: 1, 1, 1, 1 here); when none is open a COMMIT
pass runs the clean block once more and its K/V become the cache's; then the
next block. `denoise_choice` below is that rule for one pass.

Departures, each noted in the configuration file too:

- the mask id's logit is set to -inf before the softmax: trained weights never
  choose it, seeded ones do;
- which positions are open is the row's STATE, never `token == mask id`: the
  harness draws prompt ids over the whole vocabulary, so a prompt can hold
  151669, and it is then text.

Besides the output a layer reports, per token, the gap between the 8th and
the 9th router logit (where it is inside the program's rounding a different
expert may legitimately be picked: the check counts such tokens as
tie-exposed) and each expert's load.
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


def rope(x, theta: float):
    """x: [S, H, D] at positions 0..S-1; pairs (i, i + D/2)."""
    s, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block_causal(width: int, block_length: int):
    """[query, key]: key j is visible to query i iff j // B <= i // B."""
    block = jnp.arange(width) // block_length
    return block[None, :] <= block[:, None]


def attention_block(x, lp, dims):
    """x: [S, d_model] float32 -> x + attention(x) under the block-causal mask."""
    s = x.shape[0]
    h, hkv, hd, eps = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"], dims["eps"]
    a = rms_norm(x, lp["attn_norm"], eps)
    q = (a @ dequant(lp["wq"])).reshape(s, h, hd)
    k = (a @ dequant(lp["wk"])).reshape(s, hkv, hd)
    v = (a @ dequant(lp["wv"])).reshape(s, hkv, hd)
    q = rope(rms_norm(q, lp["q_norm"], eps), dims["rope_theta"])
    k = rope(rms_norm(k, lp["k_norm"], eps), dims["rope_theta"])
    group = h // hkv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd**-0.5
    seen = block_causal(s, dims["block_length"])
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h * hd)
    return x + out @ dequant(lp["wo"])


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


def layer(x, lp, dims):
    """One layer; or, where `lp` holds the leaves of one HALF only (the
    attention half's `wq` ..., or the expert half's `router` ...), that half:
    `x + attention(x)` or `x + moe(norm(x))`. The check steps through a layer
    half by half (`families/sdar_moe.py`, `system_chain`), so that the router
    here reads the very hidden state the program's router read."""
    info = {"expert_load": jnp.zeros((dims["n_experts"],), jnp.int32)}
    with jax.default_matmul_precision(HIGHEST):
        if "wq" in lp:
            x = attention_block(x, lp, dims)
        if "router" in lp:
            out, info = moe(rms_norm(x, lp["ffn_norm"], dims["eps"]), lp, dims)
            x = x + out
    return x, info


def embed(params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def unembed(params, x, dims):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, params["final_norm"], dims["eps"]) @ dequant(params["lm_head"])


def forward(params, tokens, dims):
    """Logits [S, V] of a whole sequence (whole blocks), every layer in turn."""
    x = embed(params, tokens)
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for index in range(n_layers):
        x, _ = layer(x, jax.tree.map(lambda a: a[index], params["layers"]), dims)
    return unembed(params, x, dims)


def denoise_choice(logits, is_open, step: int, dims):
    """What one greedy denoise pass fixes: `logits` [B, V] at the block's
    positions, `is_open` [B] bool. Returns (tokens [B], fixed [B] bool)."""
    logits = jnp.asarray(logits, jnp.float32).at[:, dims["mask_token_id"]].set(-jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    tokens, confidence = jnp.argmax(probs, axis=-1), jnp.max(probs, axis=-1)
    b, steps = dims["block_length"], dims["denoising_steps"]
    at_least = b // steps + (step < b % steps)
    order = jnp.argsort(jnp.where(is_open, -confidence, jnp.inf), stable=True)
    rank = jnp.argsort(order, stable=True)
    fixed = is_open & ((confidence > dims["confidence_threshold"]) | (rank < at_least))
    return tokens, fixed

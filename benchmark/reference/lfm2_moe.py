"""Plain reference of the LFM2-MoE block (`model_type: lfm2_moe`,
LiquidAI/LFM2-24B-A2B), one sequence at a time: float32 `jax.numpy` at
`jax.default_matmul_precision("highest")`, no kernel, no cache, no tail, no
batching. Weights arrive as the served int8 tree (`{"q": int8, "s": f32}` a
matrix, one scale an output channel) and are dequantised here, so system and
reference see the same numbers. `dims` is a plain dict read from the
configuration file, not the program's config object.

40 layers of hidden size 2,048, `layer_types` = (conv, conv, full_attention,
conv) x 10, no bias anywhere, RMSNorm with `norm_eps` 1e-5. A layer is

    x = x + mixer(operator_norm(x));  x = x + ffn(ffn_norm(x))

- *conv mixer* (`Lfm2ShortConv`): `[B | C | u] = h W_in` (2,048 -> 3 x 2,048);
  `z = B * u`; `c_t = sum_{i=0..K-1} w[i] * z_(t-(K-1)+i)` with K =
  `conv_L_cache` = 3: a causal depthwise convolution over time, zeros before
  the first token, `conv_bias` false, NO activation (the tap `w[K - 1]`
  multiplies the current token); `out = (C * c) W_out`.
- *attention mixer*: `q = h Wq` (32 heads x 64), `k = h Wk`, `v = h Wv` (8
  heads x 64); RMSNorm of q and of k over each head's 64 (one weight vector
  shared by the heads) BEFORE rotary; rotary over the whole head in pairs
  (i, i + 32), theta 1e6, no scaling; `softmax(q k^T 64^-0.5 + causal) v`,
  GQA 32/8; `out = a Wo`.
- *ffn*: the first `num_dense_layers` = 2 layers SwiGLU of width 11,776; every
  later layer the expert layer: `s = sigmoid(u W_r)` over all 64 experts in
  float32; the 4 experts of largest `s + expert_bias` (`use_expert_bias`)
  are chosen (a tie to the lower index, `lax.top_k`'s); their weights are `s`
  WITHOUT the bias, divided by (their sum + 1e-6) (`norm_topk_prob`), times
  `routed_scaling_factor`; `y = sum_e w_e W_down,e (silu(W_gate,e u) *
  W_up,e u)`, expert width 1,536; no shared expert. Every expert is computed
  for every token and the chosen ones kept, one expert dequantised at a time.
- final RMSNorm (the published `embedding_norm`), `logits = h E^T` on the tied
  embedding.

Departures from the published forward pass, each also under `assumed` in the
configuration file (no network here to check the modelling code against):
`head_dim` 64 = hidden_size / heads (no key states it), the per-head q/k norm
(`q_layernorm`, `k_layernorm` in the family's code, no key), the tied output
head (`tie_word_embeddings` is not among the catalog's keys) and the router's
`+ 1e-6`. The published code scores the router in the activation dtype; here,
as in the program, in float32.

`layer` runs the HALF it is handed (the mixer's leaves, or the FFN's): the
check steps through a layer half by half (`families/lfm2_moe.py`,
`system_chain`), so that the router here reads the very hidden state the
program's router read. The tree arrives under its kind's name, `{kind:
leaves}` (`conv_dense` | `conv_expert` | `attention_expert`); the kind says
nothing the leaves do not. Besides its output a half reports, per token,
`router_gap` (the gap between the 4th and the 5th biased score: where it is
inside the program's rounding another expert may legitimately be picked, and
the check counts such tokens as tie-exposed; infinite where nothing is chosen)
and each expert's load.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"
ROUTER_EPS = 1e-6
KINDS = ("conv_dense", "conv_expert", "attention_dense", "attention_expert")


def dequant(w) -> jax.Array:
    if isinstance(w, dict):
        return w["q"].astype(jnp.float32) * w["s"].astype(jnp.float32)
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32)


def swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ dequant(w_gate)) * (u @ dequant(w_up))) @ dequant(w_down)


def rope(x, theta: float):
    """x: [S, H, D] at positions 0..S-1; pairs (i, i + D/2)."""
    s, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_conv(z, taps):
    """z [S, C], taps [K, C]: c_t = sum_i taps[i] z_(t - (K - 1) + i)."""
    width = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, z.shape[1]), z.dtype), z], axis=0)
    return sum(padded[i : i + z.shape[0]] * taps[i] for i in range(width))


def conv_mixer(x, lp, dims):
    d = x.shape[-1]
    gates = rms_norm(x, lp["attn_norm"], dims["eps"]) @ dequant(lp["w_in"])
    gate_b, gate_c, u = gates[:, :d], gates[:, d : 2 * d], gates[:, 2 * d :]
    mixed = causal_conv(gate_b * u, lp["conv_w"].astype(jnp.float32))
    return x + (gate_c * mixed) @ dequant(lp["w_out"])


def attention_mixer(x, lp, dims):
    s = x.shape[0]
    h, hkv, hd, eps = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"], dims["eps"]
    a = rms_norm(x, lp["attn_norm"], eps)
    q = (a @ dequant(lp["wq"])).reshape(s, h, hd)
    k = (a @ dequant(lp["wk"])).reshape(s, hkv, hd)
    v = (a @ dequant(lp["wv"])).reshape(s, hkv, hd)
    q = rope(rms_norm(q, lp["q_norm"], eps), dims["rope_theta"])
    k = rope(rms_norm(k, lp["k_norm"], eps), dims["rope_theta"])
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd**-0.5
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h * hd)
    return x + out @ dequant(lp["wo"])


def route(u, lp, dims):
    """(gate [S, E]: a token's weight on each expert, 0 where not chosen;
    chosen [S, k]; the biased scores the choice was made on)."""
    k = dims["top_k"]
    scores = jax.nn.sigmoid(u @ lp["router"].astype(jnp.float32))  # [S, E], ALL experts
    biased = scores + lp["router_bias"].astype(jnp.float32)
    _, chosen = lax.top_k(biased, k)  # the bias chooses ...
    top = jnp.take_along_axis(scores, chosen, axis=-1)  # ... and does not weigh
    weights = dims["routed_scaling"] * top / (jnp.sum(top, axis=-1, keepdims=True) + ROUTER_EPS)
    gate = jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], chosen].set(weights)
    return gate, chosen, biased


def moe(u, lp, dims):
    """u: [S, d_model] normed hidden state -> (the expert layer's output, info)."""
    k = dims["top_k"]
    gate, chosen, biased = route(u, lp, dims)

    def one_expert(acc, xs):
        w_gate, w_up, w_down, g = xs
        return acc + g[:, None] * swiglu(u, w_gate, w_up, w_down), None

    out, _ = lax.scan(
        one_expert, jnp.zeros_like(u), (lp["w_gate"], lp["w_up"], lp["w_down"], gate.T)
    )
    ranked = lax.top_k(biased, k + 1)[0]
    info = {
        "router_gap": ranked[:, k - 1] - ranked[:, k],
        "chosen": chosen,
        "expert_load": (gate > 0).sum(axis=0),
    }
    return out, info


def layer(x, lp, dims):
    """One layer, or the half `lp` holds the leaves of: the mixer's (`w_in`
    ...: a conv layer's; `wq` ...: an attention layer's), the FFN's (`router`
    ...: the expert layer; `w_gate` without a router: a leading dense layer's),
    or both. Returns (y, info)."""
    if len(lp) == 1 and next(iter(lp)) in KINDS:
        lp = next(iter(lp.values()))
    info = {"expert_load": jnp.zeros((dims["n_experts"],), jnp.int32)}
    with jax.default_matmul_precision(HIGHEST):
        if "w_in" in lp:
            x = conv_mixer(x, lp, dims)
        if "wq" in lp:
            x = attention_mixer(x, lp, dims)
        if "w_in" in lp or "wq" in lp:
            # a mixer chooses nothing: no position is tie-exposed by it
            info = {**info, "router_gap": jnp.full((x.shape[0],), jnp.inf)}
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
        return rms_norm(x, params["final_norm"], dims["eps"]) @ dequant(params["embed"]).T


def stack_of(params, index: int, dims):
    """Layer `index` of the model -> (the stack's key in the tree, the kind's
    name there, the layer's place in that stack): `layer_pattern` a period,
    the first `n_dense` layers their kinds' leading ones
    (`params["dense_layers"][kind]`), a kind's later layers behind them
    (`params["layers"][kind]`)."""
    pattern, n_dense = dims["layer_pattern"], dims["n_dense"]
    kind = pattern[index % len(pattern)]
    at = sum(pattern[i % len(pattern)] == kind for i in range(index))
    first = sum(pattern[i % len(pattern)] == kind for i in range(n_dense))
    return ("dense_layers", kind, at) if index < n_dense else ("layers", kind, at - first)


def forward(params, tokens, dims):
    """Logits [S, V] of a whole sequence, every layer in turn."""
    x = embed(params, tokens)
    for index in range(dims["n_layers"]):
        stack, kind, at = stack_of(params, index, dims)
        x, _ = layer(x, jax.tree.map(lambda a: a[at], params[stack][kind]), dims)
    return unembed(params, x, dims)

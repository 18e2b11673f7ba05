"""Plain reference of Kimi-K2.5's language model (`model_type: kimi_k2`,
moonshotai/Kimi-K2.5: the DeepSeek-V3 layer), one sequence at a time: float32
`jax.numpy` at `jax.default_matmul_precision("highest")`, no kernel, no cache,
no batching, and ONLY the expanded form of the latent attention: nothing here
absorbs a projection into a query, and every query reads EVERY key behind it
(no indexer, no selection). Weights arrive as the served int8 tree (`{"q":
int8, "s": f32}` a matrix, one scale an output channel) and are dequantised
here, so system and reference see the same numbers. `dims` is a plain dict
read from the configuration file. It imports nothing of the program.

Layer `l` on `x` [T, 7168], no bias, `eps` 1e-5:

- `u = RMSNorm(x; g_in)`.
- Query. `c_q = RMSNorm_1536(u W_dq; g_q)`; `q = c_q W_uq` -> [T, 64, 192]; of
  each head the first 128 are `q_nope`, the last 64 `q_rope`, turned by the
  rotary rule in interleaved pairs `(2i, 2i+1)` at frequency `freq_i`, `i` =
  0..31 (below).
- Key-value latent. `[c | r] = u W_dkv` (512 | 64); `c_kv = RMSNorm_512(c;
  g_kv)`; `k_rope = rotary(r)`, ONE 64-wide key for all heads. `[k_nope | v] =
  c_kv W_ukv` -> [T, 64, 128 | 128]; `k_h = [k_nope_h | k_rope]` (192), `v_h`
  128 wide: the value is NARROWER than the key.
- `a_h = softmax_{s <= t}(scale x q_h . k_h,s) v_h,s`; `x = x + concat_h(a_h)
  W_o` (8,192 -> 7,168).
- YaRN (`rope_scaling` type yarn: factor 64, beta_fast 32, beta_slow 1, mscale
  1, mscale_all_dim 1, original 4096; rope_theta 50000), with `d` = 64 and
  `f_i = theta^(-2i/d)`: `corr(r) = d ln(original / (2 pi r)) / (2 ln theta)`,
  `low = max(floor(corr(beta_fast)), 0)` = 8, `high = min(ceil(corr(beta_slow)),
  d - 1)` = 20, `ramp_i = clip((i - low) / (high - low), 0, 1)`, `freq_i = f_i
  (1 - ramp_i) + (f_i / factor) ramp_i`: the 8 fastest frequencies are the
  plain ones, those from the 20th on are divided by 64, between them blended.
  The tables carry `mscale(factor, mscale) / mscale(factor, mscale_all_dim)` =
  1 here, `mscale(f, m) = 0.1 m ln f + 1`. The softmax scale is `192^-0.5 x
  mscale(factor, mscale_all_dim)^2` = 0.0721688 x 2.00474 = 0.144680.
- `u = RMSNorm(x; g_ffn)`. The leading dense layer: `x = x + W_down (silu(W_gate
  u) * W_up u)`, width 18,432. An expert layer: `s = sigmoid(u W_r)` over all
  384 in float32; chosen `E` = the 8 largest of `s + b` (`b` the layer's
  `e_score_correction_bias` [384], float32; `n_group` 1, `topk_group` 1: no
  groups); weights `g_e = 2.827 x s_e / sum_{e in E} s_e` (the bias chooses and
  does NOT weigh; `norm_topk_prob`, `routed_scaling_factor` applied once); `x =
  x + sum_{e in E} g_e Expert_e(u) + Shared(u)`, experts and the shared one
  SwiGLU of width 2,048. Final RMSNorm, `logits = h W_head` (untied).

A share of the experts (`dims["experts_held"]` = (first, count): the tree
holds those experts' weights alone): the router stays `n_experts` wide, every
token's 8 are chosen among ALL of them, and what an absent expert would add is
left out, as on the chip that holds the others its own part is; the shared
expert is whole in every share.

Departures from the published code (DeepSeek-V3's `modeling_deepseek.py`, which
`kimi_k2` runs), each noted in the configuration file's `assumed` too, and
written from knowledge of that file with no network to check against:

- the rotary pairing is the interleaved one, `(2i, 2i+1)`: the published code
  permutes q and k into halves and rotates halves, which is the same turn of
  the same pairs (`rope_interleave` is not a key of this config);
- the vision tower (MoonViT, in the catalog's `described_as`) is left out: the
  catalog holds no width of it; the language model alone is here;
- the attention is formed one query head at a time (a whole [64, T, T] float32
  tensor at the check's width does not fit beside the engine), the experts one
  at a time.

`dims["faults"]` (a tuple of names, absent in every configuration file) leaves
a piece OUT, so that the tests can show that the comparison sees it:
`"no_yarn_blend"` (plain `f_i`), `"no_yarn_mscale"` (`scale` = 192^-0.5).

Besides its output a layer reports, per token, `router_gap` (the gap between
the 8th and the 9th of `s + b`: under the program's rounding another expert
may legitimately be picked, and the check counts such tokens tie-exposed),
each expert's load and its `chosen`; the attention half reports an infinite
gap (it chooses nothing).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def dequant(w) -> jax.Array:
    if isinstance(w, dict):
        return w["q"].astype(jnp.float32) * w["s"].astype(jnp.float32)
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32)


def rope_interleaved(x, angles, factor: float = 1.0):
    """x: [S, H, D]; angles [S, D/2]; pairs (2i, 2i + 1) turned by angle i."""
    sin, cos = factor * jnp.sin(angles)[:, None, :], factor * jnp.cos(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(dims) -> tuple[int, int]:
    """(low, high) of the ramp over the rotary's `d / 2` frequencies."""
    d, theta, yarn = dims["qk_rope_head_dim"], dims["rope_theta"], dims["rope_scaling"]
    original = yarn["original_max_position_embeddings"]

    def corr(rotations: float) -> float:
        return d * math.log(original / (rotations * 2.0 * math.pi)) / (2.0 * math.log(theta))

    return max(math.floor(corr(yarn["beta_fast"])), 0), min(math.ceil(corr(yarn["beta_slow"])), d - 1)


def yarn_frequencies(dims) -> jax.Array:
    """[d / 2] float32: `freq_i`."""
    d, theta, yarn = dims["qk_rope_head_dim"], dims["rope_theta"], dims["rope_scaling"]
    index = jnp.arange(d // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * index / d)
    if "no_yarn_blend" in dims.get("faults", ()):
        return plain
    low, high = yarn_range(dims)
    ramp = jnp.clip((index - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / yarn["factor"] * ramp


def softmax_scale(dims) -> float:
    yarn = dims["rope_scaling"]
    scale = (dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]) ** -0.5
    if yarn.get("mscale_all_dim") and "no_yarn_mscale" not in dims.get("faults", ()):
        scale *= yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return scale


def attention_block(x, lp, dims, positions=None):
    """x: [S, d_model] float32 -> x + attention(x). The EXPANDED form: every
    position's keys and values of all heads, every query over all behind it."""
    s = x.shape[0]
    h, eps = dims["n_heads"], dims["eps"]
    kl, nope, rope, vd = (
        dims["kv_lora_rank"], dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
        dims["v_head_dim"],
    )
    if positions is None:
        positions = jnp.arange(s)
    yarn = dims["rope_scaling"]
    tables = yarn_mscale(yarn["factor"], yarn["mscale"]) / yarn_mscale(
        yarn["factor"], yarn["mscale_all_dim"]
    )
    angles = positions.astype(jnp.float32)[:, None] * yarn_frequencies(dims)[None, :]
    u = rms_norm(x, lp["attn_norm"], eps)
    c_q = rms_norm(u @ dequant(lp["wq_a"]), lp["q_a_norm"], eps)
    q = (c_q @ dequant(lp["wq_b"])).reshape(s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], rope_interleaved(q[..., nope:], angles, tables)
    down = u @ dequant(lp["wkv_a"])
    c_kv = rms_norm(down[:, :kl], lp["kv_a_norm"], eps)
    k_rope = rope_interleaved(down[:, None, kl:], angles, tables)[:, 0]  # [S, rope]: one for all heads
    up = (c_kv @ dequant(lp["wkv_b"])).reshape(s, h, nope + vd)
    k_nope, v = up[..., :nope], up[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
    scale = softmax_scale(dims)

    def one_head(_, xs):
        qn_h, qr_h, k_h, v_h = xs  # [S, nope], [S, rope], [S, nope], [S, v]
        scores = (qn_h @ k_h.T + qr_h @ k_rope.T) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return None, probs @ v_h

    heads_first = lambda a: a.transpose(1, 0, 2)  # noqa: E731
    _, out = jax.lax.scan(
        one_head, None, (heads_first(q_nope), heads_first(q_rope), heads_first(k_nope), heads_first(v))
    )
    out = out.transpose(1, 0, 2).reshape(s, h * vd)
    return x + out @ dequant(lp["wo"])


def swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ dequant(w_gate)) * (u @ dequant(w_up))) @ dequant(w_down)


def route(u, lp, dims):
    """(gate [S, E]: a token's weight of each expert, 0 where not chosen; the
    chosen [S, k]; the biased scores [S, E])."""
    k = dims["top_k"]
    scores = jax.nn.sigmoid(u @ lp["router"].astype(jnp.float32))  # [S, E], ALL experts
    biased = scores + lp["router_bias"].astype(jnp.float32)
    _, chosen = jax.lax.top_k(biased, k)  # the bias chooses ...
    top = jnp.take_along_axis(scores, chosen, axis=-1)  # ... and does not weigh
    weights = dims["routed_scaling"] * top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], chosen].set(weights)
    return gate, chosen, biased


def moe(u, lp, dims):
    """u: [S, d_model] normed hidden state -> (the expert layer's output, info).
    The tree holds experts `experts_held` = (first, count) of `n_experts`."""
    k, e = dims["top_k"], dims["n_experts"]
    first, held = dims.get("experts_held") or (0, e)
    gate, chosen, biased = route(u, lp, dims)

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
    layer half by half (`families/kimi_k2.py`, `system_chain`), so that the
    router here reads the very hidden state the program's router read. `lp`
    may come under its kind's name, `{kind: leaves}` (`dense` | `sparse`): the
    kind says nothing the leaves do not."""
    if len(lp) == 1 and next(iter(lp)) in ("dense", "sparse"):
        lp = next(iter(lp.values()))
    info = {"expert_load": jnp.zeros((dims["n_experts"],), jnp.int32)}
    with jax.default_matmul_precision(HIGHEST):
        if "wq_a" in lp:
            x = attention_block(x, lp, dims, positions)
            # the attention chooses nothing: no position is tie-exposed by it
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

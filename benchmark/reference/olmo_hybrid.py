"""Plain reference of the Olmo-Hybrid block, one sequence at a time.

Two kinds of layer in a period of four (`layer_types`: three
`linear_attention`, one `full_attention`), SwiGLU feed-forward in both,
untied output head. Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, no kernels, no chunking, no cache,
no batching. Weights arrive as the served int8 tree and are dequantised here.
`layer` is told the kind by the tree it gets: `{kind: that layer's weights}`.

*Linear layer* (Gated DeltaNet, Yang et al., arXiv:2412.06464, in the `fla`
form that `modeling_olmo_hybrid.py` uses). From the normed input x:
q~ = W_q x, k~ = W_k x, v~ = W_v x, g~ = W_g x, a = W_a x, b = W_b x. q~, k~,
v~ each pass a depthwise causal convolution of width `conv` over time (the tap
`conv_w[conv - 1]` multiplies the current token), then SiLU. Per head:
q^ = q / |q|_2 * dk^-1/2, k^ = k / |k|_2, beta = 2 sigmoid(b) (the 2 is
`linear_allow_neg_eigval`), alpha = exp(-exp(A_log) softplus(a + dt_bias)),
the state S [dk, dv] from zero:

    S_t = alpha_t S_(t-1) + beta_t k^_t (v_t - alpha_t S_(t-1)^T k^_t)^T,  o_t = S_t^T q^_t

written token by token with `lax.scan`. Output W_o [RMSNorm_dv(o_h) * w *
SiLU(g~_h)]_h. Block: h = x + GDN(norm(x)); y = h + FFN(norm(h)).

*Full layer* (the OLMo 2/3 block): q, k, v = W x; RMSNorm over the whole
width of q and of k before the heads are split; causal softmax attention;
h = x + norm(Attn(x)); y = h + norm(FFN(h)): the norm sits on the
sublayer's output. No rotary: `rope_parameters.rope_theta` is null.

Departures from the published file, each a reading of something `config.json`
does not state (the configuration's `assumed` has them): the norm placement
of the two kinds, the QK-norm's width, the null `rope_theta` as "no rotary",
the L2 norm's epsilon (1e-6 inside the root, as `fla`'s `l2norm`), no bias on
the convolution.

`dims` is a plain dict read from the configuration file, not the program's
config object.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"
L2_EPS = 1e-6


def dequant(w) -> jax.Array:
    if isinstance(w, dict):
        return w["q"].astype(jnp.float32) * w["s"].astype(jnp.float32)
    return w.astype(jnp.float32)


def rms_norm(x, weight, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32)


def swiglu(x, lp):
    return (jax.nn.silu(x @ dequant(lp["w_gate"])) * (x @ dequant(lp["w_up"]))) @ dequant(lp["w_down"])


def l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def causal_conv(x, taps):
    """x [S, C], taps [K, C]: y_t = sum_i taps[i] x_(t - (K - 1) + i)."""
    width = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(padded[i : i + x.shape[0]] * taps[i] for i in range(width))


def delta_rule(q, k, v, alpha, beta):
    """q, k [S, H, dk]; v [S, H, dv]; alpha, beta [S, H] -> o [S, H, dv]."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(state, inputs):  # state [H, dk, dv]
        q_t, k_t, v_t, a_t, b_t = inputs
        state = state * a_t[:, None, None]
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * ((v_t - read) * b_t[:, None])[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = lax.scan(step, jnp.zeros((h, dk, dv), jnp.float32), (q, k, v, alpha, beta))
    return o


def linear_layer(x, lp, dims):
    s = x.shape[0]
    h, dk, dv = dims["linear_heads"], dims["linear_key_head_dim"], dims["linear_value_head_dim"]
    a_in = rms_norm(x, lp["attn_norm"], dims["eps"])
    taps = lp["conv_w"].astype(jnp.float32)
    # the served tree keeps W_q, W_k, W_v side by side: [d, q | k | v]
    mixed = jax.nn.silu(causal_conv(a_in @ dequant(lp["wqkv"]), taps))
    q = l2norm(mixed[:, : h * dk].reshape(s, h, dk)) * dk**-0.5
    k = l2norm(mixed[:, h * dk : 2 * h * dk].reshape(s, h, dk))
    v = mixed[:, 2 * h * dk :].reshape(s, h, dv)
    beta = jax.nn.sigmoid(a_in @ dequant(lp["wb"])) * (2.0 if dims["allow_neg_eigval"] else 1.0)
    alpha = jnp.exp(
        -jnp.exp(lp["A_log"].astype(jnp.float32))
        * jax.nn.softplus(a_in @ dequant(lp["wa"]) + lp["dt_bias"].astype(jnp.float32))
    )
    o = rms_norm(delta_rule(q, k, v, alpha, beta), lp["out_norm"], dims["eps"])
    gate = jax.nn.silu(a_in @ dequant(lp["wg"]))
    x = x + (o.reshape(s, h * dv) * gate) @ dequant(lp["wo"])
    return x + swiglu(rms_norm(x, lp["ffn_norm"], dims["eps"]), lp)


def full_layer(x, lp, dims):
    s = x.shape[0]
    h, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    q = rms_norm(x @ dequant(lp["wq"]), lp["q_norm"], dims["eps"]).reshape(s, h, hd)
    k = rms_norm(x @ dequant(lp["wk"]), lp["k_norm"], dims["eps"]).reshape(s, hkv, hd)
    v = (x @ dequant(lp["wv"])).reshape(s, hkv, hd)
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd**-0.5
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h * hd)
    x = x + rms_norm(out @ dequant(lp["wo"]), lp["attn_norm"], dims["eps"])
    return x + rms_norm(swiglu(x, lp), lp["ffn_norm"], dims["eps"])


def layer(x, lp, dims):
    """One block of the kind the tree names. Returns (y, info); no routing."""
    (kind, weights), = lp.items()
    with jax.default_matmul_precision(HIGHEST):
        y = (linear_layer if kind == "linear_attention" else full_layer)(x, weights, dims)
    return y, {}


def embed(params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def unembed(params, x, dims):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, params["final_norm"], dims["eps"]) @ dequant(params["lm_head"])

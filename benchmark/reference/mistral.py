"""Plain reference of the Mistral-7B block, one sequence at a time.

Follows the published model (Jiang et al. 2023, "Mistral 7B";
`modeling_mistral.py`): pre-norm RMSNorm, rotary grouped-query attention
(half-rotation convention, no bias), SwiGLU feed-forward, untied output head.
Straightforward `jax.numpy` in float32, no kernels, no cache, no batching.
Weights arrive as the served int8 tree (`{"q": int8, "s": f32}` per matrix,
one scale per output channel) and are dequantised here, so system and
reference see the same numbers. `jax.default_matmul_precision("highest")` is
set around this module's own code only: set globally, Mosaic refuses the
program's kernels. No departure from the published equations; v0.3 has no
sliding window.

`dims` is a plain dict read from the configuration file (`n_heads`,
`n_kv_heads`, `head_dim`, `rope_theta`, `eps`), not the program's config
object.
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
    """x: [S, H, D] at positions 0..S-1; rotate_half convention."""
    s, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_block(x, lp, dims):
    """x: [S, d_model] float32 → x + attention(x)."""
    s = x.shape[0]
    h, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    a = rms_norm(x, lp["attn_norm"], dims["eps"])
    q = rope((a @ dequant(lp["wq"])).reshape(s, h, hd), dims["rope_theta"])
    k = rope((a @ dequant(lp["wk"])).reshape(s, hkv, hd), dims["rope_theta"])
    v = (a @ dequant(lp["wv"])).reshape(s, hkv, hd)
    group = h // hkv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd**-0.5
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h * hd)
    return x + out @ dequant(lp["wo"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def layer(x, lp, dims):
    """One block. Returns (y, info); a dense block has no routing to report."""
    with jax.default_matmul_precision(HIGHEST):
        x = attention_block(x, lp, dims)
        f = rms_norm(x, lp["ffn_norm"], dims["eps"])
        y = x + swiglu(f, dequant(lp["w_gate"]), dequant(lp["w_up"]), dequant(lp["w_down"]))
    return y, {}


def embed(params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def unembed(params, x, dims):
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, params["final_norm"], dims["eps"]) @ dequant(params["lm_head"])

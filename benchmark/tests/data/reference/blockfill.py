"""Plain reference of the toy block-filling model (`families/blockfill.py`),
one sequence at a time: Mistral's block under a BLOCK-CAUSAL mask. With block
length B, position i sees every j with j // B <= i // B: both ways inside its
own block, causal across blocks. The sequence is whatever one pass of the
model saw, mask ids included: the reference has no notion of a pass. float32,
no kernels, no cache; the pieces both share come from `reference/mistral.py`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.mistral import HIGHEST, dequant, embed, rms_norm, rope, swiglu, unembed  # noqa: F401


def attention_block(x, lp, dims):
    s = x.shape[0]
    h, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    a = rms_norm(x, lp["attn_norm"], dims["eps"])
    q = rope((a @ dequant(lp["wq"])).reshape(s, h, hd), dims["rope_theta"])
    k = rope((a @ dequant(lp["wk"])).reshape(s, hkv, hd), dims["rope_theta"])
    v = (a @ dequant(lp["wv"])).reshape(s, hkv, hd)
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd**-0.5
    block = jnp.arange(s) // dims["block_length"]
    seen = block[None, :] <= block[:, None]  # [query, key]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h * hd)
    return x + out @ dequant(lp["wo"])


def layer(x, lp, dims):
    with jax.default_matmul_precision(HIGHEST):
        x = attention_block(x, lp, dims)
        f = rms_norm(x, lp["ffn_norm"], dims["eps"])
        y = x + swiglu(f, dequant(lp["w_gate"]), dequant(lp["w_up"]), dequant(lp["w_down"]))
    return y, {}

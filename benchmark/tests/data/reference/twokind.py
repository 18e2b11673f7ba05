"""Plain reference of the toy two-kind block (`families/twokind.py`), one
sequence at a time: Mistral's block, but each kind masks by its own window
and turns by its own rope base. It is handed a layer as `{kind: weights}`.
float32, no kernels, no cache; the pieces both share come from
`reference/mistral.py`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.mistral import HIGHEST, dequant, embed, rms_norm, rope, swiglu, unembed  # noqa: F401


def attention_block(x, lp, dims, kind: str):
    s = x.shape[0]
    h, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    theta, window = dims["rope_theta"][kind], dims["window"][kind]
    a = rms_norm(x, lp["attn_norm"], dims["eps"])
    q = rope((a @ dequant(lp["wq"])).reshape(s, h, hd), theta)
    k = rope((a @ dequant(lp["wk"])).reshape(s, hkv, hd), theta)
    v = (a @ dequant(lp["wv"])).reshape(s, hkv, hd)
    k, v = jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd**-0.5
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]  # query minus key
    seen = (ahead >= 0) if window is None else (ahead >= 0) & (ahead < window)
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h * hd)
    return x + out @ dequant(lp["wo"])


def layer(x, lp, dims):
    ((kind, lp),) = lp.items()
    with jax.default_matmul_precision(HIGHEST):
        x = attention_block(x, lp, dims, kind)
        f = rms_norm(x, lp["ffn_norm"], dims["eps"])
        y = x + swiglu(f, dequant(lp["w_gate"]), dequant(lp["w_up"]), dequant(lp["w_down"]))
    return y, {}

"""Plain reference of the Command A+ block (`model_type: cohere2_moe`), one
sequence at a time: window and full attention layers in a parallel block, a
sigmoid-routed mixture of experts with averaged shared experts.

Written from `CohereLabs/command-a-plus-05-2026`'s `config.json` and the
family's published description (`use_parallel_block`, `layer_types`,
`position_embedding_type: rope_gptj`, `expert_selection_fn: sigmoid`,
`norm_topk_prob`, `shared_expert_combination_strategy: average`):

    u   = LN(x)                    Cohere's LayerNorm: subtract the mean, divide
                                   by sqrt(var + eps), scale, no bias
    x'  = x + Attn(u) + MoE(u)     one norm a layer, both sublayers read it
    Attn, a sliding layer: rotary over the whole head, pairs interleaved
          (2i, 2i+1), theta 50,000; causal inside `sliding_window`: query i
          sees keys i - window + 1 .. i
    Attn, a full layer: causal, NO positional turn at all
    MoE : s = sigmoid(u W_r) over ALL experts; the k largest are chosen, their
          weights s_e / sum of the chosen s; each expert a SwiGLU;
          y = sum_chosen w_e E_e(u) + (1 / n_shared) sum_s S_s(u)
    logits = logit_scale * LN(h) E^T with the tied embedding

Straightforward `jax.numpy` in float32 at `jax.default_matmul_precision
("highest")` (set around this module's own code only), no kernels, no cache,
no batching. The one concession to memory: it is BLOCKED to fit beside the
served weights and both page groups (2.7 GB are free on the chip): attention
runs one KV head's group at a time and inside it one query head at a time,
the routed and the shared experts one at a time (`lax.scan`, `lax.map`), and
a weight is dequantised a block at a time where it is used; a 6400-token
sequence at 128 heads then takes 164 MB a head's scores and not 21 GB. The
numbers are those of the whole einsum, summed in another order.

Departures from the published description, each also under the configuration
file's `assumed` or `deployment`:

- **the share**. `dims["experts_held"] = (first, count)`: the router scores and
  chooses over all `n_experts`, the top-k weights are normalised over all k
  chosen, and only the chosen experts that are HELD add to the output; what an
  absent expert would add is left out, as the program leaves it out. With
  `(0, n_experts)` the layer is the uncut one. The embedding is the slice of the
  vocabulary the tree holds.
- `shared_expert_combination_strategy: average` is read as the MEAN of the
  shared experts, added to the routed sum (the other reading, a mean of routed
  and shared output, moves a scalar).
- no router bias (the config has none); the text model only.
- the shared experts arrive side by side as the program serves them
  (`ws_gate`, `ws_up` [d, n_shared * f], `ws_down` [n_shared * f, d], int8 a
  output channel of the fused matrix) and are split back into `n_shared`
  experts here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def dequant(w) -> jax.Array:
    if isinstance(w, dict):
        return w["q"].astype(jnp.float32) * w["s"].astype(jnp.float32)
    return w.astype(jnp.float32)


def layer_norm(x, weight, eps: float):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps) * weight.astype(
        jnp.float32
    )


def rope_interleaved(x, theta: float):
    """x: [S, H, D] at positions 0..S-1; pair i is (x[2i], x[2i+1]), turned by
    position * theta^(-2i/D)."""
    s, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _by_columns(w, n: int):
    """A (quantised) matrix [K, N] as n blocks of its output columns,
    [n, K, N / n]: a head group's share of a projection, a shared expert's of
    the side-by-side matrix. Blocks are dequantised one at a time, where used."""
    def split(a):
        return a.reshape(a.shape[0], n, a.shape[1] // n).transpose(1, 0, 2)

    return jax.tree.map(split, w)


def _by_rows(w, n: int):
    """The same matrix as n blocks of its input rows, [n, K / n, N]."""
    if isinstance(w, dict):  # one scale an output channel: every block has all of them
        q = w["q"].reshape(n, w["q"].shape[0] // n, w["q"].shape[1])
        return {"q": q, "s": jnp.broadcast_to(w["s"], (n, *w["s"].shape))}
    return w.reshape(n, w.shape[0] // n, w.shape[1])


def attention(u, lp, dims, sliding: bool):
    """u: [S, d_model] normed → Attn(u) [S, d_model]: the sum over heads of
    `softmax(q_h k^T) v` times that head's rows of W_o, one KV head's group
    of query heads at a time and inside it one query head at a time."""
    s = u.shape[0]
    h, hkv, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    group = h // hkv  # query head i reads KV head i // group
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if sliding:
        seen = seen & (j > i - dims["sliding_window"])

    def turned(x):  # [S, heads, D]; a full layer turns nothing
        return rope_interleaved(x, dims["rope_theta"]) if sliding else x

    def one_group(out, weights):
        wq, wk, wv, wo = weights
        q = turned((u @ dequant(wq)).reshape(s, group, hd))
        k = turned((u @ dequant(wk)).reshape(s, 1, hd))[:, 0]
        v = u @ dequant(wv)

        def one_head(qh):  # [S, D] against the group's K and V
            scores = (qh @ k.T) * hd**-0.5
            return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ v

        heads = jax.lax.map(one_head, q.transpose(1, 0, 2))  # [group, S, D]
        return out + heads.transpose(1, 0, 2).reshape(s, group * hd) @ dequant(wo), None

    out, _ = jax.lax.scan(
        one_group, jnp.zeros_like(u),
        (_by_columns(lp["wq"], hkv), _by_columns(lp["wk"], hkv), _by_columns(lp["wv"], hkv),
         _by_rows(lp["wo"], hkv)),
    )
    return out


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe(u, lp, dims):
    """u: [S, d_model] normed → (MoE(u), info)."""
    k, n_shared = dims["top_k"], dims["n_shared"]
    first, held = dims["experts_held"]
    scores = jax.nn.sigmoid(u @ lp["router"].astype(jnp.float32))  # [S, E]
    top, chosen = jax.lax.top_k(scores, k + 1)
    weights = top[:, :k] / jnp.sum(top[:, :k], axis=-1, keepdims=True)  # over all k chosen
    # [S, E]: the weight of each expert for each token, 0 where not chosen
    gate = jnp.zeros_like(scores).at[jnp.arange(u.shape[0])[:, None], chosen[:, :k]].set(weights)
    gate = gate[:, first : first + held]  # the held experts' columns

    def one_expert(acc, xs):
        w_gate, w_up, w_down, g = xs
        return acc + g[:, None] * swiglu(u, dequant(w_gate), dequant(w_up), dequant(w_down)), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u), (lp["w_gate"], lp["w_up"], lp["w_down"], gate.T)
    )
    if n_shared:  # side by side in the served tree: expert e's columns, and rows of ws_down
        out = out + jax.lax.scan(
            lambda acc, w: (acc + swiglu(u, dequant(w[0]), dequant(w[1]), dequant(w[2])), None),
            jnp.zeros_like(u),
            (_by_columns(lp["ws_gate"], n_shared), _by_columns(lp["ws_up"], n_shared),
             _by_rows(lp["ws_down"], n_shared)),
        )[0] / n_shared
    info = {
        # the k-th minus the (k+1)-th score's LOGIT: sigmoid keeps the order
        "router_gap": _logit(top[:, k - 1]) - _logit(top[:, k]),
        "chosen": chosen[:, :k],
        "expert_load": (gate > 0).sum(axis=0),
    }
    return out, info


def _logit(p):
    return jnp.log(p) - jnp.log1p(-p)


def layer(x, lp, dims):
    """One block. `lp` is `{kind: weights}`: the kind tells the attention."""
    ((kind, lp),) = lp.items()
    with jax.default_matmul_precision(HIGHEST):
        u = layer_norm(x, lp["attn_norm"], dims["eps"])
        out, info = moe(u, lp, dims)
        y = x + attention(u, lp, dims, sliding=kind == "sliding_attention") + out
    return y, info


def embed(params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


def unembed(params, x, dims):
    with jax.default_matmul_precision(HIGHEST):
        h = layer_norm(x, params["final_norm"], dims["eps"])
        return dims["logit_scale"] * (h @ params["embed"].astype(jnp.float32).T)

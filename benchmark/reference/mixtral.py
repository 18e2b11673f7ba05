"""Plain reference of the Mixtral-8x7B block, one sequence at a time.

Mixtral (Jiang et al. 2024, "Mixtral of Experts"; `modeling_mixtral.py`) is
Mistral's block with the feed-forward replaced by a sparse mixture: a linear
router over the normed hidden state, the top-k experts by logit, softmax over
those k logits as weights, each expert a SwiGLU of the dense width. Published
Mixtral drops no token. This reference computes every expert for every token
and keeps the chosen ones, one expert dequantised to float32 at a time.

Departure of the PROGRAM, not of this file: `moe_ffn` gives each expert a
capacity (`moe_capacity_factor` 2.0) and sends overflow down the residual.
The reference has no capacity, so wherever the program's rule binds the two
disagree; the check reports per-expert load so that is visible.

Besides the output it reports, per token, the gap between the k-th and the
(k+1)-th router logit: where that gap is inside the program's bf16 error a
different expert may legitimately be picked, and the check counts such tokens
as tie-exposed instead of letting them decide the verdict.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import mistral
from .mistral import HIGHEST, dequant, embed, rms_norm, swiglu, unembed  # noqa: F401


def moe(f, lp, dims):
    """f: [S, d_model] normed hidden state → (ffn output, info)."""
    k = dims["top_k"]
    logits = f @ lp["router"].astype(jnp.float32)  # [S, E]
    top, chosen = jax.lax.top_k(logits, k + 1)
    weights = jax.nn.softmax(top[:, :k], axis=-1)  # over the k chosen logits
    # [S, E]: the routing weight of each expert for each token, 0 if not chosen
    gate = jnp.zeros_like(logits).at[jnp.arange(f.shape[0])[:, None], chosen[:, :k]].set(weights)

    def one_expert(acc, xs):
        w_gate, w_up, w_down, g = xs
        out = swiglu(f, dequant(w_gate), dequant(w_up), dequant(w_down))
        return acc + g[:, None] * out, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(f), (lp["w_gate"], lp["w_up"], lp["w_down"], gate.T)
    )
    info = {
        "router_gap": top[:, k - 1] - top[:, k],  # k-th minus (k+1)-th logit
        "chosen": chosen[:, :k],
        "expert_load": (gate > 0).sum(axis=0),
    }
    return out, info


def layer(x, lp, dims):
    with jax.default_matmul_precision(HIGHEST):
        x = mistral.attention_block(x, lp, dims)
        f = rms_norm(x, lp["ffn_norm"], dims["eps"])
        out, info = moe(f, lp, dims)
    return x + out, info

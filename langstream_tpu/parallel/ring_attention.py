"""Ring attention: causal attention with the sequence axis sharded over the
device mesh (context parallelism for long inputs).

No reference counterpart (SURVEY §5 "long-context: absent") — designed for
TPU from the ring-attention / blockwise-attention pattern: each device holds
one sequence block of Q/K/V; K/V blocks rotate around the ring via
``lax.ppermute`` (ICI neighbour exchange) while a numerically-stable online
softmax (flash-attention style m/l accumulators, fp32) folds in one block's
contribution per step. Peak memory per device is O(S/n · S/n) scores instead
of O(S²), and the K/V transfer overlaps with the block matmul under XLA's
async collectives.

Runs inside ``shard_map`` (parallel.sp wraps the model forward); the axis
name arrives via ``ModelConfig.ring_axis``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from langstream_tpu.models.configs import ModelConfig

# plain Python float, NOT jnp.float32(...): this module is lazily imported
# from inside traced functions (the engine's ring admit, _scan_layers), and
# a module-level jnp constant created during a trace is a TRACER that
# outlives its trace — every later ring dispatch then dies with
# UnexpectedTracerError. A Python scalar weaves into jnp ops just as well
# and can never leak.
_NEG = -1e30


def ring_attention(
    q: jax.Array,  # [B, Sl, H, D] local query block
    k: jax.Array,  # [B, Sl, Hkv, D] local key block
    v: jax.Array,  # [B, Sl, Hkv, D] local value block
    config: ModelConfig,
) -> jax.Array:
    """Causal GQA attention over the ring axis → [B, Sl, H*D] local output.

    Must be called under shard_map with ``config.ring_axis`` mapped; block b
    on device b covers global positions [b·Sl, (b+1)·Sl).
    """
    axis = config.ring_axis
    assert axis is not None, "ring_attention requires config.ring_axis"
    n = lax.psum(1, axis)
    my = lax.axis_index(axis)

    h, hkv = config.n_heads, config.n_kv_heads
    group = h // hkv
    b, sl, _, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    qg = q.reshape(b, sl, hkv, group, d)
    q_pos = my * sl + jnp.arange(sl)  # global positions of local queries

    def _varying(x):
        return lax.pcast(x, (axis,), to="varying")

    # fp32 online-softmax state (cast device-varying on the ring axis: the
    # carry becomes varying the moment block data folds in)
    m0 = _varying(jnp.full((b, hkv, group, sl), _NEG, jnp.float32))
    l0 = _varying(jnp.zeros((b, hkv, group, sl), jnp.float32))
    acc0 = _varying(jnp.zeros((b, sl, hkv, group, d), jnp.float32))
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        k_blk, v_blk, m, l, acc = carry
        src = (my - i) % n  # which device's block we hold at this step

        def fold(operand):
            k_blk, m, l, acc = operand
            kv_pos = src * sl + jnp.arange(sl)
            scores = (
                jnp.einsum("bshgd,bthd->bhgst", qg, k_blk).astype(jnp.float32) * scale
            )
            if config.attn_logit_softcap is not None:
                cap = jnp.float32(config.attn_logit_softcap)
                scores = jnp.tanh(scores / cap) * cap
            causal = kv_pos[None, :] <= q_pos[:, None]  # [Sl, T]
            scores = jnp.where(causal[None, None, None, :, :], scores, _NEG)

            m_new = jnp.maximum(m, scores.max(axis=-1))
            p = jnp.exp(scores - m_new[..., None])  # [B,h,g,Sl,T]
            # fully-masked rows: scores=-1e30, m_new=-1e30 → p=1 — zero them
            p = jnp.where(scores <= _NEG, 0.0, p)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum(
                "bhgst,bthd->bshgd", p.astype(v_blk.dtype), v_blk
            ).astype(jnp.float32)
            acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
            return m_new, l, acc

        # causal block skip: when the held block is entirely in this device's
        # future (src > my), every score is masked — skip both matmuls. The
        # cond is per-device control flow (shard_map), so on average each
        # device folds (n+1)/2 of the n blocks instead of all of them; the
        # ppermute below stays OUTSIDE the cond (all devices must participate)
        m, l, acc = lax.cond(
            src <= my, fold, lambda op: (op[1], op[2], op[3]), (k_blk, m, l, acc)
        )

        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return k_blk, v_blk, m, l, acc

    _, _, m, l, acc = lax.fori_loop(0, n, step, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.astype(q.dtype).reshape(b, sl, h * d)

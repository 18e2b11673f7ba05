"""Sequence/context parallelism entry points (shard_map wrappers).

``sequence_parallel_forward`` runs the full-sequence forward with the
sequence dimension sharded over the mesh's "seq" axis and ring attention
exchanging K/V blocks over ICI — the long-context path (SURVEY §5: absent in
the reference, first-class here). Params are replicated across the seq axis
(combine with TP by also sharding params over "model" outside).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from langstream_tpu.models.configs import ModelConfig
from langstream_tpu.models.transformer import Params, forward


def sequence_parallel_forward(
    params: Params,
    tokens: jax.Array,  # [B, S] with S divisible by mesh axis "seq"
    config: ModelConfig,
    mesh: Mesh,
    axis: str = "seq",
) -> jax.Array:
    """Logits [B, S, V]; S sharded over ``axis`` during compute."""
    n = mesh.shape[axis]
    if tokens.shape[1] % n != 0:
        raise ValueError(
            f"sequence length {tokens.shape[1]} must be divisible by the "
            f"'{axis}' axis size {n} (pad the batch)"
        )
    ring_config = dataclasses.replace(config, ring_axis=axis)

    fwd = shard_map(
        functools.partial(forward, config=ring_config),
        mesh=mesh,
        in_specs=(P(), P(None, axis)),
        out_specs=P(None, axis, None),
    )
    return fwd(params, tokens)


def ring_prefill(
    params: Params,
    tokens: jax.Array,  # [B, S_pad] padded prompts, S_pad divisible by |axis|
    lengths: jax.Array,  # [B] true prompt lengths
    config: ModelConfig,
    mesh: Mesh,
    axis: str = "seq",
) -> tuple[jax.Array, dict]:
    """Single-dispatch LONG-PROMPT prefill with the sequence axis sharded:
    device d embeds prompt block d, ring attention rotates K/V blocks over
    ICI (no device ever holds the full S×S scores), and the prompt's whole
    per-layer K/V comes back position-sharded for the serving-cache splice.

    This is the multi-chip serving counterpart of engine._long_step's
    single-chip segment loop: one compiled call instead of S/W sequential
    segment dispatches. Returns (last-real-token logits [B, V],
    {"k","v"} [L, B, Hkv, S_pad, D] roped head-major K/V)."""
    from langstream_tpu.models.transformer import (
        _embed,
        _rope_freqs,
        _scan_layers,
        _unembed,
    )

    n = mesh.shape[axis]
    b, s = tokens.shape
    if s % n != 0:
        raise ValueError(
            f"padded prompt length {s} must be divisible by the "
            f"'{axis}' axis size {n}"
        )
    ring_config = dataclasses.replace(config, ring_axis=axis)
    sl = s // n

    def local(params, tok_local, lengths):
        import jax.numpy as jnp
        from jax import lax

        my = lax.axis_index(axis)
        positions = jnp.broadcast_to(jnp.arange(sl), (b, sl)) + my * sl
        sin, cos = _rope_freqs(positions, ring_config)
        x = _embed(params, tok_local, ring_config)
        # mask is unused on the ring path (causality lives inside
        # ring_attention's global block positions)
        x, (k, v), _ = _scan_layers(
            params, x, sin, cos, None, ring_config, collect_kv=True
        )
        # last real token lives in exactly one device's block: that device
        # contributes its hidden state, everyone else zeros, psum selects
        last = jnp.clip(lengths - 1, 0, s - 1)  # [B] global index
        idx = jnp.clip(last - my * sl, 0, sl - 1)
        own = (last // sl) == my  # [B]
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        x_last = jnp.where(own[:, None], x_last, jnp.zeros_like(x_last))
        x_last = lax.psum(x_last, axis)
        logits = _unembed(params, x_last[:, None, :], ring_config)[:, 0]
        return logits, {"k": k, "v": v}

    kv_spec = P(None, None, None, axis, None)
    # only the seq axis is MANUAL (axis_names); every other mesh axis
    # (model/expert/data) stays AUTO so GSPMD keeps tensor-parallel params
    # SHARDED inside the ring body (manual over all axes with in_specs=P()
    # would all-gather the full weight pytree onto every device — the exact
    # memory blowup the long-context path exists to avoid)
    fwd = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(None, axis), P()),
            out_specs=(P(), {"k": kv_spec, "v": kv_spec}),
            axis_names=frozenset({axis}),
        )
    )
    return fwd(params, tokens, lengths)

"""Seeded int8 weights made on the device, in the layout they are served in.

The provider's `weights: random` builds a 7B model in bf16 on the host and
quantises it there: 135 s + 53 s on eight cores before a byte reaches the
chip (sandbox CPU run, PR 23), in every run of every cell of every later
check. This makes the same tree (`quantize_params(init_params(...))`: the
program's own `quantize_weight`, the same N(0, 1/fan_in) draw cast to the
serving dtype first) in one jitted call, one [in, out] matrix at a time under
`lax.map`, so the peak is one matrix in float32. The seed is the
configuration's `weights.seed`, never `--seed`: the correctness check needs
the same weights in every run.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from langstream_tpu.models.configs import ModelConfig
from langstream_tpu.models.quant import quantize_weight


def _normal(key, shape, fan_in: int, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5).astype(dtype)


def _quantized_stack(key, lead: tuple, fan_in: int, fan_out: int, dtype):
    """{"q": int8 [*lead, in, out], "s": f32 [*lead, 1, out]}"""
    keys = jax.random.split(key, math.prod(lead))
    stacked = lax.map(
        lambda k: quantize_weight(_normal(k, (fan_in, fan_out), fan_in, dtype)), keys
    )
    return jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), stacked)


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, h, hkv = config.d_model, config.n_heads, config.n_kv_heads
    hd, f, n, v = config.resolved_head_dim, config.d_ff, config.n_layers, config.vocab_size
    dtype = jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 12))
    stack = functools.partial(_quantized_stack, dtype=dtype)
    layers = {
        "attn_norm": jnp.ones((n, d), dtype),
        "wq": stack(next(keys), (n,), d, h * hd),
        "wk": stack(next(keys), (n,), d, hkv * hd),
        "wv": stack(next(keys), (n,), d, hkv * hd),
        "wo": stack(next(keys), (n,), h * hd, d),
        "ffn_norm": jnp.ones((n, d), dtype),
    }
    if config.is_moe:
        e = config.n_experts
        layers["router"] = _normal(next(keys), (n, d, e), d, dtype)
        lead = (n, e)
    else:
        lead = (n,)
    layers["w_gate"] = stack(next(keys), lead, d, f)
    layers["w_up"] = stack(next(keys), lead, d, f)
    layers["w_down"] = stack(next(keys), lead, f, d)
    if config.tie_embeddings:
        raise NotImplementedError("tied embeddings: no configuration needs them yet")
    return {
        "embed": _normal(next(keys), (v, d), d, dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stack(next(keys), (), d, v),
    }


def make_int8_params(config: ModelConfig, seed: int):
    return _make(jax.random.PRNGKey(seed), config)

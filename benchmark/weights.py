"""Seeded int8 weights made on the device, in the layout they are served in.

The provider's `weights: random` builds a 7B model in bf16 on the host and
quantises it there: 135 s + 53 s on eight cores before a byte reaches the
chip (sandbox CPU run, PR 23), in every run of every cell of every later
check. A family's `make_params` (`families/<family>.py`) makes the same tree
(`quantize_params(init_params(...))`: the program's own `quantize_weight`,
the same N(0, 1/fan_in) draw cast to the serving dtype first) in one jitted
call out of the two pieces here, one [in, out] matrix at a time under
`lax.map`, so the peak is one matrix in float32. The seed is the
configuration's `weights.seed`, never `--seed`: the correctness check needs
the same weights in every run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from langstream_tpu.models.quant import quantize_weight


def normal(key, shape, fan_in: int, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5).astype(dtype)


def quantized_stack(key, lead: tuple, fan_in: int, fan_out: int, dtype):
    """{"q": int8 [*lead, in, out], "s": f32 [*lead, 1, out]}"""
    keys = jax.random.split(key, math.prod(lead))
    stacked = lax.map(
        lambda k: quantize_weight(normal(k, (fan_in, fan_out), fan_in, dtype)), keys
    )
    return jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), stacked)

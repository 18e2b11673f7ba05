"""The tests' reference for the engine's tokens: the model-level ``prefill``
over a local cache, then one jitted ``decode_step`` per token through the
plain layer scan in jnp. Independent of the page pool, the paged programs
and the engine's scheduling: same weights and prompt, greedy."""

import jax.numpy as jnp

from langstream_tpu.models.transformer import decode_step, make_kv_cache, prefill


def reference_greedy(config, params, prompt, n_new, width=None):
    """``n_new`` greedy tokens after ``prompt``; the local cache is
    ``width`` columns wide (default: just what prompt + generation need)."""
    n = len(prompt)
    width = width or n + n_new
    cache = make_kv_cache(config, 1, width)
    tokens = jnp.zeros((1, n), jnp.int32).at[0].set(jnp.asarray(prompt))
    logits, cache = prefill(params, tokens, jnp.asarray([n]), cache, config)
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < n_new:
        logits, cache = decode_step(
            params, jnp.asarray([out[-1]]), jnp.asarray([n + len(out) - 1]),
            cache, config,
        )
        out.append(int(jnp.argmax(logits[0])))
    return out

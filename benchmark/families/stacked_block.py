"""What Mistral and Mixtral share: the program's one block (RMSNorm, rotary
grouped-query attention, a SwiGLU or an expert feed-forward), all layers in
one stack on a leading axis, K/V in the engine's page pool. Not a family: no
configuration names it; `mistral.py` and `mixtral.py` say what differs.

This is where the harness reaches into the program for these two families:
the private model functions `_embed`, `_layer`, `_rope_freqs`, `_unembed`
(the check's layer-by-layer chain), the public `prefill`,
`paged_insert_cache`, `paged_decode_step_inplace` (its hot path), and
`engine._pagepool`. PERF.md section 7 lists what the program should offer
instead.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from langstream_tpu.models.configs import ModelConfig
from modelcfg import refuse_unmapped
from weights import normal, quantized_stack

# published config.json key → ModelConfig field, for the dense block
FIELDS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
    "hidden_act": "activation",
    "tie_word_embeddings": "tie_embeddings",
}

QUANTIZED_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def model_config(spec: dict, name: str, fields: dict) -> ModelConfig:
    refuse_unmapped(spec, [*fields, "sliding_window"], name)
    if spec.get("sliding_window") is not None:
        raise ValueError(f"{name}: the program's block has no sliding window")
    return ModelConfig(
        name=name, **{ours: spec[theirs] for theirs, ours in fields.items() if theirs in spec}
    )


def reference_dims(spec: dict) -> dict:
    return {
        "n_heads": spec["num_attention_heads"],
        "n_kv_heads": spec["num_key_value_heads"],
        "head_dim": spec.get("head_dim") or spec["hidden_size"] // spec["num_attention_heads"],
        "rope_theta": float(spec["rope_theta"]),
        "eps": float(spec["rms_norm_eps"]),
    }


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, h, hkv = config.d_model, config.n_heads, config.n_kv_heads
    hd, f, n, v = config.resolved_head_dim, config.d_ff, config.n_layers, config.vocab_size
    dtype = jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 12))
    stack = functools.partial(quantized_stack, dtype=dtype)
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
        layers["router"] = normal(next(keys), (n, d, e), d, dtype)
        lead = (n, e)
    else:
        lead = (n,)
    layers["w_gate"] = stack(next(keys), lead, d, f)
    layers["w_up"] = stack(next(keys), lead, d, f)
    layers["w_down"] = stack(next(keys), lead, f, d)
    if config.tie_embeddings:
        raise NotImplementedError("tied embeddings: no configuration needs them yet")
    return {
        "embed": normal(next(keys), (v, d), d, dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stack(next(keys), (), d, v),
    }


def make_params(config: ModelConfig, seed: int):
    return _make(jax.random.PRNGKey(seed), config)


def system_chain(config: ModelConfig, width: int, rows: int) -> SimpleNamespace:
    """The body of transformer.forward, one layer at a time."""
    from langstream_tpu.models import transformer as program

    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))

    @jax.jit
    def sys_embed(params, tokens):
        # the sequence in row 0 of a group of `rows`, the other rows all
        # padding (id 0), as the engine fills a prefill group for one
        # request: the program's expert capacity is per dispatch
        group = jnp.zeros((rows, width), jnp.int32).at[0].set(tokens)
        return program._embed(params, group, config)

    @jax.jit
    def sys_layer(layers, index, x):
        sin, cos = program._rope_freqs(positions, config)
        mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((width, width), jnp.bool_)), (rows, width, width)
        )
        lp = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False), layers)
        y, _ = program._layer(x, lp, sin, cos, mask, config)
        return y

    @jax.jit
    def sys_unembed(params, x):
        return program._unembed(params, x[:1], config)[0]

    return SimpleNamespace(
        embed=sys_embed,
        layer=lambda params, index, x: sys_layer(params["layers"], index, x),
        unembed=sys_unembed,
        n_layers=config.n_layers,
    )


def ref_layer_params(ref_params, index: int):
    return ref_params["layers"], index


class HotPath:
    """The model functions the engine's programs are made of, called as the
    engine calls them, with its config (so its kernels and its KV type) and
    its page size, on a page pool of this check's own: the logits the engine
    samples from, which it does not hand out."""

    def __init__(self, engine, width: int, rows: int, new_tokens: int) -> None:
        from langstream_tpu.models import transformer as program

        config = engine.config
        page_size = engine._pagepool.page_size
        n_pages = -(-(width + new_tokens) // page_size)
        self.width = width
        # the sequence in row 0 of the group, its pages 0..n_pages-1; the
        # padding rows' tables are all out of bounds, so their writes drop
        tables = jnp.full((rows, n_pages), n_pages, jnp.int32).at[0].set(jnp.arange(n_pages))

        @jax.jit
        def prefill_group(params, tokens, length):
            group = jnp.zeros((rows, width), jnp.int32).at[0].set(tokens)
            lengths = jnp.ones((rows,), jnp.int32).at[0].set(length)
            logits, local = program.prefill(
                params, group, lengths, program.make_kv_cache(config, rows, width), config
            )
            pool = program.make_page_pool(config, n_pages, page_size)
            return logits[0], program.paged_insert_cache(pool, local, tables, page_size)

        @functools.partial(jax.jit, donate_argnames=("pool",))
        def decode(params, token, position, pool):
            logits, pool = program.paged_decode_step_inplace(
                params, token[None], position[None], pool, tables[:1], config, page_size
            )
            return logits[0], pool

        self._fns = (prefill_group, decode)

    def logits(self, params, prompt: list[int], generated: list[int]):
        """[len(generated), V]: row j is the distribution generated token j
        was drawn from, token j - 1 having gone through the paged cache."""
        prefill_group, decode = self._fns
        n = len(prompt)
        tokens = jnp.asarray(prompt + [0] * (self.width - n), jnp.int32)
        first, pool = prefill_group(params, tokens, jnp.int32(n))
        rows = [first]
        for j, token in enumerate(generated[:-1]):
            step, pool = decode(params, jnp.int32(token), jnp.int32(n + j), pool)
            rows.append(step)
        return jnp.stack(rows).astype(jnp.float32)


def engine_state(engine) -> dict:
    from langstream_tpu.models.quant import is_quantized

    layers = engine.params["layers"]
    int8 = all(
        is_quantized(layers[k]) and layers[k]["q"].dtype == np.int8 for k in QUANTIZED_LEAVES
    )
    pool = engine._pagepool.dev["k"]
    return {
        "weights": "int8" if int8 else "unquantized",
        "kv_dtype": "int8" if isinstance(pool, dict) else str(pool.dtype),
    }


def expected_kernels(engine) -> dict:
    pool = engine._pagepool
    return {
        f"paged-decode[s=1,t={pool.table_len * pool.page_size}]": "ragged_paged_decode_attention",
        **{
            f"prefill[s={w},t={w}]": "flash_prefill_attention"
            for w in engine.prefill_buckets
            if w % 128 == 0
        },
    }


def state_leaves(engine):
    return engine.params, engine._pagepool.dev

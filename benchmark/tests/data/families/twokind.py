"""A toy family for the harness's own tests: everything a new architecture
brings, as files under `tests/data` alone (test_family_as_files.py).

The published-style file has what neither real family has: a `layer_types`
list of two kinds in a period of two, a nested `rope_parameters` with one
entry a kind, a `sliding_window`, and a `head_dim` of null that is resolved
here and noted under `assumed`. The tree this family MAKES is one stack PER
KIND. Today's engine scans one stack, so what it serves is `served(tree)`,
the kinds interleaved into the program's layout; the chain and the hot path
are handed the engine's tree and index that, each kind through a compiled
layer of its own (its mask, its rope base), and the reference reads the
family's own tree. The program's block has no window and one rope base: a
file whose window could bind, or whose kinds turn by different bases, is
refused, which leaves the degenerate member that the block computes exactly.
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

KINDS = ("sliding_attention", "full_attention")  # the period, in order
FIELDS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
    "hidden_act": "activation",
    "tie_word_embeddings": "tie_embeddings",
}
MAPPED_HERE = ("head_dim", "layer_types", "rope_parameters", "sliding_window")


def kind_of(index: int) -> str:
    return KINDS[index % len(KINDS)]


def _thetas(spec: dict) -> dict:
    return {kind: float(spec["rope_parameters"][kind]["rope_theta"]) for kind in KINDS}


def model_config(spec: dict, name: str) -> ModelConfig:
    refuse_unmapped(spec, [*FIELDS, *MAPPED_HERE], name)
    n = spec["num_hidden_layers"]
    if spec["layer_types"] != [kind_of(i) for i in range(n)]:
        raise ValueError(f"{name}: layer_types is not {n} layers of the period {KINDS}")
    if spec["sliding_window"] < spec["max_position_embeddings"]:
        raise ValueError(f"{name}: the program's block has no sliding window, and this one can bind")
    thetas = _thetas(spec)
    if len(set(thetas.values())) != 1:
        raise ValueError(f"{name}: the engine's programs turn every layer by one rope base: {thetas}")
    return ModelConfig(
        name=name,
        head_dim=spec["head_dim"] or spec["hidden_size"] // spec["num_attention_heads"],
        rope_theta=thetas[KINDS[0]],
        **{ours: spec[theirs] for theirs, ours in FIELDS.items()},
    )


def reference_dims(spec: dict) -> dict:
    return {
        "n_heads": spec["num_attention_heads"],
        "n_kv_heads": spec["num_key_value_heads"],
        "head_dim": spec["head_dim"] or spec["hidden_size"] // spec["num_attention_heads"],
        "rope_theta": _thetas(spec),
        "window": {"sliding_attention": spec["sliding_window"], "full_attention": None},
        "eps": float(spec["rms_norm_eps"]),
    }


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, h, hkv = config.d_model, config.n_heads, config.n_kv_heads
    hd, f, v = config.resolved_head_dim, config.d_ff, config.vocab_size
    dtype = jnp.dtype(config.dtype)
    stack = functools.partial(quantized_stack, dtype=dtype)
    embed_key, head_key, *kind_keys = jax.random.split(key, 2 + len(KINDS))

    def one_kind(key, n):
        keys = iter(jax.random.split(key, 7))
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq": stack(next(keys), (n,), d, h * hd),
            "wk": stack(next(keys), (n,), d, hkv * hd),
            "wv": stack(next(keys), (n,), d, hkv * hd),
            "wo": stack(next(keys), (n,), h * hd, d),
            "ffn_norm": jnp.ones((n, d), dtype),
            "w_gate": stack(next(keys), (n,), d, f),
            "w_up": stack(next(keys), (n,), d, f),
            "w_down": stack(next(keys), (n,), f, d),
        }

    return {
        "embed": normal(embed_key, (v, d), d, dtype),
        "layers": {
            kind: one_kind(k, config.n_layers // len(KINDS)) for kind, k in zip(KINDS, kind_keys)
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stack(head_key, (), d, v),
    }


def make_params(config: ModelConfig, seed: int):
    return _make(jax.random.PRNGKey(seed), config)


def served(tree):
    """The family's tree in the layout the program scans: layer i of the one
    stack is layer i // 2 of kind i % 2's."""
    kinds = [tree["layers"][kind] for kind in KINDS]
    one = jax.tree.map(
        lambda *a: jnp.stack(a, axis=1).reshape((-1,) + a[0].shape[1:]), *kinds
    )
    return {**tree, "layers": one}


def ref_layer_params(ref_params, index: int):
    # under its kind's name: the reference's `layer` is told the kind by the
    # tree it is handed, and the check compiles one program a kind
    kind = kind_of(index)
    return {kind: ref_params["layers"][kind]}, index // len(KINDS)


def system_chain(config: ModelConfig, width: int, rows: int) -> SimpleNamespace:
    from langstream_tpu.models import transformer as program

    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    causal = jnp.tril(jnp.ones((width, width), jnp.bool_))
    # the file's window is at least max_seq_len (model_config refuses a
    # smaller one), and the config carries no other: mask by that
    window = {"sliding_attention": config.max_seq_len, "full_attention": None}

    def layer_of(kind):
        mask = causal if window[kind] is None else causal & ~jnp.tril(causal, -window[kind])

        @jax.jit
        def layer(layers, index, x):
            sin, cos = program._rope_freqs(positions, config)
            lp = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False), layers)
            y, _ = program._layer(x, lp, sin, cos, jnp.broadcast_to(mask, (rows, width, width)), config)
            return y

        return layer

    layer = {kind: layer_of(kind) for kind in KINDS}

    @jax.jit
    def embed(params, tokens):
        group = jnp.zeros((rows, width), jnp.int32).at[0].set(tokens)
        return program._embed(params, group, config)

    return SimpleNamespace(
        embed=embed,
        layer=lambda params, index, x: layer[kind_of(index)](params["layers"], index, x),
        unembed=jax.jit(lambda params, x: program._unembed(params, x[:1], config)[0]),
        n_layers=config.n_layers,
    )


def hot_path(engine, width: int, rows: int, new_tokens: int) -> SimpleNamespace:
    """Prefill into a local cache, scatter into a page pool of the engine's
    page size, then one paged decode step a token."""
    from langstream_tpu.models import transformer as program

    config, page_size = engine.config, engine._pagepool.page_size
    n_pages = -(-(width + new_tokens) // page_size)
    tables = jnp.full((rows, n_pages), n_pages, jnp.int32).at[0].set(jnp.arange(n_pages))

    @jax.jit
    def prefill(params, tokens, length):
        group = jnp.zeros((rows, width), jnp.int32).at[0].set(tokens)
        lengths = jnp.ones((rows,), jnp.int32).at[0].set(length)
        cache = program.make_kv_cache(config, rows, width)
        logits, cache = program.prefill(params, group, lengths, cache, config)
        pool = program.make_page_pool(config, n_pages, page_size)
        return logits[0], program.paged_insert_cache(pool, cache, tables, page_size)

    @functools.partial(jax.jit, donate_argnames=("pool",))
    def step(params, token, position, pool):
        logits, pool = program.paged_decode_step_inplace(
            params, token[None], position[None], pool, tables[:1], config, page_size
        )
        return logits[0], pool

    def logits(params, prompt, generated):
        n = len(prompt)
        first, pool = prefill(params, jnp.asarray(prompt + [0] * (width - n), jnp.int32), jnp.int32(n))
        out = [first]
        for j, token in enumerate(generated[:-1]):
            row, pool = step(params, jnp.int32(token), jnp.int32(n + j), pool)
            out.append(row)
        return jnp.stack(out).astype(jnp.float32)

    return SimpleNamespace(logits=logits)


def engine_state(engine) -> dict:
    from langstream_tpu.models.quant import is_quantized

    matrices = [leaf for name, leaf in engine.params["layers"].items() if name.startswith("w")]
    int8 = all(is_quantized(m) and m["q"].dtype == np.int8 for m in matrices)
    pool = engine._pagepool
    return {
        "weights": "int8" if int8 else "unquantized",
        "kv_dtype": str(pool.dev["k"].dtype),
        "page_size": pool.page_size,
    }


def expected_kernels(engine) -> dict:
    return {}  # the toy runs on the CPU, where `auto` takes the jnp path


def state_leaves(engine):
    return engine.params, engine._pagepool.dev

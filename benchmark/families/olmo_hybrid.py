"""The Olmo-Hybrid family (`model_type: olmo_hybrid`): a period of three
gated delta-rule layers and one full-attention layer, SwiGLU in both.

The program serves it through `ModelConfig.layer_pattern`: one stack of
weights a KIND of layer (`params["layers"][kind]`), a page pool over the
full-attention layers and, beside it, a row of recurrent state a slot
(`engine._pagepool.dev["rec"]`: the rule's float32 state and the short
convolution's tail). The check's chain runs the program's own layer functions
(`_linear_layer`, `_layer`) one layer at a time, a linear layer THROUGH a
state of the chain's own: 128 positions by the chunked prefill into it, the
rest by the one-token update from it. Its hot path prefills a padded group
into pages AND state (`prefill`, `paged_insert_cache`) and then steps one
token at a time (`paged_decode_step_inplace`) with the batch the state's rows,
one live and the others idle, as the engine's decode chunk does. `engine_state`
MEASURES what the state's path keeps (`state_probe`): no comparison of logits
can tell a state of fewer bits at these widths.

Where the harness reaches into the program for this family: the private model
functions `_embed`, `_layer`, `_linear_layer`, `_unembed`, the public ones
above with `make_kv_cache`, `make_page_pool`, `make_recurrent_state`,
`split_rec`, `join_rec`, and `engine._pagepool`.

Seeded weights: matrices N(0, 1 / fan_in) like the other families (the five
large projections of a linear layer, the four of a full layer and the FFN
then int8 per output channel; W_a, W_b, the convolution's taps, `A_log`,
`dt_bias` and the norms unquantised). `A_log` and `dt_bias` are drawn as the
family's published initialisation (`fla`'s GatedDeltaNet) draws them:
A uniform in (0, 16), `A_log = log A`; the step dt log-uniform in
(0.001, 0.1), `dt_bias = dt + log(-expm1(-dt))` (the inverse softplus), so
that the decay alpha = exp(-A softplus(a + dt_bias)) spans a real range and
the state neither dies nor sticks. The taps are N(0, 1 / K).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from langstream_tpu.models.configs import ModelConfig
from modelcfg import refuse_unmapped
from weights import normal, quantized_stack

PERIOD = ("linear_attention", "linear_attention", "linear_attention", "full_attention")
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
    "linear_num_value_heads": "linear_n_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel",
    "linear_allow_neg_eigval": "linear_allow_neg_eigval",
}
MAPPED_HERE = (
    "model_type", "layer_types", "rope_parameters", "attention_bias", "linear_num_key_heads",
    "head_dim",
)
QUANTIZED = {
    "linear_attention": ("wqkv", "wg", "wo", "w_gate", "w_up", "w_down"),
    "full_attention": ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"),
}


def place(index: int) -> tuple[str, int]:
    """Layer `index` of the model: its kind and its place in that kind's stack."""
    period, at = divmod(index, len(PERIOD))
    kind = PERIOD[at]
    return kind, period * PERIOD.count(kind) + PERIOD[:at].count(kind)


def _head_dim(spec: dict) -> int:
    # `head_dim` is no key of the published file: hidden_size / heads (`assumed`)
    return spec.get("head_dim") or spec["hidden_size"] // spec["num_attention_heads"]


def model_config(spec: dict, name: str) -> ModelConfig:
    refuse_unmapped(spec, [*FIELDS, *MAPPED_HERE], name)
    n = spec["num_hidden_layers"]
    if spec["layer_types"] != [PERIOD[i % len(PERIOD)] for i in range(n)] or n % len(PERIOD):
        raise ValueError(f"{name}: layer_types is not {n} layers of the period {PERIOD}")
    if spec["rope_parameters"] != {"rope_theta": None}:
        raise ValueError(f"{name}: the family's full layers turn nothing: {spec['rope_parameters']}")
    if spec["attention_bias"] or spec["linear_num_key_heads"] != spec["linear_num_value_heads"]:
        raise ValueError(f"{name}: a projection bias, or key heads other than value heads")
    return ModelConfig(
        name=name, head_dim=_head_dim(spec), layer_pattern=PERIOD,
        # the OLMo block (`assumed`): the norm on the sublayer's output, an
        # RMSNorm over the whole width of q and k, a null rope_theta: no rotary
        output_norm=True, qk_norm=True, rope=False,
        **{ours: spec[theirs] for theirs, ours in FIELDS.items()},
    )


def reference_dims(spec: dict) -> dict:
    return {
        "n_heads": spec["num_attention_heads"],
        "n_kv_heads": spec["num_key_value_heads"],
        "head_dim": _head_dim(spec),
        "linear_heads": spec["linear_num_value_heads"],
        "linear_key_head_dim": spec["linear_key_head_dim"],
        "linear_value_head_dim": spec["linear_value_head_dim"],
        "allow_neg_eigval": bool(spec["linear_allow_neg_eigval"]),
        "eps": float(spec["rms_norm_eps"]),
    }


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, f, v = config.d_model, config.d_ff, config.vocab_size
    h, hkv, hd = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    lh, kd, vd = config.linear_n_heads, config.linear_key_dim, config.linear_value_dim
    width, dtype = config.linear_conv_kernel, jnp.dtype(config.dtype)
    stack = functools.partial(quantized_stack, dtype=dtype)
    embed_key, head_key, lin_key, full_key = jax.random.split(key, 4)

    def ffn(keys, n):
        return {
            "ffn_norm": jnp.ones((n, d), dtype),
            "w_gate": stack(next(keys), (n,), d, f),
            "w_up": stack(next(keys), (n,), d, f),
            "w_down": stack(next(keys), (n,), f, d),
        }

    def linear(key, n):
        keys = iter(jax.random.split(key, 11))
        a = jax.random.uniform(next(keys), (n, lh), jnp.float32, 1e-3, 16.0)
        dt = jnp.exp(
            jax.random.uniform(next(keys), (n, lh), jnp.float32)
            * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
        )
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            # W_q, W_k and W_v side by side (a scale a column: the same
            # numbers as three matrices)
            "wqkv": stack(next(keys), (n,), d, 2 * kd + vd),
            "wg": stack(next(keys), (n,), d, vd),
            "wa": normal(next(keys), (n, d, lh), d, dtype),
            "wb": normal(next(keys), (n, d, lh), d, dtype),
            "conv_w": normal(next(keys), (n, width, config.linear_conv_dim), width, dtype),
            "A_log": jnp.log(a),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "out_norm": jnp.ones((n, config.linear_value_head_dim), dtype),
            "wo": stack(next(keys), (n,), vd, d),
            **ffn(keys, n),
        }

    def full(key, n):
        keys = iter(jax.random.split(key, 7))
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "q_norm": jnp.ones((n, h * hd), dtype),
            "k_norm": jnp.ones((n, hkv * hd), dtype),
            "wq": stack(next(keys), (n,), d, h * hd),
            "wk": stack(next(keys), (n,), d, hkv * hd),
            "wv": stack(next(keys), (n,), d, hkv * hd),
            "wo": stack(next(keys), (n,), h * hd, d),
            **ffn(keys, n),
        }

    if config.tie_embeddings:
        raise NotImplementedError("tied embeddings: no configuration needs them yet")
    return {
        "embed": normal(embed_key, (v, d), d, dtype),
        "layers": {
            "linear_attention": linear(lin_key, config.n_layers_of("linear_attention")),
            "full_attention": full(full_key, config.n_layers_of("full_attention")),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stack(head_key, (), d, v),
    }


def make_params(config: ModelConfig, seed: int):
    return _make(jax.random.PRNGKey(seed), config)


def ref_layer_params(ref_params, index: int):
    kind, at = place(index)
    return {kind: ref_params["layers"][kind]}, at


# The check's own recurrent state: one row the sequence's, the others idle, so
# that a decode step here is what the engine's is, the batch the state's rows
# (`_rows_of` / `_set_rows` take a layer's slab whole, the update kernel skips
# the idle rows); a sublane tile of rows.
STATE_ROWS = 8
# The chain's linear layers take positions below this through the chunked
# prefill INTO the state (two chunks, one carry between them) and every later
# position through the one-token update FROM it, as a slot's life goes.
SPLIT = 128
# `engine_state`'s reading of the state's path (`state_probe`), sound and with
# the state rounded to bf16 at every write: PERF.md section 6, PR 32
STATE_TOL = 2.0**-12


def _take(stack, index):
    return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False), stack)


def _one_layer_state(config: ModelConfig, dtypes=None) -> dict:
    """STATE_ROWS rows of one linear layer's state, as the program makes it
    (`dtypes`: a dtype a leaf instead)."""
    from langstream_tpu.models import transformer as program

    rec = jax.tree.map(lambda a: a[:1], program.make_recurrent_state(config, STATE_ROWS))
    return rec if dtypes is None else jax.tree.map(lambda a, d: a.astype(d), rec, dtypes)


def _linear_through_state(x, lp, config: ModelConfig, rec: dict, split: int):
    """One linear layer over x [rows, width, d] the way a slot lives it: the
    first `split` positions through the chunked prefill into the state's first
    rows (written by row, as an admit group writes them), every later position
    one token at a time through the decode update, row 0 live and the other
    STATE_ROWS - 1 idle."""
    from langstream_tpu.models import transformer as program

    rows = x.shape[0]
    head, rec = program._linear_layer(
        x[:, :split], lp, config, rec, 0,
        {"rows": jnp.arange(rows), "valid": jnp.ones((rows, split), jnp.bool_), "fresh": True},
    )
    live = jnp.arange(STATE_ROWS) < 1

    def step(rec, x_t):  # x_t [rows, d]
        batch = jnp.zeros((STATE_ROWS, 1, x.shape[2]), x.dtype).at[:rows, 0].set(x_t)
        y, rec = program._linear_layer(
            batch, lp, config, rec, 0, {"rows": None, "valid": live[:, None], "fresh": None}
        )
        return rec, y[:rows, 0]

    _, tail = lax.scan(step, rec, jnp.swapaxes(x[:, split:], 0, 1))
    return jnp.concatenate([head, jnp.swapaxes(tail, 0, 1)], axis=1)


def system_chain(config: ModelConfig, width: int, rows: int) -> SimpleNamespace:
    """The body of transformer.forward, one layer at a time: each kind a
    compiled layer of its own. A linear layer runs THROUGH a state of its own
    (`_linear_through_state`), so the level that is teacher forced holds the
    chunked prefill with its carry, the one-token update and the state's
    reads and writes to the reference; a full layer runs the whole width under
    a causal mask (its pages are the hot path's to show)."""
    from langstream_tpu.models import transformer as program

    if rows > STATE_ROWS:
        raise ValueError(f"a check group of {rows} rows: the check's state has {STATE_ROWS}")
    split = min(SPLIT, width // 2)
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((width, width), jnp.bool_)), (rows, width, width))

    @jax.jit
    def sys_embed(params, tokens):
        group = jnp.zeros((rows, width), jnp.int32).at[0].set(tokens)
        return program._embed(params, group, config)

    @jax.jit
    def linear_layer(stack, index, x):
        return _linear_through_state(
            x, _take(stack, index), config, _one_layer_state(config), split
        )

    @jax.jit
    def full_layer(stack, index, x):
        return program._layer(x, _take(stack, index), None, None, mask, config)[0]

    layers = {"linear_attention": linear_layer, "full_attention": full_layer}

    def sys_layer(params, index, x):
        kind, at = place(index)
        return layers[kind](params["layers"][kind], at, x)

    @jax.jit
    def sys_unembed(params, x):
        return program._unembed(params, x[:1], config)[0]

    return SimpleNamespace(
        embed=sys_embed, layer=sys_layer, unembed=sys_unembed, n_layers=config.n_layers
    )


class hot_path:
    """The model functions the engine's programs are made of, called as the
    engine calls them, with its config and page size, on a page pool and a
    recurrent state of this check's own, in the engine's dtypes: a padded
    group prefilled in one call into pages AND state row 0 (`prefill` with
    `rec_rows`, `paged_insert_cache`), then one `paged_decode_step_inplace` a
    token with the batch the state's STATE_ROWS rows, row 0 the sequence and
    the others idle (no table, so no length): the decode chunk's own branch."""

    def __init__(self, engine, width: int, rows: int, new_tokens: int) -> None:
        from langstream_tpu.models import transformer as program

        config = engine.config
        page_size = engine._pagepool.page_size
        n_pages = -(-(width + new_tokens) // page_size)
        self.width = width
        dtypes = jax.tree.map(lambda a: a.dtype, engine._pagepool.dev["rec"])
        # the sequence in row 0 of the group, its pages 0..n_pages-1 and state
        # row 0; every other row's table and state row are out of bounds
        group_rows = jnp.full((rows,), STATE_ROWS, jnp.int32).at[0].set(0)
        tables = jnp.full((STATE_ROWS, n_pages), n_pages, jnp.int32).at[0].set(jnp.arange(n_pages))

        @jax.jit
        def prefill_group(params, tokens, length):
            group = jnp.zeros((rows, width), jnp.int32).at[0].set(tokens)
            lengths = jnp.ones((rows,), jnp.int32).at[0].set(length)
            pool = program.make_page_pool(config, n_pages, page_size, state_rows=STATE_ROWS)
            kv, rec = program.split_rec(pool)
            # the engine's own state dtypes, whatever the program's default
            rec = jax.tree.map(lambda a, d: a.astype(d), rec, dtypes)
            local = program.join_rec(program.make_kv_cache(config, rows, width), rec)
            logits, local = program.prefill(
                params, group, lengths, local, config, rec_rows=group_rows
            )
            local, rec = program.split_rec(local)
            pool = program.paged_insert_cache(
                program.join_rec(kv, rec), local, tables[:rows], page_size
            )
            return logits[0], pool

        @functools.partial(jax.jit, donate_argnames=("pool",))
        def decode(params, token, position, pool):
            tokens = jnp.zeros((STATE_ROWS,), jnp.int32).at[0].set(token)
            positions = jnp.zeros((STATE_ROWS,), jnp.int32).at[0].set(position)
            logits, pool = program.paged_decode_step_inplace(
                params, tokens, positions, pool, tables, config, page_size
            )
            return logits[0], pool

        self._fns = (prefill_group, decode)

    def logits(self, params, prompt: list[int], generated: list[int]):
        """[len(generated), V]: row j is the distribution generated token j
        was drawn from, token j - 1 having gone through pages and state."""
        prefill_group, decode = self._fns
        n = len(prompt)
        tokens = jnp.asarray(prompt + [0] * (self.width - n), jnp.int32)
        first, pool = prefill_group(params, tokens, jnp.int32(n))
        rows = [first]
        for j, token in enumerate(generated[:-1]):
            step, pool = decode(params, jnp.int32(token), jnp.int32(n + j), pool)
            rows.append(step)
        return jnp.stack(rows).astype(jnp.float32)


def _dims_of(config: ModelConfig) -> dict:
    """`reference_dims`, read back from the program's config."""
    return {
        "n_heads": config.n_heads, "n_kv_heads": config.n_kv_heads,
        "head_dim": config.resolved_head_dim, "linear_heads": config.linear_n_heads,
        "linear_key_head_dim": config.linear_key_head_dim,
        "linear_value_head_dim": config.linear_value_head_dim,
        "allow_neg_eigval": bool(config.linear_allow_neg_eigval),
        "eps": float(config.rms_norm_eps),
    }


def state_probe(engine, width: int = 192) -> float:
    """How many bits the state's path keeps, measured: the first linear layer
    through a state in the ENGINE's state dtype (`_linear_through_state`:
    128 positions prefilled, 64 updated one at a time), with float32
    activations at the highest matmul precision, so that the state is all
    that can lose bits, against the reference on the same input. Returns
    max over positions of |program - reference|_inf / |reference|_inf.

    The logits cannot tell: rounding the state to bf16 at every write moves
    the hot path's median from 0.0784 to 0.0940 after 300 generated tokens and
    by a twentieth after 32 (PERF.md section 6, PR 32), inside what two bf16
    runs of different operation order part by. Here nothing else rounds."""
    from langstream_tpu.models import transformer as program
    from modelcfg import load_module

    ref = load_module("reference", "olmo_hybrid")
    config = dataclasses.replace(engine.config, dtype="float32")
    # the engine's state dtype; the convolution's tail in float32 like the
    # activations (in the engine both are bf16, and the tail's rounding is theirs)
    dtypes = {"s": engine._pagepool.dev["rec"]["s"].dtype, "conv": jnp.dtype(jnp.float32)}
    tokens = np.random.default_rng(width).integers(0, config.vocab_size - 1, (1, width))

    @jax.jit
    def both(params, tokens):
        stack = params["layers"]["linear_attention"]
        with jax.default_matmul_precision("highest"):
            x = program._embed(params, tokens, config).astype(jnp.float32)
            got = _linear_through_state(
                x, _take(stack, 0), config, _one_layer_state(config, dtypes), min(SPLIT, width // 2)
            )
        want, _ = ref.layer(x[0], {"linear_attention": _take(stack, 0)}, _dims_of(config))
        err = jnp.max(jnp.abs(got[0] - want), axis=-1) / jnp.max(jnp.abs(want), axis=-1)
        return jnp.max(err)

    return float(both(engine.params, jnp.asarray(tokens, jnp.int32)))


def engine_state(engine) -> dict:
    from langstream_tpu.models.quant import is_quantized

    int8 = all(
        is_quantized(engine.params["layers"][kind][k])
        and engine.params["layers"][kind][k]["q"].dtype == np.int8
        for kind, keys in QUANTIZED.items() for k in keys
    )
    pool = engine._pagepool.dev
    lost = state_probe(engine)
    return {
        "weights": "int8" if int8 else "unquantized",
        "kv_dtype": "int8" if isinstance(pool["k"], dict) else str(pool["k"].dtype),
        "state_dtype": str(pool["rec"]["s"].dtype),
        # a measurement, not a label: a kernel that keeps fewer bits of a
        # float32 array, or a state stored in fewer, reads lossy
        "state_path": "exact" if lost <= STATE_TOL else f"lossy: {lost:.2e} of a layer's output",
    }


def expected_kernels(engine) -> dict:
    """`attention_paths()` entry -> what must have been traced there: four
    Pallas kernels by their `pallas_call`'s name (`paged_kv_write` rides the
    paged decode's entry: the same gate admits both) and the chunked delta
    rule, matrix products in XLA, by its scope."""
    pool = engine._pagepool
    return {
        f"paged-decode[s=1,t={pool.table_len * pool.page_size}]": "ragged_paged_decode_attention",
        "linear-decode[s=1,t=0]": "gated_delta_update",
        **{
            f"prefill[s={w},t={w}]": "flash_prefill_attention"
            for w in engine.prefill_buckets if w % 128 == 0
        },
        **{
            f"linear-prefill[s={w},t={w}]": "gated_delta_chunk_prefill"
            for w in engine.prefill_buckets
        },
    }


def state_leaves(engine):
    return engine.params, engine._pagepool.dev

"""The LFM2-MoE family (`model_type: lfm2_moe`, LiquidAI/LFM2-24B-A2B): a
period of three gated short-convolution layers to one attention layer of
64-wide heads (conv, conv, full_attention, conv), the first `num_dense_layers`
layers with a dense SwiGLU, every later one with 64 sigmoid-routed experts
top-4 chosen under a bias, a tied head. Equations: `reference/lfm2_moe.py`'s
docstring.

The program serves it through `ModelConfig.layer_pattern` with the `conv`
kind (`conv_kernel`), `n_leading_dense` INSIDE the pattern (the dense layers
are conv layers of period 0: `params["dense_layers"]["conv"]`, and a kind's
stack under `params["layers"]` starts behind them), `experts_held` = every
expert with `moe_d_ff`, `moe_scoring: sigmoid`, `router_bias`,
`routed_scaling` and `router_norm_eps`, `qk_norm_heads`, and
`ModelConfig.kv_head_pack`: heads of 64 lie two to a 128-lane row of the cache
and the page pool, `[L, P, 4, page, 128]`, and the paged decode kernel,
`paged_kv_write`, `paged_insert_pages` and the prefill kernel take the leaf as
4 heads of 128 (a query in its own half of the lanes). Beside the pages a
slot keeps a row of recurrent state that is a convolution tail ALONE,
`engine._pagepool.dev["rec"]["conv"]` `[12, slots, 2 x 2048]`.

The check's chain runs the program's own functions HALF a layer a step
(`_short_conv_block` or `_attention_block`, then `_ffn_half`), so that the
reference's router reads the hidden state the program's router read; a conv
mixer runs THROUGH a state of the chain's own, positions 0 to 127 by the
prefill branch into it and every later position one token at a time from it,
so the teacher-forced level holds the tail's carry too. Its hot path is the
engine's own sequence at the cell's knobs: `prefill` at the engine's bucket
with the slot's state row (`rec_rows`), `paged_insert_cache` (pages and
tails), a prompt past the largest bucket in segments that carry the tail in
(`paged_prefill_segment_inplace`, `state_rows`), then one
`paged_decode_step_inplace` a token with the batch the engine's slots, one
live and the others idle.

Where the harness reaches into the program for this family: the private model
functions `_embed`, `_short_conv_block`, `_attention_block`, `_ffn_half`,
`_rope_freqs`, `_unembed`, the public ones above with `make_kv_cache`,
`make_page_pool`, `make_recurrent_state`, `split_rec`, `join_rec`, and
`engine._pagepool`, `engine.max_batch`, `engine.prefill_batch`,
`engine.prefill_buckets`.

Seeded weights: matrices N(0, 1 / fan_in) then int8 per output channel (a
conv mixer's two projections, an attention mixer's four, the dense FFNs, the
experts); the convolution's taps N(0, 1 / K), the router float32, norms ones,
the embedding in the model's dtype (the tied head reads it as it lies); the
router's bias N(`BIAS_MEAN`, `BIAS_SIGMA`^2) float32, NOT zero and NOT of
mean zero: a trained model's balances its experts' load, a zero bias would
leave the rule that it chooses and does not weigh untested, and so would a
small one of mean zero: the 4 largest of 64 sigmoid scores lie within a few
hundredths of 0.9, so the weights are near a quarter each whatever a bias of
a hundredth adds to them (the control `bias-weighs` then reads the sound
system's numbers to the digit). The common part moves no choice (the k largest
are the same under a shift of all) and moves EVERY weight if it is weighed:
(s - 0.7) / sum(s - 0.7) parts by a seventh from s / sum(s).
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

PERIOD = ("conv", "conv", "full_attention", "conv")
# every key of the published config.json (the catalog row's `config`)
PUBLISHED = (
    "conv_L_cache", "conv_bias", "hidden_size", "intermediate_size", "layer_types",
    "max_position_embeddings", "model_type", "moe_intermediate_size", "norm_eps",
    "norm_topk_prob", "num_attention_heads", "num_dense_layers", "num_experts",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads", "rope_parameters",
    "routed_scaling_factor", "use_expert_bias", "vocab_size",
)
# what a key has to say for the program's block to be the model's
_HAS_TO_SAY = {
    "model_type": "lfm2_moe", "conv_bias": False, "norm_topk_prob": True,
    "use_expert_bias": True,
}
EXPERTS = ("w_gate", "w_up", "w_down")
MIXER_HALF = {
    "conv": ("attn_norm", "w_in", "conv_w", "w_out"),
    "full_attention": ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm"),
}
DENSE_HALF = ("ffn_norm", *EXPERTS)
EXPERT_HALF = ("ffn_norm", "router", "router_bias", *EXPERTS)
QUANTIZED = {
    "conv": ("w_in", "w_out", *EXPERTS), "full_attention": ("wq", "wk", "wv", "wo", *EXPERTS),
}
# the published code's `+ 1e-6` under the chosen scores' sum (`assumed`)
ROUTER_EPS = 1e-6
# the seeded router bias's mean and spread: configs/lfm2-24b-a2b-int8-d16.json `weights.why`
BIAS_MEAN, BIAS_SIGMA = -0.7, 0.01
# the check's own state rows and where the chain's conv mixers pass from the
# prefill branch to single steps, as `families/olmo_hybrid.py` has them
STATE_ROWS, SPLIT = 8, 128


def _head_dim(spec: dict) -> int:
    # `head_dim` is no key of the published file: hidden_size / heads (`assumed`)
    return spec["hidden_size"] // spec["num_attention_heads"]


def model_config(spec: dict, name: str) -> ModelConfig:
    refuse_unmapped(spec, PUBLISHED, name)
    n = spec["num_hidden_layers"]
    differs = {k: spec.get(k) for k, v in _HAS_TO_SAY.items() if spec.get(k) != v}
    if spec["layer_types"] != [PERIOD[i % len(PERIOD)] for i in range(n)] or n % len(PERIOD):
        differs["layer_types"] = f"not {n} layers of the period {PERIOD}"
    rope = spec["rope_parameters"]
    if set(rope) != {"rope_theta", "rope_type"} or rope["rope_type"] != "default":
        differs["rope_parameters"] = rope
    if differs:
        raise ValueError(f"{name}: the program's conv-pattern model cannot express {differs}")
    return ModelConfig(
        name=name, vocab_size=spec["vocab_size"], d_model=spec["hidden_size"], n_layers=n,
        n_heads=spec["num_attention_heads"], n_kv_heads=spec["num_key_value_heads"],
        head_dim=_head_dim(spec), d_ff=spec["intermediate_size"],
        moe_d_ff=spec["moe_intermediate_size"], rope_theta=float(rope["rope_theta"]),
        rms_norm_eps=float(spec["norm_eps"]), max_seq_len=spec["max_position_embeddings"],
        # `assumed`: the tied head, the per-head q/k norm, the router's + 1e-6
        tie_embeddings=True, qk_norm_heads=True, router_norm_eps=ROUTER_EPS,
        layer_pattern=PERIOD, conv_kernel=spec["conv_L_cache"],
        n_experts=spec["num_experts"], n_experts_per_tok=spec["num_experts_per_tok"],
        experts_held=(0, spec["num_experts"]), moe_scoring="sigmoid", router_bias=True,
        routed_scaling=float(spec["routed_scaling_factor"]),
        n_leading_dense=spec["num_dense_layers"],
    )


def reference_dims(spec: dict) -> dict:
    return {
        "n_heads": spec["num_attention_heads"], "n_kv_heads": spec["num_key_value_heads"],
        "head_dim": _head_dim(spec), "eps": float(spec["norm_eps"]),
        "rope_theta": float(spec["rope_parameters"]["rope_theta"]),
        "top_k": spec["num_experts_per_tok"], "n_experts": spec["num_experts"],
        "routed_scaling": float(spec["routed_scaling_factor"]),
        "layer_pattern": tuple(PERIOD), "n_dense": spec["num_dense_layers"],
        "n_layers": spec["num_hidden_layers"],
    }


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, v, e = config.d_model, config.vocab_size, config.n_experts
    h, hkv, hd = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    dtype = jnp.dtype(config.dtype)
    stack = functools.partial(quantized_stack, dtype=dtype)

    def mixer(kind, key, n):
        keys = iter(jax.random.split(key, 4))
        if kind == "conv":
            width = config.conv_kernel
            return {
                "attn_norm": jnp.ones((n, d), dtype),
                "w_in": stack(next(keys), (n,), d, 3 * d),  # B | C | u side by side
                "conv_w": normal(next(keys), (n, width, d), width, dtype),
                "w_out": stack(next(keys), (n,), d, d),
            }
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq": stack(next(keys), (n,), d, h * hd), "wk": stack(next(keys), (n,), d, hkv * hd),
            "wv": stack(next(keys), (n,), d, hkv * hd), "wo": stack(next(keys), (n,), h * hd, d),
            "q_norm": jnp.ones((n, hd), dtype), "k_norm": jnp.ones((n, hd), dtype),
        }

    def swiglu(key, lead, width):
        keys = jax.random.split(key, 3)
        return {
            "w_gate": stack(keys[0], lead, d, width), "w_up": stack(keys[1], lead, d, width),
            "w_down": stack(keys[2], lead, width, d),
        }

    def experts(key, n):
        keys = jax.random.split(key, 3)
        return {
            "ffn_norm": jnp.ones((n, d), dtype),
            # float32: the router scores in float32 at the highest precision
            "router": normal(keys[0], (n, d, e), d, jnp.float32),
            "router_bias": BIAS_MEAN + BIAS_SIGMA * jax.random.normal(keys[1], (n, e), jnp.float32),
            **swiglu(keys[2], (n, e), config.expert_d_ff),
        }

    keys = iter(jax.random.split(key, 16))
    tree = {
        "embed": normal(next(keys), (v, d), d, dtype), "final_norm": jnp.ones((d,), dtype),
        "layers": {}, "dense_layers": {},
    }
    for kind in dict.fromkeys(PERIOD):
        first = config.dense_of(kind)
        rest = config.n_layers_of(kind) - first
        if first:
            tree["dense_layers"][kind] = {
                **mixer(kind, next(keys), first), "ffn_norm": jnp.ones((first, d), dtype),
                **swiglu(next(keys), (first,), config.d_ff),
            }
        tree["layers"][kind] = {**mixer(kind, next(keys), rest), **experts(next(keys), rest)}
    return tree


def make_params(config: ModelConfig, seed: int):
    return _make(jax.random.PRNGKey(seed), config)


def _place(params, step: int) -> tuple[str, str, str, int, int]:
    """Chain step -> (the stack's key in the tree, the layer's kind there,
    the reference's name for the kind, the layer's place in that stack, which
    half): layer `step // 2` of the pattern, one of the leading dense layers
    (`params["dense_layers"][kind]`) or behind them in its kind's stack."""
    index, half = divmod(step, 2)
    kind = PERIOD[index % len(PERIOD)]
    at = sum(PERIOD[i % len(PERIOD)] == kind for i in range(index))
    dense = {k: jax.tree.leaves(s)[0].shape[0] for k, s in params.get("dense_layers", {}).items()}
    mixer = "conv" if kind == "conv" else "attention"
    if index < sum(dense.values()):
        return "dense_layers", kind, f"{mixer}_dense", at, half
    return "layers", kind, f"{mixer}_expert", at - dense.get(kind, 0), half


def ref_layer_params(ref_params, step: int):
    """The leaves of the half a chain step runs (`reference.layer` runs the
    half it is handed), its stack's every layer, under its kind's name
    (`conv_dense` | `conv_expert` | `attention_expert`: the check compiles one
    program a kind and half), and the layer's place in that stack."""
    stack, kind, name, at, half = _place(ref_params, step)
    names = (MIXER_HALF[kind], DENSE_HALF if stack == "dense_layers" else EXPERT_HALF)[half]
    layers = ref_params[stack][kind]
    return {name: {k: layers[k] for k in names}}, at


def _one_layer_state(config: ModelConfig) -> dict:
    """STATE_ROWS rows of one conv layer's state, as the program makes it."""
    from langstream_tpu.models import transformer as program

    return jax.tree.map(lambda a: a[:1], program.make_recurrent_state(config, STATE_ROWS))


def _conv_through_state(x, lp, config: ModelConfig, split: int):
    """One conv mixer over x [rows, width, d] the way a slot lives it: the
    first `split` positions through the prefill branch into the state's first
    rows (written by row, as an admit group writes them), every later position
    one token at a time from it, row 0 live and the other STATE_ROWS - 1 idle."""
    from langstream_tpu.models import transformer as program

    rows = x.shape[0]
    head, rec = program._short_conv_block(
        x[:, :split], lp, config, _one_layer_state(config), 0,
        {"rows": jnp.arange(rows), "valid": jnp.ones((rows, split), jnp.bool_), "fresh": True},
    )
    live = jnp.arange(STATE_ROWS) < 1

    def step(rec, x_t):  # x_t [rows, d]
        batch = jnp.zeros((STATE_ROWS, 1, x.shape[2]), x.dtype).at[:rows, 0].set(x_t)
        y, rec = program._short_conv_block(
            batch, lp, config, rec, 0, {"rows": None, "valid": live[:, None], "fresh": None}
        )
        return rec, y[:rows, 0]

    _, tail = lax.scan(step, rec, jnp.swapaxes(x[:, split:], 0, 1))
    return jnp.concatenate([head, jnp.swapaxes(tail, 0, 1)], axis=1)


def system_chain(config: ModelConfig, width: int, rows: int) -> SimpleNamespace:
    """The body of `transformer.forward` over a pass's whole sequence, one
    HALF of a layer at a time: the mixer (`_short_conv_block` through a state
    of the chain's own, or `_attention_block` over the whole width under a
    causal mask: the prefill kernel at two heads of 64 a lane row) then
    `_ffn_half` (a leading dense layer's FFN, or the expert layer with the
    experts' stacks handed on whole with the layer's place, as the period loop
    hands them on), so the chain has two steps a layer and the reference is
    handed the program's input to each: the router reads the mixer's output."""
    from langstream_tpu.models import transformer as program

    if rows != 1:
        raise ValueError("this chain takes one row: no expert is dropped")
    positions = jnp.arange(width)[None]
    split = min(SPLIT, width // 2)

    def of_layer(layers, index, whole=()):
        return {
            key: leaf if key in whole else jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False), leaf)
            for key, leaf in layers.items()
        }

    @jax.jit
    def sys_embed(params, tokens):
        return program._embed(params, tokens[None], config)

    @jax.jit
    def sys_conv(layers, index, x):
        return _conv_through_state(x, of_layer(layers, index), config, split)

    @jax.jit
    def sys_attention(layers, index, x):
        sin, cos = program._rope_freqs(positions, config)
        mask = jnp.tril(jnp.ones((width, width), jnp.bool_))[None]
        return program._attention_block(x, of_layer(layers, index), sin, cos, mask, config)[0]

    @jax.jit
    def sys_dense(layers, index, x):
        return program._ffn_half(x, of_layer(layers, index), config, dense=True)[0]

    @jax.jit
    def sys_experts(layers, index, x):
        return program._ffn_half(x, of_layer(layers, index, EXPERTS), config, layer=index)[0]

    @jax.jit
    def sys_unembed(params, x):
        return program._unembed(params, x, config)[0]

    mixers = {"conv": sys_conv, "full_attention": sys_attention}

    def sys_layer(params, step, x):
        stack, kind, _, at, half = _place(params, step)
        ffn = sys_dense if stack == "dense_layers" else sys_experts
        return (mixers[kind], ffn)[half](params[stack][kind], at, x)

    return SimpleNamespace(
        embed=sys_embed, layer=sys_layer, unembed=sys_unembed, n_layers=2 * config.n_layers
    )


class hot_path:
    """The model functions the engine's programs are made of, called as the
    engine calls them for this cell's traffic, with its config (so its
    kernels), its page size, its pool's and its state's dtypes, its buckets
    and its slot count, on a page pool and a recurrent state of this check's
    own. A prompt inside the largest bucket goes through `prefill` at the
    bucket the engine would take (the smallest that holds it), its tail
    written to state row 0 at its true length (`rec_rows`), and
    `paged_insert_cache` (the admit group, row 0 of the engine's group: pages
    by whole-page copies, the state beside them); a longer one in segments of
    the largest bucket through `paged_prefill_segment_inplace`, each carrying
    the tail the last one left (`state_rows`); then one decode step a token
    with the batch the engine's slots, row 0 the sequence and every other row
    idle (its table maps nothing, so its tail stays)."""

    def __init__(self, engine, width: int, rows: int, new_tokens: int) -> None:
        from langstream_tpu.models import transformer as program

        config, pool = engine.config, engine._pagepool
        self.buckets = buckets = tuple(sorted(engine.prefill_buckets))
        page_size, slots, group = pool.page_size, engine.max_batch, engine.prefill_batch
        n_pages = -(-width // page_size)
        dtypes = jax.tree.map(lambda a: a.dtype, pool.dev["rec"])
        kept = pool.dev["k"].dtype
        # row 0 the sequence's pages and state row, every other row's table and
        # state row out of bounds
        row0 = lambda n: jnp.full((n, n_pages), n_pages, jnp.int32).at[0].set(jnp.arange(n_pages))  # noqa: E731
        tables, group_tables = row0(slots), row0(group)
        group_rows = jnp.full((group,), slots, jnp.int32).at[0].set(0)

        @jax.jit
        def fresh():
            made = program.make_page_pool(config, n_pages, page_size, dtype=kept, state_rows=slots)
            kv, rec = program.split_rec(made)
            return program.join_rec(kv, jax.tree.map(lambda a, d: a.astype(d), rec, dtypes))

        @functools.partial(jax.jit, static_argnames=("bucket",))
        def prefill_group(params, tokens, length, bucket):
            rows_tokens = jnp.zeros((group, bucket), jnp.int32).at[0].set(tokens)
            lengths = jnp.ones((group,), jnp.int32).at[0].set(length)
            kv, rec = program.split_rec(fresh())
            local = program.join_rec(program.make_kv_cache(config, group, bucket), rec)
            logits, local = program.prefill(
                params, rows_tokens, lengths, local, config, rec_rows=group_rows
            )
            local, rec = program.split_rec(local)
            return logits[0], program.paged_insert_cache(
                program.join_rec(kv, rec), local, group_tables, page_size, config
            )

        @functools.partial(jax.jit, donate_argnames=("pool",))
        def prefill_segment(params, tokens, offset, length, pool):
            logits, pool = program.paged_prefill_segment_inplace(
                params, tokens[None], offset[None], length[None], pool, row0(1), config,
                page_size, state_rows=jnp.zeros((1,), jnp.int32),
            )
            return logits[0], pool

        @functools.partial(jax.jit, donate_argnames=("pool",))
        def decode(params, token, position, pool):
            tokens = jnp.zeros((slots,), jnp.int32).at[0].set(token)
            positions = jnp.zeros((slots,), jnp.int32).at[0].set(position)
            logits, pool = program.paged_decode_step_inplace(
                params, tokens, positions, pool, tables, config, page_size
            )
            return logits[0], pool

        self._fns = (fresh, prefill_group, prefill_segment, decode)

    def logits(self, params, prompt: list[int], generated: list[int]):
        """[len(generated), V]: row j is the distribution generated token j
        was drawn from, token j - 1 having gone through pool and tails."""
        fresh, prefill_group, prefill_segment, decode = self._fns
        n, seg = len(prompt), self.buckets[-1]
        padded = lambda part, w: jnp.asarray(part + [0] * (w - len(part)), jnp.int32)  # noqa: E731
        if n <= seg:
            bucket = next(w for w in self.buckets if w >= n)
            first, pool = prefill_group(params, padded(list(prompt), bucket), jnp.int32(n), bucket)
        else:
            pool = fresh()
            for s0 in range(0, n, seg):
                part = list(prompt[s0 : s0 + seg])
                first, pool = prefill_segment(
                    params, padded(part, seg), jnp.int32(s0), jnp.int32(len(part)), pool
                )
        rows = [first]
        for j, token in enumerate(generated[:-1]):
            step, pool = decode(params, jnp.int32(token), jnp.int32(n + j), pool)
            rows.append(step)
        return jnp.stack(rows).astype(jnp.float32)


def engine_state(engine) -> dict:
    from langstream_tpu.models.quant import is_quantized

    params, pool, config = engine.params, engine._pagepool.dev, engine.config
    stacks = [
        (params[stack][kind], kind)
        for stack in ("dense_layers", "layers") for kind in params.get(stack, {})
    ]
    int8 = all(
        is_quantized(layers[k]) and layers[k]["q"].dtype == np.int8
        for layers, kind in stacks for k in QUANTIZED[kind]
    )
    first, held = config.held_experts
    return {
        "weights": "int8" if int8 else "unquantized",
        "kv_dtype": "int8" if isinstance(pool["k"], dict) else str(pool["k"].dtype),
        "router_dtype": str(params["layers"]["conv"]["router"].dtype),
        "experts_held": f"{first}-{first + held - 1} of {config.n_experts}",
        "page_leaves": sorted(k for k in pool if k in ("k", "v", "ik", "lat")),
        # how a page's rows lie: two heads of 64 to a lane row
        "page_row": "x".join(map(str, pool["k"].shape[2:])),
        # what a slot keeps beside its pages: the tails and nothing else
        "state_leaves": {k: str(v.dtype) for k, v in sorted(pool["rec"].items())},
    }


def expected_kernels(engine) -> dict:
    """`attention_paths()` entry -> what must have been traced there: the
    paged decode read and the step's pool write at two heads of 64 a lane row,
    the admit group's prefill at EVERY bucket (a bucket of 64 too) and its
    insert by whole pages; the conv mixer, products and a convolution in XLA,
    by its scope. The grouped expert product has no entry: its gate is the
    same backend test, and the traced run's
    `moe1536_grouped_matmul_roofline.drain` reads nothing without it."""
    pool = engine._pagepool
    return {
        f"paged-decode[s=1,t={pool.table_len * pool.page_size}]": "ragged_paged_decode_attention",
        "paged-decode-write[s=1]": "paged_kv_write",
        "short-conv[s=1,t=0]": "short_conv",
        **{
            f"prefill[s={w},t={w}]": "flash_prefill_attention" for w in engine.prefill_buckets
        },
        **{f"paged-insert[w={w}]": "paged_insert_pages" for w in engine.prefill_buckets},
    }


def state_leaves(engine):
    return engine.params, engine._pagepool.dev

"""The Command A+ family (`model_type: cohere2_moe`): a period of three
sliding-window layers and one full-attention layer, each a PARALLEL block
(one LayerNorm, attention and a mixture of experts side by side), a sigmoid
router over 128 experts of which a chip holds a share, four averaged shared
experts, a tied output head.

The program serves it through `ModelConfig.layer_pattern` (one stack of
weights a kind, `params["layers"][kind]`), `sliding_window`, `experts_held`
and two PAGE GROUPS: the full layers' pages as every model's, the window
layers' in a group of their own (`engine._pagepool.window`, `dev["win"]`)
where a row holds a ring of its last `sliding_window` tokens and the dispatch
in flight. The check's chain runs the program's own `_parallel_layer` one
layer at a time over the whole width (the window layers through the blocked
segment kernel, the full layers through the prefill kernel). Its hot path is
the cell's: a prompt longer than the largest bucket goes in SEGMENTS of that
width straight into both groups' pages (`paged_prefill_segment_inplace`), the
window group's table advanced before each as the engine advances it
(`WindowPageGroup.advance`: the pages behind the window are mapped again
ahead), then one `paged_decode_step_inplace` a token with the batch the
engine's slots, one live and the others idle.

Where the harness reaches into the program for this family: the private model
functions `_embed`, `_parallel_layer`, `_rope_freqs`, `_unembed`, the public
`paged_prefill_segment_inplace`, `paged_decode_step_inplace`, `make_page_pool`,
`serving.pagepool.WindowPageGroup`, and `engine._pagepool`.

Seeded weights: matrices N(0, 1 / fan_in) like the other families, the four
attention projections, the held experts and the shared experts then int8 per
output channel; the router float32 (routing is in float32); norms ones; the
embedding (the held slice of the vocabulary, tied head) in the model's dtype.
The shared experts are served side by side, `ws_gate` / `ws_up`
[d, n_shared * f] and `ws_down` [n_shared * f, d] (each row block N(0, 1 / f),
an expert's own fan-in): their sum is one SwiGLU of that width.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from langstream_tpu.models.configs import ModelConfig
from langstream_tpu.models.quant import quantize_weight
from modelcfg import refuse_unmapped
from weights import normal, quantized_stack

PERIOD = ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention")
FIELDS = {
    "vocab_size": "vocab_size",
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "layer_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_seq_len",
    "hidden_act": "activation",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "sliding_window": "sliding_window",
    "num_experts_per_tok": "n_experts_per_tok",
    "num_shared_experts": "n_shared_experts",
    "expert_selection_fn": "moe_scoring",
}
# keys this file reads itself (or holds to the one value the block has)
MAPPED_HERE = (
    "model_type", "layer_types", "layer_switch", "order_of_interleaved_layers",
    "rope_parameters", "rotary_pct", "position_embedding_type", "attention_bias",
    "rms_norm_eps", "logit_scale", "norm_topk_prob", "num_experts", "first_k_dense_replace",
    "prefix_dense_intermediate_size", "prefix_dense_sliding_window_pattern",
    "shared_expert_combination_strategy", "tf_legacy_loss", "use_embedding_sharing",
    "use_gated_activation", "use_parallel_block", "use_parallel_embedding", "use_qk_norm",
)
# what the block is: a file that says otherwise is another model
THE_BLOCK = {
    "model_type": "cohere2_moe", "layer_switch": 4,
    "order_of_interleaved_layers": "local_attn_first", "rotary_pct": 1,
    "position_embedding_type": "rope_gptj", "attention_bias": False, "rms_norm_eps": None,
    "norm_topk_prob": True, "first_k_dense_replace": 0,
    "shared_expert_combination_strategy": "average", "use_embedding_sharing": True,
    "use_gated_activation": True, "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "tie_word_embeddings": True, "expert_selection_fn": "sigmoid",
}
QUANTIZED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down")


def place(index: int) -> tuple[str, int]:
    """Layer `index` of the model: its kind and its place in that kind's stack."""
    period, at = divmod(index, len(PERIOD))
    kind = PERIOD[at]
    return kind, period * PERIOD.count(kind) + PERIOD[:at].count(kind)


def _held(spec: dict) -> tuple[int, int]:
    """(first, count) of the experts held: `num_experts` is the count held here
    (`reduced`); the published count and the first are the deployment's."""
    share = spec["deployment"]["experts"]
    return int(share["first_held"]), int(spec["num_experts"])


def model_config(spec: dict, name: str) -> ModelConfig:
    refuse_unmapped(spec, [*FIELDS, *MAPPED_HERE], name)
    n = spec["num_hidden_layers"]
    if spec["layer_types"] != [PERIOD[i % len(PERIOD)] for i in range(n)] or n % len(PERIOD):
        raise ValueError(f"{name}: layer_types is not {n} layers of the period {PERIOD}")
    wrong = {k: spec.get(k) for k, v in THE_BLOCK.items() if spec.get(k) != v}
    if wrong:
        raise ValueError(f"{name}: not the family's block: {wrong}")
    if spec["rope_parameters"] != {"rope_theta": spec["rope_theta"], "rope_type": "default"}:
        raise ValueError(f"{name}: rope_parameters {spec['rope_parameters']}")
    first, held = _held(spec)
    made = {ours: spec[theirs] for theirs, ours in FIELDS.items()}
    return ModelConfig(
        name=name, layer_pattern=PERIOD, rope_interleaved=True, norm="layer",
        logit_scale=float(spec["logit_scale"]),
        n_experts=int(spec["deployment"]["experts"]["published"]), experts_held=(first, held),
        **made,
    )


def reference_dims(spec: dict) -> dict:
    return {
        "n_heads": spec["num_attention_heads"], "n_kv_heads": spec["num_key_value_heads"],
        "head_dim": spec["head_dim"], "rope_theta": float(spec["rope_theta"]),
        "sliding_window": int(spec["sliding_window"]), "eps": float(spec["layer_norm_eps"]),
        "top_k": int(spec["num_experts_per_tok"]), "n_shared": int(spec["num_shared_experts"]),
        "experts_held": _held(spec), "logit_scale": float(spec["logit_scale"]),
    }


def _dims_of(config: ModelConfig) -> dict:
    """`reference_dims`, read back from the program's config."""
    return {
        "n_heads": config.n_heads, "n_kv_heads": config.n_kv_heads,
        "head_dim": config.resolved_head_dim, "rope_theta": float(config.rope_theta),
        "sliding_window": config.sliding_window, "eps": float(config.rms_norm_eps),
        "top_k": config.n_experts_per_tok, "n_shared": config.n_shared_experts,
        "experts_held": tuple(config.held_experts), "logit_scale": float(config.logit_scale),
    }


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, f, v = config.d_model, config.expert_d_ff, config.vocab_size
    h, hkv, hd = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    held, ns = config.held_experts[1], config.n_shared_experts
    dtype = jnp.dtype(config.dtype)
    stack = functools.partial(quantized_stack, dtype=dtype)

    def shared_down(key, n):
        # [n_shared * f, d], each expert's rows N(0, 1 / f): its own fan-in
        return lax.map(
            lambda k: quantize_weight(normal(k, (ns * f, d), f, dtype)), jax.random.split(key, n)
        )

    def layers(key, n):
        keys = iter(jax.random.split(key, 12))
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq": stack(next(keys), (n,), d, h * hd),
            "wk": stack(next(keys), (n,), d, hkv * hd),
            "wv": stack(next(keys), (n,), d, hkv * hd),
            "wo": stack(next(keys), (n,), h * hd, d),
            "router": normal(next(keys), (n, d, config.n_experts), d, jnp.float32),
            "w_gate": stack(next(keys), (n, held), d, f),
            "w_up": stack(next(keys), (n, held), d, f),
            "w_down": stack(next(keys), (n, held), f, d),
            "ws_gate": stack(next(keys), (n,), d, ns * f),
            "ws_up": stack(next(keys), (n,), d, ns * f),
            "ws_down": shared_down(next(keys), n),
        }

    embed_key, win_key, full_key = jax.random.split(key, 3)
    return {
        "embed": normal(embed_key, (v, d), d, dtype),
        "layers": {
            "sliding_attention": layers(win_key, config.n_layers_of("sliding_attention")),
            "full_attention": layers(full_key, config.n_layers_of("full_attention")),
        },
        "final_norm": jnp.ones((d,), dtype),
    }


def make_params(config: ModelConfig, seed: int):
    return _make(jax.random.PRNGKey(seed), config)


def ref_layer_params(ref_params, index: int):
    kind, at = place(index)
    return {kind: ref_params["layers"][kind]}, at


def _take(stack, index):
    return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False), stack)


def system_chain(config: ModelConfig, width: int, rows: int) -> SimpleNamespace:
    """The body of transformer.forward, one layer at a time: each kind a
    compiled layer of its own, over the whole width from position 0."""
    from langstream_tpu.models import transformer as program

    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))

    @jax.jit
    def sys_embed(params, tokens):
        group = jnp.zeros((rows, width), jnp.int32).at[0].set(tokens)
        return program._embed(params, group, config)

    def layer_of(kind):
        @jax.jit
        def run(stack, index, x):
            sin, cos = program._rope_freqs(positions, config)
            y, _, _ = program._parallel_layer(
                x, _take(stack, index), kind, sin, cos, config, positions, None, None,
                {"from_zero": True},
            )
            return y

        return run

    layers = {kind: layer_of(kind) for kind in set(PERIOD)}

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
    engine calls them for this cell's traffic, with its config, page size,
    segment width and slot count, on a page pool of this check's own with the
    engine's ring: the prompt in segments of the largest bucket into both
    page groups, the window group's table advanced before every dispatch by
    the program's own `WindowPageGroup` (so a prompt past the ring recycles
    here as it does in the engine), then one decode step a token, row 0 the
    sequence and the other rows idle."""

    def __init__(self, engine, width: int, rows: int, new_tokens: int) -> None:
        from langstream_tpu.models import transformer as program

        config, pool = engine.config, engine._pagepool
        self.page_size = page_size = pool.page_size
        self.segment = segment = engine.prefill_buckets[-1]
        self.slots = slots = engine.max_batch
        self.n_pages = n_pages = -(-width // page_size)
        self.ring, self.window = pool.window.ring, pool.window.window
        self.recycled = 0  # by the last `logits` call: the check's evidence
        n_window = min(self.ring, n_pages)

        @jax.jit
        def fresh():
            return program.make_page_pool(config, n_pages, page_size, window_pages=n_window)

        @functools.partial(jax.jit, donate_argnames=("pool",))
        def prefill_segment(params, tokens, offset, length, pool, tables):
            return program.paged_prefill_segment_inplace(
                params, tokens[None], offset[None], length[None], pool, tables, config,
                page_size,
            )

        @functools.partial(jax.jit, donate_argnames=("pool",))
        def decode(params, token, position, pool, tables):
            tokens = jnp.zeros((slots,), jnp.int32).at[0].set(token)
            positions = jnp.zeros((slots,), jnp.int32).at[0].set(position)
            logits, pool = program.paged_decode_step_inplace(
                params, tokens, positions, pool, tables, config, page_size
            )
            return logits[0], pool

        self._fns = (fresh, prefill_segment, decode)

    def _tables(self, group, rows: int):
        """[2, rows, Tp]: row 0 the sequence's, the others all sentinel."""
        full = np.full((rows, self.n_pages), self.n_pages, np.int32)
        full[0] = np.arange(self.n_pages)
        win = np.full((rows, self.n_pages), group.oob, np.int32)
        win[0] = group.tables[0]
        return jnp.asarray(np.stack([full, win]))

    def logits(self, params, prompt: list[int], generated: list[int]):
        """[len(generated), V]: row j is the distribution generated token j
        was drawn from, token j - 1 having gone through both page groups."""
        from langstream_tpu.serving.pagepool import WindowPageGroup

        fresh, prefill_segment, decode = self._fns
        n, seg = len(prompt), self.segment
        group = WindowPageGroup(
            min(self.ring, self.n_pages), self.page_size, 1, self.n_pages, self.window, self.ring
        )
        group.reserve(0, -(-(n + len(generated)) // self.page_size))
        pool = fresh()
        for s0 in range(0, n, seg):
            part = prompt[s0 : s0 + seg]
            group.advance(0, s0, s0 + seg - 1)
            tokens = jnp.asarray(part + [0] * (seg - len(part)), jnp.int32)
            first, pool = prefill_segment(
                params, tokens, jnp.int32(s0), jnp.int32(len(part)), pool, self._tables(group, 1)
            )
        rows = [first[0]]
        for j, token in enumerate(generated[:-1]):
            group.advance(0, n + j, n + j)
            step, pool = decode(
                params, jnp.int32(token), jnp.int32(n + j), pool, self._tables(group, self.slots)
            )
            rows.append(step)
        self.recycled = group.recycled_total
        return jnp.stack(rows).astype(jnp.float32)


def engine_state(engine) -> dict:
    from langstream_tpu.models.quant import is_quantized

    int8 = all(
        is_quantized(stack[k]) and stack[k]["q"].dtype == np.int8
        for stack in engine.params["layers"].values() for k in QUANTIZED
    )
    pool, config = engine._pagepool, engine.config
    first, held = config.held_experts
    return {
        "weights": "int8" if int8 else "unquantized",
        "kv_dtype": str(pool.dev["k"].dtype),
        "window_kv_dtype": str(pool.dev["win"]["k"].dtype),
        "router_dtype": str(engine.params["layers"]["full_attention"]["router"].dtype),
        # [layers, pages] of each page group, and the window group's ring
        "page_groups": {
            "full": [int(pool.dev["k"].shape[0]), pool.num_pages],
            "window": [int(pool.dev["win"]["k"].shape[0]), pool.window.num_pages],
            "window_ring_pages": pool.window.ring,
        },
        "experts_held": f"{first}-{first + held - 1} of {config.n_experts}",
    }


def expected_kernels(engine) -> dict:
    """`attention_paths()` entry -> the Pallas kernel that must have been
    traced there (`paged_kv_write` rides the paged decode's entry: the same
    gate admits both; the grouped expert product has no entry: its gate is
    the same backend test, and the traced run's `moe_grouped_matmul_roofline`
    reads nothing without it)."""
    pool = engine._pagepool
    t = pool.table_len * pool.page_size
    return {
        f"paged-decode[s=1,t={t}]": "ragged_paged_decode_attention",
        f"paged-segment[s={engine.prefill_buckets[-1]},t={t}]": "flash_segment_attention",
        **{
            f"prefill[s={w},t={w}]": "flash_prefill_attention"
            for w in engine.prefill_buckets if w % 128 == 0
        },
    }


def state_leaves(engine):
    return engine.params, engine._pagepool.dev

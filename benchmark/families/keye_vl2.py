"""The Keye-VL-2.0 family's language model (`model_type: KeyeVL2`,
Kwai-Keye/Keye-VL-2.0-30B-A3B), text only: the program's sequential pre-norm
block with an RMSNorm of q and k over each head, rotary in three position
streams (text feeds them equal), an INDEXER that scores every cached token
and lets a query attend to the `sa_config.topk` it ranks highest, 128
softmax-routed experts top-8 of a width apart from the dense one, none dropped
(`moe_ffn_held` over all of them) and an untied head.
Equations: `reference/keye_vl2.py`'s docstring.

Where the harness reaches into the program for this family: the private model
functions `_embed`, `_attention_block`, `_ffn_half`, `_rope_freqs`, `_unembed`
(the check's chain, half a layer a step, which hands the experts' stacks on
whole as the program's own layer scans do), the public `prefill`,
`paged_insert_cache`, `paged_prefill_segment_inplace`,
`paged_decode_step_inplace` (its hot path: the functions the engine's admit
group, `_paged_segment_and_sample` and `_paged_decode_chunk` are made of), and
`engine._pagepool`, `engine.max_batch`, `engine.prefill_batch`,
`engine.prefill_buckets`.
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

# every key of the published config.json, and what it has to say for the
# program's block to be the model's
PUBLISHED = (
    "attention_bias", "decoder_sparse_step", "head_dim", "hidden_act", "hidden_size",
    "intermediate_size", "max_position_embeddings", "max_window_layers", "mlp_only_layers",
    "model_type", "moe_intermediate_size", "norm_topk_prob", "num_attention_heads",
    "num_experts", "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "num_local_experts", "rms_norm_eps", "rope_scaling", "rope_theta", "sa_config",
    "sliding_window", "tie_word_embeddings", "use_sliding_window", "vocab_size",
)
_HAS_TO_SAY = {
    "model_type": "KeyeVL2", "attention_bias": False, "hidden_act": "silu",
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "sliding_window": None, "use_sliding_window": False, "tie_word_embeddings": False,
}
# the nested groups, key by key: one the block cannot express is refused by name
_SA_KEYS = {
    "indexer_head_dim", "indexer_num_heads", "indexer_num_kv_heads", "kv_chunk_size",
    "q_chunk_size", "topk",
}
_ROPE_KEYS = {"mrope_section", "rope_type", "type"}
QUANTIZED = ("wq", "wk", "wv", "wo", "wq_idx", "wk_idx", "w_gate", "w_up", "w_down")
EXPERTS = ("w_gate", "w_up", "w_down")


def model_config(spec: dict, name: str) -> ModelConfig:
    refuse_unmapped(spec, PUBLISHED, name)
    sa, rope = spec["sa_config"], spec["rope_scaling"]
    differs = {k: spec.get(k) for k, v in _HAS_TO_SAY.items() if spec.get(k) != v}
    if set(sa) != _SA_KEYS or set(rope) != _ROPE_KEYS:
        differs["sa_config / rope_scaling keys"] = sorted(
            (set(sa) ^ _SA_KEYS) | (set(rope) ^ _ROPE_KEYS)
        )
    if sa.get("indexer_num_kv_heads") != 1:
        differs["sa_config.indexer_num_kv_heads"] = sa.get("indexer_num_kv_heads")
    if {rope.get("rope_type"), rope.get("type")} != {"default"}:
        differs["rope_scaling.rope_type"] = [rope.get("rope_type"), rope.get("type")]
    if spec["num_local_experts"] != spec["num_experts"]:
        differs["num_local_experts"] = spec["num_local_experts"]
    if differs:
        raise ValueError(f"{name}: the program's sparse-attention model cannot express {differs}")
    return ModelConfig(
        name=name, vocab_size=spec["vocab_size"], d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"], n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"], head_dim=spec["head_dim"],
        # read by no layer (every layer is sparse): kept as published
        d_ff=spec["intermediate_size"], moe_d_ff=spec["moe_intermediate_size"],
        rope_theta=float(spec["rope_theta"]), rms_norm_eps=float(spec["rms_norm_eps"]),
        max_seq_len=spec["max_position_embeddings"], activation=spec["hidden_act"],
        n_experts=spec["num_experts"], n_experts_per_tok=spec["num_experts_per_tok"],
        experts_held=(0, spec["num_experts"]), qk_norm_heads=True,
        index_n_heads=sa["indexer_num_heads"], index_head_dim=sa["indexer_head_dim"],
        # q_chunk_size and kv_chunk_size are the tiles the scores are computed
        # in (ops/attention.index_scores fits 512 to the shapes) and change no
        # result: `assumed`
        index_topk=sa["topk"], mrope_section=tuple(rope["mrope_section"]),
    )


def reference_dims(spec: dict) -> dict:
    sa = spec["sa_config"]
    return {
        "n_heads": spec["num_attention_heads"], "n_kv_heads": spec["num_key_value_heads"],
        "head_dim": spec["head_dim"], "rope_theta": float(spec["rope_theta"]),
        "eps": float(spec["rms_norm_eps"]), "top_k": spec["num_experts_per_tok"],
        "n_experts": spec["num_experts"], "index_n_heads": sa["indexer_num_heads"],
        "index_head_dim": sa["indexer_head_dim"], "index_topk": sa["topk"],
        "mrope_section": list(spec["rope_scaling"]["mrope_section"]),
        # under this gap between a query's topk-th and next score the check
        # counts the query tie-exposed (`reference/keye_vl2.py`); 0: none is
        "eps_select": float(spec.get("check", {}).get("eps_select", 0.0)),
    }


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, h, hkv, hd = config.d_model, config.n_heads, config.n_kv_heads, config.resolved_head_dim
    f, n, v, e = config.expert_d_ff, config.n_layers, config.vocab_size, config.n_experts
    hi, di = config.index_n_heads, config.index_head_dim
    dtype = jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 13))
    stack = functools.partial(quantized_stack, dtype=dtype)
    return {
        "embed": normal(next(keys), (v, d), d, dtype),
        "layers": {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq": stack(next(keys), (n,), d, h * hd),
            "wk": stack(next(keys), (n,), d, hkv * hd),
            "wv": stack(next(keys), (n,), d, hkv * hd),
            "wo": stack(next(keys), (n,), h * hd, d),
            "q_norm": jnp.ones((n, hd), dtype),
            "k_norm": jnp.ones((n, hd), dtype),
            # the indexer: its two projections int8 like every projection,
            # the heads' weights float32 like a router, its key's LayerNorm
            "wq_idx": stack(next(keys), (n,), d, hi * di),
            "wk_idx": stack(next(keys), (n,), d, di),
            "w_idx": normal(next(keys), (n, d, hi), d, jnp.float32),
            "idx_norm": jnp.ones((n, di), dtype),
            "idx_bias": jnp.zeros((n, di), dtype),
            "ffn_norm": jnp.ones((n, d), dtype),
            # float32: the router scores in float32 at the highest precision
            "router": normal(next(keys), (n, d, e), d, jnp.float32),
            "w_gate": stack(next(keys), (n, e), d, f),
            "w_up": stack(next(keys), (n, e), d, f),
            "w_down": stack(next(keys), (n, e), f, d),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stack(next(keys), (), d, v),
    }


def make_params(config: ModelConfig, seed: int):
    return _make(jax.random.PRNGKey(seed), config)


def system_chain(config: ModelConfig, width: int, rows: int) -> SimpleNamespace:
    """The body of `transformer.forward` over a pass's whole sequence, one
    HALF of a layer at a time: the two calls `_layer_counted` is made of,
    `_attention_block` (at this width the indexer's scores in tiles, the
    ranking by counting and the segment walk under the selection, from
    offset 0) then `_ffn_half`, so the chain has two steps a layer and the
    reference is handed the program's input to each: the router reads the
    attention half's output, bf16 here and float32 in a reference that is fed
    the layer's input (PERF.md section 6, PR 41). The experts' stacks go on
    whole with the layer's index, as the program's layer scans hand them on
    (`_split_held`)."""
    from langstream_tpu.models import transformer as program

    if rows != 1:
        raise ValueError("this chain takes one row: no expert is dropped")
    positions = jnp.arange(width)[None]

    def of_layer(layers, index):
        return {
            key: leaf if key in EXPERTS else jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False), leaf)
            for key, leaf in layers.items()
        }

    @jax.jit
    def sys_embed(params, tokens):
        return program._embed(params, tokens[None], config)

    @jax.jit
    def sys_attention(layers, index, x):
        sin, cos = program._rope_freqs(positions, config)
        mask = jnp.tril(jnp.ones((width, width), jnp.bool_))[None]
        return program._attention_block(x, of_layer(layers, index), sin, cos, mask, config)[0]

    @jax.jit
    def sys_experts(layers, index, x):
        return program._ffn_half(x, of_layer(layers, index), config, layer=index)[0]

    @jax.jit
    def sys_unembed(params, x):
        return program._unembed(params, x, config)[0]

    halves = (sys_attention, sys_experts)
    return SimpleNamespace(
        embed=sys_embed,
        layer=lambda params, step, x: halves[step % 2](params["layers"], step // 2, x),
        unembed=sys_unembed,
        n_layers=2 * config.n_layers,
    )


ATTENTION_HALF = (
    "attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
    "wq_idx", "wk_idx", "w_idx", "idx_norm", "idx_bias",
)
EXPERT_HALF = ("ffn_norm", "router", *EXPERTS)


def ref_layer_params(ref_params, step: int):
    """The leaves of the half a chain step runs (`reference.layer` runs the
    half it is handed), every layer's stacked, and the layer's place there."""
    layers = ref_params["layers"]
    return {k: layers[k] for k in (ATTENTION_HALF, EXPERT_HALF)[step % 2]}, step // 2


class hot_path:
    """The model functions the engine's programs are made of, called as the
    engine calls them for this cell's traffic, with its config (so its
    kernels), its page size, its pool's dtypes (the indexer's keys in the
    third leaf), its segment width and its slot count, on a page pool of this
    check's own. A prompt inside the largest bucket goes through `prefill` at
    that bucket into a local cache and `paged_insert_cache` (the admit group,
    row 0 of the engine's group); a longer one in segments of the largest
    bucket through `paged_prefill_segment_inplace`, each segment ranking the
    columns earlier segments wrote; then one decode step a token, row 0 the
    sequence and the other rows idle: every step past `index_topk` writes its
    indexer key, scores the row's pages, ranks and reads the selected tokens
    alone."""

    def __init__(self, engine, width: int, rows: int, new_tokens: int) -> None:
        from langstream_tpu.models import transformer as program

        config, pool = engine.config, engine._pagepool
        self.segment = segment = engine.prefill_buckets[-1]
        page_size, slots, group = pool.page_size, engine.max_batch, engine.prefill_batch
        n_pages = -(-width // page_size)
        kept = pool.dev["k"].dtype
        # row 0 the sequence's pages, every other row's table all out of bounds
        row0 = lambda n: jnp.full((n, n_pages), n_pages, jnp.int32).at[0].set(jnp.arange(n_pages))  # noqa: E731
        tables, group_tables = row0(slots), row0(group)

        @jax.jit
        def fresh():
            return program.make_page_pool(config, n_pages, page_size, dtype=kept)

        @jax.jit
        def prefill_group(params, tokens, length):
            rows_tokens = jnp.zeros((group, segment), jnp.int32).at[0].set(tokens)
            lengths = jnp.ones((group,), jnp.int32).at[0].set(length)
            logits, local = program.prefill(
                params, rows_tokens, lengths, program.make_kv_cache(config, group, segment),
                config,
            )
            return logits[0], program.paged_insert_cache(
                fresh(), local, group_tables, page_size, config
            )

        @functools.partial(jax.jit, donate_argnames=("pool",))
        def prefill_segment(params, tokens, offset, length, pool):
            logits, pool = program.paged_prefill_segment_inplace(
                params, tokens[None], offset[None], length[None], pool, row0(1), config,
                page_size,
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
        was drawn from, token j - 1 having gone through the page pool."""
        fresh, prefill_group, prefill_segment, decode = self._fns
        n, seg = len(prompt), self.segment
        padded = lambda part: jnp.asarray(part + [0] * (seg - len(part)), jnp.int32)  # noqa: E731
        if n <= seg:
            first, pool = prefill_group(params, padded(list(prompt)), jnp.int32(n))
        else:
            pool = fresh()
            for s0 in range(0, n, seg):
                part = list(prompt[s0 : s0 + seg])
                first, pool = prefill_segment(
                    params, padded(part), jnp.int32(s0), jnp.int32(len(part)), pool
                )
        rows = [first]
        for j, token in enumerate(generated[:-1]):
            step, pool = decode(params, jnp.int32(token), jnp.int32(n + j), pool)
            rows.append(step)
        return jnp.stack(rows).astype(jnp.float32)


def engine_state(engine) -> dict:
    from langstream_tpu.models.quant import is_quantized

    layers, pool = engine.params["layers"], engine._pagepool.dev
    int8 = all(is_quantized(layers[k]) and layers[k]["q"].dtype == np.int8 for k in QUANTIZED)
    return {
        "weights": "int8" if int8 else "unquantized",
        "kv_dtype": str(pool["k"].dtype),
        "router_dtype": str(layers["router"].dtype),
        "index_key_dtype": str(pool["ik"].dtype),
        "index_weight_dtype": str(layers["w_idx"].dtype),
    }


def expected_kernels(engine) -> dict:
    """`attention_paths()` entry -> what must have been traced there. The
    decode step's entry names both of its reads (`paged_kv_write` rides it:
    the same gate admits both); the segment's two entries are the two
    branches of one program, up to `index_topk` keys and past them; the
    grouped expert product has no entry: its gate is the same backend test,
    and the traced run's `moe768_grouped_matmul_roofline` reads nothing
    without it."""
    pool, seg = engine._pagepool, engine.prefill_buckets[-1]
    t = pool.table_len * pool.page_size
    return {
        f"paged-decode-sparse[s=1,t={t}]":
            "ragged_paged_decode_attention to index_topk, xla top_k + gather past it",
        f"paged-segment[s={seg},t={t}]": "flash_segment_attention",
        f"paged-segment-sparse[s={seg},t={t}]": "sparse_segment_attention",
        **{
            f"prefill[s={w},t={w}]": "flash_prefill_attention"
            for w in engine.prefill_buckets if w % 128 == 0
        },
    }


def state_leaves(engine):
    return engine.params, engine._pagepool.dev

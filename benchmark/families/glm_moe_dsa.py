"""The GLM-5 family (`model_type: glm_moe_dsa`, zai-org/GLM-5): latent
attention (a query latent, ONE key-value latent and one rotary key a token for
64 heads) under a learned selection (an indexer that reads the query latent
and keeps `index_topk` tokens), leading dense layers, then expert layers whose
sigmoid router chooses 8 of 256 under a bias and weighs by the scores alone
times `routed_scaling_factor`, one shared expert added whole, an untied head.
Equations: `reference/glm_moe_dsa.py`'s docstring.

The program serves it through `ModelConfig.kv_lora_rank` and its four
companions (the page pool's leaves are the latent `"lat"` and the indexer's
key `"ik"`: no K, no V), `n_leading_dense` (a stack of its own,
`params["dense_layers"]`, run before the expert layers' scan), `experts_held`,
`router_bias` and `routed_scaling`. A chip holds a SHARE of a layer's routed
experts (`deployment.experts`) and a slice of the vocabulary; attention,
indexer, router, norms and the shared expert are whole.

Where the harness reaches into the program for this family: the private model
functions `_embed`, `_attention_block`, `_ffn_half`, `_rope_freqs`, `_unembed`
(the check's chain, HALF a layer a step: the expanded form over the whole
width, the selection's kernels from offset 0), the public `prefill`,
`paged_insert_cache`, `paged_prefill_segment_inplace`,
`paged_decode_step_inplace`, `make_page_pool`, `make_kv_cache` (its hot path:
the functions the engine's admit group, `_paged_segment_and_sample` and
`_paged_decode_chunk` are made of; the decode steps attend in the latent
space), and `engine._pagepool`, `engine.max_batch`, `engine.prefill_batch`,
`engine.prefill_buckets`.

Seeded weights: matrices N(0, 1 / fan_in) then int8 per output channel (the
two down-projections, the two up-projections, `wo`, the indexer's two, the
dense FFN, the held experts, the shared expert, the head); the router and the
indexer's head weights float32; norms ones, the indexer's LayerNorm bias zero;
the embedding (the held slice of the vocabulary) in the model's dtype; the
router's bias N(0, `BIAS_SIGMA`^2) float32, NOT zero: a trained model's
balances its experts' load, and a zero bias would leave the rule that it
chooses and does not weigh untested (the configuration's `weights.why` has the
share of tokens whose choice it moves).
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
    "attention_bias", "ep_size", "first_k_dense_replace", "hidden_act", "head_dim",
    "hidden_size", "index_head_dim", "index_n_heads", "index_topk",
    "indexer_rope_interleave", "intermediate_size", "kv_lora_rank",
    "max_position_embeddings", "moe_intermediate_size", "moe_layer_freq", "model_type",
    "n_group", "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "num_nextn_predict_layers", "q_lora_rank", "qk_head_dim",
    "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_interleave",
    "rope_parameters", "routed_scaling_factor", "scoring_func", "tie_word_embeddings",
    "topk_group", "topk_method", "v_head_dim", "vocab_size",
)
_HAS_TO_SAY = {
    "model_type": "glm_moe_dsa", "attention_bias": False, "hidden_act": "silu",
    "norm_topk_prob": True, "rope_interleave": True, "indexer_rope_interleave": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "ep_size": 1, "moe_layer_freq": 1, "tie_word_embeddings": False,
    # left out, and said so under `assumed`: the model's own logits do not
    # pass through the next-token module
    "num_nextn_predict_layers": 1,
}
_ROPE_KEYS = {"rope_theta", "rope_type"}
ATTENTION_HALF = (
    "attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo",
    "wq_idx", "wk_idx", "w_idx", "idx_norm", "idx_bias",
)
EXPERTS = ("w_gate", "w_up", "w_down")
SHARED = ("ws_gate", "ws_up", "ws_down")
DENSE_HALF = ("ffn_norm", *EXPERTS)
EXPERT_HALF = ("ffn_norm", "router", "router_bias", *EXPERTS, *SHARED)
QUANTIZED = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "wq_idx", "wk_idx", *EXPERTS)
# the seeded router bias's spread: configs/glm-5-int8-ep16-d7.json `weights.why`
BIAS_SIGMA = 0.0012


def _held(spec: dict) -> tuple[int, int]:
    """(first, count) of the routed experts held: `n_routed_experts` is the
    count held here (`reduced`); the published count and the first are the
    deployment's."""
    return int(spec["deployment"]["experts"]["first_held"]), int(spec["n_routed_experts"])


def model_config(spec: dict, name: str) -> ModelConfig:
    refuse_unmapped(spec, PUBLISHED, name)
    rope = spec["rope_parameters"]
    differs = {k: spec.get(k) for k, v in _HAS_TO_SAY.items() if spec.get(k) != v}
    if set(rope) != _ROPE_KEYS or rope.get("rope_type") != "default":
        differs["rope_parameters"] = rope
    if spec["head_dim"] != spec["qk_rope_head_dim"]:  # the rotary width, this family's convention
        differs["head_dim"] = spec["head_dim"]
    if spec["qk_head_dim"] != spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]:
        differs["qk_head_dim"] = spec["qk_head_dim"]
    if spec["num_key_value_heads"] != spec["num_attention_heads"]:  # the expanded form's
        differs["num_key_value_heads"] = spec["num_key_value_heads"]
    if differs:
        raise ValueError(f"{name}: the program's latent-attention model cannot express {differs}")
    first, held = _held(spec)
    return ModelConfig(
        name=name, vocab_size=spec["vocab_size"], d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"], n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"], d_ff=spec["intermediate_size"],
        moe_d_ff=spec["moe_intermediate_size"], rope_theta=float(rope["rope_theta"]),
        rms_norm_eps=float(spec["rms_norm_eps"]), max_seq_len=spec["max_position_embeddings"],
        activation=spec["hidden_act"], rope_interleaved=True,
        n_experts=int(spec["deployment"]["experts"]["published"]), experts_held=(first, held),
        n_experts_per_tok=spec["num_experts_per_tok"], moe_scoring=spec["scoring_func"],
        n_shared_experts=spec["n_shared_experts"], router_bias=True,
        routed_scaling=float(spec["routed_scaling_factor"]),
        n_leading_dense=spec["first_k_dense_replace"],
        q_lora_rank=spec["q_lora_rank"], kv_lora_rank=spec["kv_lora_rank"],
        qk_nope_head_dim=spec["qk_nope_head_dim"], qk_rope_head_dim=spec["qk_rope_head_dim"],
        v_head_dim=spec["v_head_dim"], index_n_heads=spec["index_n_heads"],
        index_head_dim=spec["index_head_dim"], index_topk=spec["index_topk"],
        # the indexer turns the rotary's width of its head, in the rotary's
        # pairs, and reads the query latent: `assumed`
        index_rope_dim=spec["qk_rope_head_dim"], index_query_input="query_latent",
    )


def reference_dims(spec: dict) -> dict:
    return {
        "n_heads": spec["num_attention_heads"], "eps": float(spec["rms_norm_eps"]),
        "rope_theta": float(spec["rope_parameters"]["rope_theta"]),
        "kv_lora_rank": spec["kv_lora_rank"], "qk_nope_head_dim": spec["qk_nope_head_dim"],
        "qk_rope_head_dim": spec["qk_rope_head_dim"], "v_head_dim": spec["v_head_dim"],
        "index_n_heads": spec["index_n_heads"], "index_head_dim": spec["index_head_dim"],
        "index_topk": spec["index_topk"], "index_query_input": "query_latent",
        "top_k": spec["num_experts_per_tok"],
        "n_experts": int(spec["deployment"]["experts"]["published"]),
        "experts_held": _held(spec), "routed_scaling": float(spec["routed_scaling_factor"]),
        # under this gap between a query's topk-th and next score the check
        # counts the query tie-exposed (`reference/glm_moe_dsa.py`); 0: none is
        "eps_select": float(spec.get("check", {}).get("eps_select", 0.0)),
    }


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, h, hd, v = config.d_model, config.n_heads, config.resolved_head_dim, config.vocab_size
    ql, kl = config.q_lora_rank, config.kv_lora_rank
    hi, di, e = config.index_n_heads, config.index_head_dim, config.n_experts
    held, f, ns = config.held_experts[1], config.expert_d_ff, config.n_shared_experts
    dtype = jnp.dtype(config.dtype)
    stack = functools.partial(quantized_stack, dtype=dtype)

    def attention_half(key, n):
        keys = iter(jax.random.split(key, 8))
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq_a": stack(next(keys), (n,), d, ql), "q_a_norm": jnp.ones((n, ql), dtype),
            "wq_b": stack(next(keys), (n,), ql, h * hd),
            "wkv_a": stack(next(keys), (n,), d, config.latent_width),
            "kv_a_norm": jnp.ones((n, kl), dtype),
            "wkv_b": stack(next(keys), (n,), kl, h * (config.qk_nope_head_dim + config.v_head_dim)),
            "wo": stack(next(keys), (n,), h * config.v_head_dim, d),
            # the indexer: its queries from the query latent, its key and its
            # heads' weights from the normed input; the weights float32
            "wq_idx": stack(next(keys), (n,), ql, hi * di),
            "wk_idx": stack(next(keys), (n,), d, di),
            "w_idx": normal(next(keys), (n, d, hi), d, jnp.float32),
            "idx_norm": jnp.ones((n, di), dtype), "idx_bias": jnp.zeros((n, di), dtype),
            "ffn_norm": jnp.ones((n, d), dtype),
        }

    def swiglu(key, lead, width, names):
        keys = jax.random.split(key, 3)
        return {
            names[0]: stack(keys[0], lead, d, width), names[1]: stack(keys[1], lead, d, width),
            names[2]: stack(keys[2], lead, width, d),
        }

    n_dense = config.n_leading_dense
    n_sparse = config.n_layers - n_dense
    keys = iter(jax.random.split(key, 10))
    return {
        "embed": normal(next(keys), (v, d), d, dtype),
        "dense_layers": {
            **attention_half(next(keys), n_dense),
            **swiglu(next(keys), (n_dense,), config.d_ff, EXPERTS),
        },
        "layers": {
            **attention_half(next(keys), n_sparse),
            # float32: the router scores in float32 at the highest precision
            "router": normal(next(keys), (n_sparse, d, e), d, jnp.float32),
            "router_bias": BIAS_SIGMA * jax.random.normal(next(keys), (n_sparse, e), jnp.float32),
            **swiglu(next(keys), (n_sparse, held), f, EXPERTS),
            **swiglu(next(keys), (n_sparse,), ns * f, SHARED),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stack(next(keys), (), d, v),
    }


def make_params(config: ModelConfig, seed: int):
    return _make(jax.random.PRNGKey(seed), config)


def _place(params, step: int) -> tuple[str, str, int, int]:
    """Chain step -> (the stack's key in the tree, its kind's name, the
    layer's place in that stack, which half)."""
    n_dense = jax.tree.leaves(params["dense_layers"])[0].shape[0]
    layer, half = divmod(step, 2)
    if layer < n_dense:
        return "dense_layers", "dense", layer, half
    return "layers", "sparse", layer - n_dense, half


def system_chain(config: ModelConfig, width: int, rows: int) -> SimpleNamespace:
    """The body of `transformer.forward` over a pass's whole sequence, one
    HALF of a layer at a time: the two calls `_layer_counted` is made of,
    `_attention_block` (the EXPANDED form over the tokens' own latents: at
    this width the indexer's scores in tiles, the ranking by counting and the
    segment walk under the selection at 64 heads of 256, from offset 0) then
    `_ffn_half` (the leading dense layer's FFN, or the expert layer with the
    held experts' stacks handed on whole with the layer's index, as the
    program's scan hands them on: `_split_held`), so the chain has two steps a
    layer and the reference is handed the program's input to each: the router
    reads the attention half's output, bf16 here and float32 in a reference
    that is fed the layer's input."""
    from langstream_tpu.models import transformer as program

    if rows != 1:
        raise ValueError("this chain takes one row: no expert is dropped")
    positions = jnp.arange(width)[None]

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

    halves = {"dense": (sys_attention, sys_dense), "sparse": (sys_attention, sys_experts)}

    def sys_layer(params, step, x):
        stack, kind, at, half = _place(params, step)
        return halves[kind][half](params[stack], at, x)

    return SimpleNamespace(
        embed=sys_embed, layer=sys_layer, unembed=sys_unembed, n_layers=2 * config.n_layers
    )


def ref_layer_params(ref_params, step: int):
    """The leaves of the half a chain step runs (`reference.layer` runs the
    half it is handed), its stack's every layer, under its kind's name
    (`dense` | `sparse`: the check compiles one program a kind and half), and
    the layer's place in that stack."""
    stack, kind, at, half = _place(ref_params, step)
    names = (ATTENTION_HALF, DENSE_HALF if kind == "dense" else EXPERT_HALF)[half]
    layers = ref_params[stack]
    return {kind: {k: layers[k] for k in names}}, at


class hot_path:
    """The model functions the engine's programs are made of, called as the
    engine calls them for this cell's traffic, with its config (so its
    kernels), its page size, its pool's dtypes (the latent and the indexer's
    key: no K, no V), its segment width and its slot count, on a page pool of
    this check's own. A prompt inside the largest bucket goes through
    `prefill` at that bucket into a local cache of latents and
    `paged_insert_cache` (the admit group, row 0 of the engine's group); a
    longer one in segments of the largest bucket through
    `paged_prefill_segment_inplace`, each segment re-expanding and ranking the
    columns earlier segments wrote; then one decode step a token, row 0 the
    sequence and the other rows idle: every step writes its latent and its
    indexer key, scores the row's pages, ranks, and attends to the selected
    latents in the ABSORBED form."""

    def __init__(self, engine, width: int, rows: int, new_tokens: int) -> None:
        from langstream_tpu.models import transformer as program

        config, pool = engine.config, engine._pagepool
        self.segment = segment = engine.prefill_buckets[-1]
        page_size, slots, group = pool.page_size, engine.max_batch, engine.prefill_batch
        n_pages = -(-width // page_size)
        kept = pool.dev["lat"].dtype
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

    params, pool, config = engine.params, engine._pagepool.dev, engine.config
    int8 = all(
        is_quantized(params[stack][k]) and params[stack][k]["q"].dtype == np.int8
        for stack in ("dense_layers", "layers") for k in QUANTIZED
    ) and all(is_quantized(params["layers"][k]) for k in SHARED)
    first, held = config.held_experts
    return {
        "weights": "int8" if int8 else "unquantized",
        # what a token's cache is kept in: the latent leaf's dtype
        "kv_dtype": str(pool["lat"].dtype),
        "router_dtype": str(params["layers"]["router"].dtype),
        "index_key_dtype": str(pool["ik"].dtype),
        "experts_held": f"{first}-{first + held - 1} of {config.n_experts}",
        "page_leaves": sorted(k for k in pool if k in ("k", "v", "ik", "lat")),
    }


def expected_kernels(engine) -> dict:
    """`attention_paths()` entry -> what must have been traced there: the
    decode step's read in the latent space; the segment's three entries (the
    two branches of one program, up to `index_topk` keys and past them, and
    the selection's one call); the admit group's prefill at the expanded
    heads. The grouped expert product has no entry: its gate is the same
    backend test, and the traced run's `moe2048_grouped_matmul_roofline` reads
    nothing without it."""
    pool, seg = engine._pagepool, engine.prefill_buckets[-1]
    t = pool.table_len * pool.page_size
    return {
        f"paged-decode-latent[s=1,t={t}]": "ragged_paged_latent_attention",
        f"paged-segment-latent[s={seg},t={t}]": "flash_segment_attention",
        f"paged-segment-latent-select[s={seg},t={t}]": "segment_select",
        f"paged-segment-latent-sparse[s={seg},t={t}]": "sparse_segment_attention",
        **{
            f"prefill[s={w},t={w}]": "flash_prefill_attention"
            for w in engine.prefill_buckets if w % 128 == 0
        },
    }


def state_leaves(engine):
    return engine.params, engine._pagepool.dev

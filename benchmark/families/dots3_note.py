"""The dots3-note family (`model_type: dots3_note`,
dots-studio/dots3-note-prev; the language model alone): TWO kinds of layer,
each keeping a latent of its own geometry. `full_attention` layers: 128 heads
of 128 + 64 (values 128), a key-value latent of 512, rotary base 8e7, read
under a learned selection (an indexer of 64 heads of 128 on the query latent
that keeps `index_topk` tokens). `sliding_attention` layers (`swa_*`): 64
heads of 192 + 64 (values 128), a key-value latent of 1,024, base 5e4, the
last `sliding_window_size` tokens. Both rescale their normed latents
(`apply_mla_qkv_lora_rescale`) and gate each head's output (`headwise`). One
leading dense layer BEFORE the first period, then expert layers whose sigmoid
router chooses 8 of 256 under a bias, one shared expert added whole, an untied
head. Equations: `reference/dots3_note.py`'s docstring.

The program serves it through `ModelConfig.window_attention` (the window
kind's own fields; `of_kind` gives a kind's geometry), `latent_rescale`,
`attn_gate`, a `layer_pattern` of the two kinds in the SEQUENTIAL block, and
GLM-5's fields for everything else. The page pool has two groups: the full
kind's leaves `"lat"` (576 kept at 640) and `"ik"`, and the window kind's ring
under `"win"`, ONE leaf `"lat"` (1,088 kept at 1,152).

Where the harness reaches into the program for this family: the private model
functions `_embed`, `_attention_block`, `_ffn_half`, `_rope_freqs`, `_unembed`
(the check's chain, HALF a layer a step, each attention half at its kind's
`of_kind` view, rotary table and window), the public `prefill`,
`paged_insert_cache`, `paged_prefill_segment_inplace`,
`paged_decode_step_inplace`, `make_page_pool`, `make_kv_cache`, and
`engine._pagepool`, `engine.max_batch`, `engine.prefill_batch`,
`engine.prefill_buckets`.

Seeded weights: as `families/glm_moe_dsa.py` (matrices N(0, 1 / fan_in) then
int8 per output channel, the heads' gate with them; router and the indexer's
head weights float32; the router's bias N(0, `BIAS_SIGMA`^2), NOT zero), but
for the three matrices that READ A RESCALED LATENT (`wq_b`, `wkv_b`, `wq_idx`):
N(0, 1 / hidden_size), the variance a full-width input's matrix would have,
which is what the rescale is for. Drawn at 1 / rank, a query is sqrt(5) and a
key sqrt(10) times GLM-5's, the scores' spread 7 times: a softmax so sharp
that one bf16 rounding of a score moves a head's output by a tenth, and no
two bf16 programs agree (the configuration's `weights.why` has the reading).
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
from langstream_tpu.models.quant import quantize_weight
from weights import normal, quantized_stack

PUBLISHED = (
    "apply_mla_qkv_lora_rescale", "attention_bias", "attention_gate_type",
    "first_k_dense_replace", "hidden_act", "hidden_size", "index_head_dim", "index_n_heads",
    "index_topk", "intermediate_size", "kv_lora_rank", "layer_types",
    "max_position_embeddings", "model_type", "moe_intermediate_size", "moe_layer_freq",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads", "q_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_scaling", "rope_theta",
    "routed_scaling_factor", "scoring_func", "sliding_window_size",
    "swa_attention_gate_type", "swa_kv_lora_rank", "swa_num_attention_heads",
    "swa_num_key_value_heads", "swa_q_lora_rank", "swa_qk_nope_head_dim",
    "swa_qk_rope_head_dim", "swa_rope_theta", "swa_v_head_dim", "tie_word_embeddings",
    "topk_method", "v_head_dim", "vocab_size",
)
_HAS_TO_SAY = {
    "model_type": "dots3_note", "attention_bias": False, "hidden_act": "silu",
    "norm_topk_prob": True, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "moe_layer_freq": 1, "tie_word_embeddings": False, "rope_scaling": None,
    "apply_mla_qkv_lora_rescale": True, "attention_gate_type": "headwise",
    "swa_attention_gate_type": "headwise",
}
FULL, WINDOW = "full_attention", "sliding_attention"
LATENT = ("attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo",
          "w_attn_gate")
INDEXER = ("wq_idx", "wk_idx", "w_idx", "idx_norm", "idx_bias")
EXPERTS = ("w_gate", "w_up", "w_down")
SHARED = ("ws_gate", "ws_up", "ws_down")
DENSE_HALF = ("ffn_norm", *EXPERTS)
EXPERT_HALF = ("ffn_norm", "router", "router_bias", *EXPERTS, *SHARED)
QUANTIZED = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_attn_gate", *EXPERTS)
# the seeded router bias's spread: GLM-5's (configs/glm-5-int8-ep16-d7.json `weights.why`)
BIAS_SIGMA = 0.0012
_SWA = {  # the window kind's published key -> the program's field
    "swa_num_attention_heads": "n_heads", "swa_q_lora_rank": "q_lora_rank",
    "swa_kv_lora_rank": "kv_lora_rank", "swa_qk_nope_head_dim": "qk_nope_head_dim",
    "swa_qk_rope_head_dim": "qk_rope_head_dim", "swa_v_head_dim": "v_head_dim",
    "swa_rope_theta": "rope_theta",
}


def _held(spec: dict) -> tuple[int, int]:
    """(first, count) of the routed experts held (`families/glm_moe_dsa.py`)."""
    return int(spec["deployment"]["experts"]["first_held"]), int(spec["n_routed_experts"])


def _pattern(spec: dict) -> tuple:
    """The period of `layer_types` behind the leading dense layers, or () where
    the list is not `first_k_dense_replace` layers, each of the kind the
    period has at its place, then whole periods."""
    kinds, lead = list(spec["layer_types"]), int(spec["first_k_dense_replace"])
    rest = kinds[lead:]
    for period in range(1, len(rest) + 1):
        pattern = rest[:period]
        if len(rest) % period == 0 and rest == pattern * (len(rest) // period):
            if kinds[:lead] == [pattern[i % period] for i in range(lead)]:
                return tuple(pattern)
            return ()
    return ()


def model_config(spec: dict, name: str) -> ModelConfig:
    refuse_unmapped(spec, PUBLISHED, name)
    differs = {k: spec.get(k) for k, v in _HAS_TO_SAY.items() if spec.get(k) != v}
    for heads in ("num", "swa_num"):  # the expanded form's count, a kind
        if spec[f"{heads}_key_value_heads"] != spec[f"{heads}_attention_heads"]:
            differs[f"{heads}_key_value_heads"] = spec[f"{heads}_key_value_heads"]
    pattern = _pattern(spec)
    if len(spec["layer_types"]) != spec["num_hidden_layers"] or not pattern:
        # (leading dense layers, then whole periods of ONE pattern: the
        # published 46, which end on a full layer behind eleven periods of
        # four, are one period of 45)
        differs["layer_types"] = spec["layer_types"]
    if differs:
        raise ValueError(f"{name}: the program's model of latent kinds cannot express {differs}")
    first, held = _held(spec)
    return ModelConfig(
        name=name, vocab_size=spec["vocab_size"], d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"], n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"], d_ff=spec["intermediate_size"],
        moe_d_ff=spec["moe_intermediate_size"], rope_theta=float(spec["rope_theta"]),
        rms_norm_eps=float(spec["rms_norm_eps"]), max_seq_len=spec["max_position_embeddings"],
        activation=spec["hidden_act"], rope_interleaved=True,  # `assumed`: rotary_pairs
        layer_pattern=pattern, sliding_window=spec["sliding_window_size"],
        window_attention=tuple(
            (field, float(spec[key]) if field == "rope_theta" else spec[key])
            for key, field in _SWA.items()
        ),
        latent_rescale=True, attn_gate="headwise",
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
    """`dims_of` the file's `ModelConfig`, and the check's `eps_select` (under
    this gap between a query's topk-th and next score the check counts the
    query tie-exposed; 0 or absent: none is)."""
    return {
        **dims_of(model_config(spec, str(spec["family"]))),
        "eps_select": float(spec.get("check", {}).get("eps_select", 0.0)),
    }


def dims_of(config: ModelConfig) -> dict:
    """What `reference/dots3_note.py` reads, off a ModelConfig."""
    def kind(of: ModelConfig) -> dict:
        return {k: getattr(of, k) for k in (
            "n_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta",
        )}

    return {
        "eps": config.rms_norm_eps,
        "kinds": {"full": kind(config), "window": kind(config.of_kind(WINDOW))},
        "sliding_window": config.sliding_window, "layer_types": layer_kinds(config),
        "n_leading_dense": config.n_leading_dense,
        "index_n_heads": config.index_n_heads, "index_head_dim": config.index_head_dim,
        "index_topk": config.index_topk, "top_k": config.n_experts_per_tok,
        "n_experts": config.n_experts, "experts_held": config.held_experts,
        "routed_scaling": config.routed_scaling,
    }


def layer_kinds(config: ModelConfig) -> tuple:
    pattern = config.layer_pattern
    lead = [pattern[i % len(pattern)] for i in range(config.dense_ahead)]
    return tuple(lead) + pattern * config.n_periods


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, v, e = config.d_model, config.vocab_size, config.n_experts
    hi, di = config.index_n_heads, config.index_head_dim
    held, f, ns = config.held_experts[1], config.expert_d_ff, config.n_shared_experts
    dtype = jnp.dtype(config.dtype)
    stack = functools.partial(quantized_stack, dtype=dtype)

    def from_rescaled(key, n, fan_in, fan_out):
        """[n, fan_in, fan_out] int8 at N(0, 1 / d): a matrix whose input is a
        latent rescaled by sqrt(d / fan_in)."""
        stacked = lax.map(
            lambda k: quantize_weight(normal(k, (fan_in, fan_out), d, dtype)),
            jax.random.split(key, n),
        )
        return jax.tree.map(lambda a: a.reshape((n,) + a.shape[1:]), stacked)

    def attention_half(key, n, kind):
        of = config.of_kind(kind)
        h, hd, ql, kl = of.n_heads, of.resolved_head_dim, of.q_lora_rank, of.kv_lora_rank
        keys = iter(jax.random.split(key, 10))
        half = {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq_a": stack(next(keys), (n,), d, ql), "q_a_norm": jnp.ones((n, ql), dtype),
            "wq_b": from_rescaled(next(keys), n, ql, h * hd),
            "wkv_a": stack(next(keys), (n,), d, of.latent_width),
            "kv_a_norm": jnp.ones((n, kl), dtype),
            "wkv_b": from_rescaled(
                next(keys), n, kl, h * (of.qk_nope_head_dim + of.v_head_dim)
            ),
            "wo": stack(next(keys), (n,), h * of.v_head_dim, d),
            "w_attn_gate": stack(next(keys), (n,), d, h),
            "ffn_norm": jnp.ones((n, d), dtype),
        }
        if of.has_indexer:  # the full kind's alone
            half.update(
                wq_idx=from_rescaled(next(keys), n, ql, hi * di),
                wk_idx=stack(next(keys), (n,), d, di),
                w_idx=normal(next(keys), (n, d, hi), d, jnp.float32),
                idx_norm=jnp.ones((n, di), dtype), idx_bias=jnp.zeros((n, di), dtype),
            )
        return half

    def swiglu(key, lead, width, names):
        keys = jax.random.split(key, 3)
        return {
            names[0]: stack(keys[0], lead, d, width), names[1]: stack(keys[1], lead, d, width),
            names[2]: stack(keys[2], lead, width, d),
        }

    def expert_half(key, n):
        keys = iter(jax.random.split(key, 4))
        return {
            # float32: the router scores in float32 at the highest precision
            "router": normal(next(keys), (n, d, e), d, jnp.float32),
            "router_bias": BIAS_SIGMA * jax.random.normal(next(keys), (n, e), jnp.float32),
            **swiglu(next(keys), (n, held), f, EXPERTS),
            **swiglu(next(keys), (n,), ns * f, SHARED),
        }

    kinds = [k for k in (FULL, WINDOW) if config.n_layers_of(k)]
    keys = iter(jax.random.split(key, 16))
    return {
        "embed": normal(next(keys), (v, d), d, dtype),
        "dense_layers": {
            kind: {
                **attention_half(next(keys), config.dense_of(kind), kind),
                **swiglu(next(keys), (config.dense_of(kind),), config.d_ff, EXPERTS),
            }
            for kind in kinds if config.dense_of(kind)
        },
        "layers": {
            kind: {
                **attention_half(next(keys), n, kind), **expert_half(next(keys), n),
            }
            for kind in kinds
            for n in [config.n_layers_of(kind) - config.dense_of(kind)]
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": stack(next(keys), (), d, v),
    }


def make_params(config: ModelConfig, seed: int):
    return _make(jax.random.PRNGKey(seed), config)


def _places(config: ModelConfig) -> list:
    """[(stack, kind, the layer's place in that stack, the reference's kind)]
    in the model's order (`reference.layer_places`, from the config)."""
    seen, places = {}, []
    for i, kind in enumerate(layer_kinds(config)):
        dense = i < config.n_leading_dense
        stack = "dense_layers" if dense else "layers"
        at = seen.get((stack, kind), 0)
        seen[stack, kind] = at + 1
        places.append(
            (stack, kind, at, "window" if kind == WINDOW else "full_dense" if dense else "full")
        )
    return places


def system_chain(config: ModelConfig, width: int, rows: int) -> SimpleNamespace:
    """The body of `transformer.forward` over a pass's whole sequence, one
    HALF of a layer at a time, as `families/glm_moe_dsa.py`'s: `_attention_block`
    at the layer's KIND's view (`config.of_kind`), rotary table and window
    (the EXPANDED form over the tokens' own latents: the full kind's
    selection kernels from offset 0, the window kind's segment walk under its
    bound), then `_ffn_half` (the leading dense layer's FFN, or the expert
    layer with the held experts' stacks handed on whole)."""
    from langstream_tpu.models import transformer as program

    if rows != 1:
        raise ValueError("this chain takes one row: no expert is dropped")
    positions = jnp.arange(width)[None]
    places = _places(config)

    def of_layer(layers, index, whole=()):
        return {
            key: leaf if key in whole else jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False), leaf)
            for key, leaf in layers.items()
        }

    @jax.jit
    def sys_embed(params, tokens):
        return program._embed(params, tokens[None], config)

    def attention_of(kind):
        of = config.of_kind(kind)

        @jax.jit
        def sys_attention(layers, index, x):
            sin, cos = program._rope_freqs(positions, of)
            mask = jnp.tril(jnp.ones((width, width), jnp.bool_))[None]
            return program._attention_block(
                x, of_layer(layers, index), sin, cos, mask, of)[0]

        return sys_attention

    @jax.jit
    def sys_dense(layers, index, x):
        return program._ffn_half(x, of_layer(layers, index), config, dense=True)[0]

    @jax.jit
    def sys_experts(layers, index, x):
        return program._ffn_half(x, of_layer(layers, index, EXPERTS), config, layer=index)[0]

    @jax.jit
    def sys_unembed(params, x):
        return program._unembed(params, x, config)[0]

    attention = {kind: attention_of(kind) for kind in (FULL, WINDOW)}

    def sys_layer(params, step, x):
        stack, kind, at, _ = places[step // 2]
        half = (attention[kind], sys_dense if stack == "dense_layers" else sys_experts)
        return half[step % 2](params[stack][kind], at, x)

    return SimpleNamespace(
        embed=sys_embed, layer=sys_layer, unembed=sys_unembed, n_layers=2 * config.n_layers
    )


def ref_layer_params(ref_params, step: int):
    """The leaves of the half a chain step runs, its stack's every layer,
    under its kind's name (`full_dense` | `full` | `window`: the check
    compiles one program a kind and half), and the layer's place in that
    stack. The order of the layers is read off the tree: the leading dense
    layers, then periods of one full layer and the window layers a period."""
    stack, kind, at, name = _tree_places(ref_params)[step // 2]
    layers = ref_params[stack][kind]
    if step % 2 == 0:
        names = LATENT + (INDEXER if "wq_idx" in layers else ())
    else:
        names = DENSE_HALF if stack == "dense_layers" else EXPERT_HALF
    return {name: {k: layers[k] for k in names}}, at


def _tree_places(params) -> list:
    """`_places` from the tree alone (check.py hands `ref_layer_params` no
    config): `n` dense layers of the full kind first (this family's leading
    layers are full layers: `model_config` holds `layer_types` to it), then
    periods of one full layer and `window / full` window layers."""
    count = lambda stack, kind: (  # noqa: E731
        jax.tree.leaves(params[stack][kind])[0].shape[0] if kind in params.get(stack, {}) else 0
    )
    n_dense, n_full, n_window = count("dense_layers", FULL), count("layers", FULL), count("layers", WINDOW)
    places = [("dense_layers", FULL, i, "full_dense") for i in range(n_dense)]
    per = n_window // n_full
    for p in range(n_full):
        places.append(("layers", FULL, p, "full"))
        places += [("layers", WINDOW, p * per + i, "window") for i in range(per)]
    return places


class hot_path:
    """The model functions the engine's programs are made of, called as the
    engine calls them for this cell's traffic (`families/glm_moe_dsa.py`'s
    `hot_path`, with TWO tables): a prompt inside the largest bucket through
    `prefill` into a local cache of both kinds' latents and
    `paged_insert_cache`; a longer one in segments through
    `paged_prefill_segment_inplace`, the full kind re-expanding and ranking
    the columns earlier segments wrote, the window kind gathering its band;
    then one decode step a token in the ABSORBED form, the full kind under
    its selection, the window kind from `length - 513`. The window group here
    has a page a logical page like the full group's (no ring: a ring's
    recycling is the engine's, and level 3 and the CPU tests hold it)."""

    def __init__(self, engine, width: int, rows: int, new_tokens: int) -> None:
        from langstream_tpu.models import transformer as program

        config, pool = engine.config, engine._pagepool
        self.segment = segment = engine.prefill_buckets[-1]
        page_size, slots, group = pool.page_size, engine.max_batch, engine.prefill_batch
        n_pages = -(-width // page_size)
        kept = pool.dev["lat"].dtype
        # row 0 the sequence's pages, every other row's table all out of bounds;
        # the same table for both groups
        def row0(n):
            one = jnp.full((n, n_pages), n_pages, jnp.int32).at[0].set(jnp.arange(n_pages))
            return jnp.stack([one, one])

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
    stacks = [params[s][k] for s in ("dense_layers", "layers") for k in params[s]]
    int8 = all(
        is_quantized(stack[k]) and stack[k]["q"].dtype == np.int8
        for stack in stacks for k in QUANTIZED
    ) and all(is_quantized(stack[k]) for stack in params["layers"].values() for k in SHARED)
    first, held = config.held_experts
    return {
        "weights": "int8" if int8 else "unquantized",
        # what a token's cache is kept in, a group: the latent leaf's dtype
        "kv_dtype": str(pool["lat"].dtype),
        "window_kv_dtype": str(pool["win"]["lat"].dtype),
        "router_dtype": str(params["layers"][FULL]["router"].dtype),
        "index_key_dtype": str(pool["ik"].dtype),
        "experts_held": f"{first}-{first + held - 1} of {config.n_experts}",
        "page_leaves": sorted(k for k in pool if k in ("k", "v", "ik", "lat")),
        "window_page_leaves": sorted(pool["win"]),
        "latent_widths": [pool["lat"].shape[-1], pool["win"]["lat"].shape[-1]],
    }


def expected_kernels(engine) -> dict:
    """`attention_paths()` entry -> what must have been traced there, a kernel
    for each of the four reads: the full kind's decode read in the latent
    space under its selection and the window kind's from its lower bound; the
    full kind's segment (both branches of one program and the selection's
    call) and the window kind's over its band; the admit group's prefill at
    the expanded heads of both kinds (the window kind's by the segment walk
    where the bucket is longer than the window)."""
    from langstream_tpu.models.transformer import latent_window_band

    pool, seg, window = engine._pagepool, engine.prefill_buckets[-1], engine.config.sliding_window
    t = pool.table_len * pool.page_size
    band = latent_window_band(seg, t, window, pool.page_size)
    return {
        f"paged-decode-latent[s=1,t={t}]": "ragged_paged_latent_attention",
        f"paged-decode-latent-window[s=1,t={t}]": "ragged_paged_latent_attention",
        f"paged-segment-latent[s={seg},t={t}]": "flash_segment_attention",
        f"paged-segment-latent-select[s={seg},t={t}]": "segment_select",
        f"paged-segment-latent-sparse[s={seg},t={t}]": "sparse_segment_attention",
        f"paged-segment-latent-window[s={seg},t={band}]": "flash_segment_attention",
        f"paged-segment-latent-expand[s={seg},t={band}]": "latent_expand_blocks",
        **{
            f"prefill[s={w},t={w}]": "flash_prefill_attention"
            for w in engine.prefill_buckets if w % 128 == 0
        },
        **{
            f"prefill-latent-window[s={w},t={w}]": "flash_segment_attention"
            for w in engine.prefill_buckets if w % 128 == 0 and w > window
        },
    }


def state_leaves(engine):
    return engine.params, engine._pagepool.dev

"""The Kimi-K2 family (`model_type: kimi_k2`, moonshotai/Kimi-K2.5's language
model: the DeepSeek-V3 layer): latent attention (a query latent, ONE key-value
latent and one rotary key a token for 64 heads) with NO selection: every
query reads every cached latent behind it. A head's q.k is 128 + 64 = 192
wide and its value 128; the rotary runs under YaRN, which blends the
frequencies and multiplies the softmax scale; one leading dense layer, then
expert layers whose sigmoid router chooses 8 of 384 under a bias and weighs by
the scores alone times `routed_scaling_factor`, one shared expert added whole,
an untied head. Equations: `reference/kimi_k2.py`'s docstring.

The program serves it through `ModelConfig.kv_lora_rank` and its four
companions with `index_topk` 0 (the page pool's ONE leaf is the latent
`"lat"`: no K, no V, no indexer's key), `rope_scaling_type` "yarn" and its
fields (`attn_scale` carries the softmax factor), `n_leading_dense` (a stack
of its own, `params["dense_layers"]`, run before the expert layers' scan),
`experts_held`, `router_bias` and `routed_scaling`. A chip holds a SHARE of a
layer's routed experts (`deployment.experts`) and a slice of the vocabulary;
attention, router, norms and the shared expert are whole.

Where the harness reaches into the program for this family: the private model
functions `_embed`, `_attention_block`, `_ffn_half`, `_rope_freqs`, `_unembed`
(the check's chain, HALF a layer a step: the expanded form over the whole
width, the causal prefill kernel from offset 0), the public `prefill`,
`paged_insert_cache`, `paged_prefill_segment_inplace`,
`paged_decode_step_inplace`, `make_page_pool`, `make_kv_cache` (its hot path:
the functions the engine's admit group, `_paged_segment_and_sample` and
`_paged_decode_chunk` are made of; the decode steps attend in the latent
space), and `engine._pagepool`, `engine.max_batch`, `engine.prefill_batch`,
`engine.prefill_buckets`.

Seeded weights: matrices N(0, 1 / fan_in) then int8 per output channel (the
two down-projections, the two up-projections, `wo`, the dense FFN, the held
experts, the shared expert, the head); the router float32; norms ones; the
embedding (the held slice of the vocabulary) in the model's dtype; the
router's bias N(0, `BIAS_SIGMA`^2) float32, NOT zero: a trained model's
balances its experts' load, and a zero bias would leave the rule that it
chooses and does not weigh untested (the configuration's `weights.why` has the
share of tokens whose choice it moves).

It imports from `families/glm_moe_dsa.py` what the two families share to the
letter (the chain's stepping by halves, the hot path over the program's entry
points, the places of a step in the two stacks) and edits nothing there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from langstream_tpu.models.configs import ModelConfig
from modelcfg import refuse_unmapped
from weights import normal, quantized_stack

from .glm_moe_dsa import (  # noqa: F401: `system_chain`, `hot_path`, `state_leaves` are this family's too
    DENSE_HALF,
    EXPERT_HALF,
    EXPERTS,
    SHARED,
    _held,
    _place,
    hot_path,
    state_leaves,
    system_chain,
)

# every key of the published config.json (the catalog row's `config`), and
# what it has to say for the program's block to be the model's
PUBLISHED = (
    "attention_bias", "ep_size", "first_k_dense_replace", "hidden_act", "hidden_size",
    "intermediate_size", "kv_lora_rank", "max_position_embeddings", "model_type",
    "moe_intermediate_size", "moe_layer_freq", "n_group", "n_routed_experts",
    "n_shared_experts", "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "num_nextn_predict_layers", "q_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_scaling", "rope_theta",
    "routed_scaling_factor", "scoring_func", "seq_aux", "tf_legacy_loss",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim", "vocab_size",
)
_HAS_TO_SAY = {
    "model_type": "kimi_k2", "attention_bias": False, "hidden_act": "silu",
    "norm_topk_prob": True, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "n_group": 1, "topk_group": 1, "tie_word_embeddings": False,
    # no multi-token module to leave out
    "num_nextn_predict_layers": 0,
    # inert here, and said so under `assumed`: every layer behind the leading
    # dense one is sparse, the expert-parallel share is the deployment's, and
    # the two loss switches are training's
    "ep_size": 1, "moe_layer_freq": 1, "seq_aux": True, "tf_legacy_loss": False,
}
_YARN_KEYS = {
    "beta_fast", "beta_slow", "factor", "mscale", "mscale_all_dim",
    "original_max_position_embeddings", "type",
}
ATTENTION_HALF = ("attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo")
QUANTIZED = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", *EXPERTS)
# the seeded router bias's spread: configs/kimi-k2.5-int8-ep32-d7.json `weights.why`
BIAS_SIGMA = 0.001


def model_config(spec: dict, name: str) -> ModelConfig:
    refuse_unmapped(spec, PUBLISHED, name)
    yarn = spec["rope_scaling"]
    differs = {k: spec.get(k) for k, v in _HAS_TO_SAY.items() if spec.get(k) != v}
    if set(yarn) != _YARN_KEYS or yarn.get("type") != "yarn":
        differs["rope_scaling"] = yarn
    if spec["num_key_value_heads"] != spec["num_attention_heads"]:  # the expanded form's
        differs["num_key_value_heads"] = spec["num_key_value_heads"]
    if differs:
        raise ValueError(f"{name}: the program's latent-attention model cannot express {differs}")
    first, held = _held(spec)
    return ModelConfig(
        name=name, vocab_size=spec["vocab_size"], d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"], n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"], d_ff=spec["intermediate_size"],
        moe_d_ff=spec["moe_intermediate_size"], rope_theta=float(spec["rope_theta"]),
        rms_norm_eps=float(spec["rms_norm_eps"]), max_seq_len=spec["max_position_embeddings"],
        activation=spec["hidden_act"], rope_interleaved=True,
        rope_scaling_type="yarn", rope_scaling_factor=float(yarn["factor"]),
        rope_scaling_original_max_seq_len=int(yarn["original_max_position_embeddings"]),
        rope_scaling_beta_fast=float(yarn["beta_fast"]),
        rope_scaling_beta_slow=float(yarn["beta_slow"]),
        rope_scaling_mscale=float(yarn["mscale"]),
        rope_scaling_mscale_all_dim=float(yarn["mscale_all_dim"]),
        n_experts=int(spec["deployment"]["experts"]["published"]), experts_held=(first, held),
        n_experts_per_tok=spec["num_experts_per_tok"], moe_scoring=spec["scoring_func"],
        n_shared_experts=spec["n_shared_experts"], router_bias=True,
        routed_scaling=float(spec["routed_scaling_factor"]),
        n_leading_dense=spec["first_k_dense_replace"],
        q_lora_rank=spec["q_lora_rank"], kv_lora_rank=spec["kv_lora_rank"],
        qk_nope_head_dim=spec["qk_nope_head_dim"], qk_rope_head_dim=spec["qk_rope_head_dim"],
        v_head_dim=spec["v_head_dim"],
    )


def reference_dims(spec: dict) -> dict:
    return {
        "n_heads": spec["num_attention_heads"], "eps": float(spec["rms_norm_eps"]),
        "rope_theta": float(spec["rope_theta"]), "rope_scaling": dict(spec["rope_scaling"]),
        "kv_lora_rank": spec["kv_lora_rank"], "qk_nope_head_dim": spec["qk_nope_head_dim"],
        "qk_rope_head_dim": spec["qk_rope_head_dim"], "v_head_dim": spec["v_head_dim"],
        "top_k": spec["num_experts_per_tok"],
        "n_experts": int(spec["deployment"]["experts"]["published"]),
        "experts_held": _held(spec), "routed_scaling": float(spec["routed_scaling_factor"]),
    }


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, h, hd, v = config.d_model, config.n_heads, config.resolved_head_dim, config.vocab_size
    ql, kl, e = config.q_lora_rank, config.kv_lora_rank, config.n_experts
    held, f, ns = config.held_experts[1], config.expert_d_ff, config.n_shared_experts
    dtype = jnp.dtype(config.dtype)
    stack = functools.partial(quantized_stack, dtype=dtype)

    def attention_half(key, n):
        keys = iter(jax.random.split(key, 5))
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq_a": stack(next(keys), (n,), d, ql), "q_a_norm": jnp.ones((n, ql), dtype),
            "wq_b": stack(next(keys), (n,), ql, h * hd),
            "wkv_a": stack(next(keys), (n,), d, config.latent_width),
            "kv_a_norm": jnp.ones((n, kl), dtype),
            # a head's key part (qk_nope_head_dim), then its value (v_head_dim)
            "wkv_b": stack(next(keys), (n,), kl, h * (config.qk_nope_head_dim + config.v_head_dim)),
            "wo": stack(next(keys), (n,), h * config.v_head_dim, d),
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


def ref_layer_params(ref_params, step: int):
    """The leaves of the half a chain step runs (`reference.layer` runs the
    half it is handed), its stack's every layer, under its kind's name
    (`dense` | `sparse`: the check compiles one program a kind and half), and
    the layer's place in that stack."""
    stack, kind, at, half = _place(ref_params, step)
    names = (ATTENTION_HALF, DENSE_HALF if kind == "dense" else EXPERT_HALF)[half]
    layers = ref_params[stack]
    return {kind: {k: layers[k] for k in names}}, at


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
        "experts_held": f"{first}-{first + held - 1} of {config.n_experts}",
        "page_leaves": sorted(k for k in pool if k in ("k", "v", "ik", "lat")),
    }


def expected_kernels(engine) -> dict:
    """`attention_paths()` entry -> what must have been traced there: the
    decode step's dense read in the latent space; the segment's causal walk
    over its expanded columns and the call that expands them; the admit
    group's prefill at the expanded heads. Nothing of a selection has an
    entry, because none is traced. The grouped expert product has no entry:
    its gate is the same backend test, and the traced run's
    `moe7168x2048_grouped_matmul_roofline.drain` reads nothing without it."""
    pool, seg = engine._pagepool, engine.prefill_buckets[-1]
    t = pool.table_len * pool.page_size
    return {
        f"paged-decode-latent[s=1,t={t}]": "ragged_paged_latent_attention",
        f"paged-segment-latent[s={seg},t={t}]": "flash_segment_attention",
        f"paged-segment-latent-expand[s={seg},t={t}]": "latent_expand_blocks",
        **{
            f"prefill[s={w},t={w}]": "flash_prefill_attention"
            for w in engine.prefill_buckets if w % 128 == 0
        },
    }

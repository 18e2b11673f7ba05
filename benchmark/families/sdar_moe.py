"""The SDAR-MoE family (`model_type: sdar_moe`, JetLM/SDAR-30B-A3B-Chat): a
model that fills a BLOCK of tokens by denoising, served by the program's own
engine. The block is the program's sequential pre-norm block under a mask
that is causal across blocks and two-way inside one, with an RMSNorm of q and
k over each head, 128 softmax-routed experts top-8 of a width apart from the
dense one, none dropped (`moe_ffn_held` over all of them) and an untied head.
Generation: `reference/sdar_moe.py`'s docstring.

Where the harness reaches into the program for this family: the private
model functions `_embed`, `_attention_block`, `_ffn_half`, `_visible`,
`_rope_freqs`, `_unembed` (the check's chain, half a layer a step, which
hands the experts' stacks on whole as the program's own layer scans do), the public `prefill`, `paged_insert_cache`,
`paged_block_step_inplace` (its hot path: the functions the engine's
`_block_admit_group` and `_paged_block_chunk` are made of), and
`engine._pagepool`, `engine.max_batch`, `engine.prefill_batch`,
`engine.prefill_buckets`.

Beyond what every family exports, this one has what `check.py` needs of an
engine whose tokens are not drawn one a forward from the clean prefix:

- `trajectory(spec, prompt, result)`: the passes that made `result.tokens`,
  rebuilt from the tokens, the engine's label of the denoise step that fixed
  each (`result.fix_steps`) and the undelivered rest of the last block
  (`result.block_rest`): never from logits or inputs the engine reports;
- `hot_path(...).pass_logits(params, prompt, passes)`: the serving path's
  logits at each pass's `read`, every pass (a commit too) through
  `paged_block_step_inplace` and the page pool, as the engine runs them;
- `choice_score(logits)`: the confidence by which open positions are chosen.
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
    "rms_norm_eps", "rope_scaling", "rope_theta", "sliding_window", "tie_word_embeddings",
    "use_sliding_window", "vocab_size",
)
_HAS_TO_SAY = {
    "model_type": "sdar_moe", "attention_bias": False, "hidden_act": "silu",
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "rope_scaling": None, "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False,
}
QUANTIZED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
EXPERTS = ("w_gate", "w_up", "w_down")


def model_config(spec: dict, name: str) -> ModelConfig:
    refuse_unmapped(spec, PUBLISHED, name)
    differs = {k: spec.get(k) for k, v in _HAS_TO_SAY.items() if spec.get(k) != v}
    if differs:
        raise ValueError(f"{name}: the program's block-filling model cannot express {differs}")
    assumed = spec["assumed"]
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
        block_length=int(assumed["block_length"]),
        denoise_steps=int(assumed["denoising_steps"]),
        confidence_threshold=float(assumed["confidence_threshold"]),
        mask_token_id=int(assumed["mask_token_id"]),
    )


def reference_dims(spec: dict) -> dict:
    assumed = spec["assumed"]
    return {
        "n_heads": spec["num_attention_heads"], "n_kv_heads": spec["num_key_value_heads"],
        "head_dim": spec["head_dim"], "rope_theta": float(spec["rope_theta"]),
        "eps": float(spec["rms_norm_eps"]), "top_k": spec["num_experts_per_tok"],
        "n_experts": spec["num_experts"],
        "block_length": int(assumed["block_length"]),
        "denoising_steps": int(assumed["denoising_steps"]),
        "confidence_threshold": float(assumed["confidence_threshold"]),
        "mask_token_id": int(assumed["mask_token_id"]),
    }


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: ModelConfig):
    d, h, hkv, hd = config.d_model, config.n_heads, config.n_kv_heads, config.resolved_head_dim
    f, n, v, e = config.expert_d_ff, config.n_layers, config.vocab_size, config.n_experts
    dtype = jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 10))
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
    """The body of `transformer.forward` over a pass's whole sequence under
    the model's own mask (the program's `_visible`: a sequence of whole
    blocks, padded with blocks no real position sees), one HALF of a layer at
    a time: the two calls `_layer_counted` is made of, `_attention_block`
    then `_ffn_half`, so the chain has two steps a layer and the reference
    is handed the program's input to each. A SEQUENTIAL block's router reads
    the attention half's output, bf16 here and float32 in a reference that
    is fed the layer's input: stepped by whole layers, 0.9% of the pairs took
    another expert than the reference's at gaps up to 0.015 and level 1 could
    hold the expert path by its median alone (PERF.md section 6, PR 41).
    The experts' stacks go on whole with the layer's index, as the program's
    layer scans hand them on (`_split_held`): the grouped product reads its
    blocks where they lie."""
    from langstream_tpu.models import transformer as program

    if rows != 1 or width % config.block_length:
        raise ValueError("this chain takes one row of whole blocks: no expert is dropped")
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
        place = jnp.arange(width)
        mask = program._visible(place[:, None], place[None, :], config)[None]
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


ATTENTION_HALF = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")
EXPERT_HALF = ("ffn_norm", "router", *EXPERTS)


def ref_layer_params(ref_params, step: int):
    """The leaves of the half a chain step runs (`reference.layer` runs the
    half it is handed), every layer's stacked, and the layer's place there."""
    layers = ref_params["layers"]
    return {k: layers[k] for k in (ATTENTION_HALF, EXPERT_HALF)[step % 2]}, step // 2


class hot_path:
    """The model functions the engine's programs are made of, called as the
    engine calls them, with its config (so its kernels), its page size, its
    pool's dtype, its slot count and its prefill group's rows, on a page pool
    of this check's own: the prompt's whole blocks through `prefill` at the
    engine's bucket into a local cache and `paged_insert_cache`, then every
    pass of the trajectory, denoise or commit, through
    `paged_block_step_inplace`, row 0 the sequence and the other rows idle.
    A pass writes its block's K/V as the engine's does, so a denoise pass
    reads a cache whose last block an earlier pass left there."""

    def __init__(self, engine, width: int, rows: int, new_tokens: int) -> None:
        from langstream_tpu.models import transformer as program

        config, pool = engine.config, engine._pagepool
        self.config, self.buckets = config, tuple(engine.prefill_buckets)
        page_size, slots, group = pool.page_size, engine.max_batch, engine.prefill_batch
        n_pages = -(-width // page_size)
        kept = pool.dev["k"].dtype
        # row 0 the sequence's pages, every other row's table all out of bounds
        row0 = lambda n: jnp.full((n, n_pages), n_pages, jnp.int32).at[0].set(jnp.arange(n_pages))  # noqa: E731
        tables, group_tables = row0(slots), row0(group)

        @jax.jit
        def prefill_group(params, tokens, whole):
            # the sequence in row 0 of the engine's group, the rest padding
            bucket = tokens.shape[0]
            rows_tokens = jnp.zeros((group, bucket), jnp.int32).at[0].set(tokens)
            lengths = jnp.zeros((group,), jnp.int32).at[0].set(whole)
            _, local = program.prefill(
                params, rows_tokens, lengths, program.make_kv_cache(config, group, bucket),
                config,
            )
            fresh = program.make_page_pool(config, n_pages, page_size, dtype=kept)
            return program.paged_insert_cache(fresh, local, group_tables, page_size, config)

        @functools.partial(jax.jit, donate_argnames=("pool",))
        def block_pass(params, block, start, pool):
            blocks = jnp.full((slots, config.block_length), config.mask_token_id, jnp.int32)
            starts = jnp.zeros((slots,), jnp.int32).at[0].set(start)
            logits, pool = program.paged_block_step_inplace(
                params, blocks.at[0].set(block), starts, pool, tables, config, page_size
            )
            return logits[0], pool

        self._fns = (prefill_group, block_pass)

    def pass_logits(self, params, prompt: list[int], passes: list[dict]) -> list:
        """One [len(read), V] a pass: a pass that read nothing is a commit."""
        prefill_group, block_pass = self._fns
        b = self.config.block_length
        whole = len(prompt) // b * b
        bucket = next(w for w in self.buckets if w >= whole)
        tokens = jnp.asarray(list(prompt[:whole]) + [0] * (bucket - whole), jnp.int32)
        pool = prefill_group(params, tokens, jnp.int32(whole))
        out = []
        for a_pass in passes:
            start = len(a_pass["tokens"]) - b
            block = jnp.asarray(a_pass["tokens"][start:], jnp.int32)
            logits, pool = block_pass(params, block, jnp.int32(start), pool)
            out.append(logits[np.asarray(a_pass["read"], np.int64) - start].astype(jnp.float32))
        return out


def trajectory(spec: dict, prompt: list[int], result) -> list[dict]:
    """The passes that made `result.tokens`. `result.fix_steps[j]` is the
    denoise step of its block in which delivered token j was fixed;
    `result.block_rest` holds the tokens of the last block that the engine
    fixed and did not deliver (it finishes the block it began), and their
    steps. A denoise pass: the clean prefix and the block with the mask id
    where positions were still open; a commit: the clean block, nothing read."""
    b, mask = int(spec["assumed"]["block_length"]), int(spec["assumed"]["mask_token_id"])
    rest_tokens, rest_steps = result.block_rest or ([], [])
    whole = list(prompt) + list(result.tokens) + list(rest_tokens)
    fix_steps = [-1] * len(prompt) + list(result.fix_steps) + list(rest_steps)  # -1: never open
    if len(whole) % b or len(fix_steps) != len(whole):
        raise ValueError("a block-filling engine ends on a whole block, a label a token")
    passes = []
    for start in range(len(prompt) // b * b, len(whole), b):
        place = range(start, start + b)
        for step in sorted({fix_steps[p] for p in place} - {-1}):
            read = [p for p in place if fix_steps[p] == step]
            passes.append({
                "tokens": whole[:start] + [whole[p] if fix_steps[p] < step else mask for p in place],
                "read": read,
                "picked": [whole[p] for p in read],
                "open": [p for p in place if fix_steps[p] >= step],
            })
        passes.append({"tokens": whole[: start + b], "read": [], "picked": []})  # the commit
    return passes


def choice_score(logits):
    """The confidence a denoise pass ranks open positions by: the log of the
    largest softmax probability, one number a position. (The program takes
    the mask id out before its softmax; over the judge's logits that moves a
    score by log(1 - p_mask), 7e-6 at this vocabulary's width.)"""
    return jnp.max(jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1), axis=-1)


def engine_state(engine) -> dict:
    from langstream_tpu.models.quant import is_quantized

    layers, config = engine.params["layers"], engine.config
    int8 = all(is_quantized(layers[k]) and layers[k]["q"].dtype == np.int8 for k in QUANTIZED)
    return {
        "weights": "int8" if int8 else "unquantized",
        "kv_dtype": str(engine._pagepool.dev["k"].dtype),
        "router_dtype": str(layers["router"].dtype),
        "block_passes": f"{config.block_length} tokens a block, {config.denoise_steps} steps, "
                        f"over {config.confidence_threshold}",
    }


def expected_kernels(engine) -> dict:
    """`attention_paths()` entry -> the Pallas kernel that must have been
    traced there (`paged_kv_write` rides the block pass's entry: the same gate
    admits both; the grouped expert product has no entry: its gate is the same
    backend test, and the traced run's `block_moe_grouped_matmul_roofline`
    reads nothing without it)."""
    pool, s = engine._pagepool, engine.config.block_length
    return {
        f"paged-block[s={s},t={pool.table_len * pool.page_size}]": "ragged_paged_block_attention",
        **{
            f"prefill[s={w},t={w}]": "flash_prefill_attention"
            for w in engine.prefill_buckets if w % 128 == 0
        },
    }


def state_leaves(engine):
    return engine.params, engine._pagepool.dev

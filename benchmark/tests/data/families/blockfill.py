"""A toy family whose model fills a BLOCK of tokens by denoising, as files
under `tests/data` alone (test_check.py): what a family brings when its
engine does not yield one token a row and a step, left to right.

The model is Mistral's block under a block-causal mask (block length B:
position i sees every j with j // B <= i // B) with a mask id. Generation:
the prompt's whole blocks are prefilled into a K/V cache; the next block
starts as the prompt's tail followed by mask ids; a DENOISE pass runs the B
positions of the block against the cache, reads the logits AT each open
position (no shift) and fixes the open positions of highest confidence, as
many as the schedule gives that step; when none is open a COMMIT pass runs
the clean block once more and stores its K/V; then the next block. So a pass
yields 0 to B tokens, and tokens inside a block are not fixed left to right.

The program has no such model and no such engine: the system's side here is
this file's own bfloat16 arithmetic (`_layer`, `BlockPasses`), which a stub
engine (`tests/data/blockfill_engine.py`) drives, and which stands where a
real family reaches into `langstream_tpu`. What is new against the families
there are, and all that `check.py` needs for it:

- `trajectory(spec, prompt, result) -> passes`: the forwards that made
  `result.tokens`, rebuilt from the tokens and the engine's label of the
  denoise step that fixed each (`result.fixed_in`): never from logits or
  inputs the engine reports, so a token altered after the fact is judged
  by the pass that would have had to choose it;
- `hot_path(...).pass_logits(params, prompt, passes)`: the serving path's
  logits at each pass's `read`, its cache written a pass later than read;
- `choice_score(logits)`: the confidence by which open positions are chosen.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from modelcfg import refuse_unmapped
from weights import normal, quantized_stack

PUBLISHED = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "head_dim", "rope_theta", "rms_norm_eps",
    "max_position_embeddings", "hidden_act", "tie_word_embeddings", "mask_token_id",
)
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


class BlockFillConfig(NamedTuple):
    """What the toy's arithmetic reads: not the program's `ModelConfig`,
    which has no block-causal model to describe."""

    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    eps: float
    max_seq_len: int
    mask_id: int
    block_length: int
    denoising_steps: int

    @property
    def schedule(self) -> tuple:
        """How many open positions each denoise step of a block fixes."""
        b, t = self.block_length, self.denoising_steps
        return tuple(b // t + (step < b % t) for step in range(t))


def model_config(spec: dict, name: str) -> BlockFillConfig:
    refuse_unmapped(spec, PUBLISHED, name)
    if spec["hidden_act"] != "silu" or spec["tie_word_embeddings"]:
        raise ValueError(f"{name}: the toy's block is SwiGLU with an untied head")
    assumed = spec["assumed"]
    if not 0 < assumed["denoising_steps"] <= assumed["block_length"]:
        raise ValueError(f"{name}: a denoise step has to fix a position")
    return BlockFillConfig(
        name=name, vocab_size=spec["vocab_size"], d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"], n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"], head_dim=spec["head_dim"],
        d_ff=spec["intermediate_size"], rope_theta=float(spec["rope_theta"]),
        eps=float(spec["rms_norm_eps"]), max_seq_len=spec["max_position_embeddings"],
        mask_id=spec["mask_token_id"], block_length=assumed["block_length"],
        denoising_steps=assumed["denoising_steps"],
    )


def reference_dims(spec: dict) -> dict:
    return {
        "n_heads": spec["num_attention_heads"],
        "n_kv_heads": spec["num_key_value_heads"],
        "head_dim": spec["head_dim"],
        "rope_theta": float(spec["rope_theta"]),
        "eps": float(spec["rms_norm_eps"]),
        "block_length": spec["assumed"]["block_length"],
    }


@functools.partial(jax.jit, static_argnames=("config",))
def _make(key, config: BlockFillConfig):
    d, h, hkv, hd = config.d_model, config.n_heads, config.n_kv_heads, config.head_dim
    f, n, v = config.d_ff, config.n_layers, config.vocab_size
    keys = iter(jax.random.split(key, 9))
    stack = functools.partial(quantized_stack, dtype=jnp.bfloat16)
    return {
        "embed": normal(next(keys), (v, d), d, jnp.bfloat16),
        "layers": {
            "attn_norm": jnp.ones((n, d), jnp.bfloat16),
            "wq": stack(next(keys), (n,), d, h * hd),
            "wk": stack(next(keys), (n,), d, hkv * hd),
            "wv": stack(next(keys), (n,), d, hkv * hd),
            "wo": stack(next(keys), (n,), h * hd, d),
            "ffn_norm": jnp.ones((n, d), jnp.bfloat16),
            "w_gate": stack(next(keys), (n,), d, f),
            "w_up": stack(next(keys), (n,), d, f),
            "w_down": stack(next(keys), (n,), f, d),
        },
        "final_norm": jnp.ones((d,), jnp.bfloat16),
        "lm_head": stack(next(keys), (), d, v),
    }


def make_params(config: BlockFillConfig, seed: int):
    return _make(jax.random.PRNGKey(seed), config)


# ---- the toy's own bfloat16 arithmetic: the "program" of this family


def _mm(a, matrix, out=jnp.bfloat16):
    w = (matrix["q"].astype(jnp.float32) * matrix["s"]).astype(jnp.bfloat16)
    return jnp.dot(a, w, preferred_element_type=jnp.float32).astype(out)


def _rms(x, weight, eps: float):
    x = x.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(jnp.float32)
    return y.astype(jnp.bfloat16)


def _rope(x, positions, theta: float):
    """x: [S, H, D] at `positions`; rotate_half convention."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(jnp.bfloat16)


def _layer(x, lp, positions, before, seen, config: BlockFillConfig):
    """One block over x [S, d] at `positions`. `before`: the (k, v)
    [T, Hkv, D] of what came earlier, or None; `seen` [S, T + S]: which of
    those T keys and of its own S each query sees. Returns y and its own
    (k, v), the keys already turned."""
    s = x.shape[0]
    h, hkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    a = _rms(x, lp["attn_norm"], config.eps)
    q = _rope(_mm(a, lp["wq"]).reshape(s, h, hd), positions, config.rope_theta)
    k = _rope(_mm(a, lp["wk"]).reshape(s, hkv, hd), positions, config.rope_theta)
    v = _mm(a, lp["wv"]).reshape(s, hkv, hd)
    keys, values = k, v
    if before is not None:
        keys = jnp.concatenate([before[0].astype(jnp.bfloat16), k])
        values = jnp.concatenate([before[1].astype(jnp.bfloat16), v])
    keys, values = jnp.repeat(keys, h // hkv, axis=1), jnp.repeat(values, h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, keys, preferred_element_type=jnp.float32) * hd**-0.5
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1).astype(jnp.bfloat16)
    out = jnp.einsum("hqk,khd->qhd", probs, values, preferred_element_type=jnp.float32)
    x = x + _mm(out.astype(jnp.bfloat16).reshape(s, h * hd), lp["wo"])
    f = _rms(x, lp["ffn_norm"], config.eps)
    gate = jax.nn.silu(_mm(f, lp["w_gate"]).astype(jnp.float32)).astype(jnp.bfloat16)
    return x + _mm(gate * _mm(f, lp["w_up"]), lp["w_down"]), (k, v)


def _head(params, x, config: BlockFillConfig):
    return _mm(_rms(x, params["final_norm"], config.eps), params["lm_head"], out=jnp.float32)


def _block_causal(width: int, block_length: int):
    block = jnp.arange(width) // block_length
    return block[None, :] <= block[:, None]  # [query, key]


def _layer_at(layers, index):
    return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False), layers)


class _Chain:
    """`embed`, `layer`, `unembed` over a whole pass, one layer at a time,
    under the model's own mask: a sequence of whole blocks, padded with
    blocks no real position sees."""

    def __init__(self, config: BlockFillConfig, width: int, rows: int) -> None:
        if rows != 1 or width % config.block_length:
            raise ValueError("the toy's chain takes one row of whole blocks")
        positions, seen = jnp.arange(width), _block_causal(width, config.block_length)
        self.n_layers = config.n_layers
        self.embed = jax.jit(lambda params, tokens: params["embed"][tokens][None])
        self._layer = jax.jit(
            lambda layers, index, x: _layer(
                x[0], _layer_at(layers, index), positions, None, seen, config)[0][None]
        )
        self.unembed = jax.jit(lambda params, x: _head(params, x[0], config))

    def layer(self, params, index: int, x):
        return self._layer(params["layers"], index, x)


system_chain = _Chain


def ref_layer_params(ref_params, index: int):
    return ref_params["layers"], index


class BlockPasses:
    """The serving path: a K/V cache of the whole blocks behind, a denoise
    pass of B queries that all see the block's end, a commit pass that
    writes the clean block's K/V. `cache_dtype` and `denoise_mask` are the
    ENGINE's (`hot_path` reads them off it): a cache of fewer bits, or a
    denoise pass under the causal mask, is then in what the check times."""

    def __init__(self, config: BlockFillConfig, width: int, cache_dtype: str,
                 denoise_mask: str) -> None:
        b, n_layers = config.block_length, config.n_layers
        kept = jnp.dtype(cache_dtype)
        inside = {"block_causal": jnp.ones((b, b), jnp.bool_),
                  "causal": jnp.tril(jnp.ones((b, b), jnp.bool_))}
        self.config, self.width = config, width

        @jax.jit
        def prefill(params, tokens):
            x = params["embed"][tokens]
            positions, seen = jnp.arange(width), _block_causal(width, b)
            kept_kv = []
            for index in range(n_layers):
                x, kv = _layer(x, _layer_at(params["layers"], index), positions, None, seen, config)
                kept_kv.append(kv)
            return tuple(jnp.stack(leaf).astype(kept) for leaf in zip(*kept_kv))  # [L, W, Hkv, D] x 2

        def block_pass(params, block, start, cache, mask_inside):
            x = params["embed"][block]
            positions = start + jnp.arange(b)
            behind = jnp.broadcast_to(jnp.arange(width)[None, :] < start, (b, width))
            seen = jnp.concatenate([behind, mask_inside], axis=1)
            fresh = []
            for index in range(n_layers):
                x, kv = _layer(x, _layer_at(params["layers"], index), positions,
                               (cache[0][index], cache[1][index]), seen, config)
                fresh.append(kv)
            return _head(params, x, config), fresh

        @jax.jit
        def denoise(params, block, start, cache):
            return block_pass(params, block, start, cache, inside[denoise_mask])[0]

        @jax.jit
        def commit(params, block, start, cache):
            _, fresh = block_pass(params, block, start, cache, inside["block_causal"])
            return tuple(
                lax.dynamic_update_slice(kept_leaf, jnp.stack(leaf).astype(kept), (0, start, 0, 0))
                for kept_leaf, leaf in zip(cache, zip(*fresh))
            )

        self._fns = (prefill, denoise, commit)

    def prefill(self, params, prefix: list[int]):
        """The cache after the prompt's whole blocks. What lies behind them is
        padding's, and is overwritten by a commit before any query sees it."""
        tokens = jnp.asarray(list(prefix) + [0] * (self.width - len(prefix)), jnp.int32)
        return self._fns[0](params, tokens)

    def denoise(self, params, block: list[int], start: int, cache):
        """Logits [B, V] AT the block's positions, nothing written."""
        return self._fns[1](params, jnp.asarray(block, jnp.int32), jnp.int32(start), cache)

    def commit(self, params, block: list[int], start: int, cache):
        return self._fns[2](params, jnp.asarray(block, jnp.int32), jnp.int32(start), cache)

    def pass_logits(self, params, prompt: list[int], passes: list[dict]) -> list:
        """One [len(read), V] a pass: a pass that read nothing is a commit."""
        b = self.config.block_length
        cache = self.prefill(params, prompt[: len(prompt) // b * b])
        out = []
        for a_pass in passes:
            start = len(a_pass["tokens"]) - b
            block = a_pass["tokens"][start:]
            if a_pass["read"]:
                logits = self.denoise(params, block, start, cache)
                out.append(logits[np.asarray(a_pass["read"]) - start])
            else:
                cache = self.commit(params, block, start, cache)
                out.append(jnp.zeros((0, self.config.vocab_size), jnp.float32))
        return out


def hot_path(engine, width: int, rows: int, new_tokens: int) -> BlockPasses:
    return BlockPasses(engine.config, width, engine.cache_dtype, engine.denoise_mask)


def trajectory(spec: dict, prompt: list[int], result) -> list[dict]:
    """The passes that made `result.tokens`. `result.fixed_in[j]` is the
    denoise step of its block in which generated token j was fixed."""
    b, mask = spec["assumed"]["block_length"], spec["mask_token_id"]
    whole = list(prompt) + list(result.tokens)
    fixed_in = [-1] * len(prompt) + list(result.fixed_in)  # the prompt was never open
    if len(whole) % b or len(fixed_in) != len(whole):
        raise ValueError("a block-filling engine ends on a whole block, a label a token")
    passes = []
    for start in range(len(prompt) // b * b, len(whole), b):
        place = range(start, start + b)
        for step in sorted({fixed_in[p] for p in place} - {-1}):
            read = [p for p in place if fixed_in[p] == step]
            passes.append({
                "tokens": whole[:start] + [whole[p] if fixed_in[p] < step else mask for p in place],
                "read": read,
                "picked": [whole[p] for p in read],
                "open": [p for p in place if fixed_in[p] >= step],
            })
        passes.append({"tokens": whole[: start + b], "read": [], "picked": []})  # the commit
    return passes


def choice_score(logits):
    """The confidence a denoise pass ranks open positions by: the log of the
    largest softmax probability, one number a position."""
    return jnp.max(jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1), axis=-1)


def engine_state(engine) -> dict:
    from langstream_tpu.models.quant import is_quantized

    layers = engine.params["layers"]
    int8 = all(is_quantized(layers[k]) and layers[k]["q"].dtype == np.int8 for k in MATRICES)
    return {"weights": "int8" if int8 else "unquantized", "kv_dtype": engine.cache_dtype}


def expected_kernels(engine) -> dict:
    return {}  # the toy runs on the CPU


def state_leaves(engine):
    return (engine.params,)

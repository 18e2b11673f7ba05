"""Decoder-only transformer (Llama / Gemma / Mixtral families) in pure JAX.

TPU-first design notes:
- layer params are STACKED on a leading axis and the layer loop is a
  `lax.scan` — one compiled layer body regardless of depth (fast compiles,
  XLA pipelining across layers);
- all shapes static; KV cache is a fixed [L, B, Hkv, Smax, D] buffer
  (head-major: the kv-head axis stays out of the last-two tiled dims so the
  Pallas kernels can block over (Smax, D) directly) with per-slot lengths and
  masked attention (paged attention kernel: ops/);
- GQA via einsum grouping; bf16 activations/params, fp32 softmax/norms;
- MoE uses the dispatch/combine einsum pattern (GShard-style) so the expert
  axis shards cleanly over an ICI mesh ("expert" axis) with `pjit`;
- sharding is annotated EXTERNALLY via parallel/sharding.py param specs —
  this file stays mesh-agnostic so the same code runs single-chip and TP/EP.

Replaces (functionally) the reference's remote completion providers
(`OpenAICompletionService.java`, `VertexAIProvider.java` — SURVEY §2.5);
there is deliberately no architectural counterpart.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from langstream_tpu.models.configs import ModelConfig
from langstream_tpu.models.quant import dequantize_weight, is_quantized, quantized_matmul

Params = dict
KVCache = dict

# The one vocabulary of `jax.named_scope` names inside the device programs
# (docs/SERVING.md §12). Metadata only: a profile's device operations carry
# the scope path, so a reader finds "the time inside attention" after any
# refactor renumbers the fusions. `sample` wraps the engine's sampling and
# grammar mask (serving/engine.py); every other name is entered here.
SCOPES = (
    "embed", "attention", "ffn", "moe_ffn", "moe_ffn.route",
    "moe_ffn.dispatch", "moe_ffn.experts", "moe_ffn.combine",
    "kv_pool.write", "head", "sample",
    "linear_attention", "linear_attention.proj", "linear_attention.conv",
    "linear_attention.state", "linear_attention.out",
    "short_conv", "short_conv.proj", "short_conv.conv", "short_conv.out",
    "attention.window", "attention.full", "moe_ffn.shared",
    "block_choice",
    "attention.index", "attention.index.scores", "attention.select", "attention.sparse",
    "attention.latent", "attention.latent.expand", "attention.latent.read",
    "attention.latent.window", "attention.gate",
)

# What `moe_ffn_counted` counts, per call, as one int32 vector: expert
# assignments made (tokens x top-k), those dropped past `capacity`, and the
# same two over REAL tokens only (``token_valid``; without it every token
# counts as real). A dense model's programs return zeros.
MOE_COUNTS = ("routed", "dropped", "routed_real", "dropped_real")


# An expert layer that holds a share (`moe_ffn_held`) counts three more: the
# real tokens' assignments that fell on the experts it holds, the held
# experts that got at least one of them (a layer: whose weights the grouped
# product has to read), and the local assignments computed in a pass past the
# first (0 where the call is one pass: how often twice the even share did not
# hold them).
MOE_HELD_COUNTS = MOE_COUNTS + ("local", "touched", "spilled")


def moe_count_names(config: ModelConfig) -> tuple:
    """What the programs of ``config`` count: the parallel block runs
    `moe_ffn_held` in every layer, the sequential block's loops where the
    model holds its experts so (``experts_held``), else `moe_ffn_counted`."""
    return MOE_HELD_COUNTS if config.holds_experts else MOE_COUNTS


def _no_moe_counts() -> jax.Array:
    return jnp.zeros(len(MOE_COUNTS), jnp.int32)


def _dtype(config: ModelConfig):
    return jnp.dtype(config.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(config: ModelConfig, key: jax.Array, dtype: Optional[Any] = None) -> Params:
    """Random-init params (shape-identical to checkpoint-loaded ones)."""
    dtype = dtype or _dtype(config)
    d, h, hkv = config.d_model, config.n_heads, config.n_kv_heads
    hd = config.resolved_head_dim
    f, L, v = config.d_ff, config.n_layers, config.vocab_size

    if config.parallel_block:
        return _init_window_params(config, key, dtype)
    if config.latent_kinds:
        return _init_latent_kinds_params(config, key, dtype)
    if config.layer_pattern:
        return _init_pattern_params(config, key, dtype)
    keys = jax.random.split(key, 12)

    def norm(k, *shape, scale=None):
        scale = scale if scale is not None else (shape[-2] if len(shape) >= 2 else d)
        return (jax.random.normal(k, shape, jnp.float32) * (scale**-0.5)).astype(dtype)

    # a model with leading dense layers keeps them in a stack of their own
    # (`_init_dense_layers`); ``layers`` is then the expert layers' alone
    L = L - config.n_leading_dense
    layers: dict[str, jax.Array] = {
        "attn_norm": jnp.ones((L, d), dtype), "ffn_norm": jnp.ones((L, d), dtype),
    }
    if config.has_latent:
        layers.update(_init_latent_attention(config, jax.random.fold_in(key, 13), L, dtype))
    else:
        layers.update(
            wq=norm(keys[0], L, d, h * hd, scale=d),
            wk=norm(keys[1], L, d, hkv * hd, scale=d),
            wv=norm(keys[2], L, d, hkv * hd, scale=d),
            wo=norm(keys[3], L, h * hd, d, scale=h * hd),
        )
    if config.qk_norm_heads:
        layers["q_norm"] = jnp.ones((L, hd), dtype)
        layers["k_norm"] = jnp.ones((L, hd), dtype)
    if config.has_indexer:
        # the indexer: its queries' and its key's projection like every
        # projection, the per-head weights float32 like a router, the
        # LayerNorm of its key (the one bias of the model)
        hi, di = config.index_n_heads, config.index_head_dim
        d_q = config.q_lora_rank if config.index_query_input == "query_latent" else d
        layers["wq_idx"] = norm(keys[10], L, d_q, hi * di, scale=d_q)
        layers["wk_idx"] = norm(keys[11], L, d, di, scale=d)
        layers["w_idx"] = (
            jax.random.normal(jax.random.fold_in(key, 12), (L, d, hi), jnp.float32) * d**-0.5
        )
        layers["idx_norm"] = jnp.ones((L, di), dtype)
        layers["idx_bias"] = jnp.zeros((L, di), dtype)
    if config.is_moe:
        e = config.n_experts
        layers["router"] = norm(keys[4], L, d, e, scale=d)
        if config.router_bias:  # float32, beside a float32 router's scores
            layers["router"] = layers["router"].astype(jnp.float32)
            layers["router_bias"] = 0.05 * jax.random.normal(
                jax.random.fold_in(key, 14), (L, e), jnp.float32
            )
        if config.experts_held:  # the router is whole, the experts a share
            e, f = config.held_experts[1], config.expert_d_ff
        layers["w_gate"] = norm(keys[5], L, e, d, f, scale=d)
        layers["w_up"] = norm(keys[6], L, e, d, f, scale=d)
        layers["w_down"] = norm(keys[7], L, e, f, d, scale=f)
        if config.n_shared_experts and not config.parallel_block:
            # side by side, as `_init_window_params` keeps them
            ns, ks = config.n_shared_experts, jax.random.split(jax.random.fold_in(key, 15), 3)
            layers["ws_gate"] = norm(ks[0], L, d, ns * f, scale=d)
            layers["ws_up"] = norm(ks[1], L, d, ns * f, scale=d)
            layers["ws_down"] = norm(ks[2], L, ns * f, d, scale=f)
    else:
        layers["w_gate"] = norm(keys[5], L, d, f, scale=d)
        layers["w_up"] = norm(keys[6], L, d, f, scale=d)
        layers["w_down"] = norm(keys[7], L, f, d, scale=f)

    params: Params = {
        "embed": norm(keys[8], v, d, scale=d),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }
    if config.n_leading_dense:
        params["dense_layers"] = _init_dense_layers(config, jax.random.fold_in(key, 16), dtype)
    if not config.tie_embeddings:
        params["lm_head"] = norm(keys[9], d, v, scale=d)
    return params


def _init_latent_attention(config: ModelConfig, key: jax.Array, n: int, dtype) -> dict:
    """The latent attention's weights of ``n`` layers (HF's names in
    brackets): ``wq_a`` [d, q_lora_rank] (q_a_proj) and its norm, ``wq_b``
    [q_lora_rank, H x (nope + rope)] (q_b_proj), ``wkv_a`` [d, kv_lora_rank +
    rope] (kv_a_proj_with_mqa) and the latent's norm, ``wkv_b`` [kv_lora_rank,
    H x (nope + v)] (kv_b_proj: a head's key part, then its value), ``wo``
    [H x v, d]."""
    d, h, hd = config.d_model, config.n_heads, config.resolved_head_dim
    ql, kl = config.q_lora_rank, config.kv_lora_rank
    keys = iter(jax.random.split(key, 5))

    def normal(*shape):  # [..., in, out]: N(0, 1 / in)
        w = jax.random.normal(next(keys), shape, jnp.float32) * shape[-2] ** -0.5
        return w.astype(dtype)

    weights = {
        "wq_a": normal(n, d, ql), "q_a_norm": jnp.ones((n, ql), dtype),
        "wq_b": normal(n, ql, h * hd),
        "wkv_a": normal(n, d, config.latent_width), "kv_a_norm": jnp.ones((n, kl), dtype),
        "wkv_b": normal(n, kl, h * (config.qk_nope_head_dim + config.v_head_dim)),
        "wo": normal(n, h * config.v_head_dim, d),
    }
    if config.attn_gate:  # a scalar a head from the normed input (`_head_gate`)
        w = jax.random.normal(jax.random.fold_in(key, 5), (n, d, h), jnp.float32)
        weights["w_attn_gate"] = (w * d**-0.5).astype(dtype)
    return weights


def _init_dense_layers(config: ModelConfig, key: jax.Array, dtype) -> dict:
    """The leading dense layers' stack, ``params["dense_layers"]``
    [n_leading_dense, ...]: the attention half as every layer's (indexer and
    all), a dense FFN of ``d_ff`` in place of router and experts."""
    dense = dataclasses.replace(
        config, n_layers=config.n_leading_dense, n_leading_dense=0, n_experts=0,
        experts_held=(), moe_d_ff=0, moe_scoring="softmax", n_shared_experts=0,
        router_bias=False, routed_scaling=1.0,
    )
    return init_params(dense, key, dtype)["layers"]


def _init_latent_kinds_params(config: ModelConfig, key: jax.Array, dtype) -> Params:
    """A model whose attention kinds each keep a latent of their own
    (``config.latent_kinds``): ``params["layers"][kind]`` a stack a kind, each
    layer what a uniform latent model's is AT ITS KIND'S GEOMETRY (the full
    kind's with the indexer, the window kind's without), experts held as a
    share; the leading dense layers under ``params["dense_layers"][kind]``."""

    def stack(kind, n, seed, **dense):
        flat = dataclasses.replace(
            config.of_kind(kind), layer_pattern=(), sliding_window=0, window_attention=(),
            n_layers=n, n_leading_dense=0, kind_view=kind, **dense,
        )
        return init_params(flat, jax.random.fold_in(key, seed), dtype)

    kinds = [k for k in ("full_attention", "sliding_attention") if config.n_layers_of(k)]
    outer = stack("full_attention", 1, 0)  # embedding, final norm and head
    params: Params = {k: v for k, v in outer.items() if k != "layers"}
    params["layers"] = {
        kind: stack(kind, config.n_layers_of(kind) - config.dense_of(kind), 1 + i)["layers"]
        for i, kind in enumerate(kinds)
    }
    no_experts = dict(
        n_experts=0, experts_held=(), moe_d_ff=0, moe_scoring="softmax", n_shared_experts=0,
        router_bias=False, routed_scaling=1.0,
    )
    if config.n_leading_dense:
        params["dense_layers"] = {
            kind: stack(kind, config.dense_of(kind), 8 + i, **no_experts)["layers"]
            for i, kind in enumerate(kinds) if config.dense_of(kind)
        }
    return params


def _init_pattern_params(config: ModelConfig, key: jax.Array, dtype) -> Params:
    """A model with a layer pattern: ``params["layers"]`` holds one stack a
    KIND of layer, ``{"linear_attention": [n of them, ...],
    "full_attention": [...]}``, each in the model's layer order."""
    d, f, v = config.d_model, config.d_ff, config.vocab_size
    h, hkv, hd = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    lh, kd, vd = config.linear_n_heads, config.linear_key_dim, config.linear_value_dim
    keys = iter(jax.random.split(key, 24))

    def normal(*shape):  # [..., in, out]: N(0, 1 / in)
        w = jax.random.normal(next(keys), shape, jnp.float32) * shape[-2] ** -0.5
        return w.astype(dtype)

    def ffn(n):
        return {
            "ffn_norm": jnp.ones((n, d), dtype),
            "w_gate": normal(n, d, f), "w_up": normal(n, d, f), "w_down": normal(n, f, d),
        }

    n_lin = config.n_layers_of("linear_attention")
    # (a pattern of conv layers makes all its stacks below)
    n_full = 0 if "conv" in config.layer_pattern else config.n_layers_of("full_attention")
    # the published initialisation's draw (fla's GatedDeltaNet): A uniform
    # in (0, 16), the step log-uniform in (0.001, 0.1) through the inverse
    # softplus, so that the decay spans a real range
    step = jnp.exp(
        jax.random.uniform(next(keys), (n_lin, lh), jnp.float32)
        * (math.log(0.1) - math.log(0.001)) + math.log(0.001)
    )
    layers = {}
    if n_lin:
        layers["linear_attention"] = {
            "attn_norm": jnp.ones((n_lin, d), dtype),
            # q, k and v side by side, [d, 2 kd + vd]: one product, and a
            # width that is whole lanes where kd alone (2880) is not
            "wqkv": normal(n_lin, d, 2 * kd + vd), "wg": normal(n_lin, d, vd),
            "wa": normal(n_lin, d, lh), "wb": normal(n_lin, d, lh),
            "conv_w": (
                jax.random.normal(
                    next(keys), (n_lin, config.linear_conv_kernel, config.linear_conv_dim),
                    jnp.float32,
                ) * config.linear_conv_kernel ** -0.5
            ).astype(dtype),
            "A_log": jnp.log(
                jax.random.uniform(next(keys), (n_lin, lh), jnp.float32, 1e-3, 16.0)
            ),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "out_norm": jnp.ones((n_lin, config.linear_value_head_dim), dtype),
            "wo": normal(n_lin, vd, d),
            **ffn(n_lin),
        }
    if n_full:
        full = {
            "attn_norm": jnp.ones((n_full, d), dtype),
            "wq": normal(n_full, d, h * hd), "wk": normal(n_full, d, hkv * hd),
            "wv": normal(n_full, d, hkv * hd), "wo": normal(n_full, h * hd, d),
            **ffn(n_full),
        }
        if config.qk_norm:
            full["q_norm"] = jnp.ones((n_full, h * hd), dtype)
            full["k_norm"] = jnp.ones((n_full, hkv * hd), dtype)
        layers["full_attention"] = full
    params: Params = {
        "embed": (jax.random.normal(next(keys), (v, d), jnp.float32) * d**-0.5).astype(dtype),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = normal(d, v)
    if "conv" in config.layer_pattern:
        params.update(_init_conv_pattern_layers(config, jax.random.fold_in(key, 17), dtype))
    return params


def _init_conv_pattern_layers(config: ModelConfig, key: jax.Array, dtype) -> dict:
    """The stacks of a pattern of conv and full-attention layers (LFM2's):
    ``{"layers": {kind: ...}, "dense_layers": {kind: ...}}``. A kind's stack
    under ``"layers"`` holds its layers BEHIND the leading dense ones
    (``config.dense_of``), each a mixer (conv: ``attn_norm`` (the published
    operator_norm), ``w_in`` [d, 3d] for B | C | u, ``conv_w`` [K, d], ``w_out``
    [d, d]; attention: the four projections and the per-head q/k norms) and
    its FFN: the router over all experts, its bias, the held experts; the
    kind's leading dense layers lie under ``"dense_layers"``, the same mixer
    with a dense FFN of ``d_ff``."""
    d, h, hkv, hd = config.d_model, config.n_heads, config.n_kv_heads, config.resolved_head_dim
    e, held, f = config.n_experts, config.held_experts[1], config.expert_d_ff
    keys = iter(jax.random.split(key, 64))

    def normal(*shape, dt=dtype):  # [..., in, out]: N(0, 1 / in)
        w = jax.random.normal(next(keys), shape, jnp.float32) * shape[-2] ** -0.5
        return w.astype(dt)

    def mixer(kind, n):
        if kind == "conv":
            return {
                "attn_norm": jnp.ones((n, d), dtype), "w_in": normal(n, d, 3 * d),
                "conv_w": (
                    jax.random.normal(next(keys), (n, config.conv_kernel, d), jnp.float32)
                    * config.conv_kernel ** -0.5
                ).astype(dtype),
                "w_out": normal(n, d, d),
            }
        norms = {"q_norm": jnp.ones((n, hd), dtype), "k_norm": jnp.ones((n, hd), dtype)}
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq": normal(n, d, h * hd), "wk": normal(n, d, hkv * hd),
            "wv": normal(n, d, hkv * hd), "wo": normal(n, h * hd, d),
            **(norms if config.qk_norm_heads else {}),
        }

    def ffn(n, dense):
        if dense or not config.is_moe:
            f_d = config.d_ff
            return {
                "ffn_norm": jnp.ones((n, d), dtype), "w_gate": normal(n, d, f_d),
                "w_up": normal(n, d, f_d), "w_down": normal(n, f_d, d),
            }
        out = {
            "ffn_norm": jnp.ones((n, d), dtype),
            "router": normal(n, d, e, dt=jnp.float32 if config.router_bias else dtype),
            "w_gate": normal(n, held, d, f), "w_up": normal(n, held, d, f),
            "w_down": normal(n, held, f, d),
        }
        if config.router_bias:
            out["router_bias"] = 0.05 * jax.random.normal(next(keys), (n, e), jnp.float32)
        return out

    stacks: dict = {"layers": {}, "dense_layers": {}}
    for kind in dict.fromkeys(config.layer_pattern):
        first = config.dense_of(kind)
        if first:
            stacks["dense_layers"][kind] = {**mixer(kind, first), **ffn(first, True)}
        stacks["layers"][kind] = {
            **mixer(kind, config.n_layers_of(kind) - first),
            **ffn(config.n_layers_of(kind) - first, False),
        }
    return {k: v for k, v in stacks.items() if v}


def _init_window_params(config: ModelConfig, key: jax.Array, dtype) -> Params:
    """A model of window and full attention layers in a parallel block with
    an expert layer that holds a share: one stack a kind, each layer
    ``attn_norm`` (the block's one norm), the four attention projections,
    the router over ALL experts [d, n_experts], the HELD experts
    ``w_gate/w_up`` [held, d, f] and ``w_down`` [held, f, d], and the shared
    experts side by side, ``ws_gate/ws_up`` [d, n_shared * f] and ``ws_down``
    [n_shared * f, d]: their sum is one SwiGLU of that width."""
    d, v, f = config.d_model, config.vocab_size, config.expert_d_ff
    h, hkv, hd = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    held, ns = config.held_experts[1], config.n_shared_experts
    keys = iter(jax.random.split(key, 32))

    def normal(*shape):  # [..., in, out]: N(0, 1 / in)
        w = jax.random.normal(next(keys), shape, jnp.float32) * shape[-2] ** -0.5
        return w.astype(dtype)

    def stack(n):
        layer = {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq": normal(n, d, h * hd), "wk": normal(n, d, hkv * hd),
            "wv": normal(n, d, hkv * hd), "wo": normal(n, h * hd, d),
            "router": normal(n, d, config.n_experts),
            "w_gate": normal(n, held, d, f), "w_up": normal(n, held, d, f),
            "w_down": normal(n, held, f, d),
        }
        if ns:
            layer.update(
                ws_gate=normal(n, d, ns * f), ws_up=normal(n, d, ns * f),
                # a shared expert's down projection has fan-in f, not ns * f
                ws_down=(normal(n, ns * f, d).astype(jnp.float32) * ns**0.5).astype(dtype),
            )
        return layer

    params: Params = {
        "embed": (jax.random.normal(next(keys), (v, d), jnp.float32) * d**-0.5).astype(dtype),
        "layers": {
            kind: stack(config.n_layers_of(kind))
            for kind in ("sliding_attention", "full_attention")
            if config.n_layers_of(kind)
        },
        "final_norm": jnp.ones((d,), dtype),
    }
    if not config.tie_embeddings:
        params["lm_head"] = normal(d, v)
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    normed = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Subtract the mean, divide by sqrt(var + eps), scale; no bias; float32."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    normed = xc * lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def _norm(x: jax.Array, weight: jax.Array, config: ModelConfig) -> jax.Array:
    fn = layer_norm if config.norm == "layer" else rms_norm
    return fn(x, weight, config.rms_norm_eps)


def _rope_freqs(
    positions: jax.Array, config: ModelConfig
) -> tuple[jax.Array, jax.Array]:
    # positions: [B, S] → sin/cos [B, S, head_dim/2], fp32. [3, B, S]: a
    # position triple a token (m-rope): frequency i turns by the stream its
    # section of ``config.mrope_section`` names; equal triples are [B, S]
    half = config.rope_dim // 2
    freqs = config.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if config.yarn:
        return _yarn_tables(positions, freqs, config)
    if config.rope_scaling_factor:
        freqs = _llama3_rope_scale(freqs, config)
    if positions.ndim == 3:
        stream = jnp.repeat(jnp.arange(3), jnp.asarray(config.mrope_section), total_repeat_length=half)
        by_stream = positions.astype(jnp.float32)[..., None] * freqs  # [3, B, S, half]
        angles = jnp.take_along_axis(by_stream, stream[None, None, None, :], axis=0)[0]
        return jnp.sin(angles), jnp.cos(angles)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [B, S, half]
    return jnp.sin(angles), jnp.cos(angles)


def _text_positions(positions: jax.Array) -> jax.Array:
    """[B, S] of a [3, B, S] triple: the temporal stream, which is a text
    token's position (the indexer's rotary turns by it)."""
    return positions[0] if positions.ndim == 3 else positions


def _yarn_tables(positions, freqs, config: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """sin/cos [B, S, half] under YaRN (HF rope_scaling type "yarn", DeepSeek-V3's
    form): frequency i is ``f_i (1 - ramp_i) + (f_i / factor) ramp_i`` with
    ``ramp_i = clip((i - low) / (high - low), 0, 1)`` (``config.yarn_blend``:
    the fast dimensions keep their frequency, the slow ones are interpolated),
    and the tables carry mscale(factor, mscale) / mscale(factor,
    mscale_all_dim), which is 1 where the two are equal; the softmax's factor
    is ``config.attn_scale``'s."""
    low, high = config.yarn_blend
    ramp = jnp.clip(
        (jnp.arange(freqs.shape[0], dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0
    )
    freqs = freqs * (1.0 - ramp) + freqs / jnp.float32(config.rope_scaling_factor) * ramp
    angles = positions.astype(jnp.float32)[..., None] * freqs
    factor = config.yarn_mscale(config.rope_scaling_mscale) / config.yarn_mscale(
        config.rope_scaling_mscale_all_dim
    )
    if factor == 1.0:
        return jnp.sin(angles), jnp.cos(angles)
    return jnp.sin(angles) * factor, jnp.cos(angles) * factor


def _llama3_rope_scale(freqs: jax.Array, config: ModelConfig) -> jax.Array:
    """NTK-by-parts scaling (HF rope_scaling type "llama3", used by
    llama-3.1+): low-frequency components slow down by ``factor``; a smooth
    ramp interpolates through the transition wavelength band."""
    factor = jnp.float32(config.rope_scaling_factor)
    low = jnp.float32(config.rope_scaling_low_freq_factor)
    high = jnp.float32(config.rope_scaling_high_freq_factor)
    original = jnp.float32(config.rope_scaling_original_max_seq_len)

    wavelen = 2.0 * jnp.pi / freqs
    low_wavelen = original / low
    high_wavelen = original / high
    # 0 → keep, 1 → fully scaled; linear in inverse wavelength through the band
    smooth = (original / wavelen - low) / (high - low)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    scaled = freqs / factor
    interpolated = (1.0 - smooth) * scaled + smooth * freqs
    return jnp.where(
        wavelen > low_wavelen,
        scaled,
        jnp.where(wavelen < high_wavelen, freqs, interpolated),
    )


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    # x: [B, S, H, D]; half-rotation convention (HF llama/gemma)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin, cos = sin[:, :, None, :], cos[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = xf1 * cos - xf2 * sin
    out2 = xf2 * cos + xf1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def apply_rope_interleaved(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: [B, S, H, D]; pairs (2i, 2i + 1) turned by angle i (GPT-J's
    layout): out[2i] = x[2i] cos_i - x[2i+1] sin_i, out[2i+1] = x[2i+1] cos_i
    + x[2i] sin_i. Each lane takes its pair's other half from a lane roll: a
    reshape to [..., D/2, 2] puts 2 in the lane axis and cost three relayouts
    of q a layer (4% of a prefill segment on a v5e)."""
    xf = x.astype(jnp.float32)
    sin2 = jnp.repeat(sin, 2, axis=-1)[:, :, None, :]
    cos2 = jnp.repeat(cos, 2, axis=-1)[:, :, None, :]
    even = jnp.arange(x.shape[-1]) % 2 == 0
    other = jnp.where(even, -jnp.roll(xf, -1, axis=-1), jnp.roll(xf, 1, axis=-1))
    return (xf * cos2 + other * sin2).astype(x.dtype)


def _softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., D] → int8 values + fp32 scale per leading index (symmetric)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(c, dtype) -> jax.Array:
    """int8 cache dict → values; XLA fuses the convert+mul into the
    attention einsum's operand load, so HBM traffic stays int8."""
    if isinstance(c, dict):
        return (c["q"].astype(jnp.float32) * c["s"][..., None]).astype(dtype)
    return c


def cache_width(cache: KVCache) -> int:
    leaf = cache["k"] if "k" in cache else cache["lat"]
    return (leaf["q"] if isinstance(leaf, dict) else leaf).shape[3]


# ---------------------------------------------------------------------------
# Multi-LoRA: gathered grouped adapter matmul (ROADMAP item 4). The adapter
# pool is a FIXED-shape stacked tree — per projection ``{"a": [L, R, din, r],
# "b": [L, R, r, dout]}`` plus ``"scale": [R]`` — where row 0 is the all-zero
# BASE row (public adapter id -1 maps there) and rows 1..R-1 are hot-swapped
# by serving/adapters.py. Each batch row gathers ITS adapter's factors, so
# one compiled program serves base + N adapters mixed in one dispatch: the
# per-slot ``adapter_rows`` array is data, not a shape. The low-rank product
# accumulates in fp32 (rank-r factors lose precision fast in bf16) and adds
# onto the base projection — mathematically W_i = W + scale_i * A_i @ B_i
# without ever materializing a merged weight per tenant (DeepServe's
# many-logical-models-one-hot-engine multiplexing, PAPERS.md).
# ---------------------------------------------------------------------------

LORA_PROJS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _lora_delta(
    x: jax.Array,  # [B, S, din]
    entry: dict,  # {"a": [R, din, r], "b": [R, r, dout]} (one layer's slice)
    scale: jax.Array,  # [R]
    rows: jax.Array,  # [B] pool row per slot (0 = base/zero row)
) -> jax.Array:
    """Per-slot low-rank correction ``scale_i * (x @ A_i) @ B_i`` with the
    factors gathered by each row's adapter id — the grouped adapter matmul.
    Row 0 is all-zero, so base slots ride the same program at the cost of a
    rank-r matmul against zeros (decode is weight-bandwidth-bound; the
    [B, r] intermediate is noise next to the base projection's stream)."""
    ag = jnp.take(entry["a"], rows, axis=0)  # [B, din, r]
    bg = jnp.take(entry["b"], rows, axis=0)  # [B, r, dout]
    t = jnp.einsum(
        "bsd,bdr->bsr", x.astype(jnp.float32), ag.astype(jnp.float32)
    )
    out = jnp.einsum("bsr,bro->bso", t, bg.astype(jnp.float32))
    sc = jnp.take(scale, rows, axis=0)  # [B]
    return (out * sc[:, None, None]).astype(x.dtype)


def _lora_proj(
    x: jax.Array, proj: str, lora: Optional[dict], lora_scale, rows
) -> jax.Array:
    """Adapter delta for one projection, or a scalar zero when the pool has
    no such projection (MoE layers carry attention-only adapters) or the
    engine runs without adapters at all."""
    if lora is None or proj not in lora:
        return jnp.zeros((), x.dtype)
    return _lora_delta(x, lora[proj], lora_scale, rows)


# ---------------------------------------------------------------------------
# Paged KV pool: ONE page-table-indexed device pool is the serving engine's
# only KV state. Layout [L, P, Hkv, page_size, D] — the same head-major
# trailing (T, D) tiling as make_kv_cache's local cache, with T = one page,
# so the Pallas paged kernel blocks are (page_size, D) slices.
# Slots own PAGES through a host-side table; logical column t of slot b
# lives at (table[b, t // ps], t % ps). Unmapped table entries carry the
# out-of-bounds sentinel (= num_pages), so scatters DROP and gathers CLAMP —
# the mask invariant ("columns beyond the written frontier never enter an
# attention mask until overwritten") makes both harmless, the same way
# bucket padding is.
# ---------------------------------------------------------------------------


def make_page_pool(
    config: ModelConfig, num_pages: int, page_size: int, dtype=None,
    state_rows: int = 0, window_pages: int = 0,
) -> KVCache:
    """Device page pool, the leaves ``config.page_leaves`` names: ``{"k","v"}``
    [L, P, Hkv, ps, D] (or the int8 ``{"q","s"}`` dicts with scales
    [L, P, Hkv, ps]), or, for a model that keeps a latent in place of K and V,
    ``"lat"`` alone [L, P, 1, ps, latent_key_width] (K's layout with one head:
    a token's normed latent and its rotary key in one row); for a model with an
    indexer one more leaf a token, ``"ik"`` [L, P, ps, index_key_width], the
    indexer's key, addressed by the same table and page index as the others
    and written where they are — structurally a
    make_kv_cache with B = pages and T = page_size, so every tree-shaped
    helper (sharding specs, byte accounting, donation) applies unchanged.
    L counts the full-attention layers. A model with recurrent layers keeps
    their state beside the pages, ``"rec"`` (`make_recurrent_state`), one
    row a slot for ``state_rows`` slots. A model with window layers keeps
    THEIR pages in a group of its own, ``"win"``: ``{"k", "v"}`` over the
    window layers and ``window_pages`` pages (0: as many as the full group),
    addressed through a table of its own (`WINDOW`)."""
    pool = make_kv_cache(
        config, num_pages, page_size, dtype=dtype, window_batch=window_pages
    )
    if config.is_recurrent:
        pool["rec"] = make_recurrent_state(config, max(1, state_rows), dtype)
    return pool


def split_rec(pool: KVCache):
    """The pool's pages (a window model's second group, ``"win"``, with
    them) and its recurrent state (None for a model without)."""
    return {k: v for k, v in pool.items() if k != "rec"}, pool.get("rec")


def join_rec(kv: KVCache, rec) -> KVCache:
    return kv if rec is None else {**kv, "rec": rec}


def _no_pattern(config: ModelConfig, what: str) -> None:
    if config.layer_pattern:
        raise NotImplementedError(
            f"{what}: not for a model with a layer pattern ({config.name})"
        )


def _page_index(table: jax.Array, positions: jax.Array, page_size: int,
                num_pages: int) -> tuple[jax.Array, jax.Array]:
    """Logical position → (physical page, in-page offset), the ONE
    definition of the table lookup rule: positions past the table
    (pipelined-chunk overshoot at the cache end) map to the out-of-bounds
    sentinel so scatters DROP instead of clamp-landing on the slot's LAST
    real page."""
    lidx = positions // page_size  # [B, S] logical page per token
    pages = jnp.take_along_axis(
        table, jnp.clip(lidx, 0, table.shape[1] - 1), axis=1
    )  # [B, S] physical page per token
    pages = jnp.where(lidx >= table.shape[1], num_pages, pages)
    return pages, positions % page_size


def _paged_lengths(table: jax.Array, positions: jax.Array, page_size: int,
                   num_pages: int) -> jax.Array:
    """The live length [B] of each row of a decode step, for the paged
    kernel: the position being written plus one, capped by what the row's
    table MAPS (a row's pages are a prefix of its table; the rest carry the
    sentinel). The device's positions advance for every row with every
    step, so without the cap an inactive, padding or warm-up row (table all
    sentinel) would read as long as its stale position says, and a row that
    steps past its reservation inside a chunk would keep growing; with it
    the first has length 0 and the second stops where its pages do. The
    kernel does work only for a length's pages."""
    mapped = (table < num_pages).sum(axis=1, dtype=jnp.int32)
    return jnp.minimum(positions.astype(jnp.int32) + 1, mapped * page_size)


def _paged_scatter(pool, layer, vals: jax.Array, table: jax.Array,
                   positions: jax.Array, page_size: int):
    """Scatter per-token K/V ``vals`` [B, Hkv, S, D] into the pool
    [L, P, Hkv, ps, D] (or its int8 dict) where it lies, at
    ``[layer, table[b, pos // ps], head, pos % ps]``. The layer is an index
    of its own, never folded into the page: the drop sentinel (= P) stays
    out of bounds on the page axis instead of naming layer + 1's page 0.
    Unmapped pages drop the write — padding rows, warmups, and steps past a
    slot's reservation all ride the same drop. The kv head is an index of
    its own, so the chip runs B × Hkv × S point updates of [D] one after
    another, dropped or not (45.8 us a leaf for 512 on a v5e; a window over
    the heads would need the pool relaid heads-minor: PERF.md §6, PR 30):
    a decode step into a bf16 pool goes through ``ops/attention.
    paged_kv_write`` instead, and this is that write's reference, the int8
    pool's write and the multi-token writers' (verify, segments)."""
    num_pages = (pool["q"] if isinstance(pool, dict) else pool).shape[1]
    pages, offs = _page_index(table, positions, page_size, num_pages)
    hkv = vals.shape[1]
    pidx = pages[:, None, :]  # [B, 1, S]
    oidx = offs[:, None, :]
    hidx = jnp.arange(hkv)[None, :, None]
    if isinstance(pool, dict):
        q, s = _quantize_kv(vals)
        return {
            "q": pool["q"].at[layer, pidx, hidx, oidx].set(q, mode="drop"),
            "s": pool["s"].at[layer, pidx, hidx, oidx].set(s, mode="drop"),
        }
    return pool.at[layer, pidx, hidx, oidx].set(
        vals.astype(pool.dtype), mode="drop"
    )


def _paged_gather(pool, layer, table: jax.Array, page_size: int):
    """Materialize the dense head-major view of every slot's logical columns
    of one layer: [L, P, Hkv, ps, D] gathered through ``(layer, table)``
    [B, Tp] in ONE gather → [B, Hkv, Tp×ps, D] (int8 dicts gather q and s
    alike, feeding the existing hoisted-scale attention math untouched).
    Sentinel table entries clamp to the last page, which the mask hides.
    This is the masked-jnp fallback read — exactness-bearing on CPU; on TPU
    the Pallas ragged-paged kernel reads pages in place instead
    (ops/attention.py)."""
    def gather(a):
        b, tp = table.shape
        g = a.at[layer, table].get(mode="clip")  # [B, Tp, Hkv, ps, ...]
        g = jnp.moveaxis(g, 2, 1)  # [B, Hkv, Tp, ps, ...]
        return g.reshape((b, a.shape[2], tp * page_size) + a.shape[4:])

    if isinstance(pool, dict):
        return {"q": gather(pool["q"]), "s": gather(pool["s"])}
    return gather(pool)


def attention(
    q: jax.Array,  # [B, S, H, D]
    k,  # [B, Hkv, T, D] head-major array, or int8 {"q","s"} cache entry
    v,
    mask: jax.Array,  # [B, S, T] bool — True = attend
    config: ModelConfig,
) -> jax.Array:
    """GQA attention, fp32 softmax. S=query len, T=key len (cache width).

    int8 caches: the per-token scales are hoisted OUT of the [.., T, D]
    operands onto the [.., T]-shaped scores/probs (D-times less scale math;
    the bare int8→bf16 convert fuses into the MXU operand load) — the
    product is mathematically identical to dequantize-then-matmul."""
    h, hkv = config.n_heads, config.n_kv_heads
    group = h // hkv
    b, s, _, d = q.shape
    qg = q.reshape(b, s, hkv, group, d)
    if isinstance(k, dict):
        # int8×int8 MXU path: quantize q per-vector, dot in s8 (s32 accum),
        # apply both scales on the [.., T]-shaped scores — the int8 cache is
        # read raw, no bf16 materialization
        qq, qs = _quantize_kv(qg)  # [B,S,Hkv,G,D] int8, [B,S,Hkv,G] f32
        scores = jnp.einsum(
            "bshgd,bhtd->bhgst", qq, k["q"], preferred_element_type=jnp.int32
        ).astype(jnp.float32)
        scores = scores * qs.transpose(0, 2, 3, 1)[:, :, :, :, None]
        scores = scores * k["s"][:, :, None, None, :]
    else:
        scores = jnp.einsum("bshgd,bhtd->bhgst", qg, k).astype(jnp.float32)
    if config.has_latent:  # its scale may carry YaRN's softmax factor
        scores = scores * config.attn_scale
    else:
        scores = scores / jnp.sqrt(jnp.float32(d))
    scores = _softcap(scores, config.attn_logit_softcap)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if isinstance(v, dict):
        # fold v's per-token scale into probs (it rides the contraction),
        # re-quantize the weighted probs per-row, dot in s8
        pv = probs * v["s"][:, :, None, None, :]
        pq, ps = _quantize_kv(pv)  # int8 [B,Hkv,G,S,T], f32 [B,Hkv,G,S]
        out = jnp.einsum(
            "bhgst,bhtd->bshgd", pq, v["q"], preferred_element_type=jnp.int32
        ).astype(jnp.float32)
        out = (out * ps.transpose(0, 3, 1, 2)[..., None]).astype(q.dtype)
    else:
        out = jnp.einsum("bhgst,bhtd->bshgd", probs.astype(q.dtype), v)
    return out.reshape(b, s, -1)  # H x the value's width (a latent model's is its own)


def _seen(positions: jax.Array, t: int, window: int = 0, kv_limit=None) -> jax.Array:
    """[B, S, T] bool, what the masked jnp path reads by: the query at
    ``positions[b, j]`` sees the columns up to its own, the last ``window`` of
    them under a window, none from ``kv_limit`` on (a cache wider than what
    was written)."""
    kv_pos = jnp.arange(t)[None, None, :]
    mask = kv_pos <= positions[:, :, None]
    if window:
        mask = mask & (kv_pos > positions[:, :, None] - window)
    if kv_limit is not None:
        mask = mask & (kv_pos < kv_limit)
    return mask


def _dispatch_attention(
    q: jax.Array,  # [B, S, H, D]
    k_all,  # [B, Hkv, T, D] array, or int8 {"q","s"} dict (cache width or S)
    v_all,
    mask: Optional[jax.Array],  # [B, S, T]; None: `_seen`, made where it is read
    config: ModelConfig,
    causal: bool,
    what: Optional[str] = None,  # `note_path`'s kind; None: by the shapes
    positions: Optional[jax.Array] = None,  # [B, S]: the queries', where they may start past 0
    window: int = 0,
    from_zero: bool = True,  # query j stands at column j
    kv_limit=None,
) -> jax.Array:
    """The one chooser of what S > 1 queries over a row's columns read
    through: a kernel that never holds the scores where the shapes fit TPU
    tiling, else the masked jnp reference (a single query's path always).
    Semantics identical; ops/attention has the kernels. Causal queries from
    column 0 that see all of one another (no window, or one that holds them)
    take the prefill kernel over the first S columns; queries at
    ``positions`` take the segment kernel over whole lane tiles of columns,
    under the window if there is one."""
    from langstream_tpu.ops.attention import (
        flash_prefill_attention,
        flash_segment_attention,
        note_path,
        pallas_ok,
    )

    s = q.shape[1]
    t = (k_all["q"] if isinstance(k_all, dict) else k_all).shape[2]
    kernels = s > 1 and causal and pallas_ok(config, s)
    interpret = jax.default_backend() != "tpu"
    if kernels and from_zero and (not window or s <= window):
        # prefill/full forward: causal over the first s cache columns (int8
        # caches dequantize just the prompt-wide slice — prefill is
        # compute-bound, the materialized slice is small)
        ksl = jax.tree.map(lambda x: x[:, :, :s], k_all)
        vsl = jax.tree.map(lambda x: x[:, :, :s], v_all)
        note_path(what or "prefill", "flash_prefill_attention", config, s=s, t=t)
        return flash_prefill_attention(
            q, _dequantize_kv(ksl, q.dtype), _dequantize_kv(vsl, q.dtype), config,
            interpret=interpret,
        )
    if kernels and positions is not None and t % min(128, t) == 0:
        note_path(what, "flash_segment_attention", config, s=s, t=t)
        return flash_segment_attention(
            q, k_all, v_all, positions[:, 0], config, window=window, interpret=interpret
        )
    # jnp path handles int8 cache dicts natively (hoisted-scale einsums)
    what = what or ("decode" if s == 1 else "prefill" if causal else "encode")
    note_path(what, "jnp", config, s=s, t=t)
    if mask is None:
        mask = _seen(positions, t, window, kv_limit)
    return attention(q, _unpacked(k_all, config), _unpacked(v_all, config), mask, config)


def _activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def dense_ffn(
    x: jax.Array, lp: dict, config: ModelConfig,
    lora: Optional[dict] = None, lora_scale=None, adapter_rows=None,
) -> jax.Array:
    gate = _activation(
        quantized_matmul(x, lp["w_gate"])
        + _lora_proj(x, "w_gate", lora, lora_scale, adapter_rows),
        config.activation,
    )
    up = quantized_matmul(x, lp["w_up"]) + _lora_proj(
        x, "w_up", lora, lora_scale, adapter_rows
    )
    h = gate * up
    return quantized_matmul(h, lp["w_down"]) + _lora_proj(
        h, "w_down", lora, lora_scale, adapter_rows
    )


def moe_ffn(x: jax.Array, lp: dict, config: ModelConfig) -> jax.Array:
    """Mixture-of-experts via dispatch/combine einsums (GShard pattern).

    Tokens route to top-k experts with a capacity limit; the [T,E,C] dispatch
    tensor keeps every shape static so the expert axis ("expert") shards over
    ICI with no data-dependent control flow. Overflowing tokens fall back to
    their residual stream (standard token-dropping).
    """
    return moe_ffn_counted(x, lp, config)[0]


def moe_ffn_counted(
    x: jax.Array, lp: dict, config: ModelConfig,
    token_valid: Optional[jax.Array] = None,  # [B, S] bool — real tokens
) -> tuple[jax.Array, jax.Array]:
    """`moe_ffn` plus its MOE_COUNTS vector. The counts read ``keep`` and
    change nothing the output is computed from: padding still routes and
    still takes capacity (masking it out is ROADMAP S5, not this)."""
    b, s, d = x.shape
    t = b * s
    e, k = config.n_experts, config.n_experts_per_tok
    xf = x.reshape(t, d)

    with jax.named_scope("moe_ffn.route"):
        logits = (xf @ lp["router"]).astype(jnp.float32)  # [T, E]
        weights, chosen = lax.top_k(logits, k)  # [T, k]
        weights = jax.nn.softmax(weights, axis=-1)

    # Capacity bounds the [T,E,C] dispatch tensor to linear in T. factor<=0
    # restores lossless C=T (exactness tests); the floor keeps tiny decode
    # batches from dropping tokens when T is comparable to E.
    factor = config.moe_capacity_factor
    if factor and factor > 0:
        capacity = min(t, max(math.ceil(t * k * factor / e), min(t, 64)))
    else:
        capacity = t
    with jax.named_scope("moe_ffn.dispatch"):
        # position of each (token, slot) within its expert's capacity buffer
        onehot = jax.nn.one_hot(chosen, e, dtype=jnp.int32)  # [T, k, E]
        flat = onehot.reshape(t * k, e)
        pos_in_expert = jnp.cumsum(flat, axis=0) - 1  # [T*k, E]
        pos = (pos_in_expert * flat).sum(-1).reshape(t, k)  # [T, k]
        keep = pos < capacity

        # dispatch: [T, E, C]
        dispatch = (
            jax.nn.one_hot(chosen, e, dtype=xf.dtype)[..., None]
            * jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity, dtype=xf.dtype)[
                :, :, None, :
            ]
        ).sum(axis=1)
        # combine weights per (token, expert, cap-slot)
        combine = (
            jax.nn.one_hot(chosen, e, dtype=jnp.float32)[..., None]
            * jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity, dtype=jnp.float32)[
                :, :, None, :
            ]
            * weights[..., None, None]
        ).sum(axis=1)
        dropped = ~keep  # [T, k]
        if token_valid is None:
            routed_real = jnp.int32(t * k)
            dropped_real = dropped.sum(dtype=jnp.int32)
        else:
            real = token_valid.reshape(t)
            routed_real = real.sum(dtype=jnp.int32) * k
            dropped_real = (dropped & real[:, None]).sum(dtype=jnp.int32)
        counts = jnp.stack([
            jnp.int32(t * k), dropped.sum(dtype=jnp.int32), routed_real,
            dropped_real,
        ])

    def expert_w(name: str) -> jax.Array:
        w = lp[name]
        return dequantize_weight(w, xf.dtype) if is_quantized(w) else w

    with jax.named_scope("moe_ffn.experts"):
        expert_in = jnp.einsum("tec,td->ecd", dispatch, xf)  # [E, C, D]
        gate = _activation(
            jnp.einsum("ecd,edf->ecf", expert_in, expert_w("w_gate")),
            config.activation,
        )
        up = jnp.einsum("ecd,edf->ecf", expert_in, expert_w("w_up"))
        expert_out = jnp.einsum(
            "ecf,efd->ecd", gate * up, expert_w("w_down")
        )  # [E, C, D]
    with jax.named_scope("moe_ffn.combine"):
        out = jnp.einsum("tec,ecd->td", combine.astype(xf.dtype), expert_out)
    return out.reshape(b, s, d), counts


# ---------------------------------------------------------------------------
# Layer + model
# ---------------------------------------------------------------------------



# ---------------------------------------------------------------------------
# Learned sparse attention (docs/SERVING.md "A model whose attention reads a
# learned selection"). An INDEXER beside the attention's projections scores
# every visible token for a query, ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
# kI[s])`` in float32, and the query attends to the ``index_topk`` tokens of
# largest score (a tie to the lower position, `lax.top_k`'s rule), one
# selection a token and layer for all heads. The indexer's key ``kI`` is a
# third leaf a token of the cache and of the page pool, ``"ik"``. Three
# scopes inside ``attention``: ``attention.index`` (projections, norm,
# rotary, scores; the scores alone ``attention.index.scores`` inside it),
# ``attention.select`` (the ranking; with the kernels a segment's scores are
# made and ranked there in one call), ``attention.sparse`` (the selected read
# and the attention over it).
# ---------------------------------------------------------------------------


def _index_proj(u, lp, positions, config, c_q=None, rotary=None):
    """The indexer's three projections at text positions [B, S]: queries
    [B, S, Hi, Di], read from the attention's normed input ``u`` [B, S, d] or,
    with ``config.index_query_input`` "query_latent", from the model's normed
    query latent ``c_q``; the key [B, S, Di] from ``u`` through its LayerNorm;
    the heads' weights [B, S, Hi] from ``u`` in float32, scaled by
    1 / sqrt(Hi Di). What turns: the indexer's WHOLE head by a rotary of its
    own (pairs (i, i + Di/2), frequencies theta^(-2i/Di)), or, with
    ``config.index_rope_dim``, each head's FIRST that many lanes by the
    attention's own angles ``rotary`` = (sin, cos) in the attention's pairs
    (interleaved where ``rope_interleaved``), the rest unturned."""
    b, s, _ = u.shape
    hi, di = config.index_n_heads, config.index_head_dim
    turned = config.index_rope_dim
    if not turned:
        freqs = config.rope_theta ** (-jnp.arange(0, di // 2, dtype=jnp.float32) / (di // 2))
        angles = positions.astype(jnp.float32)[..., None] * freqs
        sin, cos = jnp.sin(angles), jnp.cos(angles)
    q_in = c_q if config.index_query_input == "query_latent" else u
    q = quantized_matmul(q_in, lp["wq_idx"]).reshape(b, s, hi, di)
    k = layer_norm(quantized_matmul(u, lp["wk_idx"]), lp["idx_norm"], config.rms_norm_eps)
    k = k + lp["idx_bias"].astype(k.dtype)
    w = jnp.dot(
        u.astype(jnp.float32), lp["w_idx"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ) * (hi * di) ** -0.5
    if not turned:
        return apply_rope(q, sin, cos), apply_rope(k[:, :, None, :], sin, cos)[:, :, 0], w
    turn = functools.partial(_turn_first, width=turned, rotary=rotary, config=config)
    return turn(q), turn(k[:, :, None, :])[:, :, 0], w


def _turn_first(x, width, rotary, config):
    """x [B, S, H, D] with its first ``width`` lanes turned by ``rotary`` =
    (sin, cos) [B, S, width / 2], in the model's pairs."""
    turn = apply_rope_interleaved if config.rope_interleaved else apply_rope
    return jnp.concatenate([turn(x[..., :width], *rotary), x[..., width:]], axis=-1)


def _selection_kernels(config, s: int, t: int) -> bool:
    """Whether S > 1 queries over T columns take the selection's kernels
    (`segment_select`, `sparse_segment_attention`): the prefill kernel's gate
    and whole lane tiles of columns."""
    from langstream_tpu.ops.attention import pallas_ok

    return s > 1 and pallas_ok(config, s) and t % min(128, t) == 0


def _index_scores(q_idx, w, k_idx):
    """[B, S, T] float32: every query's score of every column, one einsum
    (a decode step's row, and a segment where the kernels' tiles do not fit:
    with them `ops/attention.segment_select` scores in tiles and ranks where
    they lie). A score of -0.0 (every head's ReLU shut) reads +0.0: one order
    for floats and for their bits."""
    dots = jnp.einsum("bshd,btd->bsht", q_idx, k_idx, preferred_element_type=jnp.float32)
    return jnp.einsum("bsht,bsh->bst", jax.nn.relu(dots), w) + 0.0


def _select_mask(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """[.., T] bool: of each row's ``visible`` columns the ``min(k, their
    number)`` of largest ``scores`` (float32), a tie to the lower column:
    `lax.top_k`'s set, found by counting. The k-th largest is bisected over
    the float's 32 bits (a float's order is its bits' once the sign is
    folded), 32 counts of the row against a sort's log^2; the tie rule costs
    a running count along the row only where a row holds more columns AT the
    threshold than it may keep."""
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    key = lax.bitcast_convert_type(bits ^ ((bits >> 31) & 0x7FFFFFFF), jnp.uint32)
    key = jnp.where(visible, key ^ jnp.uint32(0x80000000), jnp.uint32(0))
    keep = jnp.minimum(visible.sum(-1, dtype=jnp.int32), k)  # [..]

    def count_at_least(threshold):
        return (key >= threshold[..., None]).sum(-1, dtype=jnp.int32)

    def bit(i, found):
        tried = found | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        return jnp.where(count_at_least(tried) >= keep, tried, found)

    kth = lax.fori_loop(0, 32, bit, jnp.zeros(keep.shape, jnp.uint32))
    above = visible & (key > kth[..., None])

    def with_ties(_):
        at = visible & (key == kth[..., None])
        room = keep - above.sum(-1, dtype=jnp.int32)
        return above | (at & (jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= room[..., None]))

    def without(_):
        return visible & (key >= kth[..., None])

    return lax.cond(jnp.any(count_at_least(kth) > keep), with_ties, without, None)


def _selected_attention(q, q_idx, w, k_idx_all, k_all, v_all, mask, positions, config, what):
    """S > 1 queries a row under the selection: ``k_all``/``v_all``
    [B, Hkv, T, D] and ``k_idx_all`` [B, T, Di] the row's columns, ``mask``
    [B, S, T] what each query may see at all: causal, column <= position, in
    every caller (`_paged_mask`, `forward`, `prefill`). With the kernels, the
    scores and the ranking in ONE call whose tiles of scores never leave
    VMEM (`ops/attention.segment_select`: `_select_mask`'s set to the bit,
    the mask read off ``positions``), and the attention a walk over key
    blocks with the selection as a packed mask
    (`ops/attention.sparse_segment_attention`: the scores of [S, heads, T]
    are never held); the einsum, `_select_mask` and masked jnp where the
    kernels' tiles do not fit."""
    from langstream_tpu.ops import attention as ops

    s, t = q.shape[1], k_all.shape[2]
    if _selection_kernels(config, s, t):
        interpret = jax.default_backend() != "tpu"
        with jax.named_scope("attention.select"):
            ops.note_path(f"{what}-select", "segment_select", config, s=s, t=t)
            chosen = ops.segment_select(
                q_idx, w, k_idx_all, positions[:, 0], config.index_topk, interpret=interpret
            )
        with jax.named_scope("attention.sparse"):
            ops.note_path(f"{what}-sparse", "sparse_segment_attention", config, s=s, t=t)
            return ops.sparse_segment_attention(
                q, k_all, v_all, positions[:, 0], chosen, config, interpret=interpret
            )
    with jax.named_scope("attention.index"), jax.named_scope("attention.index.scores"):
        scores = _index_scores(q_idx, w, k_idx_all)
    with jax.named_scope("attention.select"):
        chosen = _select_mask(scores, mask, config.index_topk)
    with jax.named_scope("attention.sparse"):
        ops.note_path(f"{what}-sparse", "jnp", config, s=s, t=t)
        return attention(q, k_all, v_all, chosen, config)


def _decode_index_scores(q_idx, w, pik, table, layer, config, page_size):
    """[B, T] float32: one query a row scores every column of its table. The
    row's cached indexer keys are read by whole pages (8 KiB a page and
    layer at 64 x 64 bf16)."""
    b, t = table.shape[0], table.shape[1] * page_size
    with jax.named_scope("attention.index"), jax.named_scope("attention.index.scores"):
        k_idx = pik.at[layer, table].get(mode="clip").reshape(b, t, -1)[..., :q_idx.shape[-1]]
        return _index_scores(q_idx[:, None], w[:, None], k_idx)[:, 0]


def _sparse_decode_attention(
    q, q_idx, w, pk, pv, pik, table, layer, lengths, config, page_size
):
    """One query a row under the selection, through the page table → [B, H*D],
    by a GATHER of what it selected: the row's columns are scored and ranked
    (`lax.top_k`: the indices are what the read needs), and ONLY the
    selected tokens' K and V rows are gathered, [B, topk, Hkv, D]: the bytes
    follow ``min(length, topk)`` a row, never its length. The read of a table
    too long to walk and of every backend without the kernels
    (`_paged_selected_read` has the rule). A row of length 0 (idle) comes
    back zeros."""
    b, h, d = q.shape
    hkv = pk.shape[2]
    t = table.shape[1] * page_size
    k = min(config.index_topk, t)
    scores = _decode_index_scores(q_idx, w, pik, table, layer, config, page_size)
    with jax.named_scope("attention.select"):
        visible = jnp.arange(t)[None, :] < lengths[:, None]
        _, chosen = lax.top_k(jnp.where(visible, scores, -jnp.inf), k)  # [B, k]
        kept = jnp.arange(k)[None, :] < jnp.minimum(lengths, k)[:, None]
    with jax.named_scope("attention.sparse"):
        # the pool seen as rows [L x P x Hkv x ps, D] (merging major
        # dimensions moves no byte) and ONE index a (token, head) row: 0.65
        # ms a layer and leaf at 8 x 2,048 tokens on a v5e against 0.96 for
        # the four-index form. A token's heads as one [Hkv, D] slice gathers
        # in 0.67 alone, but inside the step the compiler then lays BOTH
        # pool leaves out heads-minor and copies each whole, 1.6 GB, every
        # layer and step (PERF.md section 6, PR 43)
        pages = jnp.take_along_axis(table, chosen // page_size, axis=1)
        rows = (
            ((layer * pk.shape[1] + pages)[:, :, None] * hkv + jnp.arange(hkv)[None, None, :])
            * page_size + (chosen % page_size)[:, :, None]
        )  # [B, k, Hkv]
        ks = jnp.take(pk.reshape(-1, d), rows, axis=0, mode="clip")  # [B, k, Hkv, D]
        vs = jnp.take(pv.reshape(-1, d), rows, axis=0, mode="clip")
        qg = q.reshape(b, hkv, h // hkv, d)
        logits = jnp.einsum("bhgd,bkhd->bhgk", qg, ks, preferred_element_type=jnp.float32)
        logits = _softcap(logits * d**-0.5, config.attn_logit_softcap)
        logits = jnp.where(kept[:, None, None, :], logits, -1e30)
        top = logits.max(axis=-1, keepdims=True)
        probs = jnp.where(kept[:, None, None, :], jnp.exp(logits - top), 0.0)
        out = jnp.einsum(
            "bhgk,bkhd->bhgd", probs.astype(q.dtype), vs, preferred_element_type=jnp.float32
        )
        out = out / jnp.maximum(probs.sum(axis=-1, keepdims=True), 1e-30)
    return out.astype(q.dtype).reshape(b, h * d)


def _write_index_key(pik, layer, k_idx, table, positions, page_size):
    """The indexer's key of each token [B, S, Di] into the pool's third leaf
    [L, P, ps, Di] at ``[layer, table[b, pos // ps], pos % ps]``; an unmapped
    page drops the write, as K's and V's does."""
    pages, offs = _page_index(table, positions, page_size, pik.shape[1])
    return pik.at[layer, pages, offs].set(_kept_width(k_idx, pik), mode="drop")


def _kept_width(k_idx, leaf):
    """The indexer's key [.., Di], or a token's latent, as the cache keeps
    it: padded with zeros to the leaf's width (``config.index_key_width``,
    ``config.latent_key_width``), in its dtype."""
    pad = leaf.shape[-1] - k_idx.shape[-1]
    return jnp.pad(k_idx.astype(leaf.dtype), [(0, 0)] * (k_idx.ndim - 1) + [(0, pad)])


def _copies_pages(leaf, width: int, page_size: int, config: Optional[ModelConfig]) -> bool:
    """Whether ``width`` columns a row reach ``leaf`` of the page pool as
    copies of whole pages (`ops/attention.paged_insert_pages` and its
    one-layer form), the rule an admission group's insert and a segment's
    write share: a leaf that holds values alone (an int8 pool's scales are
    words scattered over a page, no copy Mosaic takes), a width of whole
    pages, no mesh (``config.kernel_mesh``: GSPMD cannot partition the
    kernel), and where the paged decode kernel runs (``paged_pallas_ok``).
    Without a ``config`` (a caller off the engine, which holds no mesh) the
    gates are those of ``attention_impl: auto``."""
    from langstream_tpu.ops.attention import paged_pallas_ok, paged_tiles_ok

    if isinstance(leaf, dict) or width % page_size:
        return False
    if config is None:
        return paged_tiles_ok(leaf.shape[-1], page_size)
    return config.kernel_mesh is None and paged_pallas_ok(config, page_size)


def segment_copies_pages(pool: KVCache, width: int, page_size: int, config: ModelConfig) -> bool:
    """Whether a causal prefill segment of ``width`` tokens a row that starts
    on a page's edge writes its rows into ``pool`` by whole pages
    (`_paged_write_rows`, which asks the same `_copies_pages` of the leaves it
    is handed; where the segment starts is a runtime value, a `lax.cond`
    there): the engine, which knows each segment's offset, counts its
    segments by writer with this."""
    return _copies_pages(pool[config.page_leaves[0]], width, page_size, config)


def _paged_write_rows(pools, rows, layer, table, positions, page_size, config, segment):
    """S new tokens a row into the page pool's leaves ``pools`` at ``layer``,
    where they lie, the leaves back in their order: ``rows`` hold a leaf
    each, K, V and a latent head-major [B, Hkv, S, D], the indexer's key
    [B, S, Di] (it comes last). The scatter (`_paged_scatter`,
    `_write_index_key`: an update a (row, kv head, position), dropped or not)
    is every writer's reference and the write of a verify step, a block pass
    into an int8 pool, the int8 pool, a mesh and every backend without the
    kernels. A causal prefill ``segment`` of whole pages into leaves that
    hold values alone (`_copies_pages`) goes by whole pages instead, ONE copy
    HBM → HBM a (row, mapped page) and leaf from inside the layer loop
    (`ops/attention.paged_insert_layer_pages`: 64 copies a layer for a
    2,048-token segment's K and V where the scatter runs 8,192 to 16,384
    updates of 256 B), provided every row starts on a page's edge: a runtime
    value (a warm suffix may start inside a page), so where one does not the
    kernel is handed the sentinel for every page, which copies nothing, and
    the scatter runs after it, the body of a loop of 0 or 1 trips. (Not a
    `lax.cond` with the two writers as its branches: compiled for a v5e, that
    relays the latent's one-head leaf `[L, P, 1, ps, 640]` for its branch and
    copies it whole, in and out, every layer.) The pool after either is the
    same to the bit: the same rows to the same places, the padded tail's with
    them, an unmapped page dropped."""
    from langstream_tpu.ops import attention as ops

    def scatter(pools):
        return tuple(
            (_paged_scatter if new.ndim == 4 else _write_index_key)(
                pool, layer, new, table, positions, page_size
            )
            for pool, new in zip(pools, rows)
        )

    s = positions.shape[1]
    by_page = segment and _copies_pages(pools[0], s, page_size, config)
    if segment:
        ops.note_grid(
            f"paged-segment-write[s={s}]", "paged_insert_pages" if by_page else "scatter"
        )
    if not by_page:
        return scatter(pools)
    num_pages = pools[0].shape[1]
    pages, offs = _page_index(table, positions[:, ::page_size], page_size, num_pages)
    on_edge = jnp.all(offs[:, 0] == 0)
    pools = ops.paged_insert_layer_pages(
        [r if r.ndim == 4 else _kept_width(r, pools[-1]) for r in rows], pools,
        jnp.where(on_edge, pages, num_pages), layer, interpret=jax.default_backend() != "tpu",
    )
    return lax.fori_loop(
        0, 1 - on_edge.astype(jnp.int32), lambda _, pools: scatter(pools), tuple(pools)
    )


def _attention_block(
    x: jax.Array,
    lp: dict,
    sin: jax.Array,
    cos: jax.Array,
    mask: jax.Array,
    config: ModelConfig,
    cache_kv: Optional[tuple[jax.Array, jax.Array]] = None,
    cache_positions: Optional[jax.Array] = None,
    causal: bool = True,
    collect_kv: bool = False,
    verify: bool = False,
    paged_table: Optional[jax.Array] = None,  # [B, Tp] physical pages
    page_size: int = 0,
    lora: Optional[dict] = None,  # per-layer adapter slices {proj: {a, b}}
    lora_scale: Optional[jax.Array] = None,  # [R] per-adapter scale
    adapter_rows: Optional[jax.Array] = None,  # [B] pool row per slot
    layer: Optional[jax.Array] = None,  # scalar: this block's layer of the pool
    block: bool = False,  # a block pass (`paged_block_step_inplace`)
    lengths: Optional[jax.Array] = None,  # [B]: a latent window kind's decode step's
) -> tuple[jax.Array, Optional[tuple[jax.Array, jax.Array]]]:
    """The attention half of a block (norm, QKV, rotary, cache write, the
    kernel or jnp path, output projection, residual): the layer's input in,
    the FFN's input and the layer's new cache entry out. It names its own
    scopes: all of it is ``attention``, but for the paged branch's write of
    the new K/V rows, which is ``kv_pool.write``. With ``paged_table`` set,
    ``cache_kv`` is the WHOLE pool and comes back whole, written and read at
    ``layer`` by `_paged_attention`: nothing of a layer's size is formed.
    ``block``: the S queries of a row are one block of a model that fills
    blocks; all of them see keys ``0 .. start + S - 1``. A model with an
    indexer (``config.has_indexer``) carries a third pool leaf in
    ``cache_kv``, the indexer's keys; its decode step attends to the selected
    tokens alone and its segment reads under the selection once a query sees
    more than ``index_topk`` keys (`_paged_selected_read`). A model that
    keeps a LATENT (``config.has_latent``) has an attention half of its own
    behind this entry, `_latent_attention_block`: ``cache_kv`` is then the
    latent leaf and, where the model has an indexer, its keys."""
    if config.has_latent:
        if lora is not None or verify or block:
            raise NotImplementedError(
                f"{config.name}: no adapter terms, verify or block pass over a latent"
            )
        return _latent_attention_block(
            x, lp, sin, cos, mask, config, cache_kv, cache_positions, causal, collect_kv,
            paged_table, page_size, layer, lengths=lengths,
        )
    if paged_table is None:
        with jax.named_scope("attention"):
            return _dense_attention(
                x, lp, sin, cos, mask, config, cache_kv, cache_positions,
                causal, collect_kv, lora, lora_scale, adapter_rows,
            )
    assert cache_kv is not None and cache_positions is not None
    with jax.named_scope("attention"):
        q, k, v = _qkv(x, lp, sin, cos, config, lora, lora_scale, adapter_rows)
        index = None
        if config.has_indexer:
            with jax.named_scope("attention.index"):
                index = _index_proj(
                    rms_norm(x, lp["attn_norm"], config.rms_norm_eps), lp,
                    cache_positions, config,
                )
    # the caller's mask, not the core's own: this block's segment reads masked
    # jnp, as it always has (handing it to the segment kernel where the pool
    # is bf16 changes Mistral's and Mixtral's programs: ROADMAP D15)
    attn, leaves = _paged_attention(
        q, k, v, cache_kv, paged_table, cache_positions, layer, page_size, config,
        mask=mask, index=index, block=block, verify=verify,
    )
    with jax.named_scope("attention"):
        x = _attn_residual(
            x, quantized_matmul(attn, lp["wo"]), lp, config,
            _lora_proj(attn, "wo", lora, lora_scale, adapter_rows),
        )
    return x, leaves


@contextlib.contextmanager
def _attention_scope(sub: Optional[str] = None):
    """``attention``, and inside it ``sub`` (``attention.window``, ``.full``)."""
    with jax.named_scope("attention"), (
        jax.named_scope(sub) if sub else contextlib.nullcontext()
    ):
        yield


def _paged_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, Hkv, D]: the S tokens' new rows, as are ``v``
    v: jax.Array,
    leaves: tuple,  # the pool's K and V [L, P, Hkv, ps, D] (int8: dicts), the indexer's keys
    table: jax.Array,  # [B, Tp] physical page per logical page
    positions: jax.Array,  # [B, S]
    layer: jax.Array,  # scalar: the pool's layer read and written
    page_size: int,
    config: ModelConfig,
    mask: Optional[jax.Array] = None,  # [B, S, T]; None: causal, within ``window``
    window: int = 0,  # 0: none
    lengths: Optional[jax.Array] = None,  # [B], where the caller has them
    index: Optional[tuple] = None,  # the indexer's (queries, key, weights) of the S tokens
    block: bool = False,  # a block pass: the S queries of a row see one another
    verify: bool = False,
    sub_scope: Optional[str] = None,  # the read's scope inside ``attention``
) -> tuple[jax.Array, tuple]:
    """A layer's new K/V rows reach the page pool and its queries read it:
    (the attention's output [B, S, H*D], the pool's leaves whole, written at
    ``layer``). The ONE place that decides, from S, the page size, the
    pool's dtype and `paged_pallas_ok`, which kernel writes and which reads.
    The write is ``kv_pool.write`` (a scope cannot be left from inside, so it
    is outside ``attention``): ``paged_kv_write`` where the
    decode kernel runs over a bf16 pool, a copy a live row, else
    `_paged_scatter`; the indexer's key with them. The read: one query a row
    through the paged decode kernel, from ``lengths - window`` on under a
    window; a block pass's S queries (``block``: all see keys ``0 .. start +
    S - 1``) through that kernel with S x group query rows a KV head
    and no mask among them, their write the decode write over S rows of one
    aligned tile; a model with an indexer through `_paged_selected_read`;
    everything else over the row's gathered columns: S > 1 causal queries
    (``mask`` None) through `_dispatch_attention`'s kernels, a caller's mask
    (a block pass, verify, the sequential block's segment) and the backends
    without kernels through its masked jnp."""
    from langstream_tpu.ops import attention as ops

    pk, pv, *pik = leaves
    s = q.shape[1]
    int8 = isinstance(pk, dict)
    num_pages = (pk["q"] if int8 else pk).shape[1]
    interpret = jax.default_backend() != "tpu"
    decode_kernels = s == 1 and ops.paged_pallas_ok(config, page_size)
    block_kernels = block and not int8 and ops.paged_pallas_ok(config, page_size)
    # a block starts on a multiple of S inside a page: its S rows lie in one
    # aligned tile of the pool
    tile = block_kernels and ops.block_write_ok(s, page_size)
    with jax.named_scope("kv_pool.write"):
        if tile or (decode_kernels and not int8):
            # a copy per LIVE row. The int8 pool (a token's scales are Hkv
            # scattered words, no DMA Mosaic takes) and the other S > 1
            # writers keep the scatter
            pages, offs = _page_index(
                table, positions[:, :1] if tile else positions, page_size, num_pages
            )
            rows = (
                (k.reshape(k.shape[0], -1, k.shape[-1]), v.reshape(v.shape[0], -1, v.shape[-1]))
                if tile else (k[:, 0], v[:, 0])
            )
            pk, pv = ops.paged_kv_write(
                rows, pk, pv, pages[:, 0], offs[:, 0], layer, config, interpret=interpret
            )
            if config.kv_head_pack > 1:
                # a model new with its packed rows says who writes them (every
                # other model's notes are the ones they were: the write rides
                # the paged decode's entry, the same gate admits both)
                ops.note_grid(f"paged-decode-write[s={s}]", "paged_kv_write")
            if index is not None:
                pik = [_write_index_key(pik[0], layer, index[1], table, positions, page_size)]
        else:
            rows = [k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)]
            if index is not None:
                rows.append(index[1])
            pk, pv, *pik = _paged_write_rows(
                (pk, pv, *pik), rows, layer, table, positions, page_size, config,
                segment=s > 1 and not block and not verify,
            )
    with _attention_scope(sub_scope):
        t = table.shape[1] * page_size
        if index is not None:
            attn = _paged_selected_read(
                q, index[0], index[2], pk, pv, pik[0], table, layer, mask,
                positions, config, page_size, decode_kernels,
            )
        elif decode_kernels or block_kernels:
            if lengths is None:  # up to the query, or a block's last
                lengths = _paged_lengths(
                    table, positions[:, 0 if decode_kernels else -1], page_size, num_pages
                )
            if not decode_kernels:
                ops.note_path("paged-block", "ragged_paged_block_attention", config, s=s, t=t)
                attn = ops.ragged_paged_block_attention(
                    q, pk, pv, lengths, table, layer, config, page_size, interpret=interpret
                )
            else:
                kernel = (
                    ops.ragged_paged_decode_attention_int8 if int8
                    else ops.ragged_paged_decode_attention
                )
                ops.note_path("paged-decode", kernel.__name__, config, s=s, t=t)
                q0 = q[:, 0]
                # (no window model keeps an int8 pool, whose kernel takes no ``lower``)
                bound = {"lower": jnp.maximum(lengths - window, 0)} if window else {}
                attn = kernel(
                    q0, pk, pv, lengths, table, layer, config, page_size,
                    interpret=interpret, **bound,
                )[:, None, :]
        else:
            k_all = _paged_gather(pk, layer, table, page_size)
            v_all = _paged_gather(pv, layer, table, page_size)
            what = "block" if block else "decode" if s == 1 else "verify" if verify else "segment"
            attn = _dispatch_attention(
                q, k_all, v_all, mask, config, mask is None, what=f"paged-{what}",
                positions=positions, window=window, from_zero=False,
            )
    return attn, (pk, pv, *pik)


# Columns of table a selected token: up to here a decode step WALKS the row's
# pages under its selection as a mask, past it it gathers the selected rows.
# Two prices of one read, a layer, measured on a v5e at 8 rows x 4 KV heads
# of 128 and a top-k of 2,048 over tables of 8.5, 17 and 34 x the top-k
# (`dev/bench_selected_read.py`; PERF.md section 6, PRs 43 and 44). The
# gather pays 10 ns a (token, KV head) row of 256 B whatever a row holds, and
# `lax.top_k` over the table: 1.64 / 1.91 / 2.76 ms with the indexer's scores.
# The walk pays the row's LENGTH at the decode kernel's rate, 0.05 ms + 4.8 ns
# a token (380-415 GB/s of 2 KiB a token), and `_select_mask`'s counts: with
# the scores 0.86 ms at 17,408 tokens a row, 1.66 at 34,816, 3.55 at 69,632.
# Rows that fill their table cross at 21 x the top-k (the walk 13% ahead at
# 17 x, 29% behind at 34 x; rows half as long as a table of 34 x still walk
# 20% ahead). The program sees the table, not the lengths to come: at 16 the
# walk is never behind. No benchmark cell has a table past it.
_WALK_TABLE_PER_TOPK = 16

# What `attention_paths()` says under "paged-decode-sparse[..]" where the walk
# was traced: the HARNESS's string (benchmark/families/keye_vl2.py
# `expected_kernels` holds a chip run to it, letter for letter, and only a
# `benchmark` PR may edit it there), kept as the key it is and no longer a
# description. What was traced is under "paged-decode-selected[..]".
_WALK_LABEL = "ragged_paged_decode_attention to index_topk, xla top_k + gather past it"


def _paged_selected_read(
    q, q_idx, w_idx, pk, pv, pik, table, layer, mask, positions, config,
    page_size, decode_kernels,
):
    """The paged read of a model with an indexer → [B, S, H*D]. S = 1, where
    the decode kernels run and the table holds no more than
    ``_WALK_TABLE_PER_TOPK x index_topk`` columns: ONE call of the paged
    decode kernel under each row's selection as a mask over its pages
    (`ragged_paged_selected_attention`; `_select_mask`'s set is `lax.top_k`'s,
    and the identity for a row of no more than ``index_topk`` tokens), the
    scores and the ranking skipped whole while no row is past ``index_topk``.
    A longer table, and every backend without the kernels:
    `_sparse_decode_attention`, the same selection read by a gather. S > 1:
    the row's columns gathered through the table and
    ``flash_segment_attention`` while no query sees more than ``index_topk``
    keys, `_selected_attention` once one does."""
    from langstream_tpu.ops import attention as ops

    b, s = q.shape[:2]
    t = table.shape[1] * page_size
    topk = config.index_topk
    interpret = jax.default_backend() != "tpu"
    if s == 1:
        lengths = _paged_lengths(table, positions[:, 0], page_size, pk.shape[1])
        if not decode_kernels or t > _WALK_TABLE_PER_TOPK * topk:
            ops.note_path("paged-decode-sparse", "xla top_k + gather", config, s=s, t=t)
            return _sparse_decode_attention(
                q[:, 0], q_idx[:, 0], w_idx[:, 0], pk, pv, pik, table, layer,
                lengths, config, page_size,
            )[:, None, :]
        ops.note_path("paged-decode-sparse", _WALK_LABEL, config, s=s, t=t)
        ops.note_path(
            "paged-decode-selected", "ragged_paged_selected_attention", config, s=s, t=t
        )
        visible = jnp.arange(t)[None, :] < lengths[:, None]

        def ranked():
            scores = _decode_index_scores(
                q_idx[:, 0], w_idx[:, 0], pik, table, layer, config, page_size
            )
            with jax.named_scope("attention.select"):
                return _select_mask(scores, visible, topk)

        chosen = lax.cond(jnp.any(lengths > topk), ranked, lambda: visible)
        with jax.named_scope("attention.sparse"):
            return ops.ragged_paged_selected_attention(
                q[:, 0], pk, pv, lengths, table, layer, chosen, config, page_size,
                interpret=interpret,
            )[:, None, :]
    k_all = _paged_gather(pk, layer, table, page_size)
    v_all = _paged_gather(pv, layer, table, page_size)
    return _selected_segment_read(
        q, q_idx, w_idx, pik, k_all, v_all, table, layer, mask, positions, config,
        "paged-segment",
    )


def _selected_segment_read(
    q, q_idx, w_idx, pik, k_all, v_all, table, layer, mask, positions, config, what
):
    """S > 1 queries a row over its gathered columns ``k_all``/``v_all``
    [B, Hkv, T, D] (a latent model's: re-expanded from its latents) under the
    selection: the row's indexer keys gathered through the table, the segment
    kernel while no query sees more than ``index_topk`` keys,
    `_selected_attention` once one does."""
    from langstream_tpu.ops import attention as ops

    b, s = q.shape[:2]
    t = k_all.shape[2]
    interpret = jax.default_backend() != "tpu"
    with jax.named_scope("attention.index"):
        k_idx_all = pik.at[layer, table].get(mode="clip").reshape(b, t, -1)[
            ..., :config.index_head_dim]
    selected = functools.partial(
        _selected_attention, q, q_idx, w_idx, k_idx_all, k_all, v_all, mask,
        positions, config, what,
    )
    if not _selection_kernels(config, s, t):
        return selected()

    def plain():
        with jax.named_scope("attention.sparse"):
            return ops.flash_segment_attention(
                q, k_all, v_all, positions[:, 0], config, interpret=interpret
            )

    ops.note_path(what, "flash_segment_attention", config, s=s, t=t)
    return lax.cond(jnp.max(positions) >= config.index_topk, selected, plain)


# ---------------------------------------------------------------------------
# Latent attention (docs/SERVING.md "A model that keeps a latent, not keys and
# values"; ``config.has_latent``). The query goes through a normed latent
# ``c_q``; keys and values through ONE normed latent ``c_kv`` a token, and one
# rotary key ``k_rope`` serves every head. A token's cache is the row
# ``[c_kv | k_rope]`` (kept ``config.latent_key_width`` wide, the tail zeros):
# the leaf ``"lat"`` [L, B, 1, T, W] of a local cache and [L, P, 1, ps, W] of
# the page pool, K's layout with one head, in place of ``"k"`` and ``"v"``.
# Two forms of one attention over the SAME int8 ``wkv_b`` and scales:
#   expanded   k_h = [c_kv W_uk,h | k_rope], v_h = c_kv W_uv,h for every column
#              (`_latent_expand`), then the model's attention of H heads: the
#              dense path, and a paged segment over its row's gathered latents
#              (`_latent_expand_seen`: the columns its queries can see, where
#              the read is the walk over key blocks);
#   absorbed   q~_h = [q_nope,h W_uk,h^T | q_rope,h] against the rows as they
#              lie, o_h = (sum_s p_s c_kv,s) W_uv,h: a paged decode step, which
#              forms nothing of a head's keys or values.
# A head's q.k is ``qk_nope_head_dim + qk_rope_head_dim`` wide and its value
# ``v_head_dim``, which need not be equal (192 against 128): the expanded
# form's kernels take the two widths as they are. The scores' scale is
# ``config.attn_scale`` in both forms (YaRN's softmax factor rides there).
# Two READS of the cached latents, by what the model is:
#   selected   a model with an indexer (``config.has_indexer``): the indexer's
#              key is a second pool leaf ``"ik"``, a decode step walks the
#              row's pages under its selection as a mask, a segment reads
#              under the packed selection once a query sees more than
#              ``index_topk`` keys (`_selected_segment_read`);
#   dense      a model without: the pool holds ``"lat"`` alone, a decode step
#              walks the row's pages with NO mask operand (every byte walked
#              is a byte read), a segment is causal over its expanded columns
#              (`_dispatch_attention`'s kernels), and nothing of an indexer,
#              a ranking or a `lax.cond` on the lengths is traced.
# Scopes inside ``attention``: ``attention.latent`` (the down-projections and
# their norms, ``wq_b``, the absorb of q_nope, W_uv), ``attention.latent.expand``
# (``wkv_b`` over columns), ``attention.latent.read`` (the DENSE read: the
# decode kernel over the latent leaf, a segment's causal kernel over the
# expanded columns), the indexer's three as they are (``attention.index``,
# ``attention.select``, ``attention.sparse``: the selected read's alone).
# ---------------------------------------------------------------------------


def _rescaled(c, ratio: float):
    return (c.astype(jnp.float32) * jnp.float32(ratio**0.5)).astype(c.dtype)


def _head_gate(attn, u, lp, config):
    """``attn`` [B, S, H x v] with each head's output multiplied by its gate,
    ``sigmoid(u W_g)`` [B, S, H] in float32: one scalar a head and token from
    the layer's normed input ``u``, after the softmax's mix and before ``wo``
    (``config.attn_gate`` "headwise"; "": ``attn`` as it is)."""
    if not config.attn_gate:
        return attn
    b, s, _ = attn.shape
    with jax.named_scope("attention.gate"):
        gate = jax.nn.sigmoid(quantized_matmul(u, lp["w_attn_gate"]).astype(jnp.float32))
        gated = attn.reshape(b, s, config.n_heads, -1).astype(jnp.float32) * gate[..., None]
        return gated.astype(attn.dtype).reshape(b, s, -1)


def _latent_proj(x, lp, sin, cos, config):
    """(u, c_q, q, lat) of the layer's input ``x`` [B, S, d]: the normed
    input, the normed query latent [B, S, q_lora_rank], the queries
    [B, S, H, nope + rope] with each head's last ``qk_rope_head_dim`` turned,
    and the tokens' cache rows [B, S, kv_lora_rank + rope]: the normed
    key-value latent, then the one rotary key, turned."""
    b, s, _ = x.shape
    eps, kl, nope = config.rms_norm_eps, config.kv_lora_rank, config.qk_nope_head_dim
    turn = apply_rope_interleaved if config.rope_interleaved else apply_rope
    with jax.named_scope("attention.latent"):
        u = rms_norm(x, lp["attn_norm"], eps)
        c_q = rms_norm(quantized_matmul(u, lp["wq_a"]), lp["q_a_norm"], eps)
        if config.latent_rescale:
            # the normed latents at the variance a full-width input would give
            # their up-projections; the rotary key is not rescaled
            # (in float32: the factor rounded to bf16 would be off by up to 0.2%
            # for every token alike)
            c_q = _rescaled(c_q, config.d_model / config.q_lora_rank)
        q = quantized_matmul(c_q, lp["wq_b"]).reshape(b, s, config.n_heads, -1)
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:], sin, cos)], axis=-1)
        kv = quantized_matmul(u, lp["wkv_a"])
        c_kv = rms_norm(kv[..., :kl], lp["kv_a_norm"], eps)
        if config.latent_rescale:
            c_kv = _rescaled(c_kv, config.d_model / kl)
        k_rope = turn(kv[:, :, None, kl:], sin, cos)[:, :, 0]
        lat = jnp.concatenate([c_kv, k_rope], axis=-1)
    return u, c_q, q, lat


def _wkv_b(lp, config):
    """``wkv_b`` as (its values [kv_lora_rank, H, nope + v]: int8 where it is
    quantized, its scales [H, nope + v] float32 or None): ONE matrix and one
    rounding behind the expanded and the absorbed form."""
    w = lp["wkv_b"]
    shape = (config.kv_lora_rank, config.n_heads, config.qk_nope_head_dim + config.v_head_dim)
    if is_quantized(w):
        return w["q"].reshape(shape), w["s"].reshape(shape[1:]).astype(jnp.float32)
    return w.reshape(shape), None


def _latent_expand(lat, lp, config):
    """The expanded form's keys and values of the columns ``lat`` [B, T, W]
    (cache rows, or tokens' own) -> k [B, H, T, nope + rope], v [B, H, T, v] head-major:
    ``k_h = [c_kv W_uk,h | k_rope]``, ``v_h = c_kv W_uv,h``."""
    kl, nope, rope = config.kv_lora_rank, config.qk_nope_head_dim, config.qk_rope_head_dim
    b, t, _ = lat.shape
    with jax.named_scope("attention.latent.expand"):
        w, scale = _wkv_b(lp, config)
        if scale is not None:  # the dequantised matrix, as `quantized_matmul` forms it
            w = (w.astype(jnp.float32) * scale).astype(lat.dtype)
        c_kv = lat[..., :kl]
        k_nope = jnp.einsum("btc,chj->bhtj", c_kv, w[..., :nope])
        v = jnp.einsum("btc,chj->bhtj", c_kv, w[..., nope:])
        k_rope = jnp.broadcast_to(lat[:, None, :, kl:kl + rope], (b, config.n_heads, t, rope))
        return jnp.concatenate([k_nope, k_rope], axis=-1), v


def latent_columns_expanded(offset, s: int, t: int, config):
    """The columns of its row's table of ``t`` that a segment of ``s`` queries
    at ``offset .. offset + s - 1`` expands, a layer (``offset`` an int, or an
    array of the rows'): up to its last query's, in whole blocks of
    `ops/attention.latent_expand_block`, where the segment's read is the walk
    over key blocks, which stops at its diagonal; the whole table where it is
    masked jnp, which multiplies every column's value by its probability."""
    from langstream_tpu.ops import attention as ops

    if not _selection_kernels(config, s, t):
        return jnp.full_like(offset, t) if isinstance(offset, jax.Array) else t
    block = ops.latent_expand_block(s, t, config)
    seen = (offset + s + block - 1) // block * block
    return jnp.minimum(seen, t) if isinstance(offset, jax.Array) else min(seen, t)


def segment_blocks_visited(offset: int, s: int, t: int, config) -> dict:
    """The key blocks ONE KV head's walk visits, a layer call, in a segment of
    ``s`` queries at ``offset .. offset + s - 1`` over a table of ``t``
    columns (`ops/attention.segment_blocks_visited`: the kernel's own range,
    on the host): ``{"key_blocks": n}`` where the segment's read is the walk
    over key blocks (a latent's re-expanded heads, one query head a key head;
    a learned selection's and a window model's full layers, a group a KV
    head), with ``"key_blocks_window"`` beside it for a window layer's call;
    ``{}`` where it is masked jnp (every other model's segment, and where the
    kernels' tiles do not fit). What a segment's attention time is divided by
    for its time a key block."""
    from langstream_tpu.ops import attention as ops

    walks = config.has_latent or config.has_indexer or config.has_window
    if not (walks and _selection_kernels(config, s, t)):
        return {}
    if config.has_latent:
        d, group = config.qk_nope_head_dim + config.qk_rope_head_dim, 1
    else:
        d, group = config.resolved_head_dim, config.n_heads // config.n_kv_heads
    windows = {"key_blocks": 0}
    if config.has_window:
        windows["key_blocks_window"] = config.sliding_window
    itemsize = jnp.dtype(config.dtype).itemsize
    # (a latent window kind's walk is over its band, whose first column lies
    # on a page's edge and not on a key block's: counted here as if over the
    # table, which differs by a block a query block at most)
    widths = {"key_blocks": d, "key_blocks_window": (
        config.of_kind("sliding_attention").resolved_head_dim if config.has_latent else d
    )}
    return {
        name: ops.segment_blocks_visited(offset, s, t, widths[name], group, window, itemsize)
        for name, window in windows.items()
    }


class LaunchReads(NamedTuple):
    """What a launch of this model's programs reads, counted on the host from
    positions: the attributes its span carries and the increments of the
    `stats()` sums, by the rule of each kind of attention, beside the masks
    and walks that make the rule true. Pure: the configuration and a row's
    table (``table_columns`` = its length x ``page_size``), no engine state.
    The per-layer rooflines divide by these (benchmark/layer_metrics). A new
    kind of attention says here what it reads; the engine carries what it is
    handed."""

    config: ModelConfig
    page_size: int
    table_columns: int

    def totals(self) -> dict:
        """The sums this model's launches add to (`stats()` keys), at zero:
        what an indexer scored and its attention then read; what a latent's
        segments re-expanded; what a latent with no indexer read."""
        c, names = self.config, []
        if c.has_indexer:
            names += ["index-tokens-scored-total", "kv-tokens-selected-total"]
        if c.has_latent:
            names += ["latent-tokens-expanded-total", "latent-columns-expanded-total"]
            if not c.has_indexer:
                names.append("kv-tokens-read-total")
        return dict.fromkeys(names, 0)

    def _seen(self, lengths) -> tuple[dict, dict]:
        """(attributes, increments) of queries that see ``lengths`` columns
        each (an int64 array), a layer. A full layer reads every one
        (``kv_tokens_read``), a window layer at most the window's beside it
        (``kv_tokens_read_window``); an indexer scores every one and the
        attention READS ``index_topk`` at most, which is what
        ``kv_tokens_read`` then says."""
        c = self.config
        attrs, sums = {"kv_tokens_read": int(lengths.sum())}, {}
        if c.has_window:
            attrs["kv_tokens_read_window"] = int(np.minimum(lengths, c.sliding_window).sum())
        if c.has_indexer:
            scored = attrs["kv_tokens_read"]
            selected = int(np.minimum(lengths, c.index_topk).sum())
            attrs.update(
                kv_tokens_read=selected, index_tokens_scored=scored, kv_tokens_selected=selected
            )
            sums = {"index-tokens-scored-total": scored, "kv-tokens-selected-total": selected}
        elif c.has_latent:  # the dense latent read: every cached latent a query sees
            sums = {"kv-tokens-read-total": attrs["kv_tokens_read"]}
        return attrs, sums

    def segment(self, offset: int, real: int, width: int) -> tuple[dict, dict]:
        """A prefill segment of ``real`` tokens (``width`` computed) at
        ``offset``: real query i sees offset + i + 1 columns (a last
        segment's padding queries are no work asked for). A latent's segment
        re-expands the cached columns behind it (``latent_tokens_expanded``)
        among the columns of its table the program expands in all
        (`latent_columns_expanded`; a window kind's layer its band,
        `latent_window_band`). A model of none of these kinds reads K and V
        as every segment does and says nothing."""
        c = self.config
        if not (c.has_window or c.has_indexer or c.has_latent):
            return {}, {}
        attrs, sums = self._seen(offset + 1 + np.arange(real, dtype=np.int64))
        attrs["offset"] = offset
        if c.has_latent:
            columns = latent_columns_expanded(offset, width, self.table_columns, c)
            attrs.update(latent_tokens_expanded=offset, latent_columns_expanded=columns)
            sums.update({
                "latent-tokens-expanded-total": offset, "latent-columns-expanded-total": columns,
            })
            if c.latent_kinds:
                attrs["latent_expanded_window"] = latent_window_band(
                    width, self.table_columns, c.sliding_window, self.page_size
                )
        return attrs, sums

    def decode(self, first, steps: int) -> tuple[dict, dict]:
        """A decode chunk (or a verify) of ``steps`` over live rows whose
        first step sees ``first[i]`` columns (the position being written,
        plus one), one more each step. A decode step attends in the latent
        space: it expands nothing."""
        lengths = np.asarray(first, np.int64).reshape(-1, 1) + np.arange(steps)[None, :]
        attrs, sums = self._seen(lengths)
        if self.config.has_latent:
            attrs["latent_tokens_expanded"] = 0
        return attrs, sums

    def key_blocks(self, offset: int, width: int) -> dict:
        """`segment_blocks_visited` of a segment of ``width`` at ``offset``."""
        return segment_blocks_visited(offset, width, self.table_columns, self.config)


def _latent_expand_seen(rows, lp, offsets, s, config, whole=False):
    """`_latent_expand` of a row's gathered latents ``rows`` [B, T, W] for a
    segment of ``s`` queries at ``offsets[b] ..``: the columns
    `latent_columns_expanded` names and no others, straight into the
    head-major layout the walk reads (`ops/attention.latent_expand_blocks`).
    What lies past them is not written, and the walk does not read it."""
    from langstream_tpu.ops import attention as ops

    t = rows.shape[1]
    if not _selection_kernels(config, s, t):
        return _latent_expand(rows, lp, config)
    with jax.named_scope("attention.latent.expand"):
        ops.note_path("paged-segment-latent-expand", "latent_expand_blocks", config, s=s, t=t)
        # ``whole``: a window kind's band, every column of which is visited
        return ops.latent_expand_blocks(
            rows, *_wkv_b(lp, config),
            jnp.full_like(offsets, t) if whole else latent_columns_expanded(offsets, s, t, config),
            ops.latent_expand_block(s, t, config), config,
            interpret=jax.default_backend() != "tpu",
        )


def _latent_absorb(q, lp, config, width):
    """The absorbed queries of one query a row, ``q`` [B, H, nope + rope] ->
    [B, H, width]: ``[q_nope,h W_uk,h^T | q_rope,h | zeros]``, what scores a
    cache row as it lies. Over the int8 ``wkv_b`` the contraction runs over
    output channels: the query takes the channels' scales, then the integer
    product (int8 is exact in bf16)."""
    kl, nope, rope = config.kv_lora_rank, config.qk_nope_head_dim, config.qk_rope_head_dim
    w, scale = _wkv_b(lp, config)
    q_nope = q[..., :nope]
    if scale is not None:
        q_nope = (q_nope.astype(jnp.float32) * scale[:, :nope]).astype(q.dtype)
    absorbed = jnp.einsum(
        "bhj,chj->bhc", q_nope, w[..., :nope].astype(q.dtype),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    pad = jnp.zeros((*q.shape[:2], width - kl - rope), q.dtype)
    return jnp.concatenate([absorbed, q[..., nope:], pad], axis=-1)


def _latent_value_out(mixed, lp, config):
    """``mixed`` [B, H, kv_lora_rank], each head's probability-weighted sum of
    the latents it read -> the heads' outputs [B, H x v]: ``o_h = mixed_h
    W_uv,h``, the integer product, then the channels' scales."""
    nope = config.qk_nope_head_dim
    w, scale = _wkv_b(lp, config)
    out = jnp.einsum(
        "bhc,chj->bhj", mixed, w[..., nope:].astype(mixed.dtype),
        preferred_element_type=jnp.float32,
    )
    if scale is not None:
        out = out * scale[:, nope:]
    return out.astype(mixed.dtype).reshape(mixed.shape[0], -1)


def _latent_decode_read(q, index, plat, pik, table, layer, positions, lp, config, page_size,
                        kernels, lengths=None):
    """A decode step's read of a latent model -> [B, 1, H x v]: the attention
    IN THE LATENT SPACE, absorbed queries against ``[c_kv | k_rope]`` as the
    pool holds them, the value the same row's first ``kv_lora_rank`` lanes.
    Under a learned selection (``index`` the indexer's projections, ``pik``
    its pool leaf): the row's cached indexer keys scored by whole pages and
    ranked (skipped whole while no row is past ``index_topk``), the selected
    rows read. ``index`` None (a model with no indexer): the DENSE read,
    every cached row up to the query's, and no score, ranking or mask is
    traced. With the kernels ONE call of the paged decode kernel over the
    latent leaf (`ops/attention.ragged_paged_latent_attention`: a page is
    fetched once for key and value), under the selection as a mask over the
    row's pages or with no mask operand at all, or, under a window
    (``config.attn_window``: a window kind's layer, no indexer, ``lengths`` the
    rows' from the full group's table, this table a ring), from ``length -
    window`` on with the pages behind it never walked (scope
    ``attention.latent.window``), whatever the table's length
    (a gather of 2,048 rows of 1,280 B a row and layer pays the gather's
    10 ns a row five times over Keye's 256 B and loses sooner:
    `_WALK_TABLE_PER_TOPK`'s prices); without them the row's latents
    gathered through the table and masked jnp. Nothing of [B, H, T, nope + v]
    is formed either way."""
    from langstream_tpu.ops import attention as ops

    b, kl, topk = q.shape[0], config.kv_lora_rank, config.index_topk
    t, window = table.shape[1] * page_size, config.attn_window
    if lengths is None:
        lengths = _paged_lengths(table, positions[:, 0], page_size, plat.shape[1])
    visible = jnp.arange(t)[None, :] < lengths[:, None]
    bound = {}
    if window:
        bound["lower"] = jnp.maximum(lengths - window, 0)
        visible = visible & (jnp.arange(t)[None, :] >= bound["lower"][:, None])
    chosen = None
    if index is not None:
        q_idx, _, w_idx = index

        # (`_paged_selected_read`'s six lines, kept there as they stand: factored
        # out, the branches' names move in that model's lowered decode program)
        def ranked():
            scores = _decode_index_scores(
                q_idx[:, 0], w_idx[:, 0], pik, table, layer, config, page_size
            )
            with jax.named_scope("attention.select"):
                return _select_mask(scores, visible, topk)

        chosen = lax.cond(jnp.any(lengths > topk), ranked, lambda: visible)
    with jax.named_scope("attention.latent"):
        absorbed = _latent_absorb(q[:, 0], lp, config, plat.shape[-1])
    what = "paged-decode-latent-window" if window else "paged-decode-latent"
    with jax.named_scope(
        "attention.latent.window" if window
        else "attention.latent.read" if index is None else "attention.sparse"
    ):
        if kernels:
            ops.note_path(what, "ragged_paged_latent_attention", config, s=1, t=t)
            mixed = ops.ragged_paged_latent_attention(
                absorbed, plat, lengths, table, layer, chosen, config, page_size,
                interpret=jax.default_backend() != "tpu", **bound,
            ).reshape(b, config.n_heads, kl)
        else:
            ops.note_path(what, "jnp", config, s=1, t=t)
            seen = visible if chosen is None else chosen
            rows = _paged_gather(plat, layer, table, page_size)[:, 0]  # [B, T, W]
            logits = jnp.einsum(
                "bhw,btw->bht", absorbed, rows, preferred_element_type=jnp.float32
            ) * config.attn_scale
            logits = jnp.where(seen[:, None, :], logits, -1e30)
            probs = jnp.where(
                seen[:, None, :], jnp.exp(logits - logits.max(axis=-1, keepdims=True)), 0.0
            )
            mixed = jnp.einsum(
                "bht,btc->bhc", probs.astype(q.dtype), rows[..., :kl],
                preferred_element_type=jnp.float32,
            ) / jnp.maximum(probs.sum(axis=-1, keepdims=True), 1e-30)
            mixed = mixed.astype(q.dtype)
    with jax.named_scope("attention.latent"):
        return _latent_value_out(mixed, lp, config)[:, None, :]


def latent_window_band(s: int, t: int, window: int, page_size: int) -> int:
    """Columns of the BAND a window kind's segment of ``s`` queries gathers
    and re-expands, a layer: the segment, the ``window - 1`` columns before
    it and what a page's edge adds, in whole key blocks of the segment walk
    (512 columns) where the table of ``t`` is longer, else the whole table."""
    band = -(-(s + window - 1 + page_size - 1) // page_size) * page_size
    band = -(-band // 512) * 512 if band >= 512 else band
    return min(band, t)


def _latent_window_segment(q, plat, table, layer, positions, lp, config, page_size):
    """A window kind's segment read -> [B, S, H x v]: the row's latents of the
    band its queries see gathered through the ring's table (whole pages from
    the one that holds column ``offset - window + 1``; `latent_window_band`),
    re-expanded (`_latent_expand_seen`: every column of the band, which is all
    a walk under the window visits) and read through the segment kernel's
    window walk at positions counted from the band's first column, masked jnp
    where the tiles do not fit. A page of the band that the ring no longer
    maps, or that lies past the table, reads the sentinel's clamp: columns no
    query of the segment sees."""
    s, tp, window = q.shape[1], table.shape[1], config.attn_window
    band = latent_window_band(s, tp * page_size, window, page_size)
    # [B] the band's first logical page, the band kept inside the table
    first = jnp.maximum(positions[:, 0] - (window - 1), 0) // page_size
    first = jnp.minimum(first, tp - band // page_size)
    pages = jnp.take_along_axis(
        table, first[:, None] + jnp.arange(band // page_size)[None, :], axis=1
    )
    rows = _paged_gather(plat, layer, pages, page_size)[:, 0]  # [B, band, W]
    at = positions - (first * page_size)[:, None]  # the queries' columns in the band
    k_all, v_all = _latent_expand_seen(rows, lp, at[:, 0], s, config, whole=True)
    return _dispatch_attention(
        q, k_all, v_all, None, config, True, what="paged-segment-latent-window",
        positions=at, window=window, from_zero=False,
    )


def _latent_attention_block(
    x, lp, sin, cos, mask, config, cache_kv, cache_positions, causal, collect_kv,
    paged_table, page_size, layer, lengths=None,
):
    """`_attention_block` of a model that keeps a latent: (the FFN's input,
    the layer's cache leaves ``config.page_leaves``, written: ``("lat",
    "ik")`` with an indexer, ``("lat",)`` without). Without a table the
    EXPANDED form over a local cache entry [B, 1, T, W] written at
    ``cache_positions`` (or over the tokens' own rows), under the selection
    once a query of a model with an indexer sees more than ``index_topk``
    columns; with one, the pool's leaves whole, written at ``layer``: one
    query a row reads in the latent space (`_latent_decode_read`), a segment
    re-expands its row's gathered latents, cached columns and its own alike
    and none past its last query (`_latent_expand_seen`), into a temporary
    that never enters the pool, and reads it as a model with an indexer does
    (`_selected_segment_read`) or, with none, causally through
    `_dispatch_attention`'s kernels under ``attention.latent.read``. A model
    with no indexer traces none of its projections, leaf or branches.
    ``config`` is the layer's KIND's geometry (`ModelConfig.of_kind`); under
    its window (``config.attn_window``: a window kind's layer, which has no
    indexer) a query sees the last ``window`` columns with its own: the decode
    read walks its ring
    from ``length - window`` (``lengths``: the rows', from the full group's
    table), a segment gathers and re-expands the BAND its queries see and no
    other column (`_latent_window_segment`), both under
    ``attention.latent.window``. Where the kind gates its heads
    (``config.attn_gate``) the read's output passes `_head_gate` before ``wo``."""
    from langstream_tpu.ops import attention as ops

    b, s = x.shape[:2]
    window = config.attn_window
    positions = (
        jnp.broadcast_to(jnp.arange(s), (b, s)) if cache_positions is None else cache_positions
    )
    with jax.named_scope("attention"):
        u, c_q, q, lat = _latent_proj(x, lp, sin, cos, config)
        index = None
        if config.has_indexer:
            with jax.named_scope("attention.index"):
                index = _index_proj(u, lp, positions, config, c_q=c_q, rotary=(sin, cos))
    # the dense read's scope; the selected read names its own
    read_scope = (
        contextlib.nullcontext() if index is not None
        else jax.named_scope("attention.latent.window" if window else "attention.latent.read")
    )
    if window and mask is not None:  # what the masked jnp reads by
        mask = mask & _seen(positions, mask.shape[-1], window)
    if paged_table is not None:
        plat, *pik = cache_kv
        with jax.named_scope("kv_pool.write"):
            plat, *pik = _paged_write_rows(
                (plat, *pik),
                [_kept_width(lat, plat)[:, None], *([] if index is None else [index[1]])],
                layer, paged_table, positions, page_size, config, segment=s > 1,
            )
        with jax.named_scope("attention"):
            if s == 1:
                attn = _latent_decode_read(
                    q, index, plat, pik[0] if pik else None, paged_table, layer, positions,
                    lp, config, page_size, ops.paged_pallas_ok(config, page_size),
                    lengths=lengths,
                )
            elif window:
                with read_scope:
                    attn = _latent_window_segment(
                        q, plat, paged_table, layer, positions, lp, config, page_size
                    )
            else:
                rows = _paged_gather(plat, layer, paged_table, page_size)[:, 0]
                k_all, v_all = _latent_expand_seen(rows, lp, positions[:, 0], s, config)
                if index is None:
                    with read_scope:
                        attn = _dispatch_attention(
                            q, k_all, v_all, mask, config, True, what="paged-segment-latent",
                            positions=positions, from_zero=False,
                        )
                else:
                    attn = _selected_segment_read(
                        q, index[0], index[2], pik[0], k_all, v_all, paged_table, layer, mask,
                        positions, config, "paged-segment-latent",
                    )
            return x + quantized_matmul(_head_gate(attn, u, lp, config), lp["wo"]), (plat, *pik)
    with jax.named_scope("attention"):
        new_cache = None
        k_idx = None if index is None else index[1]
        if cache_kv is not None:
            clat, *cik = cache_kv  # [B, 1, T, W], [B, T, Wi]
            rows_at = jnp.arange(b)[:, None]
            clat = clat.at[rows_at, 0, cache_positions].set(_kept_width(lat, clat))
            if index is not None:
                cik = [cik[0].at[rows_at, cache_positions].set(_kept_width(k_idx, cik[0]))]
            new_cache = (clat, *cik)
            lat_all = clat[:, 0]
            if index is not None:
                k_idx = cik[0][..., :config.index_head_dim]
        else:
            lat_all = lat
            if collect_kv:
                new_cache = (lat[:, None], *([] if index is None else [k_idx]))
        k_all, v_all = _latent_expand(lat_all, lp, config)
        if index is not None and k_all.shape[2] > config.index_topk:
            if not causal:
                raise NotImplementedError(
                    f"{config.name}: the indexer ranks what a causal query sees"
                )
            attn = _selected_attention(
                q, index[0], index[2], k_idx, k_all, v_all, mask, positions, config,
                "prefill" if s > 1 else "decode",
            )
        else:
            with read_scope:
                bound = (
                    {"window": window, "positions": positions, "what": "prefill-latent-window"}
                    if window else {}
                )
                attn = _dispatch_attention(q, k_all, v_all, mask, config, causal, **bound)
        return x + quantized_matmul(_head_gate(attn, u, lp, config), lp["wo"]), new_cache


def _qkv(x, lp, sin, cos, config, lora, lora_scale, adapter_rows):
    """Norm, the three projections with their adapter terms, rotary:
    q [B, S, H, D], k and v [B, S, Hkv, D]. A block with its norm on the
    sublayer's output (``output_norm``) projects the bare input; with
    ``qk_norm`` q and k pass an RMSNorm over their whole width before the
    heads are split, with ``qk_norm_heads`` one over each head's
    ``head_dim`` after it; without ``rope`` nothing is turned."""
    b, s, d = x.shape
    hd = config.resolved_head_dim

    attn_in = x if config.output_norm else rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
    q = quantized_matmul(attn_in, lp["wq"]) + _lora_proj(
        attn_in, "wq", lora, lora_scale, adapter_rows
    )
    k = quantized_matmul(attn_in, lp["wk"]) + _lora_proj(
        attn_in, "wk", lora, lora_scale, adapter_rows
    )
    v = quantized_matmul(attn_in, lp["wv"]) + _lora_proj(
        attn_in, "wv", lora, lora_scale, adapter_rows
    )
    if config.qk_norm:
        q = rms_norm(q, lp["q_norm"], config.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], config.rms_norm_eps)
    q = q.reshape(b, s, config.n_heads, hd)
    k = k.reshape(b, s, config.n_kv_heads, hd)
    v = v.reshape(b, s, config.n_kv_heads, hd)
    if config.qk_norm_heads:
        q = rms_norm(q, lp["q_norm"], config.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], config.rms_norm_eps)
    if config.rope:
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    if config.kv_head_pack > 1:
        # heads of 64 travel two to a lane row from here on, as the cache and
        # the pool keep them: [B, S, Hkv / 2, 128]
        k, v = (a.reshape(b, s, -1, config.kv_head_pack * hd) for a in (k, v))
    return q, k, v


def _unpacked(leaf, config):
    """A cache leaf [B, Hkv / pack, T, pack x D] as the jnp reference reads
    it, [B, Hkv, T, D] (``config.kv_head_pack`` 1: as it is)."""
    pack = config.kv_head_pack
    if pack == 1:
        return leaf
    b, rows, t, width = leaf.shape
    return leaf.reshape(b, rows, t, pack, width // pack).transpose(0, 1, 3, 2, 4).reshape(
        b, rows * pack, t, width // pack
    )


def _attn_residual(x, attn_out, lp, config, adapter_term=None):
    """x + the attention sublayer's output (+ its adapter term), through the
    block's norm where that sits on the output."""
    if config.output_norm:
        if adapter_term is not None:
            attn_out = attn_out + adapter_term
        return x + rms_norm(attn_out, lp["attn_norm"], config.rms_norm_eps)
    x = x + attn_out
    return x if adapter_term is None else x + adapter_term


def _dense_attention(
    x, lp, sin, cos, mask, config, cache_kv, cache_positions, causal,
    collect_kv, lora, lora_scale, adapter_rows,
):
    """`_attention_block` without a page table: a dense cache entry
    [B, Hkv, T, D] written at ``cache_positions`` and read whole, or no
    cache at all."""
    b, s = x.shape[:2]
    q, k, v = _qkv(x, lp, sin, cos, config, lora, lora_scale, adapter_rows)
    new_cache = None
    cik = ()
    if config.has_indexer:
        positions = (
            jnp.broadcast_to(jnp.arange(s), (b, s)) if cache_positions is None
            else cache_positions
        )
        with jax.named_scope("attention.index"):
            q_idx, k_idx, w_idx = _index_proj(
                rms_norm(x, lp["attn_norm"], config.rms_norm_eps), lp, positions, config
            )
        if cache_kv is not None:  # the indexer's key [B, T, Di] beside K and V
            *cache_kv, ik = cache_kv
            ik = ik.at[jnp.arange(b)[:, None], cache_positions].set(_kept_width(k_idx, ik))
            k_idx = ik[..., :config.index_head_dim]
            cik = (ik,)
        else:
            cik = (k_idx,)
    if cache_kv is not None:
        ck, cv = cache_kv  # [B, Hkv, T, D] head-major (maybe int8-quantized)
        # scatter this step's k/v into the cache at cache_positions [B, S]
        hkv = k.shape[2]  # (heads of 64: packed rows)
        bidx = jnp.arange(b)[:, None, None]
        hidx = jnp.arange(hkv)[None, :, None]
        pidx = cache_positions[:, None, :]  # [B, 1, S]
        kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        if isinstance(ck, dict):  # int8 cache: per-(token, head) scales
            kq, ks = _quantize_kv(kt)
            vq, vs = _quantize_kv(vt)
            ck = {
                "q": ck["q"].at[bidx, hidx, pidx].set(kq),
                "s": ck["s"].at[bidx, hidx, pidx].set(ks),
            }
            cv = {
                "q": cv["q"].at[bidx, hidx, pidx].set(vq),
                "s": cv["s"].at[bidx, hidx, pidx].set(vs),
            }
        else:
            ck = ck.at[bidx, hidx, pidx].set(kt)
            cv = cv.at[bidx, hidx, pidx].set(vt)
        new_cache = (ck, cv, *cik)
        k_all, v_all = ck, cv
    else:
        k_all, v_all = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        if collect_kv:
            new_cache = (k_all, v_all, *cik)

    if config.has_indexer and k_all.shape[2] > config.index_topk:
        # a query can see more columns than it may keep; up to index_topk
        # columns the selection is the identity and the paths below are as
        # every model's
        if not causal:
            raise NotImplementedError(f"{config.name}: the indexer ranks what a causal query sees")
        attn = _selected_attention(
            q, q_idx, w_idx, k_idx, k_all, v_all, mask, positions, config,
            "prefill" if s > 1 else "decode",
        )
        attn_out = quantized_matmul(attn, lp["wo"]) + _lora_proj(
            attn, "wo", lora, lora_scale, adapter_rows
        )
        return _attn_residual(x, attn_out, lp, config), new_cache

    if config.ring_axis is not None and cache_kv is None:
        # sequence-parallel path: K/V blocks rotate around the ring; the
        # causal mask is derived from global block positions inside (ring
        # keeps the [B, Sl, Hkv, D] layout — blocks ppermute whole)
        from langstream_tpu.parallel.ring_attention import ring_attention

        attn_out = quantized_matmul(ring_attention(q, k, v, config), lp["wo"])
    else:
        attn = _dispatch_attention(q, k_all, v_all, mask, config, causal)
        attn_out = quantized_matmul(attn, lp["wo"]) + _lora_proj(
            attn, "wo", lora, lora_scale, adapter_rows
        )
    return _attn_residual(x, attn_out, lp, config), new_cache


def _layer_counted(
    x: jax.Array,
    lp: dict,
    sin: jax.Array,
    cos: jax.Array,
    mask: jax.Array,
    config: ModelConfig,
    cache_kv: Optional[tuple[jax.Array, jax.Array]] = None,
    cache_positions: Optional[jax.Array] = None,
    causal: bool = True,
    collect_kv: bool = False,
    verify: bool = False,
    paged_table: Optional[jax.Array] = None,  # [B, Tp] physical pages
    page_size: int = 0,
    lora: Optional[dict] = None,  # per-layer adapter slices {proj: {a, b}}
    lora_scale: Optional[jax.Array] = None,  # [R] per-adapter scale
    adapter_rows: Optional[jax.Array] = None,  # [B] pool row per slot
    token_valid: Optional[jax.Array] = None,  # [B, S] bool — real tokens
    layer: Optional[jax.Array] = None,  # scalar layer index (paged only)
    block: bool = False,  # a block pass: S queries a row that see one another
    moe_layer: Optional[jax.Array] = None,  # with held experts' stacks in ``lp``
    dense: bool = False,  # a leading dense layer of an expert model
    lengths: Optional[jax.Array] = None,  # [B]: a latent window kind's decode step's rows'
) -> tuple[jax.Array, Optional[tuple[jax.Array, jax.Array]], jax.Array]:
    """One transformer block, and its MOE_COUNTS (zeros when dense; only
    ``token_valid`` feeds them). If cache_kv given, k/v are written at
    cache_positions and attention runs over the full cache width. With
    ``collect_kv`` (cache-less paths) the layer's roped K/V come back
    head-major so a caller can build a cache from a full forward — the
    ring-prefill serving path (parallel.sp.ring_prefill). With
    ``paged_table`` set, cache_kv is the whole PAGE POOL
    ([L, P, Hkv, ps, D]) and ``layer`` this block's index into it: K/V
    scatter to the slot's pages of that layer and attention reads through
    (layer, table) (Pallas ragged-paged kernel on decode shapes when it
    applies, else the gathered masked-jnp view — same math either way);
    the pool comes back whole, no per-layer entry is ever formed.
    With ``lora`` set, every projection adds its slot-gathered low-rank
    adapter term (``_lora_delta``) — K/V written to the cache INCLUDE the
    wk/wv adapter deltas, which is why prefill must be adapter-aware too."""
    x, new_cache = _attention_block(
        x, lp, sin, cos, mask, config, cache_kv, cache_positions, causal,
        collect_kv, verify, paged_table, page_size,
        lora, lora_scale, adapter_rows, layer, block, lengths,
    )
    y, counts = _ffn_half(
        x, lp, config, config.output_norm, token_valid, lora, lora_scale,
        adapter_rows, layer if moe_layer is None else moe_layer, dense,
    )
    return y, new_cache, counts


def _ffn_half(
    x, lp, config, output_norm=False, token_valid=None, lora=None, lora_scale=None,
    adapter_rows=None, layer=None, dense=False,
):
    """The feed-forward half of a block and its counts (`moe_count_names`):
    x + f(norm(x)), or, for a dense FFN, x + norm(f(x)) with ``output_norm``.
    An expert layer is `moe_ffn` and its capacity rule, or, where the model
    holds its experts so (``experts_held``), `moe_ffn_held`, which drops
    nothing: ``lp`` then carries the held experts' whole stacks and ``layer``
    says which of them is this block's (`_split_held`). ``dense``: a leading
    dense layer of an expert model (``config.n_leading_dense``), whose ``lp``
    holds a dense FFN of ``d_ff``; its counts are the dense model's zeros."""
    eps = config.rms_norm_eps
    moe = config.is_moe and not dense
    if moe and output_norm:
        raise NotImplementedError(f"an expert FFN under an output norm ({config.name})")
    if moe and config.experts_held:
        with jax.named_scope("moe_ffn"):
            # the router reads the norm before it is rounded (`_parallel_layer`)
            u32 = rms_norm(x.astype(jnp.float32), lp["ffn_norm"], eps)
            ffn_out, counts = moe_ffn_held(
                u32.astype(x.dtype), lp, config, token_valid, layer, route_on=u32
            )
    elif moe:
        with jax.named_scope("moe_ffn"):
            ffn_in = rms_norm(x, lp["ffn_norm"], eps)
            ffn_out, counts = moe_ffn_counted(ffn_in, lp, config, token_valid)
    else:
        with jax.named_scope("ffn"):
            ffn_in = x if output_norm else rms_norm(x, lp["ffn_norm"], eps)
            ffn_out = dense_ffn(
                ffn_in, lp, config, lora=lora, lora_scale=lora_scale,
                adapter_rows=adapter_rows,
            )
            if output_norm:
                ffn_out = rms_norm(ffn_out, lp["ffn_norm"], eps)
        counts = _no_moe_counts()
    return x + ffn_out, counts


def _layer(*args, **kwargs):
    """`_layer_counted` without the counts: (output, new cache entry)."""
    y, new_cache, _ = _layer_counted(*args, **kwargs)
    return y, new_cache


# ---------------------------------------------------------------------------
# Linear attention (the gated delta rule) and the layer pattern. A model with
# ``config.layer_pattern`` keeps, beside the pages of its full-attention
# layers, a RECURRENT STATE a sequence: ``{"s": [Ll, R, dk, H * dv] float32,
# "conv": [Ll, R, (K - 1) * C]}`` over its Ll linear layers and R rows (a row
# belongs to a serving slot), the rule's state and the last K - 1 inputs of
# the short convolution; a model of "conv" layers (a gated short convolution,
# `_short_conv_block`) keeps that tail ALONE, ``{"conv": [Lc, R, (K - 1) *
# d_model]}``. It travels as ``pool["rec"]``, beside the pool's
# "k" and "v", donated and carried like them. What a call does to it is told
# by ``rctx``: ``rows`` [B] (each batch row's state row; out of bounds drops
# the write; None: batch row b IS state row b), ``valid`` [B, S] (a prefix of
# real tokens a row: the rest change neither S nor the convolution's tail)
# and ``fresh`` (True: every row starts from the zero state; [B] bool: those
# rows do; None: every row carries on from its state).
# ---------------------------------------------------------------------------


def make_recurrent_state(config: ModelConfig, rows: int, dtype=None) -> dict:
    """The leaves of the kinds the model has: the delta rule's state and its
    convolution's tail, or a conv layer's tail alone."""
    if "conv" in config.layer_pattern:
        return {
            "conv": jnp.zeros(
                (config.n_layers_of("conv"), rows, (config.conv_kernel - 1) * config.d_model),
                dtype or _dtype(config),
            ),
        }
    n = config.n_layers_of("linear_attention")
    return {
        "s": jnp.zeros(
            (n, rows, config.linear_key_head_dim, config.linear_value_dim), jnp.float32
        ),
        # [K - 1, C] a row, flat: a minor dimension of K - 1 = 3 invites a
        # layout that pads it to a tile (42.7 x, the chip's compiler, PR 32)
        "conv": jnp.zeros(
            (n, rows, (config.linear_conv_kernel - 1) * config.linear_conv_dim),
            dtype or _dtype(config),
        ),
    }


def _rows_of(leaf, layer, rows):
    """A layer's rows of a state leaf [L, R, ...]: the whole layer where the
    batch IS the rows (``rows`` None: a slice that fuses into its reader),
    else a gather (out of bounds clips: such a row's write drops)."""
    if rows is None:
        return lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    return leaf.at[layer, rows].get(mode="clip")


def _set_rows(leaf, layer, rows, new):
    """The inverse: one update of the layer's slab, or a scatter that drops
    out-of-bounds rows (a scatter runs row after row: 4.5 ms of a 40-row
    decode step over 24 layers on a v5e, PERF.md section 6, PR 32)."""
    if rows is None:
        return lax.dynamic_update_index_in_dim(leaf, new.astype(leaf.dtype), layer, 0)
    return leaf.at[layer, rows].set(new.astype(leaf.dtype), mode="drop")


def _short_conv(inputs, taps, rec, layer, rows, valid, fresh, activation=None):
    """A causal depthwise convolution of K taps over each row's last K - 1
    inputs and these: (float32 [B, S, C], the state with this layer's tails
    written). ``inputs`` [B, S, C], ``taps`` [K, C]; the tail a row carries in
    is its state's (``rec["conv"]`` [L, R, (K - 1) * C] at ``layer``,
    ``rows``), zeros for a ``fresh`` row and without a state; the tail it
    leaves is its last K - 1 REAL inputs (``valid`` [B, S], a prefix a row):
    a ragged row's come from before its padding, a row shorter than K - 1
    keeps what it carried in behind its own, and a decode step (S = 1) moves a
    live row's tail on by one and leaves an idle row's as it is. The one
    place both recurrent kinds (the delta rule's q, k, v and a conv layer's
    gated input) take and leave their tails."""
    b, s, _ = inputs.shape
    width = taps.shape[0]
    keep_old = rec is not None and fresh is not True
    tail = jnp.zeros((b, width - 1, inputs.shape[-1]), inputs.dtype)
    if keep_old:
        tail = _rows_of(rec["conv"], layer, rows).reshape(tail.shape)
        if fresh is not None:
            tail = jnp.where(fresh[:, None, None], jnp.zeros((), tail.dtype), tail)
    window = jnp.concatenate([tail, inputs], axis=1)  # [B, K - 1 + S, C]
    taps = taps.astype(jnp.float32)
    mixed = sum(
        window[:, i : i + s].astype(jnp.float32) * taps[i] for i in range(width)
    )
    if activation is not None:
        mixed = activation(mixed)
    if rec is not None:
        # the last K - 1 REAL inputs: the window from the row's count on
        if s == 1:  # a live row's window moves on by one, an idle row's stays
            new_tail = jnp.where(valid[:, :, None], window[:, 1:], window[:, :-1])
        else:
            at = valid.sum(axis=1, dtype=jnp.int32)[:, None] + jnp.arange(width - 1)
            new_tail = jnp.take_along_axis(window, at[:, :, None], axis=1)
        rec = {**rec, "conv": _set_rows(rec["conv"], layer, rows, new_tail.reshape(b, -1))}
    return mixed, rec


def _linear_attention_block(x, lp, config, rec, layer, rctx):
    """x + GDN(norm(x)) for [B, S, d], and the state with this layer's rows
    written (``rec`` None: from the zero state, nothing kept)."""
    from langstream_tpu.ops import gated_delta as gd
    from langstream_tpu.ops.attention import note_path

    b, s, _ = x.shape
    h, dk, dv = config.linear_n_heads, config.linear_key_head_dim, config.linear_value_head_dim
    kd = config.linear_key_dim
    valid = rctx["valid"] if rctx else jnp.ones((b, s), jnp.bool_)
    rows = rctx["rows"] if rctx else None
    # True: every row starts from the zero state; [B] bool: those rows do
    fresh = rctx.get("fresh") if rctx else True
    keep_old = rec is not None and fresh is not True
    with jax.named_scope("linear_attention.proj"):
        a_in = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        qkv = quantized_matmul(a_in, lp["wqkv"])  # [B, S, q | k | v]
        gate = quantized_matmul(a_in, lp["wg"])
        # the two gates in float32: exp(A_log) up to 16 multiplies a's
        # rounding into the decay (2 x 30 columns: no cost)
        a32 = a_in.astype(jnp.float32)
        g, beta = gd.gates(
            a32 @ lp["wa"].astype(jnp.float32), a32 @ lp["wb"].astype(jnp.float32),
            lp["A_log"], lp["dt_bias"], config.linear_allow_neg_eigval,
        )
        # padding: decay 1, write strength 0
        g = jnp.where(valid[..., None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    with jax.named_scope("linear_attention.conv"):
        mixed, rec = _short_conv(
            qkv, lp["conv_w"], rec, layer, rows, valid, fresh, activation=jax.nn.silu
        )
        q = gd.l2norm(mixed[..., :kd].reshape(b, s, h, dk)) * dk**-0.5
        k = gd.l2norm(mixed[..., kd : 2 * kd].reshape(b, s, h, dk))
        v = mixed[..., 2 * kd :].reshape(b, s, h, dv)
    with jax.named_scope("linear_attention.state"):
        if s == 1 and rec is not None:
            live = valid[:, 0]
            kernel = gd.gated_delta_pallas_ok(config)
            note_path(
                "linear-decode", "gated_delta_update" if kernel else "jnp", config, s=1, t=0
            )
            args = (
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], rec["s"], layer,
                jnp.arange(b) if rows is None else rows, live,
            )
            if kernel:
                o, state = gd.gated_delta_update(
                    *args, interpret=jax.default_backend() != "tpu"
                )
            else:
                o, state = gd.gated_delta_update_jnp(*args)
            rec = {**rec, "s": state}
            o = o[:, None]
        else:
            note_path("linear-prefill", "gated_delta_chunk_prefill", config, s=s, t=s)
            s0 = jnp.zeros((b, dk, h * dv), jnp.float32)
            if keep_old:
                s0 = _rows_of(rec["s"], layer, rows)
                if fresh is not None:
                    s0 = jnp.where(fresh[:, None, None], 0.0, s0)
            o, final = gd.gated_delta_chunk_prefill(q, k, v, g, beta, s0)
            if rec is not None:
                rec = {**rec, "s": _set_rows(rec["s"], layer, rows, final)}
    with jax.named_scope("linear_attention.out"):
        o = rms_norm(
            o.reshape(b, s, h, dv).astype(x.dtype), lp["out_norm"], config.rms_norm_eps
        )
        o = o.reshape(b, s, h * dv) * jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
        x = x + quantized_matmul(o, lp["wo"])
    return x, rec


def _linear_layer(x, lp, config, rec, layer, rctx):
    """A ``linear_attention`` layer: the mixer above, then the pre-norm FFN."""
    with jax.named_scope("linear_attention"):
        x, rec = _linear_attention_block(x, lp, config, rec, layer, rctx)
    y, _ = _ffn_half(x, lp, config)
    return y, rec


def _short_conv_block(x, lp, config, rec, layer, rctx):
    """x + out_proj(C * conv(B * u)), [B | C | u] = in_proj(norm(x)), for
    [B, S, d] (LFM2's conv mixer: no bias, no activation), and the state with
    this layer's tails written (``rec`` None: from zeros, nothing kept)."""
    from langstream_tpu.ops.attention import note_path

    b, s, d = x.shape
    valid = rctx["valid"] if rctx else jnp.ones((b, s), jnp.bool_)
    rows = rctx["rows"] if rctx else None
    fresh = rctx.get("fresh") if rctx else True
    note_path("short-conv", "short_conv", config, s=s, t=0)
    with jax.named_scope("short_conv.proj"):
        gates = quantized_matmul(rms_norm(x, lp["attn_norm"], config.rms_norm_eps), lp["w_in"])
        gate_b, gate_c, u = gates[..., :d], gates[..., d : 2 * d], gates[..., 2 * d :]
    with jax.named_scope("short_conv.conv"):
        mixed, rec = _short_conv(gate_b * u, lp["conv_w"], rec, layer, rows, valid, fresh)
        gated = (gate_c.astype(jnp.float32) * mixed).astype(x.dtype)
    with jax.named_scope("short_conv.out"):
        x = x + quantized_matmul(gated, lp["w_out"])
    return x, rec


# ---------------------------------------------------------------------------
# Window and full attention layers in a parallel block, an expert layer that
# holds a share (``config.has_window``; command-a-plus is the model). Each
# KIND of layer keeps cache entries of its own: the full layers' as every
# model's (``"k"``, ``"v"``), the window layers' beside them under ``"win"``,
# in a local cache [L, B, Hkv, T, D] as in the page pool [L, P, Hkv, ps, D],
# where the window group has its own number of pages and its own table: the
# paged entry points take ``table`` [2, B, Tp], `FULL` and `WINDOW`. Both
# tables are indexed by the LOGICAL page (position // page_size); a window
# row maps only the pages its queries can still see and those the dispatch
# writes (serving/pagepool.py recycles the ones behind), the rest carry the
# sentinel. The whole state rides the layer scan's carry and is written in
# place at (layer, ...), as `_scan_layers_inplace` does it.
# ---------------------------------------------------------------------------

FULL, WINDOW = 0, 1
_HELD_EXPERTS = ("w_gate", "w_up", "w_down")
_KIND_KEY = {"full_attention": None, "sliding_attention": "win"}


def _kind_leaves(config, kind) -> tuple:
    """The cache leaves a layer of ``kind`` reads and writes: "k" and "v", or
    its kind's own (``"lat"``, with the full kind's ``"ik"``) in a model whose
    kinds each keep a latent."""
    return config.of_kind(kind).page_leaves if config.latent_kinds else ("k", "v")


def _kind_entry(state, kind, config):
    tree = state if _KIND_KEY[kind] is None else state[_KIND_KEY[kind]]
    return tuple(tree[leaf] for leaf in _kind_leaves(config, kind))


def _with_entry(state, kind, entry, config):
    new = dict(zip(_kind_leaves(config, kind), entry))
    return {**state, **new} if _KIND_KEY[kind] is None else {**state, _KIND_KEY[kind]: new}


def _route_all(xf: jax.Array, router: jax.Array, config: ModelConfig, bias=None):
    """(weights [T, k] float32, chosen [T, k]) over ALL ``n_experts``, held
    here or not: the scores in float32 at the highest matmul precision (a
    bf16 product moves which experts a token near a tie takes), the k
    largest chosen, their weights normalised over the k chosen. With
    ``bias`` [E] float32 (``config.router_bias``) the k largest of score +
    bias are chosen and weighed by their scores alone: the bias balances the
    experts' load and is no part of the mixture. The weights times
    ``config.routed_scaling``."""
    logits = jnp.dot(
        xf.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )  # [T, E]
    if config.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        if bias is None:
            top, chosen = lax.top_k(scores, config.n_experts_per_tok)
        else:
            _, chosen = lax.top_k(scores + bias.astype(jnp.float32), config.n_experts_per_tok)
            top = jnp.take_along_axis(scores, chosen, axis=-1)
        total = jnp.sum(top, axis=-1, keepdims=True)
        if config.router_norm_eps:  # (0: the division every other model traces)
            total = total + config.router_norm_eps
        weights = top / total
    elif bias is not None:
        raise NotImplementedError(f"{config.name}: a router bias under softmax scoring")
    else:
        top, chosen = lax.top_k(logits, config.n_experts_per_tok)
        weights = jax.nn.softmax(top, axis=-1)
    if config.routed_scaling != 1.0:
        weights = weights * config.routed_scaling
    return weights, chosen


def _held_passes(xf, order, by_expert, weights, n_local, k, held, tile, shape, experts):
    """float32 [T, d]: every local assignment's expert output times its
    weight, summed at its token, in passes of ``shape`` (`gm.pass_shape`:
    assignments, tiles). ``order`` [T * k]: the assignments sorted by held
    expert, the ``n_local`` local ones first; ``by_expert`` their experts
    (``held``: not local). Pass p lays the window ``[p * C, (p + 1) * C)`` of
    them out in its own buffer (an expert whose rows straddle two windows has
    its weights read in both), so the rows moved follow C and the passes
    follow the rows that are real: one under even routing, none where no
    token chose a held expert, more under a skew; nothing is dropped.

    The FIRST pass's rows leave the loop and are summed after it, by the
    one-pass layer's own expression: inside the loop's body the compiler
    rounds that same expression another way (a last place in a few rows a
    call), and the cells' checks turn on such places (PERF.md section 6, PR
    54: summed in the loop, one cell's check failed; summed here, the layer
    as Kimi and GLM configure it gave the one buffer's output to the bit on
    the chip, and every check held). A further pass, which only a skew
    takes, adds into a float32 accumulator, and
    the first pass's sum joins it rounded to the activation type."""
    from langstream_tpu.ops import grouped_matmul as gm

    size, tiles = shape
    t, d = xf.shape
    n_rows = tiles * tile
    pad = -order.shape[0] % size  # a window never reads past the end
    order = jnp.pad(order, (0, pad))
    by_expert = jnp.pad(by_expert, (0, pad), constant_values=held)

    def summed(out_rows, row_of):
        """A pass's rows in [T, k]'s slots (out of bounds: not of this pass,
        filled with zero and not fetched), weighed and summed over k."""
        picked = out_rows.at[row_of].get(mode="fill", fill_value=0).reshape(t, k, -1)
        w = jnp.where(row_of < n_rows, weights, 0.0).reshape(t, k)
        return jnp.einsum("tkd,tk->td", picked.astype(jnp.float32), w)

    def one_pass(carry):
        p, acc, first_rows, first_row_of = carry
        with jax.named_scope("moe_ffn.dispatch"):
            assignment = lax.dynamic_slice(order, (p * size,), (size,))
            expert = lax.dynamic_slice(by_expert, (p * size,), (size,))
            # past the local ones: no token, no row (out of bounds both)
            token = jnp.where(expert < held, assignment // k, t)
            dest, tile_expert, used, _ = gm.plan_groups(expert, held, tile, tiles)
            # a buffer row's token through the inverse of ``dest`` (a gather of
            # rows goes at the memory's speed, a scatter of them costs by the
            # update); a row no assignment lands on reads token 0 and is
            # never read back
            source = jnp.zeros(n_rows, jnp.int32).at[dest].set(token, mode="drop")
            rows = jnp.take(xf, source, axis=0)
        out_rows = experts(rows, tile_expert, used)
        with jax.named_scope("moe_ffn.combine"):
            # each assignment's row of this pass's buffer
            row_of = jnp.full(t * k, n_rows, jnp.int32).at[
                jnp.where(expert < held, assignment, t * k)
            ].set(dest, mode="drop")
            return lax.cond(
                p == 0,
                lambda: (p + 1, acc, out_rows, row_of),
                lambda: (p + 1, acc + summed(out_rows, row_of), first_rows, first_row_of),
            )

    _, acc, first_rows, first_row_of = lax.while_loop(
        lambda carry: carry[0] * size < n_local, one_pass,
        (
            jnp.int32(0), jnp.zeros((t, d), jnp.float32),
            jnp.zeros((n_rows, d), xf.dtype), jnp.full(t * k, n_rows, jnp.int32),
        ),
    )
    with jax.named_scope("moe_ffn.combine"):
        first = summed(first_rows, first_row_of).astype(xf.dtype).astype(jnp.float32)
        return jnp.where(n_local > size, first + acc, first)


def moe_ffn_held(
    x: jax.Array, lp: dict, config: ModelConfig,
    token_valid: Optional[jax.Array] = None,  # [B, S] bool — real tokens
    layer: Optional[jax.Array] = None,  # with ``lp``'s held experts the whole stack
    route_on: Optional[jax.Array] = None,  # [B, S, d] float32: ``x`` before its rounding
) -> tuple[jax.Array, jax.Array]:
    """The expert layer of a program that holds a SHARE of the experts
    (``config.held_experts``), and its MOE_HELD_COUNTS. The router is
    ``n_experts`` wide and scores in float32; each token's top k are chosen
    among ALL experts and weighted as published (sigmoid scores divided by
    the chosen ones' sum, or a softmax over the chosen logits); the
    assignments that fall on held experts are computed, every one of a real
    token (none is dropped: rows sorted by expert, one grouped product over
    the held experts, ops/grouped_matmul.py); what an absent expert would
    add is left out, as on the chip that holds the others its own part is.
    The shared experts' mean is added once. A padding token is routed but
    holds no row: it gets the shared part only. The rows are laid out in ONE
    buffer that holds every case, or, where the layer holds a share small
    enough that twice its even share of the call's assignments is a smaller
    buffer (`gm.pass_shape`, from shapes alone: a prefill segment at 12 of
    384 experts, never a decode step, never a layer that holds every
    expert), in passes of that (`_held_passes`): the same products, and
    where one pass holds them all the one buffer's sum over a token's k."""
    from langstream_tpu.ops import grouped_matmul as gm
    from langstream_tpu.ops.attention import note_grid

    b, s, d = x.shape
    t, k = b * s, config.n_experts_per_tok
    first, held = config.held_experts
    xf = x.reshape(t, d)
    real = jnp.ones((t,), jnp.bool_) if token_valid is None else token_valid.reshape(t)

    with jax.named_scope("moe_ffn.route"):
        weights, chosen = _route_all(
            xf if route_on is None else route_on.reshape(t, d), lp["router"], config,
            *((lp["router_bias"],) if config.router_bias else ()),
        )

    tile = gm.row_tile(t, k, config.n_experts)
    passes = gm.pass_shape(t, k, held, config.n_experts, tile)
    note_grid(*gm.dispatch_note(t, k, held, config.n_experts, tile))
    with jax.named_scope("moe_ffn.dispatch"):
        local = (chosen >= first) & (chosen < first + held) & real[:, None]  # [T, k]
        expert = jnp.where(local, chosen - first, held).reshape(t * k)
        if passes is None:
            tiles = gm.buffer_tiles(t, k, held, tile)
            dest, tile_expert, used, sizes = gm.plan_groups(expert, held, tile, tiles)
            rows = jnp.zeros((tiles * tile, d), xf.dtype).at[dest].set(
                jnp.repeat(xf, k, axis=0), mode="drop"
            )
        else:
            # ONE stable sort puts the local assignments first, by expert and
            # then by token: a pass is a window of it (`_held_passes`)
            by_expert, order = lax.sort(
                (expert, jnp.arange(t * k, dtype=jnp.int32)), num_keys=1, is_stable=True
            )
            sizes = jnp.diff(jnp.searchsorted(by_expert, jnp.arange(held + 1)))
        named = dict(
            routed=jnp.int32(t * k), dropped=jnp.int32(0),
            routed_real=real.sum(dtype=jnp.int32) * k, dropped_real=jnp.int32(0),
            local=local.sum(dtype=jnp.int32), touched=(sizes > 0).sum(dtype=jnp.int32),
        )
        # what the first pass does not hold goes through a further one
        named["spilled"] = (
            jnp.int32(0) if passes is None else jnp.maximum(named["local"] - passes[0], 0)
        )
        counts = jnp.stack([named[name] for name in MOE_HELD_COUNTS])

    def held_w(name: str) -> dict:
        """[L, held, K, N]: the stack as `_scan_periods` hands it on
        (the product finds its layer there), or one layer's experts as a
        stack of one."""
        w = lp[name]
        if not is_quantized(w):
            w = {"q": w, "s": jnp.ones((*w.shape[:-2], 1, w.shape[-1]), jnp.float32)}
        return w if w["q"].ndim == 4 else jax.tree.map(lambda a: a[None], w)

    w_gate, w_up, w_down = held_w("w_gate"), held_w("w_up"), held_w("w_down")
    if layer is None or w_gate["q"].shape[0] == 1:
        layer = jnp.int32(0)

    f = config.expert_d_ff
    kernel = gm.grouped_matmul_ok(tile, d, f, config.attention_impl) and (
        gm.grouped_matmul_ok(tile, f, d, config.attention_impl)
    )
    if kernel:  # which grid each product got, a fact of its shape
        note_grid(*gm.grid_note(tile, d, f, gate_up=True))
        note_grid(*gm.grid_note(tile, f, d))

    def experts(rows, tile_expert, used):
        """[rows, d]: the buffer's rows through their tiles' experts."""
        grouped = dict(
            layer=layer, tile_expert=tile_expert, used=used, tile=tile,
            kernel=kernel, interpret=jax.default_backend() != "tpu",
        )
        with jax.named_scope("moe_ffn.experts"):
            hidden = gm.grouped_gate_up(
                rows, w_gate, w_up, functools.partial(_activation, kind=config.activation),
                **grouped,
            )
            return gm.grouped_matmul(hidden, w_down, **grouped)

    if passes is None:
        out_rows = experts(rows, tile_expert, used)  # [tiles * tile, d]
        with jax.named_scope("moe_ffn.combine"):
            # an assignment without a row reads out of bounds: zero
            picked = out_rows.at[dest].get(mode="fill", fill_value=0).reshape(t, k, d)
            w = jnp.where(local, weights, 0.0)
            out = jnp.einsum("tkd,tk->td", picked.astype(jnp.float32), w).astype(xf.dtype)
    else:
        out = _held_passes(
            xf, order, by_expert, weights.reshape(t * k), named["local"], k, held, tile, passes,
            experts,
        ).astype(xf.dtype)
    if config.n_shared_experts:
        with jax.named_scope("moe_ffn.shared"):
            gate = _activation(quantized_matmul(xf, lp["ws_gate"]), config.activation)
            hidden = gate * quantized_matmul(xf, lp["ws_up"])
            shared = quantized_matmul(hidden, lp["ws_down"])
            out = out + (
                shared.astype(jnp.float32) * (1.0 / config.n_shared_experts)
            ).astype(xf.dtype)
    return out.reshape(b, s, d), counts


def _parallel_layer(x, lp, kind, sin, cos, config, positions, entry, layer, ctx,
                    token_valid=None):
    """x + Attn(u) + MoE(u), u = norm(x): the one norm of a parallel block.
    A window layer turns q and k and sees keys ``position - sliding_window +
    1 .. position``; a full layer turns nothing and sees everything behind
    it. ``entry``: the kind's cache leaves, and they come back written.
    None: no cache; with ``ctx["table"]`` None a local cache [L, B, Hkv, T, D]
    written at ``positions``; else the kind's page group, read and written
    through ``ctx["table"]`` [B, Tp] (`_paged_attention`)."""
    # the norm is computed in float32 either way: the router reads it before
    # it is rounded to the activation dtype (a bf16 input moves a router logit
    # by 0.002, enough to swap the 8th and 9th of 128 experts for one token in
    # 65), every matrix product after it
    u32 = _norm(x.astype(jnp.float32), lp["attn_norm"], config)
    u = u32.astype(x.dtype)
    b, s, _ = u.shape
    hd, h, hkv = config.resolved_head_dim, config.n_heads, config.n_kv_heads
    window = config.sliding_window if kind == "sliding_attention" else 0
    sub_scope = "attention.window" if window else "attention.full"
    scope = functools.partial(_attention_scope, sub_scope)
    with scope():
        q = quantized_matmul(u, lp["wq"]).reshape(b, s, h, hd)
        k = quantized_matmul(u, lp["wk"]).reshape(b, s, hkv, hd)
        v = quantized_matmul(u, lp["wv"]).reshape(b, s, hkv, hd)
        if window:
            turn = apply_rope_interleaved if config.rope_interleaved else apply_rope
            q, k = turn(q, sin, cos), turn(k, sin, cos)
    if entry is not None and ctx.get("table") is not None:
        attn, entry = _paged_attention(
            q, k, v, entry, ctx["table"], positions, layer, ctx["page_size"], config,
            window=window, lengths=ctx["lengths"], sub_scope=sub_scope,
        )
    else:
        with scope():
            k_all, v_all = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            if entry is not None:
                # the local cache rides the period loop's carry whole and is
                # written in place at ``layer``. `_dense_attention` writes an
                # entry a layer (the sequential block's rides the scan's xs
                # and ys) and forms its indices before the transposes: one
                # code for both reorders one model's admission program
                at = (
                    layer, jnp.arange(b)[:, None, None], jnp.arange(hkv)[None, :, None],
                    positions[:, None, :],
                )
                ck, cv = entry[0].at[at].set(k_all), entry[1].at[at].set(v_all)
                k_all = lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
                v_all = lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
                entry = (ck, cv)
            attn = _dispatch_attention(
                q, k_all, v_all, None, config, True, what="prefill", positions=positions,
                window=window, from_zero=ctx.get("from_zero", False),
                kv_limit=ctx.get("kv_limit"),
            )
    with scope():
        attn = quantized_matmul(attn, lp["wo"])
    with jax.named_scope("moe_ffn"):
        ffn, counts = moe_ffn_held(u, lp, config, token_valid, layer, route_on=u32)
    return x + attn + ffn, entry, counts


# ---------------------------------------------------------------------------
# The period loop: how the layers of a model with ``config.layer_pattern``
# are walked. A KIND of layer is a function (x, lp, kind, layer, entry, rec,
# config, walk) -> (x, entry, rec, the layer's expert counts or None) and a
# row of `_kind_layers`: ``lp`` the layer's weights, ``layer`` its index among
# its kind's, ``entry`` its kind's cache leaves (`_kind_entry`; None without a
# cache and for a kind that keeps none), ``rec`` the recurrent state, ``walk``
# what is the same for every layer (`_run_layers`).
# ---------------------------------------------------------------------------


def _linear_kind(x, lp, kind, layer, entry, rec, config, walk):
    x, rec = _linear_layer(x, lp, config, rec, layer, walk["rctx"])
    return x, entry, rec, None


def _conv_kind(x, lp, kind, layer, entry, rec, config, walk, dense=False, stack_at=None):
    """A ``conv`` layer: the gated short convolution, then the pre-norm FFN,
    the expert layer's (its experts' stacks read at ``stack_at``, its place
    behind the kind's leading dense layers) or, ``dense``, a leading layer's."""
    with jax.named_scope("short_conv"):
        x, rec = _short_conv_block(x, lp, config, rec, layer, walk["rctx"])
    y, counts = _ffn_half(
        x, lp, config, token_valid=walk["token_valid"],
        layer=layer if stack_at is None else stack_at, dense=dense,
    )
    return y, entry, rec, counts if config.is_moe and not dense else None


def _sequential_kind(x, lp, kind, layer, entry, rec, config, walk, dense=False, stack_at=None):
    """The sequential block as a pattern's full-attention layer
    (Olmo-Hybrid's, LFM2's): over the pool's pages where there is a table,
    else over ITS entry of the local cache. ``dense``, ``stack_at``: as
    `_conv_kind`'s."""
    sin, cos, table, how, whole = walk["sin"], walk["cos"], walk["tables"], {}, None
    if config.latent_kinds:
        # the layer at ITS KIND's geometry, rotary table and bound, through its
        # kind's table; a local cache rides the carry whole (the parallel
        # block's way) and the layer's entry is cut out of it and put back
        sin, cos = walk["rotary"][kind]
        how = {"lengths": walk["lengths"]}
        if table is not None:
            table = table[WINDOW if _KIND_KEY[kind] else FULL]
        elif entry is not None:
            whole = entry
            entry = tuple(lax.dynamic_index_in_dim(a, layer, 0, keepdims=False) for a in whole)
    x, entry, counts = _layer_counted(
        x, lp, sin, cos, walk["mask"], config.of_kind(kind), cache_kv=entry,
        cache_positions=walk["positions"], paged_table=table,
        page_size=walk["page_size"], layer=layer, token_valid=walk["token_valid"],
        moe_layer=stack_at, dense=dense, **how,
    )
    if whole is not None:
        entry = tuple(
            lax.dynamic_update_index_in_dim(a, new, layer, 0) for a, new in zip(whole, entry)
        )
    return x, entry, rec, counts if config.is_moe and not dense else None


def _parallel_kind(x, lp, kind, layer, entry, rec, config, walk):
    """The parallel block (command-a-plus's), a window or a full layer, each
    through its kind's table."""
    tables = walk["tables"]
    ctx = {
        "table": None if tables is None else tables[WINDOW if _KIND_KEY[kind] else FULL],
        "page_size": walk["page_size"], "lengths": walk["lengths"],
        "kv_limit": walk["kv_limit"], "from_zero": walk["from_zero"],
    }
    x, entry, counts = _parallel_layer(
        x, lp, kind, walk["sin"], walk["cos"], config, walk["positions"], entry, layer,
        ctx, walk["token_valid"],
    )
    return x, entry, rec, counts


def _kind_layers(config: ModelConfig) -> dict:
    """kind -> its layer function. What an attention kind is follows the
    model's block: the parallel one beside window layers over K and V, else
    the sequential one (window layers over a latent with it)."""
    attention = _parallel_kind if config.parallel_block else _sequential_kind
    return {
        "linear_attention": _linear_kind, "conv": _conv_kind, "full_attention": attention,
        "sliding_attention": attention,
    }


def _scan_periods(params, x, config, state=None, rec=None, **walk):
    """The layer loop of a model with a layer pattern: a scan over its
    periods whose body runs the period's layers in order, each kind from a
    stack of its own and through its function (`_kind_layers`). The carry is
    (x, state, rec, the summed expert counts), None where a model has none:
    the page pool, or the parallel block's local cache, carried and written
    in place as in `_scan_layers_inplace`, the recurrent state beside it.
    The sequential block writes a local cache an entry a layer: its local
    cache (``state`` without tables, the admit group's temporary) rides the
    xs and comes back as ys. Leading dense layers (``n_leading_dense``) are
    layers of their kinds, the first of them: the periods that hold them run
    AHEAD of the scan, the same body with those layers' weights from
    ``params["dense_layers"][kind]``, at their own places in their kind's
    pages and state; a kind's stack under ``params["layers"]`` then starts
    behind its dense layers (``config.dense_of``). Returns (x, state, rec,
    counts)."""
    pattern = config.layer_pattern
    per = {kind: pattern.count(kind) for kind in set(pattern)}
    periods = config.n_periods
    stacks = params["layers"]
    layers = _kind_layers(config)
    # held experts' weights go on as the stack: the grouped product reads its
    # blocks at (layer, expert) where they lie
    whole = _HELD_EXPERTS if config.holds_experts else ()
    # leading dense layers INSIDE the first periods, or (``lead``) standing
    # before the first of them: the kind's layers then start behind those
    lead = config.dense_ahead
    ahead = 0 if lead else -(-config.n_leading_dense // len(pattern))  # periods with one
    behind = {kind: config.dense_of(kind) for kind in per}
    shift = behind if lead else dict.fromkeys(per, 0)
    if config.latent_kinds:  # a rotary table a kind
        walk["rotary"] = {
            kind: _rope_freqs(walk["positions"], config.of_kind(kind)) for kind in per
        }
    local = None
    if state is not None and walk["tables"] is None and not config.has_window:
        local, state = jax.tree.map(
            lambda a: a.reshape(periods, per["full_attention"], *a.shape[1:]), state
        ), None

    def period(carry, local_p, p, dense=0, places=pattern):
        """Period ``p``'s layers, the first ``dense`` of them leading dense
        layers (a period of the scan has none); ``places``: the kinds run, the
        period's or, before the first period, the leading layers'."""
        x, state, rec, counts = carry
        at = dict.fromkeys(per, 0)
        written = []
        for place, kind in enumerate(places):
            i = at[kind]
            at[kind] += 1
            layer = p * per[kind] + i
            if places is pattern and shift[kind]:  # behind the kind's leading layers
                layer = layer + shift[kind]
            # ONE layer's weights, sliced where they are used: the stacks are
            # closed over, not scanned. A period's slice [per, ...] of a
            # scanned stack is a buffer of its own, all of a period's weights
            # copied once more a step (a third of the decode step on a v5e,
            # PERF.md section 6, PR 32)
            if place < dense:
                stack, kept, at_stack = params["dense_layers"][kind], (), layer
                how = {"dense": True}
            else:
                stack, kept = stacks[kind], whole
                at_stack = layer - behind[kind] if behind[kind] else layer
                how = {"stack_at": at_stack} if behind[kind] else {}
            lp = {
                key: leaf if key in kept else jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, at_stack, 0, keepdims=False), leaf
                )
                for key, leaf in stack.items()
            }
            entry = None
            if kind in _KIND_KEY and local_p is not None:
                entry = (
                    jax.tree.map(lambda a: a[i], local_p["k"]),
                    jax.tree.map(lambda a: a[i], local_p["v"]),
                )
            elif kind in _KIND_KEY and state is not None:
                entry = _kind_entry(state, kind, config)
            x, entry, rec, c = layers[kind](x, lp, kind, layer, entry, rec, config, walk, **how)
            if c is not None:
                counts = counts + c
            if entry is not None and local_p is not None:
                written.append(entry)
            elif entry is not None:
                state = _with_entry(state, kind, entry, config)
        ys = None
        if local_p is not None:
            ys = {
                "k": jax.tree.map(lambda *a: jnp.stack(a), *[e[0] for e in written]),
                "v": jax.tree.map(lambda *a: jnp.stack(a), *[e[1] for e in written]),
            }
        return (x, state, rec, counts), ys

    counts = jnp.zeros(len(moe_count_names(config)), jnp.int32) if config.is_moe else None
    carry, first = (x, state, rec, counts), []
    if lead:
        carry, _ = period(
            carry, None, 0, dense=lead, places=[pattern[i % len(pattern)] for i in range(lead)]
        )
    for p in range(ahead):
        carry, ys = period(
            carry, None if local is None else jax.tree.map(lambda a: a[p], local), p,
            dense=min(config.n_leading_dense - p * len(pattern), len(pattern)),
        )
        first.append(ys)
    (x, state, rec, counts), ys = lax.scan(
        lambda carry, inputs: period(carry, *inputs), carry,
        (
            local if not ahead or local is None else jax.tree.map(lambda a: a[ahead:], local),
            jnp.arange(ahead, periods) if ahead else jnp.arange(periods),
        ),
    )
    if local is not None:
        if ahead:
            ys = jax.tree.map(lambda *a: jnp.concatenate([jnp.stack(a[:-1]), a[-1]]), *first, ys)
        state = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), ys)
    return x, state, rec, counts


def _embed(params: Params, tokens: jax.Array, config: ModelConfig) -> jax.Array:
    table = params["embed"]
    with jax.named_scope("embed"):
        if is_quantized(table):
            x = (
                table["q"][tokens].astype(jnp.float32) * table["s"][tokens]
            ).astype(_dtype(config))
        else:
            x = table[tokens]
        if config.embedding_scale:
            x = x * jnp.sqrt(jnp.float32(config.d_model)).astype(x.dtype)
    return x


def _unembed(params: Params, x: jax.Array, config: ModelConfig) -> jax.Array:
    with jax.named_scope("head"):
        x = _norm(x, params["final_norm"], config)
        if config.tie_embeddings:
            table = params["embed"]
            head = (
                dequantize_weight(table, x.dtype) if is_quantized(table) else table
            ).T
            logits = (x @ head).astype(jnp.float32)
        else:
            logits = quantized_matmul(x, params["lm_head"]).astype(jnp.float32)
        if config.logit_scale != 1.0:
            logits = logits * config.logit_scale
        return _softcap(logits, config.final_logit_softcap)


def _split_lora(lora: Optional[dict]):
    """Split the stacked adapter pool into its scannable per-layer arrays
    (leading L axis — ride the layer scan's xs) and the layer-independent
    ``scale`` vector (closed over by the scan body)."""
    if lora is None:
        return None, None
    layers = {k: v for k, v in lora.items() if k != "scale"}
    return (layers or None), lora.get("scale")


def _split_held(layers: dict, config: ModelConfig):
    """(the leaves a layer scan slices, the leaves it hands on whole). Where
    the sequential block holds its experts (``experts_held``) their weights
    go on as the stack [L, held, K, N], as `_scan_periods` hands them
    on: the grouped product reads its blocks at (layer, expert) where they
    lie, and a kernel's operand sliced by the scan would be copied first, a
    layer's experts a layer. Every other model: all of them sliced, as ever."""
    if not config.experts_held or config.has_window:
        return layers, None
    return (
        {k: v for k, v in layers.items() if k not in _HELD_EXPERTS},
        {k: layers[k] for k in _HELD_EXPERTS},
    )


def _scan_layers(
    params, x, sin, cos, mask, config, cache=None, cache_positions=None, causal=True,
    collect_kv=False, lora=None, adapter_rows=None, token_valid=None,
):
    """lax.scan over stacked layer params; carries (x, cache) and returns
    them with the layers' summed MOE_COUNTS as a third element. With
    ``collect_kv`` (cache-less) the scan stacks each layer's roped K/V into
    [L, B, Hkv, S, D] arrays — the makings of a serving cache. ``lora``
    (the stacked adapter pool) joins the scan xs so each layer body sees
    its own [R, din, r] slices."""
    layers, held = _split_held(params["layers"], config)
    lora_layers, lora_scale = _split_lora(lora)
    # a dense model's zeros stay out of the scan: its programs are the
    # ones they were, and the counts a constant beside them
    moe = config.is_moe
    # the layer's index rides the scan only where held experts need it (the
    # scan is over the layers behind the leading dense ones)
    index = None if held is None else jnp.arange(config.n_layers - config.n_leading_dense)
    leaves = config.page_leaves

    def body(carry, inputs):
        lp, entry, ll, l = inputs
        y, new_kv, counts = _layer_counted(
            carry, lp if held is None else {**lp, **held}, sin, cos, mask, config,
            cache_kv=entry, cache_positions=cache_positions, causal=causal,
            collect_kv=collect_kv, lora=ll, lora_scale=lora_scale,
            adapter_rows=adapter_rows, token_valid=token_valid, moe_layer=l,
        )
        return y, (new_kv, counts if moe else None)

    entries = None if cache is None else tuple(cache[leaf] for leaf in leaves)
    x, (new_kv, counts) = lax.scan(body, x, (layers, entries, lora_layers, index))
    if cache is not None:
        new_kv = dict(zip(leaves, new_kv))
    return x, new_kv, counts.sum(0) if moe else _no_moe_counts()


def _scan_layers_inplace(
    params, x, sin, cos, mask, config, pool, cache_positions, paged_table,
    page_size, verify=False, lora=None, adapter_rows=None, block=False,
    token_valid=None,
):
    """Layer loop with the page pool carried through the scan and updated
    IN PLACE, instead of consumed as scan ``xs`` and stacked as fresh ``ys``.

    The xs/ys form allocates a second pool-sized buffer every call — inside
    an outer step loop (engine `_paged_decode_chunk`'s lax.scan) that temp
    is live across the whole chunk. A while-loop carry is aliased in place
    by XLA. The pool [L, P, Hkv, ps, D] goes down to the attention block
    whole with the layer index, which scatters the new K/V rows at
    ``[l, page, head, offset]`` and reads the row's pages at ``(l, page)``.
    No per-layer entry is formed: the step's device program holds no operand
    of the shape [P, Hkv, ps, D] (tests/test_tpu_compile.py asserts it on
    the compiled HLO; slicing the entry out of the carry and writing it back
    was two real copies of it a layer on a v5e: PERF.md §6, PR 25).

    Returns (x, pool, the layers' summed counts, `moe_count_names`)."""
    layers, held = _split_held(params["layers"], config)
    lora_layers, lora_scale = _split_lora(lora)
    leaves = config.page_leaves
    # behind leading dense layers (`_run_behind_dense_layers` ran them) a layer's
    # place in the pool and its place among the experts' stacks part
    first = config.n_leading_dense

    def body(carry, inputs):
        x, pool = carry
        lp, l, ll = inputs
        y, entry, counts = _layer_counted(
            x, lp if held is None else {**lp, **held}, sin, cos, mask, config,
            cache_kv=tuple(pool[leaf] for leaf in leaves),
            cache_positions=cache_positions, verify=verify,
            paged_table=paged_table, page_size=page_size, lora=ll,
            lora_scale=lora_scale, adapter_rows=adapter_rows,
            layer=l + first if first else l, block=block, token_valid=token_valid,
            moe_layer=l if first else None,
        )
        return (y, dict(zip(leaves, entry))), (counts if config.is_moe else None)

    (x, pool), counts = lax.scan(
        body, (x, pool), (layers, jnp.arange(config.n_layers - first), lora_layers)
    )
    # a dense model's zeros stay out of the scan (see _scan_layers)
    counts = counts.sum(0) if config.is_moe else _no_moe_counts()
    return x, pool, counts


def _run_behind_dense_layers(
    params, x, sin, cos, mask, config, positions, state, table, page_size, counted
):
    """`_run_layers` of a model whose first ``n_leading_dense`` layers carry a
    dense FFN: those run first, each ONE `_layer_counted` on a layer of their
    own stack (``params["dense_layers"]``; the page pool's layers 0 ..), then
    the uniform loop over the expert layers (the pool's layers behind them).
    Nothing is added to the loops: a local cache's leading entries are cut
    off before the scan and joined on after it."""
    first, leaves = config.n_leading_dense, config.page_leaves
    written = []
    for i in range(first):
        lp = jax.tree.map(lambda a: a[i], params["dense_layers"])
        if table is not None:
            x, entry, _ = _layer_counted(
                x, lp, sin, cos, mask, config, cache_kv=tuple(state[leaf] for leaf in leaves),
                cache_positions=positions, paged_table=table, page_size=page_size,
                layer=jnp.int32(i), dense=True,
            )
            state = dict(zip(leaves, entry))
        else:
            entry = None if state is None else tuple(state[leaf][i] for leaf in leaves)
            x, entry, _ = _layer_counted(
                x, lp, sin, cos, mask, config, cache_kv=entry, cache_positions=positions,
                dense=True,
            )
            written.append(entry)
    if table is not None:
        return _scan_layers_inplace(
            params, x, sin, cos, mask, config, state, positions, table, page_size,
            token_valid=counted,
        )
    rest = None if state is None else {leaf: state[leaf][first:] for leaf in leaves}
    x, rest, counts = _scan_layers(
        params, x, sin, cos, mask, config, cache=rest, cache_positions=positions,
        token_valid=counted,
    )
    if state is not None:
        rest = {
            leaf: jnp.concatenate([jnp.stack([w[j] for w in written]), rest[leaf]])
            for j, leaf in enumerate(leaves)
        }
    return x, rest, counts


def _run_layers(
    params, x, sin, cos, mask, config, positions, state=None, *, table=None, page_size=0,
    row_positions=None, valid=None, counted=None, rec_rows=None, fresh=True,
    from_zero=False, kv_limit=None, lora=None, adapter_rows=None,
):
    """Walk a model's layers, whichever loop is theirs: the ONE place the
    entry points' paths part. ``state``: None, a local cache, or (with
    ``table``) the page pool, a recurrent state riding with either as
    ``"rec"`` (`join_rec`); it comes back written. ``valid`` [B, S]: each
    row's real tokens, a prefix (what a recurrent state may take in), and
    ``counted`` those the expert counts call real; None: all of them. A
    decode step gives ``row_positions`` [B] instead, and a pattern model's
    row is real where its table maps its position (the uniform loop counts
    every row, as it has: S5). ``rec_rows``, ``fresh``: the recurrent
    state's rows and which start from zero (`_linear_attention_block`).
    Returns (x, state, the layers' summed counts, `moe_count_names`)."""
    if config.n_leading_dense and not config.layer_pattern:
        return _run_behind_dense_layers(
            params, x, sin, cos, mask, config, positions, state, table, page_size, counted
        )
    if not config.layer_pattern:
        # the stacks ride the scan's xs here and are closed over in the
        # period loop: folding the two is a change of these models' programs
        # (ROADMAP D15)
        if table is None:
            return _scan_layers(
                params, x, sin, cos, mask, config, cache=state, cache_positions=positions,
                lora=lora, adapter_rows=adapter_rows, token_valid=counted,
            )
        # a segment's counts reach no result where the model returns none
        # (`paged_prefill_segment_inplace`): dead code, and none of it lowers
        return _scan_layers_inplace(
            params, x, sin, cos, mask, config, state, positions, table, page_size,
            lora=lora, adapter_rows=adapter_rows, token_valid=counted,
        )
    kv, rec = (None, None) if state is None else split_rec(state)
    lengths = None
    if config.latent_kinds and table is not None:
        # the entry points' mask is over ONE table: here it is the full group's
        mask = _paged_mask(table[FULL], page_size, positions)
    if row_positions is not None:
        # the row's live length, from the full group's table (a prefix of
        # mapped pages); a window layer reads its last ``sliding_window``. A
        # row whose table maps nothing, or that has stepped past its pages,
        # is idle: its recurrent state stays as it is
        pages = kv[config.page_leaves[0]]
        pages = pages["q"] if isinstance(pages, dict) else pages
        lengths = _paged_lengths(
            table[FULL] if config.has_window else table, row_positions, page_size,
            pages.shape[1],
        )
        valid = counted = (lengths > row_positions)[:, None]
    x, kv, rec, counts = _scan_periods(
        params, x, config, kv, rec, sin=sin, cos=cos, mask=mask, positions=positions,
        tables=table, page_size=page_size, lengths=lengths, token_valid=counted,
        rctx=None if valid is None else {"rows": rec_rows, "valid": valid, "fresh": fresh},
        from_zero=from_zero, kv_limit=kv_limit,
    )
    return (
        x, None if state is None else join_rec(kv, rec),
        _no_moe_counts() if counts is None else counts,
    )


# ---------------------------------------------------------------------------
# Public entry points (all jittable; config is static)
# ---------------------------------------------------------------------------


def _visible(q_pos: jax.Array, kv_pos: jax.Array, config: ModelConfig) -> jax.Array:
    """Whether the query at ``q_pos`` sees the key at ``kv_pos``: causal, or,
    for a model that fills blocks, causal across blocks of ``block_length``
    and two-way inside one."""
    if config.fills_blocks:
        return kv_pos // config.block_length <= q_pos // config.block_length
    return kv_pos <= q_pos


@functools.partial(jax.jit, static_argnames=("config",))
def forward(
    params: Params, tokens: jax.Array, config: ModelConfig,
    positions: Optional[jax.Array] = None,  # [3, B, S]: a position triple a token
) -> jax.Array:
    """Full-sequence causal forward → logits [B, S, V] (training / scoring);
    causal across blocks and two-way inside one for a model that fills
    blocks (`_visible`), whose logits at a position score the token AT it.

    With ``config.ring_axis`` set (under shard_map, parallel.sp), ``tokens``
    is the LOCAL sequence block; RoPE positions are globalised from the ring
    index and the causal mask is handled inside ring attention. ``positions``
    (a model with ``mrope_section``): the rotary's three position streams,
    text's being equal and the default; the mask stays causal in the order
    of ``tokens`` and the indexer turns by the first stream.
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if config.ring_axis is not None:
        positions = positions + lax.axis_index(config.ring_axis) * s
    sin, cos = _rope_freqs(positions, config)
    mask = jnp.tril(jnp.ones((s, s), jnp.bool_))[None, :, :]
    if config.fills_blocks:
        mask = _visible(jnp.arange(s)[:, None], jnp.arange(s)[None, :], config)[None]
    mask = jnp.broadcast_to(mask, (b, s, s))
    x = _embed(params, tokens, config)
    x, _, _ = _run_layers(
        params, x, sin, cos, mask, config, _text_positions(positions), from_zero=True
    )
    return _unembed(params, x, config)


def encode(
    params: Params,
    tokens: jax.Array,  # [B, S] padded
    lengths: jax.Array,  # [B] true lengths
    config: ModelConfig,
) -> jax.Array:
    """Mean-pooled, L2-normalised final hidden states → [B, D] embeddings.

    Backs the TPU EmbeddingsService (replacing the reference's remote
    embedding providers — EmbeddingsService.java:24-36). Bidirectional
    attention within each prompt (encoder-style pooling, not causal LM).
    """
    _no_pattern(config, "encode")
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    sin, cos = _rope_freqs(positions, config)
    valid = positions < lengths[:, None]  # [B, S]
    mask = valid[:, None, :] & valid[:, :, None]  # full attention over real tokens
    x = _embed(params, tokens, config)
    x, _, _ = _scan_layers(params, x, sin, cos, mask, config, causal=False)
    x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
    w = valid[:, :, None].astype(jnp.float32)
    pooled = (x.astype(jnp.float32) * w).sum(1) / jnp.maximum(w.sum(1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def make_kv_cache(
    config: ModelConfig, batch: int, max_len: int, dtype=None, window_batch: int = 0,
) -> KVCache:
    """Head-major cache: [L, B, Hkv, T, D] — (T, D) are the tiled trailing
    dims, so Pallas kv blocks are (block_k, D) slices with no relayout.

    With ``config.kv_cache_dtype == "int8"`` each k/v entry is an int8 dict
    ``{"q": int8 [L,B,Hkv,T,D], "s": f32 [L,B,Hkv,T]}`` (per-token per-head
    symmetric scales; ~2x less decode cache bandwidth).
    """
    dtype = dtype or _dtype(config)

    def leaves(kind: str, rows: int) -> KVCache:
        pack = config.kv_head_pack  # heads of 64: two to a lane row
        shape = (
            config.n_layers_of(kind), rows, config.n_kv_heads // pack, max_len,
            config.resolved_head_dim * pack,
        )
        if config.has_latent:
            # ONE row a token in place of K and V, K's layout with one head:
            # [L, B, 1, T, latent_key_width]; the indexer's key beside it
            # where the model has an indexer. Each at the KIND's own width
            # (a window kind of its own geometry, which has no indexer)
            of = config.of_kind(kind)
            cache = {"lat": jnp.zeros((*shape[:2], 1, max_len, of.latent_key_width), dtype)}
            if of.has_indexer:
                cache["ik"] = jnp.zeros((*shape[:2], max_len, of.index_key_width), dtype)
            return cache
        if config.kv_cache_dtype == "int8":
            if _KIND_KEY[kind]:
                raise NotImplementedError(f"an int8 KV cache for window layers ({config.name})")
            entry = lambda: {  # noqa: E731
                "q": jnp.zeros(shape, jnp.int8),
                "s": jnp.full(shape[:-1], 1e-8 / 127.0, jnp.float32),
            }
            return {"k": entry(), "v": entry()}
        cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if config.has_indexer:
            # the indexer's key a token beside K and V: [L, B, T, Di], one head
            cache["ik"] = jnp.zeros((*shape[:2], max_len, config.index_key_width), dtype)
        return cache

    cache = leaves("full_attention", batch)
    for kind, key in _KIND_KEY.items():
        if key and config.n_layers_of(kind):
            # the window layers' entries beside the full layers', ``"win"``:
            # the same leaves over ``window_batch`` rows (a page pool's
            # window group has its own number of pages)
            cache[key] = leaves(kind, window_batch or batch)
    return cache


@functools.partial(
    jax.jit, static_argnames=("config", "moe_counts"), donate_argnames=("cache",)
)
def prefill(
    params: Params,
    tokens: jax.Array,  # [B, S] padded prompts
    lengths: jax.Array,  # [B] true prompt lengths
    cache: KVCache,
    config: ModelConfig,
    lora: Optional[dict] = None,  # stacked adapter pool (serving/adapters.py)
    adapter_rows: Optional[jax.Array] = None,  # [B] pool row per prompt
    moe_counts: bool = False,
    real_lengths: Optional[jax.Array] = None,  # [B]; 0 for a padding row
    rec_rows: Optional[jax.Array] = None,  # [B] each prompt's row of ``cache["rec"]``
):
    """Process prompts, fill cache slots 0..len, return logits at the last
    real token of each prompt ([B, V]). With adapters, the prompt's K/V
    carry the wk/wv deltas — a tenant's cache is its own from token 0.
    ``moe_counts`` (here and on the decode and verify steps below; the
    segment entry points return none) appends the summed MOE_COUNTS of the
    call to the returned tuple; positions past a row's
    length are the padding its `*_real` counts leave out (``real_lengths``
    where a whole row is padding: the engine gives such a row length 1).
    A model with recurrent layers takes its state as ``cache["rec"]``
    (`join_rec`) and hands it back there, each prompt's final state, taken at
    its true length, written to row ``rec_rows[b]`` (out of bounds: dropped)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    sin, cos = _rope_freqs(positions, config)
    t = cache_width(cache)
    # causal over the prompt, nothing beyond; cache cols ≥ S are masked out
    q_pos = positions  # [B, S]
    kv_pos = jnp.arange(t)[None, None, :]  # [1, 1, T]
    mask = _visible(q_pos[:, :, None], kv_pos, config)
    mask = mask & (kv_pos < s)
    x = _embed(params, tokens, config)
    real = lengths if real_lengths is None else real_lengths
    x, cache, counts = _run_layers(
        params, x, sin, cos, mask, config, positions, cache,
        valid=positions < lengths[:, None], counted=positions < real[:, None],
        rec_rows=rec_rows, from_zero=True, kv_limit=s, lora=lora, adapter_rows=adapter_rows,
    )
    last = jnp.clip(lengths - 1, 0, s - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]  # [B, D]
    logits = _unembed(params, x_last[:, None, :], config)[:, 0]
    return (logits, cache, counts) if moe_counts else (logits, cache)


@functools.partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def decode_step(
    params: Params,
    tokens: jax.Array,  # [B] current token per slot
    positions: jax.Array,  # [B] position of that token
    cache: KVCache,
    config: ModelConfig,
) -> tuple[jax.Array, KVCache]:
    """One decode step for every active slot → logits [B, V], updated cache."""
    _no_pattern(config, "decode_step")
    b = tokens.shape[0]
    t = cache_width(cache)
    pos2 = positions[:, None]  # [B, 1]
    sin, cos = _rope_freqs(pos2, config)
    mask = _seen(pos2, t)  # attend to everything written ≤ position
    x = _embed(params, tokens[:, None], config)
    x, cache, _ = _scan_layers(
        params, x, sin, cos, mask, config, cache=cache, cache_positions=pos2
    )
    return _unembed(params, x, config)[:, 0], cache


# ---------------------------------------------------------------------------
# Paged entry points — the bodies of the engine's ONE-program-each decode /
# verify / segment dispatches (serving/engine.py). The page table bounds
# what a slot can read (its mapped pages), so each is one program for every
# sequence-length mix. None are separately jitted: they are the bodies of
# the engine's fused chunks, where the in-place layer scan keeps a chunk
# from materializing a second pool-sized buffer.
# ---------------------------------------------------------------------------


def _paged_mask(table: jax.Array, page_size: int, positions: jax.Array):
    """Causal mask over the gathered paged view: logical column t of slot b
    is visible to query j iff t <= positions[b, j]. Columns backed by
    unmapped (clamp-gathered garbage) pages always sit past the written
    frontier, so the mask is also what makes the clamped gather safe."""
    return _seen(positions, table.shape[1] * page_size)


def paged_decode_step_inplace(
    params: Params,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    pool: KVCache,  # page pool [L, P, Hkv, ps, D]
    table: jax.Array,  # [B, Tp] physical page per logical page
    config: ModelConfig,
    page_size: int,
    lora: Optional[dict] = None,
    adapter_rows: Optional[jax.Array] = None,
    moe_counts: bool = False,
):
    """decode_step through the page table: ONE compiled program for every
    sequence-length mix (a slot reads exactly its mapped pages). With
    adapters, the
    per-slot gathered low-rank terms keep it ONE program for every
    base/adapter mix too — adapter_rows is data, never a shape."""
    pos2 = positions[:, None]
    sin, cos = _rope_freqs(pos2, config)
    mask = _paged_mask(table, page_size, pos2)
    x = _embed(params, tokens[:, None], config)
    x, pool, counts = _run_layers(
        params, x, sin, cos, mask, config, pos2, pool, table=table, page_size=page_size,
        row_positions=positions, fresh=None, lora=lora, adapter_rows=adapter_rows,
    )
    logits = _unembed(params, x, config)[:, 0]
    return (logits, pool, counts) if moe_counts else (logits, pool)


def paged_verify_step_inplace(
    params: Params,
    tokens: jax.Array,  # [B, K+1]
    positions: jax.Array,  # [B] position of each row's FIRST token
    pool: KVCache,
    table: jax.Array,
    config: ModelConfig,
    page_size: int,
    lora: Optional[dict] = None,
    adapter_rows: Optional[jax.Array] = None,
    moe_counts: bool = False,
):
    """Multi-token speculative verify through the page table: score K
    drafts per slot in ONE forward — logits at EVERY position come back
    ([B, K+1, V], unlike the segment's last-token-only), so the engine's
    rejection sampler can accept the longest valid prefix. Writes K/V for
    all K+1 tokens at [positions, positions+K+1); rows past the accepted
    length hold stale draft K/V, which is safe because positions advance
    only past ACCEPTED tokens and the next dispatch overwrites the stale
    page columns before any causal mask can reach them."""
    _no_pattern(config, "paged_verify_step_inplace")
    if config.has_indexer:
        raise NotImplementedError(
            f"paged_verify_step_inplace: no verify under a learned selection ({config.name})"
        )
    b, s = tokens.shape
    pos = positions[:, None] + jnp.arange(s)[None, :]
    sin, cos = _rope_freqs(pos, config)
    mask = _paged_mask(table, page_size, pos)
    x = _embed(params, tokens, config)
    x, pool, counts = _scan_layers_inplace(
        params, x, sin, cos, mask, config, pool, pos, table, page_size,
        verify=True, lora=lora, adapter_rows=adapter_rows,
    )
    logits = _unembed(params, x, config)
    return (logits, pool, counts) if moe_counts else (logits, pool)


def paged_block_step_inplace(
    params: Params,
    tokens: jax.Array,  # [B, S] each row's block, the mask id where it is open
    starts: jax.Array,  # [B] the block's first position, a multiple of S
    pool: KVCache,
    table: jax.Array,
    config: ModelConfig,
    page_size: int,
    moe_counts: bool = False,
):
    """One PASS of a model that fills blocks, through the page table: each
    row's block of S = ``block_length`` tokens at ``starts .. starts + S - 1``
    against the row's pages and itself, logits at all S ([B, S, V]: the
    logits at a position score the token AT it). The S queries of a row see
    one another and everything behind the block, so all of them see keys
    ``0 .. starts + S - 1``. The block's K/V are written to the row's pages
    in the pass that computes them, a denoise pass's too: positions advance
    only at a commit (the pass over the clean block), whose write replaces
    them before any later block can read them, and inside a pass the block
    reads its own K/V as this pass wrote them, which is the arithmetic of a
    forward over prefix and block (the argument `paged_verify_step_inplace`
    makes for rejected drafts). So every pass is this one program, a commit
    is a pass whose row has nothing open, and rows at different steps of
    different blocks ride one dispatch. A row whose table maps nothing (idle,
    padding, warm-up) writes nothing and reads nothing; its assignments count
    as padding's."""
    if not config.fills_blocks or tokens.shape[1] != config.block_length:
        raise ValueError(f"{config.name}: a block pass takes block_length tokens a row")
    b, s = tokens.shape
    pos = starts[:, None] + jnp.arange(s)[None, :]
    sin, cos = _rope_freqs(pos, config)
    # the jnp path's mask: every key up to the block's end, for every query
    mask = _paged_mask(table, page_size, jnp.broadcast_to(pos[:, -1:], (b, s)))
    num_pages = (pool["k"]["q"] if isinstance(pool["k"], dict) else pool["k"]).shape[1]
    live = _paged_lengths(table, pos[:, -1], page_size, num_pages) > pos[:, -1]
    x = _embed(params, tokens, config)
    x, pool, counts = _scan_layers_inplace(
        params, x, sin, cos, mask, config, pool, pos, table, page_size, block=True,
        token_valid=jnp.broadcast_to(live[:, None], (b, s)),
    )
    # the head over B x S rows of one matrix: the logits come out row-major,
    # as `block_choice` reads them (over [B, S, d] the chip lays them out
    # S-major and copies 156 MB of float32 four times a pass)
    logits = _unembed(params, x.reshape(b * s, 1, -1), config).reshape(b, s, -1)
    return (logits, pool, counts) if moe_counts else (logits, pool)


def paged_prefill_segment_inplace(
    params: Params,
    tokens: jax.Array,  # [B, W] one padded prompt segment per row
    offsets: jax.Array,  # [B] global position of each row's segment start
    seg_lengths: jax.Array,  # [B] true token count within the segment
    pool: KVCache,
    table: jax.Array,
    config: ModelConfig,
    page_size: int,
    lora: Optional[dict] = None,
    adapter_rows: Optional[jax.Array] = None,
    state_rows: Optional[jax.Array] = None,  # [B] each row's recurrent state row
    moe_counts: bool = False,  # a model that holds experts returns its segment's counts
):
    """Chunked/suffix prefill straight into the slot's pages: process one
    segment of a longer prompt against pages whose columns [0, offsets) were
    written by earlier segments (or aliased from the prefix index). K/V for
    the segment scatter at global positions [offsets, offsets+W) and
    attention reads the prefix THROUGH THE TABLE, causally over prefix +
    segment — which is what makes prefix reuse zero-copy (aliased pages are
    simply visible). offsets=0 with a fresh table is a cold prefill. Returns
    logits at the last real token of the segment ([B, V]) — meaningful only
    on the final segment.

    The reference has no counterpart (its only long-input handling is
    TextSplitter.java chunking BEFORE the model); this is what makes the
    128k-context presets actually servable with bounded activation memory."""
    b, s = tokens.shape
    positions = offsets[:, None] + jnp.arange(s)[None, :]
    sin, cos = _rope_freqs(positions, config)
    mask = _paged_mask(table, page_size, positions)
    x = _embed(params, tokens, config)
    valid = jnp.arange(s)[None, :] < seg_lengths[:, None]
    # the recurrent state carries over from the row's earlier segments; a
    # segment at offset 0 starts it from zero
    x, pool, counts = _run_layers(
        params, x, sin, cos, mask, config, positions, pool, table=table,
        page_size=page_size, valid=valid, counted=valid, rec_rows=state_rows,
        fresh=offsets == 0, lora=lora, adapter_rows=adapter_rows,
    )
    last = jnp.clip(seg_lengths - 1, 0, s - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    logits = _unembed(params, x_last[:, None, :], config)[:, 0]
    return (logits, pool, counts) if moe_counts else (logits, pool)


def insert_copies_pages(
    pool: KVCache, width: int, page_size: int, config: Optional[ModelConfig] = None
) -> bool:
    """Whether `paged_insert_cache` writes a local cache of ``width`` columns
    into ``pool`` by whole pages (``ops/attention.paged_insert_pages``): one
    page group of K and V (a window model's two and a latent's leaf keep the
    scatter below) that `_copies_pages` takes, the rule a segment's write
    shares."""
    k = pool.get("k")  # None: a latent's leaf, written by the scatter
    return "win" not in pool and k is not None and _copies_pages(k, width, page_size, config)


def paged_insert_cache(
    pool: KVCache, local_cache: KVCache, tables: jax.Array, page_size: int,
    config: Optional[ModelConfig] = None,
) -> KVCache:
    """Write a batched prefill's local cache ([L, n, Hkv, W, D], the
    admit-group temporary) into each row's pages. Positions are [0, W) per row; rows whose
    table is all out-of-bounds (padding) drop every write. Where
    `insert_copies_pages` says so (on the chip: a bf16 pool of one page
    group, no mesh) the write is a copy of each mapped page where the pool
    lies, ``ops/attention.paged_insert_pages``; the int8 pool, a window
    model's two groups, a mesh and every backend but the TPU keep the
    scatter below, one update a (row, kv head, position), which is that
    write's reference: the pools are bit-equal. ``config``: the engine's,
    for its mesh and ``attention_impl``."""
    if "ik" in pool:
        # the indexer's keys [L, n, W, Di] to [L, P, ps, Di] by the same table;
        # K and V as for every model
        w = local_cache["ik"].shape[2]
        full = tables[FULL] if "win" in pool else tables  # ([2, n, Tp] with a window group)
        positions = jnp.broadcast_to(jnp.arange(w)[None, :], (full.shape[0], w))
        pages, offs = _page_index(full, positions, page_size, pool["ik"].shape[1])
        with jax.named_scope("kv_pool.write"):
            ik = pool["ik"].at[:, pages, offs].set(
                local_cache["ik"].astype(pool["ik"].dtype), mode="drop"
            )
        strip = lambda tree: {k: v for k, v in tree.items() if k != "ik"}  # noqa: E731
        return {
            **paged_insert_cache(strip(pool), strip(local_cache), tables, page_size, config),
            "ik": ik,
        }
    n = tables.shape[0]
    width = jax.tree.leaves(local_cache)[0].shape[3]
    by_page = insert_copies_pages(pool, width, page_size, config)
    if config is not None and config.kv_head_pack > 1:  # (as `paged-decode-write`)
        from langstream_tpu.ops.attention import note_grid

        note_grid(f"paged-insert[w={width}]", "paged_insert_pages" if by_page else "scatter")
    if by_page:
        from langstream_tpu.ops.attention import paged_insert_pages

        kv, rec = split_rec(pool)
        with jax.named_scope("kv_pool.write"):
            k, v = paged_insert_pages(
                (local_cache["k"], local_cache["v"]), kv["k"], kv["v"], tables,
                interpret=jax.default_backend() != "tpu",
            )
        return join_rec({"k": k, "v": v}, rec)

    def put(pl_entry, loc):
        w = loc.shape[3]
        positions = jnp.broadcast_to(jnp.arange(w)[None, :], (n, w))
        pages, offs = _page_index(tables, positions, page_size, pl_entry.shape[1])
        hkv = loc.shape[2]
        pidx = pages[:, None, :]  # [n, 1, W]
        oidx = offs[:, None, :]
        hidx = jnp.arange(hkv)[None, :, None]
        # leading ':' keeps the layer axis; advanced indices are adjacent so
        # the scattered dims stay in place
        return pl_entry.at[:, pidx, hidx, oidx].set(
            loc.astype(pl_entry.dtype), mode="drop"
        )

    if "win" in pool:
        # both kinds' rows, each through its own table ([2, n, Tp])
        both, n = tables, tables.shape[1]
        with jax.named_scope("kv_pool.write"):
            tables = both[FULL]
            out = {
                leaf: put(pool[leaf], local_cache[leaf]) for leaf in pool
                if leaf not in ("win", "rec")  # "k" and "v", or a latent kind's "lat"
            }
            tables = both[WINDOW]
            out["win"] = jax.tree.map(put, pool["win"], local_cache["win"])
        return out
    kv, rec = split_rec(pool)
    with jax.named_scope("kv_pool.write"):
        return join_rec(jax.tree.map(put, kv, local_cache), rec)


# ---------------------------------------------------------------------------
# Loss (fine-tuning; used by __graft_entry__ dryrun + training module)
# ---------------------------------------------------------------------------


def causal_lm_loss(params: Params, tokens: jax.Array, config: ModelConfig) -> jax.Array:
    """Next-token cross-entropy over a [B, S] batch (pad id 0 masked out)."""
    logits = forward(params, tokens, config)  # [B, S, V]
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = (targets != 0).astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)

"""Weight-only int8 quantization for serving.

Decode is HBM-bandwidth-bound: per-output-channel symmetric int8 halves the
bytes read per step versus bf16, and XLA fuses the dequantize
(``q.astype * scale``) into the matmul operand load — weights stay int8 in
HBM, dequantization happens in VMEM tiles. Opt-in via the tpu-serving
resource's ``quantization: int8`` (no reference counterpart — the
reference's compute is remote APIs).

Quantized weights are ``{"q": int8[..., in, out], "s": f32[..., 1, out]}``;
norms, embeddings, and the tiny MoE router stay in the original dtype.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from langstream_tpu.models.configs import ModelConfig

Params = dict

# stacked-layer matmul weights that dominate HBM traffic
# "wqkv", "wg": a linear-attention layer's q, k, v side by side and its output gate;
# "w_in", "w_out": a conv layer's two projections
_QUANT_LAYER_KEYS = (
    "wq", "wk", "wv", "wqkv", "wg", "wo", "w_gate", "w_up", "w_down", "w_in", "w_out",
    "wq_idx", "wk_idx",
    "ws_gate", "ws_up", "ws_down",
    # latent attention: the two down-projections and the two up-projections
    "wq_a", "wq_b", "wkv_a", "wkv_b",
    # and its heads' gate, a scalar a head
    "w_attn_gate",
)


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and set(leaf) == {"q", "s"}


def quantize_weight(w: jax.Array, axis: int = -2) -> dict[str, jax.Array]:
    """Symmetric int8 with the amax reduced over ``axis`` — the default -2
    gives per-output-channel scales for [in, out] matmul weights."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


def dequantize_weight(qw: dict[str, jax.Array], dtype: Any) -> jax.Array:
    return (qw["q"].astype(jnp.float32) * qw["s"]).astype(dtype)


def quantized_matmul(x: jax.Array, w: Any) -> jax.Array:
    """``x @ w`` where w is a plain array or a quantized dict; dequant in the
    matmul's compute dtype so XLA fuses it into the operand read."""
    if is_quantized(w):
        w = dequantize_weight(w, x.dtype)
    return x @ w


def quantize_row_wise(w: jax.Array) -> dict[str, jax.Array]:
    """Symmetric per-ROW int8 (embedding tables: rows are vocab entries, and
    the tied unembed's output channels are exactly those rows)."""
    return quantize_weight(w, axis=-1)


def quantize_params(params: Params, config: ModelConfig) -> Params:
    """Quantize the serving-dominant weights; everything else passes through."""
    out: Params = dict(params)

    def stack(layers: Params) -> Params:
        layers = dict(layers)
        for key in _QUANT_LAYER_KEYS:
            if key in layers:
                layers[key] = quantize_weight(layers[key])
        return layers

    if config.layer_pattern:  # one stack a kind of layer
        out["layers"] = {kind: stack(s) for kind, s in params["layers"].items()}
    else:
        out["layers"] = stack(params["layers"])
    if "dense_layers" in params and config.layer_pattern:  # a stack a kind
        out["dense_layers"] = {kind: stack(s) for kind, s in params["dense_layers"].items()}
    elif "dense_layers" in params:  # the leading dense layers' stack
        out["dense_layers"] = stack(params["dense_layers"])
    if "lm_head" in params:
        out["lm_head"] = quantize_weight(params["lm_head"])
    if config.tie_embeddings:
        # the tied unembed re-reads the whole [V, D] table every step —
        # for large-vocab models that is ~a fifth of decode's HBM traffic
        out["embed"] = quantize_row_wise(params["embed"])
    return out


def init_random_quantized_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random int8 params built DIRECTLY on device (shape-identical to
    ``quantize_params(init_params(...))``) — benchmarking big models whose
    bf16 tree would not fit HBM, without a slow host-staged init. Scales are
    sized so dequantized weights look ~N(0, 1/in_features), keeping softmax
    finite."""
    import jax.numpy as jnp

    if config.has_latent:
        # no direct form for the latent's leaves and the leading dense stack:
        # the tree's shapes are what this exists for there (serving/memory.py
        # plans under eval_shape; the benchmark makes its own weights)
        from langstream_tpu.models.transformer import init_params

        return quantize_params(init_params(config, key), config)
    d, h, hkv = config.d_model, config.n_heads, config.n_kv_heads
    hd = config.resolved_head_dim
    f, L, v = config.d_ff, config.n_layers, config.vocab_size
    if config.is_moe:  # the no-drop layer's experts have a width of their own
        f = config.expert_d_ff
    dtype = jnp.dtype(config.dtype)
    keys = iter(jax.random.split(key, 16))

    def qw(*shape, scale_of=None):
        import math

        import numpy as np

        fan_in = scale_of if scale_of is not None else shape[-2]
        # int8 values are drawn on the HOST and uploaded: device-side
        # jax.random.randint materializes a uint32 temp of the full shape
        # (4 bytes/elem — 11.3GiB for the stacked mixtral-8x1b w_gate).
        # Only a ≤64MB block is uploaded and the device tiles it along
        # axis 0 (int8 in, int8 out — no wide temps), which also spares
        # the host an 8GB draw. Repeating values along the
        # leading axis is irrelevant to what this exists for: benchmarking
        # (timing is value-independent; scales keep softmax finite).
        k = next(keys)
        if isinstance(k, jax.core.Tracer):
            # abstract evaluation (serving/memory.py plans via eval_shape):
            # only shapes/dtypes matter, so skip the host draw
            q = jnp.zeros(shape, jnp.int8)
        else:
            rng = np.random.default_rng(np.asarray(k))
            row_bytes = math.prod(shape[1:]) if len(shape) > 1 else 1
            block_rows = min(shape[0], max(1, (64 << 20) // max(row_bytes, 1)))
            block = jnp.asarray(
                rng.integers(-127, 128, (block_rows, *shape[1:]), np.int8)
            )
            if block_rows == shape[0]:
                q = block
            else:
                reps = -(-shape[0] // block_rows)  # ceil
                q = jnp.tile(block, (reps,) + (1,) * (len(shape) - 1))[: shape[0]]
        s = jnp.full(shape[:-2] + (1, shape[-1]), fan_in**-0.5 / 127.0, jnp.float32)
        return {"q": q, "s": s}

    layers: Params = {
        "attn_norm": jnp.ones((L, d), dtype),
        "wq": qw(L, d, h * hd),
        "wk": qw(L, d, hkv * hd),
        "wv": qw(L, d, hkv * hd),
        "wo": qw(L, h * hd, d),
        "ffn_norm": jnp.ones((L, d), dtype),
    }
    if config.qk_norm_heads:
        layers["q_norm"] = jnp.ones((L, hd), dtype)
        layers["k_norm"] = jnp.ones((L, hd), dtype)
    if config.has_indexer:
        hi, di = config.index_n_heads, config.index_head_dim
        layers["wq_idx"] = qw(L, d, hi * di)
        layers["wk_idx"] = qw(L, d, di)
        layers["w_idx"] = jax.random.normal(next(keys), (L, d, hi), jnp.float32) * d**-0.5
        layers["idx_norm"] = jnp.ones((L, di), dtype)
        layers["idx_bias"] = jnp.zeros((L, di), dtype)
    if config.is_moe:
        e = config.n_experts
        layers["router"] = (
            jax.random.normal(next(keys), (L, d, e), jnp.float32) * d**-0.5
        ).astype(dtype)
        if config.experts_held:  # the router is whole, the experts a share
            e = config.held_experts[1]
        layers["w_gate"] = qw(L, e, d, f)
        layers["w_up"] = qw(L, e, d, f)
        layers["w_down"] = qw(L, e, f, d)
    else:
        layers["w_gate"] = qw(L, d, f)
        layers["w_up"] = qw(L, d, f)
        layers["w_down"] = qw(L, f, d)

    params: Params = {"layers": layers, "final_norm": jnp.ones((d,), dtype)}
    if config.tie_embeddings:
        # row-quantized table (quantize_row_wise layout: scale per vocab row)
        q = jax.random.randint(next(keys), (v, d), -127, 128, jnp.int8)
        s = jnp.full((v, 1), d**-0.5 / 127.0, jnp.float32)
        params["embed"] = {"q": q, "s": s}
    else:
        params["embed"] = (
            jax.random.normal(next(keys), (v, d), jnp.float32) * d**-0.5
        ).astype(dtype)
        params["lm_head"] = qw(d, v)
    return params


def quantize_specs(specs: Params) -> Params:
    """Mirror quantize_params over a PartitionSpec tree: ``q`` keeps the
    weight's spec; ``s`` drops the contracted (second-to-last) axis."""
    from jax.sharding import PartitionSpec as P

    def scale_spec(spec: P) -> P:
        parts = list(spec)
        if len(parts) >= 2:
            parts[-2] = None
        return P(*parts)

    out = dict(specs)
    layers = dict(specs["layers"])
    for key in _QUANT_LAYER_KEYS:
        if key in layers:
            layers[key] = {"q": layers[key], "s": scale_spec(layers[key])}
    out["layers"] = layers
    if "lm_head" in specs:
        out["lm_head"] = {"q": specs["lm_head"], "s": scale_spec(specs["lm_head"])}
    return out


def quantize_specs_for_params(specs: Params, params: Params) -> Params:
    """quantize_specs plus the row-quantized embedding when present (its
    per-row scales shard like the table's vocab axis)."""
    from jax.sharding import PartitionSpec as P

    out = quantize_specs(specs)
    if is_quantized(params.get("embed")):
        embed_spec = specs["embed"]
        out["embed"] = {"q": embed_spec, "s": P(embed_spec[0], None)}
    else:
        out["embed"] = specs["embed"]
    return out

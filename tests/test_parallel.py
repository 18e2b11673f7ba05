"""Parallelism tests on the 8-device virtual CPU mesh (conftest sets
xla_force_host_platform_device_count=8): ring attention vs dense reference,
TP-sharded engine vs single-device, MoE expert parallelism."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS
from langstream_tpu.models.transformer import forward, init_params
from langstream_tpu.parallel.mesh import build_mesh
from langstream_tpu.parallel.sharding import shard_params
from langstream_tpu.parallel.sp import sequence_parallel_forward

FP32 = {"dtype": "float32"}


def fp32_config(name):
    return dataclasses.replace(MODEL_PRESETS[name], **FP32)


def test_ring_attention_matches_dense_forward():
    config = fp32_config("tiny-test")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, config.vocab_size)

    reference = forward(params, tokens, config)
    mesh = build_mesh({"seq": 8})
    ringed = sequence_parallel_forward(params, tokens, config, mesh)
    np.testing.assert_allclose(
        np.asarray(reference), np.asarray(ringed), rtol=2e-4, atol=2e-4
    )


def test_ring_attention_rejects_indivisible_length():
    config = fp32_config("tiny-test")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 30), jnp.int32)
    mesh = build_mesh({"seq": 8})
    with pytest.raises(ValueError, match="divisible"):
        sequence_parallel_forward(params, tokens, config, mesh)


def test_tp_sharded_forward_matches_single_device():
    config = fp32_config("tiny-test")
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, config.vocab_size)
    reference = forward(params, tokens, config)

    mesh = build_mesh({"model": 8})
    sharded = shard_params(params, mesh, config)
    out = forward(sharded, tokens, config)
    np.testing.assert_allclose(
        np.asarray(reference), np.asarray(out), rtol=2e-4, atol=2e-4
    )


def test_moe_expert_parallel_forward_matches():
    config = dataclasses.replace(fp32_config("tiny-moe-test"), moe_capacity_factor=0.0)
    params = init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, config.vocab_size)
    reference = forward(params, tokens, config)

    mesh = build_mesh({"expert": 4, "model": 2})
    sharded = shard_params(params, mesh, config)
    out = forward(sharded, tokens, config)
    np.testing.assert_allclose(
        np.asarray(reference), np.asarray(out), rtol=2e-4, atol=2e-4
    )


def test_tp_engine_greedy_decode_matches_single_device():
    """The full serving path (prefill + continuous decode) must produce the
    same greedy tokens sharded and unsharded."""
    from langstream_tpu.models.configs import GenerationOptions
    from langstream_tpu.serving.engine import ServingEngine

    config = fp32_config("tiny-test")
    params = init_params(config, jax.random.PRNGKey(0))
    prompt = list(range(7, 27))
    options = GenerationOptions(max_new_tokens=12, temperature=0.0)

    single = ServingEngine(config, params, max_batch=2, max_seq_len=128)
    single.start()
    try:
        ref = single.generate(prompt, options, timeout=120)
    finally:
        single.stop()

    mesh = build_mesh({"model": 8})
    sharded_params = shard_params(params, mesh, config)
    tp = ServingEngine(config, sharded_params, max_batch=2, max_seq_len=128, mesh=mesh)
    tp.start()
    try:
        out = tp.generate(prompt, options, timeout=120)
    finally:
        tp.stop()

    assert ref.tokens == out.tokens
    assert out.finish_reason == ref.finish_reason


def test_ring_prefill_matches_dense_prefill():
    """parallel.sp.ring_prefill (sequence-sharded single-dispatch long
    prefill) returns the same last-token logits and K/V the dense prefill
    writes into a cache."""
    from langstream_tpu.models.transformer import make_kv_cache, prefill
    from langstream_tpu.parallel.sp import ring_prefill

    config = fp32_config("tiny-test")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompt_len, s_pad = 100, 128
    tokens = np.zeros((1, s_pad), np.int32)
    tokens[0, :prompt_len] = rng.integers(1, config.vocab_size, size=prompt_len)
    lengths = jnp.asarray([prompt_len], jnp.int32)

    cache = make_kv_cache(config, 1, s_pad)
    dense_logits, cache = prefill(params, jnp.asarray(tokens), lengths, cache, config)

    mesh = build_mesh({"seq": 4})
    ring_logits, kv = ring_prefill(params, jnp.asarray(tokens), lengths, config, mesh)
    np.testing.assert_allclose(
        np.asarray(ring_logits), np.asarray(dense_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(kv["k"][:, :, :, :prompt_len]),
        np.asarray(cache["k"][:, :, :, :prompt_len]),
        rtol=2e-4,
        atol=2e-4,
    )

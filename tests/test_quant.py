"""int8 weight-only quantization: error bounds, forward agreement, TP
sharding of quantized trees, engine integration."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from langstream_tpu.models.configs import MODEL_PRESETS
from langstream_tpu.models.quant import (
    dequantize_weight,
    quantize_params,
    quantize_weight,
)
from langstream_tpu.models.transformer import forward, init_params

DENSE = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
MOE = dataclasses.replace(MODEL_PRESETS["tiny-moe-test"], dtype="float32")


def test_quantize_roundtrip_error_bound():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    qw = quantize_weight(w)
    assert qw["q"].dtype == jnp.int8
    deq = dequantize_weight(qw, jnp.float32)
    # symmetric int8: |err| <= scale/2 per output channel
    err = np.abs(np.asarray(w) - np.asarray(deq))
    bound = np.asarray(qw["s"])[0] / 2 + 1e-7
    assert (err <= bound[None, :]).all()


def test_forward_top1_agreement():
    for config in (DENSE, MOE):
        params = init_params(config, jax.random.PRNGKey(0))
        qparams = quantize_params(params, config)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, config.vocab_size)
        ref = np.asarray(forward(params, tokens, config))
        out = np.asarray(forward(qparams, tokens, config))
        top_ref = ref.argmax(-1)
        top_q = out.argmax(-1)
        agreement = (top_ref == top_q).mean()
        assert agreement >= 0.9, f"{config.name}: top-1 agreement {agreement}"


def test_quantized_tp_sharding_matches():
    from langstream_tpu.parallel.mesh import build_mesh
    from langstream_tpu.parallel.sharding import shard_params

    params = quantize_params(init_params(DENSE, jax.random.PRNGKey(0)), DENSE)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, DENSE.vocab_size)
    ref = np.asarray(forward(params, tokens, DENSE))
    mesh = build_mesh({"model": 8})
    sharded = shard_params(params, mesh, DENSE)
    out = np.asarray(forward(sharded, tokens, DENSE))
    np.testing.assert_allclose(ref, out, rtol=2e-4, atol=2e-4)


def test_engine_with_quantized_weights():
    from langstream_tpu.models.configs import GenerationOptions
    from langstream_tpu.serving.engine import ServingEngine

    params = quantize_params(init_params(DENSE, jax.random.PRNGKey(0)), DENSE)
    engine = ServingEngine(DENSE, params, max_batch=2, max_seq_len=128)
    engine.start()
    try:
        result = engine.generate(
            list(range(5, 25)), GenerationOptions(max_new_tokens=8, temperature=0.0),
            timeout=120,
        )
        assert len(result.tokens) == 8
    finally:
        engine.stop()


def test_tpu_serving_quantization_config(run):
    async def scenario():
        from langstream_tpu.ai.tpu_serving import TpuServingProvider

        provider = TpuServingProvider(
            {"model": "tiny-test", "tokenizer": "byte", "max-seq-len": 64,
             "quantization": "int8"}
        )
        service = provider.get_completions_service({})
        from langstream_tpu.ai.provider import ChatMessage

        result = await service.get_chat_completions(
            [ChatMessage(role="user", content="hi")], {"max-new-tokens": 4}
        )
        assert isinstance(result.content, str)
        await provider.close()

    run(scenario())


def test_tpu_serving_refuses_the_dense_layout_by_name():
    """A configuration that still asks for the deleted KV layout fails at
    build with a sentence that says so; naming the one layout is accepted."""
    import pytest

    from langstream_tpu.ai.tpu_serving import TpuServingProvider

    base = {"model": "tiny-test", "tokenizer": "byte", "max-seq-len": 64}
    provider = TpuServingProvider({**base, "kv-layout": "dense"})
    with pytest.raises(ValueError, match="kv-layout: dense is gone"):
        provider.engine()
    kept = TpuServingProvider({**base, "kv-layout": "paged"})
    try:
        assert kept.engine().stats()["kv-pages-total"] > 0
        assert "kv-layout" not in kept.engine().stats()
    finally:
        import asyncio

        asyncio.run(kept.close())


def test_tpu_serving_refuses_the_scheduler_that_is_gone_by_name():
    """`overlap: false` asked for the scheduler from before the fused
    iteration: refused at build by that name, not served by another
    scheduler in silence (`overlap: true` asked for nothing and is read past)."""
    import pytest

    from langstream_tpu.ai.tpu_serving import TpuServingProvider

    base = {"model": "tiny-test", "tokenizer": "byte", "max-seq-len": 64}
    for off in (False, "false", "off"):
        with pytest.raises(ValueError, match="overlap: false is gone"):
            TpuServingProvider({**base, "overlap": off}).engine()


def test_int8_kv_cache_matches_bf16_cache():
    """Prefill + decode with the int8 KV cache tracks the fp32 cache closely
    (per-token per-head symmetric quant; rtol bounded by 1/127)."""
    from langstream_tpu.models.transformer import decode_step, make_kv_cache, prefill

    base = DENSE
    quant = dataclasses.replace(base, kv_cache_dtype="int8")
    params = init_params(base, jax.random.PRNGKey(0))
    b, s, t = 2, 16, 64
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 1, base.vocab_size)
    lengths = jnp.asarray([s, s - 5], jnp.int32)

    logits_ref, cache_ref = prefill(params, tokens, lengths, make_kv_cache(base, b, t), base)
    cache_q = make_kv_cache(quant, b, t)
    assert cache_q["k"]["q"].dtype == jnp.int8
    logits_out, cache_q = prefill(params, tokens, lengths, cache_q, quant)
    # same top-1 and close logits despite 8-bit cache values
    np.testing.assert_array_equal(
        np.asarray(logits_ref).argmax(-1), np.asarray(logits_out).argmax(-1)
    )
    np.testing.assert_allclose(
        np.asarray(logits_ref), np.asarray(logits_out), rtol=0.1, atol=0.15
    )

    nxt = jnp.argmax(logits_ref, axis=-1).astype(jnp.int32)
    d_ref, _ = decode_step(params, nxt, lengths, cache_ref, base)
    d_out, _ = decode_step(params, nxt, lengths, cache_q, quant)
    np.testing.assert_array_equal(
        np.asarray(d_ref).argmax(-1), np.asarray(d_out).argmax(-1)
    )


def test_engine_with_int8_kv_cache():
    from langstream_tpu.models.configs import GenerationOptions
    from langstream_tpu.serving.engine import ServingEngine

    config = dataclasses.replace(DENSE, kv_cache_dtype="int8")
    params = init_params(config, jax.random.PRNGKey(0))
    engine = ServingEngine(config, params, max_batch=2, max_seq_len=128)
    engine.start()
    try:
        result = engine.generate(
            list(range(5, 25)), GenerationOptions(max_new_tokens=8, temperature=0.0),
            timeout=120,
        )
        assert len(result.tokens) == 8
    finally:
        engine.stop()


def test_int8_kv_cache_tp_sharding():
    """int8 cache shards over the 8-device mesh: q on (data, model), scales
    mirror minus the head-dim axis."""
    from langstream_tpu.models.transformer import make_kv_cache
    from langstream_tpu.parallel.mesh import build_mesh
    from langstream_tpu.parallel.sharding import shard_kv_cache

    from jax.sharding import PartitionSpec as P

    config = dataclasses.replace(DENSE, kv_cache_dtype="int8")
    mesh = build_mesh({"data": 2, "model": 4})
    cache = shard_kv_cache(make_kv_cache(config, 4, 32), mesh)
    assert cache["k"]["q"].sharding.spec == P(None, "data", "model", None, None)
    assert cache["k"]["s"].sharding.spec == P(None, "data", "model", None)
    assert len(cache["k"]["s"].shape) == 4


def test_init_random_quantized_params_matches_quantize_shapes():
    """init_random_quantized_params (device-side big-model bench init) must
    stay shape/dtype-identical to quantize_params(init_params(...)) — it is
    the contract that makes its benches representative."""
    from langstream_tpu.models.quant import init_random_quantized_params

    for config in (DENSE, MOE):
        ref = quantize_params(init_params(config, jax.random.PRNGKey(0)), config)
        fast = init_random_quantized_params(config, jax.random.PRNGKey(0))
        ref_shapes = jax.tree.map(lambda x: (x.shape, x.dtype.name), ref)
        fast_shapes = jax.tree.map(lambda x: (x.shape, x.dtype.name), fast)
        assert ref_shapes == fast_shapes, f"{config.name} trees diverge"

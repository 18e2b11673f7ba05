"""A model whose attention reads a learned selection, through the engine
(`tiny-sparse-moe-test`, float32 on the CPU):

(i)   the engine's tokens (a prompt inside a bucket, a prompt chunked into
      segments, decode chunks past the top-k) are `forward`'s greedy tokens,
      so the reference's (`tests/test_sparse_attention.py` holds `forward`);
(ii)  a prefix hit: the warm suffix ranks the aliased pages' indexer keys,
      and a copied page is whole;
(iii) `_on_pages` copies and zeroes the indexer's leaf;
(iv)  spans and counters: what the dispatches scored and read;
(v)   what the engine refuses, by name; the memory plan's page term;
(vi)  the `tpu-serving` resource serves the preset by its name.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.serving import engine as E
from langstream_tpu.serving.memory import plan_serving_memory

CONFIG = dataclasses.replace(MODEL_PRESETS["tiny-sparse-moe-test"], dtype="float32")
ENGINE = dict(
    max_batch=4, max_seq_len=128, prefill_buckets=(16,), page_size=8, prefill_batch=1,
    kv_pages=64, decode_chunk=4,
)
TOPK = CONFIG.index_topk


@pytest.fixture(scope="module")
def params():
    return T.init_params(CONFIG, jax.random.PRNGKey(0))


def make_engine(config, params, **over):
    engine = E.ServingEngine(config, params, **{**ENGINE, **over})
    engine.start()
    engine.wait_ready()
    return engine


@pytest.fixture(scope="module")
def engine(params):
    engine = make_engine(CONFIG, params)
    yield engine
    engine.stop()


def prompt_of(n: int, seed: int = 0) -> list[int]:
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


def greedy(params, prompt, new_tokens: int) -> list[int]:
    """`forward`'s greedy continuation, a whole forward a token."""
    tokens = list(prompt)
    for _ in range(new_tokens):
        logits = T.forward(params, jnp.asarray([tokens], jnp.int32), CONFIG)[0, -1]
        tokens.append(int(jnp.argmax(logits)))
    return tokens[len(prompt):]


# -- (i) the engine's tokens -------------------------------------------------------


@pytest.mark.parametrize("n", [5, 16, 40, 61], ids=lambda n: f"prompt{n}")
def test_the_engines_tokens_are_forwards(params, engine, n):
    """5 and 16: the admit group (inside the bucket; 5 is under the top-k for
    its first decode steps); 40 and 61: three and four segments, the last of
    8 and of 13 real tokens; 8 decode steps each, past the top-k."""
    prompt = prompt_of(n, seed=n)
    result = engine.generate(prompt, GenerationOptions(max_new_tokens=8), timeout=120)
    assert result.tokens == greedy(params, prompt, 8)


# -- (ii) prefix reuse ----------------------------------------------------------------


def test_a_prefix_hit_ranks_the_aliased_pages_indexer_keys(params):
    """Two prompts that share 36 tokens (four whole pages and half a page: a
    copy-on-write page): the second's answer is the one a cold engine gives."""
    shared = prompt_of(36, seed=1)
    first, second = shared + prompt_of(9, seed=2), shared + prompt_of(11, seed=3)
    engine = make_engine(
        dataclasses.replace(CONFIG, name="tiny-sparse-prefix"), params, prefix_cache=True
    )
    try:
        engine.generate(first, GenerationOptions(max_new_tokens=4), timeout=120)
        warm = engine.generate(second, GenerationOptions(max_new_tokens=6), timeout=120)
        stats = engine.stats()
    finally:
        engine.stop()
    assert warm.tokens == greedy(params, second, 6)
    assert stats["prefix-cache"] and stats["prefix-cache-hit-rate"] > 0


# -- (iii) a page is whole ------------------------------------------------------------


def test_a_copied_and_a_zeroed_page_carry_the_indexer_keys():
    pool = T.make_page_pool(CONFIG, 6, 8)
    pool = jax.tree.map(
        lambda a: jnp.arange(a.size, dtype=jnp.float32).reshape(a.shape) + 1.0, pool
    )
    copied = E._page_copy(jax.tree.map(jnp.copy, pool), 2, 5)
    for leaf in ("k", "v", "ik"):
        np.testing.assert_array_equal(np.asarray(copied[leaf][:, 5]), np.asarray(pool[leaf][:, 2]))
        np.testing.assert_array_equal(np.asarray(copied[leaf][:, :5]), np.asarray(pool[leaf][:, :5]))
    zeroed = E._page_zero(jax.tree.map(jnp.copy, pool), jnp.asarray([1, 4, 99], jnp.int32))
    for leaf in ("k", "v", "ik"):
        assert float(jnp.abs(zeroed[leaf][:, jnp.asarray([1, 4])]).max()) == 0.0
        assert float(jnp.abs(zeroed[leaf][:, jnp.asarray([0, 2, 3, 5])]).min()) > 0.0
    # a model without an indexer: the leaves they were
    plain = T.make_page_pool(MODEL_PRESETS["tiny-moe-test"], 6, 8)
    assert set(E._page_copy(plain, 0, 1)) == {"k", "v"}


# -- (iv) spans and counters -------------------------------------------------------------


def test_spans_and_counters_say_what_was_scored_and_read(params):
    from langstream_tpu.serving import observability

    spans = []
    engine = make_engine(dataclasses.replace(CONFIG, name="tiny-sparse-spans"), params)
    emit = observability.emit_dispatch_span
    record = lambda name, start, end, attrs: spans.append((name, dict(attrs)))  # noqa: E731
    try:
        E.emit_dispatch_span = record
        engine.generate(prompt_of(40, 9), GenerationOptions(max_new_tokens=8), timeout=120)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not any(n == "engine.decode_chunk" for n, _ in spans):
            time.sleep(0.01)
        stats = engine.stats()
    finally:
        E.emit_dispatch_span = emit
        engine.stop()
    segments = [a for n, a in spans if n == "engine.prefill_segment"]
    chunks = [a for n, a in spans if n == "engine.decode_chunk"]
    # a model that holds its experts: a span a segment, each with its counts
    assert [a["offset"] for a in segments] == [0, 16, 32]
    assert [a["real_tokens"] for a in segments] == [16, 16, 8]
    for attrs in segments:
        lengths = attrs["offset"] + 1 + np.arange(attrs["real_tokens"])
        assert attrs["index_tokens_scored"] == lengths.sum()
        assert attrs["kv_tokens_selected"] == np.minimum(lengths, TOPK).sum() == attrs["kv_tokens_read"]
        assert {"moe_routed", "moe_local", "moe_touched", "device_ms"} <= set(attrs)
        assert attrs["moe_routed_real"] == attrs["real_tokens"] * 4 * CONFIG.n_layers
    assert chunks
    for attrs in chunks:
        # every live row is past the top-k: a step reads the top-k, scores the row
        assert attrs["kv_tokens_selected"] == attrs["kv_tokens_read"] == TOPK * attrs["row_steps"]
        assert attrs["index_tokens_scored"] > 40 * attrs["row_steps"]
    assert stats["index-tokens-scored-total"] >= sum(a["index_tokens_scored"] for a in segments + chunks)
    assert stats["kv-tokens-selected-total"] >= sum(a["kv_tokens_selected"] for a in segments + chunks)


def test_a_model_without_an_indexer_counts_neither(params):
    moe = MODEL_PRESETS["tiny-moe-test"]
    engine = make_engine(moe, T.init_params(moe, jax.random.PRNGKey(0)), prefill_buckets=(16, 32))
    try:
        engine.generate(prompt_of(12), GenerationOptions(max_new_tokens=2), timeout=120)
        assert "index-tokens-scored-total" not in engine.stats()
    finally:
        engine.stop()


# -- (v) refusals and the memory plan ------------------------------------------------------


@pytest.mark.parametrize(
    "option",
    [
        {"host_kv_fraction": 1.0}, {"migrate_staging": True}, {"durable_dir": "under-tmp-path"},
        {"speculation": "auto"}, {"speculation": True},
        {"adapters": [{"name": "a", "rank": 4}]}, {"mesh": object()}, {"spmd": object()},
    ],
    ids=lambda o: f"{next(iter(o))}-{next(iter(o.values()))!s:.8}",
)
def test_the_engine_refuses_by_name(params, option, tmp_path):
    name = next(iter(option))
    if name == "durable_dir":  # refused before anything is made there
        option = {name: str(tmp_path / "never-made")}
    with pytest.raises(ValueError, match=f"reads a learned selection.*{name}.*indexer's keys"):
        E.ServingEngine(CONFIG, params, **{**ENGINE, **option})


def test_an_int8_pool_is_refused_by_the_config():
    with pytest.raises(ValueError, match="an indexer.*an int8 KV cache"):
        dataclasses.replace(CONFIG, kv_cache_dtype="int8")


def test_the_memory_plans_page_term_counts_the_third_leaf():
    """A token of the page pool: K and V of every layer and KV head, and the
    indexer's one key of every layer."""
    config = MODEL_PRESETS["tiny-sparse-moe-test"]  # bf16
    plan = plan_serving_memory(config, 4, 128, page_size=8, kv_pages=64)
    kv = config.n_layers * 2 * config.n_kv_heads * config.resolved_head_dim * 2
    ik = config.n_layers * config.index_key_width * 2  # whole 128-lane rows
    assert plan.page_pool_bytes == 64 * 8 * (kv + ik)
    without = dataclasses.replace(config, index_topk=0, index_n_heads=0, index_head_dim=0)
    assert plan_serving_memory(without, 4, 128, page_size=8, kv_pages=64).page_pool_bytes == 64 * 8 * kv


# -- (vi) the normal path -----------------------------------------------------------------


def test_the_tpu_serving_resource_serves_the_preset():
    """`tpu-serving` with `model: tiny-sparse-moe-test`: a prompt past the
    bucket is chunked, the stream's chunks follow, the answer is `max-tokens`
    long and the engine counted what it scored and read."""
    import asyncio

    from langstream_tpu.ai.tpu_serving import TpuServingProvider

    async def scenario():
        provider = TpuServingProvider({
            "model": "tiny-sparse-moe-test", "tokenizer": "byte", "max-seq-len": 128,
            "max-batch": 2, "prefill-buckets": [16], "page-size": 8, "decode-chunk": 4,
        })
        chunks = []
        service = provider.get_completions_service({})
        result = await service.get_text_completions(
            ["a learned selection reads some of the tokens behind a query"],
            {"max-tokens": 9, "min-chunks-per-message": 1},
            lambda chunk: chunks.append((chunk.index, chunk.content, chunk.last)),
        )
        engine = provider.engine()
        stats = engine.stats()
        await provider.close()
        return result, chunks, engine.config, stats

    result, chunks, config, stats = asyncio.run(scenario())
    assert config.has_indexer and config.index_topk == 8
    assert chunks and chunks[-1][2] is True
    assert result.finish_reason == "length"
    assert stats["index-tokens-scored-total"] > stats["kv-tokens-selected-total"] > 0

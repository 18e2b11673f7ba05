"""A model that keeps a latent in place of K and V, through the engine
(`tiny-latent-moe-test`, float32 on the CPU):

(i)   the engine's tokens (a prompt inside a bucket, a prompt chunked into
      segments, decode chunks past the top-k) are `forward`'s greedy tokens,
      so the reference's (`tests/test_latent_attention.py` holds `forward`);
(ii)  a prefix hit: the warm suffix re-expands and ranks the aliased pages;
(iii) `_on_pages` copies and zeroes both leaves;
(iv)  spans and counters: what the dispatches scored, read and expanded;
      `stats()` `kv-bytes-per-token`;
(v)   what the engine refuses, by name;
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

CONFIG = dataclasses.replace(MODEL_PRESETS["tiny-latent-moe-test"], dtype="float32")
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


@pytest.mark.parametrize("n", [5, 16, 40, 61], ids=lambda n: f"prompt{n}")
def test_the_engines_tokens_are_forwards(params, engine, n):
    """5 and 16: the admit group (`prefill` into a local cache of latents,
    `paged_insert_cache`'s scatter); 40 and 61: three and four segments; 8
    decode steps each in the latent space, past the top-k."""
    prompt = prompt_of(n, seed=n)
    result = engine.generate(prompt, GenerationOptions(max_new_tokens=8), timeout=120)
    assert result.tokens == greedy(params, prompt, 8)


def test_a_prefix_hit_reads_the_aliased_pages_latents(params):
    """Two prompts that share 36 tokens (four whole pages and half a page: a
    copy-on-write page): the second's answer is the one a cold engine gives."""
    shared = prompt_of(36, seed=1)
    first, second = shared + prompt_of(9, seed=2), shared + prompt_of(11, seed=3)
    engine = make_engine(
        dataclasses.replace(CONFIG, name="tiny-latent-prefix"), params, prefix_cache=True
    )
    try:
        engine.generate(first, GenerationOptions(max_new_tokens=4), timeout=120)
        warm = engine.generate(second, GenerationOptions(max_new_tokens=6), timeout=120)
        stats = engine.stats()
    finally:
        engine.stop()
    assert warm.tokens == greedy(params, second, 6)
    assert stats["prefix-cache"] and stats["prefix-cache-hit-rate"] > 0


def test_a_copied_and_a_zeroed_page_carry_both_leaves():
    pool = T.make_page_pool(CONFIG, 6, 8)
    assert set(pool) == {"lat", "ik"}
    pool = jax.tree.map(
        lambda a: jnp.arange(a.size, dtype=jnp.float32).reshape(a.shape) + 1.0, pool
    )
    copied = E._page_copy(jax.tree.map(jnp.copy, pool), 2, 5)
    for leaf in ("lat", "ik"):
        np.testing.assert_array_equal(np.asarray(copied[leaf][:, 5]), np.asarray(pool[leaf][:, 2]))
        np.testing.assert_array_equal(np.asarray(copied[leaf][:, :5]), np.asarray(pool[leaf][:, :5]))
    zeroed = E._page_zero(jax.tree.map(jnp.copy, pool), jnp.asarray([1, 4, 99], jnp.int32))
    for leaf in ("lat", "ik"):
        assert float(jnp.abs(zeroed[leaf][:, jnp.asarray([1, 4])]).max()) == 0.0
        assert float(jnp.abs(zeroed[leaf][:, jnp.asarray([0, 2, 3, 5])]).min()) > 0.0
    assert set(E._page_snapshot(pool, 3)) == {"lat", "ik"}


@pytest.mark.parametrize(
    "impl, table, columns",
    [
        # masked jnp multiplies every column of the table: all of it, a segment
        ("auto", 128, [128, 128, 128]),
        # the walk over key blocks (of 128 in a table of 640) stops at its
        # diagonal: a segment expands up to its last query's block
        ("pallas", 640, [128, 128, 128]),
    ],
    ids=["jnp", "kernels"],
)
def test_spans_and_counters_say_what_was_scored_read_and_expanded(params, impl, table, columns):
    from langstream_tpu.serving import observability

    spans = []
    engine = make_engine(
        dataclasses.replace(CONFIG, name=f"tiny-latent-spans-{impl}", attention_impl=impl),
        params, max_seq_len=table, kv_pages=4 * table // 8,
    )
    emit = observability.emit_dispatch_span
    record = lambda name, start, end, attrs: spans.append((name, dict(attrs)))  # noqa: E731
    engine.reset_histograms()  # the warm-up's segment is not the prompt's
    assert engine.stats()["segment-key-blocks"] == {}
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
    assert [a["offset"] for a in segments] == [0, 16, 32]
    # a segment re-expands every cached column behind it, a layer
    assert [a["latent_tokens_expanded"] for a in segments] == [0, 16, 32]
    # and the columns of its table the program expanded in all, by its own rule
    assert [a["latent_columns_expanded"] for a in segments] == columns
    assert columns == [T.latent_columns_expanded(o, 16, table, engine.config) for o in (0, 16, 32)]
    # and the key blocks its attention walked, a head and a layer (PR 56): a
    # tile of 16 queries lies inside the first block of 128 keys; masked jnp
    # walks none
    walked = [1, 1, 1] if impl == "pallas" else [None] * 3
    assert [a.get("key_blocks") for a in segments] == walked
    assert stats["segment-key-blocks"] == ({"key-blocks": 3} if impl == "pallas" else {})
    for attrs in segments:
        lengths = attrs["offset"] + 1 + np.arange(attrs["real_tokens"])
        assert attrs["index_tokens_scored"] == lengths.sum()
        assert attrs["kv_tokens_selected"] == np.minimum(lengths, TOPK).sum() == attrs["kv_tokens_read"]
        # the three expert layers route; the leading dense layer routes nothing
        assert attrs["moe_routed_real"] == attrs["real_tokens"] * 2 * 3
    assert chunks
    for attrs in chunks:
        assert attrs["latent_tokens_expanded"] == 0  # a decode step attends in the latent space
        assert attrs["kv_tokens_selected"] == attrs["kv_tokens_read"] == TOPK * attrs["row_steps"]
        assert "latent_columns_expanded" not in attrs
    assert stats["latent-tokens-expanded-total"] == 48
    assert stats["latent-columns-expanded-total"] == sum(columns)
    assert stats["kv-bytes-per-token"] == CONFIG.kv_bytes_per_token(itemsize=4)
    assert stats["kv-bytes-per-token"] == 4 * (128 + 128) * 4
    assert stats["index-tokens-scored-total"] >= sum(a["index_tokens_scored"] for a in segments + chunks)


def test_a_model_with_k_and_v_says_its_bytes_a_token_and_expands_nothing(params):
    moe = MODEL_PRESETS["tiny-moe-test"]
    engine = make_engine(moe, T.init_params(moe, jax.random.PRNGKey(0)), prefill_buckets=(16, 32))
    try:
        engine.generate(prompt_of(12), GenerationOptions(max_new_tokens=2), timeout=120)
        stats = engine.stats()
    finally:
        engine.stop()
    assert "latent-tokens-expanded-total" not in stats
    assert "latent-columns-expanded-total" not in stats
    assert stats["kv-bytes-per-token"] == moe.kv_bytes_per_token() == 2 * 2 * 4 * 8 * 2


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
    with pytest.raises(ValueError, match=f"keeps a latent.*{name}.*a latent and an indexer's keys"):
        E.ServingEngine(CONFIG, params, **{**ENGINE, **option})


def test_migration_is_refused_by_name(engine):
    from langstream_tpu.serving.migrate import MigrationError

    with pytest.raises(MigrationError, match="indexer keys and its latent have no wire format"):
        engine._migrate_rpc("snapshot", {}, 1.0)


def test_the_tpu_serving_resource_serves_the_preset():
    """`tpu-serving` with `model: tiny-latent-moe-test`: a prompt past the
    bucket is chunked, the stream's chunks follow, the answer is `max-tokens`
    long and the engine counted what it scored, read and expanded."""
    import asyncio

    from langstream_tpu.ai.tpu_serving import TpuServingProvider

    async def scenario():
        provider = TpuServingProvider({
            "model": "tiny-latent-moe-test", "tokenizer": "byte", "max-seq-len": 128,
            "max-batch": 2, "prefill-buckets": [16], "page-size": 8, "decode-chunk": 4,
        })
        chunks = []
        service = provider.get_completions_service({})
        result = await service.get_text_completions(
            ["a token keeps one latent for every head and a decode step reads it once"],
            {"max-tokens": 9, "min-chunks-per-message": 1},
            lambda chunk: chunks.append((chunk.index, chunk.content, chunk.last)),
        )
        engine = provider.engine()
        stats = engine.stats()
        pool = set(engine._pagepool.dev)
        await provider.close()
        return result, chunks, engine.config, stats, pool

    result, chunks, config, stats, pool = asyncio.run(scenario())
    assert config.has_latent and config.n_leading_dense == 1
    assert pool == {"lat", "ik"}
    assert chunks and chunks[-1][2] is True
    assert result.finish_reason == "length"
    assert stats["index-tokens-scored-total"] > stats["kv-tokens-selected-total"] > 0
    assert stats["latent-tokens-expanded-total"] > 0

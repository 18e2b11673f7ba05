"""Prefix KV-cache reuse tests: warm-prefix generations must be
token-for-token identical to cold runs (the cache is a scheduling/bandwidth
optimization, never a math change) for both the short admit-group path and
the chunked-prefill long-prompt path, on float (bf16-on-TPU) and int8
pools; plus the page index's radix semantics, its refcounted LRU eviction
and publish dedupe. The prefix cache is ``pagepool.PrefixPageIndex``: entries
pin pages of the engine's one pool (tests/test_pagepool.py has the
allocator's and the aliasing contracts)."""

import dataclasses

import jax
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
from langstream_tpu.serving.pagepool import PagePool, PrefixPageIndex

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
CFG_INT8 = dataclasses.replace(CFG, kv_cache_dtype="int8")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))


def make_engine(config=CFG, prefix=False, **kw):
    engine = ServingEngine(
        config,
        PARAMS,
        prefix_cache="auto" if prefix else "off",
        prefix_cache_entries=4 if prefix else None,
        **kw,
    )
    engine.start()
    return engine


GREEDY = GenerationOptions(max_new_tokens=10, temperature=0.0)


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["float", "int8kv"])
def test_warm_prefix_exact_short_path(config):
    """Admit-group path: a generation admitted against a warm prefix is
    bit-identical to a cold run (greedy, fixed seed). The second request
    reuses the 32-token bucket-aligned prefix the first one published."""
    prompt = [(7 + 3 * i) % CFG.vocab_size for i in range(45)]
    # a shared preamble with a DIFFERENT tail must also reuse the prefix
    other = prompt[:40] + [(3 * i + 1) % CFG.vocab_size for i in range(5)]
    cold_engine = make_engine(
        config, max_batch=2, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16, 32, 64),
    )
    try:
        cold = cold_engine.generate(prompt, GREEDY, timeout=120).tokens
        cold2 = cold_engine.generate(other, GREEDY, timeout=120).tokens
    finally:
        cold_engine.stop()

    engine = make_engine(
        config, prefix=True, max_batch=2, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16, 32, 64),
    )
    try:
        first = engine.generate(prompt, GREEDY, timeout=120).tokens
        warm = engine.generate(prompt, GREEDY, timeout=120).tokens
        stats = engine.stats()
        assert first == cold, "publishing run diverged from a cold engine"
        assert warm == cold, "warm-prefix run diverged from the cold run"
        assert stats["prefill-tokens-saved-total"] == 32  # bucket-aligned
        assert stats["prefix-cache-hit-rate"] == 0.5  # miss then hit
        warm2 = engine.generate(other, GREEDY, timeout=120).tokens
        assert warm2 == cold2
        assert engine.stats()["prefill-tokens-saved-total"] == 64
    finally:
        engine.stop()


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["float", "int8kv"])
def test_warm_prefix_exact_long_path(config):
    """Chunked-prefill path: a long prompt (wider than the largest bucket)
    admitted against a warm full-segment-width prefix — chunked prefill
    starts at the reuse point — matches the cold run token for token."""
    prompt = [(3 + 5 * i) % CFG.vocab_size for i in range(70)]  # 3 segments @32
    cold_engine = make_engine(
        config, max_batch=2, max_seq_len=256, decode_chunk=4,
        prefill_buckets=(16, 32),
    )
    try:
        cold = cold_engine.generate(prompt, GREEDY, timeout=120).tokens
    finally:
        cold_engine.stop()

    engine = make_engine(
        config, prefix=True, max_batch=2, max_seq_len=256, decode_chunk=4,
        prefill_buckets=(16, 32),
    )
    try:
        first = engine.generate(prompt, GREEDY, timeout=120).tokens
        warm = engine.generate(prompt, GREEDY, timeout=120).tokens
        assert first == cold
        assert warm == cold
        stats = engine.stats()
        # the long path aliases at the deepest published boundary
        assert stats["prefill-tokens-saved-total"] == 32
        assert stats["prefix-cache-entries"] >= 1
    finally:
        engine.stop()


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["float", "int8kv"])
def test_deeper_entry_serves_shorter_prompt(config):
    """A preamble published as part of a LONGER prompt serves shorter
    prompts sharing it: the entry's leading pages ARE that prefix's KV,
    and the radix walk reuses them at the matched depth."""
    preamble = [(9 + i) % CFG.vocab_size for i in range(32)]
    long_prompt = preamble + [(5 * i) % CFG.vocab_size for i in range(20)]
    short_prompt = preamble + [7, 8, 9]
    cold_engine = make_engine(
        config, max_batch=2, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16, 32, 64),
    )
    try:
        cold = cold_engine.generate(short_prompt, GREEDY, timeout=120).tokens
    finally:
        cold_engine.stop()
    engine = make_engine(
        config, prefix=True, max_batch=2, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16, 32, 64),
    )
    try:
        engine.generate(long_prompt, GREEDY, timeout=120)  # publishes at 32
        warm = engine.generate(short_prompt, GREEDY, timeout=120).tokens
        assert warm == cold
        assert engine.stats()["prefill-tokens-saved-total"] == 32
    finally:
        engine.stop()


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["float", "int8kv"])
def test_concurrent_shared_preamble_burst_hits(config):
    """The workload the cache exists for: after one warmup chat, a burst of
    chats sharing the preamble all reuse it (hit rate counts the warmup
    miss) and every completion matches the cold engine's output."""
    preamble = [(11 + 2 * i) % CFG.vocab_size for i in range(32)]
    tails = [[(i + 1) % CFG.vocab_size, (2 * i + 3) % CFG.vocab_size] for i in range(4)]
    opts = GenerationOptions(max_new_tokens=8, temperature=0.0)

    cold_engine = make_engine(
        config, max_batch=4, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16, 32, 64),
    )
    try:
        cold = [
            cold_engine.generate(preamble + t, opts, timeout=120).tokens
            for t in tails
        ]
    finally:
        cold_engine.stop()

    engine = make_engine(
        config, prefix=True, max_batch=4, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16, 32, 64),
    )
    try:
        engine.generate(preamble + tails[0], opts, timeout=120)  # warmup/publish
        requests = [
            engine.submit(GenerationRequest(prompt_tokens=preamble + t, options=opts))
            for t in tails
        ]
        results = [r.result(timeout=120).tokens for r in requests]
        assert results == cold
        stats = engine.stats()
        # 1 warmup miss + 4 hits
        assert stats["prefix-cache-hit-rate"] == pytest.approx(4 / 5)
        assert stats["prefill-tokens-saved-total"] == 4 * 32
    finally:
        engine.stop()


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["float", "int8kv"])
def test_engine_eviction_pressure_stays_exact(config):
    """Cycling more distinct preambles than the pool holds forces LRU
    evictions mid-traffic; generations stay bit-exact throughout."""
    cold_engine = make_engine(
        config, max_batch=2, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16, 32),
    )
    engine = ServingEngine(
        config, PARAMS, max_batch=2, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16, 32), prefix_cache="auto", prefix_cache_entries=2,
    )
    engine.start()
    try:
        prompts = [
            [(seed + 7 * i) % CFG.vocab_size for i in range(40)]
            for seed in (1, 2, 3)
        ]
        for rnd in range(2):
            for prompt in prompts:
                cold = cold_engine.generate(prompt, GREEDY, timeout=120).tokens
                warm = engine.generate(prompt, GREEDY, timeout=120).tokens
                assert warm == cold, f"diverged on round {rnd}"
        assert engine.stats()["prefix-cache-evictions-total"] > 0
    finally:
        engine.stop()
        cold_engine.stop()


def test_lru_eviction_skips_referenced_pages():
    """Entries in use are never evicted: with the index full, the LRU
    UNPINNED entry goes; with every entry pinned, eviction refuses instead
    of handing an in-flight admission's pages to someone else. Pages an
    evicted entry shared with a live slot stay allocated."""
    pool = PagePool(CFG, num_pages=8, page_size=16, max_batch=2, max_seq_len=64)
    index = PrefixPageIndex(boundaries=(16, 32), max_entries=2)
    a, b, c = (list(range(k, k + 32)) for k in (100, 200, 300))

    def publish(tokens):
        # a publisher's slot reserves, publishes its leading pages, ends
        pool.reserve(1, 2)
        entry = index.insert(pool, tokens, 32, tuple(pool.slot_pages(1)))
        pool.free_slot(1)
        return entry

    ea, eb = publish(a), publish(b)
    # touch A so B is the LRU entry ... but B is pinned by an admission
    index.record_lookup(ea)
    index.acquire(eb)
    # a third publish at the cap must evict A (LRU among the unpinned)
    ec = publish(c)
    assert ec is not None and index.evictions == 1
    assert ea.node.entry is None and eb.node.entry is eb
    assert index.has(b, 32) and index.has(c, 32) and not index.has(a, 32)
    index.acquire(ec)
    assert not index.evict_lru(pool)  # everything pinned: refuse
    # a slot aliasing B's pages keeps them allocated past B's eviction
    b_pages = list(eb.pages)
    assert pool.reserve(0, 2, shared=tuple(b_pages)) is not None
    index.release(eb)
    assert index.evict_lru(pool) and index.evictions == 2
    assert pool.slot_pages(0) == b_pages
    assert pool.pages_in_use == 4  # C's two pages + the slot's two


def test_radix_candidates_and_publish_length():
    pool = PagePool(CFG, num_pages=8, page_size=8, max_batch=2, max_seq_len=64)
    index = PrefixPageIndex(boundaries=(8, 16, 32), max_entries=4)
    tokens = list(range(40))
    assert index.candidates(tokens) == []
    assert index.publish_length(40) == 32
    assert index.publish_length(20) == 16
    assert index.publish_length(4) == 0
    e = index.insert(pool, tokens, 32, tuple(pool.alloc_pages(4)))
    assert index.has(tokens, 32)
    # full-depth candidate for a longer prompt...
    assert index.candidates(tokens + [99]) == [(32, e)]
    # ...partial reuse at the matched depth for a prompt diverging at 20
    divergent = tokens[:16] + [500] * 16
    assert index.candidates(divergent) == [(16, e)]
    # the lookup cap: at least one suffix token must remain to prefill
    assert index.candidates(tokens[:32]) == [(16, e)]
    assert not index.candidates(tokens[:8])


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["float", "int8kv"])
def test_publish_dedupe_through_engine(config):
    """A prefix already indexed is not published again: the same prompt
    twice, then a prompt sharing its first boundary only, leave one entry
    per distinct boundary-aligned prefix and hold each page once."""
    prompt = [(7 + 3 * i) % CFG.vocab_size for i in range(45)]
    branch = prompt[:20] + [(5 * i + 2) % CFG.vocab_size for i in range(25)]
    engine = make_engine(
        config, prefix=True, max_batch=2, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16, 32, 64), page_size=16,
    )
    try:
        engine.generate(prompt, GREEDY, timeout=120)
        index = engine._prefix_index
        assert index.live_entries == 1 and index.pages_held == 2
        engine.generate(prompt, GREEDY, timeout=120)  # warm: nothing new
        assert index.live_entries == 1 and index.pages_held == 2
        # diverges at 20: aliases the first 16 tokens, publishes its own 32
        engine.generate(branch, GREEDY, timeout=120)
        assert index.live_entries == 2
        assert index.has(prompt, 32) and index.has(branch, 32)
        # the shared first page is held once, not once per entry
        assert index.pages_held == 3
        assert engine.stats()["prefix-pool-bytes-in-use"] == (
            3 * engine._pagepool.bytes_per_page
        )
    finally:
        engine.stop()

"""Unified paged KV pool tests (ROADMAP item 1 / ISSUE 6).

The page pool is a memory/bandwidth organization, never a math change:
greedy generations through the page table must be token-for-token identical
to the model-level reference (``prefill`` over a local cache, then
``decode_step``: tests/reference_decode.py) — cold and prefix-warm, short
and chunked-long admissions, both KV dtypes, speculation on and off. Plus the host half's
contracts: alias refcounts (a shared page is never freed while referenced;
a mid-page prefix tail is copy-on-write), allocator exhaustion DEFERS and
sheds instead of corrupting, the decode compile surface is ONE program
across mixed sequence lengths, and the
``page`` fault site quarantines exactly one slot with zero leaked pages.
"""

import dataclasses
import time

import jax
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
from reference_decode import reference_greedy

from langstream_tpu.serving.pagepool import (
    PagePool,
    PrefixPageIndex,
    pages_for_fraction,
    table_len_for,
)

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
CFG_INT8 = dataclasses.replace(CFG, kv_cache_dtype="int8")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))

GREEDY = GenerationOptions(max_new_tokens=10, temperature=0.0)


def make_engine(config=CFG, prefix=False, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("decode_chunk", 4)
    engine = ServingEngine(
        config,
        PARAMS,
        prefix_cache="auto" if prefix else "off",
        **kw,
    )
    engine.start()
    return engine


# ---------------------------------------------------------------------------
# Token-exactness: the engine vs the model-level reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "config, spec, page_size",
    [
        # curated combos: both dtypes, both page regimes (16 = pure alias,
        # 64 = mid-page COW), speculation on and off — the full 2×2×2
        # product re-tests the same code paths at tier-1-budget cost
        (CFG, False, 16),
        (CFG, True, 64),
        (CFG_INT8, False, 64),
        (CFG_INT8, True, 16),
    ],
    ids=["float-plain-alias", "float-spec-cow", "int8kv-plain-cow",
         "int8kv-spec-alias"],
)
def test_warm_prefix_exact_short_path(config, spec, page_size):
    """Admit-group path: a generation admitted against an ALIASED prefix is
    bit-identical to the model-level reference — one comparison carries
    both halves of the acceptance bar (engine==reference cold, since the
    engine's first generation is itself cold, AND warm==cold).
    page_size=16 makes the 32-boundary prefix two pure-alias pages (zero
    copies — bytes saved must show up); page_size=64 makes it a mid-page
    tail, exercising the copy-on-write page. Speculation on top must stay
    exact either way."""
    prompt = [(7 + 3 * i) % CFG.vocab_size for i in range(45)]
    other = prompt[:40] + [(3 * i + 1) % CFG.vocab_size for i in range(5)]
    kw = dict(
        prefill_buckets=(16, 32, 64), page_size=page_size,
        speculation="auto" if spec else "off", speculation_tokens=3,
    )
    cold = reference_greedy(config, PARAMS, prompt, GREEDY.max_new_tokens)
    cold2 = reference_greedy(config, PARAMS, other, GREEDY.max_new_tokens)

    engine = make_engine(config, prefix=True, **kw)
    try:
        warm0 = engine.generate(prompt, GREEDY, timeout=120).tokens  # publishes
        warm = engine.generate(prompt, GREEDY, timeout=120).tokens  # aliases
        warm2 = engine.generate(other, GREEDY, timeout=120).tokens  # shared preamble
        stats = engine.stats()
    finally:
        engine.stop()
    assert warm0 == cold and warm == cold and warm2 == cold2
    assert stats["prefix-cache-hit-rate"] > 0
    assert stats["prefill-tokens-saved-total"] > 0
    if page_size == 16:
        # full-page aliases: real copy bytes eliminated, and no page-copy
        # program was ever dispatched
        assert stats["prefix-copy-bytes-saved-total"] > 0
        assert not any(sig[0] == "page-copy" for sig in engine._programs)
    else:
        # mid-page prefix: exactly the copy-on-write path
        assert any(sig[0] == "page-copy" for sig in engine._programs)
    # zero-copy means zero gather/publish programs: a warm admission makes
    # no device copy of the prefix
    assert not any(
        str(sig[0]).startswith("prefix-") for sig in engine._programs
    ), engine._programs


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["float", "int8kv"])
def test_warm_prefix_exact_long_path(config):
    """Chunked-prefill path: a long prompt whose prefix is cached starts
    its segment loop at the reuse offset (ANY boundary — the paged segment
    writes at global positions, no full-segment-width constraint) and stays
    token-exact with the model-level reference (one comparison =
    engine==reference cold + warm==cold, as in the short-path test)."""
    prompt = [(5 + 2 * i) % CFG.vocab_size for i in range(150)]  # > largest bucket
    kw = dict(
        max_seq_len=256, prefill_buckets=(16, 32, 64), page_size=64,
    )
    cold = reference_greedy(config, PARAMS, prompt, GREEDY.max_new_tokens)
    engine = make_engine(config, prefix=True, **kw)
    try:
        # publish via a SHORT admission sharing the preamble, then the long
        # prompt aliases it into its chunked prefill
        engine.generate(prompt[:60], GREEDY, timeout=240)
        warm = engine.generate(prompt, GREEDY, timeout=240).tokens
        stats = engine.stats()
    finally:
        engine.stop()
    assert warm == cold
    assert stats["prefill-tokens-saved-total"] > 0


def test_paged_speculation_matches_plain_decode():
    """Greedy speculative decoding through the paged verify program is
    token-exact with plain paged decode (the round-9 invariant, now with
    ONE verify program instead of a ladder)."""
    prompt = [3, 5, 7, 5, 7, 5, 7, 5, 7, 11]  # periodic: drafts will fire
    opts = GenerationOptions(max_new_tokens=16, temperature=0.0)
    outs = {}
    for spec in ("off", "auto"):
        engine = make_engine(speculation=spec, speculation_tokens=4)
        try:
            outs[spec] = engine.generate(prompt, opts, timeout=120).tokens
        finally:
            engine.stop()
    assert outs["auto"] == outs["off"], outs


# ---------------------------------------------------------------------------
# The paged model functions against the full forward (float32)
# ---------------------------------------------------------------------------


def _prefill_through_pages(config, tokens, upto, width, page):
    """Rows [0, upto) of ``tokens`` through ``paged_prefill_segment_inplace``
    in ``width``-wide segments into row 0's pages; returns the pool, the
    table and each segment's logits (at its last real token)."""
    import jax.numpy as jnp

    from langstream_tpu.models import transformer as T

    n_pages = -(-(len(tokens) + 8) // page)
    pool = T.make_page_pool(config, n_pages + 1, page)
    table = jnp.arange(n_pages, dtype=jnp.int32)[None]
    ends, logits = [], []
    for s0 in range(0, upto, width):
        seg = tokens[s0 : min(s0 + width, upto)]
        padded = jnp.zeros((1, width), jnp.int32).at[0, : len(seg)].set(
            jnp.asarray(seg)
        )
        out, pool = T.paged_prefill_segment_inplace(
            PARAMS, padded, jnp.asarray([s0]), jnp.asarray([len(seg)]), pool,
            table, config, page,
        )
        ends.append(s0 + len(seg) - 1)
        logits.append(out[0])
    return pool, table, ends, logits


def _reference_logits(config, prompt, positions):
    """Logits at ``positions`` of ``prompt`` without a page: the full causal
    ``forward`` for a float cache; for int8 KV (which ``forward``, having
    no cache, does not quantize) the one-shot ``prefill`` of the prompt up
    to each position into an int8 local cache."""
    import jax.numpy as jnp

    from langstream_tpu.models.transformer import forward, make_kv_cache, prefill

    if config.kv_cache_dtype != "int8":
        full = forward(PARAMS, jnp.asarray([prompt]), config)[0]
        return [full[p] for p in positions]
    out = []
    for p in positions:
        logits, _ = prefill(
            PARAMS, jnp.asarray([prompt[: p + 1]]), jnp.asarray([p + 1]),
            make_kv_cache(config, 1, p + 1), config,
        )
        out.append(logits[0])
    return out


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["float", "int8kv"])
def test_paged_segments_match_forward_past_the_largest_bucket(config):
    """A prompt longer than the segment width, chunked through the slot's
    pages at global offsets (the last segment padded), gives at every
    segment's end the logits the unchunked reference gives there: what the
    dense segment kernels' tests used to hold the long path to."""
    import numpy as np

    prompt = [(5 + 7 * i) % CFG.vocab_size for i in range(150)]
    _, _, ends, logits = _prefill_through_pages(config, prompt, 150, 64, 16)
    assert ends == [63, 127, 149]
    for got, want in zip(logits, _reference_logits(config, prompt, ends)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["float", "int8kv"])
def test_paged_verify_matches_forward_at_every_draft_position(config):
    """The multi-token verify through the page table scores K+1 positions
    in one call: each row of its logits is the reference's at that
    position, over a prefix written by earlier segments."""
    import jax.numpy as jnp
    import numpy as np

    from langstream_tpu.models.transformer import paged_verify_step_inplace

    prompt = [(3 + 11 * i) % CFG.vocab_size for i in range(145)]
    want = jnp.stack(_reference_logits(config, prompt, range(140, 145)))
    pool, table, _, _ = _prefill_through_pages(config, prompt, 140, 64, 16)
    logits, _ = paged_verify_step_inplace(
        PARAMS, jnp.asarray([prompt[140:145]]), jnp.asarray([140]), pool,
        table, config, 16,
    )
    assert logits.shape == (1, 5, config.vocab_size)
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(want), rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------------------------------------
# Allocator / alias semantics (host half, no engine)
# ---------------------------------------------------------------------------


def test_alias_refcount_semantics():
    pool = PagePool(CFG, num_pages=8, page_size=16, max_batch=4, max_seq_len=64)
    index = PrefixPageIndex(boundaries=(16, 32), max_entries=4)
    # slot 0 admits a 40-token prompt (3 pages), publishes its 32-prefix
    assert pool.reserve(0, 3) is not None
    owned = pool.slot_pages(0)
    assert len(owned) == 3 and pool.pages_in_use == 3
    entry = index.insert(pool, list(range(40)), 32, tuple(owned[:2]))
    assert entry is not None
    # freeing the slot keeps the published pages alive (refcounted alias)
    freed = pool.free_slot(0)
    assert set(freed) == {owned[2]}  # only the unshared page came back
    assert pool.pages_in_use == 2
    # slot 1 aliases the two shared pages and allocates one of its own
    assert pool.reserve(1, 3, shared=tuple(entry.pages)) is not None
    assert pool.slot_pages(1)[:2] == list(entry.pages)
    assert pool.shared_pages == 2
    # evicting the entry must NOT free pages slot 1 still references
    index.acquire(entry)
    assert not index.evict_lru(pool)  # pinned: nothing evictable
    index.release(entry)
    assert index.evict_lru(pool)
    assert pool.pages_in_use == 3  # slot 1 holds all three
    freed = pool.free_slot(1)
    assert pool.pages_in_use == 0 and pool.free_pages == 8
    # COW bookkeeping: a 24-token prefix on 16-token pages = 1 full page
    # aliased + the partial second page copy-on-write
    ps = pool.page_size
    p = 24
    assert p // ps == 1 and p % ps == 8  # the shape the engine computes


def test_table_integrity_validation():
    pool = PagePool(CFG, num_pages=4, page_size=16, max_batch=2, max_seq_len=32)
    pool.reserve(0, 2)
    assert pool.validate(0)
    pool.tables[0, 0] = (pool.tables[0, 0] + 1) % pool.num_pages
    assert not pool.validate(0)
    # frees still route through the authoritative owned list: no leak
    pool.free_slot(0)
    assert pool.free_pages == 4


def test_pages_for_fraction_and_plan_term():
    assert table_len_for(128, 64) == 2
    assert table_len_for(100, 64) == 2
    assert pages_for_fraction(4, 128, 64) == 8
    assert pages_for_fraction(4, 128, 64, fraction=0.25) == 10
    from langstream_tpu.serving.memory import plan_serving_memory

    plan = plan_serving_memory(
        CFG, 4, 128, page_size=64, page_fraction=0.25
    )
    from langstream_tpu.models.transformer import make_kv_cache

    # every slot's max_seq_len + 25% alias headroom, in page-granular
    # arithmetic: 10 pages of 64 tokens against 4 rows of 128
    rows = jax.eval_shape(lambda: make_kv_cache(CFG, 4, 128))
    row_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(rows))
    assert plan.page_pool_bytes == row_bytes * 10 // 8
    # the pool is the plan's only KV term: the total is the sum of what
    # the summary names
    assert plan.total_bytes == (
        plan.weights_bytes + plan.page_pool_bytes + plan.workspace_bytes
    )
    assert "page-pool" in plan.summary() and "cache " not in plan.summary()


# ---------------------------------------------------------------------------
# Exhaustion: defer + shed, never corrupt
# ---------------------------------------------------------------------------


def test_allocator_exhaustion_defers_then_completes():
    """A pool sized for ~one active request at a time forces admissions to
    wait for pages. Everything still completes, token-exact — exhaustion is
    backpressure, not corruption."""
    opts = GenerationOptions(max_new_tokens=8, temperature=0.0)
    ref_engine = make_engine(max_batch=4, prefill_buckets=(32,))
    try:
        ref = ref_engine.generate([7, 8, 9], opts, timeout=120).tokens
    finally:
        ref_engine.stop()
    # 4 slots but only 2 pages of 64 → at most ~2 concurrent admissions
    engine = make_engine(
        max_batch=4, prefill_buckets=(32,), page_size=64, kv_pages=2,
    )
    try:
        requests = [
            engine.submit(GenerationRequest(prompt_tokens=[7, 8, 9], options=opts))
            for _ in range(6)
        ]
        results = [r.result(timeout=240) for r in requests]
    finally:
        engine.stop()
    assert all(r.tokens == ref for r in results), [r.tokens for r in results]


def test_allocator_exhaustion_sheds_reject_policy():
    """With a bounded queue + reject policy, page exhaustion backs the
    queue up and submit() sheds with ShedError — the documented degradation
    path — while the engine keeps serving what it accepted."""
    from langstream_tpu.serving.engine import ShedError

    opts = GenerationOptions(max_new_tokens=8, temperature=0.0)
    engine = make_engine(
        max_batch=4, prefill_buckets=(32,), page_size=64, kv_pages=2,
        queue_depth=2, shed_policy="reject",
    )
    try:
        accepted = []
        shed = 0
        for _ in range(12):
            try:
                accepted.append(
                    engine.submit(
                        GenerationRequest(prompt_tokens=[7, 8, 9], options=opts)
                    )
                )
            except ShedError:
                shed += 1
        results = [r.result(timeout=240) for r in accepted]
        assert all(r.finish_reason == "length" for r in results)
        assert shed > 0
        assert engine.stats()["shed-total"] >= shed
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# Compile surface: ONE decode program, no ladder
# ---------------------------------------------------------------------------


def test_compiled_programs_flat_across_mixed_lengths():
    """Decode is ONE program whatever the rows hold. Serve prompts and
    generations of very different lengths and assert the program count
    never moves after the first completed mix."""
    engine = make_engine(
        max_batch=2, max_seq_len=256, decode_chunk=4, prefill_buckets=(32,),
        precompile=True,
    )
    try:
        opts_short = GenerationOptions(max_new_tokens=4, temperature=0.0)
        engine.generate([1, 2, 3], opts_short, timeout=120)
        warmed = engine.stats()["compiled_programs"]
        # a long generation pushes positions across several pages
        engine.generate(
            list(range(2, 30)),
            GenerationOptions(max_new_tokens=130, temperature=0.0),
            timeout=240,
        )
        engine.generate([4, 5], opts_short, timeout=120)
        assert engine.stats()["compiled_programs"] == warmed, (
            engine._programs
        )
        assert [sig for sig in engine._programs if "decode" in sig[0]] == [
            ("paged-decode", 4)
        ]
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# Chaos: the `page` fault site
# ---------------------------------------------------------------------------


def _run_pair(injector_spec=None):
    from langstream_tpu.serving.faultinject import FaultInjector

    opts = GenerationOptions(max_new_tokens=12, temperature=0.0)
    injector = (
        FaultInjector(injector_spec, seed=0) if injector_spec else None
    )
    engine = make_engine(
        max_batch=4, prefill_buckets=(32,), fault_injector=injector,
    )
    try:
        requests = [
            engine.submit(
                GenerationRequest(prompt_tokens=[7, 8, 9 + i], options=opts)
            )
            for i in range(4)
        ]
        results = []
        for r in requests:
            try:
                results.append(r.result(timeout=240))
            except Exception as e:  # noqa: BLE001 — quarantined victim
                results.append(e)
        # one extra round proves the engine (and the freed pages) still serve
        follow = engine.generate([7, 8, 9], opts, timeout=240)
        stats = engine.stats()
        free = engine._pagepool.free_pages
        total = engine._pagepool.num_pages
    finally:
        engine.stop()
    return results, follow, stats, free, total


def test_page_fault_site_quarantines_victim_only():
    """Corrupting one slot's page-table entry quarantines THAT slot (its
    request fails, its pages free back to the pool — no leak), survivors
    are token-exact with a fault-free run, and the engine never restarts."""
    clean, follow_clean, _, _, _ = _run_pair()
    faulty, follow, stats, free, total = _run_pair("page@2")

    failures = [r for r in faulty if isinstance(r, Exception)]
    assert len(failures) == 1, faulty
    assert "page-table corruption" in str(failures[0])
    survivors = [
        (i, r) for i, r in enumerate(faulty) if not isinstance(r, Exception)
    ]
    assert len(survivors) == 3
    for i, r in survivors:
        assert r.tokens == clean[i].tokens, (i, r.tokens, clean[i].tokens)
    assert stats["quarantined-slots-total"] == 1
    assert stats["engine-restarts-total"] == 0
    # no leak: with every request finished, every page is back on the free
    # list (the follow-up request proves the freed pages still serve)
    assert free == total
    assert follow.tokens == follow_clean.tokens


def test_nan_quarantine_frees_and_zeroes_pages():
    """The NaN-guard quarantine in paged mode frees the victim's pages
    (zeroed before reuse) instead of resetting cache rows."""
    from langstream_tpu.serving.faultinject import FaultInjector

    opts = GenerationOptions(max_new_tokens=12, temperature=0.0)
    engine = make_engine(
        max_batch=2, prefill_buckets=(32,),
        fault_injector=FaultInjector("nan@2", seed=0),
    )
    try:
        reqs = [
            engine.submit(
                GenerationRequest(prompt_tokens=[5, 6, 7 + i], options=opts)
            )
            for i in range(2)
        ]
        outcomes = []
        for r in reqs:
            try:
                outcomes.append(r.result(timeout=240))
            except Exception as e:  # noqa: BLE001
                outcomes.append(e)
        assert any(isinstance(o, Exception) for o in outcomes)
        deadline = time.monotonic() + 30
        while engine._pagepool.pages_in_use and time.monotonic() < deadline:
            time.sleep(0.05)
        assert engine._pagepool.free_pages == engine._pagepool.num_pages
        assert engine.stats()["quarantined-slots-total"] >= 1
        assert engine.stats()["engine-restarts-total"] == 0
    finally:
        engine.stop()

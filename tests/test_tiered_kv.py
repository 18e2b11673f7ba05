"""Tiered KV tests (ROADMAP item 3 / ISSUE 11): host-RAM spill + session
hibernation must be a capacity/bandwidth reorganization, never a math
change. The contracts proven here:

  - RESTORE IS TOKEN-EXACT: a session whose prefix pages were spilled to
    the host arena, demoted off the device pool, and restored on its next
    turn generates byte-identical tokens to an always-device-resident run
    — across float + int8 KV, speculation on/off, and constrained slots.
  - THE TIER DEGRADES, NEVER LIES: a corrupted host page (the ``spill``
    fault site — host-RAM-rot drill) is caught by the arena checksum and
    the victim admission falls back to a cold re-prefill, token-exact,
    with zero engine restarts; survivors restore cleanly.
  - NOTHING LEAKS: spill→evict→restore→free cycles leave BOTH free lists
    (device pool pages, host arena slots) at their initial state.
  - SPILL IS OFF THE HOT LOOP: the per-iteration spill bookkeeping stays
    within the round-11 ≤1% instrumentation bound of a decode step.

CI pins LSTPU_FAULT_SEED (tier1.yml chaos step); the tests pass explicit
seeds anyway so they are deterministic in any environment.
"""

import dataclasses
import logging
import time

import jax
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.serving.engine import ServingEngine
from langstream_tpu.serving.faultinject import FaultInjector
from langstream_tpu.serving.pagepool import (
    HostPageTier,
    PagePool,
    PrefixPageIndex,
)
from langstream_tpu.serving.tokenizer import ByteTokenizer

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
CFG_INT8 = dataclasses.replace(CFG, kv_cache_dtype="int8")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))

GREEDY = GenerationOptions(max_new_tokens=10, temperature=0.0)

# two 45-token sessions over a 16/32/64 bucket ladder at page_size=16:
# each publishes a 32-token (2-page) prefix; kv_pages=5 cannot hold two
# resident sessions, so admitting B demotes A's hibernated prefix — the
# exact churn the tier exists for
PROMPT_A = [(7 + 3 * i) % CFG.vocab_size for i in range(45)]
PROMPT_B = [(5 + 11 * i) % CFG.vocab_size for i in range(45)]


def make_engine(config=CFG, tier=True, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_buckets", (16, 32, 64))
    kw.setdefault("page_size", 16)
    if tier:
        kw.setdefault("kv_pages", 5)
        kw.setdefault("host_kv_fraction", 2.0)
        kw.setdefault("spill_idle_s", 0.0)  # hibernate as soon as idle
        kw.setdefault("prefix_cache", "auto")
        kw.setdefault("prefix_cache_entries", 8)
    else:
        kw.setdefault("prefix_cache", "off")
        kw.setdefault("host_kv_fraction", 0.0)
    engine = ServingEngine(config, PARAMS, **kw)
    engine.start()
    return engine


def wait_spilled(engine, pages: int, timeout: float = 30.0) -> None:
    """Block until the idle-sweep has landed ``pages`` cumulative spill
    pages host-side (the engine iterates ~1ms while idle, so hibernation
    happens promptly once the session finishes)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if engine.stats()["spill-pages-total"] >= pages:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"spill never reached {pages} pages: {engine.stats()['spill-pages-total']}"
    )


def assert_leak_free(engine) -> None:
    """The ISSUE-11 no-leak bar: after the engine quiesces, dropping every
    surviving prefix entry must return BOTH free lists — device pool pages
    and host arena slots — to their initial (all-free) state."""
    pool, index, hier = engine._pagepool, engine._prefix_index, engine._host_tier
    engine._drain_spills()  # fold in any copy that completed at shutdown
    for entry in list(index._live):
        index._drop(pool, entry)
    assert pool.free_pages == pool.num_pages, (
        f"device pool leaked {pool.num_pages - pool.free_pages} pages"
    )
    if hier is not None:
        assert hier.free_slots == hier.num_pages, (
            f"host arena leaked {hier.num_pages - hier.free_slots} slots"
        )


# ---------------------------------------------------------------------------
# Token-exactness: hibernate → demote → restore
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "config, spec",
    [
        # curated combos (the pagepool suite's budget discipline): the two
        # tier-1 legs cover both KV dtypes AND spec on/off; the slow pair
        # completes the product in the chaos CI step (no marker filter)
        (CFG, False),
        pytest.param(CFG, True, marks=pytest.mark.slow),
        pytest.param(CFG_INT8, False, marks=pytest.mark.slow),
        (CFG_INT8, True),
    ],
    ids=["float-plain", "float-spec", "int8kv-plain", "int8kv-spec"],
)
def test_hibernate_restore_token_exact(config, spec):
    """The acceptance bar: session A publishes its prefix, hibernates
    (idle spill), is DEMOTED off the device pool by session B's admission
    (kv_pages=5 can't hold both), and A's next turn must (a) hit the radix
    on the host-tier entry, (b) restore it via the ONE warmed traced-index
    upload program, and (c) generate byte-identically to a tier-off run —
    the restore replaced a re-prefill, not the math."""
    kw = dict(speculation="auto" if spec else "off", speculation_tokens=3)
    cold_e = make_engine(config, tier=False, **kw)
    try:
        cold_a = cold_e.generate(PROMPT_A, GREEDY, timeout=120).tokens
        cold_b = cold_e.generate(PROMPT_B, GREEDY, timeout=120).tokens
    finally:
        cold_e.stop()

    engine = make_engine(config, **kw)
    try:
        a1 = engine.generate(PROMPT_A, GREEDY, timeout=120).tokens
        wait_spilled(engine, 2)  # A's 2-page prefix lands host-side
        b1 = engine.generate(PROMPT_B, GREEDY, timeout=120).tokens
        stats = engine.stats()
        assert stats["host-demotions-total"] >= 1, (
            "B's admission should have demoted A's hibernated prefix"
        )
        tiers = {e.tier for e in engine._prefix_index._live}
        assert "host" in tiers, f"no hibernated entry after demotion: {tiers}"
        a2 = engine.generate(PROMPT_A, GREEDY, timeout=120).tokens
        stats = engine.stats()
        assert a1 == cold_a and b1 == cold_b, "publishing runs diverged"
        assert a2 == cold_a, "post-hibernation turn diverged from cold run"
        assert stats["restored-hits-total"] == 1
        assert stats["restore-pages-total"] == 2
        assert stats["restore-failures-total"] == 0
        assert stats["recompute-fallbacks-total"] == 0
        # restore traffic is accounted in bytes of the POOL's dtype — int8
        # KV halves the per-page bytes, exactly like the device side
        tier = engine._host_tier
        assert stats["restore-bytes-total"] == 2 * tier.bytes_per_page
        assert stats["spill-bytes-total"] >= 2 * tier.bytes_per_page
        # ONE traced-index restore program, regardless of which physical
        # page was the destination (and it was warmed at precompile)
        restores = [s for s in engine._programs if s[0] == "page-restore"]
        assert len(restores) == 1, engine._programs
        # restore latency landed in its own histogram (added TTFT is the
        # tier's cost — it must be observable, docs/SERVING.md §16)
        hist = stats["histograms"]["engine_restore_s"]
        assert hist["count"] >= 1
        assert_leak_free(engine)
    finally:
        engine.stop()


@pytest.mark.slow  # two-engine e2e: runs in the chaos CI step
def test_constrained_slot_hibernate_restore_exact():
    """Constrained slots compose with hibernation: a session decoding
    under a json_schema grammar, hibernated and restored, must match the
    tier-off constrained run token-for-token (the grammar DFA is
    host-side slot state — hibernation only moves KV pages)."""
    tok = ByteTokenizer()
    rf = {"type": "json_schema", "json_schema": {"schema": {
        "type": "object",
        "properties": {"name": {"type": "string", "maxLength": 8}},
    }}}
    opts = GenerationOptions(
        max_new_tokens=24, temperature=0.0, response_format=rf
    )
    prompt = tok.encode("Return the JSON object for the user named Ada now")
    assert len(prompt) >= 33  # must clear the 32-token publish boundary
    kw = dict(grammar_tokenizer=tok, eos_token_id=tok.eos_token_id)
    cold_e = make_engine(CFG, tier=False, **kw)
    try:
        cold = cold_e.generate(list(prompt), opts, timeout=120).tokens
        cold_b = cold_e.generate(PROMPT_B, GREEDY, timeout=120).tokens
    finally:
        cold_e.stop()
    engine = make_engine(CFG, **kw)
    try:
        first = engine.generate(list(prompt), opts, timeout=120).tokens
        wait_spilled(engine, 2)
        b = engine.generate(PROMPT_B, GREEDY, timeout=120).tokens  # demotes
        again = engine.generate(list(prompt), opts, timeout=120).tokens
        stats = engine.stats()
        assert first == cold and again == cold and b == cold_b
        assert stats["restored-hits-total"] >= 1
        assert_leak_free(engine)
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# Chaos: the `spill` fault site (host-RAM rot)
# ---------------------------------------------------------------------------


def test_spill_fault_degrades_to_cold_prefill():
    """``spill@1`` corrupts one host-arena page of the FIRST restore's
    entry: the checksum must catch it, the victim admission must fall back
    to a cold re-prefill (token-exact — poisoned KV is never served), the
    poisoned entry must be dropped (not retried), survivors must restore
    cleanly afterwards, the engine must not restart, and neither free list
    may leak."""
    cold_e = make_engine(CFG, tier=False)
    try:
        cold_a = cold_e.generate(PROMPT_A, GREEDY, timeout=120).tokens
        cold_b = cold_e.generate(PROMPT_B, GREEDY, timeout=120).tokens
    finally:
        cold_e.stop()
    engine = make_engine(
        CFG, fault_injector=FaultInjector("spill@1", seed=0),
        # both sessions' prefixes must coexist host-side: A hibernated +
        # B hibernated (2 pages each) before the faulted restore
        host_kv_fraction=2.0,
    )
    try:
        a1 = engine.generate(PROMPT_A, GREEDY, timeout=120).tokens
        wait_spilled(engine, 2)
        b1 = engine.generate(PROMPT_B, GREEDY, timeout=120).tokens  # demotes A
        wait_spilled(engine, 4)  # B's prefix hibernates too
        # victim turn: restore of A fires the injector, checksum rejects,
        # admission recomputes cold — and must still be token-exact
        a2 = engine.generate(PROMPT_A, GREEDY, timeout=120).tokens
        stats = engine.stats()
        assert a2 == cold_a, "victim fell back but diverged — poisoned KV?"
        assert stats["restore-failures-total"] == 1
        assert stats["recompute-fallbacks-total"] >= 1
        assert stats["restored-hits-total"] == 0
        assert engine._injector.fired["spill"] == 1
        # survivor: B's hibernated session restores cleanly (the fault was
        # one-shot) and stays token-exact
        b2 = engine.generate(PROMPT_B, GREEDY, timeout=120).tokens
        stats = engine.stats()
        assert b2 == cold_b and a1 == cold_a and b1 == cold_b
        assert stats["restored-hits-total"] == 1
        assert stats["restore-failures-total"] == 1
        assert stats["engine-restarts-total"] == 0, "host rot must not restart"
        assert_leak_free(engine)
    finally:
        engine.stop()


def test_hibernation_churn_leak_free():
    """Sustained spill→demote→restore→free churn (both sessions cycling
    through hibernation repeatedly) ends with every device page and every
    arena slot back on its free list."""
    engine = make_engine(CFG)
    try:
        expected = {
            tuple(PROMPT_A): engine.generate(PROMPT_A, GREEDY, timeout=120).tokens,
        }
        wait_spilled(engine, 2)
        expected[tuple(PROMPT_B)] = engine.generate(
            PROMPT_B, GREEDY, timeout=120
        ).tokens
        for turn in range(3):
            for prompt in (PROMPT_A, PROMPT_B):
                got = engine.generate(prompt, GREEDY, timeout=120).tokens
                assert got == expected[tuple(prompt)], f"turn {turn} diverged"
        stats = engine.stats()
        assert stats["restored-hits-total"] >= 2, stats["restored-hits-total"]
        assert stats["spill-failures-total"] == 0
        # arena occupancy gauge tracks the tier's truth
        assert stats["host-pages-in-use"] == sum(
            len(e.host) for e in engine._prefix_index._live
        )
        assert_leak_free(engine)
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# Host arena + index units (no engine)
# ---------------------------------------------------------------------------


def _pool(config=CFG, num_pages=6):
    return PagePool(config, num_pages=num_pages, page_size=16, max_batch=2,
                    max_seq_len=64)


def test_host_tier_write_read_checksum_roundtrip():
    pool = _pool()
    tier = HostPageTier(pool.dev, 3)
    assert tier.free_slots == 3 and tier.slots_in_use == 0
    assert tier.bytes_per_page > 0
    assert tier.bytes_total == 3 * tier.bytes_per_page
    slots = tier.alloc(2)
    assert len(slots) == 2 and tier.free_slots == 1
    assert tier.alloc(2) is None, "over-allocation must fail, not wrap"
    # write one page worth of leaf blocks, read it back bit-exact
    rng = np.random.default_rng(0)
    blocks = [
        rng.standard_normal((a.shape[0],) + a.shape[2:]).astype(a.dtype)
        for a in tier._arrays
    ]
    tier.write(slots[0], blocks)
    got = tier.read(slots[0])
    assert got is not None
    for want, back in zip(blocks, jax.tree.leaves(got)):
        np.testing.assert_array_equal(want, back)
    # a slot nothing was written to has no checksum: unreadable by design
    assert tier.read(slots[1]) is None
    # corruption (one flipped byte anywhere) must fail the checksum
    tier.corrupt(slots[0])
    assert tier.read(slots[0]) is None, "corrupted page served as valid"
    tier.free(slots)
    assert tier.free_slots == 3
    # freeing dropped the checksum: a recycled slot can't serve stale KV
    s2 = tier.alloc(1)
    assert tier.read(s2[0]) is None
    tier.reset()
    assert tier.free_slots == 3


def test_index_demote_restore_semantics():
    """release_device_pages/attach_device_pages are exact inverses, the
    tier property tracks them, and evict_device_lru demotes (entry
    survives, hibernated) when the spill callback secures a host copy —
    and drops outright when it can't."""
    pool = _pool()
    index = PrefixPageIndex((16, 32, 64), max_entries=4)
    tier = HostPageTier(pool.dev, 4)
    index.host_tier = tier
    tok = [3 + i % 40 for i in range(40)]
    owned = pool._alloc(2)
    entry = index.insert(pool, tok, 32, tuple(owned))
    pool.decref(owned)  # the publishing slot frees; the index holds the ref
    assert entry.tier == "device"
    # simulate a completed spill
    entry.host = tuple(tier.alloc(2))
    index._note_tier(entry)
    assert entry.tier == "both"
    assert index.advertised(4) == [(entry.digest, 32, "both")]
    freed = index.release_device_pages(pool, entry)
    assert entry.tier == "host" and len(freed) == 2
    assert pool.free_pages == pool.num_pages
    assert index.advertised(4) == [(entry.digest, 32, "host")]
    # the hibernated entry still radix-hits (pages=() — the engine's cue
    # to restore rather than miss)
    assert index.candidates(tok + [1]) == [(32, entry)]
    pages = pool.alloc_pages(2)
    index.attach_device_pages(pool, entry, pages)
    assert entry.tier == "both" and entry.pages == tuple(pages)
    # demote-before-drop: with a host copy secured the LRU victim survives
    assert index.evict_device_lru(pool, spill_cb=lambda e: bool(e.host))
    assert entry.tier == "host" and index.demotions == 1
    assert index.live_entries == 1
    # nothing holding device pages is left to victimize
    assert index.evict_device_lru(pool, spill_cb=lambda e: False) is False
    index._drop(pool, entry)
    assert index.live_entries == 0
    assert tier.free_slots == 4 and pool.free_pages == pool.num_pages


def test_drop_mid_spill_defers_slot_free_to_drain():
    """An entry dropped while its copy is in flight must NOT free its
    arena slots synchronously (the worker still owns them) — the handle is
    cancelled and the engine's drain frees them. Mirrored by
    engine._drain_spills; here the index-side contract."""
    pool = _pool()
    index = PrefixPageIndex((16, 32), max_entries=2)
    tier = HostPageTier(pool.dev, 2)
    index.host_tier = tier

    class _Handle:
        cancelled = False

    tok = [5 + i % 30 for i in range(34)]
    entry = index.insert(pool, tok, 32, tuple(pool._alloc(2)))
    slots = tier.alloc(2)
    entry.spilling = _Handle()
    handle = entry.spilling
    index._drop(pool, entry)
    assert handle.cancelled and entry.dropped
    assert tier.free_slots == 0, "slots freed while the worker owned them"
    tier.free(slots)  # what _drain_spills does for a cancelled handle
    assert tier.free_slots == 2


def test_failed_spill_of_demoted_entry_drops_zombie():
    """An entry DEMOTED on the strength of an in-flight spill whose copy
    then fails holds neither device nor host pages: the drain must drop
    it (the session re-prefills next turn) — a zombie left in the trie
    would serve a later radix hit a zero-page 'restore' of KV that was
    never written."""
    from langstream_tpu.serving.engine import _Spill

    engine = make_engine(CFG)
    engine.stop()  # engine + spill threads quiesced: drive internals
    pool, index, tier = engine._pagepool, engine._prefix_index, engine._host_tier
    tok = [9 + i % 30 for i in range(34)]
    owned = pool._alloc(2)
    entry = index.insert(pool, tok, 32, tuple(owned))
    pool.decref(owned)
    slots = tier.alloc(2)
    handle = _Spill(entry, slots, [], engine._spill_gen)
    entry.spilling = handle
    index.release_device_pages(pool, entry)  # demoted mid-spill
    handle.error = RuntimeError("device_get failed")
    engine._spill_done.put(handle)
    engine._drain_spills()
    assert entry.dropped and index.live_entries == 0
    assert index.candidates(tok + [1]) == [], "zombie survived the drain"
    assert tier.free_slots == tier.num_pages
    assert pool.free_pages == pool.num_pages
    # belt-and-braces: _restore_entry refuses a zero-page entry outright
    owned = pool._alloc(2)
    entry2 = index.insert(pool, tok, 32, tuple(owned))
    pool.decref(owned)
    index.release_device_pages(pool, entry2)  # host=() zombie by hand
    assert not engine._restore_entry(entry2, 32)
    assert entry2.dropped and engine.stats()["restore-failures-total"] == 1


def test_idle_sweep_rotates_past_hot_head():
    """The spill deque is publish-ordered, not idle-ordered: a hot entry
    at the front (its last_used_t refreshed by every hit) must rotate to
    the back, not block hibernation of the idle entries behind it."""
    engine = make_engine(CFG, spill_idle_s=60.0)
    engine.stop()
    pool, index = engine._pagepool, engine._prefix_index
    tok_a = [1 + i % 20 for i in range(34)]
    tok_b = [2 + i % 25 for i in range(34)]
    entries = []
    for tok in (tok_a, tok_b):
        owned = pool._alloc(2)
        entries.append(index.insert(pool, tok, 32, tuple(owned)))
        pool.decref(owned)
    hot, idle = entries
    hot.last_used_t = time.monotonic()  # front of the deque, recently hit
    idle.last_used_t = time.monotonic() - 120.0
    engine._spill_candidates.clear()
    engine._spill_candidates.extend([hot, idle])
    engine._spill_tick()
    assert idle.spilling is not None, "idle entry starved behind hot head"
    assert hot.spilling is None
    assert hot in engine._spill_candidates, "hot entry must rotate, not drop"


# ---------------------------------------------------------------------------
# Gating, planning, hot-loop bound, observability schema
# ---------------------------------------------------------------------------


def test_spill_needs_prefix_index_and_paged_layout(caplog):
    """host-kv-fraction is an explicit ask: when its prerequisites are
    missing the engine must say so LOUDLY (the round-14 adapters
    precedent), never silently downgrade."""
    with caplog.at_level(logging.WARNING):
        engine = make_engine(
            CFG, tier=False, host_kv_fraction=2.0, prefix_cache="off",
        )
    try:
        assert not engine._spill_on and engine._host_tier is None
        assert engine.stats()["host-tier"] is False
        assert any("prefix index" in r.message for r in caplog.records)
    finally:
        engine.stop()
    with pytest.raises(ValueError):
        ServingEngine(CFG, PARAMS, spill="sometimes")


def test_plan_host_spill_term():
    """The memory plan's host_spill_bytes term: host RAM, reported in the
    summary but EXCLUDED from the HBM total an over-committed config dies
    on; fraction × device-pool pages at the pool's per-page bytes."""
    from langstream_tpu.serving.memory import plan_serving_memory

    base = plan_serving_memory(
        CFG, 4, 128, page_size=16, kv_pages=8,
    )
    tiered = plan_serving_memory(
        CFG, 4, 128, page_size=16, kv_pages=8,
        host_kv_fraction=4.0,
    )
    assert base.host_spill_bytes == 0
    assert tiered.host_spill_bytes == 4 * base.page_pool_bytes
    assert tiered.total_bytes == base.total_bytes, (
        "host arena is RAM — it must not inflate the HBM total"
    )
    assert "host KV tier" in tiered.summary()
    assert "host KV tier" not in base.summary()
    # int8 KV halves the arena like it halves the pool
    tiered_int8 = plan_serving_memory(
        CFG_INT8, 4, 128, page_size=16, kv_pages=8,
        host_kv_fraction=4.0,
    )
    assert tiered_int8.host_spill_bytes < tiered.host_spill_bytes


def test_spill_bookkeeping_within_hot_loop_bound():
    """ISSUE-11 acceptance: the round-11 ≤1% hot-loop overhead bound holds
    with spill ENABLED. The steady-state hot-loop cost of the tier is one
    _spill_tick per iteration (drain poll + deque check — the copies
    themselves run on the worker thread); measured best-of-5 against the
    same engine's measured decode step, amortized per step."""
    engine = make_engine(CFG, kv_pages=16)  # room for a 64-token decode
    try:
        for prompt in (PROMPT_A, PROMPT_B):
            engine.generate(
                prompt, GenerationOptions(max_new_tokens=64, temperature=0.0),
                timeout=300,
            )
        stats = engine.stats()
        step_s = stats["decode-step-ms"] / 1e3
        if step_s <= 0:
            step_s = stats["histograms"]["engine_decode_step_s"]["p50"]
        assert step_s > 0, "no decode step sample — cannot measure the bound"
    finally:
        engine.stop()
    # engine thread is dead: driving _spill_tick from here races nothing.
    # Candidates empty + done-queue empty = the steady state an idle-free
    # hot loop sees every iteration.
    assert engine._spill_on and not engine._spill_candidates
    per_tick = float("inf")
    for _ in range(5):
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            engine._spill_tick()
        per_tick = min(per_tick, (time.perf_counter() - t0) / n)
    per_step = per_tick / engine.decode_chunk
    ratio = per_step / step_s
    assert ratio <= 0.01, (
        f"spill bookkeeping {per_step * 1e6:.2f}us/step is "
        f"{ratio * 100:.2f}% of the {step_s * 1e3:.3f}ms decode step "
        "(bound: 1%)"
    )


def test_spill_stall_dump_reason_and_schema():
    """`spill-stall` is a legal flight-recorder reason; its dumps carry
    the restore timings in `extra`, record host-tier occupancy per
    iteration, and stay token-content-free like every reason."""
    from langstream_tpu.serving.observability import (
        DUMP_REASONS,
        validate_flight_dump,
    )

    assert "spill-stall" in DUMP_REASONS
    engine = make_engine(CFG)
    try:
        engine.generate(PROMPT_A, GREEDY, timeout=120)
        dump = engine._flight_dump(
            "spill-stall",
            extra={"restore-ms": 1234.5, "restore-pages": 2, "reuse-tokens": 32},
        )
        assert dump is not None and validate_flight_dump(dump)
        assert all("host_pages" in it for it in dump["iterations"])
        # redaction negative: token content in the extras must be rejected
        with pytest.raises(ValueError):
            validate_flight_dump({**dump, "extra": {"tokens": [1, 2, 3]}})
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# Review regressions: stale candidates, deferred-retry gauges, wedged worker
# ---------------------------------------------------------------------------


def test_paged_bind_skips_candidate_dropped_mid_loop():
    """A deeper candidate's restore can evict_for a SHALLOWER candidate out
    of the admission's already-materialized list. The dropped entry must
    read as a cold miss — before the fix its stale .pages aliased pages the
    free list had re-issued to another slot."""
    from langstream_tpu.serving.engine import GenerationRequest

    engine = make_engine(CFG)
    engine.stop()
    pool, index = engine._pagepool, engine._prefix_index
    owned = pool._alloc(2)
    entry = index.insert(pool, PROMPT_A, 32, tuple(owned))
    pool.decref(owned)
    stale_pages = entry.pages
    index._drop(pool, entry)
    # _drop clears the alias surface AND marks the entry
    assert entry.dropped and entry.pages == ()
    # _restore_entry refuses a dropped entry outright, gauges untouched
    assert engine._restore_entry(entry, 32) is False
    assert engine.stats()["restore-failures-total"] == 0
    # the loop-level belt: even a stale entry still carrying pages (the
    # pre-fix shape, only reachable through a list materialized before the
    # drop) must not serve as a hit
    entry.pages = stale_pages
    index.candidates = lambda prompt: [(32, entry)]
    req = GenerationRequest(prompt_tokens=list(PROMPT_A), options=GREEDY)
    reuse = engine._paged_bind(0, req)
    assert reuse == 0, "dropped candidate served as a warm hit"
    entry.pages = ()
    pool.free_slot(0)
    assert pool.free_pages == pool.num_pages


def test_deferred_retry_counts_tier_fallback_once():
    """A page-deferred admission re-runs _paged_bind every engine
    iteration; its failed-restore retries must not inflate
    restore-failures / recompute-fallbacks (THE tier health gauges) —
    each request counts its failures exactly once."""
    from langstream_tpu.serving.engine import GenerationRequest

    engine = make_engine(CFG)
    engine.stop()
    pool, index, tier = engine._pagepool, engine._prefix_index, engine._host_tier
    owned = pool._alloc(2)
    entry = index.insert(pool, PROMPT_A, 32, tuple(owned))
    pool.decref(owned)
    entry.host = tuple(tier.alloc(2))  # hibernated (no checksums needed:
    index.release_device_pages(pool, entry)  # restore fails before read)
    grabbed = pool._alloc(pool.free_pages)  # full pool, nothing evictable
    req = GenerationRequest(prompt_tokens=list(PROMPT_A), options=GREEDY)
    assert engine._paged_bind(0, req) is None  # defers
    assert engine.stats()["restore-failures-total"] == 1
    # a deferral is NOT a cold ending: the retry may restore, and one
    # request must never land on both sides of the health gauge
    assert engine.stats()["recompute-fallbacks-total"] == 0
    assert getattr(req, "_tier_fallback_counted", False)
    for _ in range(25):  # the deferred request's per-iteration retries
        assert engine._paged_bind(0, req) is None
    assert engine.stats()["restore-failures-total"] == 1, (
        "deferred retries inflated the restore-failure gauge"
    )
    assert engine.stats()["recompute-fallbacks-total"] == 0
    # pool frees up; the retry's restore still fails (arena slots carry
    # no checksummed copy) so the admission finally binds COLD — the one
    # and only recompute fallback is counted here, at bind time
    pool.decref(grabbed)
    assert engine._paged_bind(0, req) == 0
    assert engine.stats()["recompute-fallbacks-total"] == 1
    assert engine.stats()["restored-hits-total"] == 0
    pool.free_slot(0)


def test_spill_worker_stop_reports_wedged_thread():
    """stop() must return False — leaving alive() truthful — when the
    worker cannot drain within the timeout (wedged device fetch): crash
    recovery keys off this to abandon the arena instead of resetting it
    under a thread that may still write into it."""
    import queue as queue_mod
    import threading

    from langstream_tpu.serving.engine import _Spill, _SpillWorker

    gate = threading.Event()
    entered = threading.Event()

    class _StuckTier:
        def write(self, slot, leaves):
            entered.set()
            gate.wait()

    worker = _SpillWorker(_StuckTier(), queue_mod.SimpleQueue())
    worker.start()
    handle = _Spill(object(), [0], [np.zeros(2)], 0)
    worker.submit(handle)
    assert entered.wait(10.0)
    assert worker.stop(timeout=0.2) is False, "wedged join reported clean"
    assert worker.alive(), "thread forgotten while still running"
    gate.set()
    assert worker.stop(timeout=10.0) is True
    assert not worker.alive()


def test_entry_cap_never_evicts_hibernated_sessions():
    """The index entry cap bounds the DEVICE-resident working set only:
    hibernated sessions hold exclusive arena slots (the tier the operator
    sized for exactly this), so publish-pressure cap eviction must
    victimize the device LRU and never drop a restorable session."""
    pool = _pool(num_pages=6)
    index = PrefixPageIndex((16, 32), max_entries=2)
    tier = HostPageTier(pool.dev, 4)
    index.host_tier = tier
    hibernated = []
    for i in range(2):
        tok = [i + 1 + j % 20 for j in range(34)]
        owned = pool._alloc(2)
        entry = index.insert(pool, tok, 32, tuple(owned))
        pool.decref(owned)
        entry.host = tuple(tier.alloc(2))
        index.release_device_pages(pool, entry)
        hibernated.append(entry)
    device_entries = []
    for i in range(3):  # one past the cap: eviction must fire
        tok = [50 + i + j % 20 for j in range(34)]
        owned = pool._alloc(2)
        entry = index.insert(pool, tok, 32, tuple(owned))
        assert entry is not None, "publish blocked by hibernated entries"
        pool.decref(owned)
        device_entries.append(entry)
    assert all(not e.dropped for e in hibernated), (
        "cap eviction dropped a hibernated session with a paid-for arena copy"
    )
    assert device_entries[0].dropped, "device LRU should have made room"
    assert sum(1 for e in index._live if e.pages) <= 2
    assert tier.free_slots == 0  # both arena copies intact
    # the incrementally-maintained device-resident list never drifts
    assert sorted(map(id, index._dev_live)) == sorted(
        id(e) for e in index._live if e.pages
    )
    for e in list(index._live):
        index._drop(pool, e)
    assert not index._dev_live and pool.free_pages == pool.num_pages


def test_cap_eviction_demotes_spilled_victim():
    """A publish-cap victim whose host copy is already secured must DEMOTE
    (hibernate, restorable) — not be dropped with its paid-for arena copy,
    which only the never-spilled victim deserves."""
    pool = _pool(num_pages=6)
    index = PrefixPageIndex((16, 32), max_entries=1)
    tier = HostPageTier(pool.dev, 2)
    index.host_tier = tier
    tok = [1 + j % 20 for j in range(34)]
    owned = pool._alloc(2)
    spilled = index.insert(pool, tok, 32, tuple(owned))
    pool.decref(owned)
    spilled.host = tuple(tier.alloc(2))  # spill completed
    owned = pool._alloc(2)
    entry2 = index.insert(pool, [77 + j % 20 for j in range(34)], 32,
                          tuple(owned))
    assert entry2 is not None
    pool.decref(owned)
    assert not spilled.dropped, "cap eviction destroyed a hibernated session"
    assert spilled.tier == "host" and index.demotions == 1
    assert index.candidates(tok + [1]) == [(32, spilled)], "not restorable"

"""The page pool of a model with window layers: two groups in one manager
(`PagePool` the full layers' pages, `PagePool.window` the window layers'),
reserved and freed together, the window group a ring a row: reserve, advance,
recycle and free for both, a row that never passes the window, a row freed in
the middle of its prompt's segments, `validate()` over both tables, the
tables a dispatch takes, and the quarantine that scrubs both groups."""

import dataclasses

import jax
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import FULL, WINDOW, init_params
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
from langstream_tpu.serving.pagepool import PagePool, WindowPageGroup, window_ring_pages

CFG = dataclasses.replace(MODEL_PRESETS["tiny-window-moe-test"], dtype="float32")
PAGE = 8  # the window of 16 is 2 pages


def make_pool(max_batch=3, max_seq_len=128, num_pages=32, in_flight=16, **kw) -> PagePool:
    return PagePool(CFG, num_pages, PAGE, max_batch, max_seq_len, window_in_flight=in_flight, **kw)


@pytest.mark.parametrize(
    "window, in_flight, page, ring",
    [
        (16, 16, 8, 5),  # 31 columns: 4 pages, 5 when the first starts mid-page
        (16, 1, 8, 3),  # a decode step: the window's 2 pages and the one it enters
        (4096, 2048, 64, 97),  # the benchmark's cell: 6143 columns
        (4096, 16, 64, 66),
        (1, 1, 8, 1),
    ],
)
def test_a_ring_holds_the_window_and_the_dispatch_in_flight(window, in_flight, page, ring):
    assert window_ring_pages(window, in_flight, page) == ring
    # the most pages any placement of window + in_flight - 1 columns touches
    columns = window + in_flight - 1
    assert ring == max(
        (start + columns - 1) // page - start // page + 1 for start in range(page)
    )


def test_both_groups_are_reserved_and_freed_together():
    pool = make_pool()
    group = pool.window
    assert isinstance(group, WindowPageGroup) and (group.ring, group.num_pages) == (5, 15)
    assert pool.dev["win"]["k"].shape[:2] == (6, 15) and pool.dev["k"].shape[:2] == (2, 32)
    assert pool.reserve(0, 12) is not None  # 96 tokens: 12 full pages, a ring of 5
    assert pool.reserve(1, 3) is not None  # 24 tokens: 3 and 3 (its whole length)
    assert (pool.pages_in_use, group.pages_in_use, group.peak_in_use) == (15, 8, 8)
    assert len(group.slot_pages(0)) == 5 and len(group.slot_pages(1)) == 3
    assert pool.validate(0) and pool.validate(1)
    assert (group.tables == group.oob).all()  # held, none mapped before a dispatch
    pool.free_slot(0)
    assert (pool.pages_in_use, group.pages_in_use) == (3, 3) and group.peak_in_use == 8
    pool.free_slot(1)
    assert pool.free_pages == 32 and group.free_pages == 15
    assert not group._mapped and not group._fresh and not group._spare and not group._limit


def test_a_reservation_the_window_group_cannot_cover_takes_nothing():
    pool = make_pool(max_batch=3)  # a pool holds max_batch rings: a smaller group by hand
    pool.window = WindowPageGroup(8, PAGE, 3, pool.table_len, CFG.sliding_window, pool.window.ring)
    assert pool.reserve(0, 12) is not None  # a ring of 5 of the group's 8
    before = (pool.free_pages, pool.window.free_pages)
    assert pool.reserve(1, 12) is None  # 3 window pages left: deferred
    assert (pool.free_pages, pool.window.free_pages) == before
    assert pool.slot_pages(1) == [] and pool.window.slot_pages(1) == []
    assert pool.reserve(1, 3) is not None  # a short row still fits


def test_a_row_past_the_window_recycles_the_pages_behind_it():
    pool = make_pool()
    group = pool.window
    pool.reserve(0, 12)
    recycled = [group.advance(0, s0, s0 + 15) for s0 in (0, 16, 32, 48)]  # 4 segments of 16
    # segment 3 (queries 32..47) sees columns 17 on: page 0 and 1 go ahead; and so on
    assert recycled == [0, 0, 1, 2]
    assert sorted(group._mapped[0]) == [4, 5, 6, 7]  # columns 33..63
    assert pool.validate(0) and len(group.slot_pages(0)) == 5
    row = group.tables[0]
    assert (row[[4, 5, 6, 7]] < group.oob).all() and (np.delete(row, [4, 5, 6, 7]) == group.oob).all()
    assert len(set(row[[4, 5, 6, 7]])) == 4
    # decode steps: one position each; a page is recycled when a step enters a new one
    steps = [group.advance(0, p, p) for p in range(64, 80)]
    assert sum(steps) == 2 and steps[0] == 1 and steps[8] == 1
    assert sorted(group._mapped[0]) == [8, 9] and group.recycled_total == 5
    # never past its reservation: the 12th page is its last
    group.advance(0, 95, 110)
    assert max(group._mapped[0]) == 11 and pool.validate(0)


def test_a_row_that_never_passes_the_window_never_recycles():
    pool = make_pool()
    group = pool.window
    pool.reserve(1, 2)  # 16 tokens
    assert group.advance(1, 0, 15) == 0  # a padded group of width 16
    assert [group.advance(1, p, p) for p in range(9, 16)] == [0] * 7
    assert sorted(group._mapped[1]) == [0, 1] and group.recycled_total == 0
    assert pool.validate(1)


def test_a_row_freed_in_the_middle_of_its_segments_gives_every_page_back():
    pool = make_pool()
    group = pool.window
    pool.reserve(0, 12)
    group.advance(0, 0, 15)
    group.advance(0, 16, 31)
    group.advance(0, 32, 47)  # four pages mapped, one unmapped behind the window
    held = set(group.slot_pages(0))
    assert len(held) == 5 and len(group._spare[0]) == 1 and len(group._mapped[0]) == 4
    freed = pool.free_slot(0)
    assert len(freed) == 12 and group.free_pages == 15 and (group.tables[0] == group.oob).all()
    assert group.advance(0, 48, 63) == 0  # a dispatch after the free maps nothing
    assert pool.reserve(0, 4) is not None and pool.validate(0)


def test_validate_reads_both_tables():
    pool = make_pool()
    pool.reserve(0, 6)
    pool.window.advance(0, 0, 15)
    assert pool.validate(0)
    keep = pool.window.tables[0, 1]
    pool.window.tables[0, 1] = pool.window.oob  # the window group's row corrupted
    assert not pool.validate(0)
    pool.window.tables[0, 1] = keep
    pool.tables[0, 0] = pool.oob  # the full group's
    assert not pool.validate(0)


def test_the_tables_a_dispatch_takes():
    pool = make_pool()
    pool.reserve(0, 6)
    pool.reserve(2, 2)
    pool.window.advance(0, 0, 15)
    pool.window.advance(2, 0, 15)
    masked = pool.tables.copy()
    masked[2] = pool.oob  # the engine masks an inactive slot in the full group's rows
    both = pool.device_tables(masked)
    assert both.shape == (2, 3, pool.table_len)
    assert (both[FULL] == masked).all() and (both[WINDOW, 0] == pool.window.tables[0]).all()
    assert (both[WINDOW, 2] == pool.window.oob).all() and (both[WINDOW, 1] == pool.window.oob).all()
    rows = pool.rows_tables([2, pool.max_batch])  # a segment's row, and a warm-up's
    assert rows.shape == (2, 2, pool.table_len)
    assert (rows[FULL, 0] == pool.tables[2]).all() and (rows[WINDOW, 0] == pool.window.tables[2]).all()
    assert (rows[FULL, 1] == pool.oob).all() and (rows[WINDOW, 1] == pool.window.oob).all()
    # a model without window layers: one table, as it was
    dense = PagePool(MODEL_PRESETS["tiny-test"], 8, PAGE, 2, 32)
    assert dense.window is None and dense.device_tables(dense.tables) is dense.tables
    assert dense.rows_tables([0]).shape == (1, dense.table_len) and dense.window_advance(0, 0, 7) == 0


def test_reset_forgets_both_groups():
    pool = make_pool()
    pool.reserve(0, 12)
    pool.window.advance(0, 16, 31)
    pool.reset()
    assert pool.free_pages == 32 and pool.window.free_pages == 15
    assert (pool.window.tables == pool.window.oob).all() and "win" in pool.dev


def test_a_quarantined_slot_s_pages_of_both_groups_are_freed_and_scrubbed():
    """`nan@2`: the NaN guard quarantines a slot; its full pages AND its ring
    go back to their free lists zeroed, and the engine goes on serving."""
    from langstream_tpu.serving.faultinject import FaultInjector

    params = init_params(CFG, jax.random.PRNGKey(0))
    opts = GenerationOptions(max_new_tokens=12, temperature=0.0)
    engine = ServingEngine(
        CFG, params, max_batch=2, max_seq_len=128, prefill_buckets=(16,), page_size=PAGE,
        decode_chunk=4, fault_injector=FaultInjector("nan@2", seed=0),
    )
    engine.start()
    try:
        prompts = [list(range(5, 45)), list(range(50, 70))]
        requests = [
            engine.submit(GenerationRequest(prompt_tokens=p, options=opts)) for p in prompts
        ]
        outcomes = []
        for r in requests:
            try:
                outcomes.append(r.result(timeout=300))
            except Exception as e:  # noqa: BLE001 — the quarantined victim
                outcomes.append(e)
        assert any(isinstance(o, Exception) for o in outcomes)
        follow = engine.generate(prompts[0], opts, timeout=300)
        stats = engine.stats()
        assert stats["quarantined-slots-total"] >= 1 and stats["engine-restarts-total"] == 0
        assert ("window-page-zero",) in engine._programs
        pool = engine._pagepool
        assert pool.free_pages == pool.num_pages and pool.window.free_pages == pool.window.num_pages
        assert not np.isnan(np.asarray(pool.dev["win"]["k"], np.float32)).any()
    finally:
        engine.stop()
    clean = ServingEngine(
        CFG, params, max_batch=2, max_seq_len=128, prefill_buckets=(16,), page_size=PAGE,
        decode_chunk=4,
    )
    clean.start()
    try:
        assert follow.tokens == clean.generate(prompts[0], opts, timeout=300).tokens
    finally:
        clean.stop()

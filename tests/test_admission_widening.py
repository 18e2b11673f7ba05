"""A narrow prompt takes the free row of a wider admission group that is
dispatched anyway (ISSUE 58, docs/SERVING.md "An admission group computes the
rows it holds"): the grouping rule alone (`engine.admission_groups`), and
engines at tiny size whose prompts, admitted in one iteration with widening,
are served exactly as the same prompts admitted one at a time."""

import dataclasses
from collections import deque

import jax
import numpy as np
import pytest

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.serving import engine as E
from langstream_tpu.tracing import TRACER

PAGE = 16


def _by_width(widths):
    """Rows ``(age, name)`` in the order the queue gave them up, by width."""
    by_width: dict[int, list] = {}
    for age, width in enumerate(widths):
        by_width.setdefault(width, []).append((age, f"r{age}"))
    return by_width


def _computed(groups, rungs):
    return sum(next(r for r in rungs if r >= len(rows)) * width for width, rows in groups)


# (widths in age order, rungs, widen, the groups expected as (width, ages))
GROUPING = {
    # ISSUE 58's cell: 8 prompts of two widths under the one rung of 8
    "lfm2-mix": ([256, 128, 256, 256, 128, 256, 128, 256], (8,), True,
                 [(256, [0, 1, 2, 3, 4, 5, 6, 7])]),
    "lfm2-mix-three-widths": ([64, 256, 128, 256, 64, 256, 128, 256], (8,), True,
                              [(256, [0, 1, 2, 3, 4, 5, 6, 7])]),
    # a lone wide prompt rides the rung of one: no free row, nothing moves
    "dense-wide-of-1": ([256, 128, 128], (1, 8), True, [(128, [1, 2]), (256, [0])]),
    "dense-wide-of-2": ([256, 128, 128, 256], (1, 8), True, [(256, [0, 1, 2, 3])]),
    "dense-wide-of-5-narrow-of-3": ([256] * 5 + [128] * 3, (1, 8), True,
                                    [(256, list(range(8)))]),
    # 7 wide rows leave one free row: a narrow group of 2 shrinks to the rung
    # of one (its oldest moves), a narrow group of 3 would stay at 8 and keeps
    "dense-wide-of-7-shrinks-2": ([256] * 7 + [128] * 2, (1, 8), True,
                                  [(128, [8]), (256, [0, 1, 2, 3, 4, 5, 6, 7])]),
    "dense-wide-of-7-leaves-3": ([256] * 7 + [128] * 3, (1, 8), True,
                                 [(128, [7, 8, 9]), (256, list(range(7)))]),
    # docs: rungs (1, 4); the lone 1 x 1024 beside a 4 x 2048 of 3 disappears
    "docs-lone-narrow": ([2048, 1024, 2048, 2048], (1, 4), True, [(2048, [0, 1, 2, 3])]),
    "docs-two-narrow-one-free": ([2048, 1024, 2048, 2048, 1024], (1, 4), True,
                                 [(1024, [4]), (2048, [0, 1, 2, 3])]),
    "docs-wide-full": ([2048] * 4 + [1024], (1, 4), True, [(1024, [4]), (2048, [0, 1, 2, 3])]),
    # three widths: the widest fills first and empties the group with the
    # fewest rows; the middle one then takes what is left of the narrowest
    "three-widths-empty-before-shrink": (
        [256, 256, 256, 256, 128, 128, 64, 64, 64], (1, 8), True,
        [(64, [8]), (256, [0, 1, 2, 3, 4, 5, 6, 7])],
    ),
    # ... and a narrow group that could not come down a rung keeps its rows
    "three-widths-empty-and-leave": (
        [256, 256, 256, 256, 256, 128, 128, 64, 64, 64, 64], (1, 8), True,
        [(64, [7, 8, 9, 10]), (256, [0, 1, 2, 3, 4, 5, 6])],
    ),
    "three-widths-middle-takes": (
        [256] * 8 + [128, 128, 64], (1, 8), True,
        [(128, [8, 9, 10]), (256, list(range(8)))],
    ),
    # more than a group of one width (overlap off: no budget): only the last
    # sub-batch has a free row
    "two-sub-batches": ([256] * 10 + [128] * 3, (1, 8), True,
                        [(256, list(range(8))), (256, [8, 9, 10, 11, 12])]),
    "one-width": ([128] * 3, (1, 8), True, [(128, [0, 1, 2])]),
    "one-rung-of-one": ([256, 128], (1,), True, [(128, [1]), (256, [0])]),
    # the capacity layer's configuration: every width keeps its own groups
    "held-out": ([256, 128, 256, 256, 128, 256, 128, 256], (8,), False,
                 [(128, [1, 4, 6]), (256, [0, 2, 3, 5, 7])]),
}


@pytest.mark.parametrize("case", sorted(GROUPING))
def test_the_grouping_rule(case):
    widths, rungs, widen, expected = GROUPING[case]
    by_width = _by_width(widths)
    before = {w: list(rows) for w, rows in by_width.items()}
    groups = E.admission_groups(by_width, rungs, widen)
    assert by_width == before, "the caller's lists were rewritten"
    assert [(w, [age for age, _ in rows]) for w, rows in groups] == expected
    # each request in exactly one group, none over prefill_batch, oldest first
    assert sorted(age for _, rows in groups for age, _ in rows) == list(range(len(widths)))
    for width, rows in groups:
        assert 0 < len(rows) <= rungs[-1]
        assert [age for age, _ in rows] == sorted(age for age, _ in rows)
        # a row's own bucket never exceeds its group's width
        assert all(widths[age] <= width for age, _ in rows)
    # never more computed tokens nor more dispatches than every width alone
    alone = E.admission_groups(by_width, rungs, widen=False)
    assert len(groups) <= len(alone)
    assert _computed(groups, rungs) <= _computed(alone, rungs)
    assert [w for w, _ in groups] == sorted(w for w, _ in groups)
    if not widen:
        assert groups == [
            (w, rows[i : i + rungs[-1]])
            for w, rows in sorted(by_width.items()) for i in range(0, len(rows), rungs[-1])
        ]


# -- engines: the same prompts with widening and one at a time ----------------

PRESETS = {
    # dense (rungs 1 and 4), a model whose expert layer holds its experts
    # under conv layers (one rung, a convolution tail a slot), a recurrent
    # hybrid (a delta-rule state a slot), a model that fills blocks
    name: dataclasses.replace(MODEL_PRESETS[name], dtype="float32")
    for name in ("tiny-test", "tiny-lfm2-test", "tiny-hybrid-test", "tiny-blockfill-moe-test")
}
# (prompt length, cap): buckets 32 and 64; the 9-token prompt reserves ONE page
# of 16, so its row of the 64-wide group maps one of the group's four
PROMPTS = ((40, 6), (20, 6), (9, 4), (50, 6))


def _engine(config, params):
    return E.ServingEngine(
        config, params, max_batch=4, max_seq_len=128, prefill_buckets=(32, 64),
        page_size=PAGE, decode_chunk=4, prefill_batch=4, precompile=False,
    )


def _requests(config):
    rng = np.random.default_rng(58)
    return [
        E.GenerationRequest(
            prompt_tokens=rng.integers(1, config.vocab_size - 1, n).tolist(),
            options=GenerationOptions(max_new_tokens=cap, temperature=0.0),
        )
        for n, cap in PROMPTS
    ]


def _admitted(engine, requests, one_at_a_time):
    """The engine's state behind the admissions alone (no decode step yet)
    and the fetch entries they left."""
    entries = []
    for request in requests:
        engine.submit(request)
        if one_at_a_time:
            entries.extend(engine._admit())
    if not one_at_a_time:
        entries.extend(engine._admit())
    pool = engine._pagepool
    return entries, {
        "positions": [slot.position for slot in engine._slots],
        "tables": pool.tables.copy(),
        "dev": jax.tree.map(np.asarray, pool.dev),
    }


def _assert_unreserved_pages_untouched(engine, got):
    """No page outside the rows' reservations was written: the pool starts
    as zeros, and what no table maps is zeros still."""
    pool = engine._pagepool
    mapped = set(got["tables"][got["tables"] != pool.oob].tolist())
    idle = sorted(set(range(pool.num_pages)) - mapped)
    for leaf in engine.config.page_leaves:
        assert float(np.abs(got["dev"][leaf][:, idle]).max()) == 0.0, leaf


def _serve(engine, requests, entries):
    """The requests served to their end: (their tokens, the attributes of the
    `engine.admit_group` spans, which a group's landing emits)."""
    pending = deque([entries])
    TRACER.clear()
    try:
        while not all(r._done.is_set() for r in requests):
            engine._iterate(pending)
    finally:
        engine.stop()
    groups = [s["attributes"] for s in TRACER.spans(4096) if s["name"] == "engine.admit_group"]
    return [list(r.result(timeout=1).tokens) for r in requests], groups


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_widened_admissions_are_served_as_the_prompts_admitted_alone(name):
    """Four prompts of two buckets admitted in ONE iteration leave as one
    64-wide group (the two 32-bucket prompts in the rows it would pad): slot
    positions, page tables, every real token's rows of the pool, the state
    rows and the greedy tokens are those of the same prompts admitted one at a
    time, each in its own bucket; a row writes no page it did not reserve."""
    config = PRESETS[name]
    params = T.init_params(config, jax.random.PRNGKey(0))
    wide = _engine(config, params)
    assert wide._admit_widens
    requests = _requests(config)
    entries, got = _admitted(wide, requests, one_at_a_time=False)
    assert wide.stats()["admit-rows-widened"] == 2
    assert wide.stats()["admit-group-rows"][4] == 1
    alone = _engine(config, params)
    alone_requests = _requests(config)
    alone_entries, want = _admitted(alone, alone_requests, one_at_a_time=True)
    assert alone.stats()["admit-rows-widened"] == 0

    assert got["positions"] == want["positions"]
    np.testing.assert_array_equal(got["tables"], want["tables"])
    # the 9-token prompt reserved one page, under a group four pages wide
    oob = wide._pagepool.oob
    assert int((got["tables"][2] != oob).sum()) == 1

    def rows_of(dev, slot, n):
        """Leaf by leaf, the pool's rows of ``slot``'s first ``n`` tokens."""
        pages = got["tables"][slot, : -(-n // PAGE)]
        out = {}
        for leaf in config.page_leaves:
            a = np.moveaxis(dev[leaf][:, pages], -2, 2)  # [L, pages, page, ...]
            out[leaf] = a.reshape(a.shape[0], -1, *a.shape[3:])[:, :n]
        return out

    whole = config.block_length or 1
    for slot, (n, _) in enumerate(PROMPTS):
        n = n // whole * whole  # a block model prefills whole blocks
        for leaf, rows in rows_of(got["dev"], slot, n).items():
            np.testing.assert_allclose(
                rows, rows_of(want["dev"], slot, n)[leaf], atol=1e-5, err_msg=f"{slot} {leaf}"
            )
    _assert_unreserved_pages_untouched(wide, got)
    for leaf, state in (got["dev"].get("rec") or {}).items():
        np.testing.assert_allclose(state, want["dev"]["rec"][leaf], atol=1e-5, err_msg=leaf)

    tokens, groups = _serve(wide, requests, entries)
    assert [(g["rows"], g["real_rows"], g["width"], g["widened_rows"]) for g in groups] == [
        (4, 4, 64, 2)
    ]
    alone_tokens, groups = _serve(alone, alone_requests, alone_entries)
    assert [(g["real_rows"], g["width"], g["widened_rows"]) for g in groups] == [
        (1, 64, 0), (1, 32, 0), (1, 32, 0), (1, 64, 0)
    ]
    assert tokens == alone_tokens


def test_a_widened_row_copies_the_pages_it_reserved_and_no_other():
    """Where the insert copies whole pages (the kernels forced: interpret
    mode), a 64-wide group's span counts the copies of its rows' MAPPED
    entries: the widened 9-token row reserved one page of the four its row
    spans, and the pool's other pages stay as they were."""
    config = dataclasses.replace(PRESETS["tiny-test"], attention_impl="pallas")
    params = T.init_params(config, jax.random.PRNGKey(0))
    engine = _engine(config, params)
    requests = _requests(config)
    entries, got = _admitted(engine, requests, one_at_a_time=False)
    _, (group,) = _serve(engine, requests, entries)
    oob = engine._pagepool.oob
    assert group["widened_rows"] == 2
    assert group["kv_pages_written"] == int((got["tables"][:, :4] != oob).sum()) == 3 + 2 + 1 + 4
    _assert_unreserved_pages_untouched(engine, got)


def test_the_capacity_layer_keeps_every_width_its_own_group():
    """`moe_ffn` hands capacity out in row order, padding included: a wider
    row's padding ahead of a later real row would cost it assignments, so an
    expert model that does not hold its experts is left out of the rule."""
    config = dataclasses.replace(MODEL_PRESETS["tiny-moe-test"], dtype="float32")
    assert config.is_moe and not config.holds_experts
    engine = _engine(config, T.init_params(config, jax.random.PRNGKey(1)))
    assert not engine._admit_widens
    requests = _requests(config)
    entries, _ = _admitted(engine, requests, one_at_a_time=False)
    tokens, groups = _serve(engine, requests, entries)
    assert [(g["rows"], g["real_rows"], g["width"], g["widened_rows"]) for g in groups] == [
        (4, 2, 32, 0), (4, 2, 64, 0)
    ]
    assert engine.stats()["admit-rows-widened"] == 0
    assert all(len(t) == cap for t, (_, cap) in zip(tokens, PROMPTS))

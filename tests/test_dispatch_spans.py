"""What the device programs and the dispatch loop say they did (ISSUE 24,
docs/SERVING.md §12): one span per dispatch with its work counts, the
iteration span, the scope vocabulary in the lowered programs, the MoE
routed/dropped counts against a reference, and that none of it changes a
logit."""

import dataclasses
import math
import re
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.serving import engine as E
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
from langstream_tpu.tracing import TRACER

DENSE = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
MOE = dataclasses.replace(MODEL_PRESETS["tiny-moe-test"], dtype="float32")
DISPATCH_SPANS = (
    "engine.admit_group", "engine.prefill_segment", "engine.decode_chunk",
    "engine.verify", "engine.iteration",
)


@pytest.fixture(scope="module")
def dense_params():
    return T.init_params(DENSE, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def moe_params():
    return T.init_params(MOE, jax.random.PRNGKey(1))


def spans_named(name):
    return [s for s in TRACER.spans(4096) if s["name"] == name]


def drain(engine, pending):
    engine._stop.set()
    while pending:
        for entry in pending.popleft():
            engine._process_entry(entry)
    engine._fail_all(RuntimeError("test torn down"))


def test_one_span_per_group_and_chunk_with_work_counts(dense_params):
    TRACER.clear()
    engine = ServingEngine(
        DENSE, dense_params, max_batch=4, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16,), prefill_batch=2,
    )
    engine.start()
    try:
        reqs = [
            engine.submit(GenerationRequest(
                prompt_tokens=[5 + i] * (3 + i),
                options=GenerationOptions(max_new_tokens=10),
                trace_id=f"dispatchspan{i:04d}",
            ))
            for i in range(3)
        ]
        for r in reqs:
            r.result(timeout=300)
    finally:
        engine.stop()
    groups = spans_named("engine.admit_group")
    chunks = spans_named("engine.decode_chunk")
    assert groups and chunks
    seqs = [s["attributes"]["seq"] for s in groups + chunks]
    assert len(set(seqs)) == len(seqs), "a dispatch was emitted twice"
    assert sum(g["attributes"]["real_rows"] for g in groups) == 3
    for g in groups:
        a = g["attributes"]
        # the smallest rung of (1, 2) that holds the group's prompts: all of it real
        assert a["program"] == "admit_group" and a["rows"] == a["real_rows"] and a["width"] == 16
        assert 0 < a["real_tokens"] <= a["computed_tokens"] == 16 * a["rows"]
        assert len(a["trace_ids"]) == a["real_rows"]
        assert a["device_ms"] <= g["durationMs"] + 1e-3
        assert a["kv_pages_written"] == 0  # off the TPU the insert is the scatter
    assert sum(g["attributes"]["real_tokens"] for g in groups) == 3 + 4 + 5
    for c in chunks:
        a = c["attributes"]
        assert a["program"] == "_paged_decode_chunk"
        assert a["steps"] >= 1 and 1 <= a["active_rows"] <= 3
        assert a["kv_tokens_read"] >= a["steps"] * a["active_rows"]
        # the paged kernel's page iterations
        assert a["steps"] * a["active_rows"] <= a["kv_pages_visited"] <= a["kv_tokens_read"]
        # every live row's write page is mapped here: a row a step, no drop
        assert a["kv_rows_written"] == a["steps"] * a["active_rows"]
        assert "moe_routed" not in a  # a dense model fetches no counts
    # device time per request class is a join: prefill child -> its group
    by_seq = {g["attributes"]["seq"]: g for g in groups}
    for i in range(3):
        prefill = [
            s for s in spans_named("engine.prefill")
            if s["traceId"] == f"dispatchspan{i:04d}"
        ]
        assert len(prefill) == 1
        group = by_seq[prefill[0]["attributes"]["group_seq"]]
        assert f"dispatchspan{i:04d}" in group["attributes"]["trace_ids"]
    iterations = spans_named("engine.iteration")
    assert iterations
    phases = iterations[-1]["attributes"]["phase_ms"]
    assert {"sweep", "prefill", "dispatch", "process", "wait", "deliver"} <= set(phases)
    assert phases["wait"] + phases["deliver"] == pytest.approx(phases["process"], abs=2e-3)
    # the flight recorder's frame IS the span's attributes: one dict
    assert engine._obs.flight.iterations()[-1] is not None
    assert any(
        f["i"] == iterations[-1]["attributes"]["i"]
        for f in engine._obs.flight.iterations()
    )
    assert engine.stats()["moe-routed-assignments-total"] == 0


def test_admit_group_rows_stat_matches_a_hand_count(dense_params, moe_params):
    """`admit-group-rows` counts the groups dispatched at each rung of the
    ladder (1 and 4 under a `prefill_batch` of 4). Seven prompts of one
    width queued before an iteration: four ride a full group, three the
    next at 4 rows; then two at 4 rows and one at 1. An expert model has
    the one rung and counts every group there."""
    opts = GenerationOptions(max_new_tokens=40, temperature=0.0)

    def admit(engine, n):
        for _ in range(n):
            engine.submit(GenerationRequest(prompt_tokens=[9, 9, 9], options=opts))
        return engine._admit()

    TRACER.clear()
    engine = ServingEngine(
        DENSE, dense_params, max_batch=10, max_seq_len=64, decode_chunk=4,
        prefill_buckets=(16,), prefill_batch=4,
    )
    assert engine.stats()["admit-group-rows"] == {1: 0, 4: 0}
    entries = admit(engine, 7) + admit(engine, 2) + admit(engine, 1)
    assert engine.stats()["admit-group-rows"] == {1: 1, 4: 3}
    drain(engine, deque([entries]))
    groups = [g["attributes"] for g in spans_named("engine.admit_group")]
    assert sorted((g["rows"], g["real_rows"]) for g in groups) == [(1, 1), (4, 2), (4, 3), (4, 4)]

    experts = ServingEngine(
        MOE, moe_params, max_batch=10, max_seq_len=64, decode_chunk=4,
        prefill_buckets=(16,), prefill_batch=4,
    )
    entries = admit(experts, 3) + admit(experts, 1)
    assert experts.stats()["admit-group-rows"] == {4: 2}
    drain(experts, deque([entries]))


def test_widened_rows_and_the_stat_read_what_the_schedule_did(dense_params, moe_params):
    """`widened_rows` on the span and `admit-rows-widened` count the rows that
    rode a group wider than their own bucket (`engine.admission_groups`),
    under buckets 16 and 32 and the ladder 1, 4. A lone 32-wide prompt rides
    the rung of one and has no free row: the two 16-wide ones keep their
    group. Two 32-wide prompts ride 4 rows: the 16-wide one takes a free row
    and its own dispatch is gone. The capacity layer keeps its groups."""
    opts = GenerationOptions(max_new_tokens=20, temperature=0.0)

    def admit(engine, lengths):
        for n in lengths:
            engine.submit(GenerationRequest(prompt_tokens=[9] * n, options=opts))
        return engine._admit()

    def served(config, params):
        TRACER.clear()
        engine = ServingEngine(
            config, params, max_batch=8, max_seq_len=64, decode_chunk=4,
            prefill_buckets=(16, 32), prefill_batch=4,
        )
        entries = admit(engine, (20, 5, 7)) + admit(engine, (20, 30, 5))
        stats = engine.stats()
        drain(engine, deque([entries]))
        groups = [g["attributes"] for g in spans_named("engine.admit_group")]
        return stats, [
            (g["width"], g["rows"], g["real_rows"], g["widened_rows"], g["real_tokens"])
            for g in groups
        ]

    stats, groups = served(DENSE, dense_params)
    assert groups == [(16, 4, 2, 0, 12), (32, 1, 1, 0, 20), (32, 4, 3, 1, 55)]
    assert stats["admit-rows-widened"] == 1
    assert stats["admit-group-rows"] == {1: 1, 4: 2}
    stats, groups = served(MOE, moe_params)
    assert groups == [
        (16, 4, 2, 0, 12), (32, 4, 1, 0, 20), (16, 4, 1, 0, 5), (32, 4, 2, 0, 50)
    ]
    assert stats["admit-rows-widened"] == 0 and stats["admit-group-rows"] == {4: 4}


def test_kv_pages_written_is_the_mapped_pages_of_the_group_s_rows(dense_params):
    """Where the insert copies pages (the kernels forced: interpret mode) a
    group's span counts the copies a layer and leaf: Σ over its rows of the
    table entries under width / page_size that are mapped. A bucket of 48 is
    three pages of 16: a row that reserved 9 + 4 tokens maps one of them, one
    that reserved 40 + 30 all three, a padding row none."""
    TRACER.clear()
    engine = ServingEngine(
        dataclasses.replace(DENSE, attention_impl="pallas"), dense_params, max_batch=4,
        max_seq_len=128, decode_chunk=4, page_size=16, prefill_buckets=(48,), prefill_batch=4,
    )
    for n, cap in ((9, 4), (40, 30)):
        engine.submit(GenerationRequest(
            prompt_tokens=[7] * n, options=GenerationOptions(max_new_tokens=cap, temperature=0.0),
        ))
    entries = engine._admit()
    mapped = (engine._pagepool.tables[:, :3] != engine._pagepool.oob).sum()
    drain(engine, deque([entries]))
    (group,) = [g["attributes"] for g in spans_named("engine.admit_group")]
    assert (group["rows"], group["real_rows"]) == (4, 2)
    assert group["kv_pages_written"] == mapped == 1 + 3


def test_kv_tokens_read_matches_a_hand_count(dense_params):
    """Two requests, driven one iteration at a time: A (3 tokens) decodes
    alone in chunk 1; B (2 tokens) joins for chunk 2, where A's device
    position leads by chunk 1's eight steps whether or not the host has
    processed them yet."""
    TRACER.clear()
    engine = ServingEngine(
        DENSE, dense_params, max_batch=2, max_seq_len=128, decode_chunk=8,
    )
    pending: deque = deque()
    opts = GenerationOptions(max_new_tokens=60, temperature=0.0)
    engine.submit(GenerationRequest(prompt_tokens=[4, 5, 6], options=opts))
    engine._iterate(pending)
    engine.submit(GenerationRequest(prompt_tokens=[7, 8], options=opts))
    engine._iterate(pending)
    drain(engine, pending)
    chunks = sorted(spans_named("engine.decode_chunk"), key=lambda s: s["attributes"]["seq"])
    assert [c["attributes"]["steps"] for c in chunks[:2]] == [8, 8]
    assert [c["attributes"]["active_rows"] for c in chunks[:2]] == [1, 2]
    lengths = lambda first: sum(first + j for j in range(8))  # noqa: E731
    # step j of a row whose token is written at position p attends p+1+j keys
    assert chunks[0]["attributes"]["kv_tokens_read"] == lengths(3 + 1)
    assert chunks[1]["attributes"]["kv_tokens_read"] == lengths(3 + 8 + 1) + lengths(2 + 1)
    # live rows x steps: one row, then two, a row written at every step
    assert [c["attributes"]["kv_rows_written"] for c in chunks[:2]] == [8, 16]
    # a warm-up chunk runs with no slot active: every row drops
    assert engine._kv_page_counts(8) == (0, 0) and not any(s.active for s in engine._slots)


def test_kv_pages_visited_matches_a_hand_count(dense_params):
    """The same two requests over pages of 8 tokens: A (3 tokens, 60 to
    come: 8 pages reserved) decodes alone in chunk 1; B (2 tokens, 5 to
    come: ONE page reserved) joins for chunk 2 and steps past its
    reservation inside it, where its length, and so the kernel's walk,
    stops at the page it has."""
    TRACER.clear()
    engine = ServingEngine(
        DENSE, dense_params, max_batch=3, max_seq_len=128, decode_chunk=8,
        page_size=8,
    )
    # B's chunk must still be in flight when its table is read below: on a
    # loaded host the device can finish it inside the second iteration,
    # which would deliver B's five tokens and free its page
    engine._batch_ready = lambda batch: False
    pending: deque = deque()
    engine.submit(GenerationRequest(
        prompt_tokens=[4, 5, 6],
        options=GenerationOptions(max_new_tokens=60, temperature=0.0),
    ))
    engine._iterate(pending)
    engine.submit(GenerationRequest(
        prompt_tokens=[7, 8],
        options=GenerationOptions(max_new_tokens=5, temperature=0.0),
    ))
    engine._iterate(pending)
    mapped = sorted((engine._pagepool.tables != engine._pagepool.oob).sum(axis=1))
    drain(engine, pending)
    assert mapped == [0, 1, 8]  # a free slot, B, A
    chunks = sorted(spans_named("engine.decode_chunk"), key=lambda s: s["attributes"]["seq"])
    assert [c["attributes"]["active_rows"] for c in chunks[:2]] == [1, 2]
    pages = lambda first, cap: sum(  # noqa: E731
        min(math.ceil((first + j) / 8), cap) for j in range(8)
    )
    assert pages(4, 8) == 5 * 1 + 3 * 2 and pages(3, 1) == 8 < pages(3, 8) == 10
    assert chunks[0]["attributes"]["kv_pages_visited"] == pages(4, 8)
    assert chunks[1]["attributes"]["kv_pages_visited"] == pages(12, 8) + pages(3, 1)
    # the rows written: A's eight a chunk; B writes positions 2..7 into its
    # one page and DROPS the two steps past it
    assert [c["attributes"]["kv_rows_written"] for c in chunks[:2]] == [8, 8 + 6]


@pytest.mark.parametrize("kv", ["model", "int8"])
def test_a_chunk_with_idle_and_finished_rows_is_token_exact_through_the_kernel(
    dense_params, kv
):
    """Six slots, three requests of unequal caps: every decode chunk
    carries rows without a table (never admitted: length 0 for the kernel)
    and, from the shortest request's end on, rows that finished inside an
    earlier chunk while their device positions run on. The kernel path
    (interpret mode) must deliver the jnp path's tokens for every
    request."""
    def tokens(impl):
        config = dataclasses.replace(DENSE, attention_impl=impl, kv_cache_dtype=kv)
        engine = ServingEngine(
            config, dense_params, max_batch=6, max_seq_len=64, decode_chunk=8,
            prefill_buckets=(16,), page_size=8,
        )
        engine.start()
        try:
            reqs = [
                engine.submit(GenerationRequest(
                    prompt_tokens=[11 + 3 * i] * (4 + 5 * i),
                    options=GenerationOptions(max_new_tokens=cap, temperature=0.0),
                ))
                for i, cap in enumerate((5, 19, 30))
            ]
            return [r.result(timeout=300).tokens for r in reqs]
        finally:
            engine.stop()

    from langstream_tpu.ops.attention import attention_paths

    got = tokens("pallas")
    assert any(
        k.startswith("paged-decode[s=1,t=64]") and v.startswith("ragged_paged_decode")
        for k, v in attention_paths().items()
    )
    assert [len(t) for t in got] == [5, 19, 30]
    assert got == tokens("jnp")


def test_nothing_is_emitted_with_observability_off(dense_params):
    TRACER.clear()
    engine = ServingEngine(
        DENSE, dense_params, max_batch=2, max_seq_len=64, decode_chunk=4,
        observability=False,
    )
    engine.start()
    try:
        r = engine.generate([5, 6, 7], GenerationOptions(max_new_tokens=8), timeout=120)
        assert len(r.tokens) == 8
        assert engine.prefill_tps_estimate() == 0.0
    finally:
        engine.stop()
    names = {s["name"] for s in TRACER.spans(4096)}
    assert not names.intersection(DISPATCH_SPANS + ("engine.request",))
    # nor counted: the device's unfed account stays where it began
    stats = engine.stats()
    assert stats["device-unfed-s"] == 0 == stats["device-unfed-with-request-s"]
    assert not engine._open and engine._slots[0].stages is None


def test_prefill_tps_estimate_is_tokens_over_dispatch_to_ready(dense_params):
    """On a blocked run (one request at a time, so every group's first
    tokens are waited for) the estimate has to sit within 2x of the real
    prompt tokens over the groups' dispatch→ready time, which the spans
    give independently — not two orders above it, as a launch time read."""
    TRACER.clear()
    engine = ServingEngine(
        DENSE, dense_params, max_batch=2, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(64,),
    )
    engine.start()
    try:
        for i in range(4):
            engine.generate([3 + i] * 40, GenerationOptions(max_new_tokens=2), timeout=120)
        estimate = engine.prefill_tps_estimate()
        hist = engine.stats()["histograms"]["engine_prefill_group_s"]
    finally:
        engine.stop()
    groups = spans_named("engine.admit_group")
    assert len(groups) == 4 == hist["count"]
    seconds = sum(g["durationMs"] for g in groups) / 1e3
    assert hist["sum"] == pytest.approx(seconds, rel=0.05, abs=1e-3)
    truth = 4 * 40 / seconds
    assert truth / 2 <= estimate <= truth * 2


# ---------------------------------------------------------------------------
# what a first token waits for, and when the device goes unfed
# ---------------------------------------------------------------------------


def request_children(trace_id):
    spans = [s for s in TRACER.spans(4096) if s["traceId"] == trace_id]
    return {s["name"]: s for s in spans}


def test_ttft_stages_add_up_and_behind_is_what_was_in_flight(dense_params):
    """Driven one iteration at a time: A meets an idle engine (nothing was
    ever in flight: `behind_ms` 0), B is admitted while A's chunk is in
    flight and rides behind it. For both, `engine.queued` and the four
    stages of `engine.prefill` add up to submitted → first token."""
    TRACER.clear()
    engine = ServingEngine(
        DENSE, dense_params, max_batch=2, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16,),
    )
    in_flight = [True]
    engine._batch_ready = lambda batch: not in_flight[0]
    pending: deque = deque()
    opts = GenerationOptions(max_new_tokens=6, temperature=0.0)
    a = engine.submit(GenerationRequest(prompt_tokens=[4, 5, 6], options=opts, trace_id="stage-a"))
    engine._iterate(pending)  # A's group lands inline (a cold start); chunk 1 goes out
    b = engine.submit(GenerationRequest(prompt_tokens=[7, 8], options=opts, trace_id="stage-b"))
    engine._iterate(pending)  # B's group is launched behind chunk 1
    in_flight[0] = False
    for _ in range(20):
        if a._done.is_set() and b._done.is_set():
            break
        engine._iterate(pending)
    drain(engine, pending)
    assert len(a.result(1).tokens) == 6 == len(b.result(1).tokens)
    groups = {g["attributes"]["seq"]: g for g in spans_named("engine.admit_group")}
    for trace_id in ("stage-a", "stage-b"):
        spans = request_children(trace_id)
        stages = spans["engine.prefill"]["attributes"]
        first_token_ms = (spans["engine.decode"]["start"] - spans["engine.request"]["start"]) * 1e3
        parts = [stages[k] for k in ("launch_ms", "behind_ms", "device_ms", "land_ms")]
        assert all(p >= 0 for p in parts)
        assert spans["engine.queued"]["durationMs"] + sum(parts) == pytest.approx(
            first_token_ms, abs=0.01
        )
        # the stages are the group's own: `group_seq` is the join
        group = groups[stages["group_seq"]]["attributes"]
        assert trace_id in group["trace_ids"]
        assert (stages["behind_ms"], stages["device_ms"]) == (group["behind_ms"], group["device_ms"])
        assert "prev_ready_ms" not in group and "behind_steps" not in group
    assert request_children("stage-a")["engine.prefill"]["attributes"]["behind_ms"] == 0
    assert request_children("stage-b")["engine.prefill"]["attributes"]["behind_ms"] > 0


def union_ms(intervals, lo, hi):
    covered, edge = 0.0, lo
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b > edge:
            covered += b - max(a, edge)
            edge = b
    return covered


def test_unfed_account_equals_a_hand_count_and_restarts_with_the_histograms(dense_params):
    """Three requests a pause apart on a running engine. From the spans'
    stamps alone: a launch finds the device unfed when every earlier
    dispatch's result was ready before it; the stretch runs from the last of
    those (or the account's start) to the launch, and its with-request part
    is what the requests' own spans cover of it. Each such launch's span and
    the two counters of `stats()` say the same; `reset_histograms` restarts
    them with `engine-loop-s`."""
    from langstream_tpu.tracing import MONO_TO_WALL_S

    engine = ServingEngine(
        DENSE, dense_params, max_batch=2, max_seq_len=64, decode_chunk=4,
        prefill_buckets=(16,),
    )
    engine.start()
    try:
        engine.generate([3, 4, 5], GenerationOptions(max_new_tokens=4), timeout=300)  # compiles
        time.sleep(0.05)
        TRACER.clear()
        engine.reset_histograms()
        t_reset = (engine._account_t0 + MONO_TO_WALL_S) * 1e3
        for i in range(3):
            time.sleep(0.03)
            engine.generate([6 + i] * 4, GenerationOptions(max_new_tokens=7), timeout=300)
        time.sleep(0.03)
        before = time.monotonic()
        stats = engine.stats()
        after = time.monotonic()
    finally:
        engine.stop()
    ms = lambda s: (s["start"] * 1e3, s["start"] * 1e3 + s["durationMs"])  # noqa: E731
    launches = sorted(
        (s for name in DISPATCH_SPANS[:4] for s in spans_named(name)),
        key=lambda s: s["attributes"]["seq"],
    )
    requests = [ms(s) for s in spans_named("engine.request")]
    assert len(requests) == 3 and len(launches) >= 6
    unfed = with_request = 0.0
    last_ready = t_reset
    for span in launches:
        start, end = ms(span)
        a = span["attributes"]
        if start >= last_ready:  # nothing in flight: a stretch ends here
            assert a["unfed_ms"] == pytest.approx(start - last_ready, abs=0.01)
            assert a["unfed_with_request_ms"] == pytest.approx(
                union_ms(requests, last_ready, start), abs=0.01
            )
            unfed += a["unfed_ms"]
            with_request += a["unfed_with_request_ms"]
        else:
            assert "unfed_ms" not in a
        last_ready = max(last_ready, end)
    # three pauses of 30 ms with no request, less the chunk still in flight
    # when each began (the last one launched before a request's end was
    # seen: some ms on a loaded host), and a request open while the engine
    # decided, launched and delivered
    assert with_request > 0 and unfed - with_request > 45
    # the counters: those stretches and the idle tail open when they were
    # read. The tail has a with-request part too, where the last result was
    # on the host before the thread had delivered the last request's end
    now = ((before + MONO_TO_WALL_S) * 1e3, (after + MONO_TO_WALL_S) * 1e3)
    tail = (now[0] - last_ready, now[1] - last_ready)
    assert unfed + tail[0] - 0.05 <= stats["device-unfed-s"] * 1e3 <= unfed + tail[1] + 0.05
    assert max(end for _, end in requests) <= now[0]
    assert stats["device-unfed-with-request-s"] * 1e3 == pytest.approx(
        with_request + union_ms(requests, last_ready, now[0]), abs=0.05
    )
    assert stats["device-unfed-s"] <= stats["engine-loop-s"] <= after - engine._account_t0 + 1e-3
    engine.reset_histograms()
    again = engine.stats()
    assert again["engine-loop-s"] < 0.5 and again["device-unfed-with-request-s"] == 0
    assert again["device-unfed-s"] <= again["engine-loop-s"]


def test_tokens_delivered_is_what_the_requests_received_from_the_chunk(dense_params):
    """Two requests of 6 and 11 tokens over chunks of 4 steps: the first
    token comes from the group, the others from chunks, and a row that ends
    inside a chunk leaves the rest of its steps computed for nobody."""
    TRACER.clear()
    engine = ServingEngine(
        DENSE, dense_params, max_batch=2, max_seq_len=64, decode_chunk=4,
        prefill_buckets=(16,),
    )
    engine.start()
    try:
        reqs = [
            engine.submit(GenerationRequest(
                prompt_tokens=[5 + i] * 3, options=GenerationOptions(max_new_tokens=cap),
            ))
            for i, cap in enumerate((6, 11))
        ]
        got = [len(r.result(timeout=300).tokens) for r in reqs]
    finally:
        engine.stop()  # lands the chunks still in flight
    assert got == [6, 11]
    chunks = [c["attributes"] for c in spans_named("engine.decode_chunk")]
    for c in chunks:
        assert c["row_steps"] == c["steps"] * c["active_rows"]
        assert 0 <= c["tokens_delivered"] <= c["row_steps"]
    assert sum(c["tokens_delivered"] for c in chunks) == (6 - 1) + (11 - 1)
    # 5 and 10 tokens are no multiple of a chunk's steps: a row ended mid-chunk
    assert any(0 < c["tokens_delivered"] < c["row_steps"] for c in chunks)


# ---------------------------------------------------------------------------
# MoE counts
# ---------------------------------------------------------------------------


def reference_counts(chosen: np.ndarray, valid: np.ndarray, capacity: int, e: int):
    """Assignments in (token, slot) order; an expert keeps its first
    `capacity` and drops the rest."""
    taken = np.zeros(e, int)
    dropped = dropped_real = 0
    for t in range(chosen.shape[0]):
        for expert in chosen[t]:
            taken[expert] += 1
            if taken[expert] > capacity:
                dropped += 1
                dropped_real += bool(valid[t])
    k = chosen.shape[1]
    return [chosen.size, dropped, int(valid.sum()) * k, dropped_real]


def skewed_layer(moe_params, bias=50.0):
    """One layer's params with the router pushed onto expert 0."""
    lp = jax.tree.map(lambda a: a[0], moe_params["layers"])
    router = np.array(lp["router"])
    router[:, 0] += bias * np.sign(router[:, 0].sum() or 1.0)
    return {**lp, "router": jnp.asarray(router)}


@pytest.mark.parametrize("factor", [0.25, 1.0, 0.0, -1.0])
def test_forced_skew_drops_what_the_reference_counts(moe_params, factor):
    config = dataclasses.replace(MOE, moe_capacity_factor=factor)
    b, s = 2, 128
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, MOE.d_model), jnp.float32)
    x = jnp.abs(x)  # every token leans the same way: all choose expert 0
    lp = skewed_layer(moe_params)
    valid = jnp.arange(s)[None, :] < jnp.asarray([100, 7])[:, None]
    out, counts = T.moe_ffn_counted(x, lp, config, valid)
    logits = np.asarray((x.reshape(b * s, -1) @ lp["router"]).astype(jnp.float32))
    chosen = np.argsort(-logits, axis=-1, kind="stable")[:, : MOE.n_experts_per_tok]
    t, k, e = b * s, MOE.n_experts_per_tok, MOE.n_experts
    capacity = (
        min(t, max(math.ceil(t * k * factor / e), min(t, 64))) if factor > 0 else t
    )
    want = reference_counts(chosen, np.asarray(valid).reshape(t), capacity, e)
    assert [int(c) for c in counts] == want
    if factor > 0:
        assert want[1] > 0 and 0 < want[3] < want[1]  # skew binds; padding drops too
    else:
        assert want[1] == 0 == want[3]  # lossless: capacity = T
    # the counting path changes no output
    assert np.array_equal(np.asarray(out), np.asarray(T.moe_ffn(x, lp, config)))


def test_logits_are_bit_identical_with_and_without_the_counts(moe_params):
    config = dataclasses.replace(MOE, moe_capacity_factor=0.25)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 1, MOE.vocab_size)
    lengths = jnp.asarray([32, 9])
    plain, cache = T.prefill(
        moe_params, tokens, lengths, T.make_kv_cache(config, 2, 32), config
    )
    counted, cache2, counts = T.prefill(
        moe_params, tokens, lengths, T.make_kv_cache(config, 2, 32), config,
        moe_counts=True,
    )
    assert np.array_equal(np.asarray(plain), np.asarray(counted))
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()), cache, cache2))
    routed, dropped, routed_real, dropped_real = (int(c) for c in counts)
    assert routed == 2 * 32 * MOE.n_experts_per_tok * MOE.n_layers
    assert routed_real == (32 + 9) * MOE.n_experts_per_tok * MOE.n_layers
    assert 0 <= dropped_real <= dropped <= routed
    page = 16
    pool = T.make_page_pool(config, 8, page)
    table = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    step = jax.jit(
        T.paged_decode_step_inplace,
        static_argnames=("config", "page_size", "moe_counts"),
    )
    args = (moe_params, tokens[:, 0], jnp.asarray([3, 5]), pool, table)
    a, _ = step(*args, config=config, page_size=page)
    b, _, decode_counts = step(*args, config=config, page_size=page, moe_counts=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(decode_counts[1]) == 0  # capacity = T at two rows


def test_engine_reports_moe_counts_on_spans_and_in_stats(moe_params):
    TRACER.clear()
    config = dataclasses.replace(MOE, moe_capacity_factor=0.25)
    engine = ServingEngine(
        config, moe_params, max_batch=2, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(64,), prefill_batch=2,
    )
    engine.start()
    try:
        engine.generate([7] * 40, GenerationOptions(max_new_tokens=6), timeout=300)
    finally:
        engine.stop()  # lands the chunk still in flight behind the last token
    stats = engine.stats()
    spans = spans_named("engine.admit_group") + spans_named("engine.decode_chunk")
    assert stats["moe-routed-assignments-total"] == sum(
        s["attributes"]["moe_routed"] for s in spans
    ) > 0
    assert stats["moe-dropped-assignments-total"] == sum(
        s["attributes"]["moe_dropped"] for s in spans
    )
    group = spans_named("engine.admit_group")[0]["attributes"]
    k, layers = MOE.n_experts_per_tok, MOE.n_layers
    assert group["moe_routed"] == 2 * 64 * k * layers
    assert group["moe_routed_real"] == 40 * k * layers
    for chunk in spans_named("engine.decode_chunk"):
        a = chunk["attributes"]
        assert a["moe_routed"] == a["steps"] * 2 * k * layers and a["moe_dropped"] == 0


# ---------------------------------------------------------------------------
# names inside the device programs
# ---------------------------------------------------------------------------


def lowered_scopes(fn, *args) -> set:
    """Every name-stack component in the lowered program's locations
    (`loc("attention/dot_general")`, nested calls each with their own). A
    location that names a FRAME of the traceback (`loc("block_choice"(#loc3))`
    where `#loc3` is a file position) is no scope: a helper jitted once a
    process keeps the frames of whoever traced it first, another test's
    engine among them."""
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    positions = set(re.findall(r'^(#loc\d+) = loc\("[^"]+":\d+:\d+', text, re.M))
    return {
        part
        for loc, inner in re.findall(r'loc\("([^"]+)"(?:\((#loc\d+)\))?', text)
        if inner not in positions
        for part in loc.split("/")
    }


def test_lowered_programs_carry_every_scope(dense_params, moe_params):
    """The union over a dense and a MoE model of the admission path
    (prefill + page scatter + first-token sample), the paged decode chunk
    and the dense-layout decode step names every scope of the vocabulary —
    and each model's own."""
    page, b = 16, 2
    tokens = jnp.ones((b, 32), jnp.int32)
    lengths = jnp.asarray([32, 9])
    table = jnp.arange(8, dtype=jnp.int32).reshape(b, 4)
    key = jax.random.PRNGKey(0)
    ones, zeros = jnp.ones(b), jnp.zeros(b, jnp.int32)
    texts = {}
    for name, config, params in (("dense", DENSE, dense_params), ("moe", MOE, moe_params)):
        pool = T.make_page_pool(config, 8, page)

        def admit(params, pool, config=config):
            logits, local = T.prefill(
                params, tokens, lengths, T.make_kv_cache(config, b, 32), config
            )
            first, _, _ = E._sample_first(
                logits, key, ones, zeros, ones, None, None, None, config.vocab_size
            )
            return first, T.paged_insert_cache(pool, local, table, page)

        def decode(params, pool, config=config):
            return E._paged_decode_chunk(
                params, tokens[:, 0], lengths, pool, table, key, ones, zeros,
                ones, 2, config, page,
            )

        texts[name] = lowered_scopes(admit, params, pool) | lowered_scopes(
            decode, params, pool
        )
    # a model with a layer pattern: the linear layers' scopes, prefill and decode
    hybrid = MODEL_PRESETS["tiny-hybrid-test"]
    hybrid_params = T.init_params(hybrid, jax.random.PRNGKey(2))
    hybrid_pool = T.make_page_pool(hybrid, 8, page, state_rows=b)

    def hybrid_admit(params, pool):
        kv, rec = T.split_rec(pool)
        return T.prefill(
            params, tokens, lengths, T.join_rec(T.make_kv_cache(hybrid, b, 32), rec), hybrid,
            rec_rows=jnp.arange(b),
        )

    def hybrid_decode(params, pool):
        return E._paged_decode_chunk(
            params, tokens[:, 0], lengths, pool, table, key, ones, zeros, ones, 2, hybrid, page,
        )

    texts["hybrid"] = lowered_scopes(hybrid_admit, hybrid_params, hybrid_pool) | lowered_scopes(
        hybrid_decode, hybrid_params, hybrid_pool
    )
    linear = {s for s in T.SCOPES if s.startswith("linear_attention")}
    assert len(linear) == 5 and linear <= texts["hybrid"]
    assert {"attention", "ffn", "kv_pool.write"} <= texts["hybrid"]  # its full layers
    assert not linear & (texts["dense"] | texts["moe"])
    # a model of conv layers (a gated short convolution, its tail a slot's whole
    # state): the mixer under its own scopes, prefill and decode; its attention
    # layers keep `attention`, its leading dense layers `ffn`, the rest `moe_ffn`
    conv = MODEL_PRESETS["tiny-lfm2-test"]
    conv_params = T.init_params(conv, jax.random.PRNGKey(8))

    def conv_admit(params, pool):
        kv, rec = T.split_rec(pool)
        return T.prefill(
            params, tokens, lengths, T.join_rec(T.make_kv_cache(conv, b, 32), rec), conv,
            rec_rows=jnp.arange(b),
        )

    def conv_decode(params, pool):
        return E._paged_decode_chunk(
            params, tokens[:, 0], lengths, pool, table, key, ones, zeros, ones, 2, conv, page,
        )

    conv_pool = T.make_page_pool(conv, 8, page, state_rows=b)
    texts["conv"] = lowered_scopes(conv_admit, conv_params, conv_pool) | lowered_scopes(
        conv_decode, conv_params, conv_pool
    )
    short = {s for s in T.SCOPES if s.startswith("short_conv")}
    assert len(short) == 4 and short <= texts["conv"]
    assert {"attention", "ffn", "moe_ffn", "moe_ffn.route", "kv_pool.write"} <= texts["conv"]
    assert not short & (texts["dense"] | texts["moe"] | texts["hybrid"])
    assert not linear & texts["conv"]
    # a model of window and full layers with an expert layer that holds a
    # share: each kind's attention under its own scope, the shared experts'
    window = MODEL_PRESETS["tiny-window-moe-test"]
    window_params = T.init_params(window, jax.random.PRNGKey(3))
    both = jnp.stack([table, table])

    def window_segment(params, pool):
        return T.paged_prefill_segment_inplace(
            params, tokens[:1], zeros[:1], lengths[:1], pool, both[:, :1], window, page
        )

    def window_decode(params, pool):
        return E._paged_decode_chunk(
            params, tokens[:, 0], lengths, pool, both, key, ones, zeros, ones, 2, window, page,
        )

    window_pool = T.make_page_pool(window, 8, page, window_pages=8)
    texts["window"] = lowered_scopes(window_segment, window_params, window_pool) | (
        lowered_scopes(window_decode, window_params, window_pool)
    )
    kinds = {"attention.window", "attention.full", "moe_ffn.shared"}
    assert kinds | {"attention", "moe_ffn", "moe_ffn.route", "moe_ffn.dispatch",
                    "moe_ffn.experts", "moe_ffn.combine", "kv_pool.write"} <= texts["window"]
    assert not kinds & (texts["dense"] | texts["moe"] | texts["hybrid"])
    # a model that fills blocks: the choice by confidence under its own scope
    # beside the head's, its no-drop expert layer under the sequential
    # block's `moe_ffn`
    blocks = MODEL_PRESETS["tiny-blockfill-moe-test"]
    block_state = {"tokens": jnp.zeros((b, 4), jnp.int32), "open": jnp.ones((b, 4), jnp.bool_),
                   "step": zeros}

    def block_chunk(params, pool):
        return E._paged_block_chunk(
            params, block_state, lengths, pool, table, key, ones, zeros, ones, 2, blocks, page,
        )

    texts["blocks"] = lowered_scopes(
        block_chunk, T.init_params(blocks, jax.random.PRNGKey(4)), T.make_page_pool(blocks, 8, page)
    )
    assert {"block_choice", "head", "attention", "kv_pool.write", "moe_ffn", "moe_ffn.route",
            "moe_ffn.dispatch", "moe_ffn.experts", "moe_ffn.combine"} <= texts["blocks"]
    assert "block_choice" not in texts["dense"] | texts["moe"] | texts["hybrid"] | texts["window"]
    # a model whose attention reads a learned selection: the indexer, the
    # ranking and the selected read under their own scopes inside
    # `attention`, in the decode chunk and in a segment past its top-k
    sparse = MODEL_PRESETS["tiny-sparse-moe-test"]

    def sparse_programs(params, pool):
        chunk = E._paged_decode_chunk(
            params, tokens[:, 0], lengths, pool, table, key, ones, zeros, ones, 2, sparse, page,
        )
        return chunk, E._paged_segment_and_sample(
            params, tokens[:1, :16], lengths[:1], lengths[:1], chunk[3], table[:1], key,
            ones[:1], zeros[:1], ones[:1], sparse, page,
        )

    texts["sparse"] = lowered_scopes(
        sparse_programs, T.init_params(sparse, jax.random.PRNGKey(5)),
        T.make_page_pool(sparse, 8, page),
    )
    selection = {"attention.index", "attention.index.scores", "attention.select", "attention.sparse"}
    assert selection | {"attention", "kv_pool.write", "moe_ffn", "head"} <= texts["sparse"]
    assert not selection & (
        texts["dense"] | texts["moe"] | texts["hybrid"] | texts["window"] | texts["blocks"]
    )
    # a model that keeps a latent in place of K and V: the projections, the
    # absorb and W_uv under `attention.latent` (a decode chunk), a segment's
    # re-expansion under `attention.latent.expand`, the selection's scopes as
    # they are, the leading dense layer's `ffn` beside the expert layers'
    latent = MODEL_PRESETS["tiny-latent-moe-test"]

    def latent_programs(params, pool):
        chunk = E._paged_decode_chunk(
            params, tokens[:, 0], lengths, pool, table, key, ones, zeros, ones, 2, latent, page,
        )
        return chunk, E._paged_segment_and_sample(
            params, tokens[:1, :16], lengths[:1], lengths[:1], chunk[3], table[:1], key,
            ones[:1], zeros[:1], ones[:1], latent, page,
        )

    texts["latent"] = lowered_scopes(
        latent_programs, T.init_params(latent, jax.random.PRNGKey(6)),
        T.make_page_pool(latent, 8, page),
    )
    kept = {"attention.latent", "attention.latent.expand"}
    assert kept | selection | {"attention", "kv_pool.write", "ffn", "moe_ffn", "moe_ffn.shared",
                               "head"} <= texts["latent"]
    assert not kept & (
        texts["dense"] | texts["moe"] | texts["hybrid"] | texts["window"] | texts["blocks"]
        | texts["sparse"]
    )
    # a latent with NO indexer: the dense read under its own scope, in the
    # decode chunk and in a segment, and nothing of a selection anywhere
    dense_latent = MODEL_PRESETS["tiny-latent-dense-moe-test"]

    def dense_latent_programs(params, pool):
        chunk = E._paged_decode_chunk(
            params, tokens[:, 0], lengths, pool, table, key, ones, zeros, ones, 2, dense_latent,
            page,
        )
        return chunk, E._paged_segment_and_sample(
            params, tokens[:1, :16], lengths[:1], lengths[:1], chunk[3], table[:1], key,
            ones[:1], zeros[:1], ones[:1], dense_latent, page,
        )

    others = set().union(*texts.values())
    texts["dense-latent"] = lowered_scopes(
        dense_latent_programs, T.init_params(dense_latent, jax.random.PRNGKey(7)),
        T.make_page_pool(dense_latent, 8, page),
    )
    assert kept | {"attention.latent.read", "attention", "kv_pool.write", "ffn", "moe_ffn",
                   "moe_ffn.shared", "head"} <= texts["dense-latent"]
    assert not selection & texts["dense-latent"]
    assert "attention.latent.read" not in others
    # a model whose KINDS of layer keep different latents: the window kind's
    # read under its own scope in the decode chunk and in a segment, the
    # heads' gate under its own, the full kind's selection as it is
    kinds_latent = MODEL_PRESETS["tiny-dots3-test"]

    def kinds_programs(params, pool):
        chunk = E._paged_decode_chunk(
            params, tokens[:, 0], lengths, pool, both, key, ones, zeros, ones, 2, kinds_latent,
            page,
        )
        return chunk, E._paged_segment_and_sample(
            params, tokens[:1, :16], lengths[:1], lengths[:1], chunk[3], both[:, :1], key,
            ones[:1], zeros[:1], ones[:1], kinds_latent, page,
        )

    others = set().union(*texts.values())
    texts["latent-kinds"] = lowered_scopes(
        kinds_programs, T.init_params(kinds_latent, jax.random.PRNGKey(8)),
        T.make_page_pool(kinds_latent, 8, page, window_pages=8),
    )
    own = {"attention.latent.window", "attention.gate"}
    assert own | kept | selection | {"attention", "kv_pool.write", "ffn", "moe_ffn",
                                     "moe_ffn.shared", "head"} <= texts["latent-kinds"]
    assert not own & others
    assert set(T.SCOPES) <= set().union(*texts.values())
    assert "ffn" in texts["dense"] and "moe_ffn" not in texts["dense"]
    assert {"moe_ffn", "moe_ffn.route", "moe_ffn.dispatch", "moe_ffn.experts",
            "moe_ffn.combine"} <= texts["moe"] and "ffn" not in texts["moe"]
    decode_only = lowered_scopes(decode, moe_params, T.make_page_pool(MOE, 8, page))
    assert {"embed", "attention", "moe_ffn", "kv_pool.write", "head",
            "sample"} <= decode_only
    # the scan reads the pool where it lies: nothing is sliced out
    assert "kv_pool.read" not in decode_only and "kv_pool.read" not in T.SCOPES
    # and the new rows' scatter, the pool's only write, is not under `attention`
    text = jax.jit(decode).lower(moe_params, T.make_page_pool(MOE, 8, page)).as_text(
        debug_info=True
    )
    writes = [loc for loc in re.findall(r'loc\("([^"]+)"', text) if "kv_pool.write" in loc]
    assert writes and not any("attention" in loc.split("/") for loc in writes)


def test_hot_loop_cost_of_dispatch_spans_and_annotations(dense_params):
    """What the tracing adds per dispatch (the record with the launch's
    stamp, the unfed account's stretch, the landing with its stages, one
    span, the late probe of an iteration's first launch) and per iteration
    (seven phase annotations, four of them with the loop's state, the look
    at what waits for an admission, the count by reason, the iteration
    span), each best-of-N, against a decode step: a chunk of `decode_chunk`
    steps pays them once. Same 1% contract as the per-token instrumentation
    (test_observability.py), against the same worst case:
    tiny-test's CPU step. A wake-up of the launch wait (the look at what
    waits, the deadline, what landed, the sweep's duties) is held to 1% of
    the shortest time between two of them, an interpreter switch interval:
    the thread waits only behind a chunk ten times that long."""
    import sys

    engine = ServingEngine(
        DENSE, dense_params, max_batch=4, max_seq_len=256, decode_chunk=8,
    )
    engine.start()
    try:
        reqs = [
            engine.submit(GenerationRequest(
                prompt_tokens=[3 + i] * 24, options=GenerationOptions(max_new_tokens=96),
            ))
            for i in range(4)
        ]
        for r in reqs:
            r.result(timeout=300)
        stats = engine.stats()
        step_s = stats["decode-step-ms"] / 1e3 or stats["histograms"]["engine_decode_step_s"]["p50"]
        assert step_s > 0
        live = [s for s in engine._slots]
        handle = E._Fetch(None, engine._fetcher)
        handle.ready_at = time.monotonic()
        # the unfed account's worst case: every launch finds the device
        # unfed and four requests open, so each closes a stretch over them
        engine._open = {
            i: GenerationRequest(prompt_tokens=[1], options=GenerationOptions())
            for i in range(4)
        }
        pending = deque([[()], [()]])
        # a chunk in flight whose result is not on the host yet
        in_flight = deque([[("chunk", E._Fetch(None, engine._fetcher), [], 8, 0.0, True, True, None)]])
        per_dispatch = per_iteration = per_wake = float("inf")
        for _ in range(5):
            n = 2_000
            t0 = time.perf_counter()
            for _ in range(n):
                engine._last_fetch, engine._launch_unfetched = handle, False
                engine._late_probe = True
                disp = engine._new_dispatch(
                    "engine.decode_chunk", program="_paged_decode_chunk", steps=8,
                    active_rows=4, row_steps=32,
                    **engine._reads.decode(engine._live_lengths(live), 8)[0],
                    clean=True, pipelined=True,
                )
                with jax.profiler.TraceAnnotation(
                    "engine.decode_chunk", seq=1, steps=8, t_mono_ns=E._mono_ns(disp)
                ):
                    pass
                handle.counts = engine._moe_counts()
                engine._land_dispatch(disp, handle)
                disp.attrs["tokens_delivered"] = 32
            per_dispatch = min(per_dispatch, (time.perf_counter() - t0) / n)
            assert disp.attrs["unfed_ms"] > 0 and "unfed_with_request_ms" in disp.attrs
            t0 = time.perf_counter()
            for _ in range(n):
                state = engine._loop_state(pending)
                for name in ("engine.sweep", "engine.admit"):
                    with jax.profiler.TraceAnnotation(name, **state):
                        pass
                with jax.profiler.TraceAnnotation(
                    "engine.dispatch", **engine._loop_state(pending, 1)
                ):
                    pass
                for name in ("engine.grace", "engine.process.wait", "engine.process.deliver"):
                    with jax.profiler.TraceAnnotation(name):
                        pass
                with jax.profiler.TraceAnnotation("engine.await", **state):
                    engine._admission_waits()
                engine._launches["deadline"] += 1
                engine._launches["late"] += engine._launched_late
                E.emit_dispatch_span("engine.iteration", 0.0, 1.0, {})
            per_iteration = min(per_iteration, (time.perf_counter() - t0) / n)
            t0 = time.perf_counter()
            for _ in range(n):
                engine._admission_waits()
                engine._launch_deadline(in_flight, 0.001)
                engine._wake.clear()
                engine._take_landed(in_flight)
                engine._sweep_duties()
            per_wake = min(per_wake, (time.perf_counter() - t0) / n)
        engine._open = {}
    finally:
        engine.stop()
    assert len(in_flight) == 1  # nothing had landed: nothing was taken
    per_step = (per_dispatch + per_iteration) / engine.decode_chunk
    assert per_wake / sys.getswitchinterval() <= 0.01, (
        f"a wake-up of the launch wait costs {per_wake * 1e6:.2f}us"
    )
    assert per_step / step_s <= 0.01, (
        f"dispatch spans and annotations cost {per_step * 1e6:.2f}us a step, "
        f"{per_step / step_s * 100:.2f}% of the {step_s * 1e3:.3f}ms decode step"
    )

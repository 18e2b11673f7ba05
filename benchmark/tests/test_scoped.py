"""The readers of the program's dispatch spans and the join of those spans to
the device's executions: `span_ratio` on hand-made spans, the matching rule on synthetic
intervals with a partial execution at each edge, the paged decode cost by
hand, and `scoped.load` / `trace_scope` on a small trace recorded on a v5e
(`data/tpu_scoped.xplane.pb`, written by the probe its docstring names: the
CPU backend's trace has no "XLA Modules" line and no scope metadata, so a CPU
recording could not exercise either)."""

from pathlib import Path

import pytest

from readers import span_ratio, trace_scope
from reduce import paged_decode_cost, scoped
from reduce.xplane_meta import op_scopes

TRACE = Path(__file__).parent / "data" / "tpu_scoped.xplane.pb"
STEPS = {1: 4, 2: 4, 3: 4, 4: 8, 5: 4, 6: 4}  # the probe's dispatches, by seq


def span(name, ms=1.0, **attributes):
    return {"name": name, "durationMs": ms, "attributes": attributes}


def test_span_ratio_sums_attributes_over_the_named_spans():
    spans = [
        span("engine.admit_group", real_tokens=400, computed_tokens=8192),
        span("engine.admit_group", real_tokens=600, computed_tokens=8192),
        span("engine.decode_chunk", real_tokens=10**6, computed_tokens=1),
        span("engine.admit_group"),  # an older program's span: no counts
    ]
    share = {"span": "engine.admit_group", "numerator": "real_tokens",
             "denominator": "computed_tokens", "scale": 100}
    assert span_ratio.read(share, {"spans": spans}) == pytest.approx(100 * 1000 / 16384)
    assert span_ratio.read(share, {"spans": spans[2:3]}) is None
    dropped = {"span": ["engine.admit_group", "engine.decode_chunk"],
               "numerator": "moe_dropped", "denominator": "moe_routed", "scale": 100}
    moe = [span("engine.admit_group", moe_dropped=30, moe_routed=100),
           span("engine.decode_chunk", moe_dropped=0, moe_routed=900)]
    assert span_ratio.read(dropped, {"spans": moe}) == pytest.approx(3.0)
    dense = [span("engine.decode_chunk", moe_dropped=0, moe_routed=0)]
    assert span_ratio.read(dropped, {"spans": dense}) is None  # nothing routed


def test_span_ratio_gives_device_time_per_step_over_every_chunk():
    # three chunks of the window, one of them behind a prefill group: its
    # device_ms is its own time on the stream, not its wait
    chunks = [span("engine.decode_chunk", ms=v, device_ms=d, steps=n)
              for v, d, n in ((760.0, 760.0, 16), (1200.0, 770.0, 16), (390.0, 385.0, 8))]
    step = {"span": "engine.decode_chunk", "numerator": "device_ms", "denominator": "steps"}
    assert span_ratio.read(step, {"spans": chunks}) == pytest.approx(1915.0 / 40)
    assert span_ratio.read(step, {"spans": []}) is None


def runs(*intervals):
    return [{"start": s, "end": e, "ops": {}} for s, e in intervals]


def waits(*intervals):
    return [{"start": s, "end": e, "seq": i} for i, (s, e) in enumerate(intervals, 10)]


MS = 1_000_000


def test_matching_drops_the_partial_execution_at_each_edge():
    """Five executions of 100 ms back to back. The first was being waited
    for when the profile began (its fetch is not in the trace); the last is
    whole but its fetch had not returned when the profile stopped. The
    device runs behind the host: execution 2 starts after launch 3 went out,
    which is why launches cannot decide the pairing."""
    executions = runs(*[(i * 100 * MS, (i + 1) * 100 * MS) for i in range(5)])
    fetches = waits((100 * MS, 200 * MS + 50_000), (200 * MS + 60_000, 300 * MS + 40_000),
                    (300 * MS + 50_000, 400 * MS + 70_000))
    pairs = scoped.match(fetches, executions)
    assert [(f["seq"], e["start"] // (100 * MS)) for f, e in pairs] == [(10, 1), (11, 2), (12, 3)]


def test_matching_survives_the_clocks_skew_and_an_idle_device():
    # the device's clock runs 0.5 ms ahead: an execution "ends" after its
    # fetch returned, by less than the skew allowed
    executions = runs((0, 10 * MS), (50 * MS, 60 * MS + 500_000))
    fetches = waits((1 * MS, 10 * MS + 100_000), (51 * MS, 60 * MS))
    assert [e["start"] for _, e in scoped.match(fetches, executions)] == [0, 50 * MS]
    # a fetch with no execution at or before its end pairs with nothing, and
    # an execution is never taken twice
    assert scoped.match(waits((0, 5 * MS)), runs((20 * MS, 30 * MS))) == []
    twice = scoped.match(waits((0, 11 * MS), (11 * MS, 12 * MS)), runs((0, 10 * MS)))
    assert len(twice) == 1


def test_paged_decode_cost_by_hand():
    # 2 active rows of lengths 100 and 300 for one step, 64 rows in the call,
    # 32 layers, 8 KV heads x 128, 32 query heads, bf16 everywhere
    cost = paged_decode_cost.paged_decode_attention(
        kv_tokens_read=400, steps=1, layers=32, rows=64, n_heads=32, n_kv_heads=8,
        head_dim=128,
    )
    k_and_v = 400 * 2 * 8 * 128 * 2
    q_and_out = 2 * 64 * 32 * 128 * 2
    assert cost["bytes"] == 32 * (k_and_v + q_and_out)
    assert cost["ops"] == 32 * 4 * 400 * 32 * 128
    int8 = paged_decode_cost.paged_decode_attention(
        kv_tokens_read=400, steps=1, layers=32, rows=64, n_heads=32, n_kv_heads=8,
        head_dim=128, kv_bytes_per_elem=1,
    )
    assert int8["bytes"] == 32 * (k_and_v // 2 + q_and_out)


# ---------------------------------------------------------------------------
# the recorded trace: `dev`-style probe on one v5e, six dispatches of
# `probe_chunk` (a layer scan inside a step scan, every scope of a decode
# program and a named Pallas kernel), launched one ahead of the fetch as the
# engine does; the profile starts with dispatch 1 in flight and stops with
# dispatch 6 running
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    return scoped.load(TRACE)


def probe_ctx(seqs=STEPS):
    spans = [span("engine.decode_chunk", seq=q, steps=STEPS[q], kv_tokens_read=1000 * STEPS[q])
             for q in seqs]
    return {"spans": spans, "trace_dir": TRACE, trace_scope.CACHE: scoped.load(TRACE),
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_scope_paths_come_from_the_events_metadata():
    scopes = op_scopes(TRACE, "/device:TPU:")
    kernel = [path for name, path in scopes.items() if name.startswith("%probe_kernel")]
    assert kernel and all(
        path.endswith("attention/kernel/probe_kernel/pallas_call:") for path in kernel
    )
    components = {part for path in scopes.values() for part in path.rstrip(":").split("/")}
    assert {"attention", "ffn", "kv_pool.write", "head", "sample"} <= components
    # a fusion counts under its ROOT's scope: here the compiler fused the
    # `kv_pool.read` slice into the attention's first matmul, so no device
    # operation is left under that name
    assert "kv_pool.read" not in components
    assert op_scopes(TRACE, "/device:GPU:") == {}


def test_the_host_plane_holds_the_engine_annotations(recorded):
    notes = recorded["annotations"]
    assert {"engine.dispatch", "engine.decode_chunk", "engine.process.wait",
            "engine.fetch"} <= set(notes)
    assert [int(a["seq"]) for a in notes["engine.decode_chunk"]] == [2, 3, 4, 5, 6]
    assert [int(a["seq"]) for a in notes["engine.fetch"]] == [1, 2, 3, 4, 5]
    assert [int(a["steps"]) for a in notes["engine.decode_chunk"]] == [4, 4, 8, 4, 4]


def test_whole_executions_pair_with_their_dispatch(recorded):
    """The trace holds five executions: dispatch 2's is its first event and
    dispatch 6's was cut after 1.5 of its 10.7 ms, so both touch an edge and
    go; dispatch 1 ran before the profile began, so its fetch finds nothing.
    Dispatch 4 ran eight steps and took twice as long."""
    executions = recorded["executions"]["jit_probe_chunk"]
    pairs = scoped.match(recorded["annotations"]["engine.fetch"], executions)
    assert [int(f["seq"]) for f, _ in pairs] == [3, 4, 5]
    took = [(e["end"] - e["start"]) / 1e6 for _, e in pairs]
    assert took[0] == pytest.approx(took[2], rel=0.01)
    assert took[1] == pytest.approx(2 * took[0], rel=0.02)
    for _, e in pairs:
        own = sum(seconds for seconds, _ in e["ops"].values())
        assert own == pytest.approx((e["end"] - e["start"]) / 1e9, rel=0.01)


def test_trace_scope_reads_scope_time_over_a_span_attribute():
    base = {"program": "probe_chunk", "span": "engine.decode_chunk", "scale": 1000,
            "per": "steps"}
    ctx = probe_ctx()
    pairs = trace_scope.pairs_of(base, ctx)
    step = sum(e["end"] - e["start"] for _, e in pairs) / 1e6 / 16  # 16 steps in 3 executions
    assert step == pytest.approx(10.698 / 4, rel=0.01)
    parts = {
        name: trace_scope.read({**base, "scopes": scopes}, ctx)
        for name, scopes in (("copy", ["kv_pool.write"]),
                             ("attention", ["attention"]), ("ffn", ["ffn", "moe_ffn"]),
                             ("head", ["head", "sample"]))
    }
    assert all(v > 0 for v in parts.values())
    assert sum(parts.values()) == pytest.approx(step, rel=0.05)  # the rest: copies, the scans
    kernel = trace_scope.read({**base, "scopes": ["probe_kernel"]}, ctx)
    assert 0 < kernel < parts["attention"]  # the kernel sits inside `attention`
    assert trace_scope.read({**base, "scopes": ["no_such_scope"]}, ctx) is None
    # over another work count of the same spans: per thousand KV tokens read
    # (the probe's spans say 1000 a step), so the same number
    per_k = trace_scope.read(
        {**base, "scopes": ["ffn"], "per": "kv_tokens_read", "scale": 1e6}, ctx
    )
    assert per_k == pytest.approx(parts["ffn"])
    # only the dispatches the window has spans for are read: dispatch 4 ran
    # eight steps in twice the time, so a step's share is the same
    only_4 = trace_scope.read({**base, "scopes": ["ffn"]}, probe_ctx(seqs=[4]))
    assert only_4 == pytest.approx(parts["ffn"], rel=0.02)


def test_trace_scope_finds_nothing_without_spans_annotations_or_a_trace():
    base = {"program": "probe_chunk", "span": "engine.decode_chunk", "per": "steps",
            "scopes": ["attention"]}
    assert trace_scope.read(base, probe_ctx()) > 0
    assert trace_scope.read(base, {**probe_ctx(), "spans": []}) is None
    assert trace_scope.read({**base, "program": "no_such"}, probe_ctx()) is None
    assert trace_scope.read(base, {"spans": [], "trace_dir": None}) is None
    bare = dict(probe_ctx())
    bare[trace_scope.CACHE] = {**bare[trace_scope.CACHE], "annotations": {}}  # the parent
    assert trace_scope.read(base, bare) is None


def test_a_kernel_roofline_share_from_the_spans_work_count():
    """The probe's kernel doubles a bf16[1024,4096] tile; priced as if it
    were the paged decode kernel, the share has to be what the cost function
    and the kernel's own time give."""
    definition = {
        "program": "probe_chunk", "span": "engine.decode_chunk", "scopes": ["probe_kernel"],
        "roofline": {
            "cost": "paged_decode_cost.paged_decode_attention", "span_attr": "kv_tokens_read",
            "shape": r"= bf16\[(?P<rows>\d+),(?P<head_dim>\d+)\]\S* custom-call\(",
            "sizes": {"n_heads": 1, "n_kv_heads": 1},
        },
    }
    ctx = probe_ctx()
    share = trace_scope.read(definition, ctx)
    pairs = trace_scope.pairs_of(definition, ctx)
    least = took = 0.0
    for s, e in pairs:
        steps = s["attributes"]["steps"]
        seconds, calls = scoped.scope_seconds(e, ctx[trace_scope.CACHE]["scope_of"], ["probe_kernel"])
        cost = paged_decode_cost.paged_decode_attention(
            1000 * steps, steps=steps, layers=calls / steps, rows=1024, n_heads=1,
            n_kv_heads=1, head_dim=4096,
        )
        least += max(cost["bytes"] / 819e9, cost["ops"] / 197e12)
        took += seconds
    assert calls / steps == 4  # one call a layer
    assert share == pytest.approx(100 * least / took)

"""The clock between a profile and the program's spans, and the two readers
that say what a first token waited for and what the engine held while the
device idled: on hand-made spans and gaps, and on one profile of a small
engine recorded here on the CPU (as `record_scoped_trace.py` records the
chip's)."""

import dataclasses
import time

import pytest

from readers import request_stage_mean, trace_idle_by_request
from reduce import clock
from reduce.xplane import find_trace, reduce_trace

CPU_PLANES = {"device_plane": "/host:CPU", "ops_line": "tf_XLAPjRtCpuClient"}
US = 1_000
PAUSES = 8  # of the recorded profile


def request(start, end, **states):
    return {"start": start, "end": end,
            "states": [(f"engine.{k}", a, b) for k, (a, b) in states.items()]}


def test_idle_is_everything_outside_the_busy_intervals_of_the_window():
    idle = trace_idle_by_request.idle_intervals(0, 50, [(10, 20), (30, 40)])
    assert idle == [(0, 10), (20, 30), (40, 50)]
    assert trace_idle_by_request.idle_intervals(0, 50, [(0, 50)]) == []
    # the stretch before the first and after the last operation is idle too
    assert trace_idle_by_request.idle_intervals(5, 9, []) == [(5, 9)]


def test_a_gap_half_inside_a_request_is_split_at_the_request_s_edge():
    r = request(50 * US, 200 * US, queued=(50 * US, 80 * US), prefill=(80 * US, 200 * US))
    out = trace_idle_by_request.split([(0, 100 * US), (300 * US, 310 * US)], [r])
    assert out == {
        "short": 10 * US, "no_request": 50 * US, "with_request": 50 * US,
        "with_request.queued": 30 * US, "with_request.prefill": 20 * US,
    }
    # the classes add up to the idle they were given
    assert out["short"] + out["no_request"] + out["with_request"] == 110 * US


def test_the_oldest_open_request_names_the_state():
    old = request(0, 100 * US, decode=(0, 100 * US))
    young = request(20 * US, 300 * US, queued=(20 * US, 300 * US))
    out = trace_idle_by_request.split([(10 * US, 150 * US)], [young, old])
    assert out["no_request"] == 0 and out["with_request"] == 140 * US
    assert out["with_request.decode"] == 90 * US and out["with_request.queued"] == 50 * US


def launch(seq, t_mono_ns, k_ns, late_ns=0, name="engine.decode_chunk"):
    return {"name": name, "seq": seq, "t_mono_ns": t_mono_ns, "start": t_mono_ns + k_ns + late_ns}


def dispatch_span(seq, t_mono_ns, offset_s, name="engine.decode_chunk", **attributes):
    return {"name": name, "start": t_mono_ns / 1e9 + offset_s, "durationMs": 1.0,
            "attributes": {"seq": seq, **attributes}}


def test_the_clock_is_the_launches_median_and_says_how_far_they_lie_from_it():
    k, offset = 7_000_000_000, 1.7e9
    launches = [launch(i, 10**12 + i * 10**8, k, late) for i, late in enumerate((0, 4_000, 10_000), 1)]
    spans = [dispatch_span(i, 10**12 + i * 10**8, offset) for i in (1, 2, 3)]
    fit = clock.fit(launches, spans)
    assert fit["k_ns"] == k + 4_000 and fit["residual_ns"] == 4_000 and fit["launches"] == 3
    assert fit["offset_s"] == pytest.approx(offset, abs=1e-6)
    # a span's wall start lands where its launch lies on the profile, to the residual
    assert clock.to_profile_ns(fit, spans[1]["start"]) == pytest.approx(launches[1]["start"], abs=1_000)


def test_a_profile_without_t_mono_ns_reads_as_nothing_not_as_zero(tmp_path):
    launches = [launch(1, 0, 0), launch(2, 0, 0)]  # the parent's annotations: seq and steps only
    assert clock.fit(launches, [dispatch_span(1, 10**12, 1.7e9)]) is None
    # stamped launches, but no span joins them
    assert clock.fit([launch(1, 10**12, 5)], []) is None
    ctx = {"trace_dir": tmp_path, "spans": []}  # and no profile at all
    assert trace_idle_by_request.read({"class": "no_request"}, ctx) is None
    assert trace_idle_by_request.read({"class": "no_request"}, {"trace_dir": None}) is None


def test_the_engine_s_account_is_clipped_to_the_profile_s_seconds():
    fit = {"k_ns": 0, "offset_s": 0.0}
    spans = [
        dispatch_span(1, 1_000_000_000, 0.0, unfed_ms=400.0, unfed_with_request_ms=100.0),
        dispatch_span(2, 2_000_000_000, 0.0),  # found work in flight: no stretch
        dispatch_span(3, 3_000_000_000, 0.0, unfed_ms=10.0, unfed_with_request_ms=10.0),
    ]
    # the window opens half way through the first stretch and holds the third
    got = trace_idle_by_request.account_in_window(fit, spans, 800_000_000, 4_000_000_000)
    assert got == pytest.approx(50_000_000 + 10_000_000)


def spans_of(trace_id, queued_ms, prefill_ms, **stages):
    return [
        {"name": "engine.queued", "traceId": trace_id, "durationMs": queued_ms, "attributes": {}},
        {"name": "engine.prefill", "traceId": trace_id, "durationMs": prefill_ms,
         "attributes": {f"{k}_ms": v for k, v in stages.items()}},
    ]


def test_the_stages_of_two_requests_average_as_means_and_add_up():
    ctx = {
        "spans": spans_of("a", 10.0, 50.0, launch=1.0, behind=0.0, device=45.0, land=4.0)
        + spans_of("b", 100.0, 150.0, launch=3.0, behind=100.0, device=46.0, land=1.0),
        "requests": [
            {"id": "a", "due": 0.0, "t_first": 0.066},
            {"id": "b", "due": 1.0, "t_first": 1.258},
            {"id": "c", "due": 2.0, "t_first": None},  # no first chunk: not in the mean
        ],
    }
    read = lambda stage: request_stage_mean.read({"stage": stage}, ctx)  # noqa: E731
    assert [read(s) for s in ("queued", "launch", "behind", "device", "land")] == [55.0, 2.0, 50.0, 45.5, 2.5]
    assert read("outside") == pytest.approx(7.0)  # (66 - 60 + 258 - 250) / 2
    ttft_mean = (66.0 + 258.0) / 2
    assert sum(read(s) for s in ("queued", "launch", "behind", "device", "land", "outside")) == (
        pytest.approx(ttft_mean)
    )
    # the parent's spans carry no stage: what is not there reads as nothing
    parent = {**ctx, "spans": spans_of("a", 10.0, 50.0) + spans_of("b", 100.0, 150.0)}
    assert request_stage_mean.read({"stage": "behind"}, parent) is None
    assert request_stage_mean.read({"stage": "queued"}, parent) == 55.0


def test_a_recorded_profile_names_engine_idle_and_fits_the_clock(tmp_path):
    """A small engine profiled on the CPU over a few requests and the pauses
    between them: the reduction names the pauses `engine.idle`, not the phase
    that launches work; the launches' stamps fit the clock to well under a
    millisecond; and the idle classes add up to the reduction's own idle."""
    import jax

    from langstream_tpu.models import transformer as T
    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.serving.engine import ServingEngine
    from langstream_tpu.tracing import TRACER

    config = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
    engine = ServingEngine(
        config, T.init_params(config, jax.random.PRNGKey(0)), max_batch=2, max_seq_len=64,
        decode_chunk=4, prefill_buckets=(16,),
    )
    engine.start()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    try:
        engine.generate([3, 4, 5], GenerationOptions(max_new_tokens=8), timeout=300)  # compiles
        TRACER.clear()
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for i in range(PAUSES):
                time.sleep(0.04)
                engine.generate([6 + i] * 5, GenerationOptions(max_new_tokens=8), timeout=300)
            time.sleep(0.04)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.stop()
    path = find_trace(tmp_path)
    reduced = reduce_trace(path, **CPU_PLANES)
    # a gap is named by ONE instant, its middle, and the idle loop spends a
    # few percent of a turn outside its sleep: most of the pauses, not each
    gaps = dict(reduced["idle_gaps"])  # seconds, a mean over the two lines of operations
    phases = sum(gaps.get(f"engine.{p}", 0) for p in ("sweep", "admit", "dispatch"))
    # (the phases are held against the pauses' name, not against a time of
    # their own: on a loaded host `engine.admit` alone has read 0.118 s)
    assert gaps["engine.idle"] > max(0.04 * PAUSES / 2 / 2, phases), reduced["idle_gaps"]
    spans = TRACER.spans(4096)
    trace = clock.load(path, **CPU_PLANES)
    fit = clock.fit(trace["launches"], spans)
    assert fit["launches"] >= 2 * PAUSES and fit["residual_ns"] < 1_000_000
    # each launch annotation starts just after its span's start, on one clock
    by_seq = {(s["name"], s["attributes"].get("seq")): s for s in spans}
    for a in trace["launches"]:
        start = clock.to_profile_ns(fit, by_seq[(a["name"], a["seq"])]["start"])
        assert abs(a["start"] - start) < 1_000_000
    ctx = {"trace_dir": tmp_path, "spans": spans, "trace_planes": CPU_PLANES}
    shares = trace_idle_by_request.shares(ctx)
    idle = 100.0 * (1 - reduced["busy_s"] / reduced["window_s"])
    assert shares["short"] + shares["no_request"] + shares["with_request"] == pytest.approx(idle, abs=0.5)
    # the pauses with no request in the engine, and the requests between them
    assert shares["no_request"] > 30 and shares["with_request"] > 0
    assert trace_idle_by_request.read({"class": "with_request"}, ctx) == shares["with_request"]

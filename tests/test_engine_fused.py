"""Fused (overlapped) prefill–decode scheduling tests: token-budgeted
prefill slices riding every engine iteration back-to-back with the decode
chunk — exactness vs the serialized path, one-iteration admission latency,
and the no-mid-traffic-compiles guarantee via the compiled_programs stat."""

import dataclasses
import gc
from collections import deque

import jax
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine, admit_rungs
from langstream_tpu.tracing import TRACER

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
MOE_CFG = dataclasses.replace(MODEL_PRESETS["tiny-moe-test"], dtype="float32")
MOE_PARAMS = init_params(MOE_CFG, jax.random.PRNGKey(1))


def make_engine(start=True, **kw):
    engine = ServingEngine(CFG, PARAMS, **kw)
    if start:
        engine.start()
    return engine


def test_mixed_prefill_decode_matches_serialized_reference():
    """Greedy tokens from a fused mixed load (active decode + two long
    prompts chunk-prefilling concurrently + short admissions) are identical
    to each request served ALONE on a one-slot engine, where nothing can
    ride beside it — the fused iterations change scheduling, never math."""
    opts = GenerationOptions(max_new_tokens=16, temperature=0.0)
    short_prompt = [5, 6, 7]
    long_a = [(3 + i) % CFG.vocab_size for i in range(70)]  # 5 segments @16
    long_b = [(11 + 2 * i) % CFG.vocab_size for i in range(55)]  # 4 segments

    ref = {}
    serial = make_engine(
        max_batch=1, max_seq_len=128, decode_chunk=4, prefill_buckets=(16,),
    )
    try:
        for name, prompt in (("s", short_prompt), ("a", long_a), ("b", long_b)):
            ref[name] = serial.generate(prompt, opts, timeout=120).tokens
    finally:
        serial.stop()

    fused = make_engine(
        max_batch=4, max_seq_len=128, decode_chunk=4, prefill_buckets=(16,),
        max_prefill_streams=2, prefill_token_budget=32,
    )
    try:
        short_req = fused.submit(
            GenerationRequest(prompt_tokens=short_prompt, options=opts)
        )
        ra = fused.submit(GenerationRequest(prompt_tokens=long_a, options=opts))
        rb = fused.submit(GenerationRequest(prompt_tokens=long_b, options=opts))
        assert short_req.result(timeout=120).tokens == ref["s"]
        assert ra.result(timeout=120).tokens == ref["a"]
        assert rb.result(timeout=120).tokens == ref["b"]
    finally:
        fused.stop()


def test_admission_rides_the_very_next_iteration_under_load():
    """With a decode chunk in flight for a saturated-busy engine, a new
    arrival's prefill must dispatch in the very next fused iteration — not
    after the running generation drains. White-box: drive _iterate by hand
    (no engine thread) so 'one iteration' is exact, not a timing guess."""
    engine = make_engine(
        start=False, max_batch=2, max_seq_len=128, decode_chunk=8,
    )
    pending: deque = deque()
    opts = GenerationOptions(max_new_tokens=60, temperature=0.0)
    engine.submit(GenerationRequest(prompt_tokens=[4, 5, 6], options=opts))
    engine._iterate(pending)  # admits A, dispatches its first chunk
    assert sum(1 for s in engine._slots if s.active) == 1

    engine.submit(GenerationRequest(prompt_tokens=[7, 8], options=opts))
    engine._iterate(pending)  # chunk in flight for A — B must still admit
    assert sum(1 for s in engine._slots if s.active) == 2, (
        "new arrival did not get its prefill within one fused iteration"
    )
    engine._stop.set()
    while pending:
        for entry in pending.popleft():
            engine._process_entry(entry)
    engine._fail_all(RuntimeError("test torn down"))


def test_prefill_token_budget_bounds_per_iteration_admission():
    """A backlog wider than the budget admits exactly one budget's worth of
    prefill per iteration (first group always rides), the rest staying
    queued — so decode chunks interleave instead of stalling behind the
    whole wave."""
    engine = make_engine(
        start=False, max_batch=8, max_seq_len=128, decode_chunk=4,
        prefill_buckets=(16,), prefill_batch=2,
        prefill_token_budget=32,
    )
    # long enough that nothing finishes within the iterations driven below
    opts = GenerationOptions(max_new_tokens=60, temperature=0.0)
    for _ in range(6):
        engine.submit(GenerationRequest(prompt_tokens=[9, 9, 9], options=opts))
    pending: deque = deque()
    engine._iterate(pending)
    # budget 32 at bucket width 16 → 2 requests this iteration, 4 queued
    assert sum(1 for s in engine._slots if s.active) == 2
    assert engine._queue.qsize() == 4
    engine._iterate(pending)
    assert sum(1 for s in engine._slots if s.active) == 4
    engine._stop.set()
    while pending:
        for entry in pending.popleft():
            engine._process_entry(entry)
    engine._fail_all(RuntimeError("test torn down"))


class FakeTime:
    """The engine module's clock, by hand: `sleep` and a FakeWake's `wait`
    are what move it; everything else is the real module's."""

    def __init__(self, t=5000.0):
        self.t = t
        self.sleeps = []

    def monotonic(self):
        return self.t

    def sleep(self, seconds):
        self.sleeps.append(seconds)
        self.t += seconds

    def __getattr__(self, name):
        import time

        return getattr(time, name)


class FakeWake:
    """The event `_await_launch` waits on. ``script`` holds (instant, what
    happens then): a wait that reaches the next instant stops there, runs it
    and returns as a signalled wait does; any other runs out its timeout."""

    def __init__(self, clock, script=()):
        self.clock = clock
        self.script = list(script)
        self.waits = []

    def wait(self, timeout=None):
        self.waits.append(timeout)
        if self.script and self.script[0][0] <= self.clock.t + timeout:
            at, happen = self.script.pop(0)
            self.clock.t = max(self.clock.t, at)
            happen()
            return True
        self.clock.t += timeout
        return False

    def set(self):
        pass

    def clear(self):
        pass


T0 = 5000.0
CHUNK_S = 0.25  # the chunk in flight, by the EMA: its tenth is 25 ms
OPTS = GenerationOptions(max_new_tokens=60, temperature=0.0)


def land(entry):
    """What the fetch thread does when a dispatch's result is on the host."""
    handle = entry[1]
    handle._value = handle.get()
    handle._event.set()


def tear_down(engine, pending):
    engine._stop.set()
    while pending:
        for entry in pending.popleft():
            engine._process_entry(entry)
    engine._fail_all(RuntimeError("test torn down"))


def decoding_engine(monkeypatch, chunk_s=CHUNK_S, **kw):
    """One request admitted from a cold start on a hand-driven clock: its
    group landed inline, its first chunk is in flight (launched at T0, after
    a result ready at T0), nothing is queued. The wait's deadline is
    T0 + chunk_s - chunk_s / 10."""
    from langstream_tpu.serving import engine as engine_mod

    clock = FakeTime(T0)
    monkeypatch.setattr(engine_mod, "time", clock)
    engine = make_engine(
        start=False, max_batch=4, max_seq_len=128, decode_chunk=8,
        prefill_buckets=(16,), **kw,
    )
    engine._wake = wake = FakeWake(clock)
    # a result is on the host when the test lands it, not when the CPU is done
    monkeypatch.setattr(engine, "_batch_ready", lambda batch: False)
    pending: deque = deque()
    seen = []
    first = engine.submit(GenerationRequest(
        prompt_tokens=[4, 5, 6], options=OPTS, on_token=lambda t: seen.append(clock.t),
    ))
    engine._iterate(pending)
    assert wake.waits == [] and clock.sleeps == [] and len(seen) == 1
    engine._step_time_ema_s = chunk_s / engine.decode_chunk
    return engine, clock, wake, pending, first


def launches(engine):
    return engine.stats()["launches"]


@pytest.mark.parametrize(
    "chunk_s,in_flight,slept",
    [(0.25, True, True), (0.25, False, False), (0.004, True, False), (0.0, True, False)],
    ids=["long-chunk-in-flight", "nothing-in-flight", "short-chunk-in-flight", "no-step-time-yet"],
)
def test_admission_grace_only_behind_a_long_chunk(monkeypatch, chunk_s, in_flight, slept):
    """A request queued at the top of the iteration takes the path it always
    took: before it decides the admission the engine thread pauses a tenth
    of the chunk in flight, once, and never waits on the launch event — the
    pause only while a dispatched chunk is unfetched and that tenth is worth
    an interpreter switch interval, so the device never waits for it (an
    idle engine, a cold start, a fast model and an engine that has timed no
    step yet skip it)."""
    from langstream_tpu.serving import engine as engine_mod

    engine = make_engine(
        start=False, max_batch=2, max_seq_len=128, decode_chunk=8,
    )
    engine._step_time_ema_s = chunk_s / engine.decode_chunk
    sleeps = []
    monkeypatch.setattr(engine_mod.time, "sleep", sleeps.append)
    engine._wake = wake = FakeWake(FakeTime())
    pending: deque = deque([[]] if in_flight else [])
    opts = GenerationOptions(max_new_tokens=8, temperature=0.0)
    engine.submit(GenerationRequest(prompt_tokens=[4, 5, 6], options=opts))
    engine._iterate(pending)
    assert sum(1 for s in engine._slots if s.active) == 1
    assert sleeps == ([pytest.approx(chunk_s / 10)] if slept else [])
    assert wake.waits == []
    assert launches(engine) == {"at-once": 1, "arrival": 0, "deadline": 0, "late": 0}
    tear_down(engine, pending)


def test_no_arrival_launches_the_plain_chunk_at_the_deadline_and_not_earlier(monkeypatch):
    """A chunk in flight and nothing to admit: the thread waits, a tenth of
    the chunk at a time with the sweep's duties after each, and launches the
    next chunk a tenth ahead of the expected end of the one in flight. The
    wait is no unfed stretch: something is in flight throughout."""
    engine, clock, wake, pending, _ = decoding_engine(monkeypatch)
    swept = []
    drain = engine._drain_migrations
    monkeypatch.setattr(engine, "_drain_migrations", lambda: (swept.append(clock.t), drain()))
    before = engine.stats()
    engine._iterate(pending)
    deadline = T0 + CHUNK_S - CHUNK_S / 10
    (chunk,) = pending[0]
    assert chunk[0] == "chunk" and chunk[4] == pytest.approx(deadline, abs=1e-6)
    assert chunk[4] >= deadline - 1e-9
    assert clock.sleeps == []  # no pause: nothing was admitted
    assert sum(wake.waits) == pytest.approx(CHUNK_S * 0.9, abs=1e-6)
    assert max(wake.waits) <= CHUNK_S / 10 + 1e-9
    # the top of the iteration, then once after every wait
    assert len(swept) == 1 + len(wake.waits)
    assert launches(engine) == {"at-once": 1, "arrival": 0, "deadline": 1, "late": 0}
    after = engine.stats()
    assert after["engine-loop-s"] - before["engine-loop-s"] == pytest.approx(CHUNK_S * 0.9, abs=1e-3)
    assert after["device-unfed-s"] == before["device-unfed-s"]
    assert after["device-unfed-with-request-s"] == before["device-unfed-with-request-s"]
    frame = engine._obs.flight.iterations()[-1]
    assert frame["launch"] == "deadline" and frame["late"] is False
    assert frame["phase_ms"]["await"] == pytest.approx(CHUNK_S * 900, abs=0.01)
    tear_down(engine, pending)


def test_the_deadline_follows_the_newest_step_sample_where_the_ema_runs_long(monkeypatch):
    """The EMA forgets a stale level by a tenth a sample (another occupancy,
    a slow first execution); an estimate that runs long leaves the device
    dry, one that runs short only launches as early as the loop used to. So
    the deadline takes the shorter of the EMA and its newest sample; the
    margin stays the pause's tenth of the EMA's chunk."""
    engine, clock, wake, pending, _ = decoding_engine(monkeypatch, chunk_s=2 * CHUNK_S)
    engine._last_step_s = CHUNK_S / engine.decode_chunk
    engine._iterate(pending)
    (chunk,) = pending[0]
    assert chunk[4] == pytest.approx(T0 + CHUNK_S - 2 * CHUNK_S / 10, abs=1e-6)
    assert launches(engine) == {"at-once": 1, "arrival": 0, "deadline": 1, "late": 0}
    tear_down(engine, pending)


@pytest.mark.parametrize(
    "arrives,paused",
    [(0.1, CHUNK_S / 10), (0.215, 0.01)],
    ids=["mid-chunk", "inside-the-last-pause-before-the-deadline"],
)
def test_an_arrival_during_the_wait_launches_after_the_grace_and_before_the_deadline(
    monkeypatch, arrives, paused
):
    """`submit` wakes the waiting thread: it pauses the admission's tenth of
    a chunk, but never past the deadline, then admits and launches the group
    with the next chunk behind what is left of the chunk in flight."""
    engine, clock, wake, pending, _ = decoding_engine(monkeypatch)
    second = GenerationRequest(prompt_tokens=[7, 8, 9], options=OPTS)
    wake.script = [(T0 + arrives, lambda: engine.submit(second))]
    engine._iterate(pending)
    deadline = T0 + CHUNK_S - CHUNK_S / 10
    assert clock.sleeps == [pytest.approx(paused, abs=1e-6)]
    group, chunk = pending[-1]
    assert group[0] == "prefill" and [r for _, r in group[2]] == [second]
    assert chunk[0] == "chunk" and chunk[4] == pytest.approx(T0 + arrives + paused, abs=1e-6)
    assert T0 + arrives < chunk[4] <= deadline + 1e-9
    assert sum(1 for s in engine._slots if s.active) == 2
    assert launches(engine) == {"at-once": 1, "arrival": 1, "deadline": 0, "late": 0}
    assert engine._obs.flight.iterations()[-1]["launch"] == "arrival"
    tear_down(engine, pending)


def test_a_first_token_that_lands_during_the_wait_is_delivered_before_the_next_launch(monkeypatch):
    """The trap: a batch used to be processed only after the next launch. A
    group launched on an arrival lands while the thread waits for the next
    deadline; the landing wakes it, the first token is delivered then, and
    the deadline moves to the end of the chunk behind the group."""
    engine, clock, wake, pending, _ = decoding_engine(monkeypatch)
    got = []
    second = GenerationRequest(
        prompt_tokens=[7, 8, 9], options=OPTS, on_token=lambda t: got.append(clock.t),
    )
    wake.script = [(T0 + 0.1, lambda: engine.submit(second))]
    engine._iterate(pending)  # group + chunk 2 launched at T0 + 0.125, chunk 1 processed
    assert [e[0] for e in pending[0]] == ["prefill", "chunk"] and got == []
    landed = T0 + 0.125 + 0.06
    wake.script = [(landed, lambda: land(pending[0][0]))]
    engine._iterate(pending)
    assert got[0] == pytest.approx(landed)  # delivered at the landing
    chunk = pending[-1][0]
    # chunk 2 started when the group ahead of it was ready
    assert chunk[4] == pytest.approx(landed + CHUNK_S - CHUNK_S / 10, abs=1e-6)
    assert got[0] < chunk[4]
    assert launches(engine) == {"at-once": 1, "arrival": 1, "deadline": 1, "late": 0}
    tear_down(engine, pending)


def test_a_chunk_that_lands_before_its_deadline_is_processed_and_the_launch_counts_late(monkeypatch):
    """An estimate that runs long (here by a factor of two) cannot leave the
    device idle to the deadline: the chunk's landing wakes the thread, its
    tokens are delivered, and with nothing in flight the next chunk follows
    at once, counted late (rows were live and the device had run dry)."""
    engine, clock, wake, pending, first = decoding_engine(monkeypatch, chunk_s=2 * CHUNK_S)
    wake.script = [(T0 + CHUNK_S, lambda: land(pending[0][0]))]
    engine._iterate(pending)
    (chunk,) = pending[0]
    assert chunk[4] == pytest.approx(T0 + CHUNK_S)
    slot = next(s for s in engine._slots if s.request is first)
    assert len(slot.generated) == 1 + engine.decode_chunk
    assert launches(engine) == {"at-once": 1, "arrival": 0, "deadline": 1, "late": 1}
    assert engine._obs.flight.iterations()[-1]["late"] is True
    tear_down(engine, pending)


def test_a_request_cancelled_during_the_wait_is_resolved_within_one_slice(monkeypatch):
    """The sweep's duties do not wait out the chunk: a request that arrives
    and is cancelled while the thread waits is resolved by the sweep that
    follows the wake-up, not at the next launch."""
    engine, clock, wake, pending, _ = decoding_engine(monkeypatch)
    resolved = []
    second = GenerationRequest(
        prompt_tokens=[7, 8, 9], options=OPTS, on_done=lambda r: resolved.append(clock.t),
    )
    wake.script = [(T0 + 0.06, lambda: (engine.submit(second), second.cancel()))]
    engine._iterate(pending)
    assert second.result(timeout=0).finish_reason == "cancelled"
    assert resolved == [pytest.approx(T0 + 0.06)]
    assert sum(1 for s in engine._slots if s.active) == 1
    assert engine.stats()["cancelled-total"] == 1
    tear_down(engine, pending)


@pytest.mark.parametrize("case", ["no-step-time-yet", "short-chunk", "nothing-in-flight", "speculative"])
def test_the_launch_wait_is_skipped_where_the_grace_is(monkeypatch, case):
    """With nothing queued the thread waits only behind an unfetched chunk
    whose tenth is worth an interpreter switch interval: not before the
    first step was timed, not for a fast model, not with nothing in flight
    (the launch is late there: rows live, the device dry), and never in the
    speculative loop, which pauses before it drains its one verify, as it
    did, and has no launch to hold back."""
    kw = {"speculation": True, "speculation_tokens": 2} if case == "speculative" else {}
    engine, clock, wake, pending, _ = decoding_engine(monkeypatch, **kw)
    if case == "no-step-time-yet":
        engine._step_time_ema_s = 0.0
    elif case == "short-chunk":
        engine._step_time_ema_s = 0.004 / engine.decode_chunk
    elif case == "nothing-in-flight":
        engine._take_landed(pending)  # nothing has landed: nothing is taken
        assert len(pending) == 1
        land(pending[0][0])
        engine._take_landed(pending)
    assert bool(pending) == (case != "nothing-in-flight")
    engine._iterate(pending)
    assert wake.waits == []
    assert clock.sleeps == ([pytest.approx(CHUNK_S / 10)] if case == "speculative" else [])
    counted = launches(engine)
    assert counted["arrival"] == counted["deadline"] == 0 and counted["at-once"] == 2
    assert counted["late"] == (case == "nothing-in-flight")
    tear_down(engine, pending)


def test_a_live_engine_that_waits_serves_the_same_tokens_and_counts_every_launch():
    """The wait on real threads: with the interpreter's switch interval
    lowered so that tiny-test's chunk is worth waiting behind, staggered
    requests are served token for token as by an engine that never waits,
    every first token and every end arrives, and the launches counted by
    reason are the iterations that launched."""
    import sys
    import time

    opts = GenerationOptions(max_new_tokens=40, temperature=0.0)
    prompts = [[3 + i, 5 + i, 7 + i] for i in range(6)]

    def serve():
        engine = make_engine(
            max_batch=4, max_seq_len=128, decode_chunk=16, prefill_buckets=(16,),
        )
        try:
            engine.generate(prompts[0], opts, timeout=300)  # compiles; times a step
            engine.reset_histograms()
            requests = []
            for prompt in prompts:
                requests.append(engine.submit(GenerationRequest(prompt_tokens=prompt, options=opts)))
                time.sleep(0.003)
            tokens = [r.result(timeout=300).tokens for r in requests]
            return tokens, engine.stats()["launches"], engine._obs.flight.iterations()
        finally:
            engine.stop()

    # the engine that never waits: whatever a loaded host makes of tiny-test's
    # chunk, it is not worth a switch interval
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sys, "getswitchinterval", lambda: float("inf"))
        plain, counted, _ = serve()
    assert counted["arrival"] == counted["deadline"] == 0
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        waited, counted, frames = serve()
    finally:
        sys.setswitchinterval(interval)
    assert waited == plain
    assert counted["arrival"] + counted["deadline"] > 0
    by_reason = [f["launch"] for f in frames if f.get("launch")]
    # the ring also holds the compile request's iterations, before the reset
    for reason in ("at-once", "arrival", "deadline"):
        assert counted[reason] <= by_reason.count(reason)
    assert counted["late"] <= sum(counted[r] for r in ("at-once", "arrival", "deadline"))


def test_warmup_freezes_the_heap_and_stop_gives_it_back():
    """A full collection over everything a serving process has built stops
    every thread for a quarter of a second in the middle of traffic: the
    warm-up ends by freezing what is alive, stop() unfreezes."""
    engine = make_engine(
        max_batch=2, max_seq_len=64, decode_chunk=4, prefill_buckets=(16,),
        precompile=True,
    )
    try:
        engine.wait_ready(timeout=300)
        assert gc.get_freeze_count() > 100_000
    finally:
        engine.stop()
    assert gc.get_freeze_count() == 0


def test_compiled_programs_flat_after_warmup_mixed_load():
    """precompile=True warms the decode ladder AND every prefill bucket (the
    fused-iteration shapes); a mixed load afterwards — bursts, sampling,
    queued work, near-tail generations — must dispatch ZERO novel device
    programs (each one would be a mid-traffic compile stall on the chip).
    Overlap retires the shrunk-chunk program entirely, so
    the surface is exactly {ladder} ∪ {prefill buckets}."""
    engine = make_engine(
        max_batch=4, max_seq_len=256, decode_chunk=8,
        prefill_buckets=(16, 32), precompile=True,
    )
    try:
        # first request completes ⇒ warmup finished (the loop warms before
        # serving); its programs are part of the warmed set by construction
        engine.generate(
            [1, 2, 3], GenerationOptions(max_new_tokens=4, temperature=0.0),
            timeout=120,
        )
        warmed = engine.stats()["compiled_programs"]
        assert warmed >= 5  # ladder (64,128,256) + 2 prefill buckets

        opts_greedy = GenerationOptions(max_new_tokens=12, temperature=0.0)
        opts_sampled = GenerationOptions(
            max_new_tokens=12, temperature=0.8, top_k=8, seed=3
        )
        requests = [
            engine.submit(GenerationRequest(
                prompt_tokens=[(7 * i + j) % CFG.vocab_size
                               for j in range(4 + 9 * (i % 3))],
                options=opts_sampled if i % 3 == 0 else opts_greedy,
            ))
            for i in range(10)
        ]
        for r in requests:
            r.result(timeout=120)
        assert engine.stats()["compiled_programs"] == warmed, (
            "mixed load dispatched a device program the warmup missed"
        )
    finally:
        engine.stop()


@pytest.mark.parametrize("prefill_batch,rungs", [(1, (1,)), (2, (1, 2)), (4, (1, 4)), (8, (1, 8))])
def test_the_ladder_is_one_row_and_the_largest_group(prefill_batch, rungs):
    assert admit_rungs(prefill_batch) == rungs


def _serve_burst(config, params, k, full_rows=False, buckets=(16,), lengths=None, widen=True):
    """k prompts (of one bucket width, 3 + i tokens, unless ``lengths`` says
    otherwise) queued before ONE iteration of a warmed engine, driven by hand
    so that they form one iteration's admission groups whatever the host's
    timing; served to the end. ``full_rows`` holds the engine to the one shape
    of `prefill_batch` rows (what every engine did before the ladder), ``widen``
    False keeps every width its own groups (what every engine did before
    `admission_groups`); no option selects either. Returns (tokens, the
    groups' span attributes, programs after warm-up, programs after the
    burst)."""
    engine = ServingEngine(
        config, params, max_batch=8, max_seq_len=64, decode_chunk=4,
        prefill_buckets=buckets, prefill_batch=8, precompile=True,
    )
    if full_rows:
        engine._admit_rungs = (engine.prefill_batch,)
    if not widen:
        engine._admit_widens = False
    engine._warmup()  # what the engine thread runs before it serves
    warmed = engine.stats()["compiled_programs"]
    TRACER.clear()
    opts = GenerationOptions(max_new_tokens=9, temperature=0.0)
    requests = [
        engine.submit(GenerationRequest(
            prompt_tokens=[(5 * i + j) % config.vocab_size for j in range(n)],
            options=opts,
        ))
        for i, n in enumerate(lengths or [3 + i for i in range(k)])
    ]
    pending: deque = deque()
    try:
        while not all(r._done.is_set() for r in requests):
            engine._iterate(pending)
    finally:
        engine.stop()  # gives the heap the warm-up froze back
    groups = [
        s["attributes"] for s in TRACER.spans(4096) if s["name"] == "engine.admit_group"
    ]
    return (
        [r.result(timeout=1).tokens for r in requests], groups, warmed,
        engine.stats()["compiled_programs"],
    )


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
def test_a_burst_dispatches_at_the_smallest_rung_that_holds_it(k):
    """An admission group computes the rows it holds: k same-width arrivals
    ride ONE group of rung(k) rows of the ladder (1, 8), every request's
    tokens are those of the same engine held to `prefill_batch` rows, and no
    program is dispatched that the warm-up had not compiled."""
    rung = 1 if k == 1 else 8
    tokens, groups, warmed, after = _serve_burst(CFG, PARAMS, k)
    assert [(g["rows"], g["real_rows"], g["computed_tokens"]) for g in groups] == [
        (rung, k, rung * 16)
    ]
    assert groups[0]["real_tokens"] == sum(3 + i for i in range(k))
    assert after == warmed, "a rung was dispatched that the warm-up had not compiled"
    full_tokens, full_groups, full_warmed, full_after = _serve_burst(CFG, PARAMS, k, full_rows=True)
    assert [(g["rows"], g["real_rows"]) for g in full_groups] == [(8, k)]
    assert tokens == full_tokens and all(len(t) == 9 for t in tokens)
    # one admit program a rung and width: one more than the one shape
    assert warmed == full_warmed + 1 and full_after == full_warmed


def test_a_widened_burst_dispatches_no_program_the_warm_up_had_not_compiled():
    """Five prompts of two buckets queued before ONE iteration of a warmed
    engine: the two 16-wide ones take free rows of the 32-wide group (3 real
    rows at the rung of 8), ONE dispatch where every width alone makes two;
    no (rung, width) beyond the warm-up's is dispatched, and every request's
    tokens are those of the same engine with every width kept its own group."""
    burst = dict(buckets=(16, 32), lengths=(20, 9, 31, 4, 17))
    shape = lambda g: (g["width"], g["rows"], g["real_rows"], g["widened_rows"])  # noqa: E731
    tokens, groups, warmed, after = _serve_burst(CFG, PARAMS, 5, **burst)
    assert [shape(g) for g in groups] == [(32, 8, 5, 2)] and after == warmed
    alone_tokens, alone_groups, alone_warmed, alone_after = _serve_burst(
        CFG, PARAMS, 5, widen=False, **burst
    )
    assert [shape(g) for g in alone_groups] == [(16, 8, 2, 0), (32, 8, 3, 0)]
    assert alone_after == alone_warmed == warmed
    assert tokens == alone_tokens and all(len(t) == 9 for t in tokens)


@pytest.mark.parametrize("k", [1, 2, 3, 5, 8])
def test_an_expert_model_keeps_prefill_batch_rows(k):
    """`moe_ffn` sizes every expert's capacity from rows x width, padding
    included, and hands it out real rows first: the padding rows buy the real
    ones their capacity, so a model with expert layers keeps the one shape."""
    tokens, groups, warmed, after = _serve_burst(MOE_CFG, MOE_PARAMS, k)
    assert [(g["rows"], g["real_rows"], g["computed_tokens"]) for g in groups] == [(8, k, 128)]
    assert after == warmed and all(len(t) == 9 for t in tokens)


@pytest.mark.parametrize("prefix_cache,widths", [(False, {32}), ("auto", {16, 32})])
def test_segment_programs_warmed_are_the_ones_the_engine_can_dispatch(prefix_cache, widths):
    """A long prompt's chunks run at the largest bucket width; the narrower
    segment widths serve only warm suffixes behind a prefix hit. An engine
    without a prefix index warms the one, and a long prompt afterwards
    compiles nothing."""
    engine = make_engine(
        max_batch=2, max_seq_len=128, decode_chunk=4, prefill_buckets=(16, 32),
        precompile=True, prefix_cache=prefix_cache,
    )
    try:
        engine.wait_ready(timeout=300)
        assert {p[1] for p in engine._programs if p[0] == "paged-segment"} == widths
        warmed = engine.stats()["compiled_programs"]
        long_prompt = [(3 + i) % CFG.vocab_size for i in range(70)]  # three segments
        opts = GenerationOptions(max_new_tokens=4, temperature=0.0)
        assert len(engine.generate(long_prompt, opts, timeout=120).tokens) == 4
        assert len(engine.generate(long_prompt[:9], opts, timeout=120).tokens) == 4
        assert engine.stats()["compiled_programs"] == warmed
    finally:
        engine.stop()


def test_concurrent_long_prefill_streams_share_iterations():
    """Two long prompts prefill CONCURRENTLY (two streams, round-robin
    segments) and both finish with correct token counts while a short
    generation keeps streaming — nobody is serialized behind a whole
    prompt."""
    engine = make_engine(
        max_batch=3, max_seq_len=256, decode_chunk=4, prefill_buckets=(16,),
        max_prefill_streams=2, prefill_token_budget=64,
    )
    try:
        opts = GenerationOptions(max_new_tokens=20, temperature=0.0)
        short = engine.submit(
            GenerationRequest(prompt_tokens=[5, 6, 7], options=opts)
        )
        la = [(3 + i) % CFG.vocab_size for i in range(120)]
        lb = [(5 + 3 * i) % CFG.vocab_size for i in range(100)]
        ra = engine.submit(GenerationRequest(prompt_tokens=la, options=opts))
        rb = engine.submit(GenerationRequest(prompt_tokens=lb, options=opts))
        rs = short.result(timeout=120)
        res_a = ra.result(timeout=120)
        res_b = rb.result(timeout=120)
        assert len(rs.tokens) == 20
        assert res_a.prompt_tokens == 120 and len(res_a.tokens) == 20
        assert res_b.prompt_tokens == 100 and len(res_b.tokens) == 20
    finally:
        engine.stop()


def test_bandwidth_gauge_reports_after_decode():
    """The achieved-HBM-bandwidth gauge is live after decode chunks ran:
    step-time EMA > 0 and the bytes-model yields a finite GB/s (the
    ~25%-of-roofline gap becomes a shipped metric, not a PERF.md note)."""
    engine = make_engine(max_batch=2, max_seq_len=64, decode_chunk=4)
    try:
        engine.generate(
            [1, 2, 3], GenerationOptions(max_new_tokens=8, temperature=0.0),
            timeout=120,
        )
        stats = engine.stats()
        assert stats["decode-step-ms"] > 0
        assert stats["hbm-gbps-decode"] > 0
        assert stats["compiled_programs"] >= 2  # ≥ one prefill + one decode
    finally:
        engine.stop()


def test_overlap_runs_full_chunks_only():
    """Queued work does not shrink the chunk (prefill rides every iteration
    instead): a chunk dispatched with a request waiting and a slot free is
    ``decode_chunk`` steps, so the decode compile surface is exactly ONE
    program — a shrunk size was a whole extra program whose first dispatch
    landed on the first real burst (the r5b mid-traffic stall class)."""
    engine = make_engine(start=False, max_batch=4, max_seq_len=256, decode_chunk=64)
    engine._dev_decode = lambda steps, stale, mask=None: None  # nothing compiles
    engine._submit_fetch = lambda *a, **kw: None
    engine._slots[0].request = GenerationRequest(
        prompt_tokens=[1], options=GenerationOptions(max_new_tokens=200)
    )
    engine._slots[0].position = 10
    engine._queue.put(object())
    entry = engine._dispatch_chunk()
    assert entry[3] == entry[-1].attrs["steps"] == engine.decode_chunk == 64
    engine._queue.get_nowait()
    engine._slots[0].request = None



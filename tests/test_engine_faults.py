"""Chaos suite: request-lifecycle + fault-recovery behavior of the engine,
driven by the deterministic fault injector (serving/faultinject.py).

Every recovery path this PR ships is PROVEN here, not described:
  - an injected dispatch crash fails only the touched slots; survivors are
    token-exact against a fault-free run
  - the NaN-logits guard quarantines one slot (KV rows reset) while the
    rest keep decoding
  - the engine loop self-restarts under bounded backoff and serves again
    WITHOUT a process restart; untouched queued admissions survive
  - a full queue sheds (ShedError + retry-after) instead of blocking
  - deadlines fire both in queue (error, promptly — even with every slot
    busy) and mid-decode (partial tokens)
  - cancel() frees the slot at the next chunk boundary
  - drain() finishes accepted work and rejects new; stop() stays hard

CI pins LSTPU_FAULT_SEED (tier1.yml chaos step); the tests pass explicit
seeds anyway so they are deterministic in any environment.
"""

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.serving.engine import (
    DeadlineExceededError,
    GenerationRequest,
    LogitsNaNError,
    ServingEngine,
    ShedError,
)
from langstream_tpu.serving.faultinject import FaultInjector, InjectedFault

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))


def make_engine(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("decode_chunk", 4)
    engine = ServingEngine(CFG, PARAMS, **kw)
    engine.start()
    return engine


_REFS: dict = {}


def solo_reference(prompt, max_new):
    """Greedy tokens for ``prompt`` on a fresh fault-free engine, cached —
    greedy decoding is deterministic for fixed params, so one reference
    engine build serves every test that needs the same prompt."""
    key = (tuple(prompt), max_new)
    if key not in _REFS:
        engine = make_engine()
        try:
            _REFS[key] = engine.generate(
                prompt, GenerationOptions(max_new_tokens=max_new), timeout=120
            ).tokens
        finally:
            engine.stop()
    return _REFS[key]


def submit_and_wait_first_token(engine, prompt, max_new):
    """Submit and block until the first token lands (the request is then
    definitely active in a slot, and its prefill dispatch has happened)."""
    got = threading.Event()
    req = GenerationRequest(
        prompt_tokens=list(prompt),
        options=GenerationOptions(max_new_tokens=max_new),
        on_token=lambda _t: got.set(),
    )
    engine.submit(req)
    assert got.wait(90), "first token never arrived"
    return req


# ---------------------------------------------------------------------------
# injected dispatch crash: only touched slots fail
# ---------------------------------------------------------------------------


def test_injected_prefill_fault_fails_only_its_group_token_exact_survivors():
    p1, p2, p3 = [3, 4, 5], [7, 8], [9, 10, 11]
    ref = solo_reference(p1, 24)

    engine = make_engine(fault_injector=FaultInjector("prefill@2", seed=0))
    try:
        r1 = submit_and_wait_first_token(engine, p1, 24)  # prefill dispatch 1
        r2 = GenerationRequest(
            prompt_tokens=p2, options=GenerationOptions(max_new_tokens=24)
        )
        engine.submit(r2)  # prefill dispatch 2 → injected fault
        with pytest.raises(InjectedFault):
            r2.result(timeout=60)
        # the survivor decodes to completion, token-exact vs fault-free
        assert r1.result(timeout=120).tokens == ref
        # the engine never died: a third request serves normally
        r3 = engine.generate(p3, GenerationOptions(max_new_tokens=6), timeout=120)
        assert len(r3.tokens) == 6
        stats = engine.stats()
        assert stats["engine-restarts-total"] == 0  # group failure ≠ crash
        assert stats["fault-injection"] == {"prefill": 1}
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# NaN guard: per-slot quarantine, KV rows reset, survivors exact
# ---------------------------------------------------------------------------


def test_nan_guard_quarantines_one_slot_survivor_token_exact():
    p1, p2 = [3, 4, 5], [7, 8]
    refs = {tuple(p1): solo_reference(p1, 24), tuple(p2): solo_reference(p2, 24)}

    engine = make_engine(fault_injector=FaultInjector("nan@3", seed=0))
    try:
        r1 = submit_and_wait_first_token(engine, p1, 24)
        r2 = submit_and_wait_first_token(engine, p2, 24)
        outcomes = {}
        for req, prompt in ((r1, p1), (r2, p2)):
            try:
                outcomes[tuple(prompt)] = req.result(timeout=120)
            except LogitsNaNError:
                outcomes[tuple(prompt)] = None
        victims = [k for k, v in outcomes.items() if v is None]
        assert len(victims) == 1, "exactly one slot must be quarantined"
        survivor = next(k for k in outcomes if k not in victims)
        assert outcomes[survivor].tokens == refs[survivor]
        stats = engine.stats()
        assert stats["nan-guard-total"] == 1
        assert stats["quarantined-slots-total"] == 1
        assert stats["engine-restarts-total"] == 0
        # quarantined KV rows were zeroed and the slot is reusable
        r3 = engine.generate([9, 9], GenerationOptions(max_new_tokens=4), timeout=120)
        assert len(r3.tokens) == 4
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# verify site (self-speculative decoding): a fault during verification
# quarantines ONLY the affected slot
# ---------------------------------------------------------------------------


def test_injected_verify_fault_quarantines_only_victim_slot():
    """The ``verify`` site corrupts one slot's fetched verify result to the
    NaN sentinel (accept forced to 0): that slot must quarantine — and ONLY
    that slot; the survivor decodes to completion token-exact vs a
    fault-free (non-speculative — greedy speculation is token-exact by
    construction) run, and the engine never restarts."""
    p1, p2 = [3, 4, 5], [7, 8]
    refs = {tuple(p1): solo_reference(p1, 24), tuple(p2): solo_reference(p2, 24)}

    engine = make_engine(
        speculation="auto", speculation_tokens=4,
        fault_injector=FaultInjector("verify@3", seed=0),
    )
    try:
        r1 = submit_and_wait_first_token(engine, p1, 24)
        r2 = submit_and_wait_first_token(engine, p2, 24)
        outcomes = {}
        for req, prompt in ((r1, p1), (r2, p2)):
            try:
                outcomes[tuple(prompt)] = req.result(timeout=120)
            except LogitsNaNError:
                outcomes[tuple(prompt)] = None
        victims = [k for k, v in outcomes.items() if v is None]
        assert len(victims) == 1, "exactly one slot must be quarantined"
        survivor = next(k for k in outcomes if k not in victims)
        assert outcomes[survivor].tokens == refs[survivor]
        stats = engine.stats()
        assert stats["quarantined-slots-total"] == 1
        assert stats["engine-restarts-total"] == 0
        assert stats["fault-injection"] == {"verify": 1}
        # the quarantined slot's KV rows were zeroed and the slot is
        # reusable — and speculation keeps serving after the fault
        r3 = engine.generate([9, 9], GenerationOptions(max_new_tokens=4), timeout=120)
        assert len(r3.tokens) == 4
    finally:
        engine.stop()


def test_verify_fault_spares_engine_under_sustained_speculation():
    """Periodic verify faults across a stream of speculative requests:
    every fault costs one request, never the engine — completed requests
    stay token-exact and the loop never crashes/restarts. The period (~12
    verify dispatches ≈ every 2nd-3rd request at these shapes) leaves both
    outcomes represented."""
    prompt = [5, 9, 11, 7] * 6
    ref = solo_reference(prompt, 12)
    engine = make_engine(
        max_batch=1, speculation="auto", speculation_tokens=4,
        fault_injector=FaultInjector("verify@5:12", seed=1),
    )
    try:
        completed = failed = 0
        for _ in range(6):
            req = GenerationRequest(
                prompt_tokens=list(prompt),
                options=GenerationOptions(max_new_tokens=12),
            )
            engine.submit(req)
            try:
                assert req.result(timeout=120).tokens == ref
                completed += 1
            except LogitsNaNError:
                failed += 1
        assert completed > 0 and failed > 0
        stats = engine.stats()
        assert stats["engine-restarts-total"] == 0
        assert stats["quarantined-slots-total"] == failed
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# decode crash: restart under backoff, untouched admissions requeued
# ---------------------------------------------------------------------------


def test_decode_fault_restarts_engine_and_preserves_queue():
    p1, p2 = [3, 4, 5], [7, 8]
    ref2 = solo_reference(p2, 10)

    engine = make_engine(
        max_batch=1,
        fault_injector=FaultInjector("decode@3", seed=0),
        restart_backoff_s=0.02,
    )
    try:
        r1 = submit_and_wait_first_token(engine, p1, 400)  # will hit decode 3
        r2 = GenerationRequest(
            prompt_tokens=p2, options=GenerationOptions(max_new_tokens=10)
        )
        engine.submit(r2)  # queued behind r1 (max_batch=1), never dispatched
        # the in-flight slot fails with the injected device error …
        with pytest.raises(InjectedFault):
            r1.result(timeout=120)
        # … but the queued admission survives the restart and serves
        # token-exact on the rebuilt device state
        assert r2.result(timeout=120).tokens == ref2
        stats = engine.stats()
        assert stats["engine-restarts-total"] == 1
        assert stats["quarantined-slots-total"] == 1
        # and the engine keeps serving (no process restart anywhere)
        r3 = engine.generate([1, 2], GenerationOptions(max_new_tokens=4), timeout=120)
        assert len(r3.tokens) == 4
    finally:
        engine.stop()


def test_restart_budget_exhausted_fails_engine():
    engine = make_engine(
        max_batch=1,
        fault_injector=FaultInjector("decode@1+", seed=0),  # every decode dies
        restart_backoff_s=0.01,
        max_restarts=2,
    )
    try:
        # keep feeding work: every decode dispatch dies, so each request
        # burns one crash; after max_restarts the supervisor gives up
        failures = 0
        deadline = time.monotonic() + 120
        while engine._dead is None and time.monotonic() < deadline:
            req = GenerationRequest(
                prompt_tokens=[3, 4], options=GenerationOptions(max_new_tokens=8)
            )
            try:
                engine.submit(req)
            except RuntimeError:
                break  # declared dead between the check and the submit
            with pytest.raises(InjectedFault):
                req.result(timeout=60)
            failures += 1
        assert engine._dead is not None, "supervisor never gave up"
        assert failures == 3  # restart budget 2 → third crash is fatal
        assert engine.stats()["engine-restarts-total"] == 2
        with pytest.raises(RuntimeError, match="stopped"):
            engine.submit(GenerationRequest(
                prompt_tokens=[1], options=GenerationOptions(max_new_tokens=2)
            ))
    finally:
        engine.stop()


def test_warmup_failure_is_a_failed_start_not_a_counted_restart(monkeypatch):
    """A program the compiler refuses during warm-up used to be swallowed
    by crash recovery: one counted restart, then serving WITHOUT warm-up
    (restarts skip it) and the same refusal mid-traffic. It must end the
    engine before it serves: wait_ready() raises for whoever built it,
    submit() refuses, a request queued meanwhile fails with the error, and
    no restart is counted."""
    refusal = RuntimeError("RESOURCE_EXHAUSTED: scoped vmem limit exceeded")

    def refuse(self):
        raise refusal

    monkeypatch.setattr(ServingEngine, "_warmup_paged", refuse)
    engine = ServingEngine(
        CFG, PARAMS, max_batch=2, max_seq_len=128, decode_chunk=4, precompile=True
    )
    queued = GenerationRequest(
        prompt_tokens=[3, 4], options=GenerationOptions(max_new_tokens=2)
    )
    engine.submit(queued)  # before start(): waits in the queue
    engine.start()
    try:
        with pytest.raises(RuntimeError, match="failed to start") as raised:
            engine.wait_ready(timeout=60)
        assert raised.value.__cause__ is refusal
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            queued.result(timeout=10)
        assert engine.stats()["engine-restarts-total"] == 0
        with pytest.raises(RuntimeError, match="stopped"):
            engine.submit(GenerationRequest(
                prompt_tokens=[1], options=GenerationOptions(max_new_tokens=2)
            ))
    finally:
        engine.stop()


def test_wait_ready_returns_once_warm():
    engine = make_engine(precompile=True, prefill_buckets=(16,))
    try:
        engine.wait_ready(timeout=120)
        warmed = engine.stats()["compiled_programs"]
        assert warmed > 0
        engine.generate([5, 6, 7], GenerationOptions(max_new_tokens=4), timeout=120)
        assert engine.stats()["compiled_programs"] == warmed
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# load shedding
# ---------------------------------------------------------------------------


def test_full_queue_sheds_instead_of_blocking():
    engine = make_engine(max_batch=1, max_seq_len=1024, queue_depth=2,
                         shed_policy="reject")
    try:
        submit_and_wait_first_token(engine, [3, 4], 800)  # slot busy for a while
        queued = [
            engine.submit(GenerationRequest(
                prompt_tokens=[5 + i], options=GenerationOptions(max_new_tokens=2)
            ))
            for i in range(2)
        ]
        t0 = time.monotonic()
        with pytest.raises(ShedError) as e:
            engine.submit(GenerationRequest(
                prompt_tokens=[9], options=GenerationOptions(max_new_tokens=2)
            ))
        assert time.monotonic() - t0 < 1.0, "shed must be immediate, not blocking"
        assert e.value.retry_after_s > 0
        assert engine.stats()["shed-total"] >= 1
        assert len(queued) == 2  # the accepted ones stay accepted
    finally:
        engine.stop()


def test_hopeless_deadline_shed_at_submit():
    engine = make_engine(max_batch=1, max_seq_len=1024)
    try:
        submit_and_wait_first_token(engine, [3, 4], 800)
        # teach the EMA a long queue wait, then submit a doomed deadline
        engine._queue_wait_ema_s = 5.0
        engine.submit(GenerationRequest(  # occupy the queue so qsize > 0
            prompt_tokens=[5], options=GenerationOptions(max_new_tokens=2)
        ))
        with pytest.raises(ShedError):
            engine.submit(GenerationRequest(
                prompt_tokens=[6],
                options=GenerationOptions(max_new_tokens=2, deadline_s=0.5),
            ))
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------


def test_deadline_in_queue_resolves_promptly_while_slots_busy():
    engine = make_engine(max_batch=1, max_seq_len=1024)
    try:
        submit_and_wait_first_token(engine, [3, 4], 800)  # slot busy
        req = GenerationRequest(
            prompt_tokens=[5, 6],
            options=GenerationOptions(max_new_tokens=4, max_queue_wait_s=0.05),
        )
        t0 = time.monotonic()
        engine.submit(req)
        with pytest.raises(DeadlineExceededError):
            req.result(timeout=60)
        # the expiry sweep resolves it within iterations, NOT when the
        # busy slot eventually frees (that would be many seconds away)
        assert time.monotonic() - t0 < 5.0
        assert engine.stats()["deadline-queue-total"] == 1
    finally:
        engine.stop()


def test_deadline_in_long_prompt_backlog_resolves_promptly():
    """A long-prompt request whose max-queue-wait expires while parked in
    the LONG backlog (_long_queue — the single prefill stream is saturated
    by another long prompt) must resolve via the expiry sweep, not
    whenever the stream eventually frees."""
    engine = make_engine(max_batch=2, max_seq_len=2048,
                         prefill_buckets=(16, 32), max_prefill_streams=1)
    try:
        # stream saturator: ~60 chunked-prefill segments of work
        busy = GenerationRequest(
            prompt_tokens=[(3 + i) % 200 for i in range(1900)],
            options=GenerationOptions(max_new_tokens=4),
        )
        engine.submit(busy)
        deadline = time.monotonic() + 60
        while not engine._longs and time.monotonic() < deadline:
            time.sleep(0.01)
        assert engine._longs, "saturator stream never started"
        req = GenerationRequest(
            prompt_tokens=[(5 + i) % 200 for i in range(100)],  # > bucket 32
            options=GenerationOptions(max_new_tokens=4, max_queue_wait_s=0.2),
        )
        t0 = time.monotonic()
        engine.submit(req)
        with pytest.raises(DeadlineExceededError):
            req.result(timeout=60)
        assert time.monotonic() - t0 < 10.0
        assert engine.stats()["deadline-queue-total"] == 1
        busy.cancel()  # unblock teardown
    finally:
        engine.stop()


def test_deadline_mid_decode_returns_partial_tokens():
    # max_seq 4096: the deadline must fire MID-decode, and the engine
    # decodes a 1024-wide cache to its
    # end in under the 1s deadline on CPU — reason "length" instead
    engine = make_engine(max_batch=1, max_seq_len=4096)
    try:
        # warm the compile caches first, else the first-dispatch compile
        # (~2s on CPU) eats the whole deadline before any token lands
        engine.generate([1, 2], GenerationOptions(max_new_tokens=2), timeout=120)
        req = GenerationRequest(
            prompt_tokens=[3, 4],
            options=GenerationOptions(max_new_tokens=100000, deadline_s=1.0),
        )
        engine.submit(req)
        result = req.result(timeout=120)
        assert result.finish_reason == "deadline"
        assert 0 < len(result.tokens) < 100000
        assert engine.stats()["deadline-decode-total"] == 1
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------


def test_cancel_frees_slot_within_one_chunk():
    engine = make_engine(max_batch=1, max_seq_len=2048, decode_chunk=4)
    try:
        r1 = submit_and_wait_first_token(engine, [3, 4], 100000)
        r1.cancel()
        res = r1.result(timeout=60)
        assert res.finish_reason == "cancelled"
        assert res.error is None
        # the slot is free again: a follow-up request serves promptly
        t0 = time.monotonic()
        r2 = engine.generate([5, 6], GenerationOptions(max_new_tokens=4), timeout=60)
        assert len(r2.tokens) == 4
        assert time.monotonic() - t0 < 30
        assert engine.stats()["cancelled-total"] == 1
    finally:
        engine.stop()


def test_cancel_queued_request_resolves_without_admission():
    engine = make_engine(max_batch=1, max_seq_len=1024)
    try:
        submit_and_wait_first_token(engine, [3, 4], 800)  # slot busy
        req = GenerationRequest(
            prompt_tokens=[5], options=GenerationOptions(max_new_tokens=4)
        )
        engine.submit(req)
        req.cancel()
        res = req.result(timeout=30)  # resolved by the sweep, slot still busy
        assert res.finish_reason == "cancelled"
        assert res.tokens == []
    finally:
        engine.stop()


def test_generate_timeout_cancels_the_orphan():
    engine = make_engine(max_batch=1, max_seq_len=2048)
    try:
        with pytest.raises(TimeoutError):
            engine.generate(
                [3, 4], GenerationOptions(max_new_tokens=100000), timeout=1.0
            )
        # the orphan was cancelled, so the slot frees without decoding
        # 100k tokens: the next request completes
        r2 = engine.generate([5], GenerationOptions(max_new_tokens=3), timeout=90)
        assert len(r2.tokens) == 3
        assert engine.stats()["cancelled-total"] >= 1
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# drain vs stop
# ---------------------------------------------------------------------------


def test_drain_finishes_accepted_work_and_rejects_new():
    engine = make_engine(max_batch=1)
    try:
        active = submit_and_wait_first_token(engine, [3, 4], 12)
        queued = engine.submit(GenerationRequest(
            prompt_tokens=[5, 6], options=GenerationOptions(max_new_tokens=6)
        ))
        assert engine.drain(grace_s=90.0) is True
        with pytest.raises(ShedError):
            engine.submit(GenerationRequest(
                prompt_tokens=[7], options=GenerationOptions(max_new_tokens=2)
            ))
        # both accepted requests finished NORMALLY (stop() would have
        # failed them with "serving engine stopped")
        assert active.result(timeout=5).finish_reason == "length"
        assert queued.result(timeout=5).finish_reason == "length"
    finally:
        engine.stop()


def test_drain_grace_expires_with_work_in_flight():
    engine = make_engine(max_batch=1, max_seq_len=2048)
    try:
        r1 = submit_and_wait_first_token(engine, [3, 4], 100000)
        assert engine.drain(grace_s=0.2) is False  # nowhere near done
        r1.cancel()  # unblock teardown
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# stall sites: slow fetch / slow client must not corrupt output
# ---------------------------------------------------------------------------


def test_fetch_and_client_stalls_do_not_corrupt_output():
    prompt = [3, 4, 5]
    ref = solo_reference(prompt, 16)
    engine = make_engine(
        fault_injector=FaultInjector("fetch@1:2,client@1:3", seed=0,
                                     stall_s=0.02),
    )
    try:
        res = engine.generate(
            prompt, GenerationOptions(max_new_tokens=16), timeout=120
        )
        assert res.tokens == ref
        fired = engine.stats()["fault-injection"]
        assert fired["fetch"] >= 1 and fired["client"] >= 1
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# sampling NaN guard (device-level unit)
# ---------------------------------------------------------------------------


def test_sample_emits_sentinel_for_nonfinite_rows_only():
    import jax.numpy as jnp

    from langstream_tpu.serving.sampling import sample

    logits = np.zeros((3, 64), np.float32)
    logits[0, 7] = 5.0          # healthy greedy row → argmax 7
    logits[1, 3] = np.nan       # poisoned row → sentinel
    logits[2, 11] = np.inf      # overflow row → sentinel
    out = np.asarray(sample(
        jnp.asarray(logits),
        jax.random.PRNGKey(0),
        jnp.zeros(3, jnp.float32),
        jnp.zeros(3, jnp.int32),
        jnp.ones(3, jnp.float32),
    ))
    assert out[0] == 7
    assert out[1] == -1
    assert out[2] == -1


# ---------------------------------------------------------------------------
# injector determinism (the harness itself)
# ---------------------------------------------------------------------------


def test_fault_injector_schedules_are_deterministic():
    for spec, expect in [
        ("decode@3", [False, False, True, False, False, False]),
        ("decode@2+", [False, True, True, True, True, True]),
        ("decode@2:2", [False, True, False, True, False, True]),
    ]:
        inj = FaultInjector(spec, seed=0)
        assert [inj.fires("decode") for _ in range(6)] == expect, spec
        assert all(not inj.fires("prefill") for _ in range(4))  # untargeted
    a = FaultInjector("decode~0.5", seed=7)
    b = FaultInjector("decode~0.5", seed=7)
    seq_a = [a.fires("decode") for _ in range(32)]
    seq_b = [b.fires("decode") for _ in range(32)]
    assert seq_a == seq_b and any(seq_a) and not all(seq_a)


def test_fault_injector_env_activation(monkeypatch):
    assert FaultInjector.from_env({}) is None
    inj = FaultInjector.from_env({
        "LSTPU_FAULTS": "nan@2", "LSTPU_FAULT_SEED": "3",
        "LSTPU_FAULT_STALL_S": "0.5",
    })
    assert inj is not None and inj.seed == 3 and inj.stall_s == 0.5
    with pytest.raises(ValueError):
        FaultInjector("warp@1")  # unknown site fails fast, not silently


def test_finish_waker_never_observes_half_torn_slot():
    """The finish-waker race (ISSUE 10 satellite): `_finish` wakes the
    waiter IMMEDIATELY — on_done runs inside it, result() unblocks — so
    every teardown (slot.request cleared, generated list detached, pages
    freed) must land strictly BEFORE. This test loses the race
    deterministically: an injected decode crash routes the in-flight
    request through `_recover`, and the on_done callback (running inside
    _finish, on the engine thread) snapshots whether any slot still wires
    to the finishing request. Before the fix, _recover finished the
    request and THEN cleared the slot — this assertion read the half-torn
    state every time."""
    observed = []

    def on_done_factory(holder):
        def on_done(result):
            engine = holder["engine"]
            req = holder["request"]
            observed.append({
                "slot_refs": sum(
                    1 for s in engine._slots if s.request is req
                ),
                "long_refs": sum(
                    1 for st in engine._longs.values()
                    if st.get("request") is req
                ),
                # the result's token list must be detached from any slot's
                # live list (a later slot reuse would mutate it under the
                # waiter otherwise)
                "aliased": any(
                    result.tokens is s.generated for s in engine._slots
                ),
            })
        return on_done

    holder: dict = {}
    engine = make_engine(
        fault_injector=FaultInjector("decode@2", seed=0),
        restart_backoff_s=0.01, max_restarts=2,
    )
    holder["engine"] = engine
    try:
        request = GenerationRequest(
            prompt_tokens=[5, 6, 7],
            options=GenerationOptions(max_new_tokens=32),
            on_done=on_done_factory(holder),
        )
        holder["request"] = request
        engine.submit(request)
        with pytest.raises(InjectedFault):
            request.result(timeout=120)
        assert observed, "on_done never ran"
        snap = observed[0]
        assert snap["slot_refs"] == 0, "waker saw its request still slotted"
        assert snap["long_refs"] == 0
        assert not snap["aliased"], "result.tokens aliases a live slot list"
        # the engine restarted and still serves
        ok = engine.generate([5, 6, 7], GenerationOptions(max_new_tokens=4),
                             timeout=120)
        assert ok.tokens == solo_reference([5, 6, 7], 4)[:4]
    finally:
        engine.stop()


def test_fail_all_waker_never_observes_half_torn_slot():
    """Same ordering contract on the UNRECOVERABLE path (_fail_all): with
    the restart budget at zero, the injected crash fails everything — and
    the waker must still see its slot fully torn down."""
    observed = []
    holder: dict = {}

    def on_done(result):
        engine = holder["engine"]
        req = holder["request"]
        observed.append(sum(1 for s in engine._slots if s.request is req))

    engine = make_engine(
        fault_injector=FaultInjector("decode@2", seed=0), max_restarts=0,
    )
    holder["engine"] = engine
    try:
        request = GenerationRequest(
            prompt_tokens=[5, 6, 7],
            options=GenerationOptions(max_new_tokens=32),
            on_done=on_done,
        )
        holder["request"] = request
        engine.submit(request)
        with pytest.raises(InjectedFault):
            request.result(timeout=120)
        assert observed and observed[0] == 0, (
            "waker saw its request still slotted during _fail_all"
        )
    finally:
        engine.stop()

"""SPMD slice resilience (ISSUE 15 / round 19, docs/SERVING.md §20).

The multi-host crash-only contract is gone; this suite proves its three
replacements, each both as a cheap unit (tier-1) and as a loopback
leader+follower drill (slow-marked; the chaos CI step runs them under the
pinned LSTPU_FAULT_SEED):

1. Coordinated recovery: an injected engine-loop crash under SPMD
   announces OP_RECOVER with a fresh epoch — BOTH sides rebuild device
   state in place (zero process exits), queued admissions survive on the
   leader, and post-recovery streams are token-exact vs an uninterrupted
   single-host run, with both free lists leak-asserted.
2. Watchdog: a silenced leader (the ``spmd-wedge`` transport site) is
   detected by the follower within 2× ``spmd-watchdog-s`` and leaves a
   schema-valid ``spmd-wedge`` flight dump; symmetrically, a leader
   iteration wedged on a fetch (the ``fetch`` stall site past the bound)
   escalates to OP_RECOVER instead of hanging the slice.
3. Divergence resync: a seq gap (the ``spmd-drop`` site losing one idle
   heartbeat) requests ONE coordinated OP_RESYNC, verifies the leader's
   authoritative tables/positions, and rejoins token-exact; a second
   divergence inside the resync window stays fatal.

Plus the satellite units: the SEQ_MOD wrap ↔ epoch-reset interaction in
``follower_loop`` (the ``last_seq % SEQ_MOD + 1`` rule at the wrap
boundary, held across an OP_RECOVER reset), dump-reason schema +
debounce, the ``recovering`` beacon (router excludes WITHOUT
quarantining, sticky pins held through the backoff window), and the
/healthz ``local_recovering`` accessor.
"""

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.parallel.spmd_serving import (
    OP_IDLE,
    OP_RECOVER,
    ControlBlock,
    LoopbackChannel,
    SpmdChannel,
    SpmdDivergenceError,
    SpmdWedgeError,
    follower_loop,
)
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
from langstream_tpu.serving.faultinject import FaultInjector
from langstream_tpu.serving.observability import (
    DUMP_REASONS,
    FlightRecorder,
    recent_dumps,
    validate_flight_dump,
)
from langstream_tpu.serving.pagepool import table_len_for

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")

MAX_SEQ = 64
PAGE = 8
BUCKETS = (16, 32)
MAX_BATCH = 2


def _engine_kwargs(**over) -> dict:
    kw = dict(
        max_batch=MAX_BATCH,
        max_seq_len=MAX_SEQ,
        decode_chunk=4,
        prefill_buckets=BUCKETS,
        prefill_batch=2,
        page_size=PAGE,
        prefix_cache=False,
        speculation=False,
        restart_backoff_s=0.05,
        max_restarts=5,
    )
    kw.update(over)
    return kw


def _channel(**over) -> LoopbackChannel:
    kw = dict(
        prefill_batch=2,
        max_width=max(BUCKETS),
        max_batch=MAX_BATCH,
        table_len=table_len_for(MAX_SEQ, PAGE),
        spec_tokens=0,
        echo=True,
    )
    kw.update(over)
    return LoopbackChannel(**kw)


class _Pair:
    """Loopback leader+follower sharing params; the follower's exit (error
    or clean) is captured for assertion. Unlike the parity suite's pair,
    the channel takes resilience knobs (watchdog, resync window, its own
    transport injector) and stop() tolerates a deliberately dead or
    wedged follower."""

    def __init__(self, *, engine_injector=None, channel_injector=None,
                 watchdog_s=0.0, resync_window_s=60.0, echo=True,
                 follower_params=None, **engine_over):
        self.params = init_params(CFG, jax.random.PRNGKey(0))
        self.channel = _channel(
            echo=echo, watchdog_s=watchdog_s,
            resync_window_s=resync_window_s, fault_injector=channel_injector,
        )
        kw = _engine_kwargs(**engine_over)
        self.leader = ServingEngine(
            CFG, self.params, spmd=self.channel,
            fault_injector=engine_injector, **kw,
        )
        self.follower = ServingEngine(
            CFG,
            follower_params if follower_params is not None else self.params,
            **kw,
        )
        self.follower_error: list = []

        def run():
            try:
                follower_loop(self.follower, self.channel)
            except BaseException as e:  # noqa: BLE001 — asserted by tests
                self.follower_error.append(e)

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()
        self.leader.start()

    def stop(self, expect_follower_exit: bool = True) -> None:
        self.leader.stop()
        self.thread.join(timeout=60)
        if expect_follower_exit:
            assert not self.thread.is_alive(), "follower never exited"

    def assert_lockstep(self) -> None:
        for attr in ("_tokens_dev", "_positions_dev"):
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(getattr(self.leader, attr))),
                np.asarray(jax.device_get(getattr(self.follower, attr))),
            )
        leaves_a = jax.tree.leaves(jax.device_get(self.leader._pagepool.dev))
        leaves_b = jax.tree.leaves(jax.device_get(self.follower._pagepool.dev))
        assert leaves_a and len(leaves_a) == len(leaves_b)
        for a, b in zip(leaves_a, leaves_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _wait(predicate, timeout_s: float = 10.0, what: str = "condition"):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# Units (tier-1): wire-level semantics, no engines
# ---------------------------------------------------------------------------


class _StubEngine:
    """Just enough engine surface for follower_loop's non-device ops."""

    def __init__(self):
        self._injector = None
        self.recovered: list[int] = []
        self.dumps: list[tuple] = []

    def _spmd_follower_recover(self, epoch: int) -> None:
        self.recovered.append(int(epoch))

    def _flight_dump(self, reason, extra=None, force=False):
        self.dumps.append((reason, dict(extra or {})))


def test_seq_wrap_and_epoch_reset():
    """The `last_seq % SEQ_MOD + 1` rule at the wrap boundary, held ACROSS
    an OP_RECOVER epoch reset (the satellite's untested interaction):
    announcements crossing 2^31−1 must not read as a gap, OP_RECOVER must
    reset both sides to the epoch base, and post-reset seq 1,2,... must
    replay cleanly."""
    ch = _channel(echo=False)
    ch._seq = SpmdChannel.SEQ_MOD - 1
    for _ in range(3):  # seqs SEQ_MOD, 1, 2 — the wrap itself
        ch.announce(ControlBlock(op=OP_IDLE))
    assert ch._seq == 2
    ch.announce(ControlBlock(op=OP_RECOVER, count=7))
    ch.reset_seq()
    for _ in range(2):  # post-epoch seqs 1, 2
        ch.announce(ControlBlock(op=OP_IDLE))
    assert ch._seq == 2
    ch.announce(ControlBlock(op=4))  # OP_STOP
    stub = _StubEngine()
    follower_loop(stub, ch)  # queue pre-filled; returns at STOP
    assert stub.recovered == [7], "OP_RECOVER did not reach the rebuild"
    assert not stub.dumps, f"clean wrap+reset produced dumps: {stub.dumps}"


def test_seq_gap_without_side_channel_is_fatal():
    """No resync transport (report_divergence False) keeps the round-13
    contract: a gap dumps spmd-divergence and raises."""
    ch = _channel(echo=False)
    ch.report_divergence = lambda *a, **k: False
    ch.announce(ControlBlock(op=OP_IDLE))
    ch._seq += 1  # lose one announcement
    ch.announce(ControlBlock(op=OP_IDLE))
    stub = _StubEngine()
    with pytest.raises(SpmdDivergenceError):
        follower_loop(stub, ch)
    assert [r for r, _ in stub.dumps] == ["spmd-divergence"]
    assert "sequence gap" in stub.dumps[0][1]["why"]


def test_seq_gap_requests_resync_and_keeps_replaying():
    """With the loopback side channel, the FIRST gap reports divergence
    (leader-pollable) and the follower keeps replaying instead of dying."""
    ch = _channel(echo=False)
    ch.announce(ControlBlock(op=OP_IDLE))
    ch._seq += 1
    ch.announce(ControlBlock(op=OP_IDLE))
    ch.announce(ControlBlock(op=OP_IDLE))
    ch.announce(ControlBlock(op=4))  # OP_STOP
    stub = _StubEngine()
    follower_loop(stub, ch)  # survives to STOP
    req = ch.poll_divergence()
    assert req is not None and "sequence gap" in req["why"]
    assert ch.poll_divergence() is None  # one-shot
    # the detection left its (debounced) evidence
    assert [r for r, _ in stub.dumps] == ["spmd-divergence"]


def test_second_gap_while_resync_pending_is_fatal():
    """Repeat divergence before the resync lands stays fatal — a resync
    request is not a license to drift."""
    ch = _channel(echo=False)
    ch.announce(ControlBlock(op=OP_IDLE))
    ch._seq += 1
    ch.announce(ControlBlock(op=OP_IDLE))  # gap 1 → resync requested
    ch._seq += 1
    ch.announce(ControlBlock(op=OP_IDLE))  # gap 2 while pending → fatal
    stub = _StubEngine()
    with pytest.raises(SpmdDivergenceError):
        follower_loop(stub, ch)


def test_wedge_site_silences_the_wire():
    """spmd-wedge at the transport: every announcement from the firing on
    is dropped while the leader's seq keeps advancing — the exact
    belief/wire divergence the follower watchdog exists to detect."""
    ch = _channel(echo=False, fault_injector=FaultInjector("spmd-wedge@1", seed=0))
    for _ in range(3):
        ch.announce(ControlBlock(op=OP_IDLE))
    assert ch._q.empty(), "wedged channel delivered announcements"
    assert ch._seq == 3 and ch.announces_total == 0
    assert ch.last_announce_t > 0


def test_drop_site_loses_one_idle_heartbeat():
    """spmd-drop consumes a seq without delivering — the next delivered
    announcement carries the gap (and ONLY idle heartbeats are eligible:
    material ops never ride this site)."""
    ch = _channel(echo=False, fault_injector=FaultInjector("spmd-drop@1", seed=0))
    ch.announce(ControlBlock(op=OP_IDLE))  # dropped, seq 1 consumed
    ch.announce(ControlBlock(op=OP_IDLE))  # delivered as seq 2
    block = ch.recv()
    assert block.op == OP_IDLE and block.seq == 2
    assert ch.announces_total == 1


def test_recv_timeout_raises_spmd_timeout():
    from langstream_tpu.parallel.spmd_serving import SpmdTimeout

    ch = _channel(echo=False)
    t0 = time.monotonic()
    with pytest.raises(SpmdTimeout):
        ch.recv(timeout_s=0.1)
    assert time.monotonic() - t0 < 2.0


def test_new_dump_reasons_schema_and_debounce():
    """spmd-recover / spmd-wedge are schema-legal reasons, and the
    divergence path is debounced per reason like every other dump path
    (a resync storm must not write N dumps per second)."""
    assert "spmd-recover" in DUMP_REASONS and "spmd-wedge" in DUMP_REASONS
    rec = FlightRecorder(capacity=8)
    for reason in ("spmd-recover", "spmd-wedge", "spmd-divergence"):
        doc = rec.dump(reason, counters={"spmd-recoveries": 1},
                       extra={"epoch": 1, "why": "drill"})
        assert doc is not None
        validate_flight_dump(doc)
        # the storm: an immediate repeat of the same reason is debounced
        assert rec.dump(reason, counters={}) is None


def test_spmd_fault_sites_parse():
    inj = FaultInjector("spmd-crash@3,spmd-wedge@1,spmd-drop@2:5", seed=0)
    assert set(inj.stats()) == {"spmd-crash", "spmd-wedge", "spmd-drop"}


def test_local_recovering_accessor():
    from langstream_tpu.serving import fleet as fleet_mod

    assert fleet_mod.local_recovering() is False
    fleet_mod.register_local(
        "rec-test", beacon_fn=lambda: {}, recovering_fn=lambda: True
    )
    try:
        assert fleet_mod.local_recovering() is True
    finally:
        fleet_mod.unregister_local("rec-test")
    assert fleet_mod.local_recovering() is False


# ---------------------------------------------------------------------------
# Router units: `recovering` excludes without quarantining, sticky held
# ---------------------------------------------------------------------------


class _FakeReplica:
    is_local = False

    def __init__(self, rid, load=0.0, **beacon_extra):
        self.replica_id = rid
        self.load = load
        self.beacon_extra = dict(beacon_extra)

    def fetch_beacon(self):
        from langstream_tpu.serving.fleet import BEACON_SCHEMA

        doc = {
            "schema": BEACON_SCHEMA,
            "id": self.replica_id,
            "url": f"fake:{self.replica_id}",
            "at": time.time(),
            "load_score": self.load,
            "queue_wait_ema_s": 0.0,
            "active_slots": 0,
            "max_batch": 4,
            "queued": 0,
            "queue_depth": 16,
            "draining": False,
            "quarantined": False,
            "prefixes": [],
        }
        doc.update(self.beacon_extra)
        return doc


def _router(replicas, **kw):
    from langstream_tpu.serving.fleet import FleetRouter

    kw.setdefault("refresh_interval_s", 3600.0)
    r = FleetRouter(replicas, **kw)
    r.refresh_all()
    return r


PROMPT = [11 + i % 60 for i in range(70)]


def test_recovering_replica_excluded_without_quarantine():
    """A `recovering` beacon takes the replica out of rotation like
    draining does — but WITHOUT a failed_at stamp, so its first
    post-recovery beacon readmits it immediately instead of serving the
    fail_cooldown_s quarantine sentence."""
    rec = _FakeReplica("rec", load=0.0, recovering=True)
    ok = _FakeReplica("ok", load=1.0)
    router = _router([rec, ok], fail_cooldown_s=60.0)
    for _ in range(3):
        assert router.route(PROMPT).replica_id == "ok"
    assert router._replicas["rec"].failed_at <= 0, "recovery was quarantined"
    # recovery ends: the very next beacon readmits (no cooldown to serve)
    rec.beacon_extra["recovering"] = False
    rec.load, ok.load = 0.0, 1.0
    router.refresh_all()
    assert router.route(PROMPT).replica_id == "rec"


def test_sticky_session_held_through_recovery_window():
    """A sticky session whose owner is merely RECOVERING is served
    elsewhere for the moment but its pin is HELD — no pop, no repoint —
    so it lands back on its owner when the backoff window ends (§20)."""
    a = _FakeReplica("a", load=0.0)
    b = _FakeReplica("b", load=0.5)
    router = _router([a, b], fail_cooldown_s=60.0)
    assert router.route(PROMPT, session_id="s1").replica_id == "a"
    a.beacon_extra["recovering"] = True
    router.refresh_all()
    moved = router.route(PROMPT, session_id="s1")
    assert moved.replica_id == "b" and moved.kind != "sticky"
    assert router._sticky["s1"][0] == "a", "pin was popped or repointed"
    assert router.sticky_held_total == 1
    a.beacon_extra["recovering"] = False
    router.refresh_all()
    back = router.route(PROMPT, session_id="s1")
    assert back.replica_id == "a" and back.kind == "sticky"


def test_beacon_carries_recovering_and_validates():
    from langstream_tpu.serving.fleet import beacon_from_engine, validate_beacon

    engine = ServingEngine(
        CFG, init_params(CFG, jax.random.PRNGKey(0)), **_engine_kwargs()
    )
    try:
        doc = beacon_from_engine("r0", engine)
        assert doc["recovering"] is False
        validate_beacon(doc)
        engine._recovering = True
        doc = beacon_from_engine("r0", engine)
        assert doc["recovering"] is True
        validate_beacon(doc)
        assert engine.recovering is True
    finally:
        engine._recovering = False
        engine.stop()


# ---------------------------------------------------------------------------
# Loopback drills (slow — the chaos CI step runs them, pinned seed)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_crash_recovery_in_place_both_sides():
    """THE acceptance drill: an injected engine-loop crash under SPMD
    recovers BOTH sides in place — zero process exits, queued admissions
    survive, post-recovery streams token-exact vs an uninterrupted run,
    both free lists leak-asserted, device state bit-identical."""
    opts = GenerationOptions(max_new_tokens=10, temperature=0.0)
    queued_prompts = [[9, 3, 5], [2, 8, 4, 6]]
    # uninterrupted reference: a fresh single-host engine serving the
    # SAME prompts cold — what the post-recovery streams must match
    ref = ServingEngine(
        CFG, init_params(CFG, jax.random.PRNGKey(0)), **_engine_kwargs()
    )
    ref.start()
    try:
        want = [ref.generate(p, opts, timeout=120).tokens for p in queued_prompts]
    finally:
        ref.stop()

    # watchdog off: cold compiles on this CPU drill would dwarf any sane
    # bound — the watchdog drills below arm it on a warm replica
    pair = _Pair(engine_injector=FaultInjector("decode@3", seed=0))
    try:
        first = [threading.Event(), threading.Event()]
        active = [
            GenerationRequest(
                prompt_tokens=[5, 6, 7], options=opts,
                on_token=lambda t, e=first[0]: e.set(),
            ),
            GenerationRequest(
                prompt_tokens=[1, 2, 3, 4], options=opts,
                on_token=lambda t, e=first[1]: e.set(),
            ),
        ]
        for r in active:
            pair.leader.submit(r)
        # both streaming (first tokens delivered ⇒ both hold slots) before
        # the queued wave goes in, so which requests die is deterministic:
        # the victims are mid-decode at the crash, the queued pair is not
        for e in first:
            assert e.wait(30), "drill victims never started streaming"
        queued = [
            GenerationRequest(prompt_tokens=list(p), options=opts)
            for p in queued_prompts
        ]
        for r in queued:
            pair.leader.submit(r)
        # decode@3 fires on the third decode dispatch → loop crash →
        # OP_RECOVER; the in-flight pair quarantines, the queued pair runs
        outcomes = []
        for r in active:
            try:
                outcomes.append(("ok", r.result(timeout=120).tokens))
            except Exception as e:  # noqa: BLE001 — quarantined by design
                outcomes.append(("failed", type(e).__name__))
        got = [r.result(timeout=120).tokens for r in queued]
        stats = pair.leader.stats()
        assert pair.thread.is_alive(), "follower exited (must recover in place)"
    finally:
        pair.stop()
    assert not pair.follower_error, pair.follower_error
    assert [k for k, _ in outcomes] == ["failed", "failed"], outcomes
    assert got == want, "post-recovery streams diverged from uninterrupted run"
    assert stats["engine-restarts-total"] == 1
    assert stats["spmd-recoveries-total"] == 1
    assert stats["spmd-recovery-epoch"] == 1
    assert stats["quarantined-slots-total"] == 2
    assert stats["recovering"] is False
    # leak assertion, BOTH sides: every page back on the leader's free
    # list, every follower table row back to the OOB sentinel
    assert pair.leader._pagepool.pages_in_use == 0
    assert np.all(
        np.asarray(pair.follower._pagepool.tables)
        == pair.follower._pagepool.oob
    )
    pair.assert_lockstep()
    dumps = [d for d in recent_dumps() if d.get("reason") == "spmd-recover"]
    assert dumps, "no spmd-recover flight dump"
    validate_flight_dump(dumps[-1])
    assert dumps[-1]["extra"]["epoch"] == 1


@pytest.mark.slow
def test_spmd_crash_site_drives_recovery():
    """The dedicated spmd-crash drill site: fires at the iteration top
    (leader only, SPMD only) and the replica recovers in place."""
    opts = GenerationOptions(max_new_tokens=6, temperature=0.0)
    pair = _Pair(engine_injector=FaultInjector("spmd-crash@3", seed=0))
    try:
        # the site fires at the third iteration top — within milliseconds
        # of start, before any request: the idle loop itself crashes and
        # recovers, and the replica then serves normally
        _wait(
            lambda: pair.leader.stats()["spmd-recoveries-total"] >= 1,
            what="coordinated recovery",
        )
        got = pair.leader.generate([5, 6, 7], opts, timeout=120).tokens
        got2 = pair.leader.generate([5, 6, 7], opts, timeout=120).tokens
        assert pair.thread.is_alive()
    finally:
        pair.stop()
    assert not pair.follower_error, pair.follower_error
    assert got2 == got  # same prompt, deterministic greedy, rebuilt state
    assert len(got) == 6
    pair.assert_lockstep()


@pytest.mark.slow
def test_leader_wedge_escalates_to_recover():
    """The leader's symmetric watchdog: a fetch stalled past
    spmd-watchdog-s (the `fetch` site with a long stall) raises
    EngineWedgedError out of the iteration and the supervisor escalates
    to OP_RECOVER — the slice never hangs on one dispatch."""
    opts = GenerationOptions(max_new_tokens=6, temperature=0.0)
    pair = _Pair(watchdog_s=0.0)
    try:
        # warm first: on CPU the cold compiles run on the engine thread
        # and dwarf any sane watchdog bound — production arms the bound
        # on a precompiled replica (docs/SERVING.md §20)
        pair.leader.generate([5, 6, 7], opts, timeout=120)
        inj = FaultInjector("fetch@1", seed=0, stall_s=8.0)
        pair.leader._injector = inj
        old_fetcher = pair.leader._fetcher
        old_fetcher._injector = inj
        pair.channel.watchdog_s = 0.4
        victim = GenerationRequest(prompt_tokens=[5, 6, 7], options=opts)
        pair.leader.submit(victim)
        with pytest.raises(Exception):
            victim.result(timeout=120)
        _wait(
            lambda: pair.leader.stats()["spmd-watchdog-trips-total"] >= 1,
            what="leader watchdog trip",
        )
        # the wedged worker is ABANDONED at recovery (a fresh one serves
        # post-recovery fetches), so this generate completes while the
        # old worker is still parked in its 8s stall — queued behind it,
        # the fetch would re-wedge and burn the restart budget
        out = pair.leader.generate([5, 6, 7], opts, timeout=120)
        assert pair.leader._fetcher is not old_fetcher, (
            "wedged fetch worker was reused"
        )
        assert len(out.tokens) == 6
        stats = pair.leader.stats()
        assert stats["spmd-watchdog-trips-total"] == 1
        assert stats["spmd-recoveries-total"] >= 1
        assert pair.thread.is_alive()
    finally:
        pair.stop()
    assert not pair.follower_error, pair.follower_error


@pytest.mark.slow
def test_follower_watchdog_detects_silenced_leader():
    """A leader that goes silent (spmd-wedge: every announcement dropped,
    heartbeats included) is detected within 2× spmd-watchdog-s and the
    follower leaves a schema-valid spmd-wedge flight dump before exiting
    cleanly."""
    wd = 1.0
    opts = GenerationOptions(max_new_tokens=4, temperature=0.0)
    pair = _Pair(watchdog_s=0.0)
    try:
        pair.leader.generate([5, 6, 7], opts, timeout=120)  # warm (compiles)
        # arm the watchdog on the warm replica and let heartbeats flow so
        # the follower's recv is deadline-bounded before the wedge hits
        pair.channel.watchdog_s = wd
        base = pair.channel.announces_total
        _wait(
            lambda: pair.channel.announces_total >= base + 2,
            what="idle heartbeats flowing",
        )
        # the wedge: the next announcement (a heartbeat, within wd/4)
        # silences the wire permanently
        pair.channel.injector = FaultInjector("spmd-wedge@1", seed=0)
        t0 = time.monotonic()
        pair.thread.join(timeout=10 * wd)
        detected = time.monotonic() - t0
        assert not pair.thread.is_alive(), "watchdog never tripped"
        # the contract: detection within 2× the watchdog of silence
        # onset. Silence began at the last DELIVERED heartbeat — before
        # t0 — so the measured-from-arming time sits at ~2×wd minus that
        # head start; the slack covers thread-scheduling noise on a
        # loaded CI box (the 2×-bound itself is structural: the recv
        # deadline is exactly 2×wd from the last received block, unit-
        # asserted by test_recv_timeout_raises_spmd_timeout)
        assert detected <= 2 * wd + 1.0, f"detection took {detected:.2f}s"
        assert pair.follower_error, "follower exited without the wedge error"
        assert isinstance(pair.follower_error[0], SpmdWedgeError)
    finally:
        pair.stop(expect_follower_exit=False)
    dumps = [d for d in recent_dumps() if d.get("reason") == "spmd-wedge"]
    assert dumps, "no spmd-wedge flight dump"
    doc = dumps[-1]
    validate_flight_dump(doc)
    assert doc["extra"]["watchdog-s"] == wd
    assert doc["extra"]["last-seq"] > 0


@pytest.mark.slow
def test_seq_gap_resync_rejoins_token_exact_then_repeat_is_fatal():
    """The divergence-resync drill: a dropped idle heartbeat (spmd-drop)
    makes the next delivered announcement a seq gap; the follower
    requests ONE coordinated OP_RESYNC, verifies the leader's
    authoritative tables/positions, rejoins — and the post-rejoin stream
    is token-exact vs an uninterrupted run. A second gap inside the
    resync window stays fatal."""
    opts = GenerationOptions(max_new_tokens=8, temperature=0.0)
    ref = ServingEngine(
        CFG, init_params(CFG, jax.random.PRNGKey(0)), **_engine_kwargs()
    )
    ref.start()
    try:
        want1 = ref.generate([5, 6, 7], opts, timeout=120).tokens
        want2 = ref.generate([8, 9, 1], opts, timeout=120).tokens
    finally:
        ref.stop()

    pair = _Pair(watchdog_s=0.0, resync_window_s=60.0)
    try:
        got1 = pair.leader.generate([5, 6, 7], opts, timeout=120).tokens
        # arm on the WARM replica: heartbeats every wd/4 drive the drop
        # site — the first idle announcement after arming is lost, the
        # next delivered one carries the seq gap
        pair.channel.watchdog_s = 0.4
        pair.channel.injector = FaultInjector("spmd-drop@1", seed=0)
        _wait(
            lambda: pair.leader.stats()["spmd-resyncs-total"] == 1,
            what="coordinated resync",
        )
        assert pair.thread.is_alive(), "follower died instead of resyncing"
        got2 = pair.leader.generate([8, 9, 1], opts, timeout=120).tokens
        assert (got1, got2) == (want1, want2), "resync rejoin not token-exact"
        stats = pair.leader.stats()
        assert stats["spmd-resyncs-total"] == 1
        assert stats["spmd-recovery-epoch"] == 1  # resync bumped the epoch
        assert stats["engine-restarts-total"] == 0  # no crash, no restart
        # the leader's result() returns before the follower drains the
        # loopback queue — wait for replay to catch up before comparing
        # device state
        _wait(lambda: pair.channel._q.empty(), what="follower replay drain")
        time.sleep(0.3)  # the dequeued final block may still be executing
        pair.assert_lockstep()
        # SECOND divergence inside the window: inject one out-of-sequence
        # block directly (deterministic, and atomic vs the engine thread's
        # own announcements — Queue.put does not race announce())
        bogus = ControlBlock(
            op=OP_IDLE,
            seq=(pair.channel._seq + 1000) % SpmdChannel.SEQ_MOD or 1,
        )
        pair.channel._q.put(pair.channel._pack(bogus))
        pair.thread.join(timeout=30)
        assert not pair.thread.is_alive(), "repeat divergence was survived"
        assert pair.follower_error
        assert isinstance(pair.follower_error[0], SpmdDivergenceError)
    finally:
        pair.stop(expect_follower_exit=False)
    recover_dumps = [
        d for d in recent_dumps()
        if d.get("reason") == "spmd-recover"
        and d.get("extra", {}).get("kind") == "resync"
    ]
    assert recover_dumps, "leader left no resync evidence"
    validate_flight_dump(recover_dumps[-1])

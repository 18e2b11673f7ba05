"""Disaggregated prefill/decode + chaos-hardened KV-page migration
(ISSUE 13, docs/SERVING.md §18).

Tiers:
1. Migration-wire units over a real engine pair: serialize → bind
   roundtrip exactness (the receiver serves the migrated prefix warm and
   token-exact), sender-frees-only-on-ACK / receiver-frees-only-on-abort
   under the ``migrate`` (corrupt page payload) and ``net-cut``
   (truncated stream) fault sites — both free lists leak-asserted — and
   the deadline-bounded migrate contract (a wedged engine fails the
   TRANSFER, never parks the hop).
2. Role-aware router units over fake beacons: prefill-heavy admissions
   land on prefill-tagged replicas (disagg flagged for the handoff),
   steady traffic keeps the decode/mixed pool, sticky sessions outrank
   role policy, and the per-role autoscale hint + its k8s
   ``status.fleet.desiredReplicasByRole`` round-trip.
3. Heavy e2e (slow — engine builds; the tier1.yml chaos step runs them
   under the pinned LSTPU_FAULT_SEED): the full prefill→migrate→decode
   handoff is token-exact vs the same request served without migration
   with zero engine restarts and both pools leak-asserted; the
   corrupt-page and net-cut drills end in a completed, token-exact
   request served decode-in-place with a schema-valid ``migrate-failed``
   flight dump; hibernated sessions migrate straight from the host
   arena; int8 KV and speculation roundtrip exactly; and a
   grammar-constrained stream RESUMES mid-derivation on a survivor via
   the DFA state its tokens frames carried (refusing only when the
   frames carried none).
"""

import dataclasses
import time

import jax
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.serving import migrate as migrate_mod
from langstream_tpu.serving.engine import ServingEngine
from langstream_tpu.serving.faultinject import FaultInjector
from langstream_tpu.serving.fleet import (
    BEACON_SCHEMA,
    FleetRouter,
    InProcessReplica,
    ReplicaError,
    beacon_from_engine,
    set_wire_injector,
    validate_beacon,
)
from langstream_tpu.serving.migrate import MigrationError
from langstream_tpu.serving.observability import (
    recent_dumps,
    validate_flight_dump,
)
from langstream_tpu.serving.tokenizer import ByteTokenizer

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
TOK = ByteTokenizer()


def prompt_for(base: int, n: int = 40) -> list:
    return [base + (3 * i) % 50 for i in range(n)]


def make_engine(**kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_buckets", (16, 32, 64))
    kw.setdefault("prefix_cache", "auto")
    engine = ServingEngine(kw.pop("config", CFG), kw.pop("params", PARAMS), **kw)
    engine.start()
    return engine


def leak_assert(engine) -> None:
    """Every in-use pool page must be accounted for by the prefix index
    or an active slot — the no-leak property both migration free paths
    (sender on ACK, receiver on abort) must preserve."""
    pool = engine._pagepool
    slot_pages = sum(len(pool.slot_pages(i)) for i in range(engine.max_batch))
    held = engine._prefix_index.pages_held
    assert pool.pages_in_use <= held + slot_pages
    assert pool.free_pages + pool.pages_in_use == pool.num_pages


@pytest.fixture(autouse=True)
def _clean_wire_injector():
    set_wire_injector(None)
    yield
    set_wire_injector(None)


@pytest.fixture(scope="module")
def pair():
    a = make_engine()
    b = make_engine()
    yield a, b
    a.stop()
    b.stop()


# ---------------------------------------------------------------------------
# Migration wire units (engine pair)
# ---------------------------------------------------------------------------


def test_transfer_roundtrip_exact_and_sender_releases_on_ack(pair):
    a, b = pair
    prompt = prompt_for(9)
    opts = GenerationOptions(max_new_tokens=6, temperature=0.0)
    base = a.generate(prompt, opts)
    assert a._prefix_index.deepest_entry(prompt) is not None
    free_b = b._pagepool.free_pages
    phases = {}
    ack = migrate_mod.transfer(a, b, prompt, phases=phases)
    assert ack["ok"] and ack["pages"] >= 1 and ack["bytes"] > 0
    assert phases["tier"] == "device" and "snapshot_ms" in phases
    # sender released ON the ack (and only then)
    assert a._prefix_index.deepest_entry(prompt) is None
    assert a.stats()["migrate-pages-out-total"] >= 1
    assert b.stats()["migrate-pages-in-total"] >= 1
    assert b._pagepool.free_pages == free_b - ack["pages"]
    # the receiver now serves the SAME request warm and token-exact
    saved0 = b.stats()["prefill-tokens-saved-total"]
    out = b.generate(prompt, opts)
    assert out.tokens == base.tokens
    assert b.stats()["prefill-tokens-saved-total"] > saved0
    leak_assert(a)
    leak_assert(b)


def test_corrupt_page_drill_receiver_discards_sender_retains(pair):
    a, b = pair
    prompt = prompt_for(10)
    a.generate(prompt, GenerationOptions(max_new_tokens=4, temperature=0.0))
    free_b = b._pagepool.free_pages
    in_b = b.stats()["migrate-pages-in-total"]
    set_wire_injector(FaultInjector("migrate@1", seed=0))
    with pytest.raises(MigrationError, match="checksum"):
        migrate_mod.transfer(a, b, prompt)
    set_wire_injector(None)
    # receiver freed on abort: nothing allocated, nothing counted
    assert b._pagepool.free_pages == free_b
    assert b.stats()["migrate-pages-in-total"] == in_b
    # sender retained: the same transfer succeeds once the wire is clean
    assert a._prefix_index.deepest_entry(prompt) is not None
    ack = migrate_mod.transfer(a, b, prompt)
    assert ack["ok"] and ack["pages"] >= 1
    leak_assert(a)
    leak_assert(b)


def test_net_cut_mid_transfer_drill(pair):
    a, b = pair
    prompt = prompt_for(11)
    a.generate(prompt, GenerationOptions(max_new_tokens=4, temperature=0.0))
    free_b = b._pagepool.free_pages
    set_wire_injector(FaultInjector("net-cut@1", seed=0))
    with pytest.raises(MigrationError, match="net-cut|commit"):
        migrate_mod.transfer(a, b, prompt)
    set_wire_injector(None)
    assert b._pagepool.free_pages == free_b
    assert a._prefix_index.deepest_entry(prompt) is not None
    leak_assert(a)
    leak_assert(b)


def test_migrate_is_deadline_bounded(pair):
    a, _ = pair
    prompt = prompt_for(12)
    a.generate(prompt, GenerationOptions(max_new_tokens=4, temperature=0.0))
    real = a._migrate_cmd

    def wedged(kind, payload):
        time.sleep(1.5)
        return real(kind, payload)

    a._migrate_cmd = wedged
    try:
        t0 = time.monotonic()
        with pytest.raises(MigrationError, match="within"):
            a.migrate_snapshot(prompt, timeout_s=0.2)
        assert time.monotonic() - t0 < 1.0
    finally:
        del a._migrate_cmd
        time.sleep(1.6)  # let the wedged command drain off the loop


def test_bind_rejects_page_count_mismatch(pair):
    a, b = pair
    prompt = prompt_for(13)
    a.generate(prompt, GenerationOptions(max_new_tokens=4, temperature=0.0))
    frames = list(migrate_mod.export_frames(a, prompt))
    # drop a page frame but keep begin/commit: the count check must abort
    cut = [f for f in frames if f["kind"] != "page"]
    for seq, f in enumerate(cut):
        f["seq"] = seq
    free_b = b._pagepool.free_pages
    with pytest.raises(MigrationError, match="count|pages"):
        migrate_mod.bind_frames(b, iter(cut))
    assert b._pagepool.free_pages == free_b
    # sender untouched by a failed EXPORT consumer
    assert a._prefix_index.deepest_entry(prompt) is not None


def test_no_published_prefix_fails_cleanly(pair):
    a, b = pair
    with pytest.raises(MigrationError, match="no published prefix"):
        migrate_mod.transfer(a, b, [1, 2, 3, 4])


# ---------------------------------------------------------------------------
# Role-aware routing units (fake beacons, no engines)
# ---------------------------------------------------------------------------


class _FakeReplica:
    is_local = False

    def __init__(self, rid, load=0.0, role="mixed", prefixes=(), **extra):
        self.replica_id = rid
        self.load = load
        self.role = role
        self.prefixes = list(prefixes)
        self.extra = dict(extra)

    def fetch_beacon(self):
        doc = {
            "schema": BEACON_SCHEMA,
            "id": self.replica_id,
            "url": f"fake:{self.replica_id}",
            "role": self.role,
            "at": time.time(),
            "load_score": self.load,
            "queue_wait_ema_s": 0.0,
            "active_slots": 0,
            "max_batch": 4,
            "queued": 0,
            "queue_depth": 16,
            "draining": False,
            "quarantined": False,
            "prefixes": [[d, n] for d, n in self.prefixes],
        }
        doc.update(self.extra)
        return doc


def _router(replicas, **kw):
    kw.setdefault("refresh_interval_s", 3600.0)
    r = FleetRouter(replicas, **kw)
    r.refresh_all()
    return r


LONG = [11 + i % 60 for i in range(70)]
SHORT = [11 + i % 60 for i in range(12)]


def test_prefill_heavy_routes_to_prefill_replica_with_disagg():
    router = _router(
        [
            _FakeReplica("pre", load=0.5, role="prefill"),
            _FakeReplica("dec", load=0.0, role="decode"),
        ],
        prefill_route_threshold=32,
    )
    d = router.route(LONG)
    assert d.replica_id == "pre" and d.kind == "prefill" and d.disagg
    assert router.stats()["fleet-routed-prefill-total"] == 1
    # short admissions keep the decode pool — the prefill replica is
    # reserved for the bursts it exists to absorb
    d = router.route(SHORT)
    assert d.replica_id == "dec" and not d.disagg


def test_disagg_needs_both_roles_and_migrate_knob():
    # decode-only fleet: no handoff, everything routes normally
    router = _router(
        [_FakeReplica("d1", role="decode"), _FakeReplica("d2", role="decode")],
        prefill_route_threshold=32,
    )
    assert not router.route(LONG).disagg
    # migrate=False: role steering stands, the handoff does not
    router = _router(
        [
            _FakeReplica("pre", role="prefill"),
            _FakeReplica("dec", role="decode"),
        ],
        prefill_route_threshold=32, migrate=False,
    )
    d = router.route(LONG)
    assert d.replica_id == "pre" and d.kind == "prefill" and not d.disagg


def test_sticky_session_outranks_role_policy():
    router = _router(
        [
            _FakeReplica("pre", role="prefill"),
            _FakeReplica("dec", role="decode"),
        ],
        prefill_route_threshold=32,
    )
    first = router.route(LONG, session_id="s1")
    assert first.replica_id == "pre"
    # the sticky map now holds the session: the next turn goes where the
    # KV lives, role policy notwithstanding
    again = router.route(LONG, session_id="s1")
    assert again.replica_id == "pre" and again.kind == "sticky"


def test_sticky_repoint_unit():
    router = _router(
        [
            _FakeReplica("pre", role="prefill"),
            _FakeReplica("dec", role="decode"),
        ],
        prefill_route_threshold=32,
    )
    router.route(LONG, session_id="s2")
    # simulate the post-migration repoint stream_generate performs
    with router._lock:
        router._sticky["s2"] = ("dec", time.monotonic())
    d = router.route(LONG, session_id="s2")
    assert d.replica_id == "dec" and d.kind == "sticky"


def test_pick_decode_target_prefers_decode_then_mixed():
    router = _router(
        [
            _FakeReplica("pre", load=0.0, role="prefill"),
            _FakeReplica("mix", load=0.0, role="mixed"),
            _FakeReplica("dec", load=0.9, role="decode"),
        ],
    )
    target = router._pick_decode_target(set())
    assert target.replica_id == "dec"  # decode beats mixed even when hotter
    target = router._pick_decode_target({"dec"})
    assert target.replica_id == "mix"
    assert router._pick_decode_target({"dec", "mix"}) is None


def test_desired_replicas_by_role():
    router = _router(
        [
            _FakeReplica("p1", role="prefill", queue_wait_ema_s=2.0),
            _FakeReplica(
                "d1", role="decode", active_slots=4, max_batch=4,
                load_score=2.5,
            ),
            _FakeReplica("d2", role="decode", active_slots=4, max_batch=4),
        ],
    )
    hint = router.desired_replicas_by_role(target_queue_wait_s=0.5)
    assert hint["prefill"] >= 2  # queue wait 4x target → scale out
    assert hint["decode"] >= 3  # occupancy 1.0 → scale out
    # homogeneous fleet: no split (the scalar hint stands alone)
    router = _router([_FakeReplica("m1"), _FakeReplica("m2")])
    assert router.desired_replicas_by_role() == {}


def test_reconciler_round_trips_role_split():
    from langstream_tpu.k8s.crds import AgentCustomResource
    from langstream_tpu.k8s.fake import FakeKubeServer
    from langstream_tpu.k8s.resources import FleetAutoscaleReconciler

    kube = FakeKubeServer()
    agent = AgentCustomResource(
        name="a", namespace="ns", tenant="t", agent_id="a",
        application_id="app", agent_type="ai-chat-completions",
        component_type="PROCESSOR", config_secret_ref="s",
        config_checksum="c", parallelism=2,
        autoscale={"enabled": True, "min-replicas": 1, "max-replicas": 8},
        status={"phase": "DEPLOYED"},
    )
    kube.apply(agent.to_manifest())
    roles = {"v": {"prefill": 2, "decode": 4}}
    rec = FleetAutoscaleReconciler(
        kube, lambda: 6, namespace="ns", name="a",
        desired_roles_fn=lambda: roles["v"],
    )
    assert rec.reconcile_once() == 6
    manifest = kube.get(AgentCustomResource.KIND, "ns", "a")
    fleet = manifest["status"]["fleet"]
    assert fleet["desiredReplicas"] == 6
    assert fleet["desiredReplicasByRole"] == {"prefill": 2, "decode": 4}
    # unchanged → skipped; a role move alone → patched
    assert rec.reconcile_once() is None
    roles["v"] = {"prefill": 3, "decode": 4}
    assert rec.reconcile_once() == 6
    fleet = kube.get(AgentCustomResource.KIND, "ns", "a")["status"]["fleet"]
    assert fleet["desiredReplicasByRole"] == {"prefill": 3, "decode": 4}
    # roles vanish (homogeneous again): the stale split is retired
    roles["v"] = {}
    assert rec.reconcile_once() == 6
    fleet = kube.get(AgentCustomResource.KIND, "ns", "a")["status"]["fleet"]
    assert "desiredReplicasByRole" not in fleet


def test_beacon_role_validation():
    class _Stats:
        def stats(self):
            return {}

    with pytest.raises(ValueError, match="unknown fleet role"):
        beacon_from_engine("r", _Stats(), role="turbo")
    doc = {
        "schema": BEACON_SCHEMA, "id": "r", "at": 0.0, "load_score": 0.0,
        "queue_wait_ema_s": 0.0, "draining": False, "quarantined": False,
        "prefixes": [], "role": "prefill",
    }
    assert validate_beacon(doc)
    doc["role"] = "turbo"
    with pytest.raises(ValueError, match="role"):
        validate_beacon(doc)


def test_memory_plan_migrate_staging_term():
    from langstream_tpu.serving.memory import plan_serving_memory

    base = plan_serving_memory(CFG, 2, 128)
    plan = plan_serving_memory(
        CFG, 2, 128, migrate_staging=True,
    )
    assert plan.migrate_staging_bytes > 0
    # HOST RAM: the staging term never inflates the HBM total
    assert plan.total_bytes == base.total_bytes
    assert "migrate staging" in plan.summary()


# ---------------------------------------------------------------------------
# Heavy e2e (slow — the tier1.yml chaos step runs these under the pinned
# LSTPU_FAULT_SEED)
# ---------------------------------------------------------------------------


def _role_router(pe, de, **kw):
    kw.setdefault("prefill_route_threshold", 8)
    kw.setdefault("refresh_interval_s", 0.1)
    router = FleetRouter(
        [
            InProcessReplica("pre", pe, role="prefill"),
            InProcessReplica("dec", de, role="decode"),
        ],
        **kw,
    )
    router.refresh_all()
    return router


def _drain(router, prompt, opts, session_id=None):
    frames = list(router.stream_generate(prompt, opts, session_id=session_id))
    toks = [t for f in frames if f["kind"] == "tokens" for t in f["tokens"]]
    assert [f["seq"] for f in frames] == list(range(len(frames)))
    assert frames[-1]["kind"] == "end"
    return frames, toks, frames[-1]


@pytest.mark.slow
def test_disagg_handoff_e2e_token_exact(pair):
    a, _ = pair
    prompt = prompt_for(14)
    opts = {"max-tokens": 8, "temperature": 0.0}
    baseline = a.generate(
        prompt, GenerationOptions.from_dict(opts)
    ).tokens

    pe, de = make_engine(), make_engine()
    router = _role_router(pe, de)
    try:
        frames, toks, end = _drain(router, prompt, opts, session_id="sess")
        assert toks == baseline  # clean migrated decode == unmigrated run
        served = {f["replica"] for f in frames if f["kind"] == "tokens"}
        assert served == {"pre", "dec"}  # TTFT on prefill, tail on decode
        assert end["replica"] == "dec" and end["failovers"] == 0
        st = router.stats()
        assert st["fleet-migrations-total"] == 1
        assert st["fleet-migrate-pages-total"] >= 1
        assert st["fleet-migrate-fallbacks-total"] == 0
        assert st["fleet-routed-prefill-total"] == 1
        # sticky repoint: the NEXT turn routes to where the KV now lives
        d = router.route(prompt + toks, session_id="sess")
        assert d.replica_id == "dec" and d.kind == "sticky"
        # zero restarts, sender released, both pools leak-free
        assert pe.stats()["engine-restarts-total"] == 0
        assert de.stats()["engine-restarts-total"] == 0
        assert pe._prefix_index.deepest_entry(prompt) is None
        assert de._prefix_index.deepest_entry(prompt) is not None
        leak_assert(pe)
        leak_assert(de)
        # the decode replica aliased the migrated pages (warm resume)
        assert de.stats()["prefill-tokens-saved-total"] > 0
    finally:
        router.stop()
        pe.stop()
        de.stop()


@pytest.mark.slow
@pytest.mark.parametrize("spec", ["migrate@1", "net-cut@1"])
def test_disagg_migration_chaos_drills(pair, spec):
    """The acceptance drills: a migration corrupted (or cut) at any byte
    still ends in a completed, greedy-token-exact request with zero
    restarts and zero leaked pages on either replica — served
    decode-in-place on the prefill replica, with a schema-valid
    ``migrate-failed`` dump."""
    a, _ = pair
    prompt = prompt_for(15)
    opts = {"max-tokens": 8, "temperature": 0.0}
    baseline = a.generate(prompt, GenerationOptions.from_dict(opts)).tokens

    pe, de = make_engine(), make_engine()
    router = _role_router(pe, de)
    dumps0 = len(
        [d for d in recent_dumps() if d.get("reason") == "migrate-failed"]
    )
    try:
        free_de = de._pagepool.free_pages
        set_wire_injector(FaultInjector(spec, seed=0))
        frames, toks, end = _drain(router, prompt, opts)
        set_wire_injector(None)
        assert toks == baseline
        served = {f["replica"] for f in frames if f["kind"] == "tokens"}
        assert served == {"pre"}  # decode-in-place fallback
        st = router.stats()
        assert st["fleet-migrations-total"] == 0
        assert st["fleet-migrate-fallbacks-total"] == 1
        assert de._pagepool.free_pages == free_de  # receiver freed on abort
        assert de.stats()["migrate-pages-in-total"] == 0
        assert pe._prefix_index.deepest_entry(prompt) is not None  # retained
        assert pe.stats()["engine-restarts-total"] == 0
        assert de.stats()["engine-restarts-total"] == 0
        leak_assert(pe)
        leak_assert(de)
        dumps = [
            d for d in recent_dumps() if d.get("reason") == "migrate-failed"
        ]
        assert len(dumps) == dumps0 + 1
        assert validate_flight_dump(dumps[-1])
        assert dumps[-1]["extra"]["fallback"] == "decode-in-place"
    finally:
        set_wire_injector(None)
        router.stop()
        pe.stop()
        de.stop()


@pytest.mark.slow
def test_hibernated_session_migrates_from_host_arena():
    """A spilled (hibernated) session's pages ship straight from the host
    arena with their STORED checksums — no device restore on the sender."""
    a = make_engine(host_kv_fraction=2.0, spill_idle_s=0.0)
    b = make_engine()
    try:
        prompt = prompt_for(16)
        opts = GenerationOptions(max_new_tokens=6, temperature=0.0)
        base = a.generate(prompt, opts)
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            entry = a._prefix_index.deepest_entry(prompt)
            if entry is not None and entry[1].host and not entry[1].spilling:
                break
            time.sleep(0.05)
        else:
            pytest.fail("prefix never spilled to the host arena")
        restores0 = a.stats()["restore-pages-total"]
        phases = {}
        ack = migrate_mod.transfer(a, b, prompt, phases=phases)
        assert ack["ok"] and phases["tier"] == "host"
        assert a.stats()["restore-pages-total"] == restores0
        out = b.generate(prompt, opts)
        assert out.tokens == base.tokens
        leak_assert(b)
    finally:
        a.stop()
        b.stop()


@pytest.mark.slow
@pytest.mark.parametrize(
    "kv_dtype,speculation",
    [("int8", False), ("int8", True), ("float32", True)],
)
def test_transfer_roundtrip_dtypes_and_speculation(kv_dtype, speculation):
    """Roundtrip exactness across the pool dtypes × speculation (the
    float32 × spec-off cell runs in the fast tier): the receiver's
    migrated-prefix decode equals the sender's, page bytes halve under
    int8 (int8 + scales ship, like the host tier)."""
    cfg = (
        dataclasses.replace(CFG, kv_cache_dtype="int8")
        if kv_dtype == "int8"
        else CFG
    )
    kw = {"config": cfg}
    if speculation:
        kw.update(speculation="auto", speculation_tokens=4)
    a = make_engine(**kw)
    b = make_engine(**kw)
    try:
        prompt = prompt_for(17)
        opts = GenerationOptions(max_new_tokens=6, temperature=0.0)
        base = a.generate(prompt, opts)
        ack = migrate_mod.transfer(a, b, prompt)
        assert ack["ok"]
        saved0 = b.stats()["prefill-tokens-saved-total"]
        out = b.generate(prompt, opts)
        assert out.tokens == base.tokens
        assert b.stats()["prefill-tokens-saved-total"] > saved0
        leak_assert(a)
        leak_assert(b)
    finally:
        a.stop()
        b.stop()


class _DiesAfterFrames(InProcessReplica):
    """Replica whose stream dies at the first frame BOUNDARY once
    ``fail_after`` tokens flowed — the §17 failure signature (frames are
    atomic on the wire; seq validation rejects partials)."""

    def __init__(self, *a, fail_after=3, strip_state=False, **k):
        super().__init__(*a, **k)
        self.fail_after = fail_after
        self.strip_state = strip_state

    def generate_stream(self, tokens, options=None, timeout_s=None):
        inner = super().generate_stream(tokens, options, timeout_s)

        def wrap():
            n = 0
            try:
                for f in inner:
                    if n >= self.fail_after:
                        raise ReplicaError("injected mid-stream death")
                    if f.get("kind") == "tokens":
                        n += len(f["tokens"])
                        if self.strip_state:
                            f = {
                                k: v for k, v in f.items()
                                if k != "dfa_state"
                            }
                    yield f
                    if f.get("kind") == "tokens" and n >= self.fail_after:
                        raise ReplicaError("injected mid-stream death")
            finally:
                close = getattr(inner, "close", None)
                if close is not None:
                    close()

        return wrap()


RF = {"type": "regex", "regex": "[ab]{6}x"}


def _constrained_engine(**kw):
    kw.setdefault("grammar_tokenizer", TOK)
    kw.setdefault("eos_token_id", TOK.eos_token_id)
    kw.setdefault("decode_chunk", 2)
    return make_engine(**kw)


@pytest.mark.slow
def test_constrained_stream_resumes_mid_derivation():
    """The lifted PR-12 refusal: the survivor resumes FROM the DFA state
    the dead replica's tokens frames carried — the finished stream is one
    valid derivation, token-exact vs an uninterrupted run."""
    import re

    ref = _constrained_engine()
    opts = {"max-tokens": 16, "temperature": 0.0, "response-format": RF}
    base = ref.generate(prompt_for(18), GenerationOptions.from_dict(opts))
    ref.stop()

    a, b = _constrained_engine(), _constrained_engine()
    router = FleetRouter(
        [_DiesAfterFrames("a", a, fail_after=3), InProcessReplica("b", b)],
        refresh_interval_s=0.1,
    )
    router.refresh_all()
    try:
        frames, toks, end = _drain(router, prompt_for(18), opts)
        assert toks == base.tokens
        assert end["finish_reason"] == "stop" and end["failovers"] == 1
        assert re.fullmatch(RF["regex"], TOK.decode(toks))
    finally:
        router.stop()
        a.stop()
        b.stop()


@pytest.mark.slow
def test_constrained_stream_still_refuses_without_state():
    """Grammar-registry-miss semantics: frames from a legacy peer carry
    no DFA state — resuming would restart the grammar at state 0, so the
    stream must fail loudly rather than emit an invalid derivation."""
    a, b = _constrained_engine(), _constrained_engine()
    router = FleetRouter(
        [
            _DiesAfterFrames("a", a, fail_after=3, strip_state=True),
            InProcessReplica("b", b),
        ],
        refresh_interval_s=0.1,
    )
    router.refresh_all()
    opts = {"max-tokens": 16, "temperature": 0.0, "response-format": RF}
    try:
        with pytest.raises(ReplicaError, match="no DFA state"):
            list(router.stream_generate(prompt_for(19), opts))
    finally:
        router.stop()
        a.stop()
        b.stop()

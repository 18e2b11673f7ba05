"""Fleet router tests (ROADMAP item 3 / ISSUE 8).

Three tiers:
1. Pure-host router units over fake replicas: affinity argmax, the λ
   load-vs-cache tradeoff, least-loaded fallback, sticky sessions,
   drain/quarantine/staleness exclusion, saturation shedding against the
   replicas' OWN exported signals, round-robin (the bench control arm),
   and the autoscale hint. Plus the non-mutating ``match_len`` probes —
   probing must NOT change eviction order — and beacon schema/redaction.
2. A 2-replica in-process e2e: shared-preamble requests converge on the
   replica that owns the warm pages (affinity), and a replica dying
   mid-burst (the ``client`` fault site keeping work in flight when it
   stops) fails over cold to the survivor with zero hung requests.
3. The transport ring: /state + /fleet/generate over a real
   RuntimeHttpServer via HttpReplica, and the persistent-compile-cache
   cold-start lever (second engine construction compiles 0 new programs
   against a warm cache dir).
"""

import dataclasses
import json
import threading
import time
import urllib.request

import jax
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.serving.engine import ServingEngine
from langstream_tpu.serving.faultinject import FaultInjector
from langstream_tpu.serving.fleet import (
    BEACON_SCHEMA,
    FleetRouter,
    FleetShedError,
    HttpReplica,
    InProcessReplica,
    ReplicaError,
    beacon_from_engine,
    prefix_digest,
    validate_beacon,
)
from langstream_tpu.serving.pagepool import PagePool, PrefixPageIndex

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))

GREEDY = GenerationOptions(max_new_tokens=8, temperature=0.0)


def make_engine(prefix=True, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_buckets", (16, 32, 64))
    engine = ServingEngine(
        CFG,
        PARAMS,
        prefix_cache="auto" if prefix else "off",
        **kw,
    )
    engine.start()
    return engine


# ---------------------------------------------------------------------------
# match_len probes: non-mutating, LRU-order preserving
# ---------------------------------------------------------------------------


def test_prefix_digest_stable_and_distinct():
    a = list(range(64))
    assert prefix_digest(a) == prefix_digest(tuple(a))
    assert prefix_digest(a) != prefix_digest(a[:32])
    assert prefix_digest(a[:32]) == prefix_digest(a[:32])
    assert len(prefix_digest(a)) == 16  # 8-byte hex


def test_paged_match_len_probe_preserves_eviction_order():
    """Probing via match_len must not refresh recency: after many probes of
    the OLDER entry, it is still the LRU victim. The control leg shows a
    real hit (record_lookup) DOES refresh and flips the victim."""
    pool = PagePool(CFG, num_pages=64, page_size=16, max_batch=2, max_seq_len=128)
    index = PrefixPageIndex(boundaries=(32, 64), max_entries=8)
    tok_a = [1 + i % 50 for i in range(40)]
    tok_b = [7 + i % 50 for i in range(40)]
    pages_a = pool._alloc(2)
    pages_b = pool._alloc(2)
    entry_a = index.insert(pool, tok_a, 32, tuple(pages_a))
    entry_b = index.insert(pool, tok_b, 32, tuple(pages_b))
    hits_before, lookups_before = index.hits, index.lookups
    for _ in range(20):
        assert index.match_len(tok_a) == 32
    assert (index.hits, index.lookups) == (hits_before, lookups_before)
    assert index.evict_lru(pool)
    assert entry_a.node.entry is None, "probed entry should STILL be the LRU victim"
    assert entry_b.node.entry is entry_b
    # control: a real hit refreshes recency — re-insert A, touch it, B evicts
    pages_a2 = pool._alloc(2)
    entry_a2 = index.insert(pool, tok_a, 32, tuple(pages_a2))
    index.record_lookup(entry_a2)
    assert index.evict_lru(pool)
    assert entry_b.node.entry is None
    assert entry_a2.node.entry is entry_a2


def test_advertised_digests_track_insert_and_evict():
    pool = PagePool(CFG, num_pages=64, page_size=16, max_batch=2, max_seq_len=128)
    index = PrefixPageIndex(boundaries=(32,), max_entries=8)
    tok = [3 + i % 40 for i in range(40)]
    index.insert(pool, tok, 32, tuple(pool._alloc(2)))
    ads = index.advertised(8)
    assert (prefix_digest(tok[:32]), 32, "device") in ads
    assert index.evict_lru(pool)
    assert index.advertised(8) == []


# ---------------------------------------------------------------------------
# Router units (fake replicas — no engines, no I/O)
# ---------------------------------------------------------------------------


class _FakeReplica:
    is_local = False

    def __init__(self, rid, load=0.0, prefixes=(), **beacon_extra):
        self.replica_id = rid
        self.load = load
        self.prefixes = list(prefixes)
        self.beacon_extra = dict(beacon_extra)
        self.generated = []
        self.fail_with = None

    def fetch_beacon(self):
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
            "prefixes": [[d, n] for d, n in self.prefixes],
        }
        doc.update(self.beacon_extra)
        return doc

    def generate(self, tokens, options=None, timeout_s=600.0):
        if self.fail_with is not None:
            raise self.fail_with
        self.generated.append(list(tokens))
        return {
            "tokens": [1, 2, 3],
            "finish_reason": "length",
            "prompt_tokens": len(tokens),
            "ttft_s": 0.01,
            "total_s": 0.02,
        }


def _router(replicas, **kw):
    kw.setdefault("refresh_interval_s", 3600.0)  # tests refresh by hand
    r = FleetRouter(replicas, **kw)
    r.refresh_all()
    return r


PROMPT = [11 + i % 60 for i in range(70)]


def test_affinity_routes_to_matching_replica():
    warm = _FakeReplica(
        "warm", load=0.1,  # 64 − 256·0.1 = 38.4 > cold's 0
        prefixes=[(prefix_digest(PROMPT[:64]), 64), (prefix_digest(PROMPT[:32]), 32)],
    )
    cold = _FakeReplica("cold", load=0.0)
    router = _router([cold, warm])
    decision = router.route(PROMPT)
    assert decision.replica_id == "warm"
    assert decision.kind == "affinity"
    assert decision.expected_match == 64
    assert router.routed_affinity_total == 1


def test_lambda_trades_cache_against_load():
    """A hot matching replica loses to an idle cold one once λ·load exceeds
    the expected match — and wins again with a smaller λ."""
    hot = _FakeReplica("hot", load=1.0, prefixes=[(prefix_digest(PROMPT[:32]), 32)])
    idle = _FakeReplica("idle", load=0.0)
    strict = _router([hot, idle], lam=256.0)  # 32 − 256 < 0 − 0
    assert strict.route(PROMPT).replica_id == "idle"
    loose = _router([hot, idle], lam=16.0)  # 32 − 16 > 0
    assert loose.route(PROMPT).replica_id == "hot"


def test_no_match_falls_back_to_least_loaded():
    r1 = _FakeReplica("r1", load=0.8)
    r2 = _FakeReplica("r2", load=0.1)
    router = _router([r1, r2])
    decision = router.route(PROMPT)
    assert decision.replica_id == "r2"
    assert decision.kind == "balanced"
    assert decision.expected_match == 0
    assert router.routed_balanced_total == 1


def test_sticky_session_pins_replica_until_it_dies():
    a = _FakeReplica("a", load=0.5)
    b = _FakeReplica("b", load=0.0)
    router = _router([a, b], fail_cooldown_s=60.0)
    first = router.route(PROMPT, session_id="s1")
    assert first.replica_id == "b"  # least-loaded wins the first route
    # b becomes the WORSE choice, but the session sticks to it
    b.load, a.load = 2.0, 0.0
    router.refresh_all()
    held = router.route(PROMPT, session_id="s1")
    assert held.replica_id == "b" and held.kind == "sticky"
    # replica death: the sticky session fails over cold
    router.mark_failed("b")
    moved = router.route(PROMPT, session_id="s1")
    assert moved.replica_id == "a"
    # and re-pins to the survivor
    assert router.route(PROMPT, session_id="s1").replica_id == "a"


def test_sticky_ttl_expires_on_lookup():
    """An idle session past fleet-sticky-ttl-s re-routes by score (its
    pages are likely evicted by then) instead of staying pinned forever."""
    a = _FakeReplica("a", load=0.0)
    b = _FakeReplica("b", load=0.5)
    router = _router([a, b], sticky_ttl_s=0.05)
    assert router.route(PROMPT, session_id="s").replica_id == "a"
    a.load, b.load = 2.0, 0.0
    router.refresh_all()
    time.sleep(0.1)  # session idles past its TTL
    moved = router.route(PROMPT, session_id="s")
    assert moved.replica_id == "b"
    assert moved.kind == "balanced"


def test_bad_request_does_not_quarantine_replica():
    """A request the engine REJECTS (ValueError) must propagate to the
    caller, not convert into ReplicaError — a malformed request retried
    across the fleet would otherwise mark every replica failed."""
    engine = make_engine()
    try:
        replica = InProcessReplica("r", engine)
        with pytest.raises(ValueError):
            replica.generate([], {"max-tokens": 4})  # no prompt tokens
        router = FleetRouter([replica], refresh_interval_s=3600.0)
        router.refresh_all()
        with pytest.raises(ValueError):
            router.generate([], {"max-tokens": 4})
        # the replica is still routable — nothing was quarantined
        assert router.route(PROMPT).replica_id == "r"
        assert router.failover_total == 0
    finally:
        engine.stop()


def test_drain_quarantine_and_stale_beacons_are_unroutable():
    ok = _FakeReplica("ok")
    draining = _FakeReplica("draining", draining=True)
    dead = _FakeReplica("dead", quarantined=True)
    router = _router([draining, dead, ok])
    for _ in range(4):
        assert router.route(PROMPT).replica_id == "ok"
    # staleness: age the good beacon out and nothing is routable
    router._replicas["ok"].beacon_at = time.monotonic() - 1e6
    with pytest.raises(FleetShedError):
        router.route(PROMPT)


def test_fleet_sheds_on_replica_exported_signals():
    """Shedding keys off the replicas' OWN queue-full / queue-wait-EMA
    exports, not a router-side request cap."""
    full1 = _FakeReplica("f1", queued=16, queue_depth=16, queue_wait_ema_s=2.5)
    full2 = _FakeReplica("f2", queued=20, queue_depth=16, queue_wait_ema_s=4.0)
    router = _router([full1, full2])
    with pytest.raises(FleetShedError) as e:
        router.route(PROMPT)
    assert e.value.retry_after_s == pytest.approx(2.5)
    assert router.shed_total == 1
    # one replica drains its queue → routable again
    full1.beacon_extra["queued"] = 0
    router.refresh_all()
    assert router.route(PROMPT).replica_id == "f1"


def test_round_robin_policy_cycles():
    reps = [_FakeReplica(f"r{i}") for i in range(3)]
    router = _router(reps, policy="round-robin")
    seen = [router.route(PROMPT).replica_id for _ in range(6)]
    assert seen == ["r0", "r1", "r2", "r0", "r1", "r2"]
    assert router.routed_affinity_total == 0


def test_generate_fails_over_on_replica_error():
    bad = _FakeReplica("bad", prefixes=[(prefix_digest(PROMPT[:32]), 32)])
    bad.fail_with = ReplicaError("boom")
    good = _FakeReplica("good")
    router = _router([bad, good])
    out, decision = router.generate(PROMPT)
    assert decision.replica_id == "good"
    assert out["finish_reason"] == "length"
    assert router.failover_total == 1
    # the failed replica is quarantined until a FRESH beacon readmits it
    assert router.route(PROMPT).replica_id == "good"


def test_generate_raises_when_everyone_sheds():
    r1 = _FakeReplica("r1")
    r2 = _FakeReplica("r2")
    r1.fail_with = FleetShedError("busy", retry_after_s=0.7)
    r2.fail_with = FleetShedError("busy", retry_after_s=0.3)
    router = _router([r1, r2])
    with pytest.raises(FleetShedError):
        router.generate(PROMPT)


def test_autoscale_hint_from_queue_wait_ema():
    reps = [
        _FakeReplica("r0", queue_wait_ema_s=2.0),
        _FakeReplica("r1", queue_wait_ema_s=2.0),
    ]
    router = _router(reps)
    # 2s mean wait vs 0.5s target → 4× (capped) → 8 desired
    assert router.desired_replicas(target_queue_wait_s=0.5) == 8
    assert router.desired_replicas(target_queue_wait_s=0.5, max_replicas=3) == 3
    # idle fleet scales IN one at a time
    for r in reps:
        r.beacon_extra["queue_wait_ema_s"] = 0.0
    router.refresh_all()
    assert router.desired_replicas(target_queue_wait_s=0.5) == 1
    # no routable beacons → hold current size, never scale blind
    for s in router._replicas.values():
        s.beacon_at = -1e18
    assert router.desired_replicas() == 2


def test_router_stats_and_dispatch_histogram():
    router = _router([_FakeReplica("r0"), _FakeReplica("r1")])
    for _ in range(32):
        router.route(PROMPT)
    stats = router.stats()
    assert stats["fleet-replica-count"] == 2
    assert stats["fleet-routed-balanced-total"] == 32
    assert stats["fleet-dispatch-p50-ms"] < 1.0, "route() must stay sub-ms"
    json.dumps(stats)


def test_k8s_statefulset_honors_autoscale_hint():
    from langstream_tpu.k8s.crds import AgentCustomResource
    from langstream_tpu.k8s.resources import AgentResourcesFactory

    def agent(autoscale=None, status=None):
        return AgentCustomResource(
            name="a", namespace="ns", tenant="t", agent_id="a",
            application_id="app", agent_type="ai-chat-completions",
            component_type="PROCESSOR", config_secret_ref="s",
            config_checksum="c", parallelism=2,
            autoscale=autoscale, status=status or {},
        )

    consumers = AgentResourcesFactory.fleet_consumers
    assert consumers(agent()) == 2  # no autoscale: spec parallelism
    hinted = {"fleet": {"desiredReplicas": 6}}
    # hint ignored unless autoscale is enabled
    assert consumers(agent(status=hinted)) == 2
    auto = {"enabled": True, "min-replicas": 1, "max-replicas": 4}
    assert consumers(agent(autoscale=auto, status=hinted)) == 4  # clamped
    assert consumers(agent(autoscale=auto, status={"fleet": {"desiredReplicas": 3}})) == 3
    assert consumers(agent(autoscale=auto, status={"fleet": {"desiredReplicas": 0}})) == 1
    assert consumers(agent(autoscale=auto)) == 2  # enabled but no hint yet
    # the CR round-trips the autoscale block
    rt = AgentCustomResource.from_manifest(agent(autoscale=auto).to_manifest())
    assert rt.autoscale == auto


def test_fleet_autoscale_reconciler_writes_hint():
    """The ops loop (ROADMAP 3c): FleetAutoscaleReconciler reads
    desired_replicas() and writes status.fleet.desiredReplicas — the field
    the StatefulSet already honors but nothing computed in-cluster. No-op
    patches are skipped (no self-triggered watch storms), other status
    fields survive, and the STS replica count follows the hint."""
    from langstream_tpu.k8s.crds import AgentCustomResource
    from langstream_tpu.k8s.fake import FakeKubeServer
    from langstream_tpu.k8s.resources import (
        AgentResourcesFactory,
        FleetAutoscaleReconciler,
    )

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
    # record the patch bodies: the reconciler must send ONLY the fleet
    # subtree, so the real client's merge-patch can never clobber status
    # fields another controller wrote between read and write
    patches: list = []
    real_patch = kube.patch_status

    def recording_patch(kind, ns, name, status):
        patches.append(status)
        return real_patch(kind, ns, name, status)

    kube.patch_status = recording_patch

    desired = {"n": 5}
    rec = FleetAutoscaleReconciler(
        kube, lambda: desired["n"], namespace="ns", name="a",
    )
    assert rec.reconcile_once() == 5
    assert patches == [{"fleet": {"desiredReplicas": 5}}], (
        "patch must be the narrow fleet subtree (merge-patch safety)"
    )
    manifest = kube.get(AgentCustomResource.KIND, "ns", "a")
    assert manifest["status"]["fleet"]["desiredReplicas"] == 5
    rv = manifest["metadata"]["resourceVersion"]

    # unchanged hint → NO patch (resourceVersion must not move)
    assert rec.reconcile_once() is None
    assert rec.skipped_total == 1
    assert (
        kube.get(AgentCustomResource.KIND, "ns", "a")["metadata"][
            "resourceVersion"
        ]
        == rv
    )

    # the hint the reconciler wrote drives the StatefulSet replica count
    updated = AgentCustomResource.from_manifest(manifest)
    assert AgentResourcesFactory.fleet_consumers(updated) == 5

    # hint moves → patched again; an API blip or vanished CR is a no-op
    # for this tick, never a reconciler-thread death
    desired["n"] = 3
    assert rec.reconcile_once() == 3
    real_get = kube.get

    def failing_get(*a, **k):
        raise RuntimeError("apiserver 503")

    kube.get = failing_get
    desired["n"] = 9
    assert rec.reconcile_once() is None
    kube.get = real_get
    kube.delete(AgentCustomResource.KIND, "ns", "a")
    desired["n"] = 7
    assert rec.reconcile_once() is None
    assert rec.patches_total == 2


# ---------------------------------------------------------------------------
# Beacon schema + redaction
# ---------------------------------------------------------------------------


def test_beacon_schema_rejects_token_content():
    doc = _FakeReplica("r", prefixes=[(prefix_digest(PROMPT[:32]), 32)]).fetch_beacon()
    assert validate_beacon(doc)
    with pytest.raises(ValueError):
        validate_beacon({**doc, "tokens": [1, 2, 3]})
    with pytest.raises(ValueError):
        validate_beacon({**doc, "prefixes": [["abc", "32"]]})  # length not int
    with pytest.raises(ValueError):
        validate_beacon({**doc, "schema": "nope"})
    # hibernated advertisements (tiered KV, §16) validate under the same
    # [digest, length] shape — and the same token-content redaction
    assert validate_beacon(
        {**doc, "spilled_prefixes": [[prefix_digest(PROMPT[:64]), 64]]}
    )
    with pytest.raises(ValueError):
        validate_beacon({**doc, "spilled_prefixes": [["abc", "64", "x"]]})


# ---------------------------------------------------------------------------
# hibernated-session routing (tiered KV, docs/SERVING.md §16)
# ---------------------------------------------------------------------------


def test_hibernated_session_routes_to_owner():
    """ISSUE-11 satellite: a session whose KV was spilled to the owner's
    host tier must STILL route to that owner — a discounted restore beats
    a cold re-prefill anywhere else — so sticky routing survives
    hibernation."""
    owner = _FakeReplica(
        "owner", load=0.0,
        spilled_prefixes=[[prefix_digest(PROMPT[:64]), 64]],
    )
    cold = _FakeReplica("cold", load=0.0)
    router = _router([cold, owner])
    decision = router.route(PROMPT)
    assert decision.replica_id == "owner"
    assert decision.kind == "affinity"
    # the discounted match is what the decision carries: a restore is
    # cheaper than a re-prefill but not free
    assert decision.expected_match == int(64 * router.spill_discount)


def test_spill_discount_trades_hibernated_against_resident():
    """The discount knob: a device-resident 32-token match beats a
    hibernated 64-token one at discount 0.25 (16 effective), loses at
    par (1.0), and a discount of 0 ignores hibernated advertisements
    entirely."""
    resident = _FakeReplica(
        "resident", load=0.0, prefixes=[(prefix_digest(PROMPT[:32]), 32)],
    )
    hibernated = _FakeReplica(
        "hibernated", load=0.0,
        spilled_prefixes=[[prefix_digest(PROMPT[:64]), 64]],
    )
    assert _router(
        [resident, hibernated], spill_discount=0.25
    ).route(PROMPT).replica_id == "resident"
    assert _router(
        [resident, hibernated], spill_discount=1.0
    ).route(PROMPT).replica_id == "hibernated"
    only_spilled = _router([hibernated], spill_discount=0.0)
    decision = only_spilled.route(PROMPT)
    assert decision.kind == "balanced" and decision.expected_match == 0


def test_beacon_splits_resident_and_hibernated_digests():
    """beacon_from_engine must advertise a hibernated prefix under
    `spilled_prefixes` (and move it back to `prefixes` after a restore):
    the fleet's view of the tier tracks the engine's."""
    import time as _time

    engine = make_engine(
        page_size=16, kv_pages=5,
        prefix_cache_entries=8, host_kv_fraction=2.0, spill_idle_s=0.0,
    )
    try:
        prompt_a = [(7 + 3 * i) % CFG.vocab_size for i in range(45)]
        prompt_b = [(5 + 11 * i) % CFG.vocab_size for i in range(45)]
        engine.generate(prompt_a, GREEDY, timeout=120)
        deadline = _time.monotonic() + 30
        while (
            _time.monotonic() < deadline
            and engine.stats()["spill-pages-total"] < 2
        ):
            _time.sleep(0.02)
        # B's admission demotes A's hibernated prefix off the device pool
        engine.generate(prompt_b, GREEDY, timeout=120)
        doc = beacon_from_engine("r0", engine)
        assert validate_beacon(doc)
        dig_a = prefix_digest(prompt_a[:32])
        assert [dig_a, 32] in doc["spilled_prefixes"], doc
        assert [dig_a, 32] not in doc["prefixes"]
        assert any(n == 32 for _, n in doc["prefixes"])  # B stays resident
        # next turn restores A: the digest moves back to the resident list
        engine.generate(prompt_a, GREEDY, timeout=120)
        assert engine.stats()["restored-hits-total"] == 1
        doc = beacon_from_engine("r0", engine)
        assert [dig_a, 32] in doc["prefixes"]
        assert [dig_a, 32] not in doc["spilled_prefixes"]
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# 2-replica in-process e2e
# ---------------------------------------------------------------------------


def _burst(router, prompts, session_ids=None, timeout_s=120.0):
    """Dispatch all prompts concurrently through the router (one thread
    each, like the gateway's executor) and return (results, errors)."""
    results, errors = [None] * len(prompts), [None] * len(prompts)

    def run(i):
        try:
            results[i] = router.generate(
                prompts[i],
                {"max-tokens": 8, "temperature": 0.0},
                session_id=(session_ids or {}).get(i),
                timeout_s=timeout_s,
            )
        except BaseException as e:  # noqa: BLE001 — collected for asserts
            errors[i] = e

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout_s)
    assert not any(t.is_alive() for t in threads), "hung fleet request"
    return results, errors


def test_two_replica_affinity_e2e():
    """Shared-preamble burst over two replicas: after the first (cold,
    balanced) admission publishes the preamble, every later request with
    that preamble routes AFFINITY to the same replica and reuses its
    pages; the other replica never sees them."""
    e1, e2 = make_engine(), make_engine()
    try:
        router = FleetRouter(
            [InProcessReplica("r1", e1), InProcessReplica("r2", e2)],
            refresh_interval_s=3600.0,
            # λ scaled to the tiny config: boundaries here are 32 tokens
            # where production preambles are 1k+, so the default 256
            # tokens-per-load-unit would let the owner's held prefix pages
            # (page pressure ≈ 0.2) outweigh its own warm cache
            lam=64.0,
        )
        router.refresh_all()
        preamble = [5 + i % 50 for i in range(40)]
        cold, first = router.generate(preamble + [100], {"max-tokens": 8, "temperature": 0.0})
        assert cold["finish_reason"] in ("length", "stop")
        router.refresh_all()  # pick up the published prefix digests
        owner = first.replica_id
        decisions = []
        for suffix in range(101, 107):
            out, decision = router.generate(
                preamble + [suffix], {"max-tokens": 8, "temperature": 0.0}
            )
            assert out["finish_reason"] in ("length", "stop")
            decisions.append(decision)
        assert all(d.replica_id == owner for d in decisions), (
            "shared-preamble requests scattered off the warm replica"
        )
        assert all(d.kind == "affinity" for d in decisions)
        assert all(d.expected_match >= 32 for d in decisions)
        owner_engine = e1 if owner == "r1" else e2
        other_engine = e2 if owner == "r1" else e1
        assert owner_engine.stats()["prefill-tokens-saved-total"] > 0
        assert other_engine.stats()["total-requests"] <= 1
        assert router.routed_affinity_total == 6
    finally:
        e1.stop()
        e2.stop()


def test_sticky_session_e2e_and_beacon_validates():
    e1, e2 = make_engine(), make_engine()
    try:
        router = FleetRouter(
            [InProcessReplica("r1", e1), InProcessReplica("r2", e2)],
            refresh_interval_s=3600.0,
        )
        router.refresh_all()
        assert validate_beacon(beacon_from_engine("r1", e1))
        # distinct prompts (no shared prefix) in one session stay together
        seen = set()
        for turn in range(4):
            prompt = [(37 * (turn + 1) + i) % 50 for i in range(20 + turn)]
            _, decision = router.generate(
                prompt, {"max-tokens": 4, "temperature": 0.0}, session_id="chat-1"
            )
            seen.add(decision.replica_id)
        assert len(seen) == 1
        assert router.routed_sticky_total >= 3
    finally:
        e1.stop()
        e2.stop()


def test_replica_death_mid_burst_fails_over_with_zero_hangs():
    """The chaos drill (tier-1 chaos step, LSTPU_FAULT_SEED pinned): one
    replica runs the ``client`` stall site so requests are IN FLIGHT when
    it dies mid-burst. Every request must still complete on the survivor —
    re-routed, failed over cold, nothing hung, engine B healthy."""
    injector = FaultInjector("client@1+", seed=0, stall_s=0.2)
    dying = make_engine(fault_injector=injector)
    survivor = make_engine()
    try:
        router = FleetRouter(
            [InProcessReplica("dying", dying), InProcessReplica("ok", survivor)],
            refresh_interval_s=3600.0,
            fail_cooldown_s=3600.0,  # no readmission during the drill
        )
        router.refresh_all()
        prompts = [[9 + i % 40 for i in range(30)] + [200 + j] for j in range(6)]
        killer_fired = threading.Event()

        def kill_when_busy():
            # wait until the stalling replica actually holds in-flight work
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if dying.stats()["active-slots"] > 0:
                    break
                time.sleep(0.01)
            dying.stop()
            killer_fired.set()

        killer = threading.Thread(target=kill_when_busy)
        killer.start()
        results, errors = _burst(router, prompts)
        killer.join(timeout=30)
        assert killer_fired.is_set()
        assert all(e is None for e in errors), f"requests failed: {errors}"
        assert all(r is not None for r in results)
        for out, _decision in results:
            assert len(out["tokens"]) > 0
        # every request ultimately completed on a live replica; anything
        # the dead one dropped was re-routed (failover counted when the
        # death raced an in-flight dispatch)
        assert survivor.stats()["total-requests"] >= 1
        # the stalled burst can outlive the 10s beacon TTL on a slow box,
        # and this router runs no refresh loop (interval 3600, by-hand
        # refreshes) — refresh like production would have, THEN assert
        # the survivor is the one routable replica
        router.refresh_all()
        assert router.route(prompts[0]).replica_id == "ok"
    finally:
        dying.stop()
        survivor.stop()


# ---------------------------------------------------------------------------
# HTTP transport ring: /state + /fleet/generate via RuntimeHttpServer
# ---------------------------------------------------------------------------


def test_http_state_and_generate_roundtrip():
    import asyncio

    from langstream_tpu.runtime.http_server import RuntimeHttpServer
    from langstream_tpu.serving import fleet as fleet_mod

    engine = make_engine()
    fleet_mod.register_local(
        "pod-0",
        beacon_fn=lambda: beacon_from_engine("pod-0", engine),
        generate_fn=lambda payload: fleet_mod.engine_generate(engine, payload),
        reset_fn=engine.reset_histograms,
    )
    loop = asyncio.new_event_loop()
    server = RuntimeHttpServer(
        metrics_text=lambda: "", agents_info=lambda: [], port=0
    )
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    try:
        asyncio.run_coroutine_threadsafe(server.start(), loop).result(10)
        replica = HttpReplica("pod-0", server.url)
        beacon = replica.fetch_beacon()
        assert validate_beacon(beacon)
        assert beacon["id"] == "pod-0"
        # a 400 (bad request) surfaces as ValueError, never ReplicaError
        with pytest.raises(ValueError):
            replica.generate([], {"max-tokens": 4})
        # warm the prefix index through the HTTP dispatch path
        preamble = [4 + i % 30 for i in range(40)]
        out = replica.generate(preamble + [1], {"max-tokens": 4, "temperature": 0.0})
        assert len(out["tokens"]) == 4
        beacon = replica.fetch_beacon()
        assert beacon["prefixes"], "published prefix missing from beacon"
        digests = {d for d, _n in beacon["prefixes"]}
        assert prefix_digest(preamble[:32]) in digests
        # histogram reset endpoint (bench warmup hygiene)
        assert engine.stats()["histograms"]["engine_ttft_s"]["count"] > 0
        replica.reset_histograms()
        assert engine.stats()["histograms"]["engine_ttft_s"]["count"] == 0
        # a router over the HTTP transport routes affinity to this pod
        router = FleetRouter([replica], refresh_interval_s=3600.0)
        router.refresh_all()
        decision = router.route(preamble + [2])
        assert decision.kind == "affinity"
    finally:
        fleet_mod.unregister_local("pod-0")
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=10)
        loop.close()
        engine.stop()


@pytest.mark.slow
def test_cross_process_fleet_cancel_e2e():
    """ROADMAP 3b end-to-end, REAL process boundary: a session's request
    fleet-routed to a subprocess replica dies at the next chunk boundary
    when the gateway-side lifecycle.cancel() fires — the cancel-key rides
    the dispatch payload into the peer's process-local registry
    (fleet.engine_generate), the owning replica URL is recorded on the
    gateway side (register_remote, what _fleet_dispatch does), and the
    forwarded POST /fleet/cancel resolves the remote decode with
    finish_reason=cancelled long before its deadline. Marked slow (one
    subprocess engine build); the chaos CI step runs it."""
    import json as _json
    import os
    import subprocess
    import sys

    from langstream_tpu.serving import lifecycle

    config = {
        "model": "tiny-test",
        "max-batch": 2,
        "max-seq-len": 256,
        "prefill-buckets": (16, 32),
        "decode-chunk": 4,
        # the client stall site slows token delivery so the generation is
        # still mid-decode when the cancel lands (50 ms × 200 tokens ≈ 10 s)
        "fault-injection": "client@1+",
        "fault-seed": 0,
        "fault-stall-s": 0.05,
        "fleet-replica-id": "peer-0",
    }
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("LSTPU_FAULTS", None)
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "langstream_tpu.serving.fleet",
            "--config", _json.dumps(config),
        ],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True,
    )
    done: list = []
    try:
        line = proc.stdout.readline()
        assert line, "replica died before serving"
        url = _json.loads(line)["url"]
        replica = HttpReplica("peer-0", url)
        session = "sess-cancel-e2e"
        # what TpuCompletionsService._fleet_dispatch does around a remote
        # route: record the owner, ship the cancel-key with the options
        lifecycle.register_remote(session, url)
        options = {
            "max-tokens": 200, "temperature": 0.0, "deadline": 120.0,
            "cancel-key": session,
        }

        def dispatch():
            done.append(replica.generate([5, 6, 7], options, timeout_s=120.0))

        t0 = time.monotonic()
        worker = threading.Thread(target=dispatch, daemon=True)
        worker.start()
        # wait until the peer is actually mid-decode (its beacon exports
        # active slots), then "disconnect": gateway-side cancel forwards
        deadline = time.monotonic() + 30
        while True:
            assert time.monotonic() < deadline, "request never went active"
            try:
                if replica.fetch_beacon().get("active_slots", 0) > 0:
                    break
            except ReplicaError:
                pass
            time.sleep(0.05)
        assert lifecycle.cancel(session) == 0  # nothing LOCAL to cancel
        worker.join(timeout=30)
        assert not worker.is_alive(), "remote decode did not die on cancel"
        assert done and done[0]["finish_reason"] == "cancelled"
        took = time.monotonic() - t0
        assert took < 30, f"cancel took {took:.1f}s — deadline-ish, not prompt"
        assert len(done[0]["tokens"]) < 200, "generation ran to completion"
        lifecycle.unregister_remote(session, url)
        # endpoint hygiene: a missing session is a 400, not a crash
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            url + "/fleet/cancel", data=b"{}",
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=5)
        assert err.value.code == 400
    finally:
        try:
            proc.stdin.close()
        except OSError:
            pass
        try:
            proc.wait(timeout=30)
        except Exception:  # noqa: BLE001 — last resort
            proc.kill()


def test_http_replica_maps_429_to_shed():
    import asyncio

    from langstream_tpu.runtime.http_server import RuntimeHttpServer
    from langstream_tpu.serving import fleet as fleet_mod

    def shedding_generate(payload):
        raise FleetShedError("full", retry_after_s=2.5)

    fleet_mod.register_local(
        "pod-shed", beacon_fn=lambda: {"schema": BEACON_SCHEMA, "id": "pod-shed"},
        generate_fn=shedding_generate,
    )
    loop = asyncio.new_event_loop()
    server = RuntimeHttpServer(metrics_text=lambda: "", agents_info=lambda: [], port=0)
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    try:
        asyncio.run_coroutine_threadsafe(server.start(), loop).result(10)
        replica = HttpReplica("pod-shed", server.url)
        with pytest.raises(FleetShedError) as e:
            replica.generate([1, 2, 3], {})
        assert e.value.retry_after_s == pytest.approx(2.5)
        # a DEAD server is a ReplicaError (failover), not a shed
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        with pytest.raises(ReplicaError):
            replica.generate([1, 2, 3], {})
        with pytest.raises(ReplicaError):
            replica.fetch_beacon()
    finally:
        fleet_mod.unregister_local("pod-shed")
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=10)
        loop.close()


def test_completions_service_fleet_auto_routes_local_and_remote():
    """The gateway/completions integration: with `fleet: auto`, a request
    whose preamble is hot on a PEER replica dispatches there over HTTP
    (the local engine never sees it); a cold request runs the normal local
    streaming path. This is the `fleet` knob end to end."""
    import asyncio

    from langstream_tpu.ai.provider import ChatChunk
    from langstream_tpu.ai.tpu_serving import TpuServingProvider
    from langstream_tpu.runtime.http_server import RuntimeHttpServer
    from langstream_tpu.serving import fleet as fleet_mod
    from langstream_tpu.serving.tokenizer import get_tokenizer

    peer_engine = make_engine()
    fleet_mod.register_local(
        "peer",
        beacon_fn=lambda: beacon_from_engine("peer", peer_engine),
        generate_fn=lambda payload: fleet_mod.engine_generate(
            peer_engine, payload
        ),
    )
    loop = asyncio.new_event_loop()
    server = RuntimeHttpServer(metrics_text=lambda: "", agents_info=lambda: [], port=0)
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    provider = None
    try:
        asyncio.run_coroutine_threadsafe(server.start(), loop).result(10)
        tok = get_tokenizer("byte")
        preamble_text = "You are a terse assistant. Answer briefly."  # 42 tokens
        # warm the PEER with the preamble so its beacon advertises it
        peer_engine.generate(
            tok.encode(preamble_text + " hi"),
            GenerationOptions(max_new_tokens=4, temperature=0.0),
        )
        provider = TpuServingProvider(
            {
                "model": "tiny-test",
                "max-batch": 2,
                "max-seq-len": 128,
                "prefill-buckets": (16, 32, 64),
                "decode-chunk": 4,
                "prefix-cache": "auto",
                "fleet": "auto",
                "fleet-replica-id": "front",
                "fleet-replicas": [{"id": "peer", "url": server.url}],
                "fleet-lambda": 16.0,
                "fleet-refresh-interval-s": 3600.0,
            }
        )
        service = provider.get_completions_service({})
        local_engine = provider.holder.engine()
        provider.holder.fleet_router().refresh_all()

        chunks: list[ChatChunk] = []
        result = asyncio.run_coroutine_threadsafe(
            service.get_text_completions(
                [preamble_text + " one"],
                {"max-tokens": 4, "temperature": 0.0},
                chunks.append,
            ),
            loop,
        ).result(120)
        assert result.completion_tokens == 4
        assert chunks and chunks[-1].last
        assert peer_engine.stats()["total-requests"] >= 2, "peer never served"
        assert local_engine.stats()["total-requests"] == 0
        router_stats = provider.holder.fleet_router().stats()
        assert router_stats["fleet-routed-affinity-total"] >= 1
        # a cold prompt (no affinity anywhere) stays LOCAL and streams
        peer_before = peer_engine.stats()["total-requests"]
        result2 = asyncio.run_coroutine_threadsafe(
            service.get_text_completions(
                ["completely different question"],
                {"max-tokens": 4, "temperature": 0.0},
                chunks.append,
            ),
            loop,
        ).result(120)
        assert result2.completion_tokens == 4
        assert (
            local_engine.stats()["total-requests"]
            + (peer_engine.stats()["total-requests"] - peer_before)
            == 1
        ), "cold request ran exactly once somewhere"
    finally:
        if provider is not None:
            asyncio.run_coroutine_threadsafe(provider.close(), loop).result(60)
        fleet_mod.unregister_local("peer")
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=10)
        loop.close()
        peer_engine.stop()


# ---------------------------------------------------------------------------
# Persistent compile cache: fleet fast cold start
# ---------------------------------------------------------------------------


def test_compile_cache_warm_dir_compiles_zero_new_programs(tmp_path, monkeypatch):
    """The scale-up story: engine #1 populates the cache dir; engine #2
    (fresh jit closures — normally a full recompile) must add ZERO new
    cache entries and register at least one persistent-cache hit."""
    from langstream_tpu.ai.tpu_serving import _EngineHolder

    # the knob decides only where the variable is unset (the variable's own
    # case is tests/test_compile_cache.py) — whatever the caller exported
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir = tmp_path / "xla-cache"
    config = {
        "model": "tiny-test",
        "compile-cache-dir": str(cache_dir),
        "max-batch": 2,
        "max-seq-len": 64,
        "prefill-buckets": (16, 32),
        "decode-chunk": 4,
    }
    hits: list[str] = []

    def listener(event: str, **kw) -> None:
        if "compilation_cache/cache_hits" in event:
            hits.append(event)

    jax.monitoring.register_event_listener(listener)
    try:
        h1 = _EngineHolder(dict(config))
        e1 = h1.engine()
        e1.generate([3, 4, 5], GenerationOptions(max_new_tokens=4, temperature=0.0))
        h1.close()
        files_after_first = set(cache_dir.iterdir())
        assert files_after_first, "first engine populated no cache entries"
        hits.clear()
        h2 = _EngineHolder(dict(config))
        e2 = h2.engine()
        e2.generate([3, 4, 5], GenerationOptions(max_new_tokens=4, temperature=0.0))
        h2.close()
        new_files = set(cache_dir.iterdir()) - files_after_first
        assert not new_files, (
            f"second engine construction compiled {len(new_files)} new "
            f"program(s) despite the warm cache dir"
        )
        assert hits, "no persistent-cache hits recorded on the warm build"
    finally:
        # conftest's _restore_compile_cache puts the cache settings back
        jax.monitoring.unregister_event_listener(listener)

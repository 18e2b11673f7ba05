"""Durable session tier tests (ROADMAP 2b/3b / ISSUE 18): crash-safe KV
checkpoints on disk, replica hibernation + resurrection. The contracts
proven here:

  - CRASH-SAFE BY CONSTRUCTION: a checkpoint torn at ANY write phase
    (pre-temp, mid-frame, pre-rename, post-rename, mid-manifest) reads as
    restore-or-clean-cold-start — never wrong KV, never a hang. Torn,
    truncated and CRC-flipped files read as DEAD ENTRIES.
  - ROT IS NEVER LAUNDERED: restore verifies against the SPILL-TIME
    checksums in the manifest; a stale manifest or flipped byte kills the
    entry instead of re-hashing it into validity.
  - RESURRECTION IS TOKEN-EXACT: a session checkpointed on replica A and
    restored on a cold replica B (same durable dir) generates
    byte-identically to an uninterrupted run.
  - EVERY FAILURE DEGRADES: the disk-torn/disk-corrupt/disk-stall/
    disk-full fault sites each end in a local cold prefill with one
    schema-valid ``durable-restore-failed`` flight dump, zero engine
    restarts, both free lists leak-asserted.
  - SCALE-TO-ZERO IS GATED: the router emits desired=0 only when demand
    is quiet AND every routable replica advertises the ``durable`` cap.

CI pins LSTPU_FAULT_SEED (tier1.yml chaos step); the tests pass explicit
seeds anyway so they are deterministic in any environment.
"""

import dataclasses
import hashlib
import json
import os
import signal
import subprocess
import sys
import time

import jax
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.serving import wire
from langstream_tpu.serving.durable import (
    DATA_SUFFIX,
    HIBERNATE_NAME,
    MANIFEST_SUFFIX,
    DurableError,
    DurableStore,
)
from langstream_tpu.serving.engine import ServingEngine
from langstream_tpu.serving.faultinject import FaultInjector
from langstream_tpu.serving.fleet import (
    BEACON_SCHEMA,
    FleetRouter,
    ReplicaError,
    local_prefetch,
    register_local_router,
    unregister_local_router,
)
from langstream_tpu.serving.pagepool import prefix_digest

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))

GREEDY = GenerationOptions(max_new_tokens=10, temperature=0.0)

# 45-token sessions over the 16/32/64 bucket ladder at page_size=16: each
# publishes a 32-token (2-page) prefix — the unit the tier checkpoints
PROMPT_A = [(7 + 3 * i) % CFG.vocab_size for i in range(45)]
PROMPT_B = [(5 + 11 * i) % CFG.vocab_size for i in range(45)]


# ---------------------------------------------------------------------------
# Store helpers (no engine, no jax — synthetic page images)
# ---------------------------------------------------------------------------


def _raw_pages(n=2, nbytes=96, seed=0):
    pages = [
        bytes((seed + 13 * i + j) % 256 for j in range(nbytes))
        for i in range(n)
    ]
    sums = [
        hashlib.blake2b(p, digest_size=16).hexdigest() for p in pages
    ]
    return pages, sums


def _write_checkpoint(store, digest="aa" * 8, n=2, length=32, seed=0):
    pages, sums = _raw_pages(n=n, seed=seed)
    nbytes = store.checkpoint(
        digest, length, list(range(length)), pages, sums,
        page_size=16, bytes_per_page=len(pages[0]),
    )
    return digest, pages, sums, nbytes


def make_engine(durable_dir=None, tier=True, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_buckets", (16, 32, 64))
    kw.setdefault("page_size", 16)
    if tier:
        kw.setdefault("kv_pages", 12)
        kw.setdefault("host_kv_fraction", 2.0)
        kw.setdefault("spill_idle_s", 0.0)  # hibernate as soon as idle
        kw.setdefault("prefix_cache", "auto")
        kw.setdefault("prefix_cache_entries", 8)
    else:
        kw.setdefault("prefix_cache", "off")
        kw.setdefault("host_kv_fraction", 0.0)
    if durable_dir is not None:
        kw.setdefault("durable", "on")
        kw["durable_dir"] = str(durable_dir)
    engine = ServingEngine(CFG, PARAMS, **kw)
    engine.start()
    return engine


def wait_stat(engine, key, want, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if engine.stats()[key] >= want:
            return
        time.sleep(0.02)
    raise AssertionError(
        f"{key} never reached {want}: {engine.stats()[key]}"
    )


def assert_leak_free(engine):
    """The ISSUE bar: after quiesce, dropping every surviving prefix entry
    returns BOTH free lists — device pages and arena slots — to all-free."""
    pool, index, hier = (
        engine._pagepool, engine._prefix_index, engine._host_tier,
    )
    engine._drain_spills()
    for entry in list(index._live):
        index._drop(pool, entry)
    assert pool.free_pages == pool.num_pages, (
        f"device pool leaked {pool.num_pages - pool.free_pages} pages"
    )
    if hier is not None:
        assert hier.free_slots == hier.num_pages, (
            f"host arena leaked {hier.num_pages - hier.free_slots} slots"
        )


# ---------------------------------------------------------------------------
# Store units: roundtrip, codec identity, rehydrate
# ---------------------------------------------------------------------------


def test_checkpoint_restore_roundtrip(tmp_path):
    store = DurableStore(str(tmp_path))
    digest, pages, sums, nbytes = _write_checkpoint(store)
    assert nbytes > 0
    assert store.contains(digest) and len(store) == 1
    assert store.entries() == [(digest, 32)]
    assert store.bytes_on_disk() == nbytes
    rec = store.restore(digest)
    assert rec["length"] == 32
    assert rec["tokens"] == list(range(32))
    assert rec["pages"] == pages
    assert rec["checksums"] == sums
    assert rec["page_size"] == 16
    assert rec["bytes_per_page"] == len(pages[0])
    s = store.stats()
    assert s["durable-checkpoints-total"] == 1
    assert s["durable-checkpoint-bytes-total"] == nbytes
    assert s["durable-restores-total"] == 1
    assert s["durable-restore-bytes-total"] == sum(len(p) for p in pages)
    assert s["durable-restore-failures-total"] == 0
    assert s["durable-dead-entries-total"] == 0


def test_disk_format_is_the_wire_codec(tmp_path):
    """The data file IS a ``lstpu-kvmig-v2`` frame stream: the migration
    decoder parses it directly — the property that lets a durable
    checkpoint serve straight onto the P2P fetch wire."""
    store = DurableStore(str(tmp_path))
    digest, pages, sums, _ = _write_checkpoint(store)
    with open(os.path.join(str(tmp_path), digest + DATA_SUFFIX), "rb") as f:
        assert f.read(len(wire.KVMIG2_PREAMBLE)) == wire.KVMIG2_PREAMBLE
        frames = list(wire.decode_mig_frames(f.read, 1 << 20))
    kinds = [fr["kind"] for fr in frames]
    assert kinds == ["begin", "page", "page", "commit"]
    assert frames[0]["digest"] == digest
    assert frames[0]["prompt_tokens"] == list(range(32))
    assert [fr["raw"] for fr in frames[1:3]] == pages
    assert [fr["checksum"] for fr in frames[1:3]] == sums


def test_rehydrate_rebuilds_index_and_reclaims_debris(tmp_path):
    root = str(tmp_path)
    store = DurableStore(root)
    d1, p1, _, _ = _write_checkpoint(store, digest="11" * 8, seed=1)
    d2, _, _, _ = _write_checkpoint(store, digest="22" * 8, seed=2)
    store.write_hibernation("replica-a", [d1, d2], compile_cache_dir="/cc")
    # debris a crash can leave: an orphan data file (aborted checkpoint),
    # a manifest whose data file vanished, and a stray temp file
    with open(os.path.join(root, "33" * 8 + DATA_SUFFIX), "wb") as f:
        f.write(b"aborted")
    orphan_manifest = {
        "schema": "lstpu-kvdur-v1", "digest": "44" * 8, "length": 32,
        "pages": 1, "page_size": 16, "bytes_per_page": 96, "bytes": 96,
        "checksums": ["00" * 16], "created": 0.0,
    }
    with open(os.path.join(root, "44" * 8 + MANIFEST_SUFFIX), "w") as f:
        json.dump(orphan_manifest, f)
    with open(os.path.join(root, "55" * 8 + DATA_SUFFIX + ".tmp"), "wb") as f:
        f.write(b"torn tmp")

    fresh = DurableStore(root)
    assert fresh.rehydrate() == 2
    assert fresh.contains(d1) and fresh.contains(d2)
    assert not fresh.contains("44" * 8)
    assert fresh.stats()["durable-dead-entries-total"] == 1
    assert not os.path.exists(os.path.join(root, "33" * 8 + DATA_SUFFIX))
    assert not os.path.exists(os.path.join(root, "44" * 8 + MANIFEST_SUFFIX))
    # the live entries actually restore, and the hibernation record held
    assert fresh.restore(d1)["pages"] == p1
    doc = fresh.read_hibernation()
    assert doc["replica"] == "replica-a"
    assert doc["digests"] == sorted([d1, d2])
    assert doc["compile_cache_dir"] == "/cc"


def test_hibernation_record_rejects_foreign_schema(tmp_path):
    store = DurableStore(str(tmp_path))
    assert store.read_hibernation() is None
    with open(os.path.join(str(tmp_path), HIBERNATE_NAME), "w") as f:
        json.dump({"schema": "something-else", "replica": "x"}, f)
    assert store.read_hibernation() is None


# ---------------------------------------------------------------------------
# The SIGKILL durability matrix (simulated): every write phase a kill can
# interrupt must read as restore-or-clean-cold-start
# ---------------------------------------------------------------------------


def _committed_artifacts(tmp_path):
    """One complete checkpoint's bytes, to replay partial write states."""
    staging = tmp_path / "staging"
    store = DurableStore(str(staging))
    digest, pages, sums, _ = _write_checkpoint(store)
    with open(str(staging / (digest + DATA_SUFFIX)), "rb") as f:
        body = f.read()
    with open(str(staging / (digest + MANIFEST_SUFFIX)), "rb") as f:
        manifest = f.read()
    return digest, body, manifest, pages


@pytest.mark.parametrize(
    "phase",
    [
        "pre-temp", "mid-frame", "pre-rename",
        "post-rename-data", "mid-manifest", "committed",
    ],
)
def test_sigkill_matrix_every_phase_restores_or_cold_starts(tmp_path, phase):
    digest, body, manifest, pages = _committed_artifacts(tmp_path)
    root = tmp_path / phase
    root.mkdir()
    data = str(root / (digest + DATA_SUFFIX))
    mpath = str(root / (digest + MANIFEST_SUFFIX))
    if phase == "pre-temp":
        pass  # killed before any byte: empty dir
    elif phase == "mid-frame":
        with open(data + ".tmp", "wb") as f:
            f.write(body[: len(body) * 2 // 3])  # torn inside a page frame
    elif phase == "pre-rename":
        with open(data + ".tmp", "wb") as f:
            f.write(body)  # full body, never renamed
    elif phase == "post-rename-data":
        with open(data, "wb") as f:
            f.write(body)  # data committed, no manifest: aborted
    elif phase == "mid-manifest":
        with open(data, "wb") as f:
            f.write(body)
        with open(mpath + ".tmp", "wb") as f:
            f.write(manifest[: len(manifest) // 2])
    else:  # committed: manifest renamed — the one state that restores
        with open(data, "wb") as f:
            f.write(body)
        with open(mpath, "wb") as f:
            f.write(manifest)

    store = DurableStore(str(root))
    live = store.rehydrate()  # must return promptly — never hang, never raise
    if phase == "committed":
        assert live == 1
        assert store.restore(digest)["pages"] == pages
    else:
        assert live == 0, f"phase {phase} must read as a clean cold start"
        assert not store.contains(digest)
        # aborted data files are reclaimed; temp files are inert
        assert not os.path.exists(data)


def test_torn_corrupt_and_stale_manifest_read_as_dead(tmp_path):
    root = str(tmp_path)
    # torn AFTER boot passed the size check (tear races the index)
    store = DurableStore(root)
    digest, _, _, nbytes = _write_checkpoint(store)
    data = os.path.join(root, digest + DATA_SUFFIX)
    with open(data, "r+b") as f:
        f.truncate(nbytes * 2 // 3)
    with pytest.raises(DurableError):
        store.restore(digest)
    assert not store.contains(digest), "torn entry must die, not retry"
    assert not os.path.exists(data)
    assert store.stats()["durable-restore-failures-total"] == 1

    # CRC flip: one PAGE PAYLOAD byte under a valid manifest (bit rot) —
    # located by image search so the flip is provably inside a frame's
    # CRC-covered region, not the prelude
    digest2, pages2, _, _ = _write_checkpoint(store, digest="bb" * 8, seed=3)
    data2 = os.path.join(root, digest2 + DATA_SUFFIX)
    with open(data2, "r+b") as f:
        body = f.read()
        at = body.index(pages2[0]) + len(pages2[0]) // 2
        f.seek(at)
        f.write(bytes([body[at] ^ 0xFF]))
    with pytest.raises(DurableError):
        store.restore(digest2)
    assert not store.contains(digest2)

    # stale manifest: valid JSON whose stamps don't match the frames
    digest3, _, sums3, _ = _write_checkpoint(store, digest="cc" * 8, seed=4)
    mpath = os.path.join(root, digest3 + MANIFEST_SUFFIX)
    with open(mpath) as f:
        doc = json.load(f)
    doc["checksums"] = list(reversed(sums3))
    with open(mpath, "w") as f:
        json.dump(doc, f)
    fresh = DurableStore(root)
    fresh.rehydrate()
    with pytest.raises(DurableError):
        fresh.restore(digest3)
    assert not fresh.contains(digest3)


def test_eviction_holds_the_disk_cap_lru(tmp_path):
    store = DurableStore(str(tmp_path))
    d1, _, _, nbytes = _write_checkpoint(store, digest="11" * 8, seed=1)
    store.max_bytes = nbytes + nbytes // 2  # room for ONE entry
    time.sleep(0.005)  # distinct created stamps (ms resolution)
    d2, _, _, _ = _write_checkpoint(store, digest="22" * 8, seed=2)
    assert not store.contains(d1), "oldest entry must be the victim"
    assert store.contains(d2)
    assert store.stats()["durable-evictions-total"] == 1
    assert store.bytes_on_disk() <= store.max_bytes
    for suffix in (DATA_SUFFIX, MANIFEST_SUFFIX):
        assert not os.path.exists(os.path.join(str(tmp_path), d1 + suffix))


def test_invalidate_counts_and_empty_stats_parity(tmp_path):
    store = DurableStore(str(tmp_path))
    digest, _, _, _ = _write_checkpoint(store)
    store.invalidate(digest, "caller proved a page bad")
    assert not store.contains(digest)
    s = store.stats()
    assert s["durable-restore-failures-total"] == 1
    assert s["durable-dead-entries-total"] == 1
    empty = DurableStore.empty_stats()
    assert set(empty) == set(s), "tier-off gauges must mirror the live keys"
    assert all(v == 0 for v in empty.values())


@pytest.mark.slow
def test_sigkill_subprocess_leaves_restorable_directory(tmp_path):
    """The real thing: SIGKILL a process mid-checkpoint-loop, then
    rehydrate its directory — every indexed entry restores cleanly and
    the debris of the killed write is reclaimed, not misread."""
    root = str(tmp_path)
    script = (
        "import hashlib, sys\n"
        "from langstream_tpu.serving.durable import DurableStore\n"
        "store = DurableStore(sys.argv[1])\n"
        "i = 0\n"
        "while True:\n"
        "    raw = [bytes((i + j) % 256 for j in range(4096))"
        " for _ in range(3)]\n"
        "    sums = [hashlib.blake2b(r, digest_size=16).hexdigest()"
        " for r in raw]\n"
        "    store.checkpoint(f'{i:016x}', 32, list(range(32)), raw, sums,"
        " 16, 4096)\n"
        "    i += 1\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", script, root],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    try:
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if any(n.endswith(DATA_SUFFIX) for n in os.listdir(root)):
                break
            time.sleep(0.01)
        time.sleep(0.1)  # let it get killed mid-write with high odds
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    store = DurableStore(root)
    live = store.rehydrate()
    assert live >= 1, "the loop committed at least one checkpoint"
    for digest, length in store.entries():
        rec = store.restore(digest)
        assert rec["length"] == length == 32
        assert len(rec["pages"]) == 3
    # no unindexed data files or temp debris survive rehydrate
    leftovers = [
        n for n in os.listdir(root)
        if n.endswith(DATA_SUFFIX) and not store.contains(n[:-len(DATA_SUFFIX)])
    ]
    assert leftovers == []


# ---------------------------------------------------------------------------
# Engine: replica death → resurrection, hibernation, fault drills
# ---------------------------------------------------------------------------


def _cold_reference():
    engine = make_engine(tier=False)
    try:
        return (
            engine.generate(PROMPT_A, GREEDY, timeout=120).tokens,
            engine.generate(PROMPT_B, GREEDY, timeout=120).tokens,
        )
    finally:
        engine.stop()


def test_replica_death_resurrection_token_exact(tmp_path):
    """THE acceptance drill: session A's prefix checkpoints on replica A
    (spill → durable worker), A dies WITHOUT a clean drain, and a cold
    replica B on the same directory serves the next turn token-exact —
    restored from disk, not re-prefilled."""
    cold_a, _ = _cold_reference()
    a = make_engine(durable_dir=tmp_path)
    try:
        first = a.generate(PROMPT_A, GREEDY, timeout=120).tokens
        assert first == cold_a
        wait_stat(a, "durable-checkpoints-total", 1)
        assert a.stats()["durable-entries"] >= 1
    finally:
        a.stop()  # replica death: no hibernate() — the checkpoint already landed

    b = make_engine(durable_dir=tmp_path)
    try:
        stats0 = b.stats()
        assert stats0["durable-tier"] is True
        assert stats0["durable-entries"] >= 1, "B must rehydrate at boot"
        # the rehydrated entry is advertised before any request lands
        _, ads = b.prefix_advertisement()
        assert any(tier == "durable" for _, _, tier in ads)
        got = b.generate(PROMPT_A, GREEDY, timeout=120).tokens
        stats = b.stats()
        assert got == cold_a, "resurrected session diverged"
        assert stats["durable-restored-hits-total"] == 1
        assert stats["durable-restores-total"] == 1
        assert stats["durable-restore-bytes-total"] > 0
        assert stats["durable-restore-failures-total"] == 0
        assert stats["engine-restarts-total"] == 0
        assert_leak_free(b)
    finally:
        b.stop()


def test_hibernate_checkpoints_every_live_session(tmp_path):
    """A clean drain: hibernate() flushes the worker, checkpoints every
    live entry, and writes the replica-level hibernation record."""
    engine = make_engine(durable_dir=tmp_path, kv_pages=16)
    try:
        engine.generate(PROMPT_A, GREEDY, timeout=120)
        engine.generate(PROMPT_B, GREEDY, timeout=120)
        ledger = engine.hibernate("replica-a")
        assert ledger["failures"] == 0
        stats = engine.stats()
        live_digests = {
            e.digest for e in engine._prefix_index._live
            if e.digest and not e.dropped
        }
        assert stats["durable-entries"] >= len(live_digests) > 0
        for d in live_digests:
            assert engine._durable.contains(d)
    finally:
        engine.stop()
    store = DurableStore(str(tmp_path))
    store.rehydrate()
    doc = store.read_hibernation()
    assert doc is not None and doc["replica"] == "replica-a"
    assert set(doc["digests"]) >= set()  # record present and well-formed


def test_disk_corrupt_drill_degrades_to_cold_prefill_with_dump(tmp_path):
    """Bit rot under a valid manifest (pinned seed): replica B's restore
    trips the frame CRC, the entry dies, the request prefills COLD and
    stays token-exact, with one schema-valid durable-restore-failed dump
    — zero restarts, leak-free."""
    from langstream_tpu.serving.observability import validate_flight_dump

    cold_a, _ = _cold_reference()
    a = make_engine(
        durable_dir=tmp_path,
        fault_injector=FaultInjector("disk-corrupt@1", seed=0),
    )
    try:
        a.generate(PROMPT_A, GREEDY, timeout=120)
        wait_stat(a, "durable-checkpoints-total", 1)
        assert a._injector.fired["disk-corrupt"] == 1
    finally:
        a.stop()

    b = make_engine(durable_dir=tmp_path)
    try:
        assert b.stats()["durable-entries"] >= 1  # manifest is valid
        got = b.generate(PROMPT_A, GREEDY, timeout=120).tokens
        stats = b.stats()
        assert got == cold_a, "cold fallback diverged — poisoned KV?"
        assert stats["durable-restored-hits-total"] == 0
        assert stats["durable-restore-failures-total"] >= 1
        assert stats["durable-dead-entries-total"] >= 1
        assert stats["engine-restarts-total"] == 0
        dump = b._obs.flight.last_dump
        assert dump is not None and dump["reason"] == "durable-restore-failed"
        assert validate_flight_dump(dump)
        assert dump["extra"]["fallback"] == "local-cold-prefill"
        assert "tokens" not in dump["extra"], "dumps are token-content-free"
        # the dead entry must not be retried: a second turn restores
        # nothing and re-uses the live (cold-prefilled) entry instead
        again = b.generate(PROMPT_A, GREEDY, timeout=120).tokens
        assert again == cold_a
        assert b.stats()["durable-restore-failures-total"] == stats[
            "durable-restore-failures-total"]
        assert_leak_free(b)
    finally:
        b.stop()


def test_disk_stall_deadline_fires_never_hangs(tmp_path):
    """A hung volume (stall > durable-timeout-s) must surface as a missed
    deadline inside the admission — cold prefill with the dump, never a
    wedged engine thread."""
    cold_a, _ = _cold_reference()
    a = make_engine(durable_dir=tmp_path)
    try:
        a.generate(PROMPT_A, GREEDY, timeout=120)
        wait_stat(a, "durable-checkpoints-total", 1)
    finally:
        a.stop()

    b = make_engine(
        durable_dir=tmp_path,
        durable_timeout_s=0.1,
        fault_injector=FaultInjector(
            "disk-stall@1:1", seed=0, stall_s=0.4,
        ),
    )
    try:
        t0 = time.monotonic()
        got = b.generate(PROMPT_A, GREEDY, timeout=120).tokens
        took = time.monotonic() - t0
        stats = b.stats()
        assert got == cold_a
        assert stats["durable-restored-hits-total"] == 0
        assert stats["durable-restore-failures-total"] >= 1
        assert stats["engine-restarts-total"] == 0
        dump = b._obs.flight.last_dump
        assert dump is not None and dump["reason"] == "durable-restore-failed"
        assert "deadline" in dump["extra"]["error"]
        assert took < 60.0, "stall must degrade within the request, not hang"
        assert_leak_free(b)
    finally:
        b.stop()


def test_disk_full_checkpoint_fails_cleanly_serving_unaffected(tmp_path):
    """ENOSPC on the worker thread: the checkpoint fails COUNTED, no
    manifest is left behind, and the serving path never notices."""
    cold_a, _ = _cold_reference()
    engine = make_engine(
        durable_dir=tmp_path,
        fault_injector=FaultInjector("disk-full@1", seed=0),
    )
    try:
        first = engine.generate(PROMPT_A, GREEDY, timeout=120).tokens
        assert first == cold_a
        wait_stat(engine, "durable-checkpoint-failures-total", 1)
        stats = engine.stats()
        assert stats["engine-restarts-total"] == 0
        # a failed checkpoint leaves NO entry — the commit record is the
        # manifest, and it was never written
        manifests = [
            n for n in os.listdir(str(tmp_path))
            if n.endswith(MANIFEST_SUFFIX) and n != HIBERNATE_NAME
        ]
        assert stats["durable-entries"] == len(manifests)
        # the engine still serves, token-exact
        assert engine.generate(PROMPT_A, GREEDY, timeout=120).tokens == cold_a
        assert_leak_free(engine)
    finally:
        engine.stop()


def test_stats_block_present_with_tier_off():
    engine = make_engine(tier=True)  # no durable_dir: tier off
    try:
        stats = engine.stats()
        assert stats["durable-tier"] is False
        assert stats["durable-entries"] == 0
        assert stats["durable-restored-hits-total"] == 0
        assert stats["durable-checkpoints-total"] == 0
    finally:
        engine.stop()


def test_memory_plan_reports_durable_disk_budget():
    from langstream_tpu.serving.memory import plan_serving_memory

    plan = plan_serving_memory(
        CFG, 2, 128, page_size=16, kv_pages=12,
        durable_max_bytes=2 << 30,
    )
    assert plan.durable_disk_bytes == 2 << 30
    assert "durable KV tier" in plan.summary()
    assert "disk" in plan.summary()
    flat = plan_serving_memory(
        CFG, 2, 128, page_size=16, kv_pages=12,
    )
    assert flat.durable_disk_bytes == 0
    assert "durable" not in flat.summary()


# ---------------------------------------------------------------------------
# Router: cost model, prefetch, scale-to-zero (fake replicas — no engines)
# ---------------------------------------------------------------------------


PROMPT = [11 + i % 60 for i in range(70)]


class _FakeReplica:
    is_local = False

    def __init__(self, rid, load=0.0, prefixes=(), **beacon_extra):
        self.replica_id = rid
        self.load = load
        self.prefixes = list(prefixes)
        self.beacon_extra = dict(beacon_extra)

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


def _router(replicas, **kw):
    kw.setdefault("refresh_interval_s", 3600.0)  # tests refresh by hand
    r = FleetRouter(replicas, **kw)
    r.refresh_all()
    return r


def test_cost_model_fetch_vs_prefill():
    """The §23 cost model: with full telemetry the router compares wire
    seconds against prefill seconds; without it, the flat threshold; and
    ``p2p_min_gap`` floors BOTH modes."""
    owner = _FakeReplica(
        "owner", prefixes=[(prefix_digest(PROMPT[:64]), 64)],
        caps=["p2p"], bytes_per_page=4096, page_size=16,
    )
    best = _FakeReplica("best", caps=["p2p"], prefill_tps=1000.0)
    router = _router([best, owner], p2p_threshold=4096, p2p_min_gap=8)
    s_best = router._replicas["best"]
    s_owner = router._replicas["owner"]

    def worth_it(gap, match):
        # _locked suffix: the real caller (_route) holds router._lock
        with router._lock:
            return router._p2p_worth_it_locked(s_best, s_owner, gap, match)

    # telemetry-complete, cheap wire: 4 pages × 4096 B at 10 MB/s
    # (~1.6 ms) beats prefilling a 64-token gap at 1000 tok/s (64 ms)
    router._p2p_bw_ema = 10e6
    assert worth_it(0, 64) is True
    assert router.p2p_cost_routed_total == 1

    # same geometry, starved wire: 4 pages at 100 B/s loses to prefill
    router._p2p_bw_ema = 100.0
    assert worth_it(0, 64) is False

    # min-gap floors even a free wire
    router._p2p_bw_ema = 10e6
    assert worth_it(60, 64) is False

    # no bandwidth observation yet → the flat threshold decides
    router._p2p_bw_ema = 0.0
    assert worth_it(0, 64) is False  # 64 < 4096
    router.p2p_threshold = 32
    assert worth_it(0, 64) is True


def test_prefetch_counts_and_fetch_path(monkeypatch):
    """prefetch() routes like the real request will, then fires the page
    fetch immediately; a hint nobody can improve on costs nothing."""
    owner = _FakeReplica(
        "owner", load=0.9,
        prefixes=[(prefix_digest(PROMPT[:64]), 64)], caps=["p2p"],
    )
    cold = _FakeReplica("cold", load=0.0, caps=["p2p"])
    router = _router([cold, owner], p2p_threshold=8, p2p_min_gap=4, lam=256.0)
    fetched = []
    monkeypatch.setattr(
        router, "_p2p_fetch", lambda decision, tokens: fetched.append(1) or True,
    )
    out = router.prefetch(PROMPT, session_id="s1")
    assert out["prefetched"] is True
    assert out["source"] == "owner"
    assert fetched == [1]
    assert router.prefetch_total == 1
    assert router.prefetch_fetch_total == 1
    # single-replica fleet: the owner IS the destination — nothing to pull
    solo = _router([owner])
    out = solo.prefetch(PROMPT)
    assert out["prefetched"] is False
    assert out["reason"] == "no-deeper-owner"
    assert solo.prefetch_total == 1 and solo.prefetch_fetch_total == 0


def test_local_prefetch_surface_validates_and_requires_router():
    unregister_local_router()
    with pytest.raises(ReplicaError):
        local_prefetch({"prompt_tokens": [1, 2, 3]})

    class _Router:
        def __init__(self):
            self.calls = []

        def prefetch(self, tokens, session_id=None, adapter=None, tenant=None):
            self.calls.append((list(tokens), session_id, adapter, tenant))
            return {"prefetched": False, "reason": "no-deeper-owner"}

    r = _Router()
    register_local_router(r)
    try:
        with pytest.raises(ValueError):
            local_prefetch({"prompt_tokens": "not-a-list"})
        with pytest.raises(ValueError):
            local_prefetch({"prompt_tokens": [1, "x"]})
        local_prefetch({
            "prompt_tokens": [1, 2], "session": "s", "tenant": "t",
        })
        assert r.calls == [([1, 2], "s", None, "t")]
    finally:
        unregister_local_router()


def test_scale_to_zero_gated_on_quiet_and_durable_caps():
    durable_fleet = [
        _FakeReplica("a", caps=["p2p", "durable"]),
        _FakeReplica("b", caps=["p2p", "durable"]),
    ]
    router = _router(durable_fleet)
    # default floor: min_replicas=1 never goes dark
    router._last_demand_t = time.monotonic() - 3600.0
    assert router.desired_replicas(min_replicas=1) >= 1
    # quiet + all-durable + min 0 → zero
    assert router.desired_replicas(min_replicas=0) == 0
    # recent demand vetoes (any route() stamps the clock)
    router._last_demand_t = time.monotonic()
    assert router.desired_replicas(min_replicas=0) >= 1
    # one replica without the durable cap vetoes: its sessions would die
    mixed = _router([
        _FakeReplica("a", caps=["p2p", "durable"]),
        _FakeReplica("b", caps=["p2p"]),
    ])
    mixed._last_demand_t = time.monotonic() - 3600.0
    assert mixed.desired_replicas(min_replicas=0) >= 1
    # in-flight work vetoes even a quiet, durable fleet
    busy = _router([
        _FakeReplica("a", caps=["durable"], active_slots=1),
        _FakeReplica("b", caps=["durable"]),
    ])
    busy._last_demand_t = time.monotonic() - 3600.0
    assert busy.desired_replicas(min_replicas=0) >= 1


def test_k8s_min_replicas_zero_is_legal():
    from langstream_tpu.k8s.crds import AgentCustomResource
    from langstream_tpu.k8s.resources import AgentResourcesFactory

    def agent(hint, min_r):
        return AgentCustomResource(
            name="x", namespace="ns", tenant="t", agent_id="ag",
            application_id="app", agent_type="ai-chat-completions",
            component_type="PROCESSOR", config_secret_ref="s",
            config_checksum="c", parallelism=2,
            autoscale={
                "enabled": True, "min-replicas": min_r, "max-replicas": 4,
            },
            status={"fleet": {"desiredReplicas": hint}},
        )

    consumers = AgentResourcesFactory.fleet_consumers
    assert consumers(agent(0, 0)) == 0
    assert consumers(agent(0, 1)) == 1  # floor holds
    assert consumers(agent(3, 0)) == 3
    assert consumers(agent(9, 0)) == 4  # cap holds

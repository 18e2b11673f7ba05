"""SPMD fast-path parity (round 13, docs/SERVING.md §14).

Prefix KV reuse, self-speculative decoding and the paged allocator all
ride the leader→follower wire now — these tests prove a loopback SPMD
replica with EVERY fast path enabled is token-exact against the
single-host engine on the same workload (cold + warm + speculative mixed
batch, both KV dtypes) and that leader/follower device state stays
bit-identical. Every loopback pair runs with the channel's ``echo``
divergence check ON, so a passing run simultaneously proves the checker
raises no false positives; a dedicated test proves it catches a real
divergence and leaves a schema-valid flight dump.

The whole module is marked ``slow``: tier-1 runs under a hard 870 s
timeout here and already truncates, so these (engine-pair-heavy) tests
run in the chaos CI step instead (pinned LSTPU_FAULT_SEED), alongside
the fault suites.
"""

import dataclasses
import threading

import jax
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.parallel.spmd_serving import (
    LoopbackChannel,
    SpmdDivergenceError,
    follower_loop,
)
from langstream_tpu.serving.engine import LogitsNaNError, ServingEngine
from langstream_tpu.serving.faultinject import FaultInjector
from langstream_tpu.serving.pagepool import table_len_for

pytestmark = pytest.mark.slow

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
CFG_INT8 = dataclasses.replace(CFG, kv_cache_dtype="int8")

MAX_SEQ = 64
PAGE = 8
BUCKETS = (16, 32)
GREEDY = GenerationOptions(max_new_tokens=5, temperature=0.0)

# a 16-token preamble (= the smallest bucket boundary, so it publishes)
PREAMBLE = [(7 + i) % CFG.vocab_size for i in range(16)]


def _engine_kwargs(prefix: bool, spec: bool) -> dict:
    return dict(
        max_batch=3,
        max_seq_len=MAX_SEQ,
        decode_chunk=4,
        prefill_buckets=BUCKETS,
        prefill_batch=4,
        page_size=PAGE,
        prefix_cache="auto" if prefix else False,
        speculation="auto" if spec else False,
        speculation_tokens=4,
    )


def _channel(spec: bool, echo: bool = True) -> LoopbackChannel:
    return LoopbackChannel(
        prefill_batch=4,
        max_width=max(BUCKETS),
        max_batch=3,
        table_len=table_len_for(MAX_SEQ, PAGE),
        spec_tokens=4 if spec else 0,
        echo=echo,
    )


class _Pair:
    """A loopback leader+follower sharing params, with the follower's
    crash (if any) captured for assertion."""

    def __init__(self, config, prefix, spec, *, echo=True,
                 injector=None, follower_params=None):
        self.params = init_params(config, jax.random.PRNGKey(0))
        self.channel = _channel(spec, echo=echo)
        kw = _engine_kwargs(prefix, spec)
        self.leader = ServingEngine(
            config, self.params, spmd=self.channel,
            fault_injector=injector, **kw,
        )
        self.follower = ServingEngine(
            config, follower_params if follower_params is not None else self.params,
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

    def stop(self) -> None:
        self.leader.stop()
        self.thread.join(timeout=60)
        assert not self.thread.is_alive(), "follower never saw STOP"

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


def _mixed_workload(engine) -> list[list[int]]:
    """Cold + warm + long, sequentially (deterministic dispatch sequence —
    the single-host reference must consume its PRNG identically). Returns
    the per-request token streams."""
    out = []
    # cold short
    out.append(engine.generate([5, 6, 7], GREEDY, timeout=120).tokens)
    # cold carrier of the shared preamble (publishes at the 16 boundary)
    out.append(engine.generate(PREAMBLE + [3, 1], GREEDY, timeout=120).tokens)
    # warm: same preamble, different suffix → prefix hit (alias/gather)
    out.append(engine.generate(PREAMBLE + [9, 2, 4], GREEDY, timeout=120).tokens)
    # long prompt (> largest bucket): chunked-prefill segments on the wire
    long_prompt = [(3 + i) % CFG.vocab_size for i in range(40)]
    out.append(engine.generate(long_prompt, GREEDY, timeout=120).tokens)
    return out


def _concurrent_batch(engine, prompts, opts=GREEDY) -> list[list[int]]:
    """Submit a batch concurrently (greedy decode is batch-composition
    independent — per-slot rows only read their own cache) and wait."""
    from langstream_tpu.serving.engine import GenerationRequest

    reqs = [
        GenerationRequest(prompt_tokens=list(p), options=opts) for p in prompts
    ]
    for r in reqs:
        engine.submit(r)
    return [r.result(timeout=120).tokens for r in reqs]


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["f32kv", "int8kv"])
def test_paged_prefix_parity_cold_warm_long(config):
    """prefix-cache=auto under loopback SPMD: page binds,
    aliased warm admissions, segment prefill and frees all replay; tokens
    equal the single-host engine's and device state stays bit-identical.
    Echo divergence checking is ON throughout (no false positives)."""
    ref = ServingEngine(
        config, init_params(config, jax.random.PRNGKey(0)),
        **_engine_kwargs(prefix=True, spec=False),
    )
    ref.start()
    try:
        want = _mixed_workload(ref)
        assert ref.stats()["prefix-cache-hit-rate"] > 0
    finally:
        ref.stop()

    pair = _Pair(config, prefix=True, spec=False)
    try:
        got = _mixed_workload(pair.leader)
        stats = pair.leader.stats()
        assert stats["prefix-cache-hit-rate"] > 0, "warm path never exercised"
        assert stats["prefill-tokens-saved-total"] >= 16
        assert stats["spmd"] and stats["spmd-announces-total"] > 0
    finally:
        pair.stop()
    assert not pair.follower_error, pair.follower_error
    assert got == want, "SPMD leader diverged from the single-host engine"
    pair.assert_lockstep()


@pytest.mark.parametrize("config", [CFG, CFG_INT8], ids=["f32kv", "int8kv"])
def test_paged_speculation_parity_mixed_batch(config):
    """speculation=auto (+ prefix, paged) under loopback SPMD: drafts ride
    OP_VERIFY, accepts are computed on device on every host. A concurrent
    mixed batch (repetitive prompts → real acceptances) is token-exact vs
    the single-host engine, and verify echoes confirm no divergence."""
    # periodic prompts make the n-gram index propose (and get accepts)
    prompts = [
        [1, 2, 3, 1, 2, 3, 1, 2, 3],
        [4, 5, 4, 5, 4, 5, 4, 5],
        [6, 7, 8, 9],
    ]
    opts = GenerationOptions(max_new_tokens=8, temperature=0.0)

    ref = ServingEngine(
        config, init_params(config, jax.random.PRNGKey(0)),
        **_engine_kwargs(prefix=True, spec=True),
    )
    ref.start()
    try:
        want = sorted(_concurrent_batch(ref, prompts, opts))
    finally:
        ref.stop()

    pair = _Pair(config, prefix=True, spec=True)
    try:
        got = sorted(_concurrent_batch(pair.leader, prompts, opts))
        stats = pair.leader.stats()
        assert stats["spec-verify-dispatches-total"] > 0
        assert stats["spec-accepted-tokens-total"] > 0, (
            "speculation never accepted — the parity run proved nothing"
        )
    finally:
        pair.stop()
    assert not pair.follower_error, pair.follower_error
    assert got == want
    pair.assert_lockstep()


def test_no_construction_disable_warnings(caplog):
    """The three construction-time SPMD disables are GONE: building an
    engine with prefix-cache + speculation + paged on an SPMD channel
    must not warn about falling back or disabling anything."""
    import logging

    channel = _channel(spec=True)
    with caplog.at_level(logging.WARNING, logger="langstream_tpu.serving.engine"):
        engine = ServingEngine(
            CFG, init_params(CFG, jax.random.PRNGKey(0)), spmd=channel,
            **_engine_kwargs(prefix=True, spec=True),
        )
    assert engine._paged and engine._spec_enabled
    assert engine._prefix_index is not None
    for msg in ("disabled", "falling back", "not supported"):
        assert not [r for r in caplog.records if msg in r.message.lower()], (
            f"construction still warns {msg!r} under SPMD"
        )


def test_page_fault_quarantines_victim_only_on_both():
    """The `page` chaos site under loopback SPMD: the leader detects the
    corrupted table row before dispatch, quarantines ONLY that slot (pages
    freed + zeroed via the wire), survivors stay token-exact, and NEITHER
    engine crashes — SPMD fault handling is no longer crash-only for
    host-detectable faults."""
    prompts = [[5, 6, 7], [8, 9, 1, 2], [3, 4]]
    opts = GenerationOptions(max_new_tokens=6, temperature=0.0)
    ref = ServingEngine(
        CFG, init_params(CFG, jax.random.PRNGKey(0)),
        **_engine_kwargs(prefix=True, spec=False),
    )
    ref.start()
    try:
        want = {tuple(p): r for p, r in zip(
            map(tuple, prompts), _concurrent_batch(ref, prompts, opts)
        )}
    finally:
        ref.stop()

    pair = _Pair(
        CFG, prefix=True, spec=False,
        injector=FaultInjector("page@1", seed=0),
    )
    try:
        from langstream_tpu.serving.engine import GenerationRequest

        reqs = [
            GenerationRequest(prompt_tokens=list(p), options=opts)
            for p in prompts
        ]
        for r in reqs:
            pair.leader.submit(r)
        outcomes = []
        for r in reqs:
            try:
                outcomes.append(("ok", r.result(timeout=120).tokens, r))
            except RuntimeError as e:
                outcomes.append(("quarantined", str(e), r))
        stats = pair.leader.stats()
    finally:
        pair.stop()
    assert not pair.follower_error, pair.follower_error
    victims = [o for o in outcomes if o[0] == "quarantined"]
    assert len(victims) == 1, outcomes
    assert stats["quarantined-slots-total"] == 1
    assert stats["engine-restarts-total"] == 0
    for kind, tokens, r in outcomes:
        if kind == "ok":
            assert tokens == want[tuple(r.prompt_tokens)], (
                "survivor diverged after a page quarantine"
            )
    pair.assert_lockstep()


def test_nan_fault_quarantines_victim_only_on_both():
    """The `nan` chaos site under loopback SPMD: round 13 replaces the
    crash-only NaN contract — the victim slot quarantines (pages freed and
    zeroed on every host), survivors keep decoding, the follower replays
    the quarantine dispatches and stays bit-identical."""
    prompts = [[5, 6, 7], [8, 9, 1, 2]]
    opts = GenerationOptions(max_new_tokens=6, temperature=0.0)
    pair = _Pair(
        CFG, prefix=False, spec=False,
        injector=FaultInjector("nan@2", seed=0),
    )
    try:
        from langstream_tpu.serving.engine import GenerationRequest

        reqs = [
            GenerationRequest(prompt_tokens=list(p), options=opts)
            for p in prompts
        ]
        for r in reqs:
            pair.leader.submit(r)
        outcomes = []
        for r in reqs:
            try:
                outcomes.append(("ok", r.result(timeout=120).tokens))
            except LogitsNaNError as e:
                outcomes.append(("nan", str(e)))
        stats = pair.leader.stats()
    finally:
        pair.stop()
    assert not pair.follower_error, pair.follower_error
    assert [o[0] for o in outcomes].count("nan") == 1, outcomes
    assert stats["nan-guard-total"] == 1
    assert stats["engine-restarts-total"] == 0, (
        "NaN under SPMD must quarantine, not crash/restart"
    )
    pair.assert_lockstep()


def test_divergence_detected_dumped_and_fatal():
    """A REAL divergence (follower built with different params) must be
    caught by the echo check and stay FATAL: the first mismatch may
    request a resync (round 19 — a one-off wire corruption deserves one
    chance), but the weights keep disagreeing, so the REPEAT mismatch
    inside the resync window crashes the follower with
    SpmdDivergenceError and leaves a schema-valid flight dump tagged with
    the ControlBlock seq — persistent divergence is never survived
    (docs/SERVING.md §20)."""
    from langstream_tpu.serving.observability import (
        recent_dumps,
        validate_flight_dump,
    )

    pair = _Pair(
        CFG, prefix=False, spec=False,
        follower_params=init_params(CFG, jax.random.PRNGKey(99)),
    )
    try:
        # the follower's different weights produce different tokens on
        # EVERY chunk: enough tokens for at least two decode-chunk echoes
        # (first mismatch → resync request; repeat → fatal)
        pair.leader.generate(
            [5, 6, 7],
            GenerationOptions(max_new_tokens=12, temperature=0.0),
            timeout=120,
        )
        pair.thread.join(timeout=60)
        assert pair.follower_error, "divergence went undetected"
        assert isinstance(pair.follower_error[0], SpmdDivergenceError)
    finally:
        pair.leader.stop()
        pair.thread.join(timeout=60)
    dumps = [d for d in recent_dumps() if d.get("reason") == "spmd-divergence"]
    assert dumps, "no spmd-divergence flight dump was produced"
    doc = dumps[-1]
    validate_flight_dump(doc)
    assert doc["extra"]["seq"] > 0 and "divergence" in doc["extra"]["why"]


def test_wire_bytes_accounted():
    """The channel measures its own overhead (announces + bytes) — the
    PERF.md round-13 ControlBlock-bytes-per-iteration number is read off
    these counters, not estimated."""
    pair = _Pair(CFG, prefix=True, spec=False, echo=False)
    try:
        pair.leader.generate([5, 6, 7], GREEDY, timeout=120)
        ch = pair.channel
        assert ch.announces_total > 0
        assert ch.bytes_announced_total > 0
        # phase-1 is (head + slots + mask) int32s — the per-announce floor
        assert ch.bytes_announced_total >= ch.announces_total * (17 + 4 + 3) * 4
    finally:
        pair.stop()
    assert not pair.follower_error, pair.follower_error


def test_two_process_full_fast_path_parity():
    """Real processes, real coordinator, ALL fast paths on: leader serves a
    cold+warm workload with prefix-cache auto, speculation auto and
    kv_layout=paged; the follower replays; leader tokens must equal the
    single-process reference. Skips honestly where the jax CPU backend has
    no multiprocess collectives (the loopback tier above carries the
    parity proof on every platform)."""
    import json
    import os
    import socket
    import subprocess
    import sys
    from pathlib import Path

    ref = ServingEngine(
        CFG, init_params(CFG, jax.random.PRNGKey(0)),
        **_engine_kwargs(prefix=True, spec=True),
    )
    ref.start()
    try:
        want = [
            ref.generate(
                [5, 6, 7, 8],
                GenerationOptions(max_new_tokens=6, temperature=0.0),
                timeout=120,
            ).tokens,
            ref.generate(
                PREAMBLE + [2, 3],
                GenerationOptions(max_new_tokens=6, temperature=0.0),
                timeout=120,
            ).tokens,
            ref.generate(
                PREAMBLE + [4, 1],
                GenerationOptions(max_new_tokens=6, temperature=0.0),
                timeout=120,
            ).tokens,
        ]
    finally:
        ref.stop()

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    worker = Path(__file__).parent / "spmd_worker.py"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device per process
    env["PYTHONPATH"] = str(Path(__file__).parent.parent)
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(i), "2", str(port), "fast"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError("SPMD processes hung (lockstep broken)")
        if p.returncode != 0 and (
            "Multiprocess computations aren't implemented" in err
        ):
            for q in procs:
                q.kill()
            pytest.skip(
                "jax CPU backend lacks multiprocess collectives on this "
                "version; two-process tier needs a TPU/GPU backend"
            )
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))
    by_role = {o["role"]: o for o in outs}
    assert by_role["follower"]["done"] is True
    assert by_role["leader"]["tokens"] == want, (
        "2-process fast-path generation diverged from single-process "
        "reference"
    )

"""Multi-host SPMD serving dispatch (round-2 verdict gap #4).

Two tiers:
1. LoopbackChannel in one process: a leader engine and a follower engine
   share the device mesh; after a generation their device-resident state
   (page pool, decode chain) must be bit-identical — the lockstep property
   the real multi-host replica depends on.
2. A REAL 2-process ``jax.distributed`` run (subprocesses, real
   coordinator, broadcast_one_to_all over the global mesh): only the
   leader consumes requests; the follower replays. The leader's greedy
   tokens must equal the single-process reference.
"""

import dataclasses
import json
import os
import socket
import subprocess
import sys
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.models.transformer import init_params
from langstream_tpu.parallel.spmd_serving import LoopbackChannel, follower_loop
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
from langstream_tpu.serving.pagepool import table_len_for

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
PAGE = 8
TABLE_LEN = table_len_for(64, PAGE)



def _assert_lockstep(leader, follower) -> None:
    """Leader/follower device state must be bit-identical (the property
    every multi-host replica depends on). Compares the decode chain plus
    the page pool."""
    for attr in ("_tokens_dev", "_positions_dev"):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(getattr(leader, attr))),
            np.asarray(jax.device_get(getattr(follower, attr))),
        )
    leaves_a = jax.tree.leaves(jax.device_get(leader._pagepool.dev))
    leaves_b = jax.tree.leaves(jax.device_get(follower._pagepool.dev))
    assert leaves_a and len(leaves_a) == len(leaves_b)
    for a, b in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loopback_follower_stays_in_lockstep():
    # the plain wire tier; prefix reuse and speculation on the wire are
    # covered by tests/test_spmd_parity.py
    params = init_params(CFG, jax.random.PRNGKey(0))
    channel = LoopbackChannel(
        prefill_batch=4, max_width=32, max_batch=2, table_len=TABLE_LEN
    )
    leader = ServingEngine(
        CFG, params, max_batch=2, max_seq_len=64, decode_chunk=4,
        prefill_buckets=(16, 32), prefill_batch=4, spmd=channel,
        page_size=PAGE,
    )
    follower = ServingEngine(
        CFG, params, max_batch=2, max_seq_len=64, decode_chunk=4,
        prefill_buckets=(16, 32), prefill_batch=4, page_size=PAGE,
    )
    follower_thread = threading.Thread(
        target=follower_loop, args=(follower, channel), daemon=True
    )
    follower_thread.start()
    leader.start()
    try:
        opts = GenerationOptions(max_new_tokens=5, temperature=0.0)
        r1 = leader.generate([5, 6, 7], opts, timeout=120)
        # a long prompt exercises the chunked-prefill ops over the channel
        long_prompt = [(3 + i) % CFG.vocab_size for i in range(40)]  # 3 segments
        r2 = leader.generate(long_prompt, opts, timeout=120)
        assert len(r1.tokens) == 5 and len(r2.tokens) == 5
    finally:
        leader.stop()
    follower_thread.join(timeout=60)
    assert not follower_thread.is_alive(), "follower never saw STOP"

    # the follower's device state must have evolved identically
    _assert_lockstep(leader, follower)


def test_two_process_jax_distributed_serving():
    """Real processes, real coordinator: leader serves, follower replays,
    greedy output equals the single-process reference."""
    # single-process reference
    params = init_params(CFG, jax.random.PRNGKey(0))
    ref_engine = ServingEngine(
        CFG, params, max_batch=2, max_seq_len=64, decode_chunk=4,
        prefill_buckets=(16, 32), prefill_batch=4,
    )
    ref_engine.start()
    try:
        ref = ref_engine.generate(
            [5, 6, 7, 8], GenerationOptions(max_new_tokens=6, temperature=0.0),
            timeout=120,
        )
    finally:
        ref_engine.stop()

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
            [sys.executable, str(worker), str(i), "2", str(port)],
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
            # platform limitation, not a lockstep bug: this jax's CPU
            # backend has no multiprocess collectives (the real TPU/GPU
            # backends do) — the loopback tier above still proves the
            # replay protocol on every platform
            for q in procs:
                q.kill()
            import pytest

            pytest.skip(
                "jax CPU backend lacks multiprocess collectives on this "
                "version; two-process tier needs a TPU/GPU backend"
            )
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))
    by_role = {o["role"]: o for o in outs}
    assert by_role["follower"]["done"] is True
    assert by_role["leader"]["tokens"] == ref.tokens, (
        "2-process sharded generation diverged from single-process reference"
    )


def test_loopback_moe_lockstep_on_expert_mesh():
    """MoE decode under SPMD: leader + follower engines on the SAME
    expert×model mesh (mixtral-style ep×tp sharding), every dispatch
    announced over the channel — device state bit-identical after serving.
    This is the multi-host story for the Mixtral MoE serving path."""
    from langstream_tpu.parallel.mesh import build_mesh
    from langstream_tpu.parallel.sharding import shard_params

    config = dataclasses.replace(MODEL_PRESETS["tiny-moe-test"], dtype="float32")
    mesh = build_mesh({"expert": 4, "model": 2})
    params = shard_params(init_params(config, jax.random.PRNGKey(2)), mesh, config)
    channel = LoopbackChannel(
        prefill_batch=2, max_width=32, max_batch=2, table_len=TABLE_LEN
    )
    mk = lambda spmd: ServingEngine(  # noqa: E731
        config, params, max_batch=2, max_seq_len=64, decode_chunk=4,
        prefill_buckets=(16, 32), prefill_batch=2, mesh=mesh, spmd=spmd,
        page_size=PAGE,
    )
    leader, follower = mk(channel), mk(None)
    follower_thread = threading.Thread(
        target=follower_loop, args=(follower, channel), daemon=True
    )
    follower_thread.start()
    leader.start()
    try:
        opts = GenerationOptions(max_new_tokens=5, temperature=0.0)
        r1 = leader.generate([5, 6, 7], opts, timeout=300)
        r2 = leader.generate([9, 2], opts, timeout=300)
        assert len(r1.tokens) == 5 and len(r2.tokens) == 5
    finally:
        leader.stop()
    follower_thread.join(timeout=60)
    assert not follower_thread.is_alive(), "follower never saw STOP"
    _assert_lockstep(leader, follower)


def test_announce_decode_packs_head_and_mask():
    """A decode announcement is head-only (no second-phase payload): its
    step count, an empty stale list and the active-slot mask survive the
    pack/unpack round trip."""
    import numpy as np

    from langstream_tpu.parallel.spmd_serving import (
        OP_DECODE,
        ControlBlock,
        LoopbackChannel,
    )

    channel = LoopbackChannel(prefill_batch=4, max_width=64, max_batch=4)
    channel.announce(ControlBlock(
        op=OP_DECODE, steps=4, n_rows=0,
        slots=np.zeros(0, np.int32), mask=np.asarray([1, 0, 1, 0], np.int32),
    ))
    block = channel.recv()
    assert block.op == OP_DECODE and block.steps == 4 and block.n_rows == 0
    assert list(block.mask) == [1, 0, 1, 0]


def test_loopback_lockstep_with_precompiled_ladder():
    """precompile=True on the leader announces every warmup family over the
    channel; the follower replays them and must STAY bit-identical through
    real generations afterwards (the warmups write only to out-of-bounds
    pages and advance the PRNG key identically on both sides)."""
    params = init_params(CFG, jax.random.PRNGKey(0))
    channel = LoopbackChannel(
        prefill_batch=4, max_width=32, max_batch=2, table_len=TABLE_LEN
    )
    leader = ServingEngine(
        CFG, params, max_batch=2, max_seq_len=64, decode_chunk=4,
        prefill_buckets=(16, 32), prefill_batch=4, spmd=channel,
        precompile=True, page_size=PAGE,
    )
    follower = ServingEngine(
        CFG, params, max_batch=2, max_seq_len=64, decode_chunk=4,
        prefill_buckets=(16, 32), prefill_batch=4,
        page_size=PAGE,
    )
    follower_thread = threading.Thread(
        target=follower_loop, args=(follower, channel), daemon=True
    )
    follower_thread.start()
    leader.start()
    try:
        opts = GenerationOptions(max_new_tokens=6, temperature=0.0)
        result = leader.generate([9, 8, 7], opts, timeout=120)
        assert len(result.tokens) == 6
    finally:
        leader.stop()
    follower_thread.join(timeout=60)
    assert not follower_thread.is_alive(), "follower never saw STOP"
    _assert_lockstep(leader, follower)


def _wire_blocks():
    from langstream_tpu.parallel import spmd_serving as w

    return {
        "prefill": w.ControlBlock(
            op=w.OP_PREFILL, width=16, n_rows=2,
            tokens=np.arange(32, dtype=np.int32).reshape(2, 16),
            lengths=np.asarray([16, 9], np.int32), slots=np.asarray([1, 4], np.int32),
            temps=np.asarray([0.0, 0.7], np.float32),
            top_ks=np.asarray([0, 5], np.int32), top_ps=np.asarray([1.0, 0.9], np.float32),
        ),
        "long-seg": w.ControlBlock(
            op=w.OP_LONG_SEG, width=32, n_rows=1,
            tokens=np.arange(32, dtype=np.int32).reshape(1, 32),
            s0=64, seg_len=21, long_start=False, long_final=True, long_idx=3,
            prompt_len=85, temps=np.asarray([0.0], np.float32),
            top_ks=np.asarray([0], np.int32), top_ps=np.asarray([1.0], np.float32),
        ),
        "verify": w.ControlBlock(
            op=w.OP_VERIFY, steps=4, n_rows=1, slots=np.asarray([2], np.int32),
            drafts=np.arange(16, dtype=np.int32).reshape(4, 4),
            mask=np.asarray([1, 1, 0, 1], np.int32),
        ),
        "page-bind": w.ControlBlock(
            op=w.OP_PAGE_BIND, long_idx=2, count=3,
            pages=np.asarray([7, 1, 5], np.int32), cow_src=7, cow_dst=9,
        ),
    }


@pytest.mark.parametrize("kind", ["prefill", "long-seg", "verify", "page-bind"])
def test_wire_block_survives_the_fourteen_field_header(kind):
    """The header lost the dense layout's three fields (kv_bound, t_long,
    entry_row) and was renumbered: every field an op still ships, and its
    payload, comes back as announced."""
    channel = LoopbackChannel(
        prefill_batch=2, max_width=32, max_batch=4, table_len=8, spec_tokens=4,
    )
    sent = _wire_blocks()[kind]
    channel.announce(sent)
    got = channel.recv()
    for field in ("op", "width", "steps", "n_rows", "s0", "seg_len", "long_start",
                  "long_final", "long_idx", "prompt_len", "cow_src", "cow_dst", "count"):
        assert getattr(got, field) == getattr(sent, field), field
    assert got.seq == 1
    for field in ("tokens", "lengths", "slots", "temps", "top_ks", "top_ps", "mask",
                  "drafts", "pages"):
        want = getattr(sent, field)
        if want is not None:
            np.testing.assert_array_equal(np.asarray(getattr(got, field))[
                tuple(slice(0, n) for n in np.shape(want))
            ], want)
    assert not hasattr(got, "kv_bound") and not hasattr(got, "entry_row")


def test_remaining_wire_ops_keep_their_numbers():
    """Four ops went with the dense layout (5 ring, 7 / 8 prefix admit and
    publish, 12 row reset); a mixed-version slice must not see an old
    number mean something new, so the survivors keep theirs."""
    from langstream_tpu.parallel import spmd_serving as w

    ops = {n: getattr(w, n) for n in dir(w) if n.startswith("OP_")}
    assert ops == {
        "OP_IDLE": 0, "OP_PREFILL": 1, "OP_LONG_SEG": 2, "OP_DECODE": 3,
        "OP_STOP": 4, "OP_VERIFY": 6, "OP_PAGE_BIND": 9, "OP_PAGE_FREE": 10,
        "OP_PAGE_ZERO": 11, "OP_ECHO": 13, "OP_WARMUP": 14, "OP_RECOVER": 15,
        "OP_RESYNC": 16,
    }
    assert (w.WARMUP_PAGED, w.WARMUP_PREFILL_BUCKETS) == (2, 3)


@pytest.mark.parametrize(
    "block_kw", [dict(op=5), dict(op=7), dict(op=12), dict(op=14, count=0)],
    ids=["ring", "prefix-admit", "row-reset", "warmup-decode-ladder"],
)
def test_follower_refuses_a_deleted_op_as_a_divergence(block_kw):
    """A leader that still announces a dense-layout op (or the dense
    ladder's warm-up family) is a structural divergence: the follower
    stops with a dump, it does not guess."""
    from langstream_tpu.parallel.spmd_serving import (
        ControlBlock,
        SpmdDivergenceError,
        _replay,
    )

    params = init_params(CFG, jax.random.PRNGKey(0))
    engine = ServingEngine(
        CFG, params, max_batch=2, max_seq_len=64, prefill_buckets=(16,),
        page_size=PAGE,
    )
    channel = LoopbackChannel(
        prefill_batch=2, max_width=16, max_batch=2, table_len=TABLE_LEN
    )
    with pytest.raises(SpmdDivergenceError, match="unknown"):
        _replay(engine, ControlBlock(**block_kw), channel, [])
    engine._fail_all(RuntimeError("never started"))

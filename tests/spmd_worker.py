"""Worker script for the 2-process jax.distributed SPMD serving tests.

Usage: python spmd_worker.py <process_id> <num_processes> <coordinator_port> [mode]

Process 0 = leader: runs the ServingEngine (broker-consumer side), submits
greedy requests, prints the tokens. Process 1+ = followers: replay the
leader's dispatches via follower_loop, never touching a request queue.
Both build IDENTICAL engine state (same params seed, same mesh over the
GLOBAL device list).

``mode``:
  basic (default) — the plain wire tier: one cold request.
  fast — round-13 parity tier: prefix-cache auto + speculation auto,
    a cold+warm workload, result echo verification ON
    (every processed chunk's tokens re-broadcast and checked on the
    follower — docs/SERVING.md §14).
"""

import json
import sys

import jax

jax.config.update("jax_platforms", "cpu")

pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
mode = sys.argv[4] if len(sys.argv) > 4 else "basic"
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=nproc, process_id=pid
)

import dataclasses  # noqa: E402

from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions  # noqa: E402
from langstream_tpu.models.transformer import init_params  # noqa: E402
from langstream_tpu.parallel.mesh import build_mesh  # noqa: E402
from langstream_tpu.parallel.sharding import shard_params  # noqa: E402
from langstream_tpu.parallel.spmd_serving import SpmdChannel, follower_loop  # noqa: E402
from langstream_tpu.serving.engine import ServingEngine  # noqa: E402
from langstream_tpu.serving.pagepool import table_len_for  # noqa: E402

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
assert len(jax.devices()) == nproc, jax.devices()

params = init_params(CFG, jax.random.PRNGKey(0))
mesh = build_mesh({"model": nproc})
params = shard_params(params, mesh, CFG)

fast = mode == "fast"
MAX_SEQ = 64
PAGE = 8
channel = SpmdChannel(
    prefill_batch=4,
    max_width=32,
    max_batch=3 if fast else 2,
    table_len=table_len_for(MAX_SEQ, PAGE),
    spec_tokens=4 if fast else 0,
    echo=fast,
)
engine = ServingEngine(
    CFG,
    params,
    max_batch=3 if fast else 2,
    max_seq_len=MAX_SEQ,
    decode_chunk=4,
    prefill_buckets=(16, 32),
    prefill_batch=4,
    mesh=mesh,
    spmd=channel,
    page_size=PAGE,
    prefix_cache="auto" if fast else False,
    speculation="auto" if fast else False,
    speculation_tokens=4,
)

PREAMBLE = [(7 + i) % CFG.vocab_size for i in range(16)]
OPTS = GenerationOptions(max_new_tokens=6, temperature=0.0)

if pid == 0:
    engine.start()
    if fast:
        tokens = [
            engine.generate([5, 6, 7, 8], OPTS, timeout=600).tokens,
            engine.generate(PREAMBLE + [2, 3], OPTS, timeout=600).tokens,
            engine.generate(PREAMBLE + [4, 1], OPTS, timeout=600).tokens,
        ]
    else:
        tokens = engine.generate([5, 6, 7, 8], OPTS, timeout=600).tokens
    engine.stop()
    print(json.dumps({"role": "leader", "tokens": tokens}), flush=True)
else:
    follower_loop(engine, channel)
    print(json.dumps({"role": "follower", "done": True}), flush=True)

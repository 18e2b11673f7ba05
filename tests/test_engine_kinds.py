"""What the engine says of a launch and refuses of a kind of state, held to
the commit before both moved (ISSUE 59, step 0).

`golden/engine_kinds_at_parent.json` was taken at commit 9a6022b (PR 58) by this
file's `capture()` and is never re-taken: `python tests/test_engine_kinds.py
<out.json>` run in a checkout of THAT commit. Two tables:

- ``reads``: for every tiny preset (and `tiny-test` over an int8 pool, and
  the kinds whose read rule has a kernel form under ``attention_impl:
  pallas``), the span attributes and the increments of the `stats()` sums of
  two streams of prefill segments (offsets 0, a page's edge, inside a page;
  whole and partial) and one decode chunk over rows of unequal lengths, on
  an engine that is built and not started, its device programs replaced by
  stand-ins: nothing compiles.
- ``refusals``: for every (kind of model, option) pair the exception
  `ServingEngine(..)` raises (type and message, or none), and the
  `MigrationError` a migration command gets.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import pytest

if __name__ == "__main__":  # capture mode: tests/conftest.py is not loaded
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import numpy as np

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.serving import engine as E
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine

AT_PARENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden", "engine_kinds_at_parent.json")

TINY = (
    "tiny-test", "tiny-moe-test", "tiny-hybrid-test", "tiny-window-moe-test",
    "tiny-blockfill-moe-test", "tiny-sparse-moe-test", "tiny-latent-moe-test",
    "tiny-latent-dense-moe-test", "tiny-dots3-test", "tiny-lfm2-test",
)
# the kinds whose segment's read is a walk over key blocks where the kernels run
WALKS = (
    "tiny-window-moe-test", "tiny-sparse-moe-test", "tiny-latent-moe-test",
    "tiny-latent-dense-moe-test", "tiny-dots3-test",
)
VARIANTS = (
    [(name, name, {}) for name in TINY]
    + [("tiny-test+int8", "tiny-test", {"kv_cache_dtype": "int8"})]
    + [(f"{name}+pallas", name, {"attention_impl": "pallas"}) for name in WALKS]
)
WIDTH, PAGE = 128, 16
# (base, prompt length): segments at 0, 128, 256 and a last of 37 tokens; then a
# stream behind a reused prefix that ends inside a page: 40 and a last of 91
STREAMS = ((0, 3 * WIDTH + 37), (40, 40 + WIDTH + 91))
# (position, steps in flight) of the decode chunk's live rows, slot by slot
ROWS = ((3, 0), (40, 8), None, (129, 0))
STEPS = 8

READ_ATTRS = (
    "offset", "kv_tokens_read", "kv_tokens_read_window", "index_tokens_scored",
    "kv_tokens_selected", "latent_tokens_expanded", "latent_columns_expanded",
    "latent_expanded_window", "key_blocks", "key_blocks_window", "window_pages_recycled",
    "segments", "real_tokens", "computed_tokens", "steps", "active_rows", "row_steps",
    "kv_pages_visited", "kv_rows_written", "state_rows",
)
READ_SUMS = (
    "index-tokens-scored-total", "kv-tokens-selected-total", "latent-tokens-expanded-total",
    "latent-columns-expanded-total", "kv-tokens-read-total", "segment-key-blocks",
    "segment-writes",
)


def variant_config(preset: str, over: dict):
    return dataclasses.replace(MODEL_PRESETS[preset], **over)


def build(preset: str, over: dict, monkeypatch=None) -> ServingEngine:
    """An engine that is built and not started, whose three device programs
    are stand-ins that hand their state back: the host side of a launch runs
    whole and nothing is traced."""
    config = variant_config(preset, over)
    engine = ServingEngine(
        config, T.init_params(config, jax.random.PRNGKey(0)), max_batch=4, max_seq_len=512,
        page_size=PAGE, prefill_buckets=(WIDTH,), decode_chunk=STEPS,
    )

    def segment(params, tokens, s0, seg_len, pool, table, key, *rest, **kw):
        return (np.zeros(1, np.int32), pool, key, None)

    def decode(params, tokens, positions, pool, table, key, *rest):
        return (None, tokens, positions, pool, key, None, engine._moe_dev)

    def block(params, blocks, positions, pool, table, key, *rest):
        return (None, blocks, positions, pool, key, engine._moe_dev)

    patch = monkeypatch.setattr if monkeypatch is not None else setattr
    patch(E, "_paged_segment_and_sample", segment)
    patch(E, "_paged_decode_chunk", decode)
    patch(E, "_paged_block_chunk", block)
    patch(E, "_chain_scatter", lambda tokens, positions, temp, top_k, top_p, *rest: (
        tokens, positions, temp, top_k, top_p
    ))
    engine._submit_fetch = lambda *a, **kw: None
    return engine


def sums(engine) -> dict:
    stats = engine.stats()
    return {k: stats[k] for k in READ_SUMS if k in stats}


def moved(before: dict, after: dict) -> dict:
    """The increments between two readings of the sums (a dict's, key by key)."""
    out = {}
    for k, v in after.items():
        if isinstance(v, dict):
            out[k] = {n: v[n] - before.get(k, {}).get(n, 0) for n in v}
        else:
            out[k] = v - before.get(k, 0)
    return out


def reads_of(engine) -> dict:
    """The launches of STREAMS and ROWS on ``engine``: what each span would
    carry of READ_ATTRS and what each added to the sums."""
    out = {"sums_at_start": sums(engine), "segments": [], "decode": None}
    for slot, (base, n) in enumerate(STREAMS):
        request = GenerationRequest(
            prompt_tokens=[1 + i % 50 for i in range(n)],
            options=GenerationOptions(max_new_tokens=4, temperature=0.0),
        )
        assert engine._paged_bind(slot, request) is not None
        st = {"idx": slot, "request": request, "seg": 0, "base": base}
        while True:
            before = sums(engine)
            s0 = base + st["seg"] * WIDTH
            entries = engine._segment_step(st)
            attrs = st["disp"].attrs
            out["segments"].append({
                "s0": s0, "real": min(WIDTH, n - s0),
                "attrs": {k: attrs[k] for k in READ_ATTRS if k in attrs},
                "sums": moved(before, sums(engine)),
            })
            if entries and entries[0][0] == "prefill":
                break
    for slot in engine._slots:
        slot.request = None
    for slot, row in zip(engine._slots, ROWS):
        if row is not None:
            slot.request = GenerationRequest(
                prompt_tokens=[1], options=GenerationOptions(max_new_tokens=400)
            )
            slot.position, slot.ahead = row
    before = sums(engine)
    disp = engine._dispatch_chunk()[-1]
    out["decode"] = {
        "attrs": {k: disp.attrs[k] for k in READ_ATTRS if k in disp.attrs},
        "sums": moved(before, sums(engine)),
    }
    return out


# -- what a kind of state refuses ------------------------------------------

# option -> values tried, each alone
ASKS = {
    "prefix_cache": [True, "on", "auto"],
    "host_kv_fraction": [1.0],
    "migrate_staging": [True],
    "durable_dir": ["/nowhere"],
    "speculation": [True, "on", "auto"],
    "adapters": [[{"name": "a"}]],
    "mesh": [object()],
    "spmd": [object()],
    "constrained_decoding": ["on", "auto", True],
    "page_size": [18],
}
CONFIG_ASKS = {"ring_axis": ["ring"], "kv_cache_dtype": ["int8"]}
# several at once: the order they are named in is the table's, not the caller's
TOGETHER = {"spmd": object(), "speculation": "on", "host_kv_fraction": 2.0, "prefix_cache": True,
            "constrained_decoding": "on"}


def ask_id(option, value):
    return f"{option}={value if isinstance(value, (str, bool, int, float)) else 'set'}"


def refusal_cases():
    for option, values in {**ASKS, **CONFIG_ASKS}.items():
        for value in values:
            yield ask_id(option, value), ({option: value} if option in ASKS else {}), (
                {option: value} if option in CONFIG_ASKS else {}
            )
    yield "together", dict(TOGETHER), {}
    yield "together+int8", dict(TOGETHER), {"kv_cache_dtype": "int8"}


class _Built(Exception):
    """A stand-in was reached: the refusals were passed."""


# what a build that passes the refusals raises at its next check
PASSED = ["ValueError", "queue_depth must be >= 1, got 0"]


def refusal(preset: str, kwargs: dict, over: dict):
    """[type name, message] of what the configuration or the engine's build
    raises for an option, or None where the refusals let it pass (the build
    is stopped at the first check behind them: no engine is built)."""
    try:
        ServingEngine(variant_config(preset, over), None, queue_depth=0, **kwargs)
    except Exception as e:  # noqa: BLE001 — the table is of whatever it raises
        raised = [type(e).__name__, str(e)]
        return None if raised == PASSED else raised
    raise AssertionError("the build went past its stop")


def migration_refusal(engine):
    from langstream_tpu.serving.migrate import MigrationError

    class Reached:  # the command would be queued: no refusal
        def put(self, cmd):
            raise _Built

    engine._migrate_cmds = Reached()
    try:
        engine._migrate_rpc("snapshot", {}, 0.05)
    except MigrationError as e:
        return str(e)
    except _Built:
        return None


def capture(path: str) -> None:
    reads, migrate = {}, {}
    for vid, preset, over in VARIANTS:
        engine = build(preset, over)
        reads[vid] = reads_of(engine)
        migrate[vid] = migration_refusal(engine)
        print(vid, "read", flush=True)
    refusals = {
        preset: {cid: refusal(preset, kwargs, over) for cid, kwargs, over in refusal_cases()}
        for preset in TINY
    }
    with open(path, "w") as f:
        json.dump({"reads": reads, "migrate": migrate, "refusals": refusals}, f, indent=1, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    capture(sys.argv[1])
    sys.exit(0)


# -- the tests --------------------------------------------------------------

with open(AT_PARENT) as f:
    PARENT = json.load(f)


@pytest.fixture(scope="module", params=VARIANTS, ids=[v[0] for v in VARIANTS])
def launched(request):
    vid, preset, over = request.param
    with pytest.MonkeyPatch.context() as mp:
        engine = build(preset, over, mp)
        yield vid, engine, reads_of(engine)


def test_a_stream_s_segments_read_what_they_read_at_the_parent(launched):
    vid, _, got = launched
    want = PARENT["reads"][vid]
    assert got["sums_at_start"] == want["sums_at_start"]
    assert got["segments"] == want["segments"]


def test_a_decode_chunk_reads_what_it_read_at_the_parent(launched):
    vid, _, got = launched
    assert got["decode"] == PARENT["reads"][vid]["decode"]


def test_a_migration_command_is_refused_as_at_the_parent(launched):
    vid, engine, _ = launched
    assert migration_refusal(engine) == PARENT["migrate"][vid]


REFUSALS = {cid: (kwargs, over) for cid, kwargs, over in refusal_cases()}


@pytest.mark.parametrize("case", REFUSALS)
@pytest.mark.parametrize("preset", TINY)
def test_a_kind_refuses_what_it_refused_at_the_parent(preset, case):
    assert refusal(preset, *REFUSALS[case]) == PARENT["refusals"][preset][case]

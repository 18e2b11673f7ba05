"""The segment walk (`ops/attention._segment_kernel`) since PR 56: its running
maximum and sum stay columns `[G, block_q, 1]` (PR 55 read them as rows
`[G, block_q]`, which Mosaic lays along the lanes: eight turns of 512 values
between sublanes and lanes a key block). Held here, in interpret mode, to PR
55's kernel, which `dev/bench_segment_walk.py` carries for the comparison
(`segment_kernel_pr55`), EXACTLY: the same products, the same float32 sums in
the same order. And the host's count of the key blocks a call visits
(`segment_blocks_visited`) to the mask itself."""

import dataclasses
import functools
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS
from langstream_tpu.ops import attention as A

TINY = MODEL_PRESETS["tiny-test"]


@functools.cache
def _bench():
    """dev/bench_segment_walk.py as a module (it holds PR 55's kernel)."""
    path = Path(__file__).resolve().parents[1] / "dev" / "bench_segment_walk.py"
    spec = importlib.util.spec_from_file_location("bench_segment_walk", path)
    # (registered before it runs: a dataclass looks its module up)
    module = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# (offset, window, query heads a KV head, what else): a segment of 1,024
# queries over 4,096 columns, tiles of 512 (a group of 16: query tiles of 128)
CASES = {
    "offset-0": dict(offset=0),
    "offset-on-a-tile-s-edge": dict(offset=512),
    "offset-2048": dict(offset=2048),
    "offset-off-a-tile-s-edge": dict(offset=2048 + 192),
    "a-window-wider-than-a-tile": dict(offset=2048, window=1024),
    "a-window-wider-than-a-tile-off-the-edge": dict(offset=2048 + 192, window=1536 + 64),
    "a-window-of-a-tile": dict(offset=2048, window=512),
    "a-window-narrower-than-a-tile": dict(offset=2048 + 192, window=192),
    "a-query-block-past-the-table": dict(offset=3584),
    "a-query-block-past-the-table-under-a-window": dict(offset=3584 + 192, window=2048),
    "a-group-of-8": dict(offset=2048 + 192, group=8),
    "a-group-of-16": dict(offset=2048, group=16),
    "a-group-of-16-under-a-window": dict(offset=2048 + 192, group=16, window=1536),
    "a-selection": dict(offset=2048, selection="a third"),
    "a-selection-off-the-edge-group-of-8": dict(offset=2048 + 192, group=8, selection="a third"),
    "a-selection-of-nothing-in-the-early-blocks": dict(offset=2048, selection="late"),
    "a-selection-some-rows-of-nothing": dict(offset=512, selection="some rows none"),
}
S, T = 1024, 4096


def _case(name):
    """(q, k, v, offsets, config, window, chosen or None) of a case."""
    case = {"window": 0, "group": 1, "selection": None, **CASES[name]}
    rng = np.random.default_rng(sorted(CASES).index(name))
    group = case["group"]
    hkv = 2 if group == 1 else 1
    d = 16 if group == 16 else 96 if group == 8 else 64
    config = dataclasses.replace(
        TINY, attention_impl="pallas", n_heads=hkv * group, n_kv_heads=hkv, head_dim=d
    )
    q = jnp.asarray(rng.standard_normal((1, S, hkv * group, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, hkv, T, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, hkv, T, d)), jnp.float32)
    chosen = None
    if case["selection"]:
        position = case["offset"] + np.arange(S)[:, None]
        column = np.arange(T)[None, :]
        chosen = (column <= position) & (rng.random((S, T)) < 1 / 3)
        if case["selection"] == "late":  # nothing before the segment's own tiles
            chosen &= column >= case["offset"]
        if case["selection"] == "some rows none":
            chosen[::7] = False
        chosen = jnp.asarray(chosen[None], jnp.int8)
    return q, k, v, jnp.asarray([case["offset"]], jnp.int32), config, case["window"], chosen


def _walk(q, k, v, offsets, config, window, chosen):
    if chosen is None:
        return A.flash_segment_attention(q, k, v, offsets, config, window=window, interpret=True)
    return A.sparse_segment_attention(q, k, v, offsets, chosen, config, interpret=True)


def _visited(q, k, offsets, window):
    group = q.shape[2] // k.shape[1]
    return A.segment_blocks_visited(int(offsets[0]), S, T, q.shape[-1], group, window, itemsize=4)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_walk_is_pr55_s_to_the_bit(monkeypatch, name):
    q, k, v, offsets, config, window, chosen = _case(name)
    got = _walk(q, k, v, offsets, config, window, chosen)
    monkeypatch.setattr(A, "_segment_kernel", _bench().segment_kernel_pr55)
    jax.clear_caches()  # a trace is cached by the function, not by the patch
    want = _walk(q, k, v, offsets, config, window, chosen)
    assert np.isfinite(np.asarray(want)).all() and np.asarray(want).any()
    assert np.array_equal(np.asarray(got), np.asarray(want))
    if "nothing" in name:  # a query that chose nothing comes back zeros
        none = ~np.asarray(chosen[0]).any(-1)
        assert none.any() and not np.asarray(got)[0, none].any()


@pytest.mark.parametrize("name", ["offset-off-a-tile-s-edge", "a-group-of-16-under-a-window"])
def test_the_walk_is_masked_attention(name):
    """And both are the plain masked softmax (float32: to a rounding)."""
    from langstream_tpu.models import transformer as T_

    q, k, v, offsets, config, window, _ = _case(name)
    got = _walk(q, k, v, offsets, config, window, None)
    seen = T_._seen(offsets[:, None] + jnp.arange(S)[None, :], T, window)
    want = T_.attention(q, k, v, seen, config)
    assert float(jnp.abs(got - want).max()) < 1e-4


def _brute_force(offset, s, t, block_q, block_k, window):
    """Key blocks with a (query, key) pair the mask lets through, over the
    explicit [s, t] mask."""
    position = offset + np.arange(s)[:, None]
    column = np.arange(t)[None, :]
    seen = column <= position
    if window:
        seen &= column > position - window
    tiles = seen.reshape(s // block_q, block_q, t // block_k, block_k)
    return int(tiles.any((1, 3)).sum())


@pytest.mark.parametrize("name", sorted(n for n in CASES if "selection" not in n))
def test_the_host_counts_the_blocks_the_mask_lets_through(name):
    q, k, _, offsets, _, window, _ = _case(name)
    group = q.shape[2] // k.shape[1]
    block_q, block_k, _ = A.segment_key_blocks(S, T, q.shape[-1], group, window, itemsize=4)
    assert _visited(q, k, offsets, window) == _brute_force(
        int(offsets[0]), S, T, block_q, block_k, window
    )


@pytest.mark.parametrize("shape", [
    (0, 2048, 17408, 192, 1, 0), (14336, 2048, 17408, 192, 1, 0), (6144, 2048, 17408, 128, 8, 0),
    (10240, 2048, 12544, 128, 16, 4096), (4096 + 64, 2048, 12544, 128, 16, 4096),
    (0, 2048, 12544, 128, 16, 4096), (8192, 2048, 12544, 128, 16, 0),
    (30000, 2048, 12544, 128, 16, 4096),  # past the table and its window: nothing to visit
], ids=lambda shape: "-".join(map(str, shape)))
def test_the_host_counts_the_cells_blocks(shape):
    """At the four cells' sizes (Kimi's first and last segment of a longdoc
    row, Keye's, command-a-plus's under its window and without)."""
    offset, s, t, d, group, window = shape
    block_q, block_k, n_k = A.segment_key_blocks(s, t, d, group, window)
    visited = A.segment_blocks_visited(offset, s, t, d, group, window)
    assert visited == _brute_force(offset, s, t, block_q, block_k, window)
    assert visited <= (s // block_q) * n_k  # the grid's key axis holds every visit


@pytest.mark.parametrize("name", [
    "offset-off-a-tile-s-edge", "a-window-wider-than-a-tile-off-the-edge",
    "a-query-block-past-the-table-under-a-window", "a-group-of-16-under-a-window",
    "a-selection-off-the-edge-group-of-8",
])
def test_the_body_runs_as_often_as_the_host_counts(monkeypatch, name):
    """The kernel's `pl.when` is `_segment_blocks`' range: the rule, wrapped
    to say a grid step's (step, range) where the kernel asks it (the index maps
    ask it too, with the grid's indices and no step), against
    `segment_blocks_visited`."""
    q, k, v, offsets, config, window, chosen = _case(name)
    rule, ran = A._segment_blocks, set()

    def telling(q_start, *sizes, **where):
        first, last = rule(q_start, *sizes, **where)
        if isinstance(q_start, jax.core.Tracer):
            jax.debug.callback(
                lambda *step: ran.add(tuple(map(int, step))), q_start, first, last
            )
        return first, last

    monkeypatch.setattr(A, "_segment_blocks", telling)
    jax.clear_caches()
    jax.block_until_ready(_walk(q, k, v, offsets, config, window, chosen))
    jax.effects_barrier()
    # every (query block, its range) the kernel or an index map was given
    assert sum(max(last - first + 1, 0) for _, first, last in ran) == _visited(
        q, k, offsets, window
    )


@pytest.mark.parametrize("preset, impl, want", [
    # a latent's re-expanded heads (one query head a key head, keys nope + rope
    # wide) and a selection's: the blocks up to the diagonal's
    ("tiny-latent-dense-moe-test", "pallas", {"key_blocks": 3}),
    ("tiny-latent-moe-test", "pallas", {"key_blocks": 3}),
    ("tiny-sparse-moe-test", "pallas", {"key_blocks": 3}),
    # a window model: its full layers' call and its window layers' (a window of 16)
    ("tiny-window-moe-test", "pallas", {"key_blocks": 3, "key_blocks_window": 2}),
    # masked jnp: the CPU's own choice, and a model whose segments never take the walk
    ("tiny-window-moe-test", "auto", {}),
    ("tiny-test", "pallas", {}),
    ("tiny-moe-test", "pallas", {}),
])
def test_a_model_says_the_blocks_its_segment_walks(preset, impl, want):
    """256 queries at offset 512 over a table of 1,280 columns, one tile of
    256 x 256: key blocks 0 .. 2, and under a window of 16 the block before
    its own and its own."""
    from langstream_tpu.models import transformer as T_

    config = dataclasses.replace(MODEL_PRESETS[preset], attention_impl=impl)
    assert T_.segment_blocks_visited(512, 256, 1280, config) == want

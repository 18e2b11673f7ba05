"""An admission group's insert by page (ISSUE 37): `ops/attention.
paged_insert_pages` copies each mapped page of a prefill's local cache into
the pool where it lies. In interpret mode on the CPU its pool is bit-equal
to `paged_insert_cache`'s scatter, its reference, and every page no table
names keeps its bytes; `insert_copies_pages` says which pools take which
write; and an engine under `attention_impl="pallas"` emits the jnp engine's
tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.ops import attention as A
from langstream_tpu.serving.engine import GenerationRequest, ServingEngine

PAGES = 12
OOB = PAGES  # the table's sentinel: the first index past the pool

# name: (page size, width, kv heads, head dim, layers, the rows' tables).
# A table names physical pages; OOB is a page the row does not hold.
CASES = {
    "one-page-one-row": (16, 16, 8, 16, 1, [[5]]),
    "many-pages-one-row": (16, 64, 8, 16, 3, [[3, 1, 7, 10]]),
    "several-rows": (16, 32, 8, 16, 2, [[0, 11], [4, 2], [9, 6]]),
    "a-row-all-sentinel": (16, 32, 8, 16, 2, [[8, 3], [OOB, OOB]]),
    "every-row-all-sentinel": (16, 32, 8, 16, 2, [[OOB, OOB], [OOB, OOB]]),
    "fewer-pages-than-the-width": (16, 64, 8, 16, 2, [[7, 2, OOB, OOB], [1, OOB, OOB, OOB]]),
    "table-shorter-than-the-width": (16, 64, 8, 16, 2, [[7, 2], [1, 4]]),
    "table-longer-than-the-width": (16, 32, 8, 16, 2, [[7, 2, 9, OOB, OOB], [1, 4, OOB, OOB, OOB]]),
    "page-64-lanes-128": (64, 128, 8, 128, 2, [[10, 0], [OOB, OOB]]),
    "page-64-one-page": (64, 64, 8, 128, 3, [[6]]),
    "thirty-kv-heads": (64, 128, 30, 128, 2, [[2, 9], [5, OOB]]),
    "thirty-kv-heads-page-16": (16, 48, 30, 16, 4, [[2, 9, 4]]),
    "more-copies-than-in-flight": (16, 64, 8, 16, 5, [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]]),
}


def _operands(case):
    ps, width, hkv, d, layers, table = CASES[case]
    n = len(table)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    pool = {
        leaf: jax.random.normal(key, (layers, PAGES, hkv, ps, d), jnp.bfloat16)
        for leaf, key in zip("kv", keys[:2])
    }
    local = {
        leaf: jax.random.normal(key, (layers, n, hkv, width, d), jnp.bfloat16)
        for leaf, key in zip("kv", keys[2:])
    }
    return pool, local, jnp.asarray(table, jnp.int32), ps


def _bits(x):
    return np.asarray(x).view(np.uint16)


@pytest.mark.parametrize("case", sorted(CASES))
def test_page_copies_land_where_the_scatter_s_do(case):
    pool, local, table, ps = _operands(case)
    if case == "more-copies-than-in-flight":  # the ring of semaphores wraps
        assert CASES[case][4] * int(np.sum(np.asarray(table) < OOB)) > A._INSERT_IN_FLIGHT
    got = jax.jit(
        lambda local, pool, table: A.paged_insert_pages(
            (local["k"], local["v"]), pool["k"], pool["v"], table, interpret=True
        )
    )(local, pool, table)
    # off the TPU `paged_insert_cache` is the scatter: the reference
    want = T.paged_insert_cache(pool, local, table, ps)
    for leaf, out in zip("kv", got):
        np.testing.assert_array_equal(_bits(out), _bits(want[leaf]))
    # by hand: a mapped (row, logical page) holds that row's columns of every
    # layer, and every page no table names keeps its bytes
    per_row = CASES[case][1] // ps
    named = {}
    for row, pages in enumerate(np.asarray(table)):
        for col, page in enumerate(pages[:per_row]):
            if page < OOB:
                named[int(page)] = (row, col)
    for leaf, out in zip("kv", got):
        out, was, loc = _bits(out), _bits(pool[leaf]), _bits(local[leaf])
        for page in range(PAGES):
            if page in named:
                row, col = named[page]
                np.testing.assert_array_equal(
                    out[:, page], loc[:, row, :, col * ps : (col + 1) * ps]
                )
            else:
                np.testing.assert_array_equal(out[:, page], was[:, page])


TINY = MODEL_PRESETS["tiny-test"]
FORCED = dataclasses.replace(TINY, attention_impl="pallas")


def test_insert_takes_the_kernel_where_the_gates_say_and_the_pools_agree():
    """`paged_insert_cache` with the engine's config: page copies where the
    paged kernels are on, and the scatter's pool to the bit; a pool with a
    recurrent state beside the pages hands it through."""
    config = dataclasses.replace(MODEL_PRESETS["tiny-hybrid-test"], attention_impl="pallas")
    ps, width, n = 16, 32, 2
    pool = T.make_page_pool(config, PAGES, ps, state_rows=n)
    pool = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.ndim), a.shape, a.dtype), pool
    )
    kv, rec = T.split_rec(pool)
    local = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(7), a.shape[:1] + (n,) + a.shape[2:3]
                                    + (width,) + a.shape[4:], a.dtype),
        kv,
    )
    table = jnp.asarray([[3, OOB, OOB], [1, 8, OOB]], jnp.int32)
    insert = lambda config: jax.jit(  # noqa: E731
        lambda pool, local, table: T.paged_insert_cache(pool, local, table, ps, config)
    )
    assert "paged_insert_pages" in str(jax.make_jaxpr(insert(config))(pool, local, table))
    assert "paged_insert_pages" not in str(jax.make_jaxpr(insert(None))(pool, local, table))
    got, want = insert(config)(pool, local, table), insert(None)(pool, local, table)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for g, was in zip(jax.tree.leaves(got["rec"]), jax.tree.leaves(rec)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(was))


@pytest.mark.parametrize("why", [
    "kernel", "auto-off-the-tpu", "no-config-off-the-tpu", "jnp", "int8-pool", "ragged-width",
    "mesh", "window-groups",
])
def test_which_pools_take_which_write(why):
    """The scatter stays for the int8 pool, a width that is no whole number
    of pages, a mesh, a window model's two groups, and (auto, or no config)
    every backend but the TPU."""
    config, width, ps = FORCED, 32, 16
    if why == "auto-off-the-tpu":
        config = TINY
    elif why == "no-config-off-the-tpu":
        config = None
    elif why == "jnp":
        config = dataclasses.replace(TINY, attention_impl="jnp")
    elif why == "int8-pool":
        config = dataclasses.replace(FORCED, kv_cache_dtype="int8")
    elif why == "ragged-width":
        width = 40
    elif why == "mesh":
        from jax.sharding import Mesh

        from langstream_tpu.parallel.mesh import AXIS_ORDER

        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 1, 2), AXIS_ORDER)
        config = dataclasses.replace(FORCED, kernel_mesh=mesh)
    elif why == "window-groups":
        config = dataclasses.replace(
            MODEL_PRESETS["tiny-window-moe-test"], attention_impl="pallas"
        )
    pool = T.make_page_pool(config or TINY, PAGES, ps)
    assert T.insert_copies_pages(pool, width, ps, config) == (why == "kernel")


def test_an_admitted_prompt_s_tokens_are_the_jnp_engine_s():
    """An engine whose admission group writes by page (interpret mode)
    against one whose group scatters: the same greedy tokens, prompts of a
    bucket of two pages and of one, and a group of two rows."""
    params = T.init_params(TINY, jax.random.PRNGKey(0))
    prompts = [[5, 9, 2] * 9, [7] * 11, [3, 1] * 4]
    tokens = {}
    for impl in ("pallas", "jnp"):
        engine = ServingEngine(
            dataclasses.replace(TINY, attention_impl=impl), params, max_batch=4,
            max_seq_len=64, decode_chunk=4, page_size=16, prefill_buckets=(16, 32),
            prefill_batch=2,
        )
        engine.start()
        try:
            reqs = [
                engine.submit(GenerationRequest(
                    prompt_tokens=p,
                    options=GenerationOptions(max_new_tokens=6, temperature=0.0),
                ))
                for p in prompts
            ]
            tokens[impl] = [r.result(timeout=300).tokens for r in reqs]
        finally:
            engine.stop()
    assert tokens["pallas"] == tokens["jnp"]
    assert all(len(t) == 6 for t in tokens["jnp"])


# ---------------------------------------------------------------------------
# A prefill SEGMENT's rows by whole pages (ISSUE 48): one layer's new rows
# from inside the layer loop, `ops/attention.paged_insert_layer_pages` behind
# `models/transformer._paged_write_rows`. Its reference is the scatter
# (`_paged_scatter`, `_write_index_key`): the pools are bit-equal.
# ---------------------------------------------------------------------------

SEG_PS = 8

# name: (which leaves, kv heads, head dim, S, each row's offset, the rows' tables)
SEGMENT_CASES = {
    "offset-0": ("kv", 2, 16, 16, [0], [[5, 2, OOB, OOB]]),
    "offset-of-several-pages": ("kv", 2, 16, 16, [24], [[5, 2, 9, 1, 7, OOB]]),
    "rows-at-their-own-offsets": (
        "kv", 4, 8, 16, [8, 32], [[5, 2, 9, 1, 7, 0], [3, 4, 6, 8, 10, 11]],
    ),
    # the first row holds one page of the segment's two, the second none
    # (a padding or warm-up row): what is not mapped drops
    "table-ends-inside-the-segment": ("kv", 2, 16, 16, [16, 0], [[5, 2, 9, OOB], [OOB] * 4]),
    "segment-past-the-table": ("kv", 2, 16, 24, [16], [[5, 2, 9, 1]]),
    "every-row-all-sentinel": ("kv", 2, 16, 16, [0, 0], [[OOB] * 3, [OOB] * 3]),
    "thirty-kv-heads": ("kv", 30, 16, 16, [8], [[4, 0, 6, OOB]]),
    # a latent in place of K and V: one head, 576 kept at 640, and the
    # indexer's key beside it; Keye's three leaves; the key's leaf alone
    "latent-leaf": ("lat+ik", 1, 640, 16, [16], [[5, 2, 9, 1, 7, OOB]]),
    "kv-and-the-indexer-s-key": ("kv+ik", 2, 16, 16, [8], [[5, 2, 9, OOB]]),
    "more-copies-than-in-flight": ("kv", 2, 16, 8 * 40, [0], [list(range(40))]),  # a pool of 40
    # a warm suffix that starts inside a page keeps the scatter: the same pool
    "offset-inside-a-page": ("kv+ik", 2, 16, 16, [11], [[5, 2, 9, 1]]),
    "one-row-inside-a-page": ("kv", 2, 16, 16, [8, 3], [[5, 2, 9, 1], [3, 4, 6, 8]]),
}


def _segment_operands(case):
    leaves, hkv, d, s, offsets, table = SEGMENT_CASES[case]
    n, layers = len(table), 3
    pages = max(PAGES, max(len(row) for row in table))  # a table longer than PAGES names them all
    keys = iter(jax.random.split(jax.random.PRNGKey(len(case)), 8))
    normal = lambda *shape: jax.random.normal(next(keys), shape, jnp.bfloat16)  # noqa: E731
    pools, rows = [], []
    for leaf in leaves.split("+"):
        if leaf == "ik":  # kept 128 lanes wide, made 24: `_kept_width` pads
            pools.append(normal(layers, pages, SEG_PS, 128))
            rows.append(normal(n, s, 24))
        else:
            for _ in range({"kv": 2, "lat": 1}[leaf]):  # the latent: one leaf, of one head
                pools.append(normal(layers, pages, hkv, SEG_PS, d))
                rows.append(normal(n, hkv, s, d))
    positions = jnp.asarray(offsets, jnp.int32)[:, None] + jnp.arange(s)[None, :]
    return tuple(pools), rows, jnp.asarray(table, jnp.int32), positions


def _scattered(pools, rows, layer, table, positions):
    return tuple(
        (T._paged_scatter if new.ndim == 4 else T._write_index_key)(
            pool, layer, new, table, positions, SEG_PS
        )
        for pool, new in zip(pools, rows)
    )


@pytest.mark.parametrize("case", sorted(SEGMENT_CASES))
def test_a_segment_s_page_copies_land_where_the_scatter_s_do(case):
    pools, rows, table, positions = _segment_operands(case)
    if case == "more-copies-than-in-flight":  # the ring of semaphores wraps
        assert positions.shape[1] // SEG_PS > A._INSERT_IN_FLIGHT
    layer = jnp.int32(1)
    write = jax.jit(
        lambda pools, rows, table, positions: T._paged_write_rows(
            pools, rows, layer, table, positions, SEG_PS, FORCED, segment=True
        )
    )
    assert "paged_insert_pages" in str(jax.make_jaxpr(write)(pools, rows, table, positions))
    got = write(pools, rows, table, positions)
    want = _scattered(pools, rows, layer, table, positions)
    assert len(got) == len(pools)
    changed = False
    for g, w, was in zip(got, want, pools):
        np.testing.assert_array_equal(_bits(g), _bits(w))
        # the other layers keep their bytes
        np.testing.assert_array_equal(_bits(g)[[0, 2]], _bits(was)[[0, 2]])
        changed |= not np.array_equal(_bits(g), _bits(was))
    assert changed == (case != "every-row-all-sentinel")


@pytest.mark.parametrize("why", [
    "segment", "verify-or-block", "auto-off-the-tpu", "jnp", "int8-pool", "ragged-width", "mesh",
])
def test_which_segments_take_which_write(why):
    """One rule (`_copies_pages`) for the program and for the engine's
    counter: the scatter stays for a verify step and a block pass (not a
    causal segment), the int8 pool, a width that is no whole number of pages,
    a mesh, and (auto) every backend but the TPU; `attention_paths()` names
    the writer a segment was traced with."""
    config, s = FORCED, 16
    if why == "auto-off-the-tpu":
        config = TINY
    elif why == "jnp":
        config = dataclasses.replace(TINY, attention_impl="jnp")
    elif why == "int8-pool":
        config = dataclasses.replace(FORCED, kv_cache_dtype="int8")
    elif why == "ragged-width":
        s = 20
    elif why == "mesh":
        from jax.sharding import Mesh

        from langstream_tpu.parallel.mesh import AXIS_ORDER

        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 1, 2), AXIS_ORDER)
        config = dataclasses.replace(FORCED, kernel_mesh=mesh)
    pool = T.make_page_pool(config, PAGES, SEG_PS)
    assert T.segment_copies_pages(pool, s, SEG_PS, config) == (
        why in ("segment", "verify-or-block")
    )
    hkv, d = config.n_kv_heads, config.resolved_head_dim
    rows = [jnp.zeros((1, hkv, s, d), jnp.bfloat16)] * 2
    table = jnp.asarray([[5, 2, 9]], jnp.int32)
    positions = jnp.arange(s)[None, :]
    segment = why != "verify-or-block"
    was = dict(A._PATHS)
    A._PATHS.clear()
    try:
        text = str(jax.make_jaxpr(
            lambda pool: T._paged_write_rows(
                (pool["k"], pool["v"]), rows, jnp.int32(0), table, positions, SEG_PS, config,
                segment=segment,
            )
        )(pool))
        paths = A.attention_paths()
    finally:
        A._PATHS.update(was)
    assert ("paged_insert_pages" in text) == (why == "segment")
    assert paths == (
        {f"paged-segment-write[s={s}]": "paged_insert_pages" if why == "segment" else "scatter"}
        if segment else {}
    )


# preset: the pool's page groups a segment writes through their own tables
SEGMENT_PRESETS = (
    "tiny-test", "tiny-sparse-moe-test", "tiny-window-moe-test", "tiny-latent-moe-test",
)


@pytest.mark.parametrize("how", ["cold", "behind-three-pages-and-short"])
@pytest.mark.parametrize("preset", SEGMENT_PRESETS)
def test_a_segment_gives_the_logits_and_the_pool_it_gave(preset, how, monkeypatch):
    """`paged_prefill_segment_inplace` with the page writer against the same
    program held to the scatter (the parent's): equal logits, bit-equal pools,
    every leaf and group (command-a-plus's window group through its own
    table, the latent, the indexer's key), a last segment with ``seg_length <
    S`` and a padding row beside it."""
    config = dataclasses.replace(MODEL_PRESETS[preset], attention_impl="pallas")
    params = T.init_params(config, jax.random.PRNGKey(0))
    pool = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.ndim), a.shape, a.dtype),
        T.make_page_pool(config, 2 * PAGES, SEG_PS),
    )
    table = jnp.asarray([[3, 7, 1, 9, 14, 20], [2 * PAGES] * 6], jnp.int32)
    if config.has_window:  # the window group's table, pages of its own
        table = jnp.stack([table, jnp.asarray([[5, 2, 11, 4, 8, 0], [2 * PAGES] * 6], jnp.int32)])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 1, config.vocab_size)
    offsets, lengths = ([0, 0], [16, 0]) if how == "cold" else ([24, 0], [11, 0])

    def run():
        return jax.jit(
            lambda params, pool: T.paged_prefill_segment_inplace(
                params, tokens, jnp.asarray(offsets, jnp.int32), jnp.asarray(lengths, jnp.int32),
                pool, table, config, SEG_PS,
            )
        )(params, pool)[:2]

    got_logits, got_pool = run()
    assert A.attention_paths()["paged-segment-write[s=16]"] == "paged_insert_pages"
    monkeypatch.setattr(T, "_copies_pages", lambda *a: False)
    want_logits, want_pool = run()
    assert A.attention_paths()["paged-segment-write[s=16]"] == "scatter"
    np.testing.assert_array_equal(np.asarray(got_logits[0]), np.asarray(want_logits[0]))
    assert jax.tree.structure(got_pool) == jax.tree.structure(want_pool)
    for g, w, was in zip(*map(jax.tree.leaves, (got_pool, want_pool, pool))):
        np.testing.assert_array_equal(_bits(g), _bits(w))
        assert not np.array_equal(_bits(g), _bits(was))


def test_the_engine_counts_its_segments_by_writer():
    """A prompt of three segments through an engine whose segments write by
    page (interpret mode) and through one that scatters: the same greedy
    tokens, `stats()["segment-writes"]` under the writer each took, restarted
    by `reset_histograms`."""
    params = T.init_params(TINY, jax.random.PRNGKey(0))
    prompt = [(7 * i) % 97 + 1 for i in range(40)]
    tokens = {}
    for impl, writer in (("pallas", "pages"), ("jnp", "scatter")):
        engine = ServingEngine(
            dataclasses.replace(TINY, attention_impl=impl), params, max_batch=2,
            max_seq_len=64, decode_chunk=4, page_size=8, prefill_buckets=(16,),
            prefill_batch=1,
        )
        engine.start()
        try:
            engine.reset_histograms()  # the warm-up's segments are not the window's
            assert engine.stats()["segment-writes"] == {"pages": 0, "scatter": 0}
            request = engine.submit(GenerationRequest(
                prompt_tokens=prompt,
                options=GenerationOptions(max_new_tokens=5, temperature=0.0),
            ))
            tokens[impl] = request.result(timeout=300).tokens
            counted = engine.stats()["segment-writes"]
            assert counted == {"pages": 0, "scatter": 0, writer: 3}
            engine.reset_histograms()
            assert engine.stats()["segment-writes"] == {"pages": 0, "scatter": 0}
        finally:
            engine.stop()
    assert tokens["pallas"] == tokens["jnp"] and len(tokens["jnp"]) == 5

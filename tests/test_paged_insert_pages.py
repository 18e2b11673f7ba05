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

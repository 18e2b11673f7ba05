"""The paged layer scan addresses the pool by (layer, page) and forms no
per-layer entry. Here, against the way it was: a reference that slices each
layer's entry [P, Hkv, ps, D] out of the pool, scatters and gathers on that
entry, and writes the entry back must give the same logits and the same
pool, bit for bit — the arithmetic is the same, on the same bytes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS

PS, PAGES, TP, B = 8, 12, 3, 4  # page size, pool pages, table length, rows
OOB = PAGES  # the table's unmapped sentinel
# Row 0 is live. Row 1 is a free slot: position 0 behind a cleared table.
# Row 2's position lies past its (fully mapped) table: `_page_index` turns
# it into the sentinel, which must DROP, not land on the next layer's page
# 0. Row 3 is padding with an all-out-of-bounds table. Pages 0, 4, 8-11 are
# in no row's table.
TABLE = np.array(
    [[3, 1, OOB], [OOB, OOB, OOB], [5, 2, 7], [OOB, OOB, OOB]], np.int32
)
POSITIONS = np.array([11, 0, TP * PS + 3, 5], np.int32)
UNMAPPED = sorted(set(range(PAGES)) - set(TABLE[TABLE < OOB].tolist()))


# -- the reference: the per-entry addressing, as it was before ---------------


def _entry_scatter(entry, vals, table, positions, page_size):
    num_pages = (entry["q"] if isinstance(entry, dict) else entry).shape[0]
    pages, offs = T._page_index(table, positions, page_size, num_pages)
    pidx, oidx = pages[:, None, :], offs[:, None, :]
    hidx = jnp.arange(vals.shape[1])[None, :, None]
    if isinstance(entry, dict):
        q, s = T._quantize_kv(vals)
        return {
            "q": entry["q"].at[pidx, hidx, oidx].set(q, mode="drop"),
            "s": entry["s"].at[pidx, hidx, oidx].set(s, mode="drop"),
        }
    return entry.at[pidx, hidx, oidx].set(vals.astype(entry.dtype), mode="drop")


def _entry_gather(entry, table, page_size):
    def gather(a):
        b, tp = table.shape
        g = jnp.moveaxis(jnp.take(a, table, axis=0, mode="clip"), 2, 1)
        return g.reshape((b, a.shape[1], tp * page_size) + a.shape[3:])

    return jax.tree.map(gather, entry)


def _sliced_scatter(pool, layer, vals, table, positions, page_size):
    entry = jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, layer, 0, keepdims=False), pool
    )
    entry = _entry_scatter(entry, vals, table, positions, page_size)
    return jax.tree.map(
        lambda a, n: lax.dynamic_update_index_in_dim(a, n, layer, 0), pool, entry
    )


def _sliced_gather(pool, layer, table, page_size):
    entry = jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, layer, 0, keepdims=False), pool
    )
    return _entry_gather(entry, table, page_size)


# -- the three entry points ---------------------------------------------------


def _decode(params, pool, config):
    tokens = jnp.asarray([7, 0, 9, 0], jnp.int32)
    return T.paged_decode_step_inplace(
        params, tokens, jnp.asarray(POSITIONS), pool, jnp.asarray(TABLE), config, PS
    )


def _verify(params, pool, config):
    tokens = jnp.asarray(np.arange(B * 3).reshape(B, 3) % 50 + 1, jnp.int32)
    # row 2 starts two columns before its table's end: its last token drops
    positions = jnp.asarray(POSITIONS).at[2].set(TP * PS - 2)
    return T.paged_verify_step_inplace(
        params, tokens, positions, pool, jnp.asarray(TABLE), config, PS
    )


def _segment(params, pool, config):
    w = 8
    tokens = jnp.asarray(np.arange(B * w).reshape(B, w) % 60 + 1, jnp.int32)
    # row 0 fills its second page from mid-page; row 2's segment straddles
    # the end of its table; rows 1 and 3 are padding (length 0)
    offsets = jnp.asarray([PS + 3, 0, TP * PS - 3, 0], jnp.int32)
    seg_lengths = jnp.asarray([w, 0, w, 0], jnp.int32)
    return T.paged_prefill_segment_inplace(
        params, tokens, offsets, seg_lengths, pool, jnp.asarray(TABLE), config, PS
    )


ENTRY_POINTS = {"decode": _decode, "verify": _verify, "segment": _segment}


def _random_pool(config, key):
    return _random_pool_of(config, PAGES, key)


def _random_pool_of(config, pages, key):
    """A pool with something in every page, so an untouched page shows."""
    shapes = jax.eval_shape(lambda: T.make_page_pool(config, pages, PS))
    leaves, tree = jax.tree.flatten(shapes)
    out = []
    for leaf, k in zip(leaves, jax.random.split(key, len(leaves))):
        if leaf.dtype == jnp.int8:
            out.append(jax.random.randint(k, leaf.shape, -127, 128, jnp.int8))
        else:
            scale = 0.02 if leaf.ndim == 4 else 1.0  # int8 scales are small
            out.append(
                (jax.random.uniform(k, leaf.shape, jnp.float32) * scale + 0.001)
                .astype(leaf.dtype)
            )
    return jax.tree.unflatten(tree, out)


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
@pytest.mark.parametrize("kv", ["model", "int8"])
@pytest.mark.parametrize("preset", ["tiny-test", "tiny-moe-test"])
def test_pool_addressed_in_place_matches_per_entry_reference(
    preset, kv, entry, monkeypatch
):
    config = dataclasses.replace(
        MODEL_PRESETS[preset], n_layers=3, kv_cache_dtype=kv, attention_impl="jnp"
    )
    params = T.init_params(config, jax.random.PRNGKey(0))
    pool = _random_pool(config, jax.random.PRNGKey(1))
    before = jax.tree.map(np.asarray, pool)
    run = ENTRY_POINTS[entry]

    logits, new_pool = jax.jit(lambda p, c: run(p, c, config))(params, pool)
    with monkeypatch.context() as m:
        m.setattr(T, "_paged_scatter", _sliced_scatter)
        m.setattr(T, "_paged_gather", _sliced_gather)
        ref_logits, ref_pool = jax.jit(lambda p, c: run(p, c, config))(params, pool)

    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    assert np.isfinite(np.asarray(logits)).all()
    for got, want, was in zip(
        jax.tree.leaves(new_pool), jax.tree.leaves(ref_pool), jax.tree.leaves(before)
    ):
        got = np.asarray(got)
        np.testing.assert_array_equal(got, np.asarray(want))
        # no row maps them: page 0 (where a sentinel folded into a flat
        # [L*P] index would land, one layer on) and the rest, in every layer
        np.testing.assert_array_equal(got[:, UNMAPPED], was[:, UNMAPPED])
        # the live row did write: its page differs in every layer
        assert all((got[l, TABLE[0, 1]] != was[l, TABLE[0, 1]]).any() for l in range(3))


# -- the decode kernel's lengths: what the table maps (PR 28) -----------------

LENGTH_CASES = {
    # name: (table row, position, the kernel's length)
    "live-mid-page": ([3, 1, OOB], 11, 12),
    "live-page-boundary": ([3, 1, OOB], PS - 1, PS),
    "live-first-column-of-a-page": ([3, 1, OOB], PS, PS + 1),
    "free-slot-stale-position": ([OOB, OOB, OOB], 2 * PS + 5, 0),
    "padding-row": ([OOB, OOB, OOB], 0, 0),
    "past-its-reservation": ([3, OOB, OOB], PS + 4, PS),
    "reserved-ahead-of-the-position": ([3, 1, 7], 2, 3),
    "full-table": ([5, 2, 7], TP * PS - 1, TP * PS),
    "past-the-table": ([5, 2, 7], TP * PS + 3, TP * PS),
}


@pytest.mark.parametrize("case", sorted(LENGTH_CASES))
def test_a_decode_rows_length_is_what_its_table_maps(case):
    row, position, want = LENGTH_CASES[case]
    got = T._paged_lengths(
        jnp.asarray([row], jnp.int32), jnp.asarray([position], jnp.int32), PS, PAGES
    )
    assert got.dtype == jnp.int32 and got.tolist() == [want]


@pytest.mark.parametrize("kv", ["model", "int8"])
@pytest.mark.parametrize("preset", ["tiny-test", "tiny-moe-test"])
def test_decode_through_the_kernel_matches_the_jnp_path_on_live_rows(preset, kv):
    """`paged_decode_step_inplace` with the kernel forced (interpret mode)
    beside the masked-jnp path, on a batch with a free slot behind a stale
    position, a padding row and a row whose position has left its table:
    the same writes to the pool, finite logits everywhere (an inactive row
    reads nothing, so no garbage page can reach it), and the live rows'
    logits the jnp path's (MoE: rows share expert capacity, so a dead row's
    zeros against the jnp path's garbage may route differently; the dense
    model's rows are independent)."""
    config = dataclasses.replace(
        MODEL_PRESETS[preset], n_layers=3, kv_cache_dtype=kv, dtype="float32",
        moe_capacity_factor=0.0,
    )
    params = T.init_params(config, jax.random.PRNGKey(0))
    pool = _random_pool(config, jax.random.PRNGKey(1))
    table = TABLE.copy()
    positions = jnp.asarray(POSITIONS).at[1].set(2 * PS + 1)  # stale, not 0

    def run(impl):
        cfg = dataclasses.replace(config, attention_impl=impl)
        tokens = jnp.asarray([7, 0, 9, 0], jnp.int32)
        return jax.jit(lambda p, c: T.paged_decode_step_inplace(
            p, tokens, positions, c, jnp.asarray(table), cfg, PS
        ))(params, pool)

    from langstream_tpu.ops.attention import attention_paths

    logits, new_pool = run("pallas")
    assert any(
        k.startswith("paged-decode") and v.startswith("ragged_paged_decode_attention")
        for k, v in attention_paths().items()
    )
    ref_logits, ref_pool = run("jnp")
    # layer 0's rows are computed before any attention: the same bytes;
    # later layers' follow the attention's output, which is close, not equal
    for got, want in zip(jax.tree.leaves(new_pool), jax.tree.leaves(ref_pool)):
        np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want)[0])
    logits, ref_logits = np.asarray(logits), np.asarray(ref_logits)
    assert np.isfinite(logits).all()
    # rows 0 (live) and 2 (its whole table, position past it). The int8
    # jnp path quantises q and the kernel does not (tests/test_pallas_ops.py
    # holds the int8 kernel to the dequantised reference): over row 2's 24
    # columns of random int8 that alone moves a logit by more than 1
    if kv == "int8":
        np.testing.assert_allclose(logits[0], ref_logits[0], atol=0.1)
    else:
        np.testing.assert_allclose(logits[[0, 2]], ref_logits[[0, 2]], atol=2e-4)


# -- the pool's write: a decode step's kernel, the scatter for the rest ------

W_PAGES, W_TP, W_LAYERS = 128, 17, 3  # pages of PS; a row's table holds 136 tokens


def _rows(b, mapped, starts):
    """A table with ``mapped`` = {row: its pages} and each row's first
    position; every other row sits at the sentinel behind a stale position."""
    table = np.full((b, W_TP), W_PAGES, np.int32)
    for row, pages in mapped.items():
        table[row, : len(pages)] = pages
    first = (np.arange(b, dtype=np.int32) * 7) % (W_TP * PS)  # stale positions
    for row, start in starts.items():
        first[row] = start
    return table, first


def _write_case(name, s):
    """(table [B, Tp], first positions [B], layer) of a named drop case for
    ``s`` tokens a row. Where a case is about an edge (the table's end, the
    last mapped page), the row starts ``s // 2`` before it: one token of a
    decode step is past it, a longer write straddles it."""
    full = list(range(40, 40 + W_TP))  # a whole table of pages
    if name == "all-rows-at-the-sentinel":
        return *_rows(4, {}, {0: 0, 1: 5, 2: W_TP * PS + 1}), 1
    if name == "a-position-past-the-table":
        return *_rows(4, {0: full, 2: [3, 9, 4]}, {0: W_TP * PS - s // 2, 2: 1}), 1
    if name == "last-mapped-page-full-next-unmapped":
        return *_rows(3, {1: [7, 2]}, {1: max(0, 2 * PS - s // 2)}), 1
    if name == "6-of-64-rows-mapped":
        live = [3, 10, 17, 30, 41, 63]
        pages = np.random.default_rng(0).permutation(W_PAGES)[: 6 * W_TP].reshape(6, W_TP)
        return *_rows(
            64, dict(zip(live, pages.tolist())), {r: i for i, r in enumerate(live)}
        ), 1
    if name == "adjacent-offsets-of-different-pages":
        return *_rows(2, {0: [3] + full[1:], 1: [4] + full[:-1][::-1]}, {0: 5, 1: 6}), 1
    if name == "layer-last":  # a sentinel folded into the page would leave the array
        return *_rows(4, {1: full, 3: [0, W_PAGES - 1]}, {1: 13, 2: 3, 3: PS}), W_LAYERS - 1
    raise KeyError(name)


WRITE_CASES = [
    "all-rows-at-the-sentinel", "a-position-past-the-table",
    "last-mapped-page-full-next-unmapped", "6-of-64-rows-mapped",
    "adjacent-offsets-of-different-pages", "layer-last",
]


@pytest.mark.parametrize("s", [1, 3, 128])
@pytest.mark.parametrize("kv", ["model", "int8"])
@pytest.mark.parametrize("case", WRITE_CASES)
def test_the_pool_write_lands_and_drops_as_the_scatter_did(case, kv, s):
    """`_attention_block`'s write of the new K/V rows, with the kernels
    forced (interpret mode): `paged_kv_write` for a decode step into the
    bf16 pool, `_paged_scatter` for the int8 pool and for S > 1. Against
    the per-entry scatter above the pools are bit-equal, and the slots
    that changed are exactly the tokens a hand count lands: nothing of a
    dropped token reaches any page of any layer."""
    config = dataclasses.replace(
        MODEL_PRESETS["tiny-test"], n_layers=W_LAYERS, kv_cache_dtype=kv,
        attention_impl="pallas",
    )
    table, first, layer = _write_case(case, s)
    b = len(first)
    positions = first[:, None] + np.arange(s, dtype=np.int32)[None, :]
    params = T.init_params(config, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda a: a[layer], params["layers"])
    pool = _random_pool_of(config, W_PAGES, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, config.d_model), jnp.bfloat16)

    @jax.jit
    def run(x, pool):
        pos = jnp.asarray(positions)
        sin, cos = T._rope_freqs(pos, config)
        mask = T._paged_mask(jnp.asarray(table), PS, pos)
        _, k, v = T._qkv(x, lp, sin, cos, config, None, None, None)
        _, (pk, pv) = T._attention_block(
            x, lp, sin, cos, mask, config, cache_kv=(pool["k"], pool["v"]),
            cache_positions=pos, paged_table=jnp.asarray(table), page_size=PS,
            layer=jnp.asarray(layer, jnp.int32),
        )
        want = tuple(
            _sliced_scatter(
                leaf, layer, vals.transpose(0, 2, 1, 3), jnp.asarray(table), pos, PS
            )
            for leaf, vals in ((pool["k"], k), (pool["v"], v))
        )
        return (pk, pv), want

    # the kernel carries exactly the decode step into the bf16 pool
    traced = str(jax.make_jaxpr(run)(x, pool))
    assert ("paged_kv_write" in traced) == (kv == "model" and s == 1)
    got, want = run(x, pool)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the hand count: token (b, j) lands iff its logical page is mapped
    landed = set()
    for row in range(b):
        for pos in positions[row]:
            lpage = pos // PS
            if lpage < W_TP and table[row, lpage] < W_PAGES:
                landed.add((int(table[row, lpage]), int(pos % PS)))
    for leaf, was in zip(jax.tree.leaves(got), jax.tree.leaves((pool["k"], pool["v"]))):
        leaf, was = np.asarray(leaf).astype(np.float32), np.asarray(was).astype(np.float32)
        differs = leaf != was  # [L, P, Hkv, ps(, D)]
        while differs.ndim > 4:
            differs = differs.any(-1)
        assert not np.delete(differs, layer, axis=0).any(), "another layer was touched"
        changed = {(int(p), int(o)) for p, _, o in zip(*np.nonzero(differs[layer]))}
        assert changed == landed


# -- a loop step of the paged decode walk takes a GROUP of a row's pages (PR 52) --
# Every entry point of the one skeleton, its group forced to 2, 4 and 8 pages
# (the rule itself gives 8, 4 or 2 by the table's length, `_walk_shape`),
# against `_paged_gather` and the stock attention math, and against the same
# call at a page a step: the fold takes a group's pages in the row's order, so
# every float32 sum is the one-page walk's and the two outputs are BIT-equal.
# (The one sum a group does take in another SHAPE is a score's own, q . k over
# the head's width, one product for the group's pages: the chip's MXU sums it
# alike, the CPU's dot need not, so queries and keys here are quarters in
# [-1, 1] and every such sum is exact in whatever order.
# dev/bench_paged_walk.py `against_single` reads 0.0 on the chip at the
# cells' shapes with normal draws.)

WALK_PS, WALK_LAYERS, WALK_D = 8, 2, 8
UNFUSED = {"xla_disable_hlo_passes": "fusion"}
WALK_ENTRIES = [
    "decode", "softcap", "int8", "block", "selected", "latent", "latent-selected", "windowed",
]


def _walk_rows(batch: str, n: int):
    """(pages a row, tokens its last page lacks, a window row's lower bound
    in pages + tokens): a batch of rows around a group of ``n`` pages."""
    if batch == "edges":
        # 0, 1, n - 1, n, n + 1, 2n + 3 pages; rows of length 0 between live
        # rows, which the fetch-ahead crosses; lengths that end mid-page
        pages = [0, 1, n - 1, 0, n, n + 1, 0, 2 * n + 3]
        short = [0, 3, 0, 0, 5, 0, 0, 1]
        # a lower bound inside the first page, inside the row's last page, on
        # a page's edge (so the row's first page is whole), none
        lower = [(0, 0), (0, 2), (0, 0), (0, 0), (n - 1, 4), (1, 0), (0, 0), (0, 5)]
    else:
        # an odd and an even long row in one batch, one that ends mid-page
        pages = [4 * n + 1, 0, 4 * n, 3 * n + 2]
        short = [0, 0, 0, 6]
        # a lower bound in the second page of an aligned group of n, one on
        # the edge that makes the row's first page the last of such a group
        lower = [(n + 1, 3), (0, 0), (n - 1, 0), (2, 7)]
    return pages, short, lower


def _quarters(x):
    return jnp.clip(jnp.round(x * 4), -4, 4) / 4


def _walk_case(entry: str, batch: str, n: int):
    """(call() -> [B, ...] float32, reference [B, ...], live rows)."""
    from langstream_tpu.ops import attention as A

    ps, d = WALK_PS, WALK_D
    per_row, short, lower = _walk_rows(batch, n)
    b, tp = len(per_row), max(per_row) + 1
    rng = np.random.default_rng(7)
    pages = b * tp + 1  # the last page holds NaN and no table names it
    table = np.full((b, tp), pages - 1, np.int32)
    free = iter(rng.permutation(pages - 1))
    for r, p in enumerate(per_row):
        table[r, :p] = [next(free) for _ in range(p)]
    table = jnp.asarray(table)
    lengths = jnp.asarray([p * ps - s for p, s in zip(per_row, short)], jnp.int32)
    t = tp * ps
    cols = jnp.arange(t)[None, :]
    seen = cols < lengths[:, None]
    if entry == "windowed":
        low = jnp.minimum(jnp.asarray([p * ps + o for p, o in lower], jnp.int32), lengths)
        seen &= cols >= low[:, None]
    chosen = None
    if entry.endswith("selected"):
        chosen = jnp.asarray(rng.random((b, t)) < 0.4)
        # nothing chosen in a row's first whole group (all-masked pages inside
        # a group and alone); in the last row, only in its short last page
        chosen = chosen.at[:, : n * ps].set(False).at[-1, : (per_row[-1] - 1) * ps].set(False)
        chosen = chosen.at[-1, (per_row[-1] - 1) * ps].set(True)
        seen &= chosen
    layer = jnp.int32(1)
    key = jax.random.PRNGKey(3)
    if entry.startswith("latent"):
        config = dataclasses.replace(
            MODEL_PRESETS["tiny-latent-dense-moe-test"], attention_impl="pallas"
        )
        h, width, kl = config.n_heads, config.latent_key_width, config.kv_lora_rank
        k1, k2 = jax.random.split(key)
        pool = _quarters(jax.random.normal(k1, (WALK_LAYERS, pages, 1, ps, width)))
        pool = pool.at[:, -1].set(jnp.nan)
        q = _quarters(jax.random.normal(k2, (b, h, width)))
        call = lambda: A.ragged_paged_latent_attention(  # noqa: E731
            q, pool, lengths, table, layer, chosen, config, ps, interpret=True
        )
        rows = T._paged_gather(pool.at[:, -1].set(0.0), layer, table, ps)[:, 0]
        logits = jnp.einsum("bhw,btw->bht", q, rows) * config.attn_scale
        probs = jnp.where(seen[:, None], jnp.exp(logits - logits.max(-1, keepdims=True)), 0.0)
        want = jnp.einsum("bht,btc->bhc", probs, rows[..., :kl]) / jnp.maximum(
            probs.sum(-1, keepdims=True), 1e-30
        )
        return call, want.reshape(b, h * kl), seen.any(-1)
    config = dataclasses.replace(
        MODEL_PRESETS["tiny-test"], attention_impl="pallas", head_dim=d,
        attn_logit_softcap=1.5 if entry == "softcap" else None,
    )
    h, hkv = config.n_heads, config.n_kv_heads
    shape = (WALK_LAYERS, pages, hkv, ps)
    keys = jax.random.split(key, 5)
    if entry == "int8":
        # the scales differ page by page: a group reads each page's own
        pool = [
            {
                "q": jax.random.randint(kq, shape + (d,), -127, 127, jnp.int8),
                "s": (jax.random.uniform(ks, shape) * 0.05 + 0.01).at[:, -1].set(jnp.inf),
            }
            for kq, ks in (keys[:2], keys[2:4])
        ]
        dense = [
            leaf["q"].astype(jnp.float32) * leaf["s"].at[:, -1].set(0.0)[..., None]
            for leaf in pool
        ]
    else:
        pool = [
            fit(jax.random.normal(k, shape + (d,))).at[:, -1].set(jnp.nan)
            for fit, k in zip((_quarters, lambda x: x), keys[:2])
        ]
        dense = [leaf.at[:, -1].set(0.0) for leaf in pool]
    s = 4 if entry == "block" else 1
    q = _quarters(jax.random.normal(keys[4], (b, s, h, d)))
    want = T.attention(
        q, *(T._paged_gather(leaf, layer, table, ps) for leaf in dense),
        jnp.broadcast_to(seen[:, None, :], (b, s, t)), config,
    ).reshape(b, -1)
    if entry == "block":
        call = lambda: A.ragged_paged_block_attention(  # noqa: E731
            q, *pool, lengths, table, layer, config, ps, interpret=True
        )
    elif entry == "int8":
        call = lambda: A.ragged_paged_decode_attention_int8(  # noqa: E731
            q[:, 0], *pool, lengths, table, layer, config, ps, interpret=True
        )
    elif entry == "selected":
        call = lambda: A.ragged_paged_selected_attention(  # noqa: E731
            q[:, 0], *pool, lengths, table, layer, chosen, config, ps, interpret=True
        )
    else:
        bound = {"lower": low} if entry == "windowed" else {}
        call = lambda: A.ragged_paged_decode_attention(  # noqa: E731
            q[:, 0], *pool, lengths, table, layer, config, ps, interpret=True, **bound
        )
    return call, want, seen.any(-1)


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("batch", ["edges", "long"])
@pytest.mark.parametrize("entry", WALK_ENTRIES)
def test_a_loop_step_of_the_walk_takes_a_group_of_pages(monkeypatch, entry, batch, n):
    """Rows of 0, 1, n - 1, n, n + 1 and 2n + 3 pages, long rows odd and even,
    lengths that end mid-page, rows of length 0 between live rows, a window's
    lower bound inside a page and on a page's edge, anywhere in a group, a
    selection that leaves whole pages and a whole group out and one that reads
    a row's short end alone, the int8 pool's scales page by page, a softcap:
    every entry point is the gathered reference on the live rows, zeros on the
    rest, never computes on a page a row does not hold (the clamped sentinel's
    page is NaN), and is the walk of single pages TO THE BIT."""
    from langstream_tpu.ops import attention as A

    call, want, live = _walk_case(entry, batch, n)
    live = np.asarray(live)
    outs = {}
    for pages_a_step in (n, 1):
        monkeypatch.setattr(A, "_walk_shape", lambda *a, g=pages_a_step: (g, A._walk_slots(g)))
        jax.clear_caches()  # a trace is cached by the function, not by the patch
        out = jax.jit(call).lower().compile(compiler_options=UNFUSED)()
        outs[pages_a_step] = np.asarray(out.astype(jnp.float32)).reshape(len(live), -1)
    got = outs[n]
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[~live], 0.0)
    np.testing.assert_allclose(
        got[live], np.asarray(want)[live], atol=2e-4 if entry == "int8" else 2e-5
    )
    np.testing.assert_array_equal(got, outs[1])
    walks = {k: v for k, v in A.attention_paths().items() if k.startswith("paged-walk[")}
    assert any(f"ps={WALK_PS}]" in k and v.startswith("pages/step ") for k, v in walks.items())


KV_PAGE = 2 * 64 * 128 * 2  # K and V of one KV head, 64 tokens of 128 bf16 lanes


@pytest.mark.parametrize(
    "cell,page_bytes,table,want",
    [
        # the nine cells' pools (pages of 64 tokens) and tables. A latent's one
        # leaf of 640 lanes; K and V of 4 heads: a step takes a group
        ("kimik25-ep32-d7-longdoc-drain", 64 * 640 * 2, 272, (8, 16)),
        ("glm5-ep16-d7-longdoc-drain", 64 * 640 * 2, 272, (8, 16)),
        ("keyevl2-d12-longdoc-drain", 4 * KV_PAGE, 272, (8, 16)),
        ("sdar30b-d12-blockdecode-drain", 4 * KV_PAGE, 11, (4, 8)),
        ("an int8 pool of 8 heads under docs' table", 8 * KV_PAGE // 2, 33, (8, 16)),
        # K and V of 8 and of 30 heads, 256 KB and 983 KB: the one-page walk
        ("mistral7b-docs-drain (its pool is bf16)", 8 * KV_PAGE, 33, (1, 4)),
        ("mistral7b-chat-steady", 8 * KV_PAGE, 20, (1, 4)),
        ("mixtral8x7b-d6-decode-drain", 8 * KV_PAGE, 10, (1, 4)),
        ("olmohybrid7b-decode-drain", 30 * KV_PAGE, 10, (1, 4)),
        ("cmdaplus-ep8-d8-ragdocs-drain: full", 8 * KV_PAGE, 196, (1, 4)),
        ("cmdaplus-ep8-d8-ragdocs-drain: window", 8 * KV_PAGE, 196, (1, 4)),
        # tables too short for two steps of 4, and for two of 2
        ("the tiny presets' table of 6", 2 * 2 * 8 * 8 * 4, 6, (2, 5)),
        ("a table of three pages", 4 * KV_PAGE, 3, (1, 4)),
    ],
)
def test_the_walk_s_group_follows_the_page_and_the_table(cell, page_bytes, table, want):
    """`_walk_shape` at the cells' shapes: 8 pages a step where a page is
    under 256 KB (4 and 2 where the table is too short to hold two steps),
    with slots for the step computed on and as many pages in flight, three at
    least; at 256 KB and above, and under tables that hold no two steps, the
    one-page walk with the four slots it had."""
    from langstream_tpu.ops import attention as A

    assert A._walk_shape(page_bytes, table) == want
    group, slots = want
    assert slots - group >= 3 and slots * page_bytes <= 16 * A._WALK_PAGE_BYTES

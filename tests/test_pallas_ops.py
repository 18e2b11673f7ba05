"""Pallas kernel correctness (interpret mode on CPU) vs the jnp reference
attention, plus end-to-end forward/prefill/decode equivalence with the
kernels forced on."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, ModelConfig
from langstream_tpu.models.transformer import (
    attention,
    decode_step,
    forward,
    init_params,
    make_kv_cache,
    prefill,
)
from langstream_tpu.ops.attention import flash_prefill_attention, pallas_ok

CFG = ModelConfig(
    name="k", vocab_size=128, d_model=64, n_layers=1, n_heads=8, n_kv_heads=4,
    d_ff=64, dtype="float32",
)
SOFTCAP_CFG = dataclasses.replace(CFG, attn_logit_softcap=30.0)


def rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def test_flash_prefill_matches_reference():
    b, s, h, hkv, d = 2, 64, 8, 4, 8
    q = rand(0, b, s, h, d)
    # head-major K/V [B, Hkv, S, D] — the cache layout
    k, v = rand(1, b, hkv, s, d), rand(2, b, hkv, s, d)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), jnp.bool_))[None], (b, s, s))
    for config in (CFG, SOFTCAP_CFG):
        ref = attention(q, k, v, causal, config)
        out = flash_prefill_attention(q, k, v, config, block_q=16, block_k=16, interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=1e-5, atol=1e-5)


def test_forward_with_pallas_matches_jnp():
    base = dataclasses.replace(
        MODEL_PRESETS["tiny-test"], dtype="float32", attention_impl="jnp"
    )
    forced = dataclasses.replace(base, attention_impl="pallas")
    params = init_params(base, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, base.vocab_size)
    ref = forward(params, tokens, base)
    out = forward(params, tokens, forced)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-4, atol=2e-4)


def test_prefill_decode_with_pallas_matches_jnp():
    """The model-level pair the paged admission runs (``prefill`` into a
    local cache) and the tests' reference step (``decode_step``): forcing
    the kernel changes the prefill's attention only, and nothing the cache
    or the next step's logits hold beyond rounding."""
    base = dataclasses.replace(
        MODEL_PRESETS["tiny-test"], dtype="float32", attention_impl="jnp"
    )
    forced = dataclasses.replace(base, attention_impl="pallas")
    params = init_params(base, jax.random.PRNGKey(0))
    b, s, t = 2, 16, 64
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 1, base.vocab_size)
    lengths = jnp.asarray([s, s - 5], jnp.int32)

    logits_ref, cache_ref = prefill(params, tokens, lengths, make_kv_cache(base, b, t), base)
    logits_out, cache_out = prefill(
        params, tokens, lengths, make_kv_cache(forced, b, t), forced
    )
    np.testing.assert_allclose(
        np.asarray(logits_ref), np.asarray(logits_out), rtol=2e-4, atol=2e-4
    )

    nxt = jnp.argmax(logits_ref, axis=-1).astype(jnp.int32)
    d_ref, _ = decode_step(params, nxt, lengths, cache_ref, base)
    d_out, _ = decode_step(params, nxt, lengths, cache_out, forced)
    np.testing.assert_allclose(
        np.asarray(d_ref), np.asarray(d_out), rtol=2e-4, atol=2e-4
    )


def test_encode_never_uses_causal_kernel():
    """Embeddings use bidirectional attention; pallas flash is causal-only,
    so encode must stay on the jnp path even when forced."""
    from langstream_tpu.models.transformer import encode

    base = dataclasses.replace(
        MODEL_PRESETS["tiny-test"], dtype="float32", attention_impl="jnp"
    )
    forced = dataclasses.replace(base, attention_impl="pallas")
    params = init_params(base, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 1, base.vocab_size)
    lengths = jnp.asarray([32, 20], jnp.int32)
    ref = encode(params, tokens, lengths, base)
    out = encode(params, tokens, lengths, forced)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-4, atol=2e-4)


def test_pallas_ok_gating():
    tpu = jax.default_backend() == "tpu"
    # jnp impl always refuses
    assert not pallas_ok(dataclasses.replace(CFG, attention_impl="jnp"), 128)
    # ring axis owns SP
    assert not pallas_ok(dataclasses.replace(CFG, ring_axis="seq"), 128)
    # auto on CPU refuses; forced accepts divisible shapes
    assert pallas_ok(dataclasses.replace(CFG, attention_impl="pallas"), 64)
    # auto requires BOTH a tpu backend and a lane-aligned head dim
    assert pallas_ok(CFG, 128) == (tpu and CFG.resolved_head_dim % 128 == 0)
    wide = dataclasses.replace(CFG, head_dim=128)
    assert pallas_ok(wide, 128) == tpu


def _int8_cache(key, b, hkv, t, d):
    from langstream_tpu.models.transformer import _quantize_kv

    q8, s = _quantize_kv(rand(key, b, hkv, t, d))
    return {"q": q8, "s": s}


def _gather_entry(entry, table, ps):
    """The gathered view of ONE layer's entry [P, Hkv, ps(, D)], as the
    paged path formed it before it addressed the pool by (layer, page)."""
    b, tp = table.shape
    g = jnp.moveaxis(jnp.take(entry, table, axis=0, mode="clip"), 2, 1)
    return g.reshape((b, entry.shape[1], tp * ps) + entry.shape[3:])


# (layers in the pool, the layer the call reads): the kernels take the whole
# pool and a layer index; every other layer holds other values
PAGED_LAYERS = [(1, 0), (3, 2), (3, 0)]


@pytest.mark.parametrize("n_layers,layer", PAGED_LAYERS)
def test_ragged_paged_decode_matches_gathered_reference(n_layers, layer):
    """The ragged-paged decode kernel (interpret mode) must match the
    gathered masked-jnp view bit-for-bit-ish: same layer, same pages, same
    logical order, same mask — the kernel only changes WHERE the read
    happens."""
    from langstream_tpu.models.transformer import _paged_gather
    from langstream_tpu.ops.attention import ragged_paged_decode_attention

    b, h, hkv, d, ps, pages, tp = 3, 8, 4, 8, 8, 16, 4
    q = rand(0, b, h, d)
    k = rand(1, n_layers, pages, hkv, ps, d)
    v = rand(2, n_layers, pages, hkv, ps, d)
    # ragged tables: unmapped entries carry the OOB sentinel (= pages)
    table = jnp.asarray(
        np.array(
            [[3, 1, pages, pages], [0, 2, 5, pages], [7, pages, pages, pages]],
            np.int32,
        )
    )
    lengths = jnp.asarray([13, 26, 5], jnp.int32)
    k_all = _gather_entry(k[layer], table, ps)
    v_all = _gather_entry(v[layer], table, ps)
    # the jnp fallback's one gather through (layer, table) is that view
    np.testing.assert_array_equal(
        np.asarray(_paged_gather(k, jnp.int32(layer), table, ps)), np.asarray(k_all)
    )
    mask = jnp.arange(tp * ps)[None, None, :] < lengths[:, None, None]
    ref = attention(q[:, None], k_all, v_all, mask, CFG)[:, 0]
    out = jax.jit(
        lambda l: ragged_paged_decode_attention(
            q, k, v, lengths, table, l, CFG, ps, interpret=True
        )
    )(jnp.int32(layer))  # traced, as the layer scan hands it down
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("n_layers,layer", PAGED_LAYERS)
def test_ragged_paged_decode_int8_matches_dequantized_reference(n_layers, layer):
    """int8 paged kernel vs attention over the dequantized gathered view.
    Like the dense int8 ragged kernel, q stays full-precision in the
    kernel (the jnp int8 path re-quantizes q), so the comparison is
    against the dequantized-K/V reference with a quantization tolerance."""
    from langstream_tpu.ops.attention import ragged_paged_decode_attention_int8

    b, h, hkv, d, ps, pages, tp = 2, 8, 4, 8, 8, 8, 3
    shape = (n_layers, pages, hkv, ps)
    q = rand(0, b, h, d)
    kq = jax.random.randint(jax.random.PRNGKey(1), shape + (d,), -127, 127, jnp.int8)
    ks = jax.random.uniform(jax.random.PRNGKey(2), shape) * 0.05 + 0.01
    vq = jax.random.randint(jax.random.PRNGKey(3), shape + (d,), -127, 127, jnp.int8)
    vs = jax.random.uniform(jax.random.PRNGKey(4), shape) * 0.05 + 0.01
    k = {"q": kq, "s": ks}
    v = {"q": vq, "s": vs}
    table = jnp.asarray(np.array([[2, 0, pages], [5, 4, 1]], np.int32))
    lengths = jnp.asarray([11, 22], jnp.int32)

    def dense(pool):
        g = {n: _gather_entry(a[layer], table, ps) for n, a in pool.items()}
        return g["q"].astype(jnp.float32) * g["s"][..., None]

    mask = jnp.arange(tp * ps)[None, None, :] < lengths[:, None, None]
    ref = attention(q[:, None], dense(k), dense(v), mask, CFG)[:, 0]
    out = jax.jit(
        lambda l: ragged_paged_decode_attention_int8(
            q, k, v, lengths, table, l, CFG, ps, interpret=True
        )
    )(jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# -- the kernel does work only for the pages that are live (PR 28) -----------

RAGGED_PS, RAGGED_PAGES, RAGGED_TP = 8, 24, 4


def _ragged_pool(int8: bool, n_layers: int):
    """A pool whose LAST page no table names and which holds NaN (inf for
    the int8 scales): the page a clamped sentinel entry reads. A kernel
    that computes on a page past a row's length, even fully masked
    (0 × NaN), shows it."""
    shape = (n_layers, RAGGED_PAGES, 4, RAGGED_PS)
    if not int8:
        k, v = (rand(n, *shape, 8).at[:, -1].set(jnp.nan) for n in (1, 2))
        return k, v, lambda pool: pool
    k, v = (
        {
            "q": jax.random.randint(jax.random.PRNGKey(n), shape + (8,), -127, 127, jnp.int8),
            "s": (jax.random.uniform(jax.random.PRNGKey(n + 1), shape) * 0.05 + 0.01)
            .at[:, -1].set(jnp.inf),
        }
        for n in (1, 3)
    )
    return k, v, lambda pool: pool["q"].astype(jnp.float32) * pool["s"][..., None]


def _ragged_table(pages_per_row):
    """Row r maps its first ``pages_per_row[r]`` table entries to pages no
    other row holds; the rest carry the sentinel."""
    oob = RAGGED_PAGES
    table = np.full((len(pages_per_row), RAGGED_TP), oob, np.int32)
    free = iter(np.random.default_rng(0).permutation(RAGGED_PAGES - 1))
    for r, n in enumerate(pages_per_row):
        table[r, :n] = [next(free) for _ in range(n)]
    return table


# name: (mapped pages per row, length per row). A row of length 0 has an
# all-sentinel table, as `_dispatch_tables` leaves an inactive row.
P_ = RAGGED_PS
RAGGED_CASES = {
    # live rows between, before and after rows without a table: every way a
    # row's first page is started (by the row before it, or by itself)
    "empty-rows-between": ([2, 0, 0, 3, 1, 0], [P_ + 5, 0, 0, 2 * P_ + 1, 3, 0]),
    "empty-first-and-last": ([0, 4, 2, 0], [0, 3 * P_ + 7, P_ + 1, 0]),
    "all-empty": ([0, 0, 0], [0, 0, 0]),
    "one-live-row": ([0, 0, 3], [0, 0, 2 * P_ + 2]),
    # lengths of exactly one page, on and just past a page boundary
    "exactly-one-page": ([1, 1, 2], [P_, 1, P_ + 1]),
    "page-boundaries": ([2, 3, 3, 1], [2 * P_, 2 * P_ + 1, 3 * P_, P_ - 1]),
    # every row holds its whole table
    "full-table": ([4, 4, 4], [4 * P_, 4 * P_, 4 * P_ - 1]),
    # more pages mapped (reserved) than the length has reached: not read
    "reserved-ahead": ([4, 3, 0, 2], [P_ + 2, 5, 0, P_]),
}


@pytest.mark.parametrize("case", sorted(RAGGED_CASES))
@pytest.mark.parametrize("n_layers,layer", [(1, 0), (3, 1)])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_ragged_paged_decode_follows_the_live_pages(int8, n_layers, layer, case):
    """Rows of length 0 (all-sentinel table) come back exact zeros, never
    NaN, beside live rows that match the gathered float32 reference; no row
    computes on a page past its length (the clamped sentinel's page holds
    NaN)."""
    from langstream_tpu.ops.attention import (
        ragged_paged_decode_attention,
        ragged_paged_decode_attention_int8,
    )

    mapped, lengths = RAGGED_CASES[case]
    table = _ragged_table(mapped)
    k, v, dense = _ragged_pool(int8, n_layers)
    q = rand(0, len(lengths), 8, 8)
    kernel = ragged_paged_decode_attention_int8 if int8 else ragged_paged_decode_attention
    out = np.asarray(jax.jit(
        lambda l: kernel(
            q, k, v, jnp.asarray(lengths, jnp.int32), jnp.asarray(table), l, CFG,
            RAGGED_PS, interpret=True,
        )
    )(jnp.int32(layer)))
    assert np.isfinite(out).all()
    live = [r for r, n in enumerate(lengths) if n > 0]
    empty = [r for r, n in enumerate(lengths) if n == 0]
    np.testing.assert_array_equal(out[empty], 0.0)
    if live:
        # the reference sees the live rows only, gathered through tables
        # whose unread entries name a finite page
        t = jnp.asarray(np.where(table[live] < RAGGED_PAGES, table[live], 0))
        k_all = _gather_entry(dense(k)[layer], t, RAGGED_PS)
        v_all = _gather_entry(dense(v)[layer], t, RAGGED_PS)
        lens = jnp.asarray(lengths, jnp.int32)[jnp.asarray(live)]
        mask = jnp.arange(RAGGED_TP * RAGGED_PS)[None, None, :] < lens[:, None, None]
        ref = attention(q[jnp.asarray(live), None], k_all, v_all, mask, CFG)[:, 0]
        np.testing.assert_allclose(out[live], np.asarray(ref), atol=1e-4 if int8 else 1e-5)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_a_row_past_its_mapped_pages_reads_only_the_mapped_ones(int8):
    """The caller's length rule with the kernel: a position that has run
    past what the table maps (a row stepping past its reservation inside a
    chunk) reads the mapped pages and no other; a stale position behind a
    cleared table reads nothing."""
    from langstream_tpu.models.transformer import _paged_lengths
    from langstream_tpu.ops.attention import (
        ragged_paged_decode_attention,
        ragged_paged_decode_attention_int8,
    )

    table = _ragged_table([2, 0, 4, 1])
    positions = jnp.asarray([3 * P_ + 2, 2 * P_ + 5, 9 * P_, 4], jnp.int32)
    lengths = _paged_lengths(jnp.asarray(table), positions, RAGGED_PS, RAGGED_PAGES)
    assert lengths.tolist() == [2 * P_, 0, 4 * P_, 5]
    k, v, dense = _ragged_pool(int8, 2)
    q = rand(0, 4, 8, 8)
    kernel = ragged_paged_decode_attention_int8 if int8 else ragged_paged_decode_attention
    out = np.asarray(kernel(
        q, k, v, lengths, jnp.asarray(table), jnp.int32(1), CFG, RAGGED_PS,
        interpret=True,
    ))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[1], 0.0)
    t = jnp.asarray(np.where(table < RAGGED_PAGES, table, 0))
    mask = jnp.arange(RAGGED_TP * RAGGED_PS)[None, None, :] < lengths[:, None, None]
    ref = attention(
        q[:, None], _gather_entry(dense(k)[1], t, RAGGED_PS),
        _gather_entry(dense(v)[1], t, RAGGED_PS), mask, CFG,
    )[:, 0]
    np.testing.assert_allclose(
        out[[0, 2, 3]], np.asarray(ref)[[0, 2, 3]], atol=1e-4 if int8 else 1e-5
    )


@pytest.mark.parametrize("step_pages", [1, 4], ids=["page-a-step", "4-pages-a-step"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_ragged_paged_decode_under_a_model_mesh_matches_one_device(int8, step_pages):
    """Under `config.kernel_mesh` the paged kernels shard_map themselves
    over the pool's kv heads, which lie on axis 2 of [L, P, Hkv, ps(, D)]:
    each of the four shards runs the kernel on its own head, and the
    stitched output is the unsharded kernel's. Under a table of three pages
    the walk takes a page a step; under one of nine a group of 4 (PR 52,
    `_walk_shape` by a SHARD's page and the table), and the last row's eight
    pages are one group and four single steps."""
    from jax.sharding import Mesh

    from langstream_tpu.ops.attention import (
        _walk_shape,
        attention_paths,
        ragged_paged_decode_attention,
        ragged_paged_decode_attention_int8,
    )
    from langstream_tpu.parallel.mesh import AXIS_ORDER

    b, h, hkv, d, ps, pages, layers = 3, 8, 4, 8, 8, 16, 2
    shape = (layers, pages, hkv, ps)
    q = rand(0, b, h, d)
    if int8:
        kernel = ragged_paged_decode_attention_int8
        k, v = (
            {
                "q": jax.random.randint(jax.random.PRNGKey(n), shape + (d,), -127, 127, jnp.int8),
                "s": jax.random.uniform(jax.random.PRNGKey(n + 1), shape) * 0.05 + 0.01,
            }
            for n in (1, 3)
        )
    else:
        kernel = ragged_paged_decode_attention
        k, v = rand(1, *shape, d), rand(2, *shape, d)
    # a live row, a row without a table (length 0), a row with a full one
    table = np.array([[2, 0, pages], [pages, pages, pages], [5, 4, 1]], np.int32)
    lengths = jnp.asarray([11, 0, 24], jnp.int32)
    if step_pages > 1:
        table = np.concatenate([table, np.full((3, 6), pages, np.int32)], axis=1)
        table[2, 3:8] = [9, 3, 12, 7, 10]
        lengths = jnp.asarray([11, 0, 61], jnp.int32)
    table = jnp.asarray(table)
    assert _walk_shape(2 * ps * d * 4, table.shape[1])[0] == step_pages
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 1, 1, 4), AXIS_ORDER)

    def run(config):
        return jax.jit(
            lambda l: kernel(q, k, v, lengths, table, l, config, ps, interpret=True)
        )(jnp.int32(1))

    sharded = np.asarray(run(dataclasses.replace(CFG, kernel_mesh=mesh)))
    np.testing.assert_allclose(sharded, np.asarray(run(CFG)), atol=1e-6)
    np.testing.assert_array_equal(sharded[1], 0.0)
    walk = attention_paths()[f"paged-walk[{kernel.__name__},ps={ps}]"]
    assert walk.startswith(f"pages/step {step_pages},")

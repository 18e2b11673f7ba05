"""A model whose attention reads a learned selection (`tiny-sparse-moe-test`'s
size, float32 on the CPU) against the plain reference of
`benchmark/reference/keye_vl2.py`, at lengths past its top-k:

(i)   what the config admits and refuses by name;
(ii)  `forward`, `prefill` then decode through the page pool, and a prompt
      chunked into segments that cross the top-k, each against the
      reference's full forward pass (logits), and the selected sets against
      the reference's away from ties;
(iii) m-rope: unequal triples against the reference, equal triples equal to
      the rotary there is;
(iv)  the ranking by counting against `lax.top_k`'s set, ties included;
(v)   the kernels against their jnp (Pallas in interpret mode): the indexer's
      scores in tiles, the segment walk under a packed selection, the decode
      walk under a row's selection as a mask over its pages;
(vi)  the page pool's third leaf: made, written where K and V are, inserted
      from a local cache.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS, ModelConfig
from langstream_tpu.ops import attention as A

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
sys.path[:0] = [p for p in (str(BENCH),) if p not in sys.path]
from modelcfg import load_module  # noqa: E402

ref = load_module("reference", "keye_vl2")

PRESET = MODEL_PRESETS["tiny-sparse-moe-test"]
TINY = dataclasses.replace(PRESET, dtype="float32")
DIMS = {
    "n_heads": TINY.n_heads, "n_kv_heads": TINY.n_kv_heads, "head_dim": TINY.resolved_head_dim,
    "rope_theta": TINY.rope_theta, "eps": TINY.rms_norm_eps, "top_k": TINY.n_experts_per_tok,
    "n_experts": TINY.n_experts, "index_n_heads": TINY.index_n_heads,
    "index_head_dim": TINY.index_head_dim, "index_topk": TINY.index_topk,
    "mrope_section": list(TINY.mrope_section),
}
# float32 against float32 at the highest precision: rounding of another order
# of summation (1e-6 seen); a wrong mask, norm, selection or expert reads
# 1e-2 and more
SOUND, FAULT = 5e-5, 1e-2
PAGE = 8
LENGTH = 40  # five times the top-k of 8: a query past position 7 selects


@pytest.fixture(scope="module")
def params():
    tree = T.init_params(TINY, jax.random.PRNGKey(0))
    # norms and the indexer's bias off their defaults, so that one left out
    # or misplaced shows
    key = jax.random.PRNGKey(1)
    for name in ("q_norm", "k_norm", "attn_norm", "ffn_norm", "idx_norm", "idx_bias"):
        key, sub = jax.random.split(key)
        shape = tree["layers"][name].shape
        base = 0.0 if name == "idx_bias" else 1.0
        tree["layers"][name] = base + 0.3 * jax.random.normal(sub, shape, jnp.float32)
    return tree


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(1, 500, (2, LENGTH)), jnp.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    """The reference's logits of each row's whole sequence."""
    return jnp.stack([ref.forward(params, row, DIMS) for row in tokens])


def rel_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# -- (i) the config ------------------------------------------------------------


@pytest.mark.parametrize("change, says", [
    ({"layer_pattern": ("full_attention",)}, "a layer pattern"),
    ({"block_length": 4, "denoise_steps": 4, "mask_token_id": 1}, "fills_blocks"),
    ({"kv_cache_dtype": "int8"}, "an int8 KV cache"),
    ({"output_norm": True}, "an output norm"),
    ({"index_head_dim": 15}, "odd index_head_dim"),
    ({"index_topk": 0}, "belong to a model with an indexer"),
    ({"mrope_section": (2, 3, 4)}, "sum to half a head"),
    ({"mrope_section": (4, 4)}, "three sections"),
])
def test_the_config_refuses_by_name(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(PRESET, **change)


def test_the_preset_holds_an_indexer_and_a_third_page_leaf():
    assert PRESET.has_indexer and PRESET.page_leaves == ("k", "v", "ik")
    assert MODEL_PRESETS["tiny-moe-test"].page_leaves == ("k", "v")
    tree = T.init_params(PRESET, jax.random.PRNGKey(0))["layers"]
    assert tree["wq_idx"].shape == (4, 64, 2 * 16) and tree["wk_idx"].shape == (4, 64, 16)
    assert tree["w_idx"].dtype == jnp.float32 and tree["w_idx"].shape == (4, 64, 2)
    assert float(tree["idx_norm"].min()) == 1.0 and float(jnp.abs(tree["idx_bias"]).max()) == 0.0


# -- (ii) the program against the reference -------------------------------------


def test_forward_is_the_references(params, tokens, want):
    assert rel_err(T.forward(params, tokens, TINY), want) < SOUND


def test_a_fault_in_the_selection_shows(params, tokens, want):
    """The controls of the tolerance: the most recent top-k keys in place of
    the ranked ones, and no selection at all."""
    dense = dataclasses.replace(TINY, index_topk=LENGTH)
    assert rel_err(T.forward(params, tokens, dense), want) > FAULT
    half = dataclasses.replace(TINY, index_topk=TINY.index_topk // 2)
    assert rel_err(T.forward(params, tokens, half), want) > FAULT


def test_the_selected_sets_are_the_references(params, tokens):
    """Layer 0's selection of row 0, from the program's own functions, is
    the reference's mask wherever the reference's topk-th and next score lie
    1e-5 apart or more, or not apart at all (two heads' ReLUs are both shut
    for a quarter of the pairs, whose score is 0 exactly: the tie rule
    decides there, and is held too)."""
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = T._embed(params, tokens[:1], TINY)
    u = T.rms_norm(x, lp["attn_norm"], TINY.rms_norm_eps)
    positions = jnp.arange(LENGTH)[None]
    q_idx, k_idx, w = T._index_proj(u, lp, positions, TINY)
    scores = T._index_scores(q_idx, w, k_idx)
    causal = jnp.tril(jnp.ones((LENGTH, LENGTH), jnp.bool_))[None]
    got = T._select_mask(scores, causal, TINY.index_topk)[0]
    with jax.default_matmul_precision("highest"):
        _, info = ref.attention_block(x[0], lp, DIMS)
    gap = np.asarray(info["select_gap"])
    clear = (gap > 1e-5) | (gap == 0)
    assert clear.sum() >= LENGTH - 2
    np.testing.assert_array_equal(np.asarray(got)[clear], np.asarray(info["selected"])[clear])
    assert (np.asarray(got).sum(-1) == np.minimum(np.arange(LENGTH) + 1, TINY.index_topk)).all()


def _pool_and_tables(n_rows: int, config: ModelConfig = TINY, per_row: int = 64 // PAGE):
    pool = T.make_page_pool(config, n_rows * per_row, PAGE)
    return pool, jnp.arange(n_rows * per_row, dtype=jnp.int32).reshape(n_rows, per_row)


# the decode step's three reads of one selection (`_paged_selected_read`):
# without the kernels the gather; with them (interpret mode) the walk under
# the mask, up to 16 x top-k columns of table; past that the gather again
# (a table length each: `attention_paths()` is keyed by it, process-wide)
DECODE_READS = {
    "gather": ("auto", 8, "xla top_k + gather", None),
    "walk": ("pallas", 9, T._WALK_LABEL, "ragged_paged_selected_attention"),
    "gather-past-the-rule": ("pallas", 40, "xla top_k + gather", None),
}


@pytest.mark.parametrize("read", sorted(DECODE_READS))
def test_prefill_then_decode_through_the_page_pool(params, tokens, want, read):
    """A prompt of 24 tokens (three times the top-k) through `prefill` and
    `paged_insert_cache`, then 16 decode steps through the table: each
    step's logits are the reference's at that position, by either read and
    on both sides of the rule that chooses between them."""
    impl, per_row, label, kernel = DECODE_READS[read]
    config = dataclasses.replace(TINY, attention_impl=impl)
    n = 24
    logits, local = T.prefill(
        params, tokens[:, :n], jnp.full((2,), n, jnp.int32), T.make_kv_cache(TINY, 2, n), TINY
    )
    assert rel_err(logits, want[:, n - 1]) < SOUND
    pool, tables = _pool_and_tables(2, per_row=per_row)
    past = per_row * PAGE > T._WALK_TABLE_PER_TOPK * TINY.index_topk
    assert past == (read == "gather-past-the-rule")
    pool = T.paged_insert_cache(pool, local, tables, PAGE, TINY)
    for position in range(n, LENGTH):
        logits, pool = T.paged_decode_step_inplace(
            params, tokens[:, position], jnp.full((2,), position, jnp.int32), pool, tables,
            config, PAGE,
        )
        assert rel_err(logits, want[:, position]) < SOUND, position
    paths, at = A.attention_paths(), f"[s=1,t={per_row * PAGE}]"
    assert paths["paged-decode-sparse" + at] == label
    assert paths.get("paged-decode-selected" + at) == kernel


def test_a_prompt_chunked_into_segments_that_cross_the_topk(params, tokens, want):
    """Five segments of 8: the first is all the selection's identity, the
    others rank columns of earlier segments; a last segment of 5 real tokens."""
    pool, tables = _pool_and_tables(2)
    for start in range(0, LENGTH, 8):
        logits, pool = T.paged_prefill_segment_inplace(
            params, tokens[:, start:start + 8], jnp.full((2,), start, jnp.int32),
            jnp.full((2,), 8, jnp.int32), pool, tables, TINY, PAGE,
        )
        assert rel_err(logits, want[:, start + 7]) < SOUND, start
    pool, tables = _pool_and_tables(2)
    for start, real in ((0, 16), (16, 16), (32, 5)):
        part = jnp.zeros((2, 16), jnp.int32).at[:, :LENGTH - start].set(tokens[:, start:start + 16])
        logits, pool, counts = T.paged_prefill_segment_inplace(
            params, part, jnp.full((2,), start, jnp.int32), jnp.full((2,), real, jnp.int32),
            pool, tables, TINY, PAGE, moe_counts=True,
        )
        assert rel_err(logits, want[:, start + real - 1]) < SOUND, start
    # the sequential block's segment counts its real tokens' assignments
    names = T.moe_count_names(TINY)
    assert int(counts[names.index("routed_real")]) == 2 * 5 * TINY.n_experts_per_tok * TINY.n_layers


def test_a_stale_index_key_moves_the_answer(params, tokens, want):
    """The decode step ranks by what the third leaf holds: with the indexer's
    keys of the prompt zeroed the logits part from the reference's."""
    n = 24
    _, local = T.prefill(
        params, tokens[:, :n], jnp.full((2,), n, jnp.int32), T.make_kv_cache(TINY, 2, n), TINY
    )
    pool, tables = _pool_and_tables(2)
    pool = T.paged_insert_cache(pool, local, tables, PAGE, TINY)
    assert float(jnp.abs(pool["ik"]).max()) > 0
    pool = {**pool, "ik": jnp.zeros_like(pool["ik"])}
    logits, _ = T.paged_decode_step_inplace(
        params, tokens[:, n], jnp.full((2,), n, jnp.int32), pool, tables, TINY, PAGE
    )
    assert rel_err(logits, want[:, n]) > FAULT


def test_no_verify_under_a_selection(params, tokens):
    pool, tables = _pool_and_tables(2)
    with pytest.raises(NotImplementedError, match="no verify under a learned selection"):
        T.paged_verify_step_inplace(
            params, tokens[:, :4], jnp.zeros((2,), jnp.int32), pool, tables, TINY, PAGE
        )


# -- (iii) m-rope ----------------------------------------------------------------


def test_mrope_with_unequal_triples_is_the_references(params, tokens):
    """An image-like stretch in the middle: the temporal stream stands still
    while height and width run."""
    t = np.arange(LENGTH)
    t[10:26] = 10
    h = np.arange(LENGTH)
    h[10:26] = 10 + np.arange(16) // 4
    w = np.arange(LENGTH)
    w[10:26] = 10 + np.arange(16) % 4
    triple = jnp.asarray(np.stack([t, h, w]), jnp.int32)
    got = T.forward(params, tokens[:1], TINY, positions=triple[:, None, :])
    want = ref.forward(params, tokens[0], DIMS, positions=triple)
    assert rel_err(got[0], want) < SOUND
    plain = T.forward(params, tokens[:1], TINY)
    assert rel_err(plain[0], want) > FAULT


def test_equal_triples_are_the_rotary_there_is():
    positions = jnp.asarray(np.random.default_rng(1).integers(0, 200, (2, 9)), jnp.int32)
    one = T._rope_freqs(positions, TINY)
    three = T._rope_freqs(jnp.broadcast_to(positions, (3, 2, 9)), TINY)
    plain = T._rope_freqs(positions, dataclasses.replace(TINY, mrope_section=()))
    for a, b, c in zip(one, three, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# -- (iv) the ranking --------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 5, 16, 64])
def test_ranking_by_counting_is_top_ks_set(k):
    """Scores with many exact ties (and signed zeros), rows that see fewer
    columns than they may keep, a row that sees none."""
    rng = np.random.default_rng(k)
    scores = rng.choice(np.asarray([-1.5, -0.0, 0.0, 0.25, 0.25, 3.0, 7.5], np.float32), (6, 48))
    scores[0] = rng.standard_normal(48).astype(np.float32)
    seen = np.arange(48)[None, :] < np.asarray([48, 48, 30, 3, 0, 17])[:, None]
    got = np.asarray(T._select_mask(jnp.asarray(scores) + 0.0, jnp.asarray(seen), k))
    for row in range(6):
        n = min(k, int(seen[row].sum()))
        masked = jnp.where(jnp.asarray(seen[row]), jnp.asarray(scores[row]) + 0.0, -jnp.inf)
        want = np.zeros(48, bool)
        want[np.asarray(jax.lax.top_k(masked, min(k, 48))[1])[:n]] = True
        np.testing.assert_array_equal(got[row], want, err_msg=f"row {row}")


# -- (v) the kernels against their jnp -----------------------------------------------


def test_index_scores_in_tiles_are_the_einsums():
    rng = np.random.default_rng(2)
    b, s, t, hi, di = 2, 256, 1024, 3, 16
    q = jnp.asarray(rng.standard_normal((b, s, hi, di)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, s, hi)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, t, di)), jnp.float32)
    offsets = jnp.asarray([256, 768], jnp.int32)
    got = A.index_scores(q, w, k, offsets, interpret=True)
    with jax.default_matmul_precision("highest"):
        want = T._index_scores(q, w, k)
    seen = jnp.arange(t)[None, None, :] <= (offsets[:, None] + jnp.arange(s))[:, :, None]
    assert float(jnp.max(jnp.abs(jnp.where(seen, got - want, 0.0)))) < 1e-4
    # a tile wholly past the diagonal is zeros, not computed
    assert float(jnp.abs(got[0, :, 512:]).max()) == 0.0


# a segment's selection in one call: (queries, columns, offsets a row, top-k,
# how the indexer's inputs are drawn). "few-values": small whole numbers, so
# that scores tie (a third of the heads' weights are 0 and a row's ReLUs shut
# together: its threshold is +0.0 with tens of ties) and the tie rule runs
SELECT_CASES = {
    "offset-0": (128, 512, [0], 16, "normal"),  # its first rows see fewer columns than k
    "mid-table": (128, 1024, [384, 256], 64, "normal"),  # two rows at two diagonals
    "the-last-segment-of-a-full-table": (128, 1024, [896], 64, "normal"),
    "fewer-visible-than-k": (128, 512, [0, 100], 200, "normal"),
    "a-threshold-of-zero-with-many-ties": (128, 1024, [896], 300, "few-values"),
    # positive heads' weights, so a row's 40th score is no shut ReLU's 0.0; an
    # offset that is no block's edge
    "no-row-ties": (128, 512, [300], 40, "positive-weights"),
    "a-table-that-forces-a-smaller-query-tile": (128, 34816, [17000], 64, "normal"),
    "padding-queries-past-the-table": (128, 512, [448], 32, "few-values"),
}


@pytest.mark.parametrize("case", sorted(SELECT_CASES))
def test_a_segments_selection_in_one_call_is_select_masks_set(case):
    """`segment_select` (Pallas in interpret mode) against `_select_mask` of
    `index_scores` under the causal mask, to the bit, every query of the tile
    a padding query included (a segment's queries past `seg_lengths` are
    ranked like the others: positions ``offset + i``, past the table's end
    in the last case)."""
    s, t, offsets, k, draw = SELECT_CASES[case]
    rng = np.random.default_rng(sorted(SELECT_CASES).index(case))
    b, hi, di = len(offsets), 3, 16
    if draw == "few-values":
        q, key = rng.integers(-2, 3, (b, s, hi, di)), rng.integers(-2, 3, (b, t, di))
        w = rng.integers(-1, 2, (b, s, hi))
    else:
        q, key = rng.standard_normal((b, s, hi, di)), rng.standard_normal((b, t, di))
        w = rng.standard_normal((b, s, hi))
        w = np.abs(w) + 0.1 if draw == "positive-weights" else w
    q, w, key = (jnp.asarray(a, jnp.float32) for a in (q, w, key))
    offsets = jnp.asarray(offsets, jnp.int32)
    block_q, block_k = A.select_blocks(s, t)
    assert (block_q < 128) == (case == "a-table-that-forces-a-smaller-query-tile")
    got = np.asarray(A.segment_select(q, w, key, offsets, k, interpret=True))
    scores = A.index_scores(q, w, key, offsets, interpret=True)
    causal = jnp.arange(t)[None, None, :] <= (offsets[:, None] + jnp.arange(s))[:, :, None]
    want = np.asarray(T._select_mask(scores, causal, k))
    np.testing.assert_array_equal(got, want.astype(np.int8))
    # the case is the case it says
    seen = np.minimum(np.asarray(offsets)[:, None] + np.arange(s) + 1, t)
    kept = np.minimum(seen, k)
    np.testing.assert_array_equal(got.sum(-1), kept)
    keyed = np.where(np.asarray(causal), np.asarray(scores), -np.inf)
    kth = np.take_along_axis(np.sort(keyed, axis=-1)[..., ::-1], kept[..., None] - 1, axis=-1)
    at_threshold = (keyed == kth).sum(-1)
    more_than_kept = (keyed >= kth).sum(-1) > kept
    if case == "a-threshold-of-zero-with-many-ties":
        assert ((kth[..., 0] == 0) & (at_threshold > 20) & more_than_kept).any()
    if case == "padding-queries-past-the-table":
        assert more_than_kept.any() and (np.asarray(offsets)[0] + s > t)
    if case == "no-row-ties":
        assert not more_than_kept.any()
    if case == "fewer-visible-than-k":
        assert (seen < k).any() and (seen > k).any()
    assert A.attention_paths()[f"segment-select[s={s},t={t}]"] == (
        f"block_q {block_q}, block_k {block_k}, to the diagonal"
    )


def test_the_segment_walk_under_a_packed_selection_is_masked_attention():
    rng = np.random.default_rng(3)
    b, s, t = 2, 128, 512
    config = dataclasses.replace(TINY, attention_impl="pallas")
    h, hkv, d = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, t, d)), jnp.float32)
    offsets = jnp.asarray([128, 384], jnp.int32)
    causal = jnp.arange(t)[None, None, :] <= (offsets[:, None] + jnp.arange(s))[:, :, None]
    chosen = causal & jnp.asarray(rng.random((b, s, t)) < 0.3)
    got = A.sparse_segment_attention(q, k, v, offsets, chosen.astype(jnp.int8), config, interpret=True)
    want = T.attention(q, k, v, chosen, config)
    # a query that chose nothing: zeros from the kernel, a mean from the softmax
    some = np.asarray(chosen.any(-1))
    assert float(jnp.max(jnp.abs(got - want)[some])) < 1e-4


# the decode walk under a selection: one batch a case, (rows' lengths, how
# the rows' scores are drawn); 6 pages of 8 a row, a top-k of 8
WALK_ROWS = {
    "a-row-of-length-0": ([0, 29, 0], "normal"),
    "under-the-topk": ([8, 5, 1], "normal"),  # the selection is the identity
    "past-the-topk": ([48, 17, 33], "normal"),
    "a-last-page-partly-filled": ([21, 43, 9], "normal"),
    "a-page-with-nothing-selected": ([48, 40, 24], "second-page-low"),
    "ties-at-the-threshold": ([48, 30, 12], "few-values"),
}


@pytest.mark.parametrize("heads", [(32, 4), (8, 2)], ids=lambda h: f"gqa{h[0]}-{h[1]}")
@pytest.mark.parametrize("case", sorted(WALK_ROWS))
def test_the_decode_walk_under_a_selection_is_masked_attention(case, heads):
    """`ragged_paged_selected_attention` (interpret mode) against masked jnp
    attention over the row's gathered pages, the mask `_select_mask`'s."""
    lengths, draw = WALK_ROWS[case]
    rng = np.random.default_rng(sorted(WALK_ROWS).index(case))
    h, hkv = heads
    b, tp, d, layers, topk, layer = len(lengths), 6, 16, 2, 8, 1
    t = tp * PAGE
    config = dataclasses.replace(TINY, n_heads=h, n_kv_heads=hkv, attention_impl="pallas")
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    pk, pv = (
        jnp.asarray(rng.standard_normal((layers, b * tp + 1, hkv, PAGE, d)), jnp.float32)
        for _ in range(2)
    )
    table = jnp.asarray(rng.permutation(b * tp).reshape(b, tp) + 1, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    visible = jnp.arange(t)[None, :] < lengths[:, None]
    if draw == "few-values":
        scores = rng.choice(np.asarray([-0.0, 0.0, 0.5, 0.5, 2.0], np.float32), (b, t))
    else:
        scores = rng.standard_normal((b, t)).astype(np.float32)
    if draw == "second-page-low":
        scores[:, PAGE:2 * PAGE] = -9.0
    scores = jnp.asarray(scores) + 0.0
    chosen = T._select_mask(scores, visible, topk)
    # the set is lax.top_k's, ties and all
    for row in range(b):
        n = min(topk, int(lengths[row]))
        top = jax.lax.top_k(jnp.where(visible[row], scores[row], -jnp.inf), topk)[1][:n]
        assert set(np.flatnonzero(chosen[row])) == set(np.asarray(top).tolist())
    if draw == "second-page-low":
        assert not bool(chosen[:, PAGE:2 * PAGE].any())
    if draw == "few-values":
        kth = jnp.sort(jnp.where(visible, scores, -jnp.inf), axis=-1)[:, -topk]
        assert bool(((scores == kth[:, None]) & visible & ~chosen).any())  # a tie cut
    args = (q, pk, pv, lengths, table, jnp.int32(layer))
    # what lies past a row's length is masked whatever the selection says of it
    given = chosen | ~visible if case == "a-last-page-partly-filled" else chosen
    got = A.ragged_paged_selected_attention(*args, given, config, PAGE, interpret=True)
    k_all, v_all = (T._paged_gather(leaf, layer, table, PAGE) for leaf in (pk, pv))
    want = T.attention(q[:, None], k_all, v_all, chosen[:, None, :], config)[:, 0]
    live = np.asarray(lengths) > 0
    assert got.shape == (b, h * d)
    assert float(jnp.max(jnp.abs(got - want)[live])) < 1e-5
    assert float(jnp.abs(got[~live]).max(initial=0.0)) == 0.0  # a row of no pages: zeros
    if case == "under-the-topk":
        plain = A.ragged_paged_decode_attention(*args, config, PAGE, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))


def test_forward_through_the_kernels_is_the_references(params):
    """128 tokens, a multiple of the kernels' lane width: the scores in
    tiles, the ranking, the walk under the selection (interpret mode); then
    four decode steps through the page pool, whose read is the decode
    kernel's walk under the selection as a mask."""
    config = dataclasses.replace(TINY, attention_impl="pallas", index_topk=32)
    dims = {**DIMS, "index_topk": 32}
    row = jnp.asarray(np.random.default_rng(4).integers(1, 500, (132,)), jnp.int32)
    want = ref.forward(params, row, dims)
    got = T.forward(params, row[None, :128], config)[0]
    assert rel_err(got, want[:128]) < SOUND
    assert A.attention_paths()["prefill-sparse[s=128,t=128]"] == "sparse_segment_attention"
    _, local = T.prefill(
        params, row[None, :128], jnp.full((1,), 128, jnp.int32),
        T.make_kv_cache(config, 1, 128), config,
    )
    pool, tables = _pool_and_tables(1, config, per_row=24)
    pool = T.paged_insert_cache(pool, local, tables, PAGE, config)
    for position in range(128, 132):
        logits, pool = T.paged_decode_step_inplace(
            params, row[None, position], jnp.full((1,), position, jnp.int32), pool, tables,
            config, PAGE,
        )
        assert rel_err(logits[0], want[position]) < SOUND, position
    paths = A.attention_paths()
    assert paths["paged-decode-selected[s=1,t=192]"] == "ragged_paged_selected_attention"
    assert paths["paged-decode-sparse[s=1,t=192]"] == T._WALK_LABEL


# -- (vi) the third leaf ---------------------------------------------------------------


def test_the_pool_holds_the_index_key_where_k_and_v_lie(params, tokens):
    pool, tables = _pool_and_tables(2)
    # kept at whole 128-lane rows, the tail zeros (`config.index_key_width`)
    assert TINY.index_key_width == 128 and TINY.index_head_dim == 16
    assert pool["ik"].shape == (TINY.n_layers, 16, PAGE, 128)
    assert T.make_kv_cache(TINY, 2, 24)["ik"].shape == (TINY.n_layers, 2, 24, 128)
    # one decode step at position 11 of row 1: page 1 of its table, offset 3
    _, after = T.paged_decode_step_inplace(
        params, tokens[:, 0], jnp.asarray([70, 11], jnp.int32), pool, tables, TINY, PAGE
    )
    written = np.asarray(jnp.abs(after["ik"]).sum(axis=(0, 3)) > 0)  # [P, ps]
    k_written = np.asarray(jnp.abs(after["k"]).sum(axis=(0, 2, 4)) > 0)
    want = np.zeros_like(written)
    want[int(tables[1, 1]), 3] = True  # row 0's position lies past its table: dropped
    np.testing.assert_array_equal(written, want)
    np.testing.assert_array_equal(k_written, want)
    assert float(jnp.abs(after["ik"][..., TINY.index_head_dim:]).max()) == 0.0

"""A model that keeps a LATENT in place of keys and values, under a learned
selection (`tiny-latent-moe-test`, float32 on the CPU), against the plain
reference `benchmark/reference/glm_moe_dsa.py` (expanded form only):

(i)    `forward`, `prefill` then decode through the page pool, and a prompt
       chunked into segments that cross the top-k, each against the
       reference's full forward pass (logits); the selected sets equal away
       from ties;
(ii)   the absorbed decode read equals the expanded attention on the same
       int8 `wkv_b`; the interpret-mode kernels against their jnp; a segment
       expands the columns its queries can see, to the bit, and reads no other;
(iii)  the router: the bias chooses and does not weigh, the scaling;
(iv)   the share tied to the model: the parts that all shares of the experts
       give, the shared expert counted once, add up to the uncut reference's;
(v)    the leading dense layer's place in the pool; the pool's leaves, the
       memory plan's page term; what the config refuses, by name.

Tolerances: float32 on both sides over the same dequantised int8 weights, so
1e-4 on logits of magnitude 4 is summation order and nothing else.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmark"))

from reference import glm_moe_dsa as R  # noqa: E402

from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.models.configs import MODEL_PRESETS, ModelConfig  # noqa: E402
from langstream_tpu.models.quant import quantize_params  # noqa: E402
from langstream_tpu.serving.memory import plan_serving_memory  # noqa: E402

CONFIG = dataclasses.replace(MODEL_PRESETS["tiny-latent-moe-test"], dtype="float32")
PAGE, PAGES = 8, 8
TOL = 1e-4


def dims_of(config: ModelConfig) -> dict:
    return dict(
        n_heads=config.n_heads, eps=config.rms_norm_eps, kv_lora_rank=config.kv_lora_rank,
        qk_nope_head_dim=config.qk_nope_head_dim, qk_rope_head_dim=config.qk_rope_head_dim,
        v_head_dim=config.v_head_dim, rope_theta=config.rope_theta,
        index_n_heads=config.index_n_heads, index_head_dim=config.index_head_dim,
        index_topk=config.index_topk, top_k=config.n_experts_per_tok,
        n_experts=config.n_experts, experts_held=config.held_experts,
        routed_scaling=config.routed_scaling,
    )


DIMS = dims_of(CONFIG)


@pytest.fixture(scope="module")
def params():
    return quantize_params(T.init_params(CONFIG, jax.random.PRNGKey(0)), CONFIG)


def tokens_of(n: int, seed: int = 1):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, CONFIG.vocab_size)


def err(a, b) -> float:
    return float(jnp.abs(a - b).max())


# -- (i) against the reference's full forward pass ----------------------------------


@pytest.mark.parametrize("n", [7, 40, 57], ids=lambda n: f"len{n}")
def test_forward_is_the_references(params, n):
    """7: under the top-k of 8, the selection is the identity; 40 and 57 select."""
    tokens = tokens_of(n, seed=n)
    assert err(T.forward(params, tokens[None], CONFIG)[0], R.forward(params, tokens, DIMS)) < TOL


def test_prefill_then_decode_through_the_page_pool(params):
    tokens = tokens_of(40)
    ref = R.forward(params, tokens, DIMS)
    logits, cache = T.prefill(
        params, tokens[None, :32], jnp.array([32]), T.make_kv_cache(CONFIG, 1, 32), CONFIG
    )
    assert set(cache) == {"lat", "ik"}
    assert err(logits[0], ref[31]) < TOL
    table = jnp.arange(PAGES)[None]
    pool = T.paged_insert_cache(T.make_page_pool(CONFIG, PAGES, PAGE), cache, table, PAGE, CONFIG)
    for j in range(32, 40):
        step, pool = T.paged_decode_step_inplace(
            params, tokens[j : j + 1], jnp.array([j]), pool, table, CONFIG, PAGE
        )
        assert err(step[0], ref[j]) < TOL, j


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_segments_that_cross_the_top_k(params, impl):
    """Segments of 16 over 40 tokens: the second and third rank the columns
    earlier segments wrote, re-expanded from the pool's latents; under
    `pallas` the selection's kernels in interpret mode (`segment_select`,
    `sparse_segment_attention` at the expanded heads), then decode steps
    through `ragged_paged_latent_attention`."""
    config = dataclasses.replace(CONFIG, attention_impl=impl)
    tokens = tokens_of(44, seed=3)
    ref = R.forward(params, tokens, DIMS)
    table = jnp.arange(PAGES)[None]
    pool = T.make_page_pool(config, PAGES, PAGE)
    for s0 in range(0, 40, 16):
        part = tokens[s0 : min(s0 + 16, 40)]
        n = part.shape[0]
        logits, pool = T.paged_prefill_segment_inplace(
            params, jnp.pad(part, (0, 16 - n))[None], jnp.array([s0]), jnp.array([n]), pool,
            table, config, PAGE,
        )
        assert err(logits[0], ref[s0 + n - 1]) < TOL, s0
    for j in range(40, 44):
        step, pool = T.paged_decode_step_inplace(
            params, tokens[j : j + 1], jnp.array([j]), pool, table, config, PAGE
        )
        assert err(step[0], ref[j]) < TOL, j


def test_the_selected_sets_are_the_references_away_from_ties(params):
    """Layer 0 (the dense layer: it has its indexer like every layer): the
    program's scores ranked by `_select_mask` against the reference's
    `select`, at every query whose topk-th and next score are apart."""
    tokens = tokens_of(40, seed=5)
    x = T._embed(params, tokens[None], CONFIG)
    lp = jax.tree.map(lambda a: a[0], params["dense_layers"])
    positions = jnp.arange(40)[None]
    sin, cos = T._rope_freqs(positions, CONFIG)
    u, c_q, _, _ = T._latent_proj(x, lp, sin, cos, CONFIG)
    q_idx, k_idx, w = T._index_proj(u, lp, positions, CONFIG, c_q=c_q, rotary=(sin, cos))
    scores = T._index_scores(q_idx, w, k_idx)[0]
    causal = jnp.tril(jnp.ones((40, 40), jnp.bool_))
    mine = T._select_mask(scores, causal, CONFIG.index_topk)
    _, info = R.layer(x[0], lp, DIMS)
    apart = np.asarray(info["select_gap"]) > 1e-5
    assert apart.sum() > 30
    np.testing.assert_array_equal(np.asarray(mine)[apart], np.asarray(info["selected"])[apart])
    assert (np.asarray(mine).sum(-1) == np.minimum(np.arange(40) + 1, CONFIG.index_topk)).all()


# -- (ii) two forms of one attention -------------------------------------------------


def test_the_absorbed_read_is_the_expanded_attention_on_the_same_int8_matrix(params):
    """Random cache rows and queries, a random selection: absorbed queries
    against the rows as they lie and `W_uv` after, against the expanded keys
    and values of every row under the same mask."""
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    assert lp["wkv_b"]["q"].dtype == jnp.int8
    kl, h, hd = CONFIG.kv_lora_rank, CONFIG.n_heads, CONFIG.resolved_head_dim
    width, t = CONFIG.latent_key_width, 24
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    rows = jax.random.normal(keys[0], (2, t, width)).at[..., CONFIG.latent_width :].set(0.0)
    q = jax.random.normal(keys[1], (2, h, hd))
    chosen = jax.random.bernoulli(keys[2], 0.5, (2, t)).at[:, 0].set(True)
    absorbed = T._latent_absorb(q, lp, CONFIG, width)
    logits = jnp.einsum("bhw,btw->bht", absorbed, rows) * hd**-0.5
    probs = jax.nn.softmax(jnp.where(chosen[:, None], logits, -jnp.inf), axis=-1)
    mixed = jnp.einsum("bht,btc->bhc", probs, rows[..., :kl])
    out = T._latent_value_out(mixed, lp, CONFIG)
    k, v = T._latent_expand(rows, lp, CONFIG)
    want = T.attention(q[:, None], k, v, chosen[:, None, :], CONFIG)[:, 0]
    assert err(out, want) < 1e-5


def test_the_latent_kernel_in_interpret_mode_is_its_jnp(params):
    """`ragged_paged_latent_attention` (a page fetched once for key and
    value) against the gathered masked jnp read, rows of 0, 13 and 30 tokens,
    one past the top-k."""
    from langstream_tpu.ops import attention as ops

    kl, h = CONFIG.kv_lora_rank, CONFIG.n_heads
    width = CONFIG.latent_key_width
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    pool = jax.random.normal(keys[0], (2, PAGES, 1, PAGE, width))
    q = jax.random.normal(keys[1], (3, h, width))
    table = jnp.asarray([[0, 1, 2, 3], [4, 5, 9, 9], [6, 7, 3, 1]], jnp.int32)
    lengths = jnp.asarray([0, 13, 30], jnp.int32)
    t = table.shape[1] * PAGE
    chosen = jax.random.bernoulli(keys[2], 0.6, (3, t)) & (jnp.arange(t)[None] < lengths[:, None])
    got = ops.ragged_paged_latent_attention(
        q, pool, lengths, table, jnp.int32(1), chosen, CONFIG, PAGE, interpret=True
    ).reshape(3, h, kl)
    rows = T._paged_gather(pool, jnp.int32(1), table, PAGE)[:, 0]
    logits = jnp.einsum("bhw,btw->bht", q, rows) * CONFIG.resolved_head_dim**-0.5
    probs = jnp.where(chosen[:, None], jnp.exp(logits - logits.max(-1, keepdims=True)), 0.0)
    want = jnp.einsum("bht,btc->bhc", probs, rows[..., :kl]) / jnp.maximum(
        probs.sum(-1, keepdims=True), 1e-30
    )
    assert float(jnp.abs(got[0]).max()) == 0.0  # a row of nothing: zeros, not NaN
    assert err(got[1:], want[1:]) < 1e-5


# -- (ii b) a segment expands the columns its queries can see -----------------------------

KERNELS = dataclasses.replace(CONFIG, attention_impl="pallas")


@pytest.mark.parametrize(
    "t, s, offsets, seen",
    [
        # the cell's table and segment (key blocks of 512, expanded two at a time)
        (17408, 2048, [0], [2048]),
        (17408, 2048, [2048], [4096]),
        (17408, 2048, [6144], [8192]),
        (17408, 2048, [17408 - 2048], [17408]),
        # a warm suffix: it starts inside a key block, and ends inside one
        (17408, 2048, [2348], [5120]),
        # the check's table (51 x 128): a last segment whose padded width passes the table
        (6528, 2048, [4096], [6144]),
        (6528, 2048, [6144], [6528]),
        # two rows at two offsets: each expands its own
        (17408, 2048, [2048, 10240], [4096, 12288]),
    ],
    ids=lambda v: "-".join(map(str, v)) if isinstance(v, list) else str(v),
)
def test_a_segment_expands_the_columns_it_sees_as_the_whole_expansion_does(
    params, t, s, offsets, seen
):
    """`latent_expand_blocks` (interpret mode) against `_latent_expand` of the
    whole table, on the columns `latent_columns_expanded` names, every row to
    its own bound. To the BIT where no sum rounds (the layer's int8 matrix
    under scales that are powers of two, latents that are small whole
    numbers: the two differ in nothing but the order of a float32 sum, which
    the CPU's two products do not share); to that order's 1e-5 with the
    layer's own scales and normal latents."""
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    assert lp["wkv_b"]["q"].dtype == jnp.int8
    width = CONFIG.latent_key_width
    keys = jax.random.split(jax.random.PRNGKey(t + offsets[0]), 3)
    exact = {**lp, "wkv_b": {
        "q": lp["wkv_b"]["q"],
        "s": 2.0 ** jax.random.randint(keys[0], lp["wkv_b"]["s"].shape, -6, -1).astype(jnp.float32),
    }}
    whole = jax.random.randint(keys[1], (len(offsets), t, width), -8, 9).astype(jnp.float32)
    normal = jax.random.normal(keys[2], (len(offsets), t, width))
    at = jnp.asarray(offsets, jnp.int32)
    assert T.latent_columns_expanded(at, s, t, KERNELS).tolist() == seen
    assert [T.latent_columns_expanded(o, s, t, KERNELS) for o in offsets] == seen  # the host's
    # what the segment's walk reads last lies inside: `_segment_blocks`' last key block
    from langstream_tpu.ops import attention as ops

    _, block_k, _ = ops.segment_key_blocks(s, t, CONFIG.resolved_head_dim, 1, 0)
    assert ops.latent_expand_block(s, t, KERNELS) % block_k == 0
    for offset, bound in zip(offsets, seen):
        assert (min((offset + s - 1) // block_k, t // block_k - 1) + 1) * block_k <= bound
    for weights, rows, tol in ((exact, whole, 0.0), (lp, normal, 1e-5)):
        rows = rows.at[..., CONFIG.latent_width:].set(0.0)
        want = T._latent_expand(rows, weights, CONFIG)
        got = T._latent_expand_seen(rows, weights, at, s, KERNELS)
        for mine, all_of_it in zip(got, want):
            assert mine.shape == all_of_it.shape and float(jnp.abs(all_of_it).max()) > 1.0
            for row, bound in enumerate(seen):
                np.testing.assert_allclose(
                    np.asarray(mine[row, :, :bound]), np.asarray(all_of_it[row, :, :bound]),
                    rtol=tol, atol=tol,
                )
    # where the read is masked jnp every column is multiplied: the whole table
    assert T.latent_columns_expanded(offsets[0], s, t, CONFIG) == t
    assert T.latent_columns_expanded(at, s, t, CONFIG).tolist() == [t] * len(offsets)


@pytest.mark.parametrize(
    "s, offset, seen",
    [
        (8, 0, 128),  # no query past the top-k: `flash_segment_attention`
        (16, 0, 128),
        (16, 200, 256),  # inside a key block
        (16, 368, 384),
        (16, 640 - 16, 640),
    ],
    ids=lambda v: str(v),
)
def test_what_a_segment_does_not_expand_it_does_not_read(params, s, offset, seen):
    """A segment through a pool of random latents and indexer keys, a table
    of 640 columns (key blocks of 128): with every latent PAST the segment's
    bound poisoned (NaN: an expanded NaN read under a probability of 0 is
    NaN), the logits and the pool's new rows are the unpoisoned run's."""
    page, pages = PAGE, 640 // PAGE
    table = jnp.arange(pages)[None]
    assert T.latent_columns_expanded(offset, s, 640, KERNELS) == seen
    keys = jax.random.split(jax.random.PRNGKey(offset + s), 2)
    pool = T.make_page_pool(KERNELS, pages, page)
    pool = {
        "lat": jax.random.normal(keys[0], pool["lat"].shape).at[..., CONFIG.latent_width:].set(0.0),
        "ik": jax.random.normal(keys[1], pool["ik"].shape),
    }
    poisoned = {**pool, "lat": pool["lat"].at[:, seen // page:].set(jnp.nan)}
    tokens = tokens_of(s, seed=offset)[None]

    def run(pool):
        return T.paged_prefill_segment_inplace(
            params, tokens, jnp.array([offset]), jnp.array([s]), pool, table, KERNELS, page
        )

    logits, after = run(pool)
    got, got_after = run(poisoned)
    assert bool(jnp.isfinite(logits).all())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(logits))
    mine = slice(offset // page, (offset + s) // page)
    for leaf in ("lat", "ik"):
        np.testing.assert_array_equal(
            np.asarray(got_after[leaf][:, mine]), np.asarray(after[leaf][:, mine])
        )
    from langstream_tpu.ops import attention as ops

    traced = ops.attention_paths()[f"paged-segment-latent-expand[s={s},t=640]"]
    assert traced == "latent_expand_blocks"


# -- (iii) the router -------------------------------------------------------------------


def test_the_bias_chooses_and_does_not_weigh_and_the_scaling():
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (50, 64))
    router = jax.random.normal(keys[1], (64, 8)) * 0.125
    bias = jax.random.normal(keys[2], (8,))  # large: it moves most choices
    weights, chosen = T._route_all(x, router, CONFIG, bias)
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    want = jax.lax.top_k(scores + bias, 2)[1]
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))
    unbiased = jax.lax.top_k(scores, 2)[1]
    assert (np.sort(np.asarray(chosen)) != np.sort(np.asarray(unbiased))).any()
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(weights, 2.5 * top / top.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    # without the two fields the router is the one it was
    plain = dataclasses.replace(CONFIG, router_bias=False, routed_scaling=1.0)
    w0, c0 = T._route_all(x, router, plain)
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(unbiased))
    np.testing.assert_allclose(w0.sum(-1), 1.0, rtol=1e-6)


# -- (iv) the share tied to the model ------------------------------------------------------


def test_the_shares_parts_add_up_to_the_uncut_references_layer():
    """All 8 experts' weights made once; the program's expert layer run as
    the share (0, 4) and as the share (4, 4), each with the shared expert
    whole: their sum less one shared expert is the reference's uncut layer
    (experts_held (0, 8)) on the same input."""
    whole = dataclasses.replace(CONFIG, experts_held=(0, 8), name="tiny-latent-uncut")
    params = quantize_params(T.init_params(whole, jax.random.PRNGKey(2)), whole)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, CONFIG.d_model))
    u = R.rms_norm(x[0], lp["ffn_norm"], CONFIG.rms_norm_eps)
    uncut, info = R.moe(u, lp, {**DIMS, "experts_held": (0, 8)})
    shared = R.swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    parts = []
    for first in (0, 4):
        share = dataclasses.replace(CONFIG, experts_held=(first, 4), name=f"share{first}")
        held = {
            **lp, **{k: jax.tree.map(lambda a: a[first : first + 4], lp[k]) for k in T._HELD_EXPERTS}
        }
        y, counts = T._ffn_half(x, held, share)
        parts.append(y[0] - x[0])
        # the reference given the same share agrees with the program's part
        ref_part, _ = R.moe(u, held, {**DIMS, "experts_held": (first, 4)})
        assert err(parts[-1], ref_part) < TOL
        assert int(counts[T.MOE_HELD_COUNTS.index("local")]) == int(
            ((info["chosen"] >= first) & (info["chosen"] < first + 4)).sum()
        )
    assert err(parts[0] + parts[1] - shared, uncut) < TOL
    assert float(jnp.abs(shared).max()) > 0.01  # the shared expert is no rounding


# -- (v) the pool, the plan, the refusals ----------------------------------------------------


def test_the_leading_dense_layer_is_layer_0_of_the_pool(params):
    """A segment through a fresh pool: layer 0's rows are the DENSE layer's
    latents of the embedded tokens (its stack runs before the scan), layers
    1 to 3 the expert layers', and every leaf's unmapped pages stay zero."""
    tokens = tokens_of(16, seed=9)
    table = jnp.asarray([[2, 5, PAGES, PAGES]], jnp.int32)
    pool = T.make_page_pool(CONFIG, PAGES, PAGE)
    assert {k: v.shape for k, v in pool.items()} == {
        "lat": (4, PAGES, 1, PAGE, 128), "ik": (4, PAGES, PAGE, 128),
    }
    _, pool = T.paged_prefill_segment_inplace(
        params, tokens[None], jnp.array([0]), jnp.array([16]), pool, table, CONFIG, PAGE
    )
    x = T._embed(params, tokens[None], CONFIG)
    lp = jax.tree.map(lambda a: a[0], params["dense_layers"])
    sin, cos = T._rope_freqs(jnp.arange(16)[None], CONFIG)
    _, _, _, lat = T._latent_proj(x, lp, sin, cos, CONFIG)
    written = pool["lat"][0, jnp.asarray([2, 5]), 0].reshape(16, -1)
    assert err(written[:, : CONFIG.latent_width], lat[0]) < 1e-6
    assert float(jnp.abs(written[:, CONFIG.latent_width :]).max()) == 0.0
    for leaf in ("lat", "ik"):
        for layer in range(CONFIG.n_layers):
            assert float(jnp.abs(pool[leaf][layer, jnp.asarray([2, 5])]).max()) > 0.0
        assert float(jnp.abs(pool[leaf][:, jnp.asarray([0, 1, 3, 4, 6, 7])]).max()) == 0.0
    assert params["dense_layers"]["w_gate"]["q"].shape == (1, 64, 128)
    assert params["layers"]["w_gate"]["q"].shape == (3, 4, 64, 32)


def test_a_token_of_the_pool_and_the_memory_plans_page_term():
    config = MODEL_PRESETS["tiny-latent-moe-test"]  # bf16
    assert config.page_leaves == ("lat", "ik")
    assert config.latent_width == 24 and config.latent_key_width == 128
    token = config.n_layers * (config.latent_key_width + config.index_key_width) * 2
    assert config.kv_bytes_per_token() == token
    plan = plan_serving_memory(config, 4, 128, page_size=8, kv_pages=64)
    assert plan.page_pool_bytes == 64 * 8 * token
    glm = dict(kv_lora_rank=512, qk_rope_head_dim=64, qk_nope_head_dim=192, v_head_dim=256,
               q_lora_rank=2048, index_head_dim=128, index_rope_dim=64, n_layers=7)
    big = dataclasses.replace(config, n_heads=64, n_kv_heads=64, d_model=6144, **glm)
    assert big.latent_width == 576 and big.latent_key_width == 640
    assert big.kv_bytes_per_token() == 7 * (640 + 128) * 2
    # the models there were: K and V of every KV head, the indexer's key where there is one
    keye = MODEL_PRESETS["tiny-sparse-moe-test"]
    assert keye.page_leaves == ("k", "v", "ik")
    assert keye.kv_bytes_per_token() == 4 * (2 * 2 * 16 + 128) * 2
    assert MODEL_PRESETS["tiny-test"].page_leaves == ("k", "v")


@pytest.mark.parametrize(
    "change, says",
    [
        # (its indexer refuses a pattern first; without one the latent does)
        ({"layer_pattern": ("full_attention",)}, "a layer pattern, a window or a recurrent"),
        ({"block_length": 4, "denoise_steps": 4, "mask_token_id": 5}, "fills_blocks"),
        ({"mrope_section": (1, 1, 2)}, "a latent.*m-rope"),
        ({"kv_cache_dtype": "int8"}, "an int8 KV cache"),
        ({"output_norm": True}, "an output norm"),
        ({"qk_norm_heads": True}, "a latent.*qk_norm"),
        ({"v_head_dim": 12}, "v_head_dim 12 apart from"),
        ({"head_dim": 16}, "head_dim 16.*leave it unset"),
        ({"n_kv_heads": 2}, "n_kv_heads 2 apart from n_heads"),
        # (a latent with NO indexer is a model since PR 50,
        # tests/test_latent_dense_attention.py; what is left of the indexer's
        # fields without one is still refused)
        ({"index_topk": 0}, "belong to a model with an indexer"),
        ({"index_rope_dim": 4}, "index_rope_dim 4 apart from the rotary's width 8"),
        ({"index_query_input": "latent"}, "index_query_input 'latent'"),
        ({"n_leading_dense": 4}, "n_leading_dense 4 belongs"),
        ({"experts_held": (), "moe_d_ff": 0}, "n_leading_dense 1 belongs"),
        ({"kv_lora_rank": 0, "rope_interleaved": False, "moe_scoring": "softmax",
          "n_shared_experts": 0, "index_rope_dim": 0, "index_query_input": "hidden"},
         "belong to a model with a latent"),
    ],
    ids=lambda v: next(iter(v)) if isinstance(v, dict) else None,
)
def test_the_config_refuses_what_contradicts_by_name(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CONFIG, **change)


def test_fields_of_the_latent_model_alone_are_refused_elsewhere():
    tiny = MODEL_PRESETS["tiny-test"]
    for change, says in [
        ({"q_lora_rank": 8}, "belong to a model with a latent"),
        ({"n_leading_dense": 1}, "n_leading_dense 1 belongs"),
        ({"router_bias": True}, "router_bias and routed_scaling"),
        ({"routed_scaling": 2.5}, "router_bias and routed_scaling"),
        ({"index_query_input": "query_latent"}, "no indexer"),
    ]:
        with pytest.raises(ValueError, match=says):
            dataclasses.replace(tiny, **change)
    keye = MODEL_PRESETS["tiny-sparse-moe-test"]
    with pytest.raises(ValueError, match="without a query latent"):
        dataclasses.replace(keye, index_query_input="query_latent")

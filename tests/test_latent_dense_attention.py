"""A model that keeps a LATENT in place of keys and values and has NO
selection (`tiny-latent-dense-moe-test`, float32 on the CPU: every query reads
every cached latent; a head's q.k is 24 wide and its value 16; YaRN), against
the plain reference `benchmark/reference/kimi_k2.py` (expanded form only):

(i)    `forward`, `prefill` then decode through the page pool (absorbed), and
       a prompt over the largest bucket chunked into segments that re-expand
       cached columns, each against the reference's full forward pass
       (logits), kernels in interpret mode and the jnp fall-back;
(ii)   YaRN: the ramp's ends, the frequencies and the softmax scale at the
       PUBLISHED numbers (8, 20, 0.144680); at test size, the blend or the
       factor left out of the reference fails the comparison;
(iii)  the kernels at two widths: the dense latent walk with NO mask operand,
       the causal prefill and segment kernels with `Dv != Dk`;
(iv)   the router: the bias chooses and does not weigh, the scaling once; the
       shares of a small layer add up to the uncut reference's;
(v)    nothing of an indexer is traced; the pool's one leaf, the memory plan's
       page term; what the config still refuses, by name; a model WITH an
       indexer still builds and selects;
(vi)   through the engine: its tokens, spans and counters, what it refuses.

Tolerances: float32 on both sides over the same dequantised int8 weights, so
1e-4 on logits of magnitude 4 is summation order and nothing else.
"""

import dataclasses
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmark"))

from reference import kimi_k2 as R  # noqa: E402

from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions, ModelConfig  # noqa: E402
from langstream_tpu.models.quant import quantize_params  # noqa: E402
from langstream_tpu.ops import attention as ops  # noqa: E402
from langstream_tpu.serving import engine as E  # noqa: E402
from langstream_tpu.serving.memory import plan_serving_memory  # noqa: E402

NAME = "tiny-latent-dense-moe-test"
CONFIG = dataclasses.replace(MODEL_PRESETS[NAME], dtype="float32")
PAGE, PAGES = 8, 8
TOL = 1e-4


def dims_of(config: ModelConfig) -> dict:
    return dict(
        n_heads=config.n_heads, eps=config.rms_norm_eps, kv_lora_rank=config.kv_lora_rank,
        qk_nope_head_dim=config.qk_nope_head_dim, qk_rope_head_dim=config.qk_rope_head_dim,
        v_head_dim=config.v_head_dim, rope_theta=config.rope_theta,
        rope_scaling={
            "type": "yarn", "factor": config.rope_scaling_factor,
            "beta_fast": config.rope_scaling_beta_fast, "beta_slow": config.rope_scaling_beta_slow,
            "mscale": config.rope_scaling_mscale,
            "mscale_all_dim": config.rope_scaling_mscale_all_dim,
            "original_max_position_embeddings": config.rope_scaling_original_max_seq_len,
        },
        top_k=config.n_experts_per_tok, n_experts=config.n_experts,
        experts_held=config.held_experts, routed_scaling=config.routed_scaling,
    )


DIMS = dims_of(CONFIG)
# Kimi-K2.5's published keys (the catalog row), as the program and the reference read them
PUBLISHED = dataclasses.replace(
    CONFIG, name="kimi-published-rotary", qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, kv_lora_rank=512, q_lora_rank=1536, rope_theta=50000.0,
    rope_scaling_factor=64.0, rope_scaling_original_max_seq_len=4096,
    rope_scaling_beta_fast=32.0, rope_scaling_beta_slow=1.0,
)


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
    tokens = tokens_of(n, seed=n)
    assert err(T.forward(params, tokens[None], CONFIG)[0], R.forward(params, tokens, DIMS)) < TOL


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_prefill_then_decode_through_the_page_pool(params, impl):
    """The admit group's path (`prefill` into a local cache of ONE leaf, the
    scatter into the pool), then absorbed decode steps over every cached row."""
    config = dataclasses.replace(CONFIG, attention_impl=impl)
    tokens = tokens_of(40)
    ref = R.forward(params, tokens, DIMS)
    logits, cache = T.prefill(
        params, tokens[None, :32], jnp.array([32]), T.make_kv_cache(config, 1, 32), config
    )
    assert set(cache) == {"lat"}
    assert err(logits[0], ref[31]) < TOL
    table = jnp.arange(PAGES)[None]
    pool = T.paged_insert_cache(T.make_page_pool(config, PAGES, PAGE), cache, table, PAGE, config)
    assert set(pool) == {"lat"}
    for j in range(32, 40):
        step, pool = T.paged_decode_step_inplace(
            params, tokens[j : j + 1], jnp.array([j]), pool, table, config, PAGE
        )
        assert err(step[0], ref[j]) < TOL, j
    want = "ragged_paged_latent_attention" if impl == "pallas" else "jnp"
    assert ops.attention_paths()[f"paged-decode-latent[s=1,t={PAGES * PAGE}]"] == want


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_a_prompt_over_the_bucket_in_segments_that_re_expand_the_cache(params, impl):
    """Segments of 16 over 40 tokens: the second and third re-expand the
    columns earlier segments wrote, from the pool's latents, to keys 24 wide
    and values 16; under `pallas` `latent_expand_blocks` and the causal
    segment kernel in interpret mode, then decode steps through
    `ragged_paged_latent_attention` with no mask."""
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
    paths = ops.attention_paths()
    if impl == "pallas":
        assert paths[f"paged-segment-latent[s=16,t={PAGES * PAGE}]"] == "flash_segment_attention"
        assert paths[f"paged-segment-latent-expand[s=16,t={PAGES * PAGE}]"] == "latent_expand_blocks"
    else:
        assert paths[f"paged-segment-latent[s=16,t={PAGES * PAGE}]"] == "jnp"


# -- (ii) YaRN ---------------------------------------------------------------------------


def test_yarn_at_the_published_numbers():
    """Kimi-K2.5's keys: the ramp from 8 to 20 of 32 frequencies, the 8
    fastest as they were, those from the 20th on divided by 64, the softmax
    scale 192^-0.5 x (0.1 ln 64 + 1)^2 = 0.144680; the program's tables and
    the reference's frequencies agree."""
    assert PUBLISHED.yarn and PUBLISHED.yarn_blend == (8, 20)
    assert PUBLISHED.resolved_head_dim == 192 and PUBLISHED.rope_dim == 64
    m = 0.1 * np.log(64.0) + 1.0
    assert abs(m - 1.41589) < 1e-5 and abs(m * m - 2.00474) < 1e-5
    assert abs(PUBLISHED.attn_scale - 0.144680) < 1e-6
    assert abs(PUBLISHED.attn_scale - 192**-0.5 * m * m) < 1e-12
    dims = dims_of(PUBLISHED)
    assert R.yarn_range(dims) == (8, 20)
    assert abs(R.softmax_scale(dims) - 0.144680) < 1e-6
    plain = 50000.0 ** (-2.0 * np.arange(32) / 64.0)
    ramp = np.clip((np.arange(32) - 8) / 12.0, 0.0, 1.0)
    want = plain * (1.0 - ramp) + plain / 64.0 * ramp
    np.testing.assert_allclose(np.asarray(R.yarn_frequencies(dims)), want, rtol=1e-6)
    np.testing.assert_allclose(want[:9], plain[:9], rtol=0)  # left as they were
    np.testing.assert_allclose(want[20:], plain[20:] / 64.0, rtol=1e-12)  # interpolated whole
    assert plain[14] / 64.0 < want[14] < plain[14]  # blended between
    # the program's tables: angle = position x freq_i, no factor on them (mscale = mscale_all_dim)
    positions = jnp.asarray([[1, 1000, 16000]])
    sin, cos = T._rope_freqs(positions, PUBLISHED)
    np.testing.assert_allclose(
        np.asarray(sin[0]), np.sin(np.asarray([[1.0], [1000.0], [16000.0]]) * want),
        atol=2e-3,  # float32 angles of up to 16,000 radians
    )
    np.testing.assert_allclose(np.asarray(sin[0, 0]), np.sin(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sin**2 + cos**2), 1.0, atol=1e-6)
    # a tables' factor apart from 1 rides sin and cos
    louder = dataclasses.replace(PUBLISHED, rope_scaling_mscale=2.0)
    factor = (0.2 * np.log(64.0) + 1.0) / m
    np.testing.assert_allclose(
        np.asarray(T._rope_freqs(positions, louder)[1]), np.asarray(cos) * factor, rtol=1e-6
    )


def test_yarn_at_test_size_moves_some_frequencies_and_leaves_others():
    assert CONFIG.yarn_blend == (1, 3)
    freqs = np.asarray(R.yarn_frequencies(DIMS))
    plain = 100.0 ** (-2.0 * np.arange(4) / 8.0)
    np.testing.assert_allclose(freqs[:2], plain[:2], rtol=1e-6)
    np.testing.assert_allclose(freqs[2], plain[2] * (0.5 + 0.5 / 8.0), rtol=1e-6)
    np.testing.assert_allclose(freqs[3], plain[3] / 8.0, rtol=1e-6)
    assert abs(CONFIG.attn_scale - 24**-0.5 * (0.1 * np.log(8.0) + 1.0) ** 2) < 1e-12


@pytest.mark.parametrize("fault", ["no_yarn_blend", "no_yarn_mscale"])
def test_yarn_left_out_fails_the_logits_comparison(params, fault):
    """The reference without the frequency blend (plain `f_i`), or without
    `m^2` in the softmax scale, is another model: the program's logits part
    from it by a thousand times the tolerance a sound comparison keeps."""
    tokens = tokens_of(57, seed=57)
    mine = T.forward(params, tokens[None], CONFIG)[0]
    assert err(mine, R.forward(params, tokens, DIMS)) < TOL
    assert err(mine, R.forward(params, tokens, {**DIMS, "faults": (fault,)})) > 1000 * TOL


# -- (iii) the kernels at two widths --------------------------------------------------------


def test_the_dense_latent_kernel_in_interpret_mode_is_its_jnp():
    """`ragged_paged_latent_attention` with NO selection (rows of 0, 13 and 30
    tokens) against the gathered masked jnp read; the same call under an
    all-true selection agrees; the call without one carries no mask operand."""
    kl, h = CONFIG.kv_lora_rank, CONFIG.n_heads
    width = CONFIG.latent_key_width
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    pool = jax.random.normal(keys[0], (2, PAGES, 1, PAGE, width))
    q = jax.random.normal(keys[1], (3, h, width))
    table = jnp.asarray([[0, 1, 2, 3], [4, 5, 9, 9], [6, 7, 3, 1]], jnp.int32)
    lengths = jnp.asarray([0, 13, 30], jnp.int32)
    t = table.shape[1] * PAGE
    visible = jnp.arange(t)[None] < lengths[:, None]
    read = lambda chosen: ops.ragged_paged_latent_attention(  # noqa: E731
        q, pool, lengths, table, jnp.int32(1), chosen, CONFIG, PAGE, interpret=True
    ).reshape(3, h, kl)
    got = read(None)
    rows = T._paged_gather(pool, jnp.int32(1), table, PAGE)[:, 0]
    logits = jnp.einsum("bhw,btw->bht", q, rows) * CONFIG.attn_scale
    probs = jnp.where(visible[:, None], jnp.exp(logits - logits.max(-1, keepdims=True)), 0.0)
    want = jnp.einsum("bht,btc->bhc", probs, rows[..., :kl]) / jnp.maximum(
        probs.sum(-1, keepdims=True), 1e-30
    )
    assert float(jnp.abs(got[0]).max()) == 0.0  # a row of nothing: zeros, not NaN
    assert err(got[1:], want[1:]) < 1e-5
    assert err(got, read(visible)) < 1e-6
    # the selected call carries the mask's float32 copy as a row block; the dense call none
    mask_block = f"f32[3,{table.shape[1]},1,{PAGE}]"
    assert mask_block not in str(jax.make_jaxpr(lambda: read(None))())
    assert mask_block in str(jax.make_jaxpr(lambda: read(visible))())


@pytest.mark.parametrize("kernel", ["prefill", "segment"])
def test_the_expanded_kernels_take_a_value_narrower_than_the_key(kernel):
    """`flash_prefill_attention` and `flash_segment_attention` at keys 24 wide
    and values 16 (interpret mode) against masked jnp: the output is H x 16
    and nothing is padded to the key's width."""
    h, dk, dv, s, t = CONFIG.n_heads, 24, 16, 16, 48
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (2, s, h, dk))
    config = dataclasses.replace(CONFIG, attention_impl="pallas")
    if kernel == "prefill":
        k, v = jax.random.normal(keys[1], (2, h, s, dk)), jax.random.normal(keys[2], (2, h, s, dv))
        got = ops.flash_prefill_attention(q, k, v, config, interpret=True)
        mask = jnp.tril(jnp.ones((s, s), jnp.bool_))[None].repeat(2, 0)
    else:
        k, v = jax.random.normal(keys[1], (2, h, t, dk)), jax.random.normal(keys[2], (2, h, t, dv))
        offsets = jnp.asarray([0, 24])
        got = ops.flash_segment_attention(q, k, v, offsets, config, interpret=True)
        positions = offsets[:, None] + jnp.arange(s)[None]
        mask = T._seen(positions, t)
    want = T.attention(q, k, v, mask, config)
    assert got.shape == want.shape == (2, s, h * dv)
    assert err(got, want) < 1e-5


def test_the_absorbed_read_is_the_expanded_attention_on_the_same_int8_matrix(params):
    """Random cache rows and queries: absorbed queries against the rows as
    they lie and `W_uv` after, against the expanded keys (24) and values (16)
    of every row, the same scale (YaRN's factor in both)."""
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    assert lp["wkv_b"]["q"].dtype == jnp.int8
    kl, h, hd = CONFIG.kv_lora_rank, CONFIG.n_heads, CONFIG.resolved_head_dim
    width, t = CONFIG.latent_key_width, 24
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    rows = jax.random.normal(keys[0], (2, t, width)).at[..., CONFIG.latent_width :].set(0.0)
    q = jax.random.normal(keys[1], (2, h, hd))
    absorbed = T._latent_absorb(q, lp, CONFIG, width)
    probs = jax.nn.softmax(jnp.einsum("bhw,btw->bht", absorbed, rows) * CONFIG.attn_scale, axis=-1)
    out = T._latent_value_out(jnp.einsum("bht,btc->bhc", probs, rows[..., :kl]), lp, CONFIG)
    k, v = T._latent_expand(rows, lp, CONFIG)
    assert k.shape == (2, h, t, 24) and v.shape == (2, h, t, 16)
    want = T.attention(q[:, None], k, v, jnp.ones((2, 1, t), jnp.bool_), CONFIG)[:, 0]
    assert out.shape == want.shape == (2, h * 16)
    assert err(out, want) < 1e-5


# -- (iv) the router and the shares -------------------------------------------------------------


def test_the_bias_chooses_and_does_not_weigh_and_the_scaling_is_applied_once():
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (50, 64))
    router = jax.random.normal(keys[1], (64, 8)) * 0.125
    bias = jax.random.normal(keys[2], (8,))  # large: it moves most choices
    weights, chosen = T._route_all(x, router, CONFIG, bias)
    gate, ref_chosen, _ = R.route(x, {"router": router, "router_bias": bias}, DIMS)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen)), np.sort(np.asarray(ref_chosen)))
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    unbiased = jax.lax.top_k(scores, 2)[1]
    assert (np.sort(np.asarray(chosen)) != np.sort(np.asarray(unbiased))).any()
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    # free of the bias to 1e-6, and the scaling once: the weights sum to 2.5, not 6.25
    np.testing.assert_allclose(weights, 2.5 * top / top.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gate.sum(-1)), 2.5, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(jnp.take_along_axis(gate, chosen, axis=-1)), np.asarray(weights), rtol=1e-6
    )


def test_the_shares_parts_add_up_to_the_uncut_references_layer():
    """All 8 experts' weights made once; the program's expert layer run as
    each of FOUR shares of two experts, each with the shared expert whole:
    their sum less three shared experts (counted once) is the reference's
    uncut layer (experts_held (0, 8)) on the same input."""
    whole = dataclasses.replace(CONFIG, experts_held=(0, 8), name="tiny-latent-dense-uncut")
    params = quantize_params(T.init_params(whole, jax.random.PRNGKey(2)), whole)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, CONFIG.d_model))
    u = R.rms_norm(x[0], lp["ffn_norm"], CONFIG.rms_norm_eps)
    uncut, info = R.moe(u, lp, {**DIMS, "experts_held": (0, 8)})
    shared = R.swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    parts, local = [], 0
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(CONFIG, experts_held=(first, 2), name=f"dense-share{first}")
        held = {
            **lp, **{k: jax.tree.map(lambda a: a[first : first + 2], lp[k]) for k in T._HELD_EXPERTS}
        }
        y, counts = T._ffn_half(x, held, share)
        parts.append(y[0] - x[0])
        ref_part, _ = R.moe(u, held, {**DIMS, "experts_held": (first, 2)})
        assert err(parts[-1], ref_part) < TOL
        local += int(counts[T.MOE_HELD_COUNTS.index("local")])
    assert local == 24 * 2  # every assignment lands on exactly one share
    assert err(sum(parts) - 3 * shared, uncut) < TOL
    assert float(jnp.abs(shared).max()) > 0.01  # the shared expert is no rounding
    assert int(info["expert_load"].sum()) == 24 * 2


# -- (v) nothing of an indexer, the pool, the plan, the refusals -----------------------------------


@pytest.mark.parametrize("program", ["decode", "segment"])
def test_nothing_of_an_indexer_is_traced(params, program):
    """The lowered program with its scopes: the dense read is under
    `attention.latent.read`; `attention.sparse`, `attention.select` and
    `attention.index` are nowhere in it, nor a `cond` on the lengths."""
    config = dataclasses.replace(CONFIG, attention_impl="pallas")
    table = jnp.arange(PAGES)[None]
    pool = T.make_page_pool(config, PAGES, PAGE)
    if program == "decode":
        lowered = jax.jit(
            lambda p, pool: T.paged_decode_step_inplace(
                p, jnp.array([3]), jnp.array([20]), pool, table, config, PAGE)
        ).lower(params, pool)
    else:
        lowered = jax.jit(
            lambda p, pool: T.paged_prefill_segment_inplace(
                p, tokens_of(16)[None], jnp.array([16]), jnp.array([16]), pool, table, config, PAGE)
        ).lower(params, pool)
    text = lowered.as_text(debug_info=True)
    assert "attention.latent.read" in text and "attention.latent/" in text
    for scope in ("attention.sparse", "attention.select", "attention.index"):
        assert scope not in text, scope
    # no branch on the lengths: the masked-jnp program (no kernel's own control flow) has no cond
    plain = dataclasses.replace(CONFIG, attention_impl="jnp")
    step = jax.make_jaxpr(
        lambda p, pool: T.paged_decode_step_inplace(
            p, jnp.array([3]), jnp.array([20]), pool, table, plain, PAGE)
    )(params, T.make_page_pool(plain, PAGES, PAGE))
    assert " cond[" not in str(step)
    assert {"wq_idx", "wk_idx", "w_idx"}.isdisjoint(params["layers"])
    # a model with an indexer keeps its scopes and no dense read
    glm = dataclasses.replace(MODEL_PRESETS["tiny-latent-moe-test"], dtype="float32",
                              attention_impl="pallas")
    glm_params = T.init_params(glm, jax.random.PRNGKey(0))
    glm_text = jax.jit(
        lambda p, pool: T.paged_decode_step_inplace(
            p, jnp.array([3]), jnp.array([20]), pool, table, glm, PAGE)
    ).lower(glm_params, T.make_page_pool(glm, PAGES, PAGE)).as_text(debug_info=True)
    assert "attention.sparse" in glm_text and "attention.latent.read" not in glm_text


def test_the_pool_holds_the_projected_latents_to_the_bit():
    """What a segment and a decode step write into the pool are the layer's
    projected rows `[c_kv | k_rope]` in the pool's dtype, bit for bit, in
    float32 and in bfloat16 (the benchmark's check cannot tell an 8-bit latent
    from a 16-bit one at Kimi-K2.5's widths: this holds the write instead),
    the tail lanes zeros, the unmapped pages untouched."""
    for dtype in ("float32", "bfloat16"):
        config = dataclasses.replace(CONFIG, dtype=dtype, name=f"dense-latent-{dtype}")
        tree = quantize_params(T.init_params(config, jax.random.PRNGKey(0)), config)
        tokens = tokens_of(17, seed=9)
        table = jnp.asarray([[2, 5, 7, PAGES]], jnp.int32)
        pool = T.make_page_pool(config, PAGES, PAGE)
        assert pool["lat"].dtype == jnp.dtype(dtype)
        _, pool = T.paged_prefill_segment_inplace(
            tree, tokens[None, :16], jnp.array([0]), jnp.array([16]), pool, table, config, PAGE
        )
        _, pool = T.paged_decode_step_inplace(
            tree, tokens[16:17], jnp.array([16]), pool, table, config, PAGE
        )
        x = T._embed(tree, tokens[None], config)
        lp = jax.tree.map(lambda a: a[0], tree["dense_layers"])
        sin, cos = T._rope_freqs(jnp.arange(17)[None], config)
        _, _, _, lat = T._latent_proj(x, lp, sin, cos, config)
        written = pool["lat"][0, jnp.asarray([2, 5, 7]), 0].reshape(24, -1)[:17]
        np.testing.assert_array_equal(
            np.asarray(written[:, : config.latent_width].astype(jnp.float32)),
            np.asarray(lat[0].astype(dtype).astype(jnp.float32)),
        )
        assert float(jnp.abs(written[:, config.latent_width :]).max()) == 0.0
        assert float(jnp.abs(pool["lat"][:, jnp.asarray([0, 1, 3, 4, 6])]).max()) == 0.0


def test_a_token_of_the_pool_and_the_memory_plans_page_term():
    config = MODEL_PRESETS[NAME]  # bf16
    assert config.page_leaves == ("lat",) and not config.has_indexer
    assert config.latent_width == 24 and config.latent_key_width == 128
    token = config.n_layers * config.latent_key_width * 2  # no indexer key
    assert config.kv_bytes_per_token() == token
    pool = T.make_page_pool(config, 64, 8)
    assert {k: v.shape for k, v in pool.items()} == {"lat": (4, 64, 1, 8, 128)}
    plan = plan_serving_memory(config, 4, 128, page_size=8, kv_pages=64)
    assert plan.page_pool_bytes == 64 * 8 * token
    kimi = dataclasses.replace(PUBLISHED, n_layers=7, n_heads=64, n_kv_heads=64, d_model=7168)
    assert kimi.latent_width == 576 and kimi.latent_key_width == 640
    assert kimi.kv_bytes_per_token() == 7 * 640 * 2 == 8960


@pytest.mark.parametrize(
    "change, says",
    [
        ({"layer_pattern": ("full_attention",)}, "a latent.*a layer pattern, a window or a recurrent"),
        ({"block_length": 4, "denoise_steps": 4, "mask_token_id": 5}, "a latent.*fills_blocks"),
        ({"mrope_section": (1, 1, 2)}, "a latent.*m-rope"),
        ({"kv_cache_dtype": "int8"}, "a latent.*an int8 KV cache"),
        ({"output_norm": True}, "experts_held belongs to a pre-norm block"),
        ({"qk_norm_heads": True}, "a latent.*qk_norm"),
        ({"attn_logit_softcap": 30.0}, "a latent.*an attention soft cap"),
        ({"v_head_dim": 0}, "v_head_dim 0 under 1"),
        ({"qk_rope_head_dim": 7}, "an odd qk_rope_head_dim"),
        ({"head_dim": 16}, "head_dim 16.*leave it unset"),
        ({"n_kv_heads": 2}, "n_kv_heads 2 apart from n_heads"),
        ({"index_n_heads": 2, "index_head_dim": 16}, "belong to a model with an indexer"),
        ({"index_query_input": "query_latent"}, "no indexer"),
        ({"rope_scaling_type": "ntk"}, "rope_scaling_type 'ntk'"),
        ({"rope_scaling_factor": None}, "yarn belongs to a model with a latent"),
        ({"rope_scaling_beta_fast": 1.0}, "beta_fast > beta_slow"),
        ({"kv_lora_rank": 0, "q_lora_rank": 0, "qk_nope_head_dim": 0, "qk_rope_head_dim": 0,
          "v_head_dim": 0, "n_leading_dense": 0, "rope_interleaved": False,
          "moe_scoring": "softmax", "n_shared_experts": 0},
         "yarn belongs to a model with a latent"),
    ],
    ids=lambda v: next(iter(v)) if isinstance(v, dict) else None,
)
def test_the_config_refuses_what_a_latent_model_still_cannot_be(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CONFIG, **change)


def test_a_latent_builds_with_and_without_an_indexer_and_no_message_says_otherwise():
    """The two reads are both models: the preset here has no indexer and a
    value narrower than its key; `tiny-latent-moe-test` has one and still
    selects (its tests are `tests/test_latent_attention.py`). Under an indexer
    a value width of its own is still refused, by name."""
    assert CONFIG.has_latent and not CONFIG.has_indexer and CONFIG.v_head_dim != CONFIG.resolved_head_dim
    glm = MODEL_PRESETS["tiny-latent-moe-test"]
    assert glm.has_latent and glm.has_indexer and glm.page_leaves == ("lat", "ik")
    assert glm.attn_scale == glm.resolved_head_dim**-0.5 and not glm.yarn
    bare = dataclasses.replace(
        glm, index_topk=0, index_n_heads=0, index_head_dim=0, index_rope_dim=0,
        index_query_input="hidden", name="tiny-latent-bare",
    )
    assert bare.page_leaves == ("lat",)
    with pytest.raises(ValueError, match="v_head_dim 12 apart from .* under an indexer"):
        dataclasses.replace(glm, v_head_dim=12)
    assert dataclasses.replace(bare, v_head_dim=12).v_head_dim == 12
    # a segment of the model with an indexer still ranks: its selection's kernels are traced
    config = dataclasses.replace(glm, dtype="float32", attention_impl="pallas")
    params = T.init_params(config, jax.random.PRNGKey(0))
    jax.eval_shape(
        lambda p, pool: T.paged_prefill_segment_inplace(
            p, jnp.zeros((1, 16), jnp.int32), jnp.array([16]), jnp.array([16]), pool,
            jnp.arange(PAGES)[None], config, PAGE),
        params, T.make_page_pool(config, PAGES, PAGE),
    )
    assert ops.attention_paths()[f"paged-segment-latent-select[s=16,t={PAGES * PAGE}]"] == "segment_select"


# -- (vi) through the engine ----------------------------------------------------------------------

ENGINE = dict(
    max_batch=4, max_seq_len=128, prefill_buckets=(16,), page_size=8, prefill_batch=1,
    kv_pages=64, decode_chunk=4,
)


@pytest.fixture(scope="module")
def served():
    return T.init_params(CONFIG, jax.random.PRNGKey(0))


def make_engine(config, params, **over):
    engine = E.ServingEngine(config, params, **{**ENGINE, **over})
    engine.start()
    engine.wait_ready()
    return engine


def prompt_of(n: int, seed: int = 0) -> list[int]:
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


def greedy(params, prompt, new_tokens: int) -> list[int]:
    tokens = list(prompt)
    for _ in range(new_tokens):
        logits = T.forward(params, jnp.asarray([tokens], jnp.int32), CONFIG)[0, -1]
        tokens.append(int(jnp.argmax(logits)))
    return tokens[len(prompt):]


@pytest.fixture(scope="module")
def engine(served):
    engine = make_engine(CONFIG, served)
    yield engine
    engine.stop()


@pytest.mark.parametrize("n", [5, 16, 40, 61], ids=lambda n: f"prompt{n}")
def test_the_engines_tokens_are_forwards(served, engine, n):
    """5 and 16: the admit group; 40 and 61: three and four segments; 8
    decode steps each in the latent space over every cached row."""
    prompt = prompt_of(n, seed=n)
    result = engine.generate(prompt, GenerationOptions(max_new_tokens=8), timeout=120)
    assert result.tokens == greedy(served, prompt, 8)
    assert set(engine._pagepool.dev) == {"lat"}


def test_a_prefix_hit_reads_the_aliased_pages_latents(served):
    shared = prompt_of(36, seed=1)
    first, second = shared + prompt_of(9, seed=2), shared + prompt_of(11, seed=3)
    engine = make_engine(
        dataclasses.replace(CONFIG, name="tiny-latent-dense-prefix"), served, prefix_cache=True
    )
    try:
        engine.generate(first, GenerationOptions(max_new_tokens=4), timeout=120)
        warm = engine.generate(second, GenerationOptions(max_new_tokens=6), timeout=120)
        stats = engine.stats()
    finally:
        engine.stop()
    assert warm.tokens == greedy(served, second, 6)
    assert stats["prefix-cache"] and stats["prefix-cache-hit-rate"] > 0


def test_spans_and_counters_say_what_the_dense_read_read(served):
    from langstream_tpu.serving import observability

    spans = []
    engine = make_engine(dataclasses.replace(CONFIG, name="tiny-latent-dense-spans"), served)
    emit = observability.emit_dispatch_span
    record = lambda name, start, end, attrs: spans.append((name, dict(attrs)))  # noqa: E731
    try:
        E.emit_dispatch_span = record
        engine.generate(prompt_of(40, 9), GenerationOptions(max_new_tokens=8), timeout=120)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not any(n == "engine.decode_chunk" for n, _ in spans):
            time.sleep(0.01)
        stats = engine.stats()
    finally:
        E.emit_dispatch_span = emit
        engine.stop()
    segments = [a for n, a in spans if n == "engine.prefill_segment"]
    chunks = [a for n, a in spans if n == "engine.decode_chunk"]
    assert [a["offset"] for a in segments] == [0, 16, 32]
    assert [a["latent_tokens_expanded"] for a in segments] == [0, 16, 32]
    for attrs in segments:
        # every query reads every column up to its own, a layer
        assert attrs["kv_tokens_read"] == (attrs["offset"] + 1 + np.arange(attrs["real_tokens"])).sum()
        assert not {"index_tokens_scored", "kv_tokens_selected"} & set(attrs)
        assert attrs["moe_routed_real"] == attrs["real_tokens"] * 2 * 3
    assert chunks
    for attrs in chunks:
        assert attrs["latent_tokens_expanded"] == 0  # a decode step attends in the latent space
        assert attrs["kv_tokens_read"] >= 40 * attrs["row_steps"]  # all of the row, every step
        assert not {"index_tokens_scored", "kv_tokens_selected"} & set(attrs)
    assert stats["latent-tokens-expanded-total"] == 48
    assert stats["kv-tokens-read-total"] >= sum(a["kv_tokens_read"] for a in segments + chunks)
    assert stats["kv-bytes-per-token"] == CONFIG.kv_bytes_per_token(itemsize=4) == 4 * 128 * 4
    assert not {"index-tokens-scored-total", "kv-tokens-selected-total"} & set(stats)


@pytest.mark.parametrize(
    "option",
    [
        {"host_kv_fraction": 1.0}, {"migrate_staging": True}, {"durable_dir": "under-tmp-path"},
        {"speculation": "auto"}, {"speculation": True},
        {"adapters": [{"name": "a", "rank": 4}]}, {"mesh": object()}, {"spmd": object()},
    ],
    ids=lambda o: f"{next(iter(o))}-{next(iter(o.values()))!s:.8}",
)
def test_the_engine_refuses_by_name_for_the_latents_own_reason(served, option, tmp_path):
    name = next(iter(option))
    if name == "durable_dir":  # refused before anything is made there
        option = {name: str(tmp_path / "never-made")}
    with pytest.raises(ValueError, match=f"keeps a latent: .*{name}.*with a latent in the page pool"):
        E.ServingEngine(CONFIG, served, **{**ENGINE, **option})


def test_migration_is_refused_by_name(engine):
    from langstream_tpu.serving.migrate import MigrationError

    with pytest.raises(MigrationError, match="a page's latent has no wire format"):
        engine._migrate_rpc("snapshot", {}, 1.0)

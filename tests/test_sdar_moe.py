"""The SDAR-MoE block in the program (`tiny-blockfill-moe-test`'s size, float32
on the CPU) against the plain reference of `benchmark/reference/sdar_moe.py`:

(i)   what the config admits and refuses: the sequential block reads
      `experts_held` and `moe_d_ff`, a block-filling model's fields hold
      together;
(ii)  the block: every layer and the logits of `forward` against the
      reference's, under the block-causal mask, with per-head q/k norm and
      the no-drop expert layer of a width apart from `d_ff`;
(iii) the shares add up: the expert parts of shares (0, 8) and (8, 8) of the
      sequential block are the whole layer's;
(iv)  the kernels against the jnp path (Pallas in interpret mode): the
      prefill kernel under the block mask, the block pass's attention, the
      block's K/V write;
(v)   the choice by confidence against the reference's rule.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS
from langstream_tpu.ops import attention as A
from langstream_tpu.serving.sampling import block_choice

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
sys.path[:0] = [p for p in (str(BENCH),) if p not in sys.path]
from modelcfg import load_module  # noqa: E402

ref = load_module("reference", "sdar_moe")

PRESET = MODEL_PRESETS["tiny-blockfill-moe-test"]
TINY = dataclasses.replace(PRESET, dtype="float32")
DIMS = {
    "n_heads": TINY.n_heads, "n_kv_heads": TINY.n_kv_heads, "head_dim": TINY.resolved_head_dim,
    "rope_theta": TINY.rope_theta, "eps": TINY.rms_norm_eps, "top_k": TINY.n_experts_per_tok,
    "n_experts": TINY.n_experts, "block_length": TINY.block_length, "denoising_steps": TINY.denoise_steps,
    "confidence_threshold": TINY.confidence_threshold, "mask_token_id": TINY.mask_token_id,
}
# float32 against float32 at the highest precision: rounding of another order
# of summation (1e-6 seen); a wrong mask, norm or expert reads 1e-2 and more
SOUND, FAULT = 2e-5, 2e-2


@pytest.fixture(scope="module")
def params():
    tree = T.init_params(TINY, jax.random.PRNGKey(0))
    # norms off one, so that a norm left out or misplaced shows
    key = jax.random.PRNGKey(1)
    for name in ("q_norm", "k_norm", "attn_norm", "ffn_norm"):
        key, sub = jax.random.split(key)
        shape = tree["layers"][name].shape
        tree["layers"][name] = 1.0 + 0.3 * jax.random.normal(sub, shape, jnp.float32)
    return tree


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(1, 500, (2, 32)), jnp.int32)


def rel_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# -- (i) the config ------------------------------------------------------------


def test_the_sequential_block_reads_held_experts_and_their_width():
    config = dataclasses.replace(MODEL_PRESETS["tiny-moe-test"], experts_held=(0, 8), moe_d_ff=32)
    assert config.holds_experts and config.expert_d_ff == 32 and not config.has_window
    assert T.moe_count_names(config) == T.MOE_HELD_COUNTS
    assert T.moe_count_names(MODEL_PRESETS["tiny-moe-test"]) == T.MOE_COUNTS
    tree = T.init_params(config, jax.random.PRNGKey(0))
    assert tree["layers"]["w_gate"].shape == (2, 8, 64, 32)
    assert tree["layers"]["router"].shape == (2, 64, 8)


@pytest.mark.parametrize(
    "change, says",
    [
        ({"denoise_steps": 0}, "denoise_steps 0 outside 1..4"),
        ({"denoise_steps": 5}, "denoise_steps 5 outside 1..4"),
        ({"mask_token_id": 512}, "mask_token_id 512 outside the vocabulary"),
        ({"mask_token_id": None}, "mask_token_id None outside the vocabulary"),
        ({"confidence_threshold": 0.0}, "confidence_threshold"),
        ({"experts_held": (), "moe_d_ff": 32}, "moe_d_ff is read by the no-drop expert layer"),
        ({"qk_norm": True}, "two norms, not one"),
        ({"block_length": 0}, "belong to a model that fills blocks"),
    ],
    ids=lambda v: v if isinstance(v, str) else "-".join(v),
)
def test_what_the_config_refuses(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(PRESET, **change)


def test_a_block_filling_model_has_no_layer_pattern():
    with pytest.raises(ValueError, match="layer pattern"):
        dataclasses.replace(
            MODEL_PRESETS["tiny-hybrid-test"], block_length=4, denoise_steps=4, mask_token_id=511,
        )


def test_the_schedule_spreads_the_remainder_over_the_first_steps():
    assert PRESET.block_schedule == (1, 1, 1, 1)
    assert dataclasses.replace(PRESET, denoise_steps=3).block_schedule == (2, 1, 1)
    assert dataclasses.replace(PRESET, denoise_steps=1).block_schedule == (4,)
    assert MODEL_PRESETS["tiny-test"].block_schedule == ()


# -- (ii) the block against the reference ---------------------------------------


def test_every_layer_is_the_references(params, tokens):
    """Teacher-forced: the reference layer is given the program's input."""
    s = tokens.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s), tokens.shape)
    sin, cos = T._rope_freqs(positions, TINY)
    place = jnp.arange(s)
    mask = jnp.broadcast_to(T._visible(place[:, None], place[None, :], TINY), (2, s, s))
    x = T._embed(params, tokens, TINY)
    for index in range(TINY.n_layers):
        lp = jax.tree.map(lambda a: a[index], params["layers"])
        y, _ = T._layer(x, lp, sin, cos, mask, TINY)
        for row in range(2):
            want, info = ref.layer(x[row], lp, DIMS)
            assert rel_err(y[row], want) < SOUND, (index, row)
            assert int(info["expert_load"].sum()) == s * TINY.n_experts_per_tok
        x = y


def test_forward_is_the_references_full_forward(params, tokens):
    got = T.forward(params, tokens, TINY)
    for row in range(2):
        assert rel_err(got[row], ref.forward(params, tokens[row], DIMS)) < SOUND


def test_the_mask_is_two_way_inside_a_block_and_causal_across(params, tokens):
    """A later token of the SAME block moves a position's logits; a token of
    a later block does not; under the causal mask neither the first."""
    base = T.forward(params, tokens[:1], TINY)[0]
    same = T.forward(params, tokens[:1].at[0, 7].set(3), TINY)[0]  # block 1: 4..7
    assert rel_err(same[4], base[4]) > FAULT  # position 4 sees position 7
    assert float(jnp.max(jnp.abs(same[:4] - base[:4]))) == 0.0  # block 0 does not
    causal = dataclasses.replace(TINY, block_length=0, denoise_steps=0, mask_token_id=None)
    c_base = T.forward(params, tokens[:1], causal)[0]
    c_same = T.forward(params, tokens[:1].at[0, 7].set(3), causal)[0]
    assert float(jnp.max(jnp.abs(c_same[:7] - c_base[:7]))) == 0.0


@pytest.mark.parametrize("fault", ["causal-mask", "whole-width-free-qk-norm", "dense-width-experts"])
def test_a_fault_fails_by_a_number(params, tokens, fault):
    want = ref.forward(params, tokens[0], DIMS)
    if fault == "causal-mask":
        config = dataclasses.replace(TINY, block_length=0, denoise_steps=0, mask_token_id=None)
        got = T.forward(params, tokens[:1], config)[0]
    elif fault == "whole-width-free-qk-norm":
        got = T.forward(params, tokens[:1], dataclasses.replace(TINY, qk_norm_heads=False))[0]
    else:  # the top-k weights not divided by their sum: softmax over all experts
        dims = {**DIMS, "top_k": TINY.n_experts_per_tok - 1}
        got, want = T.forward(params, tokens[:1], TINY)[0], ref.forward(params, tokens[0], dims)
    assert rel_err(got, want) > FAULT


# -- (iii) the shares add up -----------------------------------------------------


def test_two_shares_of_the_sequential_block_add_up_to_the_whole_layer(params, tokens):
    x = T._embed(params, tokens, TINY)
    whole, counts = T._ffn_half(x, jax.tree.map(lambda a: a[0], params["layers"]), TINY)
    half = TINY.n_experts // 2
    parts, local = 0.0, 0
    for first in (0, half):
        config = dataclasses.replace(TINY, experts_held=(first, half))
        lp = {
            k: v[0, first : first + half] if k in ("w_gate", "w_up", "w_down") else v[0]
            for k, v in params["layers"].items()
        }
        y, c = T._ffn_half(x, lp, config)
        parts, local = parts + (y - x), local + int(c[4])
    assert rel_err(parts, whole - x) < SOUND
    # every assignment fell on one of the two shares, none dropped
    assert local == int(counts[4]) == tokens.size * TINY.n_experts_per_tok
    assert int(counts[1]) == 0 and int(counts[5]) <= TINY.n_experts


# -- (iv) the kernels -------------------------------------------------------------


def test_the_prefill_kernel_masks_by_block():
    """`flash_prefill_attention` (interpret mode) under the block mask against
    the jnp attention under `_visible`'s, at two query tiles of 64."""
    config = dataclasses.replace(PRESET, attention_impl="pallas")
    rng = np.random.default_rng(1)
    s, h, hkv, d = 128, PRESET.n_heads, PRESET.n_kv_heads, PRESET.resolved_head_dim
    q = jnp.asarray(rng.normal(size=(1, s, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, hkv, s, d)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, hkv, s, d)), jnp.bfloat16)
    got = A.flash_prefill_attention(q, k, v, config, block_q=64, block_k=64, interpret=True)
    place = jnp.arange(s)
    want = T.attention(q, k, v, T._visible(place[:, None], place[None, :], config)[None], config)
    # bf16 against bf16 in another order of summation; the causal kernel on
    # the same values reads 0.5 at a block's first position
    assert rel_err(got.astype(jnp.float32), want.astype(jnp.float32)) < 0.02
    causal = dataclasses.replace(config, block_length=0, denoise_steps=0, mask_token_id=None)
    wrong = A.flash_prefill_attention(q, k, v, causal, block_q=64, block_k=64, interpret=True)
    assert rel_err(wrong.astype(jnp.float32), want.astype(jnp.float32)) > 0.1


def test_the_block_kernels_are_the_jnp_paths(params):
    """`ragged_paged_block_attention` and the block's `paged_kv_write`
    (interpret mode) against the gathered jnp read and the scatter: rows at
    different starts, one idle, a block that ends on a page's last row."""
    page, pages, hkv, d, s = 8, 16, TINY.n_kv_heads, TINY.resolved_head_dim, 4
    rng = np.random.default_rng(2)
    pool_k = jnp.asarray(rng.normal(size=(2, pages, hkv, page, d)), jnp.bfloat16)
    pool_v = jnp.asarray(rng.normal(size=(2, pages, hkv, page, d)), jnp.bfloat16)
    table = jnp.asarray([[3, 1, 7, pages], [2, 9, pages, pages], [pages] * 4], jnp.int32)
    starts = jnp.asarray([16, 12, 0], jnp.int32)  # row 1's block is its page's last rows
    q = jnp.asarray(rng.normal(size=(3, s, TINY.n_heads, d)), jnp.bfloat16)
    new_k = jnp.asarray(rng.normal(size=(3, s, hkv, d)), jnp.bfloat16)
    new_v = jnp.asarray(rng.normal(size=(3, s, hkv, d)), jnp.bfloat16)
    pos = starts[:, None] + jnp.arange(s)[None, :]
    layer = jnp.int32(1)
    assert A.block_write_ok(s, page) and not A.block_write_ok(3, page)
    pages_at, offs = T._page_index(table, pos[:, :1], page, pages)
    wk, wv = A.paged_kv_write(
        (new_k.reshape(3, -1, d), new_v.reshape(3, -1, d)), pool_k, pool_v,
        pages_at[:, 0], offs[:, 0], layer, PRESET, interpret=True,
    )
    sk = T._paged_scatter(pool_k, layer, new_k.transpose(0, 2, 1, 3), table, pos, page)
    sv = T._paged_scatter(pool_v, layer, new_v.transpose(0, 2, 1, 3), table, pos, page)
    assert jnp.array_equal(wk, sk) and jnp.array_equal(wv, sv)
    assert not jnp.array_equal(wk, pool_k)
    lengths = T._paged_lengths(table, pos[:, -1], page, pages)
    assert lengths.tolist() == [20, 16, 0]
    got = A.ragged_paged_block_attention(
        q, wk, wv, lengths, table, layer, PRESET, page, interpret=True
    )
    mask = T._paged_mask(table, page, jnp.broadcast_to(pos[:, -1:], (3, s)))
    want = T.attention(
        q, T._paged_gather(sk, layer, table, page), T._paged_gather(sv, layer, table, page),
        mask, PRESET,
    )
    assert rel_err(got[:2].astype(jnp.float32), want[:2].astype(jnp.float32)) < 0.02
    assert float(jnp.max(jnp.abs(got[2].astype(jnp.float32)))) == 0.0  # the idle row


# -- (v) the choice ------------------------------------------------------------------


@pytest.mark.parametrize("threshold", [0.9, 0.02])
def test_the_choice_is_the_references_rule(threshold):
    rng = np.random.default_rng(3)
    rows, s, v = 6, 4, TINY.vocab_size
    logits = jnp.asarray(rng.normal(size=(rows, s, v)) * 3, jnp.float32)
    logits = logits.at[:, :, TINY.mask_token_id].set(50.0)  # the mask id would win everywhere
    is_open = jnp.asarray(rng.random((rows, s)) < 0.7)
    step = jnp.asarray(rng.integers(0, 4, rows), jnp.int32)
    zeros = jnp.zeros(rows)
    tokens, fixed, over = block_choice(
        logits, jax.random.PRNGKey(0), zeros, zeros.astype(jnp.int32), zeros + 1.0,
        is_open, step, TINY.mask_token_id, threshold, TINY.block_schedule,
    )
    dims = {**DIMS, "confidence_threshold": threshold}
    for r in range(rows):
        want_tokens, want_fixed = ref.denoise_choice(logits[r], is_open[r], int(step[r]), dims)
        assert tokens[r].tolist() == want_tokens.tolist()
        assert fixed[r].tolist() == want_fixed.tolist()
        assert TINY.mask_token_id not in tokens[r].tolist()
        n_open = int(is_open[r].sum())
        assert int(fixed[r].sum()) >= min(1, n_open) and not bool((fixed[r] & ~is_open[r]).any())
    if threshold < 0.1:  # several positions stand over a low threshold
        assert int(over.sum()) > rows and int(fixed.sum()) > rows


def test_a_drawn_token_carries_the_probability_it_was_drawn_with():
    """At a temperature the token is a draw and its confidence the draw's
    probability; a row at temperature 0 beside it stays greedy."""
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.normal(size=(2, 4, 64)) * 2, jnp.float32)
    is_open = jnp.ones((2, 4), jnp.bool_)
    temps = jnp.asarray([0.0, 1.5])
    tokens, fixed, _ = block_choice(
        logits, jax.random.PRNGKey(5), temps, jnp.zeros(2, jnp.int32), jnp.ones(2),
        is_open, jnp.zeros(2, jnp.int32), 63, 0.999, (1, 1, 1, 1),
    )
    greedy = jnp.argmax(logits.at[:, :, 63].set(-jnp.inf), axis=-1)
    assert tokens[0].tolist() == greedy[0].tolist()
    assert tokens[1].tolist() != greedy[1].tolist()  # four draws at 1.5 over 63 ids
    assert fixed.sum(axis=-1).tolist() == [1, 1]

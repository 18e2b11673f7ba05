"""A model of gated short-convolution layers and attention layers of 64-wide
heads with sigmoid-routed experts behind leading dense layers (LFM2-MoE:
`ModelConfig.layer_pattern` with the `conv` kind, `n_leading_dense` inside the
pattern, `kv_head_pack`) at a tiny size, seeded, on the CPU: the program
against the benchmark's plain reference, prefill and decode through pages and
tails against the full forward, ragged groups, segments, idle rows, the
router's three rules, the kernels at two heads a lane row against the jnp
path, the engine end to end and what it refuses."""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions, ModelConfig
from langstream_tpu.ops import attention as A
from langstream_tpu.serving import engine as E

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
CFG = dataclasses.replace(MODEL_PRESETS["tiny-lfm2-test"], dtype="float32")
PAGE = 16


def _reference():
    spec = importlib.util.spec_from_file_location(
        "lfm2_moe_reference", BENCH / "reference" / "lfm2_moe.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
DIMS = {
    "n_heads": CFG.n_heads, "n_kv_heads": CFG.n_kv_heads, "head_dim": CFG.resolved_head_dim,
    "eps": CFG.rms_norm_eps, "rope_theta": CFG.rope_theta, "top_k": CFG.n_experts_per_tok,
    "n_experts": CFG.n_experts, "routed_scaling": CFG.routed_scaling,
    "layer_pattern": CFG.layer_pattern, "n_dense": CFG.n_leading_dense, "n_layers": CFG.n_layers,
}


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


def _tokens(seed, *shape):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size - 1, shape).astype(np.int32)


_FORWARD = {}


def _forward_all(params, sequence, config=CFG):
    """The full forward's logits at every position (causal: position p holds
    what the forward of the first p + 1 tokens ends in)."""
    key = (tuple(sequence), config.attention_impl)
    if key not in _FORWARD:
        _FORWARD[key] = np.asarray(
            T.forward(params, jnp.asarray([sequence], jnp.int32), config)[0]
        )
    return _FORWARD[key]


# -- the program against the plain reference ----------------------------------


def test_the_lfm2_forward_matches_the_reference(params):
    sequence = _tokens(1, 40)
    with jax.default_matmul_precision("highest"):
        got = T.forward(params, jnp.asarray(sequence[None]), CFG)[0]
    want = REF.forward(params, jnp.asarray(sequence), DIMS)
    np.testing.assert_allclose(got, want, atol=5e-5)
    assert float(jnp.abs(want).max()) > 1.0  # not a comparison of zeros


@pytest.mark.parametrize("index", [0, 1, 2, 3, 6])
def test_each_lfm2_layer_matches_the_reference_where_the_pattern_puts_it(params, index):
    """Layers 0 and 1 are the pattern's leading dense layers (conv mixers of
    period 0 with a dense FFN, `params["dense_layers"]["conv"]`); layer 2 the
    first attention layer and layer 3 the first conv layer with experts, both
    at place 0 of their stacks; layer 6 the second attention layer."""
    stack, kind, at = REF.stack_of(params, index, DIMS)
    assert (stack, kind, at) == {
        0: ("dense_layers", "conv", 0), 1: ("dense_layers", "conv", 1),
        2: ("layers", "full_attention", 0), 3: ("layers", "conv", 0),
        6: ("layers", "full_attention", 1),
    }[index]
    lp = jax.tree.map(lambda a: a[at], params[stack][kind])
    assert ("router" in lp) == (index >= CFG.n_leading_dense)
    x = jax.random.normal(jax.random.PRNGKey(index), (1, 24, CFG.d_model), jnp.float32)
    positions = jnp.arange(24)[None]
    with jax.default_matmul_precision("highest"):
        if kind == "conv":
            y, _ = T._short_conv_block(x, lp, CFG, None, 0, None)
        else:
            sin, cos = T._rope_freqs(positions, CFG)
            mask = jnp.tril(jnp.ones((24, 24), jnp.bool_))[None]
            y, _ = T._attention_block(x, lp, sin, cos, mask, CFG)
        y, _ = T._ffn_half(y, lp, CFG, dense=index < CFG.n_leading_dense)
    want, info = REF.layer(x[0], {"conv_dense": lp}, DIMS)
    np.testing.assert_allclose(y[0], want, atol=2e-5)
    assert int(info["expert_load"].sum()) == (24 * 2 if "router" in lp else 0)


def test_the_lfm2_leading_dense_layers_read_their_own_stack_and_state_rows(params):
    """A leading dense layer's weights are its own (changing them moves the
    logits, changing the expert stack's place 0 of the conv kind does too, and
    they are different layers), and its tail lies at ITS place among the conv
    layers' state rows: rows 0 and 1 of `rec["conv"]`, the kind's expert stack
    from row 2 on."""
    assert (CFG.dense_of("conv"), CFG.dense_of("full_attention")) == (2, 0)
    assert params["dense_layers"]["conv"]["w_in"].shape[0] == 2
    assert params["layers"]["conv"]["w_in"].shape[0] == CFG.n_layers_of("conv") - 2 == 4
    assert "router" not in params["dense_layers"]["conv"]
    sequence = _tokens(2, 12)
    base = _forward_all(params, sequence.tolist())

    def moved(stack, leaf, at):
        changed = jax.tree.map(lambda a: a, params)
        changed[stack] = {**params[stack], "conv": {
            **params[stack]["conv"], leaf: params[stack]["conv"][leaf].at[at].multiply(1.5)}}
        got = np.asarray(T.forward(changed, jnp.asarray(sequence[None]), CFG)[0])
        return float(np.abs(got - base).max())

    assert moved("dense_layers", "w_down", 1) > 1e-3 and moved("layers", "w_in", 0) > 1e-3
    # each conv layer writes its own state row: a prefill of one row fills all six
    cache = T.join_rec(T.make_kv_cache(CFG, 1, 16), T.make_recurrent_state(CFG, 1))
    _, cache = T.prefill(
        params, jnp.asarray([sequence.tolist() + [0] * 4], jnp.int32), jnp.asarray([12]), cache,
        CFG, rec_rows=jnp.asarray([0]),
    )
    tails = np.asarray(cache["rec"]["conv"][:, 0])
    assert tails.shape == (6, 2 * CFG.d_model) and (np.abs(tails).max(axis=1) > 0).all()
    assert len({tails[i].tobytes() for i in range(6)}) == 6


# -- the router ----------------------------------------------------------------


def _route(scores_logit, bias, **changes):
    config = dataclasses.replace(CFG, **changes)
    e = scores_logit.shape[-1]
    # a router that hands the logits through: x is the logits, W the identity
    return T._route_all(scores_logit, jnp.eye(e, dtype=jnp.float32), config, bias)


def test_the_lfm2_router_s_bias_chooses_and_does_not_weigh():
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0, -3.0, -4.0]])
    bias = jnp.zeros((8,)).at[7].set(5.0)  # lifts the last expert over every other
    weights, chosen = _route(logits, bias)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 7]
    s = jax.nn.sigmoid(logits[0])
    want = {0: float(s[0] / (s[0] + s[7] + 1e-6)), 7: float(s[7] / (s[0] + s[7] + 1e-6))}
    got = dict(zip(np.asarray(chosen[0]).tolist(), np.asarray(weights[0]).tolist()))
    assert got == pytest.approx(want, rel=1e-6)
    # the weights are the scores', not the biased ones': expert 7 weighs little
    assert got[7] < 0.03 < 0.9 < got[0]


def test_the_lfm2_router_adds_its_epsilon_under_the_sum_and_no_other_model_does():
    logits = jnp.asarray([[-12.0, -12.5, -13.0, -14.0, -15.0, -16.0, -17.0, -18.0]])
    s = np.asarray(jax.nn.sigmoid(logits[0]), np.float64)
    with_eps, _ = _route(logits, jnp.zeros((8,)))
    without, _ = _route(logits, jnp.zeros((8,)), router_norm_eps=0.0)
    # scores of 6e-6 and 4e-6: the published 1e-6 is a tenth of their sum
    assert float(with_eps.sum()) == pytest.approx((s[0] + s[1]) / (s[0] + s[1] + 1e-6), rel=1e-5)
    assert float(without.sum()) == pytest.approx(1.0, rel=1e-6)
    assert float(with_eps.sum()) < 0.92
    assert all(c.router_norm_eps == 0.0 for n, c in MODEL_PRESETS.items() if n != "tiny-lfm2-test")


def test_the_lfm2_router_breaks_a_tie_to_the_lower_index():
    logits = jnp.asarray([[0.0, 1.0, 1.0, 1.0, 0.5, 1.0, -1.0, -1.0]])
    _, chosen = _route(logits, jnp.zeros((8,)))
    assert sorted(np.asarray(chosen[0]).tolist()) == [1, 2]  # of the four tied at 1.0
    want = REF.route(logits, {"router": jnp.eye(8), "router_bias": jnp.zeros((8,))}, DIMS)[1]
    assert sorted(np.asarray(want[0]).tolist()) == [1, 2]


# -- pages, tails, groups, segments, idle rows ---------------------------------

DECODE = jax.jit(T.paged_decode_step_inplace, static_argnums=(5, 6))
SEGMENT = jax.jit(
    T.paged_prefill_segment_inplace, static_argnums=(6, 7), static_argnames=("config", "page_size")
)


def _decode_and_compare(params, pool, tables, slots, full, lengths, config, steps=4, atol=2e-4):
    rows = tables.shape[0]
    want = [_forward_all(params, full[i][: lengths[i] + steps]) for i in range(len(slots))]
    for step in range(steps):
        tok, pos = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
        for i, slot in enumerate(slots):
            tok[slot], pos[slot] = full[i][lengths[i] + step], lengths[i] + step
        logits, pool = DECODE(
            params, jnp.asarray(tok), jnp.asarray(pos), pool, jnp.asarray(tables), config, PAGE
        )
        for i, slot in enumerate(slots):
            np.testing.assert_allclose(logits[slot], want[i][lengths[i] + step], atol=atol)
    return pool


def _padded_group(params, config, lengths, slots, rows, n_pages, width=64):
    """A ragged admission group prefilled into a local cache and state rows
    `slots`, inserted into a pool: (pool, tables, the sequences, the group's
    logits)."""
    full = [_tokens(10 + i, n + 6).tolist() for i, n in enumerate(lengths)]
    group = np.zeros((len(lengths), width), np.int32)
    for i, n in enumerate(lengths):
        group[i, :n] = full[i][:n]
        group[i, n:] = _tokens(90 + i, width - n)  # padding that is NOT zeros
    tables = np.full((rows, 6), n_pages, np.int32)
    for i, slot in enumerate(slots):
        tables[slot] = np.arange(6) + 6 * i
    kv, rec = T.split_rec(T.make_page_pool(config, n_pages, PAGE, state_rows=rows))
    logits, cache = T.prefill(
        params, jnp.asarray(group), jnp.asarray(lengths),
        T.join_rec(T.make_kv_cache(config, len(lengths), width), rec), config,
        rec_rows=jnp.asarray(slots),
    )
    cache, rec = T.split_rec(cache)
    pool = T.paged_insert_cache(
        T.join_rec(kv, rec), cache, jnp.asarray(tables[slots]), PAGE, config
    )
    return pool, tables, full, logits


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_lfm2_ragged_group_then_decode_equals_the_full_forward(params, impl):
    """Rows of unequal length (one a single token, one the whole width) take
    their tails from their own last two inputs, never from the padding behind
    them; then four decode steps through pages and tails. `pallas`: the
    prefill kernel, `paged_insert_pages`, `paged_kv_write` and the paged decode
    kernel in interpret mode, two heads of 64 to a lane row."""
    config = dataclasses.replace(CFG, attention_impl=impl)
    lengths, slots, rows, n_pages = [37, 64, 1], [2, 0, 3], 4, 40
    pool, tables, full, logits = _padded_group(params, config, lengths, slots, rows, n_pages)
    assert pool["k"].shape == (2, n_pages, 1, PAGE, 128)  # 2 KV heads of 64: one lane row
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(logits[i], _forward_all(params, full[i])[n - 1], atol=2e-4)
    # a row's tail is its last two REAL inputs: what a prefill of the row alone leaves
    for i, (n, slot) in enumerate(zip(lengths, slots)):
        alone = T.join_rec(T.make_kv_cache(config, 1, 64), T.make_recurrent_state(config, 1))
        _, alone = T.prefill(
            params, jnp.asarray([full[i][:n] + [0] * (64 - n)], jnp.int32), jnp.asarray([n]),
            alone, config, rec_rows=jnp.asarray([0]),
        )
        np.testing.assert_allclose(
            pool["rec"]["conv"][:, slot], alone["rec"]["conv"][:, 0], atol=1e-5
        )
    before = np.asarray(pool["rec"]["conv"][:, 1])
    pool = _decode_and_compare(params, pool, tables, slots, full, lengths, config)
    # row 1 never held a sequence: an idle row of every step, its tail untouched
    np.testing.assert_array_equal(pool["rec"]["conv"][:, 1], before)
    assert float(jnp.abs(pool["rec"]["conv"][:, 1]).max()) == 0.0
    if impl == "pallas":
        paths = A.attention_paths()
        assert paths["prefill[s=64,t=64]"] == "flash_prefill_attention"
        assert paths["paged-decode[s=1,t=96]"] == "ragged_paged_decode_attention"
        assert paths["paged-decode-write[s=1]"] == "paged_kv_write"
        assert paths["paged-insert[w=64]"] == "paged_insert_pages"


def test_an_idle_lfm2_row_s_tail_is_untouched_by_a_step(params):
    """A row whose table maps nothing rides a decode step (free slots do) and
    its tail stays to the bit, whatever it held; a live row's moves by one."""
    lengths, slots, rows, n_pages = [20], [1], 3, 12
    pool, tables, full, _ = _padded_group(params, CFG, lengths, slots, rows, n_pages)
    marked = pool["rec"]["conv"].at[:, 0].set(7.0).at[:, 2].set(-3.0)
    pool = {**pool, "rec": {"conv": marked}}
    live_before = np.asarray(pool["rec"]["conv"][:, 1])
    pool = _decode_and_compare(params, pool, tables, slots, full, lengths, CFG, steps=1)
    after = np.asarray(pool["rec"]["conv"])
    assert (after[:, 0] == 7.0).all() and (after[:, 2] == -3.0).all()
    d = CFG.d_model
    # the live row's window moved on by one: its newer input is now the older
    np.testing.assert_array_equal(after[:, 1, :d], live_before[:, d:])
    assert not np.array_equal(after[:, 1, d:], live_before[:, d:])


def test_an_lfm2_prompt_in_two_segments_equals_one(params):
    """83 tokens as a whole segment of 64 and 19 of a second (the tail carried
    in from the row's state) against the same prompt prefilled in one segment
    of 128: the same logits, the same tails, the same decode."""
    n, n_pages = 83, 12
    full = [_tokens(20, n + 6).tolist()]
    table = jnp.asarray([list(range(8))], jnp.int32)

    def segments(width):
        pool = T.make_page_pool(CFG, n_pages, PAGE, state_rows=2)
        # row 1's state from an earlier sequence: a segment at offset 0 starts from zero
        pool["rec"] = jax.tree.map(lambda a: a + 1, pool["rec"])
        for offset in range(0, n, width):
            real = min(width, n - offset)
            segment = np.zeros((1, width), np.int32)
            segment[0, :real] = full[0][offset : offset + real]
            logits, pool = SEGMENT(
                params, jnp.asarray(segment), jnp.asarray([offset]), jnp.asarray([real]), pool,
                table, CFG, PAGE, state_rows=jnp.asarray([1]),
            )
        return logits, pool

    (two, pool), (one, whole) = segments(64), segments(128)
    np.testing.assert_allclose(two[0], one[0], atol=2e-4)
    np.testing.assert_allclose(two[0], _forward_all(params, full[0])[n - 1], atol=2e-4)
    np.testing.assert_allclose(pool["rec"]["conv"][:, 1], whole["rec"]["conv"][:, 1], atol=1e-5)
    tables = np.full((2, 8), n_pages, np.int32)
    tables[1] = np.arange(8)
    _decode_and_compare(params, pool, tables, [1], full, [n], CFG)


# -- two heads of 64 to a lane row: the kernels against jnp --------------------


def test_paired_queries_read_their_own_half_and_nothing_else():
    q = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 64))  # 8 heads over 4 KV heads
    packed = A.pair_queries(q, 2, 2)  # 2 packed rows of 2 heads: group 2 -> 4
    assert packed.shape == (3, 8, 128)
    half = [0, 0, 1, 1, 0, 0, 1, 1]  # head h reads KV head h // 2, part (h // 2) % 2
    for h, part in enumerate(half):
        np.testing.assert_array_equal(packed[:, h, 64 * part : 64 * part + 64], q[:, h])
        assert float(jnp.abs(packed[:, h, 64 * (1 - part) : 64 * (1 - part) + 64]).max()) == 0.0
    out = jax.random.normal(jax.random.PRNGKey(1), (3, 8 * 128))
    own = A.own_half(out, 8, 2, 2).reshape(3, 8, 64)
    for h, part in enumerate(half):
        np.testing.assert_array_equal(
            own[:, h], out.reshape(3, 8, 128)[:, h, 64 * part : 64 * part + 64]
        )


@pytest.mark.parametrize("s", [64, 128])
def test_the_prefill_kernel_at_64_wide_heads_equals_jnp(s):
    config = dataclasses.replace(CFG, attention_impl="pallas", dtype="float32")
    assert config.kv_head_pack == 2 and A.pallas_ok(config, s)
    keys = jax.random.split(jax.random.PRNGKey(s), 3)
    q = jax.random.normal(keys[0], (2, s, 4, 64))
    k, v = (jax.random.normal(key, (2, s, 2, 64)) for key in keys[1:])
    packed = lambda a: a.reshape(2, s, 1, 128).transpose(0, 2, 1, 3)  # noqa: E731
    got = A.flash_prefill_attention(q, packed(k), packed(v), config, interpret=True)
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), jnp.bool_)), (2, s, s))
    want = T.attention(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), mask, config)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_paged_decode_kernel_and_write_at_64_wide_heads_equal_jnp():
    """The decode step's write (`paged_kv_write`) and read (the paged decode
    kernel) over a pool of packed rows against the scatter and the gathered
    jnp read over the same pool: the pools bit-equal, the outputs to rounding."""
    config = dataclasses.replace(
        CFG, attention_impl="pallas", n_heads=8, n_kv_heads=4, head_dim=64
    )
    assert config.kv_head_pack == 2
    rows, n_pages, layers = 3, 10, 2
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 8))
    pool = jax.random.normal(next(keys), (2, layers, n_pages, 2, PAGE, 128))
    pk, pv = pool[0], pool[1]
    tables = jnp.asarray([[0, 1, 2], [10, 10, 10], [5, 6, 10]], jnp.int32)  # row 1 maps nothing
    positions = jnp.asarray([[37], [0], [17]], jnp.int32)
    q = jax.random.normal(next(keys), (rows, 1, 8, 64))
    k, v = (jax.random.normal(next(keys), (rows, 1, 4, 64)) for _ in range(2))
    k, v = (a.reshape(rows, 1, 2, 128) for a in (k, v))  # as `_qkv` hands them on
    layer = jnp.int32(1)
    got, (gk, gv) = T._paged_attention(q, k, v, (pk, pv), tables, positions, layer, PAGE, config)
    jnp_config = dataclasses.replace(config, attention_impl="jnp")
    mask = T._paged_mask(tables, PAGE, positions)
    want, (wk, wv) = T._paged_attention(
        q, k, v, (pk, pv), tables, positions, layer, PAGE, jnp_config, mask=mask
    )
    np.testing.assert_array_equal(gk, wk)
    np.testing.assert_array_equal(gv, wv)
    live = np.asarray([0, 2])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=2e-5)
    assert float(jnp.abs(got[1]).max()) == 0.0  # a row of no pages reads nothing


def test_a_config_of_64_wide_heads_packs_and_every_other_keeps_its_rows():
    assert CFG.kv_head_pack == 2
    cache = T.make_kv_cache(CFG, 3, 32)
    assert cache["k"].shape == (CFG.n_layers_of("full_attention"), 3, 1, 32, 128)
    assert CFG.kv_bytes_per_token() == 2 * 2 * 2 * 64 * 2  # layers x (k, v) x heads x 64 x bf16
    for name, preset in MODEL_PRESETS.items():
        if name != "tiny-lfm2-test":
            assert preset.kv_head_pack == 1, name
    base = dict(name="t", vocab_size=64, d_model=256, n_layers=1, n_kv_heads=2, d_ff=64)
    assert ModelConfig(**base, n_heads=4).kv_head_pack == 2
    assert ModelConfig(**base, n_heads=4, kv_cache_dtype="int8").kv_head_pack == 1  # a scale a head
    assert ModelConfig(**base, n_heads=2).kv_head_pack == 1  # heads of 128
    assert ModelConfig(**{**base, "n_kv_heads": 1}, n_heads=4).kv_head_pack == 1  # an odd head


# -- the configuration's rules -------------------------------------------------


@pytest.mark.parametrize(
    "change, says",
    [
        ({"conv_kernel": 0}, "conv_kernel >= 2"),
        ({"layer_pattern": ("conv", "linear_attention", "full_attention", "conv")}, "one tail"),
        ({"layer_pattern": ("full_attention",), "n_layers": 8}, "conv_kernel belongs"),
        ({"experts_held": ()}, "moe_d_ff is read"),
        ({"n_leading_dense": 8}, "leaves an expert layer"),
    ],
    ids=lambda v: v if isinstance(v, str) else next(iter(v)),
)
def test_what_an_lfm2_config_may_not_say_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CFG, **change)


def test_the_lfm2_state_holds_the_tails_alone_and_the_plan_counts_them():
    from langstream_tpu.serving.memory import plan_serving_memory

    rec = T.make_recurrent_state(CFG, 5)
    assert set(rec) == {"conv"} and rec["conv"].shape == (6, 5, 2 * CFG.d_model)
    olmo = T.make_recurrent_state(MODEL_PRESETS["tiny-hybrid-test"], 5)
    assert set(olmo) == {"s", "conv"}  # the delta rule's state with its tail, as ever
    bf16 = MODEL_PRESETS["tiny-lfm2-test"]
    plan = plan_serving_memory(bf16, 4, 128, quantized_weights=True, page_size=PAGE, kv_pages=32)
    assert plan.recurrent_state_bytes == 6 * 4 * 2 * bf16.d_model * 2
    assert plan.page_pool_bytes == 32 * PAGE * bf16.kv_bytes_per_token()
    assert "recurrent-state" in plan.summary()


# -- the engine ----------------------------------------------------------------


def _engine(params, **kw):
    engine = E.ServingEngine(
        CFG, params, max_batch=kw.pop("max_batch", 2), max_seq_len=256,
        prefill_buckets=(32, 64), page_size=PAGE, decode_chunk=4, precompile=False, **kw,
    )
    engine.start()
    return engine


def test_lfm2_engine_tokens_are_the_full_forward_s_and_a_reused_slot_is_clean(params):
    greedy = GenerationOptions(max_new_tokens=6, temperature=0.0)
    used, fresh = _engine(params, max_batch=1), _engine(params, max_batch=1)
    try:
        used.generate(_tokens(30, 50).tolist(), greedy, timeout=300)
        for n in (21, 100):  # a padded group; two segments that carry the tail
            prompt = _tokens(40 + n, n).tolist()
            got = list(used.generate(prompt, greedy, timeout=300).tokens)
            assert got == list(fresh.generate(prompt, greedy, timeout=300).tokens)
            logits = _forward_all(params, prompt + got)[n - 1 : -1]
            assert len(got) == 6 and [int(row.argmax()) for row in logits] == got
        stats = used.stats()
        assert stats["recurrent-state-rows-in-use"] == 0
        # a slot's state is its tails and nothing else: 6 layers x 2 x d float32
        assert stats["conv-state-bytes-per-slot"] == 6 * 2 * CFG.d_model * 4
        assert stats["recurrent-state-bytes"] == stats["conv-state-bytes-per-slot"]
    finally:
        used.stop()
        fresh.stop()


@pytest.mark.parametrize(
    "option, value",
    [
        ("prefix_cache", "auto"), ("host_kv_fraction", 1.0), ("migrate_staging", True),
        ("durable_dir", "/tmp/never-made"), ("speculation", "auto"),
        ("adapters", [{"name": "a", "rank": 2}]), ("mesh", object()), ("spmd", object()),
    ],
)
def test_an_lfm2_engine_refuses_the_option_by_name(params, option, value):
    with pytest.raises(ValueError, match=f"{option}.*recurrent state"):
        E.ServingEngine(CFG, params, max_batch=2, max_seq_len=128, **{option: value})


def test_the_lfm2_preset_is_the_benchmark_s_tiny_configuration():
    sys.path[:0] = [p for p in (str(BENCH),) if p not in sys.path]
    from modelcfg import load_json, model_config

    spec = load_json("configs", "tiny-lfm2", BENCH / "tests" / "data")
    assert model_config(spec, "tiny-lfm2-test") == MODEL_PRESETS["tiny-lfm2-test"]

"""Window and full attention layers in a parallel block with an expert layer
that holds a share (`ModelConfig.has_window`, `moe_ffn_held`; command-a-plus
is the model), at `tiny-window-moe-test`'s size in float32 on the CPU,
against the plain reference of `benchmark/reference/cohere2_moe.py`:

(i)   what the config refuses;
(ii)  the block: `forward` against the reference's full forward, on logits;
(iii) segments into both page groups, then paged decode steps, at a window of
      2 pages of 8 so that a row passes the window and recycles, beside a
      row that never does, against the same full forward;
(iv)  the shares add up: the routed parts of all 4 shares, with attention and
      the shared experts' mean counted once, are the uncut reference layer;
(v)   faults, each failing by a number a thousand times the sound reading;
(vi)  the engine end to end: greedy tokens, both groups' gauges, the spans'
      attributes, every option it refuses named;
(vii) the standing families' lowered programs, byte-equal to the parent's.
"""

import dataclasses
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions, ModelConfig
from langstream_tpu.serving import engine as E
from langstream_tpu.serving.pagepool import WindowPageGroup, window_ring_pages

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
sys.path[:0] = [p for p in (str(BENCH),) if p not in sys.path]
from modelcfg import load_module  # noqa: E402

family = load_module("families", "cohere2_moe")
ref = load_module("reference", "cohere2_moe")

TINY = dataclasses.replace(MODEL_PRESETS["tiny-window-moe-test"], dtype="float32")
UNCUT = dataclasses.replace(TINY, experts_held=())  # all 16 experts in one program
PAGE = 8
SOUND, FAULT = 2e-5, 2e-2  # a sound reading's ceiling, a fault's floor


@pytest.fixture(scope="module")
def uncut_params():
    return T.init_params(UNCUT, jax.random.PRNGKey(0))


def share_of(params, first: int, held: int):
    """The tree of the chip that holds experts first .. first + held - 1."""
    def cut(stack):
        return {
            k: v[:, first : first + held] if k in ("w_gate", "w_up", "w_down") else v
            for k, v in stack.items()
        }

    return {**params, "layers": {kind: cut(s) for kind, s in params["layers"].items()}}


@pytest.fixture(scope="module")
def params(uncut_params):
    return share_of(uncut_params, *TINY.held_experts)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(1, 500, (2, 64)), jnp.int32)


def _take(stack, at):
    return jax.tree.map(lambda a: a[at], stack)


def reference_logits(params, sequence, config: ModelConfig):
    """The reference's full forward of one sequence: [S, V]."""
    dims = family._dims_of(config)
    x = ref.embed(params, sequence)
    for index in range(config.n_layers):
        kind, at = family.place(index)
        x, _ = ref.layer(x, {kind: _take(params["layers"][kind], at)}, dims)
    return ref.unembed(params, x, dims)


def rel_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.fixture(scope="module")
def want(params, tokens):
    return jnp.stack([reference_logits(params, row, TINY) for row in tokens])


# -- (i) the config -----------------------------------------------------------


@pytest.mark.parametrize(
    "change, says",
    [
        ({"sliding_window": 0}, "sliding_window"),
        ({"layer_pattern": ("sliding_attention", "linear_attention")}, "recurrent"),
        ({"experts_held": (14, 4)}, "experts_held"),
        ({"experts_held": (0, 0)}, "experts_held"),
        # a window layer's block is the parallel one with an expert layer
        ({"n_experts": 0, "experts_held": ()}, "an expert layer"),
    ],
    ids=["no-window", "beside-recurrent", "past-the-published", "none-held", "no-experts"],
)
def test_what_the_config_refuses(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(TINY, **change)


@pytest.mark.parametrize(
    "preset, change",
    [
        ("tiny-moe-test", {"n_shared_experts": 2}),
        ("tiny-moe-test", {"moe_scoring": "sigmoid"}),
        # the sequential block reads `experts_held` (tests/test_sdar_moe.py);
        # an expert width apart from d_ff still needs the no-drop layer
        ("tiny-test", {"rope_interleaved": True}),
        ("tiny-moe-test", {"moe_d_ff": 16}),
        ("tiny-test", {"sliding_window": 16}),
        ("tiny-test", {"norm": "layer"}),
        ("tiny-hybrid-test", {"rope_interleaved": True}),
    ],
    ids=lambda v: v if isinstance(v, str) else next(iter(v)),
)
def test_what_only_the_window_block_reads_is_refused_elsewhere(preset, change):
    """No other block reads these fields: a config that set one would be
    served as if it had not."""
    with pytest.raises(ValueError, match=next(iter(change)) + ".*window layers"):
        dataclasses.replace(MODEL_PRESETS[preset], **change)


def test_the_config_says_what_it_holds():
    assert TINY.has_window and TINY.is_moe and not TINY.is_recurrent
    assert TINY.held_experts == (0, 4) and UNCUT.held_experts == (0, 16)
    assert (TINY.n_layers_of("sliding_attention"), TINY.n_layers_of("full_attention")) == (6, 2)
    assert T.moe_count_names(TINY) == T.MOE_COUNTS + ("local", "touched", "spilled")
    # mixtral keeps its one-hot dispatch and its four counts
    assert not MODEL_PRESETS["tiny-moe-test"].has_window
    assert T.moe_count_names(MODEL_PRESETS["tiny-moe-test"]) == T.MOE_COUNTS
    tree = T.init_params(TINY, jax.random.PRNGKey(0))
    counted = sum(a.size for a in jax.tree.leaves(tree)) - 9 * 64  # the norms
    assert TINY.approx_params == counted


# -- (ii) the block -----------------------------------------------------------


def test_forward_is_the_reference_s_full_forward(params, tokens, want):
    assert rel_err(T.forward(params, tokens, TINY), want) < SOUND


def test_the_kernels_in_interpret_mode_are_the_jnp_path(tokens):
    # lane-aligned widths so that the grouped product and the segment kernel
    # are taken: `pallas` forces them off the chip, in interpret mode
    wide = dataclasses.replace(TINY, d_model=128, d_ff=128, n_layers=4)
    tree = T.init_params(wide, jax.random.PRNGKey(1))
    kernels = T.forward(tree, tokens, dataclasses.replace(wide, attention_impl="pallas"))
    plain = T.forward(tree, tokens, dataclasses.replace(wide, attention_impl="jnp"))
    assert rel_err(kernels, plain) < SOUND


def test_the_quantized_tree_serves_the_same_block(params, tokens):
    from langstream_tpu.models.quant import is_quantized, quantize_params

    served = quantize_params(params, TINY)
    stack = served["layers"]["sliding_attention"]
    for key in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down"):
        assert is_quantized(stack[key]) and stack[key]["q"].dtype == jnp.int8, key
    assert stack["w_gate"]["s"].shape == (6, 4, 1, 32)  # a scale an expert's output channel
    assert not is_quantized(stack["router"]) and stack["router"].dtype == jnp.float32
    # int8 a channel at d = 64: percents, not the float32 path's 1e-6
    assert rel_err(T.forward(served, tokens, TINY), T.forward(params, tokens, TINY)) < 0.15


# -- (iii) both page groups ---------------------------------------------------


def _paged_logits(params, tokens, prompts, new: int, config=TINY, segment: int = 16):
    """Row r: ``prompts[r]`` tokens in segments of ``segment`` into both page
    groups, then ``new`` decode steps with every row in the batch: the logits
    of each row's last prompt position and of every step, [rows][1 + new, V],
    and the window group."""
    rows, width = tokens.shape
    n_pages = width // PAGE
    ring = window_ring_pages(config.sliding_window, segment, PAGE)
    group = WindowPageGroup(rows * min(ring, n_pages), PAGE, rows, n_pages,
                            config.sliding_window, ring)
    pool = T.make_page_pool(config, rows * n_pages, PAGE, window_pages=group.num_pages)
    full = np.arange(rows * n_pages, dtype=np.int32).reshape(rows, n_pages)

    def tables(of):
        return jnp.asarray(np.stack([full[of], group.tables[of]]))

    out = [[] for _ in range(rows)]
    for r, n in enumerate(prompts):
        assert group.reserve(r, -(-(n + new) // PAGE))
        for s0 in range(0, n, segment):
            part = tokens[r, s0 : min(s0 + segment, n)]
            group.advance(r, s0, s0 + segment - 1)
            logits, pool = T.paged_prefill_segment_inplace(
                params, jnp.zeros((1, segment), jnp.int32).at[0, : len(part)].set(part),
                jnp.asarray([s0]), jnp.asarray([len(part)]), pool, tables([r]), config, PAGE,
            )
        out[r].append(logits[0])
    step = jax.jit(
        lambda p, t, pos, pool, tab: T.paged_decode_step_inplace(p, t, pos, pool, tab, config, PAGE)
    )
    every = list(range(rows))
    for j in range(new):
        for r, n in enumerate(prompts):
            group.advance(r, n + j, n + j)
            assert group.validate(r)
        positions = jnp.asarray([n + j for n in prompts])
        logits, pool = step(
            params, tokens[jnp.arange(rows), positions], positions, pool, tables(every)
        )
        for r in every:
            out[r].append(logits[r])
    return [jnp.stack(o) for o in out], group


def test_segments_then_paged_decode_through_both_groups(params, tokens, want):
    prompts, new = (37, 6), 9  # row 0 passes the window of 16, row 1 never does
    got, group = _paged_logits(params, tokens, prompts, new)
    for r, n in enumerate(prompts):
        assert rel_err(got[r], want[r, n - 1 : n + new]) < SOUND, r
    # row 0: 46 positions through a ring of 5 pages; row 1: 15 positions, 2 pages
    assert group.ring == 5 and group.recycled_total == 6 - 5 + 0
    assert len(group.slot_pages(0)) == 5 and len(group.slot_pages(1)) == 2
    assert sorted(group._mapped[0]) == [3, 4, 5] and sorted(group._mapped[1]) == [0, 1]


def test_prefill_into_a_local_cache_is_the_same_forward(params, tokens, want):
    lengths = jnp.asarray([29, 11])
    logits, cache = T.prefill(params, tokens[:, :32], lengths, T.make_kv_cache(TINY, 2, 32), TINY)
    assert set(cache) == {"k", "v", "win"} and cache["win"]["k"].shape[0] == 6
    for r, n in enumerate((29, 11)):
        assert rel_err(logits[r], want[r, n - 1]) < SOUND


# -- (iv) the shares add up ---------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(uncut_params, tokens):
    """Every chip computes attention and the shared mean alike and its own
    experts' routed part: the 4 shares' outputs, with what is computed alike
    counted once, are the reference's uncut layer."""
    sequence = tokens[0]
    x = ref.embed(uncut_params, sequence)
    positions = jnp.arange(len(sequence))[None]
    sin, cos = T._rope_freqs(positions, TINY)
    whole = family._dims_of(UNCUT)
    for index in (0, 3):  # a window layer, a full layer
        kind, at = family.place(index)
        lp = _take(uncut_params["layers"][kind], at)
        uncut, _ = ref.layer(x, {kind: lp}, whole)
        shares = []
        for first in range(0, 16, 4):
            config = dataclasses.replace(TINY, experts_held=(first, 4))
            mine = _take(share_of(uncut_params, first, 4)["layers"][kind], at)
            y, _, counts = T._parallel_layer(
                x[None], mine, kind, sin, cos, config, positions, None, None, {"from_zero": True}
            )
            shares.append(y[0])
            # and the reference given the same share says what this chip says
            part, _ = ref.layer(x, {kind: mine}, family._dims_of(config))
            assert rel_err(y[0], part) < SOUND, (index, first)
        alike = _alike(x, lp, kind, whole)  # x + Attn(u) + shared mean
        assert rel_err(sum(shares) - 3 * alike, uncut) < SOUND, index
        assert rel_err(shares[0], uncut) > 0.05  # one share alone is not the layer


def _alike(x, lp, kind, dims):
    """x + Attn(u) + mean of the shared experts: the reference's layer with
    no routed expert (its moe over a router that chooses none)."""
    u = ref.layer_norm(x, lp["attn_norm"], dims["eps"])
    with jax.default_matmul_precision("highest"):
        f = lp["ws_gate"].shape[-1] // dims["n_shared"]
        shared = sum(
            ref.swiglu(u, lp["ws_gate"][:, e * f : (e + 1) * f], lp["ws_up"][:, e * f : (e + 1) * f],
                       lp["ws_down"][e * f : (e + 1) * f])
            for e in range(dims["n_shared"])
        ) / dims["n_shared"]
        return x + ref.attention(u, lp, dims, sliding=kind == "sliding_attention") + shared


def test_no_assignment_of_a_real_token_is_dropped(params):
    """Every token routed to ONE held expert (its router column raised): the
    one-hot dispatch with a capacity would drop most; here all are computed."""
    lp = _take(params["layers"]["full_attention"], 0)
    lp = {**lp, "router": lp["router"].at[:, 2].set(1.0)}
    # all positive, so that u . router[:, 2] is the largest score of every token
    u = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (2, 48, 64), jnp.float32))
    valid = jnp.arange(48)[None, :] < jnp.asarray([48, 20])[:, None]
    out, counts = T.moe_ffn_held(u, lp, TINY, valid)
    named = dict(zip(T.MOE_HELD_COUNTS, (int(c) for c in counts)))
    assert named["dropped"] == named["dropped_real"] == 0
    assert named["routed"] == 96 * 4 and named["routed_real"] == 68 * 4
    assert named["local"] >= 68  # every real token holds a row of expert 2
    want, _ = ref.moe(u[0], lp, family._dims_of(TINY))
    assert rel_err(out[0], want) < SOUND


# 4 of 64 experts held: a call of 256 tokens lays out twice its even share
# (128 assignments a pass, 9 tiles where 33 hold every case) and takes a
# further pass for what is over (`ops/grouped_matmul.pass_shape`); "pallas":
# the kernels in interpret mode at lane-aligned widths, "jnp": the einsum
PASSES = {
    impl: dataclasses.replace(TINY, n_experts=64, attention_impl=impl, **widths)
    for impl, widths in (("jnp", {}), ("pallas", {"d_model": 128, "d_ff": 256}))
}
# what the held experts' router columns are set to, the local assignments and
# the spilled ones of 2 x 128 tokens whose second row has 100 real: every
# real token's four choices on the four held experts, the router as it is
# seeded, and no token's choice on any of them
ROUTINGS = {
    "every-choice-local": (1.0, 228 * 4, 228 * 4 - 128),
    "as-seeded": (None, None, 0),
    "none-local": (-1.0, 0, 0),
}


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
@pytest.mark.parametrize("impl", sorted(PASSES))
def test_passes_compute_what_one_pass_computes(impl, routing, monkeypatch):
    """The layer in passes of twice its even share against the same layer
    over the one buffer that holds every case: the same products, nothing
    dropped, `spilled` what the first pass did not hold; where one pass holds
    every local assignment the one buffer's output to the bit (the first
    pass is summed after the loop by the one buffer's own expression), and
    under a spill a token's local assignments summed in another order."""
    from langstream_tpu.ops import grouped_matmul as gm
    from langstream_tpu.ops.attention import attention_paths

    config = PASSES[impl]
    column, local, spilled = ROUTINGS[routing]
    d = config.d_model
    lp = _take(T.init_params(config, jax.random.PRNGKey(5))["layers"]["full_attention"], 0)
    if column is not None:
        lp = {**lp, "router": lp["router"].at[:, :4].set(column)}
    # all positive, so that a raised column is every token's largest score
    u = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, 128, d), jnp.float32))
    valid = jnp.arange(128)[None, :] < jnp.asarray([128, 100])[:, None]
    out, counts = T.moe_ffn_held(u, lp, config, valid)
    assert attention_paths()["moe-dispatch[t=256,k=4,held=4/64]"] == (
        "passes of 128, 9 tiles (33 hold every case)"
    )
    named = dict(zip(T.MOE_HELD_COUNTS, (int(c) for c in counts)))
    assert named["dropped"] == named["dropped_real"] == 0
    assert named["routed_real"] == 228 * 4
    assert named["spilled"] == max(named["local"] - 128, 0) == spilled
    if local is not None:
        assert (named["local"], named["touched"]) == (local, 4 if local else 0)

    monkeypatch.setattr(gm, "pass_shape", lambda *a: None)
    whole, counts_whole = T.moe_ffn_held(u, lp, config, valid)
    assert attention_paths()["moe-dispatch[t=256,k=4,held=4/64]"] == "one pass, 33 tiles"
    assert [int(c) for c in counts_whole] == [*(int(c) for c in counts[:6]), 0]
    if named["spilled"]:  # a float32 sum regrouped: a rounding of the sum, no more
        assert float(jnp.abs(out - whole).max()) <= 4e-7 * float(jnp.abs(whole).max())
    else:
        assert bool(jnp.array_equal(out, whole))
    if routing == "none-local":  # no pass at all: the shared experts' mean alone
        xf = u.reshape(256, d)
        gate = T._activation(T.quantized_matmul(xf, lp["ws_gate"]), config.activation)
        shared = T.quantized_matmul(gate * T.quantized_matmul(xf, lp["ws_up"]), lp["ws_down"])
        assert bool(jnp.array_equal(out.reshape(256, d), shared * (1.0 / config.n_shared_experts)))
        assert bool(jnp.array_equal(out, whole))
    else:
        assert float(jnp.abs(out).max()) > 0.1


# -- (v) faults: each fails by a number ---------------------------------------


def _parallel_layer_with(change):
    """The layer under another kind or window: neither is read outside its
    attention (the norm, the router and the experts read neither)."""
    sound = T._parallel_layer

    def faulted(x, lp, kind, sin, cos, config, *rest):
        kind, config = change(kind, config)
        return sound(x, lp, kind, sin, cos, config, *rest)

    return faulted


def window_mask_off(monkeypatch):
    monkeypatch.setattr(T, "_parallel_layer", _parallel_layer_with(
        lambda kind, config: (kind, dataclasses.replace(config, sliding_window=1 << 20))
    ))


def rotary_on_a_full_layer(monkeypatch):
    # a full layer run as a window layer whose window holds everything
    monkeypatch.setattr(T, "_parallel_layer", _parallel_layer_with(
        lambda kind, config: ("sliding_attention", config) if kind == "sliding_attention"
        else ("sliding_attention", dataclasses.replace(config, sliding_window=1 << 20))
    ))


def half_split_pairs(monkeypatch):
    monkeypatch.setattr(T, "apply_rope_interleaved", T.apply_rope)


def shared_experts_summed(monkeypatch):
    sound = T.moe_ffn_held
    monkeypatch.setattr(T, "moe_ffn_held", lambda x, lp, config, *rest, **kw: sound(
        x, lp, dataclasses.replace(config, n_shared_experts=1), *rest, **kw
    ))


def _route_with(change):
    sound = T._route_all

    def faulted(xf, router, config):
        weights, chosen = sound(xf, router, config)
        first, held = config.held_experts
        local = (chosen >= first) & (chosen < first + held)
        return change(weights, chosen, local, first, held)

    return faulted


def an_absent_expert_included(monkeypatch):
    # what was routed to an absent expert is computed by a held one
    monkeypatch.setattr(T, "_route_all", _route_with(
        lambda w, chosen, local, first, held: (w, first + (chosen - first) % held)
    ))


def weights_normalised_over_the_held(monkeypatch):
    def change(w, chosen, local, first, held):
        kept = jnp.where(local, w, 0.0)
        return kept / jnp.maximum(kept.sum(-1, keepdims=True), 1e-30), chosen

    monkeypatch.setattr(T, "_route_all", _route_with(change))


def a_held_expert_skipped(tree):
    """Held expert 1's down projection zeroed in every layer."""
    def skip(stack):
        return {**stack, "w_down": stack["w_down"].at[:, 1].set(0.0)}

    return {**tree, "layers": {kind: skip(s) for kind, s in tree["layers"].items()}}


FAULTS = [window_mask_off, rotary_on_a_full_layer, half_split_pairs, shared_experts_summed,
          an_absent_expert_included, weights_normalised_over_the_held, a_held_expert_skipped]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_a_fault_fails_by_a_number(fault, params, tokens, want, monkeypatch):
    served = params
    if fault is a_held_expert_skipped:
        served = fault(params)
    else:
        fault(monkeypatch)
    # a name a fault: the jitted programs are cached by their (static) config
    config = dataclasses.replace(TINY, name=f"{TINY.name}-{fault.__name__}")
    assert rel_err(T.forward(served, tokens, config), want) > FAULT
    got, _ = _paged_logits(served, tokens[:1], (37,), 9, config)
    assert rel_err(got[0], want[0, 36:46]) > FAULT


def test_a_wrong_lower_bound_in_the_decode_kernel_fails_by_a_number(monkeypatch):
    """The paged decode kernel in interpret mode, its ``lower`` dropped: a
    window row reads from page 0, where its table maps nothing it may see."""
    from langstream_tpu.ops import attention as ops

    config = dataclasses.replace(TINY, head_dim=128, attention_impl="pallas")
    b, pages, hkv, d, tp = 2, 12, config.n_kv_heads, 128, 6
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    k = jax.random.normal(keys[0], (1, pages, hkv, PAGE, d), jnp.float32)
    v = jax.random.normal(keys[1], (1, pages, hkv, PAGE, d), jnp.float32)
    q = jax.random.normal(keys[2], (b, config.n_heads, d), jnp.float32)
    table = jnp.arange(pages, dtype=jnp.int32).reshape(b, tp)
    lengths, lower = jnp.asarray([44, 9]), jnp.asarray([28, 0])

    def plain(lower):
        k_all = k[0, table].transpose(0, 2, 1, 3, 4).reshape(b, hkv, tp * PAGE, d)
        v_all = v[0, table].transpose(0, 2, 1, 3, 4).reshape(b, hkv, tp * PAGE, d)
        at = jnp.arange(tp * PAGE)[None, None, :]
        seen = (at < lengths[:, None, None]) & (at >= lower[:, None, None])
        return T.attention(q[:, None].reshape(b, 1, config.n_heads, d), k_all, v_all, seen,
                           config)[:, 0]

    def kernel(**kw):
        return ops.ragged_paged_decode_attention(
            q, k, v, lengths, table, jnp.int32(0), config, PAGE, interpret=True, **kw
        )

    assert rel_err(kernel(lower=lower), plain(lower)) < SOUND
    assert rel_err(kernel(), plain(jnp.zeros(2, jnp.int32))) < SOUND
    assert rel_err(kernel(), plain(lower)) > FAULT
    # the pages behind the lower bound need not be mapped: the walk skips them
    unmapped = table.at[0, :3].set(pages)
    assert rel_err(
        ops.ragged_paged_decode_attention(
            q, k, v, lengths, unmapped, jnp.int32(0), config, PAGE, interpret=True, lower=lower
        ),
        plain(lower),
    ) < SOUND


# -- (vi) the engine ----------------------------------------------------------


@pytest.fixture(scope="module")
def engine(params):
    eng = E.ServingEngine(
        TINY, params, max_batch=2, max_seq_len=160, prefill_buckets=(16, 32), page_size=PAGE,
        decode_chunk=4, precompile=False,
    )
    eng.start()
    yield eng
    eng.stop()


def test_the_engine_serves_it_end_to_end(engine, params):
    from langstream_tpu.tracing import TRACER

    group = engine._pagepool.window
    # a ring: the window's 16 tokens and the widest dispatch's 32, 7 pages a row
    assert (group.window, group.ring, group.num_pages) == (16, 7, 14)
    rng = np.random.default_rng(1)
    greedy = GenerationOptions(max_new_tokens=20, temperature=0.0)
    TRACER.clear()
    for n in (20, 100, 70):  # one group, four segments, three segments
        prompt = rng.integers(1, 500, n).tolist()
        got = list(engine.generate(prompt, greedy, timeout=600).tokens)
        # greedy: each token is the forward's argmax over the sequence before it
        logits = T.forward(params, jnp.asarray([prompt + got], jnp.int32), TINY)[0]
        assert len(got) == 20 and got == jnp.argmax(logits[n - 1 : -1], axis=-1).tolist(), n
    stats = engine.stats()
    assert stats["kv-window-pages-total"] == 14 and stats["kv-window-pages-in-use"] == 0
    assert 0 < stats["kv-window-pages-peak"] <= 7 and stats["kv-pages-in-use"] == 0
    assert stats["kv-window-pages-recycled-total"] > 0
    assert stats["moe-dropped-assignments-total"] == 0 < stats["moe-routed-assignments-total"]
    assert all(engine._pagepool.validate(slot) for slot in range(2))
    spans = TRACER.spans(4096)
    segments = [s["attributes"] for s in spans if s["name"] == "engine.prefill_segment"]
    chunks = [s["attributes"] for s in spans if s["name"] == "engine.decode_chunk"]
    assert len(segments) == 4 + 3 and chunks
    for attrs in segments + chunks:
        assert attrs["moe_dropped"] == 0 and 0 <= attrs["moe_local"] <= attrs["moe_routed_real"]
        assert {"kv_tokens_read", "kv_tokens_read_window", "window_pages_recycled",
                "moe_touched", "device_ms"} <= set(attrs)
        assert attrs["moe_spilled"] == 0  # segments of 32 tokens, steps of 2 rows: one pass
    first = next(a for a in segments if a["offset"] == 0)
    assert first["kv_tokens_read"] == 32 * 33 // 2  # query i reads i + 1 columns
    assert first["kv_tokens_read_window"] == 16 * 17 // 2 + 16 * 16  # at most the window's 16
    later = next(a for a in segments if a["offset"] == 64)
    assert later["real_tokens"] == 32 and later["computed_tokens"] == 32
    assert later["kv_tokens_read"] == sum(range(65, 97)) and later["kv_tokens_read_window"] == 32 * 16
    assert sum(a["window_pages_recycled"] for a in segments + chunks) == (
        stats["kv-window-pages-recycled-total"]
    )
    past = [a for a in chunks if a["kv_tokens_read"] > 16 * a["steps"]]
    assert past and all(a["kv_tokens_read_window"] == 16 * a["steps"] for a in past)


@pytest.mark.parametrize(
    "option",
    [
        {"prefix_cache": True}, {"host_kv_fraction": 0.5}, {"migrate_staging": True},
        {"durable_dir": "/tmp/never-made"}, {"speculation": True},
    ],
    ids=lambda o: next(iter(o)),
)
def test_an_option_that_cannot_carry_two_page_groups_is_refused_by_name(option, params):
    with pytest.raises(ValueError, match=f"window layers: .*{next(iter(option))}"):
        E.ServingEngine(
            TINY, params, max_batch=2, max_seq_len=64, prefill_buckets=(16,), page_size=PAGE,
            precompile=False, **option,
        )


def test_an_int8_kv_cache_is_refused_by_name(params):
    with pytest.raises(ValueError, match="window layers: .*kv_cache_dtype"):
        E.ServingEngine(
            dataclasses.replace(TINY, kv_cache_dtype="int8"), params, max_batch=2,
            max_seq_len=64, prefill_buckets=(16,), page_size=PAGE, precompile=False,
        )


def test_the_memory_plan_sizes_both_groups():
    from langstream_tpu.serving.memory import plan_serving_memory

    plan = plan_serving_memory(
        TINY, max_batch=4, max_seq_len=256, page_size=PAGE, kv_pages=128, window_in_flight=32,
    )
    per_token = 2 * TINY.n_kv_heads * TINY.resolved_head_dim * 4  # K and V, float32
    assert plan.page_pool_bytes == 2 * 128 * PAGE * per_token  # 2 full layers
    # 6 window layers x 4 rows x a ring of 7 pages (window 16 + 32 in flight)
    assert plan.window_pool_bytes == 6 * 4 * 7 * PAGE * per_token
    assert "window-pool" in plan.summary()
    dense = plan_serving_memory(
        MODEL_PRESETS["tiny-test"], max_batch=4, max_seq_len=256, page_size=PAGE, kv_pages=128,
    )
    assert dense.window_pool_bytes == 0 and "window-pool" not in dense.summary()


# -- (vii) the standing families, as they were at the parent commit -----------

# taken at commit 07ce48e (PR 33) by the code below; tiny-test and
# tiny-moe-test are held by tests/test_olmo_hybrid.py, with their trees
AT_PARENT = {
    "tiny-hybrid-test": {
        "jit__paged_decode_chunk": "8a43a852b3b2abbe",
        "jit__paged_segment_and_sample": "402c4d4420f80e88",
        "jit_admit_group": "7bbd43fc66178f4c",
    },
}


def _lowered(name: str) -> dict:
    config = MODEL_PRESETS[name]
    b, pages, table = 4, 16, 4
    sds = jax.ShapeDtypeStruct
    i32, f32 = (lambda *s: sds(s, jnp.int32)), (lambda *s: sds(s, jnp.float32))
    key = sds((2,), jnp.uint32)
    pool = jax.eval_shape(lambda: T.make_page_pool(config, pages, PAGE, state_rows=b))
    shapes = jax.eval_shape(lambda k: T.init_params(config, k), key)
    lowered = {
        "jit__paged_decode_chunk": E._paged_decode_chunk.lower(
            shapes, i32(b), i32(b), pool, i32(b, table), key, f32(b), i32(b), f32(b), 4,
            config, PAGE,
        ),
        "jit__paged_segment_and_sample": E._paged_segment_and_sample.lower(
            shapes, i32(1, 32), i32(1), i32(1), pool, i32(1, table), key, f32(1), i32(1),
            f32(1), config, PAGE, state_rows=i32(1),
        ),
        "jit_admit_group": E._make_paged_admit_group().lower(
            shapes, pool, i32(b), i32(b), f32(b), i32(b), f32(b), key, i32(2, 32), f32(4, 2),
            i32(2), i32(2, table), config, PAGE,
        ),
    }
    return {
        program: hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
        for program, low in lowered.items()
    }


@pytest.mark.parametrize("name", sorted(AT_PARENT))
def test_the_recurrent_family_s_programs_are_what_they_were(name):
    assert _lowered(name) == AT_PARENT[name]

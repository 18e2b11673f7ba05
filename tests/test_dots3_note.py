"""Two KINDS of latent layer in one model (`ModelConfig.latent_kinds`,
`window_attention`, `latent_rescale`, `attn_gate`; dots3-note-prev is the
model), at `tiny-dots3-test`'s size in float32 on the CPU, against the plain
reference of `benchmark/reference/dots3_note.py`:

(i)   what the config refuses, by name, and that the standing presets are the
      `ModelConfig`s they were;
(ii)  each kind's layer and the whole `forward` against the reference;
(iii) segments into both page groups, then paged decode steps, logits against
      the same full forward: rows under the window, between the window and
      the top-k and past both, one that recycles its ring more than once;
      the admit group's `prefill` into a local cache and its insert;
(iv)  the kernels in interpret mode are the jnp path;
(v)   the parts of an expert layer that all shares give add up to the uncut
      reference's layer, the shared expert counted once;
(vi)  faults, each failing by a number;
(vii) the engine end to end, its gauges and spans, every option it refuses;
      the memory plan counts both groups at their own widths.
"""

import dataclasses
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
from langstream_tpu.serving.pagepool import WindowPageGroup, window_ring_pages

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
sys.path[:0] = [p for p in (str(BENCH),) if p not in sys.path]
from modelcfg import load_module  # noqa: E402

family = load_module("families", "dots3_note")
ref = load_module("reference", "dots3_note")

TINY = dataclasses.replace(MODEL_PRESETS["tiny-dots3-test"], dtype="float32")
UNCUT = dataclasses.replace(TINY, experts_held=(0, TINY.n_experts))
FULL, WINDOW = "full_attention", "sliding_attention"
PAGE = 8
SOUND, FAULT = 5e-5, 5e-3  # a sound reading's ceiling, a fault's floor


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
    return jnp.asarray(np.random.default_rng(0).integers(1, 500, (4, 96)), jnp.int32)


def rel_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def reference_logits(params, sequence, config: ModelConfig = TINY):
    return ref.forward(params, sequence, family.dims_of(config))


@pytest.fixture(scope="module")
def want(params, tokens):
    return jnp.stack([reference_logits(params, row) for row in tokens])


# -- (i) the config -----------------------------------------------------------


@pytest.mark.parametrize(
    "change, says",
    [
        ({"sliding_window": 0}, "sliding_window >= 1"),
        ({"layer_pattern": (FULL, WINDOW, "linear_attention"), "n_layers": 7}, "recurrent"),
        ({"layer_pattern": (FULL,), "n_layers": 3, "sliding_window": 0, "window_attention": ()},
         "a latent .* a layer pattern, a window or a recurrent"),
        ({"n_layers": 10}, "layer_pattern"),
        ({"window_attention": (("d_ff", 3),)}, "fields outside"),
        ({"window_attention": (("qk_rope_head_dim", 7),)}, "odd qk_rope_head_dim"),
        ({"window_attention": (("kv_lora_rank", 0),)}, "under 1"),
        ({"attn_gate": "elementwise"}, "attn_gate"),
        ({"norm": "layer"}, "a norm other than rms"),
        ({"kv_cache_dtype": "int8"}, "an int8 KV cache"),
        ({"mrope_section": (1, 1, 2)}, "m-rope"),
        ({"experts_held": (6, 4)}, "experts_held"),
        ({"n_leading_dense": 0, "n_layers": 8, "experts_held": ()}, "moe_d_ff"),
    ],
    ids=[
        "no-window", "beside-recurrent", "pattern-of-full-alone", "not-whole-periods",
        "a-field-no-kind-has", "odd-rotary", "no-latent-rank", "another-gate", "layer-norm",
        "int8-cache", "m-rope", "past-the-published", "no-share-held",
    ],
)
def test_what_the_config_refuses(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(TINY, **change)


@pytest.mark.parametrize(
    "preset, change, says",
    [
        ("tiny-test", {"latent_rescale": True}, "no latent"),
        ("tiny-test", {"attn_gate": "headwise"}, "no latent"),
        ("tiny-latent-moe-test", {"window_attention": (("n_heads", 2),)}, "no window layers"),
        ("tiny-window-moe-test", {"window_attention": (("n_heads", 2),)}, "no window layers"),
        ("tiny-window-moe-test", {"index_topk": 4, "index_n_heads": 2, "index_head_dim": 8},
         "an indexer .* a layer pattern, a window or a recurrent"),
        ("tiny-lfm2-test", {"n_layers": 10}, "layer_pattern"),
    ],
    ids=["rescale-no-latent", "gate-no-latent", "kind-fields-no-window", "kind-fields-over-kv",
         "indexer-over-kv-windows", "lfm2-not-whole-periods"],
)
def test_what_belongs_to_latent_kinds_is_refused_elsewhere(preset, change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(MODEL_PRESETS[preset], **change)


def test_the_config_answers_by_kind():
    window = TINY.of_kind(WINDOW)
    assert TINY.of_kind(FULL) is TINY and window is TINY.of_kind(WINDOW)
    assert TINY.latent_kinds and not TINY.parallel_block and TINY.holds_experts
    assert (TINY.dense_ahead, TINY.n_periods) == (1, 2)
    assert (TINY.n_layers_of(FULL), TINY.n_layers_of(WINDOW), TINY.dense_of(FULL)) == (3, 6, 1)
    assert (TINY.n_heads, window.n_heads, window.n_kv_heads) == (4, 2, 2)
    assert (TINY.latent_width, window.latent_width) == (24, 40)
    assert (TINY.latent_key_width, window.latent_key_width) == (128, 128)
    assert (TINY.rope_theta, window.rope_theta) == (1e6, 1e3)
    assert TINY.attn_scale == 16**-0.5 and window.attn_scale == 32**-0.5
    assert TINY.page_leaves == ("lat", "ik") and window.page_leaves == ("lat",)
    assert window.has_latent and not window.has_indexer
    assert (TINY.attn_window, window.attn_window) == (0, 9)
    # a token: 3 full layers of (128 + 128) x 2 B, 6 window layers of 128 x 2 B
    assert TINY.kv_bytes_per_token() == 3 * 256 * 2
    assert TINY.kv_bytes_per_token(kind=WINDOW) == 6 * 128 * 2


@pytest.mark.parametrize(
    "preset, flags, leaves, token_bytes, layers",
    [
        ("tiny-window-moe-test", (True, False, True), ("k", "v"), None, None),
        ("tiny-latent-moe-test", (False, False, False), ("lat", "ik"), 4 * 256 * 2, (4, 0)),
        ("tiny-latent-dense-moe-test", (False, False, False), ("lat",), 4 * 128 * 2, (4, 0)),
        ("tiny-lfm2-test", (False, False, False), ("k", "v"), None, (2, 0)),
    ],
)
def test_the_standing_presets_are_the_configs_they_were(preset, flags, leaves, token_bytes, layers):
    config = MODEL_PRESETS[preset]
    assert (config.has_window, config.latent_kinds, config.parallel_block) == flags
    assert config.page_leaves == leaves and config.dense_ahead == 0 and not config.kind_view
    assert all(config.of_kind(kind) is config for kind in (FULL, WINDOW))
    assert config.window_attention == () and not config.latent_rescale and not config.attn_gate
    if token_bytes is not None:
        assert config.kv_bytes_per_token() == token_bytes
    if layers is not None:
        assert (config.n_layers_of(FULL), config.n_layers_of(WINDOW)) == layers
    # what a config is, field for field, is what building it anew gives
    fields = {f.name: getattr(config, f.name) for f in dataclasses.fields(config)}
    assert ModelConfig(**fields) == config


# -- (ii) the block -------------------------------------------------------------


@pytest.mark.parametrize("place", [0, 1, 2], ids=["full-dense", "full", "window"])
def test_a_layer_of_each_kind_is_the_reference_s(params, tokens, place):
    stack, kind, at, name = family._places(TINY)[place]
    lp = jax.tree.map(lambda a: a[at], params[stack][kind])
    x = T._embed(params, tokens[:1, :40], TINY) * 8.0  # (a residual of the layers' size)
    of, positions = TINY.of_kind(kind), jnp.arange(40)[None]
    mask = jnp.tril(jnp.ones((40, 40), jnp.bool_))[None]
    with jax.default_matmul_precision("highest"):
        got, _, _ = T._layer_counted(
            x, {**lp, **{k: params[stack][kind][k] for k in T._HELD_EXPERTS}} if stack == "layers"
            else lp, *T._rope_freqs(positions, of), mask, of,
            dense=stack == "dense_layers", moe_layer=jnp.int32(at),
        )
    forced, info = ref.layer(x[0], {name: lp}, family.dims_of(TINY))
    assert rel_err(got[0], forced) < SOUND
    assert ("selected" in info) == (kind == FULL)


def test_forward_is_the_reference_s_full_forward(params, tokens, want):
    with jax.default_matmul_precision("highest"):
        assert rel_err(T.forward(params, tokens, TINY), want) < SOUND


def test_the_quantized_tree_serves_the_same_block(params, tokens):
    from langstream_tpu.models.quant import is_quantized, quantize_params

    served = quantize_params(params, TINY)
    for kind in (FULL, WINDOW):
        stack = served["layers"][kind]
        assert all(is_quantized(stack[k]) for k in ("wq_a", "wkv_b", "wo", "w_gate", "ws_up"))
        assert not is_quantized(stack["router"]) and stack["router"].dtype == jnp.float32
        assert is_quantized(stack["w_attn_gate"])
    # against the reference on the SAME int8 tree, dequantised there
    with jax.default_matmul_precision("highest"):
        got = T.forward(served, tokens[:2], TINY)
    for r in range(2):
        assert rel_err(got[r], reference_logits(served, tokens[r])) < 2 * SOUND


# -- (iii) both page groups ---------------------------------------------------


def _paged_logits(params, tokens, prompts, new: int, config=TINY, segment: int = 16):
    """Row r: ``prompts[r]`` tokens in segments of ``segment`` into both page
    groups, then ``new`` decode steps with every row in the batch: the logits
    of each row's last prompt position and of every step, [rows][1 + new, V],
    and the window group."""
    rows, width = len(prompts), tokens.shape[1]
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
    return [jnp.stack(o) for o in out], group, pool


def test_segments_then_paged_decode_through_both_groups(params, tokens, want):
    # under the window of 9; between it and the top-k of 16; past both in three
    # segments; past both in six, 12 pages through a ring of 4: turned over twice
    prompts, new = (5, 12, 37, 84), 9
    with jax.default_matmul_precision("highest"):
        got, group, pool = _paged_logits(params, tokens, prompts, new)
    for r, n in enumerate(prompts):
        assert rel_err(got[r], want[r, n - 1 : n + new]) < SOUND, r
    assert set(pool) == {"lat", "ik", "win"} and set(pool["win"]) == {"lat"}
    assert pool["lat"].shape[0] == 3 and pool["win"]["lat"].shape[0] == 6
    # rows 2 and 3: 6 and 12 pages through a ring of 4, what lies behind recycled
    assert group.ring == 4 and group.recycled_total == (6 - 4) + (12 - 4)
    assert len(group.slot_pages(3)) == 4 and len(group.slot_pages(0)) == 2


def test_prefill_into_a_local_cache_and_its_insert(params, tokens, want):
    lengths = jnp.asarray([29, 11])
    with jax.default_matmul_precision("highest"):
        logits, cache = T.prefill(
            params, tokens[:2, :32], lengths, T.make_kv_cache(TINY, 2, 32), TINY
        )
    assert set(cache) == {"lat", "ik", "win"} and cache["win"]["lat"].shape[:2] == (6, 2)
    for r, n in enumerate((29, 11)):
        assert rel_err(logits[r], want[r, n - 1]) < SOUND
    # into a pool whose window group maps every page, then a decode step
    n_pages = 64 // PAGE
    pool = T.make_page_pool(TINY, 2 * n_pages, PAGE)
    table = jnp.arange(2 * n_pages, dtype=jnp.int32).reshape(2, n_pages)
    tables = jnp.stack([table, table])
    pool = T.paged_insert_cache(pool, cache, tables, PAGE, TINY)
    with jax.default_matmul_precision("highest"):
        step, _ = T.paged_decode_step_inplace(
            params, tokens[jnp.arange(2), lengths], lengths, pool, tables, TINY, PAGE
        )
    for r, n in enumerate((29, 11)):
        assert rel_err(step[r], want[r, n]) < SOUND


# -- (iv) the kernels ---------------------------------------------------------


def test_the_kernels_in_interpret_mode_are_the_jnp_path(params, tokens, want):
    forced = dataclasses.replace(TINY, attention_impl="pallas")
    with jax.default_matmul_precision("highest"):
        got, _, _ = _paged_logits(params, tokens, (37, 52), 3, config=forced)
    for r, n in enumerate((37, 52)):
        assert rel_err(got[r], want[r, n - 1 : n + 3]) < SOUND, r
    paths = A.attention_paths()
    assert paths["paged-decode-latent-window[s=1,t=96]"] == "ragged_paged_latent_attention"
    assert paths["paged-decode-latent[s=1,t=96]"] == "ragged_paged_latent_attention"
    band = T.latent_window_band(16, 96, TINY.sliding_window, PAGE)
    assert band == 32
    assert paths[f"paged-segment-latent-window[s=16,t={band}]"] == "flash_segment_attention"
    assert paths["paged-segment-latent-sparse[s=16,t=96]"] == "sparse_segment_attention"


@pytest.mark.parametrize(
    "s, t, window, page, band",
    [(2048, 17408, 513, 64, 3072), (16, 64, 9, 8, 32), (16, 24, 9, 8, 24), (64, 4096, 513, 64, 1024)],
)
def test_the_band_a_window_segment_gathers(s, t, window, page, band):
    assert T.latent_window_band(s, t, window, page) == band
    assert band >= min(s + window - 1, t) and band % page == 0


# -- (v) the shares add up ----------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer(uncut_params, tokens):
    """Layer 1 (a full layer with experts) on one sequence: the routed parts
    of both shares (the shared expert left out of each) plus the shared
    expert once are the uncut reference's expert half."""
    dims = family.dims_of(UNCUT)
    stack = uncut_params["layers"][FULL]
    lp = jax.tree.map(lambda a: a[0], stack)
    x = T._embed(uncut_params, tokens[:1, :40], TINY)[0] * 8.0
    half = {k: lp[k] for k in family.EXPERT_HALF}
    whole, _ = ref.layer(x, {"full": half}, dims)
    total = jnp.zeros_like(x)
    for first in (0, 4):
        held = dataclasses.replace(TINY, experts_held=(first, 4))
        share = {**lp, **{k: stack[k][:, first : first + 4] for k in T._HELD_EXPERTS}}
        with jax.default_matmul_precision("highest"):
            out, _ = T.moe_ffn_held(
                T.rms_norm(x[None], lp["ffn_norm"], TINY.rms_norm_eps), share, held, None,
                jnp.int32(0),
            )
        only_shared, _ = ref.moe(
            ref.rms_norm(x, lp["ffn_norm"], dims["eps"]),
            {**half, **{k: half[k][:0] for k in T._HELD_EXPERTS}},
            {**dims, "experts_held": (0, 0)},
        )
        total = total + out[0] - only_shared
    total = total + only_shared
    assert rel_err(x + total, whole) < SOUND


# -- (vi) faults --------------------------------------------------------------


def _fault(monkeypatch, name):
    """One wrong line of the program, each a fault of dev/check_faults.py dots3."""
    if name == "no-gate":
        monkeypatch.setattr(T, "_head_gate", lambda attn, u, lp, config: attn)
    elif name == "no-rescale":
        monkeypatch.setattr(T, "_rescaled", lambda c, ratio: c)
    elif name == "window-8":
        return dataclasses.replace(TINY, sliding_window=8)
    elif name == "window-10":
        return dataclasses.replace(TINY, sliding_window=10)
    elif name == "one-rotary-base":
        own = tuple(kv for kv in TINY.window_attention if kv[0] != "rope_theta")
        return dataclasses.replace(TINY, window_attention=own)
    return TINY


def test_a_window_latent_of_8_bits_fails_by_a_number(params, tokens, want, monkeypatch):
    """No level of the chip's check sees it (`dev/check_faults.py dots3`
    `winlat8`: a token's 8 bits under one scale are bf16's precision): in
    float32 it reads a hundred times the sound path, through the ring."""
    kept = T._kept_width

    def eight_bits(row, leaf):
        if row.shape[-1] == TINY.of_kind(WINDOW).latent_width:
            scale = jnp.maximum(jnp.max(jnp.abs(row), axis=-1, keepdims=True), 1e-8) / 127
            row = jnp.round(row / scale) * scale
        return kept(row, leaf)

    monkeypatch.setattr(T, "_kept_width", eight_bits)
    with jax.default_matmul_precision("highest"):
        got, _, _ = _paged_logits(params, tokens, (37,), 4)
    assert rel_err(got[0], want[0, 36 : 37 + 4]) > FAULT


@pytest.mark.parametrize(
    "fault", ["no-gate", "no-rescale", "window-8", "window-10", "one-rotary-base"]
)
def test_a_fault_fails_by_a_number(fault, params, tokens, want, monkeypatch):
    config = _fault(monkeypatch, fault)
    jax.clear_caches()
    got = T.forward(params, tokens[:1], config)
    jax.clear_caches()
    assert rel_err(got, want[:1]) > FAULT


# -- (vii) the engine ---------------------------------------------------------


@pytest.fixture(scope="module")
def engine(params):
    eng = E.ServingEngine(
        TINY, params, max_batch=2, max_seq_len=96, prefill_buckets=(16,), page_size=PAGE,
        decode_chunk=4, precompile=False,
    )
    eng.start()
    yield eng
    eng.stop()


def test_the_engine_serves_it_end_to_end(engine, params):
    from langstream_tpu.tracing import TRACER

    group = engine._pagepool.window
    # a ring: the window's 9 tokens and the widest dispatch's 16, 4 pages a row
    assert (group.window, group.ring, group.num_pages) == (9, 4, 8)
    pool = engine._pagepool.dev
    assert set(pool) == {"lat", "ik", "win"} and set(pool["win"]) == {"lat"}
    rng = np.random.default_rng(1)
    greedy = GenerationOptions(max_new_tokens=10, temperature=0.0)
    TRACER.clear()
    for n in (7, 70, 40):  # one group, five segments, three segments
        prompt = rng.integers(1, 500, n).tolist()
        got = list(engine.generate(prompt, greedy, timeout=600).tokens)
        # greedy: each token is the forward's argmax over the sequence before it
        logits = T.forward(params, jnp.asarray([prompt + got], jnp.int32), TINY)[0]
        assert len(got) == 10 and got == jnp.argmax(logits[n - 1 : -1], axis=-1).tolist(), n
    stats = engine.stats()
    assert stats["kv-window-pages-total"] == 8 and stats["kv-window-pages-in-use"] == 0
    assert 0 < stats["kv-window-pages-peak"] <= 4 and stats["kv-pages-in-use"] == 0
    assert stats["kv-window-pages-recycled-total"] > 0
    assert stats["kv-tokens-selected-total"] < stats["index-tokens-scored-total"]
    assert stats["kv-bytes-per-token"] == TINY.kv_bytes_per_token(itemsize=4) == 3072
    assert stats["moe-dropped-assignments-total"] == 0 < stats["moe-routed-assignments-total"]
    assert all(engine._pagepool.validate(slot) for slot in range(2))
    spans = TRACER.spans(4096)
    segments = [s["attributes"] for s in spans if s["name"] == "engine.prefill_segment"]
    chunks = [s["attributes"] for s in spans if s["name"] == "engine.decode_chunk"]
    assert len(segments) == 5 + 3 and chunks
    for attrs in segments:
        assert {"kv_tokens_read_window", "latent_expanded_window", "latent_columns_expanded",
                "kv_tokens_selected", "index_tokens_scored"} <= set(attrs)
        assert attrs["latent_expanded_window"] == T.latent_window_band(16, 96, 9, PAGE) == 32
    later = next(a for a in segments if a["offset"] == 32)
    # a layer: the full kind's queries see 33..48 columns and keep 16, the window kind's 9
    assert later["index_tokens_scored"] == sum(range(33, 49))
    assert later["kv_tokens_selected"] == 16 * 16 and later["kv_tokens_read_window"] == 16 * 9
    past = [a for a in chunks if a["index_tokens_scored"] > 16 * a["steps"]]
    assert past and all(a["kv_tokens_read_window"] == 9 * a["steps"] for a in past)
    assert all(a["kv_tokens_selected"] == 16 * a["steps"] for a in past)


@pytest.mark.parametrize(
    "option",
    [{"prefix_cache": True}, {"host_kv_fraction": 0.5}, {"speculation": True},
     {"durable_dir": "/tmp/x"}, {"migrate_staging": True}],
    ids=lambda o: next(iter(o)),
)
def test_an_option_that_cannot_carry_two_page_groups_is_refused_by_name(option, params):
    with pytest.raises(ValueError, match=next(iter(option))):
        E.ServingEngine(
            TINY, params, max_batch=2, max_seq_len=64, page_size=PAGE, prefill_buckets=(16,),
            precompile=False, **option,
        )


def test_the_memory_plan_counts_both_groups_at_their_own_widths():
    from langstream_tpu.serving.memory import plan_serving_memory
    from langstream_tpu.serving.pagepool import window_group_pages

    config = MODEL_PRESETS["tiny-dots3-test"]
    plan = plan_serving_memory(
        config, max_batch=2, max_seq_len=64, page_size=PAGE, kv_pages=16, window_in_flight=16,
    )
    pages, ring = window_group_pages(config, 2, 64, PAGE, 16)
    assert (pages, ring) == (8, 4)
    assert plan.page_pool_bytes == 16 * PAGE * config.kv_bytes_per_token()
    assert plan.window_pool_bytes == pages * PAGE * config.kv_bytes_per_token(kind=WINDOW)

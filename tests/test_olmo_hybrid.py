"""A model with a layer pattern (Olmo-Hybrid: gated delta-rule layers with a
recurrent state a slot beside the page pool, full-attention layers on it) at
a tiny size, seeded, on the CPU: the program against the benchmark's plain
reference, the chunked delta rule against the token-by-token one, prefill
and decode through pages and state against the full forward, slot reuse,
the options a recurrent state refuses, and the two standing families held
to what they were at the parent commit."""

import dataclasses
import functools
import hashlib
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
from langstream_tpu.ops import gated_delta as gd
from langstream_tpu.serving import engine as E

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
CFG = dataclasses.replace(MODEL_PRESETS["tiny-hybrid-test"], dtype="float32")
PAGE = 16


def _reference():
    spec = importlib.util.spec_from_file_location(
        "olmo_hybrid_reference", BENCH / "reference" / "olmo_hybrid.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()
DIMS = {
    "n_heads": CFG.n_heads, "n_kv_heads": CFG.n_kv_heads, "head_dim": CFG.resolved_head_dim,
    "linear_heads": CFG.linear_n_heads, "linear_key_head_dim": CFG.linear_key_head_dim,
    "linear_value_head_dim": CFG.linear_value_head_dim,
    "allow_neg_eigval": CFG.linear_allow_neg_eigval, "eps": CFG.rms_norm_eps,
}


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


def _tokens(seed, *shape):
    return np.random.default_rng(seed).integers(1, CFG.vocab_size - 1, shape).astype(np.int32)


def _layer_of(params, kind, index):
    return jax.tree.map(lambda a: a[index], params["layers"][kind])


# -- (i) the program against the plain reference ------------------------------


@pytest.mark.parametrize("kind", ["linear_attention", "full_attention"])
def test_each_kind_of_layer_matches_the_reference(params, kind):
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, CFG.d_model), jnp.float32)
    lp = _layer_of(params, kind, 1)
    if kind == "linear_attention":
        got, _ = T._linear_layer(x, lp, CFG, None, 0, None)
    else:
        mask = jnp.tril(jnp.ones((40, 40), jnp.bool_))[None]
        got, _ = T._layer(x, lp, None, None, mask, CFG)
    want, _ = REF.layer(x[0], {kind: lp}, DIMS)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=1e-4)


def test_whole_forward_matches_the_reference(params):
    tokens = _tokens(2, 70)
    x = REF.embed(params, jnp.asarray(tokens))
    at = dict.fromkeys(set(CFG.layer_pattern), 0)
    for i in range(CFG.n_layers):
        kind = CFG.layer_pattern[i % len(CFG.layer_pattern)]
        x, _ = REF.layer(x, {kind: _layer_of(params, kind, at[kind])}, DIMS)
        at[kind] += 1
    want = REF.unembed(params, x, DIMS)
    got = T.forward(params, jnp.asarray(tokens)[None], CFG)[0]
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=1e-4)


# -- (ii) the chunked rule against the token-by-token one ---------------------


@pytest.mark.parametrize("length", [1, 3, 63, 64, 65, 200])
def test_chunked_prefill_equals_the_recurrence(length):
    b, h, dk, dv = 2, 4, 8, 16
    keys = jax.random.split(jax.random.PRNGKey(length), 6)
    q = gd.l2norm(jax.random.normal(keys[0], (b, length, h, dk))) * dk**-0.5
    k = gd.l2norm(jax.random.normal(keys[1], (b, length, h, dk)))
    v = jax.random.normal(keys[2], (b, length, h, dv))
    g = -jnp.exp(jax.random.normal(keys[3], (b, length, h))) * 0.3
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (b, length, h)))
    s0 = jax.random.normal(keys[5], (b, dk, h * dv))
    o_want, s_want = gd.gated_delta_recurrent(q, k, v, g, beta, s0)
    o_got, s_got = gd.gated_delta_chunk_prefill(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o_got, o_want, atol=1e-5)
    np.testing.assert_allclose(s_got, s_want, atol=1e-5)
    if length not in (3, 65):
        return
    # padding (decay 1, write strength 0) changes neither the state nor what came before
    pad = lambda x: jnp.pad(x, ((0, 0), (0, 7)) + ((0, 0),) * (x.ndim - 2))  # noqa: E731
    o_pad, s_pad = gd.gated_delta_chunk_prefill(pad(q), pad(k), pad(v), pad(g), pad(beta), s0)
    np.testing.assert_allclose(s_pad, s_want, atol=1e-5)
    np.testing.assert_allclose(o_pad[:, :length], o_want, atol=1e-5)


UPDATE_JNP = jax.jit(gd.gated_delta_update_jnp)  # which rows are live is data: one compile
UPDATE_KERNEL = jax.jit(functools.partial(gd.gated_delta_update, interpret=True))


@pytest.mark.parametrize("live", [[1, 1, 1, 1, 1], [0, 1, 0, 1, 0], [0, 0, 0, 0, 0], [1, 0, 0, 0, 1]])
def test_update_kernel_equals_jnp_and_leaves_idle_rows(live):
    """The Pallas kernel in interpret mode: live rows step, idle rows keep
    their state to the bit, whichever rows are live."""
    n, h, dk, dv = 5, 4, 8, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    state = jax.random.normal(keys[5], (3, 6, dk, h * dv))
    q = gd.l2norm(jax.random.normal(keys[0], (n, h, dk))) * dk**-0.5
    k = gd.l2norm(jax.random.normal(keys[1], (n, h, dk)))
    v = jax.random.normal(keys[2], (n, h, dv))
    g = -jnp.exp(jax.random.normal(keys[3], (n, h))) * 0.3
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (n, h)))
    rows, live = jnp.array([4, 0, 2, 5, 1]), jnp.array(live, jnp.bool_)
    o_want, s_want = UPDATE_JNP(q, k, v, g, beta, state, 1, rows, live)
    o_got, s_got = UPDATE_KERNEL(q, k, v, g, beta, state, 1, rows, live)
    np.testing.assert_allclose(o_got, o_want, atol=1e-6)
    np.testing.assert_allclose(s_got, s_want, atol=1e-6)
    untouched = np.ones((3, 6), bool)
    untouched[1, np.asarray(rows)[np.asarray(live)]] = False
    np.testing.assert_array_equal(np.asarray(s_got)[untouched], np.asarray(state)[untouched])


# -- (iii) pages and state against the full forward ---------------------------


def _forward_all(params, sequence):
    """The full forward's logits at every position: causal, so position p
    holds what the forward of the first p + 1 tokens ends in. One compile a
    length, so callers pass a whole sequence once."""
    return np.asarray(T.forward(params, jnp.asarray([sequence], jnp.int32), CFG)[0])


# the model's paged entry points are bodies of the engine's programs, not
# jitted themselves: eagerly, every call would compile its layer scan anew
DECODE = jax.jit(T.paged_decode_step_inplace, static_argnums=(5, 6))
SEGMENT = jax.jit(
    T.paged_prefill_segment_inplace, static_argnums=(6, 7), static_argnames=("config", "page_size")
)


def _decode_and_compare(params, pool, tables, slots, full, lengths, config, steps=4):
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
            np.testing.assert_allclose(logits[slot], want[i][lengths[i] + step], atol=2e-4)
    return pool


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_padded_group_then_decode_equals_the_full_forward(params, impl):
    config = dataclasses.replace(CFG, attention_impl=impl)
    lengths, width, slots, rows, n_pages = [37, 64, 5], 64, [2, 0, 3], 4, 40
    full = [_tokens(10 + i, n + 6).tolist() for i, n in enumerate(lengths)]
    group = np.zeros((3, width), np.int32)
    for i, n in enumerate(lengths):
        group[i, :n] = full[i][:n]
    tables = np.full((rows, 6), n_pages, np.int32)
    for i, slot in enumerate(slots):
        tables[slot] = np.arange(6) + 6 * i
    kv, rec = T.split_rec(T.make_page_pool(config, n_pages, PAGE, state_rows=rows))
    logits, cache = T.prefill(
        params, jnp.asarray(group), jnp.asarray(lengths),
        T.join_rec(T.make_kv_cache(config, 3, width), rec), config, rec_rows=jnp.asarray(slots),
    )
    cache, rec = T.split_rec(cache)
    for i, n in enumerate(lengths):
        np.testing.assert_allclose(logits[i], _forward_all(params, full[i])[n - 1], atol=2e-4)
    pool = T.paged_insert_cache(T.join_rec(kv, rec), cache, jnp.asarray(tables[slots]), PAGE)
    pool = _decode_and_compare(params, pool, tables, slots, full, lengths, config)
    # row 1 never held a sequence: an idle row of every step, its state untouched
    assert float(jnp.abs(pool["rec"]["s"][:, 1]).max()) == 0.0
    assert float(jnp.abs(pool["rec"]["conv"][:, 1]).max()) == 0.0


def test_two_prefill_segments_then_decode_equal_the_full_forward(params):
    n, width, n_pages = 83, 64, 12  # a whole segment, then 19 real tokens of 64
    full = [_tokens(20, n + 6).tolist()]
    pool = T.make_page_pool(CFG, n_pages, PAGE, state_rows=2)
    # row 1's state from an earlier sequence: a segment at offset 0 starts from zero
    pool["rec"] = jax.tree.map(lambda a: a + 1, pool["rec"])
    table = jnp.asarray([list(range(8))], jnp.int32)
    for offset in (0, width):
        real = min(width, n - offset)
        segment = np.zeros((1, width), np.int32)
        segment[0, :real] = full[0][offset : offset + real]
        logits, pool = SEGMENT(
            params, jnp.asarray(segment), jnp.asarray([offset]), jnp.asarray([real]), pool,
            table, CFG, PAGE, state_rows=jnp.asarray([1]),
        )
    np.testing.assert_allclose(logits[0], _forward_all(params, full[0])[n - 1], atol=2e-4)
    tables = np.full((2, 8), n_pages, np.int32)
    tables[1] = np.arange(8)
    _decode_and_compare(params, pool, tables, [1], full, [n], CFG)


# -- (iv) a slot released and admitted again ----------------------------------


def _engine(params, **kw):
    engine = E.ServingEngine(
        CFG, params, max_batch=kw.pop("max_batch", 2), max_seq_len=256,
        prefill_buckets=(32, 64), page_size=PAGE, decode_chunk=4, precompile=False, **kw,
    )
    engine.start()
    return engine


def test_a_reused_slot_gives_what_a_fresh_engine_gives(params):
    greedy = GenerationOptions(max_new_tokens=10, temperature=0.0)
    first, second = _tokens(30, 50).tolist(), _tokens(31, 21).tolist()
    long = _tokens(32, 100).tolist()  # beyond the largest bucket: two segments
    used = _engine(params, max_batch=1)
    fresh = _engine(params, max_batch=1)
    try:
        used.generate(first, greedy, timeout=300)
        for prompt in (second, long):  # an admit group; two segments
            got = used.generate(prompt, greedy, timeout=300).tokens
            want = fresh.generate(prompt, greedy, timeout=300).tokens
            assert list(got) == list(want)
            for leaf in ("s", "conv"):
                np.testing.assert_array_equal(
                    used._pagepool.dev["rec"][leaf], fresh._pagepool.dev["rec"][leaf]
                )
        stats = used.stats()
        assert stats["recurrent-state-rows-in-use"] == 0
        assert stats["recurrent-state-bytes"] == used._pagepool.state_bytes_total > 0
    finally:
        used.stop()
        fresh.stop()


def test_engine_tokens_are_the_full_forward_s_and_spans_count_state_rows(params):
    from langstream_tpu.tracing import TRACER

    TRACER.clear()
    engine = _engine(params)
    try:
        for n in (37, 100):  # a padded group; two segments
            prompt = _tokens(40 + n, n).tolist()
            got = list(engine.generate(prompt, GenerationOptions(max_new_tokens=6), timeout=300).tokens)
            # each token is the full forward's best at its position
            logits = _forward_all(params, prompt + got)[n - 1 : -1]
            assert len(got) == 6 and [int(row.argmax()) for row in logits] == got
    finally:
        engine.stop()
    spans = [s for s in TRACER.spans(4096) if s["name"].startswith("engine.")]
    chunks = [s["attributes"] for s in spans if s["name"] == "engine.decode_chunk"]
    groups = [s["attributes"] for s in spans if s["name"] == "engine.admit_group"]
    assert chunks and all(c["state_rows"] == c["kv_rows_written"] for c in chunks if "state_rows" in c)
    assert any(c.get("state_rows", 0) > 0 for c in chunks)
    assert any(g.get("state_rows_written") == g["real_rows"] for g in groups)


@pytest.mark.parametrize("k", [1, 3])
def test_the_ladder_admits_state_rows_of_the_model_s_own_prefill(params, k):
    """One prompt rides an admission group of one row, three a group of four
    (the ladder 1, 4 under a `prefill_batch` of 4): each slot's row of
    recurrent state is what `prefill` writes for that prompt alone over its
    true length, the other rows stay zero, and the tokens served are the
    full forward's."""
    from collections import deque

    from langstream_tpu.tracing import TRACER

    TRACER.clear()
    engine = E.ServingEngine(
        CFG, params, max_batch=4, max_seq_len=256, prefill_buckets=(32, 64), page_size=PAGE,
        decode_chunk=4, prefill_batch=4, precompile=False,
    )
    assert engine._admit_rungs == (1, 4)
    prompts = [_tokens(60 + i, n).tolist() for i, n in enumerate([37, 64, 41][:k])]
    requests = [
        engine.submit(E.GenerationRequest(
            prompt_tokens=p, options=GenerationOptions(max_new_tokens=5, temperature=0.0)
        ))
        for p in prompts
    ]
    pending = deque([engine._admit()])  # the group alone: no decode step has touched the state
    rec = jax.tree.map(np.asarray, engine._pagepool.dev["rec"])
    slots = {id(s.request): i for i, s in enumerate(engine._slots) if s.request is not None}
    for request, prompt in zip(requests, prompts):
        _, cache = T.prefill(
            params, jnp.asarray([prompt + [0] * (64 - len(prompt))], jnp.int32),
            jnp.asarray([len(prompt)]),
            T.join_rec(T.make_kv_cache(CFG, 1, 64), T.make_recurrent_state(CFG, 1)), CFG,
            rec_rows=jnp.asarray([0]),
        )
        for leaf in ("s", "conv"):
            np.testing.assert_allclose(
                rec[leaf][:, slots[id(request)]], cache["rec"][leaf][:, 0], atol=1e-5
            )
    idle = sorted(set(range(4)) - set(slots.values()))
    assert float(np.abs(rec["s"][:, idle]).max()) == 0.0
    try:
        while not all(r._done.is_set() for r in requests):
            engine._iterate(pending)
    finally:
        engine.stop()
    for request, prompt in zip(requests, prompts):
        got = list(request.result(timeout=1).tokens)
        logits = _forward_all(params, prompt + got)[len(prompt) - 1 : -1]
        assert [int(row.argmax()) for row in logits] == got
    groups = [s["attributes"] for s in TRACER.spans(4096) if s["name"] == "engine.admit_group"]
    assert [(g["rows"], g["real_rows"], g["state_rows_written"]) for g in groups] == [
        (1 if k == 1 else 4, k, k)
    ]


# -- (v) what a recurrent state refuses ---------------------------------------


@pytest.mark.parametrize(
    "option, value",
    [
        ("prefix_cache", "auto"), ("host_kv_fraction", 1.0), ("migrate_staging", True),
        ("durable_dir", "/tmp/never-made"), ("speculation", "auto"),
        ("adapters", [{"name": "a", "rank": 2}]), ("mesh", object()), ("spmd", object()),
    ],
)
def test_recurrent_model_refuses_the_option_by_name(params, option, value):
    with pytest.raises(ValueError, match=option):
        E.ServingEngine(CFG, params, max_batch=2, max_seq_len=128, **{option: value})


def test_recurrent_model_refuses_a_ring_axis(params):
    with pytest.raises(ValueError, match="ring_axis"):
        E.ServingEngine(dataclasses.replace(CFG, ring_axis="seq"), params, max_batch=2)


def test_entry_points_without_a_recurrent_path_say_so(params):
    tokens = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="layer pattern"):
        T.encode(params, tokens, jnp.asarray([4]), CFG)
    with pytest.raises(NotImplementedError, match="layer pattern"):
        T.paged_verify_step_inplace(params, tokens, jnp.asarray([0]), {}, tokens, CFG, PAGE)


def test_memory_plan_counts_pages_and_state():
    from langstream_tpu.serving.memory import plan_serving_memory

    config = MODEL_PRESETS["olmo-hybrid-7b"]
    plan = plan_serving_memory(
        config, 48, 640, quantized_weights=True, page_size=64, kv_pages=480
    )
    # 24 layers x 48 slots x (96 x 5760 float32 + 3 x 11520 bf16)
    assert plan.recurrent_state_bytes == 24 * 48 * (96 * 5760 * 4 + 3 * 11520 * 2)
    # 8 full layers x 480 pages x 64 tokens x 30 heads x 128 x (k, v) bf16
    assert plan.page_pool_bytes == 8 * 480 * 64 * 30 * 128 * 2 * 2
    assert 7.7e9 < plan.weights_bytes < 7.9e9
    assert "recurrent-state" in plan.summary()


def test_preset_is_the_benchmark_s_configuration():
    sys.path[:0] = [p for p in (str(BENCH),) if p not in sys.path]
    from modelcfg import load_json, model_config

    spec = load_json("configs", "olmo-hybrid-7b-int8")
    made = model_config(spec, "olmo-hybrid-7b")
    assert made == MODEL_PRESETS["olmo-hybrid-7b"]
    assert spec["reduced"] == [] and len(spec["layer_types"]) == 32


# -- (vi) the two standing families, as they were at the parent commit --------

# taken at commit fb2a262 (PR 31) by the code below: the weight tree's
# shapes and dtypes, the forward's logits on a fixed sample, and the text of
# the three lowered engine programs (their `jit_` names are the keys)
AT_PARENT = {
    "tiny-test": {
        "tree": "d85dfc767cc2c600", "logits": "494fed2ce09c8b68",
        "jit__paged_decode_chunk": "20341972a65614de",
        "jit__paged_segment_and_sample": "125a9034d95d52b8",
        "jit_admit_group": "a5caffa2fe24d76a",
    },
    "tiny-moe-test": {
        "tree": "3497e93f12be99b2", "logits": "cddff4c607389b3d",
        "jit__paged_decode_chunk": "a55cf7f982a9f739",
        "jit__paged_segment_and_sample": "7a25e68b345d01ba",
        "jit_admit_group": "96b3f6082eadf02b",
    },
}


def _sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


def _standing(name: str) -> dict:
    config = MODEL_PRESETS[name]
    params = T.init_params(config, jax.random.PRNGKey(0))
    tree = json.dumps(
        jax.tree_util.tree_map(lambda a: [list(a.shape), str(a.dtype)], params), sort_keys=True
    )
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 500, (2, 24)), jnp.int32)
    logits = np.asarray(T.forward(params, tokens, config)).astype(np.float32)
    b, pages, table = 4, 16, 4
    sds = jax.ShapeDtypeStruct
    i32, f32 = (lambda *s: sds(s, jnp.int32)), (lambda *s: sds(s, jnp.float32))
    key = sds((2,), jnp.uint32)
    pool = jax.eval_shape(lambda: T.make_page_pool(config, pages, PAGE))
    shapes = jax.eval_shape(lambda k: T.init_params(config, k), key)
    lowered = {
        "jit__paged_decode_chunk": E._paged_decode_chunk.lower(
            shapes, i32(b), i32(b), pool, i32(b, table), key, f32(b), i32(b), f32(b), 4,
            config, PAGE,
        ),
        "jit__paged_segment_and_sample": E._paged_segment_and_sample.lower(
            shapes, i32(1, 32), i32(1), i32(1), pool, i32(1, table), key, f32(1), i32(1),
            f32(1), config, PAGE,
        ),
        "jit_admit_group": E._make_paged_admit_group().lower(
            shapes, pool, i32(b), i32(b), f32(b), i32(b), f32(b), key, i32(2, 32), f32(4, 2),
            i32(2), i32(2, table), config, PAGE,
        ),
    }
    found = {"tree": _sha(tree.encode()), "logits": _sha(logits.tobytes())}
    for program, low in lowered.items():
        assert f"module @{program} " in low.as_text()[:200]
        found[program] = _sha(low.as_text().encode())
    return found


@pytest.mark.parametrize("name", sorted(AT_PARENT))
def test_standing_families_are_what_they_were(name):
    assert _standing(name) == AT_PARENT[name]

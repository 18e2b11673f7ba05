"""A model that fills BLOCKS of tokens by denoising, through the page pool and
the engine (docs/SERVING.md "A model that fills blocks"), at the tiny SDAR-MoE
size on the CPU (`benchmark/tests/data/configs/tiny-sdar.json`: the family's
int8 tree, blocks of 4, four steps, threshold 0.9):

(i)   the program: prefill of a prompt's whole blocks, then denoise and
      commit passes through the page pool (`paged_block_step_inplace`),
      against the reference's full forward over the same sequence, for
      prompt tails 0..3 and a prompt shorter than a block; a denoise pass
      that wrote its K/V leaves the committed cache as one that never ran;
(ii)  the engine: tokens, labels and `trajectory` `correct` by `check.py`,
      and the four faults of the toy family failing by their row against
      this engine; the threshold path; rows out of phase in one chunk; a
      prompt that holds the mask id; caps that are no multiple of the block;
      stop tokens; spans and counters;
(iii) what the engine refuses, by name.
"""

import dataclasses
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.models import transformer as T
from langstream_tpu.models.configs import GenerationOptions
from langstream_tpu.serving import engine as E

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
sys.path[:0] = [p for p in (str(BENCH),) if p not in sys.path]
from check import run_check  # noqa: E402
from modelcfg import load_json, load_module, model_config  # noqa: E402

DATA = BENCH / "tests" / "data"
SPEC = load_json("configs", "tiny-sdar", DATA)
family = load_module("families", "sdar_moe")
ref = load_module("reference", "sdar_moe")
CONFIG = model_config(SPEC, "tiny-sdar")
F32 = dataclasses.replace(CONFIG, name="tiny-sdar-f32", dtype="float32")
DIMS = family.reference_dims(SPEC)
B, MASK, PAGE = CONFIG.block_length, CONFIG.mask_token_id, 8
ENGINE = dict(max_batch=4, max_seq_len=128, prefill_buckets=(16, 32, 64), page_size=PAGE,
              decode_chunk=4, prefill_batch=2)
# float32 program against the float32 reference: 1e-6 seen
SOUND = 2e-5


def rel_err(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.fixture(scope="module")
def params():
    return family.make_params(CONFIG, int(SPEC["weights"]["seed"]))


@pytest.fixture(scope="module")
def f32_params():
    return family.make_params(F32, 0)


def make_engine(config, params, **over):
    engine = E.ServingEngine(config, params, **{**ENGINE, **over})
    engine.start()
    engine.wait_ready()
    return engine


@pytest.fixture(scope="module")
def engine(params):
    engine = make_engine(CONFIG, params)
    yield engine
    engine.stop()


def settled_stats(engine, delivered: int) -> dict:
    """`stats()` once the engine thread has counted the chunk that finished
    the request: a waiter wakes inside the delivery, before the chunk's
    totals are added."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        stats = engine.stats()
        if stats["block-tokens-delivered"] >= delivered:
            return stats
        time.sleep(0.01)
    raise AssertionError(f"block-tokens-delivered never reached {delivered}: {stats}")


def quiet_stats(engine) -> dict:
    """`stats()` of an engine that has nothing left to count. The request
    before (another test's) woke its waiter inside the delivery, before its
    chunk's totals were added, and the chunk launched behind that one has
    still to land: both are counted once two more iterations have begun
    after the engine went quiet, however long a loaded host takes over them."""
    deadline, quiet_at = time.monotonic() + 60, None
    while time.monotonic() < deadline:
        if engine._quiesced():
            quiet_at = engine._iterations_total if quiet_at is None else quiet_at
            if engine._iterations_total >= quiet_at + 2:
                return engine.stats()
        time.sleep(0.001)
    raise AssertionError("the engine never went quiet")


def prompt_of(n: int, seed: int = 0) -> list[int]:
    return np.random.default_rng(seed).integers(0, MASK, n).tolist()


# -- (i) the program ------------------------------------------------------------


def _prefilled(params, prompt, n_pages=12):
    """The pool after the prompt's whole blocks, and the row's table."""
    whole = len(prompt) // B * B
    width = 32
    tokens = jnp.asarray([prompt[:whole] + [0] * (width - whole)], jnp.int32)
    _, local = T.prefill(
        params, tokens, jnp.asarray([whole]), T.make_kv_cache(F32, 1, width), F32
    )
    table = jnp.arange(n_pages)[None]
    pool = T.paged_insert_cache(T.make_page_pool(F32, n_pages, PAGE), local, table, PAGE, F32)
    return pool, table, whole


@pytest.mark.parametrize("n", [16, 17, 18, 19, 3], ids=lambda n: f"prompt{n}")
def test_prefill_then_passes_are_the_references_full_forward(f32_params, n):
    """Two blocks after the prompt's whole ones: each a denoise pass with
    some positions open (the mask id there) and a commit, each pass's logits
    at the block against the reference's forward over prefix and block."""
    prompt, answer = prompt_of(n, seed=n), prompt_of(12, seed=100 + n)
    pool, table, whole = _prefilled(f32_params, prompt)
    clean = prompt + answer
    for start in (whole, whole + B):
        block = clean[start : start + B]
        first_open = max(n - start, 0) + 1  # a generated position stays clean, the rest open
        masked = block[:first_open] + [MASK] * (B - first_open)
        for tokens in (masked, block):  # the denoise pass, then the commit
            logits, pool = T.paged_block_step_inplace(
                f32_params, jnp.asarray([tokens], jnp.int32), jnp.asarray([start]), pool,
                table, F32, PAGE,
            )
            want = ref.forward(f32_params, jnp.asarray(clean[:start] + tokens, jnp.int32), DIMS)
            assert rel_err(logits[0], want[start:]) < SOUND, (start, tokens)


def test_a_denoise_pass_that_wrote_its_kv_leaves_the_committed_cache_equal(f32_params):
    prompt, block = prompt_of(16), prompt_of(4, seed=9)
    run = lambda pool, table, tokens: T.paged_block_step_inplace(  # noqa: E731
        f32_params, jnp.asarray([tokens], jnp.int32), jnp.asarray([16]), pool, table, F32, PAGE
    )
    pool, table, _ = _prefilled(f32_params, prompt)
    before = pool["k"][:, 2]  # positions 16..23 are page 2
    _, pool = run(pool, table, [block[0], MASK, MASK, MASK])
    assert not jnp.array_equal(pool["k"][:, 2], before)  # the denoise pass wrote
    logits_a, pool_a = run(pool, table, block)
    fresh, table, _ = _prefilled(f32_params, prompt)
    logits_b, pool_b = run(fresh, table, block)
    assert jnp.array_equal(logits_a, logits_b)
    assert all(jnp.array_equal(pool_a[leaf], pool_b[leaf]) for leaf in ("k", "v"))


def test_an_idle_row_writes_nothing_and_counts_as_padding(f32_params):
    pool, table, _ = _prefilled(f32_params, prompt_of(16))
    tables = jnp.concatenate([table, jnp.full_like(table, 12)])  # row 1 maps nothing
    blocks = jnp.asarray([prompt_of(4, seed=1), [MASK] * 4], jnp.int32)
    logits, after, counts = T.paged_block_step_inplace(
        f32_params, blocks, jnp.asarray([16, 40]), pool, tables, F32, PAGE, moe_counts=True
    )
    names = dict(zip(T.moe_count_names(F32), counts.tolist()))
    per_row = B * F32.n_experts_per_tok * F32.n_layers
    assert names["routed"] == 2 * per_row and names["routed_real"] == names["local"] == per_row
    assert names["dropped"] == 0
    alone, after_alone = T.paged_block_step_inplace(
        f32_params, blocks[:1], jnp.asarray([16]), pool, table, F32, PAGE
    )
    assert rel_err(logits[0], alone[0]) < SOUND
    # the idle row wrote nowhere: the pool is the lone row's (to a rounding of
    # the batch's other matmul shape)
    assert rel_err(after["k"], after_alone["k"]) < SOUND


# -- (ii) the engine --------------------------------------------------------------


def small(spec: dict, **check) -> dict:
    return {**spec, "check": {**spec["check"], **check}}


def test_the_engines_trajectory_is_correct_by_the_check(engine):
    verdict = run_check(engine, SPEC)
    assert verdict["ok"], verdict["compared"]
    # the sample's prompts leave tails 0, 1, 2, 3 and one is shorter than a
    # block; 6 tokens asked: two blocks, or three where the first holds a tail
    assert verdict["generated_tokens"] == [6] * 5
    assert verdict["engine_positions"] == 4 + 4 + (3 + 4) + (2 + 4) + (1 + 4 + 4) + (1 + 4 + 4)
    assert verdict["engine_choice_positions"] > 20
    assert verdict["compared"]["engine_choice_over_tol_untied"] == [0, 0]
    # the hot path's fused layer keeps the attention half's sum unrounded where
    # the chain's two steps a layer round it to bf16 between them: 0.028 seen
    assert verdict["engine_margin_max"] <= 0.05 and verdict["hot_err_max_unexposed"] <= 0.04
    # half a layer a step, the reference's router reads what the program's read:
    # no (half layer, position) takes another expert, tie-exposed or not
    assert verdict["layer_err_max"] <= 0.02 and verdict["tie_exposed_over_tol"] == 0


def _least_confident(logits, key, temp, top_k, top_p, is_open, step, mask_id, threshold, schedule):
    """`block_choice` with the ranking upside down: the fault."""
    tokens, _, over = E_block_choice(
        logits, key, temp, top_k, top_p, is_open, step, mask_id, threshold, schedule
    )
    probs = jax.nn.softmax(logits.at[:, :, mask_id].set(-jnp.inf), axis=-1)
    conf = jnp.where(is_open, jnp.max(probs, axis=-1), jnp.inf)
    rank = jnp.argsort(jnp.argsort(conf, axis=-1, stable=True), axis=-1, stable=True)
    at_least = jnp.asarray(schedule, jnp.int32)[jnp.clip(step, 0, len(schedule) - 1)]
    return tokens, is_open & (rank < at_least[:, None]), over


def _causal_pass(params, tokens, starts, pool, table, config, page_size, moe_counts=False):
    """A denoise pass under the CAUSAL mask: the verify step's."""
    plain = dataclasses.replace(config, block_length=0, denoise_steps=0, mask_token_id=None)
    return T.paged_verify_step_inplace(
        params, tokens, starts, pool, table, plain, page_size, moe_counts=moe_counts
    )


def _fp8_scatter(pool, layer, vals, table, positions, page_size):
    rounded = vals.astype(jnp.float8_e4m3fn).astype(vals.dtype)
    return T_paged_scatter(pool, layer, rounded, table, positions, page_size)


def _dropping_route_all(xf, router, config):
    weights, chosen = T_route_all(xf, router, config)
    return weights.at[::16, -1].set(0.0), chosen


def _bf16_route_all(xf, router, config):
    logits = jnp.dot(xf.astype(jnp.bfloat16), router.astype(jnp.bfloat16)).astype(jnp.float32)
    top, chosen = jax.lax.top_k(logits, config.n_experts_per_tok)
    return jax.nn.softmax(top, axis=-1), chosen


E_block_choice, T_paged_scatter, T_route_all = E.block_choice, T._paged_scatter, T._route_all
FAULT_SAMPLE = dict(lengths=[16, 22, 37], new_tokens=6)


@pytest.mark.parametrize(
    "fault, row",
    [
        ("token-replaced", "engine_margin_over_tol_untied"),
        ("least-confident-fixed", "engine_choice_over_tol_untied"),
        ("causal-denoise-pass", "engine_margin_over_tol_untied"),
        ("fp8-cache", "hot_err_over_tol_untied"),
        # the expert path, held by level 1 a (half layer, position) at a time
        ("expert-skipped", "layer_err_over_tol_untied"),
        ("assignment-dropped", "layer_err_over_tol_untied"),
        ("bf16-router", "layer_err_over_tol_untied"),
    ],
)
def test_a_fault_fails_by_its_row_against_the_real_engine(params, engine, monkeypatch, fault, row):
    spec, served = small(SPEC, **FAULT_SAMPLE), params
    if fault == "bf16-router":
        # the tiny file's eps_router (0.02, for levels 2 and 3) excuses the gaps
        # a bf16 product flips at (0.004 at the worst): level 1 alone needs none
        spec = small(spec, eps_router=0.0005)
    if fault == "token-replaced":
        faulty = engine
        generate = engine.generate

        def altered(prompt, options, timeout=None):
            result = generate(prompt, options, timeout=timeout)
            result.tokens[1] = (result.tokens[1] + 97) % MASK  # after the passes that chose it
            return result

        monkeypatch.setattr(engine, "generate", altered)
    else:
        # a config of its own name: the fault is traced into programs of its own
        config = dataclasses.replace(CONFIG, name=f"tiny-sdar-{fault}")
        if fault == "least-confident-fixed":
            monkeypatch.setattr(E, "block_choice", _least_confident)
        elif fault == "causal-denoise-pass":
            monkeypatch.setattr(E, "paged_block_step_inplace", _causal_pass)
        elif fault == "fp8-cache":  # a block's K/V kept at 8 bits: by a number, the pool's type says bf16
            monkeypatch.setattr(T, "_paged_scatter", _fp8_scatter)
        elif fault == "expert-skipped":  # one expert adds nothing; the reference keeps the file's tree
            down = params["layers"]["w_down"]
            served = {**params, "layers": {**params["layers"], "w_down": {
                **down, "s": down["s"].at[:, 1].set(0)}}}
        elif fault == "assignment-dropped":  # what a capacity rule does to a token
            monkeypatch.setattr(T, "_route_all", _dropping_route_all)
        else:
            monkeypatch.setattr(T, "_route_all", _bf16_route_all)
        faulty = make_engine(config, served)
    try:
        verdict = run_check(faulty, spec, ref_params=params)
    finally:
        if faulty is not engine:
            faulty.stop()
    assert not verdict["ok"]
    assert verdict["compared"][row][0] > 0, verdict["compared"]
    assert verdict["compared"]["engine_state_mismatches"] == [0, 0]


def test_a_low_threshold_fixes_several_positions_a_pass(params):
    config = dataclasses.replace(CONFIG, name="tiny-sdar-low-threshold", confidence_threshold=0.004)
    low = make_engine(config, params)
    try:
        result = low.generate(prompt_of(16), GenerationOptions(max_new_tokens=24), timeout=120)
        stats = settled_stats(low, 24)
    finally:
        low.stop()
    assert len(result.tokens) == 24 and len(result.fix_steps) == 24
    assert stats["block-fixed-over-threshold"] > 0
    # under the worst case of (steps + 1) / block_length passes a token
    assert stats["block-row-passes"] / stats["block-tokens-fixed"] < 1.25
    # some block was clean before its fourth step
    blocks = [result.fix_steps[i : i + B] for i in range(0, 24, B)]
    assert any(max(steps) < B - 1 for steps in blocks)
    passes = family.trajectory(SPEC, prompt_of(16), result)
    assert any(len(p["read"]) > 1 for p in passes)


def test_rows_out_of_phase_in_one_chunk_equal_the_same_rows_alone(f32_params):
    engine = make_engine(F32, f32_params)
    try:
        prompts = [prompt_of(17, 1), prompt_of(30, 2), prompt_of(6, 3)]
        options = GenerationOptions(max_new_tokens=14)
        alone = [engine.generate(p, options, timeout=120) for p in prompts]
        requests = []
        for p in prompts:  # each joins while the others are mid-block
            requests.append(engine.submit(E.GenerationRequest(prompt_tokens=p, options=options)))
            time.sleep(0.05)
        together = [r.result(120) for r in requests]
    finally:
        engine.stop()
    for a, t in zip(alone, together):
        assert (a.tokens, a.fix_steps, a.block_rest) == (t.tokens, t.fix_steps, t.block_rest)


def test_a_prompt_that_holds_the_mask_id_is_served_as_text(engine):
    prompt = prompt_of(18, 4)
    prompt[5] = prompt[17] = MASK  # inside a whole block, and in the tail
    result = engine.generate(prompt, GenerationOptions(max_new_tokens=6), timeout=120)
    assert len(result.tokens) == 6 and MASK not in result.tokens
    passes = family.trajectory(SPEC, prompt, result)
    # the tail's mask id is text in every pass and never read; the first
    # block holds two generated positions
    assert all(p["tokens"][17] == MASK and 17 not in p["read"] for p in passes)
    assert passes[0]["open"] == [18, 19] and result.fix_steps[:2] in ([0, 1], [1, 0])


@pytest.mark.parametrize("cap", [1, 5, 6, 8])
def test_a_cap_that_is_no_multiple_of_the_block(engine, cap):
    seen = []
    request = E.GenerationRequest(
        prompt_tokens=prompt_of(16), options=GenerationOptions(max_new_tokens=cap),
        on_token=seen.append,
    )
    result = engine.submit(request).result(120)
    rest_tokens, rest_steps = result.block_rest
    assert result.finish_reason == "length" and len(result.tokens) == cap == len(seen)
    assert seen == result.tokens and len(result.fix_steps) == cap
    # the engine finished the block it began; the client never saw the rest
    assert (cap + len(rest_tokens)) % B == 0 and len(rest_tokens) == len(rest_steps) < B
    assert sorted(result.fix_steps[-(B - len(rest_tokens)):] + rest_steps) == [0, 1, 2, 3]


def test_a_stop_token_cuts_at_the_blocks_delivery(engine):
    prompt = prompt_of(16, 5)
    free = engine.generate(prompt, GenerationOptions(max_new_tokens=8), timeout=120)
    stop = free.tokens[5]
    cut = free.tokens.index(stop)
    result = engine.generate(
        prompt, GenerationOptions(max_new_tokens=8, stop_tokens=(stop,)), timeout=120
    )
    assert result.finish_reason == "stop" and result.tokens == free.tokens[:cut]
    assert result.block_rest[0][0] == stop  # the stop token leads the undelivered rest
    assert (len(result.tokens) + len(result.block_rest[0])) % B == 0
    assert len(family.trajectory(SPEC, prompt, result)) == (cut // B + 1) * (B + 1)


def test_spans_and_counters_of_a_block_chunk(engine):
    before = quiet_stats(engine)
    result = engine.generate(prompt_of(21, 6), GenerationOptions(max_new_tokens=8), timeout=120)
    after = settled_stats(engine, before["block-tokens-delivered"] + 8)
    delta = {k: after[k] - before[k] for k in after if k.startswith("block-")}
    # 3 + 4 + 4 tokens fixed over three blocks; the last block's commit is
    # never counted as the row's: its request had left the slot
    assert len(result.tokens) == 8 and delta["block-tokens-fixed"] == 11
    assert delta["block-tokens-delivered"] == 8
    assert delta["block-denoise-row-passes"] == 11 and delta["block-commit-row-passes"] == 2
    assert delta["block-row-passes"] == 13 and delta["block-fixed-over-threshold"] == 0
    # every pass of a row reads up to its block's end: 3 denoise passes and a
    # commit at 20..23, 4 and a commit at 24..27, 4 denoise passes at 28..31
    assert delta["block-kv-tokens-read"] == 4 * 24 + 5 * 28 + 4 * 32
    assert delta["block-kv-rows-written"] == 13 * B
    assert delta["block-passes"] % ENGINE["decode_chunk"] == 0
    assert after["moe-dropped-assignments-total"] == 0
    assert after["moe-routed-assignments-total"] > before["moe-routed-assignments-total"]


def test_the_block_chunks_span_says_what_its_passes_did(params):
    from langstream_tpu.serving import observability

    spans = []
    engine = make_engine(dataclasses.replace(CONFIG, name="tiny-sdar-spans"), params)
    emit = observability.emit_dispatch_span
    record = lambda name, start, end, attrs: spans.append((name, attrs))  # noqa: E731
    try:
        E.emit_dispatch_span = record
        engine.generate(prompt_of(16, 7), GenerationOptions(max_new_tokens=8), timeout=120)
    finally:
        E.emit_dispatch_span = emit
        engine.stop()
    chunks = [attrs for name, attrs in spans if name == "engine.block_chunk"]
    groups = [attrs for name, attrs in spans if name == "engine.admit_group"]
    assert groups and groups[0]["program"] == "_block_admit_group" and groups[0]["real_tokens"] == 16
    wanted = {
        "passes", "active_rows", "row_passes", "idle_row_passes", "denoise_row_passes",
        "commit_row_passes", "tokens_fixed", "tokens_delivered", "fixed_over_threshold",
        "kv_tokens_read", "kv_rows_written", "device_ms", "moe_routed", "moe_dropped",
        "moe_local", "moe_touched", "moe_spilled", "seq", "program",
    }
    assert chunks and all(wanted <= set(attrs) for attrs in chunks)
    assert sum(c["tokens_fixed"] for c in chunks) == 8
    assert sum(c["row_passes"] for c in chunks) == 9  # 8 denoise passes and one commit
    assert all(c["moe_dropped"] == 0 and c["program"] == "_paged_block_chunk" for c in chunks)


# -- (iii) what the engine refuses ---------------------------------------------------


@pytest.mark.parametrize(
    "option",
    [
        {"constrained_decoding": "on"}, {"speculation": "auto"}, {"page_size": 6},
        {"prefix_cache": "auto"}, {"host_kv_fraction": 1.0}, {"migrate_staging": True},
        {"durable_dir": "under-tmp-path"}, {"adapters": [{"name": "a", "rank": 4}]},
        {"mesh": object()}, {"spmd": object()},
    ],
    ids=lambda o: next(iter(o)),
)
def test_the_engine_refuses_by_name(params, option, tmp_path):
    name = next(iter(option))
    if name == "durable_dir":  # refused before anything is made there
        option = {name: str(tmp_path / "never-made")}
    with pytest.raises(ValueError, match=f"fills blocks.*{name}.*advances by a block"):
        E.ServingEngine(CONFIG, params, **{**ENGINE, **option})


def test_the_engine_refuses_an_int8_pool_and_a_prompt_beyond_the_largest_bucket(params, engine):
    int8 = dataclasses.replace(CONFIG, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        E.ServingEngine(int8, params, **ENGINE)
    with pytest.raises(ValueError, match="chunked prefill.*beyond the largest prefill bucket"):
        engine.generate(prompt_of(65), GenerationOptions(max_new_tokens=4), timeout=10)
    # `constrained-decoding: auto` means "where it is supported": off here, no refusal
    assert engine._constrain_reg is None
    # a migration asked of the running engine is refused too, by its reason
    from langstream_tpu.serving.migrate import MigrationError

    with pytest.raises(MigrationError, match="advances by a block"):
        engine.migrate_snapshot(prompt_of(16))


# -- the normal path: the `tpu-serving` resource by the preset's name -----------------


def test_the_tpu_serving_resource_serves_the_preset_and_streams_its_blocks():
    """`tpu-serving` with `model: tiny-blockfill-moe-test`: the provider's
    engine fills blocks, the stream's chunks follow the first token as for
    every model (1, 2, 4 tokens), and the answer is `max-tokens` long."""
    import asyncio

    from langstream_tpu.ai.tpu_serving import TpuServingProvider

    async def scenario():
        provider = TpuServingProvider({
            "model": "tiny-blockfill-moe-test", "tokenizer": "byte", "max-seq-len": 128,
            "max-batch": 2, "prefill-buckets": [32, 64], "page-size": 8, "decode-chunk": 4,
        })
        chunks = []

        service = provider.get_completions_service({})
        result = await service.get_text_completions(
            ["a block at a time"], {"max-tokens": 11, "min-chunks-per-message": 1},
            lambda chunk: chunks.append((chunk.index, chunk.content, chunk.last)),
        )
        engine = provider.engine()
        stats = settled_stats(engine, 11)
        await provider.close()
        return result, chunks, engine.config, stats

    result, chunks, config, stats = asyncio.run(scenario())
    assert config.fills_blocks and config.block_length == 4
    assert stats["block-tokens-delivered"] == 11 and stats["block-tokens-fixed"] >= 11
    assert chunks and chunks[-1][2] is True and [i for i, _, _ in chunks] == list(range(len(chunks)))
    assert result.finish_reason == "length"

"""Time to ready, measured where it is spent (docs/SERVING.md §12,
"Start-up"): the process's compile account (`langstream_tpu/compile_account.py`,
fed by `jax.monitoring`'s own events), the engine's `engine.startup.*` spans
and the `startup-*` / `process-*` keys of `stats()`. CPU tier, small engines:
counts and structure, never a time."""

import dataclasses
import sys
import threading
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from langstream_tpu.compile_account import ACCOUNT, register
from langstream_tpu.models.configs import MODEL_PRESETS
from langstream_tpu.models.transformer import init_params, make_page_pool
from langstream_tpu.serving import engine as E
from langstream_tpu.serving.startup import program_name
from langstream_tpu.tracing import TRACER

CFG = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
ENGINE = dict(max_batch=2, max_seq_len=128, decode_chunk=4, prefill_buckets=(32, 64))
STARTUP_KEYS = (
    "startup-s", "startup-build-s", "startup-warmup-s", "startup-trace-s", "startup-lower-s",
    "startup-backend-s", "startup-cache-retrieval-s", "startup-cache-hits",
    "startup-cache-requests", "startup-programs", "startup-kernels-traced",
)
PROCESS_KEYS = (
    "process-compile-trace-s", "process-compile-lower-s", "process-compile-backend-s",
    "process-compile-cache-retrieval-s", "process-compile-cache-hits",
    "process-compile-cache-requests", "process-kernels-traced",
)
SDS = jax.ShapeDtypeStruct


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def started(params, **over):
    """An engine that warms up (`wait_ready` is the caller's)."""
    engine = E.ServingEngine(CFG, params, **{**ENGINE, "precompile": True, **over})
    engine.start()
    return engine


def spans_of(engine) -> list:
    return [
        s for s in TRACER.spans(limit=2048)
        if s["name"].startswith("engine.startup") and s["traceId"] == engine._startup._trace_id
    ]


@pytest.fixture(scope="module")
def engine(params):
    jax.clear_caches()  # what an earlier file of this worker built is built again here
    engine = started(params)
    engine.wait_ready(300)
    # taken now: the tracer's ring is the process's, and other tests fill it
    engine.startup_spans = spans_of(engine)
    yield engine
    engine.stop()


# -- the account -------------------------------------------------------------


def _nested(tag: str):
    """A fresh outer jit that traces a fresh inner jit (nothing cached)."""
    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2 + len(tag)

    @jax.jit
    def outer_of_the_case(x):
        return inner(x) + inner(x * 3) + 1

    return outer_of_the_case


def test_a_jit_nested_in_a_jit_counts_its_trace_once():
    register()
    register()  # once a process, however often asked
    x = jnp.ones(4)  # what making it compiles is not the case's
    before = ACCOUNT.snapshot()
    _nested("a")(x).block_until_ready()
    after = ACCOUNT.snapshot()
    # one program: one outermost trace, one lowering, one backend compile;
    # `inner`'s own trace events fell inside the outer's and were not summed
    assert [after[k] - before[k] for k in ("trace-n", "lower-n", "backend-n")] == [1, 1, 1]
    assert after["trace-s"] > before["trace-s"]
    spans = [s for s in TRACER.find("jax.compile") if s.attributes["fun_name"] == "outer_of_the_case"]
    assert [s.attributes["section"] for s in spans[-3:]] == ["trace", "lower", "backend"]
    assert not [s for s in TRACER.find("jax.compile") if s.attributes["fun_name"] == "inner"]
    # the listeners heard more than they summed (the nested entries and exits)
    assert after["events"] - before["events"] > 6


def test_threads_at_once_each_count_their_own_outermost_trace():
    """Eight threads build a program each at once, the interpreter switching
    between them as often as it can: the stacks are a thread's own and the
    sums are taken under one lock, so nothing is counted twice or lost."""
    register()
    x, n = jnp.ones(4), 8
    gate = threading.Barrier(n)

    def build(tag):
        fn = _nested(tag)
        gate.wait(30)
        fn(x).block_until_ready()

    before, interval = ACCOUNT.snapshot(), sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=build, args=("b" * (2 + i),)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    after = ACCOUNT.snapshot()
    assert [after[k] - before[k] for k in ("trace-n", "lower-n", "backend-n")] == [n, n, n]


def test_the_by_name_table_holds_a_program_s_three_sections_in_one_row():
    register()

    @jax.jit
    def a_row_of_its_own(x):
        return jnp.tanh(x) * 5

    a_row_of_its_own(jnp.ones(4)).block_until_ready()
    (row,) = [r for r in ACCOUNT.report() if r["name"] == "a_row_of_its_own"]
    assert min(row["trace"], row["lower"], row["backend"]) > 0 and row["programs"] == 1
    assert set(row) >= {"cache-retrieval", "cache-saved"}
    totals = [r["trace"] + r["lower"] + r["backend"] for r in ACCOUNT.report()]
    assert totals == sorted(totals, reverse=True)  # the most seconds first


# -- the engine's keys --------------------------------------------------------


def test_startup_keys_freeze_at_ready_and_process_keys_go_on(engine):
    first = engine.stats()
    assert first["startup-programs"] == first["compiled_programs"] > 0
    assert first["startup-s"] >= first["startup-build-s"] + first["startup-warmup-s"] > 0
    built = first["startup-trace-s"] + first["startup-lower-s"] + first["startup-backend-s"]
    assert 0 < built <= first["startup-s"]
    assert first["startup-cache-retrieval-s"] <= first["startup-backend-s"]

    @jax.jit
    def something_new(x):
        return jnp.cos(x) - 7

    something_new(jnp.ones(5)).block_until_ready()
    second = engine.stats()
    assert {k: second[k] for k in STARTUP_KEYS} == {k: first[k] for k in STARTUP_KEYS}
    for key in ("process-compile-trace-s", "process-compile-lower-s", "process-compile-backend-s"):
        assert second[key] > first[key], key
        assert second[key] >= second[key.replace("process-compile", "startup")]


@pytest.mark.parametrize("preset", ["tiny-test", "tiny-window-moe-test", "tiny-blockfill-moe-test"])
def test_every_new_key_is_a_number_in_stats(preset):
    """A uniform, a pattern and a block-filling engine, as built: zeros for
    what the warm-up freezes, the process's account live."""
    config = dataclasses.replace(MODEL_PRESETS[preset], dtype="float32")
    page = 8
    built = E.ServingEngine(
        config, init_params(config, jax.random.PRNGKey(0)), max_batch=2, max_seq_len=128,
        decode_chunk=4, prefill_buckets=(16,), page_size=page, precompile=False,
    )
    stats = built.stats()
    for key in (*STARTUP_KEYS, *PROCESS_KEYS):
        assert isinstance(stats[key], (int, float)) and not isinstance(stats[key], bool), key
    assert all(stats[k] == 0 for k in STARTUP_KEYS)  # not ready yet
    built.start()
    built.wait_ready(60)
    try:
        after = built.stats()
        # no warm-up asked: ready at once, no program, the constructor counted
        assert after["startup-programs"] == 0 and after["startup-warmup-s"] == 0
        assert after["startup-s"] >= after["startup-build-s"] > 0
    finally:
        built.stop()


def test_an_engine_built_again_over_one_cache_directory_reads_every_program(params, tmp_path):
    """Cold, the directory serves nothing; after `jax.clear_caches()` the same
    process builds the same programs again and every compile is a read."""
    jax.clear_caches()
    E.enable_persistent_compile_cache(str(tmp_path / "cache"))
    counts = []
    for _ in range(2):
        built = started(params, prefill_buckets=(32,))
        try:
            built.wait_ready(300)
            stats = built.stats()
            counts.append((stats["startup-cache-hits"], stats["startup-cache-requests"]))
            programs = [s for s in spans_of(built) if s["name"] == "engine.startup.program"]
        finally:
            built.stop()
        jax.clear_caches()
    (cold_hits, cold_requests), (warm_hits, warm_requests) = counts
    assert cold_hits < cold_requests
    assert warm_hits == warm_requests > 0
    assert all(s["attributes"]["cache_hit"] for s in programs)
    assert stats["startup-cache-retrieval-s"] > 0


# -- the spans ----------------------------------------------------------------


def test_the_root_holds_a_program_span_a_recorded_warm_up_program(engine):
    by_name: dict = {}
    for s in engine.startup_spans:
        by_name.setdefault(s["name"], []).append(s)
    (root,), (build,) = by_name["engine.startup"], by_name["engine.startup.build"]
    assert root["parentId"] is None and root["status"] == "ok"
    phases = [s for name, spans in by_name.items() if ".warmup." in name for s in spans]
    assert {s["name"].rpartition(".")[2] for s in phases} >= {"paged", "prefill_buckets"}
    assert all(s["parentId"] == root["spanId"] for s in [build, *phases])
    programs = by_name["engine.startup.program"]
    # one a recorded program, named as `_record_program` names it
    assert sorted(s["attributes"]["program"] for s in programs) == sorted(
        program_name(sig) for sig in engine._programs)
    assert {s["parentId"] for s in programs} == {s["spanId"] for s in phases}
    assert root["attributes"]["programs"] == len(programs) == engine.stats()["startup-programs"]
    end = lambda s: s["start"] + s["durationMs"] / 1e3  # noqa: E731
    for s in programs:
        a = s["attributes"]
        parts = a["trace_ms"] + a["lower_ms"] + a["backend_ms"] + a["run_ms"]
        assert min(a["trace_ms"], a["lower_ms"], a["backend_ms"], a["run_ms"]) >= 0
        assert parts <= s["durationMs"] + 0.01, a
        # built here, or by an earlier test of this process (then no compile at all)
        assert a["backend_ms"] > 0 or a["cache_hit"] is None, a
    for phase in phases:  # a family covers its programs, the root its families
        mine = [s for s in programs if s["parentId"] == phase["spanId"]]
        assert phase["attributes"]["programs"] == len(mine)
        assert sum(s["durationMs"] for s in mine) <= phase["durationMs"] + 0.01
        assert all(phase["start"] <= s["start"] + 1e-6 and end(s) <= end(phase) + 1e-3 for s in mine)
    assert build["durationMs"] + sum(s["durationMs"] for s in phases) <= root["durationMs"] + 0.01
    assert all(root["start"] <= s["start"] + 1e-6 and end(s) <= end(root) + 1e-3
               for s in [build, *phases])
    assert root["durationMs"] / 1e3 == pytest.approx(engine.stats()["startup-s"], abs=1e-3)


def test_a_warm_up_that_raises_still_emits_the_root_with_an_error_status(params, monkeypatch):
    def refuse(self):
        self._record_program("refused-program")
        raise RuntimeError("RESOURCE_EXHAUSTED: scoped vmem limit exceeded")

    monkeypatch.setattr(E.ServingEngine, "_warmup_paged", refuse)
    built = started(params)
    try:
        with pytest.raises(RuntimeError, match="failed to start"):
            built.wait_ready(60)
        spans = {s["name"]: s for s in spans_of(built)}
        assert spans["engine.startup"]["status"] == "error: RuntimeError"
        assert spans["engine.startup.warmup.paged"]["status"] == "error: RuntimeError"
        assert spans["engine.startup.program"]["attributes"]["program"] == "refused-program"
        assert "engine.startup.warmup.prefill_buckets" not in spans
        stats = built.stats()
        assert stats["startup-programs"] == 1 and stats["startup-s"] > 0
    finally:
        built.stop()


def test_the_provider_spans_its_tokenizer_and_its_weights():
    """What a replica's owner waits for ahead of the engine's own start-up:
    each loaded once, each a span; a tree handed in is no load."""
    from langstream_tpu.ai.tpu_serving import _EngineHolder

    holder = _EngineHolder({"model": "tiny-test", "max-batch": 2, "max-seq-len": 64})
    TRACER.clear()
    holder.tokenizer(), holder.tokenizer()
    holder.params(), holder.params()
    (tokenizer,), (weights,) = (TRACER.find(f"engine.startup.{n}") for n in ("tokenizer", "weights"))
    assert tokenizer.attributes == {"tokenizer": "byte"} and weights.attributes == {"weights": "random"}
    handed = _EngineHolder({"model": "tiny-test"})
    handed._params = holder.params()
    TRACER.clear()
    handed.params()
    assert not TRACER.find("engine.startup.weights")


# -- the kernels' counter -------------------------------------------------------

# the attention of test_tpu_compile.py's step programs (8 kv heads of 128: the
# kernel's tiling), narrow elsewhere; traced with the gates a chip process
# passes, which is where the wrappers count
UNIFORM = dataclasses.replace(
    MODEL_PRESETS["llama-3-8b"], name="llama-attn-narrow", vocab_size=2048, d_model=1024,
    d_ff=2048, n_layers=3, n_heads=8, n_kv_heads=8, head_dim=128,
)
PERIOD_OF_FOUR = dataclasses.replace(
    MODEL_PRESETS["tiny-window-moe-test"], name="window-narrow", vocab_size=2048, d_model=1024,
    d_ff=1024, n_heads=8, n_kv_heads=8, head_dim=128,
)


def _kernels_a_decode_chunk_traces(config) -> dict:
    b, pages, page, table = 16, 48, 64, 4
    params = jax.eval_shape(lambda k: init_params(config, k), SDS((2,), jnp.uint32))
    groups = {"window_pages": pages} if config.has_window else {}
    pool = jax.eval_shape(lambda: make_page_pool(config, pages, page, **groups))
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    tables = i32(2, b, table) if config.has_window else i32(b, table)
    args = (params, i32(b), i32(b), pool, tables, SDS((2,), jnp.uint32), f32(b), i32(b), f32(b))
    before, total = ACCOUNT.kernels(), ACCOUNT.snapshot()["kernels-traced"]
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        E._paged_decode_chunk.trace(*args, 4, config, page)
    after = ACCOUNT.kernels()
    counts = {k: n - before.get(k, 0) for k, n in after.items() if n != before.get(k, 0)}
    assert ACCOUNT.snapshot()["kernels-traced"] - total == sum(counts.values())
    return counts


def test_the_kernel_counter_rises_by_the_instances_a_program_s_trace_reaches():
    """A uniform model's layers are one scan body: one instance a kernel. A
    period of four layers (window x3, full) is traced layer by layer: four."""
    assert _kernels_a_decode_chunk_traces(UNIFORM) == {
        "ragged_paged_decode_attention": 1, "paged_kv_write": 1}
    assert PERIOD_OF_FOUR.layer_pattern == ("sliding_attention",) * 3 + ("full_attention",)
    assert _kernels_a_decode_chunk_traces(PERIOD_OF_FOUR) == {
        "ragged_paged_decode_attention": 4, "paged_kv_write": 4, "moe_grouped_matmul": 8}

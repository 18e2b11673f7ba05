"""The Command A+ family (`cohere2_moe`) held to the README's contract ("A
family"), its two costs and its two readers against hand counts, and the
faults its correctness check must catch, each by a number, at a tiny size on
the CPU.

The fast cases here (everything but the check's verdicts) are also run by the
repo's tier-1 through `tests/test_benchmark_families.py`.

The check's cases serve the tiny configuration in float32 (the family's
`ModelConfig` with `dtype` replaced), as Olmo-Hybrid's do: a sound system
then reads 1e-6 and every fault a hundred times its tolerance. The faults are
those of `tests/test_cohere2_moe.py` (the model against the reference's full
forward), here through an engine and the check's three levels.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from check import run_check
from modelcfg import load_json, load_module, model_config, register_preset
from readers import stats_final, trace_scope, trace_span_roofline
from reduce import grouped_matmul_cost, windowed_attention_cost

DATA = Path(__file__).parent / "data"
TINY = "tiny-cmdaplus"
REAL = "command-a-plus-05-2026-int8-ep8-d8"
CELL = "cmdaplus-ep8-d8-ragdocs-drain"
ENGINE = {"max-batch": 4, "max-seq-len": 256, "prefill-buckets": [32], "prefill-batch": 1,
          "kv-pages": 64, "page-size": 8, "tokenizer": "byte"}
family = load_module("families", "cohere2_moe")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())


# -- the README's contract ----------------------------------------------------


def test_the_family_exports_what_the_readme_lists():
    for name in ("model_config", "make_params", "reference_dims", "system_chain",
                 "ref_layer_params", "hot_path", "engine_state", "expected_kernels",
                 "state_leaves"):
        assert callable(getattr(family, name)), name
    reference = load_module("reference", "cohere2_moe")
    assert all(callable(getattr(reference, name)) for name in ("embed", "layer", "unembed"))


def test_the_real_configuration_maps_onto_its_fields():
    config = model_config(load_json("configs", REAL), REAL)
    assert (config.d_model, config.n_layers, config.d_ff, config.vocab_size) == (4096, 8, 4096, 32768)
    assert (config.n_heads, config.n_kv_heads, config.resolved_head_dim) == (128, 8, 128)
    assert config.layer_pattern == tuple(PERIOD) and config.n_periods == 2
    assert (config.sliding_window, config.rope_theta, config.rope_interleaved) == (4096, 50000, True)
    assert (config.norm, config.tie_embeddings) == ("layer", True)
    assert (config.rms_norm_eps, config.logit_scale) == (1e-5, 1.0)
    assert (config.n_experts, config.n_experts_per_tok, config.n_shared_experts) == (128, 8, 4)
    assert (config.moe_scoring, config.held_experts, config.expert_d_ff) == ("sigmoid", (0, 16), 4096)
    assert config.has_window and config.is_moe and not config.is_recurrent
    # a layer here: attention 142.6 M, shared 201.3 M, router 0.5 M, 16 experts of 50.3 M
    layer = (config.approx_params - 32768 * 4096) // 8
    assert layer == 2 * 4096 * 128 * 136 + 4096 * 128 + 20 * 3 * 4096 * 4096 == 1_149_763_584


def test_the_published_keys_are_the_catalog_s_and_the_cut_is_stated():
    spec = load_json("configs", REAL)
    assert spec["reduced"] == ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    assert spec["layer_types"] == PERIOD * 2 and spec["family"] == "cohere2_moe"
    cut = spec["deployment"]
    assert cut["chips_a_layer"] == 8 and cut["experts"] == {"published": 128, "first_held": 0, "held": 16}
    assert cut["layers"] == {"published": 32, "held": 8}
    assert cut["vocabulary"] == {"published": 262144, "held": 32768}
    assert (spec["num_experts"], spec["num_hidden_layers"], spec["vocab_size"]) == (16, 8, 32768)
    # no width is cut
    assert (spec["hidden_size"], spec["intermediate_size"], spec["head_dim"]) == (4096, 4096, 128)
    assert (spec["num_attention_heads"], spec["num_key_value_heads"]) == (128, 8)
    assert (spec["num_experts_per_tok"], spec["num_shared_experts"], spec["sliding_window"]) == (8, 4, 4096)
    assert {"expert_width", "shared_expert_combination_strategy", "router_bias", "layer_norm",
            "full_layers", "scope"} <= set(spec["assumed"])
    entry = next(c for c in BENCH["configs"] if c["name"] == REAL)
    assert entry["reduced"] == spec["reduced"] and entry["source"] == spec["source"]
    # the check's sample: a prompt past the window and the ring, whose 8 tokens cross a page
    check = spec["check"]
    ring = check["page_groups"]["window_ring_pages"]
    longest = max(check["lengths"])
    assert longest > 4096 + 64 and -(-(longest + check["new_tokens"]) // 64) > ring == 97
    assert longest // 64 < (longest + check["new_tokens"] - 1) // 64
    assert check["width"] >= longest + check["new_tokens"]


def test_the_cell_is_sized_as_the_issue_says():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "ragdocs-drain", 1)
    engine = load_json("workloads", CELL)["engine"]
    assert (engine["max-batch"], engine["max-seq-len"], engine["prefill-buckets"]) == (16, 12544, [2048])
    assert engine["kv-pages"] == 16 * 196
    traffic = load_json("traffic", "ragdocs-drain")
    assert traffic["kind"] == "topic_drain" and traffic["backlog_records"] == 200
    assert traffic["prompt_tokens"] == {"dist": "uniform", "min": 4096, "max": 12288}
    assert traffic["output_caps"] == {"256": 1.0}
    reports = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert {"windowed_decode_attn_roofline.drain", "segment_attn_roofline.drain",
            "moe_grouped_matmul_roofline.drain", "prefill_segment_ms_per_1k_tokens.drain",
            "moe_local_assignment_share", "kv_window_pages_peak_share", "kv_pages_peak_share",
            "moe_dropped_assignment_share", "active_slots_mean"} <= reports
    assert "paged_decode_attn_roofline.drain" not in reports  # it prices every layer at the full count
    ends = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert ends == {"gen_tokens_per_s", "setup_s"}


@pytest.mark.parametrize(
    "change, says",
    [
        ({"layer_types": ["full_attention"] * 8}, "period"),
        ({"layer_types": PERIOD * 2, "num_hidden_layers": 6}, "period"),
        ({"position_embedding_type": "rope"}, "position_embedding_type"),
        ({"expert_selection_fn": "softmax"}, "expert_selection_fn"),
        ({"shared_expert_combination_strategy": "sum"}, "shared_expert_combination_strategy"),
        ({"use_parallel_block": False}, "use_parallel_block"),
        ({"use_qk_norm": True}, "use_qk_norm"),
        ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
        ({"rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"}}, "rope_parameters"),
        ({"router_bias": True}, "router_bias"),
    ],
    ids=["not-the-period", "not-whole-periods", "half-split-rotary", "softmax-router",
         "shared-summed", "sequential-block", "qk-norm", "a-dense-prefix", "another-rope-base",
         "unknown-key"],
)
def test_what_the_program_cannot_express_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        model_config({**load_json("configs", TINY, DATA), **change}, TINY)


def test_the_dims_read_back_from_the_config_are_the_file_s():
    for name, root in ((REAL, None), (TINY, DATA)):
        spec = load_json("configs", name, *([root] if root else []))
        assert family._dims_of(model_config(spec, name)) == family.reference_dims(spec)


def test_seeded_weights_are_the_served_tree():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    again = family.make_params(config, 0)
    assert all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(again)))
    window, full = tree["layers"]["sliding_attention"], tree["layers"]["full_attention"]
    for stack, n in ((window, 6), (full, 2)):
        for key in family.QUANTIZED:
            assert stack[key]["q"].dtype == jnp.int8 and stack[key]["s"].shape[-2] == 1, key
        assert stack["router"].shape == (n, 64, 16) and stack["router"].dtype == jnp.float32
        assert stack["w_gate"]["q"].shape == (n, 4, 64, 32)  # 4 of the 16 experts held
        assert stack["w_down"]["q"].shape == (n, 4, 32, 64)
        assert stack["ws_gate"]["q"].shape == (n, 64, 2 * 32) and stack["ws_down"]["q"].shape == (n, 64, 64)
        assert stack["wq"]["q"].shape == (n, 64, 128) and stack["wk"]["q"].shape == (n, 64, 32)
    assert "lm_head" not in tree and tree["embed"].shape == (512, 64)  # a tied head
    assert tree["embed"].dtype == jnp.bfloat16  # unquantised: a slice of the vocabulary
    assert [family.place(i) for i in range(8)] == [
        ("sliding_attention", 0), ("sliding_attention", 1), ("sliding_attention", 2),
        ("full_attention", 0), ("sliding_attention", 3), ("sliding_attention", 4),
        ("sliding_attention", 5), ("full_attention", 1)]
    stack, at = family.ref_layer_params(tree, 6)
    assert list(stack) == ["sliding_attention"] and at == 5


# -- the costs and the readers, against hand counts ---------------------------

SIZES = dict(n_heads=128, n_kv_heads=8, head_dim=128, full_layers=2, window_layers=6)


def test_decode_attention_cost_counts_each_kind_s_own_reads():
    # 16 rows, one step, every row at 10,000 tokens: a full layer reads all, a window layer 4096
    got = windowed_attention_cost.windowed_decode_attention(
        kv_tokens_read=16 * 10_000, kv_tokens_read_window=16 * 4096, steps=1, calls=8, rows=16,
        **SIZES)
    pairs = 2 * 160_000 + 6 * 65_536
    k_and_v = pairs * 2 * 8 * 128 * 2  # 4 KiB a token and layer
    q_and_out = 2 * 16 * 128 * 128 * 2 * 8
    assert got == {"ops": 4 * pairs * 128 * 128, "bytes": k_and_v + q_and_out}
    # the standing cost would have priced all 8 layers at the full count: 1.8x the bytes
    assert 8 * 160_000 / pairs > 1.7


def test_segment_attention_cost_is_window_bounded_work():
    # a whole segment of 2048 queries at offset 8192: 2048 x 4096 pairs a window layer
    full = sum(range(8193, 8193 + 2048))
    got = windowed_attention_cost.segment_attention(
        kv_tokens_read=full, kv_tokens_read_window=2048 * 4096, real_tokens=2048, offset=8192,
        steps=1, calls=8, window=4096, **SIZES)
    pairs = 2 * full + 6 * 2048 * 4096
    assert got["ops"] == 4 * pairs * 128 * 128
    q_and_out = 2 * 2048 * 128 * 128 * 8
    keys = 2 * 8 * 128 * (2 * 10_240 + 6 * 6143)
    assert got["bytes"] == (q_and_out + keys) * 2
    # compute-bound on a v5e: operations over bytes far past the ridge
    assert got["ops"] / got["bytes"] > 1000


def test_grouped_matmul_cost_is_the_touched_weights_and_the_rows_that_arrived():
    # a decode step of 16 rows: 16 assignments over 9 experts of one layer
    got = grouped_matmul_cost.grouped_matmul(moe_local=16, moe_touched=9, steps=1, calls=3,
                                            d_model=4096, d_ff=4096)
    assert got == {"ops": 16 * 3 * 2 * 4096 * 4096,
                   "bytes": 9 * 3 * 4096 * 4096 + 16 * 3 * 8192 * 2}
    assert got["ops"] / got["bytes"] < 4  # bound by the weights it touches
    # a segment: 2048 assignments over all 16: bound by the products
    seg = grouped_matmul_cost.grouped_matmul(2048, 16, 1, 3, 4096, 4096)
    assert seg["ops"] / seg["bytes"] > 200
    assert grouped_matmul_cost.grouped_matmul(0, 0, 1, 3, 4096, 4096) == {"ops": 0, "bytes": 0}


def test_stats_final_reads_a_gauge_the_engine_keeps():
    definition = {"key": "kv-window-pages-peak", "over": "kv-window-pages-total", "scale": 100}
    stats = {"kv-window-pages-peak": 776, "kv-window-pages-total": 1552}
    assert stats_final.read(definition, {"stats": stats}) == 50.0
    assert stats_final.read({"key": "kv-window-pages-peak"}, {"stats": stats}) == 776
    # an engine without the key (the parent's), or no stats at all: nothing
    assert stats_final.read(definition, {"stats": {"kv-pages-total": 10}}) is None
    assert stats_final.read(definition, {}) is None


def test_span_roofline_prices_a_pair_once_by_several_attributes(monkeypatch):
    """Two decode chunks in the trace: the kernel's events of both layer
    kinds summed a pair, the cost called once a pair with both counts."""
    definition = load_json("layer_metrics", "windowed_decode_attn_roofline")
    spans = [
        {"attributes": {"kv_tokens_read": 160_000, "kv_tokens_read_window": 65_536, "steps": 1}},
        {"attributes": {"kv_tokens_read": 320_000, "kv_tokens_read_window": 131_072, "steps": 2}},
        {"attributes": {"steps": 1}},  # the parent's span: no window count, skipped
    ]
    kernel = "%ragged_paged_decode_attention.{} = bf16[16,128,128] custom-call("
    executions = [
        {"ops": {kernel.format(1): (0.004, 6), kernel.format(2): (0.002, 2), "fusion.1": (1.0, 8)}},
        {"ops": {kernel.format(1): (0.008, 12), kernel.format(2): (0.004, 4)}},
        {"ops": {kernel.format(1): (0.004, 6)}},
    ]
    monkeypatch.setattr(trace_scope, "pairs_of", lambda d, ctx: list(zip(spans, executions)))
    scope_of = {kernel.format(i): "attention/ragged_paged_decode_attention" for i in (1, 2)}
    scope_of["fusion.1"] = "ffn"
    ctx = {trace_scope.CACHE: {"scope_of": scope_of},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    one = windowed_attention_cost.windowed_decode_attention(160_000, 65_536, 1, 8, rows=16, **SIZES)
    two = windowed_attention_cost.windowed_decode_attention(320_000, 131_072, 2, 16, rows=16, **SIZES)
    least = (one["bytes"] + two["bytes"]) / 819e9  # memory-bound
    assert trace_span_roofline.read(definition, ctx) == pytest.approx(100 * least / 0.018)
    monkeypatch.setattr(trace_scope, "pairs_of", lambda d, ctx: [])
    assert trace_span_roofline.read(definition, ctx) is None  # no trace, no kernel: nothing


# -- the check: sound passes, each fault fails by a number (by hand) ----------

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "tests")]


def _faults():
    import test_cohere2_moe as model_tests

    return {fault.__name__: fault for fault in model_tests.FAULTS}


def verdict_of(fault_name="sound", monkeypatch=None):
    from langstream_tpu.ai.tpu_serving import TpuServingProvider
    from langstream_tpu.models.configs import MODEL_PRESETS

    # a name a fault: the engine's programs are cached by their (static) config
    name = f"{TINY}-{fault_name}"
    spec = json.loads((DATA / "configs" / f"{TINY}.json").read_text())
    config = dataclasses.replace(register_preset(spec, name), dtype="float32")
    MODEL_PRESETS[name] = config
    sound = served = family.make_params(config, int(spec["weights"]["seed"]))
    if fault_name == "a_held_expert_skipped":
        served = skip_held_expert_1(sound)
    elif fault_name != "sound":
        _faults()[fault_name](monkeypatch)
    provider = TpuServingProvider({**spec["serving"], **ENGINE, "model": name})
    provider.holder._params = served
    try:
        return run_check(provider.engine(), spec, ref_params=sound)
    finally:
        provider.holder.close()
        MODEL_PRESETS.pop(name, None)


def skip_held_expert_1(tree):
    """Held expert 1's down projection: its output scales zeroed in every layer."""
    def skip(stack):
        down = stack["w_down"]
        return {**stack, "w_down": {**down, "s": down["s"].at[:, 1].set(0.0)}}

    return {**tree, "layers": {kind: skip(s) for kind, s in tree["layers"].items()}}


def test_sound_system_passes_with_room():
    verdict = verdict_of()
    assert verdict["ok"], verdict
    found = verdict["engine_state"]["found"]
    assert found["page_groups"] == {"full": [2, 64], "window": [6, 28], "window_ring_pages": 7}
    assert found["experts_held"] == "0-3 of 16" and found["router_dtype"] == "float32"
    # float32 through and through: a hundredth of every tolerance
    assert verdict["layer_err_max"] < 1e-4 and verdict["hot_err_max_unexposed"] < 1e-4
    assert verdict["engine_positions"] == 3 * 12


@pytest.mark.parametrize(
    "fault, where",
    [
        ("window_mask_off", "unexplained_over_tol"),
        ("rotary_on_a_full_layer", "unexplained_over_tol"),
        ("half_split_pairs", "unexplained_over_tol"),
        ("shared_experts_summed", "unexplained_over_tol"),
        ("an_absent_expert_included", "unexplained_over_tol"),
        ("weights_normalised_over_the_held", "unexplained_over_tol"),
        ("a_held_expert_skipped", "unexplained_over_tol"),
    ],
)
def test_known_fault_fails_by_a_number(fault, where, monkeypatch):
    verdict = verdict_of(fault, monkeypatch)
    assert verdict["ok"] is False, verdict
    assert verdict[where] > 0, {k: v for k, v in verdict.items() if k != "hot_err_by_position"}


def test_the_tiny_cell_end_to_end_traced():
    """The whole command at the tiny size on the CPU, traced: the cell's own
    metrics come out of its spans, its stats and its two new readers."""
    import asyncio

    import run
    from langstream_tpu.messaging.memory import MemoryBroker
    from test_end_to_end import CPU_PLANES, tiny_bench

    bench = tiny_bench("tiny-cmdaplus-ragdocs-drain", TINY, "tiny-ragdocs", CELL)
    MemoryBroker.reset()
    out = asyncio.run(run.run_cell(
        bench, "tiny-cmdaplus-ragdocs-drain", 2**31 + 5, 6.0, True, platform="cpu", files=DATA,
        trace_planes=CPU_PLANES,
    ))
    assert out["failed"] == 0 and out["attempted"] > 0
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert {"prefill_segment_ms_per_1k_tokens.drain", "moe_local_assignment_share",
            "kv_window_pages_peak_share", "kv_pages_peak_share", "active_slots_mean",
            "moe_dropped_assignment_share"} <= set(metrics)
    assert metrics["moe_dropped_assignment_share"] == 0.0
    assert 15 < metrics["moe_local_assignment_share"] < 35  # 4 of 16 held: 25% when even
    assert 0 < metrics["kv_window_pages_peak_share"] <= 100

"""The SDAR-MoE family (`sdar_moe`) held to the README's contract ("A family",
with the three exports of an engine that fills blocks by denoising), its
configuration to the catalog and the stated cut, its cell to the issue's
sizes, its cost function and metric files to hand counts, and its correctness
check to a verdict, sound and faulted, at a tiny size on the CPU.

The fast cases here (everything but the check's verdicts) are also run by the
repo's tier-1 through `tests/test_benchmark_families.py`; the engine's own
trajectory, `correct` by the check, and the four faults against the REAL
engine are tier-1's too (`tests/test_block_diffusion.py`). The verdict cases
here drive the whole command's check at the tiny cell's knobs, by hand.
"""

import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from check import run_check
from modelcfg import load_json, load_module, model_config
from readers import span_ratio
from reduce import block_attention_cost, grouped_matmul_cost

DATA = Path(__file__).parent / "data"
TINY = "tiny-sdar"
REAL = "sdar-30b-a3b-chat-int8-d12"
CELL = "sdar30b-d12-blockdecode-drain"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
family = load_module("families", "sdar_moe")
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())


# -- the README's contract ----------------------------------------------------


def test_the_family_exports_what_the_readme_lists():
    for name in ("model_config", "make_params", "reference_dims", "system_chain",
                 "ref_layer_params", "hot_path", "engine_state", "expected_kernels",
                 "state_leaves", "trajectory", "choice_score"):
        assert callable(getattr(family, name)), name
    assert callable(family.hot_path.pass_logits) and not hasattr(family.hot_path, "logits")
    reference = load_module("reference", "sdar_moe")
    assert all(callable(getattr(reference, name)) for name in ("embed", "layer", "unembed"))
    # the equations and each departure stand in the reference's docstring
    for said in ("RMSNorm_128", "floor(j / B) <= floor(i / B)", "norm_topk_prob",
                 "score the token AT", "151669", "low_confidence_dynamic", "-inf", "STATE"):
        assert said in reference.__doc__, said


def test_the_real_configuration_maps_onto_its_fields():
    config = model_config(load_json("configs", REAL), REAL)
    assert (config.d_model, config.n_layers, config.vocab_size) == (2048, 12, 151936)
    assert (config.n_heads, config.n_kv_heads, config.resolved_head_dim) == (32, 4, 128)
    assert (config.n_experts, config.n_experts_per_tok, config.expert_d_ff) == (128, 8, 768)
    assert (config.d_ff, config.held_experts, config.holds_experts) == (6144, (0, 128), True)
    assert (config.rope_theta, config.rms_norm_eps, config.activation) == (1e6, 1e-6, "silu")
    assert config.qk_norm_heads and not config.qk_norm and not config.tie_embeddings
    assert (config.block_length, config.denoise_steps, config.block_schedule) == (4, 4, (1, 1, 1, 1))
    assert (config.confidence_threshold, config.mask_token_id) == (0.9, 151669)
    assert config.fills_blocks and not config.layer_pattern and not config.has_window
    # a layer here: attention 18.9 M, router 0.26 M, 128 experts of 4.72 M
    layer = (config.approx_params - 2 * 151936 * 2048) // 12
    assert layer == 2048 * 128 * (32 + 8) + 4096 * 2048 + 2048 * 128 + 128 * 3 * 2048 * 768


def test_the_published_keys_are_the_catalog_s_and_the_cut_is_stated():
    spec = load_json("configs", REAL)
    assert spec["reduced"] == ["num_hidden_layers"] and spec["num_hidden_layers"] == 12
    if CATALOG.is_file():
        entry = next(
            row for row in map(json.loads, CATALOG.read_text().splitlines())
            if row["name"] == "SDAR-30B-A3B-Chat"
        )
        assert spec["source"] == entry["source_url"]
        differs = {k for k, v in entry["config"].items() if spec.get(k, "absent") != v}
        assert differs == {"num_hidden_layers"} and entry["config"]["num_hidden_layers"] == 48
    # no width is cut
    assert (spec["hidden_size"], spec["moe_intermediate_size"], spec["head_dim"]) == (2048, 768, 128)
    assert (spec["num_attention_heads"], spec["num_key_value_heads"]) == (32, 4)
    assert (spec["num_experts"], spec["num_experts_per_tok"], spec["vocab_size"]) == (128, 8, 151936)
    assumed = spec["assumed"]
    assert {"block_length", "denoising_steps", "confidence_threshold", "mask_token_id",
            "remasking", "qk_norm", "mask_logit", "open_positions", "sources"} <= set(assumed)
    assert all(len(why) > 40 for why in assumed["sources"].values())
    assert "four pipeline stages" in spec["deployment"] and "four times" in spec["deployment"]
    row = next(c for c in BENCH["configs"] if c["name"] == REAL)
    assert row["reduced"] == spec["reduced"] and row["source"] == spec["source"]
    # the check's sample: tails 0, 1, 2, 3 inside the cell's buckets, whole blocks wide
    check = spec["check"]
    assert sorted(n % 4 for n in check["lengths"]) == [0, 1, 2, 3]
    assert max(check["lengths"]) <= 256 and check["width"] % 128 == 0
    assert check["width"] >= max(check["lengths"]) + check["new_tokens"] + 3
    assert 4 <= check["new_tokens"] <= 12 and "tol_choice" in check
    assert (check["kv_dtype"], check["weights"], check["router_dtype"]) == ("bfloat16", "int8", "float32")


def test_the_cell_is_sized_as_the_issue_says():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "decode-drain-1200", 1)
    assert "1.25 passes a token" in cell["why"] and len(cell["why"]) <= 200
    engine = load_json("workloads", CELL)["engine"]
    assert (engine["max-batch"], engine["prefill-buckets"]) == (64, [64, 128, 256])
    # 256 + 384 and the last block's overshoot, in pages of 64
    assert engine["max-seq-len"] == 704 >= 256 + 384 + 3 and engine["kv-pages"] == 64 * 11
    traffic, half = load_json("traffic", "decode-drain-1200"), load_json("traffic", "decode-drain")
    assert traffic["backlog_records"] == 1200 == 2 * half["backlog_records"]
    assert {k: traffic[k] for k in ("kind", "prompt_tokens", "output_caps")} == {
        k: half[k] for k in ("kind", "prompt_tokens", "output_caps")}
    reports = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reports == {
        "block_pass_device_ms.drain", "block_row_passes_per_token.drain",
        "attention_ms_per_block_pass.drain", "moe_ffn_ms_per_block_pass.drain",
        "head_ms_per_block_pass.drain", "block_attn_roofline.drain",
        "block_moe_grouped_matmul_roofline.drain", "active_slots_mean", "kv_pages_peak_share",
        "device_unfed_with_request_share.drain",
        # its admission groups (spans of the whole window: a 2 s profile of this
        # cell holds block chunks only, so the trace's prefill metrics stay out)
        "prefill_useful_token_share.drain", "prefill_group_ready_ms.drain",
        "block_moe_dropped_assignment_share.drain",
    }
    assert all(m["moves"] == "gen_tokens_per_s" for m in BENCH["per_layer"] if m["name"] in reports)
    ends = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert ends == {"gen_tokens_per_s", "tpot_p95_ms", "setup_s"}


@pytest.mark.parametrize(
    "change, says",
    [
        ({"model_type": "qwen3_moe"}, "model_type"),
        ({"attention_bias": True}, "attention_bias"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
        ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
        ({"mlp_only_layers": [0]}, "mlp_only_layers"),
        ({"use_sliding_window": True}, "use_sliding_window"),
        ({"sliding_window": 4096}, "sliding_window"),
        ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"hidden_act": "gelu"}, "hidden_act"),
        ({"shared_expert_intermediate_size": 768}, "shared_expert_intermediate_size"),
    ],
    ids=lambda v: v if isinstance(v, str) else next(iter(v)),
)
def test_what_the_program_cannot_express_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        model_config({**load_json("configs", TINY, DATA), **change}, TINY)


def test_the_dims_read_back_from_the_config_are_the_file_s():
    for name, root in ((REAL, None), (TINY, DATA)):
        spec = load_json("configs", name, *([root] if root else []))
        config, dims = model_config(spec, name), family.reference_dims(spec)
        assert (dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]) == (
            config.n_heads, config.n_kv_heads, config.resolved_head_dim)
        assert (dims["top_k"], dims["eps"], dims["rope_theta"]) == (
            config.n_experts_per_tok, config.rms_norm_eps, config.rope_theta)
        assert (dims["block_length"], dims["denoising_steps"], dims["mask_token_id"]) == (
            config.block_length, config.denoise_steps, config.mask_token_id)


def test_the_seeded_tree_is_the_served_layout():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    layers = tree["layers"]
    n, e, d, f = config.n_layers, config.n_experts, config.d_model, config.expert_d_ff
    assert layers["w_gate"]["q"].shape == (n, e, d, f) and layers["w_gate"]["q"].dtype == jnp.int8
    assert layers["w_down"]["q"].shape == (n, e, f, d) and layers["w_down"]["s"].shape == (n, e, 1, d)
    assert layers["router"].shape == (n, d, e) and layers["router"].dtype == jnp.float32
    assert layers["q_norm"].shape == layers["k_norm"].shape == (n, config.resolved_head_dim)
    assert tree["lm_head"]["q"].shape == (d, config.vocab_size) and tree["embed"].dtype == jnp.bfloat16
    again = family.make_params(config, 0)
    assert all(jnp.array_equal(a, b) for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(again)))
    other = family.make_params(config, 1)
    assert not jnp.array_equal(tree["layers"]["wq"]["q"], other["layers"]["wq"]["q"])


# -- the passes, from tokens and labels alone --------------------------------------


def test_a_trajectory_is_rebuilt_from_tokens_labels_and_the_undelivered_rest():
    spec = load_json("configs", TINY, DATA)
    mask = spec["assumed"]["mask_token_id"]
    prompt = [10, 11, 12, 13, 14, 15]  # a whole block and a tail of two
    # 5 tokens delivered of 6 generated: the first block's two, the second's four
    result = SimpleNamespace(tokens=[20, 21, 30, 31, 32], fix_steps=[1, 0, 3, 0, 1],
                             block_rest=([33], [0]))
    passes = family.trajectory(spec, prompt, result)
    assert [p["read"] for p in passes] == [[7], [6], [], [9, 11], [10], [8], []]
    assert passes[0]["tokens"] == prompt + [mask, mask] and passes[0]["open"] == [6, 7]
    assert passes[1]["tokens"] == prompt + [mask, 21] and passes[1]["picked"] == [20]
    assert passes[2] == {"tokens": prompt + [20, 21], "read": [], "picked": []}
    assert passes[3]["tokens"] == prompt + [20, 21] + [mask] * 4 and passes[3]["picked"] == [31, 33]
    assert passes[4]["open"] == [8, 10] and passes[5]["tokens"][-4:] == [mask, 31, 32, 33]
    assert passes[6]["tokens"] == prompt + [20, 21, 30, 31, 32, 33]
    # a step that fixed nothing made no pass (a block clean after two steps)
    quick = SimpleNamespace(tokens=[20, 21], fix_steps=[0, 0], block_rest=([], []))
    assert [p["read"] for p in family.trajectory(spec, prompt, quick)] == [[6, 7], []]
    with pytest.raises(ValueError, match="whole block"):
        family.trajectory(spec, prompt, SimpleNamespace(tokens=[20], fix_steps=[0], block_rest=([], [])))


def test_the_choice_score_is_the_log_of_the_largest_probability():
    logits = jnp.log(jnp.asarray([[0.7, 0.2, 0.1], [0.4, 0.35, 0.25]]))
    assert jnp.allclose(family.choice_score(logits), jnp.log(jnp.asarray([0.7, 0.4])), atol=1e-6)


# -- the costs and the metric files, against hand counts --------------------------------


def test_the_block_attention_cost_counts_one_walk_a_row_and_pass():
    sizes = dict(rows=64, block_length=4, n_heads=32, n_kv_heads=4, head_dim=128)
    # one layer, one pass: 64 rows at 400 tokens each
    work = block_attention_cost.block_attention(64 * 400, passes=1, steps=1, calls=1, **sizes)
    k_and_v = 64 * 400 * 2 * 4 * 128 * 2
    q_and_out = 2 * 64 * 4 * 32 * 128 * 2
    assert work["bytes"] == k_and_v + q_and_out == 52_428_800 + 4_194_304
    assert work["ops"] == 4 * 64 * 400 * 4 * 32 * 128
    # 16 passes of 12 layers: calls / passes layers of the same work
    chunk = block_attention_cost.block_attention(16 * 64 * 400, passes=16, steps=1, calls=192, **sizes)
    assert chunk["bytes"] == 12 * 16 * work["bytes"] and chunk["ops"] == 12 * 16 * work["ops"]


def test_the_metric_files_read_the_block_chunks_span():
    names = ("block_pass_device_ms", "block_row_passes_per_token", "attention_ms_per_block_pass",
             "moe_ffn_ms_per_block_pass", "head_ms_per_block_pass", "block_attn_roofline",
             "block_moe_grouped_matmul_roofline")
    files = {name: load_json("layer_metrics", name) for name in names}
    for name in names[2:5]:
        assert (files[name]["program"], files[name]["span"], files[name]["per"]) == (
            "_paged_block_chunk", "engine.block_chunk", "passes")
    assert files["head_ms_per_block_pass"]["scopes"] == ["head", "block_choice"]
    roofline = files["block_moe_grouped_matmul_roofline"]["roofline"]
    assert roofline["sizes"] == {"d_model": 2048, "d_ff": 768}
    assert roofline["span_attrs"] == ["moe_local", "moe_touched"]
    # a pass of 256 positions x top-8 over all 128 experts of 12 layers, weight-bound
    work = grouped_matmul_cost.grouped_matmul(
        moe_local=12 * 2048, moe_touched=12 * 128, steps=1, calls=36, **roofline["sizes"])
    assert work["bytes"] == 12 * 128 * 3 * 2048 * 768 + 12 * 2048 * 3 * (2048 + 768) * 2
    assert files["block_attn_roofline"]["roofline"]["span_attrs"] == ["kv_tokens_read", "passes"]
    assert files["block_attn_roofline"]["scopes"] == ["ragged_paged_block_attention"]
    # the two ratios over the spans of a window: the schedule's worst case is 1.25
    spans = [{"name": "engine.block_chunk", "attributes": a} for a in (
        {"passes": 16, "device_ms": 240.0, "row_passes": 960, "tokens_fixed": 768},
        {"passes": 16, "device_ms": 256.0, "row_passes": 958, "tokens_fixed": 766},
        {"passes": 16, "device_ms": 250.0},  # not landed in the window: no counts yet
    )]
    ctx = {"spans": spans}
    assert span_ratio.read(files["block_pass_device_ms"], ctx) == pytest.approx(746.0 / 48)
    assert span_ratio.read(files["block_row_passes_per_token"], ctx) == pytest.approx(1918 / 1534)


# -- the check's verdicts (an engine a case: by hand) --------------------------------------------


ENGINE = {"max-batch": 8, "max-seq-len": 128, "prefill-buckets": [32, 64], "kv-pages": 64,
          "page-size": 16, "decode-chunk": 4}


def _engine(config, params):
    from langstream_tpu.serving.engine import ServingEngine

    engine = ServingEngine(
        config, params, max_batch=ENGINE["max-batch"], max_seq_len=ENGINE["max-seq-len"],
        prefill_buckets=tuple(ENGINE["prefill-buckets"]), kv_pages=ENGINE["kv-pages"],
        page_size=ENGINE["page-size"], decode_chunk=ENGINE["decode-chunk"],
    )
    engine.start()
    engine.wait_ready()
    return engine


def test_sound_system_passes_with_room():
    spec = load_json("configs", TINY, DATA)
    config = model_config(spec, TINY)
    engine = _engine(config, family.make_params(config, 0))
    try:
        verdict = run_check(engine, spec)
    finally:
        engine.stop()
    assert verdict["ok"], verdict["compared"]
    check = spec["check"]
    assert verdict["layer_err_median"] < 0.5 * check["tol_med"]
    assert verdict["hot_err_max_unexposed"] < 0.5 * check["tol_hot_max"]
    assert verdict["engine_margin_max"] < 0.5 * check["tol_margin"]
    assert verdict["engine_choice_behind_max"] < 0.5 * check["tol_choice"]
    assert verdict["engine_positions_tie_exposed"] < 0.25 * verdict["engine_positions"]


def test_known_fault_fails_by_a_number():
    """The engine serves a tree whose router the reference does not have:
    rounded to bfloat16 and a tenth larger, so the same experts get sharper
    weights (the rounding alone flips two tokens at gaps the tiny file's
    `eps_router` excuses; `tests/test_block_diffusion.py` holds a bf16
    product at an `eps_router` of level 1's own)."""
    spec = load_json("configs", TINY, DATA)
    config = dataclasses.replace(model_config(spec, TINY), name="tiny-sdar-faulted")
    params = family.make_params(config, 0)
    served = {**params, "layers": {
        **params["layers"],
        "router": params["layers"]["router"].astype(jnp.bfloat16).astype(jnp.float32) * 1.1,
    }}
    engine = _engine(config, served)
    try:
        verdict = run_check(engine, spec, ref_params=params)
    finally:
        engine.stop()
    assert not verdict["ok"]
    assert verdict["compared"]["layer_err_over_tol_untied"][0] > 0

"""The Keye-VL-2.0 family (`keye_vl2`) held to the README's contract ("A
family"), its configuration to the catalog and the stated cut, its cell to the
issue's sizes, its cost functions and metric files to hand counts, and its
correctness check to a verdict, sound and faulted, at a tiny size on the CPU.

The fast cases here (everything but the check's verdicts) are also run by the
repo's tier-1 through `tests/test_benchmark_families.py`, whose cases share
one namespace: every name here says `keye`. The program against the reference
is tier-1's own (`tests/test_sparse_attention.py`, `tests/test_sparse_engine.py`).
The verdict cases drive the whole command's check at the tiny cell's knobs,
by hand.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from check import run_check
from modelcfg import load_json, load_module, model_config
from readers import span_ratio
from reduce import grouped_matmul_cost, sparse_attention_cost

DATA = Path(__file__).parent / "data"
TINY = "tiny-keye"
REAL = "keye-vl-2.0-30b-a3b-int8-d12"
CELL = "keyevl2-d12-longdoc-drain"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
family = load_module("families", "keye_vl2")
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())


# -- the README's contract ----------------------------------------------------


def test_the_keye_family_exports_what_the_readme_lists():
    for name in ("model_config", "make_params", "reference_dims", "system_chain",
                 "ref_layer_params", "hot_path", "engine_state", "expected_kernels",
                 "state_leaves"):
        assert callable(getattr(family, name)), name
    # an engine that yields one token a row and a step: none of the optional three
    assert callable(family.hot_path.logits)
    assert not any(hasattr(family, name) for name in ("trajectory", "choice_score"))
    reference = load_module("reference", "keye_vl2")
    assert all(callable(getattr(reference, name)) for name in ("embed", "layer", "unembed"))
    # the equations and each departure stand in the reference's docstring
    for said in ("RMSNorm_128", "mrope_section", "LayerNorm_64", "relu(qI[t, j] . kI[s])",
                 "min(t + 1, 2048)", "tie to the", "s in S_t", "norm_topk_prob", "Departures",
                 "one indexer head at a time", "-0.0", "eps_select", "Text only"):
        assert said in reference.__doc__, said


def test_the_real_keye_configuration_maps_onto_its_fields():
    config = model_config(load_json("configs", REAL), REAL)
    assert (config.d_model, config.n_layers, config.vocab_size) == (2048, 12, 151936)
    assert (config.n_heads, config.n_kv_heads, config.resolved_head_dim) == (32, 4, 128)
    assert (config.n_experts, config.n_experts_per_tok, config.expert_d_ff) == (128, 8, 768)
    assert (config.d_ff, config.held_experts, config.holds_experts) == (6144, (0, 128), True)
    assert (config.rope_theta, config.rms_norm_eps, config.activation) == (1e7, 1e-6, "silu")
    assert config.qk_norm_heads and not config.qk_norm and not config.tie_embeddings
    assert (config.index_n_heads, config.index_head_dim, config.index_topk) == (16, 64, 2048)
    assert config.mrope_section == (16, 24, 24) and config.page_leaves == ("k", "v", "ik")
    assert config.has_indexer and not config.fills_blocks and not config.layer_pattern
    # a token of the page pool: K and V 24 KiB, the indexer's key 1.5 KiB,
    # kept at a whole 128-lane row: 3 KiB
    assert config.index_key_width == 128
    token = 12 * 2 * 4 * 128 * 2 + 12 * config.index_key_width * 2
    assert token == 27 * 1024


def test_the_keye_published_keys_are_the_catalog_s_and_the_cut_is_stated():
    spec = load_json("configs", REAL)
    assert spec["reduced"] == ["num_hidden_layers"] and spec["num_hidden_layers"] == 12
    if CATALOG.is_file():
        entry = next(
            row for row in map(json.loads, CATALOG.read_text().splitlines())
            if row["name"] == "Keye-VL-2.0-30B-A3B"
        )
        assert spec["source"] == entry["source_url"]
        differs = {k for k, v in entry["config"].items() if spec.get(k, "absent") != v}
        assert differs == {"num_hidden_layers"} and entry["config"]["num_hidden_layers"] == 48
        # the nested groups whole
        assert spec["sa_config"] == entry["config"]["sa_config"]
        assert spec["rope_scaling"] == entry["config"]["rope_scaling"]
    # no width is cut
    assert (spec["hidden_size"], spec["moe_intermediate_size"], spec["head_dim"]) == (2048, 768, 128)
    assert (spec["num_attention_heads"], spec["num_key_value_heads"]) == (32, 4)
    assert (spec["num_experts"], spec["num_experts_per_tok"], spec["vocab_size"]) == (128, 8, 151936)
    assert (spec["sa_config"]["indexer_num_heads"], spec["sa_config"]["indexer_head_dim"],
            spec["sa_config"]["topk"]) == (16, 64, 2048)
    assumed = spec["assumed"]
    assert {"qk_norm", "indexer_input", "indexer_key_norm", "indexer_rotary", "indexer_score",
            "selection", "chunk_sizes", "scope", "sources"} <= set(assumed)
    assert all(len(why) > 40 for why in assumed["sources"].values())
    assert "four pipeline stages" in spec["deployment"] and "four times" in spec["deployment"]
    row = next(c for c in BENCH["configs"] if c["name"] == REAL)
    assert row["reduced"] == spec["reduced"] and row["source"] == spec["source"]
    # the check's sample: ONE prompt under the top-k (a dense control), the
    # others past it and past a segment boundary, inside the chain's width
    check = spec["check"]
    # (the width is what check.py's three [width, 151936] float32 logits leave
    # room for beside the engine: the file's `says`)
    assert sum(n < 2048 for n in check["lengths"]) == 1
    assert all(2048 + 32 <= n <= 6144 for n in check["lengths"] if n >= 2048)
    assert check["width"] % 128 == 0 and check["width"] >= max(check["lengths"]) + check["new_tokens"]
    assert "eps_select" not in check  # no query is excused for a selection near a tie
    assert 4 <= check["new_tokens"] <= 12
    assert (check["kv_dtype"], check["index_key_dtype"], check["weights"], check["router_dtype"],
            check["index_weight_dtype"]) == ("bfloat16", "bfloat16", "int8", "float32", "float32")


def test_the_keye_cell_is_sized_as_the_issue_says():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "longdoc-drain", 1)
    assert len(cell["why"]) <= 200
    engine = load_json("workloads", CELL)["engine"]
    # no schedule key: the engine's defaults. `queue-depth` holds the backlog whole and
    # `inflight-records` lets the agent hand it all on (`sizing`: the runner's bound in
    # batches held six or seven requests in flight for 34 s of the window)
    assert engine == {"max-batch": 8, "max-seq-len": 17408, "prefill-buckets": [2048],
                      "prefill-batch": 1, "kv-pages": 2176, "queue-depth": 640,
                      "inflight-records": 640}
    assert engine["max-seq-len"] == 16384 + 1024 == 272 * 64 and engine["kv-pages"] == 8 * 272
    traffic = load_json("traffic", "longdoc-drain")
    assert traffic["kind"] == "topic_drain" and traffic["backlog_records"] >= 160
    assert traffic["prompt_tokens"] == {"dist": "uniform", "min": 8192, "max": 16384}
    assert traffic["output_caps"] == {"1024": 1.0}
    reports = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reports == {
        "indexer_ms_per_step.drain", "sparse_select_ms_per_step.drain",
        "sparse_attn_ms_per_step.drain", "sparse_ms_per_1k_segment_tokens.drain",
        "kv_selected_share.drain", "sparse_decode_attn_roofline.drain",
        "indexer_score_roofline.drain", "sparse_segment_attn_roofline.drain",
        "moe768_grouped_matmul_roofline.drain", "active_slots_mean", "kv_pages_peak_share",
        "device_unfed_with_request_share.drain", "decode_step_device_ms.drain",
        "attention_ms_per_step.drain", "ffn_ms_per_step.drain", "head_ms_per_step.drain",
        "kv_pool_copy_ms_per_step.drain", "prefill_segment_ms_per_1k_tokens.drain",
        "attention_ms_per_1k_segment_tokens.drain", "moe_ffn_ms_per_1k_segment_tokens.drain",
        "moe_dropped_assignment_share",
    }
    assert all(m["moves"] == "gen_tokens_per_s" for m in BENCH["per_layer"] if m["name"] in reports)
    ends = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert ends == {"gen_tokens_per_s", "setup_s"}


@pytest.mark.parametrize(
    "change, says",
    [
        ({"model_type": "qwen3_moe"}, "model_type"),
        ({"attention_bias": True}, "attention_bias"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
        ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
        ({"use_sliding_window": True}, "use_sliding_window"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"num_local_experts": 8}, "num_local_experts"),
        ({"sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 2, "indexer_num_kv_heads": 2,
                        "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 8}},
         "indexer_num_kv_heads"),
        ({"sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 2, "indexer_num_kv_heads": 1,
                        "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 8, "block_size": 64}},
         "block_size"),
        ({"rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "yarn", "type": "yarn"}},
         "rope_type"),
        ({"rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default",
                           "factor": 4.0}}, "factor"),
        ({"vision_config": {"depth": 27}}, "vision_config"),
    ],
    ids=lambda v: v if isinstance(v, str) else next(iter(v)),
)
def test_what_the_keye_block_cannot_express_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        model_config({**load_json("configs", TINY, DATA), **change}, TINY)


def test_the_keye_dims_read_back_from_the_config_are_the_file_s():
    for name, root in ((REAL, None), (TINY, DATA)):
        spec = load_json("configs", name, *([root] if root else []))
        config, dims = model_config(spec, name), family.reference_dims(spec)
        assert (dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]) == (
            config.n_heads, config.n_kv_heads, config.resolved_head_dim)
        assert (dims["top_k"], dims["eps"], dims["rope_theta"]) == (
            config.n_experts_per_tok, config.rms_norm_eps, config.rope_theta)
        assert (dims["index_n_heads"], dims["index_head_dim"], dims["index_topk"]) == (
            config.index_n_heads, config.index_head_dim, config.index_topk)
        assert tuple(dims["mrope_section"]) == config.mrope_section
        assert dims["eps_select"] == spec["check"].get("eps_select", 0.0)


def test_the_keye_seeded_tree_is_the_served_layout():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    layers = tree["layers"]
    n, e, d, f = config.n_layers, config.n_experts, config.d_model, config.expert_d_ff
    hi, di = config.index_n_heads, config.index_head_dim
    assert layers["w_gate"]["q"].shape == (n, e, d, f) and layers["w_gate"]["q"].dtype == jnp.int8
    assert layers["wq_idx"]["q"].shape == (n, d, hi * di) and layers["wq_idx"]["q"].dtype == jnp.int8
    assert layers["wk_idx"]["q"].shape == (n, d, di) and layers["wk_idx"]["s"].shape == (n, 1, di)
    assert layers["w_idx"].shape == (n, d, hi) and layers["w_idx"].dtype == jnp.float32
    assert layers["idx_norm"].shape == layers["idx_bias"].shape == (n, di)
    assert layers["router"].shape == (n, d, e) and layers["router"].dtype == jnp.float32
    assert tree["lm_head"]["q"].shape == (d, config.vocab_size) and tree["embed"].dtype == jnp.bfloat16
    # the program's own tree has the same leaves
    from langstream_tpu.models.quant import init_random_quantized_params

    own = jax.eval_shape(lambda k: init_random_quantized_params(config, k), jax.random.PRNGKey(0))
    assert jax.tree.structure(own) == jax.tree.structure(tree)
    assert jax.tree.map(lambda a: a.shape, own) == jax.tree.map(lambda a: a.shape, tree)
    again = family.make_params(config, 0)
    assert all(jnp.array_equal(a, b) for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(again)))
    other = family.make_params(config, 1)
    assert not jnp.array_equal(layers["wq_idx"]["q"], other["layers"]["wq_idx"]["q"])


# -- the costs and the metric files, against hand counts --------------------------------


def test_the_keye_costs_follow_what_was_scored_and_selected():
    sizes = dict(n_heads=32, n_kv_heads=4, head_dim=128, layers=12)
    # a chunk of 8 steps over 8 rows past the top-k: 2,048 tokens a (row, step)
    read = sparse_attention_cost.sparse_decode_attention(
        kv_tokens_selected=8 * 8 * 2048, active_rows=8, steps=8, calls=999, **sizes)
    k_and_v = 8 * 8 * 2048 * 2 * 4 * 128 * 2  # 2 KiB a token
    q_and_out = 2 * 8 * 8 * 32 * 128 * 2
    assert read["bytes"] == 12 * (k_and_v + q_and_out)
    assert read["ops"] == 12 * 4 * 8 * 8 * 2048 * 32 * 128
    # the scores of those steps at 12,000 tokens a row: 128 B of key a token
    scored = sparse_attention_cost.index_scores(
        index_tokens_scored=8 * 8 * 12000, steps=8, calls=1, index_n_heads=16,
        index_head_dim=64, layers=12)
    assert scored["bytes"] == 12 * 8 * 8 * 12000 * (128 + 4)
    assert scored["ops"] == 12 * 2 * 8 * 8 * 12000 * 16 * 64
    # a 2,048-token segment at offset 8,192: every query selects 2,048
    walk = sparse_attention_cost.sparse_segment_attention(
        kv_tokens_selected=2048 * 2048, real_tokens=2048, offset=8192, steps=1, calls=12, **sizes)
    assert walk["ops"] == 12 * 4 * 2048 * 2048 * 32 * 128
    assert walk["bytes"] == 12 * 2 * (2 * 2048 * 32 * 128 + 2 * 4 * 128 * (8192 + 2048))


def test_the_keye_metric_files_read_the_spans_and_scopes_the_program_has():
    from langstream_tpu.models.transformer import SCOPES

    step = {name: load_json("layer_metrics", name) for name in (
        "indexer_ms_per_step", "sparse_select_ms_per_step", "sparse_attn_ms_per_step")}
    for definition in step.values():
        assert (definition["program"], definition["span"], definition["per"]) == (
            "_paged_decode_chunk", "engine.decode_chunk", "steps")
    assert [d["scopes"] for d in step.values()] == [
        ["attention.index"], ["attention.select"], ["attention.sparse"]]
    segment = load_json("layer_metrics", "sparse_ms_per_1k_segment_tokens")
    assert segment["scopes"] == ["attention.index", "attention.select", "attention.sparse"]
    assert (segment["program"], segment["per"]) == ("_paged_segment_and_sample", "computed_tokens")
    rooflines = {name: load_json("layer_metrics", name) for name in (
        "sparse_decode_attn_roofline", "indexer_score_roofline", "sparse_segment_attn_roofline",
        "moe768_grouped_matmul_roofline")}
    assert rooflines["indexer_score_roofline"]["scopes"] == ["attention.index.scores"]
    assert rooflines["sparse_segment_attn_roofline"]["scopes"] == ["sparse_segment_attention"]
    assert rooflines["moe768_grouped_matmul_roofline"]["roofline"]["sizes"] == {
        "d_model": 2048, "d_ff": 768}
    for definition in (*step.values(), segment, rooflines["sparse_decode_attn_roofline"],
                       rooflines["indexer_score_roofline"]):
        assert set(definition["scopes"]) <= set(SCOPES)
    # a decode step of 8 rows x top-8 that touches 50 experts of each of 12 layers
    work = grouped_matmul_cost.grouped_matmul(
        moe_local=12 * 64, moe_touched=12 * 50, steps=1, calls=24,
        **rooflines["moe768_grouped_matmul_roofline"]["roofline"]["sizes"])
    assert work["bytes"] == 12 * 50 * 3 * 2048 * 768 + 12 * 64 * 3 * (2048 + 768) * 2
    # the share read over decode chunks and segments together
    share = load_json("layer_metrics", "kv_selected_share")
    spans = [
        {"name": "engine.decode_chunk", "attributes": {
            "index_tokens_scored": 8 * 12000, "kv_tokens_selected": 8 * 2048}},
        {"name": "engine.prefill_segment", "attributes": {
            "index_tokens_scored": 2048 * 9000, "kv_tokens_selected": 2048 * 2048}},
        {"name": "engine.prefill_segment", "attributes": {"real_tokens": 5}},  # another model's
    ]
    assert span_ratio.read(share, {"spans": spans}) == pytest.approx(
        100 * (8 * 2048 + 2048 * 2048) / (8 * 12000 + 2048 * 9000))


# -- the check's verdicts (an engine a case: by hand) --------------------------------------------


def _engine(config, params):
    from langstream_tpu.serving.engine import ServingEngine

    knobs = load_json("workloads", "tiny-keye-drain", DATA)["engine"]
    engine = ServingEngine(
        config, params, max_batch=knobs["max-batch"], max_seq_len=knobs["max-seq-len"],
        prefill_buckets=tuple(knobs["prefill-buckets"]), kv_pages=knobs["kv-pages"],
        page_size=knobs["page-size"], decode_chunk=knobs["decode-chunk"],
        prefill_batch=knobs["prefill-batch"],
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


def test_known_fault_fails_by_a_number(monkeypatch):
    """The program attends to the most RECENT top-k keys in place of the
    ranked ones: the chain is the program's block, so level 1 reads it, at
    positions whose selection the reference holds apart from the next key by
    more than the tiny file's `eps_select` (`dev/keye_check_faults.py --tiny`
    runs this and seven more; a lost indexer key of a decode step is excused
    whole at this size, where 15 of 18 generated positions stand within
    `eps_select` of a tie)."""
    from langstream_tpu.models import transformer as program

    spec = load_json("configs", TINY, DATA)
    config = dataclasses.replace(model_config(spec, TINY), name="tiny-keye-recent-keys")
    select = program._select_mask

    def recent(scores, visible, k):
        place = jnp.broadcast_to(jnp.arange(scores.shape[-1], dtype=jnp.float32), scores.shape)
        return select(place, visible, k)

    monkeypatch.setattr(program, "_select_mask", recent)
    engine = _engine(config, family.make_params(config, 0))
    try:
        verdict = run_check(engine, spec)
    finally:
        engine.stop()
    assert not verdict["ok"]
    assert verdict["compared"]["layer_err_over_tol_untied"][0] > 0

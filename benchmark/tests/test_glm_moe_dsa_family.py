"""The GLM-5 family (`glm_moe_dsa`) held to the README's contract ("A
family"), its configuration to the catalog and the stated cut, its cell to the
issue's sizes, its cost functions and metric files to hand counts, and its
correctness check to a verdict, sound and faulted, at a tiny size on the CPU.

The fast cases here (everything but the check's verdicts) are also run by the
repo's tier-1 through `tests/test_benchmark_families.py`, whose cases share
one namespace: every name here says `glm`. The program against the reference
is tier-1's own (`tests/test_latent_attention.py`, `tests/test_latent_engine.py`).
The verdict cases drive the whole command's check at the tiny cell's knobs,
by hand (`dev/glm_check_faults.py --tiny` runs them and more).
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
from reduce import grouped_matmul_cost, latent_attention_cost, sparse_attention_cost

DATA = Path(__file__).parent / "data"
TINY = "tiny-glm"
REAL = "glm-5-int8-ep16-d7"
CELL = "glm5-ep16-d7-longdoc-drain"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUT = {"num_hidden_layers": 78, "first_k_dense_replace": 3, "n_routed_experts": 256,
       "vocab_size": 154880}
family = load_module("families", "glm_moe_dsa")
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())


# -- the README's contract ----------------------------------------------------


def test_the_glm_family_exports_what_the_readme_lists():
    for name in ("model_config", "make_params", "reference_dims", "system_chain",
                 "ref_layer_params", "hot_path", "engine_state", "expected_kernels",
                 "state_leaves"):
        assert callable(getattr(family, name)), name
    assert callable(family.hot_path.logits)
    assert not any(hasattr(family, name) for name in ("trajectory", "choice_score"))
    reference = load_module("reference", "glm_moe_dsa")
    assert all(callable(getattr(reference, name)) for name in ("embed", "layer", "unembed"))
    # the equations and each departure stand in the reference's docstring
    for said in ("RMSNorm_2048", "RMSNorm_512", "ONE 64-wide key", "LayerNorm_128", "QUERY LATENT",
                 "the FIRST 64", "relu(qI[t, j] .", "min(t + 1, 2048)", "tie to the",
                 "sqrt(256)", "the bias chooses and", "2.5 *", "Shared(u)", "Departures",
                 "Hadamard", "FP8", "multi-token-prediction", "ONLY the expanded form",
                 "experts_held", "eps_select"):
        assert said in reference.__doc__, said
    # the reference never absorbs: no contraction of a query with the up-projection
    source = Path(reference.__file__).read_text()
    assert "absorb" not in source.split('"""', 2)[2]


def test_the_real_glm_configuration_maps_onto_its_fields():
    config = model_config(load_json("configs", REAL), REAL)
    assert (config.d_model, config.n_layers, config.vocab_size) == (6144, 7, 19360)
    assert (config.n_heads, config.n_kv_heads, config.resolved_head_dim) == (64, 64, 256)
    assert (config.q_lora_rank, config.kv_lora_rank, config.qk_nope_head_dim,
            config.qk_rope_head_dim, config.v_head_dim) == (2048, 512, 192, 64, 256)
    assert (config.n_experts, config.n_experts_per_tok, config.expert_d_ff) == (256, 8, 2048)
    assert (config.d_ff, config.held_experts, config.n_leading_dense) == (12288, (0, 16), 1)
    assert (config.moe_scoring, config.router_bias, config.routed_scaling,
            config.n_shared_experts) == ("sigmoid", True, 2.5, 1)
    assert (config.rope_theta, config.rms_norm_eps, config.rope_dim) == (1e6, 1e-5, 64)
    assert config.rope_interleaved and not config.tie_embeddings
    assert (config.index_n_heads, config.index_head_dim, config.index_topk,
            config.index_rope_dim, config.index_query_input) == (32, 128, 2048, 64, "query_latent")
    assert config.page_leaves == ("lat", "ik") and config.has_latent and config.has_indexer
    # a token of the page pool: the latent's 576 kept at 640 lanes, the indexer's key 128
    assert (config.latent_width, config.latent_key_width, config.index_key_width) == (576, 640, 128)
    assert config.kv_bytes_per_token() == 7 * (640 + 128) * 2 == 10752


def test_the_glm_published_keys_are_the_catalog_s_and_the_cut_is_stated():
    spec = load_json("configs", REAL)
    assert spec["reduced"] == list(CUT)
    assert [spec[k] for k in CUT] == [7, 1, 16, 19360]
    if CATALOG.is_file():
        entry = next(
            row for row in map(json.loads, CATALOG.read_text().splitlines())
            if row["name"] == "GLM-5"
        )
        assert spec["source"] == entry["source_url"]
        differs = {k for k, v in entry["config"].items() if spec.get(k, "absent") != v}
        assert differs == set(CUT)
        assert {k: entry["config"][k] for k in CUT} == CUT
        assert spec["rope_parameters"] == entry["config"]["rope_parameters"]  # the nested group whole
    # no width is cut
    assert (spec["hidden_size"], spec["intermediate_size"], spec["moe_intermediate_size"]) == (
        6144, 12288, 2048)
    assert (spec["q_lora_rank"], spec["kv_lora_rank"], spec["qk_nope_head_dim"],
            spec["qk_rope_head_dim"], spec["v_head_dim"], spec["qk_head_dim"]) == (
        2048, 512, 192, 64, 256, 256)
    assert (spec["num_attention_heads"], spec["num_experts_per_tok"], spec["index_n_heads"],
            spec["index_head_dim"], spec["index_topk"]) == (64, 8, 32, 128, 2048)
    assumed = spec["assumed"]
    assert {"indexer_query", "indexer_key_norm", "indexer_rotary", "indexer_score", "selection",
            "indexer_rotation_and_fp8", "head_dim", "num_key_value_heads", "router",
            "e_score_correction_bias", "inert", "multi_token_prediction", "sources"} <= set(assumed)
    assert all(len(why) > 40 for why in assumed["sources"].values())
    deployment = spec["deployment"]
    assert deployment["chips_a_layer"] == 16
    assert deployment["experts"] == {"published": 256, "first_held": 0, "held": 16}
    assert deployment["layers"]["published"] == 78 and deployment["vocabulary"]["published"] == 154880
    for said in ("16 chips share each layer", "thirteen pipeline stages", "eleven times",
                 "5.70 GB", "10.5 KiB", "no code stands in"):
        assert said in deployment["says"], said
    row = next(c for c in BENCH["configs"] if c["name"] == REAL)
    assert row["reduced"] == spec["reduced"] and row["source"] == spec["source"]
    # the check's sample: ONE prompt under the top-k (the selection is the
    # identity), two of 4,000 to 6,500 tokens (three and four segments)
    check = spec["check"]
    assert sum(n < 2048 for n in check["lengths"]) == 1
    assert sorted(n for n in check["lengths"] if n >= 2048)[0] >= 4000
    assert max(check["lengths"]) <= 6500 and check["new_tokens"] == 8
    assert check["width"] % 128 == 0 and check["width"] >= max(check["lengths"]) + check["new_tokens"]
    assert "eps_select" not in check  # no query is excused for a selection near a tie
    assert (check["kv_dtype"], check["index_key_dtype"], check["weights"], check["router_dtype"],
            check["experts_held"], check["page_leaves"]) == (
        "bfloat16", "bfloat16", "int8", "float32", "0-15 of 256", ["ik", "lat"])
    assert "9.7%" in spec["weights"]["why"]


def test_the_glm_cell_is_sized_as_the_issue_says():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "longdoc-drain", 1)
    assert len(cell["why"]) <= 200
    engine = load_json("workloads", CELL)["engine"]
    assert engine == {"max-batch": 16, "max-seq-len": 17408, "prefill-buckets": [2048],
                      "prefill-batch": 1, "kv-pages": 4352, "queue-depth": 640,
                      "inflight-records": 640}
    assert engine["max-seq-len"] == 16384 + 1024 == 272 * 64 and engine["kv-pages"] == 16 * 272
    reports = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reports == {
        "latent_proj_ms_per_step.drain", "latent_ms_per_1k_segment_tokens.drain",
        "latent_expanded_per_segment_token.drain", "latent_decode_attn_roofline.drain",
        "latent_segment_attn_roofline.drain", "indexer32_score_roofline.drain",
        "moe2048_grouped_matmul_roofline.drain",
        "indexer_ms_per_step.drain", "sparse_select_ms_per_step.drain",
        "sparse_attn_ms_per_step.drain", "sparse_ms_per_1k_segment_tokens.drain",
        "kv_selected_share.drain", "active_slots_mean", "kv_pages_peak_share",
        "device_unfed_with_request_share.drain", "decode_step_device_ms.drain",
        "attention_ms_per_step.drain", "ffn_ms_per_step.drain", "head_ms_per_step.drain",
        "kv_pool_copy_ms_per_step.drain", "prefill_segment_ms_per_1k_tokens.drain",
        "attention_ms_per_1k_segment_tokens.drain", "moe_ffn_ms_per_1k_segment_tokens.drain",
        "moe_shared_ms_per_1k_segment_tokens.drain", "moe_dropped_assignment_share",
        "moe_local_assignment_share",
    }
    assert all(m["moves"] == "gen_tokens_per_s" for m in BENCH["per_layer"] if m["name"] in reports)
    ends = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert ends == {"gen_tokens_per_s", "setup_s"}


@pytest.mark.parametrize(
    "change, says",
    [
        ({"model_type": "deepseek_v3"}, "model_type"),
        ({"attention_bias": True}, "attention_bias"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
        ({"rope_interleave": False}, "rope_interleave"),
        ({"indexer_rope_interleave": False}, "indexer_rope_interleave"),
        ({"scoring_func": "softmax"}, "scoring_func"),
        ({"topk_method": "greedy"}, "topk_method"),
        ({"n_group": 8}, "n_group"),
        ({"moe_layer_freq": 2}, "moe_layer_freq"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers"),
        ({"head_dim": 16}, "head_dim"),
        ({"qk_head_dim": 24}, "qk_head_dim"),
        ({"num_key_value_heads": 1}, "num_key_value_heads"),
        ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}}, "rope_parameters"),
        ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "default", "factor": 4.0}},
         "rope_parameters"),
        ({"index_topk_freq": 4}, "index_topk_freq"),
        ({"v_head_dim": 12}, "v_head_dim 12 apart"),
    ],
    ids=lambda v: v if isinstance(v, str) else next(iter(v)),
)
def test_what_the_glm_block_cannot_express_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        model_config({**load_json("configs", TINY, DATA), **change}, TINY)


def test_the_glm_dims_read_back_from_the_config_are_the_file_s():
    for name, root in ((REAL, None), (TINY, DATA)):
        spec = load_json("configs", name, *([root] if root else []))
        config, dims = model_config(spec, name), family.reference_dims(spec)
        assert (dims["n_heads"], dims["eps"], dims["rope_theta"]) == (
            config.n_heads, config.rms_norm_eps, config.rope_theta)
        assert (dims["kv_lora_rank"], dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
                dims["v_head_dim"]) == (config.kv_lora_rank, config.qk_nope_head_dim,
                                        config.qk_rope_head_dim, config.v_head_dim)
        assert (dims["index_n_heads"], dims["index_head_dim"], dims["index_topk"]) == (
            config.index_n_heads, config.index_head_dim, config.index_topk)
        assert (dims["top_k"], dims["n_experts"], tuple(dims["experts_held"]),
                dims["routed_scaling"]) == (config.n_experts_per_tok, config.n_experts,
                                            config.held_experts, config.routed_scaling)
        assert dims["eps_select"] == spec["check"].get("eps_select", 0.0)


def test_the_glm_seeded_tree_is_the_served_layout():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    dense, layers = tree["dense_layers"], tree["layers"]
    d, held, f = config.d_model, config.held_experts[1], config.expert_d_ff
    assert dense["w_gate"]["q"].shape == (1, d, config.d_ff) and "router" not in dense
    assert layers["w_gate"]["q"].shape == (3, held, d, f) and layers["w_gate"]["q"].dtype == jnp.int8
    assert layers["ws_gate"]["q"].shape == (3, d, f) and layers["ws_down"]["q"].shape == (3, f, d)
    assert layers["router"].shape == (3, d, 8) and layers["router"].dtype == jnp.float32
    assert layers["router_bias"].shape == (3, 8) and layers["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(layers["router_bias"]).max()) > 0.0  # drawn, not zero
    for stack in (dense, layers):
        assert stack["wq_a"]["q"].shape[1:] == (d, 32) and stack["wq_b"]["q"].shape[1:] == (32, 4 * 16)
        assert stack["wkv_a"]["q"].shape[1:] == (d, 24) and stack["wkv_b"]["q"].shape[1:] == (16, 4 * 24)
        assert stack["wq_idx"]["q"].shape[1:] == (32, 2 * 16)  # from the query latent
        assert stack["wk_idx"]["q"].shape[1:] == (d, 16) and stack["w_idx"].dtype == jnp.float32
    assert tree["lm_head"]["q"].shape == (d, config.vocab_size) and tree["embed"].dtype == jnp.bfloat16
    # the program's own tree has the same leaves
    from langstream_tpu.models.quant import init_random_quantized_params

    own = jax.eval_shape(lambda k: init_random_quantized_params(config, k), jax.random.PRNGKey(0))
    assert jax.tree.structure(own) == jax.tree.structure(tree)
    assert jax.tree.map(lambda a: a.shape, own) == jax.tree.map(lambda a: a.shape, tree)
    assert jax.tree.map(lambda a: a.dtype, own) == jax.tree.map(lambda a: a.dtype, tree)
    again = family.make_params(config, 0)
    assert all(jnp.array_equal(a, b) for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(again)))
    other = family.make_params(config, 1)
    assert not jnp.array_equal(layers["wkv_b"]["q"], other["layers"]["wkv_b"]["q"])


def test_the_glm_chain_steps_half_a_layer_and_names_its_kinds():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    assert family.system_chain(config, 64, 1).n_layers == 2 * config.n_layers
    kinds = []
    for step in range(2 * config.n_layers):
        stack, at = family.ref_layer_params(tree, step)
        (kind, leaves), = stack.items()
        kinds.append((kind, at, "wq_a" in leaves, "router" in leaves, "w_gate" in leaves))
    assert kinds[:4] == [("dense", 0, True, False, False), ("dense", 0, False, False, True),
                         ("sparse", 0, True, False, False), ("sparse", 0, False, True, True)]
    assert kinds[-1] == ("sparse", 2, False, True, True)


# -- the costs and the metric files, against hand counts --------------------------------


def test_the_glm_costs_follow_what_was_selected():
    sizes = dict(n_heads=64, latent_width=576, value_width=512, layers=7)
    # a chunk of 8 steps over 16 rows past the top-k: 2,048 latents a (row, step)
    read = latent_attention_cost.latent_decode_attention(
        kv_tokens_selected=16 * 8 * 2048, active_rows=16, steps=8, calls=999, **sizes)
    latents = 16 * 8 * 2048 * 1152  # a token's latent once, for key and value
    q_and_out = 8 * 16 * 64 * (576 + 512) * 2
    assert read["bytes"] == 7 * (latents + q_and_out)
    assert read["ops"] == 7 * 2 * 16 * 8 * 2048 * 64 * (576 + 512)
    assert 110 < read["ops"] / read["bytes"] < 125  # close to a v5e's ridge
    # a 2,048-token segment at offset 8,192: every query selects 2,048
    walk = latent_attention_cost.latent_segment_attention(
        kv_tokens_selected=2048 * 2048, real_tokens=2048, offset=8192, steps=1, calls=7,
        n_heads=64, head_dim=256, latent_width=576, layers=7)
    assert walk["ops"] == 7 * 4 * 2048 * 2048 * 64 * 256
    assert walk["bytes"] == 7 * 2 * (2 * 2048 * 64 * 256 + 576 * (8192 + 2048))
    scored = sparse_attention_cost.index_scores(
        index_tokens_scored=16 * 8 * 12000, steps=8, calls=1,
        **load_json("layer_metrics", "indexer32_score_roofline")["roofline"]["sizes"])
    assert scored["bytes"] == 7 * 16 * 8 * 12000 * (256 + 4)
    assert scored["ops"] == 7 * 2 * 16 * 8 * 12000 * 32 * 128


def test_the_glm_metric_files_read_the_spans_and_scopes_the_program_has():
    from langstream_tpu.models.transformer import SCOPES

    step = load_json("layer_metrics", "latent_proj_ms_per_step")
    assert (step["program"], step["span"], step["per"], step["scopes"]) == (
        "_paged_decode_chunk", "engine.decode_chunk", "steps", ["attention.latent"])
    segment = load_json("layer_metrics", "latent_ms_per_1k_segment_tokens")
    assert segment["scopes"] == ["attention.latent", "attention.latent.expand"]
    assert (segment["program"], segment["per"]) == ("_paged_segment_and_sample", "computed_tokens")
    decode = load_json("layer_metrics", "latent_decode_attn_roofline")
    assert decode["scopes"] == ["attention.sparse"]
    assert decode["roofline"]["sizes"] == {
        "n_heads": 64, "latent_width": 576, "value_width": 512, "layers": 7}
    walk = load_json("layer_metrics", "latent_segment_attn_roofline")
    assert walk["scopes"] == ["sparse_segment_attention"]
    for definition in (step, segment, decode):
        assert set(definition["scopes"]) <= set(SCOPES)
    experts = load_json("layer_metrics", "moe2048_grouped_matmul_roofline")
    assert experts["roofline"]["sizes"] == {"d_model": 6144, "d_ff": 2048}
    # a decode step of 16 rows x top-8 that touches 6 of the 16 held experts of 6 layers
    work = grouped_matmul_cost.grouped_matmul(
        moe_local=6 * 8, moe_touched=6 * 6, steps=1, calls=12, **experts["roofline"]["sizes"])
    assert work["bytes"] == 6 * 6 * 3 * 6144 * 2048 + 6 * 8 * 3 * (6144 + 2048) * 2
    expanded = load_json("layer_metrics", "latent_expanded_per_segment_token")
    spans = [
        {"name": "engine.prefill_segment", "attributes": {
            "latent_tokens_expanded": 0, "computed_tokens": 2048}},
        {"name": "engine.prefill_segment", "attributes": {
            "latent_tokens_expanded": 6144, "computed_tokens": 2048}},
        {"name": "engine.prefill_segment", "attributes": {"computed_tokens": 2048}},  # another model's
        {"name": "engine.decode_chunk", "attributes": {"latent_tokens_expanded": 0, "steps": 8}},
    ]
    assert span_ratio.read(expanded, {"spans": spans}) == pytest.approx(6144 / 4096)
    # a program without the counter (the parent's): nothing to read, no raise
    assert span_ratio.read(expanded, {"spans": spans[2:3]}) is None


# -- the check's verdicts (an engine a case: by hand) --------------------------------------------


def _engine(config, params):
    from langstream_tpu.serving.engine import ServingEngine

    knobs = load_json("workloads", "tiny-glm-drain", DATA)["engine"]
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
    # room at level 1, which is what a fault fails by at this size: 17 of the
    # 18 generated positions stand at a router tie (the tiny file's `says`)
    assert verdict["layer_err_median"] < 0.5 * check["tol_med"]
    assert verdict["compared"]["layer_err_over_tol_untied"] == [0, 0]
    assert verdict["engine_state"]["found"]["page_leaves"] == ["ik", "lat"]


def test_known_fault_fails_by_a_number(monkeypatch):
    """The shared expert left out of the program's expert layer: the chain is
    the program's block, so level 1 reads it at every position of every
    expert half (`dev/glm_check_faults.py --tiny` runs this and more)."""
    from langstream_tpu.models import transformer as program

    spec = load_json("configs", TINY, DATA)
    config = dataclasses.replace(model_config(spec, TINY), name="tiny-glm-no-shared")
    held = program.moe_ffn_held

    def no_shared(x, lp, config, *args, **kwargs):
        return held(x, lp, dataclasses.replace(config, n_shared_experts=0), *args, **kwargs)

    monkeypatch.setattr(program, "moe_ffn_held", no_shared)
    engine = _engine(config, family.make_params(config, 0))
    try:
        verdict = run_check(engine, spec)
    finally:
        engine.stop()
    assert not verdict["ok"]
    assert verdict["compared"]["layer_err_over_tol_untied"][0] > 0

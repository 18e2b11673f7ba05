"""The dots3-note family (`dots3_note`) held to the README's contract ("A
family"), its configuration to the catalog and the stated cut, its cell to the
issue's sizes, its cost functions and metric files to hand counts, and its
correctness check to a verdict, sound and faulted, at a tiny size on the CPU.

The fast cases here (everything but the check's verdicts) are also run by the
repo's tier-1 through `tests/test_benchmark_families.py`, whose cases share
one namespace: every name here says `dots3`. The program against the
reference is tier-1's own (`tests/test_dots3_note.py`). The verdict cases
drive the whole command's check at the tiny cell's knobs, by hand
(`dev/dots3_check_faults.py --tiny` runs them and more).
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from check import run_check
from modelcfg import load_json, load_module, model_config
from reduce import grouped_matmul_cost, latent_attention_cost, latent_kinds_attention_cost

DATA = Path(__file__).parent / "data"
TINY = "tiny-dots3"
REAL = "dots3-note-prev-int8-ep16-d9"
CELL = "dots3-ep16-d9-longdoc-drain"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUT = {"num_hidden_layers": 46, "n_routed_experts": 256, "vocab_size": 152064}
FULL, WINDOW = "full_attention", "sliding_attention"
family = load_module("families", "dots3_note")
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())


# -- the README's contract ----------------------------------------------------


def test_the_dots3_family_exports_what_the_readme_lists():
    for name in ("model_config", "make_params", "reference_dims", "system_chain",
                 "ref_layer_params", "hot_path", "engine_state", "expected_kernels",
                 "state_leaves"):
        assert callable(getattr(family, name)), name
    assert callable(family.hot_path.logits)
    assert not any(hasattr(family, name) for name in ("trajectory", "choice_score"))
    reference = load_module("reference", "dots3_note")
    assert all(callable(getattr(reference, name)) for name in ("embed", "layer", "unembed"))
    for said in ("TWO kinds of layer", "rho_q = sqrt(5120 / r_q)", "NOT rescaled",
                 "513 with itself", "min(t + 1, index_topk)", "RESCALED query latent",
                 "tie to the lower position", "sigmoid(u W_g)", "after the softmax's mix",
                 "sqrt(d_n + d_r)", "no groups", "Shared(u)", "Departures", "vision tower",
                 "audio encoder", "multi-token prediction", "ONLY the expanded form",
                 "experts_held"):
        assert said in reference.__doc__, said
    # the reference never absorbs, keeps no ring and gathers no band
    source = Path(reference.__file__).read_text().split('"""', 2)[2]
    assert "absorb" not in source and "ring" not in source and "band" not in source


def test_the_real_dots3_configuration_maps_onto_its_fields():
    config = model_config(load_json("configs", REAL), REAL)
    window = config.of_kind(WINDOW)
    assert (config.d_model, config.n_layers, config.vocab_size) == (5120, 9, 19008)
    assert config.layer_pattern == (FULL, WINDOW, WINDOW, WINDOW)
    assert (config.dense_ahead, config.n_periods, config.n_leading_dense) == (1, 2, 1)
    assert (config.n_layers_of(FULL), config.n_layers_of(WINDOW)) == (3, 6)
    assert (config.n_heads, config.q_lora_rank, config.kv_lora_rank, config.qk_nope_head_dim,
            config.qk_rope_head_dim, config.v_head_dim, config.rope_theta) == (
        128, 1024, 512, 128, 64, 128, 8e7)
    assert (window.n_heads, window.n_kv_heads, window.q_lora_rank, window.kv_lora_rank,
            window.qk_nope_head_dim, window.qk_rope_head_dim, window.v_head_dim,
            window.rope_theta) == (64, 64, 1024, 1024, 192, 64, 128, 5e4)
    assert (config.sliding_window, window.attn_window, config.attn_window) == (513, 513, 0)
    assert config.attn_scale == 192**-0.5 and window.attn_scale == 256**-0.5
    assert config.latent_rescale and config.attn_gate == window.attn_gate == "headwise"
    assert (config.n_experts, config.n_experts_per_tok, config.expert_d_ff) == (256, 8, 1536)
    assert (config.d_ff, config.held_experts) == (13824, (0, 16))
    assert (config.moe_scoring, config.router_bias, config.routed_scaling,
            config.n_shared_experts) == ("sigmoid", True, 1.0, 1)
    assert config.rope_interleaved and not config.tie_embeddings
    assert (config.index_n_heads, config.index_head_dim, config.index_topk,
            config.index_rope_dim, config.index_query_input) == (64, 128, 2048, 64, "query_latent")
    assert config.page_leaves == ("lat", "ik") and window.page_leaves == ("lat",)
    assert config.latent_kinds and not config.parallel_block and not window.has_indexer
    # a token: the full group's 576 kept at 640 lanes and the indexer's 128;
    # the window group's 1,088 kept at 1,152
    assert (config.latent_width, config.latent_key_width, config.index_key_width) == (576, 640, 128)
    assert (window.latent_width, window.latent_key_width) == (1088, 1152)
    assert config.kv_bytes_per_token() == 3 * (640 + 128) * 2 == 4608
    assert config.kv_bytes_per_token(kind=WINDOW) == 6 * 1152 * 2 == 13824


def test_the_dots3_published_keys_are_the_catalog_s_and_the_cut_is_stated():
    spec = load_json("configs", REAL)
    assert spec["reduced"] == ["num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
    assert [spec[k] for k in CUT] == [9, 16, 19008]
    assert spec["layer_types"] == [FULL, FULL, WINDOW, WINDOW, WINDOW, FULL, WINDOW, WINDOW, WINDOW]
    if CATALOG.is_file():
        entry = next(
            row for row in map(json.loads, CATALOG.read_text().splitlines())
            if row["name"] == "dots3-note-prev"
        )
        assert spec["source"] == entry["source_url"]
        differs = {k for k, v in entry["config"].items() if spec.get(k, "absent") != v}
        assert differs == set(spec["reduced"])
        assert {k: entry["config"][k] for k in CUT} == CUT
        assert spec["layer_types"] == entry["config"]["layer_types"][:9]  # the first nine
    # no width is cut
    assert (spec["hidden_size"], spec["intermediate_size"], spec["moe_intermediate_size"]) == (
        5120, 13824, 1536)
    assert (spec["q_lora_rank"], spec["kv_lora_rank"], spec["qk_nope_head_dim"],
            spec["qk_rope_head_dim"], spec["v_head_dim"]) == (1024, 512, 128, 64, 128)
    assert (spec["swa_q_lora_rank"], spec["swa_kv_lora_rank"], spec["swa_qk_nope_head_dim"],
            spec["swa_qk_rope_head_dim"], spec["swa_v_head_dim"]) == (1024, 1024, 192, 64, 128)
    assert (spec["num_attention_heads"], spec["swa_num_attention_heads"],
            spec["num_experts_per_tok"], spec["index_n_heads"], spec["index_head_dim"],
            spec["index_topk"], spec["sliding_window_size"]) == (128, 64, 8, 64, 128, 2048, 513)
    assumed = spec["assumed"]
    assert {"apply_mla_qkv_lora_rescale", "attention_gate_type", "rotary_pairs", "window",
            "indexer", "num_key_value_heads", "router", "e_score_correction_bias", "inert",
            "vision_tower_and_audio_encoder", "multi_token_prediction", "trailing_full_layer",
            "sources"} <= set(assumed)
    assert all(len(why) > 40 for why in assumed["sources"].values())
    deployment = spec["deployment"]
    assert deployment["chips_a_layer"] == 16
    assert deployment["experts"] == {"published": 256, "first_held": 0, "held": 16}
    assert deployment["layers"]["published"] == 46 and deployment["vocabulary"]["published"] == 152064
    for said in ("16 chips share each layer", "TWO whole periods", "five times", "4.73 GB",
                 "4.5 KiB", "13.5 KiB", "no code stands in"):
        assert said in deployment["says"], said
    row = next(c for c in BENCH["configs"] if c["name"] == REAL)
    assert row["reduced"] == spec["reduced"] and row["source"] == spec["source"]
    # the check's sample: under the window, between the window and the top-k,
    # and two past the top-k that take three and four segments
    check = spec["check"]
    lengths = sorted(check["lengths"])
    assert lengths[0] < 513 <= lengths[1] < 2048 and len(lengths) == 4
    assert [-(-n // 2048) for n in lengths[2:]] == [3, 4] and check["new_tokens"] == 8
    assert check["width"] % 128 == 0 and check["width"] >= max(lengths) + check["new_tokens"]
    assert "eps_select" not in check  # no query is excused for a selection near a tie
    assert (check["kv_dtype"], check["window_kv_dtype"], check["index_key_dtype"],
            check["weights"], check["router_dtype"], check["experts_held"], check["page_leaves"],
            check["window_page_leaves"], check["latent_widths"]) == (
        "bfloat16", "bfloat16", "bfloat16", "int8", "float32", "0-15 of 256", ["ik", "lat"],
        ["lat"], [640, 1152])


def test_the_dots3_cell_is_sized_as_the_issue_says():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "longdoc-drain", 1)
    assert len(cell["why"]) <= 200
    engine = load_json("workloads", CELL)["engine"]
    assert engine == {"max-batch": 16, "max-seq-len": 17408, "prefill-buckets": [2048],
                      "prefill-batch": 1, "kv-pages": 4352, "queue-depth": 640,
                      "inflight-records": 640}
    assert engine["max-seq-len"] == 16384 + 1024 == 272 * 64 and engine["kv-pages"] == 16 * 272
    assert load_json("workloads", CELL)["trace_seconds"] == 20.0
    reports = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])}
    new = {
        "latent_window_ms_per_step.drain", "latent_window_ms_per_1k_segment_tokens.drain",
        "latent_window_decode_attn_roofline.drain", "latent_window_segment_attn_roofline.drain",
        "latent128_decode_attn_roofline.drain", "latent128_segment_attn_roofline.drain",
        "indexer64_score_roofline.drain", "moe5120x1536_grouped_matmul_roofline.drain",
    }
    assert reports == new | {
        "active_slots_mean", "kv_pages_peak_share", "kv_window_pages_peak_share",
        "decode_step_device_ms.drain", "kv_pool_copy_ms_per_step.drain",
        "attention_ms_per_step.drain", "ffn_ms_per_step.drain", "head_ms_per_step.drain",
        "moe_dropped_assignment_share", "moe_local_assignment_share",
        "prefill_segment_ms_per_1k_tokens.drain", "attention_ms_per_1k_segment_tokens.drain",
        "moe_ffn_ms_per_1k_segment_tokens.drain", "moe_shared_ms_per_1k_segment_tokens.drain",
        "indexer_ms_per_step.drain", "sparse_select_ms_per_step.drain",
        "sparse_attn_ms_per_step.drain", "sparse_ms_per_1k_segment_tokens.drain",
        "kv_selected_share.drain", "latent_proj_ms_per_step.drain",
        "latent_ms_per_1k_segment_tokens.drain", "latent_expanded_per_segment_token.drain",
        "device_unfed_with_request_share.drain",
    }
    # the new ones are this cell's alone
    assert all(m["workloads"] == [CELL] for m in BENCH["per_layer"] if m["name"] in new)
    assert all(m["moves"] == "gen_tokens_per_s" for m in BENCH["per_layer"] if m["name"] in reports)
    ends = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert ends == {"gen_tokens_per_s", "setup_s"}


@pytest.mark.parametrize(
    "change, says",
    [
        ({"model_type": "deepseek_v3"}, "model_type"),
        ({"attention_bias": True}, "attention_bias"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
        ({"scoring_func": "softmax"}, "scoring_func"),
        ({"topk_method": "greedy"}, "topk_method"),
        ({"moe_layer_freq": 2}, "moe_layer_freq"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"apply_mla_qkv_lora_rescale": False}, "apply_mla_qkv_lora_rescale"),
        ({"attention_gate_type": "elementwise"}, "attention_gate_type"),
        ({"swa_attention_gate_type": "none"}, "swa_attention_gate_type"),
        ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "rope_scaling"),
        ({"num_key_value_heads": 1}, "num_key_value_heads"),
        ({"swa_num_key_value_heads": 1}, "swa_num_key_value_heads"),
        ({"n_group": 8}, "n_group"),
        ({"num_hidden_layers": 10}, "layer_types"),
        ({"layer_types": [WINDOW] + [FULL, WINDOW, WINDOW, WINDOW] * 2}, "layer_types"),
    ],
    ids=lambda v: v if isinstance(v, str) else next(iter(v)),
)
def test_what_the_dots3_block_cannot_express_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        model_config({**load_json("configs", TINY, DATA), **change}, TINY)


def test_the_published_dots3_layer_list_is_one_period_behind_the_dense_layer():
    """All 46 published layers end on a full layer behind eleven periods of
    four: the program takes the 45 behind the leading dense layer as ONE
    period (no benchmark cell runs it: 288B do not fit the chip)."""
    if not CATALOG.is_file():
        pytest.skip("no catalog here")
    entry = next(
        row for row in map(json.loads, CATALOG.read_text().splitlines())
        if row["name"] == "dots3-note-prev"
    )
    spec = {**load_json("configs", REAL), "layer_types": entry["config"]["layer_types"],
            "num_hidden_layers": 46}
    config = model_config(spec, REAL)
    assert (len(config.layer_pattern), config.n_periods, config.dense_ahead) == (45, 1, 1)
    assert (config.n_layers_of(FULL), config.n_layers_of(WINDOW)) == (13, 33)
    assert family.layer_kinds(config) == tuple(entry["config"]["layer_types"])


def test_the_dots3_dims_read_back_from_the_config_are_the_file_s():
    for name, root in ((REAL, None), (TINY, DATA)):
        spec = load_json("configs", name, *([root] if root else []))
        config, dims = model_config(spec, name), family.reference_dims(spec)
        back = family.dims_of(config)
        assert {k: dims[k] for k in back} == back
        assert dims["eps_select"] == spec["check"].get("eps_select", 0.0)
        assert dims["layer_types"] == family.layer_kinds(config) == tuple(spec["layer_types"])


def test_the_dots3_seeded_tree_is_the_served_layout():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    dense, full, window = (
        tree["dense_layers"][FULL], tree["layers"][FULL], tree["layers"][WINDOW]
    )
    d, held, f = config.d_model, config.held_experts[1], config.expert_d_ff
    assert set(tree["dense_layers"]) == {FULL} and set(tree["layers"]) == {FULL, WINDOW}
    assert dense["w_gate"]["q"].shape == (1, d, config.d_ff) and "router" not in dense
    assert full["w_gate"]["q"].shape == (2, held, d, f) and window["w_gate"]["q"].shape == (6, held, d, f)
    assert full["router"].dtype == jnp.float32 and float(jnp.abs(full["router_bias"]).max()) > 0.0
    # each kind at its own geometry; the indexer the full kind's alone
    assert full["wkv_a"]["q"].shape[1:] == (d, 24) and window["wkv_a"]["q"].shape[1:] == (d, 40)
    assert full["wkv_b"]["q"].shape[1:] == (16, 4 * 16) and window["wkv_b"]["q"].shape[1:] == (32, 2 * 32)
    assert full["wo"]["q"].shape[1:] == (4 * 8, d) and window["wo"]["q"].shape[1:] == (2 * 8, d)
    assert full["w_attn_gate"]["q"].shape[1:] == (d, 4) and window["w_attn_gate"]["q"].shape[1:] == (d, 2)
    assert "wq_idx" in full and "wq_idx" in dense and "wq_idx" not in window
    # the program's own tree has the same leaves
    from langstream_tpu.models.quant import init_random_quantized_params

    own = jax.eval_shape(lambda k: init_random_quantized_params(config, k), jax.random.PRNGKey(0))
    assert jax.tree.structure(own) == jax.tree.structure(tree)
    assert jax.tree.map(lambda a: a.shape, own) == jax.tree.map(lambda a: a.shape, tree)
    assert jax.tree.map(lambda a: a.dtype, own) == jax.tree.map(lambda a: a.dtype, tree)
    again = family.make_params(config, 0)
    assert all(jnp.array_equal(a, b) for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(again)))


def test_the_dots3_chain_steps_half_a_layer_and_names_its_kinds():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    assert family.system_chain(config, 64, 1).n_layers == 2 * config.n_layers
    assert family._tree_places(tree) == family._places(config)
    kinds = []
    for step in range(2 * config.n_layers):
        stack, at = family.ref_layer_params(tree, step)
        (kind, leaves), = stack.items()
        kinds.append((kind, at, "wq_a" in leaves, "wq_idx" in leaves, "router" in leaves,
                      "w_gate" in leaves))
    assert kinds[:6] == [
        ("full_dense", 0, True, True, False, False), ("full_dense", 0, False, False, False, True),
        ("full", 0, True, True, False, False), ("full", 0, False, False, True, True),
        ("window", 0, True, False, False, False), ("window", 0, False, False, True, True),
    ]
    assert kinds[10] == ("full", 1, True, True, False, False)
    assert kinds[-1] == ("window", 5, False, False, True, True)


# -- the costs and the metric files, against hand counts --------------------------------


def test_the_dots3_costs_follow_what_each_kind_reads():
    window = load_json("layer_metrics", "latent_window_decode_attn_roofline")["roofline"]["sizes"]
    assert window == {"n_heads": 64, "latent_width": 1088, "kept_width": 1152,
                      "value_width": 1024, "layers": 6}
    # a chunk of 8 steps over 16 rows past the window: 513 latents a (row, step)
    read = latent_kinds_attention_cost.window_decode_attention(
        kv_tokens_read_window=16 * 8 * 513, active_rows=16, steps=8, calls=999, **window)
    assert read["bytes"] == 6 * (16 * 8 * 513 * 2304 + 8 * 16 * 64 * (1088 + 1024) * 2)
    assert read["ops"] == 6 * 2 * 16 * 8 * 513 * 64 * (1088 + 1024)
    assert 90 < read["ops"] / read["bytes"] < 120  # under a v5e's ridge: the bytes bind
    full = load_json("layer_metrics", "latent128_decode_attn_roofline")["roofline"]["sizes"]
    assert full == {"n_heads": 128, "latent_width": 576, "value_width": 512, "layers": 3}
    picked = latent_attention_cost.latent_decode_attention(
        kv_tokens_selected=16 * 8 * 2048, active_rows=16, steps=8, calls=1, **full)
    assert picked["ops"] == 3 * 2 * 16 * 8 * 2048 * 128 * (576 + 512)
    # a 2,048-token segment at offset 8,192: every query selects 2,048, sees 513
    walk = latent_kinds_attention_cost.selected_segment_attention(
        kv_tokens_selected=2048 * 2048, real_tokens=2048, offset=8192, steps=1, calls=3,
        **load_json("layer_metrics", "latent128_segment_attn_roofline")["roofline"]["sizes"])
    assert walk["ops"] == 3 * 2 * 2048 * 2048 * 128 * (192 + 128)
    assert walk["bytes"] == 3 * 2 * (2048 * 128 * (192 + 128) + 576 * (8192 + 2048))
    band = latent_kinds_attention_cost.window_segment_attention(
        kv_tokens_read_window=2048 * 513, real_tokens=2048, offset=8192, steps=1, calls=6,
        **load_json("layer_metrics", "latent_window_segment_attn_roofline")["roofline"]["sizes"])
    assert band["ops"] == 6 * 2 * 2048 * 513 * 64 * (256 + 128)
    assert band["bytes"] == 6 * 2 * (2048 * 64 * (256 + 128) + 1088 * (513 + 2048 - 1))
    # the first segment: the window's band is the segment itself
    first = latent_kinds_attention_cost.window_segment_attention(
        kv_tokens_read_window=1, real_tokens=2048, offset=0, steps=1, calls=6, n_heads=64,
        qk_head_dim=256, v_head_dim=128, latent_width=1088, window=513, layers=6)
    assert first["bytes"] == 6 * 2 * (2048 * 64 * 384 + 1088 * 2048)


def test_the_dots3_metric_files_read_the_spans_and_scopes_the_program_has():
    from langstream_tpu.models.transformer import SCOPES

    step = load_json("layer_metrics", "latent_window_ms_per_step")
    assert (step["program"], step["span"], step["per"], step["scopes"]) == (
        "_paged_decode_chunk", "engine.decode_chunk", "steps", ["attention.latent.window"])
    segment = load_json("layer_metrics", "latent_window_ms_per_1k_segment_tokens")
    assert (segment["program"], segment["per"], segment["scopes"]) == (
        "_paged_segment_and_sample", "computed_tokens", ["attention.latent.window"])
    decode = load_json("layer_metrics", "latent_window_decode_attn_roofline")
    walk = load_json("layer_metrics", "latent_window_segment_attn_roofline")
    assert decode["scopes"] == walk["scopes"] == ["attention.latent.window"]
    assert walk["roofline"]["shape"].startswith("flash_segment_attention")
    assert walk["roofline"]["sizes"]["window"] == 513
    for definition in (step, segment, decode, walk):
        assert set(definition["scopes"]) <= set(SCOPES)
    assert {"attention.latent.window", "attention.gate"} <= set(SCOPES)
    selected = load_json("layer_metrics", "latent128_segment_attn_roofline")
    assert selected["scopes"] == ["sparse_segment_attention"]
    assert selected["roofline"]["sizes"] == {
        "n_heads": 128, "qk_head_dim": 192, "v_head_dim": 128, "latent_width": 576, "layers": 3}
    scores = load_json("layer_metrics", "indexer64_score_roofline")
    assert scores["roofline"]["sizes"] == {"index_n_heads": 64, "index_head_dim": 128, "layers": 3}
    experts = load_json("layer_metrics", "moe5120x1536_grouped_matmul_roofline")
    assert experts["roofline"]["sizes"] == {"d_model": 5120, "d_ff": 1536}
    work = grouped_matmul_cost.grouped_matmul(
        moe_local=8 * 8, moe_touched=8 * 6, steps=1, calls=16, **experts["roofline"]["sizes"])
    assert work["bytes"] == 8 * 6 * 3 * 5120 * 1536 + 8 * 8 * 3 * (5120 + 1536) * 2
    # every span attribute the new costs name is one the engine sets
    from langstream_tpu.serving import engine as program

    source = Path(program.__file__).read_text()
    for definition in (decode, walk, selected, scores):
        for attr in definition["roofline"]["span_attrs"]:
            assert f'"{attr}"' in source or f"{attr}=" in source, attr


# -- the check's verdicts (an engine a case: by hand) --------------------------------------------


def _engine(config, params):
    from langstream_tpu.serving.engine import ServingEngine

    knobs = load_json("workloads", "tiny-dots3-drain", DATA)["engine"]
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
    assert verdict["layer_err_median"] < 0.5 * spec["check"]["tol_med"]
    assert verdict["compared"]["layer_err_over_tol_untied"] == [0, 0]
    assert verdict["engine_state"]["found"]["window_page_leaves"] == ["lat"]


def test_known_fault_fails_by_a_number(monkeypatch):
    """The heads' gate left out of the program: the chain is the program's
    block, so level 1 reads it at every position of every attention half
    (`dev/dots3_check_faults.py --tiny` runs this and more)."""
    from langstream_tpu.models import transformer as program

    spec = load_json("configs", TINY, DATA)
    config = dataclasses.replace(model_config(spec, TINY), name="tiny-dots3-no-gate")
    monkeypatch.setattr(program, "_head_gate", lambda attn, u, lp, config: attn)
    engine = _engine(config, family.make_params(config, 0))
    try:
        verdict = run_check(engine, spec)
    finally:
        engine.stop()
    assert not verdict["ok"]
    assert verdict["compared"]["layer_err_over_tol_untied"][0] > 0

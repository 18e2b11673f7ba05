"""The Kimi-K2 family (`kimi_k2`) held to the README's contract ("A family"),
its configuration to the catalog and the stated cut, its cell to the issue's
sizes, and its correctness check to a verdict, sound and faulted, at a tiny
size on the CPU.

The fast cases here (everything but the check's verdicts) are also run by the
repo's tier-1 through `tests/test_benchmark_families.py`, whose cases share
one namespace: every name here says `kimi`. The program against the reference
is tier-1's own (`tests/test_latent_dense_attention.py`); the cost functions'
hand counts are `test_latent_dense_attention_cost.py`'s. The verdict cases
drive the whole command's check at the tiny cell's knobs, by hand
(`dev/kimi_check_faults.py --tiny` runs them and more).
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from check import run_check
from modelcfg import load_json, load_module, model_config

DATA = Path(__file__).parent / "data"
TINY = "tiny-kimi"
REAL = "kimi-k2.5-int8-ep32-d7"
CELL = "kimik25-ep32-d7-longdoc-drain"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUT = {"num_hidden_layers": 61, "n_routed_experts": 384, "vocab_size": 163840}
family = load_module("families", "kimi_k2")
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())


# -- the README's contract ----------------------------------------------------


def test_the_kimi_family_exports_what_the_readme_lists():
    for name in ("model_config", "make_params", "reference_dims", "system_chain",
                 "ref_layer_params", "hot_path", "engine_state", "expected_kernels",
                 "state_leaves"):
        assert callable(getattr(family, name)), name
    assert callable(family.hot_path.logits)
    assert not any(hasattr(family, name) for name in ("trajectory", "choice_score"))
    reference = load_module("reference", "kimi_k2")
    assert all(callable(getattr(reference, name)) for name in ("embed", "layer", "unembed"))
    # the equations and each departure stand in the reference's docstring
    for said in ("RMSNorm_1536", "RMSNorm_512", "ONE 64-wide key", "[T, 64, 192]",
                 "NARROWER than the key", "corr(r)", "= 8", "= 20", "0.144680",
                 "the bias chooses and", "2.827 x", "Shared(u)", "Departures", "MoonViT",
                 "ONLY the expanded form", "experts_held", "no indexer"):
        assert said in reference.__doc__, said
    # plain: no kernel, no cache, nothing of the program, and it never absorbs
    source = Path(reference.__file__).read_text().split('"""', 2)[2]
    for word in ("absorb", "langstream_tpu", "pallas", "cache"):
        assert word not in source, word
    assert 'HIGHEST = "highest"' in source and "jnp.float32" in source


def test_the_real_kimi_configuration_maps_onto_its_fields():
    config = model_config(load_json("configs", REAL), REAL)
    assert (config.d_model, config.n_layers, config.vocab_size) == (7168, 7, 20480)
    assert (config.n_heads, config.n_kv_heads, config.resolved_head_dim) == (64, 64, 192)
    assert (config.q_lora_rank, config.kv_lora_rank, config.qk_nope_head_dim,
            config.qk_rope_head_dim, config.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (config.n_experts, config.n_experts_per_tok, config.expert_d_ff) == (384, 8, 2048)
    assert (config.d_ff, config.held_experts, config.n_leading_dense) == (18432, (0, 12), 1)
    assert (config.moe_scoring, config.router_bias, config.routed_scaling,
            config.n_shared_experts) == ("sigmoid", True, 2.827, 1)
    assert (config.rope_theta, config.rms_norm_eps, config.rope_dim) == (50000.0, 1e-5, 64)
    assert config.rope_interleaved and not config.tie_embeddings
    # YaRN from the published keys alone: the ramp, the softmax factor
    assert (config.rope_scaling_type, config.rope_scaling_factor,
            config.rope_scaling_original_max_seq_len, config.rope_scaling_beta_fast,
            config.rope_scaling_beta_slow, config.rope_scaling_mscale,
            config.rope_scaling_mscale_all_dim) == ("yarn", 64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert config.yarn_blend == (8, 20) and abs(config.attn_scale - 0.144680) < 1e-6
    # no indexer: ONE leaf a token, the latent's 576 kept at 640 lanes
    assert config.page_leaves == ("lat",) and config.has_latent and not config.has_indexer
    assert (config.latent_width, config.latent_key_width) == (576, 640)
    assert config.kv_bytes_per_token() == 7 * 640 * 2 == 8960


def test_the_kimi_published_keys_are_the_catalog_s_and_the_cut_is_stated():
    spec = load_json("configs", REAL)
    assert spec["reduced"] == list(CUT)
    assert [spec[k] for k in CUT] == [7, 12, 20480]
    if CATALOG.is_file():
        entry = next(
            row for row in map(json.loads, CATALOG.read_text().splitlines())
            if row["name"] == "Kimi-K2.5"
        )
        assert spec["source"] == entry["source_url"]
        differs = {k for k, v in entry["config"].items() if spec.get(k, "absent") != v}
        assert differs == set(CUT)
        assert {k: entry["config"][k] for k in CUT} == CUT
        assert spec["rope_scaling"] == entry["config"]["rope_scaling"]  # the nested group whole
        assert set(family.PUBLISHED) == set(entry["config"])
    # no width is cut
    assert (spec["hidden_size"], spec["intermediate_size"], spec["moe_intermediate_size"]) == (
        7168, 18432, 2048)
    assert (spec["q_lora_rank"], spec["kv_lora_rank"], spec["qk_nope_head_dim"],
            spec["qk_rope_head_dim"], spec["v_head_dim"]) == (1536, 512, 128, 64, 128)
    assert (spec["num_attention_heads"], spec["num_experts_per_tok"]) == (64, 8)
    # the floors: a whole period and four layers behind the dense one, 8 experts, an eighth
    assert spec["num_hidden_layers"] - spec["first_k_dense_replace"] >= 4
    assert spec["n_routed_experts"] >= 8 and spec["vocab_size"] * 8 >= CUT["vocab_size"]
    assumed = spec["assumed"]
    assert {"rotary_pairing", "yarn", "softmax_scale", "head_dim", "num_key_value_heads",
            "router", "e_score_correction_bias", "vision_tower", "inert", "sources"} <= set(assumed)
    assert all(len(why) > 40 for why in assumed["sources"].values())
    for said in ("low = floor 8", "high = ceil 20"):
        assert said in assumed["yarn"], said
    assert "0.144680" in assumed["softmax_scale"] and "LEFT OUT" in assumed["vision_tower"]
    deployment = spec["deployment"]
    assert deployment["chips_a_layer"] == 32
    assert deployment["experts"] == {"published": 384, "first_held": 0, "held": 12}
    assert deployment["layers"] == {"published": 61, "held": 7, "leading_dense_published": 1,
                                    "leading_dense_held": 1}
    assert deployment["vocabulary"] == {"published": 163840, "held": 20480}
    for said in ("32 chips share each layer", "ten pipeline stages", "nine times", "5.05 GB",
                 "8,960 B", "no code stands in", "0.25 assignments", "a thirty-second"):
        assert said in deployment["says"], said
    row = next(c for c in BENCH["configs"] if c["name"] == REAL)
    assert row["reduced"] == spec["reduced"] and row["source"] == spec["source"]
    assert row["file"] == f"benchmark/configs/{REAL}.json"
    # the check's sample: ONE prompt under the bucket (the admit group), two over it in
    # three segments or more
    check = spec["check"]
    assert sum(n < 2048 for n in check["lengths"]) == 1
    assert sorted(n for n in check["lengths"] if n >= 2048)[0] > 2 * 2048
    assert check["new_tokens"] == 8
    assert check["width"] % 128 == 0 and check["width"] >= max(check["lengths"]) + check["new_tokens"]
    assert (check["kv_dtype"], check["weights"], check["router_dtype"], check["experts_held"],
            check["page_leaves"]) == ("bfloat16", "int8", "float32", "0-11 of 384", ["lat"])
    tolerances = [k for k in check if k.startswith(("tol_", "eps_"))]
    assert tolerances and all(k in check["reasons"] for k in tolerances), tolerances
    assert "9.8%" in spec["weights"]["why"]


def test_the_kimi_cell_is_sized_as_the_issue_says():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "longdoc-drain", 1)
    assert len(cell["why"]) <= 200
    assert len(BENCH["workloads"]) == 9 and all(w["chips"] == 1 for w in BENCH["workloads"])
    assert len(BENCH["configs"]) == 8
    files = load_json("workloads", CELL)
    assert files["engine"] == {"max-batch": 16, "max-seq-len": 17408, "prefill-buckets": [2048],
                               "prefill-batch": 1, "kv-pages": 4352, "queue-depth": 640,
                               "inflight-records": 640}
    assert files["trace_seconds"] == 20.0 and "schedule" not in files["engine"]
    engine = files["engine"]
    assert engine["max-seq-len"] == 16384 + 1024 == 272 * 64 and engine["kv-pages"] == 16 * 272
    # the traffic is the file the Keye and GLM-5 cells run, as it stands
    traffic = load_json("traffic", "longdoc-drain")
    assert (traffic["kind"], traffic["backlog_records"], traffic["prompt_tokens"]) == (
        "topic_drain", 160, {"dist": "uniform", "min": 8192, "max": 16384})
    reports = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reports == {
        "latent_read_ms_per_step.drain", "latent_read_ms_per_1k_segment_tokens.drain",
        "latent_dense_decode_attn_roofline.drain", "latent_dense_segment_attn_roofline.drain",
        "moe7168x2048_grouped_matmul_roofline.drain",
        "latent_proj_ms_per_step.drain", "latent_ms_per_1k_segment_tokens.drain",
        "latent_expanded_per_segment_token.drain", "active_slots_mean", "kv_pages_peak_share",
        "device_unfed_with_request_share.drain", "decode_step_device_ms.drain",
        "attention_ms_per_step.drain", "ffn_ms_per_step.drain", "head_ms_per_step.drain",
        "kv_pool_copy_ms_per_step.drain", "prefill_segment_ms_per_1k_tokens.drain",
        "attention_ms_per_1k_segment_tokens.drain", "moe_ffn_ms_per_1k_segment_tokens.drain",
        "moe_shared_ms_per_1k_segment_tokens.drain", "moe_dropped_assignment_share",
        "moe_local_assignment_share",
    }
    # nothing of a selection, and not the shares whose files fix GLM-5's sizes and scopes
    assert not any("sparse" in name or "index" in name or "selected" in name for name in reports)
    assert all(m["moves"] == "gen_tokens_per_s" for m in BENCH["per_layer"] if m["name"] in reports)
    new = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [m["name"] for m in BENCH["per_layer"][-5:]]  # at the end
    assert {m["layer"] for m in new} == {"model", "kernels"}
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
        ({"n_group": 8}, "n_group"),
        ({"moe_layer_freq": 2}, "moe_layer_freq"),
        ({"tie_word_embeddings": True}, "tie_word_embeddings"),
        ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
        ({"num_key_value_heads": 1}, "num_key_value_heads"),
        ({"rope_scaling": {"type": "linear", "factor": 4.0}}, "rope_scaling"),
        ({"rope_scaling": None}, "rope_scaling"),
        ({"index_topk": 2048}, "index_topk"),  # a stray key maps onto nothing
        ({"rope_interleave": True}, "rope_interleave"),
        ({"v_head_dim": 0}, "v_head_dim 0 under 1"),
    ],
    ids=lambda v: v if isinstance(v, str) else next(iter(v)),
)
def test_what_the_kimi_block_cannot_express_is_refused(change, says):
    spec = {**load_json("configs", TINY, DATA), **change}
    if spec["rope_scaling"] is None:
        spec["rope_scaling"] = {}
    with pytest.raises(ValueError, match=says):
        model_config(spec, TINY)


def test_the_kimi_dims_read_back_from_the_config_are_the_file_s():
    for name, root in ((REAL, None), (TINY, DATA)):
        spec = load_json("configs", name, *([root] if root else []))
        config, dims = model_config(spec, name), family.reference_dims(spec)
        assert (dims["n_heads"], dims["eps"], dims["rope_theta"]) == (
            config.n_heads, config.rms_norm_eps, config.rope_theta)
        assert (dims["kv_lora_rank"], dims["qk_nope_head_dim"], dims["qk_rope_head_dim"],
                dims["v_head_dim"]) == (config.kv_lora_rank, config.qk_nope_head_dim,
                                        config.qk_rope_head_dim, config.v_head_dim)
        assert dims["rope_scaling"] == spec["rope_scaling"]
        assert (dims["top_k"], dims["n_experts"], tuple(dims["experts_held"]),
                dims["routed_scaling"]) == (config.n_experts_per_tok, config.n_experts,
                                            config.held_experts, config.routed_scaling)
        # the reference's YaRN from the file's keys is the program's from its fields
        reference = load_module("reference", "kimi_k2")
        assert reference.yarn_range(dims) == config.yarn_blend
        assert reference.softmax_scale(dims) == pytest.approx(config.attn_scale, rel=1e-12)
        assert "faults" not in dims


def test_the_kimi_seeded_tree_is_the_served_layout():
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
        assert stack["wq_a"]["q"].shape[1:] == (d, 32) and stack["wq_b"]["q"].shape[1:] == (32, 4 * 24)
        # a head's share of wkv_b: its key part 16, then its value 16; wo takes 4 x 16
        assert stack["wkv_a"]["q"].shape[1:] == (d, 24) and stack["wkv_b"]["q"].shape[1:] == (16, 4 * 32)
        assert stack["wo"]["q"].shape[1:] == (4 * 16, d)
        assert not {"wq_idx", "wk_idx", "w_idx", "idx_norm", "idx_bias"} & set(stack)
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


def test_the_kimi_chain_steps_half_a_layer_and_names_its_kinds():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    assert family.system_chain(config, 64, 1).n_layers == 2 * config.n_layers
    kinds = []
    for step in range(2 * config.n_layers):
        stack, at = family.ref_layer_params(tree, step)
        (kind, leaves), = stack.items()
        kinds.append((kind, at, "wq_a" in leaves, "router" in leaves, "w_gate" in leaves))
        assert "wq_idx" not in leaves
    assert kinds[:4] == [("dense", 0, True, False, False), ("dense", 0, False, False, True),
                         ("sparse", 0, True, False, False), ("sparse", 0, False, True, True)]
    assert kinds[-1] == ("sparse", 2, False, True, True)


# -- the check's verdicts (an engine a case: by hand) --------------------------------------------


def _engine(config, params):
    from langstream_tpu.serving.engine import ServingEngine

    knobs = load_json("workloads", "tiny-kimi-drain", DATA)["engine"]
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
    assert verdict["compared"]["layer_err_over_tol_untied"] == [0, 0]
    assert verdict["engine_state"]["found"]["page_leaves"] == ["lat"]


def test_known_fault_fails_by_a_number(monkeypatch):
    """YaRN's blend left out of the program (plain `f_i`): the chain is the
    program's block, so level 1 reads it in every attention half
    (`dev/kimi_check_faults.py --tiny` runs this and more)."""
    from langstream_tpu.models import transformer as program

    spec = load_json("configs", TINY, DATA)
    config = dataclasses.replace(model_config(spec, TINY), name="tiny-kimi-no-blend")

    def plain(positions, freqs, config):
        angles = positions.astype(jnp.float32)[..., None] * freqs
        return jnp.sin(angles), jnp.cos(angles)

    monkeypatch.setattr(program, "_yarn_tables", plain)
    engine = _engine(config, family.make_params(config, 0))
    try:
        verdict = run_check(engine, spec)
    finally:
        engine.stop()
    assert not verdict["ok"]
    assert verdict["compared"]["layer_err_over_tol_untied"][0] > 0


def test_the_tiny_cell_end_to_end_traced():
    """The whole command at the tiny size on the CPU, traced: the cell's own
    metrics come out of its spans and its stats (the scopes' times and the
    kernels' shares need the chip's device trace and read nothing here, which
    the line takes as it is)."""
    import asyncio

    import run
    from langstream_tpu.messaging.memory import MemoryBroker
    from test_end_to_end import CPU_PLANES, tiny_bench

    bench = tiny_bench("tiny-kimi-drain", TINY, "tiny-drain", CELL)
    MemoryBroker.reset()
    out = asyncio.run(run.run_cell(
        bench, "tiny-kimi-drain", 2**31 + 5, 6.0, True, platform="cpu", files=DATA,
        trace_planes=CPU_PLANES,
    ))
    assert out["failed"] == 0 and out["attempted"] > 0 and out["correct"] is True
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert {"latent_expanded_per_segment_token.drain", "prefill_segment_ms_per_1k_tokens.drain",
            "moe_local_assignment_share", "moe_dropped_assignment_share", "kv_pages_peak_share",
            "active_slots_mean", "decode_step_device_ms.drain"} <= set(metrics)
    assert metrics["moe_dropped_assignment_share"] == 0.0
    assert 35 < metrics["moe_local_assignment_share"] < 65  # 4 of 8 held: 50% when even
    assert metrics["latent_expanded_per_segment_token.drain"] > 0
    assert not any("sparse" in name or "index" in name for name in metrics)

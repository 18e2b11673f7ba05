"""The LFM2-MoE family (`lfm2_moe`) held to the README's contract ("A family"),
its configuration to the catalog and the stated cut, its cell to the issue's
sizes, its cost functions to hand counts, and its correctness check to a
verdict, sound and faulted, at a tiny size on the CPU.

The fast cases here (everything but the check's verdicts) are also run by the
repo's tier-1 through `tests/test_benchmark_families.py`, whose cases share
one namespace: every name here says `lfm2`. The program against the reference
is tier-1's own (`tests/test_lfm2_moe.py`). The verdict cases drive the whole
command's check at the tiny cell's knobs, by hand (`dev/lfm2_check_faults.py
--tiny` runs them and more).
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
TINY = "tiny-lfm2"
REAL = "lfm2-24b-a2b-int8-d16"
CELL = "lfm2-24b-d16-decode-drain-256"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CUT = {"num_hidden_layers": 40, "layer_types": ["conv", "conv", "full_attention", "conv"] * 10}
family = load_module("families", "lfm2_moe")
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())


# -- the README's contract ----------------------------------------------------


def test_the_lfm2_family_exports_what_the_readme_lists():
    for name in ("model_config", "make_params", "reference_dims", "system_chain",
                 "ref_layer_params", "hot_path", "engine_state", "expected_kernels",
                 "state_leaves"):
        assert callable(getattr(family, name)), name
    assert callable(family.hot_path.logits)
    assert not any(hasattr(family, name) for name in ("trajectory", "choice_score"))
    reference = load_module("reference", "lfm2_moe")
    assert all(callable(getattr(reference, name)) for name in ("embed", "layer", "unembed"))
    # the equations and each departure stand in the reference's docstring
    for said in ("[B | C | u]", "conv_L_cache", "NO activation", "zeros before", "32 heads x 64",
                 "BEFORE rotary", "(i, i + 32)", "64^-0.5", "s + expert_bias", "WITHOUT the bias",
                 "+ 1e-6", "lower index", "11,776", "1,536", "embedding_norm", "tied", "Departures",
                 "conv_dense", "conv_expert", "attention_expert"):
        assert said in reference.__doc__, said
    # plain: no kernel, no cache, no tail, nothing of the program
    source = Path(reference.__file__).read_text().split('"""', 2)[2]
    for word in ("langstream_tpu", "pallas", "cache", "tail", "rec["):
        assert word not in source, word
    assert 'HIGHEST = "highest"' in source and "jnp.float32" in source


def test_the_real_lfm2_configuration_maps_onto_its_fields():
    config = model_config(load_json("configs", REAL), REAL)
    assert (config.d_model, config.n_layers, config.vocab_size) == (2048, 16, 65536)
    assert (config.n_heads, config.n_kv_heads, config.resolved_head_dim) == (32, 8, 64)
    assert (config.n_experts, config.n_experts_per_tok, config.expert_d_ff) == (64, 4, 1536)
    assert (config.d_ff, config.held_experts, config.n_leading_dense) == (11776, (0, 64), 2)
    assert (config.moe_scoring, config.router_bias, config.routed_scaling,
            config.router_norm_eps, config.n_shared_experts) == ("sigmoid", True, 1.0, 1e-6, 0)
    assert (config.rope_theta, config.rms_norm_eps, config.conv_kernel) == (1e6, 1e-5, 3)
    assert config.layer_pattern == ("conv", "conv", "full_attention", "conv")
    assert (config.n_layers_of("conv"), config.n_layers_of("full_attention")) == (12, 4)
    assert (config.dense_of("conv"), config.dense_of("full_attention")) == (2, 0)
    assert config.tie_embeddings and config.qk_norm_heads and not config.qk_norm
    assert config.is_recurrent and config.holds_experts and not config.has_window
    # two heads of 64 to a lane row: 8 KiB a token over 4 attention layers
    assert config.kv_head_pack == 2 and config.page_leaves == ("k", "v")
    assert config.kv_bytes_per_token() == 4 * 2 * 8 * 64 * 2 == 8192


def test_the_lfm2_published_keys_are_the_catalog_s_and_the_cut_is_stated():
    spec = load_json("configs", REAL)
    assert spec["reduced"] == list(CUT)
    assert spec["num_hidden_layers"] == 16 and spec["layer_types"] == CUT["layer_types"][:16]
    if CATALOG.is_file():
        entry = next(
            row for row in map(json.loads, CATALOG.read_text().splitlines())
            if row["name"] == "LFM2-24B-A2B"
        )
        assert spec["source"] == entry["source_url"]
        differs = {k for k, v in entry["config"].items() if spec.get(k, "absent") != v}
        assert differs == set(CUT)
        assert {k: entry["config"][k] for k in CUT} == CUT
        assert spec["rope_parameters"] == entry["config"]["rope_parameters"]  # the group whole
        assert set(family.PUBLISHED) == set(entry["config"])
    # no width is cut
    assert (spec["hidden_size"], spec["intermediate_size"], spec["moe_intermediate_size"]) == (
        2048, 11776, 1536)
    assert (spec["num_attention_heads"], spec["num_key_value_heads"], spec["conv_L_cache"]) == (
        32, 8, 3)
    assert (spec["num_experts"], spec["num_experts_per_tok"], spec["vocab_size"]) == (64, 4, 65536)
    # the floors: whole periods, four layers and more behind the dense ones, 8 experts, the vocabulary
    assert spec["num_hidden_layers"] % 4 == 0
    assert spec["num_hidden_layers"] - spec["num_dense_layers"] >= 4 and spec["num_experts"] >= 8
    assumed = spec["assumed"]
    assert {"head_dim", "qk_norm", "tie_word_embeddings", "router_eps", "router", "conv",
            "rotary", "expert_bias", "sources"} <= set(assumed)
    assert all(len(why) > 40 for why in assumed["sources"].values())
    deployment = spec["deployment"]
    assert deployment["layers"] == {"published": 40, "held": 16, "periods_published": 10,
                                    "periods_held": 4, "leading_dense_published": 2,
                                    "leading_dense_held": 2}
    assert deployment["experts"] == {"published": 64, "first_held": 0, "held": 64}
    for said in ("9,138,782,720", "8,192 B", "98,304 B", "2.5 times", "23.9 GB"):
        assert said in deployment["says"], said
    row = next(c for c in BENCH["configs"] if c["name"] == REAL)
    assert row["reduced"] == spec["reduced"] and row["source"] == spec["source"]
    assert row["file"] == f"benchmark/configs/{REAL}.json"
    # the check's sample: inside each bucket and one past the largest (two segments)
    check = spec["check"]
    assert [n for n in check["lengths"] if n > 256] == [300]
    assert all(n % 64 for n in check["lengths"])
    assert check["width"] % 128 == 0 and check["width"] >= max(check["lengths"]) + check["new_tokens"]
    assert (check["kv_dtype"], check["weights"], check["router_dtype"], check["experts_held"],
            check["page_leaves"], check["page_row"], check["state_leaves"]) == (
        "bfloat16", "int8", "float32", "0-63 of 64", ["k", "v"], "4x64x128", {"conv": "bfloat16"})
    tolerances = [k for k in check if k.startswith(("tol_", "eps_"))]
    assert tolerances and all(k in check["reasons"] for k in tolerances), tolerances


def test_the_lfm2_byte_counts_are_the_tree_s():
    """The deployment's arithmetic, redone on the family's own tree."""
    config = model_config(load_json("configs", REAL), REAL)
    tree = jax.eval_shape(lambda: family.make_params(config, 0))
    size = lambda t: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(t))  # noqa: E731
    assert size(tree) == 9_138_782_720 and size(tree["embed"]) == 268_435_456
    assert size(tree["dense_layers"]["conv"]) == 2 * 89_284_608
    assert size(tree["layers"]["conv"]) == 10 * 622_645_504
    assert size(tree["layers"]["full_attention"]) == 4 * 616_329_728
    from langstream_tpu.models.transformer import make_page_pool

    pool = jax.eval_shape(lambda: make_page_pool(config, 2560, 64, state_rows=256))
    assert pool["k"].shape == (4, 2560, 4, 64, 128)
    assert size({k: v for k, v in pool.items() if k != "rec"}) == 2560 * 64 * 8192
    assert size(pool["rec"]) == 256 * 98_304


def test_the_lfm2_cell_is_sized_as_the_issue_says():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL, "decode-drain-6000", 1)
    assert len(cell["why"]) <= 200
    files = load_json("workloads", CELL)
    engine = files["engine"]
    assert (engine["max-batch"], engine["max-seq-len"], engine["prefill-buckets"]) == (
        256, 640, [64, 128, 256])
    assert engine["kv-pages"] == 256 * 10 and engine["max-seq-len"] == 10 * 64 == 256 + 384
    assert engine["inflight-records"] >= 256 + 2 * 8 and engine["queue-depth"] >= engine["inflight-records"]
    traffic = load_json("traffic", "decode-drain-6000")
    assert (traffic["kind"], traffic["backlog_records"], traffic["prompt_tokens"],
            traffic["output_caps"]) == (
        "topic_drain", 6000, {"dist": "uniform", "min": 64, "max": 256}, {"384": 1.0})
    reports = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert reports == {
        "short_conv_ms_per_step.drain", "conv_state_bytes_per_slot",
        "moe1536_grouped_matmul_roofline.drain", "paired_prefill_attn_roofline.drain",
        "paged_kv_write_roofline.drain", "paged_decode_attn_roofline.drain",
        "active_slots_mean", "kv_pages_peak_share", "decode_step_device_ms.drain",
        "attention_ms_per_step.drain", "ffn_ms_per_step.drain", "head_ms_per_step.drain",
        "kv_pool_copy_ms_per_step.drain", "moe_dropped_assignment_share",
        "device_unfed_with_request_share.drain", "prefill_useful_token_share.drain",
        "prefill_group_ready_ms.drain",
    }
    assert all(m["moves"] == "gen_tokens_per_s" for m in BENCH["per_layer"] if m["name"] in reports)
    new = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [m["name"] for m in BENCH["per_layer"][-5:]]  # at the end
    assert {m["layer"] for m in new} == {"model", "KV state", "kernels"}
    ends = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    # (tpot_p95_ms: six runs spread 0.20%, under a quarter of its 1% bound: PERF.md section 6)
    assert ends == {"gen_tokens_per_s", "tpot_p95_ms", "setup_s"}
    # every file a new metric names is there, and names a reader that is
    import run

    for m in new:
        definition = run.metric_definition(m["name"])
        assert (Path(run.__file__).parent / "readers" / f"{definition['reader']}.py").is_file()


@pytest.mark.parametrize(
    "change, says",
    [
        ({"model_type": "lfm2"}, "model_type"),
        ({"conv_bias": True}, "conv_bias"),
        ({"norm_topk_prob": False}, "norm_topk_prob"),
        ({"use_expert_bias": False}, "use_expert_bias"),
        ({"layer_types": ["conv", "full_attention"] * 4}, "layer_types"),
        ({"rope_parameters": {"rope_theta": 1000000, "rope_type": "yarn"}}, "rope_parameters"),
        ({"sliding_window": 4096}, "sliding_window"),  # a stray key maps onto nothing
        ({"tie_word_embeddings": False}, "tie_word_embeddings"),
        ({"conv_L_cache": 1}, "conv_kernel >= 2"),
        ({"num_dense_layers": 8}, "leaves an expert layer"),
    ],
    ids=lambda v: v if isinstance(v, str) else next(iter(v)),
)
def test_what_the_lfm2_block_cannot_express_is_refused(change, says):
    spec = {**load_json("configs", TINY, DATA), **change}
    with pytest.raises(ValueError, match=says):
        model_config(spec, TINY)


def test_the_lfm2_dims_read_back_from_the_config_are_the_file_s():
    for name, root in ((REAL, None), (TINY, DATA)):
        spec = load_json("configs", name, *([root] if root else []))
        config, dims = model_config(spec, name), family.reference_dims(spec)
        assert (dims["n_heads"], dims["n_kv_heads"], dims["head_dim"], dims["eps"],
                dims["rope_theta"]) == (config.n_heads, config.n_kv_heads,
                                        config.resolved_head_dim, config.rms_norm_eps,
                                        config.rope_theta)
        assert (dims["top_k"], dims["n_experts"], dims["routed_scaling"]) == (
            config.n_experts_per_tok, config.n_experts, config.routed_scaling)
        assert (dims["layer_pattern"], dims["n_dense"], dims["n_layers"]) == (
            config.layer_pattern, config.n_leading_dense, config.n_layers)
        assert load_module("reference", "lfm2_moe").ROUTER_EPS == config.router_norm_eps
        assert "faults" not in dims


def test_the_lfm2_seeded_tree_is_the_served_layout():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    dense, conv, full = (tree["dense_layers"]["conv"], tree["layers"]["conv"],
                         tree["layers"]["full_attention"])
    d, e, f = config.d_model, config.n_experts, config.expert_d_ff
    assert set(tree["dense_layers"]) == {"conv"} and set(tree["layers"]) == {"conv", "full_attention"}
    assert dense["w_gate"]["q"].shape == (2, d, config.d_ff) and "router" not in dense
    assert dense["w_in"]["q"].shape == (2, d, 3 * d) and dense["conv_w"].shape == (2, 3, d)
    assert conv["w_in"]["q"].shape == (4, d, 3 * d) and conv["w_out"]["q"].shape == (4, d, d)
    assert conv["w_gate"]["q"].shape == (4, e, d, f) and conv["w_gate"]["q"].dtype == jnp.int8
    assert full["wq"]["q"].shape == (2, d, 4 * 64) and full["wk"]["q"].shape == (2, d, 2 * 64)
    assert full["q_norm"].shape == (2, 64) and full["w_down"]["q"].shape == (2, e, f, d)
    for stack in (conv, full):
        assert stack["router"].dtype == jnp.float32 and stack["router_bias"].dtype == jnp.float32
        assert float(jnp.abs(stack["router_bias"]).max()) > 0.0  # drawn, not zero
    assert tree["embed"].dtype == jnp.bfloat16 and "lm_head" not in tree  # a tied head
    # the program's own tree has the same leaves (its tied embedding row-quantised)
    from langstream_tpu.models.quant import quantize_params
    from langstream_tpu.models.transformer import init_params

    own = jax.eval_shape(
        lambda k: quantize_params(init_params(config, k), config), jax.random.PRNGKey(0)
    )
    strip = lambda t: {k: v for k, v in t.items() if k != "embed"}  # noqa: E731
    assert jax.tree.structure(strip(own)) == jax.tree.structure(strip(tree))
    assert jax.tree.map(lambda a: a.shape, strip(own)) == jax.tree.map(lambda a: a.shape, strip(tree))
    assert jax.tree.map(lambda a: a.dtype, strip(own)) == jax.tree.map(lambda a: a.dtype, strip(tree))
    again = family.make_params(config, 0)
    assert all(jnp.array_equal(a, b) for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(again)))
    other = family.make_params(config, 1)
    assert not jnp.array_equal(conv["w_in"]["q"], other["layers"]["conv"]["w_in"]["q"])


def test_the_lfm2_chain_steps_half_a_layer_and_names_its_three_kinds():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    assert family.system_chain(config, 64, 1).n_layers == 2 * config.n_layers
    kinds = []
    for step in range(2 * config.n_layers):
        stack, at = family.ref_layer_params(tree, step)
        (kind, leaves), = stack.items()
        kinds.append((kind, at, "w_in" in leaves, "wq" in leaves, "router" in leaves,
                      "w_gate" in leaves))
    assert kinds[:8] == [
        ("conv_dense", 0, True, False, False, False), ("conv_dense", 0, False, False, False, True),
        ("conv_dense", 1, True, False, False, False), ("conv_dense", 1, False, False, False, True),
        ("attention_expert", 0, False, True, False, False),
        ("attention_expert", 0, False, False, True, True),
        ("conv_expert", 0, True, False, False, False), ("conv_expert", 0, False, False, True, True),
    ]
    assert kinds[-1] == ("conv_expert", 3, False, False, True, True)
    assert {k[0] for k in kinds} == {"conv_dense", "conv_expert", "attention_expert"}


def test_the_lfm2_costs_against_hand_counts():
    from reduce import costs, paged_decode_cost, paged_kv_write_cost, paired_heads_cost
    from reduce.grouped_matmul_cost import grouped_matmul

    # a prefill call of 8 rows x 256 at 4 packed rows of 2 x 64 and 32 query heads
    paired = paired_heads_cost.prefill_attention(8, 256, 32, 4, 128)
    assert paired == costs.prefill_attention(8, 256, 32, 8, 64)
    assert paired["ops"] * 2 == costs.prefill_attention(8, 256, 32, 4, 128)["ops"]
    assert paired["bytes"] == 2 * (2 * 8 * 256 * 32 * 64 + 2 * 8 * 256 * 8 * 64)
    # a decode chunk of 4 steps x 256 live rows over 4 layers: 8 x 64 x 2 B a leaf a row
    write = paged_kv_write_cost.paged_kv_write(1024, steps=4, layers=4.0, pool_pages=10240,
                                               n_kv_rows=4, page_size=64, row_width=128)
    assert write == {"ops": 0, "bytes": 2 * 1024 * (2 * 8 * 64 * 2) * 4}
    # the paged decode kernel's cost over the packed call's sizes is the model's in bytes
    read = paged_decode_cost.paged_decode_attention(
        100_000, steps=4, layers=4.0, rows=256, n_heads=32, n_kv_heads=4, head_dim=128)
    k_and_v = 100_000 * 2 * 8 * 64 * 2
    assert read["bytes"] == (k_and_v + 2 * 4 * 256 * 32 * 128 * 2) * 4
    # a decode step's experts: every one of 64 touched, 1,024 rows arrive, a layer
    experts = grouped_matmul(1024, 64, steps=1, calls=2, d_model=2048, d_ff=1536)
    assert experts["bytes"] == 64 * 3 * 2048 * 1536 + 1024 * 3 * (2048 + 1536) * 2
    assert experts["ops"] == 1024 * 3 * 2 * 2048 * 1536


def test_the_lfm2_metric_files_name_what_the_program_names():
    import run

    conv = run.metric_definition("short_conv_ms_per_step.drain")
    assert (conv["reader"], conv["program"], conv["scopes"], conv["per"]) == (
        "trace_scope", "_paged_decode_chunk", ["short_conv"], "steps")
    state = run.metric_definition("conv_state_bytes_per_slot")
    assert (state["reader"], state["key"]) == ("stats_final", "conv-state-bytes-per-slot")
    moe = run.metric_definition("moe1536_grouped_matmul_roofline.drain")
    assert moe["roofline"]["sizes"] == {"d_model": 2048, "d_ff": 1536}
    assert [d["program"] for d in moe["dispatches"]] == ["_paged_decode_chunk", "admit_group"]
    write = run.metric_definition("paged_kv_write_roofline.drain")
    line = ("%paged_kv_write.23 = (bf16[10240,4,64,128]{3,2,1,0:T(8,128)(2,1)}, "
            "bf16[10240,4,64,128]{3,2,1,0:T(8,128)(2,1)}) custom-call(%bitcast.1350, ")
    import re

    sizes = re.search(write["roofline"]["shape"], line).groupdict()
    assert sizes == {"pool_pages": "10240", "n_kv_rows": "4", "page_size": "64", "row_width": "128"}
    assert write["roofline"]["span_attr"] == "kv_rows_written"
    paired = load_json("reduce/kernels", run.metric_definition(
        "paired_prefill_attn_roofline.drain")["kernel"])
    line = "%flash_prefill_attention.3 = bf16[8,4,8,256,128]{4,3,2,1,0:T(8,128)(2,1)} custom-call(bf16[8,4,8,256,128]"
    assert re.search(paired["shape"], line).groupdict() == {
        "rows": "8", "n_kv_heads": "4", "group": "8", "width": "256", "head_dim": "128"}
    assert paired["cost"] == "paired_heads_cost.prefill_attention"
    # the scopes and the key are the program's own
    from langstream_tpu.models import transformer
    from langstream_tpu.serving import engine

    source = Path(transformer.__file__).read_text()
    for scope in ('named_scope("short_conv")', "short_conv.proj", "short_conv.conv", "short_conv.out"):
        assert scope in source, scope
    assert '"conv-state-bytes-per-slot"' in Path(engine.__file__).read_text()


def test_the_lfm2_engine_state_is_held_to_the_check_block():
    """`engine_state` finds what the check block names, key for key: a key
    the family finds and the file lacks (or the other way) is a mismatch."""
    from langstream_tpu.serving.engine import ServingEngine

    spec = load_json("configs", TINY, DATA)
    config = model_config(spec, TINY)
    engine = ServingEngine(config, family.make_params(config, 0), max_batch=2, max_seq_len=64,
                           prefill_buckets=(16,), kv_pages=16, page_size=8, precompile=False)
    found = family.engine_state(engine)
    assert found == {k: spec["check"][k] for k in found}
    assert set(found) == {"weights", "kv_dtype", "router_dtype", "experts_held", "page_leaves",
                          "page_row", "state_leaves"}
    kernels = family.expected_kernels(engine)
    assert kernels["paged-decode[s=1,t=64]"] == "ragged_paged_decode_attention"
    assert kernels["paged-decode-write[s=1]"] == "paged_kv_write"
    assert kernels["prefill[s=16,t=16]"] == "flash_prefill_attention"
    assert kernels["paged-insert[w=16]"] == "paged_insert_pages"
    assert kernels["short-conv[s=1,t=0]"] == "short_conv" and "jnp" not in kernels.values()
    params, pool = family.state_leaves(engine)
    assert "rec" in pool and set(pool["rec"]) == {"conv"}


# -- the check's verdicts (an engine a case: by hand) --------------------------------------------


def _engine(config, params):
    from langstream_tpu.serving.engine import ServingEngine

    knobs = load_json("workloads", "tiny-lfm2-drain", DATA)["engine"]
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
    assert verdict["engine_state"]["found"]["state_leaves"] == {"conv": "bfloat16"}


def test_known_fault_fails_by_a_number(monkeypatch):
    """A tail dropped (zeros carried into every decode step and every later
    segment): the chain's conv mixers run through a state, so level 1 reads
    it, and levels 2 and 3 by the hot path and the engine's own tokens
    (`dev/lfm2_check_faults.py --tiny` runs this and more)."""
    from langstream_tpu.models import transformer as program

    spec = load_json("configs", TINY, DATA)
    config = dataclasses.replace(model_config(spec, TINY), name="tiny-lfm2-tail-dropped")
    sound = program._short_conv

    def dropped(inputs, taps, rec, layer, rows, valid, fresh, activation=None):
        mixed, out = sound(inputs, taps, rec, layer, rows, valid, fresh, activation)
        if rec is not None and fresh is not True:
            zeros = {**rec, "conv": jnp.zeros_like(rec["conv"])}
            mixed, _ = sound(inputs, taps, zeros, layer, rows, valid, fresh, activation)
        return mixed, out

    monkeypatch.setattr(program, "_short_conv", dropped)
    engine = _engine(config, family.make_params(config, 0))
    try:
        verdict = run_check(engine, spec)
    finally:
        engine.stop()
    assert not verdict["ok"]
    assert verdict["compared"]["layer_err_over_tol_untied"][0] > 0
    assert verdict["compared"]["hot_err_over_tol_untied"][0] > 0


def test_the_tiny_cell_end_to_end_traced():
    """The whole command at the tiny size on the CPU, traced: the cell's own
    metrics come out of its spans and its stats (the scopes' times and the
    kernels' shares need the chip's device trace and read nothing here, which
    the line takes as it is)."""
    import asyncio

    import run
    from langstream_tpu.messaging.memory import MemoryBroker
    from test_end_to_end import CPU_PLANES, tiny_bench

    bench = tiny_bench("tiny-lfm2-drain", TINY, "tiny-drain", CELL)
    MemoryBroker.reset()
    out = asyncio.run(run.run_cell(
        bench, "tiny-lfm2-drain", 2**31 + 5, 6.0, True, platform="cpu", files=DATA,
        trace_planes=CPU_PLANES,
    ))
    assert out["failed"] == 0 and out["attempted"] > 0 and out["correct"] is True
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert {"conv_state_bytes_per_slot", "moe_dropped_assignment_share", "kv_pages_peak_share",
            "active_slots_mean", "decode_step_device_ms.drain",
            "prefill_useful_token_share.drain"} <= set(metrics)
    assert metrics["conv_state_bytes_per_slot"] == 6 * 2 * 256 * 2
    assert metrics["moe_dropped_assignment_share"] == 0.0

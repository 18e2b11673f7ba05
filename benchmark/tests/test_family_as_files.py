"""A model family is files: a toy family that exists only under `tests/data`
(`families/`, `reference/`, a configuration, a cell) runs end to end, and no
module of the harness knows its name or its keys."""

import asyncio
import re
from pathlib import Path

import pytest

import run
from modelcfg import load_json, load_module, model_config
from test_end_to_end import tiny_bench

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).parent / "data"
# what only the toys say: their names and a key or two of theirs that no real
# family's file has (`layer_types`, `rope_parameters`, `full_attention` and
# `sliding_attention` are published keys of real families since PRs 32 and 34)
TOY_ONLY = ("twokind", "blockfill", "BlockPasses", "fixed_in", "kind_of")


def test_the_toy_cell_runs_from_its_files_alone():
    from langstream_tpu.messaging.memory import MemoryBroker

    family = load_module("families", "twokind", DATA)
    bench = tiny_bench("tiny-twokind-chat", "tiny-twokind", "tiny-chat", "mistral7b-chat-steady")
    MemoryBroker.reset()
    # the family's tree is one stack a kind; today's engine scans one stack,
    # so it serves the interleaved copy while the reference reads the family's
    out = asyncio.run(run.run_cell(
        bench, "tiny-twokind-chat", 2**31 + 9, 6.0, False, platform="cpu", files=DATA,
        ref_params_fault=family.served,
    ))
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 24
    assert set(out["metrics"]) == {"ttft_p50_ms", "ttft_mean_ms", "tpot_mean_ms", "setup_s"}


def test_the_toy_tree_is_one_stack_a_kind_and_the_file_maps_as_the_family_says():
    spec = load_json("configs", "tiny-twokind", DATA)
    config = model_config(spec, "tiny-twokind", DATA)
    assert (config.n_layers, config.head_dim, config.rope_theta) == (4, 8, 10000.0)
    family = load_module("families", "twokind", DATA)
    tree = family.make_params(config, 0)
    assert set(tree["layers"]) == {"sliding_attention", "full_attention"}
    assert tree["layers"]["full_attention"]["wq"]["q"].shape == (2, 64, 64)
    stack, place = family.ref_layer_params(tree, 3)
    assert list(stack) == ["full_attention"] and place == 1
    assert family.served(tree)["layers"]["wq"]["q"].shape == (4, 64, 64)


@pytest.mark.parametrize(
    "change, message",
    [
        ({"sliding_window": 64}, "can bind"),
        ({"layer_types": ["full_attention"] * 4}, "period"),
        ({"rope_parameters": {"sliding_attention": {"rope_theta": 1e4},
                              "full_attention": {"rope_theta": 1e6}}}, "one rope base"),
        ({"attention_bias": False}, "attention_bias"),
    ],
    ids=["window-binds", "not-the-period", "two-rope-bases", "unmapped-key"],
)
def test_the_toy_family_refuses_what_the_block_cannot_compute(change, message):
    spec = {**load_json("configs", "tiny-twokind", DATA), **change}
    with pytest.raises(ValueError, match=message):
        model_config(spec, "tiny-twokind", DATA)


def test_no_module_of_the_harness_knows_the_toy_family():
    modules = [p for p in BENCH.rglob("*.py") if BENCH / "tests" not in p.parents]
    assert len(modules) > 20
    word = re.compile("|".join(TOY_ONLY))
    assert {str(p): word.findall(p.read_text()) for p in modules if word.search(p.read_text())} == {}


def test_the_four_harness_modules_name_nothing_of_a_family():
    """No published key beyond the harness's own, no weight leaf, no kernel and
    no model function of the program, outside a docstring."""
    import ast

    theirs = re.compile(
        r"\b(wq|wk|wv|wo|w_gate|w_up|w_down|num_local_experts|num_experts_per_tok|rope_theta"
        r"|hidden_size|paged_insert_cache|paged_decode_step_inplace|_layer|_embed|_unembed"
        r"|_rope_freqs|flash_prefill_attention|ragged_paged_decode_attention|mistral|mixtral)\b"
    )
    for name in ("modelcfg.py", "weights.py", "check.py", "run.py"):
        tree = ast.parse((BENCH / name).read_text())
        for node in ast.walk(tree):  # blank the docstrings
            if isinstance(node, (ast.Module, ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                if ast.get_docstring(node) is not None:
                    node.body[0].value.value = ""
        assert theirs.findall(ast.unparse(tree)) == [], name

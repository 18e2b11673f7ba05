"""The correctness check: it passes on the sound system, it fails on each
known fault, and its verdict does not move with `--seed`.

A fault is made by handing the ENGINE a damaged copy of the weights while
the reference keeps the sound ones (`ref_params`), or by serving a
configuration other than the one the file states. The toy family that fills
blocks by denoising (`data/families/blockfill.py`) is driven by a stub engine
(`data/blockfill_engine.py`), whose knobs are its faults.
"""

import hashlib
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import check
from check import run_check
from modelcfg import load_module, register_preset
from traffic_kinds import open_poisson

DATA = Path(__file__).parent / "data"
ENGINE = {"max-batch": 8, "max-seq-len": 128, "prefill-buckets": [32, 64],
          "kv-pages": 64, "page-size": 16, "tokenizer": "byte"}


def build(name: str, fault=None):
    from langstream_tpu.ai.tpu_serving import TpuServingProvider

    spec = json.loads((DATA / "configs" / f"{name}.json").read_text())
    config = register_preset(spec, name)
    family = load_module("families", spec["family"])
    sound = family.make_params(config, int(spec["weights"]["seed"]))
    provider = TpuServingProvider({**spec["serving"], **ENGINE, "model": name})
    provider.holder._params = fault(sound) if fault else sound
    return provider, spec, sound


def verdict_of(name: str, fault=None) -> dict:
    provider, spec, sound = build(name, fault)
    try:
        return run_check(provider.engine(), spec, ref_params=sound)
    finally:
        provider.holder.close()


def zero_scale(tree, key: str, index):
    """The named matrix contributes nothing: its int8 scale is zeroed."""
    layers = dict(tree["layers"])
    layers[key] = {**layers[key], "s": layers[key]["s"].at[index].set(0.0)}
    return {**tree, "layers": layers}


def skip_layer_1(tree):
    # attention output and FFN output both zero: the layer is the identity
    return zero_scale(zero_scale(tree, "wo", 1), "w_down", 1)


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
def test_sound_system_passes(name):
    verdict = verdict_of(name)
    assert verdict["ok"], verdict
    assert verdict["unexplained_over_tol"] == 0 and verdict["engine_margin_over_tol"] == 0


@pytest.mark.parametrize(
    "name, fault",
    [
        ("tiny-dense", skip_layer_1),
        ("tiny-moe", skip_layer_1),
        ("tiny-moe", lambda tree: zero_scale(tree, "w_down", (0, 3))),  # one expert's output
        ("tiny-dense-kv8", None),  # KV cache in int8 against a file that says bf16
    ],
    ids=["dense-layer-skipped", "moe-layer-skipped", "expert-zeroed", "kv-int8-file-says-bf16"],
)
def test_known_fault_fails(name, fault):
    verdict = verdict_of(name, fault)
    assert verdict["ok"] is False, verdict
    # by a number, never by a reported type alone: a weight fault parts the
    # chain from the reference, a cache fault parts the hot path from the chain
    assert verdict["unexplained_over_tol"] > 0 or verdict["hot_err_over_tol"] > 0, verdict


def test_a_cache_of_fewer_bits_fails_by_its_numbers():
    """int8 KV behind a file that says bf16: every hot-path position leaves
    the chain by more than the tolerance, whatever `engine_state` reports;
    the sound system's hot path is the chain's to the bit on the CPU."""
    sound, faulted = verdict_of("tiny-dense"), verdict_of("tiny-dense-kv8")
    assert sound["hot_err_over_tol"] == 0 and sound["hot_err_max_unexposed"] < 0.001
    assert faulted["hot_err_median_unexposed"] > 0.01 > sound["hot_err_median_unexposed"]
    assert faulted["hot_err_over_tol"] >= faulted["engine_positions"] == 32
    assert faulted["unexplained_over_tol"] == 0  # the model itself is sound


def test_verdict_does_not_move_with_the_seed():
    """Twenty values of --seed: the seed makes the traffic and nothing else,
    so the check, run beside each, returns the same evidence to the digit."""
    provider, spec, sound = build("tiny-moe")
    traffic = json.loads((DATA / "traffic" / "tiny-chat.json").read_text())
    try:
        engine = provider.engine()
        seen = []
        for seed in [0, 1, 2**31 + 7, *range(1000, 1017)]:
            open_poisson.schedule(traffic, seed, 6.0, engine.config.vocab_size)
            seen.append(json.dumps(run_check(engine, spec, ref_params=sound), sort_keys=True))
        assert len(seen) == 20 and len(set(seen)) == 1
    finally:
        provider.holder.close()
        jax.clear_caches()


# ---- a family whose engine fills blocks of tokens by denoising: judged by its passes


def blockfill_verdict(**faults) -> dict:
    found = importlib.util.spec_from_file_location("blockfill_engine", DATA / "blockfill_engine.py")
    stub = importlib.util.module_from_spec(found)
    found.loader.exec_module(stub)
    spec = json.loads((DATA / "configs" / "tiny-blockfill.json").read_text())
    family = load_module("families", "blockfill", DATA)
    engine = stub.BlockFillEngine(family, spec, "tiny-blockfill", **faults)
    return run_check(engine, spec, files=DATA)


def test_a_sound_block_filling_engine_passes_by_the_passes_that_made_its_tokens():
    verdict = blockfill_verdict()
    assert verdict["ok"], verdict
    # whole blocks: 16, 22, 37, 43 prompt tokens and at least 8 more
    assert verdict["generated_tokens"] == [8, 10, 11, 9]
    assert verdict["engine_positions"] == 38  # every generated token at the position that chose it
    # 13 blocks: 29 denoise passes and 13 commits of up to 56 positions, 3 rows a position
    assert verdict["layer_positions"] == 4320
    assert verdict["compared"]["engine_choice_over_tol_untied"] == [0, 0]
    assert verdict["engine_choice_positions"] == 26 and verdict["engine_choice_behind_max"] <= 0.0


@pytest.mark.parametrize(
    "fault, row",
    [
        ({"replace_token": 3}, "engine_margin_over_tol_untied"),
        ({"choose": "least"}, "engine_choice_over_tol_untied"),
        ({"denoise_mask": "causal"}, "hot_err_over_tol_untied"),
        ({"denoise_mask": "causal"}, "hot_err_median_untied"),
        ({"cache_dtype": "float8_e4m3fn"}, "hot_err_over_tol_untied"),
        ({"cache_dtype": "float8_e4m3fn"}, "hot_err_median_untied"),
    ],
    ids=["token-replaced-after-generation", "least-confident-position-fixed",
         "denoise-under-the-causal-mask", "denoise-under-the-causal-mask-median",
         "cache-of-fewer-bits", "cache-of-fewer-bits-median"],
)
def test_a_block_filling_fault_fails_by_its_row_of_compared(fault, row):
    verdict = blockfill_verdict(**fault)
    assert verdict["ok"] is False
    value, limit = verdict["compared"][row]
    assert value > limit, verdict["compared"]
    if row != "engine_choice_over_tol_untied" and "denoise_mask" not in fault:
        # an engine that chooses its positions soundly is not failed for its choice
        assert verdict["compared"]["engine_choice_over_tol_untied"] == [0, 0]
    assert verdict["compared"]["layer_err_over_tol_untied"] == [0, 0]  # the model itself is sound


@pytest.mark.parametrize(
    "prompt_len, fixed_in, reads",
    [
        # a prompt of whole blocks; one block of 4 fixed 2, 1, 1: three denoise passes, a commit
        (8, [1, 0, 2, 0], [[9, 11], [8], [10], []]),
        # the prompt's tail opens the block: 2 open positions fixed in one pass, then a whole block
        (6, [0, 0, 0, 1, 0, 2], [[6, 7], [], [8, 10], [9], [11], []]),
    ],
    ids=["whole-blocks", "prompt-tail-in-the-block"],
)
def test_the_toy_trajectory_rebuilds_the_passes_from_tokens_and_labels(prompt_len, fixed_in, reads):
    spec = json.loads((DATA / "configs" / "tiny-blockfill.json").read_text())
    family = load_module("families", "blockfill", DATA)
    prompt = list(range(100, 100 + prompt_len))
    tokens = list(range(200, 200 + len(fixed_in)))
    passes = family.trajectory(spec, prompt, SimpleNamespace(tokens=tokens, fixed_in=fixed_in))
    assert [p["read"] for p in passes] == reads
    whole, mask = prompt + tokens, spec["mask_token_id"]
    for a_pass in passes:
        assert len(a_pass["tokens"]) % 4 == 0
        assert a_pass["picked"] == [whole[p] for p in a_pass["read"]]
        if a_pass["read"]:  # what it fixes was still masked, what it left too, and nothing else
            assert set(a_pass["read"]) <= set(a_pass["open"])
            assert [p for p, t in enumerate(a_pass["tokens"]) if t == mask] == a_pass["open"]
        else:  # a commit sees the clean block
            assert a_pass["tokens"] == whole[: len(a_pass["tokens"])] and "open" not in a_pass


# What `_Scorer.score` returned at the parent (PR 39's tree, before a pass was
# the unit), as sha256 digests of each array with its dtype and shape, taken
# there on the CPU: tiny-dense's four sequences; tiny-moe's four and the four
# of its batched probe. `margin` is the parent's array from the first
# generated position on, which is all that its judge read.
PARENT_SCORES = {
    "tiny-dense": [
        ("ca09c7ad4a813584", "814adcb7f435413c", 0, "d5e11c5b85e7c3d0", "2c17ac6fea103d41", "2c17ac6fea103d41"),
        ("7f67c35b03959814", "61f54aec57581dbe", 0, "1d4ac57a5e7268e6", "85ba6ca820ec68ed", "2c17ac6fea103d41"),
        ("3fd23f97f1f19843", "d61db4c8893aa609", 0, "e5a3c6bbcd409a87", "2c17ac6fea103d41", "2c17ac6fea103d41"),
        ("55d573b27c711582", "7c18aa32e46d3335", 0, "aae1b2d5fe16c29e", "2c17ac6fea103d41", "2c17ac6fea103d41"),
    ],
    "tiny-moe": [
        ("9099ad6efd353a93", "313f2c7f2d629a65", 44, "bfcddcf63e7d84c3", "2c17ac6fea103d41", "2c17ac6fea103d41"),
        ("08fb9915c70857bc", "d3a5092dcbd999d5", 44, "5d2992d5c74d9c1e", "2c17ac6fea103d41", "e5ac69afb69664ea"),
        ("73d71b9db5c9ec2e", "d0c7ca927cd4f4e8", 41, "3bd3a5e42dccd3d0", "2c17ac6fea103d41", "2c17ac6fea103d41"),
        ("b24383253c2e976e", "29ee46718c82c8c0", 40, "715aec10c1763271", "2c17ac6fea103d41", "23a6c2a4f53102f5"),
    ] * 2,
}
PARENT_COMPARED = {
    "tiny-dense": {"layer_err_median": [0.005205606110394001, 0.02], "e2e_err_max": [0.0330289863049984, 0.15]},
    "tiny-moe": {"layer_err_median": [0.005064649973064661, 0.02]},
}


def _digest(array) -> str:
    a = np.ascontiguousarray(array)
    return hashlib.sha256(str(a.dtype).encode() + str(a.shape).encode() + a.tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
def test_the_single_pass_built_for_a_family_without_a_trajectory_scores_as_the_parent_did(name, monkeypatch):
    seen, score = [], check._Scorer.score

    def recording(self, sys_params, ref_params, a_pass, hot):
        out = score(self, sys_params, ref_params, a_pass, hot)
        seen.append((a_pass, out))
        return out

    monkeypatch.setattr(check._Scorer, "score", recording)
    verdict = verdict_of(name)
    assert [
        (_digest(out["layer_err"]), _digest(out["router_gap"]), int(out["expert_load_max"]),
         _digest(out["e2e_err"]), _digest(out["margin"]), _digest(out["hot_err"]))
        for _, out in seen
    ] == PARENT_SCORES[name]
    for a_pass, out in seen:  # one pass a sequence, read where the next token was drawn
        n_prompt = len(a_pass["tokens"]) - len(a_pass["picked"])
        assert a_pass["read"] == list(range(n_prompt - 1, len(a_pass["tokens"]) - 1))
        assert "open" not in a_pass and "choice" not in out
    # a family that exports neither `trajectory` nor `choice_score` has no new row
    assert "engine_choice_over_tol_untied" not in verdict["compared"]
    assert {k: verdict["compared"][k] for k in PARENT_COMPARED[name]} == PARENT_COMPARED[name]
    assert verdict["engine_positions"] == 32

"""The correctness check: it passes on the sound system, it fails on each
known fault, and its verdict does not move with `--seed`.

A fault is made by handing the ENGINE a damaged copy of the weights while
the reference keeps the sound ones (`ref_params`), or by serving a
configuration other than the one the file states.
"""

import json
from pathlib import Path

import jax
import pytest

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

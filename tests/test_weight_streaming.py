"""Streamed sharded weight loading (models/streamload.py, docs/SERVING.md
§22): bit-exactness vs the eager loader on every architecture × dtype ×
shard layout, on-the-fly int8 vs load-then-quantize, host staging-peak
bounding, short-read loudness, the `weight-load` chaos site through the
tpu-serving holder, and the LoRA suffix-map ambiguity guard.

Bit-EXACT means np.array_equal, not allclose: the streamed pipeline runs
the same host transforms and the same quant.py ops per layer that the
eager path runs on the stacked tree, so any tolerance here would be hiding
a real divergence (e.g. the XLA fused-division rewrite the eager-per-layer
quantize exists to avoid).
"""

import dataclasses

import jax
import numpy as np
import pytest

from langstream_tpu.models.configs import MODEL_PRESETS, ModelConfig
from langstream_tpu.models.loader import (
    load_lora_params,
    load_params,
    save_params_hf,
)
from langstream_tpu.models.quant import quantize_params
from langstream_tpu.models.streamload import (
    WeightLoadError,
    load_params_streamed,
)
from langstream_tpu.models.transformer import init_params

DENSE = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
MOE = dataclasses.replace(MODEL_PRESETS["tiny-moe-test"], dtype="float32")
GEMMA_TINY = ModelConfig(
    name="tiny-gemma", vocab_size=256, d_model=32, n_layers=2, n_heads=4,
    n_kv_heads=1, d_ff=64, activation="gelu", tie_embeddings=True,
    embedding_scale=True, dtype="float32",
)

# multi-shard: small enough that every tiny config splits into several
# files, exercising the cross-shard index + the parallel reader pool
MULTI_SHARD = 60_000


def _assert_bit_exact(a, b, path=""):
    if isinstance(a, dict):
        assert isinstance(b, dict) and set(a) == set(b), path
        for k in a:
            _assert_bit_exact(a[k], b[k], f"{path}.{k}")
        return
    na, nb = np.asarray(a), np.asarray(b)
    assert na.dtype == nb.dtype, f"{path}: {na.dtype} != {nb.dtype}"
    assert np.array_equal(na, nb), f"{path}: values differ"


def _checkpoint(config, tmp_path, max_shard_bytes):
    params = init_params(config, jax.random.PRNGKey(0))
    save_params_hf(params, config, tmp_path, max_shard_bytes=max_shard_bytes)
    return params


# ---------------------------------------------------------------------------
# Tentpole: streamed == eager, bit for bit, on every architecture the
# loader knows (dense llama-style, gemma quirks, MoE expert stacking) ×
# serving dtypes × single-file / multi-shard layouts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shard_bytes", [None, MULTI_SHARD],
                         ids=["single-file", "multi-shard"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("config", [DENSE, GEMMA_TINY, MOE],
                         ids=lambda c: c.name)
def test_streamed_matches_eager_bit_exact(config, dtype, shard_bytes, tmp_path):
    _checkpoint(config, tmp_path, shard_bytes)
    cfg = dataclasses.replace(config, dtype=dtype)
    eager = load_params(tmp_path, cfg)
    streamed, rep = load_params_streamed(tmp_path, cfg, workers=3)
    _assert_bit_exact(eager, streamed)
    assert rep.streamed and rep.blocked
    assert rep.shards == (1 if shard_bytes is None else rep.shards)
    if shard_bytes is not None:
        assert rep.shards > 1, "fixture must actually split into shards"
    assert rep.bytes_read > 0 and rep.total_s > 0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("config", [DENSE, GEMMA_TINY, MOE],
                         ids=lambda c: c.name)
def test_quantize_on_load_matches_eager_int8_bit_exact(config, dtype, tmp_path):
    """On-the-fly int8 == load-then-quantize_params, including the scales:
    per-layer eager quantization agrees with stacked quantization because
    amax reduces over a within-layer axis, and cast-to-model-dtype happens
    BEFORE quantize on both paths (f32→bf16→f32 is not identity)."""
    _checkpoint(config, tmp_path, MULTI_SHARD)
    cfg = dataclasses.replace(config, dtype=dtype)
    eager = quantize_params(load_params(tmp_path, cfg), cfg)
    streamed, rep = load_params_streamed(
        tmp_path, cfg, workers=3, quantize=True
    )
    _assert_bit_exact(eager, streamed)
    assert rep.quantize_on_load


# ---------------------------------------------------------------------------
# Host staging peak: the point of the pipeline — host RAM holds a readahead
# window of layers, never the tree (the eager path peaks at ~2× the weight
# bytes: the raw dict + the stacked copies)
# ---------------------------------------------------------------------------


def test_staging_peak_bounded_below_half_of_checkpoint(tmp_path):
    deep = dataclasses.replace(DENSE, n_layers=8, name="tiny-deep")
    _checkpoint(deep, tmp_path, MULTI_SHARD)
    _, rep = load_params_streamed(tmp_path, deep, workers=2)
    assert rep.staging_peak_bytes > 0
    # with 8 layers and a 3-layer readahead window the staging high-water
    # mark must sit well under the full checkpoint — this is the bound that
    # separates streaming from "eager with extra steps"
    assert rep.staging_peak_bytes < rep.bytes_read / 2, (
        f"staging peak {rep.staging_peak_bytes} not bounded below half of "
        f"{rep.bytes_read}"
    )


# ---------------------------------------------------------------------------
# Short reads fail LOUDLY: a truncated shard must name the file and the
# tensor, and must never produce a partial tree
# ---------------------------------------------------------------------------


def test_truncated_shard_raises_naming_shard_and_tensor(tmp_path):
    _checkpoint(DENSE, tmp_path, MULTI_SHARD)
    victim = sorted(tmp_path.glob("*.safetensors"))[-1]
    data = victim.read_bytes()
    victim.write_bytes(data[: len(data) - 64])
    with pytest.raises(WeightLoadError) as exc:
        load_params_streamed(tmp_path, DENSE, workers=2)
    msg = str(exc.value)
    assert victim.name in msg, f"shard not named in {msg!r}"
    assert "truncated" in msg


def test_header_only_tells_no_lies_single_file(tmp_path):
    """Truncation below the data a tensor needs is caught at INDEX time
    (byte spans validated against real file size) — before any read."""
    _checkpoint(DENSE, tmp_path, None)
    victim = tmp_path / "model.safetensors"
    data = victim.read_bytes()
    victim.write_bytes(data[: len(data) // 2])
    with pytest.raises(WeightLoadError, match="truncated"):
        load_params_streamed(tmp_path, DENSE)


# ---------------------------------------------------------------------------
# Chaos: the weight-load fault site through the tpu-serving holder — the
# drill for "a shard came up short mid-read on a real pod". No partial
# engine, zero retries, the error names the poison.
# ---------------------------------------------------------------------------


def test_weight_load_fault_site_no_partial_engine_zero_retries(tmp_path):
    from langstream_tpu.ai.tpu_serving import _EngineHolder

    _checkpoint(DENSE, tmp_path, MULTI_SHARD)
    holder = _EngineHolder({
        "model": "tiny-test", "max-batch": 2, "max-seq-len": 64,
        "weights": str(tmp_path),
        "fault-injection": "weight-load@1", "fault-seed": 0,
    })
    with pytest.raises(WeightLoadError) as exc:
        holder.engine()
    msg = str(exc.value)
    assert "injected weight-load fault" in msg
    assert ".safetensors" in msg, f"shard not named in {msg!r}"
    # no partial engine, no cached half-loaded params
    assert holder._engine is None
    assert holder._params is None
    # the injector fired EXACTLY once: the reader pool cancelled its
    # readahead instead of retrying the poisoned shard
    assert holder._fault_injector().stats().get("weight-load", 0) == 1


def test_fault_injector_direct_fires_once(tmp_path):
    from langstream_tpu.serving.faultinject import FaultInjector

    _checkpoint(DENSE, tmp_path, MULTI_SHARD)
    inj = FaultInjector("weight-load@1", seed=0)
    with pytest.raises(WeightLoadError):
        load_params_streamed(tmp_path, DENSE, workers=3, fault_injector=inj)
    assert inj.stats().get("weight-load", 0) == 1


# ---------------------------------------------------------------------------
# Holder integration: the stats() weight-load block + streamed-off knob
# ---------------------------------------------------------------------------


def test_holder_stats_carry_weight_load_block(tmp_path):
    from langstream_tpu.ai.tpu_serving import _EngineHolder

    _checkpoint(DENSE, tmp_path, MULTI_SHARD)
    holder = _EngineHolder({
        "model": "tiny-test", "max-batch": 2, "max-seq-len": 64,
        "weights": str(tmp_path), "weight-load-workers": 3,
    })
    engine = holder.engine()
    try:
        st = engine.stats()
        assert st["weight-load-streamed"] is True
        assert st["weight-load-s"] > 0
        assert st["weight-load-bytes-total"] > 0
        assert st["weight-load-shards"] > 1
        assert st["weight-load-workers"] == 3
        assert st["weight-load-staging-peak-bytes"] > 0
        # per-phase split present (reader threads overlap, so the parts
        # need not sum to the wall)
        for k in ("weight-load-read-s", "weight-load-transform-s",
                  "weight-load-transfer-s"):
            assert st[k] >= 0
        # holder-level parity: the engine is serving the SAME weights the
        # eager loader would have produced
        _assert_bit_exact(
            load_params(tmp_path, holder.model_config()), holder.params()
        )
    finally:
        holder.close()  # stops the engine AND takes it out of the process's fleet registry


def test_holder_weight_streaming_off_still_reports(tmp_path):
    from langstream_tpu.ai.tpu_serving import _EngineHolder

    _checkpoint(DENSE, tmp_path, None)
    holder = _EngineHolder({
        "model": "tiny-test", "max-batch": 2, "max-seq-len": 64,
        "weights": str(tmp_path), "weight-streaming": "off",
    })
    engine = holder.engine()
    try:
        st = engine.stats()
        assert st["weight-load-streamed"] is False
        # the eager baseline still fills the comparable ledger keys
        assert st["weight-load-s"] > 0
        assert st["weight-load-bytes-total"] > 0
    finally:
        holder.close()  # stops the engine AND takes it out of the process's fleet registry


def test_holder_rejects_bad_knobs():
    from langstream_tpu.ai.tpu_serving import _EngineHolder

    with pytest.raises(ValueError, match="weight-streaming"):
        _EngineHolder({
            "model": "tiny-test", "weight-streaming": "sometimes",
        }).params()
    with pytest.raises(ValueError, match="weight-load-workers"):
        _EngineHolder({
            "model": "tiny-test", "weights": "random",
            "weight-load-workers": 0,
        }).params()
    with pytest.raises(ValueError, match="quantize-on-load"):
        _EngineHolder({
            "model": "tiny-test", "quantize-on-load": "maybe",
        }).params()


# ---------------------------------------------------------------------------
# Satellite: the LoRA suffix→key map fails LOUDLY on ambiguous duplicates
# (two export prefixes sharing a canonical tail) instead of silently
# loading whichever key iterated first
# ---------------------------------------------------------------------------


def test_lora_ambiguous_duplicate_suffix_raises(tmp_path):
    from safetensors import numpy as st_numpy

    rank = 2
    a = np.zeros((rank, DENSE.d_model), np.float32)
    b = np.zeros((DENSE.d_model, rank), np.float32)
    st_numpy.save_file(
        {
            "base_model.model.model.layers.0.self_attn.q_proj.lora_A.weight": a,
            "other_export.model.layers.0.self_attn.q_proj.lora_A.weight": a,
            "base_model.model.model.layers.0.self_attn.q_proj.lora_B.weight": b,
        },
        str(tmp_path / "adapter.safetensors"),
    )
    with pytest.raises(ValueError, match="ambiguous"):
        load_lora_params(tmp_path / "adapter.safetensors", DENSE, rank)


def test_lora_prefixed_keys_still_found(tmp_path):
    """The suffix map must keep matching peft's export-dependent prefixes
    (the behavior the old endswith scan provided)."""
    from safetensors import numpy as st_numpy

    rng = np.random.default_rng(0)
    rank = 2
    tensors = {}
    for i in range(DENSE.n_layers):
        tensors[
            f"base_model.model.model.layers.{i}.self_attn.q_proj.lora_A.weight"
        ] = rng.standard_normal((rank, DENSE.d_model)).astype(np.float32)
        tensors[
            f"base_model.model.model.layers.{i}.self_attn.q_proj.lora_B.weight"
        ] = rng.standard_normal((DENSE.d_model, rank)).astype(np.float32)
    st_numpy.save_file(tensors, str(tmp_path / "adapter.safetensors"))
    out = load_lora_params(tmp_path / "adapter.safetensors", DENSE, rank)
    assert out["wq"]["a"].shape == (DENSE.n_layers, DENSE.d_model, rank)
    # transpose-on-load: peft A is [r, in], ours is [in, r]
    expect = tensors[
        "base_model.model.model.layers.0.self_attn.q_proj.lora_A.weight"
    ].T
    np.testing.assert_array_equal(np.asarray(out["wq"]["a"][0]), expect)

"""Serving HBM accounting (serving/memory.py): the plans that decide what
context length a chip honestly serves — nothing allocates, shapes only."""

import dataclasses
import inspect

from langstream_tpu.models.configs import MODEL_PRESETS
from langstream_tpu.serving.engine import ServingEngine
from langstream_tpu.serving.memory import (
    max_context_single_chip,
    plan_serving_memory,
)
from langstream_tpu.serving.pagepool import pages_for_fraction

GIB = 1024**3


def test_plan_tracks_real_param_and_pool_shapes():
    cfg = MODEL_PRESETS["tiny-test"]
    plan = plan_serving_memory(cfg, 4, 256, workspace_bytes=0)
    # bf16 weights: 2 bytes per param; the tiny config is well under 10MB
    assert 0 < plan.weights_bytes < 10 * 1024**2
    # pool: 2 (K+V) × L×P×Hkv×page×D × 2 bytes, every slot's max_seq_len
    pages = pages_for_fraction(4, 256, 64)
    assert pages == 16
    expected_pool = (
        2 * cfg.n_layers * pages * cfg.n_kv_heads * 64 * cfg.resolved_head_dim * 2
    )
    assert plan.page_pool_bytes == expected_pool
    # the pool is the only KV term: nothing a layer or a slot wide beside it
    assert plan.total_bytes == plan.weights_bytes + plan.page_pool_bytes
    # an explicit page count wins over the default sizing
    half = plan_serving_memory(cfg, 4, 256, workspace_bytes=0, kv_pages=8)
    assert half.page_pool_bytes == expected_pool // 2


def test_int8_weights_and_kv_shrink_the_plan():
    cfg = MODEL_PRESETS["tiny-test"]
    fp = plan_serving_memory(cfg, 4, 256, workspace_bytes=0)
    q = plan_serving_memory(cfg, 4, 256, quantized_weights=True, workspace_bytes=0)
    assert q.weights_bytes < fp.weights_bytes
    kv8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    q8 = plan_serving_memory(kv8, 4, 256, quantized_weights=True, workspace_bytes=0)
    assert q8.page_pool_bytes < q.page_pool_bytes


def test_llama31_single_chip_ceiling():
    """The honest long-context claim for the 128k NTK preset on a 16GiB
    chip: int8 weights + an int8 pool hold every slot's full context at
    64k for B=1, 32k for B=2, 16k for B=4. Nothing a cache wide stands
    beside the pool (the layer scan addresses it in place, long prompts
    stream into the slot's own pages), so the ceiling is weights + pool +
    workspace."""
    cfg = dataclasses.replace(MODEL_PRESETS["llama-3.1-8b"], kv_cache_dtype="int8")
    hbm = 16 * GIB
    assert max_context_single_chip(cfg, 1, hbm) == 65536
    assert max_context_single_chip(cfg, 2, hbm) == 32768
    assert max_context_single_chip(cfg, 4, hbm) == 16384
    # a bf16 pool holds 32k at B=1 and not 64k — the plan says so
    bf = MODEL_PRESETS["llama-3.1-8b"]
    assert plan_serving_memory(bf, 1, 32768, quantized_weights=True).fits(hbm)
    assert not plan_serving_memory(bf, 1, 65536, quantized_weights=True).fits(hbm)
    # the same 84 slots cost four times the pool at four times the context
    l3 = dataclasses.replace(MODEL_PRESETS["llama-3-8b"], kv_cache_dtype="int8")
    narrow = plan_serving_memory(l3, 84, 256, quantized_weights=True)
    wide = plan_serving_memory(l3, 84, 1024, quantized_weights=True)
    assert wide.page_pool_bytes == 4 * narrow.page_pool_bytes
    assert narrow.fits(hbm) and wide.fits(hbm)
    assert not plan_serving_memory(l3, 84, 2048, quantized_weights=True).fits(hbm)


def test_one_kv_layout_no_parameter_for_another():
    """The page pool is the engine's only KV state: neither the engine nor
    the memory plan takes a layout (or the dense layout's pool sizes), and
    the plan has no term for a dense cache."""
    engine_params = inspect.signature(ServingEngine.__init__).parameters
    plan_params = inspect.signature(plan_serving_memory).parameters
    assert "kv_layout" not in engine_params and "kv_layout" not in plan_params
    assert not {"prefix_pool_entries", "prefix_pool_width", "long_prefill"} & set(
        plan_params
    )
    fields = {f.name for f in dataclasses.fields(plan_serving_memory(
        MODEL_PRESETS["tiny-test"], 2, 128
    ))}
    assert "page_pool_bytes" in fields
    assert not {"cache_bytes", "long_cache_bytes", "bound_slice_bytes",
                "prefix_pool_bytes", "scan_buffer_bytes"} & fields


def test_fused_prefill_term():
    """The fused-iteration peak charges the admission local cache
    (prefill_batch rows × bucket width) alongside the pool."""
    cfg = MODEL_PRESETS["tiny-test"]
    base = plan_serving_memory(cfg, 4, 256, workspace_bytes=0)
    assert base.fused_prefill_bytes == 0  # no group shape given: no term
    fused = plan_serving_memory(
        cfg, 4, 256, workspace_bytes=0, prefill_batch=8, prefill_bucket=64,
    )
    # admit cache: 8 rows × 64 cols vs a pool of 4 × 256 → exactly half
    assert fused.fused_prefill_bytes == base.page_pool_bytes // 2
    assert fused.total_bytes == base.total_bytes + fused.fused_prefill_bytes
    # a bucket wider than the context is cut to it
    cut = plan_serving_memory(
        cfg, 4, 256, workspace_bytes=0, prefill_batch=8, prefill_bucket=1024,
    )
    assert cut.fused_prefill_bytes == 4 * fused.fused_prefill_bytes


def test_verify_chunk_term_scales_with_speculation_tokens():
    """Self-speculative decoding peaks at ~5 live [B, k+1, V] fp32 buffers
    per verify dispatch (logits + the rejection sampler's filtered-path
    temps) — a term, not workspace noise (~4.6 GiB at gemma-2b production
    shapes). Off ⇒ 0, and the term is linear in k+1."""
    cfg = MODEL_PRESETS["tiny-test"]
    base = plan_serving_memory(cfg, 4, 256, workspace_bytes=0)
    assert base.verify_chunk_bytes == 0  # speculation off: accounting unchanged
    spec = plan_serving_memory(cfg, 4, 256, workspace_bytes=0, speculation_tokens=4)
    assert spec.verify_chunk_bytes == 5 * 4 * 5 * cfg.vocab_size * 4
    assert spec.total_bytes == base.total_bytes + spec.verify_chunk_bytes
    wider = plan_serving_memory(cfg, 4, 256, workspace_bytes=0, speculation_tokens=9)
    assert wider.verify_chunk_bytes == 2 * spec.verify_chunk_bytes

"""North-star bench: chat-completions decode throughput + gateway TTFT.

Two measurements on the local chip:

1. Engine: the continuous-batching ServingEngine (the component replacing
   the reference's remote OpenAI call in ChatCompletionsStep — SURVEY §3.3)
   on int8-quantized Gemma-2B weights, aggregate generated tokens/sec across
   a full batch of concurrent requests. This is the headline value.
2. End-to-end platform: the same model behind the FULL path the reference
   benchmarks implicitly — broker → ai-chat-completions agent →
   stream-to-topic chunks → gateway WebSocket chat (mirroring
   examples/applications/openai-completions min-chunks-per-message growth
   batching) — reporting aggregate streamed tok/s and p50 TTFT at the
   websocket. Reported in "extras".

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extras"}.
vs_baseline is against BASELINE.json's 2000 tok/s aggregate target.
"""

from __future__ import annotations

import asyncio
import json
import sys
import tempfile
import time
from pathlib import Path

# short enough that the chat-template-rendered prompt stays inside the
# 64-token prefill bucket under the byte tokenizer
QUESTION = "How does a TPU multiply matrices?"

PIPELINE = """\
module: default
id: bench
topics:
  - name: questions-topic
    creation-mode: create-if-not-exists
  - name: answers-topic
    creation-mode: create-if-not-exists
  - name: debug-topic
    creation-mode: create-if-not-exists
pipeline:
  - name: convert-to-structure
    type: document-to-json
    input: questions-topic
    configuration:
      text-field: question
  - name: chat
    type: ai-chat-completions
    output: debug-topic
    configuration:
      model: "{model}"
      stream-to-topic: answers-topic
      stream-response-completion-field: value
      min-chunks-per-message: 10
      completion-field: value.answer
      max-tokens: {max_tokens}
      messages:
        - role: user
          content: "{{{{ value.question }}}}"
"""

CONFIGURATION = """\
configuration:
  resources:
    - type: tpu-serving
      name: tpu
      configuration:
        model: "{model}"
        tokenizer: byte
        max-batch: {max_batch}
        max-seq-len: {max_seq_len}
        decode-chunk: {decode_chunk}
        prefill-batch: {prefill_batch}
        prefill-buckets: [64]
        overlap: {overlap}
        {quant_line}
"""

GATEWAYS = """\
gateways:
  - id: chat
    type: chat
    parameters: [sessionId]
    chat-options:
      questions-topic: questions-topic
      answers-topic: answers-topic
      headers:
        - key: langstream-client-session-id
          value-from-parameters: sessionId
"""

INSTANCE = """\
instance:
  streamingCluster:
    type: memory
  computeCluster:
    type: local
"""


def bench_engine(preset: str, quantize: bool, max_batch: int, new_tokens: int,
                 n_requests: int, max_seq_len: int, decode_chunk: int,
                 prefill_batch: "int | None" = None,
                 kv_int8: bool = False, kv_layout: str = "paged",
                 observability: bool = True) -> float:
    import dataclasses

    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.engine import GenerationRequest, ServingEngine

    config = MODEL_PRESETS[preset]
    if kv_int8:
        config = dataclasses.replace(config, kv_cache_dtype="int8")
    if quantize:
        # random int8 params built directly on device: shape-identical to
        # quantize_params(init_params(...)) but never stages the fp tree —
        # 8B-class models would blow HBM before quantization otherwise
        from langstream_tpu.models.quant import init_random_quantized_params

        params = init_random_quantized_params(config, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    else:
        params = init_params(config, jax.random.PRNGKey(0))
    engine = ServingEngine(
        config,
        params,
        max_batch=max_batch,
        max_seq_len=min(max_seq_len, config.max_seq_len),
        prefill_buckets=(64,),
        decode_chunk=decode_chunk,
        # whole admission waves in one dispatch (the gateway phase's knob):
        # serial 8-row groups at wave boundaries were the last device gap
        prefill_batch=prefill_batch or max_batch,
        kv_layout=kv_layout,
        observability=observability,
    )
    engine.start()

    rng = np.random.default_rng(0)

    def make_request() -> GenerationRequest:
        prompt = rng.integers(1, config.vocab_size, size=32).tolist()
        return GenerationRequest(
            prompt_tokens=prompt,
            options=GenerationOptions(max_new_tokens=new_tokens, temperature=0.0),
        )

    try:
        # warmup: trigger prefill + decode compiles
        engine.submit(make_request()).result(timeout=600)

        start = time.monotonic()
        requests = [engine.submit(make_request()) for _ in range(n_requests)]
        results = [r.result(timeout=1200) for r in requests]
        elapsed = time.monotonic() - start
    finally:
        # ALWAYS stop: a failed phase must not leave the engine thread (and
        # its HBM-resident weights + cache) alive to OOM every later phase
        engine.stop()

    total_tokens = sum(len(r.tokens) for r in results)
    return total_tokens / elapsed


def bench_observability_overhead(preset: str, quantize: bool, *,
                                 max_batch: int, new_tokens: int,
                                 n_requests: int, max_seq_len: int,
                                 decode_chunk: int) -> dict:
    """Histogram + flight-recorder overhead pair (round 11): the SAME
    decode workload with the observability layer on (default) and off,
    fresh engines over shared params. The ISSUE bound is ≤1% of CPU decode
    step time for the hot-loop work (histogram record + ring append) —
    tests/test_observability.py asserts the per-step bound directly; this
    phase records the end-to-end throughput pair so PERF.md carries a
    measured number, not a claim."""
    out: dict = {}
    for on in (True, False):
        tag = "observability_on" if on else "observability_off"
        # best of two runs per leg: one fresh-engine run has enough
        # host-scheduling variance on CPU to swamp a ≤1% effect entirely
        # (first measured pair came out NEGATIVE) — the max is the
        # honest per-leg capability number
        tok_s = max(
            bench_engine(
                preset, quantize, max_batch, new_tokens, n_requests,
                max_seq_len, decode_chunk, observability=on,
            )
            for _ in range(2)
        )
        out[f"{tag}_tokens_per_sec"] = round(tok_s, 2)
        _reclaim()
    on_t = out["observability_on_tokens_per_sec"]
    off_t = out["observability_off_tokens_per_sec"]
    out["observability_overhead_pct"] = round(100.0 * (off_t - on_t) / off_t, 2)
    return out


def bench_long_prompt(preset: str, quantize: bool, prompt_len: int,
                      segment: int, max_seq_len: int, max_batch: int = 4,
                      kv_int8: bool = False) -> float:
    """Chunked-prefill TTFT: one long prompt on an otherwise idle engine —
    the latency a RAG request with a big stuffed context actually sees.
    Returns TTFT in seconds. ``kv_int8``/small ``max_batch``: the
    long-context shapes (serving/memory.py's plan is the arithmetic —
    llama-3.1-8b int8+int8kv at B=1 is what makes 32k fit 16G HBM)."""
    import dataclasses

    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.engine import GenerationRequest, ServingEngine

    config = MODEL_PRESETS[preset]
    if kv_int8:
        config = dataclasses.replace(config, kv_cache_dtype="int8")
    if quantize:
        from langstream_tpu.models.quant import init_random_quantized_params

        params = init_random_quantized_params(config, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    else:
        params = init_params(config, jax.random.PRNGKey(0))
    engine = ServingEngine(
        config,
        params,
        max_batch=max_batch,
        max_seq_len=min(max_seq_len, config.max_seq_len),
        prefill_buckets=(segment,),
        decode_chunk=8,
        # a 32k-wide engine's decode ladder is 10 programs (~15-20s compile
        # each) but this phase decodes 16 tokens after ONE long prefill —
        # the warmup request compiles the only shapes the measured request
        # uses, so the mid-traffic-stall hazard precompile exists for
        # cannot occur here
        precompile=False,
    )
    engine.start()
    rng = np.random.default_rng(1)
    opts = GenerationOptions(max_new_tokens=16, temperature=0.0)

    def req() -> GenerationRequest:
        prompt = rng.integers(1, config.vocab_size, size=prompt_len).tolist()
        return GenerationRequest(prompt_tokens=prompt, options=opts)

    try:
        engine.submit(req()).result(timeout=1200)  # warmup: compiles
        result = engine.submit(req()).result(timeout=1200)
    finally:
        engine.stop()  # leak-free even when a compile fails mid-phase
    return result.ttft_s


def _pct(sorted_values: list, p: float) -> float:
    """Percentile over an ascending list (nearest-rank). Engine-side
    phases now read percentiles from the engine's own streaming
    histograms (`_hist_pcts` — round 11: one estimator for bench, gauges
    and the load score); this stays for CLIENT-side distributions the
    engine cannot see (gateway websocket TTFT)."""
    return sorted_values[min(len(sorted_values) - 1, int(len(sorted_values) * p))]


def _hist_pcts(stats: dict, name: str, scale: float = 1e3,
               digits: int = 1) -> dict:
    """p50/p90/p99 of one engine histogram (stats()["histograms"]),
    scaled (default seconds → ms). The same numbers /metrics and the
    Grafana heatmap serve — the bench stops maintaining its own ad-hoc
    percentile lists for anything the engine already measures."""
    snap = (stats.get("histograms") or {}).get(name) or {}
    return {
        p: round(snap.get(p, 0.0) * scale, digits)
        for p in ("p50", "p90", "p99")
    }


def bench_prefix_burst(preset: str, quantize: bool, *, preamble_len: int,
                       n_chats: int, max_seq_len: int,
                       buckets: tuple, new_tokens: int = 16,
                       kv_int8: bool = False) -> dict:
    """Shared-system-prompt burst: ``n_chats`` concurrent chats with an
    IDENTICAL preamble and distinct user turns, measured twice — prefix
    cache on (auto) and off — on fresh engines over the same params. The
    chat workload the prefix cache exists for: after one warmup chat
    publishes the preamble's KV, every burst admission should reuse it and
    prefill only its own turn (p50 TTFT strictly better than off, hit rate
    ≥ (n_chats)/(n_chats+1) — the warmup miss is counted)."""
    import dataclasses

    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.engine import GenerationRequest, ServingEngine

    config = MODEL_PRESETS[preset]
    if kv_int8:
        config = dataclasses.replace(config, kv_cache_dtype="int8")
    if quantize:
        from langstream_tpu.models.quant import init_random_quantized_params

        params = init_random_quantized_params(config, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    else:
        params = init_params(config, jax.random.PRNGKey(0))

    rng = np.random.default_rng(7)
    preamble = rng.integers(1, config.vocab_size, size=preamble_len).tolist()
    turns = [
        rng.integers(1, config.vocab_size, size=24).tolist() for _ in range(n_chats)
    ]
    opts = GenerationOptions(max_new_tokens=new_tokens, temperature=0.0)

    out: dict = {"prefix_burst_chats": n_chats, "prefix_burst_preamble": preamble_len}
    for mode in ("auto", "off"):
        engine = ServingEngine(
            config,
            params,
            max_batch=max(8, n_chats),
            max_seq_len=min(max_seq_len, config.max_seq_len),
            prefill_buckets=buckets,
            decode_chunk=8,
            prefill_batch=max(8, n_chats),
            prefix_cache=mode,
            # big enough that the preamble entry survives the burst
            prefix_cache_entries=4 if mode == "auto" else None,
            # warm every program (incl. the prefix gather/segment shapes)
            # BEFORE the measured burst, as a production engine would —
            # otherwise the warm path pays its one-time compiles inside
            # the measured window and the comparison is startup, not
            # steady state
            precompile=True,
        )
        engine.start()
        try:
            # warmup chat: compiles AND (mode=auto) publishes the preamble
            engine.submit(GenerationRequest(
                prompt_tokens=preamble + turns[0], options=opts
            )).result(timeout=1200)
            # the warmup's compile-heavy TTFT must not own the measured
            # distribution's tail — the burst starts from zeroed histograms
            engine.reset_histograms()
            requests = [
                engine.submit(GenerationRequest(
                    prompt_tokens=preamble + turn, options=opts
                ))
                for turn in turns
            ]
            for r in requests:
                r.result(timeout=1200)
            stats = engine.stats()
        finally:
            engine.stop()

        tag = f"prefix_{mode}"
        # round 11: percentiles come from the engine's TTFT histogram,
        # zeroed after the warmup chat above — the burst's n_chats samples
        # only, same for both modes
        pcts = _hist_pcts(stats, "engine_ttft_s")
        out[f"{tag}_p50_ttft_ms"] = pcts["p50"]
        out[f"{tag}_p90_ttft_ms"] = pcts["p90"]
        out[f"{tag}_p99_ttft_ms"] = pcts["p99"]
        if mode == "auto":
            out["prefix_cache_hit_rate"] = stats["prefix-cache-hit-rate"]
            out["prefill_tokens_saved_total"] = stats["prefill-tokens-saved-total"]
            out["prefix_pool_bytes_in_use"] = stats["prefix-pool-bytes-in-use"]
            # paged layout (the default): hits ALIAS pages — these two are
            # the zero-copy acceptance numbers (bytes the dense gathers
            # would have moved; fraction of live pages shared)
            out["prefix_copy_bytes_saved_total"] = stats[
                "prefix-copy-bytes-saved-total"
            ]
            out["kv_page_alias_rate"] = stats["kv-page-alias-rate"]
        _reclaim()
    return out


def bench_paged_vs_dense(preset: str, quantize: bool, *, batches: tuple,
                         new_tokens: int, n_requests: int, max_seq_len: int,
                         decode_chunk: int, kv_int8: bool = False) -> dict:
    """Paged-vs-dense decode pair across a batch sweep (ISSUE 6
    acceptance): the same engine workload on the unified page pool vs the
    dense kv_bound-ladder layout, fresh engines per point. The sweep must
    include the shapes where the dense layout is known weak — B=128
    regressed on cache reads from round 2 on, and the gemma opt-in ragged
    kernel previously LOST to the dense masked path (PERF.md item 5); the
    paged kernel's content-proportional page DMAs are the rematch."""
    out: dict = {}
    for b in batches:
        for layout in ("paged", "dense"):
            try:
                tok_s = bench_engine(
                    preset, quantize, b, new_tokens,
                    max(n_requests, 2 * b), max_seq_len, decode_chunk,
                    kv_int8=kv_int8, kv_layout=layout,
                )
                out[f"{layout}_b{b}_tokens_per_sec"] = round(tok_s, 2)
            except Exception as e:  # noqa: BLE001 — record the points that ran
                print(
                    f"[bench] paged-vs-dense point {layout} B={b} failed: {e}",
                    file=sys.stderr, flush=True,
                )
            _reclaim()
    return out


def bench_speculation(preset: str, quantize: bool, *, max_batch: int,
                      n_requests: int, new_tokens: int, max_seq_len: int,
                      decode_chunk: int, spec_tokens: int = 4,
                      kv_int8: bool = False) -> dict:
    """Self-speculative decoding on the REPETITIVE-text workload (the one
    prompt-lookup drafts exist for: outputs that re-emit spans of their own
    context), measured twice — speculation on (auto) and off — on fresh
    engines over the same params. Greedy decode on fixed weights enters
    literal cycles on a periodic prompt, so acceptance is real, not
    simulated. Recorded: ms per accepted (= delivered) token, throughput,
    p50 TTFT, acceptance/hit rates — the on/off pair is the decision data
    for the `speculation` knob (PERF.md round 9)."""
    import dataclasses

    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.engine import GenerationRequest, ServingEngine

    config = MODEL_PRESETS[preset]
    if kv_int8:
        config = dataclasses.replace(config, kv_cache_dtype="int8")
    if quantize:
        from langstream_tpu.models.quant import init_random_quantized_params

        params = init_random_quantized_params(config, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    else:
        params = init_params(config, jax.random.PRNGKey(0))

    rng = np.random.default_rng(3)
    pattern = rng.integers(1, config.vocab_size, size=4).tolist()
    prompts = [
        (pattern * 12)[: 40] for _ in range(n_requests)
    ]
    opts = GenerationOptions(max_new_tokens=new_tokens, temperature=0.0)

    out: dict = {"spec_tokens": spec_tokens, "spec_requests": n_requests}
    for mode in ("auto", "off"):
        engine = ServingEngine(
            config,
            params,
            max_batch=max_batch,
            max_seq_len=min(max_seq_len, config.max_seq_len),
            prefill_buckets=(64,),
            decode_chunk=decode_chunk,
            prefill_batch=max_batch,
            speculation=mode,
            speculation_tokens=spec_tokens,
            # warm the full ladder (verify in auto mode, decode in off)
            # BEFORE the measured burst: otherwise the growing kv_bound
            # compiles novel programs inside the window and the pair
            # measures startup, not steady state
            precompile=True,
        )
        engine.start()
        try:
            # warmup: compiles whatever precompile missed (prefill shapes)
            engine.submit(GenerationRequest(
                prompt_tokens=list(prompts[0]), options=opts
            )).result(timeout=1200)
            engine.reset_histograms()  # warmup TTFT out of the tail
            start = time.monotonic()
            requests = [
                engine.submit(GenerationRequest(
                    prompt_tokens=list(p), options=opts,
                ))
                for p in prompts
            ]
            results = [r.result(timeout=1200) for r in requests]
            elapsed = time.monotonic() - start
            stats = engine.stats()
        finally:
            engine.stop()
        total = sum(len(r.tokens) for r in results)
        tag = f"spec_{mode}"
        out[f"{tag}_tokens_per_sec"] = round(total / elapsed, 2)
        out[f"{tag}_ms_per_token"] = round(1e3 * elapsed / max(1, total), 4)
        out[f"{tag}_p50_ttft_ms"] = _hist_pcts(stats, "engine_ttft_s")["p50"]
        if mode == "auto":
            out["spec_acceptance_rate"] = stats["spec-acceptance-rate"]
            out["spec_accepted_tokens_per_step"] = stats[
                "spec-accepted-tokens-per-step"
            ]
            out["spec_draft_hit_rate"] = stats["spec-draft-hit-rate"]
        _reclaim()
    return out


def bench_adapters(preset: str, quantize: bool, *, max_batch: int,
                   n_requests: int, new_tokens: int, max_seq_len: int,
                   decode_chunk: int, rank: int = 8) -> dict:
    """The agentic tier's cost model (docs/SERVING.md §15), measured as
    pairs on fresh engines over the same params:

    - decode throughput BASE (adapter pool resident but every slot base)
      vs ONE adapter vs EIGHT concurrent adapters mixed in the batch —
      the gathered grouped matmul's price, and proof the mixed batch rides
      one program (compiled_programs recorded);
    - constrained ON vs OFF ms/step on the same workload — the device-side
      mask overhead per step (one [B, V] int16/int32 gather + masked
      sample), the number the `constrained-decoding` knob trades."""
    import dataclasses

    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
    from langstream_tpu.serving.tokenizer import ByteTokenizer

    config = MODEL_PRESETS[preset]
    if quantize:
        from langstream_tpu.models.quant import init_random_quantized_params

        params = init_random_quantized_params(config, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    else:
        params = init_params(config, jax.random.PRNGKey(0))

    adapters = [
        {"name": f"tenant-{i}", "rank": rank, "scale": 1.0, "seed": i + 1}
        for i in range(8)
    ]
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(1, min(config.vocab_size, 255), size=24).tolist()
        for _ in range(n_requests)
    ]
    opts = dict(max_new_tokens=new_tokens, temperature=0.0)
    out: dict = {"adapter_rank": rank}

    def run(tag: str, engine_kw: dict, request_opts) -> dict:
        engine = ServingEngine(
            config, params, max_batch=max_batch,
            max_seq_len=min(max_seq_len, config.max_seq_len),
            prefill_buckets=(64,), decode_chunk=decode_chunk,
            prefill_batch=max_batch, precompile=True, **engine_kw,
        )
        engine.start()
        try:
            engine.submit(GenerationRequest(
                prompt_tokens=list(prompts[0]), options=request_opts(0),
            )).result(timeout=1200)
            engine.reset_histograms()
            start = time.monotonic()
            requests = [
                engine.submit(GenerationRequest(
                    prompt_tokens=list(p), options=request_opts(j),
                ))
                for j, p in enumerate(prompts)
            ]
            results = [r.result(timeout=1200) for r in requests]
            elapsed = time.monotonic() - start
            stats = engine.stats()
        finally:
            engine.stop()
        total = sum(len(r.tokens) for r in results)
        out[f"{tag}_tokens_per_sec"] = round(total / elapsed, 2)
        out[f"{tag}_ms_per_token"] = round(1e3 * elapsed / max(1, total), 4)
        out[f"{tag}_compiled_programs"] = stats["compiled_programs"]
        _reclaim()
        return stats

    # -- adapter sweep: base vs 1 vs 8 concurrent tenants -------------------
    pool_kw = dict(adapters=adapters, adapter_pool_rows=9,
                   constrained_decoding="off")
    run("adapters_base", pool_kw,
        lambda j: GenerationOptions(**opts))
    run("adapters_1", pool_kw,
        lambda j: GenerationOptions(**opts, adapter="tenant-0"))
    st8 = run("adapters_8", pool_kw,
              lambda j: GenerationOptions(**opts, adapter=f"tenant-{j % 8}"))
    out["adapters_8_swaps"] = st8["adapter-swaps-total"]
    # no-pool control: the engine without any adapter plumbing at all
    run("adapters_off", dict(constrained_decoding="off"),
        lambda j: GenerationOptions(**opts))

    # -- constrained on/off: device mask overhead per step ------------------
    tok = ByteTokenizer()
    schema = {
        "type": "object",
        "properties": {
            "name": {"type": "string", "maxLength": 16},
            "count": {"type": "integer"},
        },
    }
    rf = {"type": "json_schema", "json_schema": {"schema": schema}}
    con_kw = dict(constrained_decoding="auto", grammar_tokenizer=tok)
    st_on = run("constrained_on", con_kw,
                lambda j: GenerationOptions(**opts, response_format=rf))
    out["constrained_requests"] = st_on["constrained-requests-total"]
    out["constrain_host_overhead_ms"] = st_on["constrain-overhead-ms"]
    run("constrained_off", dict(constrained_decoding="off"),
        lambda j: GenerationOptions(**opts))
    if out.get("constrained_off_ms_per_token"):
        out["constrained_mask_overhead_ms_per_step"] = round(
            out["constrained_on_ms_per_token"]
            - out["constrained_off_ms_per_token"], 4,
        )
    return out


def bench_constrained(preset: str, quantize: bool, *, max_batch: int,
                      n_requests: int, new_tokens: int, max_seq_len: int,
                      decode_chunk: int, n_grammars: int = 16) -> dict:
    """The packed grammar pool's cost model (ISSUE 20, docs/SERVING.md
    §15), measured on fresh engines over the same params:

    - mask-apply ms/step: constrained ON (every request under a schema
      grammar) vs OFF over the same workload — the packed path's
      device-side price per step (word gather + shift/AND expand +
      masked sample + searchsorted advance);
    - residency at scale: n_grammars DISTINCT grammars mixed in one
      batch on the 64-slot default pool — resident count, swap count
      and proof the mix rides the same compiled programs;
    - packed-vs-dense pool bytes at this engine's actual vocab/states,
      plus the 256k-vocab projection (the 32×-smaller headline)."""
    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.constrain import grammar_pool_bytes
    from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
    from langstream_tpu.serving.tokenizer import ByteTokenizer

    config = MODEL_PRESETS[preset]
    if quantize:
        from langstream_tpu.models.quant import init_random_quantized_params

        params = init_random_quantized_params(config, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    else:
        params = init_params(config, jax.random.PRNGKey(0))

    tok = ByteTokenizer()
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(1, min(config.vocab_size, 255), size=24).tolist()
        for _ in range(n_requests)
    ]
    opts = dict(max_new_tokens=new_tokens, temperature=0.0)
    # n_grammars distinct schemas (distinct maxLength ⇒ distinct DFAs):
    # all resident at once on the 64-slot default pool
    n_grammars = min(n_grammars, n_requests)
    formats = [
        {"type": "json_schema", "json_schema": {"schema": {
            "type": "object",
            "properties": {"v": {"type": "string", "maxLength": 4 + i}},
        }}}
        for i in range(n_grammars)
    ]
    out: dict = {"constrained_grammars": n_grammars}

    def run(tag: str, engine_kw: dict, request_opts) -> dict:
        engine = ServingEngine(
            config, params, max_batch=max_batch,
            max_seq_len=min(max_seq_len, config.max_seq_len),
            prefill_buckets=(64,), decode_chunk=decode_chunk,
            prefill_batch=max_batch, precompile=True, **engine_kw,
        )
        engine.start()
        try:
            engine.submit(GenerationRequest(
                prompt_tokens=list(prompts[0]), options=request_opts(0),
            )).result(timeout=1200)
            start = time.monotonic()
            requests = [
                engine.submit(GenerationRequest(
                    prompt_tokens=list(p), options=request_opts(j),
                ))
                for j, p in enumerate(prompts)
            ]
            results = [r.result(timeout=1200) for r in requests]
            elapsed = time.monotonic() - start
            stats = engine.stats()
        finally:
            engine.stop()
        total = sum(len(r.tokens) for r in results)
        out[f"{tag}_ms_per_token"] = round(1e3 * elapsed / max(1, total), 4)
        out[f"{tag}_compiled_programs"] = stats["compiled_programs"]
        _reclaim()
        return stats

    con_kw = dict(constrained_decoding="auto", grammar_tokenizer=tok)
    st = run("grammar_mix", con_kw,
             lambda j: GenerationOptions(
                 **opts, response_format=formats[j % n_grammars]))
    out["grammar_rows_resident"] = st["grammars-resident"]
    out["grammar_swaps"] = st["grammar-swaps-total"]
    out["grammar_pool_bytes"] = st["grammar-pool-bytes"]
    out["constrain_host_overhead_ms"] = st["constrain-overhead-ms"]
    run("grammar_off", dict(constrained_decoding="off"),
        lambda j: GenerationOptions(**opts))
    out["mask_apply_ms_per_step"] = round(
        out["grammar_mix_ms_per_token"] - out["grammar_off_ms_per_token"], 4,
    )
    # packed vs dense, at this vocab and at the 256k headline vocab
    slots, states = 64, 128
    dense = (slots + 1) * states * config.vocab_size * 4
    out["grammar_dense_equiv_bytes"] = dense
    packed_256k = grammar_pool_bytes(slots, states, 256000)
    dense_256k = (slots + 1) * states * 256000 * 4
    out["grammar_packed_vs_dense_256k"] = round(dense_256k / packed_256k, 1)
    return out


def bench_tiered_kv(preset: str, quantize: bool, *, n_sessions: int = 8,
                    rounds: int = 3, new_tokens: int = 16,
                    page_size: int = 16, kv_int8: bool = False) -> dict:
    """Tiered-KV phase (ISSUE 11 acceptance): the idle-session CHURN
    workload the tier exists for — N chat sessions taking sequential
    turns over a device pool deliberately sized to keep only ~2 of their
    prefixes resident, so by the time a session's next turn arrives its
    prefix has been evicted (spill off: gone, full re-prefill) or demoted
    (spill on: hibernated host-side, DMA restore). Measured twice on
    fresh engines over the same params: next-turn TTFT p50/p99 plus the
    tier's own traffic accounting (spill/restore bytes, restored-hits vs
    recompute-fallbacks). Prefix cache ON in both legs — the pair
    isolates the HOST TIER, not the cache (PERF.md round 15)."""
    import dataclasses

    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.engine import GenerationRequest, ServingEngine

    config = MODEL_PRESETS[preset]
    if kv_int8:
        config = dataclasses.replace(config, kv_cache_dtype="int8")
    if quantize:
        from langstream_tpu.models.quant import init_random_quantized_params

        params = init_random_quantized_params(config, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    else:
        params = init_params(config, jax.random.PRNGKey(0))

    rng = np.random.default_rng(11)
    # distinct 80-token session preambles: each publishes a 64-token
    # (4-page at ps=16) prefix; the pool below holds ~2 of them resident
    prompts = [
        rng.integers(1, config.vocab_size, size=80).tolist()
        for _ in range(n_sessions)
    ]
    opts = GenerationOptions(max_new_tokens=new_tokens, temperature=0.0)
    prefix_pages = 64 // page_size
    active_pages = -(-(80 + new_tokens) // page_size)  # ceil
    kv_pages = active_pages + 2 * prefix_pages

    out: dict = {
        "tiered_sessions": n_sessions, "tiered_rounds": rounds,
        "tiered_kv_pages": kv_pages,
    }
    for mode in ("on", "off"):
        engine = ServingEngine(
            config,
            params,
            max_batch=2,
            max_seq_len=256,
            prefill_buckets=(16, 32, 64),
            decode_chunk=8,
            kv_layout="paged",
            page_size=page_size,
            kv_pages=kv_pages,
            prefix_cache="auto",
            prefix_cache_entries=n_sessions * 2,
            host_kv_fraction=float(n_sessions) if mode == "on" else 0.0,
            spill_idle_s=0.0,
            precompile=True,
        )
        engine.start()
        try:
            turn_ttfts: list[float] = []
            for rnd in range(rounds):
                for i, p in enumerate(prompts):
                    r = engine.submit(GenerationRequest(
                        prompt_tokens=list(p), options=opts,
                    )).result(timeout=1200)
                    if rnd > 0:  # next-turn TTFT: revisits only
                        turn_ttfts.append(r.ttft_s)
                    if mode == "on":
                        # the inter-turn idle the sweep hibernates in;
                        # sized for CPU jitter, not for the copy (one
                        # 4-page spill is <1ms of memcpy)
                        deadline = time.monotonic() + 2.0
                        while (
                            time.monotonic() < deadline
                            and any(
                                e.tier == "device"
                                for e in engine._prefix_index._live
                            )
                        ):
                            time.sleep(0.005)
            stats = engine.stats()
        finally:
            engine.stop()
        tag = f"spill_{mode}"
        arr = np.asarray(turn_ttfts)
        out[f"{tag}_next_turn_p50_ttft_ms"] = round(
            float(np.percentile(arr, 50)) * 1e3, 2)
        out[f"{tag}_next_turn_p99_ttft_ms"] = round(
            float(np.percentile(arr, 99)) * 1e3, 2)
        if mode == "on":
            out["tiered_restored_hits"] = stats["restored-hits-total"]
            out["tiered_recompute_fallbacks"] = stats[
                "recompute-fallbacks-total"]
            out["tiered_spill_mib"] = round(
                stats["spill-bytes-total"] / 2**20, 2)
            out["tiered_restore_mib"] = round(
                stats["restore-bytes-total"] / 2**20, 2)
            out["tiered_host_demotions"] = stats["host-demotions-total"]
        else:
            out["spill_off_prefix_evictions"] = stats[
                "prefix-cache-evictions-total"]
        _reclaim()
    return out


def bench_hibernate(preset: str, quantize: bool, *, n_sessions: int = 4,
                    new_tokens: int = 16, page_size: int = 16) -> dict:
    """Durable-tier resurrection phase (ISSUE 18 acceptance; docs
    §23): N chat sessions take a turn on replica A, A hibernates
    (checkpoints every live arena to the durable dir) and exits; a cold
    replica B on the same dir rehydrates the index and serves each
    session's next turn from disk. Measured against a third engine with
    the tier OFF serving the identical turns cold — the TTFT pair is
    the price of a replica death WITH vs WITHOUT the durable tier, and
    the restore accounting proves the warm leg actually came from disk
    (durable-restored-hits == sessions, zero restore failures)."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.engine import GenerationRequest, ServingEngine

    config = MODEL_PRESETS[preset]
    if quantize:
        from langstream_tpu.models.quant import init_random_quantized_params

        params = init_random_quantized_params(config, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    else:
        params = init_params(config, jax.random.PRNGKey(0))

    rng = np.random.default_rng(23)
    prompts = [
        rng.integers(1, config.vocab_size, size=80).tolist()
        for _ in range(n_sessions)
    ]
    opts = GenerationOptions(max_new_tokens=new_tokens, temperature=0.0)
    durable_dir = tempfile.mkdtemp(prefix="lstpu-bench-durable-")

    def make(durable: bool) -> ServingEngine:
        return ServingEngine(
            config,
            params,
            max_batch=2,
            max_seq_len=256,
            prefill_buckets=(16, 32, 64),
            decode_chunk=8,
            kv_layout="paged",
            page_size=page_size,
            kv_pages=4 * n_sessions * (96 // page_size),
            prefix_cache="auto",
            prefix_cache_entries=n_sessions * 2,
            durable="on" if durable else "off",
            durable_dir=durable_dir if durable else None,
            precompile=True,
        )

    out: dict = {"hibernate_sessions": n_sessions}
    try:
        # replica A: first turns, then hibernate (checkpoint + exit)
        a = make(durable=True)
        a.start()
        try:
            for p in prompts:
                a.submit(GenerationRequest(
                    prompt_tokens=list(p), options=opts,
                )).result(timeout=1200)
            t0 = time.perf_counter()
            ledger = a.hibernate("bench-a")
            out["hibernate_wall_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 2)
            out["hibernate_entries"] = ledger.get("entries", 0)
            out["hibernate_mib"] = round(
                ledger.get("bytes", 0) / 2**20, 2)
        finally:
            a.stop()
        _reclaim()

        # replica B: resurrection — rehydrate the index, serve the next
        # turns warm from disk; vs a tier-off engine serving them cold
        for tag, durable in (("resurrect", True), ("cold", False)):
            eng = make(durable=durable)
            eng.start()
            try:
                ttfts = []
                for p in prompts:
                    r = eng.submit(GenerationRequest(
                        prompt_tokens=list(p), options=opts,
                    )).result(timeout=1200)
                    ttfts.append(r.ttft_s)
                stats = eng.stats()
            finally:
                eng.stop()
            arr = np.asarray(ttfts)
            out[f"{tag}_next_turn_p50_ttft_ms"] = round(
                float(np.percentile(arr, 50)) * 1e3, 2)
            out[f"{tag}_next_turn_p99_ttft_ms"] = round(
                float(np.percentile(arr, 99)) * 1e3, 2)
            if durable:
                out["resurrect_restored_hits"] = stats[
                    "durable-restored-hits-total"]
                out["resurrect_restore_mib"] = round(
                    stats["durable-restore-bytes-total"] / 2**20, 2)
                out["resurrect_restore_failures"] = stats[
                    "durable-restore-failures-total"]
            _reclaim()
    finally:
        shutil.rmtree(durable_dir, ignore_errors=True)
    return out


def bench_tenancy(preset: str, quantize: bool, *, max_batch: int = 4,
                  n_requests: int = 24, new_tokens: int = 16,
                  max_seq_len: int = 256, decode_chunk: int = 4) -> dict:
    """Noisy-neighbor pair (docs/SERVING.md §19): the victim tenant's
    TTFT p50/p99 SOLO vs under a deterministic `tenant-burst` aggressor
    on a fair-share engine (weights 2:1, aggressor queue-share-capped).
    The headline numbers are the victim's p99 ratio (the acceptance bound
    is 2×) and the shed split (the aggressor must absorb ALL of it)."""
    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.engine import GenerationRequest, ServingEngine, ShedError
    from langstream_tpu.serving.faultinject import FaultInjector

    config = MODEL_PRESETS[preset]
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, config.vocab_size, size=24).tolist()
        for _ in range(n_requests)
    ]

    def run(burst: bool) -> dict:
        engine = ServingEngine(
            config, params, max_batch=max_batch,
            max_seq_len=min(max_seq_len, config.max_seq_len),
            prefill_buckets=(64,), decode_chunk=decode_chunk,
            shed_policy="reject", queue_depth=max_batch * 2,
            tenants=[
                {"name": "victim", "weight": 2.0},
                {"name": "chaos-burst", "weight": 1.0, "queue-share": 0.5},
            ],
            fault_injector=(
                FaultInjector("tenant-burst@1:2", seed=0) if burst else None
            ),
        )
        engine.start()
        try:
            # warm under a THROWAWAY tenant: the compile-heavy first TTFT
            # must not own the victim histogram's p99 on both legs (the
            # per-tenant histograms are cumulative; engine.reset_histograms
            # covers only the engine set)
            warm = GenerationRequest(
                prompt_tokens=prompts[0],
                options=GenerationOptions(max_new_tokens=4, tenant="warmup"),
            )
            engine.submit(warm)
            warm.result(timeout=600)
            for p in prompts:
                req = GenerationRequest(
                    prompt_tokens=p,
                    options=GenerationOptions(
                        max_new_tokens=new_tokens, tenant="victim",
                    ),
                )
                for _ in range(400):
                    try:
                        engine.submit(req)
                        break
                    except ShedError:
                        time.sleep(0.01)
                req.result(timeout=600)
            stats = engine.stats()
            t = stats["tenants"]
            return {
                "victim_ttft_p50_ms": round(
                    t["victim"]["ttft-p50-s"] * 1e3, 3
                ),
                "victim_ttft_p99_ms": round(
                    t["victim"]["ttft-p99-s"] * 1e3, 3
                ),
                "victim_shed": t["victim"]["shed-total"],
                "aggressor_shed": (
                    t.get("chaos-burst", {}).get("shed-total", 0)
                ),
                "aggressor_admitted": (
                    t.get("chaos-burst", {}).get("admitted-total", 0)
                ),
                "brownout_transitions": stats["brownout-transitions-total"],
            }
        finally:
            engine.stop()

    solo = run(burst=False)
    noisy = run(burst=True)
    p99_ratio = (
        noisy["victim_ttft_p99_ms"] / solo["victim_ttft_p99_ms"]
        if solo["victim_ttft_p99_ms"] > 0
        else 0.0
    )
    return {"tenancy": {
        "solo": solo, "noisy": noisy,
        "victim_p99_ratio": round(p99_ratio, 3),
    }}


def bench_degradation(preset: str, quantize: bool, max_batch: int,
                      new_tokens: int, n_requests: int, max_seq_len: int,
                      decode_chunk: int) -> dict:
    """Degradation phase (docs/SERVING.md §9): p50/p99 TTFT, shed rate, and
    recovery counters while the deterministic injector fires periodic
    decode crashes and a NaN-logits fault into a reject-policy engine with
    a tight queue. Graceful degradation as measured numbers: the engine
    must keep completing requests (restarting under backoff, shedding the
    overflow) rather than dying — a crash of THIS phase is a recovery bug."""
    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.engine import (
        GenerationRequest,
        ServingEngine,
        ShedError,
    )
    from langstream_tpu.serving.faultinject import FaultInjector

    config = MODEL_PRESETS[preset]
    if quantize:
        from langstream_tpu.models.quant import init_random_quantized_params

        params = init_random_quantized_params(config, jax.random.PRNGKey(0))
        jax.block_until_ready(params)
    else:
        params = init_params(config, jax.random.PRNGKey(0))
    # one decode crash every ~50 dispatches from #20, one NaN quarantine:
    # frequent enough that even the CPU smoke's ~40 dispatches exercise a
    # restart, rare enough that most requests complete (the seed is pinned
    # so the schedule is identical across runs — PERF.md comparable)
    injector = FaultInjector("decode@20:50,nan@12", seed=0)
    engine = ServingEngine(
        config,
        params,
        max_batch=max_batch,
        max_seq_len=min(max_seq_len, config.max_seq_len),
        prefill_buckets=(64,),
        decode_chunk=decode_chunk,
        prefill_batch=max_batch,
        shed_policy="reject",
        queue_depth=max_batch,
        restart_backoff_s=0.05,
        fault_injector=injector,
    )
    engine.start()
    rng = np.random.default_rng(0)
    ttfts: list = []
    shed = failed = done = 0
    try:
        warm = GenerationRequest(
            prompt_tokens=rng.integers(1, config.vocab_size, size=24).tolist(),
            options=GenerationOptions(max_new_tokens=4, temperature=0.0),
        )
        engine.submit(warm)
        warm.result(timeout=600)
        engine.reset_histograms()  # warmup TTFT out of the tail
        inflight = []
        for _ in range(n_requests):
            first: dict = {}
            t_submit = time.monotonic()
            req = GenerationRequest(
                prompt_tokens=rng.integers(1, config.vocab_size, size=24).tolist(),
                options=GenerationOptions(
                    max_new_tokens=new_tokens, temperature=0.0
                ),
                on_token=lambda _t, first=first, t0=t_submit: first.setdefault(
                    "ttft", time.monotonic() - t0
                ),
            )
            try:
                engine.submit(req)
                inflight.append((req, first))
            except ShedError:
                shed += 1
            time.sleep(0.005)  # paced arrivals: shedding reflects sustained
            # load against a crashing engine, not a one-burst artifact
        for req, first in inflight:
            try:
                req.result(timeout=1200)
                done += 1
                if "ttft" in first:
                    ttfts.append(first["ttft"])
            except Exception:  # noqa: BLE001 — quarantined by an injected fault
                failed += 1
    finally:
        engine.stop()
    stats = engine.stats()
    # round 11: percentiles from the engine TTFT histogram (same estimator
    # /metrics and Grafana serve); the client-side list stays only as the
    # completion gate above
    pcts = _hist_pcts(stats, "engine_ttft_s")
    return {
        "degraded_p50_ttft_ms": pcts["p50"] if ttfts else None,
        "degraded_p90_ttft_ms": pcts["p90"] if ttfts else None,
        "degraded_p99_ttft_ms": pcts["p99"] if ttfts else None,
        "degraded_shed_rate": round(shed / max(1, n_requests), 3),
        "degraded_completed": done,
        "degraded_failed": failed,
        "degraded_engine_restarts": stats["engine-restarts-total"],
        "degraded_quarantined_slots": stats["quarantined-slots-total"],
        "degraded_faults_fired": stats["fault-injection"],
    }


def _spawn_fleet(n_replicas: int, config_base: dict) -> tuple[list, list]:
    """Launch ``n_replicas`` standalone replica processes (CPU engines —
    JAX_PLATFORMS pinned, so the fleet phase also runs on TPU hosts without
    fighting over the chip) and return (procs, HttpReplica handles). Each
    worker prints one JSON line with its URL once its engine is warm."""
    import os
    import subprocess

    from langstream_tpu.serving.fleet import HttpReplica

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("LSTPU_FAULTS", None)  # the fleet phase measures, not drills
    procs = []
    for i in range(n_replicas):
        cfg = dict(config_base)
        cfg["fleet-replica-id"] = f"r{i}"
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-m", "langstream_tpu.serving.fleet",
                    "--config", json.dumps(cfg),
                ],
                stdin=subprocess.PIPE,
                stdout=subprocess.PIPE,
                env=env,
                text=True,
            )
        )
    replicas = []
    for i, p in enumerate(procs):
        line = p.stdout.readline()
        if not line:
            raise RuntimeError(f"fleet replica {i} died before serving")
        replicas.append(HttpReplica(f"r{i}", json.loads(line)["url"]))
    return procs, replicas


def _stop_fleet(procs: list) -> None:
    for p in procs:
        try:
            p.stdin.close()  # workers exit on stdin EOF
        except OSError:
            pass
    for p in procs:
        try:
            p.wait(timeout=30)
        except Exception:  # noqa: BLE001 — last resort
            p.kill()


def _fleet_arm(policy: str, replicas: list, preambles: list, burst_mult: int,
               new_tokens: int, lam: float) -> dict:
    """One measured arm over a FRESH fleet: one seed request per preamble
    group (cold prefill + publish, wherever the cold route lands),
    histogram reset, then the 10× concurrent burst — ``burst_mult``
    requests per group, groups interleaved. Affinity keeps each group on
    the replica that owns its preamble; round-robin scatters every group
    across every replica, re-prefilling each preamble per replica."""
    import threading

    from langstream_tpu.serving.engine import ShedError
    from langstream_tpu.serving.fleet import (
        FleetRouter,
        FleetShedError,
        ReplicaError,
    )

    router = FleetRouter(
        replicas, policy=policy, lam=lam, refresh_interval_s=0.2,
    )
    router.start()  # background beacon refresh: load spills mid-burst
    opts = {"max-tokens": new_tokens, "temperature": 0.0}
    for g, preamble in enumerate(preambles):
        router.generate(preamble + [1], opts)  # seed: cold prefill + publish
    time.sleep(0.5)  # one refresh so the burst sees the published prefixes
    for r in replicas:
        r.reset_histograms()  # the pair is WARM p50, not compile time
    ttfts: list = []
    sheds = [0]
    fails = [0]
    lock = threading.Lock()
    prompts = [
        preambles[i % len(preambles)] + [2 + i]
        for i in range(burst_mult * len(preambles))
    ]
    # SHUFFLE the arrival order (seeded): an interleaved order with
    # n_groups == n_replicas would hand round-robin a perfect
    # group-per-replica alignment by pure stride coincidence — the control
    # arm must be BLIND dispatch, not accidental affinity
    import numpy as _np

    _np.random.default_rng(3).shuffle(prompts)
    n_requests = len(prompts)

    def one(i: int) -> None:
        try:
            out, _decision = router.generate(prompts[i], opts)
            with lock:
                ttfts.append(out["ttft_s"])
        except (ShedError, FleetShedError):
            with lock:
                sheds[0] += 1
        except ReplicaError:
            # every replica died for this request (distinct from a shed
            # since round 16): counted, not a silent thread death — the
            # arm's sample size must stay honest
            with lock:
                fails[0] += 1

    threads = [
        threading.Thread(target=one, args=(i,)) for i in range(n_requests)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    wall = time.perf_counter() - t0
    beacons = [r.fetch_beacon() for r in replicas]
    stats = router.stats()
    router.stop()
    ttfts.sort()
    return {
        "p50_ttft_ms": round(_pct(ttfts, 0.50) * 1e3, 1) if ttfts else None,
        "p99_ttft_ms": round(_pct(ttfts, 0.99) * 1e3, 1) if ttfts else None,
        # per-replica engine-histogram p50s (the beacon carries them) —
        # the replica(s) that actually served show the warm number
        "replica_p50s_ms": [b["ttft_p50_ms"] for b in beacons],
        "prefill_tokens_saved": sum(
            b["prefill_tokens_saved_total"] for b in beacons
        ),
        "hit_rates": [b["prefix_hit_rate"] for b in beacons],
        "shed_rate": round(sheds[0] / max(1, n_requests), 3),
        "failed": fails[0],
        "completed": len(ttfts),
        "wall_s": round(wall, 2),
        "routed_affinity": stats["fleet-routed-affinity-total"]
        + stats["fleet-routed-sticky-total"],
        "routed_balanced": stats["fleet-routed-balanced-total"],
        "dispatch_p50_ms": stats["fleet-dispatch-p50-ms"],
        "dispatch_p99_ms": stats["fleet-dispatch-p99-ms"],
        # the streaming wire (docs/SERVING.md §17): remote-hop latency is
        # end-of-stream wall time (TTFT above is the streaming number —
        # first frame, not last), plus the failover/breaker health counters
        "hop_p50_ms": stats["fleet-hop-p50-ms"],
        "hop_p99_ms": stats["fleet-hop-p99-ms"],
        "stream_failovers": stats["fleet-stream-failovers-total"],
        "beacon_failures": stats["fleet-beacon-failures-total"],
    }


def bench_spmd_wire(*, preset: str = "tiny-test", new_tokens: int = 48,
                    n_requests: int = 6, max_seq_len: int = 256,
                    decode_chunk: int = 8, preamble_len: int = 64) -> dict:
    """SPMD fast-path parity phase (ISSUE 9 acceptance): a loopback
    leader+follower pair on a TP mesh over ALL local devices, with the
    full round-13 fast-path stack on the wire — prefix-cache auto,
    speculation auto, kv_layout=paged — serving a shared-preamble burst.
    Records decode throughput WITH the wire active and the MEASURED
    ControlBlock overhead (bytes/announce, announces and bytes per engine
    iteration, wire bytes per generated token). On CPU (or virtual
    devices) the tok/s is a smoke number; the wire-bytes numbers are
    exact everywhere — they depend only on the protocol's fixed shapes,
    which derive from the engine config."""
    import threading

    import jax as _jax

    from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.parallel.mesh import build_mesh
    from langstream_tpu.parallel.sharding import shard_params
    from langstream_tpu.parallel.spmd_serving import LoopbackChannel, follower_loop
    from langstream_tpu.serving.engine import GenerationRequest, ServingEngine
    from langstream_tpu.serving.pagepool import table_len_for

    config = MODEL_PRESETS[preset]
    if config.dtype != "float32" and _jax.default_backend() != "tpu":
        import dataclasses as _dc

        config = _dc.replace(config, dtype="float32")
    devices = _jax.devices()
    mesh = build_mesh({"model": len(devices)}, devices)
    params = shard_params(init_params(config, _jax.random.PRNGKey(0)), mesh, config)
    page_size = 16
    buckets = (32, 64, 128)
    kw = dict(
        max_batch=4, max_seq_len=max_seq_len, decode_chunk=decode_chunk,
        prefill_buckets=buckets, prefill_batch=4, mesh=mesh,
        kv_layout="paged", page_size=page_size,
        prefix_cache="auto", speculation="auto", speculation_tokens=4,
    )
    channel = LoopbackChannel(
        prefill_batch=4, max_width=max(buckets), max_batch=4,
        table_len=table_len_for(max_seq_len, page_size), spec_tokens=4,
    )
    leader = ServingEngine(config, params, spmd=channel, **kw)
    follower = ServingEngine(config, params, **kw)
    t = threading.Thread(target=follower_loop, args=(follower, channel), daemon=True)
    t.start()
    leader.start()
    rng = __import__("numpy").random.default_rng(9)
    preamble = rng.integers(1, config.vocab_size, size=preamble_len).tolist()
    opts = GenerationOptions(max_new_tokens=new_tokens, temperature=0.0)
    try:
        leader.generate(preamble + [1], opts, timeout=600)  # warm + publish
        t0 = time.monotonic()
        reqs = [
            leader.submit(GenerationRequest(
                prompt_tokens=preamble + [2 + i], options=opts,
            ))
            for i in range(n_requests)
        ]
        generated = sum(len(r.result(600).tokens) for r in reqs)
        wall = time.monotonic() - t0
        stats = leader.stats()
        iters = leader._iterations_total
    finally:
        leader.stop()
        t.join(timeout=60)
    announces = stats["spmd-announces-total"]
    wire_bytes = stats["spmd-announce-bytes-total"]
    out = {
        "spmd_devices": len(devices),
        "spmd_backend": _jax.default_backend(),
        "spmd_tokens_per_sec": round(generated / wall, 1),
        "spmd_prefix_hit_rate": stats["prefix-cache-hit-rate"],
        "spmd_spec_accepted_per_step": stats["spec-accepted-tokens-per-step"],
        "spmd_wire_announces_total": announces,
        "spmd_wire_bytes_total": wire_bytes,
        "spmd_wire_bytes_per_announce": round(wire_bytes / max(1, announces), 1),
        # engine iterations INCLUDE idle polls (no announce): the per-
        # iteration overhead under load is announces/iter × bytes/announce
        "spmd_wire_engine_iterations": iters,
        "spmd_wire_bytes_per_generated_token": round(
            wire_bytes / max(1, generated), 1
        ),
    }
    # recovery drill (round 19, docs/SERVING.md §20): deterministic
    # leader-loop crashes mid-burst on a FRESH loopback pair per trial
    # (same shapes as above, so every program is already jit-cached and
    # the latency below is the rebuild+requeue cost, not compiles);
    # recorded: fault → first post-recovery delivered token. In-flight
    # streams fail by §9 contract; queued admissions survive and resume.
    from langstream_tpu.serving.faultinject import FaultInjector as _FI

    recov_ms = []
    for trial in range(3):
        inj = _FI("decode@4", seed=trial)
        ch2 = LoopbackChannel(
            prefill_batch=4, max_width=max(buckets), max_batch=4,
            table_len=table_len_for(max_seq_len, page_size), spec_tokens=4,
        )
        lead = ServingEngine(
            config, params, spmd=ch2, fault_injector=inj,
            restart_backoff_s=0.05, **kw,
        )
        folw = ServingEngine(config, params, **kw)
        th = threading.Thread(
            target=follower_loop, args=(folw, ch2), daemon=True,
        )
        th.start()
        lead.start()
        token_times: list = []
        try:
            reqs = [
                lead.submit(GenerationRequest(
                    prompt_tokens=preamble + [2 + i], options=opts,
                    on_token=lambda t: token_times.append(time.time()),
                ))
                for i in range(n_requests)
            ]
            for r in reqs:
                try:
                    r.result(600)
                except Exception:  # noqa: BLE001 — in-flight at the crash
                    pass
            assert lead.stats()["spmd-recoveries-total"] >= 1
            fault_t = next(
                e["t"] for e in inj.events_snapshot() if e["site"] == "decode"
            )
            after = [t for t in token_times if t > fault_t]
            if after:
                recov_ms.append((min(after) - fault_t) * 1e3)
        finally:
            lead.stop()
            th.join(timeout=60)
    recov_ms.sort()
    if recov_ms:
        out["spmd_recovery_trials"] = len(recov_ms)
        out["spmd_recovery_fault_to_first_token_p50_ms"] = round(
            recov_ms[len(recov_ms) // 2], 1
        )
        out["spmd_recovery_fault_to_first_token_max_ms"] = round(
            recov_ms[-1], 1
        )
    return out


def bench_disagg(*, n_steady: int = 12, steady_tokens: int = 16,
                 n_bursts: int = 3, burst_prompt: int = 192,
                 steady_prompt: int = 24, threshold: int = 64) -> dict:
    """Disaggregated prefill/decode phase (ISSUE 13 acceptance, docs
    §18): a 2-replica fleet serving ``n_steady`` steady decode streams
    while ``n_bursts`` long-prompt bursts arrive mid-flight, measured
    twice on FRESH engine pairs — roles ON (prefill + decode replicas,
    long prompts prefill on one replica and their KV migrates to the
    other) vs roles OFF (both mixed: long prompts compete with steady
    decode wherever affinity lands them). Recorded: the steady streams'
    TTFT and inter-token p50/p99 (the number disaggregation exists to
    protect), the bursts' TTFT, and the migration ledger (count,
    p50/p99, pages, fallbacks). On this CPU smoke the engines are tiny
    and prefill is cheap — the chip run is where the burst actually
    stalls a mixed batch; the phase records the machinery's overhead
    honestly either way."""
    import dataclasses
    import threading as _threading

    import jax
    import numpy as np

    from langstream_tpu.models.configs import MODEL_PRESETS
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.serving.engine import ServingEngine
    from langstream_tpu.serving.fleet import FleetRouter, InProcessReplica

    config = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(13)
    steady_prompts = [
        rng.integers(1, 200, size=steady_prompt).tolist()
        for _ in range(n_steady)
    ]
    burst_prompts = [
        rng.integers(1, 200, size=burst_prompt).tolist()
        for _ in range(n_bursts)
    ]
    out: dict = {
        "disagg_steady_streams": n_steady,
        "disagg_bursts": n_bursts,
        "disagg_burst_prompt": burst_prompt,
        "disagg_threshold": threshold,
    }

    def _engine():
        e = ServingEngine(
            config, params, max_batch=8, max_seq_len=512,
            prefill_buckets=(16, 32, 64, 128, 256), decode_chunk=4,
            prefix_cache="auto", precompile=True,
        )
        e.start()
        return e

    warm_prompt = rng.integers(1, 200, size=burst_prompt).tolist()
    for mode in ("roles", "mixed"):
        a, b = _engine(), _engine()
        roles = ("prefill", "decode") if mode == "roles" else ("mixed",) * 2
        # wait out the precompile ladder on BOTH engines before the clock
        # starts (the phase measures steady-state tails, not warmup), and
        # reset the histograms the TTFT gauges would otherwise inherit
        from langstream_tpu.models.configs import GenerationOptions

        for e in (a, b):
            e.generate(
                list(warm_prompt),
                GenerationOptions(max_new_tokens=4, temperature=0.0),
            )
            e.reset_histograms()
        router = FleetRouter(
            [InProcessReplica("r0", a, role=roles[0]),
             InProcessReplica("r1", b, role=roles[1])],
            prefill_route_threshold=threshold, refresh_interval_s=0.2,
        )
        router.start()
        ttfts, gaps, burst_ttfts = [], [], []
        lock = _threading.Lock()

        def _stream(prompt, tokens, sink):
            t0 = time.monotonic()
            last = None
            got = 0
            for frame in router.stream_generate(
                prompt, {"max-tokens": tokens, "temperature": 0.0}
            ):
                if frame["kind"] != "tokens":
                    continue
                now = time.monotonic()
                for _ in frame["tokens"]:
                    if got == 0:
                        with lock:
                            sink.append(now - t0)
                    elif last is not None:
                        with lock:
                            gaps.append((now - last) / len(frame["tokens"]))
                    got += 1
                last = now

        threads = [
            _threading.Thread(
                target=_stream, args=(p, steady_tokens, ttfts), daemon=True,
            )
            for p in steady_prompts
        ]
        for t in threads:
            t.start()
        time.sleep(0.3)  # bursts land mid-steady-state, not first
        bursts = [
            _threading.Thread(
                target=_stream, args=(p, 8, burst_ttfts), daemon=True,
            )
            for p in burst_prompts
        ]
        for t in bursts:
            t.start()
        for t in threads + bursts:
            t.join(timeout=600)
        st = router.stats()
        key = mode
        out.update({
            f"disagg_{key}_steady_p50_ttft_ms": round(
                float(np.percentile(ttfts, 50)) * 1e3, 1
            ),
            f"disagg_{key}_steady_p99_ttft_ms": round(
                float(np.percentile(ttfts, 99)) * 1e3, 1
            ),
            f"disagg_{key}_steady_p99_intertoken_ms": round(
                float(np.percentile(gaps, 99)) * 1e3, 2
            ) if gaps else 0.0,
            f"disagg_{key}_burst_p50_ttft_ms": round(
                float(np.percentile(burst_ttfts, 50)) * 1e3, 1
            ) if burst_ttfts else 0.0,
            f"disagg_{key}_migrations": st["fleet-migrations-total"],
            f"disagg_{key}_migrate_pages": st["fleet-migrate-pages-total"],
            f"disagg_{key}_migrate_fallbacks": st[
                "fleet-migrate-fallbacks-total"
            ],
            f"disagg_{key}_migrate_p50_ms": st["fleet-migrate-p50-ms"],
            f"disagg_{key}_migrate_p99_ms": st["fleet-migrate-p99-ms"],
        })
        print(f"[bench] disagg {mode}: "
              f"{ {k: v for k, v in out.items() if key in k} }",
              file=sys.stderr, flush=True)
        router.stop()
        a.stop()
        b.stop()
    return out


def bench_cold_start(*, repeats: int = 3) -> dict:
    """Cold-start drill (ISSUE 17 acceptance, docs §22): the streamed
    three-stage weight pipeline vs the eager loader over the SAME
    multi-shard checkpoint (~28 MB, 4 shards — large enough that per-
    tensor machinery amortizes, small enough for the CI box) — the bf16
    wall-clock pair, the int8 pair (eager load-then-quantize vs streamed
    quantize-on-load), the streamed per-phase split (read / transform /
    transfer), and the host staging peak as a fraction of checkpoint
    bytes (eager peaks at ~2× the weight bytes: the raw shard dict + the
    stacked copies; streamed holds the readahead window only). Read the
    wall numbers with the core count in hand: the pipeline's overlap
    terms (readers ∥ assembly ∥ DMA) flatten to a serial sum on a
    single-core host, so there streamed ≈ eager + machinery and the
    staging/quantize-RAM bounds are the measured wins — the wall-clock
    win needs cores to overlap reads and a chip for async DMA. Best-of-
    N: cold-start is a latency number, and iteration 1 pays the jits."""
    import dataclasses
    import shutil

    import jax

    from langstream_tpu.models.configs import MODEL_PRESETS
    from langstream_tpu.models.loader import load_params, save_params_hf
    from langstream_tpu.models.quant import quantize_params
    from langstream_tpu.models.streamload import load_params_streamed
    from langstream_tpu.models.transformer import init_params

    cfg = dataclasses.replace(
        MODEL_PRESETS["tiny-test"], d_model=256, d_ff=1024, n_layers=12,
        vocab_size=4096, n_heads=8, n_kv_heads=4, name="cold-bench",
    )
    tmp = Path(tempfile.mkdtemp(prefix="lstpu-coldstart-"))

    def best(fn):
        walls = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            walls.append(time.perf_counter() - t0)
        return min(walls)

    try:
        save_params_hf(
            init_params(cfg, jax.random.PRNGKey(0)), cfg, tmp,
            max_shard_bytes=8_000_000,
        )
        n_shards = len(list(tmp.glob("*.safetensors")))
        eager = best(lambda: load_params(tmp, cfg))
        rep = None

        def streamed_once():
            nonlocal rep
            params, rep = load_params_streamed(tmp, cfg, workers=4)
            return params

        streamed = best(streamed_once)
        eager_q = best(lambda: quantize_params(load_params(tmp, cfg), cfg))
        qol = best(
            lambda: load_params_streamed(tmp, cfg, workers=4, quantize=True)[0]
        )
        return {
            "cold_start_shards": n_shards,
            "cold_start_bytes": rep.bytes_read,
            "cold_start_eager_s": round(eager, 4),
            "cold_start_streamed_s": round(streamed, 4),
            "cold_start_speedup": round(eager / streamed, 2),
            "cold_start_int8_eager_s": round(eager_q, 4),
            "cold_start_int8_streamed_s": round(qol, 4),
            "cold_start_int8_speedup": round(eager_q / qol, 2),
            "cold_start_read_s": round(rep.read_s, 4),
            "cold_start_transform_s": round(rep.transform_s, 4),
            "cold_start_transfer_s": round(rep.transfer_s, 4),
            "cold_start_staging_peak_frac": round(
                rep.staging_peak_bytes / max(1, rep.bytes_read), 3
            ),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_wire(*, prompt_len: int = 96, new_tokens: int = 24) -> dict:
    """Binary fleet wire v2 phase (ISSUE 16 acceptance, docs §21):
    measured pairs, not claims — (1) encoded migration bytes per page,
    v1 NDJSON+base64 vs the v2 binary codec (the v2/v1 ratio is the
    ≤ 0.76× acceptance bound; it is exact layout math, identical on CPU
    and chip); (2) migration wall-clock MB/s over the HTTP loopback
    wire under each codec; (3) token-stream wire bytes per streamed
    token, v1 vs v2; (4) the P2P page-fetch TTFT pair (ROADMAP 2a) — a
    radix-miss replica admitting WARM from a peer's fetched pages vs
    the same miss re-prefilling cold. The tiny CPU model keeps the
    absolute MB/s and TTFT numbers modest; the byte ratios and the
    warm-vs-cold shape are what the round records."""
    import dataclasses
    import threading as _threading

    import jax
    import numpy as np

    from langstream_tpu.models.configs import GenerationOptions, MODEL_PRESETS
    from langstream_tpu.models.transformer import init_params
    from langstream_tpu.runtime.http_server import RuntimeHttpServer
    from langstream_tpu.serving import fleet as fleet_mod
    from langstream_tpu.serving import migrate as migrate_mod
    from langstream_tpu.serving import wire as wire_mod
    from langstream_tpu.serving.engine import ServingEngine
    from langstream_tpu.serving.fleet import (
        FleetRouter,
        HttpReplica,
        InProcessReplica,
        beacon_from_engine,
        engine_generate,
        engine_generate_stream,
        engine_migrate_bind,
        engine_migrate_pages,
        engine_p2p_fetch,
    )

    config = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
    params = init_params(config, jax.random.PRNGKey(0))
    rng = np.random.default_rng(16)
    opts = GenerationOptions(max_new_tokens=new_tokens, temperature=0.0)

    def _engine():
        e = ServingEngine(
            config, params, max_batch=4, max_seq_len=512,
            prefill_buckets=(32, 64, 128, 256), decode_chunk=4,
            prefix_cache="auto", precompile=True,
        )
        e.start()
        return e

    a, b = _engine(), _engine()
    # compile the prompt bucket + decode ladder on BOTH engines before
    # any clock starts (the TTFT pair measures serving, not XLA)
    warm_prompt = rng.integers(1, 200, size=prompt_len).tolist()
    for e in (a, b):
        e.generate(list(warm_prompt),
                   GenerationOptions(max_new_tokens=8, temperature=0.0))
        e.reset_histograms()
    prompts = [
        rng.integers(1, 200, size=prompt_len).tolist() for _ in range(4)
    ]
    out: dict = {"wire_prompt_len": prompt_len}

    # --- (1) encoded bytes per migrated page: the acceptance ratio ----
    a.generate(prompts[0], opts)
    v2_pages = [
        len(wire_mod.encode_mig_frame(f))
        for f in migrate_mod.export_frames(a, prompts[0], raw=True)
        if f["kind"] == "page"
    ]
    v1_pages = [
        len((json.dumps(f) + "\n").encode())
        for f in migrate_mod.export_frames(a, prompts[0])
        if f["kind"] == "page"
    ]
    out.update({
        "wire_pages": len(v1_pages),
        "wire_v1_bytes_per_page": round(sum(v1_pages) / len(v1_pages), 1),
        "wire_v2_bytes_per_page": round(sum(v2_pages) / len(v2_pages), 1),
        "wire_v2_over_v1_page_ratio": round(
            sum(v2_pages) / sum(v1_pages), 4
        ),
    })

    # --- (2) + (3): the HTTP loopback wire, both codecs ---------------
    loop = asyncio.new_event_loop()
    server = RuntimeHttpServer(
        metrics_text=lambda: "", agents_info=lambda: [], port=0
    )
    thread = _threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    asyncio.run_coroutine_threadsafe(server.start(), loop).result(10)
    fleet_mod.register_local(
        "bench-wire",
        beacon_fn=lambda: beacon_from_engine("bench-wire", b, url=server.url),
        generate_fn=lambda p: engine_generate(b, p),
        generate_stream_fn=lambda p: engine_generate_stream(b, p),
        migrate_bind_fn=(
            lambda frames, timeout_s=30.0:
            engine_migrate_bind(b, frames, timeout_s)
        ),
        migrate_pages_fn=lambda p: engine_migrate_pages(b, p),
        p2p_fetch_fn=lambda p: engine_p2p_fetch(b, p),
        migrate_limits_fn=b.migrate_limits,
    )
    try:
        for proto, prompt in (("v1", prompts[1]), ("v2", prompts[2])):
            a.generate(prompt, opts)
            wire_mod.reset_wire_stats()
            t0 = time.monotonic()
            ack = migrate_mod.push_migration(
                server.url,
                migrate_mod.export_frames(a, prompt, raw=proto == "v2"),
                timeout_s=60.0, wire=proto,
            )
            took = time.monotonic() - t0
            sent = wire_mod.wire_stats().get(proto, 0)
            out[f"wire_{proto}_migrate_wire_bytes"] = sent
            out[f"wire_{proto}_migrate_page_bytes"] = ack.get("bytes", 0)
            out[f"wire_{proto}_migrate_mbps"] = round(
                sent / max(took, 1e-9) / 1e6, 2
            )
        replica = HttpReplica("bench-wire", server.url)
        for proto in ("v1", "v2"):
            replica.caps = (
                frozenset({"frames2"}) if proto == "v2" else frozenset()
            )
            wire_mod.reset_wire_stats()
            n = 0
            for frame in replica.generate_stream(
                prompts[3], {"max-tokens": new_tokens, "temperature": 0.0}
            ):
                if frame.get("kind") == "tokens":
                    n += len(frame["tokens"])
            out[f"wire_{proto}_stream_bytes_per_token"] = round(
                wire_mod.wire_stats().get(proto, 0) / max(n, 1), 1
            )
    finally:
        fleet_mod.unregister_local("bench-wire")
        asyncio.run_coroutine_threadsafe(server.stop(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=10)
        loop.close()

    # --- (4) P2P-warm admit vs local cold re-prefill TTFT -------------
    def _ttft(router, prompt):
        t0 = time.monotonic()
        for frame in router.stream_generate(
            prompt, {"max-tokens": 8, "temperature": 0.0}
        ):
            if frame.get("kind") == "tokens":
                return time.monotonic() - t0
        return 0.0

    for mode, p2p in (("cold", False), ("p2p_warm", True)):
        prompt = rng.integers(1, 200, size=prompt_len).tolist()
        a.generate(prompt, opts)  # the owner publishes the prefix
        router = FleetRouter(
            [InProcessReplica("owner", a), InProcessReplica("dest", b)],
            refresh_interval_s=3600.0, lam=16.0,
            p2p=p2p, p2p_threshold=16,
        )
        router.refresh_all()
        # drown the owner's affinity win so the radix-miss replica takes
        # the request — exactly the load shape P2P fetch exists for
        router._replicas["owner"].beacon["load_score"] = 50.0
        out[f"wire_{mode}_ttft_ms"] = round(_ttft(router, prompt) * 1e3, 1)
        if p2p:
            st = router.stats()
            out["wire_p2p_fetches"] = st["fleet-p2p-fetch-total"]
            out["wire_p2p_fallbacks"] = st["fleet-p2p-fetch-fallback-total"]
            out["wire_p2p_bytes_in"] = st["fleet-p2p-bytes-in-total"]
    a.stop()
    b.stop()
    print(f"[bench] wire: { {k: v for k, v in out.items()} }",
          file=sys.stderr, flush=True)
    return out


def bench_fleet(*, n_replicas: int = 3, n_groups: int = 4,
                preamble_len: int = 256, burst_mult: int = 10,
                new_tokens: int = 16, lam: float = 128.0) -> dict:
    """Fleet phase (ISSUE 8 acceptance): a multi-process CPU fleet (the
    SPMD tests' subprocess pattern) under a 10× shared-preamble burst —
    ``n_groups`` distinct preambles (multi-tenant chat: different system
    prompts), ``burst_mult`` requests per group, all concurrent — measured
    twice on FRESH replicas: prefix-affinity routing vs blind round-robin
    at equal replica count. Affinity must win warm p50 TTFT AND aggregate
    prefill-tokens-saved (round-robin re-prefills every preamble on every
    replica it touches); the router itself must cost <1 ms p50 per
    dispatch (its histogram is part of the record). Each arm gets its own
    processes: a shared fleet would hand the second arm pre-warmed
    replicas and fake the delta."""
    import numpy as np

    rng = np.random.default_rng(12)
    preambles = [
        rng.integers(1, 200, size=preamble_len).tolist()
        for _ in range(n_groups)
    ]
    config = {
        "model": "tiny-test",
        "max-batch": 4,
        "max-seq-len": 1024,
        "prefill-buckets": (64, 128, 256, 512),
        "decode-chunk": 8,
        "prefix-cache": "auto",
        "prefix-cache-entries": 2 * n_groups,
        "precompile": True,
    }
    out: dict = {
        "fleet_replicas": n_replicas,
        "fleet_preamble_groups": n_groups,
        "fleet_burst_requests": n_groups * burst_mult,
        "fleet_preamble": preamble_len,
        "fleet_lambda": lam,
    }
    for policy, key in (("affinity", "affinity"), ("round-robin", "rr")):
        procs, replicas = _spawn_fleet(n_replicas, config)
        try:
            arm = _fleet_arm(
                policy, replicas, preambles, burst_mult, new_tokens, lam
            )
        finally:
            _stop_fleet(procs)
        out.update({f"fleet_{key}_{k}": v for k, v in arm.items()})
        print(f"[bench] fleet {policy}: {arm}", file=sys.stderr, flush=True)
    return out


async def bench_gateway(preset: str, quantize: bool, max_batch: int, new_tokens: int,
                        n_sessions: int, max_seq_len: int, decode_chunk: int,
                        prefill_batch: int, overlap: bool = True) -> dict:
    """Full-platform path: app (broker + agents) + gateway WS chat.

    ``overlap``: fused prefill–decode scheduling on/off — the bench runs
    BOTH so the TTFT delta of the fused scheduler is a recorded number,
    not a claim (PERF.md round 6)."""
    import aiohttp

    from langstream_tpu.core.parser import ModelBuilder
    from langstream_tpu.core.resolver import resolve_placeholders
    from langstream_tpu.runtime.local_runner import LocalApplicationRunner

    app_dir = Path(tempfile.mkdtemp(prefix="bench-app-"))
    (app_dir / "pipeline.yaml").write_text(
        PIPELINE.format(model=preset, max_tokens=new_tokens)
    )
    (app_dir / "configuration.yaml").write_text(
        CONFIGURATION.format(
            model=preset, max_batch=max_batch, max_seq_len=max_seq_len,
            decode_chunk=decode_chunk, prefill_batch=prefill_batch,
            overlap="true" if overlap else "false",
            quant_line="quantization: int8" if quantize else "",
        )
    )
    (app_dir / "gateways.yaml").write_text(GATEWAYS)
    instance_path = app_dir / "instance.yaml"
    instance_path.write_text(INSTANCE)

    pkg = ModelBuilder.build_application_from_path(app_dir, instance_path=instance_path)
    app = resolve_placeholders(pkg.application)
    runner = LocalApplicationRunner("bench", app)
    await runner.deploy()
    await runner.start()
    server = await runner.serve_gateway()
    try:
        async with aiohttp.ClientSession() as http:
            # warmup session: pays the compile + engine spin-up
            print("[bench] gateway up; warmup chat", file=sys.stderr, flush=True)
            await _chat_once(http, server, "warmup", timeout=900)
            print("[bench] warmup done; measuring", file=sys.stderr, flush=True)

            start = time.monotonic()
            results = await asyncio.gather(
                *(_chat_once(http, server, f"s{i}") for i in range(n_sessions))
            )
            elapsed = time.monotonic() - start
        total_bytes = sum(r[1] for r in results)
        ttfts = sorted(r[0] for r in results)

        def pct(p: float) -> float:
            return _pct(ttfts, p)

        # concurrency honesty (VERDICT r4 weak #3): time-weighted mean of
        # sessions actively streaming (first token received, last not yet) —
        # if this sits near 1 the metric is session-latency-bound, not
        # engine-throughput-bound, and p50 TTFT is the lever that matters.
        active_time = sum(r[3] - r[2] for r in results)
        return {
            "e2e_gateway_tokens_per_sec": round(total_bytes / elapsed, 2),
            "gateway_p50_ttft_ms": round(pct(0.50) * 1e3, 1),
            "gateway_p95_ttft_ms": round(pct(0.95) * 1e3, 1),
            "gateway_p99_ttft_ms": round(pct(0.99) * 1e3, 1),
            "gateway_mean_active_streams": round(active_time / elapsed, 2),
            "gateway_sessions": n_sessions,
        }
    finally:
        await server.stop()
        await runner.stop()


async def _chat_once(http, server, session_id: str, timeout: float = 300.0):
    """One chat turn over the gateway WS; returns
    (ttft_s, streamed_bytes, t_first_token, t_last_token) with the times on
    the shared monotonic clock so the caller can integrate concurrency.
    Tokens ≈ bytes under the byte tokenizer."""
    url = f"{server.ws_url}/v1/chat/default/bench/chat?param:sessionId={session_id}"
    async with http.ws_connect(url) as ws:
        sent = time.monotonic()
        await ws.send_str(json.dumps({"value": QUESTION}))
        ttft = None
        t_first = sent
        nbytes = 0
        import aiohttp

        while True:
            msg = await asyncio.wait_for(ws.receive(), timeout)
            if msg.type != aiohttp.WSMsgType.TEXT:
                raise RuntimeError(
                    f"gateway socket closed mid-stream for {session_id}: "
                    f"{msg.type} {msg.data!r}"
                )
            push = json.loads(msg.data)
            record = push["record"]
            if ttft is None:
                t_first = time.monotonic()
                ttft = t_first - sent
            value = record.get("value")
            nbytes += len(value) if isinstance(value, str) else len(json.dumps(value))
            headers = record.get("headers") or {}
            if headers.get("stream-last-message") == "true":
                return ttft, nbytes, t_first, time.monotonic()



def _reclaim() -> None:
    """Drop phase garbage before the next model stages its weights: an
    8B-class phase needs nearly all of HBM, and a lingering reference
    (engine thread, traceback) from an earlier phase is an instant
    RESOURCE_EXHAUSTED (observed r5: one leaked failed phase OOMed every
    phase after it)."""
    import gc

    gc.collect()


def main() -> None:
    import jax

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    if not on_tpu:
        # CPU fallback (CI smoke): tiny config, same code paths.
        preset, quantize = "tiny-test", False
        max_batch, new_tokens, n_requests, n_sessions = 4, 32, 8, 4
        max_seq_len, decode_chunk, prefill_batch = 256, 8, 4
        long_len, long_seg, long_max_seq = 150, 32, 256
        # shared-preamble burst: tiny-test caps max_seq_len at 1024, so the
        # CPU smoke uses a 512-token preamble (same code path, smaller)
        prefix_args = dict(
            preamble_len=512, n_chats=8, max_seq_len=1024,
            buckets=(64, 128, 256, 512, 1024),
        )
    else:
        # decode is HBM-bandwidth-bound: int8 weights halve the dominant
        # read stream, and the decode chunk scans a kv_bound-sliced cache
        # (engine._decode_kv_bound) so cache reads scale with the longest
        # LIVE row, not max_seq_len. That moved the batch knee from 96 to
        # 192 (r5 sweep: 96/128/192/224/256 ->
        # 11212/13942/15686/15295/14765 tok/s at chunk=16; chunk=32
        # regressed to 14905 at B=192). prefill_batch=max_batch: a whole
        # admission wave lands in ONE prefill dispatch
        preset, quantize = "gemma-2b", True
        max_batch, new_tokens, n_requests, n_sessions = 192, 256, 384, 96
        # T=512 covers the workload (32 prompt + 256 new + inflight): the
        # decode kv_bound never exceeded 512 at T=1024 either, and the
        # smaller width drops one precompiled ladder program per engine
        max_seq_len, decode_chunk, prefill_batch = 512, 16, 192
        long_len, long_seg, long_max_seq = 8000, 2048, 8192
        # the acceptance workload: ≥8 concurrent chats over an identical
        # 1k-token preamble; int8 KV so the published pool rows are the
        # quantized values (exactness-tested path)
        prefix_args = dict(
            preamble_len=1024, n_chats=16, max_seq_len=2048,
            buckets=(64, 128, 256, 512, 1024, 2048), kv_int8=True,
        )

    print(f"[bench] engine phase: {preset} quantize={quantize}", file=sys.stderr, flush=True)
    tok_s = bench_engine(
        preset, quantize, max_batch, new_tokens, n_requests, max_seq_len, decode_chunk
    )
    print(f"[bench] engine: {tok_s:.0f} tok/s; gateway phase", file=sys.stderr, flush=True)
    extras = asyncio.run(
        bench_gateway(
            preset, quantize, max_batch,
            min(new_tokens, 128), n_sessions, max_seq_len, decode_chunk,
            prefill_batch,
        )
    )
    _reclaim()
    # same phase with fused scheduling OFF: the overlap TTFT delta must be
    # a measured pair from one run, not a cross-round comparison
    print(f"[bench] gateway (overlap on): {extras}; overlap-off phase",
          file=sys.stderr, flush=True)
    try:
        off = asyncio.run(
            bench_gateway(
                preset, quantize, max_batch,
                min(new_tokens, 128), n_sessions, max_seq_len, decode_chunk,
                prefill_batch, overlap=False,
            )
        )
        extras.update({f"overlap_off_{k}": v for k, v in off.items()})
    except Exception as e:  # noqa: BLE001 — the headline overlap-on run already landed
        print(f"[bench] overlap-off phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    print(f"[bench] gateway: {extras}; long-prompt phase", file=sys.stderr, flush=True)
    try:
        long_ttft = bench_long_prompt(preset, quantize, long_len, long_seg, long_max_seq)
        extras[f"long_prompt_{long_len}_ttft_ms"] = round(long_ttft * 1e3, 1)
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] long-prompt phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # shared-system-prompt burst: prefix cache on vs off over identical
    # params — the TTFT delta + hit rate + tokens saved are recorded
    # numbers, not claims (ISSUE 2 acceptance)
    print("[bench] prefix-cache burst phase", file=sys.stderr, flush=True)
    try:
        extras.update(bench_prefix_burst(preset, quantize, **prefix_args))
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] prefix burst phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # paged-vs-dense decode pair incl. the B=128 sweep point where the
    # dense layout is known to regress on cache reads (ISSUE 6 acceptance;
    # PERF.md round 10). On the chip this is also the gemma rematch for the
    # ragged paged kernel that previously lost (PERF.md item 5).
    print("[bench] paged-vs-dense phase", file=sys.stderr, flush=True)
    try:
        paged_batches = (96, 128, 192) if on_tpu else (max_batch,)
        extras.update(bench_paged_vs_dense(
            preset, quantize, batches=paged_batches,
            new_tokens=min(new_tokens, 128), n_requests=min(n_requests, 384),
            max_seq_len=max_seq_len, decode_chunk=decode_chunk,
        ))
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] paged-vs-dense phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # self-speculative decoding on the repetitive-text workload: the
    # on/off ms-per-accepted-token pair + acceptance rate are recorded
    # numbers, not claims (ISSUE 5 acceptance; PERF.md round 9)
    print("[bench] speculation phase", file=sys.stderr, flush=True)
    try:
        extras.update(bench_speculation(
            preset, quantize, max_batch=max_batch,
            n_requests=min(n_requests, 32), new_tokens=min(new_tokens, 128),
            max_seq_len=max_seq_len, decode_chunk=decode_chunk,
            # k sweep (CPU smoke, r9): 4 → 0.30 vs 0.16 off (loses: ≤5
            # tokens/iteration can't amortize the serialized host loop
            # against an 8-step chunk when weight reads are free), 8 →
            # 0.20 vs 0.24 (wins). On chip every verify saves k weight
            # reads, so smaller k should win too — re-measure there.
            spec_tokens=8,
        ))
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] speculation phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # the agentic tier (ISSUE 10 acceptance): base vs 1 vs 8 concurrent
    # LoRA adapters in the SAME batch, and the constrained-decoding
    # per-step mask overhead pair (docs/SERVING.md §15)
    print("[bench] adapters + constrained-decoding phase", file=sys.stderr,
          flush=True)
    try:
        extras.update(bench_adapters(
            preset, quantize, max_batch=max_batch,
            n_requests=min(n_requests, 32), new_tokens=min(new_tokens, 64),
            max_seq_len=max_seq_len, decode_chunk=decode_chunk,
        ))
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] adapters phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # packed grammar pool (ISSUE 20 acceptance, docs §15): mask-apply
    # ms/step pair, n_grammars-deep residency on the 64-slot default
    # pool, packed-vs-dense pool bytes + the 256k-vocab ratio
    print("[bench] constrained (packed grammar pool) phase", file=sys.stderr,
          flush=True)
    try:
        extras.update(bench_constrained(
            preset, quantize, max_batch=max_batch,
            n_requests=min(n_requests, 32), new_tokens=min(new_tokens, 64),
            max_seq_len=max_seq_len, decode_chunk=decode_chunk,
            n_grammars=16,
        ))
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] constrained phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # tiered-KV idle-session churn: next-turn TTFT with the host tier on
    # vs off over a pool sized to thrash (ISSUE 11 acceptance; docs §16)
    print("[bench] tiered-KV hibernation phase", file=sys.stderr, flush=True)
    try:
        extras.update(bench_tiered_kv(
            preset, quantize,
            n_sessions=8 if not on_tpu else 32, rounds=3,
            new_tokens=16, kv_int8=on_tpu,
        ))
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] tiered-KV phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # durable-tier resurrection (ISSUE 18 acceptance, docs §23): replica
    # A hibernates N sessions to disk, replica B resurrects them — the
    # next-turn TTFT pair vs a tier-off cold engine is the price of a
    # replica death with vs without the durable tier
    print("[bench] durable-tier hibernate/resurrect phase", file=sys.stderr,
          flush=True)
    try:
        extras.update(bench_hibernate(
            preset, quantize, n_sessions=4 if not on_tpu else 16,
            new_tokens=16,
        ))
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] hibernate phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # observability overhead pair: histograms + spans + flight recorder on
    # vs off over the same decode workload (§12; PERF.md round 11) — the
    # hot-loop bound itself is test-asserted, this records the end-to-end
    # throughput cost
    print("[bench] observability-overhead phase", file=sys.stderr, flush=True)
    try:
        extras.update(bench_observability_overhead(
            preset, quantize, max_batch=max_batch,
            new_tokens=min(new_tokens, 64), n_requests=min(n_requests, 64),
            max_seq_len=max_seq_len, decode_chunk=decode_chunk,
        ))
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] observability phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # degradation under injected faults: p99 TTFT + shed rate while the
    # engine takes periodic decode crashes and a NaN quarantine (§9)
    print("[bench] degradation (fault-injection) phase", file=sys.stderr, flush=True)
    try:
        extras.update(bench_degradation(
            preset, quantize, max_batch, min(new_tokens, 64),
            max(n_requests, 32), max_seq_len, decode_chunk,
        ))
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] degradation phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # multi-tenant noisy-neighbor pair (ISSUE 14 acceptance, docs §19):
    # the victim tenant's TTFT tail solo vs under the deterministic
    # tenant-burst aggressor — the p99 ratio is the isolation headline
    # (acceptance bound 2×), and the shed split proves the aggressor
    # absorbed all of it
    print("[bench] tenancy (noisy-neighbor) phase", file=sys.stderr, flush=True)
    try:
        extras.update(bench_tenancy(
            preset, quantize, max_batch=max_batch,
            n_requests=min(n_requests, 24), new_tokens=min(new_tokens, 16),
            max_seq_len=max_seq_len, decode_chunk=decode_chunk,
        ))
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] tenancy phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # fleet routing pair (ISSUE 8 acceptance): 3-process CPU fleet,
    # shared-preamble 10× burst, prefix-affinity vs round-robin — the
    # workers pin JAX_PLATFORMS=cpu, so this phase runs identically on
    # TPU hosts (the router tier is host code; engine perf has its own
    # phases)
    print("[bench] fleet (affinity vs round-robin) phase", file=sys.stderr,
          flush=True)
    try:
        extras.update(bench_fleet())
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] fleet phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # disaggregated prefill/decode (ISSUE 13 acceptance, docs §18): the
    # mixed workload — steady decode streams + long-prompt bursts — with
    # prefill/decode roles + KV-page migration ON vs a mixed 2-replica
    # fleet; records steady-stream TTFT/inter-token tails and the
    # migration ledger (count, p50/p99, fallbacks)
    print("[bench] disaggregated prefill/decode phase", file=sys.stderr,
          flush=True)
    try:
        extras.update(bench_disagg())
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] disagg phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # binary fleet wire v2 + P2P page fetch (ISSUE 16 acceptance, docs
    # §21): v1-vs-v2 encoded bytes per migrated page (the ≤0.76× bound)
    # and per streamed token, migration MB/s over the HTTP loopback under
    # both codecs, and the P2P-warm-admit vs cold-re-prefill TTFT pair
    print("[bench] fleet wire v1-vs-v2 + P2P fetch phase", file=sys.stderr,
          flush=True)
    try:
        extras.update(bench_wire())
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] wire phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # cold-start drill (ISSUE 17 acceptance, docs §22): streamed
    # three-stage weight pipeline vs the eager loader over the same
    # multi-shard checkpoint — wall pair + per-phase split + staging peak
    print("[bench] cold-start (streamed vs eager weight load) phase",
          file=sys.stderr, flush=True)
    try:
        extras.update(bench_cold_start())
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] cold-start phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    # SPMD fast-path wire (ISSUE 9 acceptance): loopback leader+follower
    # on a TP mesh over all local devices with prefix + speculation +
    # paged ON — throughput with the wire active plus the MEASURED
    # ControlBlock bytes/announce/iteration (PERF.md round 13)
    print("[bench] SPMD wire (fast-path parity) phase", file=sys.stderr,
          flush=True)
    try:
        extras.update(bench_spmd_wire())
    except Exception as e:  # noqa: BLE001 — the headline phases already ran
        print(f"[bench] SPMD wire phase failed: {e}", file=sys.stderr, flush=True)
    _reclaim()
    if on_tpu:
        # flagship phase: BASELINE.md's headline model (llama-3-8b, ≥2000
        # tok/s aggregate across chips = ~250 tok/s/chip on its 8-chip ref
        # config). int8 weights + int8 KV (+25% measured, PERF.md #4);
        # B=84 is the r5 HBM knee (the in-place layer scan killed the
        # decode-scan cache double-buffer that OOMed B>48; the kv_bound
        # chunk slice adds one bound-wide copy pair per chunk, which is
        # what stops B=88/96 — 15.9G peak vs 15.75G HBM).
        try:
            print("[bench] llama-3-8b phase", file=sys.stderr, flush=True)
            # max_seq_len sized to the WORKLOAD (32 prompt + 128 new = 160
            # → 256): the engine now precompiles the full kv_bound ladder,
            # and a 1024-wide config at B=84 compile-OOMs on the largest
            # bound — r5's "B=84 knee at 1024" only ever ran bounds ≤256,
            # i.e. it advertised capacity it couldn't serve. The honest
            # width freed ~4G of cache, and the batch re-sweep (r5b:
            # 84/128/160/192/224 → 2666/3719/3842/3883/3812) moved the
            # knee to B=192.
            llama_tok_s = bench_engine(
                "llama-3-8b", True, max_batch=192, new_tokens=128,
                n_requests=384, max_seq_len=256, decode_chunk=16,
                kv_int8=True,
            )
            extras["llama_3_8b_int8_tokens_per_sec"] = round(llama_tok_s, 2)
        except Exception as e:  # noqa: BLE001
            print(f"[bench] llama phase failed: {e}", file=sys.stderr, flush=True)
        _reclaim()
        # MoE phase (BASELINE config #5): mixtral architecture at the scale
        # ONE chip serves in int8 (mixtral-8x1b preset — 8 experts, top-2,
        # same ratios as 8x7b; ~8.9GiB weights). Expert routing under the
        # continuous batcher; the full-size 8x7b dp×ep×tp sharding is
        # dryrun-validated in __graft_entry__ instead.
        try:
            print("[bench] mixtral-8x1b MoE phase", file=sys.stderr, flush=True)
            # r5b batch sweep: 32/64/96/128/160/192/224 →
            # 1608/2552/3141/4085/4346/4510/4379 tok/s — knee at B=192
            # (top-2 expert FFNs amortize across the bigger token batch)
            moe_tok_s = bench_engine(
                "mixtral-8x1b", True, max_batch=192, new_tokens=128,
                n_requests=384, max_seq_len=256, decode_chunk=16,
                kv_int8=True,
            )
            extras["moe_mixtral_8x1b_int8_tokens_per_sec"] = round(moe_tok_s, 2)
        except Exception as e:  # noqa: BLE001
            print(f"[bench] MoE phase failed: {e}", file=sys.stderr, flush=True)
        _reclaim()
        # long-context ceiling phase: the largest context the memory plan
        # says ONE chip truly serves on the 128k NTK preset — llama-3.1-8b,
        # int8 weights + int8 KV, B=1 → 32k (serving/memory.py). TTFT of a
        # 32k-token prompt through the chunked-prefill path. 8192-token
        # segments (r5): model-dtype MXU dots + 512-wide kernel blocks took
        # the segment kernel from 14 to 35 TFLOPS, and wider segments
        # amortize the ~360ms/segment dispatch+linear floor
        # (2048/4096/8192 → 9.0/7.3/6.6s).
        try:
            print("[bench] llama-3.1 32k long-context phase", file=sys.stderr, flush=True)
            ttft32k = bench_long_prompt(
                "llama-3.1-8b", True, 32000, 8192, 32768,
                max_batch=1, kv_int8=True,
            )
            extras["long_prompt_32000_ttft_ms"] = round(ttft32k * 1e3, 1)
        except Exception as e:  # noqa: BLE001
            print(f"[bench] 32k phase failed: {e}", file=sys.stderr, flush=True)
    print(f"[bench] extras: {extras}", file=sys.stderr, flush=True)
    baseline = 2000.0  # BASELINE.json aggregate target
    name = f"{preset}-int8" if quantize else preset
    print(
        json.dumps(
            {
                "metric": f"decode_tokens_per_sec_per_chip[{name}]",
                "value": round(tok_s, 2),
                "unit": "tok/s",
                "vs_baseline": round(tok_s / baseline, 4),
                "extras": extras,
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())

"""TPU-native AI service provider: local JAX serving instead of remote APIs.

This is the component the whole rebuild exists for (the build's north star):
it implements the reference's ServiceProvider/CompletionsService/
EmbeddingsService SPI surface (`services/ServiceProvider.java:24`,
`completions/CompletionsService.java:22-33`, `embeddings/EmbeddingsService.java:24-36`)
with a local continuous-batching engine on the chip, replacing
`OpenAICompletionService.java` et al. Registered as resource type
``tpu-serving`` in `configuration.resources`.

Resource configuration:
  model: preset name (models.configs.MODEL_PRESETS) — gemma-2b, llama-3-8b, …
  tokenizer: "byte" (default) | "hf:<local path>"
  weights: "random" (default) | path to HF safetensors dir (models.loader)
  weight-streaming: auto (default) | off → streamed sharded weight load
    (models/streamload.py, docs/SERVING.md §22): safetensors shards are
    header-indexed and mmapped, a parallel reader pool assembles one
    LAYER at a time into staging, and per-layer device uploads overlap
    the next layer's host work — host RAM peaks at the readahead window
    instead of the eager path's ~2× weights, and the transfer TAIL
    overlaps engine compile-warmup (the load returns with uploads still
    in flight). Bit-exact vs the eager path on every architecture ×
    dtype; "off" is the escape hatch (and the bench_cold_start baseline)
  weight-load-workers: parallel shard-reader threads (default 4) — also
    sizes the readahead window (workers + 1 layers) the host-RAM staging
    peak is bounded by
  quantize-on-load: auto (default) | off → with `quantization: int8`,
    quantize each layer ON DEVICE as it streams in, so an int8
    deployment never materializes the full-precision tree anywhere —
    host holds one staging window, device holds the int8 tree plus one
    full-precision layer. "off" loads full-precision first, then runs
    the eager quantize_params pass (big models fall back to the
    host-staged eager path). Identical numerics either way
  max-batch / max-seq-len / prefill-buckets / decode-chunk: engine knobs
  page-size / kv-pages: the engine's KV state is ONE page-table-indexed
    device pool (serving/pagepool.py): decode, chunked prefill and
    speculative verify all attend through per-slot page tables (ONE
    compiled program each), and prefix reuse aliases pages zero-copy.
    Legal under multi-host SPMD (allocator events ride the
    leader→follower wire, docs/SERVING.md §14) and sharded meshes (the
    pool shards kv heads on "model"). `page-size` (default 64 tokens)
    sizes a page; `kv-pages` overrides the pool's page count (default:
    every slot's max-seq-len + `prefix-cache-fraction` alias headroom —
    see docs/SERVING.md §11 for the memory-plan math)
  prefill-token-budget: prefill tokens per fused prefill–decode iteration
    (every device dispatch carries a token-budgeted slice of pending
    prefill work plus the decode chunk; default: the chunked-prefill
    segment width = the largest prefill bucket)
  max-prefill-streams: concurrent chunked-prefill streams (default 2;
    each holds its reserved slot's pages)
  prefix-cache: auto | off (default off) → automatic cross-request prefix
    KV reuse (serving/pagepool.PrefixPageIndex): shared prompt preambles
    prefill once, later admissions alias the cached pages and prefill
    only the suffix. `prefix-cache-fraction` (default 0.25) adds alias
    headroom to the pool's default page count; `prefix-cache-entries`
    caps the index (default 512; 0 disables reuse). The memory plan
    accounts the pool before warmup.
  host-kv-fraction: tiered KV (docs/SERVING.md §16; prefix-cache
    only) — sizes a pinned host-RAM page arena relative to
    the device pool (e.g. 8.0 = 8× the pool in host RAM; 0, the default,
    disables the tier). Idle published prefixes spill into it off the hot
    loop (`spill-idle-s`, default 0 = as soon as published) and under HBM
    pressure LRU eviction DEMOTES to the host copy instead of dropping —
    a hibernated session's next turn restores its KV at DMA speed instead
    of re-prefilling. `spill: auto|off` (default auto) is the escape
    hatch; a restore blocking an admission past `restore-stall-dump-s`
    (default 1.0) produces a `spill-stall` flight dump. Leader-side host
    state: construction-disabled under SPMD (an explicit warning, like
    adapters in round 14).
  speculation: auto | off (default off) → self-speculative decoding
    (serving/speculation.py + engine._paged_verify_chunk): host-side n-gram
    prompt-lookup drafts verified k+1-at-a-time in one device dispatch —
    one weight read emits up to k+1 tokens per slot on repetitive text.
    `speculation-tokens` (default 4) is k, fixed engine-wide (one compiled
    verify program). Runs under SPMD too (drafts ride the wire, §14);
    composes with overlap, prefix-cache, and both KV dtypes
    (docs/SERVING.md §10).
  adapters: list of LoRA adapters to register at startup — each entry
    {name, rank (8), scale (1.0), path (HF/peft safetensors dir) | seed
    (random init)}. One engine then serves base + every adapter MIXED in
    the same decode dispatch (serving/adapters.py; per-request selection
    via the completion option `adapter: <name>`). `adapter-pool-fraction`
    (default 0.1) sizes the hot device pool as a fraction of weight HBM —
    adapters beyond it stay registered and hot-swap in LRU (watch
    engine_adapter_swaps_total); `adapter-rank` pads all adapters to one
    pool rank; `adapter-pool-rows` overrides the row count directly.
    Not yet on the SPMD wire (single-host engines only); docs §15
  constrained-decoding: auto (default) | off → grammar-constrained
    decoding (serving/constrain.py): a request carrying
    `response-format: {type: json_schema|regex, ...}` compiles to a
    token-level DFA and the sampler masks illegal tokens every step, so
    structured output is guaranteed valid — including through the
    speculative verify path. `grammar-slots` (default 64 — the packed
    bitmask pool made rows ~32× cheaper than the old dense table, so
    hundreds of resident grammars are affordable; 0 disables constrained
    decoding), `grammar-states` (default 128) and `grammar-exceptions`
    (default 65536 — per-row capacity for non-default transitions) size
    the device pool; the memory plan logs the cost (≈0.3GiB at a 256k
    vocab with 64 slots — docs §15 has the sizing table)
  inflight-records: how many records an agent that calls this service keeps
    in flight (runtime/runner.py). Unset, the runner bounds BATCHES (four
    queued), and records that arrive one by one are batches of one: six or
    seven requests reach the engine whatever `max-batch` is. Set it where
    requests are long and arrive as a trickle or a fresh backlog (a
    pipeline of whole documents): at least `max-batch` plus what should
    wait in the engine's queue, and `queue-depth` no smaller
  queue-depth / shed-policy: bounded admission queue; "block" (default)
    backpressures the broker poll loop, "reject" sheds with a retry-after
    (ShedError) so front doors degrade to fast 429s under overload
  tenants: multi-tenant overload control (serving/tenancy.py, docs
    §19) — list of {name, weight (1.0), max-slots, queue-share,
    token-rate, burst-s} blocks. Admission becomes per-tenant weighted
    deficit round-robin (the fused iteration's prefill-token budget and
    the free-slot pool divide by weight, work-conserving), per-tenant
    queue shares shed the burster instead of backpressuring everyone,
    and token-rate quotas make over-quota tenants shed FIRST under
    pressure. Unknown tenants get weight 1.0 and no caps; requests
    without a tenant land in "default".
  brownout: auto (default) | off — the graceful-degradation ladder
    (docs §19): under sustained load (engine load_score ≥
    `brownout-enter-load`, default 2.0, held for `brownout-dwell-s`,
    default 0.5) the engine walks spec-shrink → spec-off → reject-low →
    reject-quota one hysteresis-gated step at a time, and walks back
    down once load holds ≤ `brownout-exit-load` (default 1.0). Every
    transition is counted, logged and flight-dumped (`brownout` reason);
    decode of admitted work is never degraded in correctness.
  engine-restart-backoff / engine-max-restarts: loop-crash recovery —
    quarantine in-flight slots, rebuild device state, restart under
    bounded exponential backoff (single-host only; SPMD stays crash-only)
  drain-grace-s: close() drains (finish in-flight, reject new) this many
    seconds before the hard stop
  fault-injection / fault-seed / fault-stall-s: deterministic fault drills
    (serving/faultinject.py; also via LSTPU_FAULTS env)
  observability: true (default) → streaming latency histograms (TTFT,
    inter-token, queue wait, dispatch/fetch times → stats()["histograms"],
    /metrics exposition and the Grafana heatmap), per-request lifecycle
    spans on /traces, the derived load score, and the flight recorder.
    `flight-recorder-iterations` (default 256) sizes the ring of engine
    iterations dumped on NaN/page quarantines, restarts and shed bursts;
    `flight-dir` (or LSTPU_FLIGHT_DIR) writes dump JSON files there
    (docs/SERVING.md §12)
  fleet: auto | off (default off) → resolve each completion through the
    fleet router (serving/fleet.py): prefix-affinity-first, load-second
    dispatch across this engine plus the peer replicas in
    `fleet-replicas` (list of beacon base-URLs or {id,url} dicts).
    `fleet-lambda` (default 256) trades warm-prefix tokens against load;
    `fleet-policy` (affinity | round-robin | least-loaded) exists for
    benches; `fleet-replica-id`/`fleet-self-url` identify THIS replica in
    beacons; `fleet-beacon-ttl-s`/`fleet-refresh-interval-s`/
    `fleet-sticky-ttl-s` tune health and session stickiness
    (docs/SERVING.md §13). The /state beacon and /fleet/generate endpoint
    are served regardless of this knob — fleet: off only means THIS
    process routes nothing.
  fleet-role: prefill | decode | mixed (default mixed) → disaggregated
    prefill/decode (docs/SERVING.md §18): the role rides this replica's
    beacon; routers steer prefill-heavy admissions (estimated prefill ≥
    `fleet-prefill-threshold`, default 2048 tokens) at prefill-tagged
    replicas, run prefill + the first token there, MIGRATE the KV pages
    (`POST /fleet/migrate`, lstpu-kvmig-v1, per-page blake2b checksums)
    to a decode replica, and finish the stream where the steady decode
    pool lives. `fleet-migrate: auto|off` disables only the transfer
    (roles still steer; streams decode in place);
    `fleet-migrate-timeout-s` (default 30) bounds each transfer — on ANY
    migration failure the stream decodes in place on the prefill
    replica, token-exact, and the fallback is counted + flight-dumped.
  spmd-parity-echo: false (default) → on multi-host replicas, re-broadcast
    every processed decode/verify chunk's tokens so followers verify them
    against their own device results (one extra broadcast per chunk; a
    mismatch attempts ONE coordinated resync, then crashes the replica
    with a flight dump — docs/SERVING.md §14/§20 divergence semantics)
  spmd-watchdog-s: 30 (0 = off) → slice resilience bound (docs/SERVING.md
    §20): follower recv deadline (2×), leader idle-heartbeat cadence
    (¼×) and the leader's per-iteration fetch bound (1×) — a wedged or
    dead leader is detected within 2× instead of parking every pod in a
    collective forever; must exceed the worst single warmup family's
    compile stall (warm the compile cache, or raise it)
  spmd-resync-window-s: 60 → a second divergence within this window of a
    granted resync stays fatal (transient wire loss does not repeat)
  compile-cache-dir: persistent XLA compile cache directory — a scale-up
    replica pointed at a warm (shared) cache dir skips the warmup
    ladder's compile wall and serves in seconds (fleet cold-start lever).
    JAX_COMPILATION_CACHE_DIR wins when set, then this knob, then (on an
    accelerator; never on the CPU backend) a fixed `.jax_compile_cache/`
    beside the package (serving/engine.enable_persistent_compile_cache)
  mesh: {model: N, data: M, expert: K} → shard weights over the local mesh
  quantization: "int8" → weight-only int8 (halves weight HBM traffic; big
    models stage on the host so the bf16 tree never needs device HBM)
  kv-cache-quantization: "int8" → int8 KV cache with per-token per-head
    scales (int8×int8 MXU attention; ~halves decode cache bandwidth —
    the lever that matters for GQA models like llama, see PERF.md)
  hbm-bytes: device HBM budget for that staging decision (default: the
    device's own reported limit, 16GiB where it reports none)

Streaming follows the reference's growth batching (OpenAICompletionService:
"start from 1 chunk, then double the size until min-chunks-per-message"), so
the first token becomes the first chunk — TTFT is one decode step.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import uuid
from typing import Any, Optional

import numpy as np

from langstream_tpu.ai.provider import (
    ChatChunk,
    ChatCompletionsResult,
    ChatMessage,
    CompletionsService,
    EmbeddingsService,
    ServiceProvider,
    StreamingChunksConsumer,
)
from langstream_tpu.models.configs import MODEL_PRESETS, GenerationOptions, ModelConfig

log = logging.getLogger(__name__)


def _device_hbm_bytes() -> int:
    """The first device's own memory limit where it reports one (TPUs do,
    through ``memory_stats()``), else 16GiB — the v5e figure, for backends
    that report nothing (the CPU one)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit") or 16 * 1024**3)


class _EngineHolder:
    """Lazy, thread-safe singleton build of tokenizer/params/engine —
    engine construction compiles XLA programs, so it must happen once."""

    def __init__(self, config: dict[str, Any]) -> None:
        self.config = config
        self._lock = threading.Lock()
        self._engine = None
        self._tokenizer = None
        self._model_config: Optional[ModelConfig] = None
        self._params = None
        self._embed_fn = None
        self._mesh = None
        self._fleet_router = None
        self._fleet_replica_id: Optional[str] = None
        # checkpoint→device load accounting (filled by params(), read by
        # build_engine → ServingEngine stats()/memory plan); {} for
        # random-init weights
        self._weight_load_report: dict[str, Any] = {}
        # ONE injector instance for the whole holder: the streamed loader
        # consults the `weight-load` site during params() and the engine
        # consults every other site — sharing the instance keeps the
        # seeded schedule and call counters coherent across both
        self._injector: Any = None
        self._injector_built = False

    def mesh(self):
        """Device mesh for TP/EP sharding when `mesh` is configured."""
        if self._mesh is None and self.config.get("mesh"):
            from langstream_tpu.parallel.mesh import build_mesh

            self._mesh = build_mesh(dict(self.config["mesh"]))
        return self._mesh

    def model_config(self) -> ModelConfig:
        if self._model_config is None:
            name = self.config.get("model", "tiny-test")
            if name not in MODEL_PRESETS:
                raise ValueError(
                    f"unknown model preset {name!r}; known: {sorted(MODEL_PRESETS)}"
                )
            mc = MODEL_PRESETS[name]
            kv_mode = str(self.config.get("kv-cache-quantization", "") or "").lower()
            if kv_mode not in ("", "none", "int8"):
                raise ValueError(
                    f"unknown kv-cache-quantization {kv_mode!r}; "
                    "supported: int8, none"
                )
            if kv_mode == "int8":
                import dataclasses

                mc = dataclasses.replace(mc, kv_cache_dtype="int8")
            self._model_config = mc
        return self._model_config

    def tokenizer(self):
        if self._tokenizer is None:
            from langstream_tpu.serving.tokenizer import get_tokenizer
            from langstream_tpu.tracing import TRACER

            # an `hf:` tokenizer imports `transformers` before it reads a
            # file: seconds a replica's owner waits for, ahead of the
            # engine's own start-up (docs/SERVING.md §12, "Start-up")
            name = str(self.config.get("tokenizer", "byte"))
            with TRACER.span("engine.startup.tokenizer", tokenizer=name.partition(":")[0]):
                self._tokenizer = get_tokenizer(name)
        return self._tokenizer

    def params(self):
        if self._params is None:
            from langstream_tpu.tracing import TRACER

            # what a replica's owner waits for before the engine's own
            # start-up begins (docs/SERVING.md §12, "Start-up"); a tree that
            # was handed in never comes here
            with TRACER.span(
                "engine.startup.weights", weights=str(self.config.get("weights", "random"))
            ):
                self._load_params()
        return self._params

    def _load_params(self):
        import jax

        if self._params is None:
            import contextlib
            import time

            from langstream_tpu.models.transformer import init_params

            weights = self.config.get("weights", "random")
            mc = self.model_config()
            quant_mode = str(self.config.get("quantization", "") or "").lower()
            if quant_mode not in ("", "none", "int8", "w8"):
                raise ValueError(
                    f"unknown quantization {quant_mode!r}; supported: int8"
                )
            quantize = quant_mode in ("int8", "w8")
            streaming = self.config.get("weight-streaming", "auto")
            if not isinstance(streaming, bool) and str(streaming).lower() not in (
                "auto", "off",
            ):
                raise ValueError(
                    f"unknown weight-streaming {streaming!r}; "
                    "supported: auto, off"
                )
            stream_on = (
                streaming is True
                or (not isinstance(streaming, bool)
                    and str(streaming).lower() == "auto")
            )
            workers = int(self.config.get("weight-load-workers", 4))
            if workers < 1:
                raise ValueError(
                    f"weight-load-workers must be >= 1, got {workers}"
                )
            qol = self.config.get("quantize-on-load", "auto")
            if not isinstance(qol, bool) and str(qol).lower() not in (
                "auto", "off",
            ):
                raise ValueError(
                    f"unknown quantize-on-load {qol!r}; supported: auto, off"
                )
            qol_on = quantize and (
                qol is True
                or (not isinstance(qol, bool) and str(qol).lower() == "auto")
            )
            # models whose full-precision tree would not fit device HBM are
            # built + quantized on the HOST and shipped int8 (host init is
            # slower, so small models stay on-device). The STREAMED int8
            # path never materializes the full-precision tree, so it skips
            # the host stage entirely — unless quantize-on-load is forced
            # off, which reinstates the eager host-staged economics.
            hbm_budget = int(self.config.get("hbm-bytes") or _device_hbm_bytes())
            needs_host = quantize and mc.approx_params * 2 > hbm_budget // 2
            if stream_on and needs_host and quantize and not qol_on:
                stream_on = False
            report: dict[str, Any] = {}
            if weights not in (None, "random") and stream_on:
                from langstream_tpu.models.streamload import (
                    load_params_streamed,
                )

                # block=False: the transfer tail rides JAX async dispatch,
                # so engine construction + compile-warmup below overlap the
                # last layers' uploads (the §22 cold-start lever)
                params, rep = load_params_streamed(
                    weights,
                    mc,
                    workers=workers,
                    quantize=qol_on,
                    fault_injector=self._fault_injector(),
                    block=False,
                )
                if quantize and not qol_on:
                    from langstream_tpu.models.quant import quantize_params

                    params = quantize_params(params, mc)
                report = rep.as_dict()
            else:
                t0 = time.perf_counter()
                scope = (
                    jax.default_device(jax.devices("cpu")[0])
                    if needs_host
                    else contextlib.nullcontext()
                )
                with scope:
                    if weights in (None, "random"):
                        params = init_params(mc, jax.random.PRNGKey(0))
                    else:
                        from langstream_tpu.models.loader import load_params

                        params = load_params(weights, mc)
                    if quantize:
                        from langstream_tpu.models.quant import quantize_params

                        params = quantize_params(params, mc)
                if needs_host and self.mesh() is None:
                    # no mesh: move the int8 tree onto the accelerator
                    # ourselves (with a mesh, shard_params below owns
                    # placement)
                    params = jax.device_put(params, jax.devices()[0])
                if weights not in (None, "random"):
                    # the eager baseline's ledger, so streamed-vs-eager is
                    # comparable in stats()/bench without a code path probe
                    jax.block_until_ready(params)
                    report = {
                        "streamed": False,
                        "workers": 1,
                        "quantize-on-load": False,
                        "total-s": round(time.perf_counter() - t0, 4),
                        "bytes-read": sum(
                            leaf.size * leaf.dtype.itemsize
                            for leaf in jax.tree.leaves(params)
                        ),
                    }
            mesh = self.mesh()
            if mesh is not None:
                from langstream_tpu.parallel.sharding import shard_params

                params = shard_params(params, mesh, mc)
            self._weight_load_report = report
            self._params = params
        return self._params

    def build_engine(self, start: bool = True):
        """Construct the (possibly SPMD) engine. ``start=False`` is the
        multi-host follower path: the caller runs follower_loop over the
        channel instead of the leader's device loop."""
        from langstream_tpu.parallel.multihost import DistributedConfig
        from langstream_tpu.serving.engine import ServingEngine

        from langstream_tpu.serving.engine import (
            enable_persistent_compile_cache,
        )

        # persistent XLA compile cache (fleet fast cold start): a scale-up
        # replica pointed at a warm shared cache dir deserializes every
        # warmup program instead of recompiling — seconds instead of the
        # compile wall. Must be set BEFORE any jit below runs.
        cache_dir = enable_persistent_compile_cache(
            self.config.get("compile-cache-dir")
        )
        log.info("persistent compile cache: %s", cache_dir or "off")
        mc = self.model_config()
        if str(self.config.get("kv-layout", "paged")).lower() != "paged":
            raise ValueError(
                "kv-layout: dense is gone: the page pool is the engine's "
                "only KV state (remove the key)"
            )
        overlap = self.config.get("overlap", True)
        if not overlap or str(overlap).lower() in ("false", "off", "no"):
            raise ValueError(
                "overlap: false is gone: every iteration is the fused "
                "prefill–decode one (remove the key)"
            )
        page_size = int(self.config.get("page-size", 64))
        if page_size < 1:
            raise ValueError(f"page-size must be >= 1, got {page_size}")
        px = self.config.get("prefix-cache", "off")
        if not isinstance(px, bool) and str(px).lower() not in ("auto", "off"):
            raise ValueError(
                f"unknown prefix-cache {px!r}; supported: auto, off"
            )
        spill = self.config.get("spill", "auto")
        if not isinstance(spill, bool) and str(spill).lower() not in (
            "auto", "off",
        ):
            raise ValueError(f"unknown spill {spill!r}; supported: auto, off")
        host_kv_fraction = float(self.config.get("host-kv-fraction", 0.0))
        if host_kv_fraction < 0:
            raise ValueError(
                f"host-kv-fraction must be >= 0, got {host_kv_fraction}"
            )
        spill_idle_s = float(self.config.get("spill-idle-s", 0.0))
        spec = self.config.get("speculation", "off")
        if not isinstance(spec, bool) and str(spec).lower() not in ("auto", "off"):
            raise ValueError(
                f"unknown speculation {spec!r}; supported: auto, off"
            )
        spec_tokens = int(self.config.get("speculation-tokens", 4))
        if spec_tokens < 1:
            raise ValueError(
                f"speculation-tokens must be >= 1, got {spec_tokens}"
            )
        constrained = self.config.get("constrained-decoding", "auto")
        if not isinstance(constrained, bool) and str(constrained).lower() not in (
            "auto", "off",
        ):
            raise ValueError(
                f"unknown constrained-decoding {constrained!r}; "
                "supported: auto, off"
            )
        buckets = tuple(
            self.config.get("prefill-buckets", (32, 64, 128, 256, 512, 1024, 2048))
        )
        max_batch = int(self.config.get("max-batch", 8))
        # rows of the LARGEST admission group (default 8): a lone prompt
        # rides a group of ONE row, an expert model always prefill-batch
        # rows (engine.admit_rungs, docs/SERVING.md §11)
        prefill_batch = self.config.get("prefill-batch")
        max_seq = int(self.config.get("max-seq-len", min(2048, mc.max_seq_len)))
        spmd = None
        dist = DistributedConfig.from_env()
        if dist.is_multihost:
            # every process of the replica builds an IDENTICAL channel
            # (page/draft buffer sizes derive from the shared config); the
            # leader announces, followers replay (parallel/spmd_serving.py,
            # docs/SERVING.md §14 — prefix reuse, speculation and the
            # paged allocator all ride the wire since round 13)
            from langstream_tpu.parallel.spmd_serving import SpmdChannel
            from langstream_tpu.serving.pagepool import table_len_for

            spmd = SpmdChannel(
                prefill_batch=int(prefill_batch or ServingEngine.PREFILL_BATCH),
                max_width=max(buckets),
                max_batch=max_batch,
                table_len=(
                    table_len_for(max_seq, page_size)
                    if layout == "paged"
                    else 0
                ),
                spec_tokens=spec_tokens,
                echo=bool(self.config.get("spmd-parity-echo", False)),
                decode_chunk=int(self.config.get("decode-chunk", 16)),
                # slice resilience (docs/SERVING.md §20): the watchdog
                # bound arms idle heartbeats, the follower recv deadline
                # AND the leader's per-iteration fetch bound; 0 disables
                # all three (the pre-round-19 park-in-the-collective
                # behavior). Default 30s: it must exceed the worst single
                # warmup family's compile stall (seconds against a warm
                # persistent compile cache; set higher — or 0 — for cold
                # caches). The resync window is the follower's
                # repeat-divergence fatality rule.
                watchdog_s=float(self.config.get("spmd-watchdog-s", 30.0)),
                resync_window_s=float(
                    self.config.get("spmd-resync-window-s", 60.0)
                ),
            )
        # disaggregated serving (docs/SERVING.md §18): the replica's role —
        # validated HERE so a bad knob fails before the engine builds, and
        # passed down so role-tagged replicas budget migration staging RAM
        fleet_role = str(self.config.get("fleet-role") or "mixed")
        if fleet_role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"unknown fleet-role {fleet_role!r}; supported: prefill, "
                "decode, mixed"
            )
        engine = ServingEngine(
            mc,
            self.params(),
            max_batch=max_batch,
            max_seq_len=max_seq,
            eos_token_id=self.tokenizer().eos_token_id,
            prefill_buckets=buckets,
            mesh=self.mesh(),
            decode_chunk=int(self.config.get("decode-chunk", 16)),
            prefill_batch=prefill_batch,
            spmd=spmd,
            pipeline_depth=int(self.config.get("pipeline-depth", 1)),
            # default (None): precompile the decode ladder on TPU backends
            # so no XLA compile ever lands mid-traffic (PERF.md round 5b)
            precompile=self.config.get("precompile"),
            prefill_token_budget=(
                int(self.config["prefill-token-budget"])
                if self.config.get("prefill-token-budget") is not None
                else None
            ),
            max_prefill_streams=(
                int(self.config["max-prefill-streams"])
                if self.config.get("max-prefill-streams") is not None
                else None
            ),
            page_size=page_size,
            kv_pages=(
                int(self.config["kv-pages"])
                if self.config.get("kv-pages") is not None
                else None
            ),
            # tiered KV (docs/SERVING.md §16): host-RAM spill + hibernation
            host_kv_fraction=host_kv_fraction,
            spill=spill,
            spill_idle_s=spill_idle_s,
            restore_stall_dump_s=float(
                self.config.get("restore-stall-dump-s", 1.0)
            ),
            # durable session tier (docs/SERVING.md §23): crash-safe disk
            # checkpoints — `durable: auto` turns on iff `durable-dir` is
            # set, so the block is one knob in the common case
            durable=self.config.get("durable", "auto"),
            durable_dir=(
                str(self.config["durable-dir"])
                if self.config.get("durable-dir")
                else None
            ),
            durable_max_bytes=int(self.config.get("durable-max-bytes", 0)),
            durable_timeout_s=float(
                self.config.get("durable-timeout-s", 5.0)
            ),
            prefix_cache=px,  # validated at the top of this method
            prefix_cache_fraction=float(
                self.config.get("prefix-cache-fraction", 0.25)
            ),
            prefix_cache_entries=(
                int(self.config["prefix-cache-entries"])
                if self.config.get("prefix-cache-entries") is not None
                else None
            ),
            speculation=spec,  # validated at the top of this method
            speculation_tokens=spec_tokens,
            # the agentic tier (docs/SERVING.md §15): multi-LoRA adapters +
            # grammar-constrained decoding
            adapters=list(self.config.get("adapters") or []),
            adapter_pool_fraction=float(
                self.config.get("adapter-pool-fraction", 0.1)
            ),
            adapter_rank=(
                int(self.config["adapter-rank"])
                if self.config.get("adapter-rank") is not None
                else None
            ),
            adapter_pool_rows=(
                int(self.config["adapter-pool-rows"])
                if self.config.get("adapter-pool-rows") is not None
                else None
            ),
            constrained_decoding=constrained,
            grammar_slots=int(self.config.get("grammar-slots", 64)),
            grammar_states=int(self.config.get("grammar-states", 128)),
            grammar_exceptions=int(
                self.config.get("grammar-exceptions", 65536)
            ),
            grammar_tokenizer=self.tokenizer(),
            # request lifecycle / fault recovery (docs/SERVING.md §9)
            queue_depth=(
                int(self.config["queue-depth"])
                if self.config.get("queue-depth") is not None
                else None
            ),
            shed_policy=str(self.config.get("shed-policy", "block")),
            # multi-tenant overload control + brownout (docs/SERVING.md
            # §19): validated inside ServingEngine/TenantSpec so a bad
            # block fails the build, not the first burst
            tenants=list(self.config.get("tenants") or []),
            brownout=self.config.get("brownout", "auto"),
            brownout_enter_load=float(
                self.config.get("brownout-enter-load", 2.0)
            ),
            brownout_exit_load=float(
                self.config.get("brownout-exit-load", 1.0)
            ),
            brownout_dwell_s=float(
                self.config.get("brownout-dwell-s", 0.5)
            ),
            restart_backoff_s=float(
                self.config.get("engine-restart-backoff", 0.1)
            ),
            max_restarts=int(self.config.get("engine-max-restarts", 5)),
            fault_injector=self._fault_injector(),
            migrate_staging=fleet_role != "mixed",
            # per-phase load timings + staging peak from params() (§22);
            # params is an earlier argument, so the report is populated by
            # the time this kwarg is evaluated
            weight_load_report=self._weight_load_report,
            # observability layer (docs/SERVING.md §12): histograms +
            # request spans + flight recorder; off is the escape hatch for
            # the measured (<1%) hot-loop overhead
            observability=bool(self.config.get("observability", True)),
            flight_iterations=int(
                self.config.get("flight-recorder-iterations", 256)
            ),
            flight_dir=(
                str(self.config["flight-dir"])
                if self.config.get("flight-dir")
                else None
            ),
        )
        if start:
            engine.start()
            # a program the compiler refuses during warm-up fails the BUILD
            # (and with it the agent's start), not the first request
            engine.wait_ready()
            # publish this engine's state beacon + fleet dispatch endpoint
            # on the runtime HTTP server (serving/fleet.py registry): GET
            # /state and POST /fleet/generate work in every topology, not
            # just fleet-mode ones (the router on ANOTHER pod reads them)
            from langstream_tpu.serving import fleet as fleet_mod

            rid = str(self.config.get("fleet-replica-id") or "local")
            url = str(self.config.get("fleet-self-url") or "")
            role = fleet_role  # validated before the engine build above
            self._fleet_replica_id = rid
            fleet_mod.register_local(
                rid,
                beacon_fn=lambda: fleet_mod.beacon_from_engine(
                    rid, engine, url=url, role=role
                ),
                generate_fn=lambda payload: fleet_mod.engine_generate(
                    engine, payload
                ),
                # streaming remote dispatch (docs/SERVING.md §17): frames
                # flow to the dispatching router as the engine delivers
                # tokens, so a remote route keeps local TTFT semantics
                generate_stream_fn=(
                    lambda payload: fleet_mod.engine_generate_stream(
                        engine, payload
                    )
                ),
                # KV-page migration (docs/SERVING.md §18): inbound binds
                # and outbound pushes for disaggregated prefill/decode —
                # served regardless of the fleet knob, like /state
                migrate_bind_fn=(
                    lambda frames, timeout_s=30.0:
                    fleet_mod.engine_migrate_bind(
                        engine, frames, timeout_s
                    )
                ),
                migrate_out_fn=lambda payload: fleet_mod.engine_migrate_out(
                    engine, payload
                ),
                # peer-to-peer page fetch (docs/SERVING.md §21): serve
                # pages to a radix-missing peer (copy, never release) and
                # pull from an owner on command; the limits probe bounds
                # what /fleet/migrate will read off the wire
                migrate_pages_fn=(
                    lambda payload: fleet_mod.engine_migrate_pages(
                        engine, payload
                    )
                ),
                p2p_fetch_fn=lambda payload: fleet_mod.engine_p2p_fetch(
                    engine, payload
                ),
                migrate_limits_fn=engine.migrate_limits,
                reset_fn=engine.reset_histograms,
                # one attribute read (never stats()) — /healthz surfaces
                # the crash→rebuild→backoff window for readiness probes
                recovering_fn=lambda: engine.recovering,
                # same discipline for the durable tier (§23): True while
                # a disk restore is serving an admission, so readiness
                # can tell resurrection-in-progress from wedged
                restoring_fn=lambda: getattr(engine, "restoring", False),
            )
        return engine

    def _fault_injector(self):
        """Config-driven fault injection (staging drills): `fault-injection`
        is the spec string (serving/faultinject.py grammar), `fault-seed`
        pins the schedule. Built ONCE and cached: the streamed weight loader
        consults the `weight-load` site during params() and the engine
        consults every other site — a single instance keeps the seeded
        per-site schedule coherent across both consumers. With no config
        spec we fall back to LSTPU_FAULTS env activation here (instead of
        leaving it to the engine) for the same sharing reason."""
        if not self._injector_built:
            from langstream_tpu.serving.faultinject import FaultInjector

            spec = str(self.config.get("fault-injection", "") or "").strip()
            if spec:
                self._injector = FaultInjector(
                    spec,
                    seed=int(self.config.get("fault-seed", 0)),
                    stall_s=float(self.config.get("fault-stall-s", 0.05)),
                )
            else:
                self._injector = FaultInjector.from_env()
            self._injector_built = True
        return self._injector

    def engine(self):
        with self._lock:
            if self._engine is None:
                self._engine = self.build_engine(start=True)
            return self._engine

    def fleet_router(self):
        """The fleet router when `fleet: auto` is configured, else None.
        The router fronts THIS engine (InProcessReplica — local requests
        never pay an HTTP hop) plus every peer URL in `fleet-replicas`;
        its beacon refresher starts with it (docs/SERVING.md §13)."""
        mode = self.config.get("fleet", "off")
        mode_s = str(mode).lower()
        if mode is False or mode_s in ("off", "false", "none", ""):
            return None
        if mode is not True and mode_s != "auto":
            raise ValueError(f"unknown fleet mode {mode!r}; supported: auto, off")
        engine = self.engine()  # outside the lock: engine() takes it
        with self._lock:
            if self._fleet_router is None:
                from langstream_tpu.serving.fleet import (
                    FleetRouter,
                    HttpReplica,
                    InProcessReplica,
                    register_local_router,
                )

                rid = self._fleet_replica_id or "local"
                replicas: list[Any] = [
                    InProcessReplica(
                        rid, engine,
                        url=str(self.config.get("fleet-self-url") or ""),
                        role=str(self.config.get("fleet-role") or "mixed"),
                    )
                ]
                for peer in self.config.get("fleet-replicas") or []:
                    if isinstance(peer, dict):
                        replicas.append(
                            HttpReplica(
                                str(peer.get("id") or peer["url"]),
                                str(peer["url"]),
                            )
                        )
                    else:
                        replicas.append(HttpReplica(str(peer), str(peer)))
                router = FleetRouter(
                    replicas,
                    lam=float(self.config.get("fleet-lambda", 256.0)),
                    policy=str(self.config.get("fleet-policy", "affinity")),
                    beacon_ttl_s=float(
                        self.config.get("fleet-beacon-ttl-s", 10.0)
                    ),
                    refresh_interval_s=float(
                        self.config.get("fleet-refresh-interval-s", 0.5)
                    ),
                    sticky_ttl_s=float(
                        self.config.get("fleet-sticky-ttl-s", 600.0)
                    ),
                    # disaggregated prefill/decode (docs/SERVING.md §18)
                    prefill_route_threshold=int(
                        self.config.get("fleet-prefill-threshold", 2048)
                    ),
                    migrate=str(
                        self.config.get("fleet-migrate", "auto")
                    ).lower() not in ("off", "false", "0", "none"),
                    migrate_timeout_s=float(
                        self.config.get("fleet-migrate-timeout-s", 30.0)
                    ),
                    # peer-to-peer page fetch on radix miss (§21)
                    p2p=str(
                        self.config.get("fleet-p2p", "auto")
                    ).lower() not in ("off", "false", "0", "none"),
                    p2p_threshold=int(
                        self.config.get("fleet-p2p-threshold", 256)
                    ),
                    # fetch-vs-prefill cost model floor (§23): below this
                    # token gap a hint never fetches, estimates or not
                    p2p_min_gap=int(
                        self.config.get("fleet-p2p-min-gap", 0)
                    ),
                )
                router.start()
                # the HTTP prefetch surface (§23): POST /fleet/prefetch
                # reaches this router through the process registry
                register_local_router(router)
                self._fleet_router = router
            return self._fleet_router

    def embed_fn(self):
        with self._lock:
            if self._embed_fn is None:
                import functools

                import jax

                from langstream_tpu.models.transformer import encode
                from langstream_tpu.parallel.multihost import DistributedConfig

                if DistributedConfig.from_env().is_multihost:
                    # followers only replay the serving engine's dispatches
                    # (spmd_serving); an embed jit over the global mesh would
                    # hang in its first collective waiting for peers. Fail
                    # fast until embed ops join the SPMD channel.
                    raise RuntimeError(
                        "embeddings are not yet supported on a multi-host "
                        "(tpu.hosts > 1) replica — run the embedding model "
                        "on a single-host agent"
                    )

                self._embed_fn = functools.partial(
                    jax.jit(encode, static_argnames=("config",)),
                    config=self.model_config(),
                )
            return self._embed_fn

    def begin_drain(self) -> None:
        """The graceful HALF of teardown, callable while the runtime HTTP
        server is still up: stop routing, unregister the fleet beacon
        (peers see /state go empty within one refresh instead of racing
        new remote routes into the drain window — routes that would die
        as hop failures and charge the WRONG replica's breaker), then
        drain the engine so in-flight remote streams finish over the
        still-open wire. Idempotent; close() finishes with the hard
        stop."""
        with self._lock:
            if getattr(self, "_drain_begun", False):
                # idempotent for real: _serve() drains before its server
                # stops, then close() runs — a second drain() here would
                # wait the full grace period AGAIN on a wedged stream,
                # doubling worst-case shutdown
                return
            self._drain_begun = True
            router, self._fleet_router = self._fleet_router, None
            rid, self._fleet_replica_id = self._fleet_replica_id, None
            engine = self._engine
        if router is not None:
            from langstream_tpu.serving.fleet import unregister_local_router

            unregister_local_router()
            router.stop()
        if rid is not None:
            from langstream_tpu.serving import fleet as fleet_mod

            fleet_mod.unregister_local(rid)
        if engine is not None:
            # graceful: finish in-flight, reject new (ShedError) for a
            # bounded grace period — stop() alone _fail_alls work that
            # only needed a few more chunks
            engine.drain(float(self.config.get("drain-grace-s", 10.0)))
            # replica hibernation (§23): with the durable tier on,
            # checkpoint every live session to disk AFTER the drain
            # (streams finished; entries quiesced) and BEFORE close()'s
            # engine.stop() kills the command loop. No-op ({}) with the
            # tier off; failure degrades to whatever already checkpointed
            # — the drain itself never blocks on a wedged disk.
            if hasattr(engine, "hibernate"):
                ledger = engine.hibernate(rid or "")
                if ledger:
                    log.info(
                        "replica %s hibernated: %s session prefix(es), "
                        "%s bytes, %s failure(s)",
                        rid or "local", ledger.get("entries"),
                        ledger.get("bytes"), ledger.get("failures"),
                    )

    def close(self) -> None:
        self.begin_drain()
        with self._lock:
            engine, self._engine = self._engine, None
        if engine is not None:
            engine.stop()


class _StreamState:
    """Growth batching: flush after 1 raw token, then 2, 4, … capped at
    min_chunks — the reference provider's schedule."""

    def __init__(self, tokenizer, consumer: StreamingChunksConsumer, min_chunks: int):
        self.tokenizer = tokenizer
        self.consumer = consumer
        self.min_chunks = max(1, min_chunks)
        self.threshold = 1
        self.pending = 0
        self.tokens: list[int] = []
        self.emitted_text = ""
        self.index = 0
        self.answer_id = str(uuid.uuid4())

    def on_token(self, token: int) -> None:
        self.tokens.append(token)
        self.pending += 1
        if self.pending >= self.threshold:
            self._flush(last=False)
            self.threshold = min(self.threshold * 2, self.min_chunks)

    def _flush(self, last: bool) -> None:
        if last:
            text = self.tokenizer.decode(self.tokens)
        else:
            # a token boundary may split a multibyte char: hold back the
            # undecodable tail so the next flush re-emits it whole
            text = self.tokenizer.decode_stream_prefix(self.tokens)
            if not text.startswith(self.emitted_text):
                # decode prefix not stable yet (mid-grapheme) — wait
                self.pending = 0
                return
        delta = text[len(self.emitted_text) :]
        if delta or last:
            self.consumer(
                ChatChunk(content=delta, index=self.index, last=last, answer_id=self.answer_id)
            )
            self.index += 1
            self.emitted_text = text
        self.pending = 0

    def finish(self) -> None:
        self._flush(last=True)


class TpuCompletionsService(CompletionsService):
    def __init__(self, holder: _EngineHolder, step_config: dict[str, Any]) -> None:
        self.holder = holder
        self.step_config = step_config

    def engine_stats(self) -> dict[str, Any]:
        """Batch occupancy etc. for the serving gauges (only meaningful once
        the engine exists — never force a build just to report zeros)."""
        engine = self.holder._engine
        return engine.stats() if engine is not None else {}

    def fleet_stats(self) -> dict[str, Any]:
        """Router counters for the fleet gauges (empty when fleet: off or
        the router was never built — never force a build to report zeros)."""
        router = self.holder._fleet_router
        return router.stats() if router is not None else {}

    def _render_prompt(self, messages: list[ChatMessage]) -> str:
        tok = self.holder.tokenizer()
        hf = getattr(tok, "_tok", None)
        if hf is not None and getattr(hf, "chat_template", None):
            return hf.apply_chat_template(
                [{"role": m.role, "content": m.content} for m in messages],
                tokenize=False,
                add_generation_prompt=True,
            )
        lines = [f"{m.role}: {m.content}" for m in messages]
        lines.append("assistant:")
        return "\n".join(lines)

    async def get_chat_completions(
        self,
        messages: list[ChatMessage],
        options: dict[str, Any],
        chunks_consumer: Optional[StreamingChunksConsumer] = None,
    ) -> ChatCompletionsResult:
        return await self._generate(self._render_prompt(messages), options, chunks_consumer)

    async def get_text_completions(
        self,
        prompt: list[str],
        options: dict[str, Any],
        chunks_consumer: Optional[StreamingChunksConsumer] = None,
    ) -> ChatCompletionsResult:
        return await self._generate("\n".join(prompt), options, chunks_consumer)

    def _finish_result(
        self,
        tokens: list[int],
        finish_reason: str,
        prompt_tokens: int,
        ttft_s: float,
        total_s: float,
        options: dict[str, Any],
        stream_state: Optional["_StreamState"],
    ) -> ChatCompletionsResult:
        if stream_state is not None:
            stream_state.finish()
        content = self.holder.tokenizer().decode(tokens)
        # string-level stop sequences (token-level stops handled in-engine)
        for stop in options.get("stop") or []:
            cut = content.find(stop)
            if cut >= 0:
                content = content[:cut]
        return ChatCompletionsResult(
            content=content,
            finish_reason=finish_reason,
            prompt_tokens=prompt_tokens,
            completion_tokens=len(tokens),
            ttft_ms=ttft_s * 1000.0,
            total_ms=total_s * 1000.0,
        )

    async def _fleet_dispatch(
        self,
        router: Any,
        prompt_tokens: list[int],
        options: dict[str, Any],
        chunks_consumer: Optional[StreamingChunksConsumer],
    ) -> Optional[ChatCompletionsResult]:
        """Resolve one request through the fleet router. Returns None when
        the FIRST route lands on THIS replica (the caller runs the native
        zero-hop streaming path) and the completed result when it was
        dispatched over the wire.

        The hop STREAMS (docs/SERVING.md §17): router.stream_generate
        frames pipe straight into the gateway chunk writers as the peer
        delivers tokens, so a remote route keeps local TTFT semantics —
        the first chunk reaches the client long before the completion
        finishes. A peer dying mid-stream fails over WARM inside the
        router (prompt + delivered tokens re-dispatched to a survivor;
        prefix reuse makes the resume cheap) — this layer only keeps the
        cross-process cancel registration pointed at whichever replica
        currently owns the stream. The hop budget derives from the
        request's own deadline, never the flat default. Fleet sheds
        surface as the engine's ShedError so the pipeline's 429 handling
        is one code path."""
        import asyncio

        from langstream_tpu.serving import lifecycle
        from langstream_tpu.serving.engine import ShedError
        from langstream_tpu.serving.fleet import (
            FleetShedError,
            ReplicaError,
            close_frames,
            hop_timeout_s,
        )

        session_id = str(options.get("cancel-key") or "") or None
        # cross-process cancel (ROADMAP 3b): the cancel-key RIDES to the
        # peer — engine_generate_stream registers the request in the
        # peer's process-local lifecycle registry — and the owning replica
        # is recorded here per hop, so lifecycle.cancel() on a client
        # disconnect forwards POST /fleet/cancel and the remote decode
        # dies at the next chunk boundary instead of at its deadline
        remote_options = dict(options)
        loop = asyncio.get_running_loop()
        frames = router.stream_generate(
            prompt_tokens, remote_options, session_id=session_id,
            timeout_s=hop_timeout_s(options),
        )

        def _next():
            try:
                return next(frames)
            except StopIteration:
                return None

        delivered: list[int] = []
        end: Optional[dict] = None
        owner_url: Optional[str] = None
        stream_state = None

        def _point_cancel_at(url: str, is_local: bool) -> None:
            # keep exactly one remote-owner registration live, following
            # the stream across failovers
            nonlocal owner_url
            if owner_url is not None and session_id:
                lifecycle.unregister_remote(session_id, owner_url)
            owner_url = None
            if (
                session_id and url and not is_local
                and not url.startswith("local:")
            ):
                lifecycle.register_remote(session_id, url)
                owner_url = url

        # ONE try/finally owns the stream from here: a cancellation at ANY
        # await below (including the first fetch) must close the router
        # generator so the serving replica cancels its in-flight request
        try:
            try:
                first = await loop.run_in_executor(None, _next)
            except FleetShedError as e:
                raise ShedError(str(e), retry_after_s=e.retry_after_s) from e
            if first is None:
                return None  # defensive: empty stream means nothing routed
            if (
                first.get("kind") == "route"
                and first.get("local")
                and not first.get("disagg")
            ):
                # the route landed HERE: hand back to the native streaming
                # path before any dispatch happened (the route decision and
                # its counters/stickiness stand — this replica serves it).
                # NOT for a disagg prefill-handoff route (§18): the router
                # owns that orchestration (prefill here, migrate, decode
                # elsewhere) — short-circuiting would decode in place and
                # silently disable disaggregation on the local replica
                return None
            if chunks_consumer is not None:
                stream_state = _StreamState(
                    self.holder.tokenizer(),
                    chunks_consumer,
                    int(options.get("min-chunks-per-message", 20)),
                )
            frame: Optional[dict] = first
            while frame is not None:
                kind = frame.get("kind")
                if kind == "route":
                    _point_cancel_at(
                        str(frame.get("url") or ""),
                        bool(frame.get("local")),
                    )
                elif kind == "tokens":
                    for t in frame.get("tokens") or []:
                        delivered.append(int(t))
                        if stream_state is not None:
                            stream_state.on_token(int(t))
                elif kind == "end":
                    end = frame
                    break
                try:
                    frame = await loop.run_in_executor(None, _next)
                except FleetShedError as e:
                    raise ShedError(
                        str(e), retry_after_s=e.retry_after_s
                    ) from e
        except ReplicaError:
            if delivered:
                raise  # tokens already streamed: a local restart would dup
            # every replica DIED before the first token (sheds raise
            # FleetShedError→ShedError above, never this): serve locally
            # (cold) rather than fail — the engine in this process may be
            # healthy even when the router has it quarantined
            return None
        finally:
            if owner_url is not None and session_id:
                lifecycle.unregister_remote(session_id, owner_url)
            # race-safe: an executor thread may still be inside next()
            # when this coroutine is cancelled
            close_frames(frames)
        if end is None:
            raise ReplicaError(
                "fleet stream ended without a terminal frame"
            )
        return self._finish_result(
            delivered,
            str(end.get("finish_reason", "stop")),
            int(end.get("prompt_tokens", len(prompt_tokens))),
            float(end.get("ttft_s", 0.0)),
            float(end.get("total_s", 0.0)),
            options,
            stream_state,
        )

    async def _generate(
        self,
        prompt: str,
        options: dict[str, Any],
        chunks_consumer: Optional[StreamingChunksConsumer],
    ) -> ChatCompletionsResult:
        from langstream_tpu.serving.engine import GenerationRequest

        engine = self.holder.engine()
        tokenizer = self.holder.tokenizer()
        gen_options = GenerationOptions.from_dict(options)
        prompt_tokens = tokenizer.encode(prompt)
        router = self.holder.fleet_router()
        if router is not None:
            remote = await self._fleet_dispatch(
                router, prompt_tokens, options, chunks_consumer
            )
            if remote is not None:
                return remote
        stream_state = None
        on_token = None
        if chunks_consumer is not None:
            stream_state = _StreamState(
                tokenizer,
                chunks_consumer,
                int(options.get("min-chunks-per-message", 20)),
            )
            on_token = stream_state.on_token

        loop = asyncio.get_running_loop()
        done: asyncio.Future = loop.create_future()

        def _on_done(res) -> None:  # engine thread → event loop
            loop.call_soon_threadsafe(
                lambda: done.done() or done.set_result(res)
            )

        # trace correlation: the record's propagated ls-trace-id (forwarded
        # by the completions step) wins; else join whatever agent span is
        # active so the engine's request spans stitch into the pipeline
        # trace on /traces either way
        from langstream_tpu.tracing import TRACER

        trace_id = str(options.get("trace-id") or "") or TRACER.current_trace_id()
        request = GenerationRequest(
            prompt_tokens=prompt_tokens,
            options=gen_options,
            on_token=on_token,
            on_done=_on_done,
            trace_id=trace_id,
        )
        # client-disconnect wiring: the gateway cancels every request
        # registered under the record's session header when the websocket
        # drops (serving/lifecycle.py), so an abandoned stream stops
        # consuming decode steps within one chunk
        from langstream_tpu.serving import lifecycle

        cancel_key = str(options.get("cancel-key") or "")
        if cancel_key:
            lifecycle.register(cancel_key, request)
        try:
            # submit may block on a full queue (backpressure) → executor; the
            # WAIT is a loop future resolved by on_done, so an in-flight
            # generation holds no thread and agent fan-out isn't capped by
            # the executor pool size. Under shed-policy=reject the engine
            # raises ShedError with a retry-after estimate — honor it here
            # with a few PACED retries, so pipeline-level error handling
            # doesn't hammer the overloaded engine with immediate
            # resubmits (the 429/Retry-After contract, in-process)
            from langstream_tpu.serving.engine import ShedError

            for attempt in range(3):
                try:
                    await loop.run_in_executor(None, engine.submit, request)
                    break
                except ShedError as shed:
                    if attempt == 2:
                        raise
                    await asyncio.sleep(min(max(shed.retry_after_s, 0.05), 5.0))
            try:
                result = await asyncio.wait_for(done, 600.0)
            except (asyncio.TimeoutError, asyncio.CancelledError):
                # the awaiting task died (agent timeout / task cancellation):
                # without this the engine decodes the orphan to
                # max_new_tokens while its slot serves nobody
                request.cancel()
                raise
        finally:
            if cancel_key:
                lifecycle.unregister(cancel_key, request)
        if result.error is not None:
            raise result.error
        # finish_reason may be "cancelled"/"deadline": partial output flows
        # through normally (the record commits, the dead client's answer
        # goes unread) — raising here would only trigger pipeline retries
        # for work the client already abandoned
        return self._finish_result(
            result.tokens,
            result.finish_reason,
            result.prompt_tokens,
            result.ttft_s,
            result.total_s,
            options,
            stream_state,
        )


class TpuEmbeddingsService(EmbeddingsService):
    def __init__(self, holder: _EngineHolder, step_config: dict[str, Any]) -> None:
        self.holder = holder
        self.max_len = int(step_config.get("max-text-tokens", 512))

    async def compute_embeddings(self, texts: list[str]) -> list[list[float]]:
        import jax.numpy as jnp

        tokenizer = self.holder.tokenizer()
        params = self.holder.params()
        embed = self.holder.embed_fn()

        token_lists = [tokenizer.encode(t)[: self.max_len] for t in texts]
        # bucket the width to limit recompiles
        width = 16
        longest = max((len(t) for t in token_lists), default=1)
        while width < longest:
            width *= 2
        batch = np.zeros((len(texts), width), np.int32)
        lengths = np.zeros(len(texts), np.int32)
        for i, toks in enumerate(token_lists):
            batch[i, : len(toks)] = toks
            lengths[i] = max(1, len(toks))

        loop = asyncio.get_running_loop()

        def run():
            out = embed(params, jnp.asarray(batch), jnp.asarray(lengths))
            return np.asarray(out)

        vectors = await loop.run_in_executor(None, run)
        return [v.tolist() for v in vectors]


class TpuServingProvider(ServiceProvider):
    def __init__(self, resource_config: dict[str, Any]) -> None:
        self.holder = _EngineHolder(resource_config)

    def get_completions_service(self, config: dict[str, Any]) -> CompletionsService:
        return TpuCompletionsService(self.holder, config)

    def get_embeddings_service(self, config: dict[str, Any]) -> EmbeddingsService:
        return TpuEmbeddingsService(self.holder, config)

    def engine(self):
        """The provider's one ServingEngine, built and warmed on first use."""
        return self.holder.engine()

    @property
    def inflight_records(self) -> Optional[int]:
        """``inflight-records``: how many records an agent that calls this
        service keeps in flight (`runtime/runner.py`); unset: the runner's
        bound in batches."""
        n = self.holder.config.get("inflight-records")
        return int(n) if n else None

    async def close(self) -> None:
        # holder.close() drains synchronously for up to drain-grace-s —
        # run it off-loop so in-flight chunk-write coroutines (what the
        # draining generations are producing) keep running
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, self.holder.close)


def register() -> None:
    from langstream_tpu.api.doc import ConfigModel
    from langstream_tpu.core.registry import REGISTRY, ResourceTypeInfo

    REGISTRY.register_resource(
        ResourceTypeInfo(
            type="tpu-serving",
            description="Local JAX/TPU completions+embeddings serving engine.",
            config_model=ConfigModel(type="tpu-serving", allow_unknown=True),
            factory=TpuServingProvider,
        )
    )

"""Serving HBM accounting: what a (model, batch, context) configuration
actually costs on a chip, BEFORE allocating it.

The reference never has to answer this question — its serving is delegated
to remote providers (OpenAICompletionService.java etc.), so context length
is someone else's capacity problem. Here the model lives in local HBM, and
the honest ceiling for long-context serving is arithmetic, not marketing:
weights + decode cache + chunked-prefill local cache + XLA workspace must
fit. ``plan_serving_memory`` computes the terms from the real param/cache
pytree shapes (``jax.eval_shape`` — nothing is allocated), and
``max_context_single_chip`` inverts the plan to the largest power-of-two
context a given HBM budget serves.

Used by bench.py's long-prompt phases and the capacity docs/tests; the
engine logs the plan at startup so an over-committed config fails loudly
with numbers instead of an opaque RESOURCE_EXHAUSTED mid-request.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax

from langstream_tpu.models.configs import ModelConfig


def _tree_bytes(shape_tree: Any) -> int:
    return sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(shape_tree)
    )


@dataclass(frozen=True)
class ServingMemoryPlan:
    weights_bytes: int
    cache_bytes: int  # decode cache: max_batch × max_seq_len
    long_cache_bytes: int  # chunked-prefill local cache (one prompt wide)
    workspace_bytes: int  # XLA scratch / activation headroom estimate
    # Residual decode-chunk temp of the DENSE layout: the layer scan carries
    # the cache (transformer._scan_layers_inplace), so the old cache-sized
    # xs/ys double-buffer is gone (r4 it OOMed llama-3-8b past B=48); what
    # remains live is the current layer's read slice + its updated copy.
    # The paged layout has no such term: its scan addresses the pool by
    # (layer, page) and forms no per-layer entry (0 there).
    scan_buffer_bytes: int = 0
    # kv_bound slice+splice peak: a decode chunk at a SLICED bound copies
    # the cache's first `bound` columns out and back (engine._decode_chunk),
    # so up to bound/width of the cache is live ON TOP of the full cache.
    # The largest SLICED ladder bound is the largest pow2 strictly below
    # max_seq_len (the full-width program skips the slice; the ladder floors
    # at 64) — NOT width/2: for non-pow2 widths (T=1536 → bound 1024 =
    # 2/3 cache; T=1025 → bound 1024 ≈ the whole cache) the old cache/2
    # assumption under-reported and the full-ladder precompile OOMed configs
    # the plan had blessed. The r5b precompile made this peak unavoidable
    # at startup — the llama B=84 @ T=1024 config that "fit" without this
    # term compile-OOMed by exactly this allocation.
    bound_slice_bytes: int = 0
    # fused-iteration peak: with overlapped prefill–decode scheduling the
    # admission local cache (prefill_batch rows × the largest bucket width)
    # is live WHILE a decode chunk holds its kv_bound slice — before the
    # fused scheduler the two alternated, so neither plan term saw the sum.
    fused_prefill_bytes: int = 0
    # prefix KV pool (serving/prefix_cache.py): pool-entry rows × the
    # largest bucket width, resident for the engine's whole lifetime. Sized
    # by the `prefix-cache-fraction` knob; 0 when the cache is off.
    prefix_pool_bytes: int = 0
    # unified paged KV pool (serving/pagepool.py, kv_layout="paged"): ONE
    # [L, P, Hkv, page_size, D] device pool replaces the decode cache, the
    # prefix pool, the kv_bound slice/splice peak AND the chunked-prefill
    # local caches (paged segments write straight into the slot's pages) —
    # when this term is set, cache/bound_slice/long_cache/prefix_pool are 0.
    # Sized by pages_for_fraction: dense-parity token capacity plus the
    # prefix-cache-fraction alias headroom.
    page_pool_bytes: int = 0
    # multi-LoRA adapter pool (serving/adapters.py): the fixed-shape
    # stacked low-rank factor tree — rows × per-row bytes, resident for
    # the engine's lifetime. Sized by `adapter-pool-fraction`; 0 when no
    # adapters are configured.
    adapter_pool_bytes: int = 0
    # grammar DFA pool (serving/constrain.py): the PACKED planes — the
    # [G+1, S, ceil(V/32)] uint32 legality bitmask plus default-successor
    # [G+1, S] and exception key/next [G+1, E] int32 transition arrays.
    # ~1/28 of the dense [G+1, S, V] int32 table this replaced (~0.7 GiB
    # at a 256k vocab with 4×128; 64 slots now fit in ~0.3 GiB —
    # docs/SERVING.md §15 has the sizing table).
    grammar_pool_bytes: int = 0
    # tiered KV host arena (serving/pagepool.HostPageTier): pinned HOST
    # RAM, not HBM — deliberately excluded from total_bytes (which is the
    # HBM number an over-committed config dies on). Sized by the
    # `host-kv-fraction` knob relative to the device pool; it appears in
    # the plan so the startup log is honest about the process RSS a
    # million-hibernated-sessions config will claim (docs/SERVING.md §16).
    host_spill_bytes: int = 0
    # disaggregated serving (docs/SERVING.md §18): worst-case HOST-RAM
    # staging for one in-flight KV-page migration (one request's page set
    # serialized end-to-end). Host RAM like host_spill_bytes — excluded
    # from the HBM total; 0 on mixed-role replicas.
    migrate_staging_bytes: int = 0
    # streamed weight load (models/streamload.py, docs/SERVING.md §22):
    # the host-RAM staging high-water mark of the shard→device pipeline —
    # the readahead window of per-layer assembly buffers, NOT the ~2×
    # weights the eager path peaks at. HOST RAM like host_spill_bytes;
    # excluded from the HBM total, and transient (released once the last
    # layer uploads) — it appears so the startup log's RSS story covers
    # the load, the phase the pod is being health-probed through.
    weight_load_staging_bytes: int = 0
    # durable session tier (serving/durable.py, docs/SERVING.md §23): the
    # configured on-DISK checkpoint budget (`durable-max-bytes`; 0 with
    # the tier off or uncapped). Neither HBM nor RAM — it appears in the
    # summary so the startup log names every byte tier the engine can
    # touch, and so an operator sizing the durable volume sees the cap
    # they configured next to the arena it checkpoints.
    durable_disk_bytes: int = 0
    # self-speculative verify chunk (engine._verify_chunk): the multi-token
    # forward materializes fp32 logits for ALL k+1 positions of every slot
    # ([B, k+1, V] — k+1 times the decode step's [B, V], which the flat
    # workspace absorbs), and the rejection sampler's FILTER branch
    # (any slot with top-k/top-p) peaks at ~5 such buffers live at once:
    # scaled logits, the descending sort, the rank-masked copy, softmax
    # probs and their cumsum (serving/sampling.py _apply_filters). Charged
    # at 5× — at B=192, k=4, V=256k that is ~4.6 GiB, and a plan that only
    # counted the greedy path would bless configs that OOM on the first
    # sampled request. 0 with speculation off.
    verify_chunk_bytes: int = 0

    @property
    def total_bytes(self) -> int:
        return (
            self.weights_bytes
            + self.cache_bytes
            + self.long_cache_bytes
            + self.workspace_bytes
            + self.scan_buffer_bytes
            + self.bound_slice_bytes
            + self.fused_prefill_bytes
            + self.prefix_pool_bytes
            + self.page_pool_bytes
            + self.verify_chunk_bytes
            + self.adapter_pool_bytes
            + self.grammar_pool_bytes
        )

    def fits(self, hbm_bytes: int) -> bool:
        return self.total_bytes <= hbm_bytes

    def per_chip_bytes(self, devices: int) -> int:
        """First-order per-chip share on a sharded mesh: the plan's trees
        are GLOBAL, and the big terms (weights on model×expert, the dense
        cache / paged pool on model when the kv heads divide) shard across
        the mesh while the workspace allowance replicates per chip.
        Dividing everything except the workspace by the device count is
        the right startup-log read now that the paged pool is legal under
        meshes too (round 13); the achieved-bandwidth gauge does the exact
        per-axis split at runtime (engine._achieved_hbm_gbps)."""
        d = max(1, int(devices))
        return self.workspace_bytes + (self.total_bytes - self.workspace_bytes) // d

    def _agentic_summary(self) -> str:
        gib = 1024**3
        parts = []
        if self.adapter_pool_bytes:
            parts.append(f"adapter-pool {self.adapter_pool_bytes / gib:.2f}GiB + ")
        if self.grammar_pool_bytes:
            parts.append(f"grammar-pool {self.grammar_pool_bytes / gib:.2f}GiB + ")
        return "".join(parts)

    def _weight_load_suffix(self) -> str:
        if not self.weight_load_staging_bytes:
            return ""
        return (
            f" [+ weight-load staging "
            f"{self.weight_load_staging_bytes / 1024**3:.2f}GiB RAM, "
            f"transient]"
        )

    def summary(self) -> str:
        gib = 1024**3
        if self.page_pool_bytes:
            host = (
                f" [+ host KV tier {self.host_spill_bytes / gib:.2f}GiB RAM]"
                if self.host_spill_bytes
                else ""
            )
            if self.migrate_staging_bytes:
                host += (
                    f" [+ migrate staging "
                    f"{self.migrate_staging_bytes / gib:.2f}GiB RAM]"
                )
            if self.durable_disk_bytes:
                host += (
                    f" [+ durable KV tier "
                    f"≤{self.durable_disk_bytes / gib:.2f}GiB disk]"
                )
            host += self._weight_load_suffix()
            return (
                f"weights {self.weights_bytes / gib:.2f}GiB + "
                f"page-pool {self.page_pool_bytes / gib:.2f}GiB + "
                f"fused-prefill {self.fused_prefill_bytes / gib:.2f}GiB + "
                f"verify-chunk {self.verify_chunk_bytes / gib:.2f}GiB + "
                f"{self._agentic_summary()}"
                f"workspace {self.workspace_bytes / gib:.2f}GiB = "
                f"{self.total_bytes / gib:.2f}GiB{host}"
            )
        return (
            f"weights {self.weights_bytes / gib:.2f}GiB + "
            f"cache {self.cache_bytes / gib:.2f}GiB "
            f"(+{self.scan_buffer_bytes / gib:.2f}GiB scan double-buffer, "
            f"+{self.bound_slice_bytes / gib:.2f}GiB kv_bound slice peak) + "
            f"long-prefill {self.long_cache_bytes / gib:.2f}GiB + "
            f"fused-prefill {self.fused_prefill_bytes / gib:.2f}GiB + "
            f"prefix-pool {self.prefix_pool_bytes / gib:.2f}GiB + "
            f"verify-chunk {self.verify_chunk_bytes / gib:.2f}GiB + "
            f"{self._agentic_summary()}"
            f"workspace {self.workspace_bytes / gib:.2f}GiB = "
            f"{self.total_bytes / gib:.2f}GiB"
            f"{self._weight_load_suffix()}"
        )


def largest_sliced_bound(max_seq_len: int) -> int:
    """The widest kv_bound ladder step that actually SLICES the cache: the
    largest power of two strictly below ``max_seq_len``, floored at 64 (the
    ladder's first rung; the full-width program runs unsliced). 0 when the
    cache is too narrow to ever slice."""
    if max_seq_len <= 64:
        return 0
    bound = 64
    while bound * 2 < max_seq_len:
        bound *= 2
    return bound


def plan_serving_memory(
    config: ModelConfig,
    max_batch: int,
    max_seq_len: int,
    *,
    quantized_weights: bool = False,
    long_prefill: bool = True,
    workspace_bytes: int = 1 << 30,
    prefill_batch: int = 0,
    prefill_bucket: int = 0,
    prefill_streams: int = 1,
    prefix_pool_entries: int = 0,
    prefix_pool_width: int = 0,
    speculation_tokens: int = 0,
    kv_layout: str = "dense",
    page_size: int = 64,
    kv_pages: int = 0,
    page_fraction: float = 0.0,
    host_kv_fraction: float = 0.0,
    adapter_pool_rows: int = 0,
    adapter_rank: int = 0,
    grammar_slots: int = 0,
    grammar_states: int = 0,
    grammar_exceptions: int = 65536,
    migrate_staging: bool = False,
    weight_load_staging: int = 0,
    durable_max_bytes: int = 0,
) -> ServingMemoryPlan:
    """Account a ServingEngine's HBM from the actual pytree shapes.

    ``long_prefill``: include the local cache(s) the chunked-prefill /
    ring path holds while a max-length prompt streams in (engine._long_step
    allocates one at the pow2 width covering the prompt, here bounded by
    ``max_seq_len``); ``prefill_streams`` of them may be live at once under
    the fused scheduler. ``prefill_batch``/``prefill_bucket``: shape of the
    admission local cache (prefill_batch rows × the largest bucket width)
    that a fused iteration holds alongside the decode chunk's kv_bound
    slice — 0 omits the term (pre-overlap accounting).
    ``prefix_pool_entries``/``prefix_pool_width``: shape of the prefix
    KV pool (serving/prefix_cache.py) — 0 omits the term (cache off).
    ``speculation_tokens``: drafts per verify iteration (k) when
    self-speculative decoding is on — the verify dispatch holds up to
    ~5 [max_batch, k+1, vocab] fp32 buffers at the sampler's filtered
    peak (see the field note); 0 omits the term (speculation off).
    ``workspace_bytes``: flat allowance for activations, XLA scratch, and
    the collectives' staging buffers — 1GiB is empirically comfortable for
    8B-class decode at B≤96.
    ``kv_layout``: "paged" swaps the dense cache + kv_bound slice +
    long-prefill + prefix-pool terms for ONE page-pool term
    (serving/pagepool.py): ``kv_pages`` pages of ``page_size`` tokens, or
    ``pages_for_fraction(max_batch, max_seq_len, page_size,
    page_fraction)`` when kv_pages is 0.
    ``host_kv_fraction``: tiered-KV host arena pages relative to the
    device pool (``ceil(pages × fraction)``, same per-page bytes) — the
    ``host_spill_bytes`` term is HOST RAM, reported but excluded from the
    HBM total; 0 omits it (tier off, and always 0 under the dense layout).
    ``adapter_pool_rows``/``adapter_rank``: shape of the multi-LoRA device
    pool (serving/adapters.py) — 0 omits the term (no adapters).
    ``grammar_slots``/``grammar_states``/``grammar_exceptions``: shape of
    the constrained-decoding packed DFA pool (serving/constrain.py —
    bitmask + default-successor/exceptions planes) — grammar_slots 0
    omits the term (the shared zero/disabled contract).
    ``weight_load_staging``: measured (or estimated) host-RAM high-water
    mark of the streamed weight-load pipeline (models/streamload.py) —
    reported like host_spill_bytes, excluded from the HBM total; 0 omits
    it (eager load, or no checkpoint).
    ``durable_max_bytes``: configured on-disk cap of the durable session
    tier (serving/durable.py, §23) — disk, reported-only, excluded from
    every RAM/HBM total; 0 omits it (tier off or uncapped).
    """
    from langstream_tpu.models.quant import init_random_quantized_params
    from langstream_tpu.models.transformer import init_params, make_kv_cache

    adapter_bytes = 0
    if adapter_pool_rows > 0 and adapter_rank > 0:
        from langstream_tpu.serving.adapters import lora_pool_bytes

        adapter_bytes = lora_pool_bytes(config, adapter_pool_rows, adapter_rank)
    grammar_bytes = 0
    if grammar_slots > 0 and grammar_states > 0:
        from langstream_tpu.serving.constrain import grammar_pool_bytes

        grammar_bytes = grammar_pool_bytes(
            grammar_slots, grammar_states, config.vocab_size,
            grammar_exceptions,
        )

    paged = kv_layout == "paged"
    if paged:
        from langstream_tpu.models.transformer import make_page_pool
        from langstream_tpu.serving.pagepool import (
            pages_for_fraction,
            table_len_for,
        )

        num_pages = kv_pages or pages_for_fraction(
            max_batch, max_seq_len, page_size, page_fraction
        )
        pool_shape = jax.eval_shape(
            lambda: make_page_pool(config, num_pages, page_size)
        )
        pool_bytes = _tree_bytes(pool_shape)
        host_spill_bytes = 0
        if host_kv_fraction > 0:
            import math

            host_spill_bytes = (
                math.ceil(num_pages * host_kv_fraction)
                * (pool_bytes // max(1, num_pages))
            )
        # disaggregated serving (§18): one in-flight KV migration stages a
        # request's worst-case page set in host RAM on BOTH ends (sender
        # snapshot fetch, receiver frame buffer + decode) — transient, but
        # a plan that ignored it would bless hosts with no headroom for
        # the transfer the role topology exists to make. HOST RAM, like
        # host_spill_bytes; excluded from the HBM total.
        migrate_staging_bytes = 0
        if migrate_staging:
            migrate_staging_bytes = (
                table_len_for(max_seq_len, page_size)
                * (pool_bytes // max(1, num_pages))
            )
        fused_shape = (
            jax.eval_shape(
                lambda: make_kv_cache(
                    config, prefill_batch, min(prefill_bucket, max_seq_len)
                )
            )
            if prefill_batch > 0 and prefill_bucket > 0
            else None
        )
        key = jax.ShapeDtypeStruct((2,), jax.numpy.uint32)
        if quantized_weights:
            params_shape = jax.eval_shape(
                lambda k: init_random_quantized_params(config, k), key
            )
        else:
            params_shape = jax.eval_shape(lambda k: init_params(config, k), key)
        return ServingMemoryPlan(
            weights_bytes=_tree_bytes(params_shape),
            cache_bytes=0,
            long_cache_bytes=0,  # paged segments write straight into pages
            workspace_bytes=workspace_bytes,
            # the scan reads and writes the pool where it lies: the step's
            # program holds nothing of a layer's size beside the pool
            scan_buffer_bytes=0,
            bound_slice_bytes=0,  # the table IS the bound — no slice/splice
            fused_prefill_bytes=_tree_bytes(fused_shape) if fused_shape else 0,
            prefix_pool_bytes=0,  # aliasing shares the one pool
            page_pool_bytes=pool_bytes,
            host_spill_bytes=host_spill_bytes,
            migrate_staging_bytes=migrate_staging_bytes,
            weight_load_staging_bytes=max(0, int(weight_load_staging)),
            durable_disk_bytes=max(0, int(durable_max_bytes)),
            verify_chunk_bytes=(
                5 * max_batch * (speculation_tokens + 1) * config.vocab_size * 4
                if speculation_tokens > 0
                else 0
            ),
            adapter_pool_bytes=adapter_bytes,
            grammar_pool_bytes=grammar_bytes,
        )

    key = jax.ShapeDtypeStruct((2,), jax.numpy.uint32)
    if quantized_weights:
        params_shape = jax.eval_shape(
            lambda k: init_random_quantized_params(config, k), key
        )
    else:
        params_shape = jax.eval_shape(lambda k: init_params(config, k), key)
    cache_shape = jax.eval_shape(
        lambda: make_kv_cache(config, max_batch, max_seq_len)
    )
    long_shape = (
        jax.eval_shape(lambda: make_kv_cache(config, 1, max_seq_len))
        if long_prefill
        else None
    )
    fused_shape = (
        jax.eval_shape(
            lambda: make_kv_cache(
                config, prefill_batch, min(prefill_bucket, max_seq_len)
            )
        )
        if prefill_batch > 0 and prefill_bucket > 0
        else None
    )
    prefix_shape = (
        jax.eval_shape(
            lambda: make_kv_cache(
                config, prefix_pool_entries, min(prefix_pool_width, max_seq_len)
            )
        )
        if prefix_pool_entries > 0 and prefix_pool_width > 0
        else None
    )
    cache_bytes = _tree_bytes(cache_shape)
    sliced = largest_sliced_bound(max_seq_len)
    return ServingMemoryPlan(
        weights_bytes=_tree_bytes(params_shape),
        cache_bytes=cache_bytes,
        long_cache_bytes=(
            _tree_bytes(long_shape) * max(1, prefill_streams)
            if long_shape
            else 0
        ),
        workspace_bytes=workspace_bytes,
        # 2 layer slices (read + updated copy) live inside the chunk scan
        scan_buffer_bytes=2 * cache_bytes // max(config.n_layers, 1),
        # the widest chunk that still slices copies `sliced` of the cache's
        # max_seq_len columns out and back alongside the full cache — for
        # non-pow2 widths that is MORE than cache/2 (T=1536 → 2/3; T=1025 →
        # ~all of it), which the old cache//2 shortcut hid
        bound_slice_bytes=cache_bytes * sliced // max_seq_len if sliced else 0,
        fused_prefill_bytes=_tree_bytes(fused_shape) if fused_shape else 0,
        prefix_pool_bytes=_tree_bytes(prefix_shape) if prefix_shape else 0,
        # ~5 live [B, k+1, V] fp32 buffers at the sampler's filtered peak
        # (see field note)
        verify_chunk_bytes=(
            5 * max_batch * (speculation_tokens + 1) * config.vocab_size * 4
            if speculation_tokens > 0
            else 0
        ),
        adapter_pool_bytes=adapter_bytes,
        grammar_pool_bytes=grammar_bytes,
        weight_load_staging_bytes=max(0, int(weight_load_staging)),
    )


def max_context_single_chip(
    config: ModelConfig,
    max_batch: int,
    hbm_bytes: int,
    *,
    quantized_weights: bool = True,
    ceiling: int = 1 << 20,
) -> int:
    """Largest power-of-two max_seq_len (≥1k) the HBM budget serves, or 0.

    This is the number the llama-3.1 128k preset must be honest about: NTK
    scaling makes 128k *positions* work, but one chip serves only what the
    cache arithmetic allows — shard (tp/seq) for the rest.
    """
    best = 0
    width = 1024
    while width <= min(ceiling, config.max_seq_len):
        plan = plan_serving_memory(
            config, max_batch, width, quantized_weights=quantized_weights
        )
        if not plan.fits(hbm_bytes):
            break
        best = width
        width *= 2
    return best

"""Serving HBM accounting: what a (model, batch, context) configuration
actually costs on a chip, BEFORE allocating it.

The reference never has to answer this question — its serving is delegated
to remote providers (OpenAICompletionService.java etc.), so context length
is someone else's capacity problem. Here the model lives in local HBM, and
the honest ceiling for long-context serving is arithmetic, not marketing:
weights + the page pool + the admission group's local cache + XLA workspace
must fit. ``plan_serving_memory`` computes the terms from the real param/cache
pytree shapes (``jax.eval_shape`` — nothing is allocated), and
``max_context_single_chip`` inverts the plan to the largest power-of-two
context a given HBM budget serves.

Used by the capacity docs/tests and when sizing a cell's ``kv-pages``
(benchmark/README.md); the engine logs the plan at startup so an
over-committed config fails loudly with numbers instead of an opaque
RESOURCE_EXHAUSTED mid-request.
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
    workspace_bytes: int  # XLA scratch / activation headroom estimate
    # the page pool (serving/pagepool.py): ONE [L, P, Hkv, page_size, D]
    # device pool holds every slot's KV, the aliased prefixes and the
    # chunked-prefill segments (they write straight into the slot's pages).
    # The layer scan addresses it by (layer, page) and forms no per-layer
    # entry, so a decode chunk holds nothing of a layer's size beside it.
    # A model with an indexer keeps its indexer's key a token in the same
    # pages (a third leaf, [L, P, page_size, index_key_width]): this term
    # counts it, since it is `make_page_pool`'s whole tree; a model that
    # keeps a latent in place of K and V has one row a token there
    # ([L, P, 1, page_size, latent_key_width]) and is counted the same way:
    # a token is priced at ``latent_key_width x 2 B`` a layer, plus the
    # indexer's key only where the model has an indexer (a one-leaf pool).
    # Sized by pages_for_fraction: every slot's max_seq_len plus the
    # prefix-cache-fraction alias headroom.
    page_pool_bytes: int = 0
    # a model with recurrent layers keeps, beside the pages of its
    # full-attention layers, one ROW of recurrent state a slot (the delta
    # rule's float32 state and the short convolution's tail, over its
    # linear layers): max_batch rows, whatever the sequences' lengths
    recurrent_state_bytes: int = 0
    # a model with window layers keeps THEIR pages in a group of its own
    # (pagepool.WindowPageGroup): a row holds a ring of the last
    # ``sliding_window`` tokens and the dispatch in flight, whatever its
    # length, so the group is max_batch rings and not max_batch contexts.
    # ``page_pool_bytes`` is then the full layers' group alone
    window_pool_bytes: int = 0
    # fused-iteration peak: with overlapped prefill–decode scheduling the
    # admission local cache (prefill_batch rows × the largest bucket width)
    # is live WHILE a decode chunk runs.
    fused_prefill_bytes: int = 0
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
    # self-speculative verify chunk (engine._paged_verify_chunk): the
    # multi-token
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
            + self.workspace_bytes
            + self.fused_prefill_bytes
            + self.page_pool_bytes
            + self.window_pool_bytes
            + self.recurrent_state_bytes
            + self.verify_chunk_bytes
            + self.adapter_pool_bytes
            + self.grammar_pool_bytes
        )

    def fits(self, hbm_bytes: int) -> bool:
        return self.total_bytes <= hbm_bytes

    def per_chip_bytes(self, devices: int) -> int:
        """First-order per-chip share on a sharded mesh: the plan's trees
        are GLOBAL, and the big terms (weights on model×expert, the page
        pool on model when the kv heads divide) shard across the mesh while
        the workspace allowance replicates per chip. Dividing everything
        except the workspace by the device count is the right startup-log
        read."""
        d = max(1, int(devices))
        return self.workspace_bytes + (self.total_bytes - self.workspace_bytes) // d

    def _agentic_summary(self) -> str:
        gib = 1024**3
        parts = []
        if self.adapter_pool_bytes:
            parts.append(f"adapter-pool {self.adapter_pool_bytes / gib:.2f}GiB + ")
        if self.grammar_pool_bytes:
            parts.append(f"grammar-pool {self.grammar_pool_bytes / gib:.2f}GiB + ")
        if self.recurrent_state_bytes:
            parts.append(f"recurrent-state {self.recurrent_state_bytes / gib:.2f}GiB + ")
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
            + (
                f"window-pool {self.window_pool_bytes / gib:.2f}GiB + "
                if self.window_pool_bytes else ""
            )
            + f"fused-prefill {self.fused_prefill_bytes / gib:.2f}GiB + "
            f"verify-chunk {self.verify_chunk_bytes / gib:.2f}GiB + "
            f"{self._agentic_summary()}"
            f"workspace {self.workspace_bytes / gib:.2f}GiB = "
            f"{self.total_bytes / gib:.2f}GiB{host}"
        )


def plan_serving_memory(
    config: ModelConfig,
    max_batch: int,
    max_seq_len: int,
    *,
    quantized_weights: bool = False,
    workspace_bytes: int = 1 << 30,
    prefill_batch: int = 0,
    prefill_bucket: int = 0,
    speculation_tokens: int = 0,
    page_size: int = 64,
    kv_pages: int = 0,
    page_fraction: float = 0.0,
    window_in_flight: int = 0,
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

    ``prefill_batch``/``prefill_bucket``: shape of the admission local
    cache (prefill_batch rows × the largest bucket width) that a fused
    iteration holds alongside the decode chunk — 0 omits the term.
    ``speculation_tokens``: drafts per verify iteration (k) when
    self-speculative decoding is on — the verify dispatch holds up to
    ~5 [max_batch, k+1, vocab] fp32 buffers at the sampler's filtered
    peak (see the field note); 0 omits the term (speculation off).
    ``workspace_bytes``: flat allowance for activations, XLA scratch, and
    the collectives' staging buffers — 1GiB is empirically comfortable for
    8B-class decode at B≤96.
    The KV state is ONE page-pool term (serving/pagepool.py): ``kv_pages``
    pages of ``page_size`` tokens, or ``pages_for_fraction(max_batch,
    max_seq_len, page_size, page_fraction)`` when kv_pages is 0.
    A model with window layers has a second term, their group:
    ``pagepool.window_group_pages``, ``max_batch`` rings of the window and
    ``window_in_flight`` positions (the widest prefill segment or decode
    chunk: what one dispatch writes a row).
    ``host_kv_fraction``: tiered-KV host arena pages relative to the
    device pool (``ceil(pages × fraction)``, same per-page bytes) — the
    ``host_spill_bytes`` term is HOST RAM, reported but excluded from the
    HBM total; 0 omits it (tier off).
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
    from langstream_tpu.models.transformer import (
        init_params,
        make_kv_cache,
        make_page_pool,
    )

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

    from langstream_tpu.serving.pagepool import (
        pages_for_fraction,
        table_len_for,
        window_group_pages,
    )

    num_pages = kv_pages or pages_for_fraction(
        max_batch, max_seq_len, page_size, page_fraction
    )
    window_pages = window_group_pages(
        config, max_batch, max_seq_len, page_size, window_in_flight
    )[0]
    pool_shape = jax.eval_shape(
        lambda: make_page_pool(
            config, num_pages, page_size, state_rows=max_batch, window_pages=window_pages
        )
    )
    state_bytes = _tree_bytes(pool_shape.pop("rec", None))
    window_bytes = _tree_bytes(pool_shape.pop("win", None))
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
    if quantized_weights and config.layer_pattern:
        from langstream_tpu.models.quant import quantize_params

        params_shape = jax.eval_shape(
            lambda k: quantize_params(init_params(config, k), config), key
        )
    elif quantized_weights:
        params_shape = jax.eval_shape(
            lambda k: init_random_quantized_params(config, k), key
        )
    else:
        params_shape = jax.eval_shape(lambda k: init_params(config, k), key)
    return ServingMemoryPlan(
        weights_bytes=_tree_bytes(params_shape),
        workspace_bytes=workspace_bytes,
        fused_prefill_bytes=_tree_bytes(fused_shape) if fused_shape else 0,
        page_pool_bytes=pool_bytes,
        recurrent_state_bytes=state_bytes,
        window_pool_bytes=window_bytes,
        host_spill_bytes=host_spill_bytes,
        migrate_staging_bytes=migrate_staging_bytes,
        weight_load_staging_bytes=max(0, int(weight_load_staging)),
        durable_disk_bytes=max(0, int(durable_max_bytes)),
        # ~5 live [B, k+1, V] fp32 buffers at the sampler's filtered peak
        # (see field note)
        verify_chunk_bytes=(
            5 * max_batch * (speculation_tokens + 1) * config.vocab_size * 4
            if speculation_tokens > 0
            else 0
        ),
        adapter_pool_bytes=adapter_bytes,
        grammar_pool_bytes=grammar_bytes,
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
    pool arithmetic allows (every slot's full context in pages) — shard
    (tp/seq) for the rest.
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

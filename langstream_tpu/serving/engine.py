"""Continuous-batching serving engine.

The device loop owns the TPU (SURVEY §3.2 note: "the continuous batcher owns
the device; the poll loop feeds it"): requests enter a thread-safe queue, the
engine thread admits them into free KV-cache slots (prefill, bucketed padding),
then every iteration runs ONE fused decode+sample step for ALL active slots.
Tokens stream back per-slot through callbacks; finished slots free immediately
and new requests take their place — no generation waits for the longest one.

Replaces the reference's OrderedAsyncBatchExecutor slot (SURVEY §2.1) as the
batching scheduler, and the remote-API call in ChatCompletionsStep (§3.3) as
the compute. Streaming callbacks preserve the StreamingChunksConsumer timing:
first token → first chunk, before the source record commits.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import logging
import math
import operator
import os
import queue
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from langstream_tpu.compile_account import register as register_compile_account
from langstream_tpu.models.configs import PAGE_LEAVES, GenerationOptions, ModelConfig
from langstream_tpu.models.transformer import (
    MOE_COUNTS,
    moe_count_names,
    LaunchReads,
    insert_copies_pages,
    join_rec,
    make_kv_cache,
    paged_block_step_inplace,
    paged_decode_step_inplace,
    paged_insert_cache,
    paged_prefill_segment_inplace,
    paged_verify_step_inplace,
    prefill,
    segment_copies_pages,
    split_rec,
)
from langstream_tpu.parallel import spmd_serving as wire
from langstream_tpu.serving.faultinject import FaultInjector
from langstream_tpu.serving.observability import (
    Dispatch,
    EngineObservability,
    emit_dispatch_span,
    emit_request_spans,
    load_score,
)
from langstream_tpu.serving.pagepool import migration_refused, refuse_for_state
from langstream_tpu.serving.sampling import block_choice, sample, speculative_verify
from langstream_tpu.serving.speculation import NGramIndex
from langstream_tpu.serving.startup import StartupTrace, process_stats
from langstream_tpu.serving.tenancy import (
    DEFAULT_TENANT,
    BrownoutController,
    TenantQueue,
    TenantRegistry,
    TenantShareExceeded,
    TenantSpec,
    effective_max_new_tokens,
)

log = logging.getLogger(__name__)


# Where compiled programs persist when neither JAX_COMPILATION_CACHE_DIR nor
# the ``compile-cache-dir`` knob names a place: fixed and inside the checkout
# (git-ignored). The path is part of the cache key, so it never carries a
# temp name, a pid or a time.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def enable_persistent_compile_cache(cache_dir: Optional[str] = None) -> Optional[str]:
    """Turn on JAX's persistent compilation cache and return its directory:
    every XLA executable compiled by this process is serialized there, and
    a LATER process compiling the same program deserializes instead of
    recompiling. This is the fleet's fast-cold-start lever — a scale-up
    replica pointed at a warm cache dir (shared volume / persistent disk)
    skips the warmup ladder's compile wall and is serving in seconds
    (docs/SERVING.md §13).

    ONE rule for the directory: ``JAX_COMPILATION_CACHE_DIR``, when set, is
    what JAX already reads at import — no directory is set in code and the
    ``compile-cache-dir`` knob (``cache_dir``) does not override it; else
    the knob; else ``DEFAULT_COMPILE_CACHE_DIR`` — except on the CPU
    backend, where only the variable or the knob turns the cache on (None
    is returned otherwise): an XLA:CPU executable is specific to the CPU
    features of the host that compiled it, and XLA warns of SIGILL on
    stderr, at length, every time it loads one.

    Thresholds are forced to cache-everything in all three cases: the
    engine's small host-side helper programs (row resets, chain scatters)
    compile fast but there are MANY of them, and the default
    min-compile-time filter would skip exactly the long tail that makes a
    cold warmup slow. Idempotent; safe to call before any engine is built."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    # every caller is about to compile: the process's compile account hears
    # of it from here on (docs/SERVING.md §12, "Start-up")
    register_compile_account()
    before = jax.config.jax_compilation_cache_dir
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if cache_dir is None and jax.default_backend() != "cpu":
            cache_dir = DEFAULT_COMPILE_CACHE_DIR
        if cache_dir is not None:
            jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.config.jax_compilation_cache_dir != before:
        # the cache singleton latches its enabled/dir decision on first
        # use — reset so a dir configured AFTER jax already compiled
        # something (tests, multi-engine processes) still takes effect
        cc.reset_cache()
    return jax.config.jax_compilation_cache_dir


class ShedError(RuntimeError):
    """Admission rejected by load shedding (full queue, hopeless deadline,
    or a draining engine). ``retry_after_s`` is the engine's estimate of
    when capacity frees — callers surface it as HTTP 429 Retry-After."""

    def __init__(self, reason: str, retry_after_s: float = 1.0) -> None:
        super().__init__(reason)
        self.retry_after_s = retry_after_s


class DeadlineExceededError(TimeoutError):
    """The request's deadline / max-queue-wait expired while it was still
    queued — nothing was generated, the caller should NOT retry blindly."""


class LogitsNaNError(RuntimeError):
    """The sampling NaN guard tripped for this request's slot: its logits
    went non-finite (poisoned KV row or device fault). The slot was
    quarantined and its KV rows zeroed; other slots were untouched."""


class EngineWedgedError(RuntimeError):
    """A per-iteration device wait exceeded the SPMD watchdog bound
    (``spmd-watchdog-s``): a dispatch hung past the deadline, which on a
    multi-host slice would otherwise hang every pod of the replica. Raised
    out of the iteration so the loop supervisor escalates to a coordinated
    OP_RECOVER instead of the slice wedging silently (docs/SERVING.md
    §20). A plain Exception: the recovery path IS the handler."""


@dataclass
class GenerationRequest:
    prompt_tokens: list[int]
    options: GenerationOptions
    # called from the engine thread with each new token id (stream path)
    on_token: Optional[Callable[[int], None]] = None
    # called from the engine thread once, with the final GenerationResult —
    # lets async callers await completion WITHOUT parking a thread on
    # result() (the executor-thread-per-request pattern capped agent
    # fan-out at the thread-pool size)
    on_done: Optional[Callable[["GenerationResult"], None]] = None
    submitted_at: float = field(default_factory=time.monotonic)
    # distributed-tracing correlation id (the gateway/agent ``ls-trace-id``
    # header): the engine's request-lifecycle spans join this trace, so a
    # chat request's gateway→agent→engine path stitches on /traces
    trace_id: Optional[str] = None
    _done: threading.Event = field(default_factory=threading.Event)
    _result: Optional["GenerationResult"] = None
    _cancelled: threading.Event = field(default_factory=threading.Event)
    # engine-installed teardown hook, run EXACTLY ONCE inside _finish
    # BEFORE the waiter wakes (adapter/grammar refcount release — the one
    # place every completion path, including queued deaths and crash
    # recovery, funnels through)
    _finalize: Optional[Callable[[], None]] = None
    # compiled grammar (serving/constrain.TokenDFA), attached at submit()
    # when options.response_format is set
    _dfa: Optional[Any] = None
    # the host-mirrored DFA state AFTER the latest delivered token —
    # written on the engine thread strictly BEFORE on_token fires, so a
    # callback reading it inside on_token sees the state matching that
    # token. This is what rides the fleet wire's tokens frames: a
    # survivor resumes a constrained stream mid-derivation from it
    # (options.grammar_resume_state) instead of refusing (§18)
    dfa_state: Optional[int] = None
    # adapter/grammar pool rows + initial DFA state once resolved at
    # admission (idempotence marker for the page-deferral retry path):
    # (adapter_row, grammar_row, dfa_state0)
    _agentic_rows: Optional[tuple[int, int, int]] = None

    def cancel(self) -> None:
        """Request cancellation from ANY thread. The engine honors it at
        the next chunk boundary: an active slot frees (partial tokens are
        returned with finish_reason="cancelled"), a queued request resolves
        when the admission sweep reaches it. Idempotent; a no-op once the
        request already finished."""
        self._cancelled.set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def deadline_at(self) -> Optional[float]:
        """Absolute monotonic deadline, or None when the request has none."""
        if self.options.deadline_s is None:
            return None
        return self.submitted_at + self.options.deadline_s

    def result(self, timeout: Optional[float] = None) -> "GenerationResult":
        if not self._done.wait(timeout):
            raise TimeoutError("generation did not complete in time")
        assert self._result is not None
        if self._result.error is not None:
            raise self._result.error
        return self._result

    def _finish(self, result: "GenerationResult") -> None:
        if self._done.is_set():
            return  # first resolution wins (sweep vs admission pop races)
        if self._finalize is not None:
            fin, self._finalize = self._finalize, None
            try:
                fin()
            except Exception:  # noqa: BLE001 — teardown must not eat the result
                log.exception("request finalize hook failed")
        self._result = result
        self._done.set()
        if self.on_done is not None:
            try:
                self.on_done(result)
            except Exception:  # noqa: BLE001 — callback must not kill the loop
                log.exception("on_done callback failed")


@dataclass
class GenerationResult:
    tokens: list[int]
    # stop | length | cancelled | deadline | error — cancelled/deadline
    # carry the tokens generated so far (error is None: partial output is
    # valid for a stream the client walked away from or timed out)
    finish_reason: str
    prompt_tokens: int
    ttft_s: float
    total_s: float
    error: Optional[BaseException] = None
    # A model that fills blocks (docs/SERVING.md "A model that fills
    # blocks"; None for every other): the denoise step of its block that
    # fixed each of ``tokens``, and the rest of the last block, which the
    # engine finished and did not deliver (``max_new_tokens`` reached, or a
    # stop token, which stands first): (tokens, their steps). Tokens and
    # labels are enough to rebuild every pass that made them.
    fix_steps: Optional[list[int]] = None
    block_rest: Optional[tuple[list[int], list[int]]] = None


@dataclass
class _Slot:
    request: Optional[GenerationRequest] = None
    position: int = 0  # next write position (= prompt len + generated so far)
    generated: list[int] = field(default_factory=list)
    started_at: float = 0.0
    first_token_at: float = 0.0
    # observability (docs/SERVING.md §12): lifecycle-span attributes and
    # the inter-token histogram's per-slot clock — host bookkeeping only
    last_token_at: float = 0.0
    path: str = "cold"  # cold | warm | long | ring (admission route)
    prefill_chunks: int = 0
    decode_iters: int = 0
    verify_iters: int = 0
    # `seq` of the dispatch that prefilled this request (its span)
    group_seq: int = 0
    # (behind, device, land) seconds of that dispatch, copied when its first
    # token landed: `engine.prefill`'s stages (observability.emit_request_spans)
    stages: Optional[tuple[float, float, float]] = None
    # decode steps dispatched for THIS request and not processed yet: the
    # device's position leads ``position`` by as many (kv_tokens_read)
    ahead: int = 0
    # A model that fills blocks: ``position`` is the start of the block the
    # row is at; ``block`` its tokens as the host has seen them fixed (None:
    # open) and ``block_steps`` the denoise step that fixed each (-1: the
    # prompt's tail, never open); ``fix_steps`` the steps of ``generated``;
    # ``block_rest`` the (token, step) pairs of a clean block not delivered
    # yet (`_deliver_token` moves one to ``fix_steps`` a token); and when the
    # admission's prefill landed, where `engine.prefill` ends
    block: list = field(default_factory=list)
    block_steps: list = field(default_factory=list)
    fix_steps: list = field(default_factory=list)
    block_rest: list = field(default_factory=list)
    prefill_landed_at: float = 0.0

    @property
    def active(self) -> bool:
        return self.request is not None

    def reset_obs(self, path: str, chunks: int, group_seq: int = 0) -> None:
        self.last_token_at = 0.0
        self.path = path
        self.prefill_chunks = chunks
        self.decode_iters = 0
        self.verify_iters = 0
        self.group_seq = group_seq
        self.stages = None
        self.ahead = 0


def _dfa_mask(dfa, g, state):
    """Per-slot grammar mask for ONE sampling step: the PACKED legality
    bitmask row gathered by (grammar row, current state) — [B, W] uint32,
    1 bit per token, expanded to bool inside sampling's mask fold
    (serving/constrain.py, sampling._expand_allowed). ``dfa`` is the
    registry's 4-plane pool (bits, defaults, exc_key, exc_next)."""
    return dfa[0][g, state]  # [B, ceil(V/32)] uint32


def _dfa_advance(dfa, g, tokens, state, vocab_size):
    """Advance each slot's DFA state past its sampled token ON DEVICE:
    the state's default successor unless the sorted per-row exceptions
    array holds the composite key ``state · V + token`` (a searchsorted
    probe — constrain.py packs every legal-but-non-modal transition
    there, so legal tokens advance EXACTLY as the dense table did). The
    NaN sentinel (-1) clamps to token 0; wherever that lands is harmless
    — the engine quarantines the slot on sight and re-seeds its state at
    the next admit, and free slots ride row 0 (defaults all 0, no
    exceptions: the unconstrained self-loop)."""
    _, defaults, exc_key, exc_next = dfa
    tclip = jnp.clip(tokens, 0, vocab_size - 1)
    # int32-safe: the registry enforces max_states · V < 2**31
    key = state * vocab_size + tclip  # [B]
    rows_k = exc_key[g]  # [B, E] sorted, sentinel-padded
    idx = jax.vmap(functools.partial(jnp.searchsorted, side="left"))(
        rows_k, key
    )
    idx = jnp.minimum(idx, rows_k.shape[-1] - 1)
    hit_key = jnp.take_along_axis(rows_k, idx[:, None], axis=1)[:, 0]
    hit_next = jnp.take_along_axis(exc_next[g], idx[:, None], axis=1)[:, 0]
    nxt = jnp.where(hit_key == key, hit_next, defaults[g, state])
    return jnp.maximum(nxt, 0).astype(state.dtype)


def _sample_step(logits, key, temp, top_k, top_p, dfa, g, dstate, vocab_size):
    """One decode step's sampling under the `sample` scope: split the key,
    mask by each slot's grammar state when there is one, sample, advance the
    state past the sampled token. Returns (tokens, key, dstate)."""
    with jax.named_scope("sample"):
        key, sub = jax.random.split(key)
        if dfa is None:
            return sample(logits, sub, temp, top_k, top_p), key, dstate
        tokens = sample(
            logits, sub, temp, top_k, top_p, _dfa_mask(dfa, g, dstate)
        )
        return tokens, key, _dfa_advance(dfa, g, tokens, dstate, vocab_size)


def _sample_verify(logits, drafts, key, temp, top_k, top_p, dfa, g, vstates):
    """A verify iteration's accept/reject under the `sample` scope, each
    draft position masked by its own grammar state when there is one.
    Returns (emitted tokens [B, k+1], accepted count [B], key)."""
    with jax.named_scope("sample"):
        key, sub = jax.random.split(key)
        allowed = None
        if dfa is not None:
            allowed = dfa[0][g[:, None], vstates]  # [B, K+1, W] packed uint32
        out, accept = speculative_verify(
            logits, drafts, sub, temp, top_k, top_p, allowed
        )
    return out, accept, key


def _sample_first(logits, key, temps, top_ks, top_ps, dfa, g, state0, vocab_size):
    """An admission's first-token sample under the `sample` scope; with a
    grammar the token is masked by each row's INITIAL state (``state0``: 0
    fresh, the carried state on a mid-derivation resume, §18). Returns
    (first tokens, key, each row's advanced state or None)."""
    with jax.named_scope("sample"):
        key, sub = jax.random.split(key)
        if dfa is None:
            return sample(logits, sub, temps, top_ks, top_ps), key, None
        s0 = state0 if state0 is not None else jnp.zeros_like(g)
        first = sample(logits, sub, temps, top_ks, top_ps, _dfa_mask(dfa, g, s0))
        return first, key, _dfa_advance(dfa, g, first, s0, vocab_size)


@functools.partial(
    jax.jit,
    donate_argnames=(
        "tokens_dev", "positions_dev", "temp_dev", "top_k_dev", "top_p_dev"
    ),
)
def _chain_scatter(
    tokens_dev, positions_dev, temp_dev, top_k_dev, top_p_dev,
    idx, first, position, temperature, top_k, top_p,
):
    """All five decode-chain scatters for ONE slot in a single dispatch.
    ``idx`` is traced, so this is one compiled program for every slot (the
    previous five eager per-slot `.at[idx].set` ops were five dispatches
    AND compiled per slot index); out-of-bounds ``idx`` drops
    every write, which is what the warmup dispatches."""
    return (
        tokens_dev.at[idx].set(first[0], mode="drop"),
        positions_dev.at[idx].set(position, mode="drop"),
        temp_dev.at[idx].set(temperature, mode="drop"),
        top_k_dev.at[idx].set(top_k, mode="drop"),
        top_p_dev.at[idx].set(top_p, mode="drop"),
    )


@functools.partial(
    jax.jit, static_argnames=("steps", "config", "page_size"),
    donate_argnames=("pool",),
)
def _paged_decode_chunk(
    params, tokens, positions, pool, table, key, temp, top_k, top_p, steps,
    config, page_size, lora=None, arows=None, dfa=None, g=None, dstate=None,
):
    """``steps`` fused decode+sample iterations against the page pool in
    ONE dispatch (lax.scan): every step would otherwise pay a host dispatch
    and a fetch, and the engine additionally pipelines — chunk k+1 is
    dispatched from chunk k's DEVICE outputs before chunk k's tokens are
    fetched to the host. Each slot reads exactly its mapped pages, so this
    is ONE compiled program for every sequence-length mix. Adapter rows
    and grammar rows are DATA ([B] int32 gathers), so base + N adapters +
    constrained slots mixed in one batch is STILL that one program — the
    ISSUE-10 acceptance invariant."""

    def body(carry, _):
        tokens, positions, pool, key, dstate = carry
        logits, pool, moe = paged_decode_step_inplace(
            params, tokens, positions, pool, table, config, page_size,
            lora=lora, adapter_rows=arows, moe_counts=True,
        )
        next_tokens, key, dstate = _sample_step(
            logits, key, temp, top_k, top_p, dfa, g, dstate, config.vocab_size
        )
        return (next_tokens, positions + 1, pool, key, dstate), (
            next_tokens, moe if config.is_moe else None,
        )

    (tokens, positions, pool, key, dstate), (chunk, moe) = lax.scan(
        body, (tokens, positions, pool, key, dstate), None, length=steps
    )
    # a dense model's zeros stay out of the step scan: one constant
    moe = moe.sum(0) if config.is_moe else jnp.zeros(len(MOE_COUNTS), jnp.int32)
    return chunk, tokens, positions, pool, key, dstate, moe


# What a block pass reports a row, beside the block's S tokens: the denoise
# step of the pass (BLOCK_COMMIT: the row had nothing open, the pass was its
# block's commit) and how many open positions stood over the threshold. A
# position the pass did not fix reads BLOCK_UNFIXED (-1 is `sample`'s NaN
# sentinel, `block_choice`'s too).
BLOCK_COMMIT = -1
BLOCK_UNFIXED = -2
# What `engine.block_chunk` counts beside ``passes`` and ``active_rows``, and
# `stats()` totals as ``block-<name>`` (docs/SERVING.md §12). A row is LIVE
# in a pass while its request holds the slot; the passes the device ran for
# a row after its request's last block was clean (the chunk in flight cannot
# know) are ``idle_row_passes``.
BLOCK_COUNTERS = (
    "passes", "row_passes", "idle_row_passes", "denoise_row_passes",
    "commit_row_passes", "tokens_fixed", "tokens_delivered",
    "fixed_over_threshold", "kv_tokens_read", "kv_rows_written",
)


@functools.partial(
    jax.jit, static_argnames=("passes", "config", "page_size"),
    donate_argnames=("pool",),
)
def _paged_block_chunk(
    params, block, starts, pool, table, key, temp, top_k, top_p, passes,
    config, page_size,
):
    """``passes`` fused PASSES of a model that fills blocks, against the page
    pool in ONE dispatch (lax.scan), pipelined from device outputs like the
    decode chunk. A row's state is its block (``block["tokens"]`` [B, S],
    the mask id where ``block["open"]`` [B, S]), the block's denoise step
    (``block["step"]`` [B]) and its start (``starts`` [B]). A pass runs every
    row's block through `paged_block_step_inplace`; a row with open positions
    fixes some by confidence (`block_choice`), a row with none has just
    committed its block (its K/V, written by this pass over the clean block,
    are what later blocks read) and moves on: start + S, all S open, step 0.
    One program and one shape whatever the rows are at. Returns the
    per-pass report [passes, B, S + 2] (the tokens fixed, BLOCK_UNFIXED
    elsewhere; the step or BLOCK_COMMIT; the count over the threshold), the
    state, the pool, the key and the summed expert counts."""
    s, mask_id = config.block_length, config.mask_token_id

    def body(carry, _):
        tokens, is_open, step, starts, pool, key = carry
        logits, pool, moe = paged_block_step_inplace(
            params, tokens, starts, pool, table, config, page_size, moe_counts=True,
        )
        with jax.named_scope("block_choice"):
            key, sub = jax.random.split(key)
            picked, fixed, over = block_choice(
                logits, sub, temp, top_k, top_p, is_open, step, mask_id,
                config.confidence_threshold, config.block_schedule,
            )
            commit = ~is_open.any(axis=-1)
            report = jnp.concatenate([
                jnp.where(fixed, picked, BLOCK_UNFIXED),
                jnp.where(commit, BLOCK_COMMIT, step)[:, None], over[:, None],
            ], axis=1)
            tokens = jnp.where(fixed, picked, tokens)
            tokens = jnp.where(commit[:, None], mask_id, tokens)
            is_open = (is_open & ~fixed) | commit[:, None]
            step = jnp.where(commit, 0, step + 1)
            starts = jnp.where(commit, starts + s, starts)
        return (tokens, is_open, step, starts, pool, key), (
            report, moe if config.is_moe else None,
        )

    carry = (block["tokens"], block["open"], block["step"], starts, pool, key)
    (tokens, is_open, step, starts, pool, key), (reports, moe) = lax.scan(
        body, carry, None, length=passes
    )
    moe = moe.sum(0) if config.is_moe else jnp.zeros(len(MOE_COUNTS), jnp.int32)
    return (
        reports, {"tokens": tokens, "open": is_open, "step": step}, starts,
        pool, key, moe,
    )


@functools.partial(
    jax.jit, static_argnames=("config", "page_size"), donate_argnames=("pool",)
)
def _paged_verify_chunk(
    params, tokens, positions, pool, table, key, temp, top_k, top_p, drafts,
    config, page_size, lora=None, arows=None, dfa=None, g=None, vstates=None,
):
    """ONE self-speculative iteration in ONE dispatch: run the multi-token
    verify forward over [current token ++ drafts] (k+1 positions per slot),
    accept the longest valid draft prefix (greedy: argmax match; sampled:
    rejection sampling — serving/sampling.py speculative_verify), and
    advance the device decode chain by accepted+1. Decode is HBM-bound —
    every step reads the full weights to emit one token per slot — so
    scoring k+1 positions per weight read is the amortization lever. Like
    the decode chunk a SINGLE compiled program (k is fixed engine-wide).
    Rejected tokens need no KV rewind: positions advance only past accepted
    tokens, and stale draft page columns are overwritten before any causal
    mask can reach them. Draft positions are masked with the host-shipped
    per-position DFA states (``vstates`` [B, k+1]: the state after consuming
    drafts 0..j-1 — serving/constrain.py verify_states) so speculative
    verify stays token-exact under constraints. The fetched result is ONE
    packed [B, k+2] array (emitted tokens ++ accepted count)."""
    inputs = jnp.concatenate([tokens[:, None], drafts], axis=1)  # [B, k+1]
    logits, pool, moe = paged_verify_step_inplace(
        params, inputs, positions, pool, table, config, page_size,
        lora=lora, adapter_rows=arows, moe_counts=True,
    )
    out, accept, key = _sample_verify(
        logits, drafts, key, temp, top_k, top_p, dfa, g, vstates
    )
    tokens = jnp.take_along_axis(out, accept[:, None], axis=1)[:, 0]
    positions = positions + accept + 1
    dstate = None
    if dfa is not None:
        pre = jnp.take_along_axis(vstates, accept[:, None], axis=1)[:, 0]
        dstate = _dfa_advance(dfa, g, tokens, pre, config.vocab_size)
    packed = jnp.concatenate([out, accept[:, None]], axis=1)  # [B, k+2]
    return packed, tokens, positions, pool, key, dstate, moe


@functools.partial(
    jax.jit, static_argnames=("config", "page_size"), donate_argnames=("pool",)
)
def _paged_segment_and_sample(
    params, tokens, offsets, seg_lengths, pool, table, key, temp, top_k, top_p,
    config, page_size, lora=None, arows=None, dfa=None, g=None,
    state_dev=None, state_slot=None, state0=None, state_rows=None,
):
    """One chunked/suffix prefill segment straight into the slot's pages +
    a sample of its last-token logits: aliased prefix pages are already
    visible through the table, so a warm admission is ONE dispatch (plus at
    most one copy-on-write page copy). Sampling every segment (vs only the
    last) keeps one compiled shape per width; non-final samples are simply
    never fetched. With a grammar, the first generated token is masked by
    the request's INITIAL DFA state ``state0`` ([1] int32 — 0 for a fresh
    derivation, the carried state for a mid-derivation fleet resume, §18)
    and the advanced state scatters into ``state_dev`` at ``state_slot``
    (out-of-bounds on non-final segments — dropped), so the decode chain
    the engine dispatches NEXT iteration already carries the right state
    without a host round trip."""
    # a model that holds its experts (window layers' parallel block, or the
    # sequential block with ``experts_held``) counts its segments' expert
    # assignments too (`moe_count_names`): each has a fetch of its own to
    # bring the counts (`_segment_step`), and the program returns them as a
    # fifth result
    logits, pool, *moe = paged_prefill_segment_inplace(
        params, tokens, offsets, seg_lengths, pool, table, config, page_size,
        lora=lora, adapter_rows=arows, state_rows=state_rows,
        moe_counts=config.holds_experts,
    )
    first, key, s1 = _sample_first(
        logits, key, temp, top_k, top_p, dfa, g, state0, config.vocab_size
    )
    if s1 is not None:
        state_dev = state_dev.at[state_slot].set(s1[0], mode="drop")
    return (first, pool, key, state_dev, *moe)


def _on_pages(fn, pool, *rest):
    """``fn`` over the pool's PAGE leaves: "k" and "v" (for a model that
    keeps a latent "lat" in their place) and, for a model with an indexer,
    its keys "ik" (a copied or a zeroed page is whole: a page that left its
    indexer keys behind would serve stale keys to the selection).
    Every leaf has its pages on axis 1. What lies beside them passes
    through: a recurrent state ("rec", a row a slot, no page axis), and a
    window group's pages ("win"), which are never copied or restored: the
    page indices here are the full group's, and every option that moves
    pages is refused for such a model (`_window_page_zero` scrubs them)."""
    return {**pool, **jax.tree.map(fn, _page_leaves(pool), *rest)}


def _page_leaves(pool) -> dict:
    """The pool's leaves that hold a token's state by page, by NAME
    (`ModelConfig.page_leaves` chooses among them): a leaf this list does not
    know is neither copied nor zeroed by accident."""
    return {name: pool[name] for name in PAGE_LEAVES if name in pool}


@functools.partial(jax.jit, donate_argnames=("pool",))
def _page_copy(pool, src, dst):
    """Copy ONE physical page (all layers/heads) — the copy-on-write a
    prefix alias needs when the cached prefix ends mid-page. Traced
    indices: one compiled program; an out-of-bounds ``dst`` drops (warmup).
    Axis 1 is the page axis for both the value arrays and the int8 scale
    arrays (page-pool layout [L, P, Hkv, ps(, D)])."""

    def put(a):
        row = lax.dynamic_index_in_dim(a, src, 1, keepdims=False)
        return a.at[:, dst].set(row, mode="drop")

    return _on_pages(put, pool)


@functools.partial(jax.jit, donate_argnames=("pool",))
def _page_zero(pool, pages):
    """Zero physical pages (quarantine: a NaN-poisoned slot's pages must
    not re-enter the free list carrying garbage that a later partial-page
    publish could alias). ``pages`` is a fixed-width buffer padded with
    out-of-bounds entries (dropped) — one compiled program for any count."""

    def zero(a):
        return a.at[:, pages].set(jnp.zeros((), a.dtype), mode="drop")

    return _on_pages(zero, pool)


@functools.partial(jax.jit, donate_argnames=("pool",))
def _window_page_zero(pool, pages):
    """`_page_zero` for the window layers' group: a quarantined slot's ring
    goes back to ITS free list, and a row that takes a poisoned page reads
    the columns it has not written yet under a zero weight (0 x NaN)."""
    zero = lambda a: a.at[:, pages].set(jnp.zeros((), a.dtype), mode="drop")  # noqa: E731
    return {**pool, "win": jax.tree.map(zero, pool["win"])}


@jax.jit
def _page_snapshot(pool, src):
    """Slice ONE physical page (all layers/heads) out of the pool into
    fresh device buffers — the spill path's decoupling trick: the engine
    thread dispatches this (async, one traced-index program) and hands the
    RESULT arrays to the spill worker, so the worker's device→host copy
    can never race a later donating dispatch that rewrites (or a free that
    recycles) the page. NOT donated: the pool stays live."""

    def take(a):
        return lax.dynamic_index_in_dim(a, src, 1, keepdims=False)

    return jax.tree.map(take, _page_leaves(pool))


@functools.partial(jax.jit, donate_argnames=("pool",))
def _page_restore(pool, block, dst):
    """Upload ONE host-arena page back into physical page ``dst`` — the
    hibernation restore. Traced index: ONE compiled program regardless of
    destination; an out-of-bounds ``dst`` drops (warmup). int8 pools
    upload int8 + scales — half the bytes of bf16, same as the pool."""

    def put(a, b):
        return a.at[:, dst].set(b.astype(a.dtype), mode="drop")

    return _on_pages(put, pool, block)


def admit_rungs(prefill_batch: int) -> tuple[int, ...]:
    """The row counts an admission group may compile at: one row, and
    ``prefill_batch``. A group dispatches at the smallest rung that holds its
    real rows, so a lone prompt computes one row of its bucket and not
    ``prefill_batch``; the row count is a compiled shape of ``admit_group``
    and nothing else. A constant of the code: the warm-up compiles every
    (rung, width), and each compiled shape costs a replica 1.4–1.9 s of
    every warm start and 11–14 s of a cold one (v5e, PERF.md §6, PR 33),
    which is why the powers of two between the two rungs are not here:
    groups of one prompt are what open-loop arrivals and a drain's freed
    slots make, and they carried nearly all of the padding (chat's groups
    held 1.08 real rows of 8 before the rung of one)."""
    return (1, prefill_batch) if prefill_batch > 1 else (1,)


def admission_groups(
    by_width: dict[int, list], rungs: tuple[int, ...], widen: bool = True,
) -> list[tuple[int, list]]:
    """The groups ONE iteration's cold admissions leave as, narrowest first:
    ``by_width`` is each bucket width's ``(slot, request)`` rows in the order
    the queue gave them up (slots rise with it, so a row's first item is its
    age); a width's rows are cut into groups of at most ``rungs[-1]``, and a
    group of ``n`` rows dispatches at ``rung(n)``, the smallest rung that
    holds it, with ``rung(n) - n`` padding rows computed whatever they hold.

    ``widen``: rows of a NARROWER width move into those free rows, the widest
    group filled first. A narrow group gives up rows only where that takes it
    down a rung (to nothing, the dispatch gone, or to the rows a lower rung
    holds exactly), its oldest first, the narrow group with the fewest rows
    first so that one is emptied before another is shrunk. No group's rung or
    width grows, so no new (rung, width) is dispatched and neither the
    iteration's computed tokens nor its dispatches can rise. False (a model
    whose expert layer hands capacity out in row order, padding included:
    `ServingEngine.__init__`) leaves every width its own groups."""
    batch = rungs[-1]

    def rung(n: int) -> int:
        return next(r for r in rungs if r >= n) if n > 0 else 0

    groups = [
        (width, rows[start : start + batch])
        for width, rows in sorted(by_width.items())
        for start in range(0, len(rows), batch)
    ]
    if not widen:
        return groups
    for at in range(len(groups) - 1, 0, -1):
        width, rows = groups[at]
        free = rung(len(rows)) - len(rows)
        narrower = [g for g in reversed(groups[:at]) if g[0] < width]
        # the fewest rows first (the sort is stable: of two as small, the wider)
        for _, narrow in sorted(narrower, key=lambda g: len(g[1])):
            keep = rung(len(narrow) - free)
            if free and keep < rung(len(narrow)):
                moved = len(narrow) - keep
                rows.extend(narrow[:moved])
                del narrow[:moved]
                free -= moved
        rows.sort(key=operator.itemgetter(0))
    return [g for g in groups if g[1]]


def _make_paged_admit_group(mesh=None):
    """Factory for the FUSED admission step: local-cache zeros + batched
    prefill + first-token sample + page copies + every decode-chain
    scatter in ONE dispatch (the unfused path made ~14 host→device ops;
    fused + packed uploads ≈ 4). The prefill is the model-level ``prefill``
    over a local cache (the token-exactness reference); its rows' pages
    are then copied into each slot's mapped pages (``paged_insert_cache``:
    page copies on the chip, a scatter for the int8 pool, a window model
    and under a mesh). Padding rows carry all-out-of-bounds tables, so
    their writes drop. Under a mesh the transient local cache is
    constrained so the page scatter stays shard-local."""
    @functools.partial(
        jax.jit,
        static_argnames=("config", "page_size"),
        donate_argnames=(
            "pool", "tokens_dev", "positions_dev", "temp_dev",
            "top_k_dev", "top_p_dev",
        ),
    )
    def admit_group(
        params, pool, tokens_dev, positions_dev, temp_dev, top_k_dev,
        top_p_dev, key, tokens, meta, slots, tables, config, page_size,
        lora=None, arows=None, dfa=None, g_rows=None, state_dev=None,
        g_state0=None,
    ):
        # tokens [P, W] int32; meta [4, P] f32; tables [P, Tp] int32
        lengths = meta[0].astype(jnp.int32)
        temps = meta[1]
        top_ks = meta[2].astype(jnp.int32)
        top_ps = meta[3]
        n, width = tokens.shape
        local_cache = make_kv_cache(config, n, width)  # traced zeros: free
        if mesh is not None:
            from langstream_tpu.parallel.sharding import (
                constrain_serving_local_cache,
            )

            local_cache = constrain_serving_local_cache(
                local_cache, config.n_kv_heads // config.kv_head_pack, mesh
            )
        kv, rec = split_rec(pool)
        logits, local_cache, moe = prefill(
            # a recurrent model's state rides with the local cache: a row of
            # it is its slot's, written from the zero state run over the
            # prompt's TRUE length
            params, tokens, lengths, join_rec(local_cache, rec), config,
            lora=lora, adapter_rows=arows, moe_counts=True,
            # a padding row's slot is out of bounds: none of it is real
            real_lengths=jnp.where(slots < tokens_dev.shape[0], lengths, 0),
            rec_rows=slots,
        )
        local_cache, rec = split_rec(local_cache)
        pool = join_rec(kv, rec)
        first, key, s1 = _sample_first(
            logits, key, temps, top_ks, top_ps, dfa, g_rows, g_state0,
            config.vocab_size,
        )
        if s1 is not None:
            state_dev = state_dev.at[slots].set(s1, mode="drop")
        pool = paged_insert_cache(pool, local_cache, tables, page_size, config)
        tokens_dev = tokens_dev.at[slots].set(first, mode="drop")
        positions_dev = positions_dev.at[slots].set(lengths, mode="drop")
        temp_dev = temp_dev.at[slots].set(temps, mode="drop")
        top_k_dev = top_k_dev.at[slots].set(top_ks, mode="drop")
        top_p_dev = top_p_dev.at[slots].set(top_ps, mode="drop")
        return (
            first, pool, tokens_dev, positions_dev, temp_dev, top_k_dev,
            top_p_dev, key, state_dev, moe,
        )

    return admit_group


@functools.partial(
    jax.jit,
    static_argnames=("config", "page_size"),
    donate_argnames=(
        "pool", "block", "positions_dev", "temp_dev", "top_k_dev", "top_p_dev",
    ),
)
def _block_admit_group(
    params, pool, block, positions_dev, temp_dev, top_k_dev, top_p_dev,
    tokens, meta, first_block, slots, tables, config, page_size,
):
    """The admission of a model that fills blocks, in ONE dispatch: the
    prefill of each prompt's WHOLE blocks (``meta[0]``: their length, a
    multiple of the block length, possibly 0) under the block-causal mask
    into a local cache and from there into the row's pages, and the row's
    state: its first block (``first_block`` [P, S]: the prompt's tail, then
    the mask id; ``meta[4]`` the tail's length, the rest open) at the whole
    blocks' end, step 0. It yields NO token: the first tokens come from the
    first block's passes (the unread logits, and the head with them, are not
    computed). A bucket's padding starts on a block boundary, so no real
    position sees it. Returns the whole lengths as the landed marker."""
    lengths = meta[0].astype(jnp.int32)
    n, width = tokens.shape
    _, local_cache, moe = prefill(
        params, tokens, lengths, make_kv_cache(config, n, width), config,
        moe_counts=True,
        real_lengths=jnp.where(slots < positions_dev.shape[0], lengths, 0),
    )
    pool = paged_insert_cache(pool, local_cache, tables, page_size, config)
    tail = meta[4].astype(jnp.int32)
    is_open = jnp.arange(config.block_length)[None, :] >= tail[:, None]
    block = {
        "tokens": block["tokens"].at[slots].set(first_block, mode="drop"),
        "open": block["open"].at[slots].set(is_open, mode="drop"),
        "step": block["step"].at[slots].set(0, mode="drop"),
    }
    return (
        lengths, pool, block,
        positions_dev.at[slots].set(lengths, mode="drop"),
        temp_dev.at[slots].set(meta[1], mode="drop"),
        top_k_dev.at[slots].set(meta[2].astype(jnp.int32), mode="drop"),
        top_p_dev.at[slots].set(meta[3], mode="drop"),
        moe,
    )


# whether a profile is being recorded (annotations are no-ops otherwise); a
# jaxlib without the probe reads as always
_profiling = getattr(jax.profiler.TraceAnnotation, "is_enabled", lambda: True)

# how an iteration came to its launch (`ServingEngine._await_launch`)
LAUNCH_REASONS = ("at-once", "arrival", "deadline")


def _mono_ns(disp: Optional[Dispatch]) -> int:
    """A launch annotation's ``t_mono_ns``: the launch's own monotonic stamp
    (`Dispatch.start`) in ns, so a profile that holds the annotation maps
    its clock onto the spans' (0: no record was kept)."""
    return int(disp.start * 1e9) if disp is not None else 0


class _Fetch:
    """Handle for one deferred device→host token fetch. Created at dispatch
    time; the fetch thread fills ``_value`` in submission order. ``result``
    falls back to an inline ``device_get`` when no fetch thread is running
    (tests drive the loop by hand; engine drain after stop)."""

    __slots__ = ("array", "_fetcher", "_event", "_value", "seq", "ready_at",
                 "counts")

    def __init__(
        self, array, fetcher: "_TokenFetcher", seq: int = 0, counts=None
    ) -> None:
        self.array = array
        # the dispatch's MoE counts (a device int32[4]; None for a dense
        # model): they ride the tokens' transfer, so `get` leaves them
        # here as host values and no thread makes a fetch of their own
        self.counts = counts
        self._fetcher = fetcher
        self._event = threading.Event()
        self._value = None
        # the dispatch's number (0: untracked): `get` waits inside an
        # `engine.fetch` annotation that carries it, which is how a
        # profile's device executions are told which dispatch they were
        self.seq = seq
        # monotonic instant the bytes were on the host: the end of the
        # dispatch's span (the fetch thread is FIFO, so these are in
        # dispatch order)
        self.ready_at = 0.0

    def get(self):
        """Block until the array is on the host (fetch thread, or inline
        when none runs)."""
        with jax.profiler.TraceAnnotation("engine.fetch", seq=self.seq):
            value, self.counts = jax.device_get((self.array, self.counts))
        self.ready_at = time.monotonic()
        return np.asarray(value)

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout_s: Optional[float] = None):
        """``timeout_s`` bounds the wait (the leader's per-iteration SPMD
        watchdog — docs/SERVING.md §20): expiry raises EngineWedgedError,
        which the loop supervisor escalates to a coordinated OP_RECOVER.
        None (single-host default) keeps the unbounded wait."""
        if not self._event.is_set() and not self._fetcher.alive():
            return self.get()
        deadline = (
            time.monotonic() + timeout_s
            if timeout_s is not None and timeout_s > 0
            else None
        )
        poll = 0.5 if deadline is None else min(0.5, max(0.01, timeout_s / 8))
        while not self._event.wait(poll):
            if not self._fetcher.alive():
                # fetch thread went away before reaching this handle
                return self.get()
            if deadline is not None and time.monotonic() > deadline:
                raise EngineWedgedError(
                    f"device fetch exceeded the {timeout_s:.1f}s dispatch "
                    "bound (spmd-watchdog-s); escalating to coordinated "
                    "recovery"
                )
        if isinstance(self._value, BaseException):
            raise self._value
        return self._value


class _TokenFetcher:
    """Dedicated device→host fetch thread: the engine thread dispatches the
    next chunk while this thread blocks on the previous one's bytes, so the
    per-chunk token fetch hides behind compute at every chunk size. One
    FIFO queue + one worker keeps results strictly in submission (= chunk)
    order. (Built for a slow device link that is gone — ROADMAP D5.)"""

    def __init__(
        self,
        injector: Optional[FaultInjector] = None,
        obs: Optional[EngineObservability] = None,
        landed: Optional[Callable[[], None]] = None,
    ) -> None:
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None
        self._injector = injector
        self._obs = obs
        # called after each result is on the host: the engine thread may be
        # waiting for one (`ServingEngine._await_launch`)
        self._landed = landed

    def alive(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> None:
        if self.alive():
            return
        self._thread = threading.Thread(
            target=self._run, name="serving-fetch", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        if self._thread is not None:
            self._queue.put(None)
            self._thread.join(timeout=30)
            self._thread = None

    def submit(self, array, seq: int = 0, counts=None) -> _Fetch:
        handle = _Fetch(array, self, seq, counts)
        if self.alive():
            self._queue.put(handle)
        return handle

    def _run(self) -> None:
        while True:
            handle = self._queue.get()
            if handle is None:
                return
            try:
                if self._injector is not None:
                    self._injector.stall("fetch")
                t0 = time.monotonic()
                handle._value = handle.get()
                if self._obs is not None and self._obs.on:
                    # the fetch is a latency tail source — its
                    # distribution belongs on /metrics
                    self._obs.record("engine_fetch_s", time.monotonic() - t0)
            except BaseException as e:  # noqa: BLE001 — surface at result()
                handle._value = e
            handle._event.set()
            if self._landed is not None:
                self._landed()


class _Spill:
    """Handle for one in-flight entry spill (device pages → host arena).
    Created on the engine thread with the page SNAPSHOTS already
    dispatched (_page_snapshot — independent buffers, so the entry's
    device pages may be freed immediately after); the spill worker copies
    them into the arena slots and stamps checksums. ``cancelled`` is set
    by the engine (entry dropped/quarantined mid-spill) — the worker
    still completes its copy, and the completion drain frees the slots
    instead of attaching them. ``gen`` fences crash recovery: handles
    from before an engine restart are discarded at drain (the arena was
    reset; their slots are not ours to free)."""

    __slots__ = ("entry", "slots", "blocks", "gen", "cancelled", "error",
                 "event")

    def __init__(self, entry, slots: list, blocks: list, gen: int) -> None:
        self.entry = entry
        self.slots = slots
        self.blocks = blocks
        self.gen = gen
        self.cancelled = False
        self.error: Optional[BaseException] = None
        self.event = threading.Event()


class _SpillWorker:
    """Dedicated spill thread (the round-7 _TokenFetcher pattern): the
    engine thread only dispatches page snapshots and bookkeeping; the
    actual device→host transfer + arena write + checksum — the slow,
    bandwidth-bound part — happens here, strictly off the hot loop. One
    FIFO queue + one worker; completions flow back through ``done`` and
    are folded in by the engine at iteration top (_drain_spills)."""

    def __init__(
        self,
        tier: Any,
        done: "queue.SimpleQueue",
        obs: Optional[EngineObservability] = None,
    ) -> None:
        self._tier = tier
        self._done = done
        self._obs = obs
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None

    def alive(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> None:
        if self.alive():
            return
        self._thread = threading.Thread(
            target=self._run, name="serving-spill", daemon=True
        )
        self._thread.start()

    def stop(self, timeout: float = 30.0) -> bool:
        """Quiesce: handles queued before the sentinel complete their
        copies first, so after a True return no thread touches the arena
        (crash recovery resets it right after). False — with the thread
        left registered so ``alive()`` stays truthful — when the worker
        failed to drain within ``timeout`` (wedged device fetch): the
        caller must NOT reuse an arena this thread may still write into."""
        t = self._thread
        if t is None:
            return True
        self._queue.put(None)
        t.join(timeout=timeout)
        if t.is_alive():
            return False
        self._thread = None
        return True

    def submit(self, handle: _Spill) -> None:
        self._queue.put(handle)

    def _run(self) -> None:
        while True:
            handle = self._queue.get()
            if handle is None:
                return
            try:
                t0 = time.monotonic()
                for block, slot in zip(handle.blocks, handle.slots):
                    leaves = [
                        np.asarray(jax.device_get(leaf))
                        for leaf in jax.tree.leaves(block)
                    ]
                    self._tier.write(slot, leaves)
                if self._obs is not None and self._obs.on:
                    self._obs.record("engine_spill_s", time.monotonic() - t0)
            except BaseException as e:  # noqa: BLE001 — surfaced at drain
                handle.error = e
            handle.blocks = None  # release the snapshot device buffers
            self._done.put(handle)
            handle.event.set()


def _durable_empty_stats() -> dict:
    """Zeroed durable-tier stats keys (tier off) — the exporter sets its
    gauges unconditionally, so the keys must exist either way."""
    from langstream_tpu.serving.durable import DurableStore

    return DurableStore.empty_stats()


class _DurableWorker:
    """Dedicated checkpoint thread for the durable tier (docs/SERVING.md
    §23; the _SpillWorker pattern one tier down): the engine thread
    materializes immutable checkpoint jobs — raw page byte images + their
    spill-time checksums, copied OUT of the arena so a later drop/evict
    cannot race the write — and the fsync-heavy temp+rename disk write
    runs here, strictly off the hot loop. Failures are counted by the
    store and logged, never raised: a failed checkpoint leaves the
    session restorable from its owner, and crash-safety is the store's
    on-disk construction, not this thread's error handling."""

    def __init__(self, store: Any, obs: Optional[EngineObservability] = None):
        self._store = store
        self._obs = obs
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread: Optional[threading.Thread] = None

    def alive(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> None:
        if self.alive():
            return
        self._thread = threading.Thread(
            target=self._run, name="serving-durable", daemon=True
        )
        self._thread.start()

    def stop(self, timeout: float = 30.0) -> bool:
        t = self._thread
        if t is None:
            return True
        self._queue.put(None)
        t.join(timeout=timeout)
        if t.is_alive():
            return False
        self._thread = None
        return True

    def submit(self, job: dict) -> None:
        self._queue.put(job)

    def flush(self, timeout: float = 30.0) -> bool:
        """Barrier: True once every job enqueued BEFORE this call has
        been written (or failed). Hibernation flushes before it walks
        the index so no session is checkpointed twice."""
        if not self.alive():
            return True
        ev = threading.Event()
        self._queue.put(ev)
        return ev.wait(timeout)

    def _run(self) -> None:
        from langstream_tpu.serving.durable import DurableError

        while True:
            job = self._queue.get()
            if job is None:
                return
            if isinstance(job, threading.Event):
                job.set()
                continue
            t0 = time.monotonic()
            try:
                self._store.checkpoint(
                    job["digest"], job["length"], job["tokens"],
                    job["pages_raw"], job["checksums"],
                    job["page_size"], job["bytes_per_page"],
                )
                if self._obs is not None and self._obs.on:
                    self._obs.record(
                        "engine_durable_checkpoint_s", time.monotonic() - t0
                    )
            except DurableError as e:
                log.warning("durable checkpoint failed: %s", e)
            except BaseException:  # noqa: BLE001 — degrade one entry only
                log.exception("durable checkpoint crashed")


class ServingEngine:
    """One engine per model per agent replica; owns the device loop."""

    # default rows of the LARGEST admission group (admit_rungs has the
    # smaller shapes a group of fewer prompts dispatches at)
    PREFILL_BATCH = 8

    # lock discipline registry (analysis pass `locks`, docs/ANALYSIS.md):
    # every write to a guarded attribute outside `with self.<lock>:` is an
    # LSA101 finding. `__init__` and `*_locked` helpers are exempt by
    # convention.
    _GUARDED = {
        "_stats_lock": (
            "shed_total", "cancelled_total", "deadline_queue_total",
            "deadline_decode_total", "quarantined_slots_total",
            "nan_guard_total", "engine_restarts_total", "total_generated",
            "total_requests", "_busy_steps", "_queue_wait_ema_s",
            "_unfed_s", "_unfed_request_s", "_account_t0",
        ),
        "_waiting_lock": ("_waiting", "_open"),
    }

    def __init__(
        self,
        config: ModelConfig,
        params: Any,
        max_batch: int = 8,
        max_seq_len: Optional[int] = None,
        eos_token_id: Optional[int] = None,
        prefill_buckets: tuple[int, ...] = (32, 64, 128, 256, 512, 1024, 2048),
        rng_seed: int = 0,
        mesh: Optional[Any] = None,
        decode_chunk: int = 16,
        prefill_batch: Optional[int] = None,
        spmd: Optional[Any] = None,
        pipeline_depth: int = 1,
        precompile: Optional[bool] = None,
        prefill_token_budget: Optional[int] = None,
        max_prefill_streams: Optional[int] = None,
        page_size: int = 64,
        kv_pages: Optional[int] = None,
        host_kv_fraction: float = 0.0,
        spill: Any = "auto",
        spill_idle_s: float = 0.0,
        restore_stall_dump_s: float = 1.0,
        durable: Any = "auto",
        durable_dir: Optional[str] = None,
        durable_max_bytes: int = 0,
        durable_timeout_s: float = 5.0,
        prefix_cache: Any = False,
        prefix_cache_fraction: float = 0.25,
        prefix_cache_entries: Optional[int] = None,
        speculation: Any = False,
        speculation_tokens: int = 4,
        adapters: Optional[list] = None,
        adapter_pool_fraction: float = 0.1,
        adapter_rank: Optional[int] = None,
        adapter_pool_rows: Optional[int] = None,
        constrained_decoding: Any = "auto",
        grammar_slots: int = 64,
        grammar_states: int = 128,
        grammar_exceptions: int = 65536,
        grammar_tokenizer: Optional[Any] = None,
        queue_depth: Optional[int] = None,
        shed_policy: str = "block",
        tenants: Optional[list] = None,
        brownout: Any = "auto",
        brownout_enter_load: float = 2.0,
        brownout_exit_load: float = 1.0,
        brownout_dwell_s: float = 0.5,
        restart_backoff_s: float = 0.1,
        max_restarts: int = 5,
        fault_injector: Optional[FaultInjector] = None,
        migrate_staging: bool = False,
        weight_load_report: Optional[dict] = None,
        observability: bool = True,
        flight_iterations: int = 256,
        flight_dir: Optional[str] = None,
    ) -> None:
        """``mesh``: a jax Mesh with a "model" (and optionally "expert") axis.
        ``params`` must already be sharded over it (parallel.sharding);
        the KV cache is sharded to match (kv heads on "model") so every
        decode step partitions over ICI with XLA-inserted collectives —
        one psum per layer, the Megatron schedule."""
        # time to ready is counted from here (docs/SERVING.md §12, "Start-up")
        self._startup = StartupTrace()
        # what the model's kinds of state cannot be used with is refused
        # here, by the option's name (serving/pagepool STATE_KINDS)
        refuse_for_state(
            config, page_size, prefix_cache=prefix_cache,
            host_kv_fraction=host_kv_fraction, migrate_staging=migrate_staging,
            durable_dir=durable_dir, speculation=speculation, adapters=adapters,
            mesh=mesh, spmd=spmd, constrained_decoding=constrained_decoding,
        )
        if config.fills_blocks:
            constrained_decoding = "off"  # `auto`: on where it is supported
        if mesh is not None:
            # the Pallas kernels cannot be partitioned by GSPMD: they read
            # the mesh off the (static) config and shard_map themselves
            config = dataclasses.replace(config, kernel_mesh=mesh)
        self.config = config
        self.params = params
        self.mesh = mesh
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len or config.max_seq_len
        self.eos_token_id = eos_token_id
        self.prefill_buckets = tuple(
            b for b in prefill_buckets if b <= self.max_seq_len
        ) or (self.max_seq_len,)
        # bounded admission queue. ``shed_policy`` decides what a FULL queue
        # does to submit(): "block" (default) is the broker-poll-loop
        # backpressure contract; "reject" sheds with ShedError(retry-after)
        # so a front door (gateway/HTTP) degrades to fast 429s instead of
        # stacking blocked threads while clients time out anyway.
        if queue_depth is not None and int(queue_depth) <= 0:
            # the loop pops admissions from this queue, so depth 0 cannot
            # mean "no queueing" — reject loudly instead of silently
            # substituting the default
            raise ValueError(f"queue_depth must be >= 1, got {queue_depth}")
        # multi-tenant overload control (serving/tenancy.py, docs/SERVING.md
        # §19): per-tenant weights / slot caps / queue shares / token-rate
        # quotas, the per-tenant lifecycle counters, and the admission
        # queue itself — weighted deficit round-robin in prefill-token
        # units, so the fused iteration's budget and the free-slot pool
        # divide by weight. With no tenants configured every request lands
        # in the shared "default" tenant and the queue degrades to the
        # pre-tenancy FIFO exactly.
        tenant_specs = [
            t if isinstance(t, TenantSpec) else TenantSpec.from_dict(t)
            for t in (tenants or [])
        ]
        self._tenants = TenantRegistry(tenant_specs)
        self._queue: TenantQueue = TenantQueue(
            maxsize=(
                int(queue_depth) if queue_depth is not None else max_batch * 4
            ),
            registry=self._tenants,
            cost_fn=lambda r: float(
                self._bucket(len(getattr(r, "prompt_tokens", None) or ()))
            ),
            quantum=float(self.prefill_buckets[-1]),
        )
        # brownout controller (docs/SERVING.md §19): walks the declared
        # degradation ladder off the round-11 load score — spec shrink →
        # spec off → reject low priority → reject over-quota — each step
        # hysteresis-gated, counted, flight-dumped and fully reversed.
        brownout_off = str(brownout).lower() in ("off", "false", "0", "none")
        self._brownout = (
            None
            if brownout_off
            else BrownoutController(
                enter_load=float(brownout_enter_load),
                exit_load=float(brownout_exit_load),
                dwell_s=float(brownout_dwell_s),
            )
        )
        self.brownout_dumps_total = 0
        self._brownout_checked_at = 0.0
        if shed_policy not in ("block", "reject"):
            raise ValueError(
                f"unknown shed_policy {shed_policy!r}; supported: block, reject"
            )
        self.shed_policy = shed_policy
        self._slots = [_Slot() for _ in range(max_batch)]
        # KV state: ONE page-table-indexed device pool for decode,
        # prefill, verify and prefix reuse — one compiled program for every
        # sequence-length mix, prefix hits alias pages zero-copy. Legal
        # under multi-host SPMD (allocator events ride the leader→follower
        # wire — docs/SERVING.md §14) and under sharded meshes (the pool
        # shards its kv heads over "model").
        self.page_size = max(1, int(page_size))
        self._pagepool = None
        self._prefix_index = None
        # deferred admissions: popped from the queue but waiting for pool
        # pages (allocator exhaustion defers — it never corrupts); retried
        # ahead of the queue every iteration, swept like the queue
        self._page_deferred: list[GenerationRequest] = []
        # physical pages to zero on the next iteration (quarantine)
        self._pending_page_zero: list[int] = []
        self._pending_window_zero: list[int] = []  # the window group's
        # -- tiered KV: host-RAM spill + session hibernation (ROADMAP 3) -----
        # host-kv-fraction sizes a pinned host arena RELATIVE to the device
        # pool (2.0 = twice the pool's pages in host RAM; host RAM is ~10×
        # HBM per host, so large values are the point). 0 disables the tier.
        if str(spill).lower() not in ("auto", "on", "true", "1", "off",
                                      "false", "0"):
            raise ValueError(f"unknown spill {spill!r}; supported: auto, off")
        spill_off = str(spill).lower() in ("off", "false", "0")
        self.host_kv_fraction = max(0.0, float(host_kv_fraction))
        self.spill_idle_s = max(0.0, float(spill_idle_s))
        self._restore_stall_s = max(0.0, float(restore_stall_dump_s))
        spill_on = not spill_off and self.host_kv_fraction > 0
        if spmd is not None and spill_on:
            # spill/demote/restore decisions are leader-side host state
            # (arena free list, checksums, idle clocks) and the restore
            # upload is a device dispatch followers would need to replay —
            # neither rides the wire yet. Explicit, LOUD disable (the
            # round-14 adapters precedent): host-kv-fraction > 0 is an
            # explicit ask, so this is a WARNING, not a silent downgrade.
            log.warning(
                "tiered KV host spill is not on the SPMD wire yet; off on "
                "this multi-host replica (host-kv-fraction %.2f ignored)",
                self.host_kv_fraction,
            )
            spill_on = False
        self._spill_on = spill_on
        self._host_tier = None
        self._spill_worker: Optional[_SpillWorker] = None
        self._spill_done: "queue.SimpleQueue" = queue.SimpleQueue()
        self._spill_gen = 0
        # device-only entries awaiting hibernation, oldest first (engine
        # thread only); entries join at publish/restore time
        self._spill_candidates: deque = deque()
        # cumulative tier accounting (engine thread writes, stats() reads)
        self.spill_pages_total = 0
        self.spill_bytes_total = 0
        self.spill_failures_total = 0
        self.restore_pages_total = 0
        self.restore_bytes_total = 0
        self.restored_hits_total = 0
        self.restore_failures_total = 0
        self.recompute_fallbacks_total = 0
        # host-ms spent on spill/restore bookkeeping this iteration (flight
        # recorder phase_ms; reset at iteration top)
        self._spill_ms_iter = 0.0
        self._restore_ms_iter = 0.0
        # -- durable session tier: crash-safe KV checkpoints on disk
        # (docs/SERVING.md §23, ROADMAP 2b/3b). durable-dir names the
        # checkpoint directory (shared volume / object-store mount); the
        # tier checkpoints hibernated arenas there so sessions survive
        # replica death, drain and scale-to-zero, and a cold replica
        # rehydrates the index at boot (resurrection).
        if str(durable).lower() not in ("auto", "on", "true", "1", "off",
                                        "false", "0"):
            raise ValueError(
                f"unknown durable {durable!r}; supported: auto, off"
            )
        durable_off = str(durable).lower() in ("off", "false", "0")
        durable_ask = str(durable).lower() in ("on", "true", "1")
        self.durable_dir = str(durable_dir) if durable_dir else None
        self.durable_timeout_s = max(0.1, float(durable_timeout_s))
        self._durable_max_bytes = max(0, int(durable_max_bytes))
        durable_on = not durable_off and self.durable_dir is not None
        if spmd is not None and durable_on:
            # same wire gap as the host tier above: checkpoint/restore
            # decisions are leader-side host state and the restore upload
            # is a device dispatch followers would need to replay. LOUD
            # disable — durable-dir is an explicit ask.
            log.warning(
                "durable KV tier is not on the SPMD wire yet; off on this "
                "multi-host replica (durable-dir %s ignored)",
                self.durable_dir,
            )
            durable_on = False
        if durable_ask and not durable_on:
            log.warning(
                "durable: on requested but unavailable (needs durable-dir, "
                "single-host) — tier stays off"
            )
        self._durable_on = durable_on
        self._durable = None  # DurableStore, built with the pool below
        self._durable_worker: Optional[_DurableWorker] = None
        # admissions served by a durable-tier resurrection (the restore
        # split's third rung: device hit / host restore / durable restore)
        self.durable_restored_hits_total = 0
        # True while a durable restore is serving an admission — the
        # /healthz "restoring" readiness signal during resurrection
        self._durable_restoring = False
        # real prompt tokens of the prefill groups and segment streams
        # whose first tokens have landed, and the dispatch→ready seconds
        # they took (one sample each, the same one engine_prefill_group_s
        # records): the landed prefill throughput the router's
        # fetch-vs-prefill cost model consumes (prefill_tps_estimate)
        self._prefill_tokens_landed = 0
        self._prefill_landed_s = 0.0
        # -- dispatch spans (docs/SERVING.md §12): every tracked device
        # dispatch takes the next number; the MoE counts its program
        # returned wait on the device until its entry is processed
        self._dispatch_seq = 0
        self._moe_dev = None
        self._window_recycled = 0  # by the last segment's `window_advance`
        self.moe_routed_total = 0
        self.moe_dropped_total = 0
        # ready instant of the newest processed dispatch: with a span's
        # own stamps, device-side time = end - max(start, this); what is in
        # flight behind it started there (`_launch_deadline`)
        self._last_ready_t = 0.0
        # The device's unfed account (docs/SERVING.md §12): seconds since
        # `_account_t0` in which no dispatch was in flight, and the part of
        # them with a request open in the engine. A stretch is closed by the
        # launch that ends it; `_last_fetch` is the newest dispatch's fetch
        # (its `ready_at` set: every earlier one's is too, the fetch thread
        # is FIFO) and `_launch_unfetched` a launch that has none yet.
        self._unfed_s = 0.0
        self._unfed_request_s = 0.0
        self._account_t0 = time.monotonic()
        self._last_fetch: Optional[_Fetch] = None
        self._launch_unfetched = False
        # requests submitted and not yet seen finished by a launch, id() →
        # request: what "open" means to the account (observability on only)
        self._open: dict[int, GenerationRequest] = {}
        self._open_prune_at = 16
        # host seconds spent waiting for device results, cumulative: an
        # iteration's share is the difference across its process phase
        self._wait_s_iter = 0.0
        # -- KV-page migration (disaggregated serving, docs/SERVING.md §18):
        # commands from migration threads (HTTP handlers, the fleet
        # router's dispatch executors) executed at iteration top on the
        # engine thread — the pool/index are engine-thread-only, and the
        # command queue is how a snapshot/bind crosses into that domain
        # without a lock on the hot loop. Each command carries its own
        # reply queue; callers time out (deadline-bounded migrate) rather
        # than block forever on a dead engine.
        self._migrate_cmds: "queue.SimpleQueue" = queue.SimpleQueue()
        self.migrate_pages_out_total = 0
        self.migrate_bytes_out_total = 0
        self.migrate_pages_in_total = 0
        self.migrate_bytes_in_total = 0
        self.migrate_failures_total = 0
        self._paged_admit_group = _make_paged_admit_group(mesh)
        # kept: the deterministic crash-recovery rebuild derives the fresh
        # PRNG key from seed + recovery epoch, identically on every host
        self._rng_seed = int(rng_seed)
        self._key = jax.random.PRNGKey(rng_seed)
        self._stop = threading.Event()
        # set by the engine thread once its warm-up has ended, either way:
        # wait_ready() turns a failed warm-up into a failed start
        self._ready = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._dead: Optional[BaseException] = None
        # per-slot sampling params, DEVICE-resident: re-uploading them on
        # every chunk dispatch costs 3 host→device puts — they only change
        # on admit
        self._temp_dev = jnp.zeros(max_batch, jnp.float32)
        self._top_k_dev = jnp.zeros(max_batch, jnp.int32)
        self._top_p_dev = jnp.ones(max_batch, jnp.float32)
        # device-resident decode chain: last sampled token + next write
        # position per slot (kept on device so chunk k+1 can be dispatched
        # from chunk k's outputs without a host sync)
        self._tokens_dev = jnp.zeros(max_batch, jnp.int32)
        self._positions_dev = jnp.zeros(max_batch, jnp.int32)
        # a model that fills blocks: each row's block on the device (tokens,
        # which of them are open, the denoise step); ``_positions_dev`` is
        # then the block's start. Totals of what the block chunks did
        # (stats "block-*"; docs/SERVING.md §12)
        self._block_dev = self._fresh_block_state(max_batch, config)
        self._block_totals = dict.fromkeys(BLOCK_COUNTERS, 0)
        # slots freed since the last dispatch: their device temp must be
        # zeroed, else sample()'s batch-wide any_sample/any_filter predicates
        # keep paying the full-vocab sort for a slot that no longer exists
        self._freed_slots: list[int] = []
        # decode chunk size (tokens per dispatch per slot); clamped to
        # powers of two to bound recompiles
        self.decode_chunk = max(1, int(decode_chunk))
        # dispatch pipeline depth: how many decode chunks are in flight
        # (dispatched, unfetched) when the oldest lands. Depth 1: chunk k+1
        # is launched before chunk k ends (a margin ahead of its expected
        # end, or sooner when a request arrives: `_await_launch`), then
        # chunk k is fetched, so the fetch overlaps compute and the device
        # never runs dry; deeper pipelines delay completion discovery and
        # first-token fetches by a full chunk. (Default tuned for a device
        # link that is gone; not re-measured: ROADMAP D5.)
        self.pipeline_depth = max(1, int(pipeline_depth))
        # total steps of the currently in-flight (dispatched, unfetched)
        # chunks, summed over the pipeline
        self._inflight_steps = 0
        # rows of the LARGEST prefill dispatch: bigger = fewer serial
        # prefill calls under a burst. A group dispatches at the smallest
        # rung that holds it (a lone prompt at ONE row), at the price of one
        # compile per (rung, width) shape in the warm-up. An expert model
        # keeps the one shape: moe_ffn sizes every expert's capacity from
        # rows × width, padding included, and hands it out real rows first,
        # so padding rows buy the real ones their capacity and a smaller
        # group would drop assignments the full one keeps (ROADMAP S5 lifts
        # this)
        self.prefill_batch = int(prefill_batch or self.PREFILL_BATCH)
        self._admit_rungs = (
            (self.prefill_batch,) if config.is_moe
            else admit_rungs(self.prefill_batch)
        )
        # admission groups dispatched at each rung (stats "admit-group-rows")
        self._admit_group_rows = dict.fromkeys(self._admit_rungs, 0)
        # a narrow prompt rides a wider group's free row (`admission_groups`)
        # where padding costs a real token nothing: every layer but moe_ffn,
        # where a wider row's padding ahead of a later real row takes the
        # capacity that row's tokens would have had (S5 lifts this too)
        self._admit_widens = not config.is_moe or config.holds_experts
        # rows that rode a group wider than their own bucket (stats
        # "admit-rows-widened")
        self._admit_rows_widened = 0
        # fused prefill–decode scheduling: every iteration dispatches a
        # token-budgeted slice of pending prefill work (admission groups +
        # chunked-prefill segments) IMMEDIATELY followed by the decode chunk
        # — two back-to-back async dispatches, so a new arrival's first
        # segment rides the very next device dispatch instead of waiting out
        # whole-backlog prefill, and decode never stalls behind more than
        # one budget of prefill. The budget guarantees at least ONE unit of
        # progress (one admission group / one segment per active stream) per
        # iteration; beyond that, prefill work past the budget waits for the
        # next iteration so decode chunks keep interleaving.
        # Tokens of prefill work per fused iteration, sized off the
        # chunked-prefill segment width (= the largest prefill bucket): one
        # full-width segment or one admission group rides every iteration
        self.prefill_token_budget = max(
            1, int(prefill_token_budget or self.prefill_buckets[-1])
        )
        # concurrent chunked-prefill streams: two long prompts may
        # interleave their segments (each holds its own local cache —
        # serving/memory.py accounts the per-stream term)
        self.max_prefill_streams = max(1, int(max_prefill_streams or 2))
        # chunked prefill (long-context): prompts wider than the largest
        # bucket loop bucket-width segments straight into the reserved
        # slot's pages, budgeted segments per engine iteration so decode
        # keeps flowing in between. One state dict per stream, keyed by the
        # reserved slot index (the key also rides the SPMD wire).
        self._longs: dict[int, dict] = {}
        self._long_rr: int = -1  # round-robin cursor over stream slots
        self._long_queue: list[GenerationRequest] = []
        # bound the chunked-prefill backlog so submit()'s queue-full
        # backpressure engages for long prompts too (ADVICE r3)
        self._long_queue_cap = 8
        # one long request drained from the queue while the long backlog is
        # full waits HERE (engine thread only) until _long_queue frees —
        # reaching into queue.Queue internals to push it back broke the
        # maxsize/unfinished accounting (ADVICE r4)
        self._held_back: Optional[GenerationRequest] = None
        self._reserved: set[int] = set()
        # multi-host SPMD: the leader announces every device dispatch over
        # this channel before making it; followers replay via follower_loop
        # (parallel/spmd_serving.py). None = single-host, zero overhead.
        self._spmd = spmd
        # automatic prefix KV reuse (serving/pagepool.PrefixPageIndex): a
        # radix index over bucket-aligned token prefixes whose entries pin
        # pages of the one pool. Warm admissions alias the cached pages and
        # prefill ONLY the suffix (one segment at the reuse offset); every
        # completed prefill publishes its bucket-aligned prefix back
        # (refcounted, LRU-evicted). The index stays leader-only host state
        # under SPMD: only page ids ride the wire.
        enabled = (
            prefix_cache is True
            or str(prefix_cache).lower() in ("auto", "on", "true", "1")
        )
        # self-speculative decoding (prompt-lookup drafts + one-dispatch
        # multi-token verification): host-side per-slot n-gram indexes
        # propose up to ``speculation_tokens`` drafts per iteration; the
        # _paged_verify_chunk program scores them all in ONE weight read and
        # advances each slot by accepted+1 tokens. Legal under SPMD since
        # round 13: drafts ride OP_VERIFY (acceptance is computed on
        # device, identically on every host — only the proposals need the
        # wire; the n-gram index stays leader-only).
        spec_on = (
            speculation is True
            or str(speculation).lower() in ("auto", "on", "true", "1")
        )
        self._spec_enabled = spec_on
        # ONE static k engine-wide: every distinct k is a separate compiled
        # verify program, and a 15-23s mid-traffic compile costs more than
        # any per-request k tuning could win
        self.spec_tokens = max(1, int(speculation_tokens)) if spec_on else 0
        self._spec_index: dict[int, NGramIndex] = {}
        self.spec_dispatches_total = 0
        self.spec_draft_tokens_total = 0
        self.spec_accepted_tokens_total = 0
        self.spec_emitted_tokens_total = 0
        # slot-steps: one per (active slot, verify dispatch) pair — the
        # denominator that makes accepted-tokens-per-step a PER-SLOT number
        # in [1, k+1], comparable to plain decode's fixed 1.0
        self.spec_slot_steps_total = 0
        self.spec_draft_lookups_total = 0
        self.spec_draft_hits_total = 0
        # -- the agentic serving tier (ISSUE 10 / ROADMAP item 4) ------------
        # Multi-LoRA multiplexing: a fixed-shape device pool of stacked
        # low-rank factors (serving/adapters.py); every dispatch gathers
        # each slot's factors by its adapter ROW (host-uploaded [B] int32 —
        # data, not shape, so base + N adapters mix in ONE program).
        # Constrained decoding: response_format grammars compile to token
        # DFAs (serving/constrain.py); the PACKED pool — legality bitmask
        # [G+1, S, ceil(V/32)] uint32 + default-successor/exceptions
        # transition planes, ~32× smaller than the old dense [G+1, S, V]
        # int32 table — lives on device, per-slot grammar rows ride each
        # dispatch, and the DFA state advances ON DEVICE inside fused
        # chunks (searchsorted exceptions probe) while the host mirrors it
        # per delivered token (completion detection + the speculative
        # verify masks).
        adapters_cfg = list(adapters or [])
        constrain_on = (
            constrained_decoding is True
            or str(constrained_decoding).lower() in ("auto", "on", "true", "1")
        )
        if spmd is not None and (adapters_cfg or constrain_on):
            # neither the adapter rows nor the grammar pool ride the
            # leader→follower wire yet; a multi-host replica serves base
            # free-form only (docs/SERVING.md §15). `constrained-decoding:
            # auto` means "enable where supported", so the default degrades
            # SILENTLY here — only an explicit ask (adapters configured, or
            # constrained forced on) deserves the warning
            explicit = bool(adapters_cfg) or (
                constrained_decoding is True
                or str(constrained_decoding).lower() in ("on", "true", "1")
            )
            log.log(
                logging.WARNING if explicit else logging.INFO,
                "adapters/constrained decoding are not on the SPMD wire "
                "yet; off on this multi-host replica",
            )
            adapters_cfg = []
            constrain_on = False
        self._adapters = None
        self._constrain_reg = None
        # dispatch-facing + authoritative per-slot adapter rows: the pair
        # exists so the `adapter` fault site (host corruption drill) is
        # DETECTABLE — _adapter_integrity_check compares them before every
        # decode/verify dispatch, same design as the page tables' _owned
        self._adapter_rows = np.zeros(max_batch, np.int32)
        self._adapter_rows_auth = np.zeros(max_batch, np.int32)
        self._slot_adapter_name: dict[int, str] = {}
        self._g_rows = np.zeros(max_batch, np.int32)
        self._dfa_state_dev = None
        self._slot_dfa: dict[int, Any] = {}
        self._dfa_host_state: dict[int, int] = {}
        self.constrained_requests_total = 0
        self._constrain_host_ema_ms = 0.0
        self._agentic = bool(adapters_cfg) or constrain_on
        adapter_rows_cap, adapter_rank_eff = 0, 0
        if adapters_cfg:
            from langstream_tpu.serving.adapters import (
                AdapterRegistry,
                AdapterSpec,
                rows_for_fraction,
            )

            specs = [
                a if isinstance(a, AdapterSpec) else AdapterSpec.from_dict(a)
                for a in adapters_cfg
            ]
            adapter_rank_eff = int(
                adapter_rank or max((s.rank for s in specs), default=8)
            )
            weights_bytes = sum(
                leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(params)
            )
            adapter_rows_cap = (
                int(adapter_pool_rows)
                if adapter_pool_rows is not None
                else rows_for_fraction(
                    config, adapter_rank_eff, weights_bytes,
                    adapter_pool_fraction, n_registered=len(specs),
                )
            )
            self._adapters = AdapterRegistry(
                config, adapter_rows_cap, adapter_rank_eff
            )
            self._adapters.on_load_program = functools.partial(
                self._record_program, "adapter-load"
            )
            for s in specs:
                self._adapters.register(s)
        if constrain_on and int(grammar_slots) <= 0:
            # the zero/disabled contract (shared with grammar_pool_bytes,
            # which returns 0 here, and with the registry, which refuses
            # slots < 1): no pool rows means constrained decoding is OFF,
            # not a silently-coerced 1-slot pool
            log.info(
                "grammar-slots <= 0: constrained decoding disabled "
                "(grammar_pool_bytes contract)"
            )
            constrain_on = False
            self._agentic = bool(adapters_cfg)
        if constrain_on:
            from langstream_tpu.serving.constrain import GrammarRegistry

            tok = grammar_tokenizer
            if tok is None:
                from langstream_tpu.serving.tokenizer import ByteTokenizer

                tok = ByteTokenizer()
            self._constrain_reg = GrammarRegistry(
                tok, config.vocab_size, eos_token_id,
                slots=int(grammar_slots),
                max_states=max(2, int(grammar_states)),
                max_exceptions=max(1, int(grammar_exceptions)),
            )
            self._constrain_reg.on_load_program = functools.partial(
                self._record_program, "grammar-load"
            )
            self._dfa_state_dev = jnp.zeros(max_batch, jnp.int32)
        # pool sizing: every slot's max_seq_len in pages + the prefix-cache
        # fraction as ALIAS headroom (shared pages pinned by the prefix
        # index). prefix_cache_entries caps the INDEX (0 disables reuse);
        # the pages themselves live in the one pool either way.
        from langstream_tpu.serving.pagepool import pages_for_fraction

        self._page_fraction = prefix_cache_fraction if enabled else 0.0
        # the most positions one dispatch writes a row: what a window row's
        # ring holds beside its window (pagepool.window_ring_pages)
        self._window_in_flight = max(self.prefill_buckets[-1], self.decode_chunk)
        self._kv_pages = (
            int(kv_pages)
            if kv_pages is not None
            else pages_for_fraction(
                max_batch, self.max_seq_len, self.page_size,
                self._page_fraction,
            )
        )
        prefix_index_entries = 0
        if enabled:
            prefix_index_entries = (
                int(prefix_cache_entries)
                if prefix_cache_entries is not None
                else 512
            )
        # the device pool itself is allocated AFTER the memory plan below
        # has logged its arithmetic — an over-committed pool then OOMs with
        # the plan's numbers already on record instead of an unexplained
        # RESOURCE_EXHAUSTED
        # compile every device program up front (TPU default): a lazy
        # compile otherwise lands MID-TRAFFIC and stalls every active
        # stream. Off by default on CPU: tests build hundreds of engines.
        self._precompile = (
            precompile
            if precompile is not None
            else jax.default_backend() == "tpu"
        )
        # request-lifecycle / fault-recovery state ---------------------------
        # drain: finish everything already accepted (active slots + queue),
        # reject new submissions — the graceful half of shutdown; stop()
        # stays the hard half (fail whatever is left)
        self._draining = False
        # True while the engine thread is inside an iteration's admission
        # phase — the only window where a request can be popped from the
        # queue but not yet assigned to a slot; _quiesced() (drain, caller
        # thread) reads it
        self._mid_iteration = False
        # loop-restart supervisor: a crashed iteration quarantines the
        # in-flight slots, rebuilds device state, and restarts under
        # bounded exponential backoff instead of killing the process's
        # serving capacity. Since round 19 this covers SPMD replicas too
        # (docs/SERVING.md §20): the leader announces OP_RECOVER with a
        # fresh epoch instead of STOP, both sides run the identical
        # deterministic rebuild, and QUEUED admissions survive leader-side.
        self.restart_backoff_s = max(0.01, float(restart_backoff_s))
        self.max_restarts = max(0, int(max_restarts))
        self._last_crash_t = 0.0
        # SPMD slice resilience state (§20): the recovery epoch both sides
        # rebuild under (also the PRNG-reset input, so sampled streams stay
        # host-identical after recovery), the beacon's `recovering` window,
        # and the divergence-poll throttle clock
        self._spmd_epoch = 0
        self._recovering = False
        self._spmd_div_checked_at = 0.0
        self.spmd_recoveries_total = 0
        self.spmd_resyncs_total = 0
        self.spmd_watchdog_trips_total = 0
        # fault injection (serving/faultinject.py): explicit injector wins,
        # else env activation (LSTPU_FAULTS) for staging drills
        self._injector = (
            fault_injector if fault_injector is not None else FaultInjector.from_env()
        )
        # observability layer (serving/observability.py): streaming
        # histograms + request-lifecycle spans + the flight recorder.
        # ``observability: off`` is the measured-overhead escape hatch (and
        # the bench's off leg); everything hot-path gates on one flag.
        self._obs = EngineObservability(
            enabled=observability,
            flight_capacity=flight_iterations,
            flight_dir=flight_dir,
        )
        # checkpoint→device load accounting (models/streamload.py via the
        # tpu-serving holder; docs/SERVING.md §22): surfaced in stats()
        # and sampled ONCE into the cold-start histogram — engines build
        # once, so the fleet-wide distribution is the scale-up drill's
        # weight-load bound
        self._weight_load_report: dict[str, Any] = dict(weight_load_report or {})
        if self._weight_load_report.get("total-s"):
            self._obs.record(
                "engine_weight_load_s",
                float(self._weight_load_report["total-s"]),
            )
        # engine iterations, idle included (the flight recorder's clock)
        self._iterations_total = 0
        # What the engine thread waits on while a chunk is in flight and
        # nothing waits for an admission (`_await_launch`): `submit`, the
        # fetch thread (a result landed), a migration command and `stop`
        # set it. And how each iteration that launched came to its launch
        # (stats "launches", restarted by `reset_histograms`): at once (a
        # request or a segment waited already, or nothing was in flight),
        # on an arrival during the wait, at the wait's deadline; `late`
        # counts those of them that found live rows and every earlier
        # result already on the host, so the device had run dry.
        self._wake = threading.Event()
        self._launches = dict.fromkeys(LAUNCH_REASONS + ("late",), 0)
        # prefill segments dispatched, by how their new rows reach the pool
        # (stats "segment-writes", restarted with "launches"): by whole
        # pages, or by the scatter (`_count_segment_write`)
        self._segment_writes = {"pages": 0, "scatter": 0}
        # and the key blocks their attention walked, a KV head and a layer
        # call (stats "segment-key-blocks"; `_count_segment_key_blocks`): the
        # last segment's, for its span, and the sum since the same restart
        self._segment_key_blocks_last: dict = {}
        self._segment_key_blocks: dict = {}
        self._late_probe = False
        self._launched_late = False
        # dedicated device→host token fetch thread (started with the loop);
        # carries the injector for the fetch-stall site and the fetch
        # histogram
        self._fetcher = _TokenFetcher(self._injector, self._obs, self._wake.set)
        # EMA of observed queue wait (submit → admission), feeding the
        # hopeless-deadline shed decision and ShedError.retry_after_s
        self._queue_wait_ema_s = 0.0
        # shadow set of queued-but-unadmitted requests: queue.Queue cannot
        # be inspected without popping, so the per-iteration expiry sweep
        # walks this instead — a queued request whose deadline/cancellation
        # lands while every slot is busy resolves within one iteration, not
        # when a slot finally frees; its (already-resolved) queue entry is
        # skipped at pop time
        self._waiting: dict[int, GenerationRequest] = {}  # id() → request
        self._waiting_lock = threading.Lock()
        # lifecycle counters (stats() → genai gauges → Grafana). ONE lock
        # covers every counter mutation AND the whole stats() read, so a
        # stats() snapshot is internally consistent (shed totals cannot
        # disagree with queue depth read a microsecond later) — the
        # uncontended acquire is ~100ns, noise next to any dispatch
        self._stats_lock = threading.Lock()
        self.shed_total = 0
        self.cancelled_total = 0
        self.deadline_queue_total = 0
        self.deadline_decode_total = 0
        self.quarantined_slots_total = 0
        self.nan_guard_total = 0
        self.engine_restarts_total = 0
        # stats
        self.total_generated = 0
        self.total_requests = 0
        self._busy_steps = 0
        # distinct device-program signatures dispatched so far. Every tuple
        # here is a separate XLA compile (jit cache key = static args +
        # input shapes, which these capture exactly), so the counter going
        # UP after warmup means a 15-23s mid-traffic compile stall landed —
        # tests assert it stays flat (stats()["compiled_programs"]).
        self._programs: set[tuple] = set()
        # achieved-bandwidth gauge: EMA of measured decode step time + the
        # bytes-read model (the plan's weights + the live pages per step)
        # → HBM GB/s actually sustained, so the
        # gap to the chip's roofline is a shipped metric, not a PERF.md
        # footnote
        self._step_time_ema_s: float = 0.0
        # the newest sample the EMA took, unsmoothed (`_launch_deadline`)
        self._last_step_s: float = 0.0
        self._last_chunk_ready_t: float = 0.0
        self._plan = None
        # HBM accounting up front: an over-committed config should announce
        # its arithmetic here, not die in an opaque RESOURCE_EXHAUSTED
        # mid-request (serving/memory.py; divide by the mesh's device count
        # for the per-chip share when sharded)
        # bytes of the expert-sharded weight tensors (MoE w_gate/w_up/
        # w_down — the ONLY tensors param_specs puts on the "expert" axis),
        # measured from the real tree so the bandwidth gauge can divide
        # per-axis instead of flattening model×expert over ALL weights
        self._expert_weight_bytes = 0
        if config.is_moe:
            try:
                self._expert_weight_bytes = sum(
                    leaf.size * leaf.dtype.itemsize
                    for name in ("w_gate", "w_up", "w_down")
                    for leaf in jax.tree.leaves(params["layers"][name])
                )
            except Exception:  # noqa: BLE001 — gauge accounting only
                pass
        try:
            from langstream_tpu.serving.memory import plan_serving_memory

            quantized = any(
                leaf.dtype == jnp.int8 for leaf in jax.tree.leaves(params)
            )
            if self._spill_on and prefix_index_entries <= 0:
                # nothing to hibernate without the alias index: spilled
                # pages are only reachable through prefix entries. Decided
                # BEFORE the plan below so the startup log never claims
                # host arena RAM that is never allocated
                log.warning(
                    "tiered KV host spill needs the prefix index "
                    "(prefix-cache on, prefix-cache-entries > 0); off"
                )
                self._spill_on = False
            plan = plan_serving_memory(
                config, max_batch, self.max_seq_len, quantized_weights=quantized,
                prefill_batch=self.prefill_batch,
                prefill_bucket=self.prefill_buckets[-1],
                speculation_tokens=self.spec_tokens,
                page_size=self.page_size,
                kv_pages=self._kv_pages,
                page_fraction=self._page_fraction,
                window_in_flight=self._window_in_flight,
                host_kv_fraction=(
                    self.host_kv_fraction if self._spill_on else 0.0
                ),
                adapter_pool_rows=adapter_rows_cap,
                adapter_rank=adapter_rank_eff,
                grammar_slots=(
                    self._constrain_reg.slots if self._constrain_reg else 0
                ),
                grammar_states=(
                    self._constrain_reg.max_states if self._constrain_reg else 0
                ),
                grammar_exceptions=(
                    self._constrain_reg.max_exceptions
                    if self._constrain_reg
                    else 0
                ),
                # role-tagged replicas (§18): budget the host-RAM staging
                # one in-flight KV migration claims on this end
                migrate_staging=bool(migrate_staging),
                # streamed weight load (§22): the measured host staging
                # high-water mark, so the startup log's RSS story covers
                # the load phase the pod was health-probed through
                weight_load_staging=int(
                    self._weight_load_report.get("staging-peak-bytes", 0)
                ),
                # durable tier (§23): disk budget, reported-only
                durable_max_bytes=(
                    self._durable_max_bytes if self._durable_on else 0
                ),
            )
            self._plan = plan
            devices = mesh.devices.size if mesh is not None else 1
            log.info(
                "serving memory plan (%s, B=%d, T=%d, %d device%s): %s%s",
                config.name, max_batch, self.max_seq_len, devices,
                "s" if devices != 1 else "", plan.summary(),
                (
                    f" (~{plan.per_chip_bytes(devices) / 1024**3:.2f}GiB/chip)"
                    if devices > 1
                    else ""
                ),
            )
        except Exception:  # noqa: BLE001 — accounting must never block serving
            log.debug("serving memory plan unavailable", exc_info=True)
        from langstream_tpu.serving.pagepool import PagePool, PrefixPageIndex

        # allocated AFTER the memory plan logged its arithmetic: an
        # over-committed pool OOMs with the numbers on record
        self._pagepool = PagePool(
            config, self._kv_pages, self.page_size, max_batch,
            self.max_seq_len, window_in_flight=self._window_in_flight,
        )
        # what a launch reads, by the model's own rules over this pool's
        # geometry, and the sums of it `stats()` reports (`_count_reads`)
        self._reads = LaunchReads(
            config, self.page_size, self._pagepool.table_len * self.page_size
        )
        self._read_totals = self._reads.totals()
        if mesh is not None:
            # kv heads on "model" (replicated when they don't divide) —
            # every paged program then propagates the sharding from the
            # pool input
            from langstream_tpu.parallel.sharding import shard_page_pool

            self._pagepool.dev = shard_page_pool(self._pagepool.dev, mesh)
        if prefix_index_entries > 0:
            self._prefix_index = PrefixPageIndex(
                self.prefill_buckets, max_entries=prefix_index_entries
            )
        if self._spill_on:
            from langstream_tpu.serving.pagepool import HostPageTier

            host_pages = max(
                1, math.ceil(self._kv_pages * self.host_kv_fraction)
            )
            self._host_tier = HostPageTier(self._pagepool.dev, host_pages)
            self._prefix_index.host_tier = self._host_tier
            # hibernation capacity is governed by the arena alone: the
            # index's entry cap counts (and cap-evicts) only
            # DEVICE-resident entries, so idle hibernated sessions are
            # never dropped to make room for a publish
            self._spill_worker = _SpillWorker(
                self._host_tier, self._spill_done, self._obs
            )
            log.info(
                "tiered KV host arena: %d host pages (%.2f GiB RAM, "
                "%.2fx the device pool) — idle prefixes spill after "
                "%.1fs, LRU eviction demotes before dropping",
                host_pages, self._host_tier.bytes_total / 1024**3,
                self.host_kv_fraction, self.spill_idle_s,
            )
        if self._durable_on and self._prefix_index is not None:
            from langstream_tpu.serving.durable import DurableStore

            try:
                self._durable = DurableStore(
                    self.durable_dir,
                    max_bytes=self._durable_max_bytes,
                    injector=self._injector,
                )
                rehydrated = self._durable.rehydrate()
            except OSError:
                # an unwritable volume must not fail the boot — the
                # tier degrades to off, sessions fall back to the
                # host tier / re-prefill exactly as with durable: off
                log.exception(
                    "durable tier unavailable (%s) — off", self.durable_dir
                )
                self._durable = None
            if self._durable is not None:
                self._durable_worker = _DurableWorker(
                    self._durable, self._obs
                )
                log.info(
                    "durable KV tier: %s (%d checkpointed session "
                    "prefix(es) rehydrated%s) — hibernated arenas "
                    "checkpoint crash-safe; sessions survive replica "
                    "death and scale-to-zero",
                    self.durable_dir, rehydrated,
                    (
                        f", cap {self._durable_max_bytes / 1024**3:.2f} GiB"
                        if self._durable_max_bytes
                        else ""
                    ),
                )
        elif self._durable_on:
            log.warning(
                "durable tier needs the prefix index (prefix-cache: "
                "auto) — off"
            )
            self._durable_on = False
        self._startup.built()

    # -- public API ---------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._dead = None
        self._stop.clear()
        self._ready.clear()
        self._fetcher.start()
        if self._spill_worker is not None:
            self._spill_worker.start()
        if self._durable_worker is not None:
            self._durable_worker.start()
        self._thread = threading.Thread(target=self._run, name="serving-engine", daemon=True)
        self._thread.start()

    def wait_ready(self, timeout: Optional[float] = None) -> None:
        """Block until the engine thread has finished its warm-up (a no-op
        wait without ``precompile``), and raise if the warm-up failed: a
        program the compiler refuses is a failed engine START, seen by
        whoever built the engine, not by the first request."""
        if not self._ready.wait(timeout):
            raise TimeoutError(f"engine warm-up still running after {timeout}s")
        if self._dead is not None:
            raise RuntimeError("serving engine failed to start") from self._dead

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if self._precompile:
            gc.unfreeze()  # the warm-up froze the heap
        self._fetcher.stop()
        if self._spill_worker is not None:
            self._spill_worker.stop()
        if self._durable_worker is not None:
            self._durable_worker.stop()
        # resolve everything still in flight so blocked callers return now
        self._fail_all(RuntimeError("serving engine stopped"))

    def drain(self, grace_s: float = 30.0) -> bool:
        """Graceful quiescence, DISTINCT from stop(): reject new submissions
        (ShedError) but let everything already accepted — active slots,
        queued admissions, long-prefill streams — run to completion. Returns
        True when the engine went quiet within ``grace_s``, False when the
        grace period expired with work still in flight (the caller then
        decides between waiting longer and a hard stop()). Does NOT stop the
        engine thread; call stop() after. Re-entrant; ``_draining`` stays set
        so a drain→stop sequence never readmits."""
        self._draining = True
        deadline = time.monotonic() + max(0.0, grace_s)
        while time.monotonic() < deadline:
            if self._quiesced():
                return True
            if self._thread is None or not self._thread.is_alive():
                return self._quiesced()  # loop is gone; nothing will drain
            time.sleep(0.01)
        return self._quiesced()

    def _quiesced(self) -> bool:
        return (
            not self._mid_iteration
            and not any(s.active for s in self._slots)
            and self._queue.qsize() == 0
            and not self._longs
            and not self._long_queue
            and not self._page_deferred
            and self._held_back is None
        )

    def submit(self, request: GenerationRequest) -> GenerationRequest:
        """Thread-safe enqueue. A full queue blocks (shed_policy="block",
        backpressure toward the broker poll loop — SURVEY §7 hard parts) or
        sheds with ShedError carrying a retry-after estimate
        (shed_policy="reject"). Requests whose deadline cannot survive the
        CURRENT observed queue wait are shed immediately either way —
        admitting them would burn queue slots and prefill FLOPs on work
        that is already dead on arrival."""
        if self._dead is not None:
            raise RuntimeError("serving engine is stopped") from self._dead
        # (re)stamp on every submit attempt: a ShedError retry reuses the
        # SAME request object, and a construction-time stamp would count
        # the retry sleep as queue wait — expiring max_queue_wait_s
        # immediately and feeding the inflated wait into the shed EMA
        request.submitted_at = time.monotonic()
        tenant = getattr(request.options, "tenant", None) or DEFAULT_TENANT
        self._tenants.note_submit(tenant)
        if self._draining:
            self._count_shed(tenant)
            raise ShedError("serving engine is draining", retry_after_s=5.0)
        limit = self.max_seq_len - 1
        if len(request.prompt_tokens) > limit:
            raise ValueError(
                f"prompt of {len(request.prompt_tokens)} tokens exceeds the "
                f"engine limit of {limit} (max_seq_len - 1)"
            )
        if self.config.fills_blocks and (
            len(request.prompt_tokens) > self.prefill_buckets[-1]
        ):
            raise ValueError(
                f"chunked prefill: a prompt of {len(request.prompt_tokens)} tokens "
                f"is beyond the largest prefill bucket ({self.prefill_buckets[-1]}) "
                f"and {self.config.name} fills blocks: the segment program has "
                "no block-causal mask"
            )
        opts = request.options
        cost_budget = getattr(opts, "max_cost_tokens", None)
        if cost_budget is not None:
            if int(cost_budget) <= 0:
                raise ValueError(
                    f"max_cost_tokens must be >= 1, got {cost_budget}"
                )
            if len(request.prompt_tokens) + 1 > int(cost_budget):
                # the budget cannot afford a single generated token: a
                # client error, not a capacity problem — never a 429
                raise ValueError(
                    f"prompt of {len(request.prompt_tokens)} tokens leaves "
                    f"no generation room in a max_cost_tokens budget of "
                    f"{cost_budget}"
                )
        # brownout admission gates (docs/SERVING.md §19): ladder level 3
        # sheds low-priority work at the door, level 4 sheds over-quota
        # tenants outright — decode of admitted work is never touched
        bo = self._brownout
        if bo is not None and bo.reject_low and (
            getattr(opts, "priority", "normal") == "low"
        ):
            self._count_shed(tenant)
            raise ShedError(
                f"brownout level {bo.level}: low-priority admissions are "
                "shed until load clears",
                retry_after_s=max(self._tenant_wait_estimate(tenant), 0.5),
            )
        over_quota = self._tenants.over_quota(tenant)
        if bo is not None and bo.reject_quota and over_quota:
            self._count_shed(tenant)
            raise ShedError(
                f"brownout level {bo.level}: tenant {tenant!r} is over its "
                "token-rate quota",
                retry_after_s=max(
                    self._tenants.quota_retry_after_s(tenant), 0.5
                ),
            )
        # quota-aware shedding OUTSIDE brownout: over-quota tenants shed
        # FIRST — whenever there is queue pressure AND someone else's work
        # is waiting, the over-quota tenant yields before any in-quota
        # tenant is shed. With the engine otherwise idle its work still
        # runs (work-conserving: quotas bound sustained rate, not access
        # to spare capacity).
        if over_quota and self._queue.qsize() > 0:
            others = [
                t for t in self._queue.tenants_with_work() if t != tenant
            ]
            if others:
                self._count_shed(tenant)
                raise ShedError(
                    f"tenant {tenant!r} is over its token-rate quota while "
                    "other tenants wait",
                    retry_after_s=max(
                        self._tenants.quota_retry_after_s(tenant), 0.1
                    ),
                )
        adapter_name = getattr(opts, "adapter", None)
        if adapter_name and self._adapters is None:
            raise ValueError(
                f"request names adapter {adapter_name!r} but this engine has "
                "no adapter registry (configure `adapters:` on tpu-serving)"
            )
        response_format = getattr(opts, "response_format", None)
        if response_format and self._constrain_reg is None:
            raise ValueError(
                "request carries response_format but constrained decoding is "
                "off on this engine"
                + (
                    " (not supported on multi-host SPMD replicas yet — "
                    "docs/SERVING.md §15)"
                    if self._spmd is not None
                    else " (constrained-decoding: off was configured)"
                )
            )
        if response_format and request._dfa is None:
            # compile (or cache-hit) on the SUBMITTER's thread — grammar
            # compilation is pure host work and must not stall the engine
            # loop; an uncompilable schema fails HERE, loudly
            request._dfa = self._constrain_reg.compile(dict(response_format))
        resume = getattr(opts, "grammar_resume_state", None)
        if request._dfa is not None and resume is not None:
            if request._dfa.is_complete(int(resume)):
                # the derivation already FINISHED when the original stream
                # died (the cut ate only the terminal frame): there is
                # nothing left to generate — resolve immediately instead
                # of sampling a token the uninterrupted run never produced
                request.dfa_state = int(resume)
                request._finish(GenerationResult(
                    tokens=[], finish_reason="stop",
                    prompt_tokens=len(request.prompt_tokens),
                    ttft_s=0.0, total_s=0.0,
                ))
                return request
        deadline_s = request.options.deadline_s
        if deadline_s is not None:
            # the tenant's OWN observed wait decides hopelessness (and the
            # retry-after estimate): a victim tenant with an empty lane is
            # not hopeless just because an aggressor inflated the global EMA
            est_wait = self._tenant_wait_estimate(tenant)
            if deadline_s <= 0 or (self._queue.qsize() > 0 and est_wait >= deadline_s):
                self._count_shed(tenant)
                raise ShedError(
                    f"deadline of {deadline_s:.2f}s cannot survive the "
                    f"current ~{est_wait:.2f}s queue wait",
                    retry_after_s=max(est_wait, 0.1),
                )
        with self._waiting_lock:
            self._waiting[id(request)] = request
            if self._obs.on:
                self._open[id(request)] = request
        try:
            try:
                if self.shed_policy == "reject":
                    self._queue.put_nowait(request)
                else:
                    self._queue.put(request)
            except queue.Full:
                self._count_shed(tenant)
                raise ShedError(
                    f"admission queue full ({self._queue.maxsize} deep)",
                    retry_after_s=max(self._tenant_wait_estimate(tenant), 0.1),
                ) from None
            except TenantShareExceeded as e:
                # the tenant's SLICE is full even though the global queue
                # may have room: always a shed for that tenant — blocking
                # the shared submitter on one tenant's backlog would be
                # the noisy-neighbor coupling tenancy exists to remove
                self._count_shed(tenant)
                raise ShedError(
                    str(e),
                    retry_after_s=max(self._tenant_wait_estimate(tenant), 0.1),
                ) from None
        except BaseException:
            with self._waiting_lock:
                self._waiting.pop(id(request), None)
                self._open.pop(id(request), None)
            raise
        self._wake.set()  # the engine thread may be waiting for an arrival
        return request

    def _tenant_wait_estimate(self, tenant: str) -> float:
        """The queue-wait estimate shed decisions and Retry-After use:
        a NAMED tenant's own EMA when it has one — a victim with an empty
        lane must not look hopeless because an aggressor inflated the
        average — falling back to the global EMA for first contact. The
        default tenant IS the untenanted population, so it reads the
        global EMA directly (the pre-tenancy semantics, which the §9
        hopeless-deadline drill pins)."""
        if tenant == DEFAULT_TENANT:
            return self._queue_wait_ema_s
        own = self._tenants.queue_wait_ema_s(tenant)
        return own if own > 0 else self._queue_wait_ema_s

    def generate(
        self,
        prompt_tokens: Optional[list[int]] = None,
        options: Optional[GenerationOptions] = None,
        on_token: Optional[Callable[[int], None]] = None,
        timeout: float = 300.0,
        request: Optional[GenerationRequest] = None,
    ) -> GenerationResult:
        """Blocking convenience wrapper (submit + wait). A wait timeout
        CANCELS the request — before cancellation existed, the caller got
        its TimeoutError while the engine kept decoding the orphan to
        max_new_tokens, burning a slot nobody would ever read.

        ``request``: submit a caller-BUILT request instead of constructing
        one (the fleet dispatch path pre-builds it so the peer can
        register it for cross-process cancel before submitting);
        prompt_tokens/options/on_token are ignored then."""
        if request is None and prompt_tokens is None:
            # fail at the call site, not as a confusing empty-prompt
            # generation three layers later
            raise ValueError("generate() needs prompt_tokens or request")
        req = request if request is not None else GenerationRequest(
            prompt_tokens=list(prompt_tokens),
            options=options or GenerationOptions(),
            on_token=on_token,
        )
        self.submit(req)
        try:
            return req.result(timeout)
        except TimeoutError:
            req.cancel()
            raise

    def _count_shed(self, tenant: Optional[str] = None) -> None:
        """Shed bookkeeping shared by every shed site: count under the
        stats lock (attributed to the shedding tenant when known), then
        let the flight recorder's sliding window decide whether this shed
        completes a BURST worth a postmortem dump (an isolated shed is
        routine backpressure, not an incident)."""
        with self._stats_lock:
            self.shed_total += 1
        if tenant is not None:
            self._tenants.note_shed(tenant)
        if self._obs.on and self._obs.flight.note_shed():
            self._flight_dump("shed-burst")

    def _flight_dump(self, reason: str, extra: Optional[dict] = None,
                     force: bool = False) -> Optional[dict]:
        """Snapshot the flight ring into a dump artifact, stamped with the
        lifecycle counters at dump time. Callable from ANY thread (the
        shed path runs on submitters); debounced per reason inside the
        recorder."""
        if not self._obs.on:
            return None
        extra = dict(extra or {})
        if self._injector is not None:
            # which injected fault preceded this incident (chaos drills)
            extra["injector-events"] = self._injector.events_snapshot()
        return self._obs.flight.dump(
            reason, counters=self._counters_snapshot(), extra=extra,
            force=force,
        )

    def reset_histograms(self) -> None:
        """Zero the streaming histograms (buckets keep). Bench phases call
        this after their warmup request so one compile-heavy cold TTFT
        doesn't own p99 of a steady-state distribution."""
        self._obs.reset_histograms()
        window = self._pagepool.window if self._pagepool is not None else None
        if window is not None:  # the peak gauge restarts with them
            window.peak_in_use = window.pages_in_use
        with self._stats_lock:  # and the device's unfed account
            self._unfed_s = self._unfed_request_s = 0.0
            self._account_t0 = time.monotonic()
            self._segment_key_blocks = {}
        self._launches = dict.fromkeys(self._launches, 0)
        self._segment_writes = dict.fromkeys(self._segment_writes, 0)

    def prefix_advertisement(
        self, top_k: int = 32,
    ) -> tuple[tuple[int, ...], list[tuple[str, int, str]]]:
        """The fleet beacon's affinity payload: the prefix index's bucket
        boundaries plus its most-recently-used ``top_k`` prefixes as
        ``(digest, length, tier)`` triples (serving/fleet.py). ``tier``
        splits device-resident from hibernated (host-tier) sessions so
        sticky routing survives a spill — the router scores ``host`` at a
        discount. Non-mutating and thread-safe — beacon building runs on
        the runtime HTTP thread and must neither touch LRU recency nor
        leak token content."""
        index = self._prefix_index
        if index is None:
            return (), []
        ads = index.advertised(top_k)
        if self._durable is not None:
            # checkpoints that outlived their live entry still serve (the
            # snapshot path reads them off disk): beacon them at tier
            # "durable" so the router can prefetch/route onto them —
            # resurrection is useless if nobody knows the bytes exist
            live = {d for d, _, _ in ads}
            extra = top_k
            for digest, length in self._durable.entries():
                if extra <= 0:
                    break
                if digest in live:
                    continue
                ads.append((digest, length, "durable"))
                extra -= 1
        return tuple(index.boundaries), ads

    def _counters_snapshot(self) -> dict[str, Any]:
        with self._stats_lock:
            return {
                "shed": self.shed_total,
                "cancelled": self.cancelled_total,
                "deadline-queue": self.deadline_queue_total,
                "deadline-decode": self.deadline_decode_total,
                "quarantined-slots": self.quarantined_slots_total,
                "nan-guard": self.nan_guard_total,
                "engine-restarts": self.engine_restarts_total,
                "spmd-recoveries": self.spmd_recoveries_total,
                "spmd-resyncs": self.spmd_resyncs_total,
                "spmd-watchdog-trips": self.spmd_watchdog_trips_total,
                "total-requests": self.total_requests,
                "total-generated-tokens": self.total_generated,
                "queued": self._queue.qsize(),
                "active-slots": sum(1 for s in self._slots if s.active),
            }

    def stats(self, dump: bool = False) -> dict[str, Any]:
        """One CONSISTENT snapshot: every counter below is read under the
        same lock their writers hold, so shed totals, queue depth and the
        deadline counters can never disagree mid-iteration. Values are
        plain ints/floats/strs/dicts — safe to json.dumps as-is.
        ``dump=True`` additionally snapshots the flight recorder (an
        on-demand postmortem artifact; see docs/SERVING.md §12)."""
        # histogram snapshots take the per-histogram locks only — compute
        # BEFORE the stats lock so lock order is always hist→stats-free
        hist = self._obs.histograms()
        queue_wait_p90 = hist.get("engine_queue_wait_s", {}).get("p90", 0.0)
        # per-tenant block (registry + queue locks, never nested with the
        # stats lock): counters, quota state, live queue depth and active
        # slots by tenant — what beacons and the Grafana gauges consume
        active_by_tenant: dict[str, int] = {}
        for s in self._slots:
            req = s.request
            if req is not None:
                t = getattr(req.options, "tenant", None) or DEFAULT_TENANT
                active_by_tenant[t] = active_by_tenant.get(t, 0) + 1
        tenants = self._tenants.snapshot(
            queued=self._queue.depth_by_tenant(), active=active_by_tenant
        )
        with self._stats_lock:
            out = self._stats_locked()
        out["tenants"] = tenants
        out["brownout"] = (
            self._brownout.snapshot() if self._brownout is not None else None
        )
        out["brownout-level"] = (
            self._brownout.level if self._brownout is not None else 0
        )
        out["brownout-transitions-total"] = (
            self._brownout.transitions_total
            if self._brownout is not None
            else 0
        )
        out["observability"] = self._obs.on
        out["histograms"] = hist
        # load score (ROADMAP item 3): the replica-balancer routing signal
        pool = self._pagepool
        page_pressure = (
            pool.pages_in_use / max(1, pool.num_pages)
            if pool is not None
            else min(1.0, out["queued"] / max(1, self._queue.maxsize))
        )
        out["load-score"] = load_score(
            queue_wait_p90,
            out["active-slots"] / max(1, self.max_batch),
            page_pressure,
        )
        out["flight-dumps-total"] = self._obs.flight.dumps_total
        if dump:
            out["flight-recorder"] = self._flight_dump("on-demand", force=True)
        return out

    def _stats_locked(self) -> dict[str, Any]:
        active = sum(1 for s in self._slots if s.active)
        now = time.monotonic()
        # the stretch still open counts: an idle tail is not lost
        unfed, unfed_request = (
            self._unfed_stretch(now) if self._obs.on else None
        ) or (0.0, 0.0)
        return {
            "active-slots": active,
            # the device's unfed account since the engine was built or
            # `reset_histograms` (docs/SERVING.md §12): seconds with no
            # dispatch in flight, the part of them with a request open in
            # the engine, and the seconds they are a share of
            "engine-loop-s": round(now - self._account_t0, 6),
            "device-unfed-s": round(self._unfed_s + unfed, 6),
            "device-unfed-with-request-s": round(
                self._unfed_request_s + unfed_request, 6
            ),
            "max-batch": self.max_batch,
            "queued": self._queue.qsize(),
            "long-prefill-active": bool(self._longs),
            "long-prefill-streams": len(self._longs),
            "long-prefill-queued": len(self._long_queue),
            "total-requests": self.total_requests,
            "total-generated-tokens": self.total_generated,
            # expert assignments the device programs made and dropped past
            # capacity (transformer.MOE_COUNTS; 0 for a dense model),
            # summed over the dispatches processed so far
            "moe-routed-assignments-total": self.moe_routed_total,
            "moe-dropped-assignments-total": self.moe_dropped_total,
            # a model that fills blocks: what its block chunks did, summed
            # over the chunks processed so far (BLOCK_COUNTERS)
            **(
                {f"block-{k.replace('_', '-')}": v for k, v in self._block_totals.items()}
                if self.config.fills_blocks else {}
            ),
            # what the model's decode chunks and segments read a layer, summed
            # over the dispatches launched: the sums its kinds of attention
            # name (models/transformer `LaunchReads.totals`)
            **self._read_totals,
            # a model with window layers: its second page group's use
            **(
                {
                    "kv-window-pages-total": self._pagepool.window.num_pages,
                    "kv-window-pages-in-use": self._pagepool.window.pages_in_use,
                    # the most in use since `reset_histograms`
                    "kv-window-pages-peak": self._pagepool.window.peak_in_use,
                    "kv-window-bytes-per-page": self._pagepool.window_bytes_per_page,
                    "kv-window-pages-recycled-total": self._pagepool.window.recycled_total,
                }
                if self._pagepool.window is not None else {}
            ),
            "busy-steps": self._busy_steps,
            "prefill-token-budget": self.prefill_token_budget,
            # distinct device programs dispatched (= XLA compiles): flat
            # after warmup ⇔ no mid-traffic compile stalls. Underscore key
            # (vs the dict's dash convention) is the round-6 issue contract
            # — tests and the metrics exporter consume it by this exact
            # name; do not "fix" the spelling
            "compiled_programs": len(self._programs),
            # time to ready and what of it built programs: `startup-*` frozen
            # when the warm-up ended (zeros before), `process-*` the whole
            # process's compile account, live (docs/SERVING.md §12, "Start-up")
            **self._startup.stats,
            **process_stats(),
            # admission groups dispatched at each row count of the ladder
            # (admit_rungs): how far groups shrink to the prompts they hold
            "admit-group-rows": dict(self._admit_group_rows),
            # rows of those groups whose own bucket is narrower than the
            # group's width: prompts that rode a wider group's free row
            # (admission_groups) and saved their own group a rung
            "admit-rows-widened": self._admit_rows_widened,
            # iterations that launched, by how the launch was decided, and
            # the late ones among them (docs/SERVING.md, "When the engine
            # launches"), since the engine was built or `reset_histograms`
            "launches": dict(self._launches),
            # prefill segments by the writer of their new rows: whole pages
            # copied where the pool lies, or the scatter (a segment that
            # starts inside a page, an int8 pool, a mesh, no kernels;
            # docs/SERVING.md, "The pool's writers"), since the same
            "segment-writes": dict(self._segment_writes),
            # key blocks those segments' attention walked, a KV head and a
            # layer call, where the read is the walk over key blocks
            # (`key-blocks`; a window model's window layers beside its full
            # ones, `key-blocks-window`): what their attention time is divided
            # by for its time a key block; {} where segments read masked jnp
            "segment-key-blocks": dict(self._segment_key_blocks),
            "decode-step-ms": round(self._step_time_ema_s * 1e3, 3),
            "hbm-gbps-decode": self._achieved_hbm_gbps(),
            # the page pool, the engine's only KV state
            "page-size": self.page_size,
            "kv-pages-total": self._pagepool.num_pages,
            "kv-pages-in-use": self._pagepool.pages_in_use,
            "kv-bytes-per-page": self._pagepool.bytes_per_page,
            # what a token's leaves hold over all layers, in the pool's dtype
            # (K and V; a latent in their place; an indexer's key)
            "kv-bytes-per-token": self._pagepool.bytes_per_page // self.page_size,
            # the recurrent state beside the pages: one row a slot (zeros
            # for a model without recurrent layers)
            "recurrent-state-bytes": self._pagepool.state_bytes_total,
            "recurrent-state-rows-in-use": self._pagepool.state_rows_in_use,
            # what a slot's convolution tails hold, over its layers (a conv
            # model's whole state row; beside the rule's state in a delta-rule
            # model; 0 for a model without)
            "conv-state-bytes-per-slot": self._pagepool.conv_state_bytes_per_row,
            "kv-page-alias-rate": round(
                self._pagepool.aliased_pages_total
                / max(1, self._pagepool.reserved_pages_total),
                4,
            ),
            "prefix-copy-bytes-saved-total": (
                self._prefix_index.copy_bytes_saved if self._prefix_index else 0
            ),
            # prefix KV reuse (zeros with the cache off, so the metrics
            # exporter can set its gauges unconditionally)
            "prefix-cache": self._prefix_index is not None,
            "prefix-cache-hit-rate": (
                self._prefix_index.hit_rate() if self._prefix_index else 0.0
            ),
            "prefill-tokens-saved-total": (
                self._prefix_index.tokens_saved if self._prefix_index else 0
            ),
            "prefix-pool-bytes-in-use": self._prefix_index_bytes(),
            "prefix-cache-evictions-total": (
                self._prefix_index.evictions if self._prefix_index else 0
            ),
            "prefix-cache-entries": (
                self._prefix_index.live_entries if self._prefix_index else 0
            ),
            # tiered KV: host-RAM spill + session hibernation (zeros with
            # the tier off, so the metrics exporter sets its gauges
            # unconditionally — the standing contract of every block here)
            "host-tier": self._host_tier is not None,
            "host-pages-total": (
                self._host_tier.num_pages if self._host_tier else 0
            ),
            "host-pages-in-use": (
                self._host_tier.slots_in_use if self._host_tier else 0
            ),
            "host-tier-bytes-total": (
                self._host_tier.bytes_total if self._host_tier else 0
            ),
            "spill-pages-total": self.spill_pages_total,
            "spill-bytes-total": self.spill_bytes_total,
            "spill-failures-total": self.spill_failures_total,
            "restore-pages-total": self.restore_pages_total,
            "restore-bytes-total": self.restore_bytes_total,
            # the restore-vs-recompute hit split: a warm hit whose pages
            # lived host-side either restored (DMA) or fell back to a
            # re-prefill (fault/checksum/no-room) — the ratio is THE
            # health gauge of the tier
            "restored-hits-total": self.restored_hits_total,
            "restore-failures-total": self.restore_failures_total,
            "recompute-fallbacks-total": self.recompute_fallbacks_total,
            "host-demotions-total": (
                self._prefix_index.demotions if self._prefix_index else 0
            ),
            "host-evictions-total": (
                self._prefix_index.host_evictions if self._prefix_index else 0
            ),
            # KV-page migration (disaggregated serving, §18): pages/bytes
            # serialized OUT of this replica's pool and bound IN from a
            # peer's — the sender side only counts after the receiver's
            # ACK released the local copy
            "migrate-pages-out-total": self.migrate_pages_out_total,
            "migrate-bytes-out-total": self.migrate_bytes_out_total,
            "migrate-pages-in-total": self.migrate_pages_in_total,
            "migrate-bytes-in-total": self.migrate_bytes_in_total,
            "migrate-failures-total": self.migrate_failures_total,
            # durable session tier (§23) — zeros with the tier off, same
            # exporter contract as every block above
            "durable-tier": self._durable is not None,
            "durable-restored-hits-total": self.durable_restored_hits_total,
            **(
                self._durable.stats()
                if self._durable is not None
                else _durable_empty_stats()
            ),
            # self-speculative decoding (zeros with speculation off, so the
            # metrics exporter sets its gauges unconditionally)
            "speculation": self._spec_enabled,
            "speculation-tokens": self.spec_tokens,
            "spec-acceptance-rate": (
                round(
                    self.spec_accepted_tokens_total
                    / self.spec_draft_tokens_total,
                    4,
                )
                if self.spec_draft_tokens_total
                else 0.0
            ),
            "spec-accepted-tokens-per-step": (
                round(
                    self.spec_emitted_tokens_total / self.spec_slot_steps_total,
                    4,
                )
                if self.spec_slot_steps_total
                else 0.0
            ),
            "spec-draft-hit-rate": (
                round(
                    self.spec_draft_hits_total / self.spec_draft_lookups_total,
                    4,
                )
                if self.spec_draft_lookups_total
                else 0.0
            ),
            "spec-draft-tokens-total": self.spec_draft_tokens_total,
            "spec-accepted-tokens-total": self.spec_accepted_tokens_total,
            "spec-verify-dispatches-total": self.spec_dispatches_total,
            # multi-LoRA multiplexing + constrained decoding (zeros with
            # the agentic tier off, so the metrics exporter sets its
            # gauges unconditionally — the same contract every subsystem
            # block above follows)
            "adapters": self._adapters is not None,
            "adapters-registered": (
                self._adapters.stats()["registered"] if self._adapters else 0
            ),
            "adapters-resident": (
                self._adapters.resident if self._adapters else 0
            ),
            "adapter-pool-rows": (
                self._adapters.rows - 1 if self._adapters else 0
            ),
            "adapter-swaps-total": (
                self._adapters.swaps_total if self._adapters else 0
            ),
            "adapter-pool-bytes": (
                self._adapters.pool_bytes if self._adapters else 0
            ),
            "constrained-decoding": self._constrain_reg is not None,
            "constrained-requests-total": self.constrained_requests_total,
            "grammars-resident": (
                self._constrain_reg.resident if self._constrain_reg else 0
            ),
            "grammar-swaps-total": (
                self._constrain_reg.swaps_total if self._constrain_reg else 0
            ),
            "grammar-pool-bytes": (
                self._constrain_reg.pool_bytes if self._constrain_reg else 0
            ),
            "constrain-overhead-ms": round(self._constrain_host_ema_ms, 4),
            # request lifecycle / fault recovery (this PR's acceptance
            # surface: every degradation path is countable in production)
            "draining": self._draining,
            "shed-total": self.shed_total,
            "cancelled-total": self.cancelled_total,
            "deadline-exceeded-total": (
                self.deadline_queue_total + self.deadline_decode_total
            ),
            "deadline-queue-total": self.deadline_queue_total,
            "deadline-decode-total": self.deadline_decode_total,
            "quarantined-slots-total": self.quarantined_slots_total,
            "nan-guard-total": self.nan_guard_total,
            "engine-restarts-total": self.engine_restarts_total,
            "queue-wait-ema-s": round(self._queue_wait_ema_s, 4),
            "fault-injection": (
                self._injector.stats() if self._injector is not None else None
            ),
            # SPMD wire accounting (PERF.md round 13: ControlBlock
            # bytes/iteration is a MEASURED number, not an estimate)
            "spmd": self._spmd is not None,
            "spmd-announces-total": (
                getattr(self._spmd, "announces_total", 0)
                if self._spmd is not None
                else 0
            ),
            "spmd-announce-bytes-total": (
                getattr(self._spmd, "bytes_announced_total", 0)
                if self._spmd is not None
                else 0
            ),
            # SPMD slice resilience (§20): the recover-in-place ledger.
            # `recovering` is True through the crash→rebuild→backoff
            # window — beacons advertise it so routers exclude the
            # replica WITHOUT quarantining it (sticky sessions held).
            # Zeros single-host, so the exporter sets gauges
            # unconditionally (the standing contract of every block here)
            "recovering": self._recovering,
            "spmd-recovery-epoch": self._spmd_epoch,
            "spmd-recoveries-total": self.spmd_recoveries_total,
            "spmd-resyncs-total": self.spmd_resyncs_total,
            "spmd-watchdog-trips-total": self.spmd_watchdog_trips_total,
            # streamed weight load (docs/SERVING.md §22): the cold-start
            # ledger — per-phase wall times of the checkpoint→device
            # pipeline this engine was built from (zeros for random init,
            # so the metrics exporter sets its gauges unconditionally —
            # the standing contract of every block here)
            "weight-load-streamed": bool(
                self._weight_load_report.get("streamed", False)
            ),
            "weight-load-s": float(
                self._weight_load_report.get("total-s", 0.0)
            ),
            "weight-load-read-s": float(
                self._weight_load_report.get("read-s", 0.0)
            ),
            "weight-load-transform-s": float(
                self._weight_load_report.get("transform-s", 0.0)
            ),
            "weight-load-transfer-s": float(
                self._weight_load_report.get("transfer-s", 0.0)
            ),
            "weight-load-bytes-total": int(
                self._weight_load_report.get("bytes-read", 0)
            ),
            "weight-load-staging-peak-bytes": int(
                self._weight_load_report.get("staging-peak-bytes", 0)
            ),
            "weight-load-shards": int(
                self._weight_load_report.get("shards", 0)
            ),
            "weight-load-workers": int(
                self._weight_load_report.get("workers", 0)
            ),
        }

    @property
    def recovering(self) -> bool:
        """True while the loop supervisor is between a crash and the
        post-backoff restart — the cheap accessor /healthz and beacons
        read (one attribute, no stats() walk)."""
        return self._recovering

    def _prefix_index_bytes(self) -> int:
        """HBM held by pages the paged alias index references (distinct —
        deeper entries share their shallower prefixes' pages). pages_held
        is a counter the ENGINE thread maintains, so reading it from the
        metrics thread never races a _live mutation."""
        if self._prefix_index is None:
            return 0
        return self._prefix_index.pages_held * self._pagepool.bytes_per_page

    def _achieved_hbm_gbps(self) -> float:
        """Bytes-read model per decode step (the plan's weights + the pages
        the active slots read) over the measured step time — the
        achieved-HBM-bandwidth gauge. Decode is bandwidth-bound, so this ÷
        the chip's spec sheet IS the utilization number."""
        if self._plan is None or self._step_time_ema_s <= 0:
            return 0.0
        # pages actually READ per step: each active slot streams the
        # pages covering its written prefix — content-proportional,
        # which is the paged layout's whole bandwidth story
        pages_read = sum(
            -(-(s.position + 1) // self.page_size)
            for s in self._slots
            if s.active
        )
        read = (
            self._plan.weights_bytes
            + self._pagepool.bytes_per_page * pages_read
        )
        return round(read / self._step_time_ema_s / 1e9, 2)

    def _record_program(self, *signature) -> None:
        self._programs.add(tuple(signature))
        if self._startup.warming:
            self._startup.program(signature)

    @staticmethod
    def _fresh_block_state(rows: int, config: ModelConfig) -> Optional[dict]:
        """The device state of a model that fills blocks: every row at a
        block of mask ids, all open, step 0 (None for every other model)."""
        if not config.fills_blocks:
            return None
        s = config.block_length
        return {
            "tokens": jnp.full((rows, s), config.mask_token_id, jnp.int32),
            "open": jnp.ones((rows, s), jnp.bool_),
            "step": jnp.zeros(rows, jnp.int32),
        }

    # -- engine thread ------------------------------------------------------

    def _warmup_paged(self) -> None:
        """Precompile the decode-phase program surface before the first
        request: ONE decode (or verify) program for every sequence-length
        mix, plus the batch-1 segment family
        (long-prompt chunks at the largest bucket width and, with a prefix
        index, warm suffixes at every bucket width), the
        copy-on-write page copy, and the quarantine page-zero. Every
        throwaway dispatch runs against all-out-of-bounds tables/indices:
        writes drop, reads clamp into masked columns, so engine state is
        untouched except the PRNG key (which advances before any request is
        served, like the bucket warmup). The admission (paged-prefill)
        family is warmed by _warmup_prefill_buckets as usual."""
        if self._spec_enabled:
            drafts = np.zeros((self.max_batch, self.spec_tokens), np.int32)
            self._dev_verify(drafts, [self.max_batch]).block_until_ready()
        else:
            self._dev_decode(
                self.decode_chunk, [self.max_batch]
            ).block_until_ready()
        # a long prompt's chunks run at the LARGEST bucket width; the
        # narrower widths serve only warm suffixes behind a prefix hit,
        # which an engine without a prefix index never makes
        segment_widths = (
            self.prefill_buckets if self._prefix_index is not None
            else self.prefill_buckets[-1:]
        )
        if self.config.fills_blocks:
            segment_widths = ()  # no prompt is chunked, no prefix reused
        for ws in segment_widths:
            if self._stop.is_set():
                return
            first = self._dev_paged_segment(
                np.zeros((1, ws), np.int32), 0, 1, self.max_batch,
                0.0, 0, 1.0, final=False, prompt_len=1,
            )
            jax.block_until_ready(first)
        pool = self._pagepool
        self._record_program("page-copy")
        pool.dev = _page_copy(
            pool.dev, jnp.asarray(0, jnp.int32), jnp.asarray(pool.oob, jnp.int32)
        )
        self._record_program("page-zero")
        pool.dev = _page_zero(
            pool.dev, jnp.asarray(np.full(pool.table_len, pool.oob, np.int32))
        )
        if pool.window is not None:
            self._flush_window_zeros([])
        # the snapshot/restore pair serves BOTH the tiered-KV spill path
        # and the §18 migration wire (every paged engine can send/receive
        # a migration) — warmed so the first restore OR first migration is
        # DMA, not DMA + compile (the unwarmed pair measured ~14s of a
        # first HTTP migration's wall). Restore targets the OOB sentinel:
        # drops.
        self._record_program("page-snapshot")
        snap = _page_snapshot(pool.dev, jnp.asarray(0, jnp.int32))
        self._record_program("page-restore")
        pool.dev = _page_restore(
            pool.dev, snap, jnp.asarray(pool.oob, jnp.int32)
        )
        jax.block_until_ready(jax.tree.leaves(pool.dev)[0])
        log.info(
            "paged programs precompiled: ONE %s program (chunk %d), %d "
            "segment widths, page-copy, page-zero",
            "verify" if self._spec_enabled else "decode",
            self.spec_tokens + 1 if self._spec_enabled else self.decode_chunk,
            len(segment_widths),
        )

    def _warmup_prefill_buckets(self) -> None:
        """Precompile one admission program per (rung, prefill bucket
        width), every shape ``_prefill_group`` can dispatch (``admit_rungs``;
        an expert model has the one rung), so the fused iterations' prefill
        halves quantize into the warmed set too —
        before this, the first admission wave at each width compiled
        admit_group MID-TRAFFIC (the same 15-23s stall class the decode
        ladder warmup closed; the gateway bench only dodged it because its
        warmup chat happened to use the only configured bucket). All rows
        are padding (slots out of bounds → every scatter drops), so engine
        state is untouched except the PRNG key, which advances before any
        request is served. SPMD: the family replays whole (OP_WARMUP) so
        followers warm and key-advance identically."""
        for width in self.prefill_buckets:
            for n_pad in self._admit_rungs:
                if self._stop.is_set():
                    return
                tokens = np.zeros((n_pad, width), np.int32)
                lengths = np.ones(n_pad, np.int32)
                temps = np.zeros(n_pad, np.float32)
                top_ks = np.zeros(n_pad, np.int32)
                top_ps = np.ones(n_pad, np.float32)
                slots = np.full(n_pad, self.max_batch, np.int32)  # all dropped
                self._dev_prefill(
                    width, tokens, lengths, temps, top_ks, top_ps, slots
                ).block_until_ready()
        # the decode-chain scatter (warm prefix admissions AND the final
        # chunked-prefill segment dispatch it): one traced-index program,
        # warmed with an all-dropped slot so its first real use — the first
        # completed long prompt, prefix cache or not — is never a compile
        self._record_program("chain-scatter")
        (
            self._tokens_dev, self._positions_dev, self._temp_dev,
            self._top_k_dev, self._top_p_dev,
        ) = _chain_scatter(
            self._tokens_dev, self._positions_dev, self._temp_dev,
            self._top_k_dev, self._top_p_dev,
            jnp.asarray(self.max_batch, jnp.int32),
            jnp.zeros(1, jnp.int32), 0, 0.0, 0, 1.0,
        )
        jax.block_until_ready(self._tokens_dev)
        # what it took is the family's span, and its log line (serving/startup.py)
        log.info(
            "prefill buckets precompiled: widths %s, rows %s",
            list(self.prefill_buckets), list(self._admit_rungs),
        )

    def _run(self) -> None:
        """Engine-thread supervisor: warm up (a failure there ends the
        engine — it never started), then run the serving loop; on a crash,
        quarantine the in-flight slots, rebuild device state, and restart
        under bounded exponential backoff instead of leaving the process
        alive but unable to serve until a pod restart. Under SPMD the crash
        is COORDINATED (docs/SERVING.md §20): OP_RECOVER with a fresh epoch
        rides the wire before the rebuild, followers run the identical
        deterministic rebuild in place, and idle heartbeats keep their
        watchdogs fed through the backoff wait — zero process exits.
        Unrecoverable paths (a proven divergence — half the mesh must never
        serve alone — non-Exception BaseExceptions, or the restart budget
        exhausted) keep the crash-only contract: fail everything, announce
        STOP."""
        backoff = self.restart_backoff_s
        restarts = 0
        refused: Optional[BaseException] = None
        try:
            try:
                self._warmup()
            except BaseException as e:  # noqa: BLE001 — fatal, see below
                refused = e
                # OUTSIDE the restart loop on purpose: a restart skips the
                # warm-up, so recovering from a compile or lowering error
                # here would serve with the refused program still unbuilt
                # and meet the same error mid-traffic. The engine is dead
                # before it served anything; wait_ready() and submit()
                # raise this error.
                log.exception("serving engine warm-up failed; not starting")
                self._fail_all(e)
                return
            finally:
                self._startup.finish(refused)
                self._ready.set()
            while True:
                try:
                    self._recovering = False
                    self._run_once()
                    return  # clean stop
                except BaseException as e:  # noqa: BLE001 — classify below
                    now = time.monotonic()
                    if self._last_crash_t and now - self._last_crash_t > 60.0:
                        # a crash long after the previous one is a fresh
                        # incident, not an escalation — reset the budget
                        restarts = 0
                        backoff = self.restart_backoff_s
                    self._last_crash_t = now
                    recoverable = (
                        isinstance(e, Exception)
                        # a PROVEN leader/follower divergence stays fatal:
                        # rebuilding in place would let half the mesh serve
                        # state the other half provably disagrees with
                        and not isinstance(e, wire.SpmdDivergenceError)
                        and restarts < self.max_restarts
                        and not self._stop.is_set()
                    )
                    if not recoverable:
                        log.exception("serving engine loop crashed (unrecoverable)")
                        self._fail_all(e)
                        return
                    restarts += 1
                    self._recovering = True
                    with self._stats_lock:
                        self.engine_restarts_total += 1
                        if self._spmd is not None:
                            self.spmd_recoveries_total += 1
                        if isinstance(e, EngineWedgedError):
                            # the leader-side watchdog caught a wedged
                            # iteration and escalated it here (§20)
                            self.spmd_watchdog_trips_total += 1
                    # dump BEFORE _recover clears state: the ring holds the
                    # iterations that led to the crash — the postmortem
                    self._flight_dump(
                        "engine-restart",
                        extra={"error": type(e).__name__, "restart": restarts},
                    )
                    log.exception(
                        "serving engine loop crashed; quarantining %d in-flight "
                        "slot(s), restarting in %.2fs (restart %d/%d)",
                        sum(1 for s in self._slots if s.active) + len(self._longs),
                        backoff, restarts, self.max_restarts,
                    )
                    # SPMD only: epoch bump FIRST (the deterministic
                    # rebuild keys its PRNG reset off it), then the
                    # coordinated announce — followers start their
                    # identical rebuild while the leader tears down, and
                    # the seq chain restarts at the epoch base on both
                    # sides. Single-host restarts keep epoch 0 and their
                    # live PRNG (no cross-host determinism to protect).
                    if self._spmd is not None:
                        self._spmd_epoch += 1
                        try:
                            self._spmd.announce(wire.ControlBlock(
                                op=wire.OP_RECOVER, count=self._spmd_epoch,
                            ))
                            self._spmd.reset_seq()
                        except Exception:  # noqa: BLE001 — transport gone:
                            # followers will watchdog out and the pods
                            # restart together (the pre-round-19 contract)
                            log.exception(
                                "failed to announce OP_RECOVER to followers"
                            )
                        self._flight_dump(
                            "spmd-recover",
                            extra={
                                "epoch": self._spmd_epoch,
                                "error": type(e).__name__,
                                "restart": restarts,
                            },
                        )
                    try:
                        self._recover(e)
                    except BaseException as e2:  # noqa: BLE001 — recovery itself failed
                        # e.g. the cache rebuild OOMed: the crash-only
                        # contract must hold — without this, the thread
                        # dies with _dead unset and submit() keeps feeding
                        # a queue nobody serves
                        log.exception("crash recovery failed; engine is dead")
                        self._fail_all(e2)
                        return
                    if self._backoff_wait(backoff):
                        return  # stop() raced the backoff; it fails the rest
                    backoff = min(backoff * 2, 30.0)
        finally:
            self._recovering = False
            if self._spmd is not None:
                # release follower processes parked in recv() — best-effort
                # on the crash path too, else they block in the collective
                # forever while the leader pod looks alive. Announcements
                # only ever come from this thread, so STOP is totally
                # ordered after every dispatch.
                try:
                    self._spmd.announce(wire.ControlBlock(op=wire.OP_STOP))
                except Exception:  # noqa: BLE001 — transport may be gone too
                    log.exception("failed to announce STOP to SPMD followers")

    def _backoff_wait(self, backoff_s: float) -> bool:
        """The restart-backoff sleep, sliced so SPMD followers keep seeing
        idle heartbeats through it (their watchdog cannot tell a backoff
        wait from a dead leader otherwise — §20). Returns True when stop()
        raced the wait. Single-host (or watchdog off): one plain wait."""
        spmd = self._spmd
        if spmd is None or getattr(spmd, "watchdog_s", 0) <= 0:
            return self._stop.wait(backoff_s)
        slice_s = max(0.05, spmd.watchdog_s / 4)
        deadline = time.monotonic() + backoff_s
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return False
            if self._stop.wait(min(slice_s, remaining)):
                return True
            self._spmd_heartbeat()

    def _warmup(self) -> None:
        """Compile every program the loop can dispatch before the first
        request (``precompile``). Runs once per engine thread, ahead of the
        restart supervisor: restarts skip it, since every program is then
        in the jit cache (shapes are unchanged) and recovery latency is the
        point. SPMD: each family is ONE OP_WARMUP announcement — the
        follower runs the same function, so both sides make the identical
        deterministic dispatch sequence without per-dispatch wire traffic
        (docs/SERVING.md §14)."""
        if not self._precompile:
            return

        def announce_warmup(kind: int) -> None:
            if self._spmd is not None:
                self._spmd.announce(
                    wire.ControlBlock(op=wire.OP_WARMUP, count=kind)
                )

        # the decode-phase surface is ONE program (per step count)
        announce_warmup(wire.WARMUP_PAGED)
        with self._startup.phase("paged"):
            self._warmup_paged()
        announce_warmup(wire.WARMUP_PREFILL_BUCKETS)
        with self._startup.phase("prefill_buckets"):
            self._warmup_prefill_buckets()
        if self._agentic:
            # no announce: the agentic tier is construction-disabled
            # under SPMD, so this warmup never runs on a replica
            with self._startup.phase("agentic"):
                self._warmup_agentic()
        # what the process has built by now lives as long as it serves: put
        # it out of the collector's reach, so that a full collection costs
        # what was allocated since. A pass over the ~700,000 objects of a
        # serving process stops every thread for 238 ms, once inside 50 s
        # of chat traffic: each stream's tokens late, and the device idle
        # for some 7 ms when it begins while a chunk's tokens are delivered
        # (PERF.md §6, PR 29). stop() gives it back.
        gc.collect()
        gc.freeze()

    def _run_once(self) -> None:
        from collections import deque

        # batches of deferred fetch entries, one per loop iteration, newest
        # last; up to pipeline_depth batches stay unfetched so their device
        # work overlaps host bookkeeping AND the next dispatches
        pending: deque[list[tuple]] = deque()
        while not self._stop.is_set():
            self._iterate(pending)
        while pending:
            for entry in pending.popleft():
                self._process_entry(entry)

    def _recover(self, error: BaseException) -> None:
        """Quarantine-and-rebuild after a loop crash, WITHOUT failing
        untouched work: in-flight slots and long-prefill streams (their
        device state is suspect — the crashed dispatch may have consumed
        its donated buffers) fail with the error and count as quarantined;
        QUEUED admissions were never dispatched, so they stay queued and
        are served after the restart. Every device-resident array is
        rebuilt from scratch — with buffer donation there is no safe way
        to keep using arrays a failed dispatch may have invalidated."""
        quarantined = 0
        # teardown STRICTLY BEFORE _finish: the waiter wakes INSIDE _finish
        # (on_done / result()), and anything it reads right away — active
        # slots, stats(), the slot's token list — must already reflect the
        # quarantine. Finishing first left a window where a woken waiter
        # observed its own half-torn slot (the finish-waker race; the
        # regression test loses it deterministically under the injector).
        finished: list[tuple[GenerationRequest, GenerationResult]] = []
        for i, slot in enumerate(self._slots):
            request = slot.request
            if request is not None:
                quarantined += 1
                result = GenerationResult(
                    tokens=list(slot.generated), finish_reason="error",
                    prompt_tokens=len(request.prompt_tokens),
                    ttft_s=0, total_s=0, error=error,
                )
                slot.request = None
                slot.generated = []
                slot.position = 0
                slot.last_token_at = 0.0
                self._slot_clear_agentic(i)
                finished.append((request, result))
        for idx in list(self._longs):
            st = self._longs.pop(idx)
            quarantined += 1
            self._reserved.discard(idx)
            finished.append((st["request"], GenerationResult(
                tokens=[], finish_reason="error", prompt_tokens=0,
                ttft_s=0, total_s=0, error=error,
            )))
        with self._stats_lock:
            self.quarantined_slots_total += quarantined
        self._longs.clear()
        self._reserved.clear()
        for request, result in finished:
            request._finish(result)
        self._inflight_steps = 0
        # PRNG reset is an SPMD determinism measure (both hosts re-key
        # from seed+epoch); a single-host restart keeps its live key —
        # the pre-round-19 behavior, nothing cross-host to protect
        self._rebuild_device_state(reset_key=self._spmd is not None)
        if isinstance(error, EngineWedgedError):
            # the fetch worker may still be parked inside the hung
            # device_get that tripped the watchdog — every post-recovery
            # fetch would queue BEHIND it on the FIFO and re-wedge until
            # the restart budget burned down to the old crash-only
            # outcome. Abandon it like the device arrays (its late
            # result lands in an orphaned handle) and start fresh.
            log.warning("abandoning the wedged fetch worker")
            self._fetcher = _TokenFetcher(self._injector, self._obs, self._wake.set)
        if not self._fetcher.alive():
            self._fetcher.start()

    def _rebuild_device_state(self, reset_key: bool = True) -> None:
        """Deterministic device-state rebuild after a loop crash — every
        device-resident array is remade from scratch (with buffer donation
        there is no safe way to keep arrays a failed dispatch may have
        invalidated), same shapes so no recompiles land on restart.

        SHARED by the leader's ``_recover`` and the SPMD follower's
        OP_RECOVER replay (``_spmd_follower_recover``): same config + same
        epoch ⇒ byte-identical post-recovery state on every host — the
        OP_WARMUP rule applied to recovery (docs/SERVING.md §20). With
        ``reset_key`` the fresh PRNG key derives from seed + recovery
        epoch so even SAMPLED streams stay host-identical after a
        recovery (the crashed dispatch may have consumed the key on one
        side only)."""
        self._freed_slots.clear()
        self._spec_index.clear()
        self._step_time_ema_s = self._last_step_s = 0.0
        self._last_chunk_ready_t = 0.0
        self._last_ready_t = 0.0
        self._last_fetch, self._launch_unfetched = None, False
        self._moe_dev = None
        # fresh device state (same shapes → no recompiles on restart): the
        # pool buffer is donation-suspect; the allocator and every table
        # reset with it (the in-flight slots whose pages they tracked were
        # just failed above). Queued and page-deferred admissions keep their
        # backlog spots.
        self._pending_page_zero.clear()
        self._pending_window_zero.clear()
        # tiered KV: quiesce the spill worker BEFORE resetting the
        # arena (stop() completes in-flight copies first, so no thread
        # writes a slot the fresh free list is about to re-issue);
        # stale done-handles are fenced off by the generation bump
        spill_quiesced = True
        if self._spill_worker is not None:
            spill_quiesced = self._spill_worker.stop()
        self._spill_gen += 1
        self._spill_candidates.clear()
        while True:
            try:
                self._spill_done.get_nowait()
            except queue.Empty:
                break
        if self._host_tier is not None:
            if spill_quiesced:
                self._host_tier.reset()
            else:
                # the worker is wedged past the join timeout (hung
                # device fetch — the very failure mode recovery
                # handles) and may still write into whatever arena it
                # holds a reference to. Resetting THAT arena would let
                # the late write land in a slot the fresh free list
                # re-issued, with a valid checksum: silent wrong KV at
                # a later restore. Abandon arena AND worker — the
                # straggler's writes land in orphaned memory
                log.error(
                    "spill worker failed to quiesce — abandoning the "
                    "host arena (%.2f GiB) and spawning a fresh one",
                    self._host_tier.bytes_total / 1024**3,
                )
                from langstream_tpu.serving.pagepool import HostPageTier

                self._host_tier = HostPageTier(
                    self._pagepool.dev, self._host_tier.num_pages
                )
                if self._prefix_index is not None:
                    self._prefix_index.host_tier = self._host_tier
                self._spill_worker = _SpillWorker(
                    self._host_tier, self._spill_done, self._obs
                )
        self._pagepool.reset()
        if self.mesh is not None:
            from langstream_tpu.parallel.sharding import shard_page_pool

            self._pagepool.dev = shard_page_pool(
                self._pagepool.dev, self.mesh
            )
        if self._prefix_index is not None:
            self._prefix_index.reset()
        if self._spill_worker is not None:
            self._spill_worker.start()
        self._tokens_dev = jnp.zeros(self.max_batch, jnp.int32)
        self._positions_dev = jnp.zeros(self.max_batch, jnp.int32)
        self._block_dev = self._fresh_block_state(self.max_batch, self.config)
        self._temp_dev = jnp.zeros(self.max_batch, jnp.float32)
        self._top_k_dev = jnp.zeros(self.max_batch, jnp.int32)
        self._top_p_dev = jnp.ones(self.max_batch, jnp.float32)
        if self._dfa_state_dev is not None:
            self._dfa_state_dev = jnp.zeros(self.max_batch, jnp.int32)
        if reset_key:
            self._key = jax.random.PRNGKey(self._rng_seed + self._spmd_epoch)

    def _spmd_follower_recover(self, epoch: int) -> None:
        """Follower half of OP_RECOVER (parallel/spmd_serving.py): adopt
        the leader's recovery epoch and run the identical deterministic
        rebuild. The follower never owns requests or a queue — only its
        device arrays and page tables evolve — so the rebuild IS its whole
        recovery; replay resumes at the epoch-base seq afterwards."""
        self._spmd_epoch = int(epoch)
        self._rebuild_device_state()

    def _iterate(self, pending) -> None:
        """ONE fused engine iteration: a token-budgeted slice of pending
        prefill work (chunked-prefill segments first, then admission groups)
        dispatched back-to-back with the decode chunk — two async dispatches
        on the in-order device stream, so the prefill slice and the chunk
        interleave at iteration granularity and neither backlog starves the
        other. Extracted from _run so tests can drive exactly one iteration
        (the engine thread just loops this)."""
        obs_on = self._obs.on
        self._iterations_total += 1
        t0 = time.monotonic() if obs_on else 0.0
        # each phase is also a `jax.profiler.TraceAnnotation` (a no-op
        # while no profile runs): a profile holds them on the host plane
        # beside the device's lines, so an idle gap on the device can be put
        # down to the phase the engine thread was in, and to the engine's
        # state there: live rows, queue depth, dispatches launched and not
        # yet processed
        state = self._loop_state(pending)
        launch = LAUNCH_REASONS[0]
        spec_on = self._spec_enabled and not (
            # brownout level 2 (spec-off) falls back to plain decode
            # chunks — token-exact for greedy streams by the round-9
            # invariant, so in-flight work is never degraded in
            # correctness, only in weight-read amortization
            self._brownout is not None and self._brownout.spec_off
        )
        self._spill_ms_iter = 0.0
        self._restore_ms_iter = 0.0
        with jax.profiler.TraceAnnotation("engine.sweep", **state):
            self._sweep_duties()
            # What is dispatched below queues behind what is in flight, so
            # the launch is decided as late as the device allows. While a
            # dispatched chunk is unfetched, a tenth of it (`grace`; 19 ms
            # of a 190 ms chat chunk) is both the pause before an admission
            # and the margin ahead of the chunk's expected end at which the
            # next launch is due. Under one interpreter switch interval it
            # is not worth a sleep, so a fast model never waits, nor an
            # idle engine or a cold start, which hold nothing unfetched.
            grace = 0.1 * self._step_time_ema_s * self.decode_chunk
            waits = pending and grace >= sys.getswitchinterval()
            if waits and (spec_on or self._admission_waits()):
                # a request, or a long prompt's next segment, waits already
                # (every iteration of a backlog), or the loop speculates
                # (it drains its one verify before it proposes: there is no
                # launch to hold back): pause, admit, launch. In
                # the pause the loop thread, held off the interpreter while
                # this thread delivered, hands over the requests it holds,
                # arrivals a few ms apart share one group, and a schedule
                # does not turn on which thread won the interpreter
                # (PERF.md §6, PR 29).
                with jax.profiler.TraceAnnotation("engine.grace"):
                    time.sleep(grace)
                waits = False
        t_sweep = time.monotonic() if obs_on else 0.0
        if waits:
            # nothing to admit: launching now would only put the next chunk,
            # and the group of whoever arrives next, a whole chunk early
            with jax.profiler.TraceAnnotation("engine.await", **state):
                launch = self._await_launch(pending, grace)
            state = self._loop_state(pending)
        t_await = time.monotonic() if obs_on else 0.0
        with jax.profiler.TraceAnnotation("engine.admit", **state):
            # chunks dispatched in previous iterations are still unfetched when
            # this iteration's dispatch computes its headroom bound — subtract
            # ALL of them
            self._inflight_steps = sum(
                e[3] for batch in pending for e in batch if e[0] == "chunk"
            )
            had_active = any(s.active for s in self._slots)
            # the first launch below says whether the device had run dry
            # under live rows (the speculative loop drains by design)
            self._late_probe = had_active and not spec_on
            self._launched_late = False
            # the fused-iteration prefill budget. Long prefill FIRST: it
            # claims a freed slot before _admit hands them all to short
            # requests, so a long prompt can't be starved forever under
            # sustained short traffic.
            budget = self.prefill_token_budget
            # _mid_iteration marks drain()'s pop-to-slot blind spot: a request
            # get_nowait()'d here but not yet visible as an active slot exists
            # only inside this admission phase, so _quiesced() (sampling from
            # the drain caller's thread) must not report quiet during it —
            # while staying False on idle iterations, which never pop anything
            self._mid_iteration = True
            try:
                new_pending, spent = self._long_step(budget)
                n_long_entries = len(new_pending)
                new_pending.extend(self._admit(max(0, budget - spent)))  # deferred first-token fetches
            finally:
                self._mid_iteration = False
            # prefill dispatched this iteration rides the in-order stream AHEAD
            # of the chunk below — its chunk must not feed the step-time gauge
            prefill_ahead = bool(new_pending) or spent > 0
        t_prefill = time.monotonic() if obs_on else 0.0
        idle = False
        with jax.profiler.TraceAnnotation(
            "engine.dispatch", **self._loop_state(pending, len(new_pending))
        ):
            n_admitted = sum(
                len(e[2]) for e in new_pending if e[0] == "prefill"
            )
            # prefill tokens this iteration = long-segment tokens (``spent``) +
            # the ADMISSION groups' prompts (entries past the _long_step slice
            # — a long prompt's final-segment entry must not double-count the
            # segments already in ``spent``)
            prefill_tokens = spent + sum(
                len(req.prompt_tokens)
                for e in new_pending[n_long_entries:]
                if e[0] == "prefill"
                for _, req in e[2]
            )
            if new_pending and not had_active:
                # cold start (nothing was decoding): there is no compute
                # to overlap the deferred fetch with, and the fetch would
                # otherwise queue BEHIND the first decode chunk dispatched
                # below (~a full chunk of extra TTFT).
                # Do NOT widen this to low-but-nonzero occupancy: an
                # inline fetch under ANY active decode serializes the
                # loop on the in-flight chunk and collapsed the chat
                # bench to 740 tok/s / 14.8s p50 TTFT when tried (r4)
                for entry in new_pending:
                    self._process_entry(entry)
                new_pending = []
            if spec_on and (
                new_pending or pending or any(s.active for s in self._slots)
            ):
                # self-speculation serializes the host loop on fetched results:
                # the next iteration's drafts must CONTINUE from the last
                # accepted token, which only the previous verify's (and this
                # iteration's prefill entries') fetch knows. Drain everything
                # before proposing — the conscious pipelining trade the verify
                # dispatch's k+1-tokens-per-weight-read amortization buys back
                # (docs/SERVING.md §10 has the tuning story).
                while pending:
                    for entry in pending.popleft():
                        self._process_entry(entry)
                for entry in new_pending:
                    self._process_entry(entry)
                new_pending = []
                if any(s.active for s in self._slots):
                    new_pending.append(self._dispatch_verify(
                        clean=not prefill_ahead
                    ))
                    disp_kind, disp_steps = "verify", self.spec_tokens + 1
                else:
                    disp_kind, disp_steps = "", 0
            elif any(s.active for s in self._slots):
                new_pending.append(self._dispatch_chunk(
                    clean=not prefill_ahead,
                    # a chunk dispatched while earlier chunks are still in
                    # flight executes back-to-back with them on the in-order
                    # stream — its step time is the inter-COMPLETION interval,
                    # not dispatch→ready wall (which would double-count the
                    # predecessor still running at dispatch time)
                    pipelined=self._inflight_steps > 0,
                ))
                disp_kind, disp_steps = "decode", new_pending[-1][3]
            else:
                disp_kind, disp_steps = "", 0
                idle = not new_pending and not pending and not self._longs
        if idle:
            # nothing to launch and nothing in flight: the idle millisecond
            # under its own name, so `engine.dispatch` holds launches only
            with jax.profiler.TraceAnnotation("engine.idle"):
                time.sleep(0.001)
        self._late_probe = False
        t_dispatch = time.monotonic() if obs_on else 0.0
        waited_before = self._wait_s_iter
        pending.append(new_pending)
        # process the oldest batch when its device arrays are READY
        # (no host block, completions/first tokens discovered at
        # chunk granularity), or unconditionally once the pipeline
        # is full / nothing new was dispatched to overlap with
        while pending and (
            len(pending) > self.pipeline_depth
            or not new_pending
            or self._batch_ready(pending[0])
        ):
            for entry in pending.popleft():
                self._process_entry(entry)
        launched = bool(disp_kind) or prefill_ahead
        if launched:
            self._launches[launch] += 1
            self._launches["late"] += self._launched_late
        if obs_on and (disp_kind or n_admitted or spent or had_active):
            # flight-recorder frame — idle iterations (nothing active,
            # nothing dispatched) are skipped so the ring holds ~N frames
            # of actual WORK leading up to an incident, not sleep noise.
            # One dict build per iteration (not per token), handed to both
            # the ring and — as the `engine.iteration` span's attributes —
            # the tracer.
            t_end = time.monotonic()
            process_ms = (t_end - t_dispatch) * 1e3
            wait_ms = (self._wait_s_iter - waited_before) * 1e3
            frame = {
                "i": self._iterations_total,
                "t": round(time.time(), 3),
                "active": sum(1 for s in self._slots if s.active),
                "queued": self._queue.qsize(),
                "longs": len(self._longs),
                "admitted": n_admitted,
                "prefill_tokens": prefill_tokens,
                "dispatch": disp_kind,
                "steps": disp_steps,
                # how the launch was decided (LAUNCH_REASONS), and whether
                # it found live rows and nothing in flight
                "launch": launch if launched else "",
                "late": self._launched_late,
                "kv_pages": (
                    self._pagepool.pages_in_use if self._pagepool else 0
                ),
                # host-tier occupancy (tiered KV): arena slots holding
                # hibernated prefix pages; 0 with the tier off
                "host_pages": (
                    self._host_tier.slots_in_use if self._host_tier else 0
                ),
                "programs": len(self._programs),
                "injector": (
                    dict(self._injector.fired)
                    if self._injector is not None
                    else {}
                ),
                "phase_ms": {
                    "sweep": round((t_sweep - t0) * 1e3, 3),
                    # `_await_launch`: waiting for an arrival or the launch
                    # deadline, and what landed meanwhile
                    "await": round((t_await - t_sweep) * 1e3, 3),
                    "prefill": round((t_prefill - t_await) * 1e3, 3),
                    "dispatch": round((t_dispatch - t_prefill) * 1e3, 3),
                    "process": round(process_ms, 3),
                    # process = waiting for the device's results (the
                    # fetches of the entries processed after the dispatch)
                    # + delivering their tokens
                    "wait": round(wait_ms, 3),
                    "deliver": round(max(0.0, process_ms - wait_ms), 3),
                    # spill = this iteration's hibernation bookkeeping
                    # (snapshot dispatch + drain); restore = host→device
                    # upload time inside admissions. Both host-wall ms.
                    "spill": round(self._spill_ms_iter, 3),
                    "restore": round(self._restore_ms_iter, 3),
                },
            }
            self._obs.flight.record(frame)
            emit_dispatch_span("engine.iteration", t0, t_end, frame)

    def _sweep_duties(self) -> None:
        """What the engine thread owes at the top of an iteration and after
        every wake-up of `_await_launch`, whether or not a launch follows;
        each is O(1) when it has nothing to do."""
        # SPMD slice resilience (§20): the spmd-crash drill site, the
        # divergence-resync poll, and the idle heartbeat, OUTSIDE any
        # dispatch's announce sequence
        if self._spmd is not None:
            self._spmd_tick()
        if self._pending_page_zero or self._pending_window_zero:
            self._flush_page_zeros()
        # tiered KV: fold completed spills in and start hibernation spills
        # for idle prefixes — bounded per call, O(1) when idle; the restore
        # half runs inside admission (_paged_bind) where it gates
        if self._spill_on:
            self._spill_tick()
        # KV-page migration commands (snapshot/bind/release — §18) cross
        # into the engine-thread domain here; O(1) when idle (one
        # SimpleQueue emptiness check). The idle loop spins at ~1ms and a
        # command wakes `_await_launch`, so a migration waits behind a
        # launch or a delivery, never behind a chunk's length
        self._drain_migrations()
        self._sweep_waiting()
        # brownout ladder (docs/SERVING.md §19): throttled load check on
        # the engine thread — transitions count, dump and log here
        if self._brownout is not None:
            self._brownout_tick()
        # deterministic noisy-neighbor drill: the `tenant-burst` fault
        # site injects a synthetic aggressor burst here
        if self._injector is not None:
            self._tenant_burst_tick()

    def _admission_waits(self) -> bool:
        """Something waits that the admission phase would take up: a queued
        request, a long prompt's open stream or backlog, an admission
        deferred for pages or held back."""
        return bool(
            self._queue.qsize() or self._longs or self._long_queue
            or self._page_deferred or self._held_back is not None
        )

    def _await_launch(self, pending, grace: float) -> str:
        """With work in flight and nothing to admit, wait for what decides
        the next launch, on one event with three sources. An ARRIVAL
        (`submit` sets the event): pause ``grace`` as before any admission,
        but not past the deadline, and go on to admit and launch; the group
        still queues behind the chunk in flight, for what is left of it. A
        result that LANDED (the fetch thread sets it): processed here, in
        order, without a launch, so a first token is delivered when it is
        on the host and not at the next launch. The DEADLINE: the expected
        end of the oldest batch in flight less ``grace``, when the next
        chunk has to follow for the device not to run dry
        (`_launch_deadline`). A wait lasts at most ``grace`` at a time and
        the sweep's duties run after each, so none of them waits out a
        chunk. Returns the reason to launch now, one of LAUNCH_REASONS[1:]."""
        while pending and not self._stop.is_set():
            rest = self._launch_deadline(pending, grace) - time.monotonic()
            if self._admission_waits():
                if rest > 0:
                    with jax.profiler.TraceAnnotation("engine.grace"):
                        time.sleep(min(grace, rest))
                return LAUNCH_REASONS[1]
            if rest <= 0:
                break
            self._wake.wait(min(grace, rest))
            # cleared BEFORE the look at what it may have signalled: a
            # signal after this makes the next wait return at once
            self._wake.clear()
            self._take_landed(pending)
            self._sweep_duties()
        return LAUNCH_REASONS[2]

    def _take_landed(self, pending) -> None:
        """Process the entries at the head of ``pending`` whose result the
        fetch thread has on the host, in dispatch order. Never one that
        would block: an inline fetch under a live decode serializes the
        loop on the chunk in flight (the cold-start branch's r4 note)."""
        while pending:
            batch = pending[0]
            while batch and getattr(batch[0][1], "done", False):
                self._process_entry(batch.pop(0))
            if batch:
                return
            pending.popleft()

    def _launch_deadline(self, pending, margin: float) -> float:
        """The monotonic instant by which the next chunk has to be launched:
        the expected end of the oldest batch in flight, less ``margin``.
        The batch's chunk started when the result before it was ready
        (`_last_ready_t`), or at its own launch if that came later, and
        runs its steps at the step-time EMA, or at the EMA's newest sample
        where that is shorter: an estimate that runs short launches as
        early as the loop used to, one that runs long leaves the device
        dry, and the EMA forgets a stale level (another occupancy, a slow
        first execution) by a tenth a sample. A prefill group ahead of the
        chunk in the batch makes this early until the group lands and moves
        `_last_ready_t`; a batch without a chunk is due now. The oldest
        batch, not all of them: at `pipeline-depth` n the n−1 younger
        chunks stay queued behind it, as they are today."""
        step_s = min(self._step_time_ema_s, self._last_step_s or self._step_time_ema_s)
        for entry in pending[0]:
            if entry[0] == "chunk":
                return max(self._last_ready_t, entry[4]) + entry[3] * step_s - margin
        return 0.0

    def _sweep_waiting(self) -> None:
        """Resolve queued-but-unadmitted requests that died while waiting
        (cancelled, expired deadline/max-queue-wait) WITHOUT waiting for a
        slot to free: queue.Queue is opaque, so the sweep walks the shadow
        _waiting dict, the long-prompt backlog, and the held-back slot; a
        swept request's queue entry is skipped at pop time (_done already
        set). Bounded by the queue depth (≤ max_batch×4 by default), so
        this is noise next to a device dispatch."""
        now = time.monotonic()
        with self._waiting_lock:
            waiting = list(self._waiting.values())
        for request in waiting:
            if request._done.is_set() or self._resolve_if_dead(request, now):
                with self._waiting_lock:
                    self._waiting.pop(id(request), None)
        # the long-prompt backlog, page-deferred list + held-back slot are
        # engine-thread-only
        self._long_queue = [
            r for r in self._long_queue
            if not (r._done.is_set() or self._resolve_if_dead(r, now))
        ]
        self._page_deferred = [
            r for r in self._page_deferred
            if not (r._done.is_set() or self._resolve_if_dead(r, now))
        ]
        if self._held_back is not None and (
            self._held_back._done.is_set()
            or self._resolve_if_dead(self._held_back, now)
        ):
            self._held_back = None

    def _current_load_score(self) -> float:
        """The brownout controller's input: the §12 load-score formula
        over CURRENT signals. The wait term is the queue-wait EMA gated
        on an actual backlog — NOT the stats() histogram p90, which is
        cumulative and would hold the ladder engaged forever after one
        bad burst (the full-reversal contract), and not the bare EMA,
        which freezes at its last value the moment the queue empties."""
        backlog_wait = (
            self._queue_wait_ema_s if self._queue.qsize() > 0 else 0.0
        )
        pool = self._pagepool
        page_pressure = (
            pool.pages_in_use / max(1, pool.num_pages)
            if pool is not None
            else min(1.0, self._queue.qsize() / max(1, self._queue.maxsize))
        )
        occupancy = (
            sum(1 for s in self._slots if s.active) / max(1, self.max_batch)
        )
        return load_score(backlog_wait, occupancy, page_pressure)

    def _brownout_tick(self) -> None:
        """Advance the brownout ladder off the current load score
        (throttled — the p90 walk is cheap but not free at a ~1ms idle
        loop). A transition in EITHER direction is counted, logged and
        flight-dumped (`brownout` reason, debounced by the recorder) —
        the full reversal back to level 0 is part of the contract."""
        now = time.monotonic()
        if now - self._brownout_checked_at < 0.05:
            return
        self._brownout_checked_at = now
        transition = self._brownout.observe(self._current_load_score(), now)
        if transition is None:
            return
        old, new = transition
        snap = self._brownout.snapshot()
        log.warning(
            "brownout %s: level %d -> %d (step %s, load %.3f)",
            "escalated" if new > old else "released",
            old, new, snap["step"], snap["last-load"],
        )
        dumped = self._flight_dump("brownout", extra={
            "brownout-from": old,
            "brownout-to": new,
            "brownout-step": snap["step"],
            "load-score": snap["last-load"],
        })
        if dumped is not None:
            with self._stats_lock:
                self.brownout_dumps_total += 1

    BURST_TENANT = "chaos-burst"

    def _tenant_burst_tick(self) -> None:
        """`tenant-burst` fault site (docs/SERVING.md §19): when the
        schedule fires, enqueue a burst of synthetic low-priority
        admissions under the "chaos-burst" tenant — the deterministic
        aggressor of the noisy-neighbor drill. The burst takes the normal
        submit bookkeeping EXCEPT the blocking put (the engine thread
        must never park on its own full queue): full-queue/share
        rejections count as the aggressor's sheds, exactly what the drill
        asserts the victim never absorbs."""
        if not self._injector.fires("tenant-burst"):
            return
        for j in range(self.max_batch):
            prompt = [3 + (j % 5), 5, 7, 11, 13, 17, 19, 23]
            request = GenerationRequest(
                prompt_tokens=prompt,
                options=GenerationOptions(
                    max_new_tokens=16,
                    tenant=self.BURST_TENANT,
                    priority="low",
                ),
            )
            self._tenants.note_submit(self.BURST_TENANT)
            if self._draining:
                self._count_shed(self.BURST_TENANT)
                continue
            with self._waiting_lock:
                self._waiting[id(request)] = request
                if self._obs.on:
                    self._open[id(request)] = request
            try:
                self._queue.put_nowait(request)
            except (queue.Full, TenantShareExceeded):
                with self._waiting_lock:
                    self._waiting.pop(id(request), None)
                    self._open.pop(id(request), None)
                self._count_shed(self.BURST_TENANT)

    @staticmethod
    def _batch_ready(batch: list[tuple]) -> bool:
        """True when every device array in the batch has materialized (the
        fetch would not block). Backends without is_ready() report ready —
        degrading to depth-1 behavior, never deadlock."""
        for entry in batch:
            handle = entry[1]
            if isinstance(handle, _Fetch):
                if handle.done:
                    continue  # fetch thread already landed the bytes
                handle = handle.array
            arr = handle
            is_ready = getattr(arr, "is_ready", None)
            if is_ready is None:
                continue
            try:
                if not is_ready():
                    return False
            except Exception:  # noqa: BLE001 — treat probe failure as ready
                continue
        return True

    def _fetch_result(self, handle):
        """Materialize one deferred fetch. Under SPMD with the watchdog
        armed, the wait is BOUNDED by ``spmd-watchdog-s``: a fetch that
        never lands (a wedged device) raises EngineWedgedError
        out of the iteration, and the supervisor escalates to the
        coordinated OP_RECOVER — a leader must never hang the whole slice
        on one dispatch (docs/SERVING.md §20). Single-host keeps the
        unbounded wait (a pod-local hang has pod-local blast radius)."""
        t0 = time.monotonic() if self._obs.on else 0.0
        with jax.profiler.TraceAnnotation("engine.process.wait"):
            if not isinstance(handle, _Fetch):
                value = np.asarray(jax.device_get(handle))
            else:
                bound = getattr(self._spmd, "watchdog_s", 0) if self._spmd else 0
                value = handle.result(timeout_s=bound if bound > 0 else None)
        if self._obs.on:
            self._wait_s_iter += time.monotonic() - t0
        return value

    def _process_entry(self, entry: tuple) -> None:
        kind = entry[0]
        if kind == "prefill":
            # ONE fetch for the whole prefill group, not one per request;
            # the fetch thread has usually landed the bytes already
            _, first_dev, group, disp = entry
            first = self._fetch_result(first_dev)
            self._land_dispatch(disp, first_dev)
            now = time.monotonic()
            if self.config.fills_blocks:
                # the prefill yields no token: `engine.prefill` ends here,
                # the first block's passes belong to `engine.decode`
                behind, device, ready = disp.stages if disp is not None else (0, 0, now)
                for idx, request in group:
                    slot = self._slots[idx]
                    if slot.request is request:
                        slot.prefill_landed_at = now
                        if disp is not None and self._obs.on:
                            slot.stages = (behind, device, now - ready)
                return
            stages = None
            if disp is not None and self._obs.on:
                # what the first token waited for past its admission: behind
                # what was in flight, on the device, and (fetch thread's
                # stamp → this one) on the host, undelivered
                behind, device, ready = disp.stages
                stages = (behind, device, now - ready)
            with jax.profiler.TraceAnnotation("engine.process.deliver"):
                for j, (idx, request) in enumerate(group):
                    slot = self._slots[idx]
                    if slot.request is not request:
                        continue
                    slot.first_token_at = now
                    slot.stages = stages
                    slot.last_token_at = now  # inter-token clock starts here
                    if self._obs.on:
                        self._obs.record(
                            "engine_ttft_s", now - request.submitted_at
                        )
                    # per-tenant TTFT (the noisy-neighbor drill's victim-p99
                    # evidence — docs/SERVING.md §19); engine thread only,
                    # the histogram single-writer contract
                    self._tenants.note_ttft(
                        getattr(request.options, "tenant", None)
                        or DEFAULT_TENANT,
                        now - request.submitted_at,
                    )
                    self._deliver_token(idx, int(first[j]))
        elif kind == "segment":
            _, first_dev, disp = entry
            self._fetch_result(first_dev)
            self._land_dispatch(disp, first_dev)
        elif kind == "verify":
            self._process_verify(entry)
        else:
            _, chunk, snapshot, steps, t_dispatch, clean, pipelined, disp = entry
            self._process_chunk(
                chunk, snapshot, steps, t_dispatch, clean, pipelined, disp
            )

    def _loop_state(self, pending, launched: int = 0) -> dict[str, int]:
        """What a phase annotation of `_iterate` says of the engine: live
        rows, queue depth, and the dispatches launched and not yet landed
        (``launched`` of them this iteration). Counted only while a profile
        runs: the idle loop turns a thousand times a second."""
        if not _profiling():
            return {}
        return {
            "active": sum(1 for s in self._slots if s.active),
            "queued": self._queue.qsize(),
            "inflight": launched + sum(len(batch) for batch in pending),
        }

    def _new_dispatch(
        self, name: str, stages: Optional[list[float]] = None, **attrs: Any
    ) -> Optional[Dispatch]:
        """Number one device dispatch and start its span's record (None
        when nothing will read it: observability off and a dense model).
        Call just before the launch; the entry's fetch takes
        ``_moe_counts`` right after it. With observability on, the launch
        also closes the device's unfed stretch, if one is open."""
        self._dispatch_seq += 1
        if self._late_probe:
            # the iteration's first launch, with rows live: late when every
            # earlier result is on the host already (the device ran dry)
            self._late_probe = False
            last = self._last_fetch
            self._launched_late = last is not None and last.ready_at > 0
        if not (self._obs.on or self.config.is_moe):
            return None
        attrs["seq"] = self._dispatch_seq
        start = time.monotonic()
        if self._obs.on:
            self._account_launch(start, attrs)
        return Dispatch(name, start, attrs, stages)

    def _unfed_stretch(
        self, now: float, closing: bool = False
    ) -> Optional[tuple[float, float]]:
        """The stretch the device has had nothing to do, if one is open at
        ``now``: (its seconds, the part of them with a request open in the
        engine). Open: every dispatch launched has its result on the host
        (`_Fetch.ready_at`); the stretch began at the last of those, or
        where the account did. A request is open from ``submitted_at``
        until it resolved (``total_s`` later: the instant its spans end).
        None while something is in flight. ``closing`` (a launch, which
        ends the stretch) forgets the resolved; a launch behind work in
        flight only once they have doubled what it keeps. O(open requests)."""
        last = self._last_fetch
        start = None
        if not (self._launch_unfetched or (last is not None and not last.ready_at)):
            start = max(last.ready_at if last is not None else 0.0, self._account_t0)
        elif not (closing and len(self._open) > self._open_prune_at):
            return None
        with self._waiting_lock:
            opened = list(self._open.items())
        held, resolved = [], []
        for key, request in opened:
            end = now
            if request._done.is_set():
                resolved.append(key)
                result = request._result
                end = request.submitted_at + (result.total_s if result else 0.0)
            if start is not None and end > start and request.submitted_at < now:
                held.append((max(request.submitted_at, start), min(end, now)))
        if closing:
            with self._waiting_lock:
                for key in resolved:
                    self._open.pop(key, None)
            self._open_prune_at = 2 * (len(opened) - len(resolved)) + 16
        if start is None:
            return None
        covered, edge = 0.0, start
        for lo, hi in sorted(held):
            if hi > edge:
                covered += hi - max(lo, edge)
                edge = hi
        return max(0.0, now - start), covered

    def _account_launch(self, now: float, attrs: dict[str, Any]) -> None:
        """A launch at ``now`` ends the unfed stretch, if one was open: its
        seconds go to `device-unfed-s` / `-with-request-s` and onto the
        launch's span. Until `_submit_fetch` has this launch's fetch it
        counts as in flight (a segment that is never fetched: until a later
        one is)."""
        with self._stats_lock:
            stretch = self._unfed_stretch(now, closing=True)
            self._launch_unfetched = True
            if stretch is not None:
                self._unfed_s += stretch[0]
                self._unfed_request_s += stretch[1]
        if stretch is not None:
            attrs["unfed_ms"] = round(stretch[0] * 1e3, 3)
            attrs["unfed_with_request_ms"] = round(stretch[1] * 1e3, 3)

    def _submit_fetch(self, array, seq: int = 0, counts=None) -> _Fetch:
        """Hand a launch's result to the fetch thread; its ``ready_at``
        is what says the device has finished everything launched so far."""
        handle = self._fetcher.submit(array, seq, counts)
        self._last_fetch, self._launch_unfetched = handle, False
        return handle

    def _moe_counts(self):
        """The MoE counts the launch just returned, for the fetch that
        carries the dispatch's result (None: a dense model's constant
        zeros are not fetched)."""
        return self._moe_dev if self.config.is_moe else None

    def _new_segment_dispatch(
        self, program: str, width: int, real_tokens: int,
        request: GenerationRequest, stages: Optional[list[float]] = None,
    ) -> Optional[Dispatch]:
        """The record of an `engine.prefill_segment` span: a warm suffix,
        a ring admit, or the FIRST segment of a long prompt's stream
        (``_segment_step`` adds each later segment to it and the span is
        emitted once, when the final segment's first token lands — only
        that segment has a fetch to time). The segment programs return no
        MoE counts, except for a model that holds its experts
        (``config.holds_experts``: window layers' parallel block, or the
        sequential block with ``experts_held``): there every segment is
        fetched for its counts and is a span of its own, and the stream's
        dispatches sum their ``stages`` into one list."""
        return self._new_dispatch(
            "engine.prefill_segment", stages, program=program, rows=1,
            real_rows=1, width=width, segments=1, real_tokens=real_tokens,
            computed_tokens=width, trace_ids=[request.trace_id],
        )

    def _land_dispatch(self, disp: Optional[Dispatch], handle) -> None:
        """The dispatch's result is on the host: fold its MoE counts into
        the totals and emit its span (once, here — the request spans'
        rule). ``handle`` is the entry's fetch; the fetch thread stamped
        when its bytes landed and brought the counts with them."""
        end = getattr(handle, "ready_at", 0.0) or time.monotonic()
        prev, self._last_ready_t = self._last_ready_t, end
        if disp is None:
            return
        attrs = disp.attrs
        counts = getattr(handle, "counts", None)
        if counts is not None:
            counts = [int(v) for v in counts]
            attrs.update(
                (f"moe_{n}", c) for n, c in zip(moe_count_names(self.config), counts)
            )
            with self._stats_lock:
                self.moe_routed_total += counts[0]
                self.moe_dropped_total += counts[1]
        if not self._obs.on:
            return
        # the in-order stream ran this dispatch after the one before it:
        # it waited `behind` what was in flight at its launch, and its own
        # device-side time is end - max(start, the previous result's ready)
        behind = max(0.0, prev - disp.start)
        device = end - disp.start - behind
        attrs["behind_ms"] = round(behind * 1e3, 3)
        attrs["device_ms"] = round(device * 1e3, 3)
        disp.stages[0] += behind
        disp.stages[1] += device
        disp.stages[2] = end
        if "real_tokens" in attrs:  # a prefill group or segment stream
            self._obs.record("engine_prefill_group_s", end - disp.start)
            self._prefill_tokens_landed += attrs["real_tokens"]
            self._prefill_landed_s += end - disp.start
        emit_dispatch_span(disp.name, disp.start, end, attrs)

    def _sample_step_time(
        self, snapshot, steps: int, t_dispatch: float, clean: bool,
        pipelined: bool,
    ) -> None:
        """Achieved-bandwidth gauge sample, taken the moment the chunk's
        bytes LAND (before token delivery: a request finishing mid-chunk
        wakes its waiter inside the delivery loop, and the gauge must
        already be current when that caller reads stats() — sampling after
        delivery both raced that read and charged host delivery work to
        device step time). Only CLEAN chunks (no prefill ahead on the
        stream that iteration) are sampled. A PIPELINED chunk (dispatched
        while its predecessor still ran) executes back-to-back on the
        in-order stream, so its device time is the interval since the
        PREVIOUS chunk's completion — dispatch→ready wall would count the
        predecessor's remaining execution too and read ~2× at steady
        state. A non-pipelined chunk (idle stream) uses dispatch→ready
        wall directly. The EMA smooths jitter; the model side is
        _achieved_hbm_gbps."""
        now = time.monotonic()
        step_s = None
        if snapshot and clean:
            if pipelined and self._last_chunk_ready_t > 0:
                step_s = (now - self._last_chunk_ready_t) / max(1, steps)
            elif not pipelined:
                step_s = (now - t_dispatch) / max(1, steps)
        if step_s is not None:
            self._last_step_s = step_s
            self._step_time_ema_s = (
                step_s
                if self._step_time_ema_s == 0
                else 0.9 * self._step_time_ema_s + 0.1 * step_s
            )
            if self._obs.on:
                # per-STEP device time — the EMA's distribution; a fat
                # p99 with a clean p50 is the mid-traffic-compile
                # signature §12 documents
                self._obs.record("engine_decode_step_s", step_s)
        self._last_chunk_ready_t = now

    def _bucket(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    @staticmethod
    def _expired(request: GenerationRequest, now: float) -> bool:
        opts = request.options
        wait = now - request.submitted_at
        return (
            opts.deadline_s is not None and wait >= opts.deadline_s
        ) or (
            opts.max_queue_wait_s is not None and wait > opts.max_queue_wait_s
        )

    def _resolve_if_dead(self, request: GenerationRequest, now: float) -> bool:
        """Resolve a queued-but-unadmitted request that died while waiting
        (client cancel, expired deadline / max-queue-wait) WITHOUT spending
        a slot or prefill FLOPs on it. True = resolved (or already done);
        the single place the cancelled/deadline-in-queue outcome is built,
        shared by the pop gate (_prequalify) and the expiry sweep across
        every backlog (short queue, long backlog, held-back slot)."""
        if request._done.is_set():
            return True  # already resolved elsewhere — don't double-count
        wait = now - request.submitted_at
        tenant = getattr(request.options, "tenant", None) or DEFAULT_TENANT
        if request.cancelled:
            with self._stats_lock:
                self.cancelled_total += 1
            self._tenants.note_cancelled(tenant)
            request._finish(GenerationResult(
                tokens=[], finish_reason="cancelled",
                prompt_tokens=len(request.prompt_tokens),
                ttft_s=0, total_s=wait,
            ))
            self._emit_queued_death_spans(request, "cancelled", now)
            return True
        if self._expired(request, now):
            opts = request.options
            with self._stats_lock:
                self.deadline_queue_total += 1
            self._tenants.note_deadline(tenant)
            request._finish(GenerationResult(
                tokens=[], finish_reason="deadline",
                prompt_tokens=len(request.prompt_tokens),
                ttft_s=0, total_s=wait,
                error=DeadlineExceededError(
                    f"request waited {wait:.2f}s in queue against "
                    f"deadline={opts.deadline_s} "
                    f"max-queue-wait={opts.max_queue_wait_s}"
                ),
            ))
            self._emit_queued_death_spans(request, "deadline", now)
            return True
        return False

    def _emit_queued_death_spans(
        self, request: GenerationRequest, reason: str, now: float
    ) -> None:
        """Trace a request that died before admission: root + queued child
        only (no slot, no prefill, no tokens)."""
        if not self._obs.on:
            return
        emit_request_spans(
            request.trace_id,
            {"submitted": request.submitted_at, "finished": now},
            {
                "slot": -1,
                "path": "queued",
                "prompt_len": len(request.prompt_tokens),
                "generated_tokens": 0,
                "finish_reason": reason,
            },
            status="ok" if reason == "cancelled" else f"error: {reason}",
        )

    def _prequalify(self, request: GenerationRequest) -> bool:
        """Queue-exit gate (engine thread): True = still worth admitting;
        live requests feed the queue-wait EMA that submit()'s
        hopeless-deadline shed reads."""
        now = time.monotonic()
        if self._resolve_if_dead(request, now):
            return False
        wait = now - request.submitted_at
        with self._stats_lock:
            self._queue_wait_ema_s = (
                wait
                if self._queue_wait_ema_s == 0
                else 0.8 * self._queue_wait_ema_s + 0.2 * wait
            )
        self._tenants.note_queue_wait(
            getattr(request.options, "tenant", None) or DEFAULT_TENANT, wait
        )
        if self._obs.on:
            # the DISTRIBUTION the EMA flattens: queue-wait p90 is the
            # dominant term of the load score the balancer routes on
            self._obs.record("engine_queue_wait_s", wait)
        return True

    # -- multi-LoRA + constrained decoding (the agentic tier, ISSUE 10) ------

    def _resolve_agentic(self, request: GenerationRequest) -> bool:
        """Resolve a request's adapter name and grammar to their device
        pool ROWS, refcounting both; idempotent (page-deferred admissions
        retry through here). Failure — unknown adapter, pinned-full pool —
        fails the REQUEST with the error, never the engine. Installs the
        request's _finalize hook so the refcounts release exactly once, on
        whatever path the request eventually finishes (completion, cancel,
        deadline, quarantine, crash recovery — they all funnel through
        _finish)."""
        if request._agentic_rows is not None:
            return True
        from langstream_tpu.serving.adapters import AdapterPoolExhausted

        opts = request.options
        adapter_name = getattr(opts, "adapter", None)
        arow, grow = 0, 0
        try:
            if adapter_name:
                arow = self._adapters.acquire(adapter_name)
            if request._dfa is not None:
                t0 = time.monotonic()
                try:
                    grow = self._constrain_reg.acquire(request._dfa)
                except Exception:
                    if adapter_name:
                        self._adapters.release(adapter_name)
                    raise
                self._note_constrain_host((time.monotonic() - t0) * 1e3)
                with self._stats_lock:
                    self.constrained_requests_total += 1
        except Exception as e:  # noqa: BLE001 — fail the request, not the loop
            log.warning("agentic resolution failed: %s", e)
            if isinstance(e, AdapterPoolExhausted) or (
                request._dfa is not None and "pinned" in str(e)
            ):
                # every row pinned by ACTIVE requests is a transient
                # saturation, not a client error: shed with a retry-after
                # (ShedError → HTTP 429; the front door's paced retries
                # will land once an in-flight tenant finishes) — the
                # contract the registries document
                self._count_shed(
                    getattr(opts, "tenant", None) or DEFAULT_TENANT
                )
                e = ShedError(
                    str(e),
                    retry_after_s=max(self._queue_wait_ema_s, 0.25),
                )
            request._finish(GenerationResult(
                tokens=[], finish_reason="error",
                prompt_tokens=len(request.prompt_tokens),
                ttft_s=0, total_s=0, error=e,
            ))
            return False
        state0 = 0
        if request._dfa is not None:
            resume = getattr(opts, "grammar_resume_state", None)
            if resume is not None:
                state0 = int(resume)
                if not (0 <= state0 < request._dfa.n_states):
                    # an out-of-range resume state means the carried wire
                    # state indexes a DIFFERENT grammar: continuing would
                    # emit off-grammar output dressed as valid — refuse
                    if adapter_name:
                        self._adapters.release(adapter_name)
                    self._constrain_reg.release(request._dfa)
                    request._finish(GenerationResult(
                        tokens=[], finish_reason="error",
                        prompt_tokens=len(request.prompt_tokens),
                        ttft_s=0, total_s=0,
                        error=ValueError(
                            f"grammar-resume-state {state0} is out of range "
                            f"for this grammar ({request._dfa.n_states} "
                            "states) — the resumed stream's grammar does "
                            "not match"
                        ),
                    ))
                    return False
        request._agentic_rows = (arow, grow, state0)

        def _release() -> None:
            if adapter_name:
                self._adapters.release(adapter_name)
            if request._dfa is not None:
                self._constrain_reg.release(request._dfa)

        request._finalize = _release
        return True

    def _slot_bind_agentic(self, idx: int, request: GenerationRequest) -> None:
        """Copy the request's resolved rows into the per-slot dispatch
        state at activation (the moment slot.request is set)."""
        arow, grow, state0 = request._agentic_rows or (0, 0, 0)
        if self._adapters is not None:
            self._adapter_rows[idx] = arow
            self._adapter_rows_auth[idx] = arow
            name = getattr(request.options, "adapter", None)
            if name:
                self._slot_adapter_name[idx] = name
        if self._constrain_reg is not None:
            self._g_rows[idx] = grow
            if request._dfa is not None:
                self._slot_dfa[idx] = request._dfa
                # a mid-derivation fleet resume starts at the carried
                # state, not 0 (§18) — host mirror and device state agree
                # because the admit programs seed their mask from state0
                self._dfa_host_state[idx] = state0
                request.dfa_state = state0

    def _slot_clear_agentic(self, idx: int) -> None:
        if self._adapters is not None:
            self._adapter_rows[idx] = 0
            self._adapter_rows_auth[idx] = 0
            self._slot_adapter_name.pop(idx, None)
        if self._constrain_reg is not None:
            self._g_rows[idx] = 0
            self._slot_dfa.pop(idx, None)
            self._dfa_host_state.pop(idx, None)

    def _note_constrain_host(self, ms: float) -> None:
        """EMA of host-side constrained-decoding bookkeeping (grammar
        residency swaps + per-verify state tables) — the `mask overhead`
        gauge's host half; the device half is what bench_adapters measures
        as the per-step on/off delta."""
        self._constrain_host_ema_ms = (
            ms
            if self._constrain_host_ema_ms == 0
            else 0.9 * self._constrain_host_ema_ms + 0.1 * ms
        )

    def _agentic_args(self) -> tuple:
        """(lora, arows, dfa, g) dispatch inputs. The [B] row arrays are
        host-uploaded per dispatch — tiny, and keeping them host-side is
        what makes the `adapter` fault site's integrity check possible
        (compare dispatch-facing vs authoritative before upload)."""
        lora = self._adapters.pool if self._adapters is not None else None
        arows = (
            jnp.asarray(self._adapter_rows)
            if self._adapters is not None
            else None
        )
        dfa = (
            self._constrain_reg.pool if self._constrain_reg is not None else None
        )
        g = (
            jnp.asarray(self._g_rows)
            if self._constrain_reg is not None
            else None
        )
        return lora, arows, dfa, g

    def _agentic_row_args(self, requests: list, n: int) -> tuple:
        """Per-ROW (not per-slot) adapter/grammar row + initial-DFA-state
        vectors for a batched admission of ``n`` rows: entry j serves
        requests[j]; padding rows ride as base (state 0)."""
        if not self._agentic:
            return None, None, None
        arows = np.zeros(n, np.int32)
        g_rows = np.zeros(n, np.int32)
        g_state0 = np.zeros(n, np.int32)
        for j, request in enumerate(requests[:n]):
            ar, gr, s0 = (
                (request._agentic_rows or (0, 0, 0)) if request else (0, 0, 0)
            )
            arows[j] = ar
            g_rows[j] = gr
            g_state0[j] = s0
        return arows, g_rows, g_state0

    def _adapter_integrity_check(self) -> None:
        """Validate every active slot's dispatch-facing adapter row against
        the authoritative copy before a decode/verify dispatch — the
        `adapter` fault site's detection path (host memory corruption or a
        bookkeeping bug would otherwise serve slot X with tenant Y's
        weights, the worst kind of silent wrong). A mismatch quarantines
        ONLY that slot; every other slot's tokens stay exact (the chaos
        suite asserts both)."""
        if self._adapters is None:
            return
        if self._injector is not None:
            snapshot = [
                (i, s.request) for i, s in enumerate(self._slots) if s.active
            ]
            self._injector.corrupt_adapter_rows(self._adapter_rows, snapshot)
        for i, slot in enumerate(self._slots):
            if not slot.active:
                continue
            if self._adapter_rows[i] == self._adapter_rows_auth[i]:
                continue
            with self._stats_lock:
                self.quarantined_slots_total += 1
            # restore the dispatch-facing row before anything dispatches
            self._adapter_rows[i] = self._adapter_rows_auth[i]
            self._quarantine_pages(i)
            self._flight_dump("adapter-quarantine", extra={"slot": i})
            self._finish_slot(
                i, "error",
                error=RuntimeError(
                    f"adapter-row corruption detected for slot {i}; slot "
                    "quarantined"
                ),
            )

    def _warmup_agentic(self) -> None:
        """Warm the adapter/grammar row-upload programs with out-of-bounds
        rows (every write drops) so the first hot swap under traffic is
        never a mid-traffic compile — the same front-load-the-compiles
        policy as every other warmup."""
        if self._adapters is not None:
            self._adapters.warmup()
        if self._constrain_reg is not None:
            self._constrain_reg.warmup()

    def adapter_advertisement(self) -> tuple[str, ...]:
        """Resident adapter names for the fleet beacon (serving/fleet.py):
        the router scores adapter affinity alongside prefix affinity —
        routing a tenant's request to a replica already holding its
        factors skips a swap dispatch. Names only, never weights."""
        if self._adapters is None:
            return ()
        return self._adapters.advertised()

    def register_adapter(self, spec) -> None:
        """Hot-register an adapter through the control plane (no device
        work until its first request). Thread-safety note: registration
        mutates host bookkeeping the engine thread reads — call while the
        engine serves only OTHER adapters' traffic, or quiesce first."""
        if self._adapters is None:
            raise RuntimeError("this engine has no adapter registry")
        self._adapters.register(spec)

    def _admit(self, budget: Optional[int] = None) -> list[tuple]:
        """Move queued requests into free slots (prefill path); returns ALL
        the deferred first-token fetch entries. Nothing is fetched here —
        entries ride the ready-gated pending pipeline in _run (under active
        decode) or are processed immediately by _run's cold-start branch,
        which delivers a burst's groups progressively (group j's fetch
        overlaps group j+1's device compute since dispatches are async).

        Prefills are BATCHED per prompt bucket: admitting K requests costs
        one forward at batch K (memory-bound: ~the cost of batch 1), not K
        serial dispatches — serial prefill dominated wall-clock when a burst
        filled a large slot pool. Prompts wider than the largest bucket take
        the chunked-prefill path instead (_long_step).

        ``budget``: fused-scheduling token cap for THIS iteration, floored
        at one full admission group. The first group always rides whole (an
        arrival's prefill must make the very next dispatch, and a
        ≤prefill_batch burst still lands in ONE dispatch — the r4
        wave-admission win); past both the budget and a group boundary,
        further queued requests stay queued so the decode chunk dispatched
        right after is never separated from its predecessor by more than
        ~max(budget, one group) of prefill work. None = unbounded (a
        caller that drives one admission by hand).

        ``prefill_batch`` is the LARGEST group: each width's admissions are
        cut into sub-batches of at most that many, a narrow prompt takes the
        free row of a wider group that is dispatched anyway
        (``admission_groups``), and ``_prefill_group`` dispatches each group
        at the smallest rung of ``admit_rungs`` that holds it."""
        free = [
            i
            for i, slot in enumerate(self._slots)
            if not slot.active and i not in self._reserved
        ]
        pairs: list[tuple[int, GenerationRequest]] = []
        admitted_tokens = 0
        short_limit = self.prefill_buckets[-1]
        # page exhaustion gate, sampled ONCE per iteration: while deferred
        # admissions wait for pool pages, only they retry — the queue keeps
        # its entries (and its submit()-side backpressure/shedding)
        allow_new = not self._page_deferred
        # fair-share slot division (docs/SERVING.md §19): tenants admitted
        # THIS call count toward their share immediately, so one pop loop
        # cannot hand a bursting tenant every free slot before the skip
        # set notices
        pending_counts: dict[str, int] = {}
        tenant_occupancy = self._tenant_occupancy()
        # a held-back long request gets first claim on freed backlog space
        if (
            self._held_back is not None
            and len(self._long_queue) < self._long_queue_cap
        ):
            self._long_queue.append(self._held_back)
            self._held_back = None
        for idx in free:
            got_short = False
            while not got_short and self._held_back is None:
                # budget gate, FLOORED at one full admission group: a burst
                # ≤ prefill_batch still lands in ONE dispatch (the r4 wave-
                # admission win — budgeting per-request serialized a 4-wave
                # into 4 iterations and REGRESSED TTFT when first tried);
                # past both the budget and a group boundary, the rest stays
                # queued for the next fused iteration
                if (
                    budget is not None
                    and admitted_tokens >= budget
                    and len(pairs) >= self.prefill_batch
                ):
                    break
                try:
                    request = self._pop_admission(
                        allow_new,
                        skip=self._tenant_slot_skip(
                            tenant_occupancy, pending_counts
                        ),
                    )
                except queue.Empty:
                    break
                req_tenant = (
                    getattr(request.options, "tenant", None) or DEFAULT_TENANT
                )
                with self._waiting_lock:
                    self._waiting.pop(id(request), None)
                if request._done.is_set():
                    continue  # already resolved by the expiry sweep
                if not self._prequalify(request):
                    continue  # resolved in queue (cancelled / deadline)
                if len(request.prompt_tokens) > short_limit:
                    # chunked-prefill path — but keep it bounded so submit()'s
                    # queue-full backpressure still engages under sustained
                    # long-prompt traffic (otherwise memory grows unbounded)
                    if len(self._long_queue) >= self._long_queue_cap:
                        self._held_back = request
                        break
                    self._long_queue.append(request)
                    pending_counts[req_tenant] = (
                        pending_counts.get(req_tenant, 0) + 1
                    )
                elif self._agentic and not self._resolve_agentic(request):
                    continue  # unknown adapter / pinned-full pool: resolved
                else:
                    pairs.append((idx, request))
                    admitted_tokens += self._bucket(len(request.prompt_tokens))
                    pending_counts[req_tenant] = (
                        pending_counts.get(req_tenant, 0) + 1
                    )
                    got_short = True
            if not got_short:
                break
        if not pairs:
            return []
        entries: list[tuple] = []
        # reserve every admission's worst-case pages up front (defer
        # on exhaustion — never corrupt) and peel prefix-ALIAS hits off to
        # their one-dispatch warm path; the rest take the batched cold
        # admission below with their pages already bound
        cold_paged: list[tuple[int, GenerationRequest]] = []
        for idx, request in pairs:
            if self._paged_admit_one(idx, request, entries) == "cold":
                cold_paged.append((idx, request))
        pairs = cold_paged
        by_width: dict[int, list[tuple[int, GenerationRequest]]] = {}
        for idx, request in pairs:
            width = self._bucket(len(request.prompt_tokens))
            by_width.setdefault(width, []).append((idx, request))
        # each distinct (rows, width) shape is a separate XLA compile: a
        # group holds at most prefill_batch rows and dispatches at one of
        # the warmed rungs, a narrow prompt in a wider group's free row
        # where there is one
        for width, sub in admission_groups(
            by_width, self._admit_rungs, self._admit_widens
        ):
            try:
                new = self._prefill_group(width, sub)
            except Exception as e:  # noqa: BLE001 — fail the group, not the engine
                if self._spmd is not None:
                    # multi-host: an announced dispatch that failed here
                    # may have diverged (or killed) the followers —
                    # catch-and-continue would wedge every collective.
                    # Raise: the supervisor escalates to the coordinated
                    # OP_RECOVER (both sides rebuild in place, §20).
                    raise
                log.exception("prefill failed for a batch of %d requests", len(sub))
                for idx, request in sub:
                    self._free_slot_pages(idx)  # reserved at admit
                    request._finish(GenerationResult(
                        tokens=[], finish_reason="error", prompt_tokens=0,
                        ttft_s=0, total_s=0, error=e,
                    ))
                continue
            # NEVER fetch here: blocking on a group's first tokens waits
            # out the in-flight decode chunk with the engine thread
            # stalled, so the next chunk dispatches late and the device
            # idles (measured: admit fetches ate ~30% of steady-state
            # wall at B=96). Entries ride the same ready-gated pending
            # pipeline as decode chunks; on a cold start _run processes
            # them immediately (progressive group-by-group delivery).
            entries.extend(new)
        return entries

    def _prefill_group(
        self, width: int, group: list[tuple[int, GenerationRequest]]
    ) -> list[tuple]:
        """One batched prefill for every (slot, request) pair of one group
        of ``admission_groups`` (prompts of the bucket ``width``, and
        narrower ones in rows it would pad), padded to the smallest rung of
        the ladder that holds the group (``admit_rungs``: one row, or
        prefill_batch; one compiled shape per (rung, width), all warmed), so
        a lone prompt computes one row of its bucket. An expert model's
        ladder is the one rung ``prefill_batch`` (``__init__`` says why)."""
        assert len(group) <= self.prefill_batch
        if self.config.fills_blocks:
            return self._block_prefill_group(width, group)
        n_pad = next(r for r in self._admit_rungs if r >= len(group))
        tokens = np.zeros((n_pad, width), np.int32)
        lengths = np.ones(n_pad, np.int32)
        temps = np.zeros(n_pad, np.float32)
        top_ks = np.zeros(n_pad, np.int32)
        top_ps = np.ones(n_pad, np.float32)
        started = time.monotonic()
        for j, (_, request) in enumerate(group):
            prompt = request.prompt_tokens
            tokens[j, : len(prompt)] = prompt
            lengths[j] = len(prompt)
            temps[j] = request.options.temperature
            top_ks[j] = request.options.top_k
            top_ps[j] = request.options.top_p

        # one scatter for the whole group; padding rows point out of bounds
        # and are dropped
        slots = np.full(n_pad, self.max_batch, np.int32)
        for j, (idx, _) in enumerate(group):
            slots[j] = idx
        if self._spmd is not None:
            self._spmd.announce(wire.ControlBlock(
                op=wire.OP_PREFILL, width=width, n_rows=n_pad, tokens=tokens,
                lengths=lengths, slots=slots, temps=temps, top_ks=top_ks,
                top_ps=top_ps,
            ))
        arows, g_rows, g_state0 = self._agentic_row_args(
            [r for _, r in group], n_pad
        )
        widened = self._count_admit_group(n_pad, width, group)
        disp = self._new_dispatch(
            "engine.admit_group", program="admit_group", rows=n_pad,
            real_rows=len(group), width=width, widened_rows=widened,
            real_tokens=sum(len(r.prompt_tokens) for _, r in group),
            computed_tokens=n_pad * width,
            trace_ids=[r.trace_id for _, r in group],
            kv_pages_written=self._kv_pages_written(slots, width),
            # rows of recurrent state the group writes: one a real prompt
            **({"state_rows_written": len(group)} if self.config.is_recurrent else {}),
        )
        seq = self._dispatch_seq
        with jax.profiler.TraceAnnotation(
            "engine.admit_group", seq=seq, t_mono_ns=_mono_ns(disp)
        ):
            first = self._dev_prefill(
                width, tokens, lengths, temps, top_ks, top_ps, slots,
                arows=arows, g_rows=g_rows, g_state0=g_state0,
            )
        counts = self._moe_counts()

        for idx, request in group:
            slot = self._slots[idx]
            slot.request = request
            slot.position = len(request.prompt_tokens)  # next write position
            slot.generated = []
            slot.started_at = started
            slot.first_token_at = 0.0  # stamped when the deferred fetch lands
            slot.reset_obs("cold", 1, seq)
            self._slot_bind_agentic(idx, request)
            with self._stats_lock:
                self.total_requests += 1
            self._note_tenant_admitted(request)
            self._spec_admit(idx, request.prompt_tokens)
            self._maybe_publish(idx, request.prompt_tokens)
        return [(
            "prefill", self._submit_fetch(first, seq, counts), list(group), disp,
        )]

    def _count_admit_group(
        self, n_pad: int, width: int, group: list[tuple[int, GenerationRequest]]
    ) -> int:
        """One more group at the rung ``n_pad`` (stats "admit-group-rows");
        returns its ``widened_rows``, the rows whose own bucket is narrower
        than the group's width (stats "admit-rows-widened")."""
        widened = sum(self._bucket(len(r.prompt_tokens)) < width for _, r in group)
        with self._stats_lock:
            self._admit_group_rows[n_pad] += 1
            self._admit_rows_widened += widened
        return widened

    def _agentic_admit_kwargs(
        self, n: int, arows, g_rows, g_state0=None,
    ) -> dict:
        """Keyword args the admit-group programs take when the agentic
        tier is on — zeros (base rows) for warmups and padding. Empty dict
        when off, so legacy engines trace the exact pre-ISSUE-10 programs.
        ``g_state0``: per-row initial DFA states (zeros except for
        mid-derivation fleet resumes, §18)."""
        kw: dict[str, Any] = {}
        if self._adapters is not None:
            kw["lora"] = self._adapters.pool
            kw["arows"] = jnp.asarray(
                arows if arows is not None else np.zeros(n, np.int32)
            )
        if self._constrain_reg is not None:
            kw["dfa"] = self._constrain_reg.pool
            kw["g_rows"] = jnp.asarray(
                g_rows if g_rows is not None else np.zeros(n, np.int32)
            )
            kw["state_dev"] = self._dfa_state_dev
            kw["g_state0"] = jnp.asarray(
                g_state0 if g_state0 is not None else np.zeros(n, np.int32)
            )
        return kw

    def _dev_prefill(
        self, width, tokens, lengths, temps, top_ks, top_ps, slots,
        arows=None, g_rows=None, g_state0=None,
    ):
        """Device layer of a batched prefill — runs IDENTICALLY on the
        leader and (via follower_loop) every SPMD follower, so the sharded
        cache and decode chain evolve in lockstep from pure host inputs.
        (Agentic args never appear under SPMD — the tier is construction-
        disabled on multi-host replicas, so the wire needs no new ops.)"""
        if self._injector is not None:
            self._injector.fire("prefill")  # before any state mutates
        n = len(tokens)
        assert all(len(a) == n for a in (lengths, temps, top_ks, top_ps, slots))
        return self._dev_paged_prefill(
            tokens, lengths, temps, top_ks, top_ps, slots,
            arows=arows, g_rows=g_rows, g_state0=g_state0,
        )

    def _dev_paged_prefill(
        self, tokens, lengths, temps, top_ks, top_ps, slots,
        arows=None, g_rows=None, g_state0=None,
    ):
        """Paged device layer of a batched cold prefill: the fused
        local-cache forward (``prefill``), whose insert scatters into each
        row's reserved pages. Rows whose slot
        is out of bounds (padding, warmups) carry an all-sentinel table —
        every write drops."""
        pool = self._pagepool
        n = len(tokens)
        if self.config.fills_blocks:  # the warm-up's rows: nothing real
            return self._dev_block_prefill(
                tokens, np.zeros(n, np.int32), temps, top_ks, top_ps, slots,
                np.zeros(n, np.int32),
                np.full((n, self.config.block_length), self.config.mask_token_id, np.int32),
            )
        for s in slots:  # a window row maps the pages the group writes
            pool.window_advance(int(s), 0, tokens.shape[1] - 1)
        tables = pool.rows_tables(slots)
        self._record_program("paged-prefill", tokens.shape[1], n)
        meta = np.stack([lengths, temps, top_ks, top_ps]).astype(np.float32)
        kw = self._agentic_admit_kwargs(n, arows, g_rows, g_state0)
        (
            first,
            pool.dev,
            self._tokens_dev,
            self._positions_dev,
            self._temp_dev,
            self._top_k_dev,
            self._top_p_dev,
            self._key,
            state_dev,
            self._moe_dev,
        ) = self._paged_admit_group(
            self.params,
            pool.dev,
            self._tokens_dev,
            self._positions_dev,
            self._temp_dev,
            self._top_k_dev,
            self._top_p_dev,
            self._key,
            jnp.asarray(tokens),
            jnp.asarray(meta),
            jnp.asarray(slots),
            jnp.asarray(tables),
            self.config,
            self.page_size,
            **kw,
        )
        if state_dev is not None:
            self._dfa_state_dev = state_dev
        return first

    def _segment_agentic_kwargs(self, agentic_rows, state_slot) -> dict:
        """Agentic kwargs for the batch-1 segment programs (warm suffix /
        long-prompt chunks). ``state_slot`` out of bounds (non-final
        segments, warmups) drops the DFA state scatter. The request's
        initial DFA state (the _agentic_rows triple) seeds the first-token
        mask — nonzero only on a mid-derivation fleet resume (§18)."""
        kw: dict[str, Any] = {}
        arow, grow, state0 = agentic_rows or (0, 0, 0)
        if self._adapters is not None:
            kw["lora"] = self._adapters.pool
            kw["arows"] = jnp.asarray([arow], jnp.int32)
        if self._constrain_reg is not None:
            kw["dfa"] = self._constrain_reg.pool
            kw["g"] = jnp.asarray([grow], jnp.int32)
            kw["state_dev"] = self._dfa_state_dev
            kw["state_slot"] = jnp.asarray(state_slot, jnp.int32)
            kw["state0"] = jnp.asarray([state0], jnp.int32)
        return kw

    # -- paged admission / prefix aliasing -----------------------------------

    def _pop_admission(
        self, allow_new: bool = True, skip: Optional[set] = None,
    ) -> GenerationRequest:
        """Admission source for _admit: page-deferred requests (popped
        earlier, waiting for pool pages) retry ahead of the queue so
        allocator pressure never reorders them behind newer arrivals.
        ``allow_new=False`` (set while deferred admissions wait) stops
        draining the queue — the deferred list must stay bounded so the
        bounded queue keeps backpressuring submit() during exhaustion
        instead of silently absorbing the backlog host-side. ``skip``:
        tenants held back this pop (at their slot cap / fair share) —
        forwarded to the tenant queue's DRR, never applied to deferred
        retries (those already own a pop)."""
        if self._page_deferred:
            return self._page_deferred.pop(0)
        if not allow_new:
            raise queue.Empty
        return self._queue.get_nowait(skip=skip)

    def _tenant_occupancy(self) -> dict[str, int]:
        """Active-slot + long-prefill-stream counts by tenant. Computed
        ONCE per _admit call (slot occupancy cannot change inside it —
        slots activate after the pop loop); per-pop deltas ride the
        caller's pending_counts."""
        active: dict[str, int] = {}

        def _bump(req) -> None:
            t = getattr(req.options, "tenant", None) or DEFAULT_TENANT
            active[t] = active.get(t, 0) + 1

        for s in self._slots:
            if s.active:
                _bump(s.request)
        for st in self._longs.values():
            r = st.get("request")
            if r is not None:
                _bump(r)
        return active

    def _tenant_slot_skip(
        self, occupancy: dict[str, int], pending_counts: dict[str, int],
    ) -> set:
        """Tenants that must NOT claim another free slot right now: at
        their configured ``max_slots`` hard cap, or at their weighted fair
        share of the slot pool while OTHER tenants have queued work. Fair
        share = max_batch × weight / Σweights over the contending set —
        the "a bursting tenant can never exceed its weight when others
        are waiting" rule. Work-conserving both ways: a single tenant is
        never capped by fairness, and when EVERY waiting tenant would be
        fair-capped with slots still free, the caps relax (hard max_slots
        never does). Engine thread only."""
        waiting = self._queue.tenants_with_work()
        if not waiting:
            return set()
        active: dict[str, int] = dict(occupancy)
        for t, n in pending_counts.items():
            active[t] = active.get(t, 0) + n
        hard: set = set()
        fair_skip: set = set()
        contending = set(waiting) | {t for t, n in active.items() if n}
        multi = len(contending) > 1
        total_w = sum(self._tenants.weight(t) for t in contending) or 1.0
        for t in waiting:
            spec = self._tenants.state(t).spec
            n = active.get(t, 0)
            if spec.max_slots is not None and n >= spec.max_slots:
                hard.add(t)
                continue
            if multi:
                fair = max(
                    1,
                    round(self.max_batch * self._tenants.weight(t) / total_w),
                )
                if n >= fair:
                    fair_skip.add(t)
        if fair_skip and set(waiting) <= (fair_skip | hard):
            # everyone waiting is fair-capped yet slots are free: borrow
            fair_skip = set()
        return fair_skip | hard

    def _paged_bind(self, idx: int, request: GenerationRequest) -> Optional[int]:
        """Reserve slot ``idx``'s worst-case pages, aliasing the deepest
        cached prefix when the index has one: full prefix pages join the
        table by refcount bump (ZERO copies), a mid-page prefix tail gets
        one copy-on-write page copy. Under pool pressure the LRU unpinned
        prefix entries make room first. Returns the reuse offset (0 = cold
        miss) or None — slot untouched — when the pool cannot cover the
        reservation (the caller defers; exhaustion sheds upstream, it never
        corrupts). Shared by the short-admission and long-prompt paths so
        the alias/COW/eviction rules cannot drift between them."""
        pool, index = self._pagepool, self._prefix_index
        prompt = request.prompt_tokens
        # reserve only what the request can actually write: a
        # max_cost_tokens budget below max_new_tokens shrinks the
        # worst-case page reservation too (§19)
        need = pool.pages_needed(
            len(prompt),
            max(1, effective_max_new_tokens(request.options, len(prompt)))
            # the engine finishes the block it began
            + max(0, self.config.block_length - 1),
        )
        if need > pool.num_pages:
            # only reachable with an explicit kv-pages override below the
            # per-slot worst case: deferring would hang forever, so fail
            # loudly with the sizing arithmetic
            request._finish(GenerationResult(
                tokens=[], finish_reason="error",
                prompt_tokens=len(prompt), ttft_s=0, total_s=0,
                error=ShedError(
                    f"request needs {need} KV pages but the pool has only "
                    f"{pool.num_pages}; raise kv-pages (or lower "
                    "max-new-tokens)"
                ),
            ))
            return -1  # handled — nothing reserved
        hit = None
        if index is not None and not getattr(request.options, "adapter", None):
            # adapter tenants never alias the shared base-prefix pages —
            # their prompt KV includes the wk/wv adapter deltas. Deepest
            # usable candidate wins; a HIBERNATED candidate (host tier,
            # no device pages) is restored in place — the whole point of
            # the tier: a radix hit on a spilled session is a DMA upload,
            # not a miss. A failed restore (checksum/fault/no room) falls
            # back to the next-shallower candidate, then to recompute.
            failed_restores = 0
            counted = getattr(request, "_tier_fallback_counted", False)
            for p_cand, cand in reversed(index.candidates(prompt)):
                if cand.dropped:
                    # a deeper candidate's _restore_entry can evict_for a
                    # SHALLOWER candidate out of this already-materialized
                    # list — the dropped entry is stale, not a hit
                    continue
                if cand.pages:
                    hit = (p_cand, cand)
                    break
                if self._restore_entry(
                    cand, p_cand, count_failures=not counted
                ):
                    hit = (p_cand, cand)
                    request._tier_restored = True
                    break
                failed_restores += 1
            if failed_restores:
                # failure gauges count once per REQUEST: a page-deferred
                # request re-runs this loop every engine iteration, and a
                # full-pool stall must not read as thousands of failed
                # restores. The recompute-fallback side of the health
                # gauge is decided at BIND time below — a deferral is not
                # a cold ending (its retry may restore and must not land
                # on both sides of the restore-vs-recompute split)
                request._tier_fallback_counted = True
            if hit is None and self._durable is not None:
                # third rung of the ladder (§23): nothing live covered the
                # prompt — resurrect from the durable store if a checkpoint
                # does. Any failure degrades to cold prefill right here.
                hit = self._durable_admit(request, prompt)
                if hit is not None:
                    request._tier_restored = True
        shared: tuple[int, ...] = ()
        cow_src = None
        p, entry = 0, None
        if hit is not None:
            p, entry = hit
            full = p // self.page_size
            shared = tuple(entry.pages[:full])
            if p % self.page_size:
                cow_src = entry.pages[full]
            index.acquire(entry)  # pinned: eviction below must not free it
        try:
            want_fresh = need - len(shared)
            if pool.free_pages < want_fresh and index is not None:
                # tiered KV: victims DEMOTE to their host copy when one is
                # secured (spill_cb) — the device pool is a cache over the
                # host tier, and eviction stops costing re-prefills
                index.evict_for(
                    pool, want_fresh,
                    spill_cb=self._ensure_spilled if self._spill_on else None,
                )
            cow_dst = pool.reserve(idx, need, shared)
            if cow_dst is None:
                return None
            # the restore-vs-recompute health gauge is decided HERE, at
            # bind time, once per request and on exactly one side: a
            # deferral is neither outcome (its retry decides), and a
            # full-pool restore/demote cycle across retries must not
            # count one admission as several restores
            if (
                hit is not None
                and getattr(request, "_tier_restored", False)
                and not getattr(request, "_tier_restored_counted", False)
            ):
                self.restored_hits_total += 1
                request._tier_restored_counted = True
            elif (
                hit is None
                and getattr(request, "_tier_fallback_counted", False)
                and not getattr(request, "_tier_recompute_counted", False)
            ):
                # binds COLD after ≥1 failed restore: a recompute
                # fallback — a shallower device-resident candidate
                # serving the hit warm is not one
                self.recompute_fallbacks_total += 1
                request._tier_recompute_counted = True
            if self._spmd is not None:
                # the reservation RESULT rides the wire: followers bind the
                # same physical pages to the same slot table (aliased
                # prefix pages included) and make the same COW copy — the
                # free list / refcounts / prefix index stay leader-only
                owned = pool.slot_pages(idx)
                self._spmd.announce(wire.ControlBlock(
                    op=wire.OP_PAGE_BIND, long_idx=idx, count=len(owned),
                    pages=np.asarray(owned, np.int32),
                    cow_src=cow_src if cow_src is not None else -1,
                    cow_dst=cow_dst if cow_src is not None else -1,
                ))
            if index is not None:
                index.record_lookup(entry)
            if entry is None:
                return 0
            if cow_src is not None:
                self._record_program("page-copy")
                pool.dev = _page_copy(
                    pool.dev,
                    jnp.asarray(cow_src, jnp.int32),
                    jnp.asarray(cow_dst, jnp.int32),
                )
            index.tokens_saved += p
            token_bytes = pool.bytes_per_page / self.page_size
            saved = int(p * token_bytes) - (
                pool.bytes_per_page if cow_src is not None else 0
            )
            index.copy_bytes_saved += max(saved, 0)
            return p
        finally:
            if entry is not None:
                index.release(entry)

    def _paged_admit_one(self, idx: int, request: GenerationRequest,
                         entries: list) -> str:
        """Reserve pages and route one short admission in paged mode.
        Returns "cold" (pages bound — join the batched group prefill),
        "warm" (prefix alias hit — dispatched here, fetch entry appended),
        or "deferred" (pool exhausted even after LRU prefix eviction — the
        request waits host-side; nothing was corrupted, nothing leaked)."""
        base = self._paged_bind(idx, request)
        if base is None:
            self._page_deferred.append(request)
            return "deferred"
        if base < 0:
            return "failed"  # can-never-fit: _paged_bind resolved it
        if base == 0:
            return "cold"
        self._paged_prefill_prefix(idx, request, base, entries)
        return "warm"

    def _paged_prefill_prefix(
        self, idx: int, request: GenerationRequest, p: int, entries: list,
    ) -> None:
        """Warm paged admission: the aliased pages are ALREADY in the slot's
        table (_paged_bind), so all that runs on device is ONE fused
        suffix-segment dispatch."""
        pool = self._pagepool
        prompt = request.prompt_tokens
        suffix = prompt[p:]
        ws = self._bucket(len(suffix))
        tokens = np.zeros((1, ws), np.int32)
        tokens[0, : len(suffix)] = suffix
        opts = request.options
        started = time.monotonic()
        disp = self._new_segment_dispatch(
            "_paged_segment_and_sample", ws, len(suffix), request
        )
        if self._spmd is not None:
            # one warm paged admission = one suffix segment against pages
            # the preceding OP_PAGE_BIND already aliased on every host
            self._spmd.announce(wire.ControlBlock(
                op=wire.OP_LONG_SEG, width=ws, n_rows=1, tokens=tokens,
                s0=p, seg_len=len(suffix), long_idx=idx,
                long_final=True, prompt_len=len(prompt),
                temps=np.asarray([opts.temperature], np.float32),
                top_ks=np.asarray([opts.top_k], np.int32),
                top_ps=np.asarray([opts.top_p], np.float32),
            ))
        try:
            first = self._dev_paged_segment(
                tokens, p, len(suffix), idx,
                opts.temperature, opts.top_k, opts.top_p,
                final=True, prompt_len=len(prompt),
                agentic_rows=request._agentic_rows,
            )
        except Exception as e:  # noqa: BLE001 — fail the request, not the engine
            if self._spmd is not None:
                raise  # multi-host: crash the replica (see _admit rationale)
            log.exception("paged prefix-reuse prefill failed (p=%d)", p)
            self._free_slot_pages(idx)
            request._finish(GenerationResult(
                tokens=[], finish_reason="error", prompt_tokens=0,
                ttft_s=0, total_s=0, error=e,
            ))
            return
        self._note_key_blocks(disp)
        slot = self._slots[idx]
        slot.request = request
        slot.position = len(prompt)
        slot.generated = []
        slot.started_at = started
        slot.first_token_at = 0.0
        slot.reset_obs("warm", 1, self._dispatch_seq)
        self._slot_bind_agentic(idx, request)
        with self._stats_lock:
            self.total_requests += 1
        self._note_tenant_admitted(request)
        self._spec_admit(idx, prompt)
        self._maybe_publish(idx, prompt)
        entries.append(
            ("prefill", self._submit_fetch(first, self._dispatch_seq),
             [(idx, request)], disp)
        )

    def _dev_paged_segment(
        self, tokens, s0, seg_len, idx, temperature, top_k, top_p,
        *, final: bool, prompt_len: int, agentic_rows=None,
    ):
        """Device layer of one paged prefill segment (warm suffix OR one
        chunk of a long prompt): K/V go straight into the slot's pages (by
        whole pages where the program can, else the scatter:
        `_count_segment_write`), attention reads the prefix through the table. On ``final``
        the decode chain scatters — there is no insert/splice: the pages
        ARE the cache. The DFA state scatter only lands on ``final`` (the
        segment whose sampled first token actually seeds the chain)."""
        if self._injector is not None:
            self._injector.fire("segment")
        pool = self._pagepool
        # a window row's pages behind the segment's window go ahead of it
        self._window_recycled = pool.window_advance(idx, s0, s0 + tokens.shape[1] - 1)
        table = pool.rows_tables([idx])
        self._record_program("paged-segment", tokens.shape[1])
        self._count_segment_write(tokens.shape[1], s0)
        self._count_segment_key_blocks(tokens.shape[1], s0)
        kw = self._segment_agentic_kwargs(
            agentic_rows, idx if final else self.max_batch
        )
        if self.config.is_recurrent:
            # the slot's row of recurrent state carries from segment to
            # segment (an out-of-bounds ``idx``, the warm-up's, drops)
            kw["state_rows"] = jnp.asarray([idx], jnp.int32)
        first, pool.dev, self._key, state_dev, *moe = _paged_segment_and_sample(
            self.params,
            jnp.asarray(tokens),
            jnp.asarray([s0], jnp.int32),
            jnp.asarray([seg_len], jnp.int32),
            pool.dev,
            jnp.asarray(table),
            self._key,
            jnp.asarray([temperature], jnp.float32),
            jnp.asarray([top_k], jnp.int32),
            jnp.asarray([top_p], jnp.float32),
            self.config,
            self.page_size,
            **kw,
        )
        if state_dev is not None:
            self._dfa_state_dev = state_dev
        if moe:
            (self._moe_dev,) = moe
        if final:
            self._record_program("chain-scatter")
            (
                self._tokens_dev, self._positions_dev, self._temp_dev,
                self._top_k_dev, self._top_p_dev,
            ) = _chain_scatter(
                self._tokens_dev, self._positions_dev, self._temp_dev,
                self._top_k_dev, self._top_p_dev,
                jnp.asarray(idx, jnp.int32), first, prompt_len,
                temperature, top_k, top_p,
            )
        return first

    def _active_mask(self) -> np.ndarray:
        """Per-slot liveness for a decode/verify dispatch (1 = active).
        Computed ONCE at dispatch and — under SPMD — shipped on the wire:
        followers cannot observe completions (those are discovered from
        fetched tokens on the leader), so the mask is part of the dispatch
        description, not derivable state."""
        return np.asarray(
            [1 if s.active else 0 for s in self._slots], np.int32
        )

    def _dispatch_tables(self, mask: Optional[np.ndarray] = None) -> np.ndarray:
        """Page tables for a decode/verify dispatch, with every non-ACTIVE
        slot's row masked to the out-of-bounds sentinel. A decode step
        computes (garbage) K/V for inactive rows too, and a
        table row may belong to a RESERVED
        long-prefill stream whose pages are mid-prefill — an unmasked
        dispatch would scribble stale-position garbage straight into them.
        Masked rows drop their writes and read clamped (masked) garbage,
        exactly like the warmup dispatches. ``mask`` (SPMD followers: the
        leader's wire-shipped liveness) overrides the local slot view."""
        pool = self._pagepool
        tables = pool.tables.copy()
        if mask is None:
            mask = self._active_mask()
        inactive = [i for i in range(self.max_batch) if not mask[i]]
        if inactive:
            tables[inactive] = pool.oob
        return pool.device_tables(tables)

    def _page_integrity_check(self) -> None:
        """Validate every active slot's table row against the allocator's
        authoritative owned-page list before a decode/verify dispatch; a
        mismatch (the ``page`` fault site, host memory corruption, or a
        real bookkeeping bug) quarantines ONLY that slot — its request
        fails, its pages free through the owned list (no leak) and are
        zeroed — while every other slot keeps decoding untouched."""
        pool = self._pagepool
        if self._injector is not None:
            snapshot = [
                (i, s.request) for i, s in enumerate(self._slots) if s.active
            ]
            self._injector.corrupt_page_table(pool, snapshot)
        for i, slot in enumerate(self._slots):
            if not slot.active or pool.validate(i):
                continue
            with self._stats_lock:
                self.quarantined_slots_total += 1
            self._quarantine_pages(i)
            self._flight_dump("page-quarantine", extra={"slot": i})
            self._finish_slot(
                i, "error",
                error=RuntimeError(
                    f"page-table corruption detected for slot {i}; slot "
                    "quarantined, pages freed and zeroed"
                ),
            )

    def _quarantine_pages(self, idx: int) -> None:
        """Paged quarantine: evict any prefix entry sharing the victim's
        pages (poisoned KV must not be aliased into future admissions),
        free the slot's pages through the authoritative owned list, and
        queue the now-unreferenced ones for a coalesced zero dispatch
        (pages, not rows — ROADMAP item 1). SPMD followers see the free
        (OP_PAGE_FREE) and the zero (OP_PAGE_ZERO on the next flush)."""
        pool = self._pagepool
        pages = pool.slot_pages(idx)
        if not pages:
            return
        if self._prefix_index is not None:
            self._prefix_index.evict_touching(pool, pages)
        if pool.window is not None:  # its ring, before the free forgets it
            self._pending_window_zero.extend(pool.window.slot_pages(idx))
        self._pending_page_zero.extend(self._free_slot_pages(idx))

    def _free_slot_pages(self, idx: int) -> list[int]:
        """Release slot ``idx``'s pages (completion, quarantine, abort, or
        a failed admission), announcing the table clear to SPMD followers
        FIRST — their dispatch tables must stop referencing the pages
        before any later OP_PAGE_BIND re-issues them. Returns the pages
        whose refcount hit zero (the quarantine path zeroes those). The
        single gateway every ``free_slot`` call goes through, so a call
        site can never silently skip the wire. A slot that owns nothing
        (already freed — e.g. _finish_slot after a quarantine) skips the
        announce: the follower's table is already clear, and a redundant
        broadcast per quarantine is pure wire noise."""
        if self._spmd is not None and self._pagepool.slot_pages(idx):
            self._spmd.announce(
                wire.ControlBlock(op=wire.OP_PAGE_FREE, long_idx=idx)
            )
        return self._pagepool.free_slot(idx)

    def _spmd_tick(self) -> None:
        """SPMD resilience bookkeeping at the iteration top (leader only,
        engine thread — docs/SERVING.md §20): fire the ``spmd-crash``
        drill site (a raise here IS an engine-loop crash, driving the
        coordinated OP_RECOVER path end to end), answer at most one
        pending divergence-resync request (throttled — the KV-store poll
        is a coordinator round trip), and keep follower watchdogs fed
        with OP_IDLE heartbeats when no dispatch has announced lately."""
        if self._injector is not None:
            self._injector.fire("spmd-crash")
        now = time.monotonic()
        # poll at the heartbeat cadence, never faster than 4 Hz: on a
        # real slice each poll is one coordinator KV round trip PER
        # follower, and a resync is rare + not latency-critical (the
        # follower keeps replaying while it waits)
        wd = getattr(self._spmd, "watchdog_s", 0)
        if now - self._spmd_div_checked_at >= max(0.25, wd / 4):
            self._spmd_div_checked_at = now
            try:
                req = self._spmd.poll_divergence()
            except Exception:  # noqa: BLE001 — side channel gone ≠ crash
                req = None
            if req is not None:
                self._spmd_resync(req)
        self._spmd_heartbeat()

    def _spmd_heartbeat(self) -> None:
        """Announce OP_IDLE when the wire has been quiet for a quarter of
        the watchdog bound — silence then cleanly separates 'idle replica'
        from 'dead leader' on the follower side. No-op with the watchdog
        off (watchdog_s == 0), so pre-round-19 channels see zero extra
        traffic."""
        ch = self._spmd
        wd = getattr(ch, "watchdog_s", 0)
        if ch is None or wd <= 0:
            return
        if time.monotonic() - ch.last_announce_t >= max(0.05, wd / 4):
            try:
                ch.announce(wire.ControlBlock(op=wire.OP_IDLE))
            except Exception:  # noqa: BLE001 — heartbeats are best-effort
                log.exception("SPMD idle heartbeat failed")

    def _spmd_resync(self, req: dict) -> None:
        """Answer a follower's divergence report with ONE coordinated
        OP_RESYNC: re-broadcast the authoritative per-slot page tables
        and device positions at a fresh epoch, then reset the seq chain
        to the epoch base. (The active-slot MASK is per-dispatch wire
        data — every decode/verify block ships it — so a resync has
        nothing persistent to re-broadcast for it.) The follower
        VERIFIES its own state against the snapshot and rejoins on a
        match; mismatch (or a repeat divergence inside its window) stays
        fatal on its side — the leader just answers, it never decides
        (§20)."""
        pool = self._pagepool
        b = self.max_batch
        tl = pool.table_len if pool is not None else 0
        parts = []
        if tl:
            parts.append(
                np.asarray(pool.tables[:b, :tl], np.int32).reshape(-1)
            )
        parts.append(np.asarray(
            jax.device_get(self._positions_dev), np.int32
        )[:b])
        payload = np.concatenate(parts)
        epoch = self._spmd_epoch + 1
        log.warning(
            "SPMD follower reported divergence (%s); answering with "
            "OP_RESYNC at epoch %d", req.get("why", "?"), epoch,
        )
        self._spmd.announce(wire.ControlBlock(
            op=wire.OP_RESYNC, long_idx=epoch, count=len(payload),
            n_rows=b, width=tl, echo=payload,
        ))
        self._spmd.reset_seq()
        self._spmd_epoch = epoch
        with self._stats_lock:
            self.spmd_resyncs_total += 1
        self._flight_dump(
            "spmd-recover",
            extra={"kind": "resync", "epoch": epoch, "requested": dict(req)},
        )

    def _spmd_echo(self, kind: int, host: np.ndarray) -> None:
        """Re-broadcast a processed chunk's fetched tokens to followers in
        echo (divergence-check) mode: the follower compares them against
        its own device result for the same dispatch and crashes with a
        flight dump on mismatch (docs/SERVING.md §14). One extra broadcast
        per processed chunk — off in production, on in the parity suite."""
        if self._spmd is None or not getattr(self._spmd, "echo", False):
            return
        flat = np.asarray(host, np.int32).reshape(-1)
        self._spmd.announce(wire.ControlBlock(
            op=wire.OP_ECHO, long_idx=kind, count=len(flat), echo=flat,
        ))

    def _spmd_apply_bind(
        self, idx: int, pages: list, cow_src: Optional[int],
        cow_dst: Optional[int],
    ) -> None:
        """Follower half of OP_PAGE_BIND: adopt the leader's reservation
        RESULT into this process's dispatch tables (tables are the only
        allocator state a follower keeps — parallel/spmd_serving.py) and
        make the same copy-on-write page copy, in the same stream order."""
        pool = self._pagepool
        if pages:
            pool.tables[idx, : len(pages)] = pages
            pool.tables[idx, len(pages):] = pool.oob
        if cow_src is not None and cow_dst is not None:
            self._record_program("page-copy")
            pool.dev = _page_copy(
                pool.dev,
                jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(cow_dst, jnp.int32),
            )

    def _flush_page_zeros(self) -> None:
        """Zero quarantined pages, coalesced into table_len-wide dispatches
        (ONE compiled program; out-of-bounds padding drops). Runs at the top
        of the iteration, so the zero rides the in-order stream ahead of
        any admission that re-allocates the freed pages. SPMD: each zero
        dispatch rides the wire (OP_PAGE_ZERO) so followers scrub the same
        physical pages."""
        pool = self._pagepool
        pages = self._pending_page_zero
        self._pending_page_zero = []
        width = pool.table_len
        for i in range(0, len(pages), width):
            chunk = pages[i : i + width]
            if self._spmd is not None:
                self._spmd.announce(wire.ControlBlock(
                    op=wire.OP_PAGE_ZERO, count=len(chunk),
                    pages=np.asarray(chunk, np.int32),
                ))
            self._dev_page_zero(chunk)
        if self._pending_window_zero:
            window, self._pending_window_zero = self._pending_window_zero, []
            self._flush_window_zeros(window)

    def _flush_window_zeros(self, pages: list[int]) -> None:
        """One zero dispatch over the window group's quarantined pages: a
        buffer as wide as every slot's ring, out-of-bounds padding drops."""
        pool = self._pagepool
        buf = np.full(self.max_batch * pool.window.ring, pool.window.oob, np.int32)
        buf[: len(pages)] = pages
        self._record_program("window-page-zero")
        pool.dev = _window_page_zero(pool.dev, jnp.asarray(buf))

    def _dev_page_zero(self, pages) -> None:
        """Device layer of one quarantine page-zero dispatch (leader + SPMD
        followers): fixed table_len-wide buffer, OOB padding drops."""
        pool = self._pagepool
        buf = np.full(pool.table_len, pool.oob, np.int32)
        buf[: len(pages)] = list(pages)
        self._record_program("page-zero")
        pool.dev = _page_zero(pool.dev, jnp.asarray(buf))

    # -- tiered KV: host-RAM spill + hibernation restore ---------------------

    def _drain_spills(self) -> None:
        """Fold completed spills in (engine thread, iteration top): attach
        the arena slots to their entry — or free them when the entry died
        mid-copy (cancelled/quarantined), the copy failed, or the handle
        predates a crash recovery (stale generation: the arena was already
        reset; its free list owns those slots again)."""
        tier = self._host_tier
        if tier is None:
            return
        while True:
            try:
                handle = self._spill_done.get_nowait()
            except queue.Empty:
                return
            if handle.gen != self._spill_gen:
                continue
            entry = handle.entry
            if handle.cancelled or entry.dropped:
                tier.free(handle.slots)
                continue
            entry.spilling = None
            if handle.error is not None:
                log.warning("page spill failed: %s", handle.error)
                tier.free(handle.slots)
                self.spill_failures_total += 1
                if not entry.pages:
                    # the entry was DEMOTED on the strength of this spill
                    # (evict_for trusts an in-flight handle): with the copy
                    # failed it holds neither device nor host pages — a
                    # zombie a later radix hit would "restore" with zero
                    # pages. Drop it; the session re-prefills next turn.
                    self._prefix_index._drop(self._pagepool, entry)
                continue
            entry.host = tuple(handle.slots)
            self._prefix_index._note_tier(entry)
            self.spill_pages_total += len(handle.slots)
            self.spill_bytes_total += len(handle.slots) * tier.bytes_per_page
            # durable tier (§23): a completed spill is the checkpoint
            # trigger — the arena bytes and their stamps are final now,
            # so the session can be made to survive THIS replica too
            self._maybe_checkpoint(entry)

    def _ensure_spilled(self, entry) -> bool:
        """Secure a host copy for ``entry`` (the demote-before-drop gate):
        True when one exists, is in flight, or was enqueued just now. The
        engine thread only dispatches the per-page snapshot program (async,
        independent buffers — the entry's device pages may be freed the
        moment this returns); the device→host bytes move on the spill
        worker, off the hot loop."""
        if not self._spill_on or self._spill_worker is None:
            return False
        if entry.host or entry.spilling is not None:
            return True
        if not entry.pages or entry.dropped:
            return False
        tier = self._host_tier
        slots = tier.alloc(len(entry.pages))
        if slots is None:
            self._evict_host_for(len(entry.pages), keep=entry)
            slots = tier.alloc(len(entry.pages))
            if slots is None:
                return False
        pool = self._pagepool
        self._record_program("page-snapshot")
        blocks = [
            _page_snapshot(pool.dev, jnp.asarray(p, jnp.int32))
            for p in entry.pages
        ]
        handle = _Spill(entry, slots, blocks, self._spill_gen)
        entry.spilling = handle
        self._spill_worker.submit(handle)
        return True

    def _evict_host_for(self, need: int, keep=None) -> None:
        """Make arena room: free host copies LRU-first (a ``both`` victim
        just loses its spare; a ``host``-only victim is dropped outright —
        its session will re-prefill). Never touches ``keep`` (the entry
        we're making room FOR) or pinned entries."""
        index, tier = self._prefix_index, self._host_tier
        while tier.free_slots < need:
            victims = [
                e for e in index._live
                if e.host and e.pins == 0 and e is not keep
            ]
            if not victims:
                return
            victim = min(victims, key=lambda e: e.last_used)
            if victim.pages:
                tier.free(victim.host)
                victim.host = ()
                index._note_tier(victim)
                # the entry reverted to device-only: make it a spill
                # candidate again so the idle sweep can re-hibernate it
                # once the arena has room (duplicates in the deque are
                # benign — the sweep's host/spilling checks skip them)
                self._spill_candidates.append(victim)
            else:
                # durable rescue (§23): a host-only victim is gone for
                # good after the drop — materialize its checkpoint job
                # FIRST (the worker holds its own byte copies, so the
                # drop below cannot race the disk write)
                self._maybe_checkpoint(victim)
                index._drop(self._pagepool, victim)
            index.host_evictions += 1

    def _spill_tick(self) -> None:
        """Hibernation sweep, once per engine iteration: drain completed
        spills, then start at most a couple of new ones for entries idle
        past ``spill_idle_s`` (oldest first). O(1) when there is nothing
        to do — the hot loop's cost is one deque truthiness check."""
        if not self._spill_on:
            return
        t0 = time.monotonic()
        self._drain_spills()
        started = 0
        now = time.monotonic()
        # the deque is PUBLISH-ordered, not idle-ordered (last_used_t is
        # refreshed on every hit): a hot entry at the front must not starve
        # idle entries behind it, so not-yet-idle candidates ROTATE to the
        # back and the scan is bounded per tick — the hot loop does at
        # most 8 deque hops
        scanned, limit = 0, min(len(self._spill_candidates), 8)
        while self._spill_candidates and started < 2 and scanned < limit:
            scanned += 1
            entry = self._spill_candidates.popleft()
            if entry.dropped or entry.host or entry.spilling is not None:
                continue
            if now - entry.last_used_t < self.spill_idle_s:
                self._spill_candidates.append(entry)  # not idle: revisit
                continue
            if self._ensure_spilled(entry):
                started += 1
            else:
                # arena full and unevictable THIS tick: rotate to the
                # back and retry on a later sweep — a live session's
                # prefix never re-publishes, so forgetting the candidate
                # would leave it pinning HBM through its whole idle
                # period. Stop the sweep: every further candidate hits
                # the same full arena this tick
                self._spill_candidates.append(entry)
                break
        self._spill_ms_iter += (time.monotonic() - t0) * 1e3

    def _restore_entry(
        self, entry, p: int, count_failures: bool = True,
    ) -> bool:
        """Hibernation restore (the admission's warm-hit path when the
        radix hit lives host-side): allocate device pages, upload the
        arena copy with the ONE warmed traced-index program, and re-attach
        them to the entry. False — with the entry either intact (no device
        room: caller falls back) or dropped (checksum mismatch / injected
        ``spill`` fault / spill never completed: poison must not be
        retried) — when the restore cannot serve the hit; the caller
        recomputes. Synchronous on the engine thread: the admission needs
        the pages before its suffix prefill, and the upload IS the win
        (DMA speed vs re-prefill FLOPs). ``count_failures=False`` keeps a
        page-deferred request's per-iteration retries off the failure
        gauges (each request counts its failures once)."""
        pool, index, tier = self._pagepool, self._prefix_index, self._host_tier
        if entry.dropped:
            return False
        fail = 1 if count_failures else 0
        t0 = time.monotonic()
        handle = entry.spilling
        if handle is not None:
            # hit raced the copy: give it a short grace (the common case
            # is a near-drained handle) bounded by the SAME threshold the
            # feature treats as a stall incident — this wait blocks every
            # active session's decode. On expiry fall back WITHOUT
            # dropping: the copy is healthy, merely queued behind other
            # handles; it completes off-thread and the next turn restores
            if not handle.event.wait(self._restore_stall_s):
                self.restore_failures_total += fail
                self._flight_dump("spill-stall", extra={
                    "restore-wait-ms": round((time.monotonic() - t0) * 1e3, 3),
                    "reuse-tokens": p,
                })
                return False
            self._drain_spills()
            if not entry.host or entry.dropped:
                self.restore_failures_total += fail
                if not entry.dropped:
                    index._drop(pool, entry)
                return False
        n = len(entry.host)
        if n == 0:
            # belt to _drain_spills' braces: an entry with neither device
            # nor host pages can't serve anything — a zero-page "restore"
            # would count a warm hit whose prefix KV was never written
            self.restore_failures_total += fail
            index._drop(pool, entry)
            return False
        # PIN across the eviction window below: evict_for's spill_cb can
        # cascade into _evict_host_for, whose LRU victim scan would
        # otherwise pick THIS entry (host-only and idle — the natural
        # minimum) and drop it out from under the restore
        index.acquire(entry)
        try:
            if pool.free_pages < n:
                index.evict_for(pool, n, spill_cb=self._ensure_spilled)
            pages = pool.alloc_pages(n)
        finally:
            index.release(entry)
        if pages is None:
            # no device room even after demotions — entry stays hibernated,
            # the admission recomputes (or defers on its own reservation)
            self.restore_failures_total += fail
            return False
        if entry.dropped or len(entry.host) != n:
            # paranoia (python -O strips the attach assertion): the entry
            # must still own exactly the arena slots we sized against
            pool.decref(pages)
            self.restore_failures_total += fail
            if not entry.dropped:
                index._drop(pool, entry)
            return False
        if self._injector is not None:
            self._injector.corrupt_host_page(tier, entry.host)
        ok = True
        self._record_program("page-restore")
        for slot, dst in zip(entry.host, pages):
            block = tier.read(slot)
            if block is None:
                ok = False  # checksum mismatch: host copy is poison
                break
            pool.dev = _page_restore(pool.dev, block, jnp.asarray(dst, jnp.int32))
        if not ok:
            pool.decref(pages)
            index._drop(pool, entry)  # frees the arena slots too
            self.restore_failures_total += fail
            log.warning(
                "host-tier restore failed checksum (%d pages) — falling "
                "back to re-prefill", n,
            )
            return False
        index.attach_device_pages(pool, entry, pages)
        self.restore_pages_total += n
        self.restore_bytes_total += n * tier.bytes_per_page
        took = time.monotonic() - t0
        self._restore_ms_iter += took * 1e3
        if self._obs.on:
            self._obs.record("engine_restore_s", took)
        if took > self._restore_stall_s:
            # a restore that stalls an admission past the bound is an
            # incident worth a postmortem ring (slow host RAM? checksum
            # thrash? arena contention?) — same debounce as every reason
            self._flight_dump("spill-stall", extra={
                "restore-ms": round(took * 1e3, 3),
                "restore-pages": n,
                "reuse-tokens": p,
            })
        return True

    # -- durable session tier (docs/SERVING.md §23) --------------------------

    def _durable_job(self, entry) -> Optional[dict]:
        """Materialize one entry's checkpoint job (engine thread): raw
        page byte images + their SPILL-TIME checksums. Host-resident
        entries read the arena and ship the stored stamps as-is;
        device-only entries (hibernation's device path) fetch their page
        snapshots and stamp here — for a page that never spilled, this
        first hash IS its spill-time stamp. None when the entry holds
        nothing checkpointable (in-flight spill, arena rot, no token
        path) — the caller skips, never fails."""
        from langstream_tpu.serving.pagepool import (
            join_page_bytes, page_checksum,
        )

        tier, pool, index = self._host_tier, self._pagepool, self._prefix_index
        if entry.dropped or not entry.digest or entry.length <= 0:
            return None
        tokens = index.entry_tokens(entry)
        if len(tokens) != entry.length:
            return None
        n = math.ceil(entry.length / self.page_size)
        pages_raw: list[bytes] = []
        sums: list[str] = []
        if (
            entry.host
            and entry.spilling is None
            and tier is not None
            and len(entry.host) >= n
        ):
            for slot in entry.host[:n]:
                block = tier.read(slot)
                if block is None:
                    return None  # arena rot: restore paths count it
                leaves = jax.tree.leaves(block)
                pages_raw.append(join_page_bytes(leaves))
                sums.append(tier.checksum(slot).hex())
        elif entry.pages and len(entry.pages) >= n:
            self._record_program("page-snapshot")
            for pg in entry.pages[:n]:
                block = _page_snapshot(pool.dev, jnp.asarray(pg, jnp.int32))
                leaves = [
                    np.asarray(jax.device_get(leaf))
                    for leaf in jax.tree.leaves(block)
                ]
                pages_raw.append(join_page_bytes(leaves))
                sums.append(page_checksum(leaves).hex())
        else:
            return None
        return {
            "digest": entry.digest, "length": int(entry.length),
            "tokens": tokens, "pages_raw": pages_raw, "checksums": sums,
            "page_size": self.page_size,
            "bytes_per_page": pool.bytes_per_page,
        }

    def _maybe_checkpoint(self, entry) -> None:
        """Enqueue a durable checkpoint for ``entry`` if the tier is on
        and no checkpoint exists yet (engine thread; the disk write runs
        on the durable worker). Failure-free by design: anything not
        checkpointable is simply skipped — the session keeps its
        host/device copy and a later trigger retries."""
        if self._durable is None or self._durable_worker is None:
            return
        if self._durable.contains(entry.digest):
            return
        job = self._durable_job(entry)
        if job is not None:
            self._durable_worker.submit(job)

    def _durable_admit(self, request, prompt) -> Optional[tuple]:
        """Admission-path resurrection: no live index candidate covered
        ``prompt``, so probe the durable store at the deepest boundary,
        restore + verify the checkpoint and bind it INLINE on the engine
        thread (_migrate_rpc would deadlock the loop against itself).
        Returns ``(length, entry)`` like a radix hit, or None with the
        request degrading to a cold prefill. EVERY failure — torn file,
        CRC/checksum mismatch, stale manifest, stalled volume, full pool
        — dumps ``durable-restore-failed`` (token-content-free) and the
        store marks its entry dead, so a failure fires once, never a
        retry loop on poison."""
        from langstream_tpu.serving.durable import DurableError
        from langstream_tpu.serving.migrate import MigrationError, _leaf_specs
        from langstream_tpu.serving.pagepool import (
            page_checksum, prefix_digest, split_page_bytes,
        )

        store, index = self._durable, self._prefix_index
        if store is None or getattr(request, "_durable_failed", False):
            return None
        digest, length = None, 0
        for b in reversed(index.boundaries):
            if b <= len(prompt) - 1:
                d = prefix_digest(prompt[:b])
                if store.contains(d):
                    digest, length = d, b
                    break
        if digest is None:
            return None
        t0 = time.monotonic()
        self._durable_restoring = True
        try:
            rec = store.restore(digest, timeout_s=self.durable_timeout_s)
            specs = _leaf_specs(self)
            blocks = []
            for i, raw in enumerate(rec["pages"]):
                leaves = split_page_bytes(raw, specs)
                if page_checksum(leaves).hex() != rec["checksums"][i]:
                    # the manifest stamp (spill-time, never re-hashed) is
                    # the authority: poison must not be retried
                    store.invalidate(
                        digest, f"page {i} failed its spill-time checksum"
                    )
                    raise DurableError(
                        f"page {i} failed its spill-time checksum"
                    )
                blocks.append(leaves)
            self._migrate_cmd("bind", {
                "tokens": list(prompt[:length]), "length": length,
                "blocks": blocks,
            })
        except (DurableError, MigrationError, ValueError) as e:
            # a full receiver pool is the ONE retryable failure (a later
            # iteration may have evicted room); everything else is a dead
            # entry and must degrade to cold prefill exactly once
            request._durable_failed = not isinstance(e, MigrationError)
            self._flight_dump("durable-restore-failed", extra={
                "error": str(e),
                "entry-digest": digest,
                "reuse-tokens": length,
                "total-ms": round((time.monotonic() - t0) * 1e3, 3),
                "fallback": "local-cold-prefill",
            }, force=True)
            log.warning(
                "durable restore of %s failed (%s); prefilling cold",
                digest, e,
            )
            return None
        finally:
            self._durable_restoring = False
        took = time.monotonic() - t0
        if self._obs.on:
            self._obs.record("engine_durable_restore_s", took)
        self.durable_restored_hits_total += 1
        self._restore_ms_iter += took * 1e3
        # the bind inserted a live entry: serve it like any radix hit
        for p_cand, cand in reversed(index.candidates(prompt)):
            if not cand.dropped and cand.pages:
                return p_cand, cand
        return None

    def _durable_snapshot(self, tokens) -> Optional[dict]:
        """Snapshot branch for prefixes that outlived their index entry
        (engine thread, under _migrate_cmd): a P2P fetch / migration can
        be served STRAIGHT from the durable checkpoint — the wire codec
        is the disk format, so the bytes just change transports. None
        when the store has no covering entry or the read fails (the
        caller's no-prefix error stands)."""
        from langstream_tpu.serving.durable import DurableError
        from langstream_tpu.serving.migrate import _leaf_specs
        from langstream_tpu.serving.pagepool import (
            prefix_digest, split_page_bytes,
        )

        store, index = self._durable, self._prefix_index
        if store is None:
            return None
        toks = list(tokens)
        for b in reversed(index.boundaries):
            if b > len(toks):
                continue
            digest = prefix_digest(toks[:b])
            if not store.contains(digest):
                continue
            try:
                rec = store.restore(digest, timeout_s=self.durable_timeout_s)
                specs = _leaf_specs(self)
                blocks = [
                    split_page_bytes(raw, specs) for raw in rec["pages"]
                ]
            except (DurableError, ValueError) as e:
                log.warning(
                    "durable snapshot of %s failed (%s)", digest, e
                )
                return None
            return {
                "tier": "durable", "length": b, "digest": digest,
                "blocks": blocks,
                "checksums": [bytes.fromhex(s) for s in rec["checksums"]],
                "page_size": int(rec["page_size"]),
                "bytes_per_page": int(rec["bytes_per_page"]),
            }
        return None

    def hibernate(self, replica_id: str = "", timeout_s: float = 60.0) -> dict:
        """Checkpoint EVERY live prefix entry to the durable tier and
        write the replica hibernation record — the drained-replica half
        of scale-to-zero (docs/SERVING.md §23). Call AFTER drain() and
        BEFORE stop() (the holder.begin_drain ordering): the engine loop
        must still be serving commands. Returns the ledger
        ``{"entries", "bytes", "failures"}``; ``{}`` with the tier off.
        Synchronous and deadline-bounded — a wedged disk fails the
        hibernation, never the shutdown."""
        from langstream_tpu.serving.migrate import MigrationError

        if self._durable is None:
            return {}
        if self._durable_worker is not None:
            # in-flight spill-triggered checkpoints first, so the walk
            # below sees them via store.contains and skips the re-write
            self._durable_worker.flush(timeout_s)
        try:
            return self._migrate_rpc(
                "hibernate", {"replica": str(replica_id)}, timeout_s
            )
        except MigrationError as e:
            log.warning("hibernation failed (%s) — sessions stay "
                        "restorable from earlier checkpoints only", e)
            return {"entries": 0, "bytes": 0, "failures": -1}

    @property
    def restoring(self) -> bool:
        """True while a durable-tier restore is serving an admission —
        the cheap accessor /healthz surfaces as resurrection-in-progress
        (readiness probes during scale-from-zero)."""
        return self._durable_restoring

    def prefill_tps_estimate(self) -> float:
        """Landed prefill throughput (tokens/s): the real prompt tokens of
        the prefill groups and segment streams whose first tokens have
        landed, over their summed dispatch→ready time — one sample per
        dispatch span, the same one `engine_prefill_group_s` records, so
        waiting behind the decode chunk in flight counts (a fetch that
        replaces a prefill waits there too). The fleet beacon ships this
        for the router's fetch-vs-prefill cost model (docs/SERVING.md
        §21/§23); 0.0 until a group lands, or with observability off (the
        router then falls back to its flat threshold)."""
        if self._prefill_landed_s <= 0.0:
            return 0.0
        return round(self._prefill_tokens_landed / self._prefill_landed_s, 1)

    # -- KV-page migration (disaggregated serving, docs/SERVING.md §18) ------

    def _drain_migrations(self) -> None:
        """Serve queued migration commands (engine thread, iteration top).
        Each command replies on its own queue; a command that fails
        replies the exception instead of killing the loop — a broken
        migration degrades ONE transfer, never the engine."""
        from langstream_tpu.serving.migrate import MigrationError

        while True:
            try:
                kind, payload, reply = self._migrate_cmds.get_nowait()
            except queue.Empty:
                return
            try:
                reply.put(("ok", self._migrate_cmd(kind, payload)))
            except MigrationError as e:
                self.migrate_failures_total += 1
                reply.put(("err", e))
            except Exception as e:  # noqa: BLE001 — degrade the transfer only
                log.exception("migration command %s failed", kind)
                self.migrate_failures_total += 1
                reply.put(("err", MigrationError(f"{kind}: {e}")))

    def _migrate_cmd(self, kind: str, payload: dict) -> dict:
        from langstream_tpu.serving.migrate import MigrationError
        from langstream_tpu.serving.pagepool import prefix_digest

        pool, index = self._pagepool, self._prefix_index
        if index is None:
            raise MigrationError(
                "KV-page migration needs the prefix index "
                "(prefix-cache: auto)"
            )
        if kind == "snapshot":
            hit = index.deepest_entry(payload["tokens"])
            if hit is None:
                # the live index lost it, but a durable checkpoint may
                # still cover the prompt (§23): the wire codec is the
                # disk format, so serve the P2P fetch from disk directly
                durable = self._durable_snapshot(payload["tokens"])
                if durable is not None:
                    return durable
                raise MigrationError("no published prefix covers this prompt")
            length, entry = hit
            n = math.ceil(length / self.page_size)
            tier = self._host_tier
            # hibernated (and spilled-while-resident) sessions send
            # STRAIGHT from the host arena — no device restore, and the
            # stamped spill checksum ships as-is; a completed spill is
            # required (an in-flight handle's slots are the worker's)
            if entry.host and entry.spilling is None and tier is not None:
                slots = list(entry.host[:n])
                if len(slots) == n:
                    blocks, sums = [], []
                    for s in slots:
                        block = tier.read(s)
                        if block is None:
                            blocks = None  # checksum rot: fall to device
                            break
                        blocks.append(jax.tree.leaves(block))
                        sums.append(tier.checksum(s))
                    if blocks is not None:
                        return {
                            "tier": "host", "length": length,
                            "digest": prefix_digest(
                                list(payload["tokens"])[:length]
                            ),
                            "blocks": blocks, "checksums": sums,
                            "page_size": self.page_size,
                            "bytes_per_page": pool.bytes_per_page,
                        }
            if not entry.pages or len(entry.pages) < n:
                raise MigrationError(
                    "prefix entry holds no readable pages (host copy "
                    "failed verification and no device half exists)"
                )
            # device tier: slice each page into INDEPENDENT buffers (the
            # spill path's decoupling trick) — the caller's device→host
            # fetch can never race a later donating rewrite or a free
            self._record_program("page-snapshot")
            blocks = [
                _page_snapshot(pool.dev, jnp.asarray(p, jnp.int32))
                for p in entry.pages[:n]
            ]
            return {
                "tier": "device", "length": length,
                "digest": prefix_digest(list(payload["tokens"])[:length]),
                "blocks": blocks, "checksums": None,
                "page_size": self.page_size,
                "bytes_per_page": pool.bytes_per_page,
            }
        if kind == "bind":
            tokens, length = payload["tokens"], int(payload["length"])
            blocks = payload["blocks"]
            if length not in index.boundaries:
                raise MigrationError(
                    f"migrated length {length} is not a prefix boundary "
                    f"here (boundaries {index.boundaries}) — sender and "
                    "receiver disagree on bucket config"
                )
            if index.has(tokens, length):
                # idempotent re-migration (retry after a lost ACK): the
                # prefix is already resident — nothing to bind, ACK again
                return {"pages": 0, "bytes": 0, "already": True}
            n = math.ceil(length / self.page_size)
            if len(blocks) != n:
                raise MigrationError(
                    f"migration carries {len(blocks)} pages for a "
                    f"{length}-token prefix; expected {n}"
                )
            if pool.free_pages < n:
                index.evict_for(
                    pool, n,
                    spill_cb=self._ensure_spilled if self._spill_on else None,
                )
            pages = pool.alloc_pages(n)
            if pages is None:
                raise MigrationError(
                    f"receiver pool exhausted ({pool.free_pages} free, "
                    f"{n} needed) — nothing was bound"
                )
            treedef = jax.tree.structure(pool.dev)
            self._record_program("page-restore")
            try:
                for leaves, dst in zip(blocks, pages):
                    block = jax.tree.unflatten(treedef, leaves)
                    pool.dev = _page_restore(
                        pool.dev, block, jnp.asarray(dst, jnp.int32)
                    )
                entry = index.insert(pool, tokens, length, tuple(pages))
            except BaseException:
                pool.decref(pages)  # receiver frees on ANY abort — no leak
                raise
            pool.decref(pages)  # the index holds the one reference now
            if entry is None:
                # cap full and nothing evictable: insert declined (the
                # decref above already returned the pages — uploaded bytes
                # are garbage in free pages, same as any freed slot)
                raise MigrationError(
                    "receiver prefix index is at capacity with every "
                    "entry pinned — migration not bound"
                )
            if self._spill_on:
                # a migrated-in session hibernates like a published one
                self._spill_candidates.append(entry)
            self.migrate_pages_in_total += n
            self.migrate_bytes_in_total += n * pool.bytes_per_page
            return {"pages": n, "bytes": n * pool.bytes_per_page}
        if kind == "release":
            tokens, length = payload["tokens"], int(payload["length"])
            path = index._walk(tokens, limit=length)
            entry = path[-1].entry if path else None
            if entry is None or entry.length != length or entry.dropped:
                return {"released": False, "pages": 0}
            if entry.pins > 0:
                # an in-flight admission is reading it: retain (refcounts
                # keep the pages valid); LRU reclaims it once idle
                return {"released": False, "pages": 0}
            n = max(len(entry.pages), len(entry.host))
            index._drop(pool, entry)
            self.migrate_pages_out_total += n
            self.migrate_bytes_out_total += n * pool.bytes_per_page
            return {"released": True, "pages": n}
        if kind == "hibernate":
            # drained-replica shutdown (§23): checkpoint EVERY live entry
            # synchronously (the worker queue was flushed by hibernate()
            # before this RPC, so contains() skips already-durable ones),
            # then stamp the hibernation record — the resurrection beacon
            from langstream_tpu.serving.durable import DurableError

            store = self._durable
            if store is None:
                raise MigrationError("durable tier is off")
            done, failures, total_bytes, digests = 0, 0, 0, []
            for entry in list(index._live):
                if entry.dropped or not entry.digest:
                    continue
                if store.contains(entry.digest):
                    digests.append(entry.digest)
                    continue
                job = self._durable_job(entry)
                if job is None:
                    failures += 1
                    continue
                t0 = time.monotonic()
                try:
                    total_bytes += store.checkpoint(
                        job["digest"], job["length"], job["tokens"],
                        job["pages_raw"], job["checksums"],
                        job["page_size"], job["bytes_per_page"],
                    )
                except (DurableError, OSError) as e:
                    log.warning(
                        "hibernation checkpoint of %s failed: %s",
                        entry.digest, e,
                    )
                    failures += 1
                    continue
                if self._obs.on:
                    self._obs.record(
                        "engine_durable_checkpoint_s",
                        time.monotonic() - t0,
                    )
                done += 1
                digests.append(entry.digest)
            try:
                store.write_hibernation(
                    payload.get("replica") or "", digests,
                    compile_cache_dir=jax.config.jax_compilation_cache_dir,
                )
            except OSError as e:
                log.warning("hibernation record write failed: %s", e)
                failures += 1
            return {
                "entries": done, "bytes": total_bytes, "failures": failures,
            }
        raise MigrationError(f"unknown migration command {kind!r}")

    def _migrate_rpc(self, kind: str, payload: dict, timeout_s: float) -> dict:
        """Caller-thread half of a migration command: enqueue, wait, bound
        by ``timeout_s`` (the deadline-bounded-migrate contract — a wedged
        engine fails the MIGRATION, and the router falls back, rather than
        parking the hop forever)."""
        from langstream_tpu.serving.migrate import MigrationError

        if self._dead is not None:
            raise MigrationError("engine is stopped") from self._dead
        if self._spmd is not None:
            raise MigrationError(
                "KV-page migration is not on the SPMD wire yet (the bind/"
                "restore dispatches would need follower replay)"
            )
        refused = migration_refused(self.config)
        if refused:
            raise MigrationError(refused)
        reply: "queue.SimpleQueue" = queue.SimpleQueue()
        self._migrate_cmds.put((kind, payload, reply))
        self._wake.set()
        try:
            status, out = reply.get(timeout=max(0.05, float(timeout_s)))
        except queue.Empty:
            raise MigrationError(
                f"engine did not serve the {kind} command within "
                f"{timeout_s:.1f}s"
            ) from None
        if status == "err":
            raise out
        return out

    def migrate_snapshot(self, tokens, timeout_s: float = 30.0) -> dict:
        """Serialize the deepest published prefix covering ``tokens`` for
        the migration wire (any thread): per-page host leaf blocks + the
        blake2b checksum stamped the same way the host spill tier stamps
        arena pages. Device-resident entries are sliced into independent
        buffers on the engine thread and fetched HERE (off the engine
        loop); hibernated entries ship their arena bytes + stored sums
        with no device work at all. Raises MigrationError on any failure
        (nothing is freed — the sender retains until ACK)."""
        from langstream_tpu.serving.migrate import MigrationError
        from langstream_tpu.serving.pagepool import page_checksum

        out = self._migrate_rpc(
            "snapshot", {"tokens": list(tokens)}, timeout_s
        )
        if out["tier"] == "device":
            try:
                fetched = [
                    [np.asarray(jax.device_get(leaf)) for leaf in
                     jax.tree.leaves(block)]
                    for block in out["blocks"]
                ]
            except Exception as e:  # noqa: BLE001 — device fetch failed
                raise MigrationError(f"page snapshot fetch failed: {e}") from e
            out["blocks"] = fetched
            out["checksums"] = [page_checksum(b) for b in fetched]
        return out

    def migrate_bind(
        self, tokens, length: int, blocks: list, timeout_s: float = 30.0,
    ) -> dict:
        """Bind already-checksum-VERIFIED migrated pages into this
        replica's pool + prefix index (any thread; the wire layer in
        serving/migrate.py owns the verification — this method trusts its
        caller exactly as far as one process boundary). On any failure
        nothing stays bound: allocated pages return to the free list
        before the error propagates (receiver frees on abort)."""
        return self._migrate_rpc(
            "bind",
            {"tokens": list(tokens), "length": int(length), "blocks": blocks},
            timeout_s,
        )

    def migrate_release(
        self, tokens, length: int, timeout_s: float = 10.0,
    ) -> dict:
        """Drop the migrated-out prefix entry (sender side, ONLY after the
        receiver's ACK): pages still aliased by active slots survive via
        refcounts; a pinned entry is retained for LRU to reclaim."""
        return self._migrate_rpc(
            "release", {"tokens": list(tokens), "length": int(length)},
            timeout_s,
        )

    def migrate_limits(self) -> dict:
        """Static pool geometry the migration RECEIVER uses to bound what
        it will read off the wire (runtime/http_server.py §21): page-bytes
        and total page count are fixed at pool construction, so any thread
        may read them lock-free. Empty dict when this engine has no paged
        pool (nothing can bind, so the receiver refuses early)."""
        pool = getattr(self, "_pagepool", None)
        if pool is None:
            return {}
        return {
            "bytes_per_page": int(pool.bytes_per_page),
            "pages_total": int(pool.num_pages),
        }

    def _spec_admit(self, idx: int, prompt: list[int]) -> None:
        """Create the slot's draft index at admission, seeded with the
        prompt (prompt-lookup: the prompt is where repeated spans live).
        Generated tokens join via _deliver_token as they are ACCEPTED —
        never from the verify chunk's written-but-rejected columns, so the
        index can only propose continuations of tokens that were actually
        emitted."""
        if self._spec_enabled:
            index = NGramIndex()
            index.extend(prompt)
            self._spec_index[idx] = index

    def _maybe_publish(self, idx: int, prompt: list[int]) -> None:
        """Publish after a completed prefill, unless that prefix is already
        indexed: pure HOST bookkeeping — the slot's leading pages join the
        index with a refcount bump, no device copy at all.

        Speculation invariant: publish boundaries are PROMPT-prefix rows
        (p ≤ len(prompt)) written by prefill — never generated-region rows,
        where a verify chunk may have written past the ACCEPTED length and
        left stale rejected-draft K/V. Accepted-length, not written-length,
        is the only boundary the index may ever see.

        Adapter invariant: a tenant slot's prefix KV embeds its wk/wv
        adapter deltas — publishing it under the shared (base) trie would
        poison every later base admission that aliased it. Tenant slots
        never publish."""
        if self._adapters is not None and self._adapter_rows_auth[idx] != 0:
            return
        index = self._prefix_index
        if index is None:
            return
        p = index.publish_length(len(prompt))
        if p <= 0 or index.has(prompt, p):
            return
        import math as _math

        pool = self._pagepool
        n = _math.ceil(p / self.page_size)
        owned = pool.slot_pages(idx)
        if len(owned) < n:
            return  # reservation narrower than the boundary (can't
            # happen for a prompt that reached p; guard anyway)
        entry = index.insert(pool, prompt, p, tuple(owned[:n]))
        if entry is not None and self._spill_on:
            # hibernation candidate: once idle past spill-idle-s the
            # sweep spills its pages host-side (published prefix pages
            # are stable — positions only grow — so the copy is valid
            # even while the publisher keeps decoding)
            self._spill_candidates.append(entry)

    # -- chunked prefill (long-context) -------------------------------------

    def _long_step(self, budget: Optional[int] = None) -> tuple[list[tuple], int]:
        """Drive the chunked-prefill streams: start streams for queued long
        requests while slots and stream capacity allow, then dispatch ONE
        segment per active stream per iteration, round-robin, gated by the
        fused-iteration token ``budget`` (at least one segment always rides
        when a stream is active, so progress is guaranteed even with
        budget < segment width). Decode chunks interleave between segments,
        so active generations keep streaming while a 128k prompt prefills.
        Returns (deferred fetch entries, prefill tokens dispatched)."""
        entries: list[tuple] = []
        spent = 0
        width = self.prefill_buckets[-1]
        while self._long_queue and len(self._longs) < self.max_prefill_streams:
            free = next(
                (
                    i
                    for i, s in enumerate(self._slots)
                    if not s.active and i not in self._reserved
                ),
                None,
            )
            if free is None:
                break
            request = self._long_queue.pop(0)
            if not self._prequalify(request):
                continue  # resolved in the long backlog
            if self._agentic and not self._resolve_agentic(request):
                continue  # unknown adapter / pinned pool: request resolved
            # reserve the whole prompt's pages up front, aliasing ANY
            # cached prefix boundary (segments write at global offsets, so
            # no full-segment-width alignment constraint). Exhaustion
            # defers the stream; the request keeps its backlog spot.
            base = self._paged_bind(free, request)
            if base is None:
                self._long_queue.insert(0, request)
                break
            if base < 0:
                continue  # can-never-fit: _paged_bind resolved it
            self._reserved.add(free)
            self._longs[free] = {
                "idx": free, "request": request, "seg": 0, "base": base,
            }
        if not self._longs:
            return entries, spent
        # round-robin so two concurrent streams alternate segments fairly
        # when the budget covers only one of them per iteration
        order = sorted(self._longs)
        start_at = next(
            (j for j, i in enumerate(order) if i > self._long_rr), 0
        )
        for idx in order[start_at:] + order[:start_at]:
            if budget is not None and (spent or entries) and spent >= budget:
                break
            self._long_rr = idx
            entries.extend(self._segment_step(self._longs[idx]))
            spent += width
        return entries, spent

    def _segment_step(self, st: dict) -> list[tuple]:
        """Dispatch one chunked-prefill segment for one stream; on the
        final segment, activate the slot host-side. A stream whose request
        was cancelled (or blew its deadline) mid-prefill aborts here, before
        spending another segment of prefill on it — host-side only, so SPMD
        followers simply stop receiving its segments."""
        request: GenerationRequest = st["request"]
        now = time.monotonic()
        deadline = request.deadline_at()
        if request.cancelled or (deadline is not None and now >= deadline):
            idx = st["idx"]
            self._free_slot_pages(idx)
            self._reserved.discard(idx)
            self._longs.pop(idx, None)
            if request.cancelled:
                with self._stats_lock:
                    self.cancelled_total += 1
                reason = "cancelled"
            else:
                # mid-PREFILL expiry: zero tokens generated, so this is
                # the waiting bucket (prefill backlog), not mid-decode —
                # the queue/decode split is what operators alert on
                with self._stats_lock:
                    self.deadline_queue_total += 1
                reason = "deadline"
            request._finish(GenerationResult(
                tokens=[], finish_reason=reason,
                prompt_tokens=len(request.prompt_tokens),
                ttft_s=0, total_s=now - request.submitted_at,
            ))
            if self._obs.on:
                emit_request_spans(
                    request.trace_id,
                    {"submitted": request.submitted_at, "finished": now},
                    {
                        "slot": idx,
                        "path": "long",
                        "prompt_len": len(request.prompt_tokens),
                        "generated_tokens": 0,
                        "finish_reason": reason,
                        "prefill_chunks": st["seg"],
                    },
                    status="ok" if reason == "cancelled" else f"error: {reason}",
                )
            return []
        prompt = request.prompt_tokens
        width = self.prefill_buckets[-1]
        # ``base``: prefix-reuse offset — chunked prefill starts at the
        # reuse point
        s0 = st.get("base", 0) + st["seg"] * width
        seg = prompt[s0 : s0 + width]
        tokens = np.zeros((1, width), np.int32)
        tokens[0, : len(seg)] = seg
        opts = request.options
        idx = st["idx"]
        start = st["seg"] == 0
        final = s0 + width >= len(prompt)
        if self._spmd is not None:
            self._spmd.announce(wire.ControlBlock(
                op=wire.OP_LONG_SEG, width=width, n_rows=1, tokens=tokens,
                s0=s0, seg_len=len(seg),
                long_start=start, long_final=final, long_idx=idx,
                prompt_len=len(prompt),
                temps=np.asarray([opts.temperature], np.float32),
                top_ks=np.asarray([opts.top_k], np.int32),
                top_ps=np.asarray([opts.top_p], np.float32),
            ))
        disp = st.get("disp")
        # a model whose segments return expert counts fetches each one: a
        # span a segment, with its own device_ms (else one for the stream)
        per_segment = self.config.holds_experts
        if start:
            # the stream's admission: its `engine.prefill` runs from here
            st["started"] = time.monotonic()
        if start or per_segment:
            disp = st["disp"] = self._new_segment_dispatch(
                "_paged_segment_and_sample", width, len(seg), request,
                st.setdefault("stages", [0.0, 0.0, 0.0]),
            )
        elif disp is not None:
            disp.attrs["segments"] += 1
            disp.attrs["real_tokens"] += len(seg)
            disp.attrs["computed_tokens"] += width
            self._launch_unfetched = True  # a launch all the same (`_account_launch`)
        try:
            # straight into the slot's pages: no local cache, no final
            # insert/splice — the chain scatter on ``final`` is the only
            # extra dispatch
            first = self._dev_paged_segment(
                tokens, s0, len(seg), idx,
                opts.temperature, opts.top_k, opts.top_p,
                final=final, prompt_len=len(prompt),
                agentic_rows=request._agentic_rows,
            )
        except Exception as e:  # noqa: BLE001 — fail the request, not the engine
            if self._spmd is not None:
                raise  # multi-host: crash the replica (see _admit rationale)
            log.exception("chunked prefill failed at segment %d", st["seg"])
            self._free_slot_pages(idx)
            self._reserved.discard(idx)
            self._longs.pop(idx, None)
            request._finish(GenerationResult(
                tokens=[], finish_reason="error", prompt_tokens=0,
                ttft_s=0, total_s=0, error=e,
            ))
            return []
        st["seg"] += 1
        self._note_key_blocks(disp)
        self._count_reads(disp, *self._reads.segment(s0, len(seg), width))
        if disp is not None and self._pagepool.window is not None:
            disp.attrs["window_pages_recycled"] = self._window_recycled
        if not final:
            if per_segment:  # nothing to deliver: the fetch lands the span
                return [(
                    "segment",
                    self._submit_fetch(
                        first, self._dispatch_seq, self._moe_counts()
                    ),
                    disp,
                )]
            return []  # more segments to go

        # final segment landed on device: activate the slot host-side
        self._longs.pop(idx, None)
        self._reserved.discard(idx)
        slot = self._slots[idx]
        slot.request = request
        slot.position = len(prompt)
        slot.generated = []
        slot.started_at = st["started"]
        slot.first_token_at = 0.0
        slot.reset_obs(
            "long", st["seg"], disp.attrs["seq"] if disp is not None else 0
        )
        self._slot_bind_agentic(idx, request)
        with self._stats_lock:
            self.total_requests += 1
        self._note_tenant_admitted(request)
        self._spec_admit(idx, prompt)
        self._maybe_publish(idx, prompt)
        return [(
            "prefill",
            self._submit_fetch(
                first, disp.attrs["seq"] if disp is not None else 0,
                self._moe_counts() if per_segment else None,
            ),
            [(idx, request)], disp,
        )]

    def _dispatch_chunk(self, clean: bool = True, pipelined: bool = False) -> tuple:
        """Dispatch one multi-step decode; returns (device tokens,
        per-slot request snapshot, steps, dispatch time, clean, pipelined)
        for deferred host processing. ``clean``: no prefill dispatch rode
        the in-order stream ahead of this chunk in the same iteration —
        only clean chunks feed the step-time EMA, else the gauge charges
        prefill wall-time to decode and under-reports achieved bandwidth
        exactly when prefill overlaps. ``pipelined``: earlier chunks were
        still in flight at dispatch, so the EMA samples the
        inter-completion interval instead of dispatch→ready wall (which
        would read ~2× at steady state, the predecessor's remaining
        execution counted into this chunk's)."""
        # validate BEFORE the announce: a quarantine here frees pages
        # (announced as OP_PAGE_FREE) and deactivates the slot, and the
        # mask announced below must already reflect both
        self._page_integrity_check()
        self._adapter_integrity_check()
        if self.config.fills_blocks:
            return self._dispatch_block_chunk(clean, pipelined)
        # the page table is the bound on what a row reads, and every chunk
        # is a full one: the decode surface is ONE program. Tail and
        # headroom overshoot lands on out-of-bounds scatters XLA drops, and
        # the host stops delivering at max_new_tokens / cache end as always
        # (up to decode_chunk - 1 wasted steps a REQUEST: size decode_chunk
        # to the workload)
        steps = self.decode_chunk
        stale = self._collect_stale()
        mask = self._active_mask()
        if self._spmd is not None:
            self._spmd.announce(wire.ControlBlock(
                op=wire.OP_DECODE, steps=steps, n_rows=len(stale),
                slots=np.asarray(stale, np.int32),
                # slot liveness is leader-only host state (completions are
                # discovered at fetch time): ship the mask so followers
                # sentinel the same page-table rows
                mask=mask,
            ))
        live = [slot for slot in self._slots if slot.active]
        pages_visited, rows_written = self._kv_page_counts(steps)
        # a window row's pages move on to where this chunk's steps write
        recycled = self._advance_window_rows(steps)
        disp = self._new_dispatch(
            "engine.decode_chunk",
            program="_paged_decode_chunk",
            steps=steps, active_rows=len(live),
            # the (row, step) pairs the chunk computes: `tokens_delivered`
            # (`_process_chunk`) is how many of them a request received
            row_steps=steps * len(live),
            clean=clean, pipelined=pipelined,
            kv_pages_visited=pages_visited, kv_rows_written=rows_written,
            # (row, step) pairs whose recurrent state is updated, a linear
            # layer: the pairs that write a K/V row, idle rows move none
            **({"state_rows": rows_written} if self.config.is_recurrent else {}),
            **({} if recycled is None else {"window_pages_recycled": recycled}),
        )
        # what the chunk's attention reads, by the model's own rule: counted
        # into `stats()` whether or not a span carries it
        self._count_reads(disp, *self._reads.decode(self._live_lengths(live), steps))
        with jax.profiler.TraceAnnotation(
            "engine.decode_chunk", seq=self._dispatch_seq, steps=steps,
            t_mono_ns=_mono_ns(disp),
        ):
            chunk = self._dev_decode(steps, stale, mask=mask)
        counts = self._moe_counts()
        snapshot = [
            (i, slot.request) for i, slot in enumerate(self._slots) if slot.active
        ]
        for slot in live:
            slot.ahead += steps
        with self._stats_lock:
            self._busy_steps += steps
        # hand the chunk to the fetch thread NOW: it blocks on the bytes
        # while this thread keeps dispatching — the fetch is hidden at
        # every chunk size, not only when chunk compute covers it
        return (
            "chunk", self._submit_fetch(chunk, self._dispatch_seq, counts),
            snapshot, steps, time.monotonic(), clean, pipelined, disp,
        )

    @staticmethod
    def _live_lengths(live: list) -> list[int]:
        """The columns each live row's next step sees: the position being
        written, plus one. The device's position leads the host's by the
        row's steps still in flight (``ahead``)."""
        return [slot.position + slot.ahead + 1 for slot in live]

    def _count_reads(self, disp: Optional[Dispatch], attrs: dict, sums: dict) -> None:
        """What a launch read (models/transformer `LaunchReads`) onto its
        span, if it has one, and into the sums `stats()` reports. The
        attributes are the model's to name: "offset", "kv_tokens_read",
        "kv_tokens_read_window", "index_tokens_scored", "kv_tokens_selected",
        "latent_tokens_expanded", "latent_columns_expanded",
        "latent_expanded_window" (listed because the harness's family tests
        look for a span attribute's name in THIS file's text)."""
        if sums:
            with self._stats_lock:
                for name, n in sums.items():
                    self._read_totals[name] += n
        if disp is not None:
            disp.attrs.update(attrs)

    def _advance_window_rows(self, steps: int) -> Optional[int]:
        """A model with window layers, before a decode chunk of ``steps``:
        every active row's window pages move on to where the chunk writes
        (`WindowPageGroup.advance`; the device's position leads the host's
        by ``ahead``). Returns the pages recycled, for the chunk's span; None
        without window layers."""
        pool = self._pagepool
        if pool.window is None:
            return None
        recycled = 0
        for i, slot in enumerate(self._slots):
            if slot.active:
                first = slot.position + slot.ahead
                recycled += pool.window_advance(i, first, first + steps - 1)
        return recycled

    def _count_segment_write(self, width: int, s0: int) -> None:
        """One more segment under the writer its program takes for it: the
        program decides by `segment_copies_pages` (what it can see of the
        pool, the width and the config) and by whether the segment starts on
        a page's edge, which it asks of its positions and the host knows as
        ``s0`` (models/transformer `_paged_write_rows`)."""
        pages = s0 % self.page_size == 0 and segment_copies_pages(
            self._pagepool.dev, width, self.page_size, self.config
        )
        self._segment_writes["pages" if pages else "scatter"] += 1

    def _count_segment_key_blocks(self, width: int, s0: int) -> None:
        """The key blocks this segment's attention walks, by the program's
        own rule (models/transformer `segment_blocks_visited`): kept for its
        span (`_note_key_blocks`) and summed into `stats()`."""
        blocks = self._segment_key_blocks_last = self._reads.key_blocks(s0, width)
        with self._stats_lock:  # `stats()` copies the sums under it
            for name, n in blocks.items():
                key = name.replace("_", "-")
                self._segment_key_blocks[key] = self._segment_key_blocks.get(key, 0) + n

    def _note_key_blocks(self, disp: Optional[Dispatch]) -> None:
        """``key_blocks`` (and a window model's ``key_blocks_window``) of the
        segment just dispatched onto its span, added to what a stream's
        earlier segments put there."""
        if disp is not None:
            for name, n in self._segment_key_blocks_last.items():
                disp.attrs[name] = disp.attrs.get(name, 0) + n

    def _kv_pages_written(self, slots, width: int) -> int:
        """kv_pages_written of an admission group dispatched now, per layer
        and leaf: the entries of its rows' tables under ``width / page_size``
        that are mapped, which are the copies `paged_insert_pages` issues (a
        padding row maps none). 0 where the insert is the scatter
        (models/transformer `insert_copies_pages`)."""
        pool = self._pagepool
        if not insert_copies_pages(pool.dev, width, self.page_size, self.config):
            return 0
        tables = pool.rows_tables(slots)[:, : width // self.page_size]
        return int((tables != pool.oob).sum())

    def _kv_page_counts(self, steps: int) -> tuple[int, int]:
        """(kv_pages_visited, kv_rows_written) of a decode chunk dispatched
        now, per layer, from one set of lengths: each active row's live
        length at every step (`_live_lengths`'s: the position being
        written, plus one) and the pages its table maps at dispatch.
        Inactive rows have no table and count for nothing.

        Pages visited: the page iterations the paged decode kernel runs,
        the pages of that length capped by what the table maps (a row that
        steps past its reservation inside the chunk stops growing:
        models/transformer `_paged_lengths`). Rows written: the (row, step)
        pairs whose write page is mapped (`_page_index`); every other pair
        DROPS. It is the copies `paged_kv_write` issues a leaf, which costs
        per live row; a warm-up chunk writes none."""
        rows = [i for i, slot in enumerate(self._slots) if slot.active]
        pool = self._pagepool
        first = np.asarray(self._live_lengths([self._slots[i] for i in rows]), np.int64)
        mapped = (pool.tables[rows] != pool.oob).sum(axis=1)[:, None]
        lengths = first[:, None] + np.arange(steps)[None, :]
        pages = np.minimum(-(-lengths // self.page_size), mapped)
        written = (lengths - 1) // self.page_size < mapped
        return int(pages.sum()), int(written.sum())

    def _collect_stale(self) -> list[int]:
        """Slots freed since the last dispatch whose device temperature
        must be reset — skipping slots re-admitted meanwhile (admit runs
        before dispatch and already wrote their fresh params). ONE
        definition shared by the decode and verify dispatch paths so the
        re-admitted-slot rule cannot drift between them."""
        if not self._freed_slots:
            return []
        stale = [i for i in set(self._freed_slots) if not self._slots[i].active]
        self._freed_slots.clear()
        return stale

    def _reset_stale_temps(self, stale) -> None:
        """Fixed-size all-or-out-of-bounds temp-reset scatter (padding rows
        drop) — one compiled shape regardless of how many slots freed. The
        eager scatter is its own device program: recorded, because the
        compiled_programs guarantee must not have blind spots; the warmups
        dispatch one all-OOB reset so its first real use is never a
        mid-traffic compile. Shared by _dev_decode and _dev_verify."""
        self._record_program("temp-reset")
        idxs = np.full(self.max_batch, self.max_batch, np.int32)
        idxs[: len(stale)] = stale
        self._temp_dev = self._temp_dev.at[jnp.asarray(idxs)].set(0.0, mode="drop")

    def _dev_decode(
        self, steps: int, stale, mask: Optional[np.ndarray] = None,
    ) -> Any:
        """Device layer of one decode chunk (leader + SPMD followers).
        ``mask``: the dispatch's active-slot liveness (paged table
        masking); None derives it from the local slots — followers always
        pass the leader's wire-shipped mask."""
        if self._injector is not None:
            self._injector.fire("decode")  # crashes the loop → restart path
        if self.config.fills_blocks:
            return self._dev_block(steps, stale, mask)
        lora, arows, dfa, g = self._agentic_args()
        dstate = self._dfa_state_dev
        if len(stale):
            self._reset_stale_temps(stale)
        self._record_program("paged-decode", steps)
        pool = self._pagepool
        (
            chunk,
            self._tokens_dev,
            self._positions_dev,
            pool.dev,
            self._key,
            dstate,
            self._moe_dev,
        ) = _paged_decode_chunk(
            self.params,
            self._tokens_dev,
            self._positions_dev,
            pool.dev,
            jnp.asarray(self._dispatch_tables(mask)),
            self._key,
            self._temp_dev,
            self._top_k_dev,
            self._top_p_dev,
            steps,
            self.config,
            self.page_size,
            lora,
            arows,
            dfa,
            g,
            dstate,
        )
        if dstate is not None:
            self._dfa_state_dev = dstate
        return chunk

    # -- a model that fills blocks (docs/SERVING.md "A model that fills
    # blocks"): the admission prefills whole blocks and yields no token, a
    # chunk is a scan of PASSES, a row's tokens come a block at a time ------

    def _block_prefill_group(
        self, width: int, group: list[tuple[int, GenerationRequest]]
    ) -> list[tuple]:
        """`_prefill_group` for a model that fills blocks: each prompt's
        whole blocks are prefilled; its tail (``n mod S`` tokens) rides as
        the clean positions of the row's first block, whose other positions
        are open. The group's fetch brings no token, only its landing."""
        s_len, mask_id = self.config.block_length, self.config.mask_token_id
        n_pad = next(r for r in self._admit_rungs if r >= len(group))
        tokens = np.zeros((n_pad, width), np.int32)
        whole = np.zeros(n_pad, np.int32)
        tails = np.zeros(n_pad, np.int32)
        first_block = np.full((n_pad, s_len), mask_id, np.int32)
        temps = np.zeros(n_pad, np.float32)
        top_ks = np.zeros(n_pad, np.int32)
        top_ps = np.ones(n_pad, np.float32)
        slots = np.full(n_pad, self.max_batch, np.int32)
        started = time.monotonic()
        for j, (idx, request) in enumerate(group):
            prompt = request.prompt_tokens
            whole[j] = len(prompt) // s_len * s_len
            tails[j] = len(prompt) - whole[j]
            tokens[j, : whole[j]] = prompt[: whole[j]]
            first_block[j, : tails[j]] = prompt[whole[j]:]
            temps[j] = request.options.temperature
            top_ks[j] = request.options.top_k
            top_ps[j] = request.options.top_p
            slots[j] = idx
        widened = self._count_admit_group(n_pad, width, group)
        disp = self._new_dispatch(
            "engine.admit_group", program="_block_admit_group", rows=n_pad,
            real_rows=len(group), width=width, widened_rows=widened,
            real_tokens=int(whole.sum()), computed_tokens=n_pad * width,
            trace_ids=[r.trace_id for _, r in group],
            kv_pages_written=self._kv_pages_written(slots, width),
        )
        seq = self._dispatch_seq
        with jax.profiler.TraceAnnotation(
            "engine.admit_group", seq=seq, t_mono_ns=_mono_ns(disp)
        ):
            if self._injector is not None:
                self._injector.fire("prefill")  # before any state mutates
            landed = self._dev_block_prefill(
                tokens, whole, temps, top_ks, top_ps, slots, tails, first_block
            )
        counts = self._moe_counts()
        for j, (idx, request) in enumerate(group):
            slot = self._slots[idx]
            slot.request = request
            slot.position = int(whole[j])  # the start of the row's block
            slot.generated = []
            slot.block = [int(t) for t in first_block[j, : tails[j]]] + [None] * (
                s_len - int(tails[j])
            )
            slot.block_steps = [-1] * int(tails[j]) + [None] * (s_len - int(tails[j]))
            slot.fix_steps, slot.block_rest = [], []
            slot.started_at = started
            slot.first_token_at = slot.prefill_landed_at = 0.0
            slot.reset_obs("cold", 1, seq)
            with self._stats_lock:
                self.total_requests += 1
            self._note_tenant_admitted(request)
        return [(
            "prefill", self._submit_fetch(landed, seq, counts), list(group), disp,
        )]

    def _dev_block_prefill(
        self, tokens, whole, temps, top_ks, top_ps, slots, tails, first_block
    ):
        """Device layer of a block model's batched prefill
        (`_block_admit_group`); out-of-bounds slots (padding, the warm-up)
        carry all-sentinel tables, so every write drops."""
        pool = self._pagepool
        self._record_program("block-prefill", tokens.shape[1], len(tokens))
        meta = np.stack([whole, temps, top_ks, top_ps, tails]).astype(np.float32)
        (
            landed, pool.dev, self._block_dev, self._positions_dev,
            self._temp_dev, self._top_k_dev, self._top_p_dev, self._moe_dev,
        ) = _block_admit_group(
            self.params, pool.dev, self._block_dev, self._positions_dev,
            self._temp_dev, self._top_k_dev, self._top_p_dev,
            jnp.asarray(tokens), jnp.asarray(meta), jnp.asarray(first_block),
            jnp.asarray(slots), jnp.asarray(pool.rows_tables(slots)),
            self.config, self.page_size,
        )
        return landed

    def _dispatch_block_chunk(self, clean: bool, pipelined: bool) -> tuple:
        """`_dispatch_chunk` for a model that fills blocks: ``decode_chunk``
        passes over every live row in one dispatch. What the passes did
        (which were commits, what they fixed and read) is known when the
        chunk lands: `_process_block_chunk` adds it to the span."""
        passes = self.decode_chunk
        stale = self._collect_stale()
        mask = self._active_mask()
        live = [slot for slot in self._slots if slot.active]
        disp = self._new_dispatch(
            "engine.block_chunk", program="_paged_block_chunk",
            passes=passes, active_rows=len(live), clean=clean, pipelined=pipelined,
        )
        with jax.profiler.TraceAnnotation(
            "engine.block_chunk", seq=self._dispatch_seq, steps=passes,
            t_mono_ns=_mono_ns(disp),
        ):
            chunk = self._dev_decode(passes, stale, mask=mask)
        counts = self._moe_counts()
        snapshot = [
            (i, slot.request) for i, slot in enumerate(self._slots) if slot.active
        ]
        with self._stats_lock:
            self._busy_steps += passes
        return (
            "chunk", self._submit_fetch(chunk, self._dispatch_seq, counts),
            snapshot, passes, time.monotonic(), clean, pipelined, disp,
        )

    def _dev_block(self, passes: int, stale, mask: Optional[np.ndarray] = None):
        """Device layer of one block chunk (`_paged_block_chunk`)."""
        if len(stale):
            self._reset_stale_temps(stale)
        self._record_program("paged-block", passes)
        pool = self._pagepool
        (
            reports, self._block_dev, self._positions_dev, pool.dev, self._key,
            self._moe_dev,
        ) = _paged_block_chunk(
            self.params, self._block_dev, self._positions_dev, pool.dev,
            jnp.asarray(self._dispatch_tables(mask)), self._key,
            self._temp_dev, self._top_k_dev, self._top_p_dev,
            passes, self.config, self.page_size,
        )
        return reports

    def _process_block_chunk(
        self, host, snapshot, passes: int, disp: Optional[Dispatch]
    ) -> None:
        """A landed block chunk: ``host`` [passes, B, S + 2] is each pass's
        report a row (`_paged_block_chunk`). The host follows every row
        through its passes: a denoise pass's fixed tokens and their step go
        into the row's block, a block with nothing open is DELIVERED, in
        order and whole (`_deliver_block`), and a commit moves the row on.
        What the passes did is counted here, onto the chunk's span and into
        `stats()`, a row's passes until its request left the slot."""
        if self._injector is not None:
            host, _ = self._injector.corrupt_tokens(host, snapshot)
        s_len, pool = self.config.block_length, self._pagepool
        counted = dict.fromkeys(BLOCK_COUNTERS, 0)
        counted["passes"] = passes
        with jax.profiler.TraceAnnotation("engine.process.deliver"):
            for idx, request in snapshot:
                slot = self._slots[idx]
                if slot.request is not request:  # freed/reassigned meanwhile
                    counted["idle_row_passes"] += passes
                    continue
                slot.decode_iters += 1
                t_prev = slot.last_token_at
                before = len(slot.generated)
                mapped = int((pool.tables[idx] != pool.oob).sum()) * self.page_size
                for p, row in enumerate(host[:, idx].tolist()):  # plain ints from here
                    if slot.request is not request:  # finished mid-chunk
                        counted["idle_row_passes"] += passes - p
                        break
                    counted["row_passes"] += 1
                    # every query of the block reads [0, start + S); the pass
                    # writes the block's S rows where its pages are mapped
                    counted["kv_tokens_read"] += min(slot.position + s_len, mapped)
                    if slot.position + s_len <= mapped:
                        counted["kv_rows_written"] += s_len
                    step = row[s_len]
                    if step == BLOCK_COMMIT:
                        counted["commit_row_passes"] += 1
                        slot.position += s_len
                        slot.block = [None] * s_len
                        slot.block_steps = [None] * s_len
                        continue
                    counted["denoise_row_passes"] += 1
                    counted["fixed_over_threshold"] += row[s_len + 1]
                    for j in range(s_len):
                        if row[j] != BLOCK_UNFIXED and slot.block[j] is None:
                            slot.block[j], slot.block_steps[j] = row[j], step
                            counted["tokens_fixed"] += 1
                    if None not in slot.block and not slot.block_rest:
                        self._deliver_block(idx, request)
                # a request that finished in here took its tokens with it
                done = request._result if slot.request is not request else None
                delivered = len(done.tokens if done else slot.generated) - before
                counted["tokens_delivered"] += delivered
                self._record_intertoken(slot, request, t_prev, delivered)
        with self._stats_lock:
            for name, value in counted.items():
                self._block_totals[name] += value
        if disp is not None:
            # onto the span `_land_dispatch` emitted: it holds this dict
            disp.attrs.update(
                {k: v for k, v in counted.items() if k != "passes"}
            )

    def _deliver_block(self, idx: int, request: GenerationRequest) -> None:
        """The row's block is clean: deliver its generated tokens in order
        (the prompt's tail is no answer), through `_deliver_token`, which
        stops at ``max_new_tokens`` or a stop token and then finishes the
        slot with the undelivered rest. A block past which the cache has no
        room for another ends the request too."""
        slot = self._slots[idx]
        slot.block_rest = [
            (t, st) for t, st in zip(slot.block, slot.block_steps) if st != -1
        ]
        if not slot.first_token_at and slot.block_rest:
            now = time.monotonic()
            slot.first_token_at = slot.last_token_at = now
            if self._obs.on:
                self._obs.record("engine_ttft_s", now - request.submitted_at)
            self._tenants.note_ttft(
                getattr(request.options, "tenant", None) or DEFAULT_TENANT,
                now - request.submitted_at,
            )
        for token, _ in list(slot.block_rest):
            if slot.request is not request:
                return
            self._deliver_token(idx, token)
        if slot.request is request:
            slot.block_rest = []
            if slot.position + 2 * self.config.block_length > self.max_seq_len:
                self._finish_slot(idx, "length")  # no room for another block

    def _dispatch_verify(self, clean: bool = True) -> tuple:
        """Dispatch one self-speculative verify iteration: collect up to k
        drafts per active slot from its n-gram index (host-side, free), run
        _paged_verify_chunk, and return the deferred-fetch entry. Slots whose
        index has no proposal ride the fixed-shape dispatch with zero
        drafts — their verify degenerates to a 1-token decode step (the
        accept test compares against the model's own outputs, so a bad or
        empty draft can never change what is emitted)."""
        self._page_integrity_check()  # before the announce (see chunk)
        self._adapter_integrity_check()
        k = self.spec_tokens
        # brownout level 1 (spec-shrink) proposes fewer drafts — data,
        # not shape, so the compiled verify program never changes (§19)
        k_prop = (
            self._brownout.draft_k(k) if self._brownout is not None else k
        )
        stale = self._collect_stale()
        drafts = np.zeros((self.max_batch, k), np.int32)
        proposed = np.zeros(self.max_batch, np.int32)
        for i, slot in enumerate(self._slots):
            if not slot.active:
                continue
            index = self._spec_index.get(i)
            if index is None:
                continue
            prop = index.propose(k_prop)
            with self._stats_lock:
                self.spec_draft_lookups_total += 1
                if prop:
                    self.spec_draft_hits_total += 1
                    self.spec_draft_tokens_total += len(prop)
            if prop:
                drafts[i, : len(prop)] = prop
                proposed[i] = len(prop)
        vstates = None
        if self._constrain_reg is not None:
            # per-position DFA states for the verify masks, from the HOST
            # mirror — spec mode drains the pipeline before proposing, so
            # the mirror is current at dispatch time (the invariant that
            # makes host-computed states legal here)
            t0 = time.monotonic()
            vstates = np.zeros((self.max_batch, k + 1), np.int32)
            from langstream_tpu.serving.constrain import verify_states

            for i, slot in enumerate(self._slots):
                dfa_i = self._slot_dfa.get(i)
                if not slot.active or dfa_i is None:
                    continue
                vstates[i] = verify_states(
                    dfa_i, self._dfa_host_state.get(i, 0), drafts[i]
                )
            self._note_constrain_host((time.monotonic() - t0) * 1e3)
        mask = self._active_mask()
        if self._spmd is not None:
            # speculation on the wire: ship the PROPOSALS (steps = k, the
            # drafts-per-slot width) — acceptance is computed on device,
            # identically on every host, so accepts need no forward wire
            self._spmd.announce(wire.ControlBlock(
                op=wire.OP_VERIFY, steps=k, n_rows=len(stale),
                slots=np.asarray(stale, np.int32),
                drafts=drafts, mask=mask,
            ))
        live = [slot for slot in self._slots if slot.active]
        disp = self._new_dispatch(
            "engine.verify",
            program="_paged_verify_chunk",
            steps=k + 1, active_rows=len(live),
            clean=clean, pipelined=False,
        )
        # a verify scores k+1 positions a row, each over its own prefix
        self._count_reads(disp, *self._reads.decode(self._live_lengths(live), k + 1))
        packed = self._dev_verify(drafts, stale, mask=mask, vstates=vstates)
        counts = self._moe_counts()
        snapshot = [
            (i, slot.request) for i, slot in enumerate(self._slots) if slot.active
        ]
        with self._stats_lock:
            self._busy_steps += 1
            self.spec_dispatches_total += 1
        return (
            "verify", self._submit_fetch(packed, self._dispatch_seq, counts),
            snapshot, proposed, time.monotonic(), clean, disp,
        )

    def _dev_verify(
        self, drafts: np.ndarray, stale,
        mask: Optional[np.ndarray] = None,
        vstates: Optional[np.ndarray] = None,
    ) -> Any:
        """Device layer of one verify iteration — the speculative engine's
        only decode-phase dispatch, so the decode fault site fires here
        (crash/restart drills hold under speculation too; the corrupt-type
        ``verify`` site fires host-side at fetch processing instead, where
        it can target ONE slot). ``vstates``: host-computed per-position
        DFA states (None → all-zero table, what the warmups dispatch)."""
        if self._injector is not None:
            self._injector.fire("decode")
        lora, arows, dfa, g = self._agentic_args()
        vstates_dev = None
        if dfa is not None:
            if vstates is None:
                vstates = np.zeros(
                    (self.max_batch, drafts.shape[1] + 1), np.int32
                )
            vstates_dev = jnp.asarray(vstates)
        if len(stale):
            self._reset_stale_temps(stale)
        self._record_program("paged-verify", drafts.shape[1])
        pool = self._pagepool
        (
            packed,
            self._tokens_dev,
            self._positions_dev,
            pool.dev,
            self._key,
            dstate,
            self._moe_dev,
        ) = _paged_verify_chunk(
            self.params,
            self._tokens_dev,
            self._positions_dev,
            pool.dev,
            jnp.asarray(self._dispatch_tables(mask)),
            self._key,
            self._temp_dev,
            self._top_k_dev,
            self._top_p_dev,
            jnp.asarray(drafts),
            self.config,
            self.page_size,
            lora,
            arows,
            dfa,
            g,
            vstates_dev,
        )
        if dstate is not None:
            self._dfa_state_dev = dstate
        return packed

    def _process_verify(self, entry: tuple) -> None:
        """Host half of a verify iteration: one packed fetch ([B, k+2] =
        emitted tokens ++ accepted count), then per-slot delivery of
        accepted+1 tokens through the same _deliver_token path as decode
        chunks (stop/length/cancel/deadline/NaN-sentinel all behave
        identically mid-verify)."""
        _, packed, snapshot, proposed, t_dispatch, clean, disp = entry
        host = self._fetch_result(packed)
        self._land_dispatch(disp, packed)
        # divergence echo BEFORE the injector's host-side corruption: the
        # echo is the DEVICE truth both sides must agree on — a leader-host
        # corruption drill must not read as an SPMD divergence
        self._spmd_echo(wire.ECHO_VERIFY, host)
        if self._injector is not None:
            host = self._injector.corrupt_verify(host, snapshot)
        # step-time gauge BEFORE delivery (same race rationale as
        # _sample_step_time): a verify iteration is ONE weight read (that
        # is the point), so it samples as one step; spec mode drains
        # before dispatching, so dispatch→ready wall is honest here
        now = time.monotonic()
        if snapshot and clean:
            step_s = now - t_dispatch
            self._step_time_ema_s = (
                step_s
                if self._step_time_ema_s == 0
                else 0.9 * self._step_time_ema_s + 0.1 * step_s
            )
            if self._obs.on:
                self._obs.record("engine_decode_step_s", step_s)
        self._last_chunk_ready_t = now
        out, accept = host[:, :-1], host[:, -1]
        with jax.profiler.TraceAnnotation("engine.process.deliver"):
            for idx, request in snapshot:
                slot = self._slots[idx]
                if slot.request is not request:  # freed/reassigned meanwhile
                    continue
                slot.verify_iters += 1
                t_prev = slot.last_token_at
                n_acc = int(accept[idx])
                with self._stats_lock:
                    if proposed[idx] > 0:
                        # capped at the real proposal length: padding zeros that
                        # happen to match the model are luck, not draft quality,
                        # and would push the acceptance gauge past 1.0
                        self.spec_accepted_tokens_total += min(
                            n_acc, int(proposed[idx])
                        )
                    self.spec_slot_steps_total += 1
                delivered = 0
                for j in range(n_acc + 1):
                    slot.position += 1
                    token = int(out[idx, j])
                    if token >= 0:
                        # counted per token actually DELIVERED — a request that
                        # finishes mid-verify (length/stop/deadline) drops the
                        # rest, and the NaN sentinel is a quarantine, not a
                        # token; counting n_acc+1 up front overstated the
                        # amortization gauge exactly on short-generation,
                        # high-acceptance traffic
                        with self._stats_lock:
                            self.spec_emitted_tokens_total += 1
                        delivered += 1
                    self._deliver_token(idx, token)
                    if slot.request is not request:  # finished mid-verify
                        break
                if self._obs.on and delivered:
                    self._obs.record("engine_accepted_tokens_per_step", delivered)
                self._record_intertoken(slot, request, t_prev, delivered)

    def _process_chunk(
        self, chunk, snapshot, steps: int, t_dispatch: float = 0.0,
        clean: bool = False, pipelined: bool = False,
        disp: Optional[Dispatch] = None,
    ) -> None:
        # [steps, B], fetched by the fetch thread (wait watchdog-bounded
        # under SPMD — see _fetch_result)
        host = self._fetch_result(chunk)
        self._land_dispatch(disp, chunk)
        # gauge BEFORE delivery: see _sample_step_time's rationale
        self._sample_step_time(snapshot, steps, t_dispatch, clean, pipelined)
        if self.config.fills_blocks:
            return self._process_block_chunk(host, snapshot, steps, disp)
        self._spmd_echo(wire.ECHO_DECODE, host)  # before host-side corruption
        if self._injector is not None:
            host, _ = self._injector.corrupt_tokens(host, snapshot)
        total = 0
        with jax.profiler.TraceAnnotation("engine.process.deliver"):
            for idx, request in snapshot:
                slot = self._slots[idx]
                if slot.request is not request:  # freed/reassigned meanwhile
                    continue
                slot.decode_iters += 1
                slot.ahead -= steps
                t_prev = slot.last_token_at
                delivered = 0
                for s in range(steps):
                    slot.position += 1
                    self._deliver_token(idx, int(host[s, idx]))
                    delivered += 1
                    if slot.request is not request:  # finished mid-chunk
                        break
                total += delivered
                self._record_intertoken(slot, request, t_prev, delivered)
        if disp is not None:
            # onto the span `_land_dispatch` emitted: it holds this dict
            disp.attrs["tokens_delivered"] = total

    def _record_intertoken(
        self, slot: _Slot, request: GenerationRequest, t_prev: float,
        delivered: int,
    ) -> None:
        """One inter-token sample per slot per processed chunk: the MEAN
        per-token gap across the chunk ((now - previous chunk's clock) /
        tokens delivered). Deliberately chunk-granular, not per-token —
        in-chunk host gaps are ~µs noise while the chunk boundary carries
        the real dispatch+fetch interval, and per-token monotonic+record
        was the single biggest hot-loop instrumentation cost (measured
        1.0µs/token ≈ 1.6% of a tiny-model CPU step — over the §12 ≤1%
        bound this code ships under)."""
        if not self._obs.on or not delivered:
            return
        now_t = time.monotonic()
        if t_prev:
            self._obs.record("engine_intertoken_s", (now_t - t_prev) / delivered)
        if slot.request is request:  # not freed mid-chunk
            slot.last_token_at = now_t

    def _note_tenant_admitted(self, request: GenerationRequest) -> None:
        """Tenant attribution + token-rate charge for one admission: the
        prompt's prefill tokens bill the tenant's quota bucket the moment
        the slot activates (generated tokens bill per delivery)."""
        self._tenants.note_admitted(
            getattr(request.options, "tenant", None) or DEFAULT_TENANT,
            len(request.prompt_tokens),
        )

    def _deliver_token(self, idx: int, token: int) -> None:
        slot = self._slots[idx]
        request = slot.request
        assert request is not None
        opts = request.options

        if token < 0:
            # sampling's NaN guard sentinel: this slot's logits went
            # non-finite. Quarantine ONLY this slot — fail its request,
            # zero its pages (next iteration, one coalesced
            # dispatch) — while every other slot keeps decoding untouched.
            # SPMD replicas quarantine victim-only too since round 13: the
            # page-free / page-zero dispatches ride the wire,
            # so a poisoned slot degrades one request, not the replica
            # (docs/SERVING.md §14).
            with self._stats_lock:
                self.nan_guard_total += 1
                self.quarantined_slots_total += 1
            # pages, not rows: evict prefix entries sharing the slot's
            # pages, free them through the owned list, zero next flush
            self._quarantine_pages(idx)
            # the postmortem artifact: the last N iterations that LED here
            # (batch mix, pages, programs, injector firings) — the evidence
            # a counter bump discards
            self._flight_dump("nan-quarantine", extra={"slot": idx})
            self._finish_slot(
                idx, "error",
                error=LogitsNaNError(
                    f"non-finite logits for slot {idx}; slot quarantined and "
                    "its KV rows reset"
                ),
            )
            return
        if request.cancelled:
            # chunk-boundary cancellation: the slot frees NOW; tokens from
            # the rest of this (and any in-flight) chunk are dropped by the
            # snapshot identity check
            with self._stats_lock:
                self.cancelled_total += 1
            self._finish_slot(idx, "cancelled")
            return
        deadline = request.deadline_at()
        if deadline is not None and time.monotonic() >= deadline:
            with self._stats_lock:
                self.deadline_decode_total += 1
            self._tenants.note_deadline(
                getattr(opts, "tenant", None) or DEFAULT_TENANT
            )
            self._finish_slot(idx, "deadline")
            return
        if self._injector is not None:
            self._injector.stall("client")  # slow-client backpressure drill

        finished_reason = None
        is_stop = (self.eos_token_id is not None and token == self.eos_token_id) or (
            token in opts.stop_tokens
        )
        if is_stop:
            finished_reason = "stop"
        else:
            slot.generated.append(token)
            if slot.block_rest:  # a block's token: its denoise step goes with it
                slot.fix_steps.append(slot.block_rest.pop(0)[1])
            index = self._spec_index.get(idx)
            if index is not None:
                # the emitted token joins the slot's draft context — the
                # next iteration's proposals continue from it
                index.append(token)
            dfa = self._slot_dfa.get(idx)
            if dfa is not None:
                # the HOST half of constrained decoding: mirror the device's
                # DFA advance per delivered token (same table → lockstep),
                # and finish with "stop" the moment the grammar COMPLETES —
                # tokens the device's sink self-loop generates after this
                # point are dropped by the snapshot identity check, so the
                # delivered text is exactly one grammar derivation
                s = dfa.advance(self._dfa_host_state.get(idx, 0), token)
                if s < 0:
                    # unreachable while host and device share the table;
                    # reaching it means state corruption — off-grammar
                    # output must fail loudly, never stream on
                    self._finish_slot(
                        idx, "error",
                        error=RuntimeError(
                            f"constrained decode diverged at slot {idx}: "
                            f"token {token} is illegal in DFA state "
                            f"{self._dfa_host_state.get(idx, 0)}"
                        ),
                    )
                    return
                self._dfa_host_state[idx] = s
                # mirror onto the request BEFORE on_token below fires: a
                # stream callback reading dfa_state inside on_token sees
                # the state matching this token — what the fleet wire's
                # tokens frames carry for mid-derivation resume (§18)
                request.dfa_state = s
                if dfa.is_complete(s):
                    finished_reason = "stop"
            with self._stats_lock:
                self.total_generated += 1
            self._tenants.note_generated(
                getattr(opts, "tenant", None) or DEFAULT_TENANT
            )
            if request.on_token is not None:
                try:
                    request.on_token(token)
                except Exception:  # noqa: BLE001 — stream consumer must not kill the loop
                    log.exception("on_token callback failed")
            # the request's max_cost_tokens budget (prompt + generated)
            # caps the generation length alongside max_new_tokens (§19)
            if finished_reason is None and len(slot.generated) >= (
                effective_max_new_tokens(opts, len(request.prompt_tokens))
            ):
                finished_reason = "length"
            elif finished_reason is None and slot.position >= self.max_seq_len - 1:
                # cache full — scattering past the buffer would silently drop
                finished_reason = "length"

        if finished_reason is not None:
            self._finish_slot(idx, finished_reason)

    def _finish_slot(
        self, idx: int, reason: str, error: Optional[BaseException] = None
    ) -> None:
        """Resolve the slot's request and free the slot (temp reset rides
        the next dispatch via _freed_slots, as for natural completions)."""
        slot = self._slots[idx]
        request = slot.request
        assert request is not None
        now = time.monotonic()
        pages_held = len(self._pagepool.slot_pages(idx))
        result = GenerationResult(
            tokens=list(slot.generated),
            finish_reason=reason,
            prompt_tokens=len(request.prompt_tokens),
            ttft_s=(
                slot.first_token_at - request.submitted_at
                if slot.first_token_at
                else 0.0
            ),
            total_s=now - request.submitted_at,
            error=error,
        )
        if self.config.fills_blocks:
            result.fix_steps = list(slot.fix_steps)
            result.block_rest = (
                [t for t, _ in slot.block_rest], [st for _, st in slot.block_rest]
            )
        stamps = {
            "submitted": request.submitted_at,
            "admitted": slot.started_at or None,
            # a block model's prefill yields no token: `engine.prefill` ends
            # where the prefill landed
            "first_token": (slot.prefill_landed_at or slot.first_token_at) or None,
            "finished": now,
        }
        attrs = {
            "slot": idx,
            "path": slot.path,
            "prompt_len": len(request.prompt_tokens),
            "generated_tokens": len(slot.generated),
            "finish_reason": reason,
            "prefill_chunks": slot.prefill_chunks,
            "group_seq": slot.group_seq,
            "decode_iterations": slot.decode_iters,
            "verify_dispatches": slot.verify_iters,
            "kv_pages": pages_held,
        }
        # release the slot and its pages BEFORE resolving the request: the
        # waiter wakes inside _finish, and anything it reads right away —
        # free-page counts, active-slot counts, stats() — must already
        # reflect the completion (sampled pool state mid-teardown is how
        # the page-leak test flaked when span emission sat in this gap)
        slot.request = None
        slot.generated = []
        slot.position = 0
        slot.last_token_at = 0.0
        slot.block, slot.block_steps, slot.fix_steps, slot.block_rest = [], [], [], []
        slot.prefill_landed_at = 0.0
        self._spec_index.pop(idx, None)
        self._slot_clear_agentic(idx)
        self._freed_slots.append(idx)
        # slot reset = free its table (shared pages survive through the
        # prefix index's refcounts; exclusive ones return to the pool)
        self._free_slot_pages(idx)
        request._finish(result)
        if self._obs.on:
            # the request's whole lifecycle becomes ONE span tree here —
            # a single emission per request, nothing on the token loop
            emit_request_spans(
                request.trace_id, stamps, attrs,
                status="ok" if error is None else f"error: {type(error).__name__}",
                stages=slot.stages,
            )

    def _fail_all(self, error: BaseException) -> None:
        self._dead = error

        def dead_result() -> GenerationResult:
            return GenerationResult(
                tokens=[], finish_reason="error", prompt_tokens=0,
                ttft_s=0, total_s=0, error=error,
            )

        # collect every in-flight request, TEAR DOWN FIRST, resolve last:
        # _finish wakes waiters immediately, and a waiter sampling engine
        # state (active slots, stats(), long-stream dicts) must never see
        # its own request still wired into a half-torn slot (the same
        # ordering rule _finish_slot and _recover follow)
        doomed: list[GenerationRequest] = []
        if self._held_back is not None:
            doomed.append(self._held_back)
            self._held_back = None
        for st in self._longs.values():
            doomed.append(st["request"])
        self._longs.clear()
        doomed.extend(self._long_queue)
        self._long_queue.clear()
        doomed.extend(self._page_deferred)
        self._page_deferred.clear()
        self._reserved.clear()
        self._spec_index.clear()
        for i, slot in enumerate(self._slots):
            if slot.request is not None:
                doomed.append(slot.request)
                slot.request = None
                slot.generated = []
                slot.position = 0
                self._slot_clear_agentic(i)
        while True:
            try:
                doomed.append(self._queue.get_nowait())
            except queue.Empty:
                break
        with self._waiting_lock:
            self._waiting.clear()
            self._open.clear()
        for request in doomed:
            request._finish(dead_result())

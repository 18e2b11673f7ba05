"""Token sampling: greedy / temperature / top-k / top-p, jittable and batched.

Per-slot sampling params are carried as arrays so one compiled sampler serves
a heterogeneous continuous batch (different temperatures per request).

Perf note (a figure from a deleted chip record, a claim to check): a
full-vocab sort at [64, 256000] cost ~25ms — more than the whole gemma-2b
transformer step — so the sort only runs when some slot actually has top-k/top-p enabled
(lax.cond, runtime-gated), and the top-k + top-p cutoffs share ONE sort.
All-greedy batches (the common chat default, temperature=0) reduce to a
single argmax with no gumbel draw.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _greedy_argmax(logits: jax.Array) -> jax.Array:
    """Two-stage argmax over the vocab: per-group MAX first, then the
    argmax within the single winning group. The wide [B, 256k] pass is now
    a pure max reduction — no index tracking at vocab width at all (the
    previous grouped form still ran a full-width argmax to precompute every
    group's within-offset, index math this version defers to ONE gathered
    [B, 128] group). PERF.md's untaken two-stage-argmax lever: ~0.4 ms/step
    on gemma's 256k vocab, now the default for every greedy slot.
    Tie semantics match jnp.argmax exactly (first index wins): the winning
    group is the FIRST group attaining the global max, and the within-group
    argmax picks the first position inside it — the same element a global
    first-index scan lands on.

    Ragged vocabs (GPT-2-family 50257 etc.) pad with -inf columns to the
    next multiple of 128 so the grouped path ALWAYS runs — the old silent
    fallback to the slow single-pass argmax cost exactly the models it was
    meant to serve. -inf pads sit past every real column, so first-index
    tie-breaking never selects one: a pad wins its group only when the
    group is all -inf, and an all--inf row resolves to index 0 the same
    way jnp.argmax does."""
    b, v = logits.shape
    group = 128
    if v % group:
        pad = group - v % group
        logits = jnp.pad(logits, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        v += pad
    grouped = logits.reshape(b, v // group, group)
    maxima = jnp.max(grouped, axis=-1)  # [B, v/group] — pure max, no indices
    top_group = jnp.argmax(maxima, axis=-1)  # [B] first group with the max
    winner = jnp.take_along_axis(
        grouped, top_group[:, None, None], axis=1
    )[:, 0]  # [B, group]
    return top_group * group + jnp.argmax(winner, axis=-1)


def _expand_allowed(allowed: jax.Array, vocab: int) -> jax.Array:
    """Grammar mask → [..., V] bool. Two spellings arrive here:

    - packed ``[..., ceil(V/32)]`` uint32 (serving/constrain.py's
      legality bitmask, LSB-first: token t → bit t % 32 of word t // 32)
      — expanded on device with one shift/AND, so the mask rides HBM at
      1 bit/token and only becomes bytes inside the fused step;
    - legacy ``[..., V]`` bool — passed through untouched.

    The dtype dispatch is a Python branch: dtypes are static under jit,
    so each spelling traces its own (already-distinct-signature) program.
    """
    if allowed.dtype != jnp.uint32:
        return allowed
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (allowed[..., None] >> shifts) & jnp.uint32(1)  # [..., W, 32]
    flat = bits.reshape(*allowed.shape[:-1], allowed.shape[-1] * 32)
    return flat[..., :vocab].astype(bool)


def _apply_filters(s: jax.Array, top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """top-k + top-p cutoffs over [R, V] scaled logits with per-row params
    (0 / 1.0 = disabled); one descending sort serves both. Shared by
    ``sample`` (R = batch) and ``speculative_verify`` (R = batch x draft
    positions) so the two samplers cannot drift apart."""
    v = s.shape[-1]
    sorted_desc = jnp.sort(s, axis=-1)[:, ::-1]
    # top-k: value at rank k-1 (k=0 → keep all → rank v-1)
    k_idx = jnp.clip(jnp.where(top_k > 0, top_k, v) - 1, 0, v - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    # top-p on the top-k-masked distribution, masked by rank (equivalent
    # to re-sorting the masked logits: masking keeps a sorted prefix)
    ranks = jnp.arange(v)[None, :]
    sorted_masked = jnp.where(ranks <= k_idx[:, None], sorted_desc, -jnp.inf)
    probs = jax.nn.softmax(sorted_masked, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p[:, None]  # cumulative prob EXCLUSIVE < p
    cutoff = jnp.where(keep, sorted_masked, jnp.inf).min(axis=-1, keepdims=True)
    return jnp.where(s < jnp.maximum(kth, cutoff), -jnp.inf, s)


@functools.partial(jax.jit, static_argnames=())
def sample(
    logits: jax.Array,  # [B, V] fp32
    key: jax.Array,
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32, 0 = disabled
    top_p: jax.Array,  # [B] fp32, 1.0 = disabled
    allowed: jax.Array = None,  # [B, W] uint32 packed / [B, V] bool mask
) -> jax.Array:
    """Returns sampled token ids [B]. temperature 0 → greedy for that slot.

    ``allowed`` (constrained decoding, serving/constrain.py): illegal
    tokens drop to -inf BEFORE the greedy argmax and the top-k/top-p
    filters, so a constrained slot's output is guaranteed inside its
    grammar on both the greedy and sampled paths. The mask lands AFTER the
    NaN guard's finite check — a grammar's own -inf columns must not read
    as a poisoned row (the guard exists for device faults, not masks), and
    the DFA's no-dead-end invariant guarantees at least one True per row
    so the masked softmax stays finite.

    NaN guard: a row whose logits contain any non-finite value (NaN/±inf
    overflow — a numerically-poisoned KV row or a device fault) returns the
    sentinel ``-1`` instead of a token. Sampling from such a row is
    undefined (categorical over NaN probabilities), and silently emitting
    garbage poisons the slot's cache for every later step; the engine
    quarantines the slot on sight of the sentinel (fails that request,
    zeroes its KV rows) while every other slot keeps decoding. +inf alone
    also trips it: softmax over +inf is NaN anyway. The check is one
    vocab-wide AND-reduction — VPU-cheap next to the transformer step,
    unlike the sort this module already gates behind any_filter."""
    b, v = logits.shape
    finite = jnp.all(jnp.isfinite(logits), axis=-1)  # [B]
    if allowed is not None:
        logits = jnp.where(_expand_allowed(allowed, v), logits, -jnp.inf)
    greedy = _greedy_argmax(logits)

    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp

    any_sample = jnp.any(temperature > 0.0)
    any_filter = jnp.any((temperature > 0.0) & ((top_k > 0) | (top_p < 1.0)))

    def sampled_branch(s: jax.Array) -> jax.Array:
        filtered = lax.cond(
            any_filter, lambda x: _apply_filters(x, top_k, top_p), lambda x: x, s
        )
        return jax.random.categorical(key, filtered, axis=-1)

    sampled = lax.cond(any_sample, sampled_branch, lambda _: greedy, scaled)
    out = jnp.where(temperature <= 0.0, greedy, sampled)
    return jnp.where(finite, out, -1)


@functools.partial(jax.jit, static_argnames=())
def speculative_verify(
    logits: jax.Array,  # [B, K+1, V] fp32 — per-position next-token logits
    drafts: jax.Array,  # [B, K] int32 — the n-gram drafts being verified
    key: jax.Array,
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32, 0 = disabled
    top_p: jax.Array,  # [B] fp32, 1.0 = disabled
    allowed: jax.Array = None,  # [B, K+1, W] uint32 / [B, K+1, V] bool mask
) -> tuple[jax.Array, jax.Array]:
    """Batched draft verification for self-speculative decoding.

    ``allowed`` (constrained decoding): position j's mask is derived from
    the DFA state AFTER consuming drafts 0..j-1 (the engine ships the
    per-position state ids; serving/constrain.py). Masking the verify
    logits with the SAME per-position masks non-speculative decode would
    apply keeps the exactness invariants under constraints: greedy rows
    accept the longest prefix matching the MASKED argmax chain (an illegal
    draft's -inf logit can never equal the argmax, so it is rejected
    exactly where plain masked decode would have emitted something else),
    and sampled rows rejection-sample against the masked softmax (an
    illegal draft has p(d)=0 → never accepted; corrections/bonus draws
    come from the masked residual) — the emitted marginal is exactly the
    masked p.

    Position j of ``logits`` is the model's next-token distribution after
    consuming verify input j (input 0 = the slot's current token, inputs
    1..K = the drafts), all scored in ONE forward. Returns
    ``(out [B, K+1] int32, accept [B] int32)``: ``accept`` drafts were
    accepted and the emitted tokens are ``out[:, :accept+1]`` — out[:, j]
    equals drafts[:, j] for j < accept, and out[:, accept] is the
    correction (greedy: the argmax the draft failed to match; sampled: a
    residual draw) or, at accept == K, the bonus token from the last
    position. Every verify thus emits between 1 and K+1 tokens per slot.

    Greedy rows (temperature <= 0) accept the longest draft prefix matching
    the argmax chain — token-exact with non-speculative greedy decode by
    construction, since each position's logits condition on exactly the
    accepted prefix. Sampled rows use standard rejection sampling against
    the point-mass draft distribution the n-gram index implies (q(d) = 1):
    accept d with prob min(1, p(d)/q(d)) = p(d); on the first rejection
    resample from the residual norm(max(p - q, 0)) — p with d removed,
    renormalized — so the emitted marginal is exactly p (the lossless
    speculative-sampling identity).

    NaN guard (same contract as ``sample``): a slot with ANY non-finite
    position among its K+1 rows emits the ``-1`` sentinel with accept 0;
    the engine quarantines it on sight.
    """
    b, k1, v = logits.shape
    k = k1 - 1
    finite = jnp.all(jnp.isfinite(logits.reshape(b, -1)), axis=-1)  # [B]
    if allowed is not None:
        logits = jnp.where(_expand_allowed(allowed, v), logits, -jnp.inf)
    greedy = _greedy_argmax(logits.reshape(b * k1, v)).reshape(b, k1)
    greedy_acc = drafts == greedy[:, :k]  # [B, K]

    any_sample = jnp.any(temperature > 0.0)
    any_filter = jnp.any((temperature > 0.0) & ((top_k > 0) | (top_p < 1.0)))

    def sampled_branch(_) -> tuple[jax.Array, jax.Array]:
        temp = jnp.maximum(temperature, 1e-6)[:, None, None]
        flat = (logits / temp).reshape(b * k1, v)
        # per-slot filters repeat across the K+1 positions (one request =
        # one sampling config); the sort is gated exactly like sample()'s
        flat = lax.cond(
            any_filter,
            lambda s: _apply_filters(
                s, jnp.repeat(top_k, k1), jnp.repeat(top_p, k1)
            ),
            lambda s: s,
            flat,
        )
        filtered = flat.reshape(b, k1, v)
        probs = jax.nn.softmax(filtered, axis=-1)
        key_u, key_r = jax.random.split(key)
        u = jax.random.uniform(key_u, (b, k))
        p_draft = jnp.take_along_axis(
            probs[:, :k], drafts[..., None], axis=-1
        )[..., 0]
        acc = u < p_draft  # [B, K]
        # corrections: residual (draft token removed) at positions 0..K-1;
        # position K is the bonus draw — its mask index is out of bounds,
        # so the drop-mode scatter leaves it unfiltered. A correction row
        # is only CONSUMED when its draft was rejected (prob 1 - p(d)), so
        # the all--inf row a p(d)=1 draft would leave can never be read.
        mask_cols = jnp.concatenate(
            [drafts, jnp.full((b, 1), v, jnp.int32)], axis=1
        )
        masked = filtered.at[
            jnp.arange(b)[:, None], jnp.arange(k1)[None, :], mask_cols
        ].set(-jnp.inf, mode="drop")
        corr = jax.random.categorical(key_r, masked, axis=-1)  # [B, K+1]
        return acc, corr

    s_acc, s_corr = lax.cond(
        any_sample, sampled_branch, lambda _: (greedy_acc, greedy), 0
    )
    is_greedy = (temperature <= 0.0)[:, None]
    acc = jnp.where(is_greedy, greedy_acc, s_acc)
    corr = jnp.where(is_greedy, greedy, s_corr)
    # accepted length = longest all-accepted prefix
    accept = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=-1), axis=-1)
    drafts_padded = jnp.concatenate(
        [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1
    )
    positions = jnp.arange(k1)[None, :]
    out = jnp.where(positions < accept[:, None], drafts_padded, corr)
    accept = jnp.where(finite, accept, 0)
    out = jnp.where(finite[:, None], out, -1)
    return out.astype(jnp.int32), accept.astype(jnp.int32)


def block_choice(
    logits: jax.Array,  # [B, S, V] fp32: the logits AT each position of a block
    key: jax.Array,
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32, 0 = disabled
    top_p: jax.Array,  # [B] fp32, 1.0 = disabled
    is_open: jax.Array,  # [B, S] bool: the positions still to be fixed
    step: jax.Array,  # [B] int32: the block's denoise step
    mask_id: int,
    threshold: float,
    schedule: tuple,  # how many positions each denoise step fixes at least
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """What one denoise pass of a model that fills blocks fixes, on the
    device: at every position the token (the argmax, or a draw at the row's
    temperature / top-k / top-p, as `sample` takes them) and its CONFIDENCE,
    the probability of that token under the softmax it was taken from; then,
    among the row's open positions, every one whose confidence exceeds
    ``threshold`` and at least ``schedule[step]`` of the most confident (all
    that are open, where fewer are). The mask id is never an answer: its
    logit is -inf before the softmax. Returns (tokens [B, S], fixed [B, S]
    bool, how many open positions stood over the threshold [B]). A position
    whose logits are not finite gets `sample`'s sentinel -1 and confidence 0:
    it is fixed in its turn and the engine quarantines the row on sight."""
    b, s, v = logits.shape
    flat = logits.reshape(b * s, v)
    finite = jnp.all(jnp.isfinite(flat), axis=-1)
    # a select, which fuses into its readers (a column set is a copy of the whole)
    flat = jnp.where(jnp.arange(v)[None, :] == mask_id, -jnp.inf, flat)
    # a row's value at each of its S positions
    temp, k_rows, p_rows = (jnp.repeat(a, s) for a in (temperature, top_k, top_p))

    def confidence(scores, tokens):
        picked = jnp.take_along_axis(scores, tokens[:, None], axis=-1)[:, 0]
        return jnp.exp(picked - jax.nn.logsumexp(scores, axis=-1))

    greedy = _greedy_argmax(flat)
    any_sample = jnp.any(temperature > 0.0)
    any_filter = jnp.any((temperature > 0.0) & ((top_k > 0) | (top_p < 1.0)))

    def drawn(scores):
        scaled = scores / jnp.maximum(temp, 1e-6)[:, None]
        filtered = lax.cond(
            any_filter, lambda x: _apply_filters(x, k_rows, p_rows), lambda x: x, scaled
        )
        tokens = jax.random.categorical(key, filtered, axis=-1)
        tokens = jnp.where(temp <= 0.0, greedy, tokens)
        # a greedy row's confidence is under the plain softmax, not the scaled
        return tokens, jnp.where(
            temp <= 0.0, confidence(scores, greedy), confidence(filtered, tokens)
        )

    tokens, conf = lax.cond(
        any_sample, drawn, lambda scores: (greedy, confidence(scores, greedy)), flat
    )
    tokens = jnp.where(finite, tokens, -1).reshape(b, s).astype(jnp.int32)
    conf = jnp.where(finite, conf, 0.0).reshape(b, s)

    # the open positions ranked by confidence (the first of equals first)
    order = jnp.argsort(jnp.where(is_open, -conf, jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    over = is_open & (conf > threshold)
    at_least = jnp.asarray(schedule, jnp.int32)[jnp.clip(step, 0, len(schedule) - 1)]
    fixed = is_open & (over | (rank < at_least[:, None]))
    return tokens, fixed, over.sum(axis=-1, dtype=jnp.int32)

"""Flash-attention Pallas kernels.

Two attention hot paths, both GQA-aware (queries grouped per kv head so K/V
blocks are read once per group, not once per query head). K/V come in HEAD-MAJOR layout
[B, Hkv, T, D] — the kv-head axis stays out of the trailing two dims, so the
Mosaic TPU lowering's (8, 128) block-tiling constraint falls on (T, D) where
blocks are naturally aligned, and a per-head kv block is a contiguous
(block_k, D) slice (no relayout per grid step).

- ``flash_prefill_attention``: causal blocked attention with fp32
  online-softmax scratch accumulators — O(block_q x block_k) VMEM instead of
  the O(S^2) masked score tensor the jnp path materializes.
- ``ragged_paged_decode_attention`` (and its int8 twin): one query per
  sequence against the page pool, walking only each row's live pages (the
  continuous batcher packs rows of very different lengths into one step, so
  a masked read over a fixed width wastes bandwidth proportional to
  max_len - mean_len).
  ``ragged_paged_selected_attention`` is that walk under a row's selection
  (a model with an indexer), and ``ragged_paged_latent_attention`` the same
  over a pool that holds ONE row a token for every head, a latent in place of
  K and V: absorbed queries of 64 heads against the row as it lies, a page
  fetched once and read as key and, its first lanes, as value.
- ``flash_segment_attention``: a prefill segment's queries over the row's
  gathered columns, causal (windowed), a query block visiting only the key
  blocks it can see; ``sparse_segment_attention`` is that walk under a
  packed [S, T] selection (a model with an indexer), and ``segment_select``
  makes that selection in one call: the indexer's scores of a segment in
  tiles, ranked where they lie in VMEM, so nothing of [S, heads, T] is ever
  held and no [S, T] of scores reaches HBM (``index_scores``: the scores
  alone, what it is held to). ``latent_expand_blocks`` makes the K and V a
  latent model's segment walks, from its row's latents: the key blocks the
  segment's queries can see, every head's, head-major.
- ``paged_kv_write``: that step's new K and V rows into the bf16 pool where
  it lies, a copy per live row (a scatter pays per (row, kv head), dropped
  rows included).
- ``paged_insert_pages``: an admission group's prefilled K and V into the
  pool where it lies, one copy HBM → HBM a (layer, row, mapped page) and
  leaf (a scatter has the whole pool relaid for its window, there and back).

No reference counterpart (the reference's compute is remote HTTP calls);
kernel structure follows the public flash/paged-attention pattern from the
Pallas TPU guide.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from langstream_tpu.compile_account import note_kernel
from langstream_tpu.models.configs import ModelConfig

_NEG = -1e30

# Scoped VMEM the prefill kernel is sized against AND the limit
# stated to Mosaic (CompilerParams.vmem_limit_bytes): one number on both
# sides, so the block-size choice below cannot drift from what the compiler
# enforces (its unstated default, 16MiB, refused gemma-2b's 256-row q blocks
# at 16.99MiB). A v5e core has 128MiB of VMEM.
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _score_scale(config: ModelConfig, d: int) -> float:
    """What the expanded form's kernels multiply q.k by: 1 / sqrt(the key's
    width), or a latent model's own scale (YaRN's softmax factor with it)."""
    return config.attn_scale if config.has_latent else 1.0 / (d**0.5)


# ---------------------------------------------------------------------------
# Heads of 64: two KV heads to a lane row (``ModelConfig.kv_head_pack``). The
# cache and the pool keep heads 2j and 2j + 1 side by side, a leaf
# [.., Hkv / 2, T, 128], and every kernel here takes that leaf as Hkv / 2
# heads of 128 with twice the query group: a query is laid in ITS half of the
# 128 lanes and zeros in the other (`pair_queries`), so q . k over 128 lanes
# is its own head's 64 products, and of the 128 lanes p . v gives it keeps its
# half (`own_half`). The kernels' bodies are the 128-wide ones to the line;
# the MXU multiplies zeros for half its lanes, which a read bound by the
# pool's bytes does not see, and a page holds what its tokens take and no
# padding. What a call packs is read from its own sizes: the leaf's width
# over the query's.
# ---------------------------------------------------------------------------


def _halves(h: int, hkv_packed: int, pack: int) -> jax.Array:
    """[H, pack] one-hot: which part of the packed row query head h reads
    (its KV head j = h // G lies in part j % pack of row j // pack)."""
    group = h // (hkv_packed * pack)
    return jax.nn.one_hot((jnp.arange(h) // group) % pack, pack, dtype=jnp.float32)


def pair_queries(q: jax.Array, hkv_packed: int, pack: int) -> jax.Array:
    """q [.., H, D] -> [.., H, pack x D], each head in its own part of the
    packed row, zeros in the others. The heads keep their order: seen as
    [Hkv / pack, pack x G] they are each packed row's own."""
    *lead, h, d = q.shape
    part = _halves(h, hkv_packed, pack).astype(q.dtype)
    return (q[..., None, :] * part[:, :, None]).reshape(*lead, h, pack * d)


def own_half(out: jax.Array, h: int, hkv_packed: int, pack: int) -> jax.Array:
    """The inverse on a kernel's output [.., H x pack x D]: each head's own
    part, [.., H x D]."""
    lead = out.shape[:-1]
    out = out.reshape(*lead, h, pack, -1)
    part = _halves(h, hkv_packed, pack).astype(out.dtype)
    return (out * part[:, :, None]).sum(axis=-2).reshape(*lead, -1)


def _fit_block(block: int, n: int) -> int:
    """Largest block ≤ ``block`` that divides ``n``. pallas_ok blesses any
    128-multiple length, so a 512 default block must step down (512 → 256 →
    128) for lengths like 640/768 rather than tripping the divisibility
    assert."""
    block = min(block, n)
    while block > 1 and n % block != 0:
        block //= 2
    return block


def _vmem_block_q(
    block_q: int, block_k: int, group: int, d: int, itemsize: int,
) -> int:
    """Shrink block_q until one grid step of the prefill kernel fits
    ``_VMEM_LIMIT_BYTES``. Counted per step: the double-buffered q/out
    blocks [G, block_q, D] and K/V blocks [block_k, D], the f32 m/l/acc scratch
    [G, block_q, 128|128|D], and the [G, block_q, block_k] score and
    probability tiles (f32 each, plus the probabilities' model-dtype copy
    that feeds the PV dot). Shape-aware rather than a smaller global
    default: fat-head models (gemma G=8 D=256) step down to 256 rows while
    llama (G=4 D=128) keeps the full 512."""
    kv = 2 * 2 * block_k * d * itemsize  # k + v, ×2 buffers
    while block_q > 128:
        io = 2 * 2 * group * block_q * d * itemsize  # q + out, ×2 buffers
        scratch = group * block_q * (128 + 128 + d) * 4
        tiles = group * block_q * block_k * (4 + 4 + itemsize)
        if io + kv + scratch + tiles <= _VMEM_LIMIT_BYTES:
            break
        block_q //= 2
    return block_q


def _model_on(ndim: int, axis: int) -> P:
    return P(*("model" if i == axis else None for i in range(ndim)))


def _per_kv_head(n_replicated: int, kv_head_axis: int = 1, returns_pool: bool = False):
    """Decorator for kernels of signature ``fn(q, k, v, *replicated, config,
    ...)``. Mosaic kernels cannot be partitioned by GSPMD, so when
    ``config.kernel_mesh`` is set (the engine runs under a mesh) the call
    is wrapped in a fully manual shard_map that splits the head axis of q
    and of the K/V cache or page pool over "model" — what param_specs and
    serving_cache_specs/page_pool_specs already produce. ``kv_head_axis``
    is where the kv heads lie in every K/V leaf: 1 in a dense cache
    [B, Hkv, T(, D)], 2 in the page pool [L, P, Hkv, ps(, D)]. Every kernel here
    is independent per kv head (each q-head group reads only its own kv
    head), so the body needs no collective; the ``replicated`` operands
    (offsets, lengths, page tables) and every other mesh axis stay
    replicated. ``pallas_ok``/``paged_pallas_ok`` only admit meshes whose
    "model" axis divides the kv heads. ``q`` may be a tuple of arrays with
    the heads on their last axis but one (the new K and V rows of
    ``paged_kv_write``); ``returns_pool``: the result is the (k, v) pool,
    split as it came in, not an attention output."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(q, k, v, *args, **kwargs):
            config = args[n_replicated]
            mesh = config.kernel_mesh
            if mesh is None:
                return fn(q, k, v, *args, **kwargs)
            replicated, tail = args[:n_replicated], args[n_replicated + 1:]
            local = dataclasses.replace(config, kernel_mesh=None)

            def body(q, k, v, *replicated):
                return fn(q, k, v, *replicated, local, *tail, **kwargs)

            kv_spec = jax.tree.map(lambda x: _model_on(x.ndim, kv_head_axis), k)
            q_spec = jax.tree.map(lambda x: _model_on(x.ndim, x.ndim - 2), q)
            return jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(q_spec, kv_spec, kv_spec) + (P(),) * n_replicated,
                out_specs=(kv_spec, kv_spec) if returns_pool
                else _model_on(q.ndim - 1, q.ndim - 2),
                check_vma=False,
            )(q, k, v, *replicated)

        return wrapper

    return deco


def _mesh_ok(config: ModelConfig) -> bool:
    """Kernels run under a mesh only when its "model" axis divides the kv
    heads (the per-kv-head split of ``_per_kv_head``); otherwise the cache
    is replicated (serving_cache_specs) and attention stays on the jnp
    path, which GSPMD partitions by itself."""
    mesh = config.kernel_mesh
    rows = config.n_kv_heads // config.kv_head_pack  # of the cache's head axis
    return mesh is None or rows % mesh.shape.get("model", 1) == 0


# Which implementation each attention call shape was traced with, keyed by
# a readable name — what tells a kernel from a reference that quietly took
# its place. Process-wide like the jit cache it describes: a shape traces
# once per process, whichever engine dispatched it first.
_PATHS: dict[str, str] = {}


def note_path(kind: str, impl: str, config: ModelConfig, s: int, t: int) -> None:
    """Record (at trace time) that a ``kind`` call of ``s`` queries per row
    against ``t`` cache columns took ``impl`` (a kernel's name, or "jnp")."""
    mesh = config.kernel_mesh
    if mesh is not None and impl != "jnp":
        impl += f"/shard_map[model={mesh.shape.get('model', 1)}]"
    _PATHS[f"{kind}[s={s},t={t}]"] = impl


def note_grid(key: str, grid: str) -> None:
    """Record (at trace time) the grid a kernel outside this file gave a
    shape: ``moe-grouped[tile=32,k=2048,n=768]`` -> ``blocks 2048x768,
    steps/tile 1, gate+up shared`` (ops/grouped_matmul.grid_note)."""
    _PATHS[key] = grid


def attention_paths() -> dict[str, str]:
    """Snapshot of the trace-time log: ``{"prefill[s=512,t=512]":
    "flash_prefill_attention", "paged-segment[s=64,t=2048]": "jnp", ...}``."""
    return dict(_PATHS)


# ---------------------------------------------------------------------------
# Prefill: causal blocked flash attention
# ---------------------------------------------------------------------------


def _prefill_kernel(
    q_ref,  # [1, 1, G, block_q, D]
    k_ref,  # [1, 1, block_k, D]
    v_ref,  # [1, 1, block_k, D]
    o_ref,  # [1, 1, G, block_q, D]
    m_scr,  # [G, block_q, 128] f32
    l_scr,  # [G, block_q, 128] f32
    acc_scr,  # [G, block_q, D] f32
    *,
    block_q: int,
    block_k: int,
    scale: float,
    softcap,
    block_length: int = 0,
):
    i = pl.program_id(2)  # query block
    j = pl.program_id(3)  # key block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    k_start = j * block_k

    # causal: skip key blocks strictly above the diagonal
    @pl.when(k_start <= q_start + block_q - 1)
    def _body():
        # dots stay in the MODEL dtype (bf16 in production) with fp32
        # accumulation — casting operands to f32 forced multi-pass f32 MXU
        # matmuls and capped the kernel at ~14 TFLOPS effective (measured
        # r5; the entire 19s 32k-prefill TTFT was this)
        q = q_ref[0, 0, :, :, :]  # [G, block_q, D]
        k = k_ref[0, 0, :, :]  # [block_k, D]
        v = v_ref[0, 0, :, :]
        s = (
            jax.lax.dot_general(
                q,
                k,
                dimension_numbers=(((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [G, block_q, block_k] f32
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_q, block_k), 1)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_q, block_k), 2)
        if block_length:
            # causal across blocks of ``block_length``, two-way inside one.
            # Both tiles are whole blocks, so the key tiles a query tile
            # visits are the causal ones (the `pl.when` above)
            s = jnp.where(k_pos < (q_pos // block_length + 1) * block_length, s, _NEG)
        else:
            s = jnp.where(k_pos <= q_pos, s, _NEG)

        m_prev = m_scr[:, :, 0]  # [G, block_q]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, :, None])
        p = jnp.where(s <= _NEG, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, :, 0] = l_scr[:, :, 0] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G, block_q, D]
        acc_scr[...] = acc_scr[...] * corr[:, :, None] + pv
        m_scr[:, :, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :, 0], 1e-30)[:, :, None]  # [G, block_q, 1]
        o_ref[0, 0, :, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@_per_kv_head(0)
def flash_prefill_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, Hkv, S, D] head-major
    v: jax.Array,  # [B, Hkv, S, D]
    config: ModelConfig,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Causal GQA attention → [B, S, H*D]; for a model that fills blocks
    (``config.block_length``) causal across blocks and two-way inside one."""
    b, s, h, d = q.shape
    scale = _score_scale(config, d)
    hkv, dv = k.shape[1], v.shape[-1]  # a latent model's value has a width of its own
    pack = k.shape[-1] // d  # heads of 64 lie two to a lane row (`pair_queries`)
    if pack > 1:
        q = pair_queries(q, hkv, pack)
        d = pack * d
    group = h // hkv
    block_k = _fit_block(block_k, s)
    block_q = _fit_block(
        _vmem_block_q(block_q, block_k, group, d, jnp.dtype(q.dtype).itemsize), s
    )
    assert s % block_q == 0 and s % block_k == 0, "caller gates divisibility"
    extra = {}
    if config.block_length:  # the causal program is the one it was
        assert block_q % config.block_length == 0 and block_k % config.block_length == 0
        extra["block_length"] = config.block_length
    # head-major queries: [B, Hkv, G, S, D] so the blocked dims are (S, D)
    qg = q.reshape(b, s, hkv, group, d).transpose(0, 2, 3, 1, 4)

    kernel = functools.partial(
        _prefill_kernel,
        block_q=block_q,
        block_k=block_k,
        scale=scale,
        softcap=config.attn_logit_softcap,
        **extra,
    )
    note_kernel("flash_prefill_attention")
    out = pl.pallas_call(
        kernel,
        name="flash_prefill_attention",
        grid=(b, hkv, s // block_q, s // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, 1, group, block_q, d), lambda b, h, i, j: (b, h, 0, i, 0)
            ),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, block_q, dv), lambda b, h, i, j: (b, h, 0, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, block_q, 128), jnp.float32),
            pltpu.VMEM((group, block_q, 128), jnp.float32),
            pltpu.VMEM((group, block_q, dv), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(qg, k, v)
    # [B, Hkv, G, S, Dv] → [B, S, H*Dv]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h * dv)
    return own_half(out, h, hkv, pack) if pack > 1 else out


# ---------------------------------------------------------------------------
# Segment: S queries a row at global positions offset .. offset + S - 1 against
# T cache columns of that row, causal, optionally inside a sliding window. The
# prefill kernel's body with a query offset and a lower bound: the scores are
# never held (128 heads x 2048 queries x 12.5k keys are 13.4 GB in float32),
# and a query block visits only the key blocks its rows can see: the index map
# clamps the key block into that range (a block index that does not change is
# not fetched again) and the body runs inside it. Over a window the grid's key
# axis is as long as the widest range any query block has, not T.
# ---------------------------------------------------------------------------


def _segment_kernel(
    offsets_ref,  # scalar-prefetch [B]
    q_ref, k_ref, v_ref, *refs,  # [chosen_ref,] o_ref, m_scr, l_scr, acc_scr
    block_q: int, block_k: int, window: int, n_t: int, scale: float, softcap,
    selected: bool = False,
):
    # under a selection one more block rides beside K and V: the (query, key)
    # pairs that were chosen, int8 [1, block_q, block_k]
    chosen_ref = refs[0] if selected else None
    o_ref, m_scr, l_scr, acc_scr = refs[1:] if selected else refs
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = offsets_ref[b] + i * block_q
    first, last = _segment_blocks(q_start, block_q, block_k, window, n_t)
    at = first + j  # the key block this step is at

    @pl.when(at <= last)
    def _body():
        q = q_ref[0, 0, :, :, :]  # [G, block_q, D]
        k = k_ref[0, 0, :, :]  # [block_k, D]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [G, block_q, block_k] f32
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_q, block_k), 1)
        k_pos = at * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_q, block_k), 2)
        seen = k_pos <= q_pos
        if window:
            seen = seen & (k_pos > q_pos - window)
        if selected:
            seen = seen & (chosen_ref[...].astype(jnp.int32) != 0)
        s = jnp.where(seen, s, _NEG)
        # the running maximum and sum stay COLUMNS [G, block_q, 1], a row of
        # the tile a sublane, as the reductions leave them and the tile and
        # the accumulator take them: read as [G, block_q] (`m_scr[:, :, 0]`)
        # Mosaic lays block_q along the lanes, and turning 512 values from
        # sublanes to lanes and back cost 7,300 of a key block's 11,800
        # operations on a v5e (PERF.md section 6, PR 56). The same float32
        # values in the same order: the output is PR 55's to the bit
        m_prev = m_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= _NEG, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, :, :1] = l_scr[:, :, :1] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[:, :, :1] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :, :1], 1e-30)  # [G, block_q, 1]
        o_ref[0, 0, :, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def _segment_blocks(q_start, block_q: int, block_k: int, window: int, n_t: int, xp=jnp):
    """First and last key block, of ``n_t``, that the query block at
    ``q_start`` sees (``xp``: numpy where the host counts them)."""
    last = xp.minimum((q_start + block_q - 1) // block_k, n_t - 1)
    if not window:
        return xp.int32(0), last
    return xp.maximum(q_start - window + 1, 0) // block_k, last


def segment_key_blocks(s: int, t: int, d: int, group: int, window: int,
                       itemsize: int = 2) -> tuple[int, int, int]:
    """(block_q, block_k, key blocks a query block's grid axis has) of a
    segment call: what the kernel runs at, for its callers' arithmetic
    (benchmark/reduce) as for the call itself."""
    block_k = _fit_block(512, t)
    block_q = _fit_block(_vmem_block_q(512, block_k, group, d, itemsize), s)
    n_k = t // block_k
    if window:
        n_k = min(n_k, (window + block_q - 2) // block_k + 2)
    return block_q, block_k, n_k


def segment_blocks_visited(offset: int, s: int, t: int, d: int, group: int, window: int,
                           itemsize: int = 2) -> int:
    """Key blocks ONE KV head's walk of a segment call runs its body for: the
    kernel's own rule (`_segment_blocks`) on the host, summed over the query
    blocks of ``s`` queries at ``offset .. offset + s - 1`` over ``t``
    columns. What a call's time is divided by for its time a key block."""
    block_q, block_k, _ = segment_key_blocks(s, t, d, group, window, itemsize)
    q_start = offset + block_q * np.arange(s // block_q)
    first, last = _segment_blocks(q_start, block_q, block_k, window, t // block_k, xp=np)
    return int(np.maximum(last - first + 1, 0).sum())


@_per_kv_head(1)
def flash_segment_attention(
    q: jax.Array,  # [B, S, H, D] at positions offsets[b] + (0 .. S-1)
    k: jax.Array,  # [B, Hkv, T, D] the row's cache columns 0 .. T-1
    v: jax.Array,
    offsets: jax.Array,  # [B]
    config: ModelConfig,
    window: int = 0,  # > 0: query i sees keys i - window + 1 .. i
    interpret: bool = False,
    chosen: jax.Array | None = None,  # `sparse_segment_attention`'s
) -> jax.Array:
    """Causal (windowed) GQA attention of a segment over its row's cache →
    [B, S, H*D]. Every column a query can see has to hold its key: the
    caller writes the segment's own K/V first. A column past T is never
    seen (a query past T sees its window's part below T)."""
    b, s, h, d = q.shape
    hkv, t, dv = k.shape[1], k.shape[2], v.shape[-1]  # (a latent model's value: its own width)
    group = h // hkv
    block_q, block_k, n_k = segment_key_blocks(
        s, t, d, group, window, jnp.dtype(q.dtype).itemsize
    )
    assert s % block_q == 0 and t % block_k == 0, "caller gates divisibility"
    qg = q.reshape(b, s, hkv, group, d).transpose(0, 2, 3, 1, 4)
    n_t = t // block_k

    def kv_index(b, h, i, j, offsets):
        first, last = _segment_blocks(
            offsets[b] + i * block_q, block_q, block_k, window, n_t
        )
        return (b, h, jnp.clip(first + j, 0, last), 0)

    def q_index(b, h, i, j, offsets):
        return (b, h, 0, i, 0)

    selection, extra = [], {}
    if chosen is not None:  # the window model's program is the one it was
        def chosen_index(b, h, i, j, offsets):
            return (b, i, kv_index(b, h, i, j, offsets)[2])

        selection = [pl.BlockSpec((1, block_q, block_k), chosen_index)]
        extra["selected"] = True

    name = "flash_segment_attention" if chosen is None else "sparse_segment_attention"
    note_kernel(name)
    out = pl.pallas_call(
        functools.partial(
            _segment_kernel, block_q=block_q, block_k=block_k, window=window,
            n_t=n_t, scale=_score_scale(config, d), softcap=config.attn_logit_softcap,
            **extra,
        ),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, s // block_q, n_k),
            in_specs=[
                pl.BlockSpec((1, 1, group, block_q, d), q_index),
                pl.BlockSpec((1, 1, block_k, d), kv_index),
                pl.BlockSpec((1, 1, block_k, dv), kv_index),
                *selection,
            ],
            out_specs=pl.BlockSpec((1, 1, group, block_q, dv), q_index),
            scratch_shapes=[
                pltpu.VMEM((group, block_q, 128), jnp.float32),
                pltpu.VMEM((group, block_q, 128), jnp.float32),
                pltpu.VMEM((group, block_q, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, s, dv), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(offsets.astype(jnp.int32), qg, k, v, *([] if chosen is None else [chosen]))
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h * dv)


def sparse_segment_attention(
    q: jax.Array,  # [B, S, H, D] at positions offsets[b] + (0 .. S-1)
    k: jax.Array,  # [B, Hkv, T, D]
    v: jax.Array,
    offsets: jax.Array,  # [B]
    chosen: jax.Array,  # [B, S, T] int8: nonzero where the query attends to the key
    config: ModelConfig,
    interpret: bool = False,
) -> jax.Array:
    """`flash_segment_attention` under a learned selection → [B, S, H*D]: a
    query attends to the keys ``chosen`` names among those behind it, one
    selection for all heads. The same walk over the key blocks up to the
    diagonal with one more int8 block a step; the work skipped is the
    softmax's, not the walk's (a query's 2,048 chosen keys lie in every
    block). A query that chose nothing comes back zeros. Under its own name
    on the `pallas_call`."""
    return flash_segment_attention(
        q, k, v, offsets, config, interpret=interpret, chosen=chosen.astype(jnp.int8)
    )


def _index_score_tile(q_ref, w_ref, k):
    """[block_q, block_k] float32: the indexer's scores of one tile, the Hi
    products summed where they are made."""
    w = w_ref[0]
    acc = jnp.zeros((q_ref.shape[2], k.shape[0]), jnp.float32)
    for head in range(q_ref.shape[1]):
        dots = jax.lax.dot_general(
            q_ref[0, head], k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        acc = acc + jnp.maximum(dots, 0.0) * w[:, head:head + 1]
    return acc + 0.0  # -0.0 reads +0.0


def _index_score_kernel(
    offsets_ref,  # scalar-prefetch [B]
    q_ref,  # [1, Hi, block_q, Di]
    w_ref,  # [1, block_q, Hi] float32
    k_ref,  # [1, block_k, Di]
    o_ref,  # [1, block_q, block_k] float32
    *, block_q: int, block_k: int,
):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last_query = offsets_ref[b] + (i + 1) * block_q - 1

    @pl.when(j * block_k > last_query)
    def _unseen():  # no query of the block sees a key of this one
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j * block_k <= last_query)
    def _body():
        o_ref[0] = _index_score_tile(q_ref, w_ref, k_ref[0])


def index_scores(
    q_idx: jax.Array,  # [B, S, Hi, Di] the indexer's queries at offsets[b] + (0 .. S-1)
    w: jax.Array,  # [B, S, Hi] float32, the heads' weights
    k_idx: jax.Array,  # [B, T, Di] the row's indexer keys, columns 0 .. T-1
    offsets: jax.Array,  # [B]
    interpret: bool = False,
) -> jax.Array:
    """The indexer's scores of a segment → [B, S, T] float32,
    ``sum_h w[s, h] relu(q_idx[s, h] . k_idx[t])``, in tiles of (512, 512)
    (the published ``q_chunk_size`` and ``kv_chunk_size``, fitted to S and
    T): a tile's Hi products are summed where they are made, so nothing of
    [S, Hi, T] is held. A tile no query of which sees a key of it (wholly
    past the diagonal) is written zeros and not computed; the caller masks
    what a query may not see."""
    b, s, hi, di = q_idx.shape
    t = k_idx.shape[1]
    block_q, block_k = _fit_block(512, s), _fit_block(512, t)
    note_kernel("index_scores")
    return pl.pallas_call(
        functools.partial(_index_score_kernel, block_q=block_q, block_k=block_k),
        name="index_scores",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s // block_q, t // block_k),
            in_specs=[
                pl.BlockSpec((1, hi, block_q, di), lambda b, i, j, off: (b, 0, i, 0)),
                pl.BlockSpec((1, block_q, hi), lambda b, i, j, off: (b, i, 0)),
                pl.BlockSpec((1, block_k, di), lambda b, i, j, off: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, block_k), lambda b, i, j, off: (b, i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, t), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(offsets.astype(jnp.int32), q_idx.transpose(0, 2, 1, 3), w.astype(jnp.float32), k_idx)


# ---------------------------------------------------------------------------
# A segment's SELECTION in one call a layer (`segment_select`): what
# `index_scores` and models/transformer `_select_mask` compute between them,
# with a query tile's scores never leaving VMEM. XLA ranks by 32 counts of a
# [S, T] key in HBM, the columns no query of the segment can see included (its
# shapes are static): 36 passes over 142 MB a layer at 2,048 x 17,408, 6.2 ms.
# Here a tile of ``block_q`` queries walks the key blocks up to its diagonal
# alone, keeps each tile of scores as its sortable key in a [block_q, T]
# scratch, and bisects the k-th largest over the key's 32 bits with the tile
# resident: the counts are VPU work over VMEM and follow what the tile sees.
# The set is `_select_mask`'s to the bit: the same products in the same order
# (`_index_score_tile`), the same fold of a float's bits, the same threshold,
# and its tie rule (a tie to the lower column), run only in a tile where some
# row holds more columns AT its threshold than it may keep, as a second
# bisection by the same counting, over the column below which a row keeps its
# ties. Visibility is the segment's: column <= position (`_paged_mask`,
# `forward`'s and `prefill`'s causal masks), from ``offsets``.
# On a v5e at 2,048 queries x 16 heads of 64 over 17,408 columns
# (`dev/bench_segment_select.py`; PERF.md section 6, PR 45): 0.62 ms a layer
# at offset 2,048, 1.29 at 8,192, 2.06 at 15,360, against 8.1-8.5 for
# `index_scores` + `_select_mask`; ranking `index_scores`'s output read back
# from HBM instead of scoring in the call costs 0.2 ms more at every offset.
# `attention_paths()` says where it was traced, "paged-segment-select[s=..,
# t=..]" -> "segment_select" (models/transformer `_selected_attention`), and
# the grid it got, "segment-select[s=2048,t=17408]" -> "block_q 128, block_k
# 512, to the diagonal". `index_scores` above stays as what this call is held
# to (tests/test_sparse_attention.py, the bench); no program calls it. A
# DECODE step still ranks by `_select_mask` in XLA, on purpose: its
# [8, 17408] key is VMEM-resident as it is (32 counts cost 0.23 ms a step).
# ---------------------------------------------------------------------------

_INT32_MIN = jnp.iinfo(jnp.int32).min

# VMEM a query tile's row of the table may take: its int32 keys and its int8
# output in two buffers, 6 B a (query, column). Half the stated limit, the
# rest for the tiles in flight (q, w and K blocks, a tile of scores and its
# products): 128 queries at 17,408 columns (13.4 MB), 64 at 34,816.
_SELECT_VMEM_BYTES = _VMEM_LIMIT_BYTES // 2


def select_blocks(s: int, t: int) -> tuple[int, int]:
    """(block_q, block_k) of a `segment_select` call: the key blocks are
    `index_scores`'s, the query tile the largest whose row of the table fits
    ``_SELECT_VMEM_BYTES``, never under 8 rows."""
    block_q, block_k = _fit_block(512, s), _fit_block(512, t)
    while block_q > 8 and block_q % 2 == 0 and block_q * t * 6 > _SELECT_VMEM_BYTES:
        block_q //= 2
    return block_q, block_k


def _segment_select_kernel(
    offsets_ref,  # scalar-prefetch [B]
    q_ref,  # [1, Hi, block_q, Di]
    w_ref,  # [1, block_q, Hi] float32
    k_ref,  # [1, block_k, Di]
    o_ref,  # [1, block_q, T] int8
    keys_scr,  # [T / block_k, block_q, block_k] int32
    *, block_q: int, block_k: int, n_t: int, topk: int,
):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q_start = offsets_ref[b] + i * block_q
    last = jnp.minimum((q_start + block_q - 1) // block_k, n_t - 1)  # the diagonal's block
    position = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def column(c):
        return c * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    @pl.when(j <= last)
    def _score():
        # a float's order is its bits' once the sign is folded: as SIGNED
        # int32, `_select_mask`'s uint32 key with its top bit flipped. A
        # column the query cannot see sorts below every score
        bits = jax.lax.bitcast_convert_type(_index_score_tile(q_ref, w_ref, k_ref[0]), jnp.int32)
        key = bits ^ ((bits >> 31) & 0x7FFFFFFF)
        keys_scr[j] = jnp.where(column(j) <= position, key, _INT32_MIN)

    @pl.when(j == n_t - 1)
    def _rank():
        lanes = min(128, block_k)

        def count(hit):
            """[block_q, 1]: a row's columns, of the blocks up to the
            diagonal, where ``hit(keys, block)``: summed lane on lane, one
            reduction across lanes a count."""
            def block(c, acc):
                ones = hit(keys_scr[c], c).astype(jnp.int32)
                for at in range(0, block_k, lanes):
                    acc = acc + ones[:, at:at + lanes]
                return acc

            acc = jax.lax.fori_loop(0, last + 1, block, jnp.zeros((block_q, lanes), jnp.int32))
            return acc.sum(axis=-1, keepdims=True)

        keep = jnp.minimum(jnp.minimum(position + 1, n_t * block_k), topk)

        def bit(n, state):
            found, at_least = state
            tried = found | jnp.left_shift(jnp.int32(1), 31 - n)
            seen = count(lambda keys, c: keys >= (tried ^ _INT32_MIN))
            ok = seen >= keep
            return jnp.where(ok, tried, found), jnp.where(ok, seen, at_least)

        found, at_least = jax.lax.fori_loop(0, 32, bit, (jnp.zeros_like(keep), keep))
        kth = found ^ _INT32_MIN  # [block_q, 1], signed like the keys
        tied = jnp.max(at_least - keep) > 0  # some row may not keep all AT its threshold

        def write(chosen):
            def columns(c):
                return pl.ds(pl.multiple_of(c * block_k, block_k), block_k)

            def visited(c, _):
                o_ref[0, :, columns(c)] = chosen(keys_scr[c], c).astype(jnp.int8)
                return _

            def unseen(c, _):
                o_ref[0, :, columns(c)] = jnp.zeros((block_q, block_k), jnp.int8)
                return _

            jax.lax.fori_loop(0, last + 1, visited, 0)
            jax.lax.fori_loop(last + 1, n_t, unseen, 0)

        @pl.when(jnp.logical_not(tied))
        def _without_ties():
            write(lambda keys, c: keys >= kth)

        @pl.when(tied)
        def _with_ties():
            # of the columns AT the threshold a row keeps the lowest ``room``:
            # those below the largest bound with no more than ``room`` of them
            # under it (every one of them where the row keeps them all)
            room = keep - count(lambda keys, c: keys > kth)
            bits = (n_t * block_k).bit_length()

            def bit(n, bound):
                tried = bound | jnp.left_shift(jnp.int32(1), bits - 1 - n)
                under = count(lambda keys, c: (keys == kth) & (column(c) < tried))
                return jnp.where(under <= room, tried, bound)

            bound = jax.lax.fori_loop(0, bits, bit, jnp.zeros_like(keep))
            write(lambda keys, c: (keys > kth) | ((keys == kth) & (column(c) < bound)))


def segment_select(
    q_idx: jax.Array,  # [B, S, Hi, Di] the indexer's queries at offsets[b] + (0 .. S-1)
    w: jax.Array,  # [B, S, Hi] float32, the heads' weights
    k_idx: jax.Array,  # [B, T, Di] the row's indexer keys, columns 0 .. T-1
    offsets: jax.Array,  # [B]
    topk: int,
    interpret: bool = False,
) -> jax.Array:
    """The selection of a segment → [B, S, T] int8, what
    `sparse_segment_attention` takes: 1 where the query at ``offsets[b] + i``
    attends to the column, of the columns it sees (column <= its position)
    the ``min(topk, their number)`` of largest `index_scores`, a tie to the
    lower column; zeros past the diagonal. `_select_mask`'s set of
    `index_scores`'s scores under the causal mask, to the bit, padding
    queries included."""
    b, s, hi, di = q_idx.shape
    t = k_idx.shape[1]
    block_q, block_k = select_blocks(s, t)
    assert s % block_q == 0 and t % block_k == 0, "caller gates divisibility"
    n_t = t // block_k

    def k_index(b, i, j, offsets):  # past the diagonal nothing is fetched
        last = (offsets[b] + (i + 1) * block_q - 1) // block_k
        return (b, jnp.minimum(j, jnp.minimum(last, n_t - 1)), 0)

    note_grid(
        f"segment-select[s={s},t={t}]",
        f"block_q {block_q}, block_k {block_k}, to the diagonal",
    )
    note_kernel("segment_select")
    return pl.pallas_call(
        functools.partial(
            _segment_select_kernel, block_q=block_q, block_k=block_k, n_t=n_t, topk=topk
        ),
        name="segment_select",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s // block_q, n_t),
            in_specs=[
                pl.BlockSpec((1, hi, block_q, di), lambda b, i, j, off: (b, 0, i, 0)),
                pl.BlockSpec((1, block_q, hi), lambda b, i, j, off: (b, i, 0)),
                pl.BlockSpec((1, block_k, di), k_index),
            ],
            out_specs=pl.BlockSpec((1, block_q, t), lambda b, i, j, off: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((n_t, block_q, block_k), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, t), jnp.int8),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(offsets.astype(jnp.int32), q_idx.transpose(0, 2, 1, 3), w.astype(jnp.float32), k_idx)


# ---------------------------------------------------------------------------
# A latent model's segment EXPANDS its row's latents into the keys and values
# the walk above reads (models/transformer `_latent_expand`: ``k_h = [c_kv
# W_uk,h | k_rope]``, ``v_h = c_kv W_uv,h``). In XLA the expansion is as wide
# as the table, the shapes being static, and its output [t, h, j] is relaid
# head-major by two copies of the whole. Here a grid step takes one block of
# the row's latents (fetched once a block: the head axis innermost) and one
# head's share of the int8 ``wkv_b``, dequantised in VMEM as `_latent_expand`
# forms it, and writes that head's keys and values where the walk reads them,
# [B, H, T, D]; the blocks are whole key blocks of the walk and those past the
# segment's last query are not expanded: neither written nor, the walk
# stopping at its diagonal, read.
# ---------------------------------------------------------------------------


def latent_expand_block(s: int, t: int, config: ModelConfig) -> int:
    """Columns a grid step of `latent_expand_blocks` expands for ``s`` queries
    over a table of ``t``: two of the walk's key blocks where they divide the
    table (a head's weights are dequantised once a step), else one."""
    block_k = segment_key_blocks(s, t, config.resolved_head_dim, 1, 0)[1]
    return _fit_block(2 * block_k, t)


def _latent_expand_kernel(
    blocks_ref,  # scalar-prefetch [B]: the blocks a row expands
    lat_ref,  # [1, block, W]: [c_kv | k_rope | zeros]
    *refs,  # wk [1, kl, nope], wv [1, kl, v] (, their scales [1, 1, nope], [1, 1, v]), k, v
    kl: int, rope: int, quantized: bool,
):
    wk_ref, wv_ref = refs[:2]
    sk_ref, sv_ref = refs[2:4] if quantized else (None, None)
    k_ref, v_ref = refs[-2:]

    @pl.when(pl.program_id(1) < blocks_ref[pl.program_id(0)])
    def _body():
        lat = lat_ref[0]
        c_kv = lat[:, :kl]

        def product(w_ref, s_ref):
            w = w_ref[0]
            if quantized:
                w = (w.astype(jnp.float32) * s_ref[0]).astype(lat.dtype)
            return jnp.dot(c_kv, w, preferred_element_type=jnp.float32).astype(lat.dtype)

        k_ref[0, 0] = jnp.concatenate([product(wk_ref, sk_ref), lat[:, kl:kl + rope]], axis=-1)
        v_ref[0, 0] = product(wv_ref, sv_ref)


def latent_expand_blocks(
    lat: jax.Array,  # [B, T, W] the row's latents, columns 0 .. T-1
    w: jax.Array,  # [kl, H, nope + v] ``wkv_b``'s values (int8 with ``scale``)
    scale: jax.Array | None,  # [H, nope + v] float32
    seen: jax.Array,  # [B] the columns to expand: whole blocks of ``block``
    block: int,
    config: ModelConfig,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """`_latent_expand` of the first ``seen[b]`` columns of each row → k, v
    [B, H, T, nope + rope], [B, H, T, v] head-major. A column past
    ``seen[b]`` is NOT written: what a caller reads there is undefined."""
    kl, nope, rope = config.kv_lora_rank, config.qk_nope_head_dim, config.qk_rope_head_dim
    b, t, width = lat.shape
    h, v_dim = config.n_heads, w.shape[-1] - nope
    assert t % block == 0, "caller gates divisibility"
    blocks = (seen // block).astype(jnp.int32)

    # past a row's last block a step does nothing and every index stays where
    # the last step that did left it (a block index that does not change is
    # neither fetched nor written again)
    def lat_index(b, j, h_at, blocks):
        return (b, jnp.minimum(j, blocks[b] - 1), 0)

    def w_index(b, j, h_at, blocks):
        return (jnp.where(j < blocks[b], h_at, h - 1), 0, 0)

    def out_index(b, j, h_at, blocks):
        return (b, w_index(b, j, h_at, blocks)[0], lat_index(b, j, h_at, blocks)[1], 0)

    # a head's share of the matrix as one block: [H, kl, nope], [H, kl, v]
    heads_first = w.transpose(1, 0, 2)
    weights = [heads_first[..., :nope], heads_first[..., nope:]]
    specs = [pl.BlockSpec((1, kl, nope), w_index), pl.BlockSpec((1, kl, v_dim), w_index)]
    if scale is not None:
        weights += [scale[:, None, :nope], scale[:, None, nope:]]
        specs += [pl.BlockSpec((1, 1, nope), w_index), pl.BlockSpec((1, 1, v_dim), w_index)]
    note_kernel("latent_expand_blocks")
    return pl.pallas_call(
        functools.partial(_latent_expand_kernel, kl=kl, rope=rope, quantized=scale is not None),
        name="latent_expand_blocks",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t // block, h),
            in_specs=[pl.BlockSpec((1, block, width), lat_index), *specs],
            out_specs=[
                pl.BlockSpec((1, 1, block, nope + rope), out_index),
                pl.BlockSpec((1, 1, block, v_dim), out_index),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, nope + rope), lat.dtype),
            jax.ShapeDtypeStruct((b, h, t, v_dim), lat.dtype),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(blocks, lat, *weights)


# ---------------------------------------------------------------------------
# Ragged PAGED decode: one query per row against a page-table-indexed KV
# pool [P, Hkv, page_size, D] (arxiv 2502.10490 "Ragged Paged Attention":
# per-slot sequence lengths index pages through a table, (8,128) tiling on
# the (page_size, D) trailing dims, f32 accumulation). What the kernel
# EXECUTES follows the live pages, not the table's width: the grid is over
# rows only, the pool's values stay in HBM, and a row walks its own
# `cdiv(length, page_size)` pages in a loop inside the kernel. The fetches
# (page table[b, j], every kv head of it, one fat block, by async copy into
# VMEM slots) run ahead of the arithmetic along the batch's
# live pages taken as ONE sequence: over a row's end into the next row
# that holds anything, so a row's first page is there when its grid step
# starts. Where a page is SMALL a loop step takes a GROUP of the row's pages
# (PR 52; `_walk_shape`: 8 at the long-document cells' 80 KB and 128 KB pages,
# 4 under tables of 10 or 11): their scores come from ONE product
# [G, n x 64] and the pages are folded into the running softmax one after the
# other in the row's order (`_fold_pages`), so every sum is the one a walk of
# single pages makes and the output is that walk's to the bit. What a small
# page cost before was its loop step, not its bytes: the fetch's scalar
# `while_loop` and `pl.when` cut every page's serial chain (product, two lane
# reductions, two exponentials, product, rescale) into basic blocks of its
# own; a latent page of 80 KB read 0.47 us alone on a v5e where its bytes
# take 0.10, and reads 0.18 at 8 a step (K and V pages of 128 KB under a
# selection 0.34 -> 0.20, their bytes 0.16; my chip runs, PR 52, PERF.md
# section 6). Only pages that a row's bounds cannot cut ride a group: a
# window row's first page and a row's last one to n pages go one a step
# under the masks of the length and the lower bound, and the groups between
# carry none of that arithmetic (a selection's mask rides every step). A pool
# whose page is 256 KB or more walks a page a step as it did, in the module
# it had. A row of length 0 (the caller gives one to every row whose table
# maps nothing: inactive, padding, warm-up; models/transformer
# `_paged_lengths`) costs its grid step, some 0.3 us, and
# returns zeros. Before PR 28 the grid was (B, table_len): 0.20 us for
# every table entry of every row whatever the rows held, with the fetch
# (not the step) elided past a row's length, so bytes scaled with content
# and time did not (PERF.md §5). The table IS the bound on what a row
# reads: one compiled program for every sequence-length mix. The
# kernels take the WHOLE pool [L, P, Hkv, ps, D] and a layer index, so the
# caller's layer scan never slices a per-layer entry out of the pool to
# hand one over (a custom call's operand is materialised: that slice was
# 39.7% of a chat decode step, PERF.md §6 PR 25). The layer costs the
# kernel nothing: the pool is seen as [L·P, ...] (merging two major
# dimensions moves no byte) and the layer's offset is added to the table
# before the call. The bf16 and the int8 kernel are ONE skeleton
# (`_paged_decode_kernel`) with two page loads (`_page_bf16`,
# `_page_int8`). The masked-jnp fallback (gather through (layer, table),
# then the stock attention math) lives in models/transformer._paged_gather
# and carries tier-1 exactness.
# A model with an indexer reads a SELECTION of the row's tokens, and the
# skeleton takes it as one more optional row block, a mask over the row's
# pages (`ragged_paged_selected_attention`): the walk's bytes follow the
# row's length where the selection's follow its top-k, but a page is what a
# DMA can take from this pool. The decode read is that masked walk up to
# 16 x top-k columns of table and an XLA gather of the selected rows past it
# (models/transformer `_paged_selected_read` has the rule). The two prices,
# a layer on a v5e at 8 rows x 4 KV heads x 128 (PERF.md section 6, PR 44):
# the walk 0.05 ms + 4.8 ns a token of context (0.54 ms at 12,533 tokens a
# row, 380 GB/s of the K and V it walks; the mask adds under 1%); the gather
# 10 ns a (token, head) row of 256 B, 1.3 ms at a top-k of 2,048 whatever
# the context, and `lax.top_k` over the table. With scores and ranking, rows
# that fill their table cross at 21 x the top-k (walk 13% ahead at 17 x, 29%
# behind at 34 x). Without the operand nothing of it is traced: the other
# entry points hand Mosaic the modules they did (tests/test_tpu_compile.py
# pins them, and their models' decode programs whole).
# ---------------------------------------------------------------------------


def _page_bf16(q, page, scales, j, n, scale):
    """Scores of a loop step's ``n`` pages, ``j`` the first, and their
    values: ``page`` is the (k, v) pair of VMEM blocks [Hkv, n x ps, D] the
    step waited for."""
    k, v = (leaf.astype(jnp.float32) for leaf in page)
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale  # [Hkv, G, n x ps]
    return s, None, v


def _row_block_pages(ref, j, n):
    """Pages ``j .. j + n - 1`` of a row block [1, Tp, X, ps] whose tokens
    lie on lanes (the int8 pool's scales, a selection's mask), side by side
    as the step's scores lie: [X, n x ps]."""
    if n == 1:
        return ref[0, j]
    return jnp.concatenate([ref[0, j + i] for i in range(n)], axis=-1)


def _page_int8(q, page, scales, j, n, scale):
    """`_page_bf16` over the int8 pool: a page is (kq, vq), read raw int8
    from HBM, with the row's per-token f32 scales (ks, vs) [1, Tp, Hkv, ps]
    beside it, pages ``j .. j + n - 1`` of them. The scales ride the
    [.., n x ps]-shaped scores and, a page at a time, the probabilities
    (tokens on lanes, as the pool stores them), not the [.., n x ps, D]
    operands: the same product, D times less scale math."""
    kq, vq = page
    ks, vs = scales
    ks = _row_block_pages(ks, j, n)[:, None, :]  # [Hkv, 1, n x ps]
    vs = [vs[0, j + i][:, None, :] for i in range(n)]  # [Hkv, 1, ps] a page
    s = jax.lax.dot_general(
        q, kq.astype(jnp.float32),
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale * ks
    return s, vs, vq.astype(jnp.float32)


def _page_latent(q, page, scales, j, n, scale, value_width):
    """`_page_bf16` over a pool of LATENTS: a page is ONE VMEM block
    [1, n x ps, W], a token's row its key for every head and, its first
    ``value_width`` lanes, its value: read once, used twice. The products
    take the page as it lies (bf16 in, float32 out): 64 query heads against
    one key head put the kernel near the chip's ridge, where a float32
    product would bind before the bytes do."""
    (rows,) = page
    s = jax.lax.dot_general(
        q.astype(rows.dtype), rows, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale  # [1, H, n x ps]
    return s, None, rows[..., :value_width]


# Pages of K and V held in VMEM at once by the walk of ONE page a loop step:
# the one computed on and the next three live pages of the batch in flight
# behind it (1 MB of bf16 at 8 kv heads x 64 x 128). One page in flight left
# the copy's latency in the open: 196 us a call at two slots, 146 at three,
# 140 at four (decode drain's shape: 64 rows, 330 live pages; my chip runs,
# PR 28).
_PAGE_SLOTS = 4
# A loop step of the walk has a fixed price, some 0.32 us on a v5e whatever
# the page holds (the fetch's scalar loop and branch cut each page's serial
# chain of product, reductions, exponentials, product and rescale into basic
# blocks of its own; PR 51's chip runs, PERF.md section 6): the time the
# chip's 819 GB/s take over 256 KB. Under that a page's STEP sets the walk's
# time and a step takes a group of pages; from there on its COPY does, the
# step hides behind it and the walk is the one-page walk it was. (Groups at
# 256 KB pages and above read 3-10% faster on the walk alone, which moved no
# end-to-end metric of four cells in PR 51 and, at the size PR 51 traced them,
# cost their set-up 5-14 s: a later PR's, with ROADMAP S14 (4), what a kernel
# instance costs to lower; PERF.md section 6, PR 52, has both readings.)
_WALK_PAGE_BYTES = 256 * 1024
# Pages a loop step takes at most: at 8 the latent walk reads within a fifth
# of its bytes' time and K and V pages of 128 KB are on it (PERF.md section
# 6, PR 51).
_WALK_GROUP = 8


def _walk_slots(group: int) -> int:
    """Page slots for loop steps of ``group`` pages: the step computed on and
    as many pages in flight, three at least; the one-page walk's four."""
    return group + max(group, 3) if group > 1 else _PAGE_SLOTS


def _walk_shape(page_bytes: int, table_len: int) -> tuple[int, int]:
    """(pages a loop step of the walk takes inside a row, page slots held in
    VMEM) for a pool whose page (every leaf of it) is ``page_bytes`` under
    tables of ``table_len`` pages: `_WALK_GROUP` pages where a page is under
    `_WALK_PAGE_BYTES`, halved while a row that fills its table would not
    hold two steps (16 slots of under 256 KB: 4 MiB at most of the 16 the
    chip's compiler grants a kernel). Else 1: the one-page walk, whose Mosaic
    module is PR 50's byte for byte."""
    group = _WALK_GROUP if page_bytes < _WALK_PAGE_BYTES else 1
    while group > 1 and 2 * group > table_len:
        group //= 2
    return group, _walk_slots(group)


def _fold_pages(carry, s, p_scale, v, masked: bool, page_size: int):
    """A loop step's scores ``s`` [Hkv, G, n x ps] and values ``v``
    [Hkv, n x ps, D] folded into the running softmax ``carry`` (m, l
    [Hkv, G, 1], acc [Hkv, G, D]) a PAGE at a time, in the row's order: every
    sum is the one a walk of single pages makes, to the bit. ``p_scale``: the
    int8 pool's value scales [Hkv, 1, ps], a page each, or None; ``masked``:
    some score may be `_NEG`."""
    n = s.shape[-1] // page_size
    for i in range(n):
        at = slice(i * page_size, (i + 1) * page_size)
        m_prev, l_prev, acc = carry  # [Hkv, G, 1] twice, [Hkv, G, D]
        s_i, v_i = (s, v) if n == 1 else (s[..., at], v[:, at])
        m_new = jnp.maximum(m_prev, s_i.max(axis=-1, keepdims=True))
        p = jnp.exp(s_i - m_new)
        if masked:  # a row of nothing but masked columns: exp(0) each
            p = jnp.where(s_i <= _NEG, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            # (a float32 page, every loader's but the latent's: no cast)
            (p if p_scale is None else p * p_scale[i]).astype(v_i.dtype),
            v_i,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, G, D]
        carry = m_new, l_prev * corr + p.sum(axis=-1, keepdims=True), acc * corr + pv
    return carry


def _paged_decode_kernel(
    lengths_ref,  # scalar-prefetch [B]
    *refs,  # [lower_ref], pages_ref, q_ref, n_scales row blocks, [chosen_ref],
    # n_leaves pool leaves in HBM, o_ref, scratch
    load,  # _page_bf16 | _page_int8 | _page_latent
    n_scales: int,
    n_leaves: int,
    page_size: int,
    table_len: int,
    scale: float,
    softcap,
    group: int = 1,  # pages a loop step takes inside a row (`_walk_shape`)
    windowed: bool = False,
    selected: bool = False,
    value_width: int = 0,  # of the output where it is not q's (a latent's value)
):
    # a window layer's rows read [lower, length): one more prefetched vector
    lower_ref = refs[0] if windowed else None
    pages_ref, q_ref, *refs = refs[1:] if windowed else refs
    scales, refs = refs[:n_scales], refs[n_scales:]
    if selected:  # the row's selection [1, Tp, 1, ps], 1.0 where a token is read
        chosen_ref, *refs = refs
    pool, o_ref = refs[:n_leaves], refs[n_leaves]
    bufs = refs[n_leaves + 1: 2 * n_leaves + 1]  # per leaf [slots, a page]
    sems, walk = refs[2 * n_leaves + 1:]
    slots = bufs[0].shape[0]
    b = pl.program_id(0)
    nb = pl.num_programs(0)

    def pages_of(row):
        length = lengths_ref[jnp.minimum(row, nb - 1)]
        return jnp.minimum(pl.cdiv(length, page_size), table_len)

    def first_of(row):  # the row's first page: that of its lower bound
        if not windowed:
            return jnp.int32(0)
        return lower_ref[jnp.minimum(row, nb - 1)] // page_size

    def copies(page, slot):
        return [
            pltpu.make_async_copy(src.at[page], buf.at[slot], sems.at[i, slot])
            for i, (src, buf) in enumerate(zip(pool, bufs))
        ]

    # The batch's live pages form one sequence, row after row, and the
    # fetches run ahead of the arithmetic along it, across the rows' ends
    # and over rows that hold nothing: `walk` (SMEM, carried from one grid
    # step to the next: the grid runs in order on one core) holds how many
    # pages were computed on, how many fetched, and the row and the page
    # the next fetch is at. The n-th page of the sequence lands in slot
    # n % slots; a loop step fetches as many pages as it computes on, so the
    # fetches stay `slots - group` pages ahead.
    computed, fetched, at_row, at_page = range(4)

    def fetch_next():
        row, j = jax.lax.while_loop(  # over the rows that are exhausted
            lambda at: (at[0] < nb) & (at[1] >= pages_of(at[0])),
            lambda at: (at[0] + 1, first_of(at[0] + 1)),
            (walk[at_row], walk[at_page]),
        )

        @pl.when(row < nb)
        def _start():
            page = pages_ref[row * table_len + j]
            for copy in copies(page, walk[fetched] % slots):
                copy.start()
            walk[fetched] = walk[fetched] + 1

        walk[at_row] = row
        walk[at_page] = j + 1

    def times(n, fn):
        """``fn(i)`` for i in [0, n): traced ONCE where a step takes a group
        (what a kernel lowers to is what every start pays for it, ROADMAP
        S14), unrolled in the one-page walk, whose module is what it was."""
        if group == 1 or n == 1:
            for i in range(n):
                fn(i)
        else:
            jax.lax.fori_loop(0, n, lambda i, c: (fn(i), c)[1], 0)

    def fetch_ahead(n):
        """The next ``n`` pages of the sequence: in one run where the row the
        fetches are at still holds them all, a page at a time over rows' ends."""
        if n == 1:
            return fetch_next()
        row, j = walk[at_row], walk[at_page]

        def whole():
            def start(i):
                page = pages_ref[row * table_len + j + i]
                for copy in copies(page, jax.lax.rem(walk[fetched] + i, slots)):
                    copy.start()

            times(n, start)
            walk[fetched] = walk[fetched] + n
            walk[at_page] = j + n

        jax.lax.cond(
            (row < nb) & (j + n <= pages_of(row)), whole,
            lambda: times(n, lambda _: fetch_next()),
        )

    @pl.when(b == 0)
    def _first_row():
        for i in range(4):
            walk[i] = 0
        if windowed:
            walk[at_page] = first_of(0)
        times(slots - group, lambda _: fetch_next())

    length = lengths_ref[b]
    q = q_ref[0].astype(jnp.float32)  # [Hkv, G, D]
    hkv, heads, d = q.shape

    def step(j, carry, n, edge):
        """Pages ``j .. j + n - 1`` of the row, the next ``n`` of the
        sequence, folded into the carry. ``edge``: pages that a row's bounds
        can cut, its last ones and a window row's first."""
        fetch_ahead(n)
        first = walk[computed]
        one = first % slots if n == 1 else None  # (`%`, once: the module it was)

        def slot_of(i):
            return one if n == 1 else jax.lax.rem(first + i, slots)

        def wait(i):
            for copy in copies(0, slot_of(i)):  # a wait names the slot, not the page
                copy.wait()

        times(n, wait)
        walk[computed] = walk[computed] + n
        page = [
            buf[slot_of(0)] if n == 1
            else jnp.concatenate([buf[slot_of(i)] for i in range(n)], axis=-2)
            for buf in bufs
        ]
        s, p_scale, v = load(q, page, scales, j, n, scale)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        if edge:
            k_pos = j * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, n * page_size), 2
            )
            s = jnp.where(k_pos < length, s, _NEG)  # the last page's tail
            if windowed:  # and the first page's head
                s = jnp.where(k_pos >= lower_ref[b], s, _NEG)
        if selected:  # and every token the row's query did not choose
            s = jnp.where(_row_block_pages(chosen_ref, j, n)[None] > 0, s, _NEG)
        return _fold_pages(carry, s, p_scale, v, edge or selected, page_size)

    edge_step = functools.partial(step, n=1, edge=True)
    start, end = first_of(b), pages_of(b)
    carry = (
        jnp.full((hkv, heads, 1), _NEG, jnp.float32),
        jnp.zeros((hkv, heads, 1), jnp.float32),
        jnp.zeros((hkv, heads, value_width or d), jnp.float32),
    )
    if group == 1:
        carry = jax.lax.fori_loop(start, end, edge_step, carry)
    else:
        # Only pages that the row's bounds cannot cut ride a group: a window
        # row's first page goes alone (its lower bound cuts it), then whole
        # groups with none of the bounds' arithmetic, then the last one to
        # ``group`` pages, one a step, under the length's mask. Each kind of
        # step is traced once: a window row's first page is a pass of its own
        # through the one pair of loops, with no group.
        head = jnp.minimum(start + 1, end) if windowed else start
        groups = jnp.maximum(end - 1 - head, 0) // group

        def run(first, n_groups, last, carry):
            """``n_groups`` whole groups from page ``first``, then single
            pages up to ``last``."""
            carry = jax.lax.fori_loop(
                0, n_groups, lambda g, c: step(first + g * group, c, group, False), carry
            )
            return jax.lax.fori_loop(first + n_groups * group, last, edge_step, carry)

        if windowed:
            carry = jax.lax.fori_loop(
                0, 2,
                lambda rest, c: run(
                    jnp.where(rest == 1, head, start), jnp.where(rest == 1, groups, 0),
                    jnp.where(rest == 1, end, head), c,
                ),
                carry,
            )
        else:
            carry = run(start, groups, end, carry)
    _, l, acc = carry
    # a row of no pages: acc 0 over the floor of l, zeros and not NaN
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_row_index(b, *_):
    """Row ``b``'s block of q, of the output and of the int8 scales."""
    return (b, 0, 0, 0)


def _layer_pages(table: jax.Array, layer: jax.Array, num_pages: int) -> jax.Array:
    """The flattened table as pages of the pool seen as [L·P, ...]
    (`_flat_pool`), inside ``layer``: each physical index is clamped into
    the layer first, so an unmapped sentinel entry (possible only on
    masked-out pages) reads SOME page of this layer instead of faulting or
    reaching into the next one."""
    pages = jnp.clip(table.astype(jnp.int32), 0, num_pages - 1)
    return (jnp.asarray(layer, jnp.int32) * num_pages + pages).reshape(-1)


def _flat_pool(leaf: jax.Array) -> jax.Array:
    """[L, P, ...] → [L·P, ...]: two major dimensions merged, no byte moved."""
    return leaf.reshape((-1,) + leaf.shape[2:])


def _paged_decode_call(
    name: str, load, q: jax.Array, leaves: list, scales: list,
    lengths: jax.Array, table: jax.Array, layer: jax.Array,
    config: ModelConfig, page_size: int, interpret: bool,
    lower: jax.Array | None = None,
    chosen: jax.Array | None = None,
    scale: float | None = None,  # the scores'; None: 1 / sqrt(q's width)
    value_width: int = 0,  # the output's; 0: q's
) -> jax.Array:
    """The one `pallas_call` of the paged kernels. ``leaves`` are the
    pool's arrays [L, P, Hkv, ps, D] whose pages the kernel fetches itself;
    ``scales`` are the int8 pool's [L, P, Hkv, ps], which reach it as each
    row's own [Tp, Hkv, ps] block, gathered through (layer, table) here: a
    page of them is [Hkv, ps < 128] f32, which Mosaic cannot slice out of
    HBM (the minor dimension is narrower than a tile), and they are 1/32 of
    the pool's bytes. ``chosen`` [B, Tp x ps] bool is a row's selection
    among its columns: it rides as one more row block, float32
    [Tp, 1, ps] (the scales' form: a page of it is ``ref[0, j]``), and a
    column it leaves out is masked like one past the row's length."""
    b, h, d = q.shape
    tp = table.shape[1]
    hkv = leaves[0].shape[2]
    if scale is None:
        scale = 1.0 / (d**0.5)
    # heads of 64 lie two to a lane row of K and V (`pair_queries`); a latent's
    # row is wider than its absorbed query's by its padding alone
    pack = leaves[0].shape[-1] // d if len(leaves) == 2 else 1
    if pack > 1:
        q = pair_queries(q, hkv, pack)
        d = pack * d
    group = h // hkv
    step_pages, slots = _walk_shape(
        sum(math.prod(leaf.shape[2:]) * leaf.dtype.itemsize for leaf in leaves), tp
    )
    note_grid(f"paged-walk[{name},ps={page_size}]", f"pages/step {step_pages}, slots {slots}")
    kernel = functools.partial(
        _paged_decode_kernel,
        load=load,
        n_scales=len(scales),
        n_leaves=len(leaves),
        page_size=page_size,
        table_len=tp,
        scale=scale,
        softcap=config.attn_logit_softcap,
        group=step_pages,
        windowed=lower is not None,
        selected=chosen is not None,
        value_width=value_width,
    )
    dv = value_width or d
    bounds = [lengths.astype(jnp.int32)]
    if lower is not None:
        bounds.append(jnp.minimum(lower.astype(jnp.int32), bounds[0]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(bounds) + 1,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hkv, group, d), _paged_row_index)]
        + [pl.BlockSpec((1, tp, hkv, page_size), _paged_row_index)] * len(scales)
        + [pl.BlockSpec((1, tp, 1, page_size), _paged_row_index)] * (chosen is not None)
        + [pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)] * len(leaves),
        out_specs=pl.BlockSpec((1, hkv, group, dv), _paged_row_index),
        scratch_shapes=[
            pltpu.VMEM((slots,) + leaf.shape[2:], leaf.dtype)
            for leaf in leaves
        ] + [
            pltpu.SemaphoreType.DMA((len(leaves), slots)),
            pltpu.SMEM((4,), jnp.int32),
        ],
    )
    note_kernel(name)
    out = pl.pallas_call(
        kernel,
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dv), q.dtype),
        # rows in order on one core: the walk along the batch's live pages
        # is carried from one row to the next
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(
        *bounds,
        _layer_pages(table, layer, leaves[0].shape[1]),
        q.reshape(b, hkv, group, d),
        *(leaf.at[layer, table].get(mode="clip") for leaf in scales),
        *([] if chosen is None
          else [chosen.astype(jnp.float32).reshape(b, tp, 1, page_size)]),
        *(_flat_pool(leaf) for leaf in leaves),
    )
    out = out.reshape(b, h * dv)
    return own_half(out, h, hkv, pack) if pack > 1 else out


@_per_kv_head(3, kv_head_axis=2)
def ragged_paged_decode_attention(
    q: jax.Array,  # [B, H, D] single query per row
    k: jax.Array,  # the page pool [L, P, Hkv, ps, D], read at `layer`
    v: jax.Array,
    lengths: jax.Array,  # [B] valid logical columns per row; 0 = no work
    table: jax.Array,  # [B, Tp] physical page per logical page
    layer: jax.Array,  # scalar: which layer of the pool
    config: ModelConfig,
    page_size: int,
    interpret: bool = False,
    lower: jax.Array | None = None,  # [B]: a window layer's first visible column
) -> jax.Array:
    """GQA paged decode attention over one layer of the pool → [B, H*D].
    A row reads its first ``cdiv(length, page_size)`` table entries and no
    other; a row of length 0 comes back zeros. With ``lower`` the row reads
    columns [lower, length): the walk starts at page ``lower // page_size``
    (the pages behind it need not be mapped) and the first page's head is
    masked."""
    return _paged_decode_call(
        "ragged_paged_decode_attention", _page_bf16, q, [k, v], [], lengths,
        table, layer, config, page_size, interpret, lower,
    )


def ragged_paged_block_attention(
    q: jax.Array,  # [B, S, H, D]: a block of S queries a row
    k: jax.Array,  # the page pool [L, P, Hkv, ps, D], read at `layer`
    v: jax.Array,
    lengths: jax.Array,  # [B] the block's end: every query of the row sees [0, length)
    table: jax.Array,  # [B, Tp]
    layer: jax.Array,
    config: ModelConfig,
    page_size: int,
    interpret: bool = False,
) -> jax.Array:
    """A block pass's attention over one layer of the pool → [B, S, H*D]:
    the S queries of a row see one another and everything behind them, so
    all of them read the same keys and there is no mask among them. It is
    the paged decode kernel with S x group query rows a KV head (32 for a
    block of 4 under GQA 32/4, where a decode step has 8): one walk over the
    row's pages serves the whole block. Under its own name on the
    `pallas_call`; no mesh (`ServingEngine` refuses one for such a model)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    # [B, S, Hkv, G, D] -> [B, Hkv, S x G, D]: the kernel's query tile a head
    tile = q.reshape(b, s, hkv, group, d).transpose(0, 2, 1, 3, 4)
    out = _paged_decode_call(
        "ragged_paged_block_attention", _page_bf16,
        tile.reshape(b, hkv * s * group, d), [k, v], [], lengths, table, layer,
        config, page_size, interpret,
    )
    out = out.reshape(b, hkv, s, group, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, h * d)


@_per_kv_head(4, kv_head_axis=2)
def ragged_paged_selected_attention(
    q: jax.Array,  # [B, H, D] single query per row
    k: jax.Array,  # the page pool [L, P, Hkv, ps, D], read at `layer`
    v: jax.Array,
    lengths: jax.Array,  # [B] valid logical columns per row; 0 = no work
    table: jax.Array,  # [B, Tp]
    layer: jax.Array,
    chosen: jax.Array,  # [B, Tp x ps] bool: the columns the row's query reads
    config: ModelConfig,
    page_size: int,
    interpret: bool = False,
) -> jax.Array:
    """`ragged_paged_decode_attention` under a row's SELECTION → [B, H*D]:
    the row walks its ``cdiv(length, page_size)`` pages as ever and attends
    to the columns of ``chosen`` alone, one selection for all heads (a model
    with an indexer, models/transformer `_paged_selected_read`). The bytes
    follow the row's length, not the selection's size: the pool gives a DMA
    whole pages, and a gather of the selected tokens' 256 B rows costs 10 ns
    a row whatever it holds, so the walk wins up to some 21 x ``index_topk``
    tokens of context (the rule and both prices: `_paged_selected_read`). A
    page with nothing chosen adds nothing; a row with nothing chosen comes
    back zeros. Under its own name on the `pallas_call`."""
    return _paged_decode_call(
        "ragged_paged_selected_attention", _page_bf16, q, [k, v], [], lengths,
        table, layer, config, page_size, interpret, chosen=chosen,
    )


def ragged_paged_latent_attention(
    q: jax.Array,  # [B, H, W]: a row's absorbed queries, [q_nope W_uk^T | q_rope | 0]
    latents: jax.Array,  # the page pool's latent leaf [L, P, 1, ps, W], read at `layer`
    lengths: jax.Array,  # [B] valid logical columns per row; 0 = no work
    table: jax.Array,  # [B, Tp]
    layer: jax.Array,
    chosen: jax.Array | None,  # [B, Tp x ps] bool: the columns the row's query reads
    config: ModelConfig,
    page_size: int,
    interpret: bool = False,
    lower: jax.Array | None = None,  # [B]: a window kind's first visible column
) -> jax.Array:
    """A decode step's attention IN THE LATENT SPACE -> [B, H x
    kv_lora_rank]: the paged decode walk over a pool that holds ONE row a
    token, [normed latent | rotary key | zeros], which is the key of all H
    heads and, its first ``config.kv_lora_rank`` lanes, their value
    (models/transformer `_latent_decode_read` absorbs the up-projection into
    the query before and into the output after: nothing of a head's keys or
    values is formed). Under the row's selection ``chosen``
    (`ragged_paged_selected_attention`'s mask, a model with an indexer), or
    with ``chosen`` None DENSE: every row up to the length, and no mask
    operand rides the call at all (not an all-true one: its float32 copy is
    among what the selected read pays). A page is fetched once and serves
    both products; the scores' scale is ``config.attn_scale``, the expanded
    head's 1 / sqrt(qk_nope_head_dim + qk_rope_head_dim) and YaRN's factor
    where the model has one. The bytes follow the row's length either way.
    With ``lower`` (a window kind's layer, ``config`` that kind's geometry and
    ``table`` its ring's) the row reads columns [lower, length): the walk
    starts at page ``lower // page_size``, as `ragged_paged_decode_attention`'s
    does under a window, and the bytes follow the window.
    Under its own name on the `pallas_call`; no mesh (`ServingEngine` refuses
    one for such a model)."""
    return _paged_decode_call(
        "ragged_paged_latent_attention",
        functools.partial(_page_latent, value_width=config.kv_lora_rank),
        q, [latents], [], lengths, table, layer, config, page_size, interpret,
        lower=lower, chosen=chosen, scale=config.attn_scale,
        value_width=config.kv_lora_rank,
    )


@_per_kv_head(3, kv_head_axis=2)
def ragged_paged_decode_attention_int8(
    q: jax.Array,  # [B, H, D]
    k: dict,  # int8 pool {"q": [L,P,Hkv,ps,D] i8, "s": [L,P,Hkv,ps] f32}
    v: dict,
    lengths: jax.Array,  # [B]
    table: jax.Array,  # [B, Tp]
    layer: jax.Array,  # scalar: which layer of the pool
    config: ModelConfig,
    page_size: int,
    interpret: bool = False,
) -> jax.Array:
    """GQA paged decode attention over one layer of the int8 page pool →
    [B, H*D]: the bf16 kernel's skeleton with the int8 page load."""
    return _paged_decode_call(
        "ragged_paged_decode_attention_int8", _page_int8, q,
        [k["q"], v["q"]], [k["s"], v["s"]], lengths, table, layer, config,
        page_size, interpret,
    )


# ---------------------------------------------------------------------------
# Paged decode write: a step's new K/V rows into the pool, where it lies
# ---------------------------------------------------------------------------

# Rows of a page that one copy moves: the bf16 pool's tile in HBM is
# (8, 128) with row pairs packed, and Mosaic refuses a window narrower than
# the tile ("Slice shape along dimension 2 must be aligned to tiling"), so
# a token's row travels inside the aligned 8 rows that hold it.
_WRITE_ROWS = 8
# Rows of the batch whose tiles are in VMEM together: one grid step's
# (64 x 8 kv heads x 8 x 128 bf16 is 1 MB a leaf).
_WRITE_BLOCK = 64


def _paged_kv_write_kernel(
    pages_ref,  # scalar-prefetch [B]: each row's write page; >= num_pages drops
    offs_ref,  # scalar-prefetch [B]: the row's (first) offset inside that page
    layer_ref,  # scalar-prefetch [1]
    k_ref,  # [block, span x Hkv, D]: this grid step's new rows, span a batch row
    v_ref,
    _k_in,  # the pool leaves [L*P, Hkv, ps, D] in HBM, aliased to the outputs
    _v_in,
    k_pool,
    v_pool,
    k_buf,  # [block, Hkv, _WRITE_ROWS, D]
    v_buf,
    read_sems,  # DMA [2, block]
    write_sems,
    live_rows,  # SMEM [block]
    *,
    num_pages: int,
):
    block, rows, d = k_ref.shape
    hkv = k_pool.shape[1]
    span = rows // hkv  # consecutive offsets a batch row writes: 1 for a decode step
    first = pl.program_id(0) * block
    base = layer_ref[0] * num_pages
    leaves = ((k_pool, k_buf, k_ref), (v_pool, v_buf, v_ref))

    # the block's live rows, compacted: a dropped row (its page the
    # sentinel) costs this loop's iteration and nothing else
    def collect(i, n):
        live_rows[n] = i
        page = pages_ref[first + i]
        return n + ((page >= 0) & (page < num_pages)).astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, block, collect, jnp.int32(0), unroll=True)

    def tile(pool, i):
        # the layer is added only to a page that is inside the pool
        off = offs_ref[first + i]
        start = pl.multiple_of(off - off % _WRITE_ROWS, _WRITE_ROWS)
        return pool.at[
            base + pages_ref[first + i], :, pl.ds(start, _WRITE_ROWS), :
        ]

    def over_live_rows(fn):
        def body(j, carry):
            fn(live_rows[j])
            return carry

        jax.lax.fori_loop(0, n_live, body, 0)

    def read(i):
        for leaf, (pool, buf, _) in enumerate(leaves):
            pltpu.make_async_copy(
                tile(pool, i), buf.at[i], read_sems.at[leaf, i]
            ).start()

    def replace_row(i):
        # (no `+ 0` for a decode step: its program is the one it was)
        at = [
            jax.lax.broadcasted_iota(jnp.int32, (_WRITE_ROWS, d), 0) == (
                offs_ref[first + i] % _WRITE_ROWS + j if j else offs_ref[first + i] % _WRITE_ROWS
            )
            for j in range(span)
        ]
        for leaf, (pool, buf, new) in enumerate(leaves):
            pltpu.make_async_copy(
                tile(pool, i), buf.at[i], read_sems.at[leaf, i]
            ).wait()
            for h in range(hkv):
                for j in range(span):
                    row = jnp.broadcast_to(
                        new[i, pl.ds(j * hkv + h, 1), :], (_WRITE_ROWS, d)
                    )
                    buf[i, h] = jnp.where(at[j], row, buf[i, h])
            pltpu.make_async_copy(
                buf.at[i], tile(pool, i), write_sems.at[leaf, i]
            ).start()

    def written(i):
        for leaf, (pool, buf, _) in enumerate(leaves):
            pltpu.make_async_copy(
                buf.at[i], tile(pool, i), write_sems.at[leaf, i]
            ).wait()

    # no two live rows write one page, so their tiles never overlap: every
    # row's read is in flight before the first is waited for, and every
    # write before the first is
    over_live_rows(read)
    over_live_rows(replace_row)
    over_live_rows(written)


@_per_kv_head(3, kv_head_axis=2, returns_pool=True)
def paged_kv_write(
    new: tuple[jax.Array, jax.Array],  # a decode step's K and V rows [B, Hkv, D],
    # or a block pass's [B, S x Hkv, D] (position-major: `block_write_ok`)
    k: jax.Array,  # the page pool [L, P, Hkv, ps, D], written at `layer`
    v: jax.Array,
    pages: jax.Array,  # [B] each row's write page (models/transformer `_page_index`)
    offsets: jax.Array,  # [B] and the offset inside it
    layer: jax.Array,  # scalar: which layer of the pool
    config: ModelConfig,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Write row ``b``'s K and V at ``[layer, pages[b], :, offsets[b]]`` of
    the pool where it lies (the leaves are aliased to the outputs and seen
    as [L·P, ...], as the decode kernel sees them) and give both leaves
    back. A row whose page is the sentinel (>= P) DROPS, at no copy at all:
    the cost is per live row. The write is read-modify-write of the
    ``_WRITE_ROWS`` aligned rows that hold the offset; every other byte of
    the pool is untouched. With S x Hkv rows a batch row (a block pass), row
    ``b`` writes offsets ``offsets[b] .. offsets[b] + S - 1``, which lie in
    one aligned tile (`block_write_ok`): still one copy in and one out."""
    del config  # `_per_kv_head`'s: the mesh to split the heads over
    # heads of 64 lie two to a lane row: a step's rows [B, Hkv, 64] are the
    # pool's [B, Hkv / 2, 128] as they stand
    new = tuple(leaf.reshape(leaf.shape[0], -1, k.shape[-1]) for leaf in new)
    b, rows, d = new[0].shape
    num_pages, hkv = k.shape[1], k.shape[2]
    block = _fit_block(_WRITE_BLOCK, b)
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    row_block = pl.BlockSpec((block, rows, d), lambda i, *_: (i, 0, 0))
    flat = [_flat_pool(leaf) for leaf in (k, v)]
    note_kernel("paged_kv_write")
    out = pl.pallas_call(
        functools.partial(_paged_kv_write_kernel, num_pages=num_pages),
        name="paged_kv_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b // block,),
            in_specs=[row_block, row_block, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((block, hkv, _WRITE_ROWS, d), leaf.dtype)
                for leaf in flat
            ] + [
                pltpu.SemaphoreType.DMA((2, block)),
                pltpu.SemaphoreType.DMA((2, block)),
                pltpu.SMEM((block,), jnp.int32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype) for leaf in flat],
        # operands 5 and 6 (after the three prefetched scalars and the two
        # row blocks) are the pool: written in place
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(
        pages.astype(jnp.int32), offsets.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *(leaf.astype(k.dtype) for leaf in new), *flat,
    )
    return out[0].reshape(k.shape), out[1].reshape(v.shape)


# Page copies of a leaf in flight together in `paged_insert_pages`: a copy
# waits for the one that used its semaphore this many copies earlier.
_INSERT_IN_FLIGHT = 32


def _paged_insert_pages_kernel(*refs, num_pages: int, leaves: int, every_layer: bool):
    """Copies of whole pages into the pool, HBM → HBM. Operands: the mapped
    pages, scalar-prefetched [n * W/ps] (row r's page of the write c at
    r * W/ps + c) and, where ONE layer is written, that layer [1] after them;
    ``leaves`` sources in HBM, every layer's [L, n, .., W, D] (``every_layer``:
    an admission group's local cache) or one layer's [n, .., W, D] (a
    segment's new rows), ``..`` the kv heads or, the indexer's key, nothing;
    the pool's leaves [L*P, .., ps, D], aliased to the outputs; the DMA
    semaphores [leaves, _INSERT_IN_FLIGHT] and SMEM [n * W/ps]."""
    scalars = 1 if every_layer else 2
    table_ref, layer_ref = refs[0], None if every_layer else refs[1]
    sources = refs[scalars:scalars + leaves]
    pools = refs[scalars + 2 * leaves:scalars + 3 * leaves]
    sems, live = refs[-2:]
    layers = sources[0].shape[0] if every_layer else 1
    width, page_size = sources[0].shape[-2], pools[0].shape[-2]
    per_row = width // page_size
    in_flight = sems.shape[1]

    # the mapped entries, compacted: a page the row does not hold (or a
    # padding row's, the sentinel) costs this loop's iteration and no byte
    def collect(e, n):
        live[n] = e
        page = table_ref[e]
        return n + ((page >= 0) & (page < num_pages)).astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, table_ref.shape[0], collect, jnp.int32(0))

    def copies(layer, row, col, page, slot):
        # a pool page is the contiguous [Hkv, ps, D]; its source is Hkv runs
        # of ps x D, strided by the source's width (one run of the indexer's
        # key, which has no head)
        start = pl.multiple_of(col * page_size, page_size)
        at, pool_layer = ((layer, row), layer) if every_layer else ((row,), layer_ref[0])
        return [
            pltpu.make_async_copy(
                src.at[(
                    *at, *(slice(None),) * (src.ndim - len(at) - 2),
                    pl.ds(start, page_size), slice(None),
                )],
                pool.at[pool_layer * num_pages + page],
                sems.at[leaf, slot],
            )
            for leaf, (src, pool) in enumerate(zip(sources, pools))
        ]

    def wait(slot):
        # every copy of a leaf moves one page: any page's descriptor waits
        # for a slot
        for copy in copies(0, 0, 0, 0, slot):
            copy.wait()

    def entry(j, carry):
        e = live[j]
        page = table_ref[e]

        def layer_copy(layer, carry):
            issued = j * layers + layer
            slot = issued % in_flight

            @pl.when(issued >= in_flight)
            def _():
                wait(slot)

            for copy in copies(layer, e // per_row, e % per_row, page, slot):
                copy.start()
            return carry

        if every_layer:
            return jax.lax.fori_loop(0, layers, layer_copy, carry)
        return layer_copy(0, carry)

    # no two mapped entries name one page, so no two copies overlap
    jax.lax.fori_loop(0, n_live, entry, 0)

    def drain(slot, carry):
        wait(slot)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(n_live * layers, in_flight), drain, 0)


def _paged_insert_pages_call(
    new: tuple, pools: tuple, pages: jax.Array, layer, interpret: bool
) -> list[jax.Array]:
    """`_paged_insert_pages_kernel` over ``pools`` [L, P, .., ps, D], each
    written in place and given back: every layer from ``new`` [L, n, .., W, D]
    (``layer`` None) or the one ``layer`` from ``new`` [n, .., W, D];
    ``pages`` [n, W/ps], the pool's page of each page of the write (outside
    [0, P): dropped)."""
    leaves = len(pools)
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    flat = [_flat_pool(leaf) for leaf in pools]
    scalars = [pages.astype(jnp.int32).reshape(-1)]
    if layer is not None:
        scalars.append(jnp.asarray(layer, jnp.int32).reshape(1))
    note_kernel("paged_insert_pages")
    out = pl.pallas_call(
        functools.partial(
            _paged_insert_pages_kernel, num_pages=pools[0].shape[1], leaves=leaves,
            every_layer=layer is None,
        ),
        name="paged_insert_pages",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(1,),
            in_specs=[hbm] * (2 * leaves),
            out_specs=[hbm] * leaves,
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((leaves, _INSERT_IN_FLIGHT)),
                pltpu.SMEM((pages.size,), jnp.int32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype) for leaf in flat],
        # the operands after the prefetched scalars and the sources are the
        # pool: written in place
        input_output_aliases={len(scalars) + leaves + i: i for i in range(leaves)},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*scalars, *(src.astype(pool.dtype) for src, pool in zip(new, pools)), *flat)
    return [o.reshape(pool.shape) for o, pool in zip(out, pools)]


def paged_insert_pages(
    new: tuple[jax.Array, jax.Array],  # a prefill's local K and V [L, n, Hkv, W, D]
    k: jax.Array,  # the page pool [L, P, Hkv, ps, D], every layer written
    v: jax.Array,
    table: jax.Array,  # [n, Tp] each row's pages; outside [0, P) is unmapped
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Write row ``r``'s columns ``[p * ps, (p + 1) * ps)`` of every layer to
    page ``table[r, p]`` of the pool where it lies (the leaves are aliased
    to the outputs and seen as [L·P, ...]) and give both leaves back: ONE
    copy HBM → HBM a (layer, row, mapped page) and leaf, ``_INSERT_IN_FLIGHT``
    of them in flight. ``W`` is a whole number of pages. An entry outside
    the pool (a padding or warm-up row, a page the row does not hold, a
    logical page past the table) DROPS at no copy; every byte of the pool
    other than the mapped pages is neither read nor written. All layers in
    one call: the caller holds the whole local cache, and a custom call's
    operand is materialised, so no layer scan may hand this a slice."""
    layers, n, hkv, width, d = new[0].shape
    num_pages, page_size = k.shape[1], k.shape[3]
    assert width % page_size == 0 and k.shape == (layers, num_pages, hkv, page_size, d)
    per_row = width // page_size
    # the logical pages the local cache covers, the sentinel past the table
    table = table.astype(jnp.int32)[:, :per_row]
    table = jnp.pad(
        table, ((0, 0), (0, per_row - table.shape[1])), constant_values=num_pages
    )
    k, v = _paged_insert_pages_call(new, (k, v), table, None, interpret)
    return k, v


def paged_insert_layer_pages(
    new: tuple,  # ONE layer's new rows, a leaf each: [n, Hkv, W, D], or [n, W, Di]
    pools: tuple,  # the pool's leaves [L, P, Hkv, ps, D] / [L, P, ps, Di], written at `layer`
    pages: jax.Array,  # [n, W/ps] the pool's page of each page of the write
    layer: jax.Array,  # scalar: which layer of the pool
    interpret: bool = False,
) -> list[jax.Array]:
    """`paged_insert_pages` for the rows ONE layer has just made (a prefill
    segment's, inside the layer loop: that layer's attention then reads the
    pool): row ``r``'s columns ``[c * ps, (c + 1) * ps)`` of every leaf go to
    page ``pages[r, c]`` of ``layer`` where the pool lies, one copy HBM → HBM
    a (row, mapped page) and leaf, and the leaves come back. The write starts
    on a page's edge and ``W`` is a whole number of pages; K and V
    (head-major, the pool's order), a latent's one leaf and the indexer's key
    (no head axis) ride one call. An entry outside [0, P) (a padding row, a
    page past the table or past the row's reservation: `models/transformer.
    _page_index`'s sentinel) DROPS at no copy."""
    for src, pool in zip(new, pools):
        assert src.shape[-2] % pool.shape[-2] == 0 and src.shape[1:-2] == pool.shape[2:-2], (
            src.shape, pool.shape,
        )
    # a leaf of ONE head (a latent's) goes as a leaf of none, the same bytes:
    # with the axis of 1 the chip's compiler gives the scan's carry a layout
    # of its own (that axis outermost) and copies the whole leaf into the
    # kernel's and back, every layer
    lone = [pool.ndim == 5 and pool.shape[2] == 1 for pool in pools]
    out = _paged_insert_pages_call(
        [src[:, 0] if one else src for src, one in zip(new, lone)],
        [pool[:, :, 0] if one else pool for pool, one in zip(pools, lone)],
        pages, layer, interpret,
    )
    return [leaf[:, :, None] if one else leaf for leaf, one in zip(out, lone)]


def block_write_ok(block_length: int, page_size: int) -> bool:
    """Whether `paged_kv_write` can write a block of ``block_length`` rows
    that starts on a multiple of it: the block lies in one aligned tile of
    ``_WRITE_ROWS`` rows of one page."""
    return _WRITE_ROWS % block_length == 0 and page_size % _WRITE_ROWS == 0


def paged_tiles_ok(head_dim: int, page_size: int) -> bool:
    """``attention_impl: auto``'s gate of the paged kernels: a real TPU, and
    the Mosaic tiling constraints on a page's (page_size, D) block dims."""
    return (
        jax.default_backend() == "tpu"
        and head_dim % 128 == 0
        and page_size % 16 == 0
    )


def paged_pallas_ok(config: ModelConfig, page_size: int) -> bool:
    """True when the ragged-paged decode kernel should carry the paged
    decode read. ``attention_impl="pallas"`` forces it (interpret mode
    off-TPU, for exactness tests); ``"auto"`` requires a real TPU plus the
    Mosaic tiling constraints on the (page_size, D) block dims — off-TPU
    the gathered masked-jnp view is both exact and faster. ``"jnp"``
    disables it outright (the tier-1 reference path). Under a mesh the
    "model" axis must divide the kv heads (``_mesh_ok``)."""
    if config.attention_impl == "jnp":
        return False
    if config.ring_axis is not None or not _mesh_ok(config):
        return False
    if config.attention_impl == "pallas":
        return page_size % 8 == 0
    # what a page's rows are wide: a head's K (two heads of 64 to a row), or
    # a latent model's kept row
    width = (
        config.latent_key_width if config.has_latent
        else config.resolved_head_dim * config.kv_head_pack
    )
    return paged_tiles_ok(width, page_size)


# ---------------------------------------------------------------------------
# Dispatch gate
# ---------------------------------------------------------------------------


def _head_tiles_ok(config: ModelConfig) -> bool:
    """``auto``'s gate on a head's width: whole 128-lane tiles. A latent
    model's expanded key is ``[k_nope | k_rope]``, the one rotary key behind
    the head's own part, and its value has a width of its own: the value in
    whole tiles and the key in whole HALF tiles (192 = 128 + 64 is the
    block's whole minor dimension, which Mosaic takes and keeps at 256 lanes
    in VMEM; tests/test_tpu_compile.py compiles both widths for a v5e)."""
    if config.has_latent:
        return config.resolved_head_dim % 64 == 0 and config.v_head_dim % 128 == 0
    # (two heads of 64 share a lane row: ``kv_head_pack``)
    return config.resolved_head_dim * config.kv_head_pack % 128 == 0


def pallas_ok(config: ModelConfig, seq_len: int) -> bool:
    """True when the prefill kernel applies: no ring axis (ring attention
    owns the sequence-parallel path), and under a mesh only when its "model"
    axis divides the kv heads (``_mesh_ok``).

    ``attention_impl="pallas"`` forces the kernel (interpret mode off-TPU,
    for tests) gated only on block divisibility; ``"auto"`` additionally
    requires a real TPU backend and lane-aligned (128) head dim / length —
    the engine's prefill buckets guarantee those in production."""
    if config.attention_impl == "jnp":
        return False
    if config.ring_axis is not None or not _mesh_ok(config):
        return False
    if config.attention_impl == "pallas":
        return seq_len == 1 or seq_len % min(128, seq_len) == 0
    if jax.default_backend() != "tpu":
        return False
    if not _head_tiles_ok(config):
        return False
    if seq_len > 1 and seq_len % 128 != 0:
        # a bucket of 64: whole sublane tiles, one block a head. Taken where
        # the heads are packed (a model new with them); every other model's
        # 64-wide bucket keeps the masked jnp it has always traced
        return config.kv_head_pack > 1 and seq_len % 64 == 0
    return True

"""Flash-attention Pallas kernels.

Two hot paths, both GQA-aware (queries grouped per kv head so K/V blocks are
read once per group, not once per query head). K/V come in HEAD-MAJOR layout
[B, Hkv, T, D] — the kv-head axis stays out of the trailing two dims, so the
Mosaic TPU lowering's (8, 128) block-tiling constraint falls on (T, D) where
blocks are naturally aligned, and a per-head kv block is a contiguous
(block_k, D) slice (no relayout per grid step).

- ``flash_prefill_attention``: causal blocked attention with fp32
  online-softmax scratch accumulators — O(block_q x block_k) VMEM instead of
  the O(S^2) masked score tensor the jnp path materializes.
- ``ragged_decode_attention``: one query per sequence against a KV cache,
  skipping cache blocks past each row's true length (the continuous batcher
  packs rows of very different lengths into one step, so the dense masked
  read wastes bandwidth proportional to max_len - mean_len).

No reference counterpart (the reference's compute is remote HTTP calls);
kernel structure follows the public flash/paged-attention pattern from the
Pallas TPU guide.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from langstream_tpu.models.configs import ModelConfig

_NEG = -1e30

# Scoped VMEM the prefill/segment kernels are sized against AND the limit
# stated to Mosaic (CompilerParams.vmem_limit_bytes): one number on both
# sides, so the block-size choice below cannot drift from what the compiler
# enforces (its unstated default, 16MiB, refused gemma-2b's 256-row q blocks
# at 16.99MiB). A v5e core has 128MiB of VMEM.
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)


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
    int8_kv: bool = False,
) -> int:
    """Shrink block_q until one grid step of the prefill/segment kernels
    fits ``_VMEM_LIMIT_BYTES``. Counted per step: the double-buffered q/out
    blocks [G, block_q, D] and K/V blocks [block_k, D] (int8 caches add
    their f32 scale columns, lane-padded to 128), the f32 m/l/acc scratch
    [G, block_q, 128|128|D], and the [G, block_q, block_k] score and
    probability tiles (f32 each, plus the probabilities' model-dtype copy
    that feeds the PV dot). Shape-aware rather than a smaller global
    default: fat-head models (gemma G=8 D=256) step down to 256 rows while
    llama (G=4 D=128) keeps the full 512."""
    kv_row = d + 128 * 4 if int8_kv else d * itemsize
    kv = 2 * 2 * block_k * kv_row  # k + v, ×2 buffers
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


def _per_kv_head(n_replicated: int, kv_head_axis: int = 1):
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
    "model" axis divides the kv heads."""

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
            return jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(_model_on(q.ndim, q.ndim - 2), kv_spec, kv_spec)
                + (P(),) * n_replicated,
                out_specs=_model_on(q.ndim - 1, q.ndim - 2),
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
    return mesh is None or config.n_kv_heads % mesh.shape.get("model", 1) == 0


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
    """Causal GQA attention → [B, S, H*D]."""
    b, s, h, d = q.shape
    hkv = k.shape[1]
    group = h // hkv
    block_k = _fit_block(block_k, s)
    block_q = _fit_block(
        _vmem_block_q(block_q, block_k, group, d, jnp.dtype(q.dtype).itemsize), s
    )
    assert s % block_q == 0 and s % block_k == 0, "caller gates divisibility"
    # head-major queries: [B, Hkv, G, S, D] so the blocked dims are (S, D)
    qg = q.reshape(b, s, hkv, group, d).transpose(0, 2, 3, 1, 4)

    kernel = functools.partial(
        _prefill_kernel,
        block_q=block_q,
        block_k=block_k,
        scale=1.0 / (d**0.5),
        softcap=config.attn_logit_softcap,
    )
    out = pl.pallas_call(
        kernel,
        name="flash_prefill_attention",
        grid=(b, hkv, s // block_q, s // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, 1, group, block_q, d), lambda b, h, i, j: (b, h, 0, i, 0)
            ),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, block_q, d), lambda b, h, i, j: (b, h, 0, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, block_q, 128), jnp.float32),
            pltpu.VMEM((group, block_q, 128), jnp.float32),
            pltpu.VMEM((group, block_q, d), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(qg, k, v)
    # [B, Hkv, G, S, D] → [B, S, H*D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h * d)


# ---------------------------------------------------------------------------
# Chunked prefill: a prompt SEGMENT at a global offset attending to the
# already-written cache prefix (long-context serving; the engine loops this
# over 2k-token segments so any prompt <= max_seq_len serves with bounded
# activation memory — the O(S^2) single-shot prefill never materializes)
# ---------------------------------------------------------------------------


def _segment_body(
    off_ref,  # [B] int32 scalar-prefetch: global position of segment start
    q_ref,  # [1, 1, G, block_q, D]
    load_kv,  # (q_dtype) -> ([block_k, D], [block_k, D]) in model dtype
    o_ref,  # [1, 1, G, block_q, D]
    m_scr,  # [G, block_q, 128] f32
    l_scr,  # [G, block_q, 128] f32
    acc_scr,  # [G, block_q, D] f32
    *,
    block_q: int,
    block_k: int,
    scale: float,
    softcap,
):
    """Shared online-softmax body of the two segment kernels (bf16 cache
    and int8 cache differ only in how the K/V block materializes)."""
    b = pl.program_id(0)
    i = pl.program_id(2)  # query block (within the segment)
    j = pl.program_id(3)  # key block (over the full cache width)
    nk = pl.num_programs(3)
    off = off_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = off + i * block_q  # GLOBAL position of this q block's first row
    k_start = j * block_k

    # causal against global positions: the whole prefix (k < off) is visible,
    # plus the lower triangle within the segment
    @pl.when(k_start <= q_start + block_q - 1)
    def _body():
        # model-dtype dots, fp32 accumulation (see _prefill_kernel note:
        # f32-cast operands ran the MXU at ~14 TFLOPS — the 32k TTFT)
        q = q_ref[0, 0, :, :, :]  # [G, block_q, D]
        k, v = load_kv(q.dtype)
        s = (
            jax.lax.dot_general(
                q,
                k,
                dimension_numbers=(((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_q, block_k), 1)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_q, block_k), 2)
        s = jnp.where(k_pos <= q_pos, s, _NEG)

        m_prev = m_scr[:, :, 0]
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
        )
        acc_scr[...] = acc_scr[...] * corr[:, :, None] + pv
        m_scr[:, :, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :, 0], 1e-30)[:, :, None]
        o_ref[0, 0, :, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def _segment_kernel(
    off_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, **opts
):
    _segment_body(
        off_ref, q_ref,
        lambda _dt: (k_ref[0, 0, :, :], v_ref[0, 0, :, :]),
        o_ref, m_scr, l_scr, acc_scr, **opts,
    )


@_per_kv_head(1)
def flash_segment_attention(
    q: jax.Array,  # [B, S, H, D] — segment queries
    k: jax.Array,  # [B, Hkv, T, D] cache (head-major), T >= offset + S
    v: jax.Array,  # [B, Hkv, T, D]
    offset: jax.Array,  # [B] int32 global position of the segment start
    config: ModelConfig,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Causal GQA attention of a segment against cache prefix + itself
    → [B, S, H*D]. The segment's own K/V must already be scattered into the
    cache at [offset, offset+S)."""
    b, s, h, d = q.shape
    hkv = k.shape[1]
    t = k.shape[2]
    group = h // hkv
    block_k = _fit_block(block_k, t)
    block_q = _fit_block(
        _vmem_block_q(block_q, block_k, group, d, jnp.dtype(q.dtype).itemsize), s
    )
    assert s % block_q == 0 and t % block_k == 0, "caller gates divisibility"
    qg = q.reshape(b, s, hkv, group, d).transpose(0, 2, 3, 1, 4)

    kernel = functools.partial(
        _segment_kernel,
        block_q=block_q,
        block_k=block_k,
        scale=1.0 / (d**0.5),
        softcap=config.attn_logit_softcap,
    )

    def kv_index(b, h, i, j, off):
        # clamp past-diagonal blocks to the last block this q block needs:
        # Pallas re-references the SAME block and elides the HBM→VMEM DMA,
        # so early segments don't stream the whole (mostly-unwritten) cache
        last = jnp.maximum(pl.cdiv(off[b] + (i + 1) * block_q, block_k) - 1, 0)
        return (b, h, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, s // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, 1, group, block_q, d), lambda b, h, i, j, off: (b, h, 0, i, 0)
            ),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, block_q, d), lambda b, h, i, j, off: (b, h, 0, i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group, block_q, 128), jnp.float32),
            pltpu.VMEM((group, block_q, 128), jnp.float32),
            pltpu.VMEM((group, block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_segment_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, s, d), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(offset.astype(jnp.int32), qg, k, v)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h * d)


def _segment_int8_kernel(
    off_ref,  # [B] int32 scalar-prefetch: global position of segment start
    q_ref,  # [1, 1, G, block_q, D]
    kq_ref,  # [1, 1, block_k, D] int8
    ks_ref,  # [1, 1, block_k, 1] f32 per-token scales
    vq_ref,  # [1, 1, block_k, D] int8
    vs_ref,  # [1, 1, block_k, 1] f32
    o_ref,  # [1, 1, G, block_q, D]
    m_scr,  # [G, block_q, 128] f32
    l_scr,  # [G, block_q, 128] f32
    acc_scr,  # [G, block_q, D] f32
    **opts,
):
    """_segment_body over an int8 KV cache: the HBM read stays int8
    (the r5 32k-TTFT residual was the materialized bf16 cache copy the
    non-quantized kernel forced — ~8.6GB of traffic per late segment);
    K/V dequantize in VMEM to the model dtype so the dots still ride the
    MXU at bf16 rate (f32 operands measured 14 vs 34.8 TFLOPS)."""

    def load_kv(dtype):
        k = (kq_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]).astype(dtype)
        v = (vq_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]).astype(dtype)
        return k, v

    _segment_body(off_ref, q_ref, load_kv, o_ref, m_scr, l_scr, acc_scr, **opts)


@_per_kv_head(1)
def flash_segment_attention_int8(
    q: jax.Array,  # [B, S, H, D] — segment queries
    k: dict,  # int8 cache entry {"q": [B,Hkv,T,D] i8, "s": [B,Hkv,T] f32}
    v: dict,
    offset: jax.Array,  # [B] int32 global position of the segment start
    config: ModelConfig,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """flash_segment_attention directly over the int8 KV cache → no
    cache-sized bf16 temp, int8 on the HBM wire. Same causal/GQA math."""
    b, s, h, d = q.shape
    hkv = k["q"].shape[1]
    t = k["q"].shape[2]
    group = h // hkv
    block_k = _fit_block(block_k, t)
    block_q = _fit_block(
        _vmem_block_q(
            block_q, block_k, group, d, jnp.dtype(q.dtype).itemsize, int8_kv=True
        ),
        s,
    )
    assert s % block_q == 0 and t % block_k == 0, "caller gates divisibility"
    qg = q.reshape(b, s, hkv, group, d).transpose(0, 2, 3, 1, 4)

    kernel = functools.partial(
        _segment_int8_kernel,
        block_q=block_q,
        block_k=block_k,
        scale=1.0 / (d**0.5),
        softcap=config.attn_logit_softcap,
    )

    def kv_index(b, h, i, j, off):
        # clamp past-diagonal blocks to the last block this q block needs
        # (same DMA-eliding trick as the bf16 segment kernel)
        last = jnp.maximum(pl.cdiv(off[b] + (i + 1) * block_q, block_k) - 1, 0)
        return (b, h, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, s // block_q, t // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, 1, group, block_q, d), lambda b, h, i, j, off: (b, h, 0, i, 0)
            ),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            # trailing singleton: Mosaic needs the block's last two dims
            # (8,128)-divisible or equal to the array's — [.., block_k, 1]
            pl.BlockSpec((1, 1, block_k, 1), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, 1), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, block_q, d), lambda b, h, i, j, off: (b, h, 0, i, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group, block_q, 128), jnp.float32),
            pltpu.VMEM((group, block_q, 128), jnp.float32),
            pltpu.VMEM((group, block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="flash_segment_attention_int8",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, s, d), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(
        offset.astype(jnp.int32),
        qg,
        k["q"],
        k["s"][..., None],
        v["q"],
        v["s"][..., None],
    )
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h * d)


# ---------------------------------------------------------------------------
# Decode: one query per row against a ragged KV cache
# ---------------------------------------------------------------------------


def _decode_kernel(
    lengths_ref,  # scalar-prefetch [B]
    q_ref,  # [1, 1, G, D]
    k_ref,  # [1, 1, block_k, D]
    v_ref,  # [1, 1, block_k, D]
    o_ref,  # [1, 1, G, D]
    m_scr,  # [G, 128] f32
    l_scr,  # [G, 128] f32
    acc_scr,  # [G, D] f32
    *,
    block_k: int,
    scale: float,
    softcap,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    length = lengths_ref[b]
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip cache blocks entirely past this row's written length
    @pl.when(k_start < length)
    def _body():
        q = q_ref[0, 0, :, :].astype(jnp.float32)  # [G, D]
        k = k_ref[0, 0, :, :].astype(jnp.float32)  # [block_k, D]
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                q,
                k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [G, block_k]
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(k_pos < length, s, _NEG)

        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(s <= _NEG, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0] = l_scr[:, 0] * corr + p.sum(axis=-1)
        pv = jnp.dot(p, v, preferred_element_type=jnp.float32)  # [G, D]
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[:, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
        o_ref[0, 0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@_per_kv_head(1)
def ragged_decode_attention(
    q: jax.Array,  # [B, H, D] single query per row
    k: jax.Array,  # [B, Hkv, T, D] cache (head-major)
    v: jax.Array,  # [B, Hkv, T, D]
    lengths: jax.Array,  # [B] int32 — valid cache prefix per row
    config: ModelConfig,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """GQA decode attention → [B, H*D]."""
    b, h, d = q.shape
    hkv = k.shape[1]
    t = k.shape[2]
    group = h // hkv
    block_k = _fit_block(block_k, t)
    assert t % block_k == 0, "caller gates divisibility"
    qg = q.reshape(b, hkv, group, d)

    kernel = functools.partial(
        _decode_kernel,
        block_k=block_k,
        scale=1.0 / (d**0.5),
        softcap=config.attn_logit_softcap,
    )
    def kv_index(b, h, j, lens):
        # paged-attention trick: clamp the block index at this row's last
        # valid block, so grid steps past the length re-reference the SAME
        # block and Pallas elides the HBM→VMEM copy — the DMA skip is where
        # the ragged bandwidth saving actually comes from (the pl.when only
        # skips the FLOPs)
        last = jnp.maximum(pl.cdiv(lens[b], block_k) - 1, 0)
        return (b, h, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, t // block_k),
        in_specs=[
            # index maps receive the scalar-prefetch ref as a trailing arg
            pl.BlockSpec((1, 1, group, d), lambda b, h, j, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, d), lambda b, h, j, lens: (b, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="ragged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(b, h * d)


# ---------------------------------------------------------------------------
# Decode over an INT8 cache: same ragged structure, but k/v blocks are read
# raw int8 (+ per-token f32 scales) straight from HBM — cache bandwidth is
# the decode bottleneck (measured r5: llama-3-8b B=96 step time 27.9ms at
# T=256 vs 61.8ms at T=1024 — the dense masked read scales with cache WIDTH,
# not content), and the block-skip makes it scale with the longest row
# instead.
# ---------------------------------------------------------------------------


def _decode_int8_kernel(
    lengths_ref,  # scalar-prefetch [B]
    q_ref,  # [1, Hkv, G, D]
    kq_ref,  # [1, Hkv, block_k, D] int8
    ks_ref,  # [1, Hkv, block_k, 1] f32 per-token scales
    vq_ref,  # [1, Hkv, block_k, D] int8
    vs_ref,  # [1, Hkv, block_k, 1] f32
    o_ref,  # [1, Hkv, G, D]
    m_scr,  # [Hkv, G, 128] f32
    l_scr,  # [Hkv, G, 128] f32
    acc_scr,  # [Hkv, G, D] f32
    *,
    block_k: int,
    scale: float,
    softcap,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nk = pl.num_programs(1)
    length = lengths_ref[b]
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(k_start < length)
    def _body():
        # ALL kv heads ride one grid step (batched dots): an [B,Hkv,·]
        # grid needed 8x the steps, and per-step grid overhead made the
        # kernel LOSE to the dense masked path (592 vs 1322 tok/s, r5)
        q = q_ref[0].astype(jnp.float32)  # [Hkv, G, D]
        # dequantize IN VMEM: the HBM read stays int8 (the bandwidth win)
        k = kq_ref[0].astype(jnp.float32) * ks_ref[0]  # [Hkv, block_k, D]
        v = vq_ref[0].astype(jnp.float32) * vs_ref[0]
        s = (
            jax.lax.dot_general(
                q,
                k,
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [Hkv, G, block_k]
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_k), 2
        )
        s = jnp.where(k_pos < length, s, _NEG)

        m_prev = m_scr[:, :, 0]  # [Hkv, G]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, :, None])
        p = jnp.where(s <= _NEG, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, :, 0] = l_scr[:, :, 0] * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p,
            v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, G, D]
        acc_scr[...] = acc_scr[...] * corr[:, :, None] + pv
        m_scr[:, :, 0] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :, 0], 1e-30)[:, :, None]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@_per_kv_head(1)
def ragged_decode_attention_int8(
    q: jax.Array,  # [B, H, D] single query per row
    k: dict,  # int8 cache entry {"q": [B,Hkv,T,D] i8, "s": [B,Hkv,T] f32}
    v: dict,
    lengths: jax.Array,  # [B]
    config: ModelConfig,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """GQA decode attention over an int8 KV cache → [B, H*D].

    Grid is (B, T/block_k) with every kv head inside the block — fewer,
    fatter grid steps and ~1MB DMAs. Blocks past a row's length clamp to
    its last valid block (DMA elided), so HBM traffic scales with CONTENT
    (sum of lengths), not cache width, and stays int8 on the wire.

    Differs from the jnp int8 path in q handling (q stays full precision
    here; the jnp path re-quantizes q to ride the int8 MXU) — slightly MORE
    accurate, same K/V math."""
    b, h, d = q.shape
    hkv = k["q"].shape[1]
    t = k["q"].shape[2]
    group = h // hkv
    block_k = _fit_block(block_k, t)
    assert t % block_k == 0, "caller gates divisibility"
    qg = q.reshape(b, hkv, group, d)

    kernel = functools.partial(
        _decode_int8_kernel,
        block_k=block_k,
        scale=1.0 / (d**0.5),
        softcap=config.attn_logit_softcap,
    )

    def kv_index(b, j, lens):
        # clamp past-length blocks to the row's last valid block: Pallas
        # re-references the same block and elides the HBM→VMEM DMA
        last = jnp.maximum(pl.cdiv(lens[b], block_k) - 1, 0)
        return (b, 0, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, t // block_k),
        in_specs=[
            pl.BlockSpec((1, hkv, group, d), lambda b, j, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, hkv, block_k, d), kv_index),
            # trailing singleton: Mosaic needs the block's last two dims
            # (8,128)-divisible or equal to the array's — [.., block_k, 1]
            pl.BlockSpec((1, hkv, block_k, 1), kv_index),
            pl.BlockSpec((1, hkv, block_k, d), kv_index),
            pl.BlockSpec((1, hkv, block_k, 1), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, hkv, group, d), lambda b, j, lens: (b, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, 128), jnp.float32),
            pltpu.VMEM((hkv, group, 128), jnp.float32),
            pltpu.VMEM((hkv, group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="ragged_decode_attention_int8",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        qg,
        k["q"],
        k["s"][..., None],
        v["q"],
        v["s"][..., None],
    )
    return out.reshape(b, h * d)


# ---------------------------------------------------------------------------
# Ragged PAGED decode: one query per row against a page-table-indexed KV
# pool [P, Hkv, page_size, D] (arxiv 2502.10490 "Ragged Paged Attention":
# per-slot sequence lengths index pages through a table, (8,128) tiling on
# the (page_size, D) trailing dims, f32 accumulation). What the kernel
# EXECUTES follows the live pages, not the table's width: the grid is over
# rows only, the pool's values stay in HBM, and a row walks its own
# `cdiv(length, page_size)` pages in a loop inside the kernel. The fetches
# (page table[b, j], every kv head of it, one fat block, by async copy into
# `_PAGE_SLOTS` VMEM slots) run ahead of the arithmetic along the batch's
# live pages taken as ONE sequence: over a row's end into the next row
# that holds anything, so a row's first page is there when its grid step
# starts. A row of length 0 (the caller gives one to every row whose table
# maps nothing: inactive, padding, warm-up; models/transformer
# `_paged_lengths`) costs its grid step, some 0.3 us, and
# returns zeros. Before PR 28 the grid was (B, table_len): 0.20 us for
# every table entry of every row whatever the rows held, with the fetch
# (not the step) elided past a row's length, so bytes scaled with content
# and time did not (PERF.md §5). No kv_bound ladder is needed: the table
# IS the bound, one compiled program for every sequence-length mix. The
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
# ---------------------------------------------------------------------------


def _page_bf16(q, page, scales, j, scale):
    """Scores of one page and its values: ``page`` is the (k, v) pair of
    VMEM blocks [Hkv, ps, D] an iteration waited for."""
    k, v = (leaf.astype(jnp.float32) for leaf in page)
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale  # [Hkv, G, ps]
    return s, None, v


def _page_int8(q, page, scales, j, scale):
    """`_page_bf16` over the int8 pool: a page is (kq, vq), read raw int8
    from HBM, with the row's per-token f32 scales (ks, vs) [1, Tp, Hkv, ps]
    beside it, page ``j`` of them. The scales ride the [.., ps]-shaped
    scores and probabilities (tokens on lanes, as the pool stores them),
    not the [.., ps, D] operands: the same product, D times less scale
    math."""
    kq, vq = page
    ks, vs = (ref[0, j][:, None, :] for ref in scales)  # [Hkv, 1, ps]
    s = jax.lax.dot_general(
        q, kq.astype(jnp.float32),
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale * ks
    return s, vs, vq.astype(jnp.float32)


# Pages of K and V held in VMEM at once: the one computed on and the next
# three live pages of the batch in flight behind it (1 MB of bf16 at 8 kv
# heads x 64 x 128). One page in flight left the copy's latency in the
# open: 196 us a call at two slots, 146 at three, 140 at four (decode
# drain's shape: 64 rows, 330 live pages; my chip runs, PR 28).
_PAGE_SLOTS = 4


def _paged_decode_kernel(
    lengths_ref,  # scalar-prefetch [B]
    pages_ref,  # scalar-prefetch [B * Tp]: the flattened table, layer added
    q_ref,  # [1, Hkv, G, D]
    *refs,  # n_scales row blocks, n_leaves pool leaves in HBM, o_ref, scratch
    load,  # _page_bf16 | _page_int8
    n_scales: int,
    n_leaves: int,
    page_size: int,
    table_len: int,
    scale: float,
    softcap,
):
    scales, refs = refs[:n_scales], refs[n_scales:]
    pool, o_ref = refs[:n_leaves], refs[n_leaves]
    bufs = refs[n_leaves + 1: 2 * n_leaves + 1]  # per leaf [_PAGE_SLOTS, a page]
    sems, walk = refs[2 * n_leaves + 1:]
    b = pl.program_id(0)
    nb = pl.num_programs(0)

    def pages_of(row):
        length = lengths_ref[jnp.minimum(row, nb - 1)]
        return jnp.minimum(pl.cdiv(length, page_size), table_len)

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
    # n % _PAGE_SLOTS.
    computed, fetched, at_row, at_page = range(4)

    def fetch_next():
        row, j = jax.lax.while_loop(  # over the rows that are exhausted
            lambda at: (at[0] < nb) & (at[1] >= pages_of(at[0])),
            lambda at: (at[0] + 1, jnp.int32(0)),
            (walk[at_row], walk[at_page]),
        )

        @pl.when(row < nb)
        def _start():
            page = pages_ref[row * table_len + j]
            for copy in copies(page, walk[fetched] % _PAGE_SLOTS):
                copy.start()
            walk[fetched] = walk[fetched] + 1

        walk[at_row] = row
        walk[at_page] = j + 1

    @pl.when(b == 0)
    def _first_row():
        for i in range(4):
            walk[i] = 0
        for _ in range(_PAGE_SLOTS - 1):
            fetch_next()

    length = lengths_ref[b]
    q = q_ref[0].astype(jnp.float32)  # [Hkv, G, D]
    hkv, group, d = q.shape

    def body(j, carry):
        m_prev, l_prev, acc = carry  # [Hkv, G, 1] twice, [Hkv, G, D]
        fetch_next()
        slot = walk[computed] % _PAGE_SLOTS
        for copy in copies(0, slot):  # a wait names the slot, not the page
            copy.wait()
        walk[computed] = walk[computed] + 1
        s, p_scale, v = load(q, [buf[slot] for buf in bufs], scales, j, scale)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2
        )
        s = jnp.where(k_pos < length, s, _NEG)  # the last page's tail

        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= _NEG, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            p if p_scale is None else p * p_scale,
            v,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, G, D]
        return m_new, l_prev * corr + p.sum(axis=-1, keepdims=True), acc * corr + pv

    _, l, acc = jax.lax.fori_loop(
        0, pages_of(b), body,
        (
            jnp.full((hkv, group, 1), _NEG, jnp.float32),
            jnp.zeros((hkv, group, 1), jnp.float32),
            jnp.zeros((hkv, group, d), jnp.float32),
        ),
    )
    # a row of no pages: acc 0 over the floor of l, zeros and not NaN
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_row_index(b, lens, pages):
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
) -> jax.Array:
    """The one `pallas_call` of both paged kernels. ``leaves`` are the
    pool's arrays [L, P, Hkv, ps, D] whose pages the kernel fetches itself;
    ``scales`` are the int8 pool's [L, P, Hkv, ps], which reach it as each
    row's own [Tp, Hkv, ps] block, gathered through (layer, table) here: a
    page of them is [Hkv, ps < 128] f32, which Mosaic cannot slice out of
    HBM (the minor dimension is narrower than a tile), and they are 1/32 of
    the pool's bytes."""
    b, h, d = q.shape
    tp = table.shape[1]
    hkv = leaves[0].shape[2]
    group = h // hkv
    kernel = functools.partial(
        _paged_decode_kernel,
        load=load,
        n_scales=len(scales),
        n_leaves=len(leaves),
        page_size=page_size,
        table_len=tp,
        scale=1.0 / (d**0.5),
        softcap=config.attn_logit_softcap,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hkv, group, d), _paged_row_index)]
        + [pl.BlockSpec((1, tp, hkv, page_size), _paged_row_index)] * len(scales)
        + [pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)] * len(leaves),
        out_specs=pl.BlockSpec((1, hkv, group, d), _paged_row_index),
        scratch_shapes=[
            pltpu.VMEM((_PAGE_SLOTS,) + leaf.shape[2:], leaf.dtype)
            for leaf in leaves
        ] + [
            pltpu.SemaphoreType.DMA((len(leaves), _PAGE_SLOTS)),
            pltpu.SMEM((4,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        # rows in order on one core: the walk along the batch's live pages
        # is carried from one row to the next
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        _layer_pages(table, layer, leaves[0].shape[1]),
        q.reshape(b, hkv, group, d),
        *(leaf.at[layer, table].get(mode="clip") for leaf in scales),
        *(_flat_pool(leaf) for leaf in leaves),
    )
    return out.reshape(b, h * d)


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
) -> jax.Array:
    """GQA paged decode attention over one layer of the pool → [B, H*D].
    A row reads its first ``cdiv(length, page_size)`` table entries and no
    other; a row of length 0 comes back zeros."""
    return _paged_decode_call(
        "ragged_paged_decode_attention", _page_bf16, q, [k, v], [], lengths,
        table, layer, config, page_size, interpret,
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
    return (
        jax.default_backend() == "tpu"
        and config.resolved_head_dim % 128 == 0
        and page_size % 16 == 0
    )


# ---------------------------------------------------------------------------
# Fused prefill+decode batch: one attention call whose rows mix S-token
# prompt SEGMENTS (chunked prefill at a global offset) with single-token
# decode queries against the same big KV cache (arxiv 2604.15464's ragged
# mixed batch, expressed as a dispatch over the two existing paths rather
# than a third kernel: prefill rows ride the segment kernel, decode rows
# the kv_bound-sliced dense read that beat both ragged decode kernels in
# r5). This is the attention-layer BUILDING BLOCK for a true single-program
# fused iteration; the shipped engine runs two back-to-back dispatches
# instead (PERF.md round 6 records the decision), so nothing calls this in
# production yet — it is exactness-tested and kept for the revisit.
# ---------------------------------------------------------------------------


def fused_segment_decode_attention(
    q_seg: jax.Array,  # [P, S, H, D] segment queries (prefill rows)
    seg_offsets: jax.Array,  # [P] int32 global position of each segment start
    q_dec: jax.Array,  # [Bd, H, D] one query per decode row
    k,  # [B, Hkv, T, D] shared head-major cache (array or int8 {"q","s"})
    v,
    seg_rows: jax.Array,  # [P] int32 cache row of each prefill row
    dec_rows: jax.Array,  # [Bd] int32 cache row of each decode row
    dec_lengths: jax.Array,  # [Bd] int32 valid cache prefix per decode row
    config: ModelConfig,
    kv_bound: int | None = None,  # static cap on decode rows' readable columns
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Mixed prefill-segment + decode attention over ONE cache
    → ([P, S, H*D] segment out, [Bd, H*D] decode out).

    The segment rows' own K/V must already be scattered into the cache at
    [offset, offset+S) (same contract as flash_segment_attention); decode
    rows attend to their first ``dec_lengths`` columns. Exactness: each half
    is bit-identical to its standalone path — this function only routes, it
    never re-derives math — so a fused iteration built on it matches the
    serialized prefill-then-decode reference token for token."""
    from langstream_tpu.models.transformer import attention as jnp_attention

    quantized = isinstance(k, dict)
    t = (k["q"] if quantized else k).shape[2]

    # prefill rows → the segment path (Pallas kernel when shapes fit)
    k_seg = jax.tree.map(lambda x: x[seg_rows], k)
    v_seg = jax.tree.map(lambda x: x[seg_rows], v)
    p, s = q_seg.shape[0], q_seg.shape[1]
    if pallas_ok(config, s, t):
        if quantized:
            seg_out = flash_segment_attention_int8(
                q_seg, k_seg, v_seg, seg_offsets, config, interpret=interpret
            )
        else:
            seg_out = flash_segment_attention(
                q_seg, k_seg, v_seg, seg_offsets, config, interpret=interpret
            )
    else:
        positions = seg_offsets[:, None] + jnp.arange(s)[None, :]  # [P, S]
        kv_pos = jnp.arange(t)[None, None, :]
        seg_mask = kv_pos <= positions[:, :, None]
        seg_out = jnp_attention(q_seg, k_seg, v_seg, seg_mask, config)

    # decode rows → the dense masked read over the kv_bound-sliced cache
    # (r5 measured this beating both ragged kernels at decode shapes)
    k_dec = jax.tree.map(lambda x: x[dec_rows], k)
    v_dec = jax.tree.map(lambda x: x[dec_rows], v)
    t_dec = t
    if kv_bound is not None and kv_bound < t:
        k_dec = jax.tree.map(lambda x: x[:, :, :kv_bound], k_dec)
        v_dec = jax.tree.map(lambda x: x[:, :, :kv_bound], v_dec)
        t_dec = kv_bound
    dec_mask = (
        jnp.arange(t_dec)[None, None, :] < dec_lengths[:, None, None]
    )  # [Bd, 1, T]
    dec_out = jnp_attention(q_dec[:, None], k_dec, v_dec, dec_mask, config)
    return seg_out, dec_out[:, 0]


# ---------------------------------------------------------------------------
# Multi-token verify: K+1 speculative-draft queries per row against the big
# cache (self-speculative decoding, engine._verify_chunk). Decode-shaped
# work, not prefill-shaped: S is tiny (k+1 ≤ ~9) and never 128-aligned, so
# the segment kernels' tiling can't apply — and r5 measured the dense masked
# read over the kv_bound-sliced cache beating the ragged kernels at exactly
# these shapes. One routing function for both cache dtypes keeps the verify
# path on the SAME jnp attention math as single-token decode, which is what
# makes greedy speculation token-exact with non-speculative greedy.
# ---------------------------------------------------------------------------


def multitoken_verify_attention(
    q: jax.Array,  # [B, S, H, D] — current token + S-1 draft queries per row
    k,  # [B, Hkv, T, D] cache (head-major array, or int8 {"q","s"} entry)
    v,
    mask: jax.Array,  # [B, S, T] bool — per-slot causal, built by the caller
    config: ModelConfig,
) -> jax.Array:
    """Per-slot causal attention of a draft chunk against the cache
    → [B, S, H*D]. Query j of row b attends columns ≤ position[b] + j (the
    prefix written by earlier steps plus the drafts' own lower triangle —
    their K/V must already be scattered at the query positions, the
    prefill_segment contract). The mask comes from verify_step_inplace,
    which owns the ONLY definition of the verify causal frontier — columns
    past a row's frontier may hold stale rejected-draft K/V from a
    previous verify, and the mask is what makes that harmless.

    Deliberately a named entry point here rather than an inlined call in
    transformer._dispatch_attention: this is the seam a Pallas multi-token
    verify kernel would replace if a chip measurement ever justified one
    (r5's data says it won't at small S — the dense path won)."""
    from langstream_tpu.models.transformer import attention as jnp_attention

    return jnp_attention(q, k, v, mask, config)


# ---------------------------------------------------------------------------
# Dispatch gate
# ---------------------------------------------------------------------------


def pallas_ok(config: ModelConfig, seq_len: int, cache_len: int | None = None) -> bool:
    """True when the pallas kernels apply; no ring axis (ring attention owns
    the sequence-parallel path), and under a mesh only when its "model"
    axis divides the kv heads (``_mesh_ok``).

    ``attention_impl="pallas"`` forces the kernels (interpret mode off-TPU,
    for tests) gated only on block divisibility; ``"auto"`` additionally
    requires a real TPU backend and lane-aligned (128) head dim / lengths —
    the engine's prefill buckets and cache widths guarantee those in
    production."""
    if config.attention_impl == "jnp":
        return False
    if config.ring_axis is not None or not _mesh_ok(config):
        return False
    force = config.attention_impl == "pallas"
    if force:
        ok_seq = seq_len == 1 or seq_len % min(128, seq_len) == 0
        ok_cache = cache_len is None or cache_len % min(128, cache_len) == 0
        return ok_seq and ok_cache
    if jax.default_backend() != "tpu":
        return False
    if config.resolved_head_dim % 128 != 0:
        return False
    if seq_len > 1 and seq_len % 128 != 0:
        return False
    if cache_len is not None and cache_len % 128 != 0:
        return False
    return True

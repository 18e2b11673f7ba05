"""Ahead-of-time compiles of the main-path Pallas kernels for a DESCRIBED
TPU v5e (no chip attached): what the installed TPU compiler refuses —
scoped-VMEM overflow, tiling, a Mosaic call GSPMD cannot partition — fails
here, on the CPU tier, instead of on the chip. Nothing runs, so these say
nothing about results or times (chip_smoke.py checks results on a chip)."""

import base64
import dataclasses
import functools
import hashlib
import importlib.util
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from langstream_tpu.models.configs import MODEL_PRESETS
from langstream_tpu.ops import attention as A
from langstream_tpu.parallel.mesh import AXIS_ORDER
from langstream_tpu.parallel.sharding import page_pool_specs

SDS = jax.ShapeDtypeStruct
GEMMA = MODEL_PRESETS["gemma-2b"]
LLAMA = MODEL_PRESETS["llama-3-8b"]
PAGE, PAGES, TABLE, BATCH = 64, 2048, 32, 192
POOL_LAYERS = 2  # the kernels take the whole pool and a layer index


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        ).devices
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True)
def _as_on_the_chip():
    """Compile under the settings a chip process has, not the CPU tier's.
    Persistent cache off: an executable compiled for a described chip is
    written to it but cannot be read back without that chip (the next
    compile warns and recompiles). Matmul precision at JAX's default:
    conftest forces "highest" for the CPU correctness tests, and Mosaic
    rejects an fp32-precision contraction of bf16 operands."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.default_matmul_precision("default"):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _prefill_args(config, s):
    """(q, k, v) shapes of a prefill call (a latent model's value has a width
    of its own)."""
    h, hkv, d = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    dv = config.v_head_dim if config.has_latent else d
    pack = config.kv_head_pack  # heads of 64: two to a lane row of K and V
    return (
        SDS((1, s, h, d), jnp.bfloat16), SDS((1, hkv // pack, s, d * pack), jnp.bfloat16),
        SDS((1, hkv // pack, s, dv * pack), jnp.bfloat16),
    )


def _paged_args(config, int8, batch=BATCH, table=TABLE, pages=PAGES, layers=POOL_LAYERS):
    """(q, k, v, lengths, table, layer) shapes of a paged decode call."""
    h, hkv, d = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    q = SDS((batch, h, d), jnp.bfloat16)
    pack = config.kv_head_pack
    pool = (layers, pages, hkv // pack, PAGE)
    if int8:
        kv = {"q": SDS(pool + (d,), jnp.int8), "s": SDS(pool, jnp.float32)}
    else:
        kv = SDS(pool + (d * pack,), jnp.bfloat16)
    return (
        q, kv, kv, SDS((batch,), jnp.int32), SDS((batch, table), jnp.int32),
        SDS((), jnp.int32),
    )


def _prefill(config, s):
    return (
        lambda q, k, v: A.flash_prefill_attention(q, k, v, config),
        _prefill_args(config, s),
    )


def _paged(config, int8, **sizes):
    fn = (
        A.ragged_paged_decode_attention_int8 if int8
        else A.ragged_paged_decode_attention
    )
    return (
        lambda q, k, v, lens, table, layer: fn(
            q, k, v, lens, table, layer, config, PAGE
        ),
        _paged_args(config, int8, **sizes),
    )


def _kv_write(config, batch, pages, layers, table=None):
    """(the decode step's pool write, its arguments' shapes): K and V rows
    [B, Hkv, D], both bf16 pool leaves, a write page, an offset a row, and
    the layer (``table`` is the attention kernel's, not an operand here)."""
    pack = config.kv_head_pack
    hkv, d = config.n_kv_heads // pack, config.resolved_head_dim * pack
    rows = SDS((batch, hkv, d), jnp.bfloat16)
    pool = SDS((layers, pages, hkv, PAGE, d), jnp.bfloat16)
    at = SDS((batch,), jnp.int32)
    return (
        lambda k, v, pk, pv, page, offset, layer: A.paged_kv_write(
            (k, v), pk, pv, page, offset, layer, config
        ),
        (rows, rows, pool, pool, at, at, SDS((), jnp.int32)),
    )


def _insert_pages(config, rows, width, pages, layers, table):
    """(an admission group's insert by page, its arguments' shapes): the
    prefill's local K and V [L, rows, Hkv, width, D], both bf16 pool leaves
    and the rows' tables."""
    pack = config.kv_head_pack
    hkv, d = config.n_kv_heads // pack, config.resolved_head_dim * pack
    local = SDS((layers, rows, hkv, width, d), jnp.bfloat16)
    pool = SDS((layers, pages, hkv, PAGE, d), jnp.bfloat16)
    return (
        lambda k, v, pk, pv, table: A.paged_insert_pages((k, v), pk, pv, table),
        (local, local, pool, pool, SDS((rows, table), jnp.int32)),
    )


def _insert_layer_pages(leaves, width, pages, layers):
    """(a segment's write of ONE layer by page, its arguments' shapes):
    ``leaves`` name each leaf's (kv heads, row width), 0 heads the indexer's
    key (no head axis); one row of ``width`` new tokens, head-major, the
    pool's leaves of ``layers`` x ``pages`` pages, the pool's page of each
    page of the write, and the layer."""
    new, pools = [], []
    for hkv, d in leaves:
        heads = (hkv,) if hkv else ()
        new.append(SDS((1, *heads, width, d), jnp.bfloat16))
        pools.append(SDS((layers, pages, *heads, PAGE, d), jnp.bfloat16))
    return (
        lambda new, pools, at, layer: A.paged_insert_layer_pages(new, pools, at, layer),
        (new, pools, SDS((1, width // PAGE), jnp.int32), SDS((), jnp.int32)),
    )


# The benchmark's three cells (BENCHMARK.json; benchmark/workloads/*.json):
# slots x table pages, the pool's pages, the layers. Mistral-7B and Mixtral
# have llama-3-8b's attention (32 q / 8 kv heads of 128).
CELLS = {
    "chat64x20": dict(batch=64, table=20, pages=512, layers=32),
    "docs16x33": dict(batch=16, table=33, pages=528, layers=32),
    "drain64x10": dict(batch=64, table=10, pages=640, layers=6),
}


OLMO = MODEL_PRESETS["olmo-hybrid-7b"]


def _delta_update(config, batch, layers):
    """(the decode step's recurrent-state update, its arguments' shapes):
    q and k [B, H, dk], v [B, H, dv], the two gates [B, H], the whole state
    [L, rows, dk, H * dv], the layer, each row's state row, and who is live."""
    from langstream_tpu.ops import gated_delta as gd

    h, dk, dv = config.linear_n_heads, config.linear_key_head_dim, config.linear_value_head_dim
    f32 = lambda *s: SDS(s, jnp.float32)  # noqa: E731
    return (
        lambda q, k, v, g, beta, state, layer, rows, live: gd.gated_delta_update(
            q, k, v, g, beta, state, layer, rows, live
        ),
        (f32(batch, h, dk), f32(batch, h, dk), f32(batch, h, dv), f32(batch, h), f32(batch, h),
         f32(layers, batch, dk, h * dv), SDS((), jnp.int32), SDS((batch,), jnp.int32),
         SDS((batch,), jnp.bool_)),
    )


# command-a-plus-05-2026 as the benchmark cuts it (`tiny-window-moe-test`'s
# block at the published widths): 128 Q / 8 KV heads x 128, a window of 4096,
# 16 held experts of 4096 x 4096 in int8; the cell: 16 slots x 196 pages
CMDA = dataclasses.replace(
    MODEL_PRESETS["tiny-window-moe-test"], name="cmdaplus-widths", d_model=4096, d_ff=4096,
    n_heads=128, n_kv_heads=8, head_dim=128, sliding_window=4096, n_experts=128,
    n_experts_per_tok=8, n_shared_experts=4, experts_held=(0, 16), vocab_size=32768,
)


# SDAR-30B-A3B-Chat as the benchmark cuts it (`tiny-blockfill-moe-test`'s block
# at the published widths): 32 Q / 4 KV heads x 128, blocks of 4 tokens, 128
# experts of 2048 x 768 in int8, 12 layers, the whole vocabulary; the cell:
# 64 slots x 11 pages
SDAR = dataclasses.replace(
    MODEL_PRESETS["tiny-blockfill-moe-test"], name="sdar-widths", d_model=2048, d_ff=6144,
    moe_d_ff=768, n_layers=12, n_heads=32, n_kv_heads=4, head_dim=128, n_experts=128,
    n_experts_per_tok=8, experts_held=(0, 128), vocab_size=151936, mask_token_id=151669,
    max_seq_len=32768,
)


# Keye-VL-2.0-30B-A3B's language model as the benchmark cuts it
# (`tiny-sparse-moe-test`'s block at the published widths): 32 Q / 4 KV heads
# x 128, an indexer of 16 heads x 64 that keeps 2,048 tokens, 128 experts of
# 2048 x 768 in int8, 12 layers, the whole vocabulary; the cell: 8 slots x
# 272 pages
KEYE = dataclasses.replace(
    MODEL_PRESETS["tiny-sparse-moe-test"], name="keye-widths", d_model=2048, d_ff=6144,
    moe_d_ff=768, n_layers=12, n_heads=32, n_kv_heads=4, head_dim=128, n_experts=128,
    n_experts_per_tok=8, experts_held=(0, 128), vocab_size=151936, index_n_heads=16,
    index_head_dim=64, index_topk=2048, mrope_section=(16, 24, 24), max_seq_len=262144,
)


# GLM-5 as the benchmark cuts it (`tiny-latent-moe-test`'s block at the
# published widths): a query latent of 2,048, a key-value latent of 512 and a
# rotary key of 64 for 64 heads of 192 + 64 (values 256), an indexer of 32
# heads x 128 (64 turned) that keeps 2,048 tokens, one leading dense layer of
# 12,288 and six expert layers that hold 16 of 256 experts of 6144 x 2048 and
# a shared one, a slice of 19,360 rows of the vocabulary; the cell: 16 slots x
# 272 pages, a token of the pool one row of 640 lanes and one of 128
GLM = dataclasses.replace(
    MODEL_PRESETS["tiny-latent-moe-test"], name="glm-widths", d_model=6144, d_ff=12288,
    moe_d_ff=2048, n_layers=7, n_heads=64, n_kv_heads=64, n_experts=256, n_experts_per_tok=8,
    experts_held=(0, 16), vocab_size=19360, q_lora_rank=2048, kv_lora_rank=512,
    qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256, index_n_heads=32,
    index_head_dim=128, index_topk=2048, index_rope_dim=64, max_seq_len=202752,
)


# Kimi-K2.5's language model as the benchmark cuts it
# (`tiny-latent-dense-moe-test`'s block at the published widths): a query
# latent of 1,536, a key-value latent of 512 and a rotary key of 64 for 64
# heads whose q.k is 128 + 64 = 192 wide and whose value 128, NO indexer, YaRN
# (factor 64 over 4,096), one leading dense layer of 18,432 and six expert
# layers that hold 12 of 384 experts of 7168 x 2048 and a shared one, a slice
# of 20,480 rows of the vocabulary; the cell: 16 slots x 272 pages, a token of
# the pool ONE row of 640 lanes
KIMI = dataclasses.replace(
    MODEL_PRESETS["tiny-latent-dense-moe-test"], name="kimi-widths", d_model=7168, d_ff=18432,
    moe_d_ff=2048, n_layers=7, n_heads=64, n_kv_heads=64, n_experts=384, n_experts_per_tok=8,
    experts_held=(0, 12), vocab_size=20480, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, routed_scaling=2.827,
    rope_theta=50000.0, rope_scaling_factor=64.0, rope_scaling_original_max_seq_len=4096,
    rope_scaling_beta_fast=32.0, max_seq_len=262144,
)


# dots3-note-prev's language model as the benchmark cuts it (`tiny-dots3-test`'s
# block at the published widths): TWO kinds of latent layer. Full: 128 heads
# of 128 + 64 (values 128) over a key-value latent of 512, base 8e7, an indexer
# of 64 heads x 128 that keeps 2,048. Window (513): 64 heads of 192 + 64
# (values 128) over a key-value latent of 1,024, base 5e4. A leading dense
# layer of 13,824 before two periods of (full, window x 3) that hold 16 of 256
# experts of 5120 x 1536 and a shared one, a slice of 19,008 rows of the
# vocabulary; the cell: 16 slots x 272 pages, a ring of 41 pages a row
DOTS3 = dataclasses.replace(
    MODEL_PRESETS["tiny-dots3-test"], name="dots3-widths", d_model=5120, d_ff=13824,
    moe_d_ff=1536, n_layers=9, n_heads=128, n_kv_heads=128, n_experts=256, n_experts_per_tok=8,
    experts_held=(0, 16), vocab_size=19008, q_lora_rank=1024, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, index_n_heads=64,
    index_head_dim=128, index_topk=2048, index_rope_dim=64, rope_theta=80000000.0,
    sliding_window=513, max_seq_len=524288,
    window_attention=(
        ("n_heads", 64), ("q_lora_rank", 1024), ("kv_lora_rank", 1024),
        ("qk_nope_head_dim", 192), ("qk_rope_head_dim", 64), ("v_head_dim", 128),
        ("rope_theta", 50000.0),
    ),
)


# LFM2-24B-A2B as the benchmark cuts it (`tiny-lfm2-test`'s block at the
# published widths): 32 Q / 8 KV heads x 64, two KV heads to a lane row of the
# cache and the pool ([L, P, 4, 64, 128]), 12 conv layers of 2,048 with a
# convolution of 3 taps and 4 attention layers, two leading dense layers of
# 11,776 and 14 expert layers of 64 experts of 2048 x 1536 top-4, the whole
# vocabulary on a tied head; the cell: 256 slots x 10 pages
LFM2 = dataclasses.replace(
    MODEL_PRESETS["tiny-lfm2-test"], name="lfm2-widths", d_model=2048, d_ff=11776,
    moe_d_ff=1536, n_layers=16, n_heads=32, n_kv_heads=8, head_dim=64, n_experts=64,
    n_experts_per_tok=4, experts_held=(0, 64), vocab_size=65536, max_seq_len=128000,
)


def _latent_decode(config, batch, table, pages, layers):
    """A decode step's attention in the latent space: absorbed queries
    against ONE leaf of rows, a page fetched once for key and value; under a
    row's selection, or (a model with no indexer) with no mask operand."""
    width = config.latent_key_width
    shapes = (
        SDS((batch, config.n_heads, width), jnp.bfloat16),
        SDS((layers, pages, 1, PAGE, width), jnp.bfloat16), SDS((batch,), jnp.int32),
        SDS((batch, table), jnp.int32), SDS((), jnp.int32),
    )
    if not config.has_indexer:
        return (
            lambda q, rows, lengths, tab, layer: A.ragged_paged_latent_attention(
                q, rows, lengths, tab, layer, None, config, PAGE
            ),
            shapes,
        )
    return (
        lambda q, rows, lengths, tab, layer, chosen: A.ragged_paged_latent_attention(
            q, rows, lengths, tab, layer, chosen, config, PAGE
        ),
        (*shapes, SDS((batch, table * PAGE), jnp.bool_)),
    )


def _latent_expand(config, s, t):
    """A segment's expansion of its row's latents into the keys and values
    of every head, head-major, up to the columns its queries can see."""
    kl, h = config.kv_lora_rank, config.n_heads
    out = config.qk_nope_head_dim + config.v_head_dim
    block = A.latent_expand_block(s, t, config)
    return (
        lambda lat, w, scale, seen: A.latent_expand_blocks(lat, w, scale, seen, block, config),
        (SDS((1, t, config.latent_key_width), jnp.bfloat16), SDS((kl, h, out), jnp.int8),
         SDS((h, out), jnp.float32), SDS((1,), jnp.int32)),
    )


def _index_scores(config, s, t):
    """The indexer's scores of a segment, in tiles."""
    hi, di = config.index_n_heads, config.index_head_dim
    return (
        lambda q, w, k, offsets: A.index_scores(q, w, k, offsets),
        (SDS((1, s, hi, di), jnp.bfloat16), SDS((1, s, hi), jnp.float32),
         SDS((1, t, di), jnp.bfloat16), SDS((1,), jnp.int32)),
    )


def _segment_select(config, s, t):
    """A segment's selection in one call: scores in tiles, ranked where they lie."""
    _, args = _index_scores(config, s, t)
    return lambda q, w, k, offsets: A.segment_select(q, w, k, offsets, config.index_topk), args


def _sparse_segment(config, s, t):
    """A segment's attention under a packed selection."""
    fn, (q, k, v, offsets) = _segment(config, s, t, 0)
    return (
        lambda q, k, v, offsets, chosen: A.sparse_segment_attention(
            q, k, v, offsets, chosen, config
        ),
        (q, k, v, offsets, SDS((1, s, t), jnp.int8)),
    )


def _paged_block(config, batch, table, pages, layers):
    """A block pass's attention: `block_length` queries a row against the
    row's pages, one walk for all of them."""
    _, (_, k, v, lengths, tab, layer) = _paged(
        config, False, batch=batch, table=table, pages=pages, layers=layers
    )
    q = SDS((batch, config.block_length, config.n_heads, config.resolved_head_dim), jnp.bfloat16)
    return (
        lambda q, k, v, lengths, tab, layer: A.ragged_paged_block_attention(
            q, k, v, lengths, tab, layer, config, PAGE
        ),
        (q, k, v, lengths, tab, layer),
    )


def _block_kv_write(config, batch, pages, layers):
    """The block pass's pool write: `block_length` x Hkv rows a batch row,
    into one aligned tile of the row's page."""
    fn, (rows, _, pool, _, at, _, layer) = _kv_write(config, batch, pages, layers)
    rows = SDS((batch, config.block_length * config.n_kv_heads, rows.shape[-1]), jnp.bfloat16)
    return fn, (rows, rows, pool, pool, at, at, layer)


def _windowed_decode(config, batch, table, pages, layers):
    """The paged decode kernel over a window layer's page group: a lower
    bound a row beside its length."""
    fn, (q, k, v, lengths, tab, layer) = _paged(
        config, False, batch=batch, table=table, pages=pages, layers=layers
    )
    return (
        lambda q, k, v, lengths, lower, tab, layer: A.ragged_paged_decode_attention(
            q, k, v, lengths, tab, layer, config, PAGE, lower=lower
        ),
        (q, k, v, lengths, lengths, tab, layer),
    )


def _selected_decode(config, batch, table, pages, layers):
    """The paged decode kernel under a row's selection: a mask over the
    columns of its table beside its length."""
    fn, (q, k, v, lengths, tab, layer) = _paged(
        config, False, batch=batch, table=table, pages=pages, layers=layers
    )
    return (
        lambda q, k, v, lengths, tab, layer, chosen: A.ragged_paged_selected_attention(
            q, k, v, lengths, tab, layer, chosen, config, PAGE
        ),
        (q, k, v, lengths, tab, layer, SDS((batch, table * PAGE), jnp.bool_)),
    )


def _segment(config, s, t, window):
    """A prefill segment's attention over its row's gathered columns."""
    bf16 = lambda *shape: SDS(shape, jnp.bfloat16)  # noqa: E731
    hd = config.resolved_head_dim
    dv = config.v_head_dim if config.has_latent else hd  # a latent model's value: its own width
    return (
        lambda q, k, v, offsets: A.flash_segment_attention(
            q, k, v, offsets, config, window=window
        ),
        (bf16(1, s, config.n_heads, hd), bf16(1, config.n_kv_heads, t, hd),
         bf16(1, config.n_kv_heads, t, dv), SDS((1,), jnp.int32)),
    )


def _grouped(config, tokens, layers, down=False):
    """The held experts' product over the rows ``tokens`` tokens route here:
    the whole int8 stack and a layer index, as the layer scan hands it on."""
    from langstream_tpu.ops import grouped_matmul as gm

    held, k = config.held_experts[1], config.n_experts_per_tok
    tile = gm.row_tile(tokens, k, config.n_experts)
    # a pass's buffer where the layer holds a share (PR 54), else every case's
    passes = gm.pass_shape(tokens, k, held, config.n_experts, tile)
    tiles = passes[1] if passes else gm.buffer_tiles(tokens, k, held, tile)
    d, f = (config.expert_d_ff, config.d_model) if down else (config.d_model, config.expert_d_ff)
    w = {"q": SDS((layers, held, d, f), jnp.int8), "s": SDS((layers, held, 1, f), jnp.float32)}
    return (
        lambda x, w, layer, tile_expert, used: gm.grouped_matmul(
            x, w, layer, tile_expert, used, tile, kernel=True
        ),
        (SDS((tiles * tile, d), jnp.bfloat16), w, SDS((), jnp.int32),
         SDS((tiles,), jnp.int32), SDS((1,), jnp.int32)),
    )


def _gate_up(config, tokens, layers):
    """The gate's and the up's product and the activation over the same rows:
    ONE call where a step holds an expert's whole matrix (`gate_up_shared`)."""
    from langstream_tpu.ops import grouped_matmul as gm

    fn, (x, w, *rest) = _grouped(config, tokens, layers)
    tile = gm.row_tile(tokens, config.n_experts_per_tok, config.n_experts)
    assert gm.gate_up_shared(tile, config.d_model, config.expert_d_ff)
    return (
        lambda x, w_gate, w_up, layer, tile_expert, used: gm.grouped_gate_up(
            x, w_gate, w_up, jax.nn.silu, layer, tile_expert, used, tile, kernel=True
        ),
        (x, w, w, *rest),
    )


CASES = {
    # the command-a-plus cell: both page groups' decode (2 full layers x 3136
    # pages; 6 window layers x 1552 pages with a lower bound), a 2048-token
    # segment against the row's 12,544 columns with and without the window,
    # and the grouped expert product of a decode step (16 tokens, tiles of 16
    # rows) and of a segment (2048 tokens, tiles of 256)
    "cmdaplus16x196-paged-decode": _paged(CMDA, False, batch=16, table=196, pages=3136, layers=2),
    "cmdaplus16x196-windowed-decode": _windowed_decode(CMDA, 16, 196, 1552, 6),
    "cmdaplus16x196-paged-kv-write": _kv_write(CMDA, batch=16, pages=1552, layers=6, table=196),
    "cmdaplus-segment-2048": _segment(CMDA, 2048, 12544, 0),
    "cmdaplus-window-segment-2048": _segment(CMDA, 2048, 12544, 4096),
    "cmdaplus-grouped-matmul-16": _grouped(CMDA, 16, 6),
    "cmdaplus-grouped-matmul-2048": _grouped(CMDA, 2048, 6),
    "cmdaplus-down-grouped-matmul-2048": _grouped(CMDA, 2048, 2, down=True),
    # the SDAR cell: the block pass's attention (32 query rows a KV head) and
    # its write at 64 slots x 11 pages x 12 layers, the prefill kernel under
    # the block mask at the cell's two kernel widths, and the grouped product
    # of a pass (256 positions x top-8 over 128 experts: tiles of 32 rows, an
    # expert's matrix ONE block, gate and up in one call) and of an admission
    # group (8 rows x 256 tokens: tiles of 256, K = 768 whole)
    "sdardrain64x11-paged-block": _paged_block(SDAR, 64, 11, 704, 12),
    "sdardrain64x11-block-kv-write": _block_kv_write(SDAR, 64, 704, 12),
    **{f"sdar-prefill-{s}": _prefill(SDAR, s) for s in (128, 256)},
    "sdar-grouped-matmul-256": _grouped(SDAR, 256, 12),
    "sdar-down-grouped-matmul-256": _grouped(SDAR, 256, 12, down=True),
    "sdar-gate-up-grouped-matmul-256": _gate_up(SDAR, 256, 12),
    "sdar-grouped-matmul-2048": _grouped(SDAR, 2048, 12),
    "sdar-down-grouped-matmul-2048": _grouped(SDAR, 2048, 12, down=True),
    # the Keye cell: a decode step's walk of 8 rows x 272 pages under the
    # selection as a mask, a 2048-token segment against the row's 17,408 columns,
    # its selection in one call (and the scores in tiles that call is held to)
    # and its walk under the packed selection, and the check's chain from
    # offset 0: at its width, 2,432, and at 4,608; the selection over a table
    # twice the cell's, where a query tile is 64 rows
    "keye8x272-selected-decode": _selected_decode(KEYE, 8, 272, 2176, 12),
    "keye-index-scores-2048": _index_scores(KEYE, 2048, 17408),
    "keye-segment-select-2048": _segment_select(KEYE, 2048, 17408),
    "keye-sparse-segment-2048": _sparse_segment(KEYE, 2048, 17408),
    "keye-index-scores-4608": _index_scores(KEYE, 4608, 4608),
    "keye-segment-select-4608": _segment_select(KEYE, 4608, 4608),
    "keye-sparse-segment-4608": _sparse_segment(KEYE, 4608, 4608),
    "keye-index-scores-2432": _index_scores(KEYE, 2432, 2432),
    "keye-segment-select-2432": _segment_select(KEYE, 2432, 2432),
    "keye-sparse-segment-2432": _sparse_segment(KEYE, 2432, 2432),
    "keye34816-segment-select-2048": _segment_select(KEYE, 2048, 34816),
    # the GLM-5 cell: 16 slots x 272 pages of a 4,352-page pool of latents, a
    # 2,048-token segment against 17,408 columns at 64 expanded heads of 256
    # (one query head a key head: `_vmem_block_q` keeps 512-row query blocks)
    # with an indexer of 32 heads x 128, and the check's width (6,528 = 51 x
    # 128 from offset 0)
    "glm16x272-latent-decode": _latent_decode(GLM, 16, 272, 4352, 7),
    "glm-segment-select-2048": _segment_select(GLM, 2048, 17408),
    "glm-sparse-segment-2048": _sparse_segment(GLM, 2048, 17408),
    "glm-segment-select-6528": _segment_select(GLM, 6528, 6528),
    "glm-sparse-segment-6528": _sparse_segment(GLM, 6528, 6528),
    # a segment's expansion (PR 49): 1 row, 17,408 columns of 640-lane latents
    # into 64 heads' keys and values, two key blocks a step; the check's table
    # at its key block of 128
    "glm1x2048-latent-expand": _latent_expand(GLM, 2048, 17408),
    "glm1x6528-latent-expand": _latent_expand(GLM, 6528, 6528),
    # the Kimi-K2.5 cell: 16 slots x 272 pages of a 4,352-page pool of latents
    # walked with NO mask operand, a 2,048-token segment against 17,408
    # columns at 64 expanded heads whose keys are 192 wide and whose values
    # 128 (no lane of a value padded to the key's width), the expansion to
    # those two widths, and the admit group's and the check's causal prefill
    # (2,048; 6,528 = 51 x 128 from offset 0)
    "kimi16x272-latent-decode": _latent_decode(KIMI, 16, 272, 4352, 7),
    "kimi-segment-2048": _segment(KIMI, 2048, 17408, 0),
    "kimi1x2048-latent-expand": _latent_expand(KIMI, 2048, 17408),
    "kimi1x6528-latent-expand": _latent_expand(KIMI, 6528, 6528),
    **{f"kimi-prefill-{s}": _prefill(KIMI, s) for s in (2048, 6528)},
    # the shapes the compiler refused before _vmem_block_q counted the K/V
    # buffers and the score tiles (gemma-2b: G=8, D=256)
    **{f"gemma-prefill-{s}": _prefill(GEMMA, s) for s in (512, 1024, 2048)},
    # every bucket width of the benchmark's cells that takes the kernel
    # (128-multiples; the 64 bucket runs jnp)
    **{f"llama-prefill-{s}": _prefill(LLAMA, s) for s in (128, 256, 512, 1024, 2048)},
    "gemma-paged-decode": _paged(GEMMA, False),
    "gemma-paged-decode-int8": _paged(GEMMA, True),
    "llama-paged-decode": _paged(LLAMA, False),
    "llama-paged-decode-int8": _paged(LLAMA, True),
    **{f"{cell}-paged-decode": _paged(LLAMA, False, **sizes) for cell, sizes in CELLS.items()},
    **{f"{cell}-paged-decode-int8": _paged(LLAMA, True, **sizes) for cell, sizes in CELLS.items()},
    # the write of a decode step's new rows into a bf16 pool
    "gemma-paged-kv-write": _kv_write(GEMMA, BATCH, PAGES, POOL_LAYERS),
    "llama-paged-kv-write": _kv_write(LLAMA, BATCH, PAGES, POOL_LAYERS),
    **{f"{cell}-paged-kv-write": _kv_write(LLAMA, **sizes) for cell, sizes in CELLS.items()},
    # an admission group's insert at the cells' pools: chat's narrowest and
    # widest lone prompt, docs' widest group, Olmo's at 30 kv heads
    "chat1x64-paged-insert-pages": _insert_pages(LLAMA, 1, 64, 512, 32, 20),
    "chat1x1024-paged-insert-pages": _insert_pages(LLAMA, 1, 1024, 512, 32, 20),
    "docs4x2048-paged-insert-pages": _insert_pages(LLAMA, 4, 2048, 528, 32, 33),
    "olmodrain8x256-paged-insert-pages": _insert_pages(OLMO, 8, 256, 480, 8, 10),
    # a 2,048-token segment's write of one layer by page (PR 48), at the three
    # segment cells' pools: Keye's K, V and indexer's key in one call,
    # command-a-plus's full group and its window group (8 kv heads), GLM's
    # latent (one head of 640 lanes) with its indexer's key
    "keye1x2048-paged-insert-layer-pages": _insert_layer_pages(
        [(4, 128), (4, 128), (0, 128)], 2048, 2176, 12
    ),
    "cmdaplus1x2048-paged-insert-layer-pages": _insert_layer_pages([(8, 128)] * 2, 2048, 3136, 2),
    "cmdapluswin1x2048-paged-insert-layer-pages": _insert_layer_pages(
        [(8, 128)] * 2, 2048, 1552, 6
    ),
    "glm1x2048-paged-insert-layer-pages": _insert_layer_pages([(1, 640), (0, 128)], 2048, 4352, 7),
    # the Olmo-Hybrid cell (40 slots x 10 pages, 400 pages, 8 full layers of
    # 30 kv heads in groups of ONE; 24 linear layers of 30 x 96 x 192)
    "olmodrain40x10-paged-decode": _paged(OLMO, False, batch=40, table=10, pages=400, layers=8),
    "olmodrain40x10-paged-kv-write": _kv_write(OLMO, batch=40, pages=400, layers=8),
    **{f"olmo-prefill-{s}": _prefill(OLMO, s) for s in (128, 256, 384)},
    "olmodrain40-gated-delta-update": _delta_update(OLMO, 40, 24),
    # the LFM2 cell (256 slots x 10 pages, 2,560 pages, 4 attention layers of
    # 8 kv heads of 64, two to a lane row): the decode step's read and write,
    # the admit group's prefill at EVERY bucket (64 too) and its insert, the
    # grouped product of a step (256 rows x top-4 over 64 experts) and of an
    # admission group (8 x 256 tokens)
    "lfm2drain256x10-paged-decode": _paged(LFM2, False, batch=256, table=10, pages=2560, layers=4),
    "lfm2drain256x10-paged-kv-write": _kv_write(LFM2, batch=256, pages=2560, layers=4),
    **{f"lfm2-prefill-{s}": _prefill(LFM2, s) for s in (64, 128, 256, 384)},
    "lfm2drain8x256-paged-insert-pages": _insert_pages(LFM2, 8, 256, 2560, 4, 10),
    "lfm2-grouped-matmul-256": _grouped(LFM2, 256, 14),
    "lfm2-down-grouped-matmul-256": _grouped(LFM2, 256, 14, down=True),
    "lfm2-grouped-matmul-2048": _grouped(LFM2, 2048, 14),
}


def _placed(args, shardings):
    """The case's shapes, placed by ``shardings``: one sharding for every
    leaf, or a tree of them matching ``args``."""
    if not isinstance(shardings, tuple):
        shardings = jax.tree.map(lambda _: shardings, args)
    return jax.tree.map(
        lambda x, sh: SDS(x.shape, x.dtype, sharding=sh), args, shardings
    )


def _kernel_of(case: str) -> str:
    """The public function a case calls, which is its pallas_call's name=."""
    kind = re.sub(r"-\d+$", "", case.split("-", 1)[1])  # drop the width
    return {
        "prefill": "flash_prefill_attention",
        "paged-decode": "ragged_paged_decode_attention",
        "paged-decode-int8": "ragged_paged_decode_attention_int8",
        "paged-kv-write": "paged_kv_write",
        "paged-block": "ragged_paged_block_attention",
        "block-kv-write": "paged_kv_write",
        "paged-insert-pages": "paged_insert_pages",
        "paged-insert-layer-pages": "paged_insert_pages",
        "gated-delta-update": "gated_delta_update",
        "windowed-decode": "ragged_paged_decode_attention",
        "selected-decode": "ragged_paged_selected_attention",
        "latent-decode": "ragged_paged_latent_attention",
        "segment": "flash_segment_attention",
        "window-segment": "flash_segment_attention",
        "grouped-matmul": "moe_grouped_matmul",
        "down-grouped-matmul": "moe_grouped_matmul",
        "gate-up-grouped-matmul": "moe_grouped_matmul",
        "index-scores": "index_scores",
        "segment-select": "segment_select",
        "sparse-segment": "sparse_segment_attention",
        "latent-expand": "latent_expand_blocks",
    }[kind]


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    fn, args = CASES[case]
    one_chip = SingleDeviceSharding(v5e[0])
    text = jax.jit(fn).lower(*_placed(args, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's instruction is named after it, which is how a profile's
    # device events are told apart (`_int8` is not a suffix of the match)
    assert re.search(rf"%{_kernel_of(case)}(\.\d+)? = ", text), _kernel_of(case)


def test_chunked_delta_rule_compiles_for_v5e_under_its_scope(v5e):
    """`gated_delta_chunk_prefill` is matrix products in XLA, no Pallas
    kernel: it compiles at the cell's widest admit group (8 x 256) and its
    operations carry the scope a profile finds them by."""
    from langstream_tpu.ops import gated_delta as gd

    b, s, h, dk, dv = 8, 256, OLMO.linear_n_heads, OLMO.linear_key_head_dim, OLMO.linear_value_head_dim
    f32 = lambda *shape: SDS(shape, jnp.float32)  # noqa: E731
    args = (f32(b, s, h, dk), f32(b, s, h, dk), f32(b, s, h, dv), f32(b, s, h), f32(b, s, h),
            f32(b, dk, h * dv))
    compiled = jax.jit(gd.gated_delta_chunk_prefill).lower(
        *_placed(args, SingleDeviceSharding(v5e[0]))
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text and "gated_delta_chunk_prefill/" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_model_sharded_paged_decode_compiles_for_four_chips(v5e):
    """The tensor-parallel layout of parallel/sharding.py — q heads and the
    page pool's kv heads on "model" — lowers only because the kernel
    shard_maps itself over config.kernel_mesh; without that Mosaic refuses
    ("cannot be automatically partitioned")."""
    import numpy as np

    mesh = Mesh(np.array(v5e).reshape(1, 1, 1, 4), AXIS_ORDER)
    config = dataclasses.replace(LLAMA, kernel_mesh=mesh)
    assert A.paged_pallas_ok(dataclasses.replace(config, attention_impl="pallas"), PAGE)
    fn, args = _paged(config, int8=True)

    def on(*spec):
        return NamedSharding(mesh, P(*spec))

    pool = page_pool_specs(config.n_kv_heads, mesh)
    kv = {"q": on(*pool), "s": on(*pool[:-1])}
    shardings = (on(None, "model", None), kv, kv, on(), on(), on())
    compiled = jax.jit(fn).lower(*_placed(args, shardings)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%ragged_paged_decode_attention_int8" in text
    # a shard's page is 2 of the 8 kv heads, 32 KB of int8: a step takes a group
    walk = A.attention_paths()[f"paged-walk[ragged_paged_decode_attention_int8,ps={PAGE}]"]
    assert walk == "pages/step 8, slots 16"
    # independent per kv head: the shard_map body needs no collective
    assert "all-reduce" not in text and "all-gather" not in text
    # each chip holds a quarter of the pool (k and v: int8 values + scales)
    pool_bytes = (
        2 * POOL_LAYERS * PAGES * config.n_kv_heads * PAGE
        * (config.resolved_head_dim + 4)
    )
    assert compiled.memory_analysis().argument_size_in_bytes < 1.1 * pool_bytes / 4


# ---------------------------------------------------------------------------
# The paged decode-side PROGRAMS: the layer scan reads and writes the pool
# where it lies. A per-layer entry [P, Hkv, ps, D] sliced out of the scan's
# carry and written back was two real copies a layer on the chip (39.7% of a
# Mistral-7B decode step, PERF.md section 6, PR 25); only the compiled
# program says whether one is there.
# ---------------------------------------------------------------------------

# llama's attention (8 kv heads of 128: the kernel's tiling, a 4-way head
# split), narrow and shallow elsewhere so a whole program compiles in seconds
STEP_CFG = dataclasses.replace(
    LLAMA, name="llama-attn-narrow", vocab_size=2048, d_model=1024, d_ff=2048,
    n_layers=3, n_heads=8, n_kv_heads=8, head_dim=128,
)
STEP_PAGES, STEP_TABLE, STEP_BATCH = 48, 4, 16


def _step_program(program: str, config, page):
    """(jitted engine program, its arguments' shapes) at STEP_* sizes."""
    from langstream_tpu.models.transformer import init_params, make_page_pool
    from langstream_tpu.serving import engine as E

    b = STEP_BATCH
    params = jax.eval_shape(lambda k: init_params(config, k), SDS((2,), jnp.uint32))
    pool = jax.eval_shape(lambda: make_page_pool(config, STEP_PAGES, page))
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    key = SDS((2,), jnp.uint32)
    if program == "_paged_decode_chunk":
        args = (params, i32(b), i32(b), pool, i32(b, STEP_TABLE), key,
                f32(b), i32(b), f32(b))
        static = (4, config, page)  # steps
    elif program == "_paged_verify_chunk":
        args = (params, i32(b), i32(b), pool, i32(b, STEP_TABLE), key,
                f32(b), i32(b), f32(b), i32(b, 3))
        static = (config, page)
    else:
        args = (params, i32(1, 128), i32(1), i32(1), pool, i32(1, STEP_TABLE), key,
                f32(1), i32(1), f32(1))
        static = (config, page)
    return getattr(E, program), args, static


def _pool_shapes(config, page):
    """(per-layer entry shapes, whole-pool shapes) as HLO prints them, for
    every leaf of the pool: values and, in int8, scales."""
    hkv, d = config.n_kv_heads, config.resolved_head_dim
    entry = [f"[{STEP_PAGES},{hkv},{page},{d}]"]
    if config.kv_cache_dtype == "int8":
        entry.append(f"[{STEP_PAGES},{hkv},{page}]")
    return entry, [f"[{config.n_layers},{e[1:]}" for e in entry]


def _compile_as_on_chip(monkeypatch, fn, args, static):
    """Lower with the gates a chip process passes (`paged_pallas_ok`'s
    "auto" and the kernels' `interpret=` ask `jax.default_backend()`, which
    here still says cpu: the test steers it, the program has no knob)."""
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        lowered = fn.lower(*args, *static)
    return lowered.compile()


STEP_PROGRAMS = ("_paged_decode_chunk", "_paged_verify_chunk", "_paged_segment_and_sample")


def _assert_decode_write(text: str, pool_shapes: list, bf16_pool: bool) -> None:
    """How a decode step's new K/V rows reach the pool: into a bf16 pool by
    the `paged_kv_write` kernel (a copy per live row) and by no scatter;
    the int8 pool keeps the scatter of its values and scales."""
    scatters = [
        shape for shape in pool_shapes
        if re.search(rf"= \w+{re.escape(shape)}\S* scatter\(", text)
    ]
    if bf16_pool:
        assert re.search(r"%paged_kv_write(\.\d+)? = ", text)
        assert not scatters
    else:
        assert "%paged_kv_write" not in text
        assert scatters == pool_shapes


@pytest.mark.parametrize("kv", ["model", "int8"])
@pytest.mark.parametrize("program", STEP_PROGRAMS)
def test_paged_program_holds_no_per_layer_pool_entry(v5e, monkeypatch, program, kv):
    config = dataclasses.replace(STEP_CFG, kv_cache_dtype=kv)
    fn, args, static = _step_program(program, config, PAGE)
    one_chip = SingleDeviceSharding(v5e[0])
    text = _compile_as_on_chip(monkeypatch, fn, _placed(args, one_chip), static).as_text()
    entry_shapes, pool_shapes = _pool_shapes(config, PAGE)
    for line in text.splitlines():
        bare = line
        for shape in pool_shapes:
            bare = bare.replace(shape, "")
        assert not any(e in bare for e in entry_shapes), line.strip()[:300]
    # no copy of the pool, values or (int8) scales: the decode kernel
    # fetches the values' pages from HBM itself and takes the scales
    # gathered through the table (before PR 28 the scale leaf was its
    # operand, and the chip, which keeps f32[L, P, Hkv, ps < 128]
    # pages-minor, relaid all of it row-major for every layer's call)
    for shape in pool_shapes:
        assert not re.search(rf"= \w+{re.escape(shape)}\S* copy\(", text)
    if program == "_paged_decode_chunk":
        kernel = "ragged_paged_decode_attention" + ("_int8" if kv == "int8" else "")
        assert re.search(rf"%{kernel}(\.\d+)? = ", text)
        t = STEP_TABLE * PAGE
        assert A.attention_paths()[f"paged-decode[s=1,t={t}]"] == kernel
        _assert_decode_write(text, pool_shapes, bf16_pool=kv == "model")
    elif program == "_paged_segment_and_sample" and kv == "model":
        # a causal segment of whole pages into a bf16 pool: its rows reach the
        # pool by whole pages (PR 48), the one kernel of the program (the
        # dense model's read stays jnp); the scatter of the same leaves is
        # the branch of a segment that starts inside a page
        calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
        assert calls and all(
            re.match(r"\s*(ROOT )?%paged_insert_pages(\.\d+)? = ", line) for line in calls
        ), calls[0][:200]
        assert A.attention_paths()["paged-segment-write[s=128]"] == "paged_insert_pages"
    else:
        assert "tpu_custom_call" not in text  # verify, the int8 pool's segments: jnp
        if program == "_paged_segment_and_sample":
            assert A.attention_paths()["paged-segment-write[s=128]"] == "scatter"


# olmo-hybrid's linear layers (heads of 96 x 192, the folded state whole
# lanes: 8 x 192 = 12 x 128) and its full layers' attention, narrow elsewhere
HYBRID_CFG = dataclasses.replace(
    OLMO, name="olmo-hybrid-narrow", vocab_size=2048, d_model=1024, d_ff=2048, n_layers=8,
    n_heads=8, n_kv_heads=8, head_dim=128, linear_n_heads=8,
)


def test_recurrent_decode_chunk_holds_one_copy_of_the_state(v5e, monkeypatch):
    """The recurrent state is donated and carried through the decode
    chunk's step scan like the pool: the compiled chunk holds ONE copy of
    it (the argument, aliased to the result), forms no per-layer entry of it
    and updates it by the kernel where it lies (a second 2.1 GB copy would
    not fit beside the Olmo-Hybrid cell's weights and pool)."""
    config = HYBRID_CFG
    from langstream_tpu.models.transformer import init_params, make_page_pool
    from langstream_tpu.serving import engine as E

    b = STEP_BATCH
    params = jax.eval_shape(lambda k: init_params(config, k), SDS((2,), jnp.uint32))
    pool = jax.eval_shape(lambda: make_page_pool(config, STEP_PAGES, PAGE, state_rows=b))
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    args = (params, i32(b), i32(b), pool, i32(b, STEP_TABLE), SDS((2,), jnp.uint32),
            f32(b), i32(b), f32(b))
    one_chip = SingleDeviceSharding(v5e[0])
    compiled = _compile_as_on_chip(
        monkeypatch, E._paged_decode_chunk, _placed(args, one_chip), (4, config, PAGE)
    )
    text = compiled.as_text()
    layers, dk, hv = config.n_layers_of("linear_attention"), config.linear_key_head_dim, config.linear_value_dim
    state, entry = f"f32[{layers},{b},{dk},{hv}]", f"f32[{b},{dk},{hv}]"
    assert state in text and re.search(r"%gated_delta_update(\.\d+)? = ", text)
    assert A.attention_paths()["linear-decode[s=1,t=0]"] == "gated_delta_update"
    assert not re.search(rf"= {re.escape(state)}\S* copy\(", text)
    assert all(entry not in line.replace(state, "") for line in text.splitlines())
    # the convolution's tail beside it: flat rows, no copy either
    conv = f"[{layers},{b},{(config.linear_conv_kernel - 1) * config.linear_conv_dim}]"
    assert not re.search(rf"= \w+{re.escape(conv)}\S* copy\(", text)
    memory = compiled.memory_analysis()
    state_bytes = layers * b * dk * hv * 4
    assert memory.alias_size_in_bytes >= state_bytes  # updated in place
    assert memory.temp_size_in_bytes < state_bytes  # and no second copy among the temporaries


@pytest.mark.parametrize("kv", ["model", "int8"])
def test_model_sharded_paged_decode_chunk_holds_no_pool_entry(v5e, monkeypatch, kv):
    """The same on the four-chip `model`-sharded mesh: the kernels'
    shard_map splits the pool's kv heads where they now lie (axis 2), the
    write's as the read's: each chip writes its own heads' slab."""
    import numpy as np

    from langstream_tpu.parallel.sharding import _kv_entry_specs, param_specs

    mesh = Mesh(np.array(v5e).reshape(1, 1, 1, 4), AXIS_ORDER)
    config = dataclasses.replace(STEP_CFG, kv_cache_dtype=kv, kernel_mesh=mesh)
    fn, args, static = _step_program("_paged_decode_chunk", config, PAGE)
    named = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    entry = _kv_entry_specs(page_pool_specs(config.n_kv_heads, mesh), kv == "int8")
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    shardings = (
        jax.tree.map(named, param_specs(config), is_leaf=is_spec),
        named(P()), named(P()),
        jax.tree.map(named, {"k": entry, "v": entry}, is_leaf=is_spec),
    ) + (named(P()),) * 5
    compiled = _compile_as_on_chip(monkeypatch, fn, _placed(args, shardings), static)
    text = compiled.as_text()
    kernel = "ragged_paged_decode_attention" + ("_int8" if kv == "int8" else "")
    assert re.search(rf"%{kernel}(\.\d+)? = ", text)
    hkv, d = config.n_kv_heads // 4, config.resolved_head_dim
    local_entry = f"[{STEP_PAGES},{hkv},{PAGE},{d}]"
    local_pool = f"[{config.n_layers},{STEP_PAGES},{hkv},{PAGE},{d}]"
    assert local_pool in text  # each chip holds a quarter of the heads
    assert all(local_entry not in l.replace(local_pool, "") for l in text.splitlines())
    assert not re.search(rf"= \w+{re.escape(local_pool)}\S* copy\(", text)
    local_scales = f"[{config.n_layers},{STEP_PAGES},{hkv},{PAGE}]"
    _assert_decode_write(
        text, [local_pool] + [local_scales] * (kv == "int8"), bf16_pool=kv == "model"
    )


def test_mesh_that_does_not_divide_kv_heads_keeps_the_jnp_path():
    """gemma-2b has ONE kv head: under model=4 the cache is replicated
    (serving_cache_specs) and the gate must say so — an explicit jnp
    route, reported by attention_paths(), never a kernel that cannot
    lower."""
    import numpy as np

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 1, 1, 4), AXIS_ORDER)
    forced = dataclasses.replace(GEMMA, attention_impl="pallas", kernel_mesh=mesh)
    assert not A.pallas_ok(forced, 512)
    assert not A.paged_pallas_ok(forced, PAGE)
    assert A.pallas_ok(dataclasses.replace(forced, kernel_mesh=None), 512)


# ---------------------------------------------------------------------------
# The admission group at the SMALLEST row count of its ladder, at the sizes
# the benchmark's cells serve (engine.admit_rungs: a lone prompt rides a group
# of one row). The whole 32-layer program with int8 weights, the cell's pool
# donated: the compiler refuses what does not fit the chip beside them.
# ---------------------------------------------------------------------------

V5E_HBM_BYTES = int(15.75 * 2**30)  # what the chip's compiler grants a program
# Mistral-7B has llama-3-8b's block at a vocabulary of 32768
DENSE_7B = dataclasses.replace(LLAMA, name="dense-7b", vocab_size=32768, rope_theta=1e6)
ONE_ROW_GROUPS = {
    # cell: (config, slots, max-seq-len, kv-pages, the cell's widest bucket)
    "chat-1x1024": (DENSE_7B, 64, 1280, 512, 1024),
    "docs-1x2048": (DENSE_7B, 16, 2112, 528, 2048),
    "olmodrain-1x256": (OLMO, 48, 640, 480, 256),
}


@pytest.mark.parametrize("case", sorted(ONE_ROW_GROUPS))
def test_one_row_admit_group_compiles_for_v5e_beside_the_cell_s_state(v5e, monkeypatch, case):
    from langstream_tpu.models.quant import init_random_quantized_params, quantize_params
    from langstream_tpu.models.transformer import init_params, make_page_pool
    from langstream_tpu.serving import engine as E
    from langstream_tpu.serving.pagepool import table_len_for

    config, slots, seq_len, pages, width = ONE_ROW_GROUPS[case]
    key = SDS((2,), jnp.uint32)
    if config.layer_pattern:
        params = jax.eval_shape(lambda k: quantize_params(init_params(config, k), config), key)
    else:
        params = jax.eval_shape(lambda k: init_random_quantized_params(config, k), key)
    pool = jax.eval_shape(lambda: make_page_pool(config, pages, PAGE, state_rows=slots))
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    rows = E.admit_rungs(8)[0]
    args = (
        params, pool, i32(slots), i32(slots), f32(slots), i32(slots), f32(slots), key,
        i32(rows, width), f32(4, rows), i32(rows), i32(rows, table_len_for(seq_len, PAGE)),
    )
    compiled = _compile_as_on_chip(
        monkeypatch, E._make_paged_admit_group(),
        _placed(args, SingleDeviceSharding(v5e[0])), (config, PAGE),
    )
    assert f"prefill[s={width},t={width}]" in A.attention_paths()
    # the insert is page copies where the pool lies (`paged_insert_pages`):
    # no scatter has the compiler relay a whole leaf of the pool for its
    # window and back (four copies of 2.15 GB in the chat cell: 26 of a
    # group's 67.9 ms on the chip, PERF.md section 6, PR 37), and nothing
    # relays the prefill's local cache in front of the kernel
    text = compiled.as_text()
    assert re.search(r"%paged_insert_pages(\.\d+)? = ", text)
    kv = pool["k"].shape
    local = (kv[0], rows) + kv[2:3] + (width,) + kv[4:]
    for shape in (kv, local):
        dims = re.escape("[" + ",".join(map(str, shape)) + "]")
        assert not re.search(rf"= \w+{dims}\S* (copy|scatter)\(", text), shape
    memory = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool))
    assert memory.alias_size_in_bytes >= pool_bytes  # the pool and the state, updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert held <= V5E_HBM_BYTES


# The LFM2 cell's two programs whole, at its sizes: 256 slots x 10 pages of 64
# (2,560 pages of [4, 64, 128] bf16 in 4 layers), 12 conv layers' tails a slot,
# 8.85 GB of int8 weights. Both must fit the chip beside the pool, take the
# kernels at two heads a lane row and move no whole leaf of pool or tails.
@pytest.mark.parametrize("program", ["_paged_decode_chunk", "admit-1x64", "admit-8x256"])
def test_lfm2_programs_compile_for_v5e_beside_the_cell_s_state(v5e, monkeypatch, program):
    from langstream_tpu.models.quant import quantize_params
    from langstream_tpu.models.transformer import init_params, make_page_pool
    from langstream_tpu.serving import engine as E
    from langstream_tpu.serving.pagepool import table_len_for

    config, slots, seq_len, pages = LFM2, 256, 640, 2560
    key = SDS((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: quantize_params(init_params(config, k), config), key)
    assert params["dense_layers"]["conv"]["w_gate"]["q"].shape == (2, 2048, 11776)
    assert params["layers"]["conv"]["w_gate"]["q"].shape == (10, 64, 2048, 1536)
    assert params["layers"]["full_attention"]["w_gate"]["q"].shape == (4, 64, 2048, 1536)
    pool = jax.eval_shape(lambda: make_page_pool(config, pages, PAGE, state_rows=slots))
    assert {k: v.shape for k, v in pool.items() if k != "rec"} == {
        "k": (4, pages, 4, PAGE, 128), "v": (4, pages, 4, PAGE, 128)}
    assert {k: v.shape for k, v in pool["rec"].items()} == {"conv": (12, slots, 2 * 2048)}
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    table = table_len_for(seq_len, PAGE)
    one_chip = SingleDeviceSharding(v5e[0])
    A._PATHS.clear()
    if program == "_paged_decode_chunk":
        args = (params, i32(slots), i32(slots), pool, i32(slots, table), key, f32(slots),
                i32(slots), f32(slots))
        compiled = _compile_as_on_chip(
            monkeypatch, E._paged_decode_chunk, _placed(args, one_chip), (4, config, PAGE)
        )
        paths = A.attention_paths()
        assert paths[f"paged-decode[s=1,t={table * PAGE}]"] == "ragged_paged_decode_attention"
        assert paths["paged-decode-write[s=1]"] == "paged_kv_write"
        assert paths["short-conv[s=1,t=0]"] == "short_conv"
        assert paths["moe-dispatch[t=256,k=4,held=64/64]"].startswith("one pass")
        wanted = ("ragged_paged_decode_attention", "paged_kv_write", "moe_grouped_matmul")
    else:
        rows, width = map(int, program.split("-")[1].split("x"))
        args = (
            params, pool, i32(slots), i32(slots), f32(slots), i32(slots), f32(slots), key,
            i32(rows, width), f32(4, rows), i32(rows), i32(rows, table),
        )
        compiled = _compile_as_on_chip(
            monkeypatch, E._make_paged_admit_group(), _placed(args, one_chip), (config, PAGE)
        )
        paths = A.attention_paths()
        assert paths[f"prefill[s={width},t={width}]"] == "flash_prefill_attention"
        assert paths[f"paged-insert[w={width}]"] == "paged_insert_pages"
        wanted = ("flash_prefill_attention", "paged_insert_pages", "moe_grouped_matmul")
    text = compiled.as_text()
    for kernel in wanted:
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    # no whole leaf of the pool is copied, relaid or scattered; the tails (25 MB,
    # which the compiler keeps rows-minor inside the step loop) are relaid once
    # into a chunk and once out of it, never a step or a layer
    dims = lambda leaf: re.escape("[" + ",".join(map(str, leaf.shape)) + "]")  # noqa: E731
    assert not re.search(rf"= \w+{dims(pool['k'])}\S* (copy|scatter|transpose)\(", text)
    # (an admit group scatters its rows' tails into the leaf where it lies)
    tails = re.findall(rf"= \w+{dims(pool['rec']['conv'])}\S* (?:copy|transpose)\(", text)
    assert len(tails) <= 2, tails
    # nor a layer's experts out of their stack
    experts = "[64,2048,1536]"
    assert not re.search(rf"= \w+{re.escape(experts)}\S* (copy|dynamic-slice)\(", text)
    memory = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool))
    assert memory.alias_size_in_bytes >= pool_bytes  # pool and tails, updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    print(program, "arguments", memory.argument_size_in_bytes, "temporaries",
          memory.temp_size_in_bytes, "held", held)
    assert held <= V5E_HBM_BYTES


@pytest.mark.parametrize("program", ["_paged_block_chunk", "_block_admit_group"])
def test_block_programs_compile_for_v5e_beside_the_cell_s_state(v5e, monkeypatch, program):
    """The SDAR cell's two device programs whole, at its sizes (64 slots, 704
    pages, 16 passes a chunk; a group of 8 x 256), int8 weights and the pool
    donated: the kernels are in, nothing copies or scatters a leaf of the
    pool or slices a layer's experts out of their stack, and the program fits
    the chip beside its state."""
    from langstream_tpu.models.quant import init_random_quantized_params
    from langstream_tpu.models.transformer import make_page_pool
    from langstream_tpu.serving import engine as E

    slots, pages, table, passes = 64, 704, 11, 16
    key = SDS((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_random_quantized_params(SDAR, k), key)
    pool = jax.eval_shape(lambda: make_page_pool(SDAR, pages, PAGE))
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    b = SDAR.block_length
    block = {"tokens": i32(slots, b), "open": SDS((slots, b), jnp.bool_), "step": i32(slots)}
    if program == "_paged_block_chunk":
        args = (params, block, i32(slots), pool, i32(slots, table), key,
                f32(slots), i32(slots), f32(slots))
        static, kernels = (passes, SDAR, PAGE), ("ragged_paged_block_attention", "paged_kv_write")
        path = f"paged-block[s={b},t={table * PAGE}]"
    else:
        rows, width = 8, 256
        args = (params, pool, block, i32(slots), f32(slots), i32(slots), f32(slots),
                i32(rows, width), f32(5, rows), i32(rows, b), i32(rows), i32(rows, table))
        static, kernels = (SDAR, PAGE), ("flash_prefill_attention", "paged_insert_pages")
        path = f"prefill[s={width},t={width}]"
    compiled = _compile_as_on_chip(
        monkeypatch, getattr(E, program), _placed(args, SingleDeviceSharding(v5e[0])), static
    )
    text = compiled.as_text()
    assert A.attention_paths()[path] == kernels[0]
    for kernel in kernels + ("moe_grouped_matmul",):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    experts = [SDAR.n_experts, SDAR.d_model, SDAR.expert_d_ff]
    for shape in (list(pool["k"].shape), experts, experts[:1] + experts[:0:-1]):
        dims = re.escape("[" + ",".join(map(str, shape)) + "]")
        assert not re.search(rf"= \w+{dims}\S* (copy|scatter|dynamic-slice)\(", text), shape
    memory = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool))
    assert memory.alias_size_in_bytes >= pool_bytes  # the pool, updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert held <= V5E_HBM_BYTES



@pytest.mark.parametrize("program", ["_paged_decode_chunk", "_paged_segment_and_sample"])
def test_sparse_programs_compile_for_v5e_beside_the_cell_s_state(v5e, monkeypatch, program):
    """The Keye cell's two device programs whole, at its sizes (8 slots x 272
    pages of a 2,176-page pool with the indexer's keys as a third leaf; a
    decode chunk, and a 2,048-token segment against 17,408 columns), int8
    weights and the pool donated. The decode step's read is the paged decode
    kernel under the selection as a mask: it holds no operand of a row's whole
    table of K or V and no gather of index_topk rows a row; the segment ranks
    in one call (`segment_select`): it never forms scores of [S, heads, T],
    writes no [S, T] of float32 scores or of uint32 keys and loops over none;
    and each fits the chip beside its state."""
    from langstream_tpu.models.quant import init_random_quantized_params
    from langstream_tpu.models.transformer import make_page_pool
    from langstream_tpu.serving import engine as E

    slots, pages, table, seg = 8, 2176, 272, 2048
    t = table * PAGE
    key = SDS((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_random_quantized_params(KEYE, k), key)
    pool = jax.eval_shape(lambda: make_page_pool(KEYE, pages, PAGE))
    assert pool["ik"].shape == (12, pages, PAGE, 128)  # 64 kept at a whole lane row
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    if program == "_paged_decode_chunk":
        args = (params, i32(slots), i32(slots), pool, i32(slots, table), key,
                f32(slots), i32(slots), f32(slots))
        static = (8, KEYE, PAGE)
        kernels = ("ragged_paged_selected_attention", "paged_kv_write", "moe_grouped_matmul")
        path = f"paged-decode-selected[s=1,t={t}]"
    else:
        args = (params, i32(1, seg), i32(1), i32(1), pool, i32(1, table), key,
                f32(1), i32(1), f32(1))
        static = (KEYE, PAGE)
        kernels = (
            "flash_segment_attention", "sparse_segment_attention", "segment_select",
            "moe_grouped_matmul", "paged_insert_pages",
        )
        path = f"paged-segment-sparse[s={seg},t={t}]"
    compiled = _compile_as_on_chip(
        monkeypatch, getattr(E, program), _placed(args, SingleDeviceSharding(v5e[0])), static
    )
    text = compiled.as_text()
    assert path in A.attention_paths()
    for kernel in kernels:
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    h, hkv, d = KEYE.n_heads, KEYE.n_kv_heads, KEYE.resolved_head_dim
    if program == "_paged_decode_chunk":
        # K and V of a row's whole table, in either order of heads and columns
        for shape in ([slots, hkv, t, d], [slots, t, hkv, d], [slots, table, hkv, PAGE, d]):
            assert "[" + ",".join(map(str, shape)) + "]" not in text, shape
        assert "[" + ",".join(map(str, [slots, KEYE.index_topk, hkv, d])) + "]" not in text
        assert not re.search(r"%ragged_paged_decode_attention(\.\d+)? = ", text)
        # what was traced, and the harness's key with the harness's string
        # (benchmark/families/keye_vl2.py `expected_kernels`), which guarded
        # what the lines above now guard
        paths = A.attention_paths()
        assert paths[path] == "ragged_paged_selected_attention"
        assert paths[f"paged-decode-sparse[s=1,t={t}]"] == (
            "ragged_paged_decode_attention to index_topk, xla top_k + gather past it"
        )
    else:
        for heads in (h, hkv, KEYE.index_n_heads):
            for shape in ([1, seg, heads, t], [1, heads, seg, t], [seg, heads, t], [heads, seg, t]):
                assert "[" + ",".join(map(str, shape)) + "]" not in text, shape
        # the ranking is the kernel's: the scores and their keys stay in VMEM
        # (the one [S, T] the program holds is the int8 selection), and XLA's
        # 32 counts of a key that size are gone with the loop that made them
        paths = A.attention_paths()
        # K, V and the indexer's key reach the pool by whole pages (PR 48)
        assert paths[f"paged-segment-write[s={seg}]"] == "paged_insert_pages"
        assert paths[f"paged-segment-select[s={seg},t={t}]"] == "segment_select"
        assert paths[f"segment-select[s={seg},t={t}]"] == "block_q 128, block_k 512, to the diagonal"
        assert not re.search(r"%index_scores(\.\d+)? = ", text)
        whole = f"[1,{seg},{t}]"
        assert f"s8{whole}" in text
        for dtype in ("f32", "u32", "s32", "pred"):
            assert dtype + whole not in text, dtype
        assert not [line for line in text.splitlines() if " while(" in line and whole in line]
    memory = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool))
    assert memory.alias_size_in_bytes >= pool_bytes  # the pool, updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert held <= V5E_HBM_BYTES
    # no leaf of the pool is relaid or copied: at a width of 64 the compiler
    # laid the indexer's keys out pages-minor and copied the whole leaf every
    # layer and step (PERF.md section 6, PR 43)
    for leaf in pool.values():
        dims = re.escape("[" + ",".join(map(str, leaf.shape)) + "]")
        assert not re.search(rf"= \w+{dims}\S* (copy|transpose)\(", text), leaf.shape


@pytest.mark.parametrize("program", ["_paged_decode_chunk", "_paged_segment_and_sample"])
def test_latent_programs_compile_for_v5e_beside_the_cell_s_state(v5e, monkeypatch, program):
    """The GLM-5 cell's two device programs whole, at its sizes (16 slots x
    272 pages of a 4,352-page pool whose leaves are the latent, 640 lanes a
    token, and the indexer's key; a decode chunk, and a 2,048-token segment
    against 17,408 columns), int8 weights and the pool donated. The decode
    step attends in the latent space: its program holds NO operand or
    temporary of a row's expanded cache (keys or values of 64 heads over a
    table, nope + v or 256 wide, in any order) and no gather of a row's
    latents; the segment expands its row's latents into keys and values of 64
    heads in one kernel that leaves them head-major (no transpose, copy or
    fill of a `bf16[1,64,17408,256]` outside it), ranks in one call and
    never forms scores of [S, heads, T]; each
    fits the chip beside its state, and no leaf of the pool is relaid."""
    from langstream_tpu.models.quant import init_random_quantized_params
    from langstream_tpu.models.transformer import make_page_pool
    from langstream_tpu.serving import engine as E

    slots, pages, table, seg = 16, 4352, 272, 2048
    t = table * PAGE
    key = SDS((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_random_quantized_params(GLM, k), key)
    assert set(params) == {"embed", "layers", "dense_layers", "final_norm", "lm_head"}
    assert params["dense_layers"]["w_gate"]["q"].shape == (1, 6144, 12288)
    assert params["layers"]["w_gate"]["q"].shape == (6, 16, 6144, 2048)
    assert params["layers"]["wkv_b"]["q"].shape == (6, 512, 64 * 448)
    pool = jax.eval_shape(lambda: make_page_pool(GLM, pages, PAGE))
    assert {k: v.shape for k, v in pool.items()} == {
        "lat": (7, pages, 1, PAGE, 640), "ik": (7, pages, PAGE, 128),
    }
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    if program == "_paged_decode_chunk":
        args = (params, i32(slots), i32(slots), pool, i32(slots, table), key,
                f32(slots), i32(slots), f32(slots))
        static = (8, GLM, PAGE)
        kernels = ("ragged_paged_latent_attention", "moe_grouped_matmul")
        path = f"paged-decode-latent[s=1,t={t}]"
    else:
        args = (params, i32(1, seg), i32(1), i32(1), pool, i32(1, table), key,
                f32(1), i32(1), f32(1))
        static = (GLM, PAGE)
        kernels = (
            "flash_segment_attention", "sparse_segment_attention", "segment_select",
            "moe_grouped_matmul", "paged_insert_pages", "latent_expand_blocks",
        )
        path = f"paged-segment-latent-sparse[s={seg},t={t}]"
    compiled = _compile_as_on_chip(
        monkeypatch, getattr(E, program), _placed(args, SingleDeviceSharding(v5e[0])), static
    )
    text = compiled.as_text()
    paths = A.attention_paths()
    assert path in paths
    for kernel in kernels:
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    h, width = GLM.n_heads, GLM.latent_key_width
    has = lambda shape: "[" + ",".join(map(str, shape)) + "]" in text  # noqa: E731
    if program == "_paged_decode_chunk":
        assert paths[path] == "ragged_paged_latent_attention"
        for d in (448, 256, 192):  # a row's expanded keys or values, either order
            for shape in ([slots, h, t, d], [slots, t, h, d], [slots, t, h * d], [h, t, d]):
                assert not has(shape), shape
        # nor its latents gathered: the kernel reads the pages where they lie
        for shape in ([slots, 1, t, width], [slots, t, width], [slots, table, 1, PAGE, width]):
            assert not has(shape), shape
        assert not re.search(r"%ragged_paged_(decode|selected)_attention(\.\d+)? = ", text)
    else:
        assert paths[path] == "sparse_segment_attention"
        # the latent and the indexer's key reach the pool by whole pages (PR 48)
        assert paths[f"paged-segment-write[s={seg}]"] == "paged_insert_pages"
        assert paths[f"paged-segment-latent-select[s={seg},t={t}]"] == "segment_select"
        assert paths[f"paged-segment-latent[s={seg},t={t}]"] == "flash_segment_attention"
        assert has([1, h, t, 256])  # the expanded keys and values of the row's columns
        # which leave their kernel head-major, as the walk reads them (PR 49):
        # nothing of that size is relaid, copied or filled outside it, and the
        # einsum's [t, h, j] is formed in no order
        assert paths[f"paged-segment-latent-expand[s={seg},t={t}]"] == "latent_expand_blocks"
        # under the scope `latent_ms_per_1k_segment_tokens.drain` finds its events by
        calls = re.findall(r"%latent_expand_blocks(?:\.\d+)? = .*", text)
        assert calls and all("/attention.latent.expand/" in call for call in calls), calls
        expanded = re.escape(f"bf16[1,{h},{t},256]")
        made = re.findall(rf"= {expanded}\S* ([\w-]+)\(", text)
        assert made and set(made) <= {"custom-call", "get-tuple-element", "parameter"}, set(made)
        for shape in ([1, t, h, 256], [1, t, h, 192], [1, t, h, 448], [t, h, 448], [1, h, t, 192]):
            assert not has(shape), shape
        for heads in (h, GLM.index_n_heads):
            for shape in ([1, seg, heads, t], [1, heads, seg, t], [seg, heads, t], [heads, seg, t]):
                assert not has(shape), shape
        whole = f"[1,{seg},{t}]"
        assert f"s8{whole}" in text
        for dtype in ("f32", "u32", "s32", "pred"):
            assert dtype + whole not in text, dtype
    memory = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool))
    assert pool_bytes == pages * PAGE * GLM.kv_bytes_per_token()
    assert memory.alias_size_in_bytes >= pool_bytes  # the pool, updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    print(program, "temp", memory.temp_size_in_bytes, "args", memory.argument_size_in_bytes)
    assert held <= V5E_HBM_BYTES
    for leaf in pool.values():
        dims = re.escape("[" + ",".join(map(str, leaf.shape)) + "]")
        assert not re.search(rf"= \w+{dims}\S* (copy|transpose)\(", text), leaf.shape


@pytest.mark.parametrize("program", ["_paged_decode_chunk", "_paged_segment_and_sample"])
def test_dense_latent_programs_compile_for_v5e_beside_the_cell_s_state(v5e, monkeypatch, program):
    """The Kimi-K2.5 cell's two device programs whole, at its sizes (16 slots
    x 272 pages of a 4,352-page pool whose ONE leaf is the latent, 640 lanes a
    token; a decode chunk, and a 2,048-token segment against 17,408 columns),
    int8 weights and the pool donated. NOTHING of a selection is in either:
    no indexer's scope, no mask's float32 copy of a row's table, none of the
    selection's kernels. The decode step attends in the latent space over
    every cached row: no operand or temporary of a row's expanded cache and no
    gather of its latents. The segment expands its row's latents to keys 192
    wide and values 128, head-major, in one kernel (nothing of 256 lanes: no
    value padded to the key's width), and reads them through the causal
    segment kernel; each fits the chip beside its state, and the pool's leaf
    is not relaid."""
    from langstream_tpu.models.quant import init_random_quantized_params
    from langstream_tpu.models.transformer import make_page_pool
    from langstream_tpu.serving import engine as E

    slots, pages, table, seg = 16, 4352, 272, 2048
    t = table * PAGE
    key = SDS((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_random_quantized_params(KIMI, k), key)
    assert set(params) == {"embed", "layers", "dense_layers", "final_norm", "lm_head"}
    assert params["dense_layers"]["w_gate"]["q"].shape == (1, 7168, 18432)
    assert params["layers"]["w_gate"]["q"].shape == (6, 12, 7168, 2048)
    assert params["layers"]["wq_b"]["q"].shape == (6, 1536, 64 * 192)
    assert params["layers"]["wkv_b"]["q"].shape == (6, 512, 64 * 256)
    assert params["layers"]["wo"]["q"].shape == (6, 64 * 128, 7168)
    assert params["layers"]["router"].shape == (6, 7168, 384)
    assert "wq_idx" not in params["layers"]
    pool = jax.eval_shape(lambda: make_page_pool(KIMI, pages, PAGE))
    assert {k: v.shape for k, v in pool.items()} == {"lat": (7, pages, 1, PAGE, 640)}
    assert KIMI.yarn_blend == (8, 20) and abs(KIMI.attn_scale - 0.144680) < 1e-6
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    if program == "_paged_decode_chunk":
        args = (params, i32(slots), i32(slots), pool, i32(slots, table), key,
                f32(slots), i32(slots), f32(slots))
        static = (8, KIMI, PAGE)
        kernels = ("ragged_paged_latent_attention", "moe_grouped_matmul")
        path = f"paged-decode-latent[s=1,t={t}]"
    else:
        args = (params, i32(1, seg), i32(1), i32(1), pool, i32(1, table), key,
                f32(1), i32(1), f32(1))
        static = (KIMI, PAGE)
        kernels = (
            "flash_segment_attention", "moe_grouped_matmul", "paged_insert_pages",
            "latent_expand_blocks",
        )
        path = f"paged-segment-latent[s={seg},t={t}]"
    compiled = _compile_as_on_chip(
        monkeypatch, getattr(E, program), _placed(args, SingleDeviceSharding(v5e[0])), static
    )
    text = compiled.as_text()
    paths = A.attention_paths()
    assert path in paths
    for kernel in kernels:
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    # nothing of a selection: its scopes, its kernels, a row's mask
    for scope in ("attention.sparse", "attention.select", "attention.index"):
        assert f"/{scope}/" not in text and f"/{scope}\"" not in text, scope
    for kernel in ("segment_select", "sparse_segment_attention", "index_scores",
                   "ragged_paged_selected_attention", "ragged_paged_decode_attention"):
        assert not re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    assert "/attention.latent.read/" in text  # where the new per-layer metrics look
    h, width = KIMI.n_heads, KIMI.latent_key_width
    has = lambda shape: "[" + ",".join(map(str, shape)) + "]" in text  # noqa: E731
    assert not has([slots, table, 1, PAGE]) and not has([slots, t])  # no mask over a row's table
    if program == "_paged_decode_chunk":
        assert paths[path] == "ragged_paged_latent_attention"
        for d in (256, 192, 128):  # a row's expanded keys or values, either order
            for shape in ([slots, h, t, d], [slots, t, h, d], [slots, t, h * d], [h, t, d]):
                assert not has(shape), shape
        # nor its latents gathered: the kernel reads the pages where they lie
        for shape in ([slots, 1, t, width], [slots, t, width], [slots, table, 1, PAGE, width]):
            assert not has(shape), shape
        calls = re.findall(r"%ragged_paged_latent_attention(?:\.\d+)? = .*", text)
        assert calls and all("/attention.latent.read/" in call for call in calls), calls[:1]
    else:
        assert paths[path] == "flash_segment_attention"
        assert paths[f"paged-segment-write[s={seg}]"] == "paged_insert_pages"
        assert paths[f"paged-segment-latent-expand[s={seg},t={t}]"] == "latent_expand_blocks"
        # the expanded keys and values of the row's columns, each at its own width
        assert has([1, h, t, 192]) and has([1, h, t, 128])
        for d in (256, 320):  # no value padded to the key's width, no [k | v] formed whole
            for shape in ([1, h, t, d], [1, t, h, d], [t, h, d]):
                assert not has(shape), shape
        for name, d in (("keys", 192), ("values", 128)):
            made = re.findall(rf"= {re.escape(f'bf16[1,{h},{t},{d}]')}\S* ([\w-]+)\(", text)
            assert made and set(made) <= {"custom-call", "get-tuple-element", "parameter"}, (
                name, set(made))
        calls = re.findall(r"%latent_expand_blocks(?:\.\d+)? = .*", text)
        assert calls and all("/attention.latent.expand/" in call for call in calls), calls[:1]
        calls = re.findall(r"%flash_segment_attention(?:\.\d+)? = .*", text)
        assert calls and all("/attention.latent.read/" in call for call in calls), calls[:1]
        for shape in ([1, seg, h, t], [1, h, seg, t], [seg, h, t], [h, seg, t]):
            assert not has(shape), shape  # the scores are never held
    memory = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool))
    assert pool_bytes == pages * PAGE * KIMI.kv_bytes_per_token() == pages * PAGE * 8960
    assert memory.alias_size_in_bytes >= pool_bytes  # the pool, updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    print(program, "temp", memory.temp_size_in_bytes, "args", memory.argument_size_in_bytes)
    assert held <= V5E_HBM_BYTES
    dims = re.escape("[" + ",".join(map(str, pool["lat"].shape)) + "]")
    assert not re.search(rf"= \w+{dims}\S* (copy|transpose)\(", text)


@pytest.mark.parametrize("program", ["_paged_decode_chunk", "_paged_segment_and_sample"])
def test_latent_kinds_programs_compile_for_v5e_beside_the_cell_s_state(v5e, monkeypatch, program):
    """The dots3 cell's two device programs whole, at its sizes (16 slots x
    272 pages of a 4,352-page full group whose leaves are the 640-lane latent
    and the indexer's key, and a window group of 16 rings of 41 pages whose
    one leaf is the 1,152-lane latent; a decode chunk, and a 2,048-token
    segment against 17,408 columns), int8 weights and the pool donated. Each
    of the four reads is a kernel: the decode step attends in the latent space
    in both kinds (one `pallas_call` name, two geometries) and holds no
    gathered or expanded cache of either; the segment's full kind expands,
    ranks and walks under the selection as GLM-5's does, its window kind
    expands a BAND of 3,072 columns and no more and walks it under the window.
    Each fits the chip beside its state, and no leaf of either group is
    relaid."""
    from langstream_tpu.models.quant import init_random_quantized_params
    from langstream_tpu.models.transformer import latent_window_band, make_page_pool
    from langstream_tpu.serving import engine as E
    from langstream_tpu.serving.pagepool import window_group_pages

    slots, pages, table, seg = 16, 4352, 272, 2048
    t = table * PAGE
    window_pages, ring = window_group_pages(DOTS3, slots, t, PAGE, seg)
    assert (window_pages, ring) == (16 * 41, 41)
    band = latent_window_band(seg, t, 513, PAGE)
    assert band == 3072
    key = SDS((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_random_quantized_params(DOTS3, k), key)
    full, window = (params["layers"][k] for k in ("full_attention", "sliding_attention"))
    assert params["dense_layers"]["full_attention"]["w_gate"]["q"].shape == (1, 5120, 13824)
    assert full["w_gate"]["q"].shape == (2, 16, 5120, 1536)
    assert window["w_gate"]["q"].shape == (6, 16, 5120, 1536)
    assert full["wkv_b"]["q"].shape == (2, 512, 128 * 256) and "wq_idx" in full
    assert window["wkv_b"]["q"].shape == (6, 1024, 64 * 320) and "wq_idx" not in window
    assert full["w_attn_gate"]["q"].shape == (2, 5120, 128)
    pool = jax.eval_shape(lambda: make_page_pool(DOTS3, pages, PAGE, window_pages=window_pages))
    assert {k: v.shape for k, v in pool.items() if k != "win"} == {
        "lat": (3, pages, 1, PAGE, 640), "ik": (3, pages, PAGE, 128),
    }
    assert {k: v.shape for k, v in pool["win"].items()} == {
        "lat": (6, window_pages, 1, PAGE, 1152),
    }
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    if program == "_paged_decode_chunk":
        args = (params, i32(slots), i32(slots), pool, i32(2, slots, table), key,
                f32(slots), i32(slots), f32(slots))
        static = (8, DOTS3, PAGE)
        kernels = ("ragged_paged_latent_attention", "moe_grouped_matmul")
    else:
        args = (params, i32(1, seg), i32(1), i32(1), pool, i32(2, 1, table), key,
                f32(1), i32(1), f32(1))
        static = (DOTS3, PAGE)
        kernels = (
            "flash_segment_attention", "sparse_segment_attention", "segment_select",
            "moe_grouped_matmul", "paged_insert_pages", "latent_expand_blocks",
        )
    compiled = _compile_as_on_chip(
        monkeypatch, getattr(E, program), _placed(args, SingleDeviceSharding(v5e[0])), static
    )
    text = compiled.as_text()
    paths = A.attention_paths()
    for kernel in kernels:
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    has = lambda shape: "[" + ",".join(map(str, shape)) + "]" in text  # noqa: E731
    if program == "_paged_decode_chunk":
        assert paths[f"paged-decode-latent[s=1,t={t}]"] == "ragged_paged_latent_attention"
        assert paths[f"paged-decode-latent-window[s=1,t={t}]"] == "ragged_paged_latent_attention"
        # both kinds' calls, each under its scope
        calls = re.findall(r"%ragged_paged_latent_attention(?:\.\d+)? = .*", text)
        assert any("/attention.latent.window/" in c for c in calls)
        assert any("/attention.sparse/" in c for c in calls)
        for width in (640, 1152):  # no row's latents gathered: the kernel reads the pages
            for shape in ([slots, 1, t, width], [slots, t, width], [slots, table, 1, PAGE, width]):
                assert not has(shape), shape
        for h, d in ((128, 192), (128, 128), (64, 256), (64, 128), (64, 320), (128, 256)):
            for shape in ([slots, h, t, d], [slots, t, h, d], [h, t, d]):
                assert not has(shape), shape
    else:
        assert paths[f"paged-segment-write[s={seg}]"] == "paged_insert_pages"
        assert paths[f"paged-segment-latent-select[s={seg},t={t}]"] == "segment_select"
        assert paths[f"paged-segment-latent-sparse[s={seg},t={t}]"] == "sparse_segment_attention"
        assert paths[f"paged-segment-latent[s={seg},t={t}]"] == "flash_segment_attention"
        assert paths[f"paged-segment-latent-window[s={seg},t={band}]"] == "flash_segment_attention"
        assert paths[f"paged-segment-latent-expand[s={seg},t={band}]"] == "latent_expand_blocks"
        # the full kind's expanded keys 192 and values 128 wide over the table,
        # the window kind's 256 and 128 over its BAND and never over the table
        assert has([1, 128, t, 192]) and has([1, 128, t, 128])
        assert has([1, 64, band, 256]) and has([1, 64, band, 128])
        assert not has([1, 64, t, 256]) and not has([1, 64, t, 128]) and not has([1, t, 1152])
        walks = re.findall(r"%flash_segment_attention(?:\.\d+)? = .*", text)
        assert any("/attention.latent.window/" in c for c in walks)
        for heads in (128, 64, DOTS3.index_n_heads):
            for shape in ([1, seg, heads, t], [1, heads, seg, t], [seg, heads, t]):
                assert not has(shape), shape
    memory = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool))
    assert pool_bytes == (
        pages * PAGE * DOTS3.kv_bytes_per_token()
        + window_pages * PAGE * DOTS3.kv_bytes_per_token(kind="sliding_attention")
    )
    assert memory.alias_size_in_bytes >= pool_bytes  # both groups, updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    print(program, "temp", memory.temp_size_in_bytes, "args", memory.argument_size_in_bytes)
    assert held <= V5E_HBM_BYTES
    for leaf in jax.tree.leaves(pool):
        dims = re.escape("[" + ",".join(map(str, leaf.shape)) + "]")
        assert not re.search(rf"= \w+{dims}\S* (copy|transpose)\(", text), leaf.shape


def test_window_segment_program_compiles_for_v5e_beside_the_cell_s_state(v5e, monkeypatch):
    """The command-a-plus cell's segment program whole, at its sizes (a
    2,048-token segment of one row against 196 pages a table, the full
    layers' group of 3,136 pages and the window layers' of 1,552, each
    through its own table), int8 weights and the pool donated: every layer's
    K and V reach their group by whole pages (`paged_insert_pages`, PR 48),
    the window and the full layers read through `flash_segment_attention`,
    no leaf of either group is copied or relaid, and the program fits the
    chip beside its state."""
    from langstream_tpu.models.quant import quantize_params
    from langstream_tpu.models.transformer import init_params, make_page_pool
    from langstream_tpu.serving import engine as E

    pages, window_pages, table, seg = 3136, 1552, 196, 2048
    key = SDS((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: quantize_params(init_params(CMDA, k), CMDA), key)
    pool = jax.eval_shape(lambda: make_page_pool(CMDA, pages, PAGE, window_pages=window_pages))
    assert pool["k"].shape == (2, pages, 8, PAGE, 128)
    assert pool["win"]["k"].shape == (6, window_pages, 8, PAGE, 128)
    i32, f32 = (lambda *s: SDS(s, jnp.int32)), (lambda *s: SDS(s, jnp.float32))
    args = (params, i32(1, seg), i32(1), i32(1), pool, i32(2, 1, table), key,
            f32(1), i32(1), f32(1))
    compiled = _compile_as_on_chip(
        monkeypatch, E._paged_segment_and_sample, _placed(args, SingleDeviceSharding(v5e[0])),
        (CMDA, PAGE),
    )
    text = compiled.as_text()
    paths = A.attention_paths()
    assert paths[f"paged-segment-write[s={seg}]"] == "paged_insert_pages"
    assert paths[f"paged-segment[s={seg},t={table * PAGE}]"] == "flash_segment_attention"
    for kernel in ("paged_insert_pages", "flash_segment_attention", "moe_grouped_matmul"):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    memory = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool))
    assert memory.alias_size_in_bytes >= pool_bytes  # both groups, updated in place
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert held <= V5E_HBM_BYTES
    for leaf in jax.tree.leaves(pool):
        dims = re.escape("[" + ",".join(map(str, leaf.shape)) + "]")
        assert not re.search(rf"= \w+{dims}\S* (copy|transpose)\(", text), leaf.shape


# ---------------------------------------------------------------------------
# The paged decode skeleton is shared: the selection is a static option of it,
# and without one nothing of it is traced. Two pins of that, both the text the
# parent gave (commit b2c5b1e, PR 43), both taken by the code below, in this
# file (its autouse fixture sets the matmul precision a chip process has):
#
# 1. the KERNELS alone, for the described v5e: the Mosaic module each shared
#    entry hands the chip's compiler, at its cell's sizes, as text without
#    debug locations (a line that moves in ops/attention.py moves none of it).
#    This is what a change to `_paged_decode_kernel` / `_paged_decode_call`
#    must hold still for the models it does not mean to touch;
# 2. the other models' decode programs whole (Mistral's block plain and over
#    an int8 pool, Mixtral's, Olmo-Hybrid's, command-a-plus's with its window
#    bound, SDAR's block pass), lowered for the CPU with the kernels in
#    interpret mode (ISSUE 44's acceptance). These six cover every line of a
#    decode chunk, so a PR that changes a model's step ON PURPOSE, or a JAX
#    bump, moves them for reasons the kernels have no part in: such a PR
#    re-takes the hashes (the failure prints the new one) and says why in
#    CHANGES.md. A PR that did not mean to change these programs does not.
# ---------------------------------------------------------------------------

# PR 52 holds FIVE of the seven and re-takes two on purpose. A loop step of the
# skeleton takes a group of the row's pages where a page is under 256 KB
# (`ops/attention._walk_shape`). At 256 KB and above the walk is the one-page
# walk and its module the parent's byte for byte: chat's, Mixtral's ("drain"),
# both of command-a-plus's page groups' and Olmo's are the hashes PR 46 took,
# which is the proof that those four cells' programs cannot move. The int8
# pool's pages (the `docs16x33` case's, 128 KB) and SDAR's (128 KB, the block pass) ride groups of
# 8 and 4: re-taken, as PR 52 left them, with the selected and the latent
# entries (Keye's 128 KB, GLM's and Kimi's 80 KB latent pages), pinned here
# for the first time.
KERNEL_BODIES_AT_PARENT = {
    "chat64x20-paged-decode": "178024633ae8f3d4",
    "drain64x10-paged-decode": "a5c6d968d9af6508",
    "cmdaplus16x196-paged-decode": "c0e5b8ef23935735",
    "cmdaplus16x196-windowed-decode": "3bdeae2c481e0a2d",
    "olmodrain40x10-paged-decode": "e3d02f9b3ac55bc2",
}
KERNEL_BODIES_AT_PR52 = {
    "docs16x33-paged-decode-int8": "a2df74e2c7710972",
    "sdardrain64x11-paged-block": "8bf5541686d68d3e",
    "keye8x272-selected-decode": "f52667d126a7f0e1",
    "glm16x272-latent-decode": "6b1de6ed21dea290",
    "kimi16x272-latent-decode": "a4e20884c5c42957",
}
KERNEL_BODIES = {**KERNEL_BODIES_AT_PARENT, **KERNEL_BODIES_AT_PR52}


def _kernel_bodies(fn, args, device) -> list[str]:
    """The Mosaic module of every `pallas_call` of ``fn`` lowered for
    ``device``, as text without debug locations."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    text = jax.jit(fn).lower(*_placed(args, SingleDeviceSharding(device))).as_text()
    bodies = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text):
        context = jax_mlir.make_ir_context()
        context.allow_unregistered_dialects = True  # `stable_mosaic`, the serialised form
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            bodies.append(module.operation.get_asm(enable_debug_info=False))
    return bodies


def _short_hash(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("case", sorted(KERNEL_BODIES))
def test_the_shared_kernels_hand_mosaic_what_they_did(v5e, case):
    (body,) = _kernel_bodies(*CASES[case], v5e[0])
    assert f"module @{_kernel_of(case)} " in body
    assert _short_hash(body) == KERNEL_BODIES[case]


# What a kernel instance hands Mosaic is what every start of an engine pays to
# lower and to hash, warm or cold, once a program and a period's layer
# (ROADMAP S14): PR 51's grouped kernels were 5 x the one-page module's text
# (unrolled copy starts, waits and fetches, three loops a row) and cost
# command-a-plus 14 s of set-up. PR 52's trace each kind of step once: 1.8 to
# 2.0 x at 8 pages a step, 1.5 x at 4 (PERF.md section 6, PR 52). A later edit
# that doubles the trace fails here, on the CPU tier.
@pytest.mark.parametrize("case", sorted(KERNEL_BODIES_AT_PR52))
def test_a_grouped_walk_s_module_stays_near_the_one_page_module_s(v5e, monkeypatch, case):
    (grouped,) = _kernel_bodies(*CASES[case], v5e[0])
    monkeypatch.setattr(A, "_walk_shape", lambda *a: (1, A._walk_slots(1)))
    jax.clear_caches()  # a trace is cached by the function, not by the patch
    (single,) = _kernel_bodies(*CASES[case], v5e[0])
    jax.clear_caches()
    assert len(single) < len(grouped) < 2.1 * len(single), (len(grouped), len(single))


# An admission group's `paged_insert_pages` module, as the parent (PR 47) handed
# it to Mosaic: PR 48 gave the kernel a one-layer form for a segment's write
# (`every_layer=False`), and the every-layer form's module is the parent's.
INSERT_BODIES_AT_PARENT = {
    "chat1x64-paged-insert-pages": "6215463845a0dd9c",
    "chat1x1024-paged-insert-pages": "2851d67ff9ea116a",
    "docs4x2048-paged-insert-pages": "d9f4384d6b7736ea",
    "olmodrain8x256-paged-insert-pages": "f0907edbec4831a6",
}


@pytest.mark.parametrize("case", sorted(INSERT_BODIES_AT_PARENT))
def test_an_admission_group_s_page_writer_hands_mosaic_what_it_did(v5e, case):
    (body,) = _kernel_bodies(*CASES[case], v5e[0])
    assert "module @paged_insert_pages " in body
    assert _short_hash(body) == INSERT_BODIES_AT_PARENT[case]


# The segment's expansion kernel of a latent model, new in PR 49, as that PR
# handed it to Mosaic at the GLM cell's shapes: a later PR that does not mean
# to touch it holds it still.
LATENT_EXPAND_BODY_AT_PR49 = {"glm1x2048-latent-expand": "17e09ee2548379aa"}


@pytest.mark.parametrize("case", sorted(LATENT_EXPAND_BODY_AT_PR49))
def test_the_latent_expansion_hands_mosaic_what_it_did(v5e, case):
    (body,) = _kernel_bodies(*CASES[case], v5e[0])
    assert "module @latent_expand_blocks " in body
    assert _short_hash(body) == LATENT_EXPAND_BODY_AT_PR49[case]


# The segment walk's module at the four segment cells' shapes. PR 56 keeps the
# walk's running maximum and sum as columns `[G, block_q, 1]`: the five modules
# as PR 56 handed them to Mosaic, for a later PR that does not mean to touch the
# walk to hold still. PR 55's kernel (which `dev/bench_segment_walk.py` carries
# for the chip's comparison: its rows `[G, block_q]` lie along the lanes, eight
# turns of 512 values a key block) still lowers to the module PR 55 handed
# Mosaic, hash for hash, so the copy is the parent; and the new module's text
# is no longer than that one's (what a start pays to lower and to hash an
# instance, ROADMAP S14 (4); command-a-plus traces four a segment program).
SEGMENT_BODIES_AT_PR55 = {
    "kimi-segment-2048": "f816eb2490353386",
    "cmdaplus-segment-2048": "81755607ed3d7e3c",
    "cmdaplus-window-segment-2048": "bb8414c787ae90a0",
    "glm-sparse-segment-2048": "08c4006ee2f127eb",
    "keye-sparse-segment-2048": "02fda9013aae60cc",
}
SEGMENT_BODIES_AT_PR56 = {
    "kimi-segment-2048": "f613a6218b390e14",
    "cmdaplus-segment-2048": "6707a07407535b6b",
    "cmdaplus-window-segment-2048": "616a75ecab0baa9d",
    "glm-sparse-segment-2048": "00aa4a0b83181e3c",
    "keye-sparse-segment-2048": "cf0d48af1eb99d59",
}


@functools.cache
def _segment_kernel_pr55():
    """PR 55's `_segment_kernel`, from dev/bench_segment_walk.py."""
    path = Path(__file__).resolve().parents[1] / "dev" / "bench_segment_walk.py"
    spec = importlib.util.spec_from_file_location("bench_segment_walk", path)
    # (registered before it runs: a dataclass looks its module up)
    module = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.segment_kernel_pr55


@pytest.fixture
def the_walk_pr55_had(monkeypatch):
    """The segment walk with its running maximum and sum as rows, as before PR 56."""
    monkeypatch.setattr(A, "_segment_kernel", _segment_kernel_pr55())
    jax.clear_caches()  # a trace is cached by the function, not by the patch
    yield
    jax.clear_caches()


@pytest.mark.parametrize("case", sorted(SEGMENT_BODIES_AT_PR56))
def test_the_segment_walk_hands_mosaic_what_pr56_left(v5e, case, request):
    (body,) = _kernel_bodies(*CASES[case], v5e[0])
    assert f"module @{_kernel_of(case)} " in body
    assert _short_hash(body) == SEGMENT_BODIES_AT_PR56[case]
    # no arithmetic on the running maximum or sum as a ROW, block_q along the
    # lanes: a reduction's result goes straight back to a column
    block_q = 256 if case.startswith("cmdaplus") else 512
    on_rows = rf"stable_mosaic\.(arith\.(?!constant)|math\.)\w+\"\(.* -> vector<\d+x{block_q}xf32>"
    assert not re.search(on_rows, body)
    request.getfixturevalue("the_walk_pr55_had")
    (parent,) = _kernel_bodies(*CASES[case], v5e[0])
    assert _short_hash(parent) == SEGMENT_BODIES_AT_PR55[case]
    assert re.search(on_rows, parent)  # the maximum, the rescale's exponential, the sum
    assert len(body) <= len(parent), (len(body), len(parent))


def test_the_selection_is_the_only_difference_of_its_kernel(v5e):
    """The selected walk's Mosaic module against the plain decode kernel's
    at the same sizes: one more operand (a row's block, float32
    [1, 272, 1, 64], with its index map), a page of it compared with 0 and
    one `select` on the scores; no line of the plain kernel is gone."""
    import difflib

    sizes = dict(batch=8, table=272, pages=2176, layers=12)
    (plain,) = _kernel_bodies(*_paged(KEYE, False, **sizes), v5e[0])
    (selected,) = _kernel_bodies(*CASES["keye8x272-selected-decode"], v5e[0])
    # SSA numbers and argument numbers shift behind the new operand
    blank = lambda text: re.sub(r"%(arg)?\d+", "%_", text).splitlines()  # noqa: E731
    delta = [
        line for line in difflib.ndiff(blank(plain), blank(selected))
        if line[0] in "+-" and not line.startswith(("- module @", "+ module @"))
    ]
    # lines are added, none goes but the signatures the new operand is part of
    gone = [line for line in delta if line[0] == "-"]
    assert all("^bb0(" in line or "function_type = " in line for line in gone), gone[:3]
    # (PR 52: a loop step takes 8 pages here, so the mask is read a page at a
    # time and laid side by side in the group's step as well as in a single
    # page's: 149 lines where the one-page walk added under 40)
    assert 0 < len(delta) - len(gone) < 160, len(delta)
    assert sum("memref<1x272x1x64xf32" in line for line in delta) >= 2  # the row's block


# PR 52 re-took these six, the indexer preset's decode chunk below
# (`ENGINE_PROGRAMS_AT_PARENT`'s first row) and the latent presets' two
# (`LATENT_PROGRAMS_AT_PR47`'s and `LATENT_DENSE_PROGRAMS_AT_PR50`'s first rows)
# on purpose: the tiny presets' pages are a few hundred bytes, so every decode
# (and block) chunk that holds a paged decode kernel walks its rows a group of
# 2 pages a step (`ops/attention._walk_shape` under tables of 6). The segment
# and admit programs hold no such kernel and are the parent's, every row.
DECODE_PROGRAMS_AT_PARENT = {
    "tiny-test": "08678b69c9038965",
    "tiny-test-int8": "b49cfa793f35c4f0",
    "tiny-moe-test": "ee109ccd5746e99e",
    "tiny-hybrid-test": "3dee33c893d6e5f5",
    "tiny-window-moe-test": "7dc9536f59709e87",
    "tiny-blockfill-moe-test": "b780241efec3ab66",
}


# Every other engine program a cell runs, for the same cases where the model
# has the program, and the indexer's preset in every row: the text the parent
# gave (commit 0089f57, PR 45), taken by the code below before ISSUE 46 moved
# a line of models/transformer.py. "segment": `_paged_segment_and_sample`;
# "admit": the admission group (`_make_paged_admit_group()`; a model that
# fills blocks has `_block_admit_group` and no segment).
ENGINE_PROGRAMS_AT_PARENT = {
    "tiny-sparse-moe-test": "1f8e0060c26342db",
    "segment/tiny-test": "17b4532d195db002",
    "segment/tiny-test-int8": "5442e93148fd62a0",
    "segment/tiny-moe-test": "4d69d76d663c6419",
    "segment/tiny-hybrid-test": "88f390ad18e03100",
    "segment/tiny-window-moe-test": "5faf99eba088cd8f",
    "segment/tiny-sparse-moe-test": "6150db6ae34db846",
    "admit/tiny-test": "a8dfcebd92d7ec65",
    "admit/tiny-test-int8": "2a0a61a7fd0843a9",
    "admit/tiny-moe-test": "498b293e7ebb210d",
    "admit/tiny-hybrid-test": "86241862dfcc1760",
    "admit/tiny-window-moe-test": "21f120a3f856fe24",
    "admit/tiny-sparse-moe-test": "b2db2c60957a39ee",
    "admit/tiny-blockfill-moe-test": "5a6863a3f918125c",
}


TINY_ROWS, TINY_PAGE = 4, 8


def _i32(*shape):
    return SDS(shape, jnp.int32)


def _tiny_case(case: str, impl: str):
    """(config, params, pool, tables) of a tiny preset ("-int8": over an int8
    pool) as shapes: 4 slots, 24 pages of 8, a table of 6 pages a row;
    ``tables(rows)`` is the paged entry points' table argument."""
    from langstream_tpu.models.transformer import init_params, make_page_pool

    name, int8 = case.removesuffix("-int8"), case.endswith("-int8")
    config = dataclasses.replace(
        MODEL_PRESETS[name], attention_impl=impl,
        kv_cache_dtype="int8" if int8 else MODEL_PRESETS[name].kv_cache_dtype,
    )
    params = jax.eval_shape(lambda k: init_params(config, k), SDS((2,), jnp.uint32))
    pool = jax.eval_shape(
        lambda: make_page_pool(config, 24, TINY_PAGE, state_rows=TINY_ROWS)
    )

    def tables(rows):
        return _i32(2, rows, 6) if config.has_window else _i32(rows, 6)

    return config, params, pool, tables


def _engine_program_text(case: str) -> str:
    """The lowered text of one engine program of one tiny preset, kernels in
    interpret mode: ``case`` is a preset's name ("-int8": over an int8 pool),
    the decode (or block) chunk, or "segment/<name>", "admit/<name>"."""
    from langstream_tpu.serving import engine as E

    program, _, case = case.rpartition("/")
    config, params, pool, tables = _tiny_case(case, "pallas")
    b, page, i32, key = TINY_ROWS, TINY_PAGE, _i32, SDS((2,), jnp.uint32)
    f32 = lambda *s: SDS(s, jnp.float32)  # noqa: E731

    if program == "segment":
        return E._paged_segment_and_sample.lower(
            params, i32(1, 16), i32(1), i32(1), pool, tables(1), key, f32(1), i32(1),
            f32(1), config, page,
            **({"state_rows": i32(1)} if config.is_recurrent else {}),
        ).as_text()
    if program == "admit" and config.fills_blocks:
        s = config.block_length
        block = {"tokens": i32(b, s), "open": SDS((b, s), jnp.bool_), "step": i32(b)}
        return E._block_admit_group.lower(
            params, pool, block, i32(b), f32(b), i32(b), f32(b), i32(2, 16), f32(5, 2),
            i32(2, s), i32(2), tables(2), config, page,
        ).as_text()
    if program == "admit":
        return E._make_paged_admit_group().lower(
            params, pool, i32(b), i32(b), f32(b), i32(b), f32(b), key, i32(2, 16),
            f32(4, 2), i32(2), tables(2), config, page,
        ).as_text()
    if config.fills_blocks:
        s = config.block_length
        block = {"tokens": i32(b, s), "open": SDS((b, s), jnp.bool_), "step": i32(b)}
        return E._paged_block_chunk.lower(
            params, block, i32(b), pool, tables(b), key, f32(b), i32(b), f32(b), 2, config, page
        ).as_text()
    return E._paged_decode_chunk.lower(
        params, i32(b), i32(b), pool, tables(b), key, f32(b), i32(b), f32(b), 2, config, page
    ).as_text()


# The latent model's three engine programs (`tiny-latent-moe-test`), as PR 47
# left them: what a later PR that does not mean to touch them holds still.
# ONE row is PR 49's, re-taken on purpose: with the kernels forced the SEGMENT
# expands the columns its queries can see in `latent_expand_blocks`, in place
# of `_latent_expand` of the whole table ("5d841ea49914bce2" at PR 47, under
# the scatter); the decode chunk and the admit group are PR 47's.
LATENT_PROGRAMS_AT_PR47 = {
    "tiny-latent-moe-test": "11d5d124bbfe31f9",
    "segment/tiny-latent-moe-test": "f92ebd1992a5fe51",
    "admit/tiny-latent-moe-test": "7ccaa668ff6f12fc",
}

# The latent model with NO indexer (`tiny-latent-dense-moe-test`, PR 50): its
# three engine programs as that PR left them, kernels forced; the segment row
# under the scatter like the others (its page-writing form is
# `SEGMENT_PROGRAMS_AT_PR48`'s last row).
LATENT_DENSE_PROGRAMS_AT_PR50 = {
    "tiny-latent-dense-moe-test": "6de4d5a93e313f96",
    "admit/tiny-latent-dense-moe-test": "bdec01f32b991102",
}

ENGINE_PROGRAMS = {
    **DECODE_PROGRAMS_AT_PARENT, **ENGINE_PROGRAMS_AT_PARENT, **LATENT_PROGRAMS_AT_PR47,
    **LATENT_DENSE_PROGRAMS_AT_PR50,
}

# PR 48 changes the SEGMENT programs and no other, on purpose: a causal
# segment of whole pages writes its rows into a bf16 pool by whole pages
# (`paged_insert_pages` a layer, the scatter behind a trip count of 0 or 1),
# so with the kernels forced these six lower anew, as PR 48 left them. The
# tables above are NOT re-taken: every decode, block and admit row holds as it
# is, `segment/tiny-test-int8` too (an int8 pool keeps the scatter), and each
# of these six still lowers to its hash THERE once `_copies_pages` says no:
# the scatter's branch is the parent's program byte for byte.
SEGMENT_PROGRAMS_AT_PR48 = {
    "segment/tiny-test": "e436eed989adc2fd",
    "segment/tiny-moe-test": "8fb8ffb110d43a60",
    "segment/tiny-hybrid-test": "41ea43aa7585926d",
    "segment/tiny-window-moe-test": "dca68855cfb508c3",
    "segment/tiny-sparse-moe-test": "cb4b43784ed5ccfa",
    # (PR 49's, re-taken on purpose with `LATENT_PROGRAMS_AT_PR47`'s row: the
    # bounded expansion; "33335341cfab4025" at PR 48)
    "segment/tiny-latent-moe-test": "21c00b25ad4d8841",
    # (PR 50's own: the latent model with no indexer, as that PR left it)
    "segment/tiny-latent-dense-moe-test": "887bf6290c0f3b0d",
}


# PR 54 gives `moe_ffn_held` a seventh count, `spilled`, and every program of a
# model that holds its experts returns it. At these tables' widths (segments
# of 16 tokens, steps of 4 rows) `ops/grouped_matmul.pass_shape` keeps the one
# pass, so the count is a constant 0 and the ONLY difference of such a
# program: with `MOE_HELD_COUNTS` patched to its six the tables above and
# below hold, every row, as they stand (the two tests that read them do so).
# As the programs are, the seventeen rows lower to what PR 54 left:
HELD_PRESETS = (
    "tiny-window-moe-test", "tiny-blockfill-moe-test", "tiny-sparse-moe-test",
    "tiny-latent-moe-test", "tiny-latent-dense-moe-test",
)
HELD_PROGRAMS_AT_PR54 = {
    "admit/tiny-blockfill-moe-test": "83a8dd64040c2c92",
    "admit/tiny-latent-dense-moe-test": "8230c7d44364ca62",
    "admit/tiny-latent-moe-test": "6a1126273c90d921",
    "admit/tiny-sparse-moe-test": "e1c04d8670b121de",
    "admit/tiny-window-moe-test": "998fb2590ba979f8",
    "segment/tiny-latent-moe-test": "f9cb662eb45dabb5",
    "segment/tiny-sparse-moe-test": "8cb7ab4fc0f2a8cb",
    "segment/tiny-window-moe-test": "9d29306b35fc5fb4",
    "tiny-blockfill-moe-test": "bb966f526c25a285",
    "tiny-latent-dense-moe-test": "013b35a046c1315e",
    "tiny-latent-moe-test": "770651baa99765f6",
    "tiny-sparse-moe-test": "1468003eef5a46e9",
    "tiny-window-moe-test": "1a6f06b4fb927126",
}
# the same rows with a segment's rows written by whole pages (PR 48)
HELD_SEGMENT_PROGRAMS_AT_PR54 = {
    "segment/tiny-latent-dense-moe-test": "580914ad0aa698a3",
    "segment/tiny-latent-moe-test": "d07ad1be78f76cc7",
    "segment/tiny-sparse-moe-test": "6700671fea72817d",
    "segment/tiny-window-moe-test": "e9c92f7360098646",
}


def _holds_experts(case: str) -> bool:
    return case.rpartition("/")[2] in HELD_PRESETS


@pytest.fixture
def six_counts(monkeypatch):
    """The held models' programs without PR 54's count."""
    from langstream_tpu.models import transformer as T

    monkeypatch.setattr(T, "MOE_HELD_COUNTS", T.MOE_HELD_COUNTS[:6])
    jax.clear_caches()  # the jitted program's trace is cached by its arguments' shapes
    yield
    jax.clear_caches()


@pytest.fixture
def under_the_scatter(monkeypatch):
    """A segment's rows written by the scatter, as before PR 48."""
    from langstream_tpu.models import transformer as T

    monkeypatch.setattr(T, "_copies_pages", lambda *a: False)
    jax.clear_caches()  # the jitted program's trace is cached by its arguments' shapes
    yield
    jax.clear_caches()


# PR 56 keeps the segment walk's (`ops/attention._segment_kernel`) running
# maximum and sum as columns, and every program that holds the kernel lowers
# anew, in interpret mode too: the four presets' segment programs whose
# segments walk key blocks, and the two indexer presets' admit groups (their
# prefill under the selection is the same walk). Those six, as PR 56 left them
# (the pages' writer, the seventh count). The tables above are NOT re-taken:
# with PR 55's kernel patched back (`the_walk_pr55_had`: the copy
# dev/bench_segment_walk.py holds the chip's comparison by) each of the six
# still lowers to every hash it had THERE, which is the proof that nothing
# else of these programs moved.
PROGRAMS_AT_PR56 = {
    "admit/tiny-latent-moe-test": "8cac4c6f2c77c039",
    "admit/tiny-sparse-moe-test": "c6b76e33fc9d4a00",
    "segment/tiny-latent-dense-moe-test": "ab05293bc5ad887e",
    "segment/tiny-latent-moe-test": "1fe546a047e877b0",
    "segment/tiny-sparse-moe-test": "7325dbc1995988f8",
    "segment/tiny-window-moe-test": "833f10e44c7df4a3",
}


@pytest.mark.parametrize("case", sorted(ENGINE_PROGRAMS))
def test_the_other_models_decode_programs_lower_as_they_did(case, request):
    if _holds_experts(case):
        request.getfixturevalue("six_counts")
    if case in SEGMENT_PROGRAMS_AT_PR48:
        request.getfixturevalue("under_the_scatter")
    if case in PROGRAMS_AT_PR56:
        request.getfixturevalue("the_walk_pr55_had")
    assert _short_hash(_engine_program_text(case)) == ENGINE_PROGRAMS[case]


@pytest.mark.parametrize("case", sorted(SEGMENT_PROGRAMS_AT_PR48))
def test_the_segment_programs_lower_as_pr48_left_them(case, request):
    if _holds_experts(case):
        request.getfixturevalue("six_counts")
    if case in PROGRAMS_AT_PR56:
        request.getfixturevalue("the_walk_pr55_had")
    assert _short_hash(_engine_program_text(case)) == SEGMENT_PROGRAMS_AT_PR48[case]
    assert A.attention_paths()["paged-segment-write[s=16]"] == "paged_insert_pages"


@pytest.mark.parametrize("case", sorted(c for c in ENGINE_PROGRAMS if _holds_experts(c)))
def test_the_held_models_programs_lower_as_pr54_left_them(case, request):
    """With the seventh count; a segment's row under the scatter as above."""
    if case in SEGMENT_PROGRAMS_AT_PR48:
        request.getfixturevalue("under_the_scatter")
    if case in PROGRAMS_AT_PR56:
        request.getfixturevalue("the_walk_pr55_had")
    assert _short_hash(_engine_program_text(case)) == HELD_PROGRAMS_AT_PR54[case]


@pytest.mark.parametrize("case", sorted(c for c in SEGMENT_PROGRAMS_AT_PR48 if _holds_experts(c)))
def test_the_held_models_segments_lower_as_pr54_left_them(case, request):
    if case in PROGRAMS_AT_PR56:
        request.getfixturevalue("the_walk_pr55_had")
    assert _short_hash(_engine_program_text(case)) == HELD_SEGMENT_PROGRAMS_AT_PR54[case]


@pytest.mark.parametrize("case", sorted(PROGRAMS_AT_PR56))
def test_the_programs_that_walk_key_blocks_lower_as_pr56_left_them(case):
    """As the programs are. The admit rows under the seventh count
    (`HELD_PROGRAMS_AT_PR54`'s with PR 55's walk), the segment rows with their
    rows written by whole pages (`HELD_SEGMENT_PROGRAMS_AT_PR54`'s)."""
    assert _short_hash(_engine_program_text(case)) == PROGRAMS_AT_PR56[case]


# What `attention_paths()` says after a prefill over a local cache, a segment
# and a decode step (a model that fills blocks: its prefill and a block pass)
# of each tiny preset, kernels forced ("pallas") and as the CPU chooses
# ("auto"): what the parent said (commit 0089f57, PR 45), as data. The
# families' `expected_kernels` hold a chip run to such strings letter for
# letter; this holds a refactor of the callers of `note_path` to them here.
# ONE key is PR 48's and here on purpose: every preset that traces a segment
# now says how the segment's new rows reach the pool,
# `paged-segment-write[s=16]`: by whole pages where the kernels are forced
# over a bf16 pool, by the scatter on the CPU's own choice and into an int8
# pool. And ONE is PR 49's: the latent preset, kernels forced, says which call
# expands a segment's columns, `paged-segment-latent-expand[..]`. Every other
# entry is the parent's.
PATHS_AT_PARENT = {
    "tiny-blockfill-moe-test/auto": {
        "paged-block[s=4,t=48]": "jnp",
        "prefill[s=16,t=16]": "jnp",
    },
    "tiny-blockfill-moe-test/pallas": {
        "paged-block[s=4,t=48]": "ragged_paged_block_attention",
        "prefill[s=16,t=16]": "flash_prefill_attention",
    },
    "tiny-hybrid-test/auto": {
        "linear-decode[s=1,t=0]": "jnp",
        "linear-prefill[s=16,t=16]": "gated_delta_chunk_prefill",
        "paged-decode[s=1,t=48]": "jnp",
        "paged-segment-write[s=16]": "scatter",
        "paged-segment[s=16,t=48]": "jnp",
        "prefill[s=16,t=16]": "jnp",
    },
    "tiny-hybrid-test/pallas": {
        "linear-decode[s=1,t=0]": "gated_delta_update",
        "linear-prefill[s=16,t=16]": "gated_delta_chunk_prefill",
        "paged-decode[s=1,t=48]": "ragged_paged_decode_attention",
        "paged-segment-write[s=16]": "paged_insert_pages",
        "paged-segment[s=16,t=48]": "jnp",
        "prefill[s=16,t=16]": "flash_prefill_attention",
    },
    # (PR 47's rows: the latent model's entries are its own)
    "tiny-latent-moe-test/auto": {
        "paged-decode-latent[s=1,t=48]": "jnp",
        "paged-segment-latent-sparse[s=16,t=48]": "jnp",
        "paged-segment-write[s=16]": "scatter",
        "prefill-sparse[s=16,t=16]": "jnp",
    },
    "tiny-latent-moe-test/pallas": {
        "paged-decode-latent[s=1,t=48]": "ragged_paged_latent_attention",
        "paged-segment-latent-expand[s=16,t=48]": "latent_expand_blocks",  # (PR 49's key)
        "paged-segment-latent-select[s=16,t=48]": "segment_select",
        "paged-segment-latent-sparse[s=16,t=48]": "sparse_segment_attention",
        "paged-segment-latent[s=16,t=48]": "flash_segment_attention",
        "paged-segment-write[s=16]": "paged_insert_pages",
        "prefill-select[s=16,t=16]": "segment_select",
        "prefill-sparse[s=16,t=16]": "sparse_segment_attention",
        "segment-select[s=16,t=16]": "block_q 16, block_k 16, to the diagonal",
        "segment-select[s=16,t=48]": "block_q 16, block_k 48, to the diagonal",
    },
    # (PR 50's rows: a latent with no indexer notes nothing of a selection)
    "tiny-latent-dense-moe-test/auto": {
        "paged-decode-latent[s=1,t=48]": "jnp",
        "paged-segment-latent[s=16,t=48]": "jnp",
        "paged-segment-write[s=16]": "scatter",
        "prefill[s=16,t=16]": "jnp",
    },
    "tiny-latent-dense-moe-test/pallas": {
        "paged-decode-latent[s=1,t=48]": "ragged_paged_latent_attention",
        "paged-segment-latent-expand[s=16,t=48]": "latent_expand_blocks",
        "paged-segment-latent[s=16,t=48]": "flash_segment_attention",
        "paged-segment-write[s=16]": "paged_insert_pages",
        "prefill[s=16,t=16]": "flash_prefill_attention",
    },
    "tiny-moe-test/auto": {
        "paged-decode[s=1,t=48]": "jnp",
        "paged-segment-write[s=16]": "scatter",
        "paged-segment[s=16,t=48]": "jnp",
        "prefill[s=16,t=16]": "jnp",
    },
    "tiny-moe-test/pallas": {
        "paged-decode[s=1,t=48]": "ragged_paged_decode_attention",
        "paged-segment-write[s=16]": "paged_insert_pages",
        "paged-segment[s=16,t=48]": "jnp",
        "prefill[s=16,t=16]": "flash_prefill_attention",
    },
    "tiny-sparse-moe-test/auto": {
        "paged-decode-sparse[s=1,t=48]": "xla top_k + gather",
        "paged-segment-sparse[s=16,t=48]": "jnp",
        "paged-segment-write[s=16]": "scatter",
        "prefill-sparse[s=16,t=16]": "jnp",
    },
    "tiny-sparse-moe-test/pallas": {
        "paged-decode-selected[s=1,t=48]": "ragged_paged_selected_attention",
        "paged-decode-sparse[s=1,t=48]": "ragged_paged_decode_attention to index_topk, xla top_k + gather past it",
        "paged-segment-select[s=16,t=48]": "segment_select",
        "paged-segment-sparse[s=16,t=48]": "sparse_segment_attention",
        "paged-segment-write[s=16]": "paged_insert_pages",
        "paged-segment[s=16,t=48]": "flash_segment_attention",
        "prefill-select[s=16,t=16]": "segment_select",
        "prefill-sparse[s=16,t=16]": "sparse_segment_attention",
        "segment-select[s=16,t=16]": "block_q 16, block_k 16, to the diagonal",
        "segment-select[s=16,t=48]": "block_q 16, block_k 48, to the diagonal",
    },
    "tiny-test-int8/auto": {
        "paged-decode[s=1,t=48]": "jnp",
        "paged-segment-write[s=16]": "scatter",
        "paged-segment[s=16,t=48]": "jnp",
        "prefill[s=16,t=16]": "jnp",
    },
    "tiny-test-int8/pallas": {
        "paged-decode[s=1,t=48]": "ragged_paged_decode_attention_int8",
        "paged-segment-write[s=16]": "scatter",
        "paged-segment[s=16,t=48]": "jnp",
        "prefill[s=16,t=16]": "flash_prefill_attention",
    },
    "tiny-test/auto": {
        "paged-decode[s=1,t=48]": "jnp",
        "paged-segment-write[s=16]": "scatter",
        "paged-segment[s=16,t=48]": "jnp",
        "prefill[s=16,t=16]": "jnp",
    },
    "tiny-test/pallas": {
        "paged-decode[s=1,t=48]": "ragged_paged_decode_attention",
        "paged-segment-write[s=16]": "paged_insert_pages",
        "paged-segment[s=16,t=48]": "jnp",
        "prefill[s=16,t=16]": "flash_prefill_attention",
    },
    "tiny-window-moe-test/auto": {
        "paged-decode[s=1,t=48]": "jnp",
        "paged-segment-write[s=16]": "scatter",
        "paged-segment[s=16,t=48]": "jnp",
        "prefill[s=16,t=16]": "jnp",
    },
    "tiny-window-moe-test/pallas": {
        "paged-decode[s=1,t=48]": "ragged_paged_decode_attention",
        "paged-segment-write[s=16]": "paged_insert_pages",
        "paged-segment[s=16,t=48]": "flash_segment_attention",
        "prefill[s=16,t=16]": "flash_prefill_attention",
    },
}


def _traced_paths(case: str) -> dict:
    from langstream_tpu.models import transformer as T

    case, _, impl = case.rpartition("/")
    config, params, pool, tables = _tiny_case(case, impl)
    b, page, width, i32 = TINY_ROWS, TINY_PAGE, 16, _i32
    was = dict(A._PATHS)
    A._PATHS.clear()
    try:
        # the functions themselves, not their jits: a cached trace notes nothing
        jax.eval_shape(
            lambda p, tokens, lengths, rec: T.prefill.__wrapped__(
                p, tokens, lengths, T.join_rec(T.make_kv_cache(config, 2, width), rec),
                config, rec_rows=lengths,
            ),
            params, i32(2, width), i32(2), pool.get("rec"),
        )
        if config.fills_blocks:
            jax.eval_shape(
                lambda *a: T.paged_block_step_inplace(*a, config, page),
                params, i32(b, config.block_length), i32(b), pool, tables(b),
            )
        else:
            jax.eval_shape(
                lambda *a: T.paged_prefill_segment_inplace(
                    *a, config, page, state_rows=jnp.zeros(1, jnp.int32)
                ),
                params, i32(1, width), i32(1), i32(1), pool, tables(1),
            )
            jax.eval_shape(
                lambda *a: T.paged_decode_step_inplace(*a, config, page),
                params, i32(b), i32(b), pool, tables(b),
            )
        return A.attention_paths()
    finally:
        A._PATHS.update(was)


# PR 52's keys, on purpose: every paged entry point a preset traces with the
# kernels forced says how its walk takes the row's pages (`_walk_shape`: the
# tiny presets' pages are a few hundred bytes and their tables hold 6, so a
# step takes 2 and five slots hold them); the CPU's own choice reads through
# no kernel and says nothing. Every other key and value is `PATHS_AT_PARENT`'s.
# And PR 54's, on purpose: an expert layer that holds its experts says how each
# call lays its rows out, kernels forced or not (`ops/grouped_matmul.
# dispatch_note`: a fact of the call's tokens, top-k and share). A prefill of 2 x
# 16 tokens, a segment of 16, a step of 4 rows (a block pass: 4 rows x 4
# positions): at these widths every call keeps its one pass.
DISPATCH_AT_PR54 = {
    "tiny-blockfill-moe-test": {
        "moe-dispatch[t=32,k=4,held=16/16]": "one pass, 25 tiles",
        "moe-dispatch[t=16,k=4,held=16/16]": "one pass, 17 tiles",
    },
    "tiny-sparse-moe-test": {
        "moe-dispatch[t=32,k=4,held=16/16]": "one pass, 25 tiles",
        "moe-dispatch[t=16,k=4,held=16/16]": "one pass, 17 tiles",
        "moe-dispatch[t=4,k=4,held=16/16]": "one pass, 17 tiles",
    },
    "tiny-window-moe-test": {
        "moe-dispatch[t=32,k=4,held=4/16]": "one pass, 9 tiles",
        "moe-dispatch[t=16,k=4,held=4/16]": "one pass, 5 tiles",
        "moe-dispatch[t=4,k=4,held=4/16]": "one pass, 5 tiles",
    },
    "tiny-latent-moe-test": {
        "moe-dispatch[t=32,k=2,held=4/8]": "one pass, 9 tiles",
        "moe-dispatch[t=16,k=2,held=4/8]": "one pass, 5 tiles",
        "moe-dispatch[t=4,k=2,held=4/8]": "one pass, 5 tiles",
    },
}
DISPATCH_AT_PR54["tiny-latent-dense-moe-test"] = DISPATCH_AT_PR54["tiny-latent-moe-test"]


@pytest.mark.parametrize("case", sorted(PATHS_AT_PARENT))
def test_every_preset_notes_the_paths_it_did(case):
    walk = {
        f"paged-walk[{kernel},ps={TINY_PAGE}]": "pages/step 2, slots 5"
        for key, kernel in PATHS_AT_PARENT[case].items()
        if key.startswith(("paged-decode", "paged-block"))
        and re.fullmatch(r"ragged_paged_\w+", kernel)
    }
    assert len(walk) == case.endswith("/pallas")
    dispatch = DISPATCH_AT_PR54.get(case.rpartition("/")[0], {})
    assert bool(dispatch) == _holds_experts(case.rpartition("/")[0])
    assert _traced_paths(case) == {**PATHS_AT_PARENT[case], **walk, **dispatch}


# The tables' segments are 16 tokens wide and keep the one pass. ONE program
# whose shapes take the passes (`moe_ffn_held`'s `lax.while_loop` over windows
# of the sorted assignments): the window preset's segment at 2,048 tokens (4 of
# 16 experts held, top-4: twice the even share is 4,096 of its 8,192
# assignments), as PR 54 left it, and what it says of itself.
@pytest.mark.parametrize("walk", ["pr55", "pr56"])
def test_a_segment_wide_enough_takes_the_passes(walk, request):
    """(PR 56: the window preset's segment holds the segment walk, so the
    program PR 54 left is the one with PR 55's walk; as it is, it lowers to
    what PR 56 left.)"""
    from langstream_tpu.models.transformer import make_page_pool

    if walk == "pr55":
        request.getfixturevalue("the_walk_pr55_had")
    from langstream_tpu.serving import engine as E

    config, params, _, _ = _tiny_case("tiny-window-moe-test", "pallas")
    width, table = 2048, 2048 // TINY_PAGE
    pool = jax.eval_shape(lambda: make_page_pool(config, 2 * table, TINY_PAGE, state_rows=1))
    f32 = lambda *s: SDS(s, jnp.float32)  # noqa: E731
    A._PATHS.clear()
    text = E._paged_segment_and_sample.lower(
        params, _i32(1, width), _i32(1), _i32(1), pool, _i32(2, 1, table), SDS((2,), jnp.uint32),
        f32(1), _i32(1), f32(1), config, TINY_PAGE,
    ).as_text()
    assert A.attention_paths()["moe-dispatch[t=2048,k=4,held=4/16]"] == (
        "passes of 4096, 13 tiles (17 hold every case)"
    )
    assert _short_hash(text) == {"pr55": "0b5d43e29de6db3a", "pr56": "0b6f9a196effb440"}[walk]

"""What the two files of ahead-of-time compiles for a DESCRIBED TPU v5e
share (`test_tpu_compile.py`: the kernels, the step programs and the cells'
programs whole beside their state; `test_tpu_compile_pinned.py`: pinned
lowerings and noted paths): the two fixtures (imported by each file: a
fixture lives in the module that uses it), the cells' configurations at
their published widths, the kernels' cases, and the head and the tail a
cell's program test has. Not a test file: each of the two is one unit of the
`loadfile` scheduler."""

import dataclasses
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from langstream_tpu.models.configs import MODEL_PRESETS
from langstream_tpu.ops import attention as A

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


def _compile_as_on_chip(monkeypatch, fn, args, static):
    """Lower with the gates a chip process passes (`paged_pallas_ok`'s
    "auto" and the kernels' `interpret=` ask `jax.default_backend()`, which
    here still says cpu: the test steers it, the program has no knob)."""
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        lowered = fn.lower(*args, *static)
    return lowered.compile()


# -- a cell's program whole: the head and the tail every such test has --------

V5E_HBM_BYTES = int(15.75 * 2**30)  # what the chip's compiler grants a program
KEY = SDS((2,), jnp.uint32)


def i32(*shape):
    return SDS(shape, jnp.int32)


def f32(*shape):
    return SDS(shape, jnp.float32)


def cell_params(config, from_init: bool = False):
    """A cell's int8 weights as shapes (`eval_shape`: nothing is allocated):
    drawn as int8, or ``from_init`` the bf16 tree quantized (a model with a
    layer pattern, whose tree `init_random_quantized_params` does not draw)."""
    from langstream_tpu.models.quant import init_random_quantized_params, quantize_params
    from langstream_tpu.models.transformer import init_params

    if from_init:
        return jax.eval_shape(lambda k: quantize_params(init_params(config, k), config), KEY)
    return jax.eval_shape(lambda k: init_random_quantized_params(config, k), KEY)


def cell_pool(config, pages, **groups):
    """A cell's page pool as shapes (``groups``: `state_rows`, `window_pages`)."""
    from langstream_tpu.models.transformer import make_page_pool

    return jax.eval_shape(lambda: make_page_pool(config, pages, PAGE, **groups))


def compile_on_one_chip(v5e, monkeypatch, fn, args, static):
    """`_compile_as_on_chip` of ``args`` placed on the described chip."""
    return _compile_as_on_chip(
        monkeypatch, fn, _placed(args, SingleDeviceSharding(v5e[0])), static
    )


def fits_beside_its_state(compiled, pool):
    """The pool (and what rides in it) is updated in place, and what the
    program holds beside it fits the chip -> (memory analysis, the pool's
    bytes, the bytes held)."""
    memory = compiled.memory_analysis()
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool))
    assert memory.alias_size_in_bytes >= pool_bytes
    held = (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes
    )
    assert held <= V5E_HBM_BYTES
    return memory, pool_bytes, held


def no_leaf_moved(text: str, leaves) -> None:
    """No leaf of the pool is relaid or copied whole in the program ``text``."""
    for leaf in leaves:
        dims = re.escape("[" + ",".join(map(str, leaf.shape)) + "]")
        assert not re.search(rf"= \w+{dims}\S* (copy|transpose)\(", text), leaf.shape


# `from tpu_compile_shared import *` takes every name above, the underscored
# ones too: the autouse fixture is one of them
__all__ = [name for name in globals() if not name.startswith("__")]

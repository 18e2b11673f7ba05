"""Grouped int8 matmul: the held experts' product over rows sorted by expert.

An expert layer that drops nothing cannot give every expert a fixed number of
rows. It sorts its (token, expert) assignments by expert instead and lays the
rows out in one buffer, each expert's rows starting at a multiple of the row
tile (`plan_groups`): a tile of rows then belongs to ONE expert, and the
product is a tiled matmul whose weight block is found through a prefetched
vector, the tile's expert. Work is done for the tiles that hold rows; the
buffer is bounded by the assignments (T x k rows and a tile of padding an
expert), never by T x experts. The weights stay int8 in HBM: a block is
converted in VMEM on its way into the MXU and the per-output-channel scale
multiplies the float32 accumulator once, at the last block of K (what
`quantized_matmul` does for a dense weight).

Off the chip, and wherever the shapes do not fit the tiling, `grouped_matmul`
takes the same buffer through an einsum over the tiles (`_jnp`): the same
numbers, the tiles' weights gathered.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM stated to Mosaic: the largest blocks below (a 512-row tile:
# x 2 MiB and w 1 MiB twice buffered, the converted block 2 MiB, the
# accumulator 1 MiB, out 0.5 MiB twice) come to 10 MiB
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=48 * 1024 * 1024,
)


def row_tile(tokens: int, k: int, n_experts: int) -> int:
    """Rows a tile: twice an expert's even share of the assignments, a power
    of two in [16, 512]. 16 (a bf16 tile's sublanes) for a decode step, where
    a weight block is read for a handful of rows whatever the tile; 256 to
    512 for a prefill segment, where a block read is paid by that many rows
    (the chip's ridge is 240 operations a byte)."""
    share = max(1, 2 * tokens * k // max(1, n_experts))
    return int(min(512, max(16, 2 ** math.ceil(math.log2(share)))))


def buffer_tiles(tokens: int, k: int, held: int, tile: int) -> int:
    """Tiles of the buffer: what holds every case (an expert has at most one
    row a token, and the experts together at most ``tokens x min(k, held)``
    rows and a partly filled tile each) and one spare, the last, that never
    holds a row: the product's skipped tiles all write there."""
    per_expert = -(-tokens // tile)
    together = tokens * min(k, held) // tile + held
    return max(1, min(held * per_expert, together)) + 1


def plan_groups(expert: jax.Array, held: int, tile: int, tiles: int):
    """Where each assignment's row goes. ``expert`` [A]: the held expert
    (0 .. held-1) of an assignment, or ``held`` for one that is not this
    program's to compute. Returns ``dest`` [A] (its row of the buffer;
    ``tiles * tile``, out of bounds, where there is none), ``tile_expert``
    [tiles] (a tile past the used ones repeats the last used expert),
    ``used`` [1] (tiles that hold rows) and ``sizes`` [held] (rows an
    expert)."""
    a = expert.shape[0]
    sizes = jnp.zeros(held + 1, jnp.int32).at[expert].add(1)[:held]
    padded = -(-sizes // tile) * tile
    starts = jnp.cumsum(padded) - padded  # each expert's first row
    # an assignment's rank among its expert's: a stable sort keeps token order
    order = jnp.argsort(expert, stable=True)
    sorted_e = expert[order]
    first_of = jnp.cumsum(sizes) - sizes
    rank_sorted = jnp.arange(a, dtype=jnp.int32) - jnp.take(
        jnp.concatenate([first_of, jnp.zeros(1, jnp.int32)]), sorted_e
    )
    dest_sorted = jnp.where(
        sorted_e < held,
        jnp.take(jnp.concatenate([starts, jnp.zeros(1, jnp.int32)]), sorted_e) + rank_sorted,
        tiles * tile,
    )
    dest = jnp.zeros(a, jnp.int32).at[order].set(dest_sorted)
    ends = jnp.cumsum(padded)  # [held]
    used = ends[-1] // tile
    at = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32), jnp.maximum(used - 1, 0)) * tile
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, at, side="right").astype(jnp.int32), held - 1
    )
    return dest, tile_expert, used.reshape(1).astype(jnp.int32), sizes


def _kernel(tile_expert_ref, used_ref, layer_ref, x_ref, w_ref, s_ref, o_ref, acc_ref):
    i, kk = pl.program_id(0), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(i < used_ref[0])
    def _tile():
        @pl.when(kk == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[0, 0].astype(x_ref.dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(kk == nk - 1)
        def _out():
            o_ref[...] = (acc_ref[...] * s_ref[0, 0]).astype(o_ref.dtype)


def grouped_matmul_ok(tile: int, k_dim: int, n_dim: int, attention_impl: str) -> bool:
    """The kernel where it compiles: a real TPU (or ``"pallas"`` forced, in
    interpret mode off it) and lane-aligned K and N."""
    if attention_impl == "jnp" or k_dim % 128 or n_dim % 128 or tile % 16:
        return False
    return attention_impl == "pallas" or jax.default_backend() == "tpu"


def _blocks(tile: int, k_dim: int, n_dim: int) -> tuple[int, int]:
    """(block of K, block of N): few and fat for a decode step's 16-row tiles,
    where every grid step, computed or skipped, costs its 0.35 us."""
    def fit(block, n):
        while n % block:
            block //= 2
        return block

    if tile <= 64:
        return fit(4096, k_dim), fit(1024, n_dim)
    return fit(2048, k_dim), fit(512, n_dim)


def grouped_matmul(
    x: jax.Array,  # [tiles * tile, K] rows laid out by `plan_groups`
    w: dict,  # {"q": int8 [L, E, K, N], "s": f32 [L, E, 1, N]}: the held experts of every layer
    layer: jax.Array,  # the layer whose experts these rows go through
    tile_expert: jax.Array,  # [tiles]
    used: jax.Array,  # [1]
    tile: int,
    kernel: bool,
    interpret: bool = False,
) -> jax.Array:
    """[tiles * tile, N]: row r times the weights of its tile's expert in
    layer ``layer``. The weights come as the whole STACK and the kernel finds
    its blocks at (layer, expert): a layer's experts sliced out of the stack
    would be copied whole before every call (268 MB a matrix at 16 experts of
    4096 x 4096). Rows of tiles past ``used`` come back unspecified: the
    kernel skips them, and the buffer's last tile is the spare they share
    (`buffer_tiles`)."""
    m, k_dim = x.shape
    n_dim = w["q"].shape[-1]
    tiles = m // tile
    if not kernel:
        return _jnp(x, jax.tree.map(lambda a: a[layer], w), tile_expert, tile)
    bk, bn = _blocks(tile, k_dim, n_dim)
    nk, nn = k_dim // bk, n_dim // bn

    def live(i, used):  # a skipped tile stays on the last used tile's blocks
        return jnp.minimum(i, jnp.maximum(used[0] - 1, 0)), i < used[0]

    def x_index(i, j, kk, tile_expert, used, layer):
        at, on = live(i, used)
        return (at, jnp.where(on, kk, nk - 1))

    def w_index(i, j, kk, tile_expert, used, layer):
        at, on = live(i, used)
        return (layer[0], tile_expert[at], jnp.where(on, kk, nk - 1), jnp.where(on, j, nn - 1))

    def s_index(i, j, kk, tile_expert, used, layer):
        at, on = live(i, used)
        return (layer[0], tile_expert[at], 0, jnp.where(on, j, nn - 1))

    def o_index(i, j, kk, tile_expert, used, layer):
        # skipped tiles share the buffer's spare last tile: written once
        _, on = live(i, used)
        return (jnp.where(on, i, tiles - 1), jnp.where(on, j, 0))

    return pl.pallas_call(
        _kernel,
        name="moe_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles, nn, nk),
            in_specs=[
                pl.BlockSpec((tile, bk), x_index),
                pl.BlockSpec((1, 1, bk, bn), w_index),
                pl.BlockSpec((1, 1, 1, bn), s_index),
            ],
            out_specs=pl.BlockSpec((tile, bn), o_index),
            scratch_shapes=[pltpu.VMEM((tile, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n_dim), x.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(tile_expert, used, jnp.reshape(layer, (1,)).astype(jnp.int32), x, w["q"], w["s"])


def _jnp(x, w, tile_expert, tile: int):
    """The same product through an einsum over the tiles."""
    rows = x.reshape(-1, tile, x.shape[1])
    wq = jnp.take(w["q"], tile_expert, axis=0).astype(x.dtype)  # [tiles, K, N]
    out = jnp.einsum("tmk,tkn->tmn", rows, wq, preferred_element_type=jnp.float32)
    out = out * jnp.take(w["s"], tile_expert, axis=0)
    return out.astype(x.dtype).reshape(-1, out.shape[-1])

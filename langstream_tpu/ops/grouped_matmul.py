"""Grouped int8 matmul: the held experts' product over rows sorted by expert.

An expert layer that drops nothing cannot give every expert a fixed number of
rows. It sorts its (token, expert) assignments by expert instead and lays the
rows out in one buffer, each expert's rows starting at a multiple of the row
tile (`plan_groups`): a tile of rows then belongs to ONE expert, and the
product is a tiled matmul whose weight block is found through a prefetched
vector, the tile's expert. Work is done for the tiles that hold rows; the
buffer is bounded by the assignments, never by T x experts. Which buffer:
where every expert is held, or the call is a decode step, the one that
"holds every case" (`buffer_tiles`: T x min(k, held) rows and a tile of
padding an expert); where a layer holds a SHARE of the experts a PASS's
(`pass_shape`: twice the even share of the call's assignments, 21 tiles
where 141 hold every case at 12 of 384 experts), and what is over goes
through a further pass (`transformer.moe_ffn_held`): moving rows costs by
the row the buffer could hold, and the product's grid by its tiles, whether
they are used or skipped. The weights stay int8 in HBM: a block is
converted in VMEM on its way into the MXU and the per-output-channel scale
multiplies the float32 accumulator once, at the last block of K (what
`quantized_matmul` does for a dense weight).

The grid is (tiles, blocks of N, blocks of K), and every step of it, computed
or skipped, costs its 0.35 us whatever it moves: a 256 x 1024 block of int8
is 0.32 us of HBM time, so a grid of such blocks is bound by its steps and
not by its bytes. `_blocks` therefore takes the largest blocks a stated
budget of VMEM holds, by multiples of 128 that divide K and N (768 is not a
power of two): an expert of 2048 x 768 or 768 x 2048 (1.5 MiB of int8) is ONE
block, one step a tile. Where the gate and the up matrix are each one block,
`grouped_gate_up` reads a tile's rows once, takes both products, the
activation and their product in ONE call and writes `hidden` once: two calls
a layer (gate + up, down) and not three, and the tiles past ``used`` are
walked twice. Which grid a shape got is a fact of (tile, K, N) alone;
`grid_note` says it, for the engine's `attention_paths()`.

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

from langstream_tpu.compile_account import note_kernel

# Scoped VMEM stated to Mosaic, over the largest blocks `_blocks` can pick.
# A tile of 64 rows or fewer at the budget's edge (4096 x 1024): w 4 MiB twice
# buffered, its converted copy 8 MiB, x (64 x 4096 bf16) 0.5 MiB twice, the
# accumulator 0.25 MiB, out 0.125 MiB twice: 17.5 MiB. `grouped_gate_up` holds
# TWO weight blocks and no accumulator: at most twice the weight budget and
# the rows, the output and two float32 products of a tile, 35.8 MiB at the
# worst shape the budget admits (64 rows, 256 x 13312); SDAR's 2048 x 768
# pair at 32 rows is 12.5 MiB. A 512-row tile at its edge (2048 x 512): x
# 2 MiB and w 1 MiB twice buffered, the converted block 2 MiB, the
# accumulator 1 MiB, out 0.5 MiB twice: 10 MiB
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
    """Tiles of the buffer that holds EVERY case in one pass (an expert has
    at most one row a token, and the experts together at most ``tokens x
    min(k, held)`` rows and a partly filled tile each) and one spare, the
    last, that never holds a row: the product's skipped tiles all write
    there. What a call lays out where `pass_shape` finds nothing smaller: a
    decode step, a layer that holds every expert."""
    per_expert = -(-tokens // tile)
    together = tokens * min(k, held) // tile + held
    return max(1, min(held * per_expert, together)) + 1


def pass_shape(tokens: int, k: int, held: int, n_experts: int, tile: int):
    """(assignments, tiles) of one PASS of a layer that holds a share of the
    experts, or None where that is no smaller than `buffer_tiles` and the
    call keeps its one pass. A pass takes twice the even share of the call's
    assignments (`row_tile`'s rule for a tile), whole tiles of them and never
    more than there are, in a buffer of a partly filled tile a held expert
    more, and the spare: what holds even routing twice over, where
    `buffer_tiles` holds every token's every choice landing here. What is
    over goes through a further pass (`transformer.moe_ffn_held`)."""
    assignments = tokens * k
    even_twice = -(-2 * assignments * held // n_experts)
    rows = min(assignments, -(-even_twice // tile) * tile)
    tiles = -(-rows // tile) + held + 1
    return (rows, tiles) if tiles < buffer_tiles(tokens, k, held, tile) else None


def dispatch_note(tokens: int, k: int, held: int, n_experts: int, tile: int) -> tuple[str, str]:
    """(key, value) for `attention_paths()`: how a call of these shapes lays
    its rows out, e.g. ``moe-dispatch[t=2048,k=8,held=12/384]`` -> ``passes
    of 1024, 21 tiles (141 hold every case)``, or ``one pass, 13 tiles``."""
    every = buffer_tiles(tokens, k, held, tile)
    shape = pass_shape(tokens, k, held, n_experts, tile)
    what = (
        f"one pass, {every} tiles" if shape is None
        else f"passes of {shape[0]}, {shape[1]} tiles ({every} hold every case)"
    )
    return f"moe-dispatch[t={tokens},k={k},held={held}/{n_experts}]", what


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


def _dot(x_ref, w_ref):
    """The tile's rows times one int8 block, converted on its way: float32."""
    return jax.lax.dot_general(
        x_ref[...], w_ref[0, 0].astype(x_ref.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _kernel(tile_expert_ref, used_ref, layer_ref, x_ref, w_ref, s_ref, o_ref, acc_ref):
    i, kk = pl.program_id(0), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(i < used_ref[0])
    def _tile():
        @pl.when(kk == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += _dot(x_ref, w_ref)

        @pl.when(kk == nk - 1)
        def _out():
            o_ref[...] = (acc_ref[...] * s_ref[0, 0]).astype(o_ref.dtype)


def _gate_up_kernel(
    tile_expert_ref, used_ref, layer_ref, x_ref, wg_ref, sg_ref, wu_ref, su_ref, o_ref,
    *, activation,
):
    """Both products of a tile whose gate and up matrices are one block each:
    float32 up to ``hidden``'s rounding (the two calls round gate and up to
    the rows' type first: one rounding fewer each, toward the reference)."""
    @pl.when(pl.program_id(0) < used_ref[0])
    def _tile():
        gate = _dot(x_ref, wg_ref) * sg_ref[0, 0]
        up = _dot(x_ref, wu_ref) * su_ref[0, 0]
        o_ref[...] = (activation(gate) * up).astype(o_ref.dtype)


def grouped_matmul_ok(tile: int, k_dim: int, n_dim: int, attention_impl: str) -> bool:
    """The kernel where it compiles: a real TPU (or ``"pallas"`` forced, in
    interpret mode off it) and lane-aligned K and N."""
    if attention_impl == "jnp" or k_dim % 128 or n_dim % 128 or tile % 16:
        return False
    return attention_impl == "pallas" or jax.default_backend() == "tpu"


def _vmem_bytes(tile: int, bk: int, bn: int) -> int:
    """What a weight block costs in VMEM: int8 twice buffered, its converted
    bf16 copy, and the float32 accumulator of the tile it feeds."""
    return 2 * bk * bn + 2 * bk * bn + 4 * tile * bn


def _blocks(tile: int, k_dim: int, n_dim: int) -> tuple[int, int]:
    """(block of K, block of N): the fewest grid steps a budget of VMEM
    allows, since every step, computed or skipped, costs its 0.35 us. The
    block of K is the largest multiple of 128 that divides K up to the
    tier's largest, the block of N the largest that divides N and keeps
    `_vmem_bytes` inside the tier's budget: what a 4096 x 1024 block takes
    for tiles of 64 rows or fewer (a decode step or a block pass, bound by
    the weights it reads: an expert of 1.5 MiB is one block), and what
    2048 x 512 takes for larger ones (a prefill segment's tiles are bound by
    their products, not by steps: command-a-plus keeps the blocks its
    readings were taken with)."""
    cap_k, budget = (
        (4096, _vmem_bytes(64, 4096, 1024)) if tile <= 64 else (2048, _vmem_bytes(512, 2048, 512))
    )

    def dividing(n):  # multiples of 128 that divide n, largest first
        return [b for b in range(n, 0, -128) if n % b == 0]

    bk = next(b for b in dividing(k_dim) if b <= cap_k)
    bn = next((b for b in dividing(n_dim) if _vmem_bytes(tile, bk, b) <= budget), 128)
    return bk, bn


def gate_up_shared(tile: int, k_dim: int, n_dim: int) -> bool:
    """One call for gate and up: where a step holds an expert's whole matrix
    (`_blocks`' own outcome; command-a-plus's 4096 x 4096 never does)."""
    return _blocks(tile, k_dim, n_dim) == (k_dim, n_dim)


def grid_note(tile: int, k_dim: int, n_dim: int, gate_up: bool = False) -> tuple[str, str]:
    """(key, value) for `attention_paths()`: the grid a product of this shape
    gets (``gate_up``: it is the gate's and the up's), e.g.
    ``moe-grouped[tile=32,k=2048,n=768]`` -> ``blocks 2048x768, steps/tile 1,
    gate+up shared``."""
    bk, bn = _blocks(tile, k_dim, n_dim)
    what = f"blocks {bk}x{bn}, steps/tile {(k_dim // bk) * (n_dim // bn)}"
    if gate_up and gate_up_shared(tile, k_dim, n_dim):
        what += ", gate+up shared"
    return f"moe-grouped[tile={tile},k={k_dim},n={n_dim}]", what


def _call(kernel, stacks, x, layer, tile_expert, used, tile: int, interpret: bool,
          accumulate: bool) -> jax.Array:
    """One `pallas_call` over the grid (tiles, blocks of N, blocks of K):
    ``kernel`` is handed the three prefetched vectors, the tile's rows, a
    (weight block, scale block) pair for every stack of ``stacks`` at the
    tile's (layer, expert), the output block and, with ``accumulate``, a
    float32 scratch of its shape."""
    m, k_dim = x.shape
    n_dim = stacks[0]["q"].shape[-1]
    tiles = m // tile
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

    note_kernel("moe_grouped_matmul")
    return pl.pallas_call(
        kernel,
        name="moe_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles, nn, nk),
            in_specs=[
                pl.BlockSpec((tile, bk), x_index),
                *(pl.BlockSpec((1, 1, bk, bn), w_index), pl.BlockSpec((1, 1, 1, bn), s_index))
                * len(stacks),
            ],
            out_specs=pl.BlockSpec((tile, bn), o_index),
            scratch_shapes=[pltpu.VMEM((tile, bn), jnp.float32)] * accumulate,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n_dim), x.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(tile_expert, used, jnp.reshape(layer, (1,)).astype(jnp.int32), x,
      *(a for w in stacks for a in (w["q"], w["s"])))


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
    if not kernel:
        return _jnp(x, jax.tree.map(lambda a: a[layer], w), tile_expert, tile)
    return _call(_kernel, [w], x, layer, tile_expert, used, tile, interpret, accumulate=True)


def grouped_gate_up(
    x: jax.Array, w_gate: dict, w_up: dict, activation, layer: jax.Array,
    tile_expert: jax.Array, used: jax.Array, tile: int, kernel: bool, interpret: bool = False,
) -> jax.Array:
    """``activation(x @ gate) * (x @ up)`` over the same buffer,
    [tiles * tile, f]: one call where `gate_up_shared` says a step holds both
    matrices, else two `grouped_matmul` calls and the activation outside
    them."""
    if kernel and gate_up_shared(tile, x.shape[1], w_gate["q"].shape[-1]):
        return _call(
            functools.partial(_gate_up_kernel, activation=activation), [w_gate, w_up],
            x, layer, tile_expert, used, tile, interpret, accumulate=False,
        )
    product = functools.partial(
        grouped_matmul, x, layer=layer, tile_expert=tile_expert, used=used, tile=tile,
        kernel=kernel, interpret=interpret,
    )
    return activation(product(w_gate)) * product(w_up)


def _jnp(x, w, tile_expert, tile: int):
    """The same product through an einsum over the tiles."""
    rows = x.reshape(-1, tile, x.shape[1])
    wq = jnp.take(w["q"], tile_expert, axis=0).astype(x.dtype)  # [tiles, K, N]
    out = jnp.einsum("tmk,tkn->tmn", rows, wq, preferred_element_type=jnp.float32)
    out = out * jnp.take(w["s"], tile_expert, axis=0)
    return out.astype(x.dtype).reshape(-1, out.shape[-1])

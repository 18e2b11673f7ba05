"""The grouped expert product's grid (ops/grouped_matmul.py): which blocks a
shape gets, that the kernel over them is the einsum over the tiles, that
gate and up in one call are the two calls and the activation between them,
and that command-a-plus's shapes lower as they did before the rule changed."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from langstream_tpu.ops import grouped_matmul as gm


def _blocks_at_parent(tile, k_dim, n_dim):
    """`_blocks` as it stood before PR 42: a power of two halved until it divides."""
    def fit(block, n):
        while n % block:
            block //= 2
        return block

    if tile <= 64:
        return fit(4096, k_dim), fit(1024, n_dim)
    return fit(2048, k_dim), fit(512, n_dim)


@pytest.mark.parametrize(
    "shape, blocks, as_at_parent",
    [
        # SDAR's block pass (tiles of 32): an expert's whole matrix, one step a tile
        ((32, 768, 2048), (768, 2048), False),
        ((32, 2048, 768), (2048, 768), False),
        # command-a-plus: a decode step, a segment (its cell's 256, and 512)
        ((16, 4096, 4096), (4096, 1024), True),
        ((256, 4096, 4096), (2048, 512), True),
        ((512, 4096, 4096), (2048, 512), True),
        # SDAR's admission group (tiles of 256): K whole where it was 3 x 256
        ((256, 768, 2048), (768, 1024), False),
        ((256, 2048, 768), (2048, 384), False),
        # a K that no power of two over 128 divides
        ((16, 384, 256), (384, 256), False),
    ],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None,
)
def test_blocks_by_what_fits(shape, blocks, as_at_parent):
    assert gm._blocks(*shape) == blocks
    assert (gm._blocks(*shape) == _blocks_at_parent(*shape)) == as_at_parent
    bk, bn = blocks
    assert shape[1] % bk == 0 and shape[2] % bn == 0 and bk % 128 == 0 and bn % 128 == 0


@pytest.mark.parametrize(
    "shape, shared, says",
    [
        ((32, 2048, 768), True, "blocks 2048x768, steps/tile 1, gate+up shared"),
        ((32, 768, 2048), True, "blocks 768x2048, steps/tile 1, gate+up shared"),
        ((256, 2048, 768), False, "blocks 2048x384, steps/tile 2"),
        ((16, 4096, 4096), False, "blocks 4096x1024, steps/tile 4"),
        ((512, 4096, 4096), False, "blocks 2048x512, steps/tile 16"),
    ],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None,
)
def test_a_shape_says_its_grid(shape, shared, says):
    tile, k_dim, n_dim = shape
    assert gm.gate_up_shared(*shape) == shared
    assert gm.grid_note(*shape, gate_up=True) == (
        f"moe-grouped[tile={tile},k={k_dim},n={n_dim}]", says
    )
    # the down product never says "shared", whatever its blocks
    assert "shared" not in gm.grid_note(*shape)[1]


# (tokens, top-k, held, experts) of the benchmark's configurations at the widths
# their cells run, and what `pass_shape` makes of each: (assignments a pass,
# its tiles) where a layer that holds a share lays out twice its even share,
# None where that is no smaller than the buffer that holds every case
# (`buffer_tiles`, the second number) and the call keeps its one pass
THE_RULE = {
    "kimi-segment": ((2048, 8, 12, 384), (1024, 21), 141),
    "glm-segment": ((2048, 8, 16, 256), (2048, 33), 145),
    "cmdaplus-segment": ((2048, 8, 16, 128), (4096, 33), 81),
    "kimi-decode": ((16, 8, 12, 384), None, 13),
    "glm-decode": ((16, 8, 16, 256), None, 17),
    "cmdaplus-decode": ((16, 8, 16, 128), None, 17),
    # every expert held: twice the even share is every assignment
    "keye-segment": ((2048, 8, 128, 128), None, 193),
    "keye-decode": ((8, 8, 128, 128), None, 129),
    "sdar-block-pass": ((64 * 4, 8, 128, 128), None, 193),
    "sdar-admit-group": ((8 * 256, 8, 128, 128), None, 193),
    # a one-row admission group of a short prompt, and a half-held layer
    "kimi-admit-256": ((256, 8, 12, 384), (128, 21), 141),
    "half-held-segment": ((2048, 2, 4, 8), None, 13),
}


@pytest.mark.parametrize("case", sorted(THE_RULE))
def test_shapes_alone_say_who_takes_the_passes(case):
    (tokens, k, held, n_experts), passes, every = THE_RULE[case]
    tile = gm.row_tile(tokens, k, n_experts)
    assert gm.buffer_tiles(tokens, k, held, tile) == every
    assert gm.pass_shape(tokens, k, held, n_experts, tile) == passes
    key, says = gm.dispatch_note(tokens, k, held, n_experts, tile)
    assert key == f"moe-dispatch[t={tokens},k={k},held={held}/{n_experts}]"
    if passes is None:
        assert says == f"one pass, {every} tiles"
    else:
        size, tiles = passes
        assert says == f"passes of {size}, {tiles} tiles ({every} hold every case)"
        # twice the even share in whole tiles, a partly filled tile an expert, the spare
        assert size % tile == 0 and size >= 2 * tokens * k * held / n_experts > size - tile
        assert tiles == size // tile + held + 1 < every


def test_the_models_that_hold_no_share_never_ask():
    """Mixtral keeps its one-hot dispatch, the dense models have no expert:
    none of them reaches `moe_ffn_held` (or counts its seven)."""
    from langstream_tpu.models.configs import MODEL_PRESETS

    for name in ("mixtral-8x7b", "tiny-moe-test", "llama-3-8b", "olmo-hybrid-7b"):
        assert not MODEL_PRESETS[name].holds_experts, name


def _buffer(tile, k_dim, n_dim, held=6, tokens=40, k=2, layers=2):
    """Rows routed over ``held`` experts (one of them empty), laid out by
    `plan_groups`, and three int8 stacks: more tiles than the rows use."""
    keys = jax.random.split(jax.random.PRNGKey(tile + k_dim + n_dim), 6)

    def stack(key, a, b):
        return {
            "q": jax.random.randint(key, (layers, held, a, b), -127, 128, jnp.int8),
            "s": jax.random.uniform(key, (layers, held, 1, b), jnp.float32, 0.5, 1.5)
            / (127 * np.sqrt(a)),
        }

    expert = jax.random.randint(keys[3], (tokens * k,), 0, held + 1)  # ``held``: no row
    expert = jnp.where(expert == 1, 3, expert)
    tiles = gm.buffer_tiles(tokens, k, held, tile)
    dest, tile_expert, used, sizes = gm.plan_groups(expert, held, tile, tiles)
    assert int(sizes[1]) == 0 and 0 < int(used[0]) < tiles - 1  # some tiles are skipped
    x = jax.random.normal(keys[4], (tokens * k, k_dim), jnp.bfloat16)
    rows = jnp.zeros((tiles * tile, k_dim), jnp.bfloat16).at[dest].set(x, mode="drop")
    grouped = dict(layer=jnp.int32(1), tile_expert=tile_expert, used=used, tile=tile)
    stacks = (
        stack(keys[0], k_dim, n_dim), stack(keys[1], k_dim, n_dim), stack(keys[2], n_dim, k_dim)
    )
    return rows, stacks, grouped, int(used[0]) * tile


@pytest.mark.parametrize(
    "shape",
    [
        (16, 384, 256),  # K no power of two: one block of 384
        (16, 256, 384),
        (128, 4096, 1024),  # two blocks of K, two of N: the accumulator's path
    ],
    ids=lambda v: "x".join(map(str, v)),
)
def test_the_kernel_is_the_einsum_over_the_tiles(shape):
    rows, (w, _, _), grouped, n = _buffer(*shape, tokens=40 if shape[0] == 16 else 160)
    bk, bn = gm._blocks(*shape)
    assert (shape[1] // bk, shape[2] // bn) == ((2, 2) if shape[0] == 128 else (1, 1))
    got = gm.grouped_matmul(rows, w, kernel=True, interpret=True, **grouped)
    want = gm.grouped_matmul(rows, w, kernel=False, **grouped)
    assert got.shape == want.shape == (rows.shape[0], shape[2]) and got.dtype == jnp.bfloat16
    # one float32 sum over K, or two added in float32: the einsum's rounding at most
    np.testing.assert_allclose(
        np.asarray(got[:n], np.float32), np.asarray(want[:n], np.float32), rtol=0, atol=2e-2
    )
    if shape[0] == 16:
        assert bool(jnp.array_equal(got[:n], want[:n]))


# taken at commit e58267c (PR 41) by the code below, under this suite's conftest
# (matmul precision "highest"): command-a-plus's expert
# (4096 x 4096) at its decode step's tile, its segment's, and 512
AT_PARENT = {16: "eb82b9568b06fbcc", 256: "26c68cc2af5dc472", 512: "877a7462de334436"}


@pytest.mark.parametrize("tile", sorted(AT_PARENT))
def test_command_a_plus_s_product_lowers_as_it_did(tile):
    sds = jax.ShapeDtypeStruct
    w = {"q": sds((2, 2, 4096, 4096), jnp.int8), "s": sds((2, 2, 1, 4096), jnp.float32)}
    low = jax.jit(
        lambda x, w, layer, tile_expert, used: gm.grouped_matmul(
            x, w, layer, tile_expert, used, tile, kernel=True, interpret=True
        )
    ).lower(sds((5 * tile, 4096), jnp.bfloat16), w, sds((), jnp.int32), sds((5,), jnp.int32),
            sds((1,), jnp.int32))
    assert hashlib.sha256(low.as_text().encode()).hexdigest()[:16] == AT_PARENT[tile]


def _calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("activation", [jax.nn.silu, lambda x: jax.nn.gelu(x, approximate=True)],
                         ids=["silu", "gelu"])
def test_gate_and_up_in_one_call_are_the_two_calls(activation):
    tile, k_dim, n_dim = 16, 384, 256
    assert gm.gate_up_shared(tile, k_dim, n_dim)
    rows, (w_gate, w_up, w_down), grouped, n = _buffer(tile, k_dim, n_dim)

    def one(rows):
        return gm.grouped_gate_up(rows, w_gate, w_up, activation, kernel=True, interpret=True,
                                  **grouped)

    def two(rows):
        product = lambda w: gm.grouped_matmul(  # noqa: E731
            rows, w, kernel=True, interpret=True, **grouped
        )
        return activation(product(w_gate)) * product(w_up)

    assert (_calls(one, rows), _calls(two, rows)) == (1, 2)
    got, want = np.asarray(one(rows)[:n], np.float32), np.asarray(two(rows)[:n], np.float32)
    exact = np.asarray(
        gm.grouped_gate_up(rows.astype(jnp.float32), w_gate, w_up, activation, kernel=False,
                           **grouped)[:n]
    )
    # two roundings to bf16 fewer: apart by a rounding or two, and no farther
    # from the float32 product than the two calls are
    np.testing.assert_allclose(got, want, rtol=0, atol=4e-2 * np.abs(want).max())
    assert np.abs(got - exact).mean() <= np.abs(want - exact).mean()
    # and the down product over it, as the layer goes on
    down = gm.grouped_matmul(one(rows), w_down, kernel=True, interpret=True, **grouped)
    assert down.shape == rows.shape and bool(jnp.isfinite(down[:n].astype(jnp.float32)).all())


def test_a_matrix_that_is_not_one_block_keeps_its_calls():
    """command-a-plus's shape in kind (a step never holds the whole matrix):
    the gate's and the up's product stay two calls, the activation outside."""
    tile, k_dim, n_dim = 128, 4096, 1024
    assert not gm.gate_up_shared(tile, k_dim, n_dim)
    rows, (w_gate, w_up, _), grouped, n = _buffer(tile, k_dim, n_dim, tokens=160)

    def both(rows, kernel):
        return gm.grouped_gate_up(rows, w_gate, w_up, jax.nn.silu, kernel=kernel, interpret=True,
                                  **grouped)

    assert _calls(lambda r: both(r, True), rows) == 2
    assert _calls(lambda r: both(r, False), rows) == 0
    np.testing.assert_allclose(
        np.asarray(both(rows, True)[:n], np.float32), np.asarray(both(rows, False)[:n], np.float32),
        rtol=0, atol=2e-2,
    )


def test_the_expert_layer_says_which_grid_it_got():
    from langstream_tpu.models import transformer as T
    from langstream_tpu.models.configs import MODEL_PRESETS
    from langstream_tpu.ops.attention import attention_paths

    # lane-aligned widths, so that `pallas` takes the kernel (in interpret mode here)
    wide = dataclasses.replace(
        MODEL_PRESETS["tiny-window-moe-test"], d_model=128, d_ff=256, attention_impl="pallas"
    )
    lp = jax.tree.map(
        lambda a: a[0], T.init_params(wide, jax.random.PRNGKey(0))["layers"]["full_attention"]
    )
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 128), jnp.float32)
    tile = gm.row_tile(16, wide.n_experts_per_tok, wide.n_experts)
    by_kernel, _ = T.moe_ffn_held(u, lp, wide)
    paths = attention_paths()
    assert paths[f"moe-grouped[tile={tile},k=128,n=256]"] == (
        "blocks 128x256, steps/tile 1, gate+up shared"
    )
    assert paths[f"moe-grouped[tile={tile},k=256,n=128]"] == "blocks 256x128, steps/tile 1"
    plain, _ = T.moe_ffn_held(u, lp, dataclasses.replace(wide, attention_impl="jnp"))
    assert float(jnp.abs(by_kernel - plain).max()) < 0.02 * float(jnp.abs(plain).max())

"""Ahead-of-time compiles of the main-path Pallas kernels for a DESCRIBED
TPU v5e (no chip attached): what the installed TPU compiler refuses —
scoped-VMEM overflow, tiling, a Mosaic call GSPMD cannot partition — fails
here, on the CPU tier, instead of on the chip. Nothing runs, so these say
nothing about results or times (chip_smoke.py checks results on a chip).
This file: the kernels alone, the paged decode-side step programs, and the
benchmark cells' device programs WHOLE at the cells' sizes beside their
state (one function a family, its own assertions where they are; the head —
weights and pool by `eval_shape`, the compile as on the chip — and the tail —
the pool aliased in place, the program inside the chip's memory, no leaf
relaid or copied — are `tpu_compile_shared`'s; a new configuration adds its
function at the end). The pinned lowerings are `test_tpu_compile_pinned.py`'s.
(The cells' 17 cases take most of this file's time and stay in it on purpose:
xdist's `loadfile` deals files by their number of cases, largest first, and
a file of few slow cases starts last and is the run's tail.)"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from langstream_tpu.ops import attention as A
from langstream_tpu.parallel.mesh import AXIS_ORDER
from langstream_tpu.parallel.sharding import page_pool_specs
from tpu_compile_shared import *  # noqa: F401,F403 — the fixtures, the cases and the builders


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
    from langstream_tpu.serving import engine as E
    from langstream_tpu.serving.pagepool import table_len_for

    config, slots, seq_len, pages, width = ONE_ROW_GROUPS[case]
    key = KEY
    params = cell_params(config, from_init=bool(config.layer_pattern))
    pool = cell_pool(config, pages, state_rows=slots)
    rows = E.admit_rungs(8)[0]
    args = (
        params, pool, i32(slots), i32(slots), f32(slots), i32(slots), f32(slots), key,
        i32(rows, width), f32(4, rows), i32(rows), i32(rows, table_len_for(seq_len, PAGE)),
    )
    compiled = compile_on_one_chip(
        v5e, monkeypatch, E._make_paged_admit_group(), args, (config, PAGE)
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
    fits_beside_its_state(compiled, pool)


# The LFM2 cell's two programs whole, at its sizes: 256 slots x 10 pages of 64
# (2,560 pages of [4, 64, 128] bf16 in 4 layers), 12 conv layers' tails a slot,
# 8.85 GB of int8 weights. Both must fit the chip beside the pool, take the
# kernels at two heads a lane row and move no whole leaf of pool or tails.
@pytest.mark.parametrize("program", ["_paged_decode_chunk", "admit-1x64", "admit-8x256"])
def test_lfm2_programs_compile_for_v5e_beside_the_cell_s_state(v5e, monkeypatch, program):
    from langstream_tpu.serving import engine as E
    from langstream_tpu.serving.pagepool import table_len_for

    config, slots, seq_len, pages = LFM2, 256, 640, 2560
    key = KEY
    params = cell_params(config, from_init=True)
    assert params["dense_layers"]["conv"]["w_gate"]["q"].shape == (2, 2048, 11776)
    assert params["layers"]["conv"]["w_gate"]["q"].shape == (10, 64, 2048, 1536)
    assert params["layers"]["full_attention"]["w_gate"]["q"].shape == (4, 64, 2048, 1536)
    pool = cell_pool(config, pages, state_rows=slots)
    assert {k: v.shape for k, v in pool.items() if k != "rec"} == {
        "k": (4, pages, 4, PAGE, 128), "v": (4, pages, 4, PAGE, 128)}
    assert {k: v.shape for k, v in pool["rec"].items()} == {"conv": (12, slots, 2 * 2048)}
    table = table_len_for(seq_len, PAGE)
    A._PATHS.clear()
    if program == "_paged_decode_chunk":
        args = (params, i32(slots), i32(slots), pool, i32(slots, table), key, f32(slots),
                i32(slots), f32(slots))
        compiled = compile_on_one_chip(
            v5e, monkeypatch, E._paged_decode_chunk, args, (4, config, PAGE)
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
        compiled = compile_on_one_chip(
            v5e, monkeypatch, E._make_paged_admit_group(), args, (config, PAGE)
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
    memory, pool_bytes, held = fits_beside_its_state(compiled, pool)
    print(program, "arguments", memory.argument_size_in_bytes, "temporaries",
          memory.temp_size_in_bytes, "held", held)


@pytest.mark.parametrize("program", ["_paged_block_chunk", "_block_admit_group"])
def test_block_programs_compile_for_v5e_beside_the_cell_s_state(v5e, monkeypatch, program):
    """The SDAR cell's two device programs whole, at its sizes (64 slots, 704
    pages, 16 passes a chunk; a group of 8 x 256), int8 weights and the pool
    donated: the kernels are in, nothing copies or scatters a leaf of the
    pool or slices a layer's experts out of their stack, and the program fits
    the chip beside its state."""
    from langstream_tpu.serving import engine as E

    slots, pages, table, passes = 64, 704, 11, 16
    key = KEY
    params = cell_params(SDAR)
    pool = cell_pool(SDAR, pages)
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
    compiled = compile_on_one_chip(
        v5e, monkeypatch, getattr(E, program), args, static
    )
    text = compiled.as_text()
    assert A.attention_paths()[path] == kernels[0]
    for kernel in kernels + ("moe_grouped_matmul",):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    experts = [SDAR.n_experts, SDAR.d_model, SDAR.expert_d_ff]
    for shape in (list(pool["k"].shape), experts, experts[:1] + experts[:0:-1]):
        dims = re.escape("[" + ",".join(map(str, shape)) + "]")
        assert not re.search(rf"= \w+{dims}\S* (copy|scatter|dynamic-slice)\(", text), shape
    fits_beside_its_state(compiled, pool)



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
    from langstream_tpu.serving import engine as E

    slots, pages, table, seg = 8, 2176, 272, 2048
    t = table * PAGE
    key = KEY
    params = cell_params(KEYE)
    pool = cell_pool(KEYE, pages)
    assert pool["ik"].shape == (12, pages, PAGE, 128)  # 64 kept at a whole lane row
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
    compiled = compile_on_one_chip(
        v5e, monkeypatch, getattr(E, program), args, static
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
    fits_beside_its_state(compiled, pool)
    # no leaf of the pool is relaid or copied: at a width of 64 the compiler
    # laid the indexer's keys out pages-minor and copied the whole leaf every
    # layer and step (PERF.md section 6, PR 43)
    no_leaf_moved(text, pool.values())


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
    from langstream_tpu.serving import engine as E

    slots, pages, table, seg = 16, 4352, 272, 2048
    t = table * PAGE
    key = KEY
    params = cell_params(GLM)
    assert set(params) == {"embed", "layers", "dense_layers", "final_norm", "lm_head"}
    assert params["dense_layers"]["w_gate"]["q"].shape == (1, 6144, 12288)
    assert params["layers"]["w_gate"]["q"].shape == (6, 16, 6144, 2048)
    assert params["layers"]["wkv_b"]["q"].shape == (6, 512, 64 * 448)
    pool = cell_pool(GLM, pages)
    assert {k: v.shape for k, v in pool.items()} == {
        "lat": (7, pages, 1, PAGE, 640), "ik": (7, pages, PAGE, 128),
    }
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
    compiled = compile_on_one_chip(
        v5e, monkeypatch, getattr(E, program), args, static
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
    memory, pool_bytes, held = fits_beside_its_state(compiled, pool)
    assert pool_bytes == pages * PAGE * GLM.kv_bytes_per_token()
    print(program, "temp", memory.temp_size_in_bytes, "args", memory.argument_size_in_bytes)
    no_leaf_moved(text, pool.values())


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
    from langstream_tpu.serving import engine as E

    slots, pages, table, seg = 16, 4352, 272, 2048
    t = table * PAGE
    key = KEY
    params = cell_params(KIMI)
    assert set(params) == {"embed", "layers", "dense_layers", "final_norm", "lm_head"}
    assert params["dense_layers"]["w_gate"]["q"].shape == (1, 7168, 18432)
    assert params["layers"]["w_gate"]["q"].shape == (6, 12, 7168, 2048)
    assert params["layers"]["wq_b"]["q"].shape == (6, 1536, 64 * 192)
    assert params["layers"]["wkv_b"]["q"].shape == (6, 512, 64 * 256)
    assert params["layers"]["wo"]["q"].shape == (6, 64 * 128, 7168)
    assert params["layers"]["router"].shape == (6, 7168, 384)
    assert "wq_idx" not in params["layers"]
    pool = cell_pool(KIMI, pages)
    assert {k: v.shape for k, v in pool.items()} == {"lat": (7, pages, 1, PAGE, 640)}
    assert KIMI.yarn_blend == (8, 20) and abs(KIMI.attn_scale - 0.144680) < 1e-6
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
    compiled = compile_on_one_chip(
        v5e, monkeypatch, getattr(E, program), args, static
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
    memory, pool_bytes, held = fits_beside_its_state(compiled, pool)
    assert pool_bytes == pages * PAGE * KIMI.kv_bytes_per_token() == pages * PAGE * 8960
    print(program, "temp", memory.temp_size_in_bytes, "args", memory.argument_size_in_bytes)
    no_leaf_moved(text, [pool["lat"]])


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
    from langstream_tpu.models.transformer import latent_window_band
    from langstream_tpu.serving import engine as E
    from langstream_tpu.serving.pagepool import window_group_pages

    slots, pages, table, seg = 16, 4352, 272, 2048
    t = table * PAGE
    window_pages, ring = window_group_pages(DOTS3, slots, t, PAGE, seg)
    assert (window_pages, ring) == (16 * 41, 41)
    band = latent_window_band(seg, t, 513, PAGE)
    assert band == 3072
    key = KEY
    params = cell_params(DOTS3)
    full, window = (params["layers"][k] for k in ("full_attention", "sliding_attention"))
    assert params["dense_layers"]["full_attention"]["w_gate"]["q"].shape == (1, 5120, 13824)
    assert full["w_gate"]["q"].shape == (2, 16, 5120, 1536)
    assert window["w_gate"]["q"].shape == (6, 16, 5120, 1536)
    assert full["wkv_b"]["q"].shape == (2, 512, 128 * 256) and "wq_idx" in full
    assert window["wkv_b"]["q"].shape == (6, 1024, 64 * 320) and "wq_idx" not in window
    assert full["w_attn_gate"]["q"].shape == (2, 5120, 128)
    pool = cell_pool(DOTS3, pages, window_pages=window_pages)
    assert {k: v.shape for k, v in pool.items() if k != "win"} == {
        "lat": (3, pages, 1, PAGE, 640), "ik": (3, pages, PAGE, 128),
    }
    assert {k: v.shape for k, v in pool["win"].items()} == {
        "lat": (6, window_pages, 1, PAGE, 1152),
    }
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
    compiled = compile_on_one_chip(
        v5e, monkeypatch, getattr(E, program), args, static
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
    memory, pool_bytes, held = fits_beside_its_state(compiled, pool)
    assert pool_bytes == (
        pages * PAGE * DOTS3.kv_bytes_per_token()
        + window_pages * PAGE * DOTS3.kv_bytes_per_token(kind="sliding_attention")
    )
    print(program, "temp", memory.temp_size_in_bytes, "args", memory.argument_size_in_bytes)
    no_leaf_moved(text, jax.tree.leaves(pool))


def test_window_segment_program_compiles_for_v5e_beside_the_cell_s_state(v5e, monkeypatch):
    """The command-a-plus cell's segment program whole, at its sizes (a
    2,048-token segment of one row against 196 pages a table, the full
    layers' group of 3,136 pages and the window layers' of 1,552, each
    through its own table), int8 weights and the pool donated: every layer's
    K and V reach their group by whole pages (`paged_insert_pages`, PR 48),
    the window and the full layers read through `flash_segment_attention`,
    no leaf of either group is copied or relaid, and the program fits the
    chip beside its state."""
    from langstream_tpu.serving import engine as E

    pages, window_pages, table, seg = 3136, 1552, 196, 2048
    key = KEY
    params = cell_params(CMDA, from_init=True)
    pool = cell_pool(CMDA, pages, window_pages=window_pages)
    assert pool["k"].shape == (2, pages, 8, PAGE, 128)
    assert pool["win"]["k"].shape == (6, window_pages, 8, PAGE, 128)
    args = (params, i32(1, seg), i32(1), i32(1), pool, i32(2, 1, table), key,
            f32(1), i32(1), f32(1))
    compiled = compile_on_one_chip(
        v5e, monkeypatch, E._paged_segment_and_sample, args, (CMDA, PAGE)
    )
    text = compiled.as_text()
    paths = A.attention_paths()
    assert paths[f"paged-segment-write[s={seg}]"] == "paged_insert_pages"
    assert paths[f"paged-segment[s={seg},t={table * PAGE}]"] == "flash_segment_attention"
    for kernel in ("paged_insert_pages", "flash_segment_attention", "moe_grouped_matmul"):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    fits_beside_its_state(compiled, pool)
    no_leaf_moved(text, jax.tree.leaves(pool))

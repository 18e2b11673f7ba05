"""Pinned lowerings and noted paths: what the shared kernels hand Mosaic
for a DESCRIBED TPU v5e, what the other models' programs lower to, and which
paths every preset notes, each as an earlier PR left it (the tables below
say which): a PR that does not mean to move them holds them still."""

import base64
import dataclasses
import functools
import hashlib
import importlib.util
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from langstream_tpu.models.configs import MODEL_PRESETS
from langstream_tpu.ops import attention as A
from tpu_compile_shared import *  # noqa: F401,F403 — the fixtures, the cases and the builders


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

"""Ahead-of-time compiles of the main-path Pallas kernels for a DESCRIBED
TPU v5e (no chip attached): what the installed TPU compiler refuses —
scoped-VMEM overflow, tiling, a Mosaic call GSPMD cannot partition — fails
here, on the CPU tier, instead of on the chip. Nothing runs, so these say
nothing about results or times (chip_smoke.py checks results on a chip)."""

import dataclasses
import os
import re

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


def _dense_args(config, s, t, int8):
    """(q, k, v, offset) shapes of a prefill (t == s) or segment call."""
    h, hkv, d = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    q = SDS((1, s, h, d), jnp.bfloat16)
    if int8:
        kv = {"q": SDS((1, hkv, t, d), jnp.int8), "s": SDS((1, hkv, t), jnp.float32)}
    else:
        kv = SDS((1, hkv, t, d), jnp.bfloat16)
    return q, kv, kv, SDS((1,), jnp.int32)


def _paged_args(config, int8):
    """(q, k, v, lengths, table) shapes of a paged decode call."""
    h, hkv, d = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    q = SDS((BATCH, h, d), jnp.bfloat16)
    if int8:
        kv = {
            "q": SDS((PAGES, hkv, PAGE, d), jnp.int8),
            "s": SDS((PAGES, hkv, PAGE), jnp.float32),
        }
    else:
        kv = SDS((PAGES, hkv, PAGE, d), jnp.bfloat16)
    return q, kv, kv, SDS((BATCH,), jnp.int32), SDS((BATCH, TABLE), jnp.int32)


def _prefill(config, s):
    return (
        lambda q, k, v, _off: A.flash_prefill_attention(q, k, v, config),
        _dense_args(config, s, s, int8=False),
    )


def _segment(config, s, t, int8):
    fn = A.flash_segment_attention_int8 if int8 else A.flash_segment_attention
    return (
        lambda q, k, v, off: fn(q, k, v, off, config),
        _dense_args(config, s, t, int8),
    )


def _paged(config, int8):
    fn = (
        A.ragged_paged_decode_attention_int8 if int8
        else A.ragged_paged_decode_attention
    )
    return (
        lambda q, k, v, lens, table: fn(q, k, v, lens, table, config, PAGE),
        _paged_args(config, int8),
    )


CASES = {
    # the shapes the compiler refused before _vmem_block_q counted the K/V
    # buffers and the score tiles (gemma-2b: G=8, D=256)
    **{f"gemma-prefill-{s}": _prefill(GEMMA, s) for s in (512, 1024, 2048)},
    **{f"gemma-segment-{s}": _segment(GEMMA, s, 4 * s, False) for s in (512, 1024, 2048)},
    **{f"gemma-segment-int8-{s}": _segment(GEMMA, s, 4 * s, True) for s in (512, 1024, 2048)},
    "llama-prefill-2048": _prefill(LLAMA, 2048),
    "llama-segment-2048": _segment(LLAMA, 2048, 8192, False),
    "llama-segment-int8-2048": _segment(LLAMA, 2048, 8192, True),
    "gemma-paged-decode": _paged(GEMMA, False),
    "gemma-paged-decode-int8": _paged(GEMMA, True),
    "llama-paged-decode": _paged(LLAMA, False),
    "llama-paged-decode-int8": _paged(LLAMA, True),
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
        "segment": "flash_segment_attention",
        "segment-int8": "flash_segment_attention_int8",
        "paged-decode": "ragged_paged_decode_attention",
        "paged-decode-int8": "ragged_paged_decode_attention_int8",
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

    pool = page_pool_specs(config.n_kv_heads, mesh)[1:]  # one layer's entry
    kv = {"q": on(*pool), "s": on(*pool[:-1])}
    shardings = (on(None, "model", None), kv, kv, on(), on())
    compiled = jax.jit(fn).lower(*_placed(args, shardings)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%ragged_paged_decode_attention_int8" in text
    # independent per kv head: the shard_map body needs no collective
    assert "all-reduce" not in text and "all-gather" not in text
    # each chip holds a quarter of the pool (k and v: int8 values + scales)
    pool_bytes = 2 * PAGES * config.n_kv_heads * PAGE * (config.resolved_head_dim + 4)
    assert compiled.memory_analysis().argument_size_in_bytes < 1.1 * pool_bytes / 4


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

"""chip_smoke.py between chip runs: its phases at tiny-test size on the CPU
tier, with the device check injected from here (the script itself has no
CPU option — `python chip_smoke.py` without a TPU must fail)."""

import asyncio
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from langstream_tpu.models.configs import MODEL_PRESETS

REPO = Path(chip_smoke.__file__).resolve().parent


def _lines(capsys) -> list[dict]:
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()]


def test_serve_phase_at_tiny_size(capsys):
    spec = chip_smoke.ServeSpec(
        model="tiny-test", max_batch=4, max_seq_len=256, decode_chunk=4,
        sessions=2, turns=2, long_question_chars=150,
    )
    asyncio.run(chip_smoke.serve_phase(spec, "cpu", chip_smoke.CacheCounts()))
    setup, serve = _lines(capsys)
    assert setup["phase"] == "serve-setup" and serve["phase"] == "serve"
    assert serve["requests"] == 5 == len(serve["generated_tokens"])
    assert serve["placed"] == {"params": ["cpu"], "page_pool": ["cpu"]}
    assert max(serve["prompt_lens"]) >= 150
    # off-TPU `auto` never selects a kernel — and the report says so by name
    assert serve["attention_paths"]["paged-decode[s=1,t=256]"] == "jnp"
    assert set(serve["engine"].values()) == {0}


def test_serve_phase_fails_when_state_is_on_the_wrong_device():
    spec = chip_smoke.ServeSpec(
        model="tiny-test", max_batch=2, max_seq_len=128, decode_chunk=4,
        sessions=1, turns=1, long_question_chars=40,
    )
    with pytest.raises(AssertionError, match="expected kernels|not on tpu"):
        asyncio.run(chip_smoke.serve_phase(spec, "tpu", chip_smoke.CacheCounts()))


@pytest.mark.slow  # ~12 s of interpret-mode compiles
def test_kernel_checks_in_interpret_mode():
    config = dataclasses.replace(MODEL_PRESETS["tiny-test"], dtype="float32")
    checks = chip_smoke.kernel_checks(
        config, prefill_lens=(16,), paged=(6, 8, 3),
        interpret=True,
    )
    # prefill, paged decode, the step's write and the group's insert (k and v), int8
    assert len(checks) == 7
    assert all(c["max_abs_err"] <= chip_smoke.KERNEL_ERR_BOUND for c in checks)
    assert [c["max_abs_err"] for c in checks if "paged_kv_write" in c["kernel"]] == [0.0, 0.0]
    assert [c["max_abs_err"] for c in checks if "paged_insert_pages" in c["kernel"]] == [0.0, 0.0]


def test_without_a_tpu_it_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs 1 TPU chip" in proc.stderr


def test_importing_a_launcher_initialises_no_jax_backend():
    """One process per chip: the gateway, control-plane, k8s and CLI
    processes import jax (serving/__init__ → adapters) but must never
    initialise a backend — on a chip machine that would take the TPU from
    the agent runtime."""
    modules = [
        "langstream_tpu.gateway.server", "langstream_tpu.k8s",
        "langstream_tpu.cli.main", "langstream_tpu.entrypoint",
        "langstream_tpu.webservice.server", "langstream_tpu.grpc_runtime.bridge",
        "langstream_tpu.serving", "langstream_tpu.ai.tpu_serving",
    ]
    code = (
        "import importlib, sys\n"
        f"for m in {modules!r}: importlib.import_module(m)\n"
        "from jax._src import xla_bridge\n"
        "sys.exit(1 if xla_bridge.backends_are_initialized() else 0)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.slow
def test_mesh_phase_on_four_virtual_devices(capsys):
    spec = chip_smoke.MeshSpec(
        model="tiny-test", max_batch=2, max_seq_len=128, bucket=64,
        decode_chunk=4, new_tokens=8, prompts=2,
    )
    chip_smoke.mesh_phase(spec, "cpu", chip_smoke.CacheCounts())
    mesh, versus = _lines(capsys)
    assert [s["shards"] for s in mesh["shards"]] == [4, 4, 4]
    assert max(versus["first_token_logits_max_rel_err"]) <= chip_smoke.MESH_LOGITS_REL_TOL

"""The one rule for where compiled programs persist
(serving/engine.enable_persistent_compile_cache): JAX_COMPILATION_CACHE_DIR
when set — no directory set in code, the knob does not override it — else
the `compile-cache-dir` knob, else a fixed directory inside the checkout
(on an accelerator; on the CPU backend the cache then stays off).
conftest's _restore_compile_cache undoes each case's settings."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from langstream_tpu.serving.engine import (
    DEFAULT_COMPILE_CACHE_DIR,
    enable_persistent_compile_cache,
)

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("case", ["variable", "knob", "neither", "neither-on-cpu"])
def test_cache_directory_rule(case, tmp_path, monkeypatch):
    knob = str(tmp_path / "knob")
    set_before = jax.config.jax_compilation_cache_dir
    if case == "variable":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "variable"))
        # JAX read the variable (here: its absence) at import; with it set,
        # the program sets NO directory — not the knob's, not the default
        assert enable_persistent_compile_cache(knob) == set_before
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        if case == "neither":  # as on the chip: the backend is steered here
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        want = {
            "knob": knob, "neither": DEFAULT_COMPILE_CACHE_DIR,
            "neither-on-cpu": set_before,
        }[case]
        assert enable_persistent_compile_cache(knob if case == "knob" else None) == want
        assert jax.config.jax_compilation_cache_dir == want
    # cache-everything thresholds in all three cases
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_default_directory_is_fixed_inside_the_checkout_and_ignored():
    assert Path(DEFAULT_COMPILE_CACHE_DIR) == REPO / ".jax_compile_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_compile_cache/" in ignored


_ENGINE_ONCE = """
import json, sys
import jax
from chip_smoke import CacheCounts
cache = CacheCounts()
jax.monitoring.register_event_listener(cache)
from langstream_tpu.ai.tpu_serving import _EngineHolder
from langstream_tpu.models.configs import GenerationOptions
holder = _EngineHolder({
    "model": "tiny-test", "compile-cache-dir": sys.argv[1], "max-batch": 2,
    "max-seq-len": 64, "prefill-buckets": (16,), "decode-chunk": 4,
})
holder.engine().generate([3, 4, 5], GenerationOptions(max_new_tokens=4, temperature=0.0))
holder.close()
print(json.dumps(cache.report()))
"""


def test_variable_wins_and_a_fresh_process_hits(tmp_path):
    """Two fresh processes with the variable set (and a knob that must
    lose): the first fills the variable's directory and no other, the second
    compiles the same programs out of it."""
    variable, knob = tmp_path / "variable", tmp_path / "knob"
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(variable),
        PYTHONPATH=str(REPO),
    )

    def engine_once() -> dict:
        proc = subprocess.run(
            [sys.executable, "-c", _ENGINE_ONCE, str(knob)], env=env, cwd=tmp_path,
            capture_output=True, text=True, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.splitlines()[-1])

    cold = engine_once()
    assert cold["dir"] == str(variable) and cold["misses"] > 0
    entries = set(variable.iterdir())
    assert entries and not knob.exists()
    assert not (tmp_path / ".jax_compile_cache").exists()
    warm = engine_once()
    assert warm["dir"] == str(variable)
    assert warm["hits"] > 0 and warm["misses"] == 0
    assert set(variable.iterdir()) == entries

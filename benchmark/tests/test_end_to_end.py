"""The whole command at a tiny size on the CPU: one dense chat cell with
`--trace 0`, one MoE drain cell with `--trace 1`, and the refusals."""

import asyncio
import json
import subprocess
import sys
from pathlib import Path

import pytest

import run

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).parent / "data"
REAL = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CPU_PLANES = {"device_plane": "/host:CPU", "ops_line": "tf_XLAPjRtCpuClient"}


def tiny_bench(cell: str, config: str, traffic: str, stands_for: str) -> dict:
    """BENCHMARK.json's metric entries, with the tiny cell in the place of
    the real one it stands for."""
    def retarget(entries):
        kept = [m for m in entries if stands_for in m.get("workloads", [stands_for])]
        return [{**m, "workloads": [cell]} if "workloads" in m else m for m in kept]

    return {
        "workloads": [{"name": cell, "config": config, "traffic": traffic, "chips": 1}],
        "end_to_end": retarget(REAL["end_to_end"]),
        "per_layer": retarget(REAL["per_layer"]),
    }


def run_tiny(bench, cell, trace):
    from langstream_tpu.messaging.memory import MemoryBroker

    MemoryBroker.reset()
    return asyncio.run(run.run_cell(
        bench, cell, 2**31 + 5, 6.0, trace, platform="cpu", files=DATA,
        trace_planes=CPU_PLANES,
    ))


def test_dense_chat_cell_end_to_end():
    bench = tiny_bench("tiny-dense-chat", "tiny-dense", "tiny-chat", "mistral7b-chat-steady")
    out = run_tiny(bench, "tiny-dense-chat", trace=False)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 24
    assert set(out["metrics"]) == {"ttft_p50_ms", "ttft_mean_ms", "tpot_mean_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert "busy_s" not in out["device"]
    # what `correct` rests on, each number beside its limit, as the line's last key
    assert list(out)[-1] == "compared" and len(out["compared"]) >= 6
    assert all(value <= limit for value, limit in out["compared"].values())


def test_moe_drain_cell_traced():
    bench = tiny_bench("tiny-moe-drain", "tiny-moe", "tiny-drain", "mixtral8x7b-d6-decode-drain")
    out = run_tiny(bench, "tiny-moe-drain", trace=True)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert {"decode_step_ms.drain", "active_slots_mean", "kv_pages_peak_share"} <= set(out["metrics"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("workload", [w["name"] for w in REAL["workloads"]])
def test_without_a_tpu_the_command_refuses(workload):
    done = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", workload, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env={"JAX_PLATFORMS": "cpu", "PATH": ""},
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_every_named_file_exists():
    """Everything BENCHMARK.json names is a file the harness finds by name."""
    for cell in REAL["workloads"]:
        for kind, name in (("workloads", cell["name"]), ("traffic", cell["traffic"]),
                           ("configs", cell["config"])):
            assert (BENCH / kind / f"{name}.json").is_file(), (kind, name)
        traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
        assert (BENCH / "traffic_kinds" / f"{traffic['kind']}.py").is_file()
        config = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
        for kind in ("families", "reference"):
            assert (BENCH / kind / f"{config['family']}.py").is_file(), (kind, config["family"])
    for metric in REAL["per_layer"]:
        definition = run.metric_definition(metric["name"])
        assert (BENCH / "readers" / f"{definition['reader']}.py").is_file()
        if "kernel" in definition:
            assert (BENCH / "reduce" / "kernels" / f"{definition['kernel']}.json").is_file()
        assert metric["moves"] in {m["name"] for m in REAL["end_to_end"]}

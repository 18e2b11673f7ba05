"""The benchmark's families, guarded by tier-1: the fast cases of
`benchmark/tests/test_families.py` (Mistral, Mixtral: the fields each
configuration maps onto, the seeded trees bit-equal to what they were, what
the program's block cannot express refused, every family's two files) and of
`benchmark/tests/test_olmo_hybrid_family.py` (Olmo-Hybrid: the README's
contract, the mapping, the refusals, the seeded tree, the update's cost, the
state probe) and of `benchmark/tests/test_cohere2_moe_family.py` (Command A+:
the contract, the mapping and the stated cut, the cell's sizing, the
refusals, the seeded tree, the costs and the readers) and of
`benchmark/tests/test_sdar_moe_family.py` (SDAR-MoE: the contract with the
three exports of an engine that fills blocks, the catalog's keys and the
stated cut, the cell's sizing, the refusals, the seeded tree, a trajectory
from tokens and labels, the cost and the metric files) and of
`benchmark/tests/test_keye_vl2_family.py` (Keye-VL-2.0: the contract, the
catalog's keys with the nested groups whole and the stated cut, the cell's
sizing, the refusals, the seeded tree against the program's own, the costs
and the metric files; its names all say `keye`, since the cases here share
one namespace) and of
`benchmark/tests/test_glm_moe_dsa_family.py` (GLM-5: the contract, the
catalog's keys and the stated cut of four, the cell's sizing, the refusals,
the seeded tree against the program's own, the chain's halves by kind, the
costs and the metric files; its names all say `glm`) and of
`benchmark/tests/test_kimi_k2_family.py` (Kimi-K2.5: the contract, the
catalog's keys with `rope_scaling` whole and the stated cut of three, the
cell's sizing and its metrics, the refusals, the seeded tree against the
program's own, the chain's halves; its names all say `kimi`) with
`benchmark/tests/test_latent_dense_attention_cost.py` (the dense latent
read's two cost functions and the new metric files, against hand counts) and
of `benchmark/tests/test_lfm2_moe_family.py` (LFM2-24B-A2B: the contract, the
catalog's keys and the stated cut of two, the deployment's bytes against the
family's tree, the cell's sizing and its metrics, the refusals, the seeded
tree against the program's own, the chain's halves in three kinds, the costs
against hand counts, the metric files against the program's scopes, the
engine's state against the check block; its names all say `lfm2`) and of
`benchmark/tests/test_dots3_note_family.py` (dots3-note-prev: the contract, the
catalog's keys and the stated cut of four with the first nine `layer_types`,
each kind's geometry off one file, the cell's sizing and its metrics, the
refusals, the seeded tree against the program's own, the chain's halves in
three kinds, the costs against hand counts; its names all say `dots3`) and
the cases of
`benchmark/tests/test_request_readers.py` (the clock between a profile and the
spans, a first token's stages, the device's idle by what the engine held; one
of them records a profile of a small engine) run here as they stand
there. The check's verdicts (an engine a case) stay with the
harness's own suite, run by hand: `python -m pytest benchmark/tests`."""

import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
for path in (str(BENCH.parent), str(BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)

# the cases that build an engine and run the whole check: minutes, by hand
BY_HAND = ("test_sound_system_passes_with_room", "test_known_fault_fails_by_a_number",
           "test_the_tiny_cell_end_to_end_traced")


def _cases(file: str, bench_as_of: int = 0) -> dict:
    """``bench_as_of``: the file's cases read BENCHMARK.json as it stood when
    the benchmark held that many cells (a family's file that counts the
    benchmark's cells and names its own metrics "the last five" is a file of
    the benchmark, which a later PR adds to and may not edit)."""
    spec = importlib.util.spec_from_file_location(f"benchmark_{file}", BENCH / "tests" / f"{file}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if bench_as_of:
        module.BENCH = _benchmark_at(module.BENCH, bench_as_of)
    return {
        name: case for name, case in vars(module).items()
        if name.startswith("test_") and name not in BY_HAND
    }


def _benchmark_at(bench: dict, cells: int) -> dict:
    """BENCHMARK.json without what was appended behind its first ``cells``
    cells: their configurations, and the metrics that only they report."""
    kept = bench["workloads"][:cells]
    names, configs = {w["name"] for w in kept}, {w["config"] for w in kept}
    return {
        **bench, "workloads": kept,
        "configs": [c for c in bench["configs"] if c["name"] in configs],
        "per_layer": [
            m for m in bench["per_layer"] if "workloads" not in m or names & set(m["workloads"])
        ],
    }


globals().update(_cases("test_families"))
globals().update(_cases("test_olmo_hybrid_family"))
globals().update(_cases("test_cohere2_moe_family"))
globals().update(_cases("test_sdar_moe_family"))
globals().update(_cases("test_keye_vl2_family"))
globals().update(_cases("test_glm_moe_dsa_family"))
# (its cell's case counts nine cells and eight configurations: PR 50's benchmark)
globals().update(_cases("test_kimi_k2_family", bench_as_of=9))
# (its cell's case holds its metrics to the END of `per_layer`: PR 55's benchmark, ten cells)
globals().update(_cases("test_lfm2_moe_family", bench_as_of=10))
globals().update(_cases("test_dots3_note_family"))
globals().update(_cases("test_latent_dense_attention_cost"))
globals().update(_cases("test_request_readers"))

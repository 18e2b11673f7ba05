"""The trace reduction, on synthetic intervals and on a small recorded
trace kept beside this file (a CPU-backend trace: the reduction is told its
plane names; on the chip the defaults name the TPU's)."""

from pathlib import Path

import pytest

from reduce import costs
from reduce.xplane import _self_times, _union, reduce_trace

TRACE = Path(__file__).parent / "data" / "cpu_small.xplane.pb"


def test_union_merges_overlaps():
    assert _union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_a_loop_keeps_only_its_own_time():
    events = [(0, 100, "while"), (10, 30, "fusion.1"), (40, 90, "fusion.2"), (50, 60, "inner")]
    own = dict(_self_times(events))
    assert own == {"while": 30, "fusion.1": 20, "fusion.2": 40, "inner": 10}


def test_recorded_trace_reduces():
    out = reduce_trace(TRACE, device_plane="/host:CPU", ops_line="tf_XLAPjRtCpuClient")
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_planes"] == 1
    names = [name for name, _ in out["device_ops"]]
    assert any(name.startswith("dot_general") for name in names)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError, match="no operation ran on the device"):
        reduce_trace(TRACE)  # the default planes are the TPU's


def test_costs_and_roofline():
    c = costs.prefill_attention(rows=8, width=2048, n_heads=32, n_kv_heads=8, head_dim=128)
    assert c["ops"] == 4 * 8 * 32 * (2048 * 2049 // 2) * 128
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert costs.roofline_seconds(c, peaks)[1] == "compute"
    assert costs.roofline_seconds({"ops": 1, "bytes": 10**9}, peaks)[1] == "memory"


def test_hlo_lines_are_shortened_and_the_prefill_kernel_is_read_from_its_shapes():
    from readers import trace_kernel
    from reduce.xplane import short_name

    line = (
        "%closed_call.11 = bf16[8,8,4,1024,128]{4,3,2,1,0:T(8,128)(2,1)} custom-call("
        "bf16[8,8,4,1024,128]{4,3,2,1,0} %q, bf16[8,8,1024,128]{3,2,1,0} %k), "
        'custom_call_target="tpu_custom_call"'
    )
    decode = "%closed_call.17 = bf16[64,8,4,128]{3,2,1,0} custom-call(s32[64]{0} %lengths)"
    assert short_name(line) == "%closed_call.11 custom-call bf16[8,8,4,1024,128]"
    tuple_line = "%fusion.289 = (f32[4,2048]{1,0:T(4,128)S(1)}, bf16[4,2048,4096]{2,1,0}) fusion(bf16[4] %x)"
    assert short_name(tuple_line) == "%fusion.289 fusion f32[4,2048]"
    assert short_name("ThunkExecutor::Execute") == "ThunkExecutor::Execute"
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cost = costs.prefill_attention(rows=8, width=1024, n_heads=32, n_kv_heads=8, head_dim=128)
    least = costs.roofline_seconds(cost, peaks)[0]
    ctx = {"peaks": peaks, "trace": {"ops": {
        line: {"seconds": 4 * least * 32, "calls": 32.0},
        decode: {"seconds": 1.0, "calls": 1327.0},
    }}}
    definition = {"kernel": "flash_prefill_attention"}
    assert trace_kernel.read(definition, ctx) == pytest.approx(25.0)
    with pytest.raises(FileNotFoundError, match="no_such_kernel"):  # reduce/kernels/<kernel>.json
        trace_kernel.read({"kernel": "no_such_kernel"}, ctx)
    assert trace_kernel.read(definition, {"trace": None}) is None


def test_a_program_device_time_is_read_per_execution():
    from readers import trace_module

    trace = {"modules": {
        "jit_admit_group": {"seconds": 3.0, "calls": 2},
        "jit__paged_decode_chunk": {"seconds": 1.0, "calls": 10},
    }}
    definition = {"module": "admit_group", "scale": 1000}
    assert trace_module.read(definition, {"trace": trace}) == pytest.approx(1500.0)
    assert trace_module.read({"module": "no_such"}, {"trace": trace}) is None
    assert trace_module.read(definition, {"trace": None}) is None


def test_the_recorded_trace_has_no_modules_line_and_says_so():
    out = reduce_trace(TRACE, device_plane="/host:CPU", ops_line="tf_XLAPjRtCpuClient")
    assert out["modules"] == {} and out["device_lines"]

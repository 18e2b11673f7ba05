"""The Olmo-Hybrid family held to the README's contract ("A family"), the
cost of `reduce/gated_delta_cost.py` against hand counts, and the faults its
correctness check must catch, each by a number, at a tiny size on the CPU.

The fast cases here (everything but the check's verdicts) are also run by the
repo's tier-1 through `tests/test_benchmark_families.py`.

The check's cases serve the tiny configuration in float32 (the family's
`ModelConfig` with `dtype` replaced): at d = 64 the bf16 rounding of one
activation moves a decay exp(-A softplus(a)) with A up to 16 by percents, so
a bf16 run of this size reads 0.05 to 0.13 on the hot path with nothing wrong
(my CPU run, PR 32), and no fault could be told from it. In float32 a sound
system reads 1e-5 and every fault below reads a hundred times its tolerance.
"""

import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from check import run_check
from modelcfg import load_json, load_module, model_config, register_preset
from reduce import gated_delta_cost as cost

DATA = Path(__file__).parent / "data"
TINY = "tiny-olmo-hybrid"
REAL = "olmo-hybrid-7b-int8"
ENGINE = {"max-batch": 4, "max-seq-len": 256, "prefill-buckets": [32, 64],
          "kv-pages": 64, "page-size": 16, "tokenizer": "byte"}
family = load_module("families", "olmo_hybrid")
PERIOD = ["linear_attention"] * 3 + ["full_attention"]


# -- the README's contract ----------------------------------------------------


def test_the_family_exports_what_the_readme_lists():
    for name in ("model_config", "make_params", "reference_dims", "system_chain",
                 "ref_layer_params", "hot_path", "engine_state", "expected_kernels",
                 "state_leaves"):
        assert callable(getattr(family, name)), name
    reference = load_module("reference", "olmo_hybrid")
    assert all(callable(getattr(reference, name)) for name in ("embed", "layer", "unembed"))


def test_the_real_configuration_maps_onto_its_fields():
    config = model_config(load_json("configs", REAL), REAL)
    assert (config.d_model, config.n_layers, config.d_ff, config.vocab_size) == (3840, 32, 11008, 100352)
    assert (config.n_heads, config.n_kv_heads, config.resolved_head_dim) == (30, 30, 128)
    assert config.layer_pattern == tuple(PERIOD) and config.n_periods == 8
    assert (config.linear_n_heads, config.linear_key_head_dim, config.linear_value_head_dim) == (30, 96, 192)
    assert config.linear_conv_kernel == 4 and config.linear_allow_neg_eigval
    assert config.output_norm and config.qk_norm and not config.rope
    assert config.n_layers_of("full_attention") == 8 and config.n_layers_of("linear_attention") == 24


def test_the_published_keys_are_the_catalog_s():
    spec = load_json("configs", REAL)
    assert spec["reduced"] == [] and spec["family"] == "olmo_hybrid"
    assert spec["layer_types"] == PERIOD * 8 and spec["rope_parameters"] == {"rope_theta": None}
    assert {"norm_placement", "qk_norm_width", "rope_theta_null", "head_dim"} <= set(spec["assumed"])
    assert spec["check"]["state_dtype"] == "float32" and spec["check"]["new_tokens"] == 32
    assert all(n % 64 for n in spec["check"]["lengths"])  # inside a wider bucket


@pytest.mark.parametrize(
    "change, says",
    [
        ({"layer_types": ["full_attention"] * 8}, "period"),
        ({"layer_types": PERIOD * 2, "num_hidden_layers": 6}, "period"),
        ({"rope_parameters": {"rope_theta": 500000.0}}, "turn nothing"),
        ({"attention_bias": True}, "bias"),
        ({"linear_num_key_heads": 2}, "key heads"),
        ({"sliding_window": 4096}, "sliding_window"),
    ],
    ids=["not-the-period", "not-whole-periods", "a-rope-base", "bias", "key-heads", "unknown-key"],
)
def test_what_the_program_cannot_express_is_refused(change, says):
    with pytest.raises(ValueError, match=says):
        model_config({**load_json("configs", TINY, DATA), **change}, TINY)


def test_the_dims_read_back_from_the_config_are_the_file_s():
    for name, root in ((REAL, None), (TINY, DATA)):
        spec = load_json("configs", name, *([root] if root else []))
        assert family._dims_of(model_config(spec, name)) == family.reference_dims(spec)


def test_seeded_weights_are_the_served_tree():
    config = model_config(load_json("configs", TINY, DATA), TINY)
    tree = family.make_params(config, 0)
    again = family.make_params(config, 0)
    assert all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(again)))
    linear, full = tree["layers"]["linear_attention"], tree["layers"]["full_attention"]
    for kind, keys in family.QUANTIZED.items():
        for key in keys:
            leaf = tree["layers"][kind][key]
            assert leaf["q"].dtype == jnp.int8 and leaf["s"].shape[-2] == 1, (kind, key)
    assert linear["wqkv"]["q"].shape == (6, 64, 2 * 32 + 64) and full["wq"]["q"].shape == (2, 64, 64)
    for key in ("wa", "wb", "conv_w", "A_log", "dt_bias", "attn_norm", "out_norm", "ffn_norm"):
        assert not isinstance(linear[key], dict), key
    assert linear["A_log"].dtype == linear["dt_bias"].dtype == jnp.float32
    assert linear["conv_w"].shape == (6, 4, 128)
    # the published initialisation's ranges: A in (0, 16), the step in (0.001, 0.1)
    a, step = np.exp(linear["A_log"]), np.asarray(jax.nn.softplus(linear["dt_bias"]))
    assert 0 < a.min() and a.max() < 16 and a.max() > 4 * a.min()
    assert 0.001 <= step.min() + 1e-6 and step.max() <= 0.1 + 1e-6
    kind_at = [family.place(i) for i in range(8)]
    assert kind_at == [("linear_attention", 0), ("linear_attention", 1), ("linear_attention", 2),
                       ("full_attention", 0), ("linear_attention", 3), ("linear_attention", 4),
                       ("linear_attention", 5), ("full_attention", 1)]
    stack, at = family.ref_layer_params(tree, 6)
    assert list(stack) == ["linear_attention"] and at == 5



# -- what the state's path keeps, measured -------------------------------------


def _probed(state_dtype, monkeypatch=None, patch=None):
    """`state_probe` on what it reads of an engine: config, weights, the state."""
    from types import SimpleNamespace

    from langstream_tpu.models import transformer as program

    config = model_config(load_json("configs", TINY, DATA), TINY)
    if patch:
        patch(monkeypatch)
    rec = program.make_recurrent_state(config, 2)
    engine = SimpleNamespace(
        config=config, params=family.make_params(config, 0),
        _pagepool=SimpleNamespace(dev={"rec": {**rec, "s": rec["s"].astype(state_dtype)}}),
    )
    return family.state_probe(engine, width=96)


def test_the_state_probe_reads_a_float32_state_exact():
    # bf16 activations in the served config: the probe runs in float32 whatever it is
    assert _probed(jnp.float32) < family.STATE_TOL / 8


@pytest.mark.parametrize("how", ["stored-in-bf16", "rounded-at-every-write"])
def test_the_state_probe_reads_fewer_bits_lossy(how, monkeypatch):
    if how == "stored-in-bf16":
        lost = _probed(jnp.bfloat16)
    else:
        lost = _probed(jnp.float32, monkeypatch, state_rounded_at_every_write)
    assert lost > 4 * family.STATE_TOL, lost


# -- the two costs, against hand counts ---------------------------------------


def test_update_cost_is_the_state_once_in_and_once_out():
    # 7 (row, step) pairs updated of 3 steps x 4 rows, one layer, H 30, dk 96, dv 192
    got = cost.gated_delta_update(7, steps=3, layers=1, rows=4, key_head_dim=96,
                                  value_dim=5760, n_heads=30)
    state = 7 * 2 * 96 * 5760 * 4
    io = 12 * (2 * 30 * 96 + 2 * 5760 + 2 * 30) * 4
    assert got == {"ops": 7 * 3 * 2 * 96 * 5760, "bytes": state + io}
    # 24 layers: 24 times that; and a step of 40 live slots moves 4.25 GB of state
    assert cost.gated_delta_update(7, 3, 24, 4, 96, 5760, 30)["bytes"] == 24 * (state + io)
    step = cost.gated_delta_update(40, 1, 24, 40, 96, 5760, 30)
    assert math.isclose(step["bytes"], 40 * 24 * 2 * 2.21184e6 + 40 * 24 * 69360, rel_tol=1e-6)


def test_an_idle_row_costs_its_inputs_and_output_but_no_state():
    # the same call with no (row, step) pair updated: q, k, v, gates and output only
    idle = cost.gated_delta_update(0, steps=3, layers=2, rows=4, key_head_dim=96,
                                   value_dim=5760, n_heads=30)
    assert idle == {"ops": 0, "bytes": 2 * 12 * (2 * 30 * 96 + 2 * 5760 + 2 * 30) * 4}


# -- the check: sound passes, each fault fails by a number --------------------


def skip_linear_layer_1(tree):
    """Its mixer's and its FFN's output scales zeroed: the layer is the identity."""
    linear = dict(tree["layers"]["linear_attention"])
    for key in ("wo", "w_down"):
        linear[key] = {**linear[key], "s": linear[key]["s"].at[1].set(0.0)}
    return {**tree, "layers": {**tree["layers"], "linear_attention": linear}}


def block_fault(change):
    """A fault inside the linear mixer: `change(x, rec, rctx) -> (rec, rctx)`
    is applied on the way into the program's `_linear_attention_block`."""
    def patch(monkeypatch):
        from langstream_tpu.models import transformer as program

        sound = program._linear_attention_block

        def faulted(x, lp, config, rec, layer, rctx):
            rec, rctx = change(x, rec, rctx)
            return sound(x, lp, config, rec, layer, rctx)

        monkeypatch.setattr(program, "_linear_attention_block", faulted)
    return patch


def padding_unmasked(x, rec, rctx):
    if rctx and x.shape[1] > 1:
        rctx = {**rctx, "valid": jnp.ones_like(rctx["valid"])}
    return rec, rctx


def conv_tail_dropped(x, rec, rctx):
    if rec is not None and x.shape[1] == 1:
        rec = {**rec, "conv": jnp.zeros_like(rec["conv"])}
    return rec, rctx


def slot_not_zeroed(x, rec, rctx):
    if rctx and x.shape[1] > 1:
        rctx = {**rctx, "fresh": None}  # start from whatever the row held
    return rec, rctx


def state_in_bf16(engine):
    rec = engine._pagepool.dev["rec"]
    engine._pagepool.dev["rec"] = {**rec, "s": rec["s"].astype(jnp.bfloat16)}


def state_rounded_at_every_write(monkeypatch):
    """A float32 array that holds bf16 numbers: both writers of the state
    round what they write (a kernel that computes or stores in fewer bits).
    `reduce_precision`, because a pair of converts is elided on a TPU."""
    from langstream_tpu.ops import gated_delta as gd

    def rounded(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def update(sound):
        def faulted(*args, **kw):
            o, state = sound(*args, **kw)
            return o, rounded(state)
        return faulted

    chunk = gd.gated_delta_chunk_prefill

    def faulted_chunk(*args, **kw):
        o, final = chunk(*args, **kw)
        return o, rounded(final)

    monkeypatch.setattr(gd, "gated_delta_update", update(gd.gated_delta_update))
    monkeypatch.setattr(gd, "gated_delta_update_jnp", update(gd.gated_delta_update_jnp))
    monkeypatch.setattr(gd, "gated_delta_chunk_prefill", faulted_chunk)


def verdict_of(fault_name="sound", *, tree_fault=None, config_fault=None, patch=None,
               engine_fault=None, monkeypatch=None):
    from langstream_tpu.ai.tpu_serving import TpuServingProvider
    from langstream_tpu.models.configs import MODEL_PRESETS

    # a name a fault: the engine's programs are cached by their (static) config
    name = f"{TINY}-{fault_name}"
    spec = json.loads((DATA / "configs" / f"{TINY}.json").read_text())
    config = dataclasses.replace(register_preset(spec, name), dtype="float32")
    sound = family.make_params(config, int(spec["weights"]["seed"]))
    MODEL_PRESETS[name] = dataclasses.replace(config, **(config_fault or {}))
    if patch:
        patch(monkeypatch)
    provider = TpuServingProvider({**spec["serving"], **ENGINE, "model": name})
    provider.holder._params = tree_fault(sound) if tree_fault else sound
    try:
        engine = provider.engine()
        if engine_fault:
            engine_fault(engine)
        return run_check(engine, spec, ref_params=sound)
    finally:
        provider.holder.close()
        MODEL_PRESETS.pop(name, None)


def test_sound_system_passes_with_room():
    verdict = verdict_of()
    assert verdict["ok"], verdict
    assert verdict["engine_state"]["found"] == {
        "weights": "int8", "kv_dtype": "float32", "state_dtype": "float32", "state_path": "exact"}
    # float32 through and through: a hundredth of every tolerance
    assert verdict["layer_err_max"] < 1e-3 and verdict["hot_err_max_unexposed"] < 1e-3
    assert verdict["engine_positions"] == 4 * 32


@pytest.mark.parametrize(
    "fault, where",
    [
        (dict(engine_fault=state_in_bf16), "hot_err_over_tol"),
        (dict(patch=state_rounded_at_every_write), "hot_err_over_tol"),
        (dict(patch=block_fault(padding_unmasked)), "hot_err_over_tol"),
        (dict(config_fault={"linear_allow_neg_eigval": False}), "unexplained_over_tol"),
        (dict(patch=block_fault(conv_tail_dropped)), "hot_err_over_tol"),
        (dict(tree_fault=skip_linear_layer_1), "unexplained_over_tol"),
        (dict(patch=block_fault(slot_not_zeroed)), "engine_margin_over_tol"),
    ],
    ids=["state-in-bf16", "state-rounded-at-every-write", "padding-unmasked", "beta-without-its-2", "conv-tail-dropped",
         "linear-layer-skipped", "slot-not-zeroed"],
)
def test_known_fault_fails_by_a_number(fault, where, request, monkeypatch):
    verdict = verdict_of(request.node.callspec.id, monkeypatch=monkeypatch, **fault)
    assert verdict["ok"] is False, verdict
    assert verdict[where] > 0, {k: v for k, v in verdict.items() if k != "hot_err_by_position"}
    # what the state's path keeps is MEASURED: fewer bits read lossy whatever
    # the array's dtype says (and so does a fault of the layer's own path)
    if "state" in request.node.callspec.id:
        assert verdict["engine_state"]["found"]["state_path"].startswith("lossy")
        assert verdict["compared"]["engine_state_mismatches"][0] >= 1

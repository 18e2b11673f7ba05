"""The LFM2-24B-A2B configuration's check on the chip, sound and faulted, in
ONE process: the weights are made once, then an engine a case (the cell's
knobs, no warm-up: only the check's shapes compile), `check.run_check` over
it, and the rows of `compared` printed with every number of the verdict and
the worst pairs of level 1. By hand, through the chip tool; not part of the
benchmark's command.

    python3 dev/lfm2_check_faults.py [--tiny] [case ...]

(`--tiny`: the test-size configuration and cell of `benchmark/tests/data`, a
rehearsal on the CPU.) Every case's per-position numbers go to
`chiprun_out/lfm2_scores/<case>.npz`, so any `eps_router` and tolerance can be
judged again from the files with no chip (`dev/keye_check_faults.py`'s
`rejudge`).

Cases; the engine, the chain and the hot path all run the fault where the
fault is the program's, the reference keeps the file's arithmetic and the
sound tree. `sound`. The router: `bf16-router` (its product in bfloat16, the
nearest precision below the float32 stated), `bias-weighs` (`expert_bias`
added to the weights and not only to the choice), `no-router-eps` (the
published `+ 1e-6` left out: the control that must NOT fail, a millionth of a
sum near 2). The experts: `expert-adds-nothing` (expert 0's down projection
zero in every layer of the served tree). The mixers: `int4-mixers` (a conv
mixer's two and an attention mixer's four matrices rounded to 4 bits in the
served tree), `no-qk-norm` (the per-head q/k norm left out). The tails, which
the reference has none of: `tail-dropped` (zeros carried into every decode
step and every segment past the first, where the state holds the row's last
two inputs), `tail-from-padding` (a ragged row's tail taken from the last two
columns of its padded width, not from its own last two inputs).
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "benchmark"), str(ROOT / "dev")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import check  # noqa: E402
from glm_check_faults import say  # noqa: E402
from keye_check_faults import rejudge  # noqa: E402,F401
from modelcfg import load_json, load_module, register_preset  # noqa: E402

from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.serving import engine as E  # noqa: E402

CONFIG, CELL = "lfm2-24b-a2b-int8-d16", "lfm2-24b-d16-decode-drain-256"
CASES = ("sound", "bf16-router", "bias-weighs", "expert-adds-nothing", "int4-mixers",
         "no-qk-norm", "tail-dropped", "tail-from-padding", "no-router-eps")
PATCHED = ("_route_all", "_short_conv")
SOUND = {name: getattr(T, name) for name in PATCHED}
MIXERS = ("w_in", "w_out", "wq", "wk", "wv", "wo")


def route(weigh_bias: bool = False, bf16: bool = False):
    """`_route_all` of a sigmoid router under a bias, faulted."""
    def route_all(xf, router, config, bias=None):
        kind = jnp.bfloat16 if bf16 else jnp.float32
        logits = jnp.dot(
            xf.astype(kind), router.astype(kind),
            precision=None if bf16 else jax.lax.Precision.HIGHEST,
        ).astype(jnp.float32)
        scores = jax.nn.sigmoid(logits)
        biased = scores + bias.astype(jnp.float32)
        _, chosen = jax.lax.top_k(biased, config.n_experts_per_tok)
        top = jnp.take_along_axis(biased if weigh_bias else scores, chosen, axis=-1)
        total = jnp.sum(top, axis=-1, keepdims=True) + config.router_norm_eps
        return config.routed_scaling * top / total, chosen

    return route_all


def tail_dropped(inputs, taps, rec, layer, rows, valid, fresh, activation=None):
    """`_short_conv` reading zeros wherever it would read a carried tail (the
    tail it leaves is the sound one: the NEXT reader drops it again)."""
    sound = SOUND["_short_conv"]
    mixed, out = sound(inputs, taps, rec, layer, rows, valid, fresh, activation)
    if rec is not None and fresh is not True:
        zeros = {**rec, "conv": jnp.zeros_like(rec["conv"])}
        mixed, _ = sound(inputs, taps, zeros, layer, rows, valid, fresh, activation)
    return mixed, out


def tail_from_padding(inputs, taps, rec, layer, rows, valid, fresh, activation=None):
    """`_short_conv` leaving each row of a group the last K - 1 columns of the
    padded width (a decode step's one column is its own)."""
    everything = valid if inputs.shape[1] == 1 else jnp.ones_like(valid)
    return SOUND["_short_conv"](inputs, taps, rec, layer, rows, everything, fresh, activation)


def served(params, case: str):
    """The tree the engine serves under a fault of the WEIGHTS (the reference
    keeps the sound one)."""
    def over_stacks(fn):
        out = dict(params)
        for stack in ("dense_layers", "layers"):
            out[stack] = {kind: fn(dict(layers)) for kind, layers in params[stack].items()}
        return out

    if case == "expert-adds-nothing":
        def silence(layers):
            if "router" in layers:  # expert 0 of every expert layer
                down = layers["w_down"]
                layers["w_down"] = {**down, "q": down["q"].at[:, 0].set(0)}
            return layers
        return over_stacks(silence)
    if case == "int4-mixers":
        def coarse(layers):
            for name in MIXERS:
                if name in layers:  # 15 levels in place of 255: round to multiples of 16
                    w = layers[name]
                    q = (jnp.round(w["q"].astype(jnp.float32) / 16.0) * 16.0).clip(-127, 127)
                    layers[name] = {**w, "q": q.astype(jnp.int8)}
            return layers
        return over_stacks(coarse)
    return params


def main(cases: list[str], tiny: bool = False) -> int:
    files = ROOT / "benchmark" / ("tests/data" if tiny else "")
    name, cell = ("tiny-lfm2", "tiny-lfm2-drain") if tiny else (CONFIG, CELL)
    spec = load_json("configs", name, files)
    knobs = load_json("workloads", cell, files)["engine"]
    family = load_module("families", spec["family"])
    config = register_preset(spec, name, files)
    t = time.monotonic()
    params = family.make_params(config, int(spec["weights"]["seed"]))
    jax.block_until_ready(params)
    say(phase="weights", seconds=round(time.monotonic() - t, 1),
        device=jax.devices()[0].device_kind)
    failed = 0
    out = ROOT / "chiprun_out" / ("lfm2_scores_tiny" if tiny else "lfm2_scores")
    out.mkdir(parents=True, exist_ok=True)
    kept: dict = {}
    judge = check._judge

    def keeping(scores, limits):
        kept["scores"] = scores
        return judge(scores, limits)

    check._judge = keeping
    for case in cases:
        for attr, sound in SOUND.items():
            setattr(T, attr, sound)
        # a config of its own name: the case is traced into programs of its own
        named = dataclasses.replace(config, name=f"{name}-{case}")
        if case == "bf16-router":
            T._route_all = route(bf16=True)
        elif case == "bias-weighs":
            T._route_all = route(weigh_bias=True)
        elif case == "no-router-eps":
            named = dataclasses.replace(named, router_norm_eps=0.0)
        elif case == "no-qk-norm":
            named = dataclasses.replace(named, qk_norm_heads=False)
        elif case == "tail-dropped":
            T._short_conv = tail_dropped
        elif case == "tail-from-padding":
            T._short_conv = tail_from_padding
        elif case not in ("sound", "expert-adds-nothing", "int4-mixers"):
            raise SystemExit(f"no case {case!r}: {CASES}")
        engine = E.ServingEngine(
            named, served(params, case), max_batch=knobs["max-batch"],
            max_seq_len=knobs["max-seq-len"],
            prefill_buckets=tuple(knobs["prefill-buckets"]), kv_pages=knobs["kv-pages"],
            page_size=knobs.get("page-size", 64), prefill_batch=knobs.get("prefill-batch", 8),
            precompile=False,
        )
        engine.start()
        engine.wait_ready()
        t = time.monotonic()
        try:
            verdict = check.run_check(engine, spec, ref_params=params, files=files)
        finally:
            engine.stop()
            del engine
            gc.collect()  # an engine is a cycle of threads and callbacks: its pool with it
        by_position = verdict.pop("hot_err_by_position", None)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices())
        say(case=case, seconds=round(time.monotonic() - t, 1), ok=verdict["ok"],
            compared=verdict["compared"], memory_peak_bytes=peak,
            hot_err_by_position=by_position,
            **{k: v for k, v in verdict.items() if isinstance(v, (int, float)) and k != "ok"})
        # `no-router-eps` is the control that must pass
        failed += (case in ("sound", "no-router-eps")) != bool(verdict["ok"])
        scores = kept.pop("scores", None)
        if scores is None:  # a check that ended before it judged
            continue
        np.savez_compressed(
            out / f"{case}.npz",
            **{f"{i}.{j}.{k}": v for i, passes in enumerate(scores)
               for j, s in enumerate(passes) for k, v in s.items()})
        # the worst pairs of level 1, by (sequence, chain step, position)
        worst = []
        for i, passes in enumerate(scores):
            for s in passes:
                err, gap = s["layer_err"], s["router_gap"]
                for flat in np.argsort(err, axis=None)[-4:]:
                    step, at = np.unravel_index(flat, err.shape)
                    worst.append((float(err[step, at]), i, int(step), int(at),
                                  float(gap[step, at]) if step < gap.shape[0] else None))
        say(case=case, worst_pairs=sorted(worst, reverse=True)[:8])
    for attr, sound in SOUND.items():
        setattr(T, attr, sound)
    say(phase="done", cases=len(cases), not_as_expected=failed)
    return 1 if failed else 0


if __name__ == "__main__":
    args = sys.argv[1:]
    tiny = "--tiny" in args
    chosen = [a for a in args if not a.startswith("--")] or list(CASES)
    sys.exit(main(chosen, tiny=tiny))

"""The Kimi-K2.5 configuration's check on the chip, sound and faulted, in ONE
process: the weights are made once, then an engine a case (the cell's knobs,
no warm-up: only the check's shapes compile), `check.run_check` over it, and
the rows of `compared` printed with every number of the verdict and the worst
pairs of level 1. By hand, through the chip tool; not part of the benchmark's
command.

    python3 dev/kimi_check_faults.py [--tiny] [case ...]

(`--tiny`: the test-size configuration and cell of `benchmark/tests/data`, a
rehearsal on the CPU.) Every case's per-position numbers go to
`chiprun_out/kimi_scores/<case>.npz`, so any `eps_router` and tolerance can be
judged again from the files with no chip: `rejudge(directory, limits)`
(`dev/keye_check_faults.py`'s; this family's attention halves choose nothing,
so their rows of `router_gap` are infinite as they stand).

Cases; the engine, the chain and the hot path all run the fault where the
fault is the model's, the reference keeps the file's arithmetic and tree.
`sound`. YaRN: `no-yarn-blend` (plain `f_i`: no frequency is interpolated),
`no-mscale` (`m^2` left out of the softmax scale: 192^-0.5). The router
(`dev/glm_check_faults.py`'s): `bias-weighs`, `no-bias`, `scaling-1`,
`no-shared`, `bf16-router`. The latent path, which the chain never takes (it
runs the expanded form over the tokens' own rows, so it stays sound and levels
2 and 3 have to see these): `no-krope` (the rotary key left out of the decode
step's score), `value-from-key` (`W_uv` applied at the wrong width: the decode
step's output read through the KEY's 128 lanes of `wkv_b` in place of the
value's), `lat8` (the latent rounded to 8 bits where the pool is written).
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
from glm_check_faults import absorb_without_rope, route, say, scatter_8_bits  # noqa: E402
from keye_check_faults import rejudge  # noqa: E402,F401
from modelcfg import load_json, load_module, register_preset  # noqa: E402

from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.serving import engine as E  # noqa: E402

CONFIG, CELL = "kimi-k2.5-int8-ep32-d7", "kimik25-ep32-d7-longdoc-drain"
CASES = ("sound", "no-yarn-blend", "no-mscale", "no-krope", "value-from-key", "no-shared",
         "scaling-1", "no-bias", "bf16-router", "lat8", "bias-weighs")
PATCHED = ("_yarn_tables", "_route_all", "_latent_absorb", "_latent_value_out", "_paged_scatter")
SOUND = {name: getattr(T, name) for name in PATCHED}


def plain_tables(positions, freqs, config):
    """sin/cos of the plain frequencies `f_i`: YaRN's blend left out."""
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def value_from_key_lanes(mixed, lp, config):
    """`_latent_value_out` through the first `v_head_dim` lanes of a head's
    share of `wkv_b`, which are its KEY's."""
    v = config.v_head_dim
    w, scale = T._wkv_b(lp, config)
    out = jnp.einsum(
        "bhc,chj->bhj", mixed, w[..., :v].astype(mixed.dtype), preferred_element_type=jnp.float32
    )
    if scale is not None:
        out = out * scale[:, :v]
    return out.astype(mixed.dtype).reshape(mixed.shape[0], -1)


def main(cases: list[str], tiny: bool = False) -> int:
    files = ROOT / "benchmark" / ("tests/data" if tiny else "")
    name, cell = ("tiny-kimi", "tiny-kimi-drain") if tiny else (CONFIG, CELL)
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
    out = ROOT / "chiprun_out" / ("kimi_scores_tiny" if tiny else "kimi_scores")
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
        if case == "no-yarn-blend":
            T._yarn_tables = plain_tables
        elif case == "no-mscale":  # both zero: the tables' own factor stays 1
            named = dataclasses.replace(
                named, rope_scaling_mscale=0.0, rope_scaling_mscale_all_dim=0.0
            )
        elif case == "no-krope":
            T._latent_absorb = absorb_without_rope
        elif case == "value-from-key":
            T._latent_value_out = value_from_key_lanes
        elif case == "bias-weighs":
            T._route_all = route(weigh_bias=True)
        elif case == "no-bias":
            T._route_all = route(choose_bias=False)
        elif case == "scaling-1":
            named = dataclasses.replace(named, routed_scaling=1.0)
        elif case == "no-shared":
            named = dataclasses.replace(named, n_shared_experts=0)
        elif case == "bf16-router":
            T._route_all = route(bf16=True)
        elif case == "lat8":
            T._paged_scatter = scatter_8_bits
        elif case != "sound":
            raise SystemExit(f"no case {case!r}: {CASES}")
        engine = E.ServingEngine(
            named, params, max_batch=knobs["max-batch"], max_seq_len=knobs["max-seq-len"],
            prefill_buckets=tuple(knobs["prefill-buckets"]), kv_pages=knobs["kv-pages"],
            page_size=knobs.get("page-size", 64), prefill_batch=knobs.get("prefill-batch", 1),
            precompile=False,
        )
        engine.start()
        engine.wait_ready()
        t = time.monotonic()
        try:
            verdict = check.run_check(engine, spec, files=files)
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
        failed += (case == "sound") != bool(verdict["ok"])
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

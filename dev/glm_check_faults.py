"""The GLM-5 configuration's check on the chip, sound and faulted, in ONE
process: the weights are made once, then an engine a case (the cell's knobs,
no warm-up: only the check's shapes compile), `check.run_check` over it, and
the rows of `compared` printed with every number of the verdict and the worst
pairs of level 1. By hand, through the chip tool; not part of the benchmark's
command.

    python3 dev/glm_check_faults.py [--tiny] [case ...]

(`--tiny`: the test-size configuration and cell of `benchmark/tests/data`, a
rehearsal on the CPU.) Every case's per-position numbers go to
`chiprun_out/glm_scores/<case>.npz` with the RAW gap between a query's topk-th
and next indexer score in the attention halves' rows of `router_gap`, so any
`eps_router`, `eps_select` and tolerance can be judged again from the files
with no chip: `rejudge(directory, limits)` (`dev/keye_check_faults.py`'s, whose
chain alternates attention and expert halves as this one does).

Cases; the engine, the chain and the hot path all run the fault where the
fault is the model's, the reference keeps the file's arithmetic and tree.
`sound`. The selection: `recent-keys` (the most RECENT top-k keys in place of
the ranked ones, in a decode step's ranking and in a segment's), `dense` (no
selection: every query attends to all it sees), `indexer-from-u` (the
indexer's queries read the normed input's first `q_lora_rank` columns through
the same projection, in place of the query latent). The router:
`bias-weighs` (the bias added to the weights too), `no-bias` (the bias left
out of the choice), `scaling-1` (`routed_scaling_factor` 1), `no-shared` (the
shared expert left out), `bf16-router` (the router's product in bfloat16, the
nearest precision below the float32 the file states). The latent path, which
the chain never takes (it runs the expanded form over the tokens' own rows, so
it stays sound and levels 2 and 3 have to see these): `no-krope` (the rotary
key left out of the decode step's score: the absorbed query over the 512
alone), `lat8` (the latent rounded to 8 bits, one scale a token, where the
pool is written: the nearest precision below the bfloat16 the file states).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "benchmark")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import numpy as np  # noqa: E402

import check  # noqa: E402
from modelcfg import load_json, load_module, register_preset  # noqa: E402

sys.path.insert(0, str(ROOT / "dev"))
from keye_check_faults import as_the_file_judges, raw_select_gap, rejudge  # noqa: E402,F401

from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.ops import attention as A  # noqa: E402
from langstream_tpu.serving import engine as E  # noqa: E402

CONFIG, CELL = "glm-5-int8-ep16-d7", "glm5-ep16-d7-longdoc-drain"
CASES = ("sound", "recent-keys", "dense", "indexer-from-u", "bias-weighs", "no-bias",
         "scaling-1", "no-shared", "no-krope", "lat8", "bf16-router")
PATCHED = {
    T: ("_select_mask", "_index_proj", "_route_all", "_latent_absorb", "_paged_scatter"),
    A: ("segment_select",),
}
SOUND = {(module, name): getattr(module, name) for module, names in PATCHED.items() for name in names}


def say(**line) -> None:
    print(json.dumps(line, default=float), flush=True)


def recent_keys(scores, visible, k):
    place = jnp.broadcast_to(jnp.arange(scores.shape[-1], dtype=jnp.float32), scores.shape)
    return SOUND[T, "_select_mask"](place, visible, k)


def recent_segment_select(q_idx, w, k_idx, offsets, topk, interpret=False):
    """[B, S, T] int8: each query's most recent `topk` columns."""
    s, t = q_idx.shape[1], k_idx.shape[1]
    at = offsets[:, None, None] + jnp.arange(s)[None, :, None]
    column = jnp.arange(t)[None, None, :]
    return ((column <= at) & (column > at - topk)).astype(jnp.int8)


def index_from_u(u, lp, positions, config, c_q=None, rotary=None):
    return SOUND[T, "_index_proj"](
        u, lp, positions, config, c_q=u[..., : config.q_lora_rank], rotary=rotary
    )


def route(weigh_bias: bool = False, choose_bias: bool = True, bf16: bool = False):
    def route_all(xf, router, config, bias=None):
        kind = jnp.bfloat16 if bf16 else jnp.float32
        logits = jnp.dot(
            xf.astype(kind), router.astype(kind),
            precision=None if bf16 else jax.lax.Precision.HIGHEST,
        ).astype(jnp.float32)
        scores = jax.nn.sigmoid(logits)
        biased = scores + bias.astype(jnp.float32)
        _, chosen = jax.lax.top_k(biased if choose_bias else scores, config.n_experts_per_tok)
        top = jnp.take_along_axis(biased if weigh_bias else scores, chosen, axis=-1)
        return config.routed_scaling * top / jnp.sum(top, axis=-1, keepdims=True), chosen

    return route_all


def absorb_without_rope(q, lp, config, width):
    nope = config.qk_nope_head_dim
    return SOUND[T, "_latent_absorb"](q.at[..., nope:].set(0), lp, config, width)


def scatter_8_bits(pool, layer, vals, table, positions, page_size):
    """The latent's rows [B, 1, S, W] rounded to 8 bits, one scale a token."""
    f32 = vals.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(f32), axis=-1, keepdims=True), 1e-8) / 127
    rounded = (jnp.round(f32 / scale) * scale).astype(vals.dtype)
    return SOUND[T, "_paged_scatter"](pool, layer, rounded, table, positions, page_size)


def main(cases: list[str], tiny: bool = False) -> int:
    files = ROOT / "benchmark" / ("tests/data" if tiny else "")
    name, cell = ("tiny-glm", "tiny-glm-drain") if tiny else (CONFIG, CELL)
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
    out = ROOT / "chiprun_out" / ("glm_scores_tiny" if tiny else "glm_scores")
    out.mkdir(parents=True, exist_ok=True)
    kept: dict = {}
    judge = check._judge

    def keeping(scores, limits):
        kept["scores"] = scores
        return judge(as_the_file_judges(scores, float(limits.get("eps_select", 0.0))), limits)

    check._judge = keeping
    check.load_module = raw_select_gap(check.load_module)
    for case in cases:
        for (module, attr), sound in SOUND.items():
            setattr(module, attr, sound)
        # a config of its own name: the case is traced into programs of its own
        named = dataclasses.replace(config, name=f"{name}-{case}")
        if case == "recent-keys":
            T._select_mask, A.segment_select = recent_keys, recent_segment_select
        elif case == "dense":
            named = dataclasses.replace(named, index_topk=knobs["max-seq-len"])
        elif case == "indexer-from-u":
            T._index_proj = index_from_u
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
        elif case == "no-krope":
            T._latent_absorb = absorb_without_rope
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
        # the worst pairs of level 1, by (sequence, chain step, position): which
        # half, where, how near a tie (an attention half's gap is its
        # selection's, an expert half's its router's)
        worst = []
        for i, passes in enumerate(scores):
            for s in passes:
                err, gap = s["layer_err"], s["router_gap"]
                for flat in np.argsort(err, axis=None)[-4:]:
                    step, at = np.unravel_index(flat, err.shape)
                    worst.append((float(err[step, at]), i, int(step), int(at),
                                  float(gap[step, at]) if step < gap.shape[0] else None))
        say(case=case, worst_pairs=sorted(worst, reverse=True)[:8])
    for (module, attr), sound in SOUND.items():
        setattr(module, attr, sound)
    say(phase="done", cases=len(cases), not_as_expected=failed)
    return 1 if failed else 0


if __name__ == "__main__":
    args = sys.argv[1:]
    tiny = "--tiny" in args
    chosen = [a for a in args if not a.startswith("--")] or list(CASES)
    sys.exit(main(chosen, tiny=tiny))

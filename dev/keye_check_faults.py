"""The Keye-VL-2.0 configuration's check on the chip, sound and faulted, in ONE
process: the weights are made once, then an engine a case (the cell's knobs,
no warm-up: only the check's shapes compile), `check.run_check` over it, and
the rows of `compared` printed. By hand, through the chip tool; not part of
the benchmark's command.

    python3 dev/keye_check_faults.py [--tiny] [--samples W:n,n,n;W:n,n,n]
        [--check-seeds n,n] [--new-tokens n] [case ...]

(`--tiny`: the test-size configuration and cell of `benchmark/tests/data`, a
rehearsal on the CPU. `--samples`: widths with their prompt lengths in place
of the file's, tried in turn by the first case until one fits the device:
the check holds three float32 `[width, vocabulary]` arrays beside the engine.
`--check-seeds`: every case once a seed, each in place of the file's
`check_seed`, which draws the sample's token ids: other prompts of the same
lengths. A case's line then holds the hot path's error at each of the
engine's positions, `hot_err_by_position`, a tie-exposed one negative: two
commits' lines side by side say whether a moved median is a shift of every
position or the scatter of a few, PERF.md section 6, PR 44. `--new-tokens`:
tokens generated a sequence in place of the file's 8, for more such positions;
the prompts of `--samples` must leave them room under the width.)

Every case's per-position numbers go to `chiprun_out/keye_scores/<case>.npz`
with the RAW gap between a query's topk-th and next indexer score in the
attention halves' rows of `router_gap` (the verdict printed here is judged as
the harness judges: a query under the file's `eps_select` reads 0, every other
infinity), so any `eps_select` and tolerance can be judged again from the
files by `check._judge`, with no chip: `rejudge()`.

Cases. `sound`. `gather`: sound, with a decode step's selected read held to
`_sparse_decode_attention` where the kernels would walk the row's pages under
the mask (the rule's other side, the read of PR 43): the same selection read
another way, so what differs from `sound` is the read's rounding alone. The
selection (the engine, the chain and the hot path all run
the fault; the reference keeps the file's arithmetic): `recent-keys` (the most
RECENT top-k keys in place of the ranked ones), `dense` (no selection: every
query attends to all it sees), `half-topk` (top-k halved), `bf16-scores` (the
indexer's scores rounded to bfloat16 where the file says float32). The pool's
third leaf (the chain never writes a pool, so it stays sound): `ik8` (the
indexer's keys rounded to 8 bits, one scale a token, where they are written),
`stale-ik` (a decode step's indexer key is not written: later steps rank a
zero key at its place), `own-columns` (a segment ranks only its OWN columns:
the indexer's keys of earlier segments read as zeros). Controls on the block
this model shares (SDAR's cases): `bf16-router` (the router's product in
bfloat16, the nearest precision below the float32 the file states),
`expert-skipped` (one of the 128 experts adds nothing, in every layer; the
reference keeps the file's tree).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "benchmark")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import check  # noqa: E402
from modelcfg import load_json, load_module, register_preset  # noqa: E402

from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.serving import engine as E  # noqa: E402

CONFIG, CELL = "keye-vl-2.0-30b-a3b-int8-d12", "keyevl2-d12-longdoc-drain"
CASES = ("sound", "gather", "recent-keys", "dense", "half-topk", "bf16-scores", "own-columns", "ik8",
         "expert-skipped", "bf16-router", "stale-ik")
SKIPPED_EXPERT = 5
WALK_TABLE_PER_TOPK = getattr(T, "_WALK_TABLE_PER_TOPK", None)
SELECT, SCORES, WRITE, SELECTED, ROUTE_ALL = (
    T._select_mask, T._index_scores, T._write_index_key, T._selected_attention, T._route_all)


def _bytes_in_use() -> int:
    return max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.devices())


def say(**line) -> None:
    print(json.dumps(line, default=float), flush=True)


def bf16_route_all(xf, router, config):
    """`_route_all` with the product in bfloat16 at the default precision."""
    logits = jnp.dot(xf.astype(jnp.bfloat16), router.astype(jnp.bfloat16)).astype(jnp.float32)
    top, chosen = jax.lax.top_k(logits, config.n_experts_per_tok)
    return jax.nn.softmax(top, axis=-1), chosen


def raw_select_gap(load_module):
    """`check.load_module`, with the reference's attention half reporting the
    RAW gap of its selection as `router_gap` (the file's reference reports 0
    under `eps_select`, else infinity: `as_the_file_judges` maps one to the
    other on the host)."""

    def load(kind, name, *rest, **kw):
        module = load_module(kind, name, *rest, **kw)
        if kind != "reference":
            return module
        layer = module.layer

        def layer_raw(x, lp, dims, positions=None):
            y, info = layer(x, lp, dims, positions)
            if "select_gap" in info:
                info = {**info, "router_gap": info["select_gap"]}
            return y, info

        return SimpleNamespace(**{**vars(module), "layer": layer_raw})

    return load


def as_the_file_judges(scores, eps_select: float):
    """The attention halves' rows (even rows of `router_gap`: the chain steps
    attention, experts, attention ...) from the raw gap to what the file's
    reference reports."""
    out = []
    for passes in scores:
        out.append([])
        for s in passes:
            gap = s["router_gap"].copy()
            gap[0::2] = np.where(gap[0::2] < eps_select, 0.0, np.inf)
            out[-1].append({**s, "router_gap": gap})
    return out


def load_scores(path) -> list:
    flat = np.load(path)
    scores: dict = {}
    for key in flat.files:
        i, j, name = key.split(".", 2)
        scores.setdefault(int(i), {}).setdefault(int(j), {})[name] = flat[key]
    return [[scores[i][j] for j in sorted(scores[i])] for i in sorted(scores)]


def rejudge(directory, limits: dict) -> dict:
    """case -> the verdict `check._judge` gives the saved numbers under `limits`
    (a `check` block: its tolerances, `eps_router` and `eps_select`)."""
    out = {}
    for path in sorted(Path(directory).glob("*.npz")):
        scores = as_the_file_judges(load_scores(path), float(limits.get("eps_select", 0.0)))
        out[path.stem] = check._judge(scores, limits)
    return out


def recent_keys(scores, visible, k):
    place = jnp.broadcast_to(jnp.arange(scores.shape[-1], dtype=jnp.float32), scores.shape)
    return SELECT(place, visible, k)


def bf16_scores(q_idx, w, k_idx, offsets, config):
    return SCORES(q_idx, w, k_idx, offsets, config).astype(jnp.bfloat16).astype(jnp.float32)


def ik8_write(pik, layer, k_idx, table, positions, page_size):
    scale = jnp.maximum(jnp.max(jnp.abs(k_idx.astype(jnp.float32)), axis=-1, keepdims=True), 1e-8) / 127
    rounded = jnp.round(k_idx.astype(jnp.float32) / scale) * scale
    return WRITE(pik, layer, rounded.astype(k_idx.dtype), table, positions, page_size)


def stale_write(pik, layer, k_idx, table, positions, page_size):
    return pik if k_idx.shape[1] == 1 else WRITE(pik, layer, k_idx, table, positions, page_size)


def own_columns(q, q_idx, w, k_idx_all, k_all, v_all, mask, positions, config, what):
    if what == "paged-segment":  # columns before the segment's first: zeros
        before = jnp.arange(k_idx_all.shape[1])[None, :, None] < positions[:, :1, None]
        k_idx_all = jnp.where(before, 0, k_idx_all)
    return SELECTED(q, q_idx, w, k_idx_all, k_all, v_all, mask, positions, config, what)


def main(cases: list[str], tiny: bool = False, samples=(), check_seeds=(), new_tokens=0) -> int:
    files = ROOT / "benchmark" / ("tests/data" if tiny else "")
    name, cell = ("tiny-keye", "tiny-keye-drain") if tiny else (CONFIG, CELL)
    spec = load_json("configs", name, files)
    knobs = load_json("workloads", cell, files)["engine"]
    family = load_module("families", spec["family"])
    config = register_preset(spec, name, files)
    samples = list(samples) or [(spec["check"]["width"], spec["check"]["lengths"])]
    out = ROOT / "chiprun_out" / ("keye_scores_tiny" if tiny else "keye_scores")
    out.mkdir(parents=True, exist_ok=True)
    t = time.monotonic()
    params = family.make_params(config, int(spec["weights"]["seed"]))
    jax.block_until_ready(params)
    say(phase="weights", seconds=round(time.monotonic() - t, 1),
        device=jax.devices()[0].device_kind)
    kept: dict = {}
    judge = check._judge

    def keeping(scores, limits):
        kept["scores"] = scores
        return judge(as_the_file_judges(scores, float(limits.get("eps_select", 0.0))), limits)

    check._judge = keeping
    check.load_module = raw_select_gap(check.load_module)
    for case, check_seed in [(c, n) for n in (check_seeds or [None]) for c in cases]:
        T._select_mask, T._index_scores, T._write_index_key, T._selected_attention, T._route_all = (
            SELECT, SCORES, WRITE, SELECTED, ROUTE_ALL)
        T._WALK_TABLE_PER_TOPK = WALK_TABLE_PER_TOPK
        # a config of its own name: the case is traced into programs of its own
        named = dataclasses.replace(config, name=f"{name}-{case}")
        served = params
        if case == "gather":
            T._WALK_TABLE_PER_TOPK = 0
        elif case == "recent-keys":
            T._select_mask = recent_keys
        elif case == "dense":
            named = dataclasses.replace(named, index_topk=knobs["max-seq-len"])
        elif case == "half-topk":
            named = dataclasses.replace(named, index_topk=config.index_topk // 2)
        elif case == "bf16-scores":
            T._index_scores = bf16_scores
        elif case == "ik8":
            T._write_index_key = ik8_write
        elif case == "stale-ik":
            T._write_index_key = stale_write
        elif case == "own-columns":
            T._selected_attention = own_columns
        elif case == "bf16-router":
            T._route_all = bf16_route_all
        elif case == "expert-skipped":
            down = params["layers"]["w_down"]  # q [L, E, f, d], s [L, E, 1, d]
            served = {**params, "layers": {**params["layers"], "w_down": {
                **down, "s": down["s"].at[:, SKIPPED_EXPERT].set(0)}}}
        while True:
            width, lengths = samples[0]
            sized = {**spec, "check": {**spec["check"], "width": width, "lengths": lengths}}
            if check_seed is not None:
                sized["check"]["check_seed"] = check_seed
            if new_tokens:
                sized["check"]["new_tokens"] = new_tokens
            in_use = _bytes_in_use()
            # the first case finds the width that fits: its engine is warmed as
            # the provider warms the cell's, so that what fits here fits there
            engine = E.ServingEngine(
                named, served, max_batch=knobs["max-batch"], max_seq_len=knobs["max-seq-len"],
                prefill_buckets=tuple(knobs["prefill-buckets"]), kv_pages=knobs["kv-pages"],
                page_size=knobs.get("page-size", 64), prefill_batch=knobs.get("prefill-batch", 1),
                precompile=(case, check_seed) == (cases[0], (check_seeds or [None])[0])
                and len(samples) > 1,
            )
            engine.start()
            engine.wait_ready()
            t = time.monotonic()
            does_not_fit = None
            try:
                verdict = check.run_check(
                    engine, sized, ref_params=params if served is not params else None)
            except Exception as e:  # noqa: BLE001 - the allocator's, by its message
                if "RESOURCE_EXHAUSTED" not in str(e) or len(samples) == 1:
                    raise
                does_not_fit = str(e).splitlines()[0][:300]
            finally:
                engine.stop()
                del engine
                if does_not_fit:
                    kept.pop("scores", None)
                gc.collect()  # an engine is a cycle of threads and callbacks: its pool with it
            if does_not_fit is None:
                break
            say(case=case, width=width, does_not_fit=does_not_fit, bytes_in_use_before=in_use,
                bytes_in_use_after=_bytes_in_use())
            samples.pop(0)
        by_position = verdict.pop("hot_err_by_position", None)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices())
        say(case=case, width=width, lengths=lengths, seconds=round(time.monotonic() - t, 1),
            ok=verdict["ok"], compared=verdict["compared"], memory_peak_bytes=peak,
            bytes_in_use_before=in_use,
            **({} if check_seed is None else {
                "check_seed": check_seed, "hot_err_by_position": by_position}),
            **{k: v for k, v in verdict.items() if isinstance(v, (int, float)) and k != "ok"})
        scores = kept.pop("scores", None)
        if scores is None:  # a check that ended before it judged
            continue
        np.savez_compressed(
            out / (f"{case}.npz" if check_seed is None else f"{case}-{check_seed}.npz"),
            **{f"{i}.{j}.{k}": v for i, passes in enumerate(scores)
               for j, s in enumerate(passes) for k, v in s.items()})
        # the worst pairs, by (sequence, chain step, position): which half, where, how
        # near a tie (an attention half's gap is its selection's, an expert half's its router's)
        worst = []
        for i, passes in enumerate(scores):
            for s in passes:
                err, gap = s["layer_err"][:-1], s["router_gap"]
                for flat in np.argsort(err, axis=None)[-6:]:
                    step, pos = np.unravel_index(flat, err.shape)
                    worst.append([round(float(err[step, pos]), 5), i, int(step), int(pos),
                                  round(float(gap[step, pos]), 6)])
        say(case=case, worst_err_seq_step_pos_gap=sorted(worst, reverse=True)[:8])
    return 0


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--samples", default="", help="W:n,n,n;W:n,n,n, tried in turn")
    parser.add_argument("--check-seeds", default="", help="n,n: each in place of the file's")
    parser.add_argument("--new-tokens", type=int, default=0, help="in place of the file's")
    parser.add_argument("cases", nargs="*", metavar="case", help=f"of {CASES}; none: all")
    args = parser.parse_args()
    if set(args.cases) - set(CASES):
        parser.error(f"unknown cases {sorted(set(args.cases) - set(CASES))}; there are {CASES}")
    samples = [
        (int(w), [int(n) for n in ns.split(",")])
        for w, ns in (part.split(":") for part in args.samples.split(";") if part)
    ]
    seeds = [int(n) for n in args.check_seeds.split(",") if n]
    raise SystemExit(main(args.cases or list(CASES), args.tiny, samples, seeds, args.new_tokens))

"""The SDAR-MoE configuration's check on the chip, sound and faulted, in ONE
process: the weights are made once, then an engine a case (the cell's knobs,
no warm-up: only the check's shapes compile), `check.run_check` over it, and
the rows of `compared` printed beside what they rest on. By hand, through the
chip tool; not part of the benchmark's command.

    python3 dev/sdar_check_faults.py [--tiny] [--check-seed N] [--new-tokens N] [case ...]

(`--tiny`: the test-size configuration and cell of `benchmark/tests/data`, a
rehearsal on the CPU. `--check-seed` / `--new-tokens`: another sample than the
file's, to see how the limits stand on sequences the file did not choose.)

Every case prints its verdict and, for every read position, the smallest
router gap of its own token over the layers beside its hot-path error and
margin; its per-position numbers go whole to
`chiprun_out/sdar_scores/<case>[-<check seed>].npz` (`sdar_scores_tiny` with
`--tiny`), so that `check._judge`
can be run over them again under other limits without the chip. `sound` also
prints the verdict under a grid of `eps_router`.

Cases. The whole system: `sound`. Level 1, the model (the engine, the chain
and the hot path all run the fault; the reference keeps the file's tree and
arithmetic): `bf16-router` (the router's product in bfloat16, the nearest
precision below the float32 the configuration states), `int4-attention` (the
attention matrices and the head with their int8 values cut to 4 bits, the
nearest precision below the int8 the configuration states), `int4-expert-down`
(the same cut of the 128 experts' down projections: a second copy of all
three matrices does not fit the chip beside the reference's), `expert-skipped` (one of
the 128 experts adds nothing, in every layer), `assignment-dropped` (every
16th token loses the least of its eight assignments: what a capacity rule
does). Level 2, the hot path (the chain never writes a pool, so it stays
sound): `kv8-block-write` (a block's K/V rounded to 8 bits, one scale a
token and head, where a pass writes them), `fp8-block-write` (the same at
float8 e4m3), `block-write-skipped` (a pass writes nothing: a block reads
what an earlier tenant of its pages left, later blocks read that block so).
Level 3, the engine alone: `causal-denoise-pass` (a denoise pass under the
causal mask), `least-confident` (the ranking upside down), `token-replaced`
(a token altered after the passes that chose it).
"""

from __future__ import annotations

import dataclasses
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

from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.ops import attention as A  # noqa: E402
from langstream_tpu.serving import engine as E  # noqa: E402

CONFIG, CELL = "sdar-30b-a3b-chat-int8-d12", "sdar30b-d12-blockdecode-drain"
CASES = ("sound", "bf16-router", "int4-attention", "int4-expert-down", "expert-skipped",
         "assignment-dropped", "kv8-block-write", "fp8-block-write", "block-write-skipped",
         "causal-denoise-pass", "least-confident", "token-replaced")
SKIPPED_EXPERT = 5


def say(**line) -> None:
    print(json.dumps(line, default=float), flush=True)


def bf16_route_all(xf, router, config):
    """`_route_all` with the product in bfloat16 at the default precision."""
    logits = jnp.dot(xf.astype(jnp.bfloat16), router.astype(jnp.bfloat16)).astype(jnp.float32)
    top, chosen = jax.lax.top_k(logits, config.n_experts_per_tok)
    return jax.nn.softmax(top, axis=-1), chosen


def dropping_route_all(xf, router, config):
    """`_route_all`, and every 16th token's least assignment weighs nothing."""
    weights, chosen = ROUTE_ALL(xf, router, config)
    return weights.at[::16, -1].set(0.0), chosen


def rounded_writes(to):
    """The two writers of a step's new K/V rows (`paged_kv_write` where the
    kernels run, `_paged_scatter` elsewhere), the rows rounded first as a
    cache of fewer bits keeps them, or not written at all."""

    def rounded(a):
        if to == "fp8":
            return a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        # int8, one scale a token and head, as the program's int8 pool keeps it
        f = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=-1, keepdims=True) / 127.0
        return (jnp.round(f / jnp.maximum(scale, 1e-30)) * scale).astype(a.dtype)

    def write(new, pk, pv, *rest, **kw):
        if to == "skipped":
            return pk, pv
        return KV_WRITE(tuple(rounded(a) for a in new), pk, pv, *rest, **kw)

    def scatter(pool, layer, vals, *rest):
        return pool if to == "skipped" else KV_SCATTER(pool, layer, rounded(vals), *rest)

    return write, scatter


def causal_pass(params, tokens, starts, pool, table, config, page_size, moe_counts=False):
    plain = dataclasses.replace(config, block_length=0, denoise_steps=0, mask_token_id=None)
    return T.paged_verify_step_inplace(
        params, tokens, starts, pool, table, plain, page_size, moe_counts=moe_counts
    )


def least_confident(logits, key, temp, top_k, top_p, is_open, step, mask_id, threshold, schedule):
    tokens, _, over = BLOCK_CHOICE(
        logits, key, temp, top_k, top_p, is_open, step, mask_id, threshold, schedule
    )
    probs = jax.nn.softmax(logits.at[:, :, mask_id].set(-jnp.inf), axis=-1)
    conf = jnp.where(is_open, jnp.max(probs, axis=-1), jnp.inf)
    rank = jnp.argsort(jnp.argsort(conf, axis=-1, stable=True), axis=-1, stable=True)
    at_least = jnp.asarray(schedule, jnp.int32)[jnp.clip(step, 0, len(schedule) - 1)]
    return tokens, is_open & (rank < at_least[:, None]), over


BLOCK_CHOICE, ROUTE_ALL, BLOCK_STEP = E.block_choice, T._route_all, E.paged_block_step_inplace
KV_WRITE, KV_SCATTER = A.paged_kv_write, T._paged_scatter


def cut_to_4_bits(tree: dict, names) -> dict:
    return {**tree, **{k: {**tree[k], "q": (tree[k]["q"] // 16) * 16} for k in names}}


def main(cases: list[str], tiny: bool = False, check_seed=None, new_tokens=None) -> int:
    files = ROOT / "benchmark" / ("tests/data" if tiny else "")
    name, cell = ("tiny-sdar", "tiny-sdar-drain") if tiny else (CONFIG, CELL)
    spec = load_json("configs", name, files)
    knobs = load_json("workloads", cell, files)["engine"]
    family = load_module("families", spec["family"])
    config = register_preset(spec, name, files)
    sample = {k: v for k, v in (("check_seed", check_seed), ("new_tokens", new_tokens)) if v}
    spec = {**spec, "check": {**spec["check"], **sample}}
    out = ROOT / "chiprun_out" / ("sdar_scores_tiny" if tiny else "sdar_scores")
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
        return judge(scores, limits)

    check._judge = keeping
    for case in cases:
        E.block_choice, T._route_all, E.paged_block_step_inplace = BLOCK_CHOICE, ROUTE_ALL, BLOCK_STEP
        A.paged_kv_write, T._paged_scatter = KV_WRITE, KV_SCATTER
        if case == "bf16-router":
            T._route_all = bf16_route_all
        elif case == "assignment-dropped":
            T._route_all = dropping_route_all
        elif case in ("kv8-block-write", "fp8-block-write", "block-write-skipped"):
            A.paged_kv_write, T._paged_scatter = rounded_writes(
                {"kv8": "int8", "fp8": "fp8"}.get(case[:3], "skipped"))
        elif case == "causal-denoise-pass":
            E.paged_block_step_inplace = causal_pass
        elif case == "least-confident":
            E.block_choice = least_confident
        # a config of its own name: the case is traced into programs of its own
        named = dataclasses.replace(config, name=f"{CONFIG}-{case}")
        served = params
        if case == "int4-attention":
            served = {**cut_to_4_bits(params, ["lm_head"]),
                      "layers": cut_to_4_bits(params["layers"], ("wq", "wk", "wv", "wo"))}
        elif case == "int4-expert-down":
            served = {**params, "layers": cut_to_4_bits(params["layers"], ("w_down",))}
        elif case == "expert-skipped":
            down = params["layers"]["w_down"]  # q [L, E, f, d], s [L, E, 1, d]
            served = {**params, "layers": {**params["layers"], "w_down": {
                **down, "s": down["s"].at[:, SKIPPED_EXPERT].set(0)}}}
        engine = E.ServingEngine(
            named, served, max_batch=knobs["max-batch"], max_seq_len=knobs["max-seq-len"],
            prefill_buckets=tuple(knobs["prefill-buckets"]), kv_pages=knobs["kv-pages"],
            page_size=knobs.get("page-size", 64), precompile=False,
        )
        engine.start()
        engine.wait_ready()
        if case == "token-replaced":
            generate = engine.generate

            def altered(prompt, options, timeout=None, generate=generate):
                result = generate(prompt, options, timeout=timeout)
                result.tokens[1] = (result.tokens[1] + 97) % config.mask_token_id
                return result

            engine.generate = altered
        t = time.monotonic()
        try:
            verdict = check.run_check(
                engine, spec, ref_params=params if served is not params else None)
        finally:
            engine.stop()
        verdict.pop("hot_err_by_position", None)
        say(case=case, seconds=round(time.monotonic() - t, 1), ok=verdict["ok"],
            compared=verdict["compared"],
            **{k: v for k, v in verdict.items() if isinstance(v, (int, float)) and k != "ok"})
        scores = kept.pop("scores", None)
        if scores is None:  # a check that ended before it judged
            continue
        np.savez_compressed(
            out / f"{case}{'-' + str(check_seed) if check_seed else ''}.npz",
            **{f"{i}.{j}.{k}": v for i, passes in enumerate(scores)
               for j, s in enumerate(passes) for k, v in s.items()})
        rows = []
        for passes in scores:
            for s in passes:
                own = s["router_gap"].min(axis=0)[s["read"]] if len(s["read"]) else []
                rows += [
                    [round(float(g), 5), round(float(h), 5), round(float(m), 4)]
                    for g, h, m in zip(own, s["hot_err"], s["margin"])
                ]
        say(case=case, own_gap_hot_err_margin=rows)
        if case == "sound":
            gaps = np.concatenate([s["router_gap"].ravel() for p in scores for s in p])
            errs = np.concatenate([s["layer_err"][:-1].ravel() for p in scores for s in p])
            say(case=case, pairs=int(gaps.size),
                gap_share_under={str(e): float((gaps < e).mean()) for e in (0.0005, 0.001, 0.002, 0.005, 0.02)},
                layer_err_quantiles={str(q): float(np.quantile(errs, q)) for q in (0.5, 0.9, 0.99, 0.999)},
                flipped_gap_quantiles={str(q): float(np.quantile(gaps[errs > 0.05], q))
                                       for q in (0.5, 0.9, 0.99, 1.0)} if (errs > 0.05).any() else {})
            for eps in (0.0, 0.0005, 0.001, 0.002, 0.005, 0.02):
                v = judge(scores, {**spec["check"], "eps_router": eps})
                say(case=case, eps_router=eps, ok=v["ok"], compared=v["compared"],
                    engine_positions_tie_exposed=v["engine_positions_tie_exposed"],
                    hot_err_max_unexposed=v["hot_err_max_unexposed"],
                    engine_margin_max=v["engine_margin_max"],
                    engine_choice_behind_max=v.get("engine_choice_behind_max"))
        del engine
    return 0


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--check-seed", type=int)
    parser.add_argument("--new-tokens", type=int)
    parser.add_argument("cases", nargs="*", metavar="case", help=f"of {CASES}; none: all")
    args = parser.parse_args()
    if set(args.cases) - set(CASES):
        parser.error(f"unknown cases {sorted(set(args.cases) - set(CASES))}; there are {CASES}")
    raise SystemExit(main(args.cases or list(CASES), args.tiny, args.check_seed, args.new_tokens))

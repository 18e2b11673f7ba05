"""The correctness check behind `correct`.

Runs in set-up, outside the window, on a sample fixed in the configuration's
`check` block (its own `check_seed`), through an otherwise idle engine, one
request at a time: same program, same weights, same sample, same batch
composition in every run of every seed. Nothing drawn from `--seed` and
nothing observed in the timed window enters the verdict.

The unit of the check is a *pass*: one forward of the model over a sequence
as the model saw it, with the positions whose logits chose tokens in that
forward (`read`), the tokens chosen there (`picked`) and, where the model
chooses WHICH positions to fix, the positions that could have been chosen
(`open`). A family whose engine yields one token a row and a step, left to
right, says nothing and gets ONE pass, built here (`single_pass`): `tokens =
prompt + generated`, `read = n_prompt-1 .. n-2` (the logits at position p
score the token at p + 1), `picked = generated`. A family whose engine fills a
block of tokens by denoising exports `trajectory(spec, prompt, result) -> passes`:
the clean prefix and the block with its mask ids where positions were still
open, once for every forward that chose tokens (and for a forward that only
wrote its state, with an empty `read`); its hot path then returns
`pass_logits(params, prompt, passes)`, one `[len(read), V]` a pass.

Three levels, all against the plain float32 reference on the same dequantised
int8 weights, each over every pass of a sequence and pooled over them:

1. *Model level, teacher-forced.* The program's own block (with the engine's
   config, so its attention dispatch and its mask: the family's
   `system_chain`, `families/<family>.py`) is applied layer by layer to each
   pass's `tokens`. At every layer the reference block is given the
   SYSTEM's input to that layer and the two outputs are compared per
   position: `e = |sys - ref|_inf / |ref|_inf`. Teacher forcing keeps one
   layer's rounding, and one token's router tie, from spreading to every
   later layer and position, so the tolerance can be tight and a tie is
   local: a (layer, position) is *tie-exposed* where the reference's gap
   between the k-th and (k+1)-th router logit is under `eps_router`. Pass:
   median `e` <= `tol_med`, and every (layer, position) with `e > tol_max`
   is tie-exposed. A dense model has no router, so none may exceed. The
   sequence sits in row 0 of a group of `rows` rows, the others padding, as
   the engine fills a prefill group for a lone request: the program's expert
   capacity is per dispatch (`ceil(T*k*2/E)`, T = rows x width), and the
   check says that the model agrees with the published equations where that
   rule does not bind.
2. *Hot path, logits.* The model functions the engine's own programs are
   made of, called as the engine calls them (the family's `hot_path`): for
   Mistral and Mixtral, `prefill` of the group into a local cache (the
   flash prefill kernel), `paged_insert_cache` into a page pool of the
   engine's page size and KV type, then one `paged_decode_step_inplace` per
   generated token through the page table (the ragged paged decode kernel),
   teacher-forced on the engine's own tokens. Per read position
   `h = |hot - chain|_inf / |chain|_inf`
   against the logits of step 1's chain OF THAT PASS at that position, every layer of
   which was just held to the reference (and, for a dense model, its logits
   to the free-running reference by `tol_e2e_max`). Both run in bf16 on the
   same weights and part by one more bf16 rounding a layer, like two runs of
   one kernel in another order; a sequence's first read position (of its
   first pass) comes from the prefill alone, the same kernel on the same values as the chain,
   and sits far closer. Pass: the median `h` over positions not tie-exposed
   <= `tol_hot_med`, and every position with `h > tol_hot_max` (a first
   position: `tol_hot_first`) is one whose OWN token is tie-exposed (router
   gap under `eps_router` in some layer at that position: its own routing
   decides its logits; an earlier token's flip reaches it only through
   attention, diluted by the context length). A KV
   cache of fewer bits, a paged read of the wrong page or a decode kernel of
   lower precision shows here as a number, whatever types the engine reports.
3. *Engine level, margins.* Each sample prompt goes through the engine itself
   (its fused admit and decode programs, sampling, scheduling), greedy,
   `new_tokens` tokens. Per read position p of a pass the margin is
   `max(ref[p]) - ref[p][picked]`, where `ref` is the free-running float32
   reference's logits OF THE PASS THAT CHOSE THE TOKEN (`engine_scores:
   reference`) or, where router ties make a
   free-running reference part ways with any bf16 run, the chain's
   (`engine_scores: verified_chain`). Random weights give near-flat logits,
   so tokens are never compared, only margins. Pass:
   every margin <= `tol_margin`, except at positions whose own token is
   tie-exposed. Where a pass has `open` and the family exports
   `choice_score(logits) -> one number a position` (for a model that fixes
   its most confident positions: the log of the largest softmax probability),
   the engine's CHOICE of positions is held too, from the same `ref`: the
   highest score among the open positions it left may lie above the score of
   a position it fixed by at most `tol_choice`
   (`engine_choice_over_tol_untied`, a row of `compared` only for such a
   family; a position is excused by the same own-token rule).

The engine's state is also held to the file: whatever the family's
`engine_state` finds (int8 weights, the KV dtype) against the same keys of the
`check` block.

This file keeps the loop, the comparison, the tie logic, the judge and what
every tolerance means; it knows no published key, no weight leaf and no model
function, and not how an engine came by its tokens: a family's file does.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any, Callable, Optional

import numpy as np

from modelcfg import HERE, load_module


def sample_prompts(check: dict, vocab_size: int) -> list[list[int]]:
    """The fixed sample: uniform ids below the unknown-word id, from `check_seed`."""
    rng = np.random.default_rng(int(check["check_seed"]))
    return [rng.integers(0, vocab_size - 1, int(n)).tolist() for n in check["lengths"]]


class _Scorer:
    """Layer-by-layer system chain, teacher-forced reference and
    free-running reference over one padded sequence."""

    def __init__(self, family, ref, config, dims: dict, width: int, rows: int = 1,
                 scores: str = "reference") -> None:
        import jax
        import jax.numpy as jnp
        from jax import lax

        self.width, self.rows = width, rows
        self.judge = {"reference": "free", "verified_chain": "chain"}[scores]
        self._chain = family.system_chain(config, width, rows)
        self._ref_stack = family.ref_layer_params

        def rel_err(got, want):
            diff = jnp.max(jnp.abs(got.astype(jnp.float32) - want), axis=-1)
            return diff / jnp.max(jnp.abs(want), axis=-1)

        @jax.jit
        def ref_layer(layers, index, sys_in, sys_out, free_in):
            lp = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, index, 0, keepdims=False), layers
            )
            forced, info = ref.layer(sys_in[0].astype(jnp.float32), lp, dims)
            free, _ = ref.layer(free_in, lp, dims)
            gap = info.get("router_gap", jnp.full((width,), jnp.inf))
            load = info.get("expert_load", jnp.zeros((1,), jnp.int32))
            return rel_err(sys_out[0], forced), gap, load, free

        @jax.jit
        def ref_head(params, sys_x, sys_logits, free_x):
            forced = ref.unembed(params, sys_x[0].astype(jnp.float32), dims)
            free = ref.unembed(params, free_x, dims)
            return rel_err(sys_logits, forced), rel_err(sys_logits, free), free

        @jax.jit
        def margins(logits, read, picked):
            # the logits AT a read position chose the token picked there
            rows = logits[read]
            return jnp.max(rows, axis=-1) - jnp.take_along_axis(rows, picked[:, None], axis=-1)[:, 0]

        @jax.jit
        def hot_err(logits, hot, read):
            # hot[j] is the hot path's distribution at read position j of
            # this pass, which the pass-wide logits hold at read[j]
            return rel_err(hot, logits[read].astype(jnp.float32))

        self._fns = (ref_layer, ref_head, margins, hot_err)
        self._ref_embed = jax.jit(ref.embed)
        choice = getattr(family, "choice_score", None)
        self._choice = None if choice is None else jax.jit(choice)

    def score(self, sys_params, ref_params, a_pass: dict, hot) -> dict:
        """All the per-position numbers for one pass (host arrays).
        `hot` [len(read), V]: the hot path's logits at the pass's read positions."""
        import jax
        import jax.numpy as jnp

        chain = self._chain
        ref_layer, ref_head, margins, hot_err = self._fns
        sequence, n = list(a_pass["tokens"]), len(a_pass["tokens"])
        if n > self.width:
            raise ValueError(f"check sequence of {n} tokens exceeds width {self.width}")
        if len(a_pass["read"]) != len(a_pass["picked"]) or not all(0 <= p < n for p in a_pass["read"]):
            raise ValueError("a pass reads positions of its own tokens, one picked token each")
        tokens = jnp.asarray(sequence + [0] * (self.width - n), jnp.int32)
        read = jnp.asarray(a_pass["read"], jnp.int32)
        picked = jnp.asarray(a_pass["picked"], jnp.int32)
        x = chain.embed(sys_params, tokens)
        free = self._ref_embed(ref_params, tokens)
        errs, gaps, loads = [], [], []
        for index in range(chain.n_layers):
            y = chain.layer(sys_params, index, x)
            # the stack of like layers that holds this one, and its place
            # there: sliced inside the compiled program, never copied out
            err, gap, load, free = ref_layer(*self._ref_stack(ref_params, index), x, y, free)
            errs.append(err)
            gaps.append(gap)
            loads.append(load)
            x = y
        chain_logits = chain.unembed(sys_params, x)
        head_err, e2e_err, free_logits = ref_head(ref_params, x, chain_logits, free)
        judged = chain_logits if self.judge == "chain" else free_logits
        out = {
            "layer_err": jnp.stack(errs + [head_err])[:, :n],  # [L + 1, n]
            "router_gap": jnp.stack(gaps)[:, :n],  # [L, n]
            "expert_load_max": jnp.max(jnp.stack(loads)),
            "e2e_err": e2e_err[:n],
            "margin": margins(judged, read, picked),  # [read]
            "hot_err": hot_err(chain_logits, hot, read),  # [read]
        }
        if self._choice is not None and "open" in a_pass:
            out["choice"] = self._choice(judged)[:n]
        out = {k: np.asarray(v) for k, v in jax.device_get(out).items()}
        # which positions the numbers belong to travels with them to the judge
        out["read"] = np.asarray(a_pass["read"], np.int64)
        if "choice" in out:
            out["left_open"] = np.asarray(sorted(set(a_pass["open"]) - set(a_pass["read"])), np.int64)
        return out


def _engine_state(family, engine, check: dict) -> dict:
    """What the engine holds, against what the file says it should."""
    found = family.engine_state(engine)
    wanted = {key: check.get(key) for key in found}
    return {"found": found, "wanted": wanted, "ok": found == wanted}


def _generate(engine, prompts: list[list[int]], new_tokens: int, together: bool) -> list:
    """Each request's whole result: its `.tokens`, and whatever else the
    engine says of how it made them, which only a family's file can read."""
    from langstream_tpu.models.configs import GenerationOptions
    from langstream_tpu.serving.engine import GenerationRequest

    greedy = GenerationOptions(max_new_tokens=new_tokens, temperature=0.0)
    if not together:
        return [engine.generate(p, greedy, timeout=600) for p in prompts]
    requests = [
        engine.submit(GenerationRequest(prompt_tokens=list(p), options=greedy))
        for p in prompts
    ]
    return [r.result(600) for r in requests]


def single_pass(prompt: list[int], generated: list[int]) -> list[dict]:
    """What an engine that yields one token a row and a step means, as ONE
    pass: a forward over the clean sequence, whose logits at position p chose
    the token at p + 1."""
    n = len(prompt)
    return [{
        "tokens": list(prompt) + list(generated),
        "read": list(range(n - 1, n - 1 + len(generated))),
        "picked": list(generated),
    }]


def _judge(scores: list[list[dict]], check: dict) -> dict:
    """Tolerances of the file over the per-position numbers: `scores` holds,
    for each sequence, the numbers of each of its passes."""
    eps = float(check.get("eps_router", 0.0))
    layer_errs, unexplained, exposed, flipped = [], 0, 0, 0
    gen_n, gen_exposed, margin_bad, hot_bad, first_bad, first_max = 0, 0, 0, 0, 0, 0.0
    margins, hot_errs, e2e, over_gap_max = [], [], [], 0.0
    choice_seen, choice_n, choice_bad, choice_max = False, 0, 0, 0.0
    for passes in scores:
        first = True  # the sequence's first read position is still to come
        for s in passes:
            err, gap, read = s["layer_err"], s["router_gap"], s["read"]
            if not (np.isfinite(err).all() and np.isfinite(s["hot_err"]).all()):
                return {"ok": False, "reason": "non-finite activations",
                        "compared": {"non_finite_sequences": [1, 0]}}
            tie = np.zeros_like(err, bool)
            tie[:-1] = gap < eps  # the head has no router
            over = err > float(check["tol_max"])
            if over[:-1].any():
                finite = gap[over[:-1]][np.isfinite(gap[over[:-1]])]
                over_gap_max = max(over_gap_max, float(finite.max(initial=0.0)))
            layer_errs.append(err.ravel())
            exposed += int(tie.sum())
            flipped += int((over & tie).sum())
            unexplained += int((over & ~tie).sum())
            e2e.append(s["e2e_err"])
            # a token is drawn from the logits at its read position: the token
            # THERE, as this pass saw it, is the one whose own routing decides them
            tied_here = tie[:-1].any(axis=0)
            margin, own_tie = s["margin"], tied_here[read]
            gen_n += len(margin)
            gen_exposed += int(own_tie.sum())
            margin_bad += int(((margin > float(check["tol_margin"])) & ~own_tie).sum())
            hot_bad += int(((s["hot_err"] > float(check["tol_hot_max"])) & ~own_tie).sum())
            if first and len(read):
                first = False
                if not own_tie[0]:  # the prefill's own logits, before any paged read
                    first_max = max(first_max, float(s["hot_err"][0]))
                    first_bad += int(s["hot_err"][0] > float(check["tol_hot_first"]))
            margins.append(margin)
            hot_errs.append(np.where(own_tie, -s["hot_err"], s["hot_err"]))
            if "choice" in s:
                # the engine chose WHICH open positions to fix: the best it
                # left may not outscore one it fixed by more than the tolerance
                choice_seen = True
                left = s["left_open"]
                if len(left) and len(read):
                    best = left[np.argmax(s["choice"][left])]
                    behind = s["choice"][best] - s["choice"][read]
                    excused = own_tie | tied_here[best]
                    choice_n += len(read)
                    choice_max = max(choice_max, float(behind.max()))
                    choice_bad += int(((behind > float(check["tol_choice"])) & ~excused).sum())
    all_err = np.concatenate(layer_errs)
    e2e_all = np.concatenate(e2e)
    hot_all = np.concatenate(hot_errs)  # a tie-exposed position is written negative
    median = float(np.median(all_err))
    verdict = {
        "layer_err_median": median,
        "layer_err_max": float(all_err.max()),
        "layer_positions": int(all_err.size),
        "tie_exposed": exposed,
        "tie_exposed_over_tol": flipped,
        "unexplained_over_tol": unexplained,
        "over_tol_router_gap_max": over_gap_max,
        "e2e_err_median": float(np.median(e2e_all)),
        "e2e_err_max": float(e2e_all.max()),
        "engine_positions": gen_n,
        "engine_positions_tie_exposed": gen_exposed,
        "hot_err_median_unexposed": float(np.median(hot_all[hot_all >= 0])) if (hot_all >= 0).any() else 0.0,
        "hot_err_max_unexposed": float(hot_all.max(initial=0.0)),
        "hot_err_first_max_unexposed": first_max,
        "hot_err_over_tol": hot_bad + first_bad,
        "engine_margin_max": float(np.concatenate(margins).max(initial=0.0)),
        "engine_margin_over_tol": margin_bad,
        "hot_err_by_position": [round(float(v), 5) for v in hot_all],
    }
    # every number the verdict rests on, beside its limit
    compared = {
        "layer_err_median": [median, float(check["tol_med"])],
        "layer_err_over_tol_untied": [unexplained, 0],
        "hot_err_over_tol_untied": [hot_bad + first_bad, 0],
        "engine_margin_over_tol_untied": [margin_bad, 0],
    }
    if check.get("tol_e2e_max") is not None:
        compared["e2e_err_max"] = [verdict["e2e_err_max"], float(check["tol_e2e_max"])]
    if check.get("tol_hot_med") is not None:
        compared["hot_err_median_untied"] = [
            verdict["hot_err_median_unexposed"], float(check["tol_hot_med"])]
    if choice_seen:
        verdict.update(engine_choice_positions=choice_n, engine_choice_behind_max=choice_max,
                       engine_choice_over_tol=choice_bad)
        compared["engine_choice_over_tol_untied"] = [choice_bad, 0]
    verdict["compared"] = compared
    verdict["ok"] = all(value <= limit for value, limit in compared.values())
    return verdict


def run_check(
    engine,
    spec: dict,
    *,
    ref_params: Optional[Any] = None,
    emit: Callable[..., None] = lambda **_: None,
    files: Path = HERE,
) -> dict:
    """The verdict and its evidence. `ref_params` is the tree the reference
    reads; it is the engine's own except in the harness's tests, which hand
    the engine a faulted copy to show that the check can fail. `files` is
    where the cell's files are, the family's and its reference among them."""
    check = spec["check"]
    config = engine.config
    family = load_module("families", spec["family"], files)
    ref = load_module("reference", spec["family"], files)
    dims = family.reference_dims(spec)
    ref_params = engine.params if ref_params is None else ref_params
    prompts = sample_prompts(check, config.vocab_size)
    new_tokens = int(check["new_tokens"])

    state = _engine_state(family, engine, check)
    width, rows = int(check["width"]), int(check.get("rows", 1))
    scorer = _Scorer(family, ref, config, dims, width, rows, check["engine_scores"])
    hot_path = family.hot_path(engine, width, rows, new_tokens)
    if hasattr(family, "trajectory"):
        def passes_of(prompt, result):
            return family.trajectory(spec, prompt, result)

        hot_of = hot_path.pass_logits
    else:
        def passes_of(prompt, result):
            return single_pass(prompt, list(result.tokens))

        def hot_of(params, prompt, passes):
            return [hot_path.logits(params, prompt, passes[0]["picked"])]

    def score(prompt: list[int], result) -> list[dict]:
        passes = passes_of(prompt, result)
        hots = hot_of(engine.params, prompt, passes)
        return [scorer.score(engine.params, ref_params, p, h) for p, h in zip(passes, hots, strict=True)]

    results = _generate(engine, prompts, new_tokens, together=False)
    generated = [list(r.tokens) for r in results]
    answered = all(len(g) > 0 for g in generated)
    if not answered:  # a prompt that produced nothing was not checked at all
        verdict = {"ok": False, "reason": "a check prompt produced no token",
                   "compared": {"check_prompts_unanswered": [sum(not g for g in generated), 0]}}
        emit(phase="check", **verdict)
        return verdict
    scores = [score(p, r) for p, r in zip(prompts, results)]
    verdict = _judge(scores, check)
    verdict["engine_state"] = state
    verdict["generated_tokens"] = [len(g) for g in generated]
    verdict["expert_load_max"] = int(max(s["expert_load_max"] for passes in scores for s in passes))
    verdict["compared"]["engine_state_mismatches"] = [
        sum(state["found"][key] != state["wanted"][key] for key in state["found"]), 0]
    verdict["ok"] = bool(verdict["ok"] and state["ok"])
    emit(phase="check", **verdict)

    if check.get("batched_probe"):
        # the same prompts submitted at once share prefill groups, where the
        # program's expert capacity can drop real tokens (PERF.md, fault 1).
        # A finding to print, never part of the verdict.
        together = _generate(engine, prompts, new_tokens, together=True)
        probe = _judge([score(p, r) for p, r in zip(prompts, together)], check)
        emit(
            phase="check-batched-probe",
            engine_positions=probe.get("engine_positions"),
            engine_margin_over_tol=probe.get("engine_margin_over_tol"),
            engine_margin_max=probe.get("engine_margin_max"),
            hot_err_over_tol=probe.get("hot_err_over_tol"),
            same_tokens_as_alone=[a == list(r.tokens) for a, r in zip(generated, together)],
        )
    return verdict

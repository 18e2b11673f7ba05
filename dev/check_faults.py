"""A configuration's check on the chip, sound and faulted, in ONE process: the
weights are made once, then an engine a case (the cell's knobs, no warm-up:
only the check's shapes compile), `check.run_check` over it, and the rows of
`compared` printed beside what they rest on. By hand, through the chip tool;
not part of the benchmark's command.

    python3 dev/check_faults.py <family> [--tiny] [--samples W:n,n,n;W:n,n,n]
        [--check-seeds n,n] [--new-tokens n] [case ...]

`<family>` is one of FAMILIES (`keye`, `glm`, `kimi`, `dots3`, `lfm2`, `sdar`);
no case: all of the family's. `--tiny`: the test-size configuration and cell
of `benchmark/tests/data`, a rehearsal on the CPU. `--samples`: widths with
their prompt lengths in place of the file's, tried in turn by the first case
until one fits the device (the check holds three float32 `[width,
vocabulary]` arrays beside the engine; the first case's engine is then
warmed as the provider warms the cell's, so that what fits here fits there).
`--check-seeds`: every case once a seed, each in place of the file's
`check_seed`, which draws the sample's token ids: other prompts of the same
lengths. `--new-tokens`: tokens generated a sequence in place of the file's.

A case's line holds its verdict, the hot path's error at each of the engine's
positions (`hot_err_by_position`, a tie-exposed one negative) and the
family's own report of its worst pairs; every case's per-position numbers go
whole to `chiprun_out/<family>_scores/<case>[-<seed>].npz` (`_scores_tiny`
with `--tiny`), so that `check._judge` can run over them again under other
limits with no chip: `rejudge()`. Where a family says which cases must pass
(`controls`), the exit code is 1 unless those pass and every other fails.

A case is a row of its family's table: what it puts in place of a private
name of the program (PATCHED says which names; `tests/test_dev_scripts.py`
holds every one of them to exist, so a rename fails a test and not a chip
session), what it alters of the configuration, of the weights the engine
serves (the reference keeps the sound tree) or of the engine once built.

KEYE (PERF.md section 6, PRs 43, 44). `gather`: sound, with a decode step's
selected read held to `_sparse_decode_attention` where the kernels would walk
the row's pages under the mask: the same selection read another way. The
selection: `recent-keys` (the most RECENT top-k keys in place of the ranked
ones), `dense` (no selection), `half-topk`, `bf16-scores` (the indexer's
scores rounded to bfloat16 where the file says float32). The pool's third
leaf (the chain never writes a pool, so it stays sound): `ik8` (the indexer's
keys rounded to 8 bits, one scale a token, where they are written),
`stale-ik` (a decode step's indexer key is not written), `own-columns` (a
segment ranks only its OWN columns). Controls on the block this model shares:
`bf16-router`, `expert-skipped`. The attention halves' rows of `router_gap`
are saved RAW (the gap between a query's topk-th and next indexer score; the
verdict printed is judged as the harness judges: a query under the file's
`eps_select` reads 0, every other infinity).

GLM (PR 47) adds `indexer-from-u` (the indexer's queries from the hidden
state's first lanes, not the query latent), `bias-weighs` / `no-bias` (the
router's bias weighs an expert, or does not choose it), `scaling-1`,
`no-shared`, `no-krope` (the absorbed query's rotary half zeroed), `lat8` (the
latent rounded to 8 bits where it is written). KIMI (PR 50): `no-yarn-blend`,
`no-mscale`, `value-from-key` (a head's value through its KEY's lanes of
`wkv_b`). DOTS3 (PR 57): `no-gate`, `gate-on-v`, `window-512` / `window-514`,
`one-rotary-base`, `window-scale-192`, `no-rescale-kv`, `value-at-64`,
`winlat8` (the window kind's latent at 8 bits). LFM2 (PR 55):
`expert-adds-nothing`, `int4-mixers`, `no-qk-norm`, `tail-dropped` /
`tail-from-padding` (the convolution's carried tail), `no-router-eps` (a
control that must pass). SDAR (PR 41), by level: the model (`bf16-router`,
`int4-attention`, `int4-expert-down`, `expert-skipped`, `assignment-dropped`),
the hot path (`kv8-block-write`, `fp8-block-write`, `block-write-skipped`),
the engine alone (`causal-denoise-pass`, `least-confident`,
`token-replaced`); `sound` also prints the verdict under a grid of
`eps_router`.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Any, Callable, NamedTuple, Optional

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "benchmark")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import check  # noqa: E402
from modelcfg import load_json, load_module, register_preset  # noqa: E402

from langstream_tpu.models import configs as C  # noqa: E402
from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.models.quant import quantized_matmul  # noqa: E402
from langstream_tpu.ops import attention as A  # noqa: E402
from langstream_tpu.serving import engine as E  # noqa: E402

# every name a case puts something in place of, by where it lives
PATCHED = {
    T: ("_select_mask", "_index_scores", "_write_index_key", "_selected_attention", "_route_all",
        "_WALK_TABLE_PER_TOPK", "_index_proj", "_latent_absorb", "_paged_scatter",
        "_yarn_tables", "_latent_value_out", "_head_gate", "_rescaled", "_wkv_b", "_kept_width",
        "_latent_proj", "_latent_expand", "_short_conv"),
    A: ("segment_select", "paged_kv_write"),
    E: ("block_choice", "paged_block_step_inplace"),
    C.ModelConfig: ("attn_scale",),
}
SOUND = {
    (module, name): module.__dict__[name] if module is C.ModelConfig else getattr(module, name)
    for module, names in PATCHED.items() for name in names
}
WINDOW = "sliding_attention"
SKIPPED_EXPERT = 5


def restore() -> None:
    for (module, name), sound in SOUND.items():
        setattr(module, name, sound)
    C._kind_view.cache_clear()


class From:
    """A replacement made from the run (`ctx`: the configuration, the cell's
    knobs), where a fault needs one of its sizes."""

    def __init__(self, make: Callable[[Any], Any]) -> None:
        self.make = make


class Case(NamedTuple):
    """What a case alters; `sound` alters nothing."""

    patch: dict = {}  # (module, name) of PATCHED -> the replacement, or a From
    config: Optional[Callable] = None  # (the case's configuration, ctx) -> another
    weights: Optional[Callable] = None  # the tree -> the tree the ENGINE serves
    engine: Optional[Callable] = None  # (the engine built, ctx): altered in place


def say(**line) -> None:
    print(json.dumps(line, default=float), flush=True)


def _bytes_in_use() -> int:
    return max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.devices())


def replaced(**fields) -> Callable:
    return lambda named, ctx: dataclasses.replace(named, **fields)


def eight_bits(rows):
    """``rows`` rounded to 8 bits, one scale a row of the last axis."""
    f32 = rows.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(f32), axis=-1, keepdims=True), 1e-8) / 127
    return (jnp.round(f32 / scale) * scale).astype(rows.dtype)


# -- the selection's raw gap: saved, and judged as the file judges ----------------


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


def rejudge(directory, limits: dict, select_gap: bool = True) -> dict:
    """case -> the verdict `check._judge` gives the saved numbers under
    `limits` (a `check` block: its tolerances, `eps_router` and `eps_select`).
    ``select_gap``: the family saved its selection's raw gap (FAMILIES)."""
    out = {}
    for path in sorted(Path(directory).glob("*.npz")):
        scores = load_scores(path)
        if select_gap:
            scores = as_the_file_judges(scores, float(limits.get("eps_select", 0.0)))
        out[path.stem] = check._judge(scores, limits)
    return out


# -- the faults -------------------------------------------------------------------


def bf16_route_all(xf, router, config):
    """`_route_all` (softmax) with the product in bfloat16 at the default precision."""
    logits = jnp.dot(xf.astype(jnp.bfloat16), router.astype(jnp.bfloat16)).astype(jnp.float32)
    top, chosen = jax.lax.top_k(logits, config.n_experts_per_tok)
    return jax.nn.softmax(top, axis=-1), chosen


def dropping_route_all(xf, router, config):
    """`_route_all`, and every 16th token's least assignment weighs nothing."""
    weights, chosen = SOUND[T, "_route_all"](xf, router, config)
    return weights.at[::16, -1].set(0.0), chosen


def route(weigh_bias: bool = False, choose_bias: bool = True, bf16: bool = False):
    """`_route_all` of a sigmoid router under a bias, faulted."""

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
        total = jnp.sum(top, axis=-1, keepdims=True) + config.router_norm_eps
        return config.routed_scaling * top / total, chosen

    return route_all


def expert_skipped(params):
    down = params["layers"]["w_down"]  # q [L, E, f, d], s [L, E, 1, d]
    return {**params, "layers": {**params["layers"], "w_down": {
        **down, "s": down["s"].at[:, SKIPPED_EXPERT].set(0)}}}


def recent_keys(scores, visible, k):
    place = jnp.broadcast_to(jnp.arange(scores.shape[-1], dtype=jnp.float32), scores.shape)
    return SOUND[T, "_select_mask"](place, visible, k)


def recent_segment_select(q_idx, w, k_idx, offsets, topk, interpret=False):
    """[B, S, T] int8: each query's most recent `topk` columns."""
    s, t = q_idx.shape[1], k_idx.shape[1]
    at = offsets[:, None, None] + jnp.arange(s)[None, :, None]
    column = jnp.arange(t)[None, None, :]
    return ((column <= at) & (column > at - topk)).astype(jnp.int8)


RECENT_KEYS = Case({(T, "_select_mask"): recent_keys, (A, "segment_select"): recent_segment_select})


def bf16_scores(q_idx, w, k_idx):
    """(A decode step's scores, and a segment's where the kernels' tiles do
    not fit: with them `segment_select` scores in tiles, which this leaves.)"""
    return SOUND[T, "_index_scores"](q_idx, w, k_idx).astype(jnp.bfloat16).astype(jnp.float32)


def ik8_write(pik, layer, k_idx, table, positions, page_size):
    return SOUND[T, "_write_index_key"](pik, layer, eight_bits(k_idx), table, positions, page_size)


def stale_write(pik, layer, k_idx, table, positions, page_size):
    if k_idx.shape[1] == 1:
        return pik
    return SOUND[T, "_write_index_key"](pik, layer, k_idx, table, positions, page_size)


def own_columns(q, q_idx, w, k_idx_all, k_all, v_all, mask, positions, config, what):
    if what == "paged-segment":  # columns before the segment's first: zeros
        before = jnp.arange(k_idx_all.shape[1])[None, :, None] < positions[:, :1, None]
        k_idx_all = jnp.where(before, 0, k_idx_all)
    return SOUND[T, "_selected_attention"](
        q, q_idx, w, k_idx_all, k_all, v_all, mask, positions, config, what)


def index_from_u(u, lp, positions, config, c_q=None, rotary=None):
    return SOUND[T, "_index_proj"](
        u, lp, positions, config, c_q=u[..., : config.q_lora_rank], rotary=rotary
    )


def absorb_without_rope(q, lp, config, width):
    nope = config.qk_nope_head_dim
    return SOUND[T, "_latent_absorb"](q.at[..., nope:].set(0), lp, config, width)


def scatter_8_bits(pool, layer, vals, table, positions, page_size):
    """The latent's rows [B, 1, S, W] rounded to 8 bits, one scale a token."""
    return SOUND[T, "_paged_scatter"](pool, layer, eight_bits(vals), table, positions, page_size)


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


def no_gate(attn, u, lp, config):
    return attn


_GATE: dict = {}  # `gate-on-v`: the projection's gate, until the expansion takes it


def gating_proj(x, lp, sin, cos, config):
    """`_latent_proj` that keeps the gate of the tokens it saw ..."""
    out = SOUND[T, "_latent_proj"](x, lp, sin, cos, config)
    _GATE["gate"] = jax.nn.sigmoid(
        quantized_matmul(out[0], lp["w_attn_gate"]).astype(jnp.float32)
    )  # [B, S, H]
    return out


def gated_expand(lat, lp, config):
    """... and the `_latent_expand` of the same tokens, which gates each one's
    VALUE by it (a trace runs the two one after the other)."""
    k, v = SOUND[T, "_latent_expand"](lat, lp, config)
    gate = _GATE.pop("gate", None)
    if gate is not None and gate.shape[:2] == (v.shape[0], v.shape[2]):
        v = (v.astype(jnp.float32) * gate.transpose(0, 2, 1)[..., None]).astype(v.dtype)
    return k, v


def scale_of_the_full_kind(ctx):
    sound, full_width = SOUND[C.ModelConfig, "attn_scale"].fget, ctx.config.resolved_head_dim

    def attn_scale(self):
        return full_width**-0.5 if self.kind_view == WINDOW else sound(self)

    return property(attn_scale)


def rescale_but(ratio_left_out: float):
    def rescaled(c, ratio):
        return c if ratio == ratio_left_out else SOUND[T, "_rescaled"](c, ratio)

    return rescaled


def value_from_lane_64(lp, config):
    w, scale = SOUND[T, "_wkv_b"](lp, config)
    if config.kind_view:  # the window kind's is sound
        return w, scale
    nope, v = config.qk_nope_head_dim, config.v_head_dim
    at = nope // 2  # 64 of 128: where a 192-wide value would start in a head's 256
    shift = lambda a: jnp.concatenate([a[..., :nope], a[..., at:at + v]], axis=-1)  # noqa: E731
    return shift(w), None if scale is None else shift(scale)


def latent_8_bits(width: int):
    """`_kept_width` that rounds the latent of ``width`` (one kind's) to 8 bits."""

    def kept_width(row, leaf):
        return SOUND[T, "_kept_width"](eight_bits(row) if row.shape[-1] == width else row, leaf)

    return kept_width


def tail_dropped(inputs, taps, rec, layer, rows, valid, fresh, activation=None):
    """`_short_conv` reading zeros wherever it would read a carried tail (the
    tail it leaves is the sound one: the NEXT reader drops it again)."""
    sound = SOUND[T, "_short_conv"]
    mixed, out = sound(inputs, taps, rec, layer, rows, valid, fresh, activation)
    if rec is not None and fresh is not True:
        zeros = {**rec, "conv": jnp.zeros_like(rec["conv"])}
        mixed, _ = sound(inputs, taps, zeros, layer, rows, valid, fresh, activation)
    return mixed, out


def tail_from_padding(inputs, taps, rec, layer, rows, valid, fresh, activation=None):
    """`_short_conv` leaving each row of a group the last K - 1 columns of the
    padded width (a decode step's one column is its own)."""
    everything = valid if inputs.shape[1] == 1 else jnp.ones_like(valid)
    return SOUND[T, "_short_conv"](inputs, taps, rec, layer, rows, everything, fresh, activation)


def _over_stacks(params, fn):
    out = dict(params)
    for stack in ("dense_layers", "layers"):
        out[stack] = {kind: fn(dict(layers)) for kind, layers in params[stack].items()}
    return out


def expert_adds_nothing(params):
    def silence(layers):
        if "router" in layers:  # expert 0 of every expert layer
            down = layers["w_down"]
            layers["w_down"] = {**down, "q": down["q"].at[:, 0].set(0)}
        return layers

    return _over_stacks(params, silence)


def int4_mixers(params):
    def coarse(layers):
        for name in ("w_in", "w_out", "wq", "wk", "wv", "wo"):
            if name in layers:  # 15 levels in place of 255: round to multiples of 16
                w = layers[name]
                q = (jnp.round(w["q"].astype(jnp.float32) / 16.0) * 16.0).clip(-127, 127)
                layers[name] = {**w, "q": q.astype(jnp.int8)}
        return layers

    return _over_stacks(params, coarse)


def cut_to_4_bits(tree: dict, names) -> dict:
    return {**tree, **{k: {**tree[k], "q": (tree[k]["q"] // 16) * 16} for k in names}}


def rounded_writes(to) -> dict:
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
        return SOUND[A, "paged_kv_write"](tuple(rounded(a) for a in new), pk, pv, *rest, **kw)

    def scatter(pool, layer, vals, *rest):
        if to == "skipped":
            return pool
        return SOUND[T, "_paged_scatter"](pool, layer, rounded(vals), *rest)

    return {(A, "paged_kv_write"): write, (T, "_paged_scatter"): scatter}


def causal_pass(params, tokens, starts, pool, table, config, page_size, moe_counts=False):
    plain = dataclasses.replace(config, block_length=0, denoise_steps=0, mask_token_id=None)
    return T.paged_verify_step_inplace(
        params, tokens, starts, pool, table, plain, page_size, moe_counts=moe_counts
    )


def least_confident(
    logits, key, temperature, top_k, top_p, is_open, step, mask_id, threshold, schedule
):
    tokens, _, over = SOUND[E, "block_choice"](
        logits, key, temperature, top_k, top_p, is_open, step, mask_id, threshold, schedule
    )
    probs = jax.nn.softmax(logits.at[:, :, mask_id].set(-jnp.inf), axis=-1)
    conf = jnp.where(is_open, jnp.max(probs, axis=-1), jnp.inf)
    rank = jnp.argsort(jnp.argsort(conf, axis=-1, stable=True), axis=-1, stable=True)
    at_least = jnp.asarray(schedule, jnp.int32)[jnp.clip(step, 0, len(schedule) - 1)]
    return tokens, is_open & (rank < at_least[:, None]), over


def token_replaced(engine, ctx) -> None:
    """A token altered after the passes that chose it."""
    generate = engine.generate

    def altered(prompt, options, timeout=None):
        result = generate(prompt, options, timeout=timeout)
        result.tokens[1] = (result.tokens[1] + 97) % ctx.config.mask_token_id
        return result

    engine.generate = altered


# -- what a family prints of a case's scores ----------------------------------------


def worst_pairs(case, scores, ctx) -> None:
    """The worst pairs of level 1, by (sequence, chain step, position): which
    half, where, how near a tie (an attention half's gap is its selection's,
    an expert half's its router's)."""
    worst = []
    for i, passes in enumerate(scores):
        for s in passes:
            err, gap = s["layer_err"], s["router_gap"]
            for flat in np.argsort(err, axis=None)[-4:]:
                step, at = np.unravel_index(flat, err.shape)
                worst.append((float(err[step, at]), i, int(step), int(at),
                              float(gap[step, at]) if step < gap.shape[0] else None))
    say(case=case, worst_pairs=sorted(worst, reverse=True)[:8])


def worst_under_the_selection(case, scores, ctx) -> None:
    worst = []
    for i, passes in enumerate(scores):
        for s in passes:
            err, gap = s["layer_err"][:-1], s["router_gap"]
            for flat in np.argsort(err, axis=None)[-6:]:
                step, pos = np.unravel_index(flat, err.shape)
                worst.append([round(float(err[step, pos]), 5), i, int(step), int(pos),
                              round(float(gap[step, pos]), 6)])
    say(case=case, worst_err_seq_step_pos_gap=sorted(worst, reverse=True)[:8])


def gaps_beside_errors(case, scores, ctx) -> None:
    """For every read position the smallest router gap of its own token over
    the layers beside its hot-path error and margin; of `sound` also the
    verdict under a grid of `eps_router`."""
    rows = []
    for passes in scores:
        for s in passes:
            own = s["router_gap"].min(axis=0)[s["read"]] if len(s["read"]) else []
            rows += [
                [round(float(g), 5), round(float(h), 5), round(float(m), 4)]
                for g, h, m in zip(own, s["hot_err"], s["margin"])
            ]
    say(case=case, own_gap_hot_err_margin=rows)
    if case != "sound":
        return
    gaps = np.concatenate([s["router_gap"].ravel() for p in scores for s in p])
    errs = np.concatenate([s["layer_err"][:-1].ravel() for p in scores for s in p])
    say(case=case, pairs=int(gaps.size),
        gap_share_under={str(e): float((gaps < e).mean()) for e in (0.0005, 0.001, 0.002, 0.005, 0.02)},
        layer_err_quantiles={str(q): float(np.quantile(errs, q)) for q in (0.5, 0.9, 0.99, 0.999)},
        flipped_gap_quantiles={str(q): float(np.quantile(gaps[errs > 0.05], q))
                               for q in (0.5, 0.9, 0.99, 1.0)} if (errs > 0.05).any() else {})
    for eps in (0.0, 0.0005, 0.001, 0.002, 0.005, 0.02):
        v = ctx.judge(scores, {**ctx.spec["check"], "eps_router": eps})
        say(case=case, eps_router=eps, ok=v["ok"], compared=v["compared"],
            engine_positions_tie_exposed=v["engine_positions_tie_exposed"],
            hot_err_max_unexposed=v["hot_err_max_unexposed"],
            engine_margin_max=v["engine_margin_max"],
            engine_choice_behind_max=v.get("engine_choice_behind_max"))


# -- the families -------------------------------------------------------------------


class Family(NamedTuple):
    config: str
    cell: str
    tiny: str  # `tiny-<it>` and `tiny-<it>-drain` of benchmark/tests/data
    cases: dict
    select_gap: bool = False  # the attention halves' raw gap is saved (`raw_select_gap`)
    controls: Optional[tuple] = ("sound",)  # the cases that must pass; None: not judged
    report: Callable = worst_pairs


DENSE = Case(config=lambda named, ctx: dataclasses.replace(
    named, index_topk=ctx.knobs["max-seq-len"]))
SIGMOID_ROUTER = {
    "bias-weighs": Case({(T, "_route_all"): route(weigh_bias=True)}),
    "no-bias": Case({(T, "_route_all"): route(choose_bias=False)}),
    "bf16-router": Case({(T, "_route_all"): route(bf16=True)}),
}
LATENT = {
    "scaling-1": Case(config=replaced(routed_scaling=1.0)),
    "no-shared": Case(config=replaced(n_shared_experts=0)),
    "no-krope": Case({(T, "_latent_absorb"): absorb_without_rope}),
    "lat8": Case({(T, "_paged_scatter"): scatter_8_bits}),
}

FAMILIES = {
    "keye": Family(
        "keye-vl-2.0-30b-a3b-int8-d12", "keyevl2-d12-longdoc-drain", "keye",
        {
            "sound": Case(),
            "gather": Case({(T, "_WALK_TABLE_PER_TOPK"): 0}),
            "recent-keys": Case({(T, "_select_mask"): recent_keys}),
            "dense": DENSE,
            "half-topk": Case(config=lambda named, ctx: dataclasses.replace(
                named, index_topk=ctx.config.index_topk // 2)),
            "bf16-scores": Case({(T, "_index_scores"): bf16_scores}),
            "own-columns": Case({(T, "_selected_attention"): own_columns}),
            "ik8": Case({(T, "_write_index_key"): ik8_write}),
            "stale-ik": Case({(T, "_write_index_key"): stale_write}),
            "bf16-router": Case({(T, "_route_all"): bf16_route_all}),
            "expert-skipped": Case(weights=expert_skipped),
        },
        select_gap=True, controls=None, report=worst_under_the_selection,
    ),
    "glm": Family(
        "glm-5-int8-ep16-d7", "glm5-ep16-d7-longdoc-drain", "glm",
        {
            "sound": Case(), "recent-keys": RECENT_KEYS, "dense": DENSE,
            "indexer-from-u": Case({(T, "_index_proj"): index_from_u}),
            **SIGMOID_ROUTER, **LATENT,
        },
        select_gap=True,
    ),
    "kimi": Family(
        "kimi-k2.5-int8-ep32-d7", "kimik25-ep32-d7-longdoc-drain", "kimi",
        {
            "sound": Case(),
            "no-yarn-blend": Case({(T, "_yarn_tables"): plain_tables}),
            # both zero: the tables' own factor stays 1
            "no-mscale": Case(config=replaced(
                rope_scaling_mscale=0.0, rope_scaling_mscale_all_dim=0.0)),
            "value-from-key": Case({(T, "_latent_value_out"): value_from_key_lanes}),
            **SIGMOID_ROUTER, **LATENT,
        },
    ),
    "dots3": Family(
        "dots3-note-prev-int8-ep16-d9", "dots3-ep16-d9-longdoc-drain", "dots3",
        {
            "sound": Case(),
            "no-gate": Case({(T, "_head_gate"): no_gate}),
            "gate-on-v": Case({
                (T, "_head_gate"): no_gate, (T, "_latent_proj"): gating_proj,
                (T, "_latent_expand"): gated_expand,
            }),
            "window-512": Case(config=lambda named, ctx: dataclasses.replace(
                named, sliding_window=ctx.config.sliding_window - 1)),
            "window-514": Case(config=lambda named, ctx: dataclasses.replace(
                named, sliding_window=ctx.config.sliding_window + 1)),
            "one-rotary-base": Case(config=lambda named, ctx: dataclasses.replace(
                named, window_attention=tuple(
                    kv for kv in ctx.config.window_attention if kv[0] != "rope_theta"))),
            "window-scale-192": Case({(C.ModelConfig, "attn_scale"): From(scale_of_the_full_kind)}),
            "no-rescale-kv": Case({(T, "_rescaled"): From(
                lambda ctx: rescale_but(ctx.config.d_model / ctx.config.kv_lora_rank))}),
            "value-at-64": Case({(T, "_wkv_b"): value_from_lane_64}),
            "recent-keys": RECENT_KEYS,
            "winlat8": Case({(T, "_kept_width"): From(
                lambda ctx: latent_8_bits(ctx.config.of_kind(WINDOW).latent_width))}),
            "lat8": Case({(T, "_kept_width"): From(
                lambda ctx: latent_8_bits(ctx.config.latent_width))}),
            "bf16-router": SIGMOID_ROUTER["bf16-router"],
        },
        select_gap=True,
    ),
    "lfm2": Family(
        "lfm2-24b-a2b-int8-d16", "lfm2-24b-d16-decode-drain-256", "lfm2",
        {
            "sound": Case(),
            "bf16-router": SIGMOID_ROUTER["bf16-router"],
            "bias-weighs": SIGMOID_ROUTER["bias-weighs"],
            "expert-adds-nothing": Case(weights=expert_adds_nothing),
            "int4-mixers": Case(weights=int4_mixers),
            "no-qk-norm": Case(config=replaced(qk_norm_heads=False)),
            "tail-dropped": Case({(T, "_short_conv"): tail_dropped}),
            "tail-from-padding": Case({(T, "_short_conv"): tail_from_padding}),
            "no-router-eps": Case(config=replaced(router_norm_eps=0.0)),
        },
        controls=("sound", "no-router-eps"),
    ),
    "sdar": Family(
        "sdar-30b-a3b-chat-int8-d12", "sdar30b-d12-blockdecode-drain", "sdar",
        {
            "sound": Case(),
            "bf16-router": Case({(T, "_route_all"): bf16_route_all}),
            "int4-attention": Case(weights=lambda params: {
                **cut_to_4_bits(params, ["lm_head"]),
                "layers": cut_to_4_bits(params["layers"], ("wq", "wk", "wv", "wo"))}),
            # (a second copy of all three expert matrices does not fit the chip
            # beside the reference's)
            "int4-expert-down": Case(weights=lambda params: {
                **params, "layers": cut_to_4_bits(params["layers"], ("w_down",))}),
            "expert-skipped": Case(weights=expert_skipped),
            "assignment-dropped": Case({(T, "_route_all"): dropping_route_all}),
            "kv8-block-write": Case(rounded_writes("int8")),
            "fp8-block-write": Case(rounded_writes("fp8")),
            "block-write-skipped": Case(rounded_writes("skipped")),
            "causal-denoise-pass": Case({(E, "paged_block_step_inplace"): causal_pass}),
            "least-confident": Case({(E, "block_choice"): least_confident}),
            "token-replaced": Case(engine=token_replaced),
        },
        controls=None, report=gaps_beside_errors,
    ),
}


# -- the loop ------------------------------------------------------------------------


def main(family: str, cases: list[str], tiny: bool = False, samples=(), check_seeds=(),
         new_tokens: int = 0) -> int:
    fam = FAMILIES[family]
    files = ROOT / "benchmark" / ("tests/data" if tiny else "")
    name, cell = (f"tiny-{fam.tiny}", f"tiny-{fam.tiny}-drain") if tiny else (fam.config, fam.cell)
    spec = load_json("configs", name, files)
    knobs = load_json("workloads", cell, files)["engine"]
    config = register_preset(spec, name, files)
    samples = list(samples) or [(spec["check"]["width"], spec["check"]["lengths"])]
    out = ROOT / "chiprun_out" / f"{family}_scores{'_tiny' if tiny else ''}"
    out.mkdir(parents=True, exist_ok=True)
    t = time.monotonic()
    params = load_module("families", spec["family"]).make_params(
        config, int(spec["weights"]["seed"]))
    jax.block_until_ready(params)
    say(phase="weights", seconds=round(time.monotonic() - t, 1),
        device=jax.devices()[0].device_kind)
    kept: dict = {}
    judge, load = check._judge, check.load_module
    ctx = SimpleNamespace(config=config, knobs=knobs, spec=spec, judge=judge)

    def keeping(scores, limits):
        kept["scores"] = scores
        if fam.select_gap:
            scores = as_the_file_judges(scores, float(limits.get("eps_select", 0.0)))
        return judge(scores, limits)

    check._judge = keeping
    if fam.select_gap:
        check.load_module = raw_select_gap(load)
    failed = 0
    first = (cases[0], (check_seeds or [None])[0])
    try:
        for case, check_seed in [(c, n) for n in (check_seeds or [None]) for c in cases]:
            restore()
            fault = fam.cases[case]
            for (module, attr), put in fault.patch.items():
                setattr(module, attr, put.make(ctx) if isinstance(put, From) else put)
            # a config of its own name: the case is traced into programs of its own
            named = dataclasses.replace(config, name=f"{name}-{case}")
            if fault.config is not None:
                named = fault.config(named, ctx)
            served = params if fault.weights is None else fault.weights(params)
            while True:
                width, lengths = samples[0]
                sized = {**spec, "check": {**spec["check"], "width": width, "lengths": lengths}}
                if check_seed is not None:
                    sized["check"]["check_seed"] = check_seed
                if new_tokens:
                    sized["check"]["new_tokens"] = new_tokens
                in_use = _bytes_in_use()
                engine = E.ServingEngine(
                    named, served, max_batch=knobs["max-batch"], max_seq_len=knobs["max-seq-len"],
                    prefill_buckets=tuple(knobs["prefill-buckets"]), kv_pages=knobs["kv-pages"],
                    page_size=knobs.get("page-size", 64), prefill_batch=knobs.get("prefill-batch"),
                    # the first case finds the width that fits: see `--samples`
                    precompile=(case, check_seed) == first and len(samples) > 1,
                )
                engine.start()
                engine.wait_ready()
                if fault.engine is not None:
                    fault.engine(engine, ctx)
                t = time.monotonic()
                does_not_fit = None
                try:
                    verdict = check.run_check(
                        engine, sized, ref_params=params if served is not params else None,
                        files=files)
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
                bytes_in_use_before=in_use, hot_err_by_position=by_position,
                **({} if check_seed is None else {"check_seed": check_seed}),
                **{k: v for k, v in verdict.items() if isinstance(v, (int, float)) and k != "ok"})
            if fam.controls is not None:
                failed += (case in fam.controls) != bool(verdict["ok"])
            scores = kept.pop("scores", None)
            if scores is None:  # a check that ended before it judged
                continue
            np.savez_compressed(
                out / (f"{case}.npz" if check_seed is None else f"{case}-{check_seed}.npz"),
                **{f"{i}.{j}.{k}": v for i, passes in enumerate(scores)
                   for j, s in enumerate(passes) for k, v in s.items()})
            fam.report(case, scores, ctx)
    finally:
        restore()
        check._judge, check.load_module = judge, load
    say(phase="done", cases=len(cases), not_as_expected=failed if fam.controls else None)
    return 1 if failed else 0


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser()
    parser.add_argument("family", choices=sorted(FAMILIES))
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--samples", default="", help="W:n,n,n;W:n,n,n, tried in turn")
    parser.add_argument("--check-seeds", default="", help="n,n: each in place of the file's")
    parser.add_argument("--new-tokens", type=int, default=0, help="in place of the file's")
    parser.add_argument("cases", nargs="*", metavar="case", help="of the family's; none: all")
    args = parser.parse_args()
    known = FAMILIES[args.family].cases
    if set(args.cases) - set(known):
        parser.error(f"unknown cases {sorted(set(args.cases) - set(known))}; there are {list(known)}")
    raise SystemExit(main(
        args.family, args.cases or list(known), args.tiny,
        [(int(w), [int(n) for n in ns.split(",")])
         for w, ns in (part.split(":") for part in args.samples.split(";") if part)],
        [int(n) for n in args.check_seeds.split(",") if n], args.new_tokens,
    ))

"""The dots3-note-prev configuration's check on the chip, sound and faulted, in
ONE process, in the manner of `dev/glm_check_faults.py` (whose loop, printing
and files this follows): the weights are made once, then an engine a case (the
cell's knobs, no warm-up), `check.run_check` over it, and the rows of
`compared` printed with every number of the verdict and the worst pairs of
level 1. By hand, through the chip tool; not part of the benchmark's command.

    python3 dev/dots3_check_faults.py [--tiny] [case ...]

(`--tiny`: the test-size configuration and cell of `benchmark/tests/data`, a
rehearsal on the CPU.) Every case's per-position numbers go to
`chiprun_out/dots3_scores/<case>.npz`; `rejudge(directory, limits)`
(`dev/keye_check_faults.py`'s) judges them again under any limits with no chip.

Cases; the engine, the chain and the hot path all run the fault, the reference
keeps the file's arithmetic and tree. `sound`. The gate: `no-gate` (left
out), `gate-on-v` (taken BEFORE the softmax's mix, on each token's value, in
place of after: the expanded form over a sequence's own tokens, so the chain
and the admit group's prefill; elsewhere the gate is left out). The window:
`window-512`, `window-514` (a token fewer, a token more), `one-rotary-base`
(the window kind turned by the full kind's base), `window-scale-192` (the
window kind's scores scaled by 1/sqrt(192), the full kind's width).
`no-rescale-kv` (the rescale left out of ONE latent, the full kind's key-value
latent). `value-at-64` (the full kind's head split as if its value were 192
wide, the key's width: read from lane 64 of a head's 256, its first 128 kept).
`recent-keys` (the most recent 2,048 in place of the ranked, in a decode
step's ranking and a segment's). `winlat8` (a WINDOW latent rounded to 8 bits,
one scale a token, where cache and pool are written: the nearest precision
below the bfloat16 the file states; the chain keeps no cache, so levels 2 and
3 alone can see it), `lat8` (the same of the FULL kind's latent), `bf16-router`
(the router's product in bfloat16, the nearest precision below the float32
the file states; `dev/glm_check_faults.py`'s).
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
from glm_check_faults import recent_keys, recent_segment_select, route, say  # noqa: E402
from keye_check_faults import as_the_file_judges, raw_select_gap, rejudge  # noqa: E402,F401
from modelcfg import load_json, load_module, register_preset  # noqa: E402

from langstream_tpu.models import configs as C  # noqa: E402
from langstream_tpu.models import transformer as T  # noqa: E402
from langstream_tpu.models.quant import quantized_matmul  # noqa: E402
from langstream_tpu.ops import attention as A  # noqa: E402
from langstream_tpu.serving import engine as E  # noqa: E402

CONFIG, CELL = "dots3-note-prev-int8-ep16-d9", "dots3-ep16-d9-longdoc-drain"
CASES = ("sound", "no-gate", "gate-on-v", "window-512", "window-514", "one-rotary-base",
         "window-scale-192", "no-rescale-kv", "value-at-64", "recent-keys", "winlat8", "lat8",
         "bf16-router")
WINDOW = "sliding_attention"
PATCHED = {
    T: ("_select_mask", "_head_gate", "_rescaled", "_wkv_b", "_kept_width", "_latent_proj",
        "_latent_expand", "_route_all"),
    A: ("segment_select",),
    C.ModelConfig: ("attn_scale",),
}
SOUND = {(module, name): module.__dict__[name] if module is C.ModelConfig
         else getattr(module, name) for module, names in PATCHED.items() for name in names}


def no_gate(attn, u, lp, config):
    return attn


def gate_on_v():
    """(`_latent_proj`, `_latent_expand`) that gate each token's VALUE: the
    projection keeps the gate of the tokens it saw, the expansion of the same
    tokens takes it (a trace runs the two one after the other)."""
    kept = {}

    def proj(x, lp, sin, cos, config):
        out = SOUND[T, "_latent_proj"](x, lp, sin, cos, config)
        kept["gate"] = jax.nn.sigmoid(
            quantized_matmul(out[0], lp["w_attn_gate"]).astype(jnp.float32)
        )  # [B, S, H]
        return out

    def expand(lat, lp, config):
        k, v = SOUND[T, "_latent_expand"](lat, lp, config)
        gate = kept.pop("gate", None)
        if gate is not None and gate.shape[:2] == (v.shape[0], v.shape[2]):
            v = (v.astype(jnp.float32) * gate.transpose(0, 2, 1)[..., None]).astype(v.dtype)
        return k, v

    return proj, expand


def scale_of_the_full_kind(full_width: int):
    sound = SOUND[C.ModelConfig, "attn_scale"].fget

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


def window_latent_8_bits(window_width: int):
    def kept_width(row, leaf):
        if row.shape[-1] == window_width:
            f32 = row.astype(jnp.float32)
            scale = jnp.maximum(jnp.max(jnp.abs(f32), axis=-1, keepdims=True), 1e-8) / 127
            row = (jnp.round(f32 / scale) * scale).astype(row.dtype)
        return SOUND[T, "_kept_width"](row, leaf)

    return kept_width


def restore() -> None:
    for (module, attr), sound in SOUND.items():
        setattr(module, attr, sound)
    C._kind_view.cache_clear()


def main(cases: list[str], tiny: bool = False) -> int:
    files = ROOT / "benchmark" / ("tests/data" if tiny else "")
    name, cell = ("tiny-dots3", "tiny-dots3-drain") if tiny else (CONFIG, CELL)
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
    out = ROOT / "chiprun_out" / ("dots3_scores_tiny" if tiny else "dots3_scores")
    out.mkdir(parents=True, exist_ok=True)
    kept: dict = {}
    judge = check._judge

    def keeping(scores, limits):
        kept["scores"] = scores
        return judge(as_the_file_judges(scores, float(limits.get("eps_select", 0.0))), limits)

    check._judge = keeping
    check.load_module = raw_select_gap(check.load_module)
    window = config.of_kind(WINDOW)
    for case in cases:
        restore()
        # a config of its own name: the case is traced into programs of its own
        named = dataclasses.replace(config, name=f"{name}-{case}")
        if case == "no-gate":
            T._head_gate = no_gate
        elif case == "gate-on-v":
            T._head_gate = no_gate
            T._latent_proj, T._latent_expand = gate_on_v()
        elif case in ("window-512", "window-514"):
            by = -1 if case == "window-512" else 1
            named = dataclasses.replace(named, sliding_window=config.sliding_window + by)
        elif case == "one-rotary-base":
            own = tuple(kv for kv in config.window_attention if kv[0] != "rope_theta")
            named = dataclasses.replace(named, window_attention=own)
        elif case == "window-scale-192":
            C.ModelConfig.attn_scale = scale_of_the_full_kind(config.resolved_head_dim)
        elif case == "no-rescale-kv":
            T._rescaled = rescale_but(config.d_model / config.kv_lora_rank)
        elif case == "value-at-64":
            T._wkv_b = value_from_lane_64
        elif case == "recent-keys":
            T._select_mask, A.segment_select = recent_keys, recent_segment_select
        elif case == "winlat8":
            T._kept_width = window_latent_8_bits(window.latent_width)
        elif case == "lat8":
            T._kept_width = window_latent_8_bits(config.latent_width)
        elif case == "bf16-router":
            T._route_all = route(bf16=True)
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
        worst = []
        for i, passes in enumerate(scores):
            for s in passes:
                err, gap = s["layer_err"], s["router_gap"]
                for flat in np.argsort(err, axis=None)[-4:]:
                    step, at = np.unravel_index(flat, err.shape)
                    worst.append((float(err[step, at]), i, int(step), int(at),
                                  float(gap[step, at]) if step < gap.shape[0] else None))
        say(case=case, worst_pairs=sorted(worst, reverse=True)[:8])
    restore()
    say(phase="done", cases=len(cases), not_as_expected=failed)
    return 1 if failed else 0


if __name__ == "__main__":
    args = sys.argv[1:]
    tiny = "--tiny" in args
    chosen = [a for a in args if not a.startswith("--")] or list(CASES)
    sys.exit(main(chosen, tiny=tiny))

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path still starts on
a TPU. Not a benchmark: it claims no speed, and the seconds it prints are
labelled set-up or smoke, never a metric.

    python chip_smoke.py            one chip: phases `kernels` and `serve`
    python chip_smoke.py --chips 4  one four-chip host: the tensor-parallel
                                    path and its one-chip comparison, only

One process, no child that needs the chip. The first act is jax.devices():
without a TPU the script exits non-zero at once and prints no result. Any
failed check raises, so the exit code is non-zero; nothing here catches an
error and carries on. Every stdout line is one JSON object; the last one is
`{"ok": true, "device": {"platform", "kind", "count"}}` as JAX reports it.

`kernels`: every main-path Pallas kernel COMPILED (interpret=False) against
the jax.numpy reference the tests use, at gemma-2b and llama-3-8b widths.
`serve`: examples/applications/tpu-completions (its pipeline and gateway
files) with gemma-2b int8 at full width, random weights from a seed, through
LocalApplicationRunner + serve_gateway() — the path `langstream-tpu run
local` takes — driven over the chat websocket.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any

REPO = Path(__file__).resolve().parent
EXAMPLE = REPO / "examples" / "applications" / "tpu-completions"
NEW_TOKENS = 64  # the example pipeline's `max-new-tokens`
KERNEL_ERR_BOUND = 5e-2  # max-abs vs the f32 reference; outputs are O(1), bf16
MESH_LOGITS_REL_TOL = 0.1  # max-abs difference over max-abs logit (0.023 measured)


def emit(**line: Any) -> None:
    print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# Compile-cache accounting
# ---------------------------------------------------------------------------


class CacheCounts:
    """Persistent-compile-cache hits and misses, as JAX's own monitoring
    events count them."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0

    def __call__(self, event: str, **_: Any) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def report(self) -> dict:
        import jax

        return {
            "dir": jax.config.jax_compilation_cache_dir,
            "hits": self.hits,
            "misses": self.misses,
        }


# ---------------------------------------------------------------------------
# Phase `kernels`
# ---------------------------------------------------------------------------


def kernel_checks(
    config, *, prefill_lens, paged, interpret: bool = False
) -> list[dict]:
    """Each main-path kernel at ``config``'s head widths against the
    jax.numpy reference of tests/test_pallas_ops.py (``attention`` over an
    explicit mask, in float32 at "highest" matmul precision; int8 caches
    dequantized first; the pool's writes, a step's rows and an admission
    group's pages, against the scatters, where any difference is an error). ``paged`` = (B, page, pages
    per row): rows of unequal length, every third one inactive. Returns
    one ``{kernel, max_abs_err}`` per check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from langstream_tpu.models.transformer import (
        _dequantize_kv,
        _page_index,
        _paged_gather,
        _paged_lengths,
        _paged_scatter,
        _quantize_kv,
        attention,
        paged_insert_cache,
    )
    from langstream_tpu.ops.attention import (
        flash_prefill_attention,
        paged_insert_pages,
        paged_kv_write,
        ragged_paged_decode_attention,
        ragged_paged_decode_attention_int8,
    )

    h, hkv, d = config.n_heads, config.n_kv_heads, config.resolved_head_dim
    dtype = jnp.dtype(config.dtype)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def rand(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    @jax.jit
    def reference(q, k, v, mask):
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        with jax.default_matmul_precision("highest"):
            return attention(f32(q), f32(k), f32(v), mask, config)

    out: list[dict] = []

    def check(name: str, got, want) -> None:
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        if not err <= KERNEL_ERR_BOUND:  # also catches NaN
            raise AssertionError(
                f"{config.name} {name}: max-abs error {err} exceeds "
                f"{KERNEL_ERR_BOUND}"
            )
        out.append({"kernel": f"{config.name}/{name}", "max_abs_err": err})

    for s in prefill_lens:
        q, k, v = rand(1, s, h, d), rand(1, hkv, s, d), rand(1, hkv, s, d)
        causal = jnp.tril(jnp.ones((s, s), jnp.bool_))[None]
        check(
            f"flash_prefill_attention[s={s}]",
            flash_prefill_attention(q, k, v, config, interpret=interpret),
            reference(q, k, v, causal),
        )

    b, page, per_row = paged
    pages = b * per_row
    rng = np.random.default_rng(0)
    lengths = rng.integers(1, per_row * page + 1, b).astype(np.int32)
    lengths[0], lengths[-1] = 1, per_row * page  # both ends of the ragged range
    table = rng.permutation(pages).astype(np.int32).reshape(b, per_row)
    # unmapped tail pages carry the out-of-bounds sentinel, as in the engine
    used = -(-lengths // page)
    table[np.arange(per_row)[None, :] >= used[:, None]] = pages
    # every third row is INACTIVE as a decode dispatch leaves one: the
    # table cleared to the sentinel, the device's position stale and still
    # advancing. The kernels get their lengths by the caller's rule (what
    # the table maps caps the position), which makes those rows 0 long:
    # they must come back zeros, and the others unmoved by them
    positions = lengths - 1
    idle = np.arange(1, b - 1, 3)
    table[idle] = pages
    positions[idle] = rng.integers(page, per_row * page, len(idle))
    table = jnp.asarray(table)
    lengths = _paged_lengths(table, jnp.asarray(positions), page, pages)
    assert (np.asarray(lengths)[idle] == 0).all() and int(lengths[-1]) == per_row * page
    live = (lengths > 0)[:, None]
    q = rand(b, h, d)
    mask = jnp.arange(per_row * page)[None, None, :] < lengths[:, None, None]
    # the kernels read the pool [L, P, Hkv, ps, D] where it lies, at a
    # layer index: the second of two layers, so a wrong layer shows
    layer = jnp.int32(1)
    kp, vp = rand(2, pages, hkv, page, d), rand(2, pages, hkv, page, d)
    check(
        f"ragged_paged_decode_attention[b={b},page={page}]",
        ragged_paged_decode_attention(
            q, kp, vp, lengths, table, layer, config, page, interpret=interpret
        ),
        jnp.where(live, reference(
            q[:, None], _paged_gather(kp, layer, table, page),
            _paged_gather(vp, layer, table, page), mask,
        )[:, 0], 0.0),
    )
    # the step's new rows into that pool: the kernel's copies against the
    # scatter it replaced, every byte of both leaves (the idle rows drop)
    pos = jnp.asarray(positions)[:, None]
    kn, vn = rand(b, hkv, d), rand(b, hkv, d)
    write_pages, write_offs = _page_index(table, pos, page, pages)
    wrote = paged_kv_write(
        (kn, vn), kp, vp, write_pages[:, 0], write_offs[:, 0], layer, config,
        interpret=interpret,
    )
    for leaf, got, pool, rows in zip("kv", wrote, (kp, vp), (kn, vn)):
        check(
            f"paged_kv_write[{leaf},b={b},page={page}]", got,
            _paged_scatter(pool, layer, rows[:, :, None], table, pos, page)
            .astype(jnp.float32),
        )
    # an admission group's prefill into that pool, every layer: the kernel's
    # page copies against the scatter that stays its reference (the idle
    # rows and the pages a row does not hold drop)
    lk, lv = rand(2, b, hkv, per_row * page, d), rand(2, b, hkv, per_row * page, d)
    inserted = paged_insert_pages((lk, lv), kp, vp, table, interpret=interpret)
    scattered = paged_insert_cache(
        {"k": kp, "v": vp}, {"k": lk, "v": lv}, table, page,
        dataclasses.replace(config, attention_impl="jnp"),
    )
    for leaf, got in zip("kv", inserted):
        check(
            f"paged_insert_pages[{leaf},b={b},page={page}]", got,
            scattered[leaf].astype(jnp.float32),
        )
    kp8, vp8 = (dict(zip("qs", _quantize_kv(x))) for x in (kp, vp))
    check(
        f"ragged_paged_decode_attention_int8[b={b},page={page}]",
        ragged_paged_decode_attention_int8(
            q, kp8, vp8, lengths, table, layer, config, page,
            interpret=interpret,
        ),
        jnp.where(live, reference(
            q[:, None],
            _dequantize_kv(_paged_gather(kp8, layer, table, page), jnp.float32),
            _dequantize_kv(_paged_gather(vp8, layer, table, page), jnp.float32),
            mask,
        )[:, 0], 0.0),
    )
    return out


def kernels_phase() -> None:
    from langstream_tpu.models.configs import MODEL_PRESETS

    t0 = time.monotonic()
    checks: list[dict] = []
    for name in ("gemma-2b", "llama-3-8b"):
        checks += kernel_checks(
            MODEL_PRESETS[name], prefill_lens=(512, 2048),
            paged=(32, 64, 32),
        )
    emit(
        phase="kernels", compiled=True, err_bound=KERNEL_ERR_BOUND,
        checks=checks, smoke_seconds=round(time.monotonic() - t0, 1),
    )


# ---------------------------------------------------------------------------
# Phase `serve`
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """What the serve phase runs. The chip values are the issue's; the CPU
    tier drives the same code at tiny-test size (tests/test_chip_smoke.py)."""

    model: str = "gemma-2b"
    max_batch: int = 32
    max_seq_len: int = 2048
    decode_chunk: int = 16
    sessions: int = 8
    turns: int = 2
    long_question_chars: int = 1480  # ≈1,500 tokens under the byte tokenizer

    @property
    def requests(self) -> int:
        return self.sessions * self.turns + 1


def _write_app(spec: ServeSpec, root: Path) -> tuple[Path, Path]:
    """The example application with this spec's tpu-serving resource: the
    example's own pipeline.yaml and gateways.yaml, a configuration.yaml that
    names the model, and everything the issue does not name left at its
    default (attention-impl auto, precompile on a TPU)."""
    app = root / "app"
    app.mkdir()
    for name in ("pipeline.yaml", "gateways.yaml"):
        shutil.copy(EXAMPLE / name, app / name)
    (app / "configuration.yaml").write_text(
        "configuration:\n"
        "  resources:\n"
        "    - type: tpu-serving\n"
        "      name: tpu\n"
        "      configuration:\n"
        f"        model: {spec.model}\n"
        "        quantization: int8\n"
        "        weights: random\n"
        "        tokenizer: byte\n"
        f"        max-batch: {spec.max_batch}\n"
        f"        max-seq-len: {spec.max_seq_len}\n"
        f"        decode-chunk: {spec.decode_chunk}\n"
    )
    instance = root / "instance.yaml"
    instance.write_text(
        "instance:\n"
        "  streamingCluster:\n"
        "    type: memory\n"
        "  computeCluster:\n"
        "    type: local\n"
        "  globals:\n"
        f"    serving-model: {spec.model}\n"
    )
    return app, instance


async def _chat_turn(ws, question: str, timeout: float = 300.0) -> dict:
    """One question over an open chat websocket: collect the streamed
    pushes up to the one marked last, and check the stream's own framing."""
    import aiohttp

    await ws.send_str(json.dumps({"value": question}))
    text, indexes, stream_ids = "", [], set()
    while True:
        msg = await asyncio.wait_for(ws.receive(), timeout)
        if msg.type != aiohttp.WSMsgType.TEXT:
            raise RuntimeError(f"chat socket closed mid-stream: {msg.type} {msg.data!r}")
        record = json.loads(msg.data)["record"]
        headers = record.get("headers") or {}
        value = record.get("value")
        text += value if isinstance(value, str) else json.dumps(value)
        indexes.append(int(headers["stream-index"]))
        stream_ids.add(headers["stream-id"])
        if headers.get("stream-last-message") == "true":
            break
    if indexes != list(range(len(indexes))) or len(stream_ids) != 1:
        raise AssertionError(f"broken chunk framing: {indexes} {stream_ids}")
    return {"chunks": len(indexes), "chars": len(text)}


async def _chat_session(http, url: str, questions: list[str]) -> list[dict]:
    async with http.ws_connect(url) as ws:
        return [await _chat_turn(ws, q) for q in questions]


def _platforms_of(tree) -> list[str]:
    import jax

    return sorted(
        {d.platform for leaf in jax.tree.leaves(tree) for d in leaf.devices()}
    )


def _check_engine_clean(stats: dict) -> dict:
    """Nothing restarted, shed, failed, cancelled or tripped the NaN guard."""
    keys = (
        "engine-restarts-total", "shed-total", "nan-guard-total",
        "quarantined-slots-total", "cancelled-total", "deadline-exceeded-total",
    )
    bad = {k: stats[k] for k in keys if stats[k] != 0}
    if bad:
        raise AssertionError(f"engine not clean after the smoke: {bad}")
    return {k: stats[k] for k in keys}


async def serve_phase(spec: ServeSpec, platform: str, cache: CacheCounts) -> None:
    """Deploy the example app, build and warm the engine through its
    provider, drive the chat gateway, and check what came back. ``platform``
    is where the engine's weights and page pool must live — "tpu" from
    main(); the CPU-tier test injects "cpu"."""
    import aiohttp

    from langstream_tpu.core.parser import ModelBuilder
    from langstream_tpu.core.resolver import resolve_placeholders
    from langstream_tpu.models.configs import GenerationOptions
    from langstream_tpu.ops.attention import attention_paths
    from langstream_tpu.runtime.local_runner import LocalApplicationRunner
    from langstream_tpu.tracing import TRACER

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        app_dir, instance = _write_app(spec, Path(tmp))
        pkg = ModelBuilder.build_application_from_path(app_dir, instance_path=instance)
        runner = LocalApplicationRunner("smoke", resolve_placeholders(pkg.application))
        await runner.deploy()
        await runner.start()
        server = await runner.serve_gateway()
        try:
            provider = runner.service_registry.get_provider()
            t0 = time.monotonic()
            # off the loop: the build compiles the whole warm-up surface
            engine = await asyncio.get_running_loop().run_in_executor(
                None, provider.engine
            )
            warmed = engine.stats()["compiled_programs"]
            paths = attention_paths()
            emit(
                phase="serve-setup", model=spec.model,
                setup_seconds=round(time.monotonic() - t0, 1),
                compiled_programs_after_warmup=warmed,
                attention_paths=paths, compile_cache=cache.report(),
            )
            if platform == "tpu":
                # `auto` on a TPU: a kernel that quietly gave way to the
                # jnp reference is a failure here, not a footnote
                pool = engine._pagepool
                expected = {
                    f"paged-decode[s=1,t={pool.table_len * pool.page_size}]":
                        "ragged_paged_decode_attention",
                    **{
                        f"prefill[s={w},t={w}]": "flash_prefill_attention"
                        for w in engine.prefill_buckets if w % 128 == 0
                    },
                }
                gave_way = {k: paths.get(k) for k, v in expected.items() if paths.get(k) != v}
                if gave_way:
                    raise AssertionError(f"expected kernels, traced: {gave_way}")

            base = f"{server.ws_url}/v1/chat/default/smoke/chat?param:sessionId="
            short = [
                [f"Session {i}, turn {j}: what is {i} plus {j}?" for j in range(spec.turns)]
                for i in range(spec.sessions)
            ]
            long_q = ("Summarise this. " + "lorem ipsum dolor sit amet " * 80)[
                : spec.long_question_chars
            ]
            TRACER.clear()
            t0 = time.monotonic()
            async with aiohttp.ClientSession() as http:
                answers = await asyncio.gather(
                    *(_chat_session(http, f"{base}s{i}", q) for i, q in enumerate(short)),
                    _chat_session(http, f"{base}long", [long_q]),
                )
            served_s = time.monotonic() - t0
            turns = [turn for session in answers for turn in session]

            # the engine's own per-request record: one `engine.request`
            # span each, emitted just after the request resolves
            deadline = time.monotonic() + 10
            while len(TRACER.find("engine.request")) < spec.requests:
                if time.monotonic() > deadline:
                    break
                await asyncio.sleep(0.05)
            spans = [s.attributes for s in TRACER.find("engine.request")]
            stats = engine.stats()
            if not len(turns) == len(spans) == stats["total-requests"] == spec.requests:
                raise AssertionError(
                    f"{len(turns)} answers, {len(spans)} request spans, "
                    f"{stats['total-requests']} engine requests; expected "
                    f"{spec.requests}"
                )
            for a in spans:
                # the asked length — or the model's own EOS, which random
                # weights are free to sample (reported below, never silent)
                full = a["finish_reason"] == "length" and a["generated_tokens"] == NEW_TOKENS
                eos = a["finish_reason"] == "stop" and a["generated_tokens"] <= NEW_TOKENS
                if not (full or eos):
                    raise AssertionError(f"request not answered as asked: {a}")
            if sum(a["generated_tokens"] for a in spans) != stats["total-generated-tokens"]:
                raise AssertionError("request spans and engine token count disagree")
            if max(a["prompt_len"] for a in spans) < spec.long_question_chars:
                raise AssertionError("the long question never reached the engine whole")
            # token ids are not visible past the tokenizer: one direct
            # request on the same engine shows them
            tokenizer = provider.holder.tokenizer()
            probe = await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: engine.generate(
                    tokenizer.encode("user: hello\nassistant:"),
                    GenerationOptions(max_new_tokens=NEW_TOKENS, temperature=0.0),
                ),
            )
            vocab = engine.config.vocab_size
            if not probe.tokens or not all(0 <= t < vocab for t in probe.tokens):
                raise AssertionError(f"bad probe tokens: {probe.tokens}")

            stats = engine.stats()
            clean = _check_engine_clean(stats)
            if engine._precompile and stats["compiled_programs"] != warmed:
                raise AssertionError(
                    f"a program compiled mid-traffic: {warmed} after warm-up, "
                    f"{stats['compiled_programs']} at the end"
                )
            placed = {
                "params": _platforms_of(engine.params),
                "page_pool": _platforms_of(engine._pagepool.dev),
            }
            if placed != {"params": [platform], "page_pool": [platform]}:
                raise AssertionError(f"engine state not on {platform}: {placed}")
            emit(
                phase="serve", model=spec.model, requests=spec.requests,
                new_tokens_asked=NEW_TOKENS,
                generated_tokens=[a["generated_tokens"] for a in spans],
                finish_reasons=sorted({a["finish_reason"] for a in spans}),
                prompt_lens=[a["prompt_len"] for a in spans],
                chunks=[t["chunks"] for t in turns],
                chars=[t["chars"] for t in turns],
                smoke_seconds=round(served_s, 1),
                compiled_programs_at_end=stats["compiled_programs"],
                attention_paths=attention_paths(), engine=clean, placed=placed,
                compile_cache=cache.report(),
            )
        finally:
            await server.stop()
            await runner.stop()


# ---------------------------------------------------------------------------
# --chips 4: tensor parallel over one four-chip host, against one chip
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    model: str = "llama-3-8b"
    ways: int = 4
    max_batch: int = 8
    max_seq_len: int = 512
    bucket: int = 128
    decode_chunk: int = 16
    new_tokens: int = 32
    prompts: int = 4


def _quarter_shards(name: str, array, ways: int) -> dict:
    shards = array.addressable_shards
    devices = {s.device for s in shards}
    sizes = {s.data.size for s in shards}
    if len(shards) != ways or len(devices) != ways or sizes != {array.size // ways}:
        raise AssertionError(
            f"{name}: {len(shards)} shards on {len(devices)} devices, sizes "
            f"{sorted(sizes)} of {array.size}"
        )
    return {"array": name, "shards": ways, "shard_elems": array.size // ways}


def _first_token_logits(engine, prompt: list[int], width: int):
    """Last-position logits of one prompt through the model's own prefill,
    with the engine's config (so its attention dispatch) and placement."""
    import jax.numpy as jnp
    import numpy as np

    from langstream_tpu.models.transformer import make_kv_cache, prefill
    from langstream_tpu.parallel.sharding import shard_serving_cache

    tokens = np.zeros((1, width), np.int32)
    tokens[0, : len(prompt)] = prompt
    cache = make_kv_cache(engine.config, 1, width)
    if engine.mesh is not None:
        cache = shard_serving_cache(cache, engine.mesh)
    logits, _ = prefill(
        engine.params, jnp.asarray(tokens),
        jnp.asarray([len(prompt)], jnp.int32), cache, engine.config,
    )
    return np.asarray(logits[0], np.float32)


def mesh_phase(spec: MeshSpec, platform: str, cache: CacheCounts) -> None:
    """The tensor-parallel engine through the provider, then the same
    weights and prompts on one device in the same process."""
    import jax
    import numpy as np

    from langstream_tpu.ai.tpu_serving import TpuServingProvider
    from langstream_tpu.models.configs import GenerationOptions
    from langstream_tpu.ops.attention import attention_paths

    base = {
        "model": spec.model, "quantization": "int8", "weights": "random",
        "tokenizer": "byte", "max-batch": spec.max_batch,
        "max-seq-len": spec.max_seq_len, "prefill-buckets": [spec.bucket],
        "decode-chunk": spec.decode_chunk,
    }
    devices = jax.devices()[: spec.ways]
    greedy = GenerationOptions(max_new_tokens=spec.new_tokens, temperature=0.0)

    def in_use() -> list:
        return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]

    def run(engine, prompts):
        logits = [_first_token_logits(engine, p, spec.bucket) for p in prompts]
        streams = [engine.generate(p, greedy, timeout=600).tokens for p in prompts]
        _check_engine_clean(engine.stats())
        return logits, streams

    t0 = time.monotonic()
    sharded = TpuServingProvider({**base, "mesh": {"model": spec.ways}})
    single = TpuServingProvider(base)
    try:
        engine = sharded.engine()
        mesh_devices = set(engine.mesh.devices.flat)
        if len(mesh_devices) != spec.ways or {d.platform for d in mesh_devices} != {platform}:
            raise AssertionError(f"mesh does not span {spec.ways} {platform} devices: {mesh_devices}")
        layers = engine.params["layers"]
        shards = [
            _quarter_shards("wq", layers["wq"]["q"], spec.ways),
            _quarter_shards("w_up", layers["w_up"]["q"], spec.ways),
            _quarter_shards("page_pool.k", engine._pagepool.dev["k"], spec.ways),
        ]
        tokenizer = sharded.holder.tokenizer()
        prompts = [
            tokenizer.encode(f"user: question {i}: count to {10 + i}\nassistant:")
            for i in range(spec.prompts)
        ]
        mesh_logits, mesh_streams = run(engine, prompts)
        mesh_bytes = in_use()
        mesh_paths = attention_paths()
        emit(
            phase="mesh", model=spec.model, mesh={"model": spec.ways},
            devices=[str(d) for d in sorted(mesh_devices, key=lambda d: d.id)],
            shards=shards, bytes_in_use_per_device=mesh_bytes,
            attention_paths=mesh_paths,
            setup_and_smoke_seconds=round(time.monotonic() - t0, 1),
            compile_cache=cache.report(),
        )
        if platform == "tpu" and not any(
            k.startswith("paged-decode") and "shard_map" in v
            for k, v in mesh_paths.items()
        ):
            raise AssertionError(f"no kernel under the mesh: {mesh_paths}")

        # the same weights on ONE device: a copy of the sharded tree spares
        # the comparison a second host-staged init of the same seed
        t0 = time.monotonic()
        single.holder._params = jax.device_put(engine.params, devices[0])
        one_logits, one_streams = run(single.engine(), prompts)
        one_bytes = in_use()
    finally:
        sharded.holder.close()
        single.holder.close()

    one_chip = None
    if None not in mesh_bytes:
        # "everything on device 0" is the failure to catch: the mesh engine's
        # bytes are even across chips and well under what one chip needs for
        # the whole model (device 0 holds both engines in `one_bytes`)
        one_chip = one_bytes[0] - mesh_bytes[0]
        if max(mesh_bytes) > 1.25 * min(mesh_bytes) or max(mesh_bytes) > 0.5 * one_chip:
            raise AssertionError(
                f"uneven or unsharded placement: {mesh_bytes} per device "
                f"under the mesh, {one_chip} for the one-chip engine"
            )
    elif platform == "tpu":
        raise AssertionError("the TPU reported no memory_stats()")

    rel_err = []
    for a, b in zip(mesh_logits, one_logits):
        if not (np.isfinite(a).all() and np.isfinite(b).all()):
            raise AssertionError("non-finite first-token logits")
        rel_err.append(float(np.max(np.abs(a - b)) / np.max(np.abs(b))))
    # bf16 tolerance: bf16 activations through every layer, partial sums
    # reduced in another order — a few percent of the logits' range.
    # Mixed-up heads or shards give errors of order 1.
    if max(rel_err) > MESH_LOGITS_REL_TOL:
        raise AssertionError(f"mesh and one-chip logits disagree: {rel_err}")
    diverge = [
        next((i for i, (x, y) in enumerate(zip(m, o)) if x != y), None)
        for m, o in zip(mesh_streams, one_streams)
    ]
    emit(
        phase="mesh-vs-one-chip", one_chip_bytes_in_use=one_chip,
        first_token_logits_max_rel_err=rel_err,
        first_token_argmax_agree=[
            int(a.argmax()) == int(b.argmax())
            for a, b in zip(mesh_logits, one_logits)
        ],
        # random weights give near-flat logits, so greedy streams may part
        # ways on a rounding difference: reported, not asserted
        greedy_streams_first_differ_at=diverge, stream_len=spec.new_tokens,
        attention_paths=attention_paths(),
        setup_and_smoke_seconds=round(time.monotonic() - t0, 1),
        compile_cache=cache.report(),
    )


# ---------------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: only the tensor-parallel path and its one-chip comparison",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(
            f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform}",
            file=sys.stderr,
        )
        return 2

    from importlib.metadata import version

    from langstream_tpu import native

    try:
        cpu_beside_tpu = len(jax.devices("cpu")) > 0
    except RuntimeError:  # JAX_PLATFORMS names the TPU alone
        cpu_beside_tpu = False
    cache = CacheCounts()
    jax.monitoring.register_event_listener(cache)
    emit(
        phase="env", jax=jax.__version__, jaxlib=version("jaxlib"),
        libtpu=version("libtpu"),
        device_kind=devices[0].device_kind, device_count=len(devices),
        hbm_bytes_limit=(devices[0].memory_stats() or {}).get("bytes_limit"),
        # tpu-serving stages big int8 models on the host through this backend
        cpu_backend_beside_tpu=cpu_beside_tpu,
        native_extension=native.NATIVE,
    )
    if args.chips == 4:
        mesh_phase(MeshSpec(), "tpu", cache)
    else:
        kernels_phase()
        asyncio.run(serve_phase(ServeSpec(), "tpu", cache))
    emit(
        ok=True,
        device={
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

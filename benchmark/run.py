#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip: it builds the application from files
through ModelBuilder → LocalApplicationRunner → serve_gateway(), makes the
weights on the device, builds and warms the engine through its provider,
runs the correctness check on the configuration's fixed sample, drives the
gateway from a child process that never imports jax, and prints the
contract's one JSON object as its last line. Any failed check raises, so the
exit code is non-zero and no result is printed. The first act is
jax.devices(): no TPU, fewer chips than the cell asks for, or a device kind
missing from the peaks table is an error, never a fallback.

`--sweep r1,r2,...` (not used by the driver) steps an open-loop cell through
offered rates in one process, to find its knee.

Everything that belongs to one cell is a file found by name: see README.md.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for path in (str(ROOT), str(HERE)):
    if path not in sys.path:
        sys.path.insert(0, path)

import metrics  # noqa: E402
from modelcfg import load_json, load_module, register_preset  # noqa: E402

APP_ID = "bench"
STATS_SAMPLE_S = 0.1
SPAN_POLL_S = 2.0  # the ring holds 2,048 spans; a window emits some 50 a second
GRACE_S = 45.0  # after the window: requests due inside it may still finish
LEAD_S = 2.0  # child start-up before the window opens
TEARDOWN_S = 30.0
PROBE_TOKENS = 40
PROBE_CHUNKS = 3  # chunks grow 1, 2, 4 tokens
ENGINE_FATAL = ("engine-restarts-total", "quarantined-slots-total")
ENGINE_FAILED = ("shed-total", "nan-guard-total")


def emit(**line: Any) -> None:
    """An earlier line: evidence, never the result."""
    print(json.dumps(line), flush=True)


def cell_entry(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")


def metrics_of(entries: list[dict], cell: str) -> list[dict]:
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


class SpanSink:
    """The tracer's ring holds 2,048 spans, fewer than a window emits: copy
    what is new out of it as the window runs."""

    def __init__(self) -> None:
        self.by_id: dict[str, dict] = {}

    def poll(self) -> None:
        from langstream_tpu.tracing import TRACER

        for span in TRACER.spans(limit=4096):
            if span["name"].startswith("engine."):
                self.by_id.setdefault(span["spanId"], span)

    def spans(self) -> list[dict]:
        return list(self.by_id.values())


async def probe(ws_url: str, cap: int, prompt: str, trace_id: str) -> dict:
    """The start of one request through the chat gateway of `cap`: warms the
    path from the socket to the engine and back, and shows what a client sees.
    After PROBE_CHUNKS chunks the client hangs up, which cancels the
    generation (serving/lifecycle.py): set-up does not wait out a long cap."""
    import aiohttp

    from traffic_kinds.common import parse_push

    chunks = []
    url = f"{ws_url}/v1/chat/default/{APP_ID}/chat-{cap}?param:sessionId={trace_id}"
    async with aiohttp.ClientSession() as http, http.ws_connect(url) as ws:
        await ws.send_str(json.dumps({"value": prompt, "headers": {"ls-trace-id": trace_id}}))
        while len(chunks) < PROBE_CHUNKS:
            msg = await asyncio.wait_for(ws.receive(), 600)
            if msg.type != aiohttp.WSMsgType.TEXT:
                raise RuntimeError(f"probe socket closed mid-stream: {msg.type}")
            headers, text = parse_push(msg.data)
            chunks.append(len(text.split()))
            if headers.get("stream-last-message") == "true":
                break
    return {"cap": cap, "chunk_tokens": chunks}


def judge_requests(requests: list[dict], spans: list[dict], open_loop: bool) -> dict:
    """attempted / failed, and the client's token count against the
    engine's own record of each request."""
    roots = {s["traceId"]: s for s in spans if s["name"] == "engine.request"}
    attempted = failed = mismatched = 0
    for r in requests:
        finished = r["done"] or r["error"] is not None
        if not (open_loop or finished):
            continue  # a drain's backlog: not reached inside the window
        attempted += 1
        root = roots.get(r["id"])
        bad = r["error"] is not None or not r["done"]
        if not bad:
            if root is None or root["attributes"].get("generated_tokens") != r["tokens"]:
                mismatched += 1
                bad = True
            elif root["attributes"].get("finish_reason") not in ("length", "stop"):
                bad = True
            elif r.get("first_chunk_tokens") != 1:
                bad = True  # the first chunk has to follow the first token
        failed += bad
    return {"attempted": attempted, "failed": failed, "token_count_mismatches": mismatched}


async def run_window(
    *, engine, server, kind_name: str, traffic: dict, caps: list[int], seed: int,
    seconds: float, vocab_size: int, work: Path, trace: bool, trace_seconds: float,
) -> dict:
    """One measured window: histograms reset, child started, spans and
    stats collected while it runs. Returns the raw material of the metrics."""
    from app import gateway_urls
    from langstream_tpu.tracing import TRACER
    from tokenizer import prompt_text

    kind = importlib.import_module(f"traffic_kinds.{kind_name}")
    requests = kind.schedule(traffic, seed, seconds, vocab_size)
    for r in requests:
        ids = r.pop("prompt_ids")
        r["prompt"], r["prompt_tokens"] = prompt_text(ids), len(ids)
    before = engine.stats()
    engine.reset_histograms()
    TRACER.clear()
    sink = SpanSink()
    t0 = time.monotonic() + LEAD_S
    plan = {
        "kind": kind_name, "t0": t0, "seconds": seconds,
        "request_timeout_s": seconds + GRACE_S,
        "urls": gateway_urls(server.ws_url, APP_ID, caps), "requests": requests,
    }
    tag = f"{seed & 0xFFFFFFFF:08x}-{int(time.monotonic() * 1e3)}"
    plan_path, result_path = work / f"plan-{tag}.json", work / f"result-{tag}.json"
    plan_path.write_text(json.dumps(plan))
    child = await asyncio.create_subprocess_exec(
        sys.executable, str(HERE / "loadgen.py"), str(plan_path), str(result_path),
    )
    samples: list[dict] = []
    trace_dir = work / f"trace-{tag}"
    trace_at = t0 + max(0.0, (seconds - trace_seconds) * 0.6)

    lag = metrics.LagWatch()  # this loop serves gateway, broker and agents

    async def poll_spans() -> None:
        while True:
            sink.poll()
            await asyncio.sleep(SPAN_POLL_S)

    async def sample_stats() -> None:
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        while time.monotonic() < t0 + seconds:
            s = engine.stats()
            samples.append({
                k: s[k] for k in ("active-slots", "queued", "kv-pages-in-use", "kv-pages-total")
            })
            await asyncio.sleep(STATS_SAMPLE_S)

    async def take_trace() -> None:
        # a few seconds of the steady window, not all of it: traces are
        # large and tracing slows the host
        import jax

        loop = asyncio.get_running_loop()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        await asyncio.sleep(max(0.0, trace_at - time.monotonic()))
        await loop.run_in_executor(
            None, lambda: jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        )
        try:
            await asyncio.sleep(trace_seconds)
        finally:
            await loop.run_in_executor(None, jax.profiler.stop_trace)

    side = [asyncio.ensure_future(poll_spans()), asyncio.ensure_future(lag.run())]
    if trace:
        side += [asyncio.ensure_future(sample_stats()), asyncio.ensure_future(take_trace())]
    try:
        code = await asyncio.wait_for(child.wait(), LEAD_S + seconds + GRACE_S + 60)
        if code != 0:
            raise RuntimeError(f"the load generator exited with {code}")
        if trace:
            await asyncio.gather(*side[2:])  # a failed trace fails the run
    finally:
        for task in side:
            task.cancel()
        await asyncio.gather(*side, return_exceptions=True)
        if child.returncode is None:
            child.kill()
            await child.wait()
    result = json.loads(result_path.read_text())
    # the engine emits a request's spans just after it resolves
    settle = time.monotonic() + 3.0
    done_ids = {r["id"] for r in result["requests"] if r["done"]}
    while time.monotonic() < settle:
        sink.poll()
        if done_ids <= {s["traceId"] for s in sink.spans() if s["name"] == "engine.request"}:
            break
        await asyncio.sleep(0.1)
    after = engine.stats()
    fatal = {k: after[k] - before[k] for k in ENGINE_FATAL if after[k] != before[k]}
    if fatal:
        raise RuntimeError(f"the engine restarted or quarantined inside the window: {fatal}")
    if engine._precompile and after["compiled_programs"] != before["compiled_programs"]:
        raise RuntimeError(
            f"a program compiled inside the window: {before['compiled_programs']} "
            f"before, {after['compiled_programs']} after"
        )
    chunks = [tuple(c) for r in result["requests"] for c in r["chunks"]]
    return {
        "t0": t0, "seconds": seconds, "requests": result["requests"], "chunks": chunks,
        "generator_late_s": result["generator_late_s"], "spans": sink.spans(),
        "server_loop_lag_max_s": lag.max_s,
        "histograms": after["histograms"], "stats_samples": samples,
        "engine_failed": sum(after[k] - before[k] for k in ENGINE_FAILED),
        "stats": after, "trace_dir": trace_dir if trace else None,
    }


def metric_definition(name: str) -> dict:
    """`layer_metrics/<name>.json`. A quantity split over cells that report
    different end-to-end metrics (`decode_step_ms.chat`, `.drain`: `moves`
    holds one name) shares `layer_metrics/decode_step_ms.json`, unless a split
    has a file of its own."""
    try:
        return load_json("layer_metrics", name)
    except FileNotFoundError:
        quantity = name.rpartition(".")[0]
        if not quantity:
            raise
        return load_json("layer_metrics", quantity)


def layer_metrics(definitions: list[dict], window: dict) -> dict:
    """Each per-layer metric is read by the reader its own file names; a
    reader that finds nothing to read returns nothing and the metric is left
    out of the line."""
    out = {}
    for m in definitions:
        definition = metric_definition(m["name"])
        reader = importlib.import_module(f"readers.{definition['reader']}")
        value = reader.read(definition, window)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


async def wait_idle(engine, timeout: float = 120.0) -> None:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        s = engine.stats()
        if s["active-slots"] == 0 and s["queued"] == 0:
            return
        await asyncio.sleep(0.2)
    raise RuntimeError("the engine did not go idle")


async def run_cell(
    bench: dict, cell_name: str, seed: int, seconds: float, trace: bool,
    *, platform: str = "tpu", files: Path = HERE, sweep: Optional[list[float]] = None,
    ref_params_fault=None, trace_planes: Optional[dict] = None,
) -> dict:
    """Set-up, check, window(s), result. `platform` is where the engine's
    state must live: "tpu" from main(); the harness's own tests inject "cpu"
    and read the returned object, which is never printed as a result.
    `ref_params_fault` (tests only) maps the served tree to a faulted copy the
    engine serves while the reference keeps the original; `trace_planes` (tests
    only) names the CPU backend's planes to the trace reduction."""
    import jax

    from app import write_app
    from check import run_check
    from langstream_tpu.core.parser import ModelBuilder
    from langstream_tpu.core.resolver import resolve_placeholders
    from langstream_tpu.ops.attention import attention_paths
    from langstream_tpu.runtime.local_runner import LocalApplicationRunner
    from langstream_tpu.serving.engine import enable_persistent_compile_cache
    from tokenizer import prompt_text, write_tokenizer

    cell = cell_entry(bench, cell_name)
    workload = load_json("workloads", cell_name, files)
    spec = load_json("configs", cell["config"], files)
    traffic = load_json("traffic", cell["traffic"], files)
    family = load_module("families", spec["family"], files)
    config = register_preset(spec, cell["config"], files)
    caps = sorted(int(c) for c in traffic["output_caps"])
    devices = jax.devices()[: cell["chips"]]

    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        work = Path(tmp)
        tokenizer_dir = write_tokenizer(work / "tokenizer", config.vocab_size)
        serving = {
            **spec["serving"], **workload["engine"],
            "model": cell["config"], "tokenizer": f"hf:{tokenizer_dir}",
        }
        if platform == "tpu":
            # inside the checkout, at a fixed path: the path is part of the
            # cache's key. JAX_COMPILATION_CACHE_DIR, where set, wins.
            serving["compile-cache-dir"] = str(ROOT / ".jax_compile_cache")
            enable_persistent_compile_cache(serving["compile-cache-dir"])
        app_dir, instance = write_app(work, cell["config"], caps, serving)
        pkg = ModelBuilder.build_application_from_path(app_dir, instance_path=instance)
        runner = LocalApplicationRunner(APP_ID, resolve_placeholders(pkg.application))
        await runner.deploy()
        await runner.start()
        server = await runner.serve_gateway()
        loop = asyncio.get_running_loop()
        try:
            provider = runner.service_registry.get_provider()
            ref_params = None
            if spec["weights"]["init"] == "device":
                t = time.monotonic()
                params = family.make_params(config, int(spec["weights"]["seed"]))
                jax.block_until_ready(params)
                if ref_params_fault is not None:
                    ref_params, params = params, ref_params_fault(params)
                # the provider builds its engine around this tree instead of
                # staging one through the host (weights.py says why)
                provider.holder._params = params
                emit(phase="weights", seconds=round(time.monotonic() - t, 2))
            t = time.monotonic()
            engine = await loop.run_in_executor(None, provider.engine)
            paths = attention_paths()
            emit(
                phase="engine", seconds=round(time.monotonic() - t, 2),
                compiled_programs=engine.stats()["compiled_programs"],
                attention_paths=paths, kv_pages=engine.stats()["kv-pages-total"],
                compile_cache_dir=jax.config.jax_compilation_cache_dir,
            )
            if platform == "tpu":
                # `auto` on a TPU: a kernel that quietly gave way to the jnp
                # path is a failure here, not a footnote (as chip_smoke.py checks)
                gave_way = {
                    k: paths.get(k) for k, v in family.expected_kernels(engine).items()
                    if paths.get(k) != v
                }
                if gave_way:
                    raise RuntimeError(f"expected kernels, traced: {gave_way}")
            placed = {
                d.platform for leaf in jax.tree.leaves(family.state_leaves(engine))
                for d in leaf.devices()
            }
            if placed != {platform}:
                raise RuntimeError(f"engine state on {placed}, not on {platform}")

            t = time.monotonic()
            verdict = await loop.run_in_executor(
                None, lambda: run_check(
                    engine, spec, ref_params=ref_params, emit=emit, files=files)
            )
            emit(phase="check-time", seconds=round(time.monotonic() - t, 2))

            rng_ids = range(1, PROBE_TOKENS + 1)
            probes = [
                await probe(server.ws_url, cap, prompt_text(rng_ids), f"probe{cap:06d}")
                for cap in caps
            ]
            emit(phase="probe", probes=probes)
            for p in probes:
                if p["chunk_tokens"][:1] != [1] or sum(p["chunk_tokens"]) > p["cap"]:
                    raise RuntimeError(f"the probe's stream is not as asked: {p}")
            await wait_idle(engine)
            setup_s = time.monotonic() - T_START
            emit(phase="ready", setup_s=round(setup_s, 2))

            window_args = dict(
                engine=engine, server=server, kind_name=traffic["kind"], caps=caps,
                vocab_size=config.vocab_size, work=work,
                trace_seconds=float(workload.get("trace_seconds", 4.0)),
            )
            if sweep:
                table = []
                for rate in sweep:
                    w = await run_window(
                        traffic={**traffic, "rate_per_s": rate}, seed=seed,
                        seconds=seconds, trace=False, **window_args,
                    )
                    judged = judge_requests(w["requests"], w["spans"], open_loop=True)
                    row = {
                        "rate_per_s": rate, **judged, "generator_late_s": w["generator_late_s"],
                        "server_loop_lag_max_s": w["server_loop_lag_max_s"],
                        **metrics.end_to_end(w["requests"], w["chunks"], w["t0"], seconds),
                        "queue_wait_p90_ms": w["histograms"]["engine_queue_wait_s"]["p90"] * 1e3,
                        "decode_step_mean_ms": 1e3 * w["histograms"]["engine_decode_step_s"]["sum"]
                        / max(1, w["histograms"]["engine_decode_step_s"]["count"]),
                    }
                    emit(phase="sweep", **row)
                    table.append(row)
                    await wait_idle(engine)
                window = None
            else:
                window = await run_window(
                    traffic=traffic, seed=seed, seconds=seconds, trace=trace, **window_args
                )

            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
            device = {
                "platform": devices[0].platform, "kind": devices[0].device_kind,
                "count": len(devices), "memory_peak_bytes": peak,
            }
            if window is None:
                return {"sweep": table, "correct": verdict["ok"], "device": device,
                        "setup_s": setup_s}
            open_loop = traffic["kind"] == "open_poisson"
            judged = judge_requests(window["requests"], window["spans"], open_loop)
            emit(
                phase="window", **judged, engine_failed=window["engine_failed"],
                generator_late_s=window["generator_late_s"],
                server_loop_lag_max_s=window["server_loop_lag_max_s"],
                requests_finished=sum(r["done"] for r in window["requests"]),
                all_end_to_end=metrics.end_to_end(
                    window["requests"], window["chunks"], window["t0"], seconds),
                # in the order they were due: where two runs of one schedule
                # part, the first request that differs says when
                ttft_ms_by_request=[
                    round(v) if (v := metrics.ttft_ms(r)) is not None else None
                    for r in window["requests"]
                ] if open_loop else None,
                engine_stats={k: window["stats"][k] for k in (
                    "total-requests", "total-generated-tokens", "compiled_programs",
                    "kv-pages-in-use", "decode-step-ms", *ENGINE_FATAL, *ENGINE_FAILED)},
            )
            result: dict[str, Any] = {
                "correct": bool(verdict["ok"]),
                "attempted": judged["attempted"],
                "failed": judged["failed"] + window["engine_failed"],
                "device": device,
            }
            if trace:
                from reduce.xplane import find_trace, reduce_trace

                reduced = await loop.run_in_executor(
                    None, lambda: reduce_trace(find_trace(window["trace_dir"]), **(trace_planes or {}))
                )
                if not reduced["busy_s"] > 0:
                    raise RuntimeError("no operation ran on the device in the traced window")
                window["trace"] = reduced
                window["peaks"] = load_json("reduce", "peaks")["devices"].get(devices[0].device_kind)
                emit(
                    phase="trace", window_s=reduced["window_s"], busy_s=reduced["busy_s"],
                    device_planes=reduced["device_planes"], device_lines=reduced["device_lines"],
                    modules={n: [round(m["seconds"], 6), m["calls"]]
                             for n, m in reduced["modules"].items()},
                    ops_top=sorted(
                        ([n, round(o["seconds"], 6), o["calls"]] for n, o in reduced["ops"].items()),
                        key=lambda row: -row[1],
                    )[:40],
                )
                device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
                result["breakdown"] = {
                    "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
                }
                result["metrics"] = layer_metrics(
                    metrics_of(bench["per_layer"], cell_name), window)
            else:
                values = metrics.end_to_end(
                    window["requests"], window["chunks"], window["t0"], seconds)
                values["setup_s"] = setup_s
                result["metrics"] = {}
                for m in metrics_of(bench["end_to_end"], cell_name):
                    if m["name"] not in values:
                        raise RuntimeError(f"the window gave no {m['name']}")
                    result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
            result["compared"] = verdict.get("compared", {})  # last: what `correct` rests on
            return result
        finally:
            try:
                await asyncio.wait_for(_teardown(server, runner), TEARDOWN_S)
            except asyncio.TimeoutError:
                print("benchmark: teardown timed out", file=sys.stderr, flush=True)


async def _teardown(server, runner) -> None:
    await server.stop()
    await runner.stop()


def main(argv: Optional[list[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--sweep", default="", help="comma-separated offered rates (req/s)")
    args = parser.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = cell_entry(bench, args.workload)
    peaks = load_json("reduce", "peaks")["devices"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"benchmark: {args.workload} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform}", file=sys.stderr,
        )
        return 2
    if devices[0].device_kind not in peaks:
        print(
            f"benchmark: device kind {devices[0].device_kind!r} is not in reduce/peaks.json",
            file=sys.stderr,
        )
        return 2
    sweep = [float(x) for x in args.sweep.split(",") if x]
    result = asyncio.run(
        run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace), sweep=sweep)
    )
    print(json.dumps(result), flush=True)
    for name, (value, limit) in result["compared"].items():
        print(f"benchmark: compared {name} = {value}, limit {limit}", file=sys.stderr)
    # engine and agent threads may outlive a timed-out teardown; the result
    # is out and every child has been waited for
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0 if result["correct"] else 1)


if __name__ == "__main__":
    sys.exit(main())

"""Where an engine's time to ready went (docs/SERVING.md §12, "Start-up").

One `StartupTrace` an engine, made on the constructor's first line. It reads
the process's compile account (`langstream_tpu/compile_account.py`) at each
boundary and turns the differences into spans of the process's tracer,

    engine.startup                      constructor's first line → warm-up's end
      engine.startup.build              the constructor
      engine.startup.warmup.<family>    paged | prefill_buckets | agentic
        engine.startup.program          a warm-up dispatch, by its program

and into the `startup-*` keys of `stats()`, frozen when the warm-up ends. A
program's span runs from the `_record_program` that names it to the next one
(or its family's end): the warm-up waits for each dispatch before the next,
and the engine thread is the only one compiling then, so the account's
difference over the span is that program's trace, lowering and backend
seconds; what is left of the span's length is execution, transfer and Python
(`run_ms`).
"""

from __future__ import annotations

import contextlib
import logging
import time
import uuid
from typing import Any, Iterator, Optional

import jax

from langstream_tpu.compile_account import ACCOUNT, register
from langstream_tpu.tracing import MONO_TO_WALL_S, TRACER, Span

log = logging.getLogger(__name__)

# `stats()` keys → the account's sums they are differences of (frozen at the
# warm-up's end), and the whole process's, read live
_FROZEN = {
    "startup-trace-s": "trace-s",
    "startup-lower-s": "lower-s",
    "startup-backend-s": "backend-s",
    "startup-cache-retrieval-s": "cache-retrieval-s",
    "startup-cache-hits": "cache-hits",
    "startup-cache-requests": "cache-requests",
    "startup-kernels-traced": "kernels-traced",
}
_PROCESS = {
    "process-compile-trace-s": "trace-s",
    "process-compile-lower-s": "lower-s",
    "process-compile-backend-s": "backend-s",
    "process-compile-cache-retrieval-s": "cache-retrieval-s",
    "process-compile-cache-hits": "cache-hits",
    "process-compile-cache-requests": "cache-requests",
    "process-kernels-traced": "kernels-traced",
}


def process_stats() -> dict[str, float]:
    """The `process-*` keys: the account since its listeners were registered,
    the check's programs and a harness's weights among them."""
    now = ACCOUNT.snapshot()
    return {key: now[of] for key, of in _PROCESS.items()}


def program_name(signature: tuple) -> str:
    """`("paged-prefill", 512, 8)` → `paged-prefill[512,8]`."""
    head, *rest = signature
    return f"{head}[{','.join(str(r) for r in rest)}]" if rest else str(head)


def _compile_ms(before: dict, after: dict) -> dict[str, Any]:
    """A span's attributes: the account's difference across it."""
    requests = after["cache-requests"] - before["cache-requests"]
    return {
        "trace_ms": round((after["trace-s"] - before["trace-s"]) * 1e3, 3),
        "lower_ms": round((after["lower-s"] - before["lower-s"]) * 1e3, 3),
        "backend_ms": round((after["backend-s"] - before["backend-s"]) * 1e3, 3),
        "cache_retrieval_ms": round(
            (after["cache-retrieval-s"] - before["cache-retrieval-s"]) * 1e3, 3),
        # every compile of the span was read from the persistent cache (None:
        # nothing was compiled, the process had built the program before)
        "cache_hit": (after["cache-hits"] - before["cache-hits"] == requests) if requests else None,
        "kernels_traced": after["kernels-traced"] - before["kernels-traced"],
    }


class StartupTrace:
    def __init__(self) -> None:
        register()
        self._t0 = time.monotonic()
        self._base = ACCOUNT.snapshot()
        self._trace_id = uuid.uuid4().hex[:16]
        self._root_id = uuid.uuid4().hex[:16]
        self._phase_id: Optional[str] = None
        # the open program: (name, began, the account then, its annotation)
        self._program: Optional[tuple] = None
        self._programs = 0
        self._build_s = 0.0
        self._warmup_s = 0.0
        self._finished = False
        # True while a warm-up family runs: `_record_program` looks here
        self.warming = False
        # zeros until the warm-up ends, so that an exporter sets its gauges
        # unconditionally
        self.stats: dict[str, float] = dict.fromkeys(
            ("startup-s", "startup-build-s", "startup-warmup-s", *_FROZEN, "startup-programs"), 0)

    def _emit(self, name: str, span_id: str, parent_id: Optional[str], began: float,
              ended: float, status: str = "ok", **attributes: Any) -> None:
        TRACER.emit(Span(
            name, self._trace_id, span_id, parent_id, began + MONO_TO_WALL_S,
            ended - began, attributes, status,
        ))

    def built(self) -> None:
        """The constructor's last line: plan, page pool and device state are
        there."""
        now = time.monotonic()
        self._build_s = now - self._t0
        self._emit("engine.startup.build", uuid.uuid4().hex[:16], self._root_id, self._t0, now,
                   **_compile_ms(self._base, ACCOUNT.snapshot()))

    @contextlib.contextmanager
    def phase(self, family: str) -> Iterator[None]:
        """One warm-up family, on the engine thread."""
        if self._finished:  # a restarted engine thread: every program is built
            yield
            return
        began, before, programs = time.monotonic(), ACCOUNT.snapshot(), self._programs
        self._phase_id, self.warming, status = uuid.uuid4().hex[:16], True, "ok"
        try:
            yield
        except BaseException as e:
            status = f"error: {type(e).__name__}"
            raise
        finally:
            self._close_program()
            self.warming = False
            ended = time.monotonic()
            self._warmup_s += ended - began
            spent = _compile_ms(before, ACCOUNT.snapshot())
            self._emit(f"engine.startup.warmup.{family}", self._phase_id, self._root_id,
                       began, ended, status, programs=self._programs - programs, **spent)
            log.info(
                "start-up, %s: %d programs in %.1fs (trace %.1f, lower %.1f, backend %.1f, "
                "of it cache reads %.1f), %s",
                family, self._programs - programs, ended - began, spent["trace_ms"] / 1e3,
                spent["lower_ms"] / 1e3, spent["backend_ms"] / 1e3,
                spent["cache_retrieval_ms"] / 1e3, status,
            )

    def program(self, signature: tuple) -> None:
        """A warm-up dispatch begins (`_record_program` names it); the one
        before it has been waited for."""
        self._close_program()
        name = program_name(signature)
        annotation = jax.profiler.TraceAnnotation(f"engine.startup.program {name}")
        annotation.__enter__()
        self._program = (name, time.monotonic(), ACCOUNT.snapshot(), annotation)

    def _close_program(self) -> None:
        if self._program is None:
            return
        name, began, before, annotation = self._program
        self._program = None
        annotation.__exit__(None, None, None)
        ended = time.monotonic()
        spent = _compile_ms(before, ACCOUNT.snapshot())
        built_ms = spent["trace_ms"] + spent["lower_ms"] + spent["backend_ms"]
        self._programs += 1
        self._emit("engine.startup.program", uuid.uuid4().hex[:16], self._phase_id, began, ended,
                   program=name, **spent,
                   run_ms=round(max(0.0, (ended - began) * 1e3 - built_ms), 3))

    def finish(self, error: Optional[BaseException] = None) -> None:
        """The warm-up has ended, either way: the root span, and the keys
        `stats()` carries from now on."""
        if self._finished:
            return
        self._finished = True
        ended, now = time.monotonic(), ACCOUNT.snapshot()
        frozen = {key: now[of] - self._base[of] for key, of in _FROZEN.items()}
        self.stats = {
            "startup-s": ended - self._t0, "startup-build-s": self._build_s,
            "startup-warmup-s": self._warmup_s, **frozen, "startup-programs": self._programs,
        }
        self._emit(
            "engine.startup", self._root_id, None, self._t0, ended,
            "ok" if error is None else f"error: {type(error).__name__}",
            programs=self._programs, **_compile_ms(self._base, now),
        )

"""The process's compile account: what building its device programs cost.

JAX reports, through `jax.monitoring`, every program it builds, on the thread
that builds it: a duration for tracing the function to a jaxpr, one for
lowering the jaxpr to an MLIR module, one for the backend's compile (which,
with the persistent cache on, is the cache key's hash over the module plus
the read of the executable, or XLA's compile on a miss), and the cache's own
events. One set of listeners, registered once a process (`register`), sums
them here: the engine's programs, and whatever else the process compiles.

The OUTERMOST rule: a jit traced inside another's trace reports its own
tracing time inside the outer's, and a kernel's lowering traces small jitted
helpers inside the module's lowering, so a plain sum counts nested seconds
twice or more. JAX also sends a scalar under the same event name when a timed
section BEGINS; a stack a thread follows those, and only the event that
leaves a thread's stack empty is summed. Seconds of `trace`, `lower` and
`backend` on one thread therefore never overlap.

Kernel wrappers bump `note_kernel` at trace time: the instances of a kernel a
program's trace reaches (a layer loop that unrolls a period of four layers
reaches four; docs/SERVING.md §12, "Start-up").

Every outermost event is also a `jax.compile` span of the process's tracer,
stamped on the tracer's clock when the listener hears of it (JAX's own stamps
are `time.time()`): its end is the listener's stamp, its start the stamp less
the duration. A compile under traffic shows on `/traces` by name.

No jax at import (the kernels' modules and the tracer's users import this).
"""

from __future__ import annotations

import threading
import time
import uuid
from typing import Any

from langstream_tpu.tracing import MONO_TO_WALL_S, TRACER, Span

# the three timed sections of a build (jax/_src/dispatch.py), by the short
# name the sums carry
_SECTIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
# the persistent cache's durations and events (jax/_src/compiler.py): they
# carry no name, and fire inside the `backend` section they belong to
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache-retrieval",
    "/jax/compilation_cache/compile_time_saved_sec": "cache-saved",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache-requests",
    "/jax/compilation_cache/cache_hits": "cache-hits",
    "/jax/compilation_cache/cache_misses": "cache-misses",
}
_SECONDS = ("trace", "lower", "backend", "cache-retrieval", "cache-saved")
_COUNTS = ("trace", "lower", "backend", "cache-requests", "cache-hits", "cache-misses")
# names the by-name table holds before the rest share one row
_MAX_NAMES = 512
_OTHER = "(other)"


def _program_name(fun_name: Any) -> str:
    """One row a program: tracing names the function, lowering and the
    backend name its module (`jit(<function>)`, `jit_<function>`)."""
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name[4:] if name.startswith("jit_") else name


class CompileAccount:
    """Sums of the process's compile events (see the module's docstring).
    One lock: events are tens to hundreds a start and none while serving."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._open = threading.local()  # .stack: names of sections begun here
        self._seconds = dict.fromkeys(_SECONDS, 0.0)
        self._counts = dict.fromkeys(_COUNTS, 0)
        self._by_name: dict[str, dict[str, float]] = {}
        self._kernels: dict[str, int] = {}
        self._events = 0
        self._listener_s = 0.0
        self.registered = False

    # -- listeners ----------------------------------------------------------

    def _stack(self) -> list:
        try:
            return self._open.stack
        except AttributeError:
            stack = self._open.stack = []
            return stack

    def _row(self, name: str) -> dict[str, float]:
        row = self._by_name.get(name)
        if row is None:
            if len(self._by_name) >= _MAX_NAMES:
                name = _OTHER
            row = self._by_name.setdefault(name, {**dict.fromkeys(_SECONDS, 0.0), "programs": 0})
        return row

    def _spent(self, began: float) -> None:
        self._events += 1
        self._listener_s += time.perf_counter() - began

    def on_scalar(self, event: str, value: float, **kwargs: Any) -> None:
        """A timed section begins on this thread."""
        if event not in _SECTIONS:
            return
        began = time.perf_counter()
        self._stack().append(_program_name(kwargs.get("fun_name", "")))
        with self._lock:
            self._spent(began)

    def on_duration(self, event: str, seconds: float, **kwargs: Any) -> None:
        """A timed section ends on this thread, or the cache says what a
        read took (inside the `backend` section it served)."""
        began = time.perf_counter()
        section, stack = _SECTIONS.get(event), self._stack()
        if section is None:
            key = _CACHE_SECONDS.get(event)
            if key is not None:
                with self._lock:
                    self._seconds[key] += seconds
                    if stack:
                        self._row(stack[0])[key] += seconds
                    self._spent(began)
            return
        name = stack.pop() if stack else _program_name(kwargs.get("fun_name", ""))
        if not stack:  # else another section of this thread holds these seconds
            TRACER.emit(Span(
                "jax.compile", uuid.uuid4().hex[:16], uuid.uuid4().hex[:16], None,
                time.monotonic() - seconds + MONO_TO_WALL_S, seconds,
                {"section": section, "fun_name": name},
            ))
        with self._lock:
            if not stack:
                self._seconds[section] += seconds
                self._counts[section] += 1
                row = self._row(name)
                row[section] += seconds
                row["programs"] += int(section == "backend")
            self._spent(began)

    def on_event(self, event: str, **kwargs: Any) -> None:
        key = _CACHE_EVENTS.get(event)
        if key is not None:
            began = time.perf_counter()
            with self._lock:
                self._counts[key] += 1
                self._spent(began)

    # -- the kernels' counter ----------------------------------------------

    def note_kernel(self, name: str) -> None:
        with self._lock:
            self._kernels[name] = self._kernels.get(name, 0) + 1

    # -- readers ------------------------------------------------------------

    def snapshot(self) -> dict[str, float]:
        """Every sum, one flat dict: `trace-s`, `lower-s`, `backend-s` and
        their counts `-n`, the cache's `cache-retrieval-s`, `cache-saved-s`,
        `cache-requests`, `cache-hits`, `cache-misses`, `kernels-traced`, and
        what the listeners themselves took (`events`, `listener-s`)."""
        with self._lock:
            out: dict[str, float] = {f"{k}-s": v for k, v in self._seconds.items()}
            for key, n in self._counts.items():
                out[key if key.startswith("cache-") else f"{key}-n"] = n
            out["kernels-traced"] = sum(self._kernels.values())
            out["events"] = self._events
            out["listener-s"] = self._listener_s
        return out

    def kernels(self) -> dict[str, int]:
        """Instances traced so far, by kernel."""
        with self._lock:
            return dict(self._kernels)

    def report(self) -> list[dict[str, Any]]:
        """The by-name table, the most seconds first: a row a program (and a
        row an eager operation, which is a program of its own)."""
        with self._lock:
            rows = [{"name": name, **row} for name, row in self._by_name.items()]
        rows.sort(key=lambda r: -(r["trace"] + r["lower"] + r["backend"]))
        return rows


ACCOUNT = CompileAccount()
note_kernel = ACCOUNT.note_kernel


def register() -> None:
    """Point JAX's events at the account. Once a process: `jax.monitoring`
    has no public way to take a listener off, and the account is the
    process's, not an engine's."""
    with ACCOUNT._lock:
        if ACCOUNT.registered:
            return
        ACCOUNT.registered = True
    import jax.monitoring

    jax.monitoring.register_scalar_listener(ACCOUNT.on_scalar)
    jax.monitoring.register_event_duration_secs_listener(ACCOUNT.on_duration)
    jax.monitoring.register_event_listener(ACCOUNT.on_event)

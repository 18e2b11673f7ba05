"""Process-wide request-cancellation registry, keyed by client session.

The gateway and the serving engine meet only through topics (questions in,
answers out), so when a websocket client disconnects the reference simply
lets the pipeline finish into the void — and the engine keeps decoding the
orphan to max_new_tokens, burning a KV slot. This registry is the short
circuit for the deployments where both ends live in one process (the local
runner's embedded gateway, the standalone runner + agent pod):

  - the completions step registers every in-flight GenerationRequest under
    the record's ``langstream-client-session-id`` header (the same header
    the chat-gateway examples route answers by),
  - the gateway's ClientDisconnected paths call ``cancel(session_id)``,
  - the engine frees the cancelled slots at the next chunk boundary.

Cross-process FLEET routes are covered too (ROADMAP 3b): when the fleet
router dispatches a session's request to a REMOTE replica, the completions
step records the owning replica's base URL here (``register_remote``), the
peer's ``engine_generate`` registers the in-flight request in ITS
process-local registry under the same session key, and ``cancel()``
forwards ``POST /fleet/cancel`` to every recorded owner — so a
disconnected client's remote decode dies at the next chunk boundary
instead of at its deadline. Forwarding is best-effort on a background
thread (a dead peer must not stall the gateway's disconnect path); the
deadline knobs remain the backstop for topologies with no runtime HTTP
server between the processes (docs/SERVING.md §9).
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Protocol

log = logging.getLogger(__name__)

# the chat-gateway convention header (examples, benchmark/run.py's probe) — the
# gateway resolves it from the client's ?param.sessionId, the completions
# agent sees it as a record property
SESSION_HEADER = "langstream-client-session-id"


class Cancellable(Protocol):
    def cancel(self) -> None: ...


_lock = threading.Lock()
# lock discipline registry (analysis pass `locks`, docs/ANALYSIS.md):
# both registries are written from gateway threads and read from
# cancel/debug paths — every mutation must hold _lock.
_GUARDED = {"_lock": ("_by_key", "_remote_by_key")}
_by_key: dict[str, dict[int, Any]] = {}
# session → {replica base URL: refcount}: which REMOTE replicas currently
# own in-flight work for the session (fleet dispatch). Refcounted — a
# session can have overlapping requests on the same peer.
_remote_by_key: dict[str, dict[str, int]] = {}


def register_remote(key: str, base_url: str) -> None:
    """Record that session ``key`` has an in-flight request on the replica
    at ``base_url`` (the fleet dispatch path). cancel() forwards there."""
    if not key or not base_url:
        return
    with _lock:
        owners = _remote_by_key.setdefault(key, {})
        owners[base_url] = owners.get(base_url, 0) + 1


def unregister_remote(key: str, base_url: str) -> None:
    if not key or not base_url:
        return
    with _lock:
        owners = _remote_by_key.get(key)
        if owners is None:
            return
        left = owners.get(base_url, 0) - 1
        if left > 0:
            owners[base_url] = left
        else:
            owners.pop(base_url, None)
        if not owners:
            _remote_by_key.pop(key, None)


def _forward_cancel(key: str, urls: list[str]) -> None:
    """POST /fleet/cancel to each owning replica. Runs on a daemon thread:
    best-effort — a dead peer's requests die by deadline as before, and
    the gateway's disconnect path must never stall on a peer timeout."""
    import json as _json
    import urllib.error
    import urllib.request

    for url in urls:
        try:
            req = urllib.request.Request(
                url.rstrip("/") + "/fleet/cancel",
                data=_json.dumps({"session": key}).encode("utf-8"),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=2.0) as r:
                out = _json.loads(r.read().decode("utf-8"))
            log.info(
                "forwarded cancel for session %r to %s (%s cancelled there)",
                key, url, out.get("cancelled"),
            )
        except (urllib.error.URLError, OSError, ValueError) as e:
            log.warning(
                "cancel forward to %s failed for session %r: %s "
                "(deadline remains the backstop)", url, key, e,
            )


def register(key: str, request: Cancellable) -> None:
    """Track ``request`` under session ``key`` until unregister()."""
    if not key:
        return
    with _lock:
        _by_key.setdefault(key, {})[id(request)] = request


def unregister(key: str, request: Cancellable) -> None:
    if not key:
        return
    with _lock:
        bucket = _by_key.get(key)
        if bucket is not None:
            bucket.pop(id(request), None)
            if not bucket:
                _by_key.pop(key, None)


def cancel(key: str) -> int:
    """Cancel every in-flight request registered under ``key``; returns the
    number cancelled LOCALLY. Requests stay registered until their owner
    unregisters (cancellation resolves them through the engine, which is
    what triggers the owner's unregister). Sessions whose work was fleet-
    routed to a remote replica additionally get the cancel FORWARDED to
    the owning replica's /fleet/cancel endpoint (background thread,
    best-effort — ROADMAP 3b)."""
    if not key:
        return 0
    with _lock:
        requests = list(_by_key.get(key, {}).values())
        remote_urls = list(_remote_by_key.get(key, {}))
    if remote_urls:
        threading.Thread(
            target=_forward_cancel, args=(key, remote_urls),
            name="fleet-cancel-forward", daemon=True,
        ).start()
    for request in requests:
        try:
            request.cancel()
        except Exception:  # noqa: BLE001 — one bad entry must not shield the rest
            log.exception("cancel() failed for a request under key %r", key)
    if requests:
        log.info("cancelled %d in-flight request(s) for session %r", len(requests), key)
        _trace_disconnect(key, requests)
    return len(requests)


def _trace_disconnect(key: str, requests: list) -> None:
    """Mark the disconnect-driven cancellation on each request's trace —
    an incident reader asking "why did this generation end early?" finds
    the WebSocket disconnect next to the engine's cancelled span instead
    of inferring it from a counter (docs/SERVING.md §12)."""
    try:
        import time as _time
        import uuid as _uuid

        from langstream_tpu.tracing import TRACER, Span

        if not TRACER.enabled:
            return
        for request in requests:
            trace_id = getattr(request, "trace_id", None)
            if not trace_id:
                continue
            TRACER.emit(Span(
                name="gateway.disconnect-cancel",
                trace_id=trace_id,
                span_id=_uuid.uuid4().hex[:16],
                parent_id=None,
                start_s=_time.time(),
                duration_s=0.0,
                attributes={"session": key},
            ))
    except Exception:  # noqa: BLE001 — tracing must never break teardown
        log.exception("disconnect trace emission failed")


def active_keys() -> list[str]:
    """Snapshot of sessions with in-flight requests (tests/debugging)."""
    with _lock:
        return [k for k, v in _by_key.items() if v]

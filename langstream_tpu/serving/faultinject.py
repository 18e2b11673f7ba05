"""Deterministic fault injection for the serving engine.

The recovery paths in ``serving/engine.py`` (slot quarantine, loop restart
under backoff, shed-on-full-queue, NaN-guard) are only trustworthy if they
can be DRIVEN on demand — a failure story that has never executed is a
comment, not a feature. This module is the driver: a seedable injector the
engine consults at every fault site, so chaos tests (and staging drills via
env vars) replay the exact same fault sequence on every run.

Sites (where the engine asks ``fires(site)``):
  prefill   raise before a batched admission dispatch (fails one group)
  segment   raise before a chunked-prefill segment dispatch (fails a stream)
  decode    raise before a decode-chunk dispatch (crashes the engine loop —
            exercises quarantine + restart-under-backoff)
  nan       corrupt one active slot's fetched tokens to the NaN-guard
            sentinel (exercises per-slot quarantine + KV row reset)
  verify    corrupt one active slot's fetched VERIFY result (self-
            speculative decoding) to the sentinel with accept forced to 0
            — a fault during verification must quarantine only that slot
  page      corrupt one active slot's page-table entry (paged KV layout:
            host bookkeeping / memory corruption drill) — the engine's
            integrity check must quarantine ONLY that slot and free its
            pages back to the pool through the authoritative owned list
  adapter   corrupt one active slot's dispatch-facing adapter row (the
            multi-LoRA gather index, serving/adapters.py) — serving slot X
            with tenant Y's factors is SILENT wrongness, so the engine
            compares the row against its authoritative copy before every
            decode/verify dispatch and must quarantine ONLY the victim
            while every survivor stays token-exact
  spill     corrupt one host-arena page of the entry a hibernation restore
            is about to upload (tiered KV, serving/pagepool.HostPageTier:
            host-RAM-rot drill) — the arena checksum must catch it and the
            victim admission must fall back to a cold re-prefill, token-
            exact, while survivors and the free lists stay untouched
  weight-load  raise from the streamed shard reader as if a safetensors
            shard came up short mid-read (models/streamload.py) — the
            engine build must abort loudly with the shard + tensor named,
            never retry the poisoned bytes, never serve partial weights
  fetch     stall the device→host fetch thread (slow-device-link simulation)
  client    stall token delivery before the on_token callback (slow-client
            backpressure simulation)

Durable-tier disk sites (serving/durable.py — docs/SERVING.md §23; these
are consulted by the checkpoint store the engine hands its injector to):
  disk-torn     truncate a just-written checkpoint mid-frame (torn write:
                the CRC32 frame prelude must read it as a dead entry)
  disk-corrupt  flip one payload byte under a valid manifest (bit rot:
                the frame CRC / spill-time checksum must catch it)
  disk-stall    sleep ``stall_s`` inside checkpoint/restore (slow or hung
                volume — the restore deadline must fire, never a hang)
  disk-full     raise before any byte is written (ENOSPC simulation)

Network sites (the fleet wire, serving/fleet.py + runtime/http_server.py —
docs/SERVING.md §17; these drive the replica-to-replica streaming
transport, not the engine, and are consulted by the process-wide WIRE
injector ``fleet.set_wire_injector`` / LSTPU_FAULTS):
  net-connect  refuse the hop before it connects (client-side: HttpReplica
               raises ReplicaError as if the peer's socket was refused)
  net-stall    the stream goes silent mid-token (server-side: the handler
               sleeps ``stall_s`` before the next frame — no tokens, no
               heartbeats; the client's idle timeout must distinguish this
               dead-peer signature from ordinary slow decode)
  net-cut      connection reset after N frames (server-side: the handler
               aborts the transport instead of writing the frame — the
               mid-stream death the warm-failover path exists for)
  net-corrupt  malformed frame (server-side: the handler writes a
               non-JSON line in the frame's place — the client's frame
               validation must fail the hop, never deliver garbage)

Spec grammar (comma-separated, e.g. ``"decode@3,nan@5:4,fetch~0.1"``):
  site@N      fire exactly once, on the Nth call to that site (1-based)
  site@N+     fire on every call from the Nth on
  site@N:M    fire on call N, then every M calls after (periodic)
  site~P      fire with probability P per call (seeded RNG → deterministic
              for a given seed + call sequence)

Activation: pass a ``FaultInjector`` to ``ServingEngine(fault_injector=…)``
(tests), or set env vars for a staging drill —
  LSTPU_FAULTS="decode@40:120,nan@77"   the spec
  LSTPU_FAULT_SEED=0                     RNG seed (pinned in CI chaos runs)
  LSTPU_FAULT_STALL_S=0.05               stall duration for fetch/client
The ``tpu-serving`` resource also forwards ``fault-injection`` /
``fault-seed`` / ``fault-stall-s`` config keys (docs/SERVING.md §9).
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
from dataclasses import dataclass
from typing import Optional

log = logging.getLogger(__name__)

SITES = (
    "prefill", "segment", "decode", "nan", "verify", "page", "adapter",
    "spill", "fetch", "client",
    # fleet-wire sites (docs/SERVING.md §17): applied by the streaming
    # transport and the /fleet/generate handler, not the engine
    "net-connect", "net-stall", "net-cut", "net-corrupt",
    # KV-page migration site (docs/SERVING.md §18): corrupt one page
    # payload of an in-flight replica-to-replica migration — the
    # receiver's per-page checksum must catch it, discard the partial
    # bind (no leaked pages), and the sender must RETAIN its copy so the
    # router can fall back to decode-in-place, token-exact
    "migrate",
    # multi-tenant noisy-neighbor site (docs/SERVING.md §19): when it
    # fires, the engine injects a burst of synthetic low-priority
    # admissions under the "chaos-burst" tenant at the iteration top —
    # the deterministic aggressor of the fair-share drill. The victim
    # tenant's streams must stay token-exact with bounded p99 TTFT while
    # the aggressor absorbs ALL the shedding.
    "tenant-burst",
    # SPMD slice-resilience sites (docs/SERVING.md §20). spmd-crash is
    # consulted by the LEADER engine at the iteration top — a raise there
    # is an engine-loop crash under SPMD, driving the coordinated
    # OP_RECOVER drill (both sides rebuild in place, zero process exits).
    # spmd-wedge and spmd-drop are consulted by the CHANNEL at announce
    # time (transport-layer wire loss, the leader believes it announced):
    # wedge silences the leader permanently (the follower watchdog must
    # detect it within the bound and leave a spmd-wedge flight dump);
    # drop loses ONE idle heartbeat, so the next delivered announcement
    # carries the seq gap the divergence-resync path must heal.
    "spmd-crash", "spmd-wedge", "spmd-drop",
    # streamed weight load (models/streamload.py, docs/SERVING.md §22):
    # consulted by the shard reader before each tensor slice — a firing
    # simulates a truncated/corrupt shard read. The load must fail with a
    # WeightLoadError naming the shard file AND the tensor, no partial
    # engine may come up, and the poisoned checkpoint must never be
    # re-read (zero retries — wrong weights are worse than no weights)
    "weight-load",
    # durable-tier disk sites (serving/durable.py, docs/SERVING.md §23):
    # consulted by the checkpoint store around its read/write paths.
    # disk-torn truncates a just-renamed checkpoint mid-frame (the torn
    # write a crash between rename and the last flushed block leaves);
    # disk-corrupt flips one payload byte (bit rot under a valid
    # manifest); disk-stall sleeps stall_s inside checkpoint/restore
    # (slow or hung volume — the restore deadline must fire); disk-full
    # raises before any byte is written (ENOSPC). Every firing must
    # degrade to a local cold prefill with a durable-restore-failed
    # flight dump — dead entries, never wrong KV, never a hang.
    "disk-torn", "disk-corrupt", "disk-stall", "disk-full",
)

# the NaN-guard sentinel sampling.sample() emits for a non-finite logits row;
# the injector writes the same value into fetched tokens so the engine's
# quarantine path is exercised end-to-end without needing to corrupt device
# memory (serving/sampling.py is unit-tested against real NaN logits)
NAN_SENTINEL = -1


class InjectedFault(RuntimeError):
    """Raised at raise-type sites; stands in for an XLA/device error."""


@dataclass
class _Rule:
    """One site's firing schedule."""

    site: str
    at: int = 0  # first firing call number (1-based); 0 = probability mode
    every: int = 0  # 0 = fire once; >0 = period after `at`; -1 = every call from `at`
    prob: float = 0.0

    def fires(self, call_no: int, rng: random.Random) -> bool:
        if self.at == 0:
            return rng.random() < self.prob
        if call_no < self.at:
            return False
        if self.every == -1:
            return True
        if self.every == 0:
            return call_no == self.at
        return (call_no - self.at) % self.every == 0


def _parse_spec(spec: str) -> dict[str, _Rule]:
    rules: dict[str, _Rule] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "~" in part:
            site, _, p = part.partition("~")
            rule = _Rule(site=site.strip(), prob=float(p))
        elif "@" in part:
            site, _, sched = part.partition("@")
            site = site.strip()
            if sched.endswith("+"):
                rule = _Rule(site=site, at=int(sched[:-1]), every=-1)
            elif ":" in sched:
                n, _, m = sched.partition(":")
                rule = _Rule(site=site, at=int(n), every=max(1, int(m)))
            else:
                rule = _Rule(site=site, at=int(sched))
        else:
            raise ValueError(
                f"bad fault spec part {part!r}: expected site@N, site@N+, "
                "site@N:M, or site~P"
            )
        if rule.site not in SITES:
            raise ValueError(
                f"unknown fault site {rule.site!r}; known: {', '.join(SITES)}"
            )
        rules[rule.site] = rule
    return rules


class FaultInjector:
    """Seedable, thread-safe fault schedule. One per engine.

    Call counters are PER SITE and only advance for sites with a rule, so a
    spec targeting ``decode`` leaves every other path byte-identical to a
    fault-free run — the survivor-token-exactness property the chaos suite
    asserts."""

    def __init__(self, spec: str, seed: int = 0, stall_s: float = 0.05) -> None:
        self.spec = spec
        self.seed = seed
        self.stall_s = stall_s
        self._rules = _parse_spec(spec)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._calls: dict[str, int] = {s: 0 for s in self._rules}
        self.fired: dict[str, int] = {s: 0 for s in self._rules}
        # recent firings (site, call number, wall time) — the flight
        # recorder folds these into its dumps so a postmortem shows WHICH
        # injected fault preceded the quarantine/restart it captured
        from collections import deque

        self.events: "deque[dict]" = deque(maxlen=32)

    @classmethod
    def from_env(cls, env=os.environ) -> Optional["FaultInjector"]:
        spec = env.get("LSTPU_FAULTS", "").strip()
        if not spec:
            return None
        return cls(
            spec,
            seed=int(env.get("LSTPU_FAULT_SEED", "0")),
            stall_s=float(env.get("LSTPU_FAULT_STALL_S", "0.05")),
        )

    def fires(self, site: str) -> bool:
        rule = self._rules.get(site)
        if rule is None:
            return False
        with self._lock:
            self._calls[site] += 1
            hit = rule.fires(self._calls[site], self._rng)
            if hit:
                self.fired[site] += 1
                self.events.append({
                    "site": site,
                    "call": self._calls[site],
                    "t": round(time.time(), 3),
                })
                log.warning(
                    "fault injection: %s fires (call %d, total %d)",
                    site, self._calls[site], self.fired[site],
                )
            return hit

    def fire(self, site: str) -> None:
        """Raise-type sites: raise InjectedFault on schedule."""
        if self.fires(site):
            raise InjectedFault(
                f"injected {site} fault #{self.fired[site]} (spec {self.spec!r})"
            )

    def stall(self, site: str) -> None:
        """Stall-type sites: sleep on schedule."""
        if self.fires(site):
            time.sleep(self.stall_s)

    def corrupt_tokens(self, host, snapshot):
        """``nan`` site: overwrite one active slot's tokens in a fetched
        [steps, B] chunk with the NaN-guard sentinel, exactly as if
        sampling's non-finite guard had tripped on device for that slot.
        The victim is drawn from the seeded RNG over the chunk's snapshot
        (deterministic for a pinned seed). Returns ``(host, victim)`` —
        ``host`` is a writable copy when the site fires (device fetches can
        be read-only), the original array otherwise (victim None)."""
        import numpy as np

        if not snapshot or not self.fires("nan"):
            return host, None
        with self._lock:
            victim = snapshot[self._rng.randrange(len(snapshot))][0]
        host = np.array(host)
        host[:, victim] = NAN_SENTINEL
        return host, victim

    def corrupt_verify(self, packed, snapshot):
        """``verify`` site: corrupt one active slot's row of a fetched
        verify result (``[B, k+2]`` = emitted tokens ++ accepted count) so
        the slot's first delivered token is the NaN-guard sentinel with
        accept forced to 0 — exactly what speculative_verify emits when a
        device fault poisons that slot's logits mid-verification. The
        engine's quarantine path then runs end-to-end for ONE slot while
        every other slot's accepted tokens deliver untouched. Victim drawn
        from the seeded RNG; returns a writable copy when the site fires,
        the original array otherwise."""
        import numpy as np

        if not snapshot or not self.fires("verify"):
            return packed
        with self._lock:
            victim = snapshot[self._rng.randrange(len(snapshot))][0]
        packed = np.array(packed)
        packed[victim, 0] = NAN_SENTINEL  # first emitted token → sentinel
        packed[victim, -1] = 0  # accept 0 → the sentinel is delivered first
        return packed

    def corrupt_adapter_rows(self, rows, snapshot):
        """``adapter`` site: bump one active slot's entry in the engine's
        dispatch-facing adapter-row array, leaving the authoritative copy
        intact — the host-corruption drill for the multi-LoRA gather
        index. The engine's pre-dispatch integrity check must catch the
        mismatch and quarantine only that slot. Victim drawn from the
        seeded RNG; returns the victim slot or None."""
        if not snapshot or not self.fires("adapter"):
            return None
        with self._lock:
            victim = snapshot[self._rng.randrange(len(snapshot))][0]
            rows[victim] = rows[victim] + 1  # any mismatch will do
        return victim

    def corrupt_page_table(self, pool, snapshot):
        """``page`` site: scramble one active slot's page-table entry in
        the HOST table array (the device-facing derivation), leaving the
        allocator's authoritative owned list intact — exactly the class of
        bug/corruption the engine's pre-dispatch integrity check exists to
        catch. Victim drawn from the seeded RNG over the active snapshot;
        returns the victim slot or None."""
        if not snapshot or not self.fires("page"):
            return None
        with self._lock:
            victim = snapshot[self._rng.randrange(len(snapshot))][0]
            # point the slot's first mapped entry somewhere else entirely
            pool.tables[victim, 0] = (pool.tables[victim, 0] + 1) % pool.num_pages
        return victim

    def corrupt_migration_frame(self, frame):
        """``migrate`` site: flip bytes of one page payload of an
        in-flight KV migration (serving/migrate.py) — the wire-corruption
        drill for the replica-to-replica transfer. The frame's stamped
        checksum is left INTACT while the payload is damaged, so the
        receiver's per-page verification must catch the mismatch and
        abort the bind. Returns True when the site fired (the frame was
        mutated in place)."""
        if frame.get("kind") != "page" or not self.fires("migrate"):
            return False
        raw = frame.get("raw")
        if raw:
            # v2 binary payload (serving/wire.py): flip the first raw
            # byte — same bit-rot class, same checksum-must-catch-it
            # contract as the base64 branch below
            damaged = bytearray(raw)
            damaged[0] ^= 0xFF
            frame["raw"] = bytes(damaged)
            return True
        data = frame.get("data") or []
        if not data or not data[0]:
            return False
        # flip the first base64 character to a DIFFERENT valid one: the
        # payload still decodes (same length, same charset) but its bytes
        # differ — exactly the bit-rot-in-flight class the per-page
        # checksum exists to catch, exercised through the verify path
        # rather than the cheaper undecodable-garbage path
        first = data[0][0]
        data[0] = ("A" if first != "A" else "B") + data[0][1:]
        return True

    def corrupt_host_page(self, tier, slots):
        """``spill`` site: flip one byte of one arena slot the restore is
        about to read (drawn from the seeded RNG over the entry's slots) —
        the host-memory-rot drill for the tiered-KV path. The tier's
        checksum verification must catch it and the engine must degrade
        the hit to a cold re-prefill, never serve the poisoned KV.
        Returns the corrupted slot or None."""
        if tier is None or not slots or not self.fires("spill"):
            return None
        with self._lock:
            victim = slots[self._rng.randrange(len(slots))]
        tier.corrupt(victim)
        return victim

    def events_snapshot(self) -> list[dict]:
        """Copy of the recent-firings ring, taken under the injector lock —
        iterating the deque lock-free races fires() appends from the
        engine/fetch threads (deque mutation during iteration raises)."""
        with self._lock:
            return list(self.events)

    def stats(self) -> dict[str, int]:
        return dict(self.fired)

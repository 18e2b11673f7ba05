"""Unified paged KV pool: ONE device-resident page pool + host allocator.

ROADMAP item 1 collapses the engine's three KV memory schemes — per-slot
dense caches sized by the ``kv_bound`` compile ladder, the bucket-aligned
prefix pool with copy-on-admit gathers, and the opt-in ragged paged decode
kernel — into a single page-table-indexed pool (PAPERS.md "Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for TPU").
This module is the HOST half:

- ``PagePool``: the device tree (``models.transformer.make_page_pool`` —
  ``[L, P, Hkv, page_size, D]``, bf16 or int8+scales) plus a free-list page
  allocator with refcounts and per-slot page tables. A slot's table row
  maps logical page ``t // page_size`` to a physical page; unmapped entries
  carry the out-of-bounds sentinel (= num_pages) so device scatters drop
  and gathers clamp into the masked region.
- ``PrefixPageIndex``: the radix index that turns prefix reuse into page
  ALIASING — a hit appends the shared pages to the slot's table (refcount
  bump, zero device copies; only a final PARTIAL page is copy-on-write,
  one page-sized dispatch) and publish-on-prefill just bumps refcounts.
  Compare ``serving/prefix_cache.py``: the dense design needed a separate
  pool-width device pool, a gather per hit, and a row copy per publish.

Eviction and exhaustion: prefix entries are evicted LRU (unpinned only)
when an admission cannot allocate; if the pool is STILL exhausted the
admission defers (the engine retries next iteration and the bounded queue
sheds upstream) — pages are never over-committed, so exhaustion can shed
but can never corrupt. All methods run on the engine thread — no locking.

The injector's ``page`` fault site corrupts a table row (host memory /
bookkeeping corruption drill); ``_owned`` is the AUTHORITATIVE per-slot
page list kept apart from the table array, so ``validate`` detects the
corruption and ``free_slot`` still returns every page to the free list —
the no-leak property the chaos suite asserts.

Tiered KV (ROADMAP item 3): ``HostPageTier`` is a host-RAM page arena
UNDER the device pool — idle published prefixes (hibernated chat/agent
sessions) spill their pages into it asynchronously, and under HBM
pressure the LRU eviction DEMOTES an entry's device pages to the host
copy instead of dropping the prefix, so the device pool behaves as a
cache over host RAM (~10× larger per host). ``PrefixPages`` tracks the
tier per entry (``device`` | ``both`` | ``host``); a radix hit on a
host-resident entry triggers a device restore (engine._restore_entry —
one warmed traced-index upload program, DMA speed) instead of a miss.
Every arena slot carries a blake2b checksum written at spill time and
verified at restore time, so a corrupted host page (the ``spill`` fault
site, or real RAM rot) degrades to a cold re-prefill — never to silently
wrong KV.
"""

from __future__ import annotations

import hashlib
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple, Optional

import jax
import numpy as np


def prefix_digest(tokens) -> str:
    """Stable, process-independent digest of a token prefix (blake2b over
    the int32 byte image). This is what replicas ADVERTISE in their fleet
    beacon instead of the tokens themselves — prompt content must never
    leave the engine (same redaction stance as the flight recorder), and a
    16-hex digest is 8 bytes of beacon per prefix instead of kilobytes.
    The router hashes an incoming prompt at the advertised lengths and
    matches digests, so both sides must use THIS function."""
    arr = np.asarray(list(tokens), np.int32)
    return hashlib.blake2b(arr.tobytes(), digest_size=8).hexdigest()


def page_checksum(blocks) -> bytes:
    """blake2b-16 over one page's leaf blocks (``jax.tree.leaves`` order,
    C-contiguous). ONE definition shared by the host spill tier and the
    inter-replica migration wire (serving/migrate.py): a page spilled to
    host RAM and a page serialized onto the fleet wire carry the SAME
    digest, so a hibernated session migrates straight from the arena with
    its stamped checksum — no device restore, no re-hash drift."""
    h = hashlib.blake2b(digest_size=16)
    for b in blocks:
        h.update(np.ascontiguousarray(b))
    return h.digest()


def join_page_bytes(blocks) -> bytes:
    """One page's leaf blocks (``jax.tree.leaves`` order) → the raw
    concatenated byte image the v2 migration wire ships (serving/wire.py):
    every leaf at its NATIVE dtype width, C-contiguous — int8 pools move
    int8 bytes, no base64 tax. The byte order matches ``page_checksum``'s
    update order, so the stamped digest verifies either representation."""
    return b"".join(
        np.ascontiguousarray(b).tobytes() for b in blocks
    )


def split_page_bytes(raw: bytes, specs) -> list:
    """Inverse of ``join_page_bytes``: split one raw page payload back
    into per-leaf arrays against the receiver pool's layout ``specs``
    (``(page_shape, dtype)`` pairs, serving/migrate._leaf_specs order).
    Raises ValueError on any size mismatch — a truncated or padded
    payload must abort BEFORE the checksum, never reshape garbage."""
    out = []
    off = 0
    for shape, dtype in specs:
        nb = int(math.prod(shape)) * np.dtype(dtype).itemsize
        chunk = raw[off:off + nb]
        if len(chunk) != nb:
            raise ValueError(
                f"page payload truncated at leaf {len(out)} "
                f"({len(chunk)} of {nb} bytes)"
            )
        out.append(np.frombuffer(chunk, dtype=dtype).reshape(shape))
        off += nb
    if off != len(raw):
        raise ValueError(
            f"page payload carries {len(raw) - off} trailing byte(s) "
            f"past its {off}-byte leaf layout"
        )
    return out


# -- what a kind of state refuses (docs/SERVING.md §11 is written from this) --
#
# The pool's formats are those of K and V a page: the host tier, the migration
# wire and the durable checkpoint snapshot and restore those two leaves
# (`_page_snapshot`, serving/wire.py, serving/durable.py), a prefix is reused
# by aliasing pages, the verify path is jnp over K and V and can be rolled
# back, an int8 pool is K's and V's, adapter terms reach the K/V projections
# and the pool shards its KV heads. A row that keeps another kind of state
# beside or in place of that is refused, at build and by the option's name,
# whatever of it no format carries yet. A kind new to the engine adds a row
# here; the engine's build and its migration commands read nothing else.


class StateKind(NamedTuple):
    """One kind of state a row keeps, and what cannot be used with it."""

    has: Callable[[Any], bool]  # of a ModelConfig
    does: str  # "<model> <does>: <options> cannot be used with <state>"
    state: str
    refuses: tuple[str, ...]  # in the order the message names them
    migration: Optional[str]  # what a migration command is told, if refused


_TIERS = ("host_kv_fraction", "migrate_staging", "durable_dir")
_SHAPES = ("adapters", "mesh", "spmd")
_OTHER_LEAVES = (*_TIERS, "speculation", "kv_cache_dtype", *_SHAPES)  # a latent's, an indexer's
_NO_WIRE = "KV-page migration carries K and V only: a page's {} no wire format yet"

STATE_KINDS: tuple[StateKind, ...] = (
    # overwritten in place: it cannot be aliased between slots, has no spill,
    # migrate or durable format, cannot be rolled back past a rejected draft,
    # takes no adapter terms and is not sharded
    StateKind(
        lambda c: c.is_recurrent, "has recurrent layers", "a recurrent state",
        ("prefix_cache", *_TIERS, "speculation", *_SHAPES, "ring_axis"),
        "KV-page migration carries pages only: a recurrent state row has no "
        "wire format yet",
    ),
    # a ring a row beside the pages: what lies behind the window is gone, so
    # a prefix cannot be aliased out of it, spilled, migrated or checkpointed
    # whole; a rejected draft's rows may already have recycled a page; the
    # parallel block takes no adapter terms; the window group is not sharded
    # and has no int8 pages
    StateKind(
        lambda c: c.has_window, "has window layers", "two page groups",
        ("prefix_cache", *_TIERS, "speculation", *_SHAPES, "ring_axis", "kv_cache_dtype"),
        None,
    ),
    # a leaf "lat" where "k" and "v" stand, with the indexer's key beside it or
    # without: no tier, wire or checkpoint format carries it; no verify or
    # adapter term reaches the latent's attention half; its decode kernel takes
    # no mesh. A prefix IS reused by all three: a cached page holds its tokens'
    # latents and indexer keys, which depend on nothing after them
    StateKind(
        lambda c: c.has_latent and c.has_indexer,
        "keeps a latent under a learned selection",
        "a latent and an indexer's keys in the page pool",
        _OTHER_LEAVES,
        _NO_WIRE.format("indexer keys and its latent have"),
    ),
    StateKind(
        lambda c: c.has_latent, "keeps a latent", "a latent in the page pool",
        _OTHER_LEAVES,
        _NO_WIRE.format("latent has"),
    ),
    # one more leaf a token: a page that came back without its indexer keys
    # would be ranked by stale ones; the verify path knows no selection; an
    # int8 pool has no third leaf; the indexer takes no adapter terms and its
    # gathers are not sharded
    StateKind(
        lambda c: c.has_indexer, "reads a learned selection",
        "an indexer's keys in the page pool",
        _OTHER_LEAVES,
        _NO_WIRE.format("indexer keys have"),
    ),
    # a grammar advances left to right, a block's tokens are fixed out of
    # order; a verify yields autoregressive tokens; the block pass reads and
    # writes a bf16 pool where it lies; a block may not straddle two pages;
    # between passes a row's last block holds K/V of tokens not final yet, so
    # its pages cannot be spilled, migrated or checkpointed; a prefix hit's
    # warm suffix would go through the segment program, which has no
    # block-causal mask; the block pass carries no adapter terms, its kernel
    # call no mesh
    StateKind(
        lambda c: c.fills_blocks, "fills blocks of {block} tokens by denoising",
        "a row that advances by a block",
        ("constrained_decoding", "speculation", "kv_cache_dtype", "page_size",
         "prefix_cache", *_TIERS, *_SHAPES),
        "KV-page migration: a row that advances by a block holds, between "
        "passes, K/V of tokens that are not final yet",
    ),
)

# the options read as a switch, and whether `auto` asks for one: it does where
# `auto` turns the feature on when it can, and a refusal then beats a silent
# off; `constrained-decoding: auto` means "where it is supported", which a
# model that fills blocks takes as off
_SWITCHES = {"prefix_cache": True, "speculation": True, "constrained_decoding": False}


def options_asked(config: Any, page_size: int, **options: Any) -> dict[str, bool]:
    """What of STATE_KINDS' options a build asks for: the engine's keywords
    as it got them (``options``), and the three the configuration carries."""

    def switched(name: str) -> bool:
        said = str(options[name]).lower()
        return said in ("on", "true", "1") or (_SWITCHES[name] and said == "auto")

    return {
        **{name: switched(name) for name in _SWITCHES},
        "host_kv_fraction": float(options["host_kv_fraction"]) > 0,
        "migrate_staging": bool(options["migrate_staging"]),
        "durable_dir": bool(options["durable_dir"]),
        "adapters": bool(options["adapters"]),
        "mesh": options["mesh"] is not None,
        "spmd": options["spmd"] is not None,
        "ring_axis": config.ring_axis is not None,
        "kv_cache_dtype": config.kv_cache_dtype == "int8",
        "page_size": config.fills_blocks and int(page_size) % config.block_length != 0,
    }


def refuse_for_state(config: Any, page_size: int, **options: Any) -> None:
    """Raise for the first kind of state of ``config`` that refuses something
    asked for, naming every such option (`ServingEngine.__init__`)."""
    asked = options_asked(config, page_size, **options)
    for kind in STATE_KINDS:
        named = [name for name in kind.refuses if asked[name]] if kind.has(config) else []
        if named:
            raise ValueError(
                f"{config.name} {kind.does.format(block=config.block_length)}: "
                f"{', '.join(named)} cannot be used with {kind.state}"
                + (f" (page_size {page_size})" if "page_size" in named else "")
            )


def migration_refused(config: Any) -> Optional[str]:
    """What a migration command is told of ``config``'s state, or None."""
    return next(
        (k.migration for k in STATE_KINDS if k.migration and k.has(config)), None
    )


def table_len_for(max_seq_len: int, page_size: int) -> int:
    """Per-slot worst-case page-table length: enough logical pages to map
    every position a slot can ever write (the memory-plan term)."""
    return max(1, math.ceil(max_seq_len / page_size))


def pages_for_fraction(
    max_batch: int, max_seq_len: int, page_size: int, fraction: float = 0.0,
) -> int:
    """Pool size in pages: the dense cache's token capacity (max_batch ×
    max_seq_len — every slot can still reach max_seq_len, dense parity) plus
    ``fraction`` headroom for refcount-pinned shared prefix pages. This is
    the ``prefix-cache-fraction`` knob's migration target: the fraction no
    longer sizes a SEPARATE pool-width pool, it adds alias headroom to the
    one pool (docs/SERVING.md §11)."""
    base = max_batch * table_len_for(max_seq_len, page_size)
    extra = math.ceil(base * fraction) if fraction > 0 else 0
    return base + extra


def window_ring_pages(window: int, in_flight: int, page_size: int) -> int:
    """Pages a window row can need at once: the columns its dispatch's first
    query can still see and those the dispatch writes, ``window + in_flight
    - 1`` of them, wherever their first one lies in its page."""
    return (window + in_flight + page_size - 3) // page_size + 1


def window_group_pages(
    config: Any, max_batch: int, max_seq_len: int, page_size: int, in_flight: int,
) -> tuple[int, int]:
    """(pages, ring) of a model's window group: ``max_batch`` rings, each
    the window and the ``in_flight`` positions one dispatch writes a row, or
    a row's whole table where that is shorter. (0, 0) without window layers.
    The one sizing rule: the pool and the memory plan both read it."""
    if not config.has_window:
        return 0, 0
    ring = window_ring_pages(config.sliding_window, max(1, int(in_flight)), page_size)
    return max_batch * min(ring, table_len_for(max_seq_len, page_size)), ring


class WindowPageGroup:
    """The page group of a model's WINDOW layers: its own pages, free list
    and table, beside the full layers' (`PagePool`). The table is indexed by
    the logical page like theirs, but a row maps only a RING: the pages that
    hold the last ``window`` positions and the dispatch in flight. A row is
    bound with as many pages as it can ever hold at once (``ring`` of them,
    or its whole length if that is less) and never allocates again: before
    every dispatch `advance` unmaps the pages that lie wholly behind the
    dispatch's first visible column and maps them again, ahead, where the
    dispatch writes (a recycle; the page's old rows are overwritten before
    the causal mask reaches them, as a fresh page's are). A row that never
    passes the window never recycles."""

    def __init__(self, num_pages: int, page_size: int, max_batch: int,
                 table_len: int, window: int, ring: int) -> None:
        if num_pages < 1 or ring < 1:
            raise ValueError("a window page group needs >= 1 page and a ring of >= 1")
        self.num_pages, self.page_size = int(num_pages), int(page_size)
        self.window, self.ring = int(window), int(ring)
        self.oob = self.num_pages
        self.tables = np.full((max_batch, table_len), self.oob, np.int32)
        self._free = list(range(self.num_pages - 1, -1, -1))
        # authoritative, a slot: {logical page: physical page} of what is
        # mapped, the pages held and never mapped yet, those unmapped behind
        # the window, and the logical pages the row may ever write (its
        # reservation's length)
        self._mapped: dict[int, dict[int, int]] = {}
        self._fresh: dict[int, list[int]] = {}
        self._spare: dict[int, list[int]] = {}
        self._limit: dict[int, int] = {}
        self.recycled_total = 0
        self.peak_in_use = 0  # since the engine last reset it (stats' gauge)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def pages_needed(self, n_pages: int) -> int:
        """Window pages for a row of ``n_pages`` logical pages."""
        return min(int(n_pages), self.ring)

    def reserve(self, slot: int, n_pages: int) -> bool:
        """Hold ``pages_needed(n_pages)`` pages for ``slot``, none mapped yet
        (`advance` maps them). False, nothing taken, if they are not free."""
        assert slot not in self._mapped, slot
        want = self.pages_needed(n_pages)
        if want > len(self._free):
            return False
        self._fresh[slot] = [self._free.pop() for _ in range(want)]
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        self._spare[slot] = []
        self._mapped[slot] = {}
        self._limit[slot] = int(n_pages)
        return True

    def advance(self, slot: int, first_pos: int, last_pos: int) -> int:
        """Before a dispatch whose queries sit at ``first_pos .. last_pos``:
        map the pages of columns ``first_pos - window + 1 .. last_pos``
        (inside the row's reservation), unmapping those behind first. Returns
        the pages recycled: mapped again after having held other columns."""
        mapped = self._mapped.get(slot)
        if mapped is None:
            return 0
        fresh, spare, row = self._fresh[slot], self._spare[slot], self.tables[slot]
        lo = max(first_pos - self.window + 1, 0) // self.page_size
        hi = min(last_pos // self.page_size, self._limit[slot] - 1)
        for logical in [p for p in mapped if p < lo]:
            spare.append(mapped.pop(logical))
            row[logical] = self.oob
        recycled = 0
        for logical in range(lo, hi + 1):
            if logical in mapped:
                continue
            assert fresh or spare, (slot, first_pos, last_pos, self.ring)
            # a row's first pass over its pages is no recycle
            recycled += not fresh
            mapped[logical] = row[logical] = (fresh or spare).pop()
        self.recycled_total += recycled
        return recycled

    def slot_pages(self, slot: int) -> list[int]:
        return [
            *self._mapped.get(slot, {}).values(), *self._spare.get(slot, ()),
            *self._fresh.get(slot, ()),
        ]

    def free_slot(self, slot: int) -> list[int]:
        pages = self.slot_pages(slot)
        for held in (self._mapped, self._fresh, self._spare, self._limit):
            held.pop(slot, None)
        self.tables[slot, :] = self.oob
        self._free.extend(pages)
        return pages

    def validate(self, slot: int) -> bool:
        """The device-facing row against what the allocator says is mapped."""
        want = np.full(self.tables.shape[1], self.oob, np.int32)
        for logical, page in self._mapped.get(slot, {}).items():
            want[logical] = page
        return bool(np.array_equal(self.tables[slot], want))

    def reset(self) -> None:
        self.tables[:] = self.oob
        self._free = list(range(self.num_pages - 1, -1, -1))
        for held in (self._mapped, self._fresh, self._spare, self._limit):
            held.clear()


class PagePool:
    """Device page pool + free-list allocator + per-slot page tables."""

    def __init__(
        self,
        config: Any,
        num_pages: int,
        page_size: int,
        max_batch: int,
        max_seq_len: int,
        window_in_flight: int = 0,
    ) -> None:
        """``window_in_flight``: for a model with window layers, the most
        positions one dispatch writes a row (the widest prefill segment or
        decode chunk): it sizes the ring of their group, which holds
        ``max_batch`` of them (`window_group_pages`)."""

        if num_pages < 1 or page_size < 1:
            raise ValueError("page pool needs >= 1 page of >= 1 token")
        self.config = config
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_batch = int(max_batch)
        self.table_len = table_len_for(max_seq_len, page_size)
        self.oob = self.num_pages  # sentinel: scatters drop, gathers clamp
        # a model with recurrent layers: ``dev["rec"]``, one row of
        # recurrent state a SLOT beside the pages (models/transformer
        # `make_recurrent_state`). The row is the slot's index: `reserve`
        # hands it out with the pages and `free_slot` takes both back. No
        # dispatch zeroes a freed row: the next admission writes it whole
        # from the zero state (the admit group) or starts its first
        # segment from zero, so nothing of the old state is ever read
        # a model with an indexer: a third leaf a token beside K and V,
        # ``dev["ik"]`` [L, P, ps, index_key_width], its indexer's keys, under
        # the SAME table and page index: it is part of a page
        # (``bytes_per_page`` counts it), so nothing here tells it apart; a
        # model that keeps a latent has ``dev["lat"]`` [L, P, 1, ps,
        # latent_key_width] where the others have "k" and "v": pages all the
        # same, under the same table (with no indexer it is the pool's ONE leaf)
        # a model with window layers: their pages are a group of their own
        # (``dev["win"]``, `WindowPageGroup`), reserved with the full
        # group's in `reserve` and freed with them in `free_slot`
        self.window: Optional[WindowPageGroup] = None
        if config.has_window:
            pages, ring = window_group_pages(
                config, self.max_batch, max_seq_len, self.page_size, window_in_flight
            )
            self.window = WindowPageGroup(
                pages, self.page_size, self.max_batch, self.table_len,
                config.sliding_window, ring,
            )
        self._window_pages = self.window.num_pages if self.window else 0
        self.dev = self._make_dev()

        def nbytes(tree) -> int:
            return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

        self.state_bytes_total = nbytes(self.dev.get("rec"))
        self.state_bytes_per_row = self.state_bytes_total // self.max_batch
        # of which the convolution tails (all of it for a model of conv layers)
        self.conv_state_bytes_per_row = (
            nbytes((self.dev.get("rec") or {}).get("conv")) // self.max_batch
        )
        self.window_bytes_total = nbytes(self.dev.get("win"))
        self.window_bytes_per_page = self.window_bytes_total // max(1, self._window_pages)
        self.bytes_total = nbytes(self.dev) - self.state_bytes_total - self.window_bytes_total
        self.bytes_per_page = self.bytes_total // self.num_pages
        self.tables = np.full(
            (self.max_batch, self.table_len), self.oob, np.int32
        )
        self._refs = np.zeros(self.num_pages, np.int64)
        self._free = list(range(self.num_pages - 1, -1, -1))
        # authoritative per-slot page lists, logical order — the table array
        # above is the DEVICE-facing derivation; integrity checks compare
        # the two and page frees always go through this
        self._owned: dict[int, list[int]] = {}
        # cumulative reservation accounting: the alias-rate gauge is the
        # fraction of reserved pages satisfied by aliasing instead of fresh
        # allocation (live refcounts read 0 the moment a burst drains)
        self.reserved_pages_total = 0
        self.aliased_pages_total = 0

    def _make_dev(self):
        from langstream_tpu.models.transformer import make_page_pool

        return make_page_pool(
            self.config, self.num_pages, self.page_size, state_rows=self.max_batch,
            window_pages=self._window_pages,
        )

    def device_tables(self, full: np.ndarray) -> np.ndarray:
        """What a decode dispatch takes as its table, from the full group's
        rows as the caller masked them: those [B, Tp], or for a model with
        window layers both groups' [2, B, Tp] (models/transformer `FULL`,
        `WINDOW`), a row that is all sentinel masked in the window group
        too."""
        if self.window is None:
            return full
        masked = (full == self.oob).all(axis=1, keepdims=True)
        return np.stack([full, np.where(masked, self.window.oob, self.window.tables)])

    def rows_tables(self, slots) -> np.ndarray:
        """The table of an admit group or a segment: row j is slot
        ``slots[j]``'s, all sentinel where that is out of range (padding,
        a warm-up); [n, Tp], or both groups' [2, n, Tp]."""
        out = []
        for group in (self, self.window):
            if group is None:
                continue
            tables = np.full((len(slots), self.table_len), group.oob, np.int32)
            for j, s in enumerate(slots):
                if 0 <= s < self.max_batch:
                    tables[j] = group.tables[s]
            out.append(tables)
        return out[0] if self.window is None else np.stack(out)

    def window_advance(self, slot: int, first_pos: int, last_pos: int) -> int:
        """`WindowPageGroup.advance` before a dispatch whose queries of
        ``slot`` sit at ``first_pos .. last_pos``; 0 for a model without
        window layers."""
        if self.window is None:
            return 0
        return self.window.advance(slot, first_pos, last_pos)

    # -- sizing ---------------------------------------------------------------

    def pages_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case pages a request can write: positions [0, prompt +
        max_new), capped by the table (the host stops delivering at the
        cache end anyway). Reserved IN FULL at admission, so decode and
        verify dispatches never allocate — exhaustion can only defer an
        admission, never corrupt an in-flight slot."""
        tokens = min(prompt_len + max(1, max_new_tokens),
                     self.table_len * self.page_size)
        return min(self.table_len, math.ceil(tokens / self.page_size))

    # -- allocator ------------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def state_rows_in_use(self) -> int:
        """Rows of recurrent state bound to a slot (0 for a model without)."""
        return len(self._owned) if self.state_bytes_total else 0

    @property
    def shared_pages(self) -> int:
        return int(np.count_nonzero(self._refs > 1))

    def incref(self, pages) -> None:
        for p in pages:
            assert self._refs[p] > 0, p  # aliasing a free page is a bug
            self._refs[p] += 1

    def decref(self, pages) -> list[int]:
        """Drop one reference per page; pages reaching zero return to the
        free list. Returns the freed pages (quarantine zeroes them)."""
        freed = []
        for p in pages:
            assert self._refs[p] > 0, p
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
                freed.append(p)
        return freed

    def _alloc(self, n: int) -> Optional[list[int]]:
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def alloc_pages(self, n: int) -> Optional[list[int]]:
        """Allocate ``n`` pages with refcount 1 held by the CALLER (the
        restore path: the prefix index adopts them via
        ``attach_device_pages``, mirroring how ``insert`` holds one ref).
        None — nothing allocated — when the free list cannot cover it."""
        return self._alloc(n)

    # -- slot binding ---------------------------------------------------------

    def reserve(
        self, slot: int, n_pages: int, shared: tuple[int, ...] = (),
    ) -> Optional[int]:
        """Bind slot ``slot``'s table: ``shared`` aliased pages first
        (refcount bump — the zero-copy prefix hit), then freshly allocated
        pages up to ``n_pages`` total. Returns the first allocated page
        (the copy-on-write destination when the aliased prefix ends
        mid-page) or None — with the slot untouched — when the pool cannot
        cover the allocation."""
        assert slot not in self._owned, slot
        assert n_pages <= self.table_len
        want = n_pages - len(shared)
        assert want >= 0, (n_pages, len(shared))
        if self.window is not None and self.window.pages_needed(n_pages) > self.window.free_pages:
            return None
        fresh = self._alloc(want)
        if fresh is None:
            return None
        if self.window is not None:
            self.window.reserve(slot, n_pages)
        self.reserved_pages_total += n_pages
        self.aliased_pages_total += len(shared)
        self.incref(shared)
        owned = list(shared) + fresh
        self._owned[slot] = owned
        self.tables[slot, : len(owned)] = owned
        self.tables[slot, len(owned):] = self.oob
        return fresh[0] if fresh else -1

    def slot_pages(self, slot: int) -> list[int]:
        return list(self._owned.get(slot, ()))

    def free_slot(self, slot: int) -> list[int]:
        """Release the slot's pages (via the authoritative owned list, so a
        corrupted table row can never leak pages) and clear its table row.
        Returns the pages whose refcount hit zero."""
        owned = self._owned.pop(slot, None)
        self.tables[slot, :] = self.oob
        if self.window is not None:
            self.window.free_slot(slot)
        if not owned:
            return []
        return self.decref(owned)

    def validate(self, slot: int) -> bool:
        """Table-row integrity: the device-facing row must equal the
        authoritative owned list (+ sentinel padding). A mismatch means the
        table was corrupted (the ``page`` fault site, or a real bookkeeping
        bug) — dispatching it would read/write someone else's pages."""
        owned = self._owned.get(slot, ())
        row = self.tables[slot]
        n = len(owned)
        return bool(
            np.array_equal(row[:n], np.asarray(owned, np.int32))
            and np.all(row[n:] == self.oob)
            and (self.window is None or self.window.validate(slot))
        )

    def reset(self) -> None:
        """Crash recovery: rebuild the device pool and forget every binding
        (the engine fails the in-flight slots; prefix entries are reset by
        their index)."""
        self.dev = self._make_dev()
        if self.window is not None:
            self.window.reset()
        self.tables[:] = self.oob
        self._refs[:] = 0
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._owned.clear()


# -- host-RAM page tier (spill / hibernation arena) ---------------------------


class HostPageTier:
    """Host-RAM page arena mirroring the device pool's leaf structure:
    one numpy array per pool leaf with the page axis (axis 1) sized to
    ``num_pages`` host pages. int8 KV pools spill int8 + scales — half the
    bytes of a bf16 pool, exactly like the device side.

    Thread contract: the free list, checksum map and all alloc/free calls
    are ENGINE-THREAD-ONLY; ``write`` runs on the dedicated spill worker
    thread, but only ever against slots the engine allocated to an
    in-flight spill and will not read or reuse until the worker's done
    handle drains — so no two threads ever touch the same arena slot
    concurrently (the checksum map takes a small lock because the engine
    reads entries the worker wrote)."""

    # lock discipline registry (analysis pass `locks`): only the checksum
    # map crosses the engine/spill-worker boundary — everything else in
    # this class is engine-thread-only by the contract above.
    _GUARDED = {"_sum_lock": ("_sums",)}

    def __init__(self, dev_pool: Any, num_pages: int) -> None:
        if num_pages < 1:
            raise ValueError("host page tier needs >= 1 page")
        self.num_pages = int(num_pages)
        leaves = jax.tree.leaves(dev_pool)
        self._treedef = jax.tree.structure(dev_pool)
        # device leaf [L, P, Hkv, ps(, D)] → host arena [L, HP, Hkv, ps(, D)]
        self._arrays = [
            np.zeros((leaf.shape[0], self.num_pages) + tuple(leaf.shape[2:]),
                     leaf.dtype)
            for leaf in leaves
        ]
        self.bytes_per_page = sum(
            int(np.prod((a.shape[0],) + a.shape[2:])) * a.dtype.itemsize
            for a in self._arrays
        )
        self.bytes_total = self.bytes_per_page * self.num_pages
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._sums: dict[int, bytes] = {}
        self._sum_lock = threading.Lock()

    # -- allocator (engine thread) -------------------------------------------

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def slots_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def alloc(self, n: int) -> Optional[list[int]]:
        if n > len(self._free):
            return None
        return [self._free.pop() for _ in range(n)]

    def free(self, slots) -> None:
        for s in slots:
            self._free.append(int(s))
        with self._sum_lock:
            for s in slots:
                self._sums.pop(int(s), None)

    # -- page data ------------------------------------------------------------

    @staticmethod
    def _digest_blocks(blocks: list) -> bytes:
        # the module-level page_checksum: the migration wire stamps the
        # SAME digest, so arena pages ship with their stored sum
        return page_checksum(blocks)

    def _slot_blocks(self, slot: int) -> list:
        return [np.ascontiguousarray(a[:, slot]) for a in self._arrays]

    def write(self, slot: int, blocks: list) -> None:
        """Store one device page's leaf blocks ([L, Hkv, ps(, D)] each, in
        ``jax.tree.leaves`` order) into arena slot ``slot`` and stamp its
        checksum. Spill-worker-thread."""
        # hash the INCOMING blocks (already contiguous off device_get,
        # byte-identical to what lands in the arena once coerced to the
        # leaf dtype) — re-materializing the strided arena slot just to
        # feed the hash would double the worker's memory traffic per page
        blocks = [
            np.ascontiguousarray(b, dtype=a.dtype)
            for a, b in zip(self._arrays, blocks)
        ]
        for a, b in zip(self._arrays, blocks):
            a[:, slot] = b
        d = self._digest_blocks(blocks)
        with self._sum_lock:
            self._sums[slot] = d

    def read(self, slot: int) -> Optional[Any]:
        """Return arena slot ``slot`` as a pytree shaped like one device
        page (the restore program's upload operand), or None when the
        stored checksum no longer matches the bytes — a corrupted host
        page must degrade to a re-prefill, never to silently wrong KV."""
        with self._sum_lock:
            want = self._sums.get(slot)
        if want is None:
            return None
        # ONE contiguous materialization per leaf: the same buffers are
        # hashed AND returned — this runs inside the admission stall
        # window the engine_restore_s histogram polices, so the bytes
        # must not be copied twice
        blocks = self._slot_blocks(slot)
        if self._digest_blocks(blocks) != want:
            return None
        return jax.tree.unflatten(self._treedef, blocks)

    def checksum(self, slot: int) -> Optional[bytes]:
        """The digest stamped at spill time for arena slot ``slot`` (None
        when the slot holds no completed spill). The migration wire sends
        a hibernated page with THIS sum — recomputing would hash bytes
        that rot may already have touched, laundering the corruption."""
        with self._sum_lock:
            return self._sums.get(slot)

    def corrupt(self, slot: int) -> None:
        """Flip one byte of the slot's first leaf — the ``spill`` fault
        site's host-RAM-rot drill. The checksum verification in ``read``
        must catch it."""
        a = self._arrays[0]
        idx = (0, slot) + (0,) * (a.ndim - 2)
        one = np.array([a[idx]], a.dtype)
        one.view(np.uint8)[0] ^= 0xFF
        a[idx] = one[0]

    def reset(self) -> None:
        """Crash recovery: every arena slot is forgotten (the entries that
        referenced them are gone with the index reset)."""
        self._free = list(range(self.num_pages - 1, -1, -1))
        with self._sum_lock:
            self._sums.clear()


# -- prefix alias index -------------------------------------------------------


class _Node:
    """Radix-trie node, one level per bucket boundary (the same shape as
    serving/prefix_cache.py's trie — kept separate because the payload is a
    page list, not a pool row)."""

    __slots__ = ("parent", "edge", "children", "entry")

    def __init__(self, parent: Optional["_Node"] = None, edge: tuple = ()):
        self.parent = parent
        self.edge = edge
        self.children: dict[tuple, _Node] = {}
        self.entry: Optional[PrefixPages] = None


@dataclass
class PrefixPages:
    """One cached prefix: ``length`` tokens whose KV lives in ``pages``
    (refcounted in the pool; the LAST page is partial when length % ps).
    ``pins`` guards in-flight admissions reading the entry.

    Tiered KV: ``host`` holds the entry's arena slots once a spill
    completed (one per original device page, same order). The entry's
    tier is derived — device pages only = ``device``, both = ``both``,
    arena only (device half demoted under HBM pressure) = ``host``; a
    host-tier entry survives in the trie with ``pages == ()`` so a radix
    hit restores it instead of missing. ``spilling`` carries the
    in-flight spill handle (engine._Spill); ``dropped`` lets the spill
    completion drain detect an entry that was evicted/quarantined while
    its copy was in flight."""

    pages: tuple[int, ...]
    length: int
    pins: int = 0
    last_used: int = 0
    node: Any = field(default=None, repr=False)
    digest: str = ""  # prefix_digest(tokens[:length]) — beacon advertisement
    host: tuple[int, ...] = ()
    spilling: Any = field(default=None, repr=False)
    dropped: bool = False
    # wall clock of publish/last hit — the spill-idle-s hibernation gate
    last_used_t: float = 0.0

    @property
    def tier(self) -> str:
        if self.pages:
            return "both" if self.host else "device"
        return "host"


class PrefixPageIndex:
    """Radix-indexed prefix → pages map. Aliasing semantics that keep reuse
    EXACT: prefix KV is a pure function of the prefix tokens, and a page
    fully covered by a published prefix is never rewritten by its publisher
    (positions only grow), so an aliased page always equals what a fresh
    prefill would have written. The final partial page IS still written by
    the publisher (its own later tokens) — readers therefore COPY that one
    page (copy-on-write) and overwrite its tail with their own suffix; the
    columns below the published length are stable by the same
    positions-only-grow argument."""

    # lock discipline registry (analysis pass `locks`): the beacon
    # advertisement map is the one surface read from the /state thread.
    _GUARDED = {"_ad_lock": ("_ads",)}

    def __init__(self, boundaries: tuple[int, ...], max_entries: int = 512):
        self.boundaries = tuple(sorted({int(b) for b in boundaries if b > 0}))
        if not self.boundaries or max_entries < 1:
            raise ValueError("prefix index needs >= 1 boundary and >= 1 entry")
        self.max_entries = int(max_entries)
        self._root = _Node()
        self._live: list[PrefixPages] = []
        # distinct pages referenced by live entries (page → entry count):
        # maintained on the engine thread so the bytes-in-use gauge is one
        # len() read — stats() runs on metrics threads, which must never
        # iterate _live mid-mutation
        self._page_holds: dict[int, int] = {}
        # device-RESIDENT live entries (pages != ()): the insert cap's
        # denominator AND the victim-scan universe for device eviction /
        # quarantine, maintained incrementally — _live grows to arena
        # scale under hibernation and must not be walked per publish or
        # per admission-path eviction. (Host-side victim selection in
        # engine._evict_host_for still scans host-holding entries: that
        # cost is amortized against an actual arena eviction and bounded
        # to one failed attempt per idle-sweep tick.)
        self._dev_live: list[PrefixPages] = []
        self._tick = 0
        # beacon advertisement: digest → [length, recency tick], mutated on
        # the engine thread (insert/drop/hit) but READ from the runtime
        # HTTP server's /state thread — the one index surface that crosses
        # threads, hence the one lock in this module
        self._ads: dict[str, list] = {}
        self._ad_lock = threading.Lock()
        # host tier (set by the engine when spill is enabled): _drop frees
        # an entry's arena slots through this, so drop/evict/quarantine
        # paths can never leak host pages
        self.host_tier: Optional[HostPageTier] = None
        # stats (cumulative since engine start)
        self.lookups = 0
        self.hits = 0
        self.tokens_saved = 0
        self.evictions = 0
        self.copy_bytes_saved = 0
        # tiered-KV stats: demotions = device half dropped in favour of the
        # host copy (the entry stays restorable); host_evictions = a host
        # copy freed to make arena room (a host-only victim is gone for good)
        self.demotions = 0
        self.host_evictions = 0

    # -- trie (mirrors prefix_cache.PrefixCachePool) --------------------------

    def _walk(self, tokens, limit: int, create: bool = False) -> list[_Node]:
        path: list[_Node] = []
        node, prev = self._root, 0
        for b in self.boundaries:
            if b > limit:
                break
            seg = tuple(tokens[prev:b])
            child = node.children.get(seg)
            if child is None:
                if not create:
                    break
                child = _Node(parent=node, edge=seg)
                node.children[seg] = child
            path.append(child)
            node, prev = child, b
        return path

    @staticmethod
    def _subtree_entry(node: _Node) -> Optional[PrefixPages]:
        stack = list(node.children.values())
        while stack:
            n = stack.pop()
            if n.entry is not None:
                return n.entry
            stack.extend(n.children.values())
        return None

    def candidates(self, tokens) -> list[tuple[int, PrefixPages]]:
        """Usable ``(reuse_length, entry)`` pairs, ascending by length; at
        least one suffix token must remain to prefill. A deeper entry's
        leading pages serve a shorter boundary too (same prefix KV)."""
        out: list[tuple[int, PrefixPages]] = []
        path = self._walk(tokens, limit=len(tokens) - 1)
        depth = 0
        for node, b in zip(path, self.boundaries):
            if node.entry is not None:
                out.append((b, node.entry))
            depth = b
        if path and (not out or out[-1][0] < depth):
            sub = self._subtree_entry(path[-1])
            if sub is not None:
                out.append((depth, sub))
        return out

    def record_lookup(self, used: Optional[PrefixPages]) -> None:
        self.lookups += 1
        if used is not None:
            self.hits += 1
            self._tick += 1
            used.last_used = self._tick
            used.last_used_t = time.monotonic()
            if used.digest:
                with self._ad_lock:
                    ad = self._ads.get(used.digest)
                    if ad is not None:
                        ad[1] = self._tick

    def match_len(self, tokens) -> int:
        """Non-mutating probe: the longest cached prefix length usable for
        ``tokens`` (at least one suffix token must remain to prefill), or 0.
        Touches NEITHER the LRU recency ticks NOR the hit/lookup counters —
        the fleet router and the /state beacon probe constantly, and a probe
        that refreshed recency would pin whatever the router asks about,
        inverting the eviction order real admissions deserve."""
        cands = self.candidates(tokens)
        return cands[-1][0] if cands else 0

    def deepest_entry(self, tokens) -> Optional[tuple[int, "PrefixPages"]]:
        """Non-mutating: the deepest live, non-dropped entry usable for
        ``tokens`` as ``(length, entry)``, or None. The migration export
        serializes THIS entry's pages (serving/migrate.py); like
        ``match_len`` it must not touch LRU recency — probing a session
        for migration must not pin it."""
        for p, entry in reversed(self.candidates(tokens)):
            if not entry.dropped and (entry.pages or entry.host):
                return p, entry
        return None

    @staticmethod
    def entry_tokens(entry: "PrefixPages") -> list[int]:
        """Reconstruct the token prefix backing ``entry`` from its trie
        node's parent edges (an entry stores only its digest — the tokens
        live nowhere else once the request is gone). The durable tier's
        checkpoint begin frame carries these (serving/durable.py) so ANY
        replica can re-key the restored prefix into its own trie; the
        fleet beacon still ships digests only."""
        node = entry.node
        parts: list[tuple] = []
        while node is not None and node.edge:
            parts.append(node.edge)
            node = node.parent
        out: list[int] = []
        for seg in reversed(parts):
            out.extend(int(t) for t in seg)
        return out

    def advertised(self, top_k: int = 32) -> list[tuple[str, int, str]]:
        """Most-recently-used ``top_k`` prefix digests as ``(digest,
        length, tier)`` triples — the beacon's affinity advertisement.
        ``tier`` is ``device`` | ``both`` | ``host``: the fleet beacon
        advertises hibernated (host-tier) sessions alongside resident
        ones so sticky routing survives a spill, and the router scores
        them at a discount (a restore is cheaper than a re-prefill but
        not free). Thread-safe (the /state endpoint serves this from the
        HTTP thread)."""
        with self._ad_lock:
            items = sorted(
                self._ads.items(), key=lambda kv: kv[1][1], reverse=True
            )[: max(0, top_k)]
        return [(digest, ad[0], ad[2]) for digest, ad in items]

    def has(self, tokens, length: int) -> bool:
        path = self._walk(tokens, limit=length)
        return bool(path) and path[-1].entry is not None and (
            path[-1].entry.length == length
        )

    def publish_length(self, prompt_len: int) -> int:
        best = 0
        for b in self.boundaries:
            if b <= prompt_len:
                best = b
        return best

    # -- entries --------------------------------------------------------------

    def acquire(self, entry: PrefixPages) -> None:
        entry.pins += 1

    def release(self, entry: PrefixPages) -> None:
        assert entry.pins > 0
        entry.pins -= 1

    def insert(
        self, pool: PagePool, tokens, length: int, pages: tuple[int, ...],
    ) -> Optional[PrefixPages]:
        """Publish ``tokens[:length]`` as an alias of ``pages`` (the
        publishing slot's leading table entries): refcount bump only, no
        device copy. Over the entry cap, the LRU unpinned DEVICE-holding
        entry makes room (or the publish is skipped — never blocks). The
        cap bounds the device-resident working set only: hibernated
        entries each hold ≥1 exclusive arena slot, so the host tier's own
        free list is their ceiling — cap eviction must not drop a
        restorable session the arena was sized to keep."""
        assert length in self.boundaries, (length, self.boundaries)
        if len(self._dev_live) >= self.max_entries:
            if not self.evict_device_lru(pool):
                return None
        pool.incref(pages)
        node = self._walk(tokens, limit=length, create=True)[-1]
        self._tick += 1
        entry = PrefixPages(
            pages=tuple(pages), length=length, last_used=self._tick, node=node,
            digest=prefix_digest(tokens[:length]),
        )
        if node.entry is not None:
            # re-publish of the same prefix raced an eviction: keep newest
            self._drop(pool, node.entry)
        node.entry = entry
        entry.last_used_t = time.monotonic()
        self._live.append(entry)
        if entry.pages:
            self._dev_live.append(entry)
        for p in entry.pages:
            self._page_holds[p] = self._page_holds.get(p, 0) + 1
        # advertise AFTER the re-publish _drop above, which removed the
        # same digest (same tokens, same length)
        with self._ad_lock:
            self._ads[entry.digest] = [entry.length, entry.last_used, "device"]
        return entry

    def _note_tier(self, entry: PrefixPages) -> None:
        """Refresh the entry's advertised tier (spill completed, demotion,
        restore) so the fleet beacon's resident-vs-hibernated split tracks
        reality."""
        if entry.digest:
            with self._ad_lock:
                ad = self._ads.get(entry.digest)
                if ad is not None:
                    ad[2] = entry.tier

    def _drop(self, pool: PagePool, entry: PrefixPages) -> None:
        node = entry.node
        if node.entry is entry:
            node.entry = None
            while (
                node is not None
                and node.parent is not None
                and node.entry is None
                and not node.children
            ):
                parent = node.parent
                del parent.children[node.edge]
                node = parent
        self._live.remove(entry)
        if entry.pages:
            self._dev_live.remove(entry)
        for p in entry.pages:
            left = self._page_holds.get(p, 0) - 1
            if left > 0:
                self._page_holds[p] = left
            else:
                self._page_holds.pop(p, None)
        if entry.digest:
            with self._ad_lock:
                self._ads.pop(entry.digest, None)
        entry.dropped = True
        if entry.spilling is not None:
            # copy in flight: the worker owns the arena slots until its
            # done handle drains — the engine frees them there (freeing
            # now would let a new spill write the same slots concurrently)
            entry.spilling.cancelled = True
            entry.spilling = None
        elif entry.host and self.host_tier is not None:
            self.host_tier.free(entry.host)
        entry.host = ()
        pool.decref(entry.pages)
        # a dropped entry can survive in an admission's already-materialized
        # candidate list (evict_for mid-loop); stale .pages there would
        # alias pages the free list has re-issued to another slot
        entry.pages = ()

    def evict_lru(self, pool: PagePool) -> bool:
        """Evict the least-recently-used UNPINNED entry. False when every
        entry is pinned by an in-flight admission."""
        victims = [e for e in self._live if e.pins == 0]
        if not victims:
            return False
        self._drop(pool, min(victims, key=lambda e: e.last_used))
        self.evictions += 1
        return True

    def release_device_pages(
        self, pool: PagePool, entry: PrefixPages,
    ) -> list[int]:
        """Demote: drop the entry's DEVICE half only (decref + bytes-gauge
        bookkeeping), leaving the trie node, advertisement and host copy
        intact — the entry hibernates as ``host`` tier and a later radix
        hit restores it. Returns the pages whose refcount hit zero."""
        pages = entry.pages
        entry.pages = ()
        if pages:
            self._dev_live.remove(entry)
        for p in pages:
            left = self._page_holds.get(p, 0) - 1
            if left > 0:
                self._page_holds[p] = left
            else:
                self._page_holds.pop(p, None)
        self._note_tier(entry)
        return pool.decref(pages)

    def attach_device_pages(
        self, pool: PagePool, entry: PrefixPages, pages,
    ) -> None:
        """Restore: adopt freshly allocated (refcount-1) pages as the
        entry's device half — the inverse of ``release_device_pages``; the
        index now holds the one reference, exactly like ``insert``. The
        restore counts as a USE: without the recency bump a restored entry
        whose admission then page-defers (record_lookup never runs) would
        sit at the LRU minimum and be re-demoted by the next competing
        bind's evict_for — a restore/demote upload loop every engine
        iteration for as long as the pool stays full."""
        assert not entry.pages and not entry.dropped
        entry.pages = tuple(int(p) for p in pages)
        self._dev_live.append(entry)
        for p in entry.pages:
            self._page_holds[p] = self._page_holds.get(p, 0) + 1
        self._tick += 1
        entry.last_used = self._tick
        entry.last_used_t = time.monotonic()
        self._note_tier(entry)

    def evict_device_lru(
        self, pool: PagePool, spill_cb=None,
    ) -> bool:
        """Free DEVICE pages by victimizing the LRU unpinned entry that
        holds any: when ``spill_cb(entry)`` secures a host copy (already
        spilled, spill in flight, or one enqueued now) the entry DEMOTES —
        device half dropped, prefix still restorable — else it is dropped
        outright (the pre-tier behaviour). False when nothing holding
        device pages is evictable."""
        victims = [e for e in self._dev_live if e.pins == 0]
        if not victims:
            return False
        victim = min(victims, key=lambda e: e.last_used)
        # a victim whose host copy already exists (or is in flight) is
        # ALWAYS demoted, spill_cb or not: the publish-cap path used to
        # drop it outright, destroying a restorable hibernated session
        # the arena had already paid for on a mere cap event
        secured = bool(victim.host) or victim.spilling is not None
        if secured or (spill_cb is not None and spill_cb(victim)):
            self.release_device_pages(pool, victim)
            self.demotions += 1
        else:
            self._drop(pool, victim)
            self.evictions += 1
        return True

    def evict_for(
        self, pool: PagePool, need_pages: int, spill_cb=None,
    ) -> bool:
        """Free pool pages by demoting/evicting LRU entries until
        ``need_pages`` fit (or nothing evictable remains). Eviction only
        helps when it drops a page's LAST reference, so progress is
        re-checked per victim. With ``spill_cb`` set (tiered KV), victims
        demote to the host tier before dropping — the device pool becomes
        a cache over host RAM."""
        while pool.free_pages < need_pages:
            if not self.evict_device_lru(pool, spill_cb):
                return False
        return True

    def evict_touching(self, pool: PagePool, pages) -> int:
        """Evict every entry referencing any of ``pages`` — the quarantine
        path: a poisoned slot's published prefixes must not outlive it."""
        touched = set(pages)
        # only device-holding entries can reference device pages
        victims = [e for e in self._dev_live if touched.intersection(e.pages)]
        for e in victims:
            self._drop(pool, e)
            self.evictions += 1
        return len(victims)

    def reset(self) -> None:
        """Crash recovery (the pool itself was rebuilt — page refs are gone
        with it, so entries just vanish; counters are cumulative). Host
        copies vanish with their entries: the engine resets the arena
        right after (its spill worker is quiesced first), and marking the
        entries dropped here makes any straggler spill handle discard."""
        for e in self._live:
            e.dropped = True
            if e.spilling is not None:
                e.spilling.cancelled = True
                e.spilling = None
            e.host = ()
        self._root = _Node()
        self._live = []
        self._page_holds = {}
        self._dev_live = []
        with self._ad_lock:
            self._ads = {}
        self._tick = 0

    # -- stats ----------------------------------------------------------------

    @property
    def live_entries(self) -> int:
        return len(self._live)

    @property
    def pages_held(self) -> int:
        """Distinct pages live entries reference — a single len() read, safe
        from the metrics thread (GIL-atomic snapshot of a size)."""
        return len(self._page_holds)

    def hit_rate(self) -> float:
        return round(self.hits / self.lookups, 4) if self.lookups else 0.0

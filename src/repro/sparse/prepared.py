"""Zero-rebuild serving: the device-resident prepared-operand cache.

SpChar's finding is that sparse work is bound by memory latency and poor
reuse, not FLOPs — and the serving-path analogue one level up is host prep:
on repeat traffic the facade used to re-run container construction, the
symbolic phase, and device staging for every ``plan()`` call even when the
same (matrix, schedule) pair was served moments ago. ``PreparedStore`` is
the fix: a byte-budgeted LRU keyed by ``(content key, schedule, ...)``
whose values are finished device-resident products — prepared
``SparseTensor``s, staged spgemm/spadd symbolic products, stacked bucket
arrays — so a warm ``plan()`` is a hash plus a dict lookup.

Two key notions live here because they are what make the cache correct and
what make it pay off:

* ``content_key(csr)`` hashes the exact bytes of the matrix (structure AND
  values). The selector's ``fingerprint`` deliberately rounds features so
  near-identical matrices share a schedule; the prepared cache must do the
  opposite — a cached container embeds the values, so only byte-identical
  matrices may share an entry.
* ``bucket_edge(n)`` rounds container dimensions up to power-of-two-ish
  edges (1x and 1.5x powers of two). Cached operands only skip XLA
  retracing if their jit cache keys match, and the jit key is the leaf
  shapes + static meta — so prepared containers are padded up to bucket
  edges and differing matrices land on identical compiled executors
  (asserted via ``plan.trace_count``).
"""
from __future__ import annotations

import hashlib
import os
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from ..core.csr import CSR
from ..obs import default_registry, ordered, scoped_int
from ..obs import trace as obs_trace
from .resilience import (InjectedFault, atomic_write_json, checksum_entries,
                         fault_fired, load_json_guarded, note_recovery,
                         verify_entries)

# v3: per-entry generation counters (dynamic-sparsity reload safety,
# DESIGN.md §14); v2 added per-entry crc32 checksums + guarded load.
# Older index versions cold-start empty (the version check below).
STORE_INDEX_VERSION = 3

# Default device-byte budget of a store: enough for serving working sets,
# small enough that an unbounded stream of distinct matrices cannot pin
# device memory (the LRU evicts cold entries instead).
DEFAULT_BYTE_BUDGET = 256 << 20


def bucket_edge(n: int) -> int:
    """Smallest power-of-two-ish edge >= n: 1, 2, 3, 4, 6, 8, 12, 16, ...

    Two mantissa points per octave (1x and 1.5x each power of two) bounds
    padding waste at 50% worst-case / ~20% expected while collapsing the
    long tail of distinct container dimensions onto a handful of compile
    keys — the stable-padded-tile-shape argument of Gale et al. applied to
    jit cache keys.
    """
    n = max(int(n), 1)
    edge = 1
    while edge < n:
        if edge * 3 // 2 >= n and edge * 3 % 2 == 0:
            return edge * 3 // 2
        edge *= 2
    return edge


def content_key(csr: CSR) -> str:
    """Exact-bytes identity of a matrix for the prepared cache.

    Unlike ``selector.fingerprint`` (rounded features: many matrices, one
    schedule), this key must separate any two matrices whose prepared
    containers differ — structure or values — so it hashes the raw CSR
    arrays. O(nnz) but a single sha1 pass, orders of magnitude below the
    container build it lets a warm hit skip.

    Versioned mutable operands (``repro.sparse.mutate``, DESIGN.md §14)
    carry a ``version_key`` attribute of the form ``<base sha1>@g<gen>``:
    the identity is then ``(base_key, generation)`` — O(1) instead of a
    re-hash per lookup, and a mutated matrix can never alias its own
    pre-mutation cache entries because every delta bumps the generation.
    """
    vk = getattr(csr, "version_key", None)
    if vk is not None:
        return str(vk)
    h = hashlib.sha1()
    h.update(f"csr;{csr.shape[0]}x{csr.shape[1]};{csr.nnz};".encode())
    for arr in (csr.row_ptrs, csr.col_idxs, csr.nnz_vals):
        a = np.ascontiguousarray(arr)
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def raw_content_key(csr: CSR) -> str:
    """The exact-bytes sha1, ignoring any ``version_key`` — the *base* half
    of a versioned ``(base_key, generation)`` identity, computed once when a
    matrix is wrapped for mutation."""
    vk = getattr(csr, "version_key", None)
    if vk is None:
        return content_key(csr)
    try:
        delattr(csr, "version_key")
        return content_key(csr)
    finally:
        csr.version_key = vk


def split_version_key(token: str) -> Tuple[str, int]:
    """``(base, generation)`` of a content-key token: ``"<base>@g<N>"``
    splits, an unversioned key is generation 0 of itself."""
    if "@g" in token:
        base, _, gen = token.rpartition("@g")
        if gen.isdigit():
            return base, int(gen)
    return token, 0


def array_key(arr: np.ndarray) -> str:
    """Exact-bytes identity of one host array (moe routing tiles etc.)."""
    a = np.ascontiguousarray(arr)
    h = hashlib.sha1()
    h.update(f"arr;{a.shape};{a.dtype};".encode())
    h.update(a.tobytes())
    return h.hexdigest()


def entry_charges(value: Any, nbytes: Optional[int] = None) -> Dict[Any, int]:
    """Bytes a cached value holds on each device: every shard of a device
    array on its own device, so an operand sharded over chips is charged to
    each chip it lives on. Host leaves, and an explicit ``nbytes``, count
    against the default device; a value with no bytes still charges it 0,
    so the LRU can reach it."""
    default = jax.devices()[0]
    if nbytes is not None:
        return {default: int(nbytes)}
    charges: Dict[Any, int] = {default: 0}
    for leaf in jax.tree_util.tree_leaves(value):
        if isinstance(leaf, jax.Array):
            for piece in leaf.addressable_shards:
                charges[piece.device] = (charges.get(piece.device, 0)
                                         + int(piece.data.nbytes))
        else:
            charges[default] += int(getattr(leaf, "nbytes", 0))
    return charges


def _leaves_alive(value: Any) -> bool:
    """False if any device leaf was deleted out from under the cache (a jit
    consumer donated the cached buffers); such an entry must be served as a
    miss and rebuilt, never handed out with dead buffers."""
    for leaf in jax.tree_util.tree_leaves(value):
        is_deleted = getattr(leaf, "is_deleted", None)
        if is_deleted is not None and is_deleted():
            return False
    return True


def _key_version(key: Tuple) -> Dict:
    """``{"base": ..., "generation": ...}`` of a store key: the newest
    versioned content-key token found anywhere in the (nested) tuple, or
    generation 0 of the empty base when the key is unversioned."""
    base, gen = "", 0

    def _walk(t: Tuple) -> None:
        nonlocal base, gen
        for el in t:
            if isinstance(el, tuple):
                _walk(el)
            elif isinstance(el, str) and "@g" in el:
                b, g = split_version_key(el)
                if b != el and g >= gen:
                    base, gen = b, g

    _walk(key)
    return {"base": base, "generation": gen}


class PreparedStore:
    """Byte-budgeted LRU of finished prepared operands.

    Keys are tuples ``(kind, content_key(s)..., Schedule, prep kwargs)``;
    values are whatever the planner needs to skip host prep entirely — the
    store never interprets them beyond byte accounting. The budget is per
    device: an entry is charged to each chip it lives on
    (``entry_charges``), so one sharded over four chips takes a quarter of
    its bytes from each chip's budget. Entries larger than a chip's budget
    are rejected (counted, not raised): a single huge matrix must not
    flush the working set that is getting hits.

    Donation safety: cached values are returned by reference, and the
    facade's executors never donate operand buffers. A jit consumer that
    *does* donate cached leaves deletes the underlying device buffers —
    ``get`` checks leaf liveness on every hit and serves such an entry as
    a miss (dropped + counted in ``invalidated``) so the caller rebuilds
    instead of crashing on dead arrays (tests/test_serving_path.py pins
    this).
    """

    # counters are views into this store's MetricsRegistry scope
    # (DESIGN.md §12): telemetry() and registry snapshots agree by
    # construction, and increments are lock-protected for threaded callers
    bytes_in_use = scoped_int("bytes_in_use")
    hits = scoped_int("hits")
    misses = scoped_int("misses")
    puts = scoped_int("puts")
    evictions = scoped_int("evictions")
    rejected = scoped_int("rejected")
    invalidated = scoped_int("invalidated")
    fault_evictions = scoped_int("fault_evictions")
    save_failures = scoped_int("save_failures")
    corrupt_loads = scoped_int("corrupt_loads")
    mutation_rekeys = scoped_int("mutation_rekeys")
    mutation_invalidated = scoped_int("mutation_invalidated")
    stale_drops = scoped_int("stale_drops")

    def __init__(self, byte_budget: int = DEFAULT_BYTE_BUDGET) -> None:
        self._metrics = default_registry().scope("prepared_store")
        self.byte_budget = int(byte_budget)
        self._entries: "OrderedDict[Tuple, Tuple[Any, int]]" = OrderedDict()
        # per entry, its bytes on each device; and each device's total
        self._charges: Dict[Tuple, Dict[Any, int]] = {}
        self._device_bytes: Dict[Any, int] = {}

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: Tuple) -> bool:
        return key in self._entries

    def get(self, key: Tuple) -> Optional[Any]:
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            return None
        if fault_fired("store-evict", str(key)):
            # injected fault: lose the entry, recover by serving a miss —
            # the caller rebuilds exactly as after a real eviction
            self._drop(key)
            self.fault_evictions += 1
            self.misses += 1
            note_recovery("store-evict")
            obs_trace.emit("store_evict", "fault", reason="fault",
                           nbytes=entry[1])
            return None
        if not _leaves_alive(entry[0]):
            # a consumer donated the cached buffers — drop the entry and
            # serve a miss so the caller rebuilds instead of crashing on
            # deleted device arrays
            self._drop(key)
            self.invalidated += 1
            self.misses += 1
            obs_trace.emit("store_evict", "donated", reason="donated",
                           nbytes=entry[1])
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return entry[0]

    def _drop(self, key: Tuple) -> Tuple[Any, int]:
        """Remove ``key``'s entry and its charges; its (value, bytes)."""
        value, nb = self._entries.pop(key)
        for dev, b in self._charges.pop(key).items():
            self._device_bytes[dev] -= b
        self.bytes_in_use -= nb
        return value, nb

    def device_bytes(self) -> Dict[Any, int]:
        """Bytes the store holds on each device."""
        return {d: b for d, b in self._device_bytes.items() if b}

    def _over(self) -> set:
        return {d for d, b in self._device_bytes.items()
                if b > self.byte_budget}

    def put(self, key: Tuple, value: Any,
            nbytes: Optional[int] = None) -> bool:
        charges = entry_charges(value, nbytes)
        if max(charges.values()) > self.byte_budget:
            self.rejected += 1
            return False
        if key in self._entries:
            self._drop(key)
        nb = sum(charges.values())
        self._entries[key] = (value, nb)
        self._charges[key] = charges
        for dev, b in charges.items():
            self._device_bytes[dev] = self._device_bytes.get(dev, 0) + b
        self.bytes_in_use += nb
        self.puts += 1
        over = self._over()
        while over and len(self._entries) > 1:
            # the least recently used entry on a chip past its budget
            victim = next(k for k in self._entries
                          if over & self._charges[k].keys())
            _, freed = self._drop(victim)
            self.evictions += 1
            obs_trace.emit("store_evict", "lru", reason="lru", nbytes=freed)
            over = self._over()
        # a lone over-budget survivor cannot happen (rejected above), but an
        # exactly-at-budget single entry is fine — loop guard keeps >= 1.
        return True

    def resident(self, content_key: str) -> bool:
        """True when any cached entry's key references this exact-bytes
        content key — i.e. some prepared product of that matrix (a
        container, a staged symbolic product, a stacked bucket array) is
        device-resident right now. The serving engine's slot-based
        admission (DESIGN.md §13) keys slots on this: a tenant whose
        operands are resident drains without paying host prep, so resident
        slots are preferred drain targets. O(entries × key width) per
        probe, both bounded by the byte budget."""

        def _walk(t: Tuple) -> bool:
            for el in t:
                if isinstance(el, tuple):
                    if _walk(el):
                        return True
                elif el == content_key:
                    return True
            return False

        return any(_walk(k) for k in self._entries)

    def pop_matching(self, content_keys) -> list:
        """Remove and return every ``(key, value)`` whose key tuple
        references any of ``content_keys`` — the sub-matrix-granularity
        invalidation primitive of the mutation path (DESIGN.md §14).

        ``repro.sparse.mutate`` calls this with a mutated operand's old
        version key: single-container entries get the delta applied in
        place and are re-inserted under the new generation (counted
        ``mutation_rekeys`` by the caller via ``note_rekeyed``); derived
        products embedding copied values — stacked buckets, spgemm/spadd
        staged products, row partitions — are dropped for lazy rebuild
        (``mutation_invalidated``). Entries for *other* matrices are never
        touched: siblings stay resident.
        """
        cks = set(content_keys)

        def _refs(t: Tuple) -> bool:
            for el in t:
                if isinstance(el, tuple):
                    if _refs(el):
                        return True
                elif el in cks:
                    return True
            return False

        matched = [k for k in self._entries if _refs(k)]
        return [(k, self._drop(k)[0]) for k in matched]

    @staticmethod
    def rewrite_key(key: Tuple, old_ck: str, new_ck: str) -> Tuple:
        """The same key tuple with every occurrence of ``old_ck`` replaced
        by ``new_ck`` (nested tuples included) — how a rekeyed entry moves
        to the next generation without re-deriving its prep kwargs."""

        def _rw(t):
            return tuple(_rw(el) if isinstance(el, tuple)
                         else (new_ck if el == old_ck else el) for el in t)

        return _rw(key)

    def get_or_build(self, key: Optional[Tuple],
                     builder: Callable[[], Any]) -> Any:
        """Cached value for ``key``, building (and inserting) on a miss.
        ``key=None`` bypasses the store entirely (uncacheable operand)."""
        if key is None:
            return builder()
        value = self.get(key)
        if value is None:
            value = builder()
            self.put(key, value)
        return value

    def clear(self) -> None:
        self._entries.clear()
        self._charges.clear()
        self._device_bytes.clear()
        self.bytes_in_use = 0

    # -------------------------------------------------- cross-run persistence
    # Only the *index* (key reprs + byte sizes, LRU order) and the telemetry
    # counters persist — never the device buffers. The cached values are
    # live jax.Array handles whose backing memory is process- and
    # device-local: serializing them would mean a full host round-trip of
    # the working set, and a reloaded copy would still have to be
    # re-uploaded and re-validated against a fresh jit cache — i.e. exactly
    # the cold rebuild the store already performs on a miss. What a serving
    # restart actually needs is context: what the prior process's hit rate
    # was and how big its working set ran, which is what save()/load() carry
    # (the ScheduleCache JSON pattern: atomic tmp+rename, versioned format).

    def save(self, path: str) -> bool:
        """Persist the store's index + telemetry as JSON: checksummed
        entries, unique temp file + fsync + ``os.replace`` — a crash (or
        injected cache-write fault) mid-save leaves the previous index
        intact. Returns False (and counts) instead of raising on failure:
        losing an index snapshot must never take the serving loop down."""
        payload = {
            "version": STORE_INDEX_VERSION,
            "telemetry": self.telemetry(),
            "entries": checksum_entries(
                [dict({"key": repr(k), "nbytes": nb},
                      **_key_version(k))
                 for k, (_, nb) in self._entries.items()]),
        }
        try:
            atomic_write_json(path, payload)
        except (RuntimeError, OSError) as e:
            self.save_failures += 1
            if isinstance(e, InjectedFault):
                note_recovery(e.site)
            return False
        return True

    def load(self, path: str) -> Dict:
        """Load a prior run's index + telemetry for reporting context.

        Device buffers are not (and cannot usefully be) restored — entries
        rebuild lazily on first touch. The prior counters surface in
        ``telemetry()`` under ``prior_*`` so a restarted server can report
        its steady-state hit-rate expectation before the new process has
        warmed up. A missing, stale-format, truncated, or bit-flipped file
        loads as empty-or-partial context (corrupt entries skipped and
        counted) — cold start from empty, never a crash.
        """
        self.prior: Dict = {}
        payload = load_json_guarded(path)
        if payload is None:
            if os.path.exists(path):
                self.corrupt_loads += 1
            return self.prior
        if payload.get("version") != STORE_INDEX_VERSION:
            return self.prior
        raw = payload.get("entries", [])
        entries, corrupt = verify_entries(raw if isinstance(raw, list) else [])
        self.corrupt_loads += corrupt
        # Dynamic-sparsity reload safety (DESIGN.md §14): an index written
        # mid-mutation can list several generations of one base matrix.
        # Only the newest generation per base survives the reload — a
        # pre-mutation entry must never be reported (or re-warmed) as if
        # it were current.
        newest: Dict[str, int] = {}
        for e in entries:
            base = e.get("base", "")
            if base:
                gen = int(e.get("generation", 0))
                newest[base] = max(newest.get(base, 0), gen)
        kept = []
        for e in entries:
            base = e.get("base", "")
            if base and int(e.get("generation", 0)) < newest[base]:
                self.stale_drops += 1
            else:
                kept.append(e)
        entries = kept
        tel = payload.get("telemetry", {})
        self.prior = {"telemetry": tel if isinstance(tel, dict) else {},
                      "entries": entries}
        return self.prior

    def telemetry(self) -> Dict[str, float]:
        lookups = self.hits + self.misses
        out = {
            "entries": float(len(self._entries)),
            "bytes_in_use": float(self.bytes_in_use),
            "byte_budget": float(self.byte_budget),
            "hits": float(self.hits),
            "misses": float(self.misses),
            "puts": float(self.puts),
            "evictions": float(self.evictions),
            "rejected": float(self.rejected),
            "invalidated": float(self.invalidated),
            "fault_evictions": float(self.fault_evictions),
            "save_failures": float(self.save_failures),
            "corrupt_loads": float(self.corrupt_loads),
            "mutation_rekeys": float(self.mutation_rekeys),
            "mutation_invalidated": float(self.mutation_invalidated),
            "stale_drops": float(self.stale_drops),
            "hit_rate": self.hits / lookups if lookups else 0.0,
            # eviction pressure (DESIGN.md §13): fraction of inserts the
            # LRU had to pay for by dropping a colder entry — ~0 while the
            # working set fits the byte budget, ->1 as a multi-tenant
            # population thrashes it. The serving bench reports this next
            # to latency/SLO so byte-budget tuning under real traffic has
            # its measurement.
            "eviction_pressure": self.evictions / max(self.puts, 1),
        }
        prior = getattr(self, "prior", None)
        if prior:
            ptel = prior.get("telemetry", {})
            out["prior_entries"] = float(len(prior.get("entries", [])))
            out["prior_hit_rate"] = float(ptel.get("hit_rate", 0.0))
            out["prior_bytes_in_use"] = float(ptel.get("bytes_in_use", 0.0))
        return ordered(out)

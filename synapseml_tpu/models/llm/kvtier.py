"""Session survivability plane: host-tier KV spill + crash journal.

A conversation served through the :class:`~synapseml_tpu.models.llm
.slots.SlotEngine` lives in exactly one slot row of one replica's HBM.
That is three single points of loss: the slot is LRU-reclaimed (the
prefix cache dies), the replica is preempted (every in-flight session
dies), or the process is SIGKILLed mid-decode (the committed tokens the
client never received die with it).  This module is the host-side tier
that makes all three survivable, with one invariant everywhere: **a
degraded path falls back to cold prefill — it never produces a wrong
token.**

Three pieces, deliberately jax-free (the serving loop imports this
module directly):

- :class:`RadixPrefixIndex` — a compressed radix trie over token-id
  sequences.  Replaces the slot engine's single-hash candidate probe:
  ``longest_prefix`` returns the true longest common prefix against
  ANY indexed sequence (matching is exact by construction — there is
  no hash to collide), so both the device-resident slot prefixes and
  the host arena entries are searched with one structure.
- :class:`HostKVArena` — a byte-budgeted host-RAM LRU of spilled K/V
  spans.  Entries store the cache-NATIVE bytes (a bf16 cache spills as
  uint16 bit patterns — the :mod:`~synapseml_tpu.io.colstore`
  bit-pattern layout, half the f32 footprint; an f32 test cache spills
  as f32, because rounding it through bf16 would break the token-exact
  restore pin) plus a CRC32 per entry.  A checksum mismatch at fetch
  drops the entry and reports ``corrupt`` — the engine cold-prefills.
  Arena pressure drops LRU tails; an entry that cannot fit is counted
  and discarded, never stored torn.
- :class:`SessionJournal` — an append-only, fsync'd, per-session log
  of ``prompt + committed token ids``.  Records are CRC-framed lines;
  a torn tail (the SIGKILL case) fails its CRC and replay truncates to
  the last valid record.  State rewrites (``begin`` / ``compact``) go
  through the ``telemetry.artifact`` tmp+fsync+rename idiom, so a kill
  mid-compaction leaves the previous state intact.  A per-session byte
  cap triggers compaction at the append site (the ``_retired_window``
  prune-at-append pattern) and, as a last resort, oldest-token
  truncation — a truncated state is MARKED, because replaying a suffix
  is not token-exact and the caller must cold-start instead.

Fault sites (:mod:`~synapseml_tpu.resilience.faults`): every spill
walks ``kvtier.spill``, every fetch ``kvtier.restore``, every journal
append ``kvtier.journal_append`` — arm ``kill`` for hard-death tests or
the ``corrupt`` kind for deterministic bit-rot.

See docs/api/serving.md "Session survivability & KV tiering".
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import zlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ...resilience.faults import get_faults
from ...telemetry import get_registry
from ...telemetry.flight import record as flight_record

__all__ = ["ChecksumError", "HostKVArena", "KVTIER_METRICS",
           "KVTransfer", "RadixPrefixIndex", "SessionJournal",
           "SessionState", "TRANSFER_MAGIC", "kvtier_metrics",
           "pack_kv_transfer", "token_prefix_hash", "unpack_kv_transfer"]

#: every metric this plane registers — the docs-hygiene sweep holds
#: these to the GANG_METRICS bar (each name must appear in
#: docs/api/serving.md, counters end ``_total``, histograms carry a
#: unit suffix)
KVTIER_METRICS = (
    "kvtier_spills_total",
    "kvtier_restores_total",
    "kvtier_arena_bytes",
    "kvtier_arena_evictions_total",
    "kvtier_admit_latency_seconds",
)


class ChecksumError(RuntimeError):
    """A spilled entry's stored CRC no longer matches its bytes —
    bit-rot (or an armed ``corrupt`` fault).  The entry is dropped and
    the caller cold-prefills; wrong K/V is never restored."""


@dataclasses.dataclass
class _KVTierMetrics:
    spills: Any
    restores: Any
    arena_bytes: Any
    arena_evictions: Any
    admit_latency: Any


def kvtier_metrics() -> _KVTierMetrics:
    """Get-or-create the plane's metric handles (the registry
    deduplicates by name, so every arena/engine/loop shares one set)."""
    reg = get_registry()
    return _KVTierMetrics(
        spills=reg.counter(
            "kvtier_spills_total",
            "K/V spans spilled to the host arena", ("engine", "kind")),
        restores=reg.counter(
            "kvtier_restores_total",
            "warm-restore attempts by source (host arena / session "
            "journal) and outcome (ok, corrupt, miss, truncated — "
            "every non-ok outcome fell back to cold prefill)",
            ("engine", "source", "outcome")),
        arena_bytes=reg.gauge(
            "kvtier_arena_bytes",
            "bytes resident in the host KV arena", ("engine",)),
        arena_evictions=reg.counter(
            "kvtier_arena_evictions_total",
            "arena entries dropped (pressure = LRU tail under the byte "
            "budget, superseded = covered by a longer spill, corrupt = "
            "failed its checksum at fetch)", ("engine", "reason")),
        admit_latency=reg.histogram(
            "kvtier_admit_latency_seconds",
            "slot-admission latency by path (restore = host-arena span "
            "restored, cold = full prefill) — the restore-vs-cold "
            "comparison surface", ("engine", "path"),
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 2.5, 5.0)),
    )


# ---------------------------------------------------------------------------
# Radix prefix index
# ---------------------------------------------------------------------------

class _RadixNode:
    __slots__ = ("edges", "refs")

    def __init__(self):
        #: first token -> (label tuple, child node); labels are
        #: compressed runs, split lazily on divergence
        self.edges: Dict[int, Tuple[Tuple[int, ...], "_RadixNode"]] = {}
        #: refs whose registered sequence passes through this node
        #: (i.e. shares the root→node path as a prefix)
        self.refs: set = set()


class RadixPrefixIndex:
    """Longest-common-prefix index over token-id sequences.

    ``insert(ids, ref)`` registers a sequence under an opaque hashable
    ref (a slot number, an arena entry key); re-inserting a ref
    replaces its sequence.  ``longest_prefix(query)`` returns
    ``(ref, lcp)`` — a ref whose registered sequence shares the longest
    prefix with the query, and that length.  Matching is exact by
    construction (the trie compares tokens, not hashes), so unlike the
    old single-hash candidate probe there is nothing to verify and no
    first-k-tokens blind spot: two sequences diverging inside the old
    hash window still share whatever true prefix they share.

    Not thread-safe; callers lock (the arena does, the engine is
    single-threaded by contract).
    """

    def __init__(self):
        self._root = _RadixNode()
        self._paths: Dict[Any, Tuple[int, ...]] = {}

    def __len__(self) -> int:
        return len(self._paths)

    def insert(self, ids, ref) -> None:
        seq = tuple(int(t) for t in ids)
        if self._paths.get(ref) == seq:
            return
        if ref in self._paths:
            self.remove(ref)
        self._paths[ref] = seq
        node = self._root
        node.refs.add(ref)
        i = 0
        while i < len(seq):
            edge = node.edges.get(seq[i])
            if edge is None:
                child = _RadixNode()
                child.refs.add(ref)
                node.edges[seq[i]] = (seq[i:], child)
                return
            label, child = edge
            m = _match_len(label, seq, i)
            if m == len(label):
                child.refs.add(ref)
                node, i = child, i + m
                continue
            # diverged (or exhausted) mid-edge: split it at m
            mid = _RadixNode()
            mid.refs = set(child.refs)
            mid.refs.add(ref)
            mid.edges[label[m]] = (label[m:], child)
            node.edges[seq[i]] = (label[:m], mid)
            if i + m < len(seq):
                tail = _RadixNode()
                tail.refs.add(ref)
                mid.edges[seq[i + m]] = (seq[i + m:], tail)
            node = mid
            return

    def remove(self, ref) -> None:
        seq = self._paths.pop(ref, None)
        if seq is None:
            return
        node = self._root
        node.refs.discard(ref)
        i = 0
        while i < len(seq):
            edge = node.edges.get(seq[i])
            if edge is None:
                return                      # defensive: path already gone
            label, child = edge
            child.refs.discard(ref)
            if not child.refs:
                del node.edges[seq[i]]
                return
            node, i = child, i + len(label)

    def clear(self) -> None:
        self._root = _RadixNode()
        self._paths.clear()

    def longest_prefix(self, ids, prefer=None) -> Tuple[Optional[Any], int]:
        """Deepest match for ``ids``: ``(ref, lcp)``, or ``(None, 0)``
        when nothing is indexed.  Ties at the deepest node prefer
        ``prefer`` when it is among the candidates (the engine's
        in-place multi-turn resume), else the smallest ref
        (deterministic)."""
        node, depth, i = self._root, 0, 0
        while i < len(ids):
            edge = node.edges.get(int(ids[i]))
            if edge is None:
                break
            label, child = edge
            m = _match_len(label, ids, i)
            depth += m
            node = child
            if m < len(label):
                break                      # partial edge: child's refs all
                #                            share exactly `depth` tokens
            i += m
        if not node.refs or depth == 0:
            return None, 0
        if prefer is not None and prefer in node.refs:
            return prefer, depth
        return min(node.refs, key=_ref_order), depth


def _match_len(label: Tuple[int, ...], seq, start: int) -> int:
    n = min(len(label), len(seq) - start)
    m = 0
    while m < n and label[m] == int(seq[start + m]):
        m += 1
    return m


def _ref_order(ref):
    return (str(type(ref)), repr(ref))


# ---------------------------------------------------------------------------
# Host KV arena
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _ArenaEntry:
    key: int
    ids: np.ndarray                 # (span,) int32 — the tokens the K/V covers
    blob: bytes                     # packed K/V bytes (cache-native layout)
    crc: int
    shape: Tuple[int, ...]          # (layers, 2, span, kv_heads, d_head)
    dtype_name: str
    packed_bf16: bool               # stored as uint16 bit patterns
    nbytes: int
    tenant: str = "default"         # namespace: lookups never cross tenants


class HostKVArena:
    """Byte-budgeted host-RAM LRU of spilled K/V spans, radix-indexed
    by token ids (see module docstring).  Thread-safe: the decode loop
    spills from its own thread while tests probe from another.

    ``put`` accepts per-layer ``{"k", "v"}`` rows of shape
    ``(span, kv_heads, d_head)`` in the cache's native dtype and packs
    them into one contiguous blob; bf16 arrays are stored as their
    uint16 bit patterns (the colstore layout — lossless, half the f32
    width).  ``fetch`` verifies the CRC and returns rows sliced to the
    requested length, raising :class:`ChecksumError` (entry dropped)
    on mismatch and :class:`KeyError` on a miss — the engine maps both
    to a counted cold-prefill fallback.
    """

    def __init__(self, max_bytes: int = 256 * 1024 * 1024,
                 name: str = "llm"):
        self.max_bytes = int(max_bytes)
        self.name = name
        self._lock = threading.Lock()
        self._entries: "OrderedDict[int, _ArenaEntry]" = OrderedDict()
        #: one radix index PER TENANT — a lookup can only ever match a
        #: span the same tenant spilled, so a cross-tenant session-id
        #: (or prompt-prefix) collision cannot leak another tenant's
        #: K/V bytes through the restore path
        self._radices: Dict[str, RadixPrefixIndex] = {}
        self._next_key = 0
        self._bytes = 0
        self._m = kvtier_metrics()
        self._m.arena_bytes.set(0, engine=self.name)

    # -- introspection -----------------------------------------------------
    @property
    def bytes_resident(self) -> int:
        with self._lock:
            return self._bytes

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def _radix_for(self, tenant: str) -> RadixPrefixIndex:
        # caller holds the lock
        idx = self._radices.get(tenant)
        if idx is None:
            idx = self._radices[tenant] = RadixPrefixIndex()
        return idx

    # -- spill -------------------------------------------------------------
    def put(self, ids, rows: List[Dict[str, np.ndarray]],
            kind: str = "retire", tenant: str = "default") -> Optional[int]:
        """Spill one K/V span into ``tenant``'s namespace.  Returns the
        entry key, or None when the entry was refused (over-budget even
        alone, or an exact/shorter duplicate of what the same tenant
        already has resident)."""
        ids = np.asarray(ids, np.int32).reshape(-1)
        if len(ids) == 0 or not rows:
            return None
        faults = get_faults()
        stacked = np.stack(
            [np.stack([np.asarray(r["k"]), np.asarray(r["v"])])
             for r in rows])            # (L, 2, span, KH, DH), native dtype
        blob, packed_bf16, dtype_name = _pack(stacked)
        crc = zlib.crc32(blob)
        # the fault site sits BETWEEN checksum and store: an armed
        # ``corrupt`` rule flips a stored byte and the mismatch is
        # caught at fetch — exactly silent bit-rot; ``kill`` dies here
        blob = faults.corrupt_point("kvtier.spill", blob, tenant=tenant)
        entry = _ArenaEntry(0, ids, blob, crc, stacked.shape, dtype_name,
                            packed_bf16, len(blob) + ids.nbytes,
                            tenant=str(tenant))
        with self._lock:
            if entry.nbytes > self.max_bytes:
                self._m.arena_evictions.inc(1, engine=self.name,
                                            reason="pressure")
                return None
            # a resident entry this one extends (or duplicates) is
            # superseded: its tokens are a prefix of ours, so every
            # lookup it could win, we win at least as long — scoped to
            # THIS tenant's index (another tenant's identical tokens
            # are a different namespace, never deduplicated across)
            radix = self._radix_for(entry.tenant)
            old_key, lcp = radix.longest_prefix(ids)
            if old_key is not None:
                old = self._entries.get(old_key)
                if old is not None and lcp == len(old.ids):
                    if len(old.ids) == len(ids):
                        self._entries.move_to_end(old_key)
                        return None       # exact duplicate: refresh LRU
                    self._drop(old_key, "superseded")
            entry.key = self._next_key
            self._next_key += 1
            self._entries[entry.key] = entry
            self._bytes += entry.nbytes
            # re-fetch: _drop prunes a tenant's radix from the map when
            # it empties, so the supersede path may have orphaned the
            # local reference — inserting into it would strand the entry
            self._radix_for(entry.tenant).insert(ids, entry.key)
            while self._bytes > self.max_bytes and len(self._entries) > 1:
                tail_key = next(iter(self._entries))
                if tail_key == entry.key:
                    break
                self._drop(tail_key, "pressure")
            self._m.arena_bytes.set(self._bytes, engine=self.name)
        self._m.spills.inc(1, engine=self.name, kind=kind)
        flight_record("kvtier_spill", engine=self.name, spill_kind=kind,
                      tenant=entry.tenant, tokens=int(len(ids)),
                      bytes=entry.nbytes)
        return entry.key

    def _drop(self, key: int, reason: str) -> None:
        # caller holds the lock
        entry = self._entries.pop(key, None)
        if entry is None:
            return
        self._bytes -= entry.nbytes
        radix = self._radices.get(entry.tenant)
        if radix is not None:
            radix.remove(key)
            if not len(radix):
                del self._radices[entry.tenant]
        self._m.arena_evictions.inc(1, engine=self.name, reason=reason)
        self._m.arena_bytes.set(self._bytes, engine=self.name)

    # -- restore -----------------------------------------------------------
    def longest_prefix(self, ids,
                       tenant: str = "default") -> Tuple[Optional[int], int]:
        with self._lock:
            radix = self._radices.get(str(tenant))
            if radix is None:
                return None, 0
            key, lcp = radix.longest_prefix(ids)
            if key is not None:
                self._entries.move_to_end(key)
            return key, lcp

    def fetch(self, key: int, length: int,
              tenant: str = "default") -> List[Dict[str, np.ndarray]]:
        """K/V rows ``[0, length)`` of entry ``key`` as per-layer
        ``{"k", "v"}`` arrays in the cache-native dtype.  Raises
        ``KeyError`` (miss — dropped under pressure since the probe, OR
        a key from another tenant's namespace: a leaked key must read
        as a miss, never as data) or :class:`ChecksumError` (corrupt;
        the entry is removed)."""
        get_faults().kill_point("kvtier.restore", tenant=tenant)
        with self._lock:
            entry = self._entries.get(key)
            if entry is None or entry.tenant != str(tenant):
                raise KeyError(key)
            if zlib.crc32(entry.blob) != entry.crc:
                self._drop(key, "corrupt")
                raise ChecksumError(
                    f"arena entry {key} failed its checksum "
                    f"({len(entry.blob)} bytes, {len(entry.ids)} tokens)")
            self._entries.move_to_end(key)
            stacked = _unpack(entry.blob, entry.shape, entry.dtype_name,
                              entry.packed_bf16)
        length = int(length)
        return [{"k": stacked[layer, 0, :length],
                 "v": stacked[layer, 1, :length]}
                for layer in range(stacked.shape[0])]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._radices.clear()
            self._bytes = 0
            self._m.arena_bytes.set(0, engine=self.name)


def _pack(arr: np.ndarray) -> Tuple[bytes, bool, str]:
    """Cache-native serialization: bf16 arrays ship as their uint16 bit
    patterns (the colstore layout — bit-lossless at 2 B/elem, half the
    f32 master width); every other dtype ships raw.  NEVER rounds an
    f32 cache through bf16 — that would break the token-exact pin."""
    name = arr.dtype.name if hasattr(arr.dtype, "name") else str(arr.dtype)
    if name == "bfloat16":
        return np.ascontiguousarray(arr).view(np.uint16).tobytes(), \
            True, name
    return np.ascontiguousarray(arr).tobytes(), False, name


def _unpack(blob: bytes, shape: Tuple[int, ...], dtype_name: str,
            packed_bf16: bool) -> np.ndarray:
    if packed_bf16:
        import ml_dtypes
        raw = np.frombuffer(blob, np.uint16).reshape(shape)
        return raw.view(ml_dtypes.bfloat16)
    return np.frombuffer(blob, np.dtype(dtype_name)).reshape(shape)


# ---------------------------------------------------------------------------
# KV handoff transfer framing (disaggregated prefill → decode)
# ---------------------------------------------------------------------------

#: wire magic of a packed KV transfer (version baked in: a decode
#: replica speaking a different frame era refuses loudly, it never
#: guesses at foreign bytes)
TRANSFER_MAGIC = b"SMLKV1\n"


@dataclasses.dataclass
class KVTransfer:
    """A decoded prefill→decode handoff: the prompt ids the K/V covers,
    the per-layer ``{"k", "v"}`` rows in cache-native dtype, and the
    identity triple (session, tenant, token-prefix hash) the lease is
    keyed on.  Produced only by :func:`unpack_kv_transfer` — by
    construction every row passed its CRC and the prefix hash matched
    the ids, so adopting these rows can never seed a wrong token."""
    session: Optional[str]
    tenant: str
    ids: List[int]
    rows: List[Dict[str, np.ndarray]]
    prefix_hash: str


def token_prefix_hash(ids) -> str:
    """Order-sensitive identity of a token prefix: sha1 over the int32
    byte stream, truncated to 16 hex chars.  Carried in every transfer
    header so a frame whose ids were damaged (or swapped with another
    session's) is rejected before its K/V can be adopted."""
    arr = np.asarray(ids, np.int32).reshape(-1)
    return hashlib.sha1(arr.tobytes()).hexdigest()[:16]


def pack_kv_transfer(ids, rows: List[Dict[str, np.ndarray]],
                     session: Optional[str] = None,
                     tenant: str = "default") -> bytes:
    """Frame one finished prefill as wire bytes: magic, a CRC-framed
    JSON header line (session, tenant, ids, token-prefix hash, per-row
    shape/dtype and a CRC32 **per row**), then the per-layer row blobs
    in cache-native packing (bf16 as uint16 bit patterns — the
    :func:`_pack` layout the arena itself stores).  Every check
    :func:`unpack_kv_transfer` applies is derived from this header, so
    a single flipped byte anywhere in the frame is detected."""
    ids = np.asarray(ids, np.int32).reshape(-1)
    if len(ids) == 0 or not rows:
        raise ValueError("a KV transfer needs a non-empty prompt and rows")
    blobs: List[bytes] = []
    crcs: List[int] = []
    lens: List[int] = []
    shape: Optional[Tuple[int, ...]] = None
    dtype_name = ""
    packed_bf16 = False
    for row in rows:
        stacked = np.stack([np.asarray(row["k"]),
                            np.asarray(row["v"])])   # (2, span, KH, DH)
        blob, packed_bf16, dtype_name = _pack(stacked)
        if shape is None:
            shape = stacked.shape
        elif tuple(stacked.shape) != tuple(shape):
            raise ValueError("KV transfer rows must share one shape")
        blobs.append(blob)
        crcs.append(zlib.crc32(blob))
        lens.append(len(blob))
    header = {
        "session": None if session is None else str(session),
        "tenant": str(tenant),
        "ids": [int(t) for t in ids],
        "prefix_hash": token_prefix_hash(ids),
        "shape": [int(d) for d in shape],
        "dtype": dtype_name,
        "packed_bf16": bool(packed_bf16),
        "row_bytes": lens,
        "row_crcs": crcs,
    }
    # the journal's CRC-framed-line idiom guards the header itself
    return TRANSFER_MAGIC + SessionJournal._frame(header) + b"".join(blobs)


def unpack_kv_transfer(blob: bytes) -> KVTransfer:
    """Decode and VERIFY a wire frame from :func:`pack_kv_transfer`.
    Raises ``ValueError`` when the bytes are not a KV transfer at all
    (wrong magic / missing header line) and :class:`ChecksumError` when
    they are one that was damaged in flight — header CRC mismatch, any
    row CRC mismatch, a short body, or a token-prefix hash that no
    longer matches the ids.  Either way nothing is adopted: the caller
    counts ``corrupt`` and cold-prefills."""
    if not blob.startswith(TRANSFER_MAGIC):
        raise ValueError("not a KV transfer frame (bad magic)")
    rest = blob[len(TRANSFER_MAGIC):]
    nl = rest.find(b"\n")
    if nl < 0:
        raise ValueError("KV transfer frame has no header line")
    line, body = rest[:nl].decode("utf-8", "replace"), rest[nl + 1:]
    crc_hex, _, text = line.partition(" ")
    try:
        want_crc = int(crc_hex, 16)
    except ValueError:
        raise ChecksumError("KV transfer header frame is malformed")
    if zlib.crc32(text.encode()) != want_crc:
        raise ChecksumError("KV transfer header failed its checksum")
    header = json.loads(text)
    ids = [int(t) for t in header["ids"]]
    if token_prefix_hash(ids) != header["prefix_hash"]:
        raise ChecksumError("KV transfer token-prefix hash mismatch")
    lens = [int(n) for n in header["row_bytes"]]
    crcs = [int(c) for c in header["row_crcs"]]
    if len(lens) != len(crcs) or len(body) != sum(lens):
        raise ChecksumError(
            f"KV transfer body is torn ({len(body)} bytes, "
            f"expected {sum(lens)})")
    shape = tuple(int(d) for d in header["shape"])
    rows: List[Dict[str, np.ndarray]] = []
    off = 0
    for i, (n, crc) in enumerate(zip(lens, crcs)):
        chunk = body[off:off + n]
        off += n
        if zlib.crc32(chunk) != crc:
            raise ChecksumError(f"KV transfer row {i} failed its checksum")
        stacked = _unpack(chunk, shape, header["dtype"],
                          bool(header["packed_bf16"]))
        rows.append({"k": stacked[0], "v": stacked[1]})
    return KVTransfer(session=header["session"], tenant=header["tenant"],
                      ids=ids, rows=rows,
                      prefix_hash=str(header["prefix_hash"]))


# ---------------------------------------------------------------------------
# Session journal
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SessionState:
    """What :meth:`SessionJournal.replay` reconstructs: the turn's
    prompt, the tokens committed so far, the turn's original token
    budget, and how many OLDEST tokens the size cap truncated away
    (``truncated > 0`` ⇒ the remaining ids are a SUFFIX and a
    token-exact resume is impossible — cold-start instead).
    ``tenant`` is the namespace the turn was journaled under — replay
    for any other tenant answers None, exactly like a missing session."""
    session: str
    prompt: List[int]
    committed: List[int]
    max_new: int
    truncated: int = 0
    tenant: str = "default"

    @property
    def ids(self) -> List[int]:
        return list(self.prompt) + list(self.committed)


class SessionJournal:
    """Append-only, fsync'd, CRC-framed per-session conversation log
    (see module docstring).  One file per session under ``root``:
    each line is ``"%08x %s\\n" % (crc32(json), json)`` — a torn tail
    from a SIGKILL fails its CRC and :meth:`replay` truncates the file
    back to the last valid record.  ``begin``/``compact`` rewrite the
    whole file through mkstemp+fsync+rename (the ``telemetry.artifact``
    idiom), so state rewrites are kill-atomic too."""

    def __init__(self, root: str, max_bytes_per_session: int = 256 * 1024,
                 fsync: bool = True, name: str = "llm"):
        self.root = str(root)
        self.max_bytes_per_session = int(max_bytes_per_session)
        self.fsync = bool(fsync)
        self.name = name
        os.makedirs(self.root, exist_ok=True)
        self._lock = threading.Lock()
        #: public — the serving loop (jax-free, duck-typed) counts its
        #: journal-replay restore outcomes through the journal's own
        #: metric handles instead of importing this package
        self.metrics = kvtier_metrics()

    def path(self, session: str, tenant: str = "default") -> str:
        """The session's journal file, namespaced by tenant: the digest
        covers ``tenant NUL session``, so two tenants using the SAME
        session id journal to two different files — a cross-tenant
        session-id collision can never replay (or truncate, or drop)
        another tenant's conversation."""
        digest = hashlib.sha1(
            f"{tenant}\x00{session}".encode()).hexdigest()[:24]
        return os.path.join(self.root, f"{digest}.jnl")

    # -- writes ------------------------------------------------------------
    def begin(self, session: str, prompt_ids, max_new: int,
              tenant: str = "default") -> None:
        """Start (or reset) a turn: the journal's state becomes exactly
        ``prompt_ids`` with no committed tokens.  Atomic rewrite — a
        kill mid-begin leaves the previous turn's state intact."""
        state = SessionState(str(session),
                             [int(t) for t in prompt_ids], [],
                             int(max_new), tenant=str(tenant))
        with self._lock:
            self._write_state(state)

    def append_tokens(self, session: str, tokens,
                      tenant: str = "default") -> None:
        """Append committed tokens; fsync'd before return, so a token
        acknowledged here survives a SIGKILL one instruction later.
        Over the per-session byte cap the journal compacts in place
        (prune at the append site), then — only when the conversation
        itself outgrows the cap — truncates oldest tokens, marked."""
        rec = {"op": "tokens", "ids": [int(t) for t in tokens]}
        with self._lock:
            self._append(session, rec, tenant=str(tenant))
            path = self.path(str(session), str(tenant))
            try:
                size = os.path.getsize(path)
            except OSError:
                return
            if size > self.max_bytes_per_session:
                self._compact(str(session), str(tenant))

    def compact(self, session: str, tenant: str = "default") -> None:
        """Consolidate the session's records into one state record
        (called at retirement — a long-lived conversation's file stays
        one bounded record, not an unbounded append history)."""
        with self._lock:
            self._compact(str(session), str(tenant))

    retire = compact

    def drop(self, session: str, tenant: str = "default") -> None:
        with self._lock:
            try:
                os.unlink(self.path(str(session), str(tenant)))
            except OSError:
                pass

    # -- replay ------------------------------------------------------------
    def replay(self, session: str,
               tenant: str = "default") -> Optional[SessionState]:
        """Rebuild the session's state, truncating the file back to the
        last valid record when the tail is torn or a record is corrupt
        (everything after the first bad record is dropped — later
        records may depend on the lost one).  Namespaced: replaying a
        session id under the wrong tenant answers None (belt: the path
        digest differs; braces: a recorded state whose tenant mismatches
        is refused even if the file were somehow shared)."""
        with self._lock:
            state = self._replay(str(session), str(tenant))
            if state is not None and state.tenant != str(tenant):
                return None
            return state

    def sessions(self) -> List[str]:
        """Names of every replayable session in the journal root."""
        out = []
        for fn in sorted(os.listdir(self.root)):
            if not fn.endswith(".jnl"):
                continue
            state = self._replay_path(os.path.join(self.root, fn))
            if state is not None:
                out.append(state.session)
        return out

    # -- internals ---------------------------------------------------------
    @staticmethod
    def _frame(rec: Dict[str, Any]) -> bytes:
        text = json.dumps(rec, separators=(",", ":"), sort_keys=True)
        return (f"{zlib.crc32(text.encode()):08x} {text}\n").encode()

    def _append(self, session: str, rec: Dict[str, Any],
                tenant: str = "default") -> None:
        line = self._frame(rec)
        # the fault site covers the whole append: ``kill`` dies with
        # the record unwritten (the previous fsync'd state survives),
        # ``corrupt`` flips a stored byte so replay truncates here
        line = get_faults().corrupt_point("kvtier.journal_append", line,
                                          tenant=tenant)
        fd = os.open(self.path(session, tenant),
                     os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
        try:
            os.write(fd, line)
            if self.fsync:
                os.fsync(fd)
        finally:
            os.close(fd)

    def _write_state(self, state: SessionState) -> None:
        import tempfile
        rec = {"op": "state", "session": state.session,
               "prompt": state.prompt, "committed": state.committed,
               "max_new": state.max_new, "truncated": state.truncated,
               "tenant": state.tenant}
        path = self.path(state.session, state.tenant)
        fd, tmp = tempfile.mkstemp(dir=self.root,
                                   prefix=os.path.basename(path) + ".tmp.")
        try:
            os.write(fd, self._frame(rec))
            if self.fsync:
                os.fsync(fd)
            os.close(fd)
            os.chmod(tmp, 0o644)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.close(fd)
            except OSError:
                pass
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        if self.fsync:
            try:
                dfd = os.open(self.root, os.O_RDONLY)
                try:
                    os.fsync(dfd)
                finally:
                    os.close(dfd)
            except OSError:  # pragma: no cover — platform without dir fsync
                pass

    def _compact(self, session: str, tenant: str = "default") -> None:
        state = self._replay(session, tenant)
        if state is None:
            return
        cap = self.max_bytes_per_session
        # oldest-token truncation, only when the conversation ITSELF
        # outgrows the cap (~6 bytes/token framed): drop from the head
        # and mark — replaying a suffix is not token-exact, and the
        # mark is what keeps the fallback honest
        budget = max(16, cap // 8)
        ids = state.ids
        if len(ids) > budget:
            drop = len(ids) - budget
            state.truncated += drop
            keep_prompt = state.prompt[drop:]
            if len(keep_prompt) < len(state.prompt):
                extra = drop - (len(state.prompt) - len(keep_prompt))
            else:
                extra = drop
            state.prompt = keep_prompt
            if extra > 0:
                state.committed = state.committed[extra:]
            flight_record("kvtier_journal_truncated", engine=self.name,
                          session=session, dropped=drop)
        self._write_state(state)

    def _replay(self, session: str,
                tenant: str = "default") -> Optional[SessionState]:
        return self._replay_path(self.path(session, tenant), truncate=True)

    def _replay_path(self, path: str,
                     truncate: bool = False) -> Optional[SessionState]:
        try:
            with open(path, "rb") as f:
                data = f.read()
        except OSError:
            return None
        state: Optional[SessionState] = None
        valid_end = 0
        pos = 0
        while pos < len(data):
            nl = data.find(b"\n", pos)
            if nl < 0:
                break                          # torn tail (no newline)
            line = data[pos:nl]
            rec = self._parse(line)
            if rec is None:
                break                          # corrupt record: stop here
            pos = nl + 1
            valid_end = pos
            if rec.get("op") == "state":
                state = SessionState(
                    str(rec.get("session", "")),
                    [int(t) for t in rec.get("prompt", [])],
                    [int(t) for t in rec.get("committed", [])],
                    int(rec.get("max_new", 0)),
                    int(rec.get("truncated", 0)),
                    tenant=str(rec.get("tenant", "default")))
            elif rec.get("op") == "tokens" and state is not None:
                state.committed.extend(int(t) for t in rec.get("ids", []))
        if truncate and valid_end < len(data):
            flight_record("kvtier_journal_torn", engine=self.name,
                          path=path, dropped_bytes=len(data) - valid_end)
            try:
                with open(path, "r+b") as f:
                    f.truncate(valid_end)
            except OSError:
                pass
        return state

    @staticmethod
    def _parse(line: bytes) -> Optional[Dict[str, Any]]:
        if len(line) < 10 or line[8:9] != b" ":
            return None
        try:
            crc = int(line[:8], 16)
            body = line[9:]
            if zlib.crc32(body) != crc:
                return None
            rec = json.loads(body.decode())
        except (ValueError, UnicodeDecodeError):
            return None
        return rec if isinstance(rec, dict) else None

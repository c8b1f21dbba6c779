"""Durable execution (§4.2): write-ahead journal, deterministic replay, DI.

A run of a ContextGraph is journaled as an append-only event log (the same
event-sourcing shape Temporal uses). Each committed node records:

    (node_id, context_digest, input_digest, output_digest, payload-or-ref)

Replaying a run re-executes the graph but *skips* any node whose
(context_digest, input_digest) matches a committed entry, re-injecting the
recorded output — effectively-once semantics on top of at-least-once retries.
Large payloads (model/optimizer state) are stored by reference: the journal
holds a ``ref`` string resolved by the checkpoint store, never raw tensors.

The journal format is length-prefixed msgpack records with a crc32 per record
and tagged-compression payload bodies (zstd when available, zlib fallback) —
see docs/journal-format.md for the full spec. Torn tails (a crash mid-append)
are detected and truncated on open — an explicit durability requirement.

Stream nodes commit at *chunk* granularity (``CHUNK_COMMIT`` /
``STREAM_EOS``, docs/streaming.md §4); the ``ReplayCache`` indexes those
records too, so a killed stream resumes from its last committed offset.

The payload codec lives in ``repro.wire.payload``; ``encode_payload``,
``decode_payload`` and ``payload_digest`` are re-exported here for
compatibility with seed-era call sites.
"""

from __future__ import annotations

import binascii
import os
import struct
import threading
import time
import warnings
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, Mapping, Optional, Tuple

from repro.obs.trace import current_span, get_tracer
from repro.wire import decode_payload, encode_payload, payload_digest

from .context import Context

_TRACER = get_tracer()

__all__ = [
    "Journal",
    "JournalRecord",
    "ReplayCache",
    "Interrupted",
    "interrupt",
    "KNOWN_KINDS",
    "REPLAY_IGNORED_KINDS",
    "SNAPSHOT_VERSION",
    "encode_payload",
    "decode_payload",
    "payload_digest",
    "atomic_task",
]

_HEADER = struct.Struct("<II")  # (length, crc32)

#: Layout version of the SNAPSHOT record this reader understands
#: (docs/journal-format.md §2.6). A SNAPSHOT stamped with a HIGHER version
#: was folded by a newer writer whose state layout this reader cannot
#: interpret; ``records()`` skips it with a RuntimeWarning instead of
#: mis-applying a half-understood state bundle.
SNAPSHOT_VERSION = 1

#: Every record kind this reader version interprets. Kinds outside this set
#: are *tolerated* (docs/journal-format.md §5): ``records()`` yields them
#: untouched and interpreting readers (ReplayCache, executors) ignore them,
#: so a journal written by a newer writer stays readable.
KNOWN_KINDS = frozenset(
    {
        "RUN_START",
        "NODE_START",
        "NODE_COMMIT",
        "NODE_REQUEUE",
        "CHUNK_COMMIT",
        "STREAM_EOS",
        "CACHE_HIT",
        "CACHE_STORE",
        "NODE_FAIL",
        "RUN_END",
        "CKPT",
        "SUSPEND",
        "RESUME",
        "FORK",
        "LINEAGE",
        "GW_HANDOFF",
        "SNAPSHOT",
    }
)

#: Kinds :class:`ReplayCache` deliberately does NOT index: they carry run
#: activity or annotations, never replayable output state. Kept in sync
#: with the scan in ``ReplayCache.__init__`` — ``python -m repro lint``
#: (INV101) diffs ``handled ∪ ignored`` against ``KNOWN_KINDS``, so adding
#: a kind without classifying it here or handling it there fails the gate.
REPLAY_IGNORED_KINDS = frozenset(
    {
        "RUN_START",
        "RUN_END",
        "NODE_START",
        "NODE_FAIL",
        "NODE_REQUEUE",
        "CACHE_HIT",
        "CACHE_STORE",
        "CKPT",
        "SUSPEND",
        "RESUME",
        "FORK",
        "LINEAGE",
        "GW_HANDOFF",
        "SNAPSHOT",
    }
)


class Interrupted(Exception):
    """A task reached a named interrupt point without an answer in its ξ.

    Raised by :func:`interrupt`; executors treat it as a *suspension
    request*, not a failure: in-flight work drains to commit, the pending
    frontier is journaled as a ``SUSPEND`` record, and the run returns a
    report with ``suspended=True`` (docs/durable-workflows.md §2).
    """

    def __init__(self, name: str, payload: Any = None):
        super().__init__(name)
        self.name = name
        self.payload = payload


_MISSING = object()


def interrupt(ctx: Context, name: str, payload: Any = None) -> Any:
    """Named interrupt point — call from inside a task function.

    If the context carries a fact under ``name`` (injected by
    ``resume(workflow_id, inputs={name: ...})``), its value is returned and
    the task proceeds. Otherwise the run suspends by raising
    :class:`Interrupted`; ``payload`` rides along in the ``SUSPEND`` record
    for the operator who will answer it.
    """
    value = ctx.get(name, _MISSING)
    if value is _MISSING:
        raise Interrupted(name, payload)
    return value


# --------------------------------------------------------------------------
# journal
# --------------------------------------------------------------------------


@dataclass
class JournalRecord:
    """One journal event — see docs/journal-format.md §2 for the field contract."""

    kind: str  # RUN_START | NODE_START | NODE_COMMIT | NODE_REQUEUE
    #          # | CHUNK_COMMIT | STREAM_EOS (chunk-granular streams)
    #          # | CACHE_HIT | CACHE_STORE | NODE_FAIL | RUN_END | CKPT
    #          # | SUSPEND | RESUME | FORK | LINEAGE (durable workflows)
    node_id: str = ""
    context_digest: str = ""
    input_digest: str = ""
    output_digest: str = ""
    payload: Any = None  # inline output (small) — mutually exclusive with ref
    ref: str = ""  # checkpoint-store reference for large outputs
    wall_time: float = 0.0
    attempt: int = 0
    meta: Dict[str, Any] = field(default_factory=dict)

    def to_obj(self) -> dict:
        return {
            "k": self.kind,
            "n": self.node_id,
            "c": self.context_digest,
            "i": self.input_digest,
            "o": self.output_digest,
            "p": self.payload,
            "r": self.ref,
            "t": self.wall_time,
            "a": self.attempt,
            "m": self.meta,
        }

    @staticmethod
    def from_obj(o: Mapping) -> "JournalRecord":
        """Decode one record object — forward-compatibly.

        Missing fields default (a future writer may drop one) and unknown
        keys are ignored (a future writer may add one), so a pre-upgrade
        reader never raises on records written by a newer version — the
        forward-compat contract of docs/journal-format.md §5.
        """
        return JournalRecord(
            kind=str(o.get("k", "")),
            node_id=o.get("n", ""),
            context_digest=o.get("c", ""),
            input_digest=o.get("i", ""),
            output_digest=o.get("o", ""),
            payload=o.get("p"),
            ref=o.get("r", ""),
            wall_time=o.get("t", 0.0),
            attempt=o.get("a", 0),
            meta=dict(o.get("m") or {}),
        )


class Journal:
    """Append-only, crash-safe event log. Thread-safe appends.

    ``sync`` policy: "always" fsyncs per commit (paper-faithful durable mode),
    "batch" fsyncs on flush()/close() (the beyond-paper async mode measured in
    benchmarks), "never" for in-memory tests.
    """

    def __init__(
        self,
        path: str,
        sync: str = "always",
        lineage: Optional[Mapping[str, Any]] = None,
    ):
        assert sync in ("always", "batch", "never")
        self.path = path
        self.sync = sync
        self._lock = threading.Lock()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._recover_tail()
        empty = not os.path.exists(path) or os.path.getsize(path) == 0
        self._fh = open(path, "ab")
        if lineage is not None and empty:
            # lineage header: the FIRST record of a fresh journal names the
            # durable identity the file belongs to (workflow_id, parent,
            # fork point) — see docs/journal-format.md §2.5
            self.append(JournalRecord(kind="LINEAGE", meta=dict(lineage)))

    # -- crash recovery ------------------------------------------------------
    def _recover_tail(self) -> None:
        """Truncate a torn tail record (partial append at crash time)."""
        if not os.path.exists(self.path):
            return
        good = 0
        with open(self.path, "rb") as fh:
            data = fh.read()
        off = 0
        while off + _HEADER.size <= len(data):
            length, crc = _HEADER.unpack_from(data, off)
            body = data[off + _HEADER.size : off + _HEADER.size + length]
            if len(body) < length or binascii.crc32(body) != crc:
                break
            off += _HEADER.size + length
            good = off
        if good != len(data):
            with open(self.path, "r+b") as fh:
                fh.truncate(good)

    # -- append ----------------------------------------------------------------
    # Traced appends and flushes are ``journal.append`` / ``journal.flush``
    # spans inside the work that made them (a node, a task); one made outside
    # any span (requeue bookkeeping, a shutdown flush) is left untimed rather
    # than opening a one-span trace of its own.
    def append(self, rec: JournalRecord) -> None:
        if _TRACER.enabled and current_span() is not None:
            with _TRACER.span("journal.append", attrs={"kind": rec.kind}):
                self._append(rec)
        else:
            self._append(rec)

    def _append(self, rec: JournalRecord) -> None:
        rec.wall_time = rec.wall_time or time.time()  # record timestamp
        body = encode_payload(rec.to_obj())
        frame = _HEADER.pack(len(body), binascii.crc32(body)) + body
        with self._lock:
            self._fh.write(frame)
            if self.sync == "always":
                self._fh.flush()
                os.fsync(self._fh.fileno())

    def flush(self) -> None:
        if _TRACER.enabled and current_span() is not None:
            with _TRACER.span("journal.flush"):
                self._flush()
        else:
            self._flush()

    def _flush(self) -> None:
        with self._lock:
            self._fh.flush()
            if self.sync != "never":
                os.fsync(self._fh.fileno())

    def close(self) -> None:
        self.flush()
        self._fh.close()

    # -- read -----------------------------------------------------------------
    def kinds(self) -> Dict[str, int]:
        """Histogram of record kinds — cheap integrity/debug view of a run.

        E.g. a fault-tolerant cluster run reads as RUN_START=1, NODE_START=n,
        NODE_REQUEUE=k (worker evictions), NODE_COMMIT=n, RUN_END=1; a
        cache-accelerated run additionally shows CACHE_HIT=h and
        CACHE_STORE=n-h (every hit still commits, so NODE_COMMIT stays n);
        a streaming run adds CHUNK_COMMIT=Σchunks and one STREAM_EOS per
        stream stage.
        """
        return dict(Counter(rec.kind for rec in self.records()))

    def records(self, expand: bool = True) -> Iterator[JournalRecord]:
        """Yield every committed record, in append order.

        A checksum-valid frame whose body nonetheless fails to decode (e.g.
        written by an incompatible future version) is skipped with a
        warning, never raised — interpreting readers must stay usable on
        journals that carry record shapes they predate (format §5).

        A ``SNAPSHOT`` record (journal compaction, format §2.6) is yielded
        and then — with ``expand=True``, the default — *expanded*: the live
        records it folded stream out after it, exactly as the pre-compaction
        journal carried them, so every interpreting reader (replay oracle,
        workflow runner, lineage index) sees an identical history. A
        snapshot stamped with a layout version NEWER than
        :data:`SNAPSHOT_VERSION` is skipped whole with a RuntimeWarning —
        mis-applying a half-understood state bundle would corrupt replay.
        ``expand=False`` yields the raw physical frames (compaction tooling).
        """
        with open(self.path, "rb") as fh:
            data = fh.read()
        off = 0
        while off + _HEADER.size <= len(data):
            length, crc = _HEADER.unpack_from(data, off)
            body = data[off + _HEADER.size : off + _HEADER.size + length]
            if len(body) < length or binascii.crc32(body) != crc:
                break
            off += _HEADER.size + length
            try:
                rec = JournalRecord.from_obj(decode_payload(body))
            except Exception as exc:
                warnings.warn(
                    f"journal {self.path}: skipping undecodable record at "
                    f"offset {off - _HEADER.size - length} ({exc})",
                    RuntimeWarning,
                    stacklevel=2,
                )
                continue
            if rec.kind not in KNOWN_KINDS:
                # forward-compat (format §5): a newer writer may introduce
                # record kinds this reader predates — skip, never raise, so
                # replay of the records we DO understand stays available
                warnings.warn(
                    f"journal {self.path}: skipping record of unknown kind "
                    f"{rec.kind!r} at offset {off - _HEADER.size - length}",
                    RuntimeWarning,
                    stacklevel=2,
                )
                continue
            if rec.kind == "SNAPSHOT":
                version = int(rec.meta.get("version") or 0)
                if version > SNAPSHOT_VERSION:
                    # the version gate (format §2.6): a well-formed SNAPSHOT
                    # from a newer layout version must NOT be applied — its
                    # state layout may have changed meaning under this reader
                    warnings.warn(
                        f"journal {self.path}: skipping SNAPSHOT of newer "
                        f"layout version {version} (reader understands "
                        f"<= {SNAPSHOT_VERSION}) at offset "
                        f"{off - _HEADER.size - length}; compacted history "
                        "is unavailable to this reader",
                        RuntimeWarning,
                        stacklevel=2,
                    )
                    continue
                yield rec
                if not expand:
                    continue
                for obj in rec.meta.get("records") or ():
                    try:
                        sub = JournalRecord.from_obj(obj)
                    except Exception as exc:
                        warnings.warn(
                            f"journal {self.path}: skipping undecodable "
                            f"snapshot state record ({exc})",
                            RuntimeWarning,
                            stacklevel=2,
                        )
                        continue
                    if sub.kind not in KNOWN_KINDS or sub.kind == "SNAPSHOT":
                        warnings.warn(
                            f"journal {self.path}: skipping snapshot state "
                            f"record of unknown kind {sub.kind!r}",
                            RuntimeWarning,
                            stacklevel=2,
                        )
                        continue
                    yield sub
                continue
            yield rec

    # -- compaction bookkeeping (docs/journal-format.md §2.6) ----------------
    def snapshot(self) -> Optional[JournalRecord]:
        """The journal's SNAPSHOT record (always the first frame), or None."""
        for rec in self.records(expand=False):
            if rec.kind == "SNAPSHOT":
                return rec
            return None
        return None

    def base_seq(self) -> int:
        """First logical record seq still individually addressable.

        An uncompacted journal starts at 0. A compacted journal's SNAPSHOT
        folded the original records ``0 .. base_seq-1``; those seqs are no
        longer addressable (e.g. as a ``fork(at=...)`` point) — only the
        folded *live state* survives, not per-record identity.
        """
        snap = self.snapshot()
        return int(snap.meta.get("base_seq") or 0) if snap is not None else 0

    def end_seq(self) -> int:
        """One past the last logical record seq (``base_seq + raw suffix``)."""
        seq = 0
        for rec in self.records(expand=False):
            if rec.kind == "SNAPSHOT":
                seq = int(rec.meta.get("base_seq") or 0)
            else:
                seq += 1
        return seq

    def indexed_records(
        self,
    ) -> Iterator[Tuple[Optional[int], JournalRecord]]:
        """Yield ``(logical_seq, record)`` pairs, expanding snapshots.

        Records folded into a SNAPSHOT carry ``None`` — their individual
        seqs were retired by compaction (only live state survives); physical
        suffix records carry their stable logical seq, which addressing
        operations (``fork(at=...)``) keep honouring across compactions.
        """
        seq = 0
        for rec in self.records(expand=False):
            if rec.kind != "SNAPSHOT":
                yield seq, rec
                seq += 1
                continue
            seq = int(rec.meta.get("base_seq") or 0)
            for obj in rec.meta.get("records") or ():
                try:
                    sub = JournalRecord.from_obj(obj)
                except Exception:
                    continue
                if sub.kind in KNOWN_KINDS and sub.kind != "SNAPSHOT":
                    yield None, sub

    def lineage(self) -> Optional[Dict[str, Any]]:
        """The lineage header (first record, if it is a ``LINEAGE``), or None.

        Compaction-transparent: a compacted journal leads with its SNAPSHOT
        record, whose expansion re-yields the original LINEAGE header first.
        """
        for rec in self.records():
            if rec.kind == "SNAPSHOT":
                continue
            if rec.kind == "LINEAGE":
                return dict(rec.meta)
            return None
        return None

    def __enter__(self) -> "Journal":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class ReplayCache:
    """Index of committed node outputs from a journal — the replay oracle.

    Also indexes the *chunk-granular* stream records (``CHUNK_COMMIT`` /
    ``STREAM_EOS``, docs/streaming.md §4): for a stream identity
    ``(node, ξ-digest, input-digest)`` it answers which chunk sequence
    numbers are already durable, the digest chain head, and whether the
    stream reached EOS — the facts a resumed producer needs to skip every
    committed chunk and continue from its last committed offset.
    """

    def __init__(self, journal: Optional[Journal] = None):
        self._committed: Dict[Tuple[str, str, str], JournalRecord] = {}
        self._chunks: Dict[Tuple[str, str, str], Dict[int, JournalRecord]] = {}
        self._eos: Dict[Tuple[str, str, str], JournalRecord] = {}
        # ``scanned`` counts the records this oracle had to walk to build
        # itself — the observable replay cost a compaction is meant to cut
        # from O(history) to O(live state) (docs/journal-lifecycle.md §1)
        self.stats = {"commits": 0, "replayed": 0, "chunks": 0, "scanned": 0}
        if journal is not None and os.path.exists(journal.path):
            for rec in journal.records():
                self.stats["scanned"] += 1
                if rec.kind == "NODE_COMMIT":
                    key = (rec.node_id, rec.context_digest, rec.input_digest)
                    self._committed[key] = rec
                    self.stats["commits"] += 1
                elif rec.kind == "CHUNK_COMMIT":
                    self.record_chunk(rec)
                elif rec.kind == "STREAM_EOS":
                    key = (rec.node_id, rec.context_digest, rec.input_digest)
                    self._eos[key] = rec

    def lookup(
        self, node_id: str, context_digest: str, input_digest: str
    ) -> Optional[JournalRecord]:
        rec = self._committed.get((node_id, context_digest, input_digest))
        if rec is not None:
            self.stats["replayed"] += 1
        return rec

    def record(self, rec: JournalRecord) -> None:
        self._committed[(rec.node_id, rec.context_digest, rec.input_digest)] = rec

    # -- chunk-granular stream state (docs/streaming.md §4) ------------------
    def record_chunk(self, rec: JournalRecord) -> None:
        """Index one ``CHUNK_COMMIT`` (keyed by stream identity + seq)."""
        key = (rec.node_id, rec.context_digest, rec.input_digest)
        self._chunks.setdefault(key, {})[int(rec.meta.get("seq", 0))] = rec
        self.stats["chunks"] += 1

    def record_eos(self, rec: JournalRecord) -> None:
        """Index one ``STREAM_EOS`` marker."""
        self._eos[(rec.node_id, rec.context_digest, rec.input_digest)] = rec

    def stream_progress(
        self, node_id: str, context_digest: str, input_digest: str
    ) -> Tuple[int, str, bool]:
        """Durable state of a stream: ``(next_seq, chain, eos_reached)``.

        ``next_seq`` is the first sequence number with no committed chunk
        (committed chunks form a contiguous prefix 0..next_seq-1 by
        construction — a chunk only commits after its predecessor);
        ``chain`` is the digest-chain head after the last committed chunk.
        """
        by_seq = self._chunks.get((node_id, context_digest, input_digest), {})
        next_seq = 0
        chain = ""
        while next_seq in by_seq:
            chain = str(by_seq[next_seq].meta.get("chain", ""))
            next_seq += 1
        eos = (node_id, context_digest, input_digest) in self._eos
        return next_seq, chain, eos

    def stream_chunks(
        self, node_id: str, context_digest: str, input_digest: str
    ) -> "list[JournalRecord]":
        """Committed chunk records, in sequence order (contiguous prefix)."""
        by_seq = self._chunks.get((node_id, context_digest, input_digest), {})
        out = []
        seq = 0
        while seq in by_seq:
            out.append(by_seq[seq])
            seq += 1
        return out


# --------------------------------------------------------------------------
# atomic task decorator — dependency injection contract (§3.2 assumption 2)
# --------------------------------------------------------------------------


def atomic_task(fn: Callable[..., Any]) -> Callable[..., Any]:
    """Mark ``fn`` as an atomic durable task.

    The contract: fn(ctx: Context, **injected_inputs) -> output. The wrapper
    rejects ambient-state smuggling (positional args) and stamps metadata the
    executor uses for digesting.
    """

    def wrapper(ctx: Context, **inputs: Any) -> Any:
        return fn(ctx, **inputs)

    wrapper.__name__ = getattr(fn, "__name__", "task")
    wrapper.__atomic_task__ = True  # type: ignore[attr-defined]
    wrapper.__wrapped__ = fn  # type: ignore[attr-defined]
    return wrapper

"""Reusable fault-injection harness for crash/kill tests.

One injector, four parametrizable kill points — replacing the ad-hoc
"bomb" closures that used to be duplicated across test_cluster_dataflow,
test_stream, and test_distributed_train:

  - ``pre-commit``:    ``fail_call(fn, ...)`` — the wrapped node/task fn
    raises :class:`InjectedFault` before its result can commit.
  - ``post-commit-pre-cache-store``: ``fail_cache_store(executor)`` — the
    NODE_COMMIT lands durably, then the process "dies" before the result
    reaches the cross-run cache.
  - ``mid-chunk``:     ``fail_chunk(fn, value=...)`` — a stream mapper dies
    on a chosen chunk, after earlier chunks committed.
  - ``mid-suspend``:   ``fail_suspend_append(journal)`` — the crash lands
    while the SUSPEND record itself is being journaled.
  - ``fail-gateway``:  ``fail_gateway(replica, after=N)`` — a (sharded)
    gateway replica dies right after accepting its Nth submission, with
    queued and in-flight work stranded for journal-backed handoff.
  - ``mid-compact-publish``: ``fail_compact()`` — compaction dies after
    fully writing the candidate snapshot file but BEFORE the atomic
    ``os.replace`` publish: the original journal must remain the source of
    truth and the partial ``.compact.tmp.*`` must be swept, never applied.
  - ``remote-store``:  ``fail_remote_store(backend)`` — a tiered cache's
    remote publish dies mid-write, leaving a torn tmp blob on the shared
    tier: the local tier must still hit and no torn remote blob may ever
    be visible under its final name.

Worker-level faults go through :meth:`FaultInjector.flaky_worker`, which
wraps ``repro.core.FlakyWorker`` and auto-releases hung workers on
teardown. Use the ``faults`` fixture::

    from _faults import InjectedFault, faults  # noqa: F401

    def test_crash(faults):
        flaky = faults.flaky_worker("w0", registry, after=1)
        ...

:class:`InjectedFault` subclasses RuntimeError so existing
``pytest.raises(RuntimeError)`` assertions keep matching.
"""

import pytest

from repro.core import FlakyWorker

KILL_POINTS = (
    "pre-commit",
    "post-commit-pre-cache-store",
    "mid-chunk",
    "mid-suspend",
    "fail-gateway",
    "mid-compact-publish",
    "remote-store",
)


class InjectedFault(RuntimeError):
    """The typed crash every injected kill raises (a RuntimeError subclass)."""


class FaultInjector:
    """Builds fault-wrapped callables/workers and undoes patches on restore()."""

    def __init__(self):
        self._restores = []
        self._workers = []

    # -- kill point: pre-commit ---------------------------------------------
    def fail_call(self, fn, *, at=None, when=None, message="injected fault"):
        """Wrap ``fn`` to raise InjectedFault BEFORE it runs (pre-commit kill).

        ``at=N`` arms exactly the Nth invocation (1-based): the fault fires
        once and later calls pass through (a retried incarnation succeeds).
        ``when(*args, **kw)`` is a predicate over the call's arguments and
        fires on EVERY match — within one incarnation the process stays
        dead, including across gateway-level retries; a fresh wrap (new
        incarnation) runs clean. Exactly one of ``at``/``when`` is required.
        """
        assert (at is None) != (when is None), "give exactly one of at=/when="
        state = {"calls": 0, "fired": 0}

        def wrapped(*args, **kw):
            state["calls"] += 1
            if at is not None:
                hit = state["calls"] == at and not state["fired"]
            else:
                hit = when(*args, **kw)
            if hit:
                state["fired"] += 1
                raise InjectedFault(message)
            return fn(*args, **kw)

        wrapped.state = state
        return wrapped

    # -- kill point: mid-chunk ----------------------------------------------
    def fail_chunk(self, fn, *, value, kwarg=None, message="killed mid-stream"):
        """Wrap a stream mapper to die when its chunk equals ``value``.

        Chunks mapped before the trigger have already CHUNK_COMMITted — the
        canonical mid-stream kill. Executors pass the chunk under the stream
        kwarg (the producer dep's name); ``kwarg`` picks it out explicitly,
        or defaults to the sole keyword when there is exactly one.
        """

        def wrapped(ctx, **kw):
            chunk = kw[kwarg] if kwarg is not None else next(iter(kw.values()))
            if chunk == value:
                raise InjectedFault(message)
            return fn(ctx, **kw)

        return wrapped

    # -- kill point: post-commit-pre-cache-store -----------------------------
    def fail_cache_store(self, executor, message="died before cache store"):
        """Patch ``executor`` so the first cache store crashes AFTER the commit.

        Models a process death in the window between the durable NODE_COMMIT
        and the CACHE_STORE: the journal replays the node, the cache stays
        cold. Restored on teardown.
        """
        orig = executor._cache_store
        state = {"fired": False}

        def dying(*args, **kw):
            if not state["fired"]:
                state["fired"] = True
                raise InjectedFault(message)
            return orig(*args, **kw)

        executor._cache_store = dying
        self._restores.append(lambda: setattr(executor, "_cache_store", orig))  # noqa: B010
        return state

    # -- kill point: mid-suspend ---------------------------------------------
    def fail_suspend_append(self, journal, message="died journaling SUSPEND"):
        """Patch ``journal.append`` so the first SUSPEND record crashes the run.

        The suspension itself is torn: no durable SUSPEND exists, and a
        resume must fall back to re-running (and re-suspending) cleanly.
        Restored on teardown.
        """
        orig = journal.append
        state = {"fired": False}

        def dying(rec):
            if rec.kind == "SUSPEND" and not state["fired"]:
                state["fired"] = True
                raise InjectedFault(message)
            return orig(rec)

        journal.append = dying
        self._restores.append(lambda: setattr(journal, "append", orig))  # noqa: B010
        return state

    # -- kill point: fail-gateway ---------------------------------------------
    def fail_gateway(self, gateway, *, after=1, message="gateway replica killed"):
        """Arm ``gateway`` to crash right after its ``after``-th accepted submit.

        The submission itself lands (queued or in-flight on the replica),
        then :meth:`Gateway.crash` fires — the fault-injection death that
        abandons queued work without draining. With a
        :class:`~repro.core.shards.ShardedGateway` replica this is the handoff
        trigger: the monitor notices ``crashed`` and a survivor adopts the
        dead replica's partition. Restored on teardown.
        """
        orig = gateway.submit
        state = {"submits": 0, "fired": False}

        def dying(*args, **kw):
            fut = orig(*args, **kw)
            state["submits"] += 1
            if state["submits"] >= after and not state["fired"]:
                state["fired"] = True
                gateway.crash()
            return fut

        gateway.submit = dying
        self._restores.append(lambda: setattr(gateway, "submit", orig))  # noqa: B010
        return state

    # -- kill point: mid-compact-publish --------------------------------------
    def fail_compact(self, message="died before snapshot publish"):
        """Arm journal compaction to die between tmp-write and publish.

        Patches ``repro.journal.compact._publish`` (the ``os.replace``
        crash-safety boundary) to raise once: the candidate snapshot file is
        fully written and fsynced, but never installed. The original journal
        must remain byte-identical, and the next compaction must sweep the
        orphaned ``.compact.tmp.*`` file. Restored on teardown.
        """
        import repro.journal.compact as compact_mod

        orig = compact_mod._publish
        state = {"fired": False}

        def dying(tmp_path, path):
            if not state["fired"]:
                state["fired"] = True
                raise InjectedFault(message)
            return orig(tmp_path, path)

        compact_mod._publish = dying
        self._restores.append(lambda: setattr(compact_mod, "_publish", orig))  # noqa: B010
        return state

    # -- kill point: remote-store ---------------------------------------------
    def fail_remote_store(self, backend, message="died mid remote publish"):
        """Arm a ``TieredCacheBackend`` to die during its first remote publish.

        Models the worst crash the shared tier can see: a torn partial file
        is left behind under a tmp name (NEVER the final blob name — the
        atomic-rename contract), then the process dies. The local tier
        already holds the blob, so reads keep hitting; other hosts simply
        miss until a later successful publish. Restored on teardown.
        """
        orig = backend._remote_put
        state = {"fired": False}

        def dying(key, body):
            if not state["fired"]:
                state["fired"] = True
                path = backend.remote.path_for(key)
                import os

                os.makedirs(os.path.dirname(path), exist_ok=True)
                with open(path + ".tmp.fault", "wb") as fh:
                    fh.write(body[: max(1, len(body) // 2)])  # torn partial
                raise InjectedFault(message)
            return orig(key, body)

        backend._remote_put = dying
        self._restores.append(lambda: setattr(backend, "_remote_put", orig))  # noqa: B010
        return state

    # -- worker-level faults --------------------------------------------------
    def flaky_worker(self, name, registry, *, after=1, mode="drop", **kw):
        """A ``FlakyWorker`` armed to die at its ``after``-th task start.

        ``mode="drop"`` fails fast with ConnectionError; ``mode="hang"``
        parks in-flight calls until heartbeat eviction. Hung workers are
        released automatically when the injector restores.
        """
        worker = FlakyWorker(name, registry, kill_after_starts=after, mode=mode, **kw)
        self._workers.append(worker)
        return worker

    def restore(self):
        """Undo every patch and release every hung worker (teardown)."""
        while self._restores:
            self._restores.pop()()
        for w in self._workers:
            w.release()
        self._workers.clear()


@pytest.fixture
def faults():
    """Function-scoped FaultInjector that restores its patches on teardown."""
    inj = FaultInjector()
    yield inj
    inj.restore()


__all__ = ["KILL_POINTS", "InjectedFault", "FaultInjector", "faults"]

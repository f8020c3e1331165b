"""Background compaction scheduler: flush/compaction off the write path.

Production LSM stores decouple compaction from the foreground write path —
synchronous merges tax every write burst exactly when the merge policy is
most active (LevelDB's single background thread; the scheduling analysis in
the Luo & Carey LSM survey).  This module is that subsystem for the Autumn
engine (DESIGN.md §11):

``CompactionScheduler``
    Owns the job queue and ``compaction_workers`` daemon worker threads.
    Foreground ``put``/``put_batch``/``flush`` only *rotate* the full
    memtable into the immutable queue and submit a :class:`FlushJob`; the
    worker turns it into an L0 run, installs the new version, and chains
    :class:`CompactJob` continuations until the tree is shaped — exactly the
    sequence the synchronous engine runs inline, which is what makes the
    sync store a bit-for-bit differential oracle after ``wait_for_quiesce``.

Determinism contract
    Jobs execute strictly one at a time in queue order (a turnstile: a
    worker only pops when no job is in flight), and a job's compaction
    continuations are pushed to the *front* of the queue — so the apply
    order for any op sequence is flush₁, its compactions, flush₂, … —
    byte-identical to the synchronous engine's trajectory.  Extra workers
    are hot standbys today (the job pipeline is inherently sequential:
    each plan depends on the previous apply); the knob exists for the
    sharding follow-on, where per-shard schedulers drain independent trees.

Safety
    The worker is the only thread that mutates levels (copy-on-write list
    swaps; readers are lock-free on the captured reference), every version
    installs through the mutex-guarded ``Manifest``, and each in-flight
    compaction pins its input version (``Manifest.pin_current``) so
    concurrent snapshot release / GC can never free the runs mid-merge.
    ``abort_and_drain`` (crash path) stops the in-flight job at its next
    safe point, clears the queue, and returns only when nothing is running
    — pins and cache entries are released before the engine wipes volatile
    state, so a crash mid-compaction leaks neither.
"""
from __future__ import annotations

import os
import sys
import threading
import time
import weakref
from collections import deque
from typing import Callable, Deque, Optional

from .memtable import ImmutableMemtable


def _pin_worker_to_spare_core(offset: int = 0) -> None:
    """Best-effort: move the calling worker thread onto one of the trailing
    cores of the process affinity set, leaving the first core to the
    foreground.

    Production stores give background compaction pools dedicated cores for
    exactly this reason (RocksDB's background-thread affinity): without it
    the OS migrates the write-path thread onto the worker's core mid-burst
    and the two ping-pong.  ``offset`` spreads per-shard schedulers'
    workers round-robin from the last core downwards (DESIGN.md §12) —
    offset 0 is the last core, exactly the pre-sharding behavior; with
    more shards than spare cores the wrap reaches the foreground's core,
    which is the right trade once the foreground finishes and the drain
    phase would otherwise leave that core idle.  On Linux
    ``sched_setaffinity(0, ...)`` scopes to the calling *thread*; no-ops
    (with the full mask kept) on single-core affinities and on platforms
    without the syscall.
    """
    try:
        aff = sorted(os.sched_getaffinity(0))
        if len(aff) > 1:
            os.sched_setaffinity(0, {aff[-1 - (offset % len(aff))]})
    except (AttributeError, OSError):
        pass
    try:
        # Background work must lose scheduling ties against the foreground
        # writer (RocksDB runs its compaction pool at low priority for the
        # same reason): with several shards' workers runnable at once, an
        # equal-priority pool would take a proportional share of the
        # writer's core/GIL time mid-burst.  Linux-only on purpose: there
        # ``who=0`` scopes setpriority to the calling *thread* (the kernel
        # takes a TID); on other POSIX systems the same call would renice
        # the whole process — writer included — irreversibly.
        if sys.platform.startswith("linux"):
            os.setpriority(os.PRIO_PROCESS, 0, 10)
    except (AttributeError, OSError):
        pass


class WorkerBudget:
    """Resizable counting semaphore for the cross-shard worker budget.

    Drop-in for the plain ``threading.Semaphore`` the sharded facade hands
    its per-shard schedulers (DESIGN.md §12) — workers ``acquire``/``release``
    around each job exactly as before — plus :meth:`resize`, the online
    tuner's worker-reallocation actuator (§17).  Grow is always safe
    (permits are minted).  Shrink only retires *free* permits, non-blocking:
    the caller invokes it at a quiesce/idle boundary where every permit is
    home; if a straggler still holds one, the shrink aborts cleanly (False)
    rather than blocking the foreground or stranding a worker.
    """

    def __init__(self, n: int):
        self._size = max(1, int(n))
        self._sem = threading.Semaphore(self._size)
        self._mu = threading.Lock()

    @property
    def size(self) -> int:
        return self._size

    def acquire(self, *args, **kwargs):
        return self._sem.acquire(*args, **kwargs)

    def release(self) -> None:
        self._sem.release()

    # `with budget:` — same protocol as threading.Semaphore
    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def resize(self, n: int) -> bool:
        """Retarget the budget to ``n`` permits; True iff it landed."""
        n = max(1, int(n))
        with self._mu:
            delta = n - self._size
            if delta > 0:
                for _ in range(delta):
                    self._sem.release()
            elif delta < 0:
                got = 0
                for _ in range(-delta):
                    if not self._sem.acquire(blocking=False):
                        for _ in range(got):   # roll back: all-or-nothing
                            self._sem.release()
                        return False
                    got += 1
            self._size = n
            return True


class FlushJob:
    """Turn one immutable memtable into an L0 run + version install."""

    __slots__ = ("imm", "retries")

    def __init__(self, imm: ImmutableMemtable):
        self.imm = imm
        self.retries = 0

    def run(self, store) -> Optional["CompactJob"]:
        return store._bg_flush(self.imm)

    def __repr__(self):
        return f"FlushJob(entries={len(self.imm.memtable)})"


class CompactJob:
    """Plan-and-apply one compaction task against the *current* tree.

    Generation is decoupled from apply (``policy.plan`` runs when the job
    executes, never earlier), so a task can never go stale; the planned
    task's captured ``src_run_ids`` are still validated by ``_apply`` as the
    discipline check.  Returns another CompactJob while the tree is
    unshaped — the scheduler front-queues it, keeping all compactions of a
    flush ahead of the next flush.  ``cause`` is the id of the memtable
    whose flush started the chain (the compaction spans carry it).
    """

    __slots__ = ("last_task", "retries", "cause")

    def __init__(self, cause: int = 0):
        self.last_task = None
        self.retries = 0
        self.cause = cause

    def run(self, store) -> Optional["CompactJob"]:
        task = store._bg_compact_one(self.cause)
        self.last_task = task
        if task is not None:
            return CompactJob(self.cause)
        # Tree is shaped: this worker just paid for the sort work a range
        # view reuses, so refresh the view here (DESIGN.md §13) — the
        # foreground write path never rebuilds.  No-op unless the store has
        # ``use_range_views`` set.
        store._bg_refresh_view()
        return None

    def __repr__(self):
        return f"CompactJob(last={self.last_task})"


class CompactionScheduler:
    def __init__(self, store, workers: int = 1,
                 budget: Optional[threading.Semaphore] = None,
                 worker_offset: int = 0):
        # Weak reference only: the parked worker threads must not root the
        # store.  An async store whose owner drops every reference (without
        # calling close()) stays collectable — the workers notice the dead
        # ref on their idle-wait heartbeat and exit, unrooting the
        # scheduler itself.
        #
        # ``budget`` (sharded facade, DESIGN.md §12): a semaphore shared by
        # N sibling schedulers bounding how many background jobs run
        # concurrently across the whole facade — each shard keeps its own
        # determinism turnstile (one in-flight job per shard, queue order),
        # while the shared budget caps total background CPU at
        # ``compaction_workers``.  ``worker_offset`` spreads the pools over
        # the spare cores.
        self._store = weakref.ref(store)
        self._budget = budget
        self._worker_offset = int(worker_offset)
        self.workers = max(1, int(workers))
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        self._queue: Deque = deque()
        self._inflight = 0
        self._paused = False
        self._abort = False
        self._stop = False
        self._failure: Optional[BaseException] = None
        # Optional facade hook (DESIGN.md §15): called by the worker that
        # just drained the queue (outside the condition lock).  The sharded
        # facade points this at its imbalance check so rebalancing is
        # *detected* at compaction/quiesce boundaries; the hook must only
        # set flags — the actual rebalance runs on a foreground thread
        # (running it here would deadlock: a rebalance quiesces this very
        # scheduler from its only worker).
        self.on_idle: Optional[Callable[[], None]] = None
        self._threads = []
        for i in range(self.workers):
            t = threading.Thread(target=self._loop, daemon=True,
                                 name=f"autumn-compaction-{i}")
            t.start()
            self._threads.append(t)

    # ------------------------------------------------------------ submission
    @property
    def lock(self) -> threading.Condition:
        """The scheduler condition: guards the queue AND the engine's
        immutable-memtable list (rotation appends and flush-install pops are
        both read-modify-write on ``store._imm``, so they share this lock;
        readers still see the list lock-free via reference capture)."""
        return self._cv

    def submit(self, job) -> None:
        with self._cv:
            if self._stop:
                raise RuntimeError("scheduler is shut down")
            if self._failure is not None:
                raise RuntimeError(
                    "background compaction failed; the store's durable "
                    "state is intact — crash()+recover() to resume"
                ) from self._failure
            self._queue.append(job)
            self._cv.notify_all()

    # --------------------------------------------------------------- workers
    def _loop(self) -> None:
        _pin_worker_to_spare_core(self._worker_offset)
        while True:
            with self._cv:
                # turnstile: strict one-job-at-a-time in FIFO order is the
                # determinism contract (see module docstring)
                while (not self._queue or self._inflight or self._paused) \
                        and not self._stop:
                    # timed wait = GC heartbeat: a store dropped without
                    # close() must not be kept alive by its parked workers
                    self._cv.wait(timeout=1.0)
                    if self._store() is None:
                        return
                if self._stop:
                    return
                job = self._queue.popleft()
                self._inflight += 1
            store = self._store()
            cont = None
            try:
                if not self._abort and store is not None:
                    if self._budget is None:
                        cont = job.run(store)
                    else:
                        # Shared worker budget: at most `budget` jobs run
                        # at once across all sibling shards' schedulers.
                        # Acquired outside the condition (no lock held), so
                        # a waiting shard never blocks another's turnstile;
                        # abort is re-checked after the wait.
                        with self._budget:
                            if not self._abort:
                                cont = job.run(store)
            except BaseException as e:    # worker must survive a failed job:
                cfg = store.config if store is not None else None
                tel = cfg.telemetry if cfg is not None else None
                job.retries += 1
                if cfg is not None and job.retries <= cfg.bg_max_retries \
                        and not self._abort and not self._stop:
                    # graceful degradation, stage 1 (§16.3): transient
                    # failures get bounded exponential backoff, then the
                    # same job re-runs from the front of the queue (its
                    # turnstile slot — determinism order is preserved)
                    store._stats.local().bg_retries += 1
                    if tel is not None:
                        tel.emit("bg_retry", job=type(job).__name__,
                                 attempt=job.retries, error=repr(e))
                    time.sleep(min(0.001 * (1 << (job.retries - 1)), 0.05))
                    with self._cv:
                        self._queue.appendleft(job)
                else:
                    # stage 2: retry budget exhausted — poison the pipeline
                    # and flip the store read-only (writes raise
                    # StoreDegradedError; reads keep serving)
                    if tel is not None:
                        tel.emit("bg_failure", job=type(job).__name__,
                                 error=repr(e), retries=job.retries - 1)
                    if store is not None:
                        store._stats.local().bg_gave_up += 1
                        # Degrade BEFORE publishing the failure: a writer
                        # that passed the store's _degraded check must not
                        # be the first to find the dead pipeline — submit()
                        # can only start refusing after the degraded flag
                        # is visible, and _rotate translates the residual
                        # window into the same StoreDegradedError.
                        store._enter_degraded(e)
                    with self._cv:        # a dead consumer would deadlock
                        if self._failure is None:   # writers at the stall
                            self._failure = e       # trigger escape
                        self._queue.clear()  # nothing will drain; idle()
                                             # goes True
            finally:
                store = None   # don't root the store across the idle wait
                with self._cv:
                    self._inflight -= 1
                    if cont is not None and not self._abort \
                            and self._failure is None:
                        self._queue.appendleft(cont)
                    drained = not self._queue and self._inflight == 0
                    self._cv.notify_all()
                hook = self.on_idle
                if drained and hook is not None and not self._abort:
                    try:
                        hook()     # flag-setting only; outside the condition
                    except Exception:
                        pass       # a broken hook must not kill the worker

    # ------------------------------------------------------------- lifecycle
    @property
    def aborting(self) -> bool:
        """Checked by jobs between pipeline stages (plan/merge/install)."""
        return self._abort

    def pending(self) -> int:
        with self._cv:
            return len(self._queue) + self._inflight

    def idle(self) -> bool:
        """Queue empty and nothing in flight (or the pipeline is dead).

        Lock-free peek — exact when the caller already holds the scheduler
        condition, which is the case inside ``wait_until`` predicates (the
        mutex is non-reentrant, so predicates must not call the locking
        accessors).  A failed pipeline reports idle so stalled writers
        escape instead of deadlocking; the failure surfaces on the next
        ``submit``/``wait_for_quiesce``.
        """
        return self._failure is not None or \
            (not self._queue and self._inflight == 0)

    def wait_until(self, pred: Callable[[], bool],
                   timeout: Optional[float] = None) -> bool:
        """Block the calling (foreground) thread until ``pred()`` holds;
        re-evaluated after every job completion (write-stall control)."""
        with self._cv:
            return self._cv.wait_for(pred, timeout)

    def wait_for_quiesce(self, timeout: Optional[float] = None) -> bool:
        """Block until the queue is drained and nothing is in flight.

        After a True return the store's levels are exactly what the
        synchronous engine would hold for the same op sequence (modulo any
        still-unrotated active memtable, which quiesce never flushes).
        Raises RuntimeError if a background job failed — a quiesce after a
        dead pipeline must be loud, not a plausible-looking settled tree.
        """
        with self._cv:
            ok = self._cv.wait_for(
                lambda: self._failure is not None
                or (not self._queue and self._inflight == 0), timeout)
            if self._failure is not None:
                raise RuntimeError(
                    "background compaction failed; the store's durable "
                    "state is intact — crash()+recover() to resume"
                ) from self._failure
            return ok

    def pause(self) -> None:
        """Stop popping new jobs (in-flight job finishes).  Holds the
        immutable-memtable read window open — used by tests to make the
        rotation pipeline observable deterministically.  A paused scheduler
        with queued work is not ``idle()``, so writes that hit the hard
        stall trigger will block until ``resume``; pause with the triggers
        disabled (tests do) or resume from another thread."""
        with self._cv:
            self._paused = True

    def resume(self) -> None:
        with self._cv:
            self._paused = False
            self._cv.notify_all()

    def abort_and_drain(self) -> None:
        """Crash path: discard all queued work and wait out the in-flight job.

        The abort flag makes the running job bail at its next safe point
        (its cleanup releases any input-version pin); queued jobs are
        dropped un-run.  Returns with the scheduler idle and reusable —
        ``recover()`` just starts submitting again.
        """
        store = self._store()
        tel = store.config.telemetry if store is not None else None
        if tel is not None:
            tel.emit("bg_abort", dropped=len(self._queue))
        with self._cv:
            self._abort = True
            self._queue.clear()
            self._cv.notify_all()
            self._cv.wait_for(lambda: self._inflight == 0)
            self._queue.clear()   # a bailing job may have pushed its cont
            self._abort = False
            self._failure = None  # crash wipes volatile state; the pipeline
                                  # is reusable after recover()

    def shutdown(self) -> None:
        """Stop the worker threads (final; the scheduler is not reusable)."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)

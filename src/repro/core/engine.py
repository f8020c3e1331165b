"""The Autumn LSM storage engine.

Composes memtable + WAL, immutable sorted runs, a pluggable merge policy
(Garnering by default), MVCC manifest, Monkey/Autumn bloom allocation, and a
RocksDB-style L0 rate limiter.  All reads/writes are accounted in the block
I/O cost model (types.IOStats) so the paper's Table 2 complexities can be
validated empirically.

With ``LSMConfig.async_compaction`` the flush/compaction pipeline moves off
the write path onto a background ``CompactionScheduler`` (DESIGN.md §11):
full memtables rotate into a readable immutable queue, workers install
versions in the exact synchronous order (sync mode stays the bit-for-bit
differential oracle after ``wait_for_quiesce``), and write pressure is
governed by ``slowdown_trigger``/``stall_trigger``.  The engine is
single-writer multi-reader: one thread writes; readers are lock-free on
copy-on-write level/queue references and immutable runs.  IOStats counters
are accumulated **losslessly** through a :class:`~repro.core.types.StatsHub`:
every thread mutates its own private shard (no lock, no lost ``+=``
read-modify-writes between scheduler workers and foreground threads) and
``store.stats`` merges the shards fieldwise at read time.

Optional telemetry (DESIGN.md §14): ``LSMConfig.telemetry`` carries a
:class:`~repro.core.telemetry.Telemetry` facade.  When ``None`` (default)
every instrumentation site is a single attribute load + ``is None`` test;
when set, public ops record per-op-class latency into per-thread histograms
(no locks on the read path) and lifecycle paths (flush/compaction/stall/
view-rebuild) emit trace events.  Always on, whatever ``telemetry`` says:
the ``IOStats`` timers of multi_get, the device probe, flush, compaction
and scan, and the profiler spans (``lsm.*``) that ``telemetry.span`` opens
at the same sites while a ``jax.profiler`` session records.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .bloom import allocate_fprs, bits_for_fpr
from .cache import BlockCache, PinnedLevelManager
from .faults import CorruptionError, FaultInjector, StoreDegradedError
from .iterator import MergingIterator, combined_mem_items
from .manifest import Manifest, RunStorage, Version
from .memtable import ImmutableMemtable, Memtable, WriteAheadLog
from .policy import CompactionTask, MergePolicy, make_policy
from .run import SortedRun, build_run, merge_runs
from .scheduler import CompactJob, CompactionScheduler, FlushJob
from .telemetry import Telemetry, span, tracing
from .tuner import OnlineTuner, TunerStep
from .types import (BLOCK_SIZE, KEY_BYTES, KEY_DTYPE, SEQ_DTYPE,
                    TOMBSTONE_LEN, IOStats, StatsHub)
from .view import RangeView, build_range_view

# Soft write-pressure delay.  LevelDB sleeps 1 ms here, but its pressure unit
# is a 4 MB L0 file; ours is a ~32 KB memtable whose whole fill takes well
# under 1 ms — and on coarse-tick kernels (CONFIG_HZ=100) any nonzero sleep
# rounds up to a full 1-10 ms scheduler tick.  sleep(0) instead *yields* the
# GIL and the CPU slice to the compaction workers, which is the actual goal
# of the soft trigger; the hard stall_trigger remains the memory backstop.
_SLOWDOWN_SLEEP_S = 0.0


@dataclasses.dataclass
class LSMConfig:
    policy: str = "garnering"
    T: float = 2.0
    c: float = 0.8                      # Garnering scaling factor (c=1 => Leveling)
    memtable_bytes: int = 1 << 20       # 1 MiB write buffer
    base_level_bytes: int = 10 << 20    # max_bytes_for_level_base (OptimizeForSmallDb)
    l0_compaction_trigger: int = 4
    l0_stop_writes_trigger: int = 12    # rate limiter (level0_stop_writes_trigger)
    bits_per_key: float = 0.0           # 0 => no bloom filters
    bloom_allocation: str = "uniform"   # "uniform" | "monkey"
    wal_fsync_every_write: bool = False # False => fsync at flush (db default)
    block_size: int = BLOCK_SIZE
    key_bytes: int = KEY_BYTES
    use_pallas_bloom: bool = False      # route multi_get probes AND filter
                                        # rebuilds through the device hash
                                        # family (kernels.ops)
    use_pallas_merge: bool = False      # route compaction's pairwise merges
                                        # through the bitonic merge-path
                                        # kernel (kernels.ops)
    cache_bytes: int = 0                # block cache budget; 0 => no cache
    pin_l0_bytes: int = 0               # DRAM-resident L0 budget (paper's
                                        # "bounded space of DRAM"); 0 => none
    cache_policy: str = "clock"         # "clock" (second-chance) | "lru"
    async_compaction: bool = False      # pipeline flush+compaction onto
                                        # background workers (DESIGN.md §11);
                                        # False == today's synchronous engine,
                                        # the differential oracle
    compaction_workers: int = 1         # background worker threads
    slowdown_trigger: int = 64          # queued L0 runs + immutable memtables
                                        # beyond which each rotation yields
                                        # its CPU slice to the workers (soft
                                        # pressure); <=0 disables.  Triggers
                                        # count ~memtable_bytes units, so 64
                                        # = ~2 MiB of deferred flushes at the
                                        # default write buffer
    stall_trigger: int = 256            # ... beyond which rotation blocks
                                        # until the backlog drains below the
                                        # trigger or the workers go idle
                                        # (hard pressure, ~8 MiB memory
                                        # backstop); <=0 disables
    shards: int = 1                     # >1: `make_store` builds a
                                        # ShardedLSMStore — N independent
                                        # range-partitioned LSMStores behind
                                        # one facade with parallel per-shard
                                        # schedulers and a shared budgeted
                                        # BlockCache (DESIGN.md §12).  Plain
                                        # LSMStore ignores this field.
    use_range_views: bool = False       # REMIX-style cross-run range views
                                        # (DESIGN.md §13): a globally-sorted
                                        # key index over every run, rebuilt
                                        # off the write path (scheduler
                                        # workers in async mode, lazily by
                                        # the first reader in sync mode), so
                                        # scan/seek cost one binary search +
                                        # one sequential sweep instead of a
                                        # per-refill multi-way merge.  The
                                        # MergingIterator remains both the
                                        # stale-view fallback and (with
                                        # scan_scalar) the differential
                                        # oracle.
    shard_splitters: Optional[Tuple[int, ...]] = None
                                        # order-preserving range splitters
                                        # (shards-1 ascending uint64 bounds;
                                        # key k lives in the first shard
                                        # with k < splitter).  None =>
                                        # uniform split of the full uint64
                                        # space (right for hashed keys —
                                        # kvcache/checkpoint; pass explicit
                                        # splitters for dense key ranges)
    telemetry: Optional[Telemetry] = None
                                        # latency histograms + event trace
                                        # (DESIGN.md §14).  None (default)
                                        # disables all instrumentation — the
                                        # only residual cost is an `is None`
                                        # test per public op.  The sharded
                                        # facade hands its live config to
                                        # every shard, so one Telemetry
                                        # aggregates across shards for free.
    rebalance_interval_ops: int = 0     # sharded facade only (DESIGN.md §15):
                                        # re-check per-shard load imbalance
                                        # every N routed ops (and at
                                        # scheduler-idle boundaries).  0
                                        # (default) disables rebalancing —
                                        # static splitters, bit-for-bit the
                                        # PR-5 behavior.  Plain LSMStore
                                        # ignores this field.
    rebalance_ratio: float = 2.0        # imbalance trigger: rebalance when
                                        # max/mean per-shard op share over
                                        # the current window exceeds this
                                        # (1.0 = perfectly balanced, N =
                                        # fully skewed into one shard)
    paranoid_checks: bool = False       # verify per-block checksums on every
                                        # point-read/seek block touch
                                        # (DESIGN.md §16.2); a mismatch
                                        # raises CorruptionError.  Recovery
                                        # scrubs regardless of this flag.
    faults: Optional["FaultInjector"] = None
                                        # fault-injection hooks (§16.1).
                                        # None (default) disables every
                                        # site at the cost of one `is None`
                                        # test — the same zero-overhead
                                        # contract as `telemetry`.
    bg_max_retries: int = 2             # background flush/compaction retry
                                        # budget (bounded exponential
                                        # backoff, §16.3); past it the job
                                        # is abandoned and the store
                                        # degrades read-only
    tuner: Optional[OnlineTuner] = None
                                        # online workload-adaptive tuner
                                        # (DESIGN.md §17): senses windowed
                                        # IOStats/Telemetry deltas and
                                        # hill-climbs c/T, the cache↔pin
                                        # split, slowdown_trigger, and the
                                        # facade's worker budget — applied
                                        # only at compaction-chain/quiesce
                                        # boundaries via apply_tuning().
                                        # None (default): zero overhead
                                        # beyond one `is None` test per
                                        # write, same contract as telemetry.
                                        # Needs `telemetry` to sense; inert
                                        # without it.


class LSMStore:
    def __init__(self, config: Optional[LSMConfig] = None, *,
                 scheduler_budget=None, scheduler_offset: int = 0):
        # scheduler_budget / scheduler_offset: sharded-facade wiring (a
        # shared worker-budget semaphore and a core-spreading offset handed
        # to this store's CompactionScheduler, DESIGN.md §12).  Plain
        # single-store use leaves both at their defaults.
        self.config = config or LSMConfig()
        self.policy: MergePolicy = make_policy(
            self.config.policy, T=self.config.T, c=self.config.c,
            l0_trigger=self.config.l0_compaction_trigger)
        self._stats = StatsHub()
        self.storage = RunStorage()
        self.manifest = Manifest(self.storage)
        self.memtable = Memtable(self.config.memtable_bytes,
                                 self.config.key_bytes,
                                 self.config.block_size)
        self.wal = WriteAheadLog()
        self._levels: List[List[SortedRun]] = [[]]
        self._max_level = 1
        self._seq = 0
        # Graceful degradation (DESIGN.md §16.3): set to the root failure
        # when the background pipeline exhausts its retry budget.  Writes
        # then raise StoreDegradedError; reads keep serving the committed
        # tree (no lock — a single attribute test on the write path).
        self._degraded: Optional[BaseException] = None
        # Set once the root pipeline failure has been surfaced to a caller
        # through wait_for_quiesce (close() raises via the same call);
        # close() afterwards is an idempotent, loss-free no-raise cleanup
        # instead of a second raise.  Write-path StoreDegradedError is a
        # *rejection*, not the surfacing — it can fire many times without
        # consuming the one loud raise of the underlying failure.
        self._bg_failure_surfaced = False
        # Async compaction (DESIGN.md §11): rotated memtables queue here
        # (oldest first) and stay readable until their background flush
        # installs; the maintenance lock serializes the gc+retain+repin
        # triplet between worker installs and snapshot releases.
        self._imm: List[ImmutableMemtable] = []
        self._maint_lock = threading.Lock()
        # Ids of flushed memtables: the spans of a rotation, its flush and
        # the compactions that flush causes carry the same one.
        self._memtable_ids = itertools.count(1)
        # Online tuning (DESIGN.md §17).  The tuner is cached on the store
        # so the per-write check is one attribute + `is None` test (the
        # telemetry zero-overhead contract); bind() makes this store the
        # single driver — sharded facades hand shards tuner=None configs
        # and bind the facade instead.
        self._tuner = self.config.tuner
        self._tune_ops = 0
        self._tune_armed = False
        if self._tuner is not None:
            self._tuner.bind(self)
        # REMIX-style cross-run range view (DESIGN.md §13).  The view is a
        # snapshot of one published ``self._levels`` object; freshness is a
        # pointer compare (copy-on-write installs swap the list object), so
        # invalidation is free.  ``_view_cache`` memoizes per-level sorted
        # columns keyed by run-id tuple so rebuilds only re-merge levels
        # whose membership actually changed.
        self._range_view: Optional[RangeView] = None
        self._view_cache: dict = {}
        self._scheduler: Optional[CompactionScheduler] = None
        if self.config.async_compaction:
            self._scheduler = CompactionScheduler(
                self, self.config.compaction_workers,
                budget=scheduler_budget, worker_offset=scheduler_offset)
        self.block_cache: Optional[BlockCache] = None
        self.pinned_l0: Optional[PinnedLevelManager] = None
        if self.config.cache_bytes > 0 or self.config.pin_l0_bytes > 0:
            self.configure_cache(self.config.cache_bytes,
                                 self.config.pin_l0_bytes,
                                 self.config.cache_policy)

    @property
    def stats(self) -> IOStats:
        """Merged view of every thread's counter shard (a fresh IOStats —
        ``.snapshot()``/``.delta()``/field reads all behave as before; the
        lossless-accumulation design is :class:`~repro.core.types.StatsHub`).
        Internal mutation sites never touch this property — they charge the
        calling thread's shard via ``self._stats.local()``."""
        return self._stats.merged()

    @property
    def telemetry(self) -> Optional[Telemetry]:
        return self.config.telemetry

    # ------------------------------------------------------ degraded mode
    @property
    def degraded(self) -> bool:
        """True when persistent background failure flipped the store
        read-only (§16.3); cleared by ``crash()`` + ``recover()``."""
        return self._degraded is not None

    def _enter_degraded(self, exc: BaseException) -> None:
        """Flip read-only (idempotent; called by the scheduler worker when
        a background job exhausts its retry budget)."""
        if self._degraded is None:
            self._degraded = exc
            tel = self.config.telemetry
            if tel is not None:
                tel.emit("degraded", error=repr(exc))

    def _raise_degraded(self) -> None:
        raise StoreDegradedError(
            "store is read-only after persistent background failure; "
            "reads keep serving — crash()+recover() to restore writes"
        ) from self._degraded

    def _wal_fsync(self, st: IOStats) -> None:
        """fsync the active WAL, charging ``st`` and (when telemetry is on)
        recording the fsync latency — the single helper every durability
        point uses so the ``wal_fsync`` histogram sees all of them."""
        f = self.config.faults
        if f is not None:
            f.check("wal_fsync")
        tel = self.config.telemetry
        with span("lsm.wal_fsync"):
            if tel is None:
                self.wal.fsync(st)
                return
            t0 = time.perf_counter_ns()
            self.wal.fsync(st)
            tel.record("wal_fsync", time.perf_counter_ns() - t0)

    def configure_cache(self, cache_bytes: int, pin_l0_bytes: int = 0,
                        policy: Optional[str] = None) -> None:
        """(Re)build the memory subsystem on a live store.

        Replaces any existing cache (contents are dropped) and immediately
        repins the current L0 within the new budget.  Passing zeros detaches
        the cache and reverts every read path to raw block accounting.
        ``policy=None`` keeps the store's configured ``cache_policy``.
        """
        self.config.cache_bytes = int(cache_bytes)
        self.config.pin_l0_bytes = int(pin_l0_bytes)
        if policy is not None:
            self.config.cache_policy = policy
        policy = self.config.cache_policy
        if cache_bytes <= 0 and pin_l0_bytes <= 0:
            self.block_cache = None
            self.pinned_l0 = None
            return
        self.block_cache = BlockCache(cache_bytes, policy)
        self.block_cache.telemetry = self.config.telemetry
        self.pinned_l0 = PinnedLevelManager(self.block_cache, pin_l0_bytes)
        # attaching mid-life: resident L0 blocks must be loaded (charged)
        with self._maint_lock:
            self.pinned_l0.repin(self._levels[0], stats=self._stats.local())

    def attach_cache(self, cache, pin_l0_bytes: int = 0) -> None:
        """Attach an externally owned cache object (the sharded facade's
        namespaced ``BlockCacheView`` of the shared ``BlockCache``,
        DESIGN.md §12) instead of building a private one.

        The object must speak the BlockCache read/retain/pin protocol;
        every read path and the commit-time invalidation triplet use it
        exactly as they use a private cache.  Pins the current L0 within
        ``pin_l0_bytes`` immediately (charged: a mid-life attach's resident
        blocks are real reads, same as :meth:`configure_cache`).
        """
        self.block_cache = cache
        self.pinned_l0 = PinnedLevelManager(cache, pin_l0_bytes)
        with self._maint_lock:
            self.pinned_l0.repin(self._levels[0], stats=self._stats.local())

    # ------------------------------------------------------------- writes
    def put(self, key: int, value: bytes):
        tel = self.config.telemetry
        if tel is None:
            self._write(key, value)
        else:
            t0 = time.perf_counter_ns()
            self._write(key, value)
            tel.record("put", time.perf_counter_ns() - t0)
        if self._tuner is not None:
            self._maybe_tune(1)

    def delete(self, key: int):
        tel = self.config.telemetry
        if tel is None:
            self._write(key, None)
        else:
            t0 = time.perf_counter_ns()
            self._write(key, None)
            tel.record("put", time.perf_counter_ns() - t0)
        if self._tuner is not None:
            self._maybe_tune(1)

    def _write(self, key: int, value: Optional[bytes]):
        if self._degraded is not None:
            self._raise_degraded()
        f = self.config.faults
        if f is not None:
            f.check("wal_append")  # before any mutation: a failed append
                                   # leaves no partial record anywhere
        st = self._stats.local()
        self._seq += 1
        if tracing():       # one check per write: no span object when off
            with span("lsm.wal_append", bytes=len(value or b"")):
                self._log(key, value, st)
            with span("lsm.memtable_put"):
                self.memtable.put(int(key), self._seq, value)
        else:
            self._log(key, value, st)
            self.memtable.put(int(key), self._seq, value)
        if self.memtable.is_full():
            self._on_memtable_full()

    def _log(self, key: int, value: Optional[bytes], st: IOStats) -> None:
        """Append one write to the active WAL under the current seq (and
        fsync it when every write must be durable)."""
        self.wal.append(1 if value is None else 0, key, self._seq,
                        value or b"", st)
        if self.config.wal_fsync_every_write:
            self._wal_fsync(st)

    # ------------------------------------------------------- batched writes
    def put_batch(self, keys, values) -> None:
        """Batched puts: semantically ``[put(k, v) for k, v in zip(...)]``.

        ``values`` is either a sequence aligned with ``keys`` or a single
        ``bytes`` broadcast to every key.  See :meth:`write_batch`.
        """
        if isinstance(values, (bytes, bytearray)):
            values = [bytes(values)] * len(keys)
        tel = self.config.telemetry
        if tel is None:
            self._write_batch(zip(keys, values))
        else:
            t0 = time.perf_counter_ns()
            self._write_batch(zip(keys, values))
            tel.record("put_batch", time.perf_counter_ns() - t0)
        if self._tuner is not None:
            self._maybe_tune(len(keys))

    def delete_batch(self, keys) -> None:
        """Batched deletes: semantically ``[delete(k) for k in keys]``."""
        self.write_batch((k, None) for k in keys)

    def write_batch(self, ops: Iterable[Tuple[int, Optional[bytes]]]) -> None:
        tel = self.config.telemetry
        if tel is None:
            self._write_batch(ops)
        else:
            t0 = time.perf_counter_ns()
            self._write_batch(ops)
            tel.record("write_batch", time.perf_counter_ns() - t0)
        if self._tuner is not None:
            self._maybe_tune(1)

    def _write_batch(self, ops: Iterable[Tuple[int, Optional[bytes]]]) -> None:
        """Batched puts + deletes (value=None), the vectorized ingest lane.

        Bit-for-bit equivalent to the scalar write loop — same WAL bytes,
        same sequence numbers, same memtable state, and same flush
        boundaries, hence identical IOStats — but the work is amortized:
        each chunk appends one vectorized WAL batch record, bulk-inserts
        into the memtable, and checks the flush trigger once.  Chunks are
        sized so no *intermediate* insert could have filled the memtable
        (entry sizes only shrink when an overwrite refunds bytes, so the
        running upper bound is safe); a chunk degenerates to one entry only
        when that single entry might fill it — exactly where the scalar
        loop would flush.  With ``wal_fsync_every_write`` the batch fsyncs
        once per chunk (group commit) instead of once per record; that is
        the only accounting difference from the scalar loop.
        """
        pairs = list(ops)
        n = len(pairs)
        if n == 0:
            return
        if self._degraded is not None:
            self._raise_degraded()
        faults = self.config.faults
        st = self._stats.local()
        keys_l, vals_l = zip(*pairs)
        keys_l = list(map(int, keys_l))
        # one pass of column prep for the whole batch; chunks take views
        keys_arr = np.fromiter(keys_l, np.uint64, n)
        vlens = np.fromiter(
            (len(v) if v is not None else 0 for v in vals_l), np.int64, n)
        ops_arr = np.fromiter((v is None for v in vals_l), np.uint8, n)
        kb = self.memtable.key_bytes
        cum = np.cumsum(vlens + kb)
        i = 0
        while i < n:
            room = self.memtable.capacity_bytes - self.memtable.size_bytes
            base = int(cum[i - 1]) if i else 0
            # first index whose running total reaches the bound — O(log n)
            # on the uncut cumsum, no per-chunk array copy
            j = max(i + 1,
                    int(np.searchsorted(cum, base + room, side="left")))
            chunk_vals = vals_l[i:j]
            if faults is not None:
                faults.check("wal_append")  # per chunk, before mutation
            first_seq = self._seq + 1
            self._seq += j - i
            self.wal.append_batch_cols(
                chunk_vals, keys_arr[i:j], ops_arr[i:j], vlens[i:j],
                first_seq, st)
            if self.config.wal_fsync_every_write:
                self._wal_fsync(st)
            self.memtable.put_batch(keys_l[i:j], chunk_vals, first_seq,
                                    added=int(cum[j - 1] - base))
            if self.memtable.is_full():
                self._on_memtable_full()
            i = j

    def fsync_wal(self) -> None:
        """Explicit durability barrier on the active WAL (group commit for
        callers that batch writes and fsync once, e.g. the checkpoint
        store's save path)."""
        self._wal_fsync(self._stats.local())

    def _on_memtable_full(self):
        """Full write buffer: flush inline (sync) or rotate + enqueue (async).

        Rotation happens at exactly the point the synchronous engine would
        flush, so the memtable contents handed to the background worker are
        identical to what the sync path freezes — the root of the
        differential-oracle guarantee (DESIGN.md §11).
        """
        if self._scheduler is None:
            self.flush()
        else:
            self._rotate()

    def flush(self):
        """Freeze the memtable into an L0 run (no merge — §3.2 L0 tiering).

        Async mode (``LSMConfig.async_compaction``): the call only rotates
        the memtable into the immutable queue and returns — the run build,
        version install, and any triggered compactions all happen on the
        scheduler's workers.  ``wait_for_quiesce`` blocks until that
        background pipeline drains.
        """
        if self._scheduler is not None:
            self._rotate()
            return
        if len(self.memtable) == 0:
            return
        st = self._stats.local()
        # Rate limiter: too many L0 runs => write stall until compaction.
        if len(self._levels[0]) >= self.config.l0_stop_writes_trigger:
            st.write_stalls += 1
            self._compact_until_quiet()
        tel = self.config.telemetry
        mid = next(self._memtable_ids)
        entries = len(self.memtable)
        tok = tel.emit("flush_start", entries=entries) \
            if tel is not None else 0
        t0, c0 = time.perf_counter_ns(), time.thread_time_ns()
        with span("lsm.flush", memtable=mid, entries=entries):
            self._wal_fsync(st)
            f = self.config.faults
            if f is not None:
                f.check("flush_write")
            run = self._build_l0(self.memtable, st)
            # The WAL/memtable are released only *after* the manifest fsync
            # in _commit(): if that fsync fails, the flushed records are
            # still in the (fsynced) WAL and crash()+recover() replays them
            # — releasing first would turn a manifest fault into silent
            # data loss.
            self.memtable.clear()
            self.wal.truncate()
        dur = time.perf_counter_ns() - t0
        st.flush_ns += dur
        st.flush_cpu_ns += time.thread_time_ns() - c0
        if tel is not None:
            tel.record("flush", dur)
            tel.emit("flush_end", token=tok, entries=len(run),
                     t0=t0, dur_ns=dur)
        self._compact_until_quiet(cause=mid)

    def _build_l0(self, memtable: Memtable, st: IOStats) -> SortedRun:
        """The flush body both paths share: freeze ``memtable`` into a run
        and install it as the newest L0 run (an atomic list swap: readers
        never see a torn L0)."""
        with span("lsm.run_build", entries=len(memtable)):
            run = memtable.to_run(self._bits_for_level(0), st,
                                  hash_fn=self._bloom_hash_fn())
        if len(run):
            levels = [list(lvl) for lvl in self._levels]
            levels[0].append(run)  # newest last
            self._levels = levels
            self._commit()
        return run

    # ------------------------------------------------- async rotation path
    def _rotate(self):
        """Foreground half of a pipelined flush (async mode).

        Applies write-pressure control, fsyncs the WAL (the rotated
        segment's durability point — same one-fsync-per-flush cadence as the
        sync path), freezes the memtable + WAL pair into the immutable
        queue where it stays readable, and enqueues the background
        :class:`FlushJob`.  The engine is single-writer: only the foreground
        thread rotates, only scheduler workers install.
        """
        if len(self.memtable) == 0:
            return
        mid = next(self._memtable_ids)
        depth = len(self._imm) + len(self._levels[0])
        with span("lsm.rotate", memtable=mid, depth=depth):
            self._throttle(depth)
            self._wal_fsync(self._stats.local())
            imm = ImmutableMemtable(self.memtable, self.wal, mid)
        with self._scheduler.lock:
            self._imm = self._imm + [imm]   # copy-on-write: readers hold refs
        self.memtable = Memtable(self.config.memtable_bytes,
                                 self.config.key_bytes,
                                 self.config.block_size)
        self.wal = WriteAheadLog()
        try:
            self._scheduler.submit(FlushJob(imm))
        except RuntimeError as exc:
            # Raced the worker poisoning the pipeline: this rotation's write
            # passed the _degraded check an instant before the failure was
            # published.  The write is ACCEPTED, not rejected — its record
            # is already in the rotated segment (appended + fsynced above)
            # and stays readable from the immutable queue; the flush will
            # never run, but close() folds the queue back into the sync
            # path and crash()+recover() replays the fsynced WAL, so
            # nothing acknowledged is lost.  Raising here would reject a
            # write that is already durable state.  The *next* write gets
            # the clean StoreDegradedError from the _degraded fast check:
            # the worker sets that flag before publishing the failure
            # submit() just saw, so it is guaranteed visible by now.  A
            # cause-less RuntimeError is "scheduler is shut down" — a
            # lifecycle error, not degradation — and propagates unchanged.
            if exc.__cause__ is None:
                raise
            self._enter_degraded(exc.__cause__)

    def _throttle(self, depth: int):
        """LevelDB-style write-pressure control at rotation points.

        Pressure (``depth``) = queued L0 runs + immutable memtables.  At
        ``slowdown_trigger`` each rotation yields its CPU slice to the
        workers (see ``_SLOWDOWN_SLEEP_S``); at ``stall_trigger`` the
        rotation blocks until the scheduler drains below the trigger (or
        goes idle — steady-state L0 pressure cannot drain further).  Both
        charge ``IOStats.stall_ns`` so benchmarks can report the foreground
        time actually lost to pressure (``stall_pct``).
        """
        cfg = self.config
        st = self._stats.local()
        tel = cfg.telemetry
        t0 = time.perf_counter_ns()
        if cfg.stall_trigger > 0 and depth >= cfg.stall_trigger:
            st.write_stalls += 1
            tok = tel.emit("stall_enter", depth=depth) if tel is not None \
                else 0
            # A stall only waits while the background can still shrink the
            # backlog; once the scheduler is idle the pressure is the tree's
            # steady state (e.g. L0 legitimately holds l0_trigger-1 runs)
            # and waiting longer would deadlock the writer.
            sched = self._scheduler
            with span("lsm.throttle", kind="stall", depth=depth):
                sched.wait_until(
                    lambda: sched.idle()
                    or (len(self._imm) + len(self._levels[0]))
                    < cfg.stall_trigger)
            dt = time.perf_counter_ns() - t0
            if tel is not None:
                tel.record("stall", dt)
                tel.emit("stall_exit", token=tok, depth=depth,
                         t0=t0, dur_ns=dt)
        elif cfg.slowdown_trigger > 0 and depth >= cfg.slowdown_trigger:
            st.write_slowdowns += 1
            with span("lsm.throttle", kind="slowdown", depth=depth):
                time.sleep(_SLOWDOWN_SLEEP_S)
            dt = time.perf_counter_ns() - t0
            if tel is not None:
                tel.record("stall", dt)
                tel.emit("slowdown", depth=depth, t0=t0, dur_ns=dt)
        else:
            return
        st.stall_ns += time.perf_counter_ns() - t0

    def wait_for_quiesce(self, timeout: Optional[float] = None) -> bool:
        """Block until all background flush/compaction work has drained.

        After a True return the tree (levels, keys, seqs, values) is
        state-identical to the synchronous engine's for the same op
        sequence — the async-vs-sync differential contract.  The active
        (unrotated) memtable is *not* flushed; call ``flush()`` first to
        rotate it.  Sync mode returns True immediately.
        """
        if self._scheduler is None:
            return True
        try:
            ok = self._scheduler.wait_for_quiesce(timeout)
        except RuntimeError:
            # the pipeline failure has now been surfaced to the caller;
            # close() afterwards is an idempotent no-raise cleanup
            self._bg_failure_surfaced = True
            raise
        if ok and self._tuner is not None and self._tune_armed:
            # a drained pipeline is a tuning boundary too (§17)
            self.apply_tuning()
        return ok

    # --------------------------------------------- online tuning (§17)
    def _maybe_tune(self, k: int = 1) -> None:
        """Cheap write-boundary tuning trigger (the facade's
        ``_maybe_rebalance`` shape): count ops; once ``interval_ops``
        elapse, arm, and fire at the first compaction-chain boundary —
        immediately in sync mode (every inter-op point is one), at the
        next scheduler-idle check in async mode."""
        tun = self._tuner
        self._tune_ops += k
        if not self._tune_armed:
            if self._tune_ops < tun.interval_ops:
                return
            self._tune_armed = True
        sched = self._scheduler
        if sched is not None and not sched.idle():
            return
        self._tune_ops = 0
        self._tune_armed = False
        tun.tick(self)

    def apply_tuning(self) -> Optional[TunerStep]:
        """Run one tuner tick now iff the store is at a boundary.

        The single actuation entry point (DESIGN.md §17): changes land only
        here — with the scheduler idle (sync mode always is, between ops) —
        so COW readers and the bit-for-bit oracles are never perturbed
        mid-op.  Returns the decision, or None when not at a boundary, the
        tuner is absent/unbound, or the window was too small to decide.
        """
        tun = self._tuner
        if tun is None:
            return None
        if self._scheduler is not None and not self._scheduler.idle():
            return None
        self._tune_ops = 0
        self._tune_armed = False
        return tun.tick(self)

    def retune_policy(self, *, T: Optional[float] = None,
                      c: Optional[float] = None) -> None:
        """Swap in a same-family policy with new knobs (tuner actuator).

        Only *future* ``plan()`` calls see the new capacities — the
        installed tree is never rewritten; overflow against the new
        schedule resolves through normal compaction churn.  The swap is a
        single reference assignment; call at a boundary (``apply_tuning``
        does) so no planned-but-unapplied task straddles the change."""
        cfg = self.config
        if T is not None:
            cfg.T = float(T)
        if c is not None:
            cfg.c = float(c)
        self.policy = self.policy.retuned(T=cfg.T, c=cfg.c)

    def set_cache_split(self, pin_l0_bytes: int) -> None:
        """Move budget between the block cache and the pinned-L0 slice at
        constant total memory (tuner actuator).  Gentle, unlike
        ``configure_cache``: the cache resizes in place (a shrink sheds
        only its coldest bytes) and the L0 repins under the new budget."""
        if self.block_cache is None or self.pinned_l0 is None:
            return
        cfg = self.config
        total = cfg.cache_bytes + cfg.pin_l0_bytes
        pin = max(0, min(int(pin_l0_bytes), total))
        cfg.pin_l0_bytes = pin
        cfg.cache_bytes = total - pin
        self.block_cache.resize(cfg.cache_bytes)
        self.pinned_l0.pin_l0_bytes = pin
        with self._maint_lock:
            self.pinned_l0.repin(self._levels[0], stats=self._stats.local())

    def compact_to_shape(self, max_merges: int = 64) -> int:
        """Maintenance compaction: fold the tree to the policy's shape.

        ``retune_policy`` deliberately never rewrites the installed tree —
        but when a retune *widens* the capacity schedule (larger ``T``,
        smaller ``c``) every level of the old, deeper shape sits under its
        new cap, so no organic compaction ever fires and reads keep paying
        the old shape's per-level cost indefinitely.  This is the explicit
        maintenance window (RocksDB's manual ``CompactRange`` shape): merge
        the shallowest populated deep level into the next one until the
        populated-level count matches ``policy.predicted_levels`` for the
        current data size, then let the normal planner settle any overflow
        the folding introduced.  L0 is left to its own trigger (it is the
        flush buffer, usually DRAM-pinned).  Runs through the same
        ``_apply`` as every other compaction, so COW publication, cache
        retention, and view invalidation all hold.  Call at a quiesce
        boundary (async callers drain first; returns 0 when not idle).
        Returns the number of maintenance merges performed.
        """
        if self._scheduler is not None and not self._scheduler.idle():
            return 0
        self._compact_until_quiet()     # settle organic triggers first
        pred = getattr(self.policy, "predicted_levels", None)
        merges = 0
        while merges < max_merges:
            deep = [i for i, lvl in enumerate(self._levels) if lvl and i >= 1]
            if len(deep) < 2 or pred is None:
                break
            total = sum(r.data_bytes
                        for lvl in self._levels for r in lvl)
            target = max(1, int(math.ceil(
                pred(total, self.config.base_level_bytes))))
            if len(deep) <= target:
                break
            src, dst = deep[0], deep[1]
            task = CompactionTask(
                src, dst, True, "reshape",
                src_run_ids=tuple(r.run_id for r in self._levels[src]))
            if not self._apply(task):
                break       # tree changed under us: stop, planner recovers
            merges += 1
        if merges:
            # the folds changed level sizes; re-settle, then drop the
            # monotone level-count watermark to the real new depth so
            # future capacity schedules price the reshaped tree
            self._compact_until_quiet()
            self._max_level = max(
                (i for i, lvl in enumerate(self._levels) if lvl), default=1)
            tel = self.config.telemetry
            if tel is not None:
                tel.emit("reshape", merges=merges,
                         levels=len([l for l in self._levels if l]))
        return merges

    def _tuning_actuators(self):
        """Knob accessors the tuner hill-climbs: {name: (get, set)}.

        Only knobs that exist on this store are offered — no ``pin_frac``
        without a memory subsystem, no ``slowdown_trigger`` without the
        async pressure path (sync mode never throttles).  The facade
        overrides this with its shard-wide twin."""
        acts = {
            "c": (lambda: self.policy.c,
                  lambda v: self.retune_policy(c=v)),
            "T": (lambda: self.policy.T,
                  lambda v: self.retune_policy(T=v)),
        }
        if self._scheduler is not None:
            acts["slowdown_trigger"] = (
                lambda: self.config.slowdown_trigger,
                lambda v: setattr(self.config, "slowdown_trigger", int(v)))
        if self.block_cache is not None and self.pinned_l0 is not None:
            acts["pin_frac"] = (self._get_pin_frac, self._set_pin_frac)
        return acts

    def _get_pin_frac(self) -> float:
        total = self.config.cache_bytes + self.config.pin_l0_bytes
        return self.config.pin_l0_bytes / total if total else 0.0

    def _set_pin_frac(self, v: float) -> None:
        total = self.config.cache_bytes + self.config.pin_l0_bytes
        self.set_cache_split(int(total * float(v)))

    def close(self) -> None:
        """Drain and stop the background workers (async mode).

        The store stays fully usable afterwards — it simply reverts to the
        synchronous flush/compaction path, which is state-equivalent.  Used
        by tests and benchmarks so short-lived stores don't accumulate
        parked worker threads.  No-op in sync mode.

        On a failed/degraded pipeline, close() raises the background
        failure the *first* time it is surfaced — but always completes the
        full cleanup (worker shutdown + stranded-rotation fold-back) before
        raising, and every subsequent close() is an idempotent no-raise
        no-op (§16.3): the failure must be loud exactly once, never lost,
        and never doubled.
        """
        sched = self._scheduler
        if sched is None:
            return
        surfaced = self._bg_failure_surfaced
        try:
            sched.wait_for_quiesce()   # raises on a dead pipeline
        except BaseException:
            self._bg_failure_surfaced = True
            if not surfaced:
                raise                  # finally still completes the cleanup
        finally:
            # shutdown() joins the workers, so by the time the fold-back
            # below runs no job can race the immutable queue — the failed
            # job's error can never resurface from _consolidate_imm_wal
            # with the scheduler already aborted.
            sched.shutdown()
            self._scheduler = None
            if self._imm:
                # A dead pipeline left rotated memtables stranded (the
                # exception fired before their flush installed).  The sync
                # path never reads the immutable queue, so fold them back
                # into the active WAL + memtable — durability and readable
                # state unchanged.
                self._consolidate_imm_wal()
            # With the workers gone and every rotation folded back the
            # store is loss-free on the synchronous path — degraded mode
            # (a property of the dead background pipeline) ends here.
            self._degraded = None

    def _consolidate_imm_wal(self) -> int:
        """Fold the immutable queue's WAL segments into one active log.

        Segment concatenation (oldest first, active last) is record
        concatenation, so replay order equals write order; the rotated
        segments were fully fsynced at rotation, so the consolidated synced
        watermark is their total length plus the active WAL's own
        watermark.  The memtable is rebuilt by replaying every record
        (including the unsynced tail — that is live process state, exactly
        what the active memtable held).  Shared by ``recover`` and the
        failed-pipeline ``close`` fold-back so the durability bookkeeping
        cannot drift between them.  Returns the number of records replayed.
        """
        wal = WriteAheadLog()
        buf = bytearray()
        synced = 0
        for imm in self._imm:
            buf += imm.wal._buf
            synced += len(imm.wal._buf)       # fully fsynced at rotation
        synced += self.wal._synced_upto
        buf += self.wal._buf
        wal._buf = buf
        wal._synced_upto = synced
        self.wal = wal
        self._imm = []
        self.memtable = Memtable(self.config.memtable_bytes,
                                 self.config.key_bytes,
                                 self.config.block_size)
        n = 0
        for op, key, seq, value in self.wal.records():
            n += 1
            self._seq = max(self._seq, seq)
            self.memtable.put(key, seq, None if op == 1 else value)
        return n

    # --------------------------------------------------- background applies
    def _bg_flush(self, imm: ImmutableMemtable) -> Optional[CompactJob]:
        """Worker-thread half of a pipelined flush.

        Replicates the synchronous ``flush`` body step for step (rate
        limiter before the run build, install, then compaction planning) so
        the level trajectory is bit-for-bit the sync engine's.  Returns the
        compaction continuation job; the scheduler front-queues it ahead of
        any later flushes.
        """
        sched = self._scheduler
        st = self._stats.local()
        if len(self._levels[0]) >= self.config.l0_stop_writes_trigger:
            st.write_stalls += 1
            self._compact_until_quiet(cause=imm.mid)
        if sched.aborting:
            return None     # crash in progress: imm stays queued for replay
        f = self.config.faults
        if f is not None:
            f.check("flush_write")
        tel = self.config.telemetry
        entries = len(imm.memtable)
        tok = tel.emit("flush_start", entries=entries, bg=1) \
            if tel is not None else 0
        t0, c0 = time.perf_counter_ns(), time.thread_time_ns()
        with span("lsm.flush", memtable=imm.mid, entries=entries):
            run = self._build_l0(imm.memtable, st)
            # Only now drop the readable immutable memtable: between install
            # and pop a reader may see the entries twice (same seq, same
            # value) but never zero times.  The WAL segment retires with it
            # — the data is durable in the manifest as of _commit's fsync.
            with sched.lock:
                self._imm = [m for m in self._imm if m is not imm]
                sched.lock.notify_all()     # wake write-pressure waiters
        dur = time.perf_counter_ns() - t0
        st.bg_flushes += 1
        st.flush_queue_ns += t0 - imm.rotated_ns
        st.flush_ns += dur
        st.flush_cpu_ns += time.thread_time_ns() - c0
        if tel is not None:
            tel.record("flush", dur)
            tel.emit("flush_end", token=tok, entries=len(run), bg=1,
                     t0=t0, dur_ns=dur)
        return CompactJob(cause=imm.mid)

    def _bg_compact_one(self, cause: int) -> Optional[CompactionTask]:
        """Plan + apply one compaction task (worker thread); ``cause`` is
        the id of the memtable whose flush queued it.

        The input version is pinned for the duration of the merge — exactly
        the retention ``_commit``'s cache-invalidation protocol assumes —
        so concurrent snapshot releases can never GC the input runs
        mid-merge; the pin is released (and GC + cache retention re-run)
        whether the apply succeeds, goes stale, or aborts.
        """
        if self._scheduler.aborting:
            return None
        pinned = self.manifest.pin_current()
        try:
            task = self._plan_one()
            if task is None or not self._apply(task, cause):
                return None
            self._stats.local().bg_compactions += 1
            return task
        finally:
            if self.manifest.unpin(pinned.version_id):
                with self._maint_lock:
                    self.manifest.gc()
                    if self.block_cache is not None:
                        self.block_cache.retain(self.storage.ids())

    # -------------------------------------------------------- compactions
    def _plan_one(self) -> Optional[CompactionTask]:
        """Generate the next compaction task against the current tree.

        Task generation is decoupled from apply (DESIGN.md §11): the
        returned task captures its source level's run ids so a (stale)
        apply against a changed tree is refused rather than silently
        merging the wrong runs.  The synchronous loop and the scheduler's
        CompactJob both plan immediately before applying, so staleness is a
        discipline check, not an expected path.
        """
        sizes = [[r.data_bytes for r in lvl] for lvl in self._levels]
        new_L, task, delayed = self.policy.plan(
            sizes, self._max_level, self.config.base_level_bytes)
        if delayed:
            self._stats.local().delayed_last_level_compactions += delayed
        self._max_level = max(self._max_level, new_L)
        if task is None:
            return None
        srcs = (self._levels[task.src_level]
                if task.src_level < len(self._levels) else [])
        return dataclasses.replace(
            task, src_run_ids=tuple(r.run_id for r in srcs))

    def _compact_until_quiet(self, cause: int = 0):
        while True:
            if self._scheduler is not None and self._scheduler.aborting:
                return      # crash in progress: bail at the task boundary
            task = self._plan_one()
            if task is None:
                return
            self._apply(task, cause)

    def _apply(self, task: CompactionTask, cause: int = 0) -> bool:
        """Merge the task's inputs and install the result as a new version.
        ``cause``: the id of the memtable whose flush led to this task (0
        for maintenance), carried by the ``lsm.compaction`` span.

        The merged level lists are built copy-on-write and published with
        one reference assignment, so concurrent readers either see the old
        version or the new one — never a torn intermediate (async mode's
        lock-free read contract).  Returns False without mutating anything
        if the task's captured inputs no longer match the tree.
        """
        levels = [list(lvl) for lvl in self._levels]
        while len(levels) <= task.dst_level:
            levels.append([])
        srcs = levels[task.src_level]
        if not task.matches(srcs):
            return False
        dsts = levels[task.dst_level] if task.include_dst else []
        st = self._stats.local()
        tel = self.config.telemetry
        tok = tel.emit("compaction_start", src=task.src_level,
                       dst=task.dst_level, runs=len(srcs) + len(dsts)) \
            if tel is not None else 0
        t0, c0 = time.perf_counter_ns(), time.thread_time_ns()
        with span("lsm.compaction", src=task.src_level, dst=task.dst_level,
                  entries=sum(len(r) for r in srcs + dsts), cause=cause):
            deepest = self._deepest_nonempty()
            drop_tombs = task.include_dst and task.dst_level >= deepest
            f = self.config.faults
            if f is not None:
                f.check("compaction_merge")
            merged = merge_runs(srcs + dsts,
                                self._bits_for_level(task.dst_level),
                                st, drop_tombstones=drop_tombs,
                                block_size=self.config.block_size,
                                key_bytes=self.config.key_bytes,
                                pair_merge=self._pair_merge_fn(),
                                bloom_hash=self._bloom_hash_fn())
            levels[task.src_level] = []
            if task.include_dst:
                levels[task.dst_level] = [merged] if len(merged) else []
            elif len(merged):
                levels[task.dst_level].append(merged)
            self._levels = levels
            self._max_level = max(self._max_level, task.dst_level)
            self._commit()
        dur = time.perf_counter_ns() - t0
        st.compaction_ns += dur
        st.compaction_cpu_ns += time.thread_time_ns() - c0
        if tel is not None:
            tel.record("compaction", dur)
            tel.emit("compaction_end", token=tok, src=task.src_level,
                     dst=task.dst_level, entries=len(merged),
                     t0=t0, dur_ns=dur)
        return True

    def _deepest_nonempty(self) -> int:
        deepest = 1
        for i in range(len(self._levels) - 1, 0, -1):
            if self._levels[i]:
                deepest = i
                break
        return deepest

    def _commit(self):
        with span("lsm.commit"):
            st = self._stats.local()
            self.manifest.commit(self._levels, self._max_level, self._seq,
                                 st)
            f = self.config.faults
            if f is not None:
                # after the in-memory commit, before durability: the edit is
                # appended but not synced — exactly the window a real fsync
                # failure leaves behind
                f.check("manifest_fsync")
            self.manifest.fsync(st)
            with self._maint_lock:
                # The gc + retain + repin triplet must not interleave with a
                # concurrent snapshot release (or another install): a retain
                # computed from a stale id set could drop blocks the newer
                # version just pinned.
                self.manifest.gc()
                if self.block_cache is not None:
                    # Invalidation protocol (DESIGN.md §9): drop blocks of
                    # runs that compaction retired (snapshot-pinned runs stay
                    # live in storage), then re-derive the DRAM-resident L0
                    # from the new version.
                    self.block_cache.retain(self.storage.ids())
                    self.pinned_l0.repin(self._levels[0])

    # -------------------------------------------------------------- bloom
    def _bits_for_level(self, level: int) -> float:
        cfg = self.config
        if cfg.bits_per_key <= 0:
            return 0.0
        if cfg.bloom_allocation == "uniform":
            return cfg.bits_per_key
        # Monkey/Autumn allocation (Eq. 8-10): optimal FPR per level given the
        # total budget of bits_per_key * total_entries.
        counts = [sum(len(r) for r in lvl) for lvl in self._levels]
        while len(counts) <= level:
            counts.append(0)
        total = sum(counts)
        if total == 0:
            return cfg.bits_per_key
        # The level being (re)built will hold roughly the entries being merged
        # into it; use current counts as the Monkey size profile.
        fprs = allocate_fprs(counts, cfg.bits_per_key * total)
        return bits_for_fpr(float(fprs[level])) if counts[level] > 0 else cfg.bits_per_key

    # -------------------------------------------------------------- reads
    def _read_state(self, snapshot: Optional[Version] = None
                    ) -> List[List[SortedRun]]:
        if snapshot is None:
            return self._levels
        return snapshot.runs(self.storage)

    def _mem_sources(self) -> List[Memtable]:
        """Memtables in resolution order: active, then immutables newest
        first (the rotation queue's read window, DESIGN.md §11).  The lists
        are copy-on-write, so capturing the reference is a consistent view;
        in sync mode this is always just the active memtable.

        Capture order matters: the active memtable must be read *before*
        the immutable list — rotation publishes in the opposite order
        (append to the queue, then swap the active) — so a racing reader's
        worst case is seeing the rotated memtable twice (benign: identical
        entries, newest-first dedup), never zero times."""
        active = self.memtable
        imm = self._imm
        if not imm:
            return [active]
        return [active] + [m.memtable for m in reversed(imm)]

    def _runs_newest_first(self, levels: List[List[SortedRun]]):
        for r in reversed(levels[0]):
            yield r
        for lvl in levels[1:]:
            for r in reversed(lvl):
                yield r

    # ------------------------------------------------- range views (§13)
    def _view_fresh(self) -> Optional[RangeView]:
        """The current range view iff it indexes the *published* level
        list.  Copy-on-write installs swap ``self._levels``, so one pointer
        compare is the entire staleness check — no locks, no epochs."""
        v = self._range_view
        if v is not None and v.levels_ref is self._levels:
            return v
        return None

    def refresh_range_view(self, background: bool = False
                           ) -> Optional[RangeView]:
        """(Re)build the cross-run range view from the published levels.

        Incremental: per-level sorted columns are cached by run-id tuple
        (``self._view_cache``), so only levels whose membership changed
        since the last rebuild are re-sorted.  Called by a scheduler worker
        once the tree is shaped (``background=True``) or lazily by the
        first view-eligible read in sync mode — never by the write path.
        """
        if not self.config.use_range_views:
            return None
        levels = self._levels
        v = self._range_view
        if v is not None and v.levels_ref is levels:
            return v
        t0 = time.perf_counter_ns()
        view = build_range_view(levels, self._view_cache,
                                telemetry=self.config.telemetry)
        dt = time.perf_counter_ns() - t0
        st = self._stats.local()
        st.view_rebuilds += 1
        if background:
            st.bg_view_rebuilds += 1
        st.view_entries_built += len(view)
        st.view_rebuild_ns += dt
        tel = self.config.telemetry
        if tel is not None:
            tel.record("view_rebuild", dt)
        self._range_view = view
        return view

    def _bg_refresh_view(self) -> None:
        """Scheduler hook: piggyback a view rebuild on the worker that just
        found the tree quiet (CompactJob with no task to run).  The rebuild
        re-uses the sort work that compaction already paid; foreground
        writes never rebuild."""
        if not self.config.use_range_views:
            return
        if self._scheduler is not None and self._scheduler.aborting:
            return
        self.refresh_range_view(background=True)

    def get(self, key: int, snapshot: Optional[Version] = None) -> Optional[bytes]:
        tel = self.config.telemetry
        if tel is None:
            return self._get_impl(key, snapshot)
        t0 = time.perf_counter_ns()
        try:
            out = self._get_impl(key, snapshot)
        except CorruptionError as e:
            tel.emit("corruption", run_id=e.run_id, block_id=e.block_id,
                     where="get")
            raise
        # thread-local histogram record: no locks on the lock-free read path
        tel.record("get", time.perf_counter_ns() - t0)
        return out

    def _get_impl(self, key: int, snapshot: Optional[Version] = None
                  ) -> Optional[bytes]:
        st = self._stats.local()
        st.point_reads += 1
        if snapshot is None:
            # active captured BEFORE the imm check (the rotation publish
            # order makes this safe — see _mem_sources); the empty-queue
            # fast path keeps the sync hot read loop allocation-free
            active = self.memtable
            if not self._imm:
                hit = active.get(int(key))
                if hit is not None:
                    return hit[1]
            else:
                for mt in self._mem_sources():
                    hit = mt.get(int(key))
                    if hit is not None:
                        return hit[1]
        cfg = self.config
        use_bloom = cfg.bits_per_key > 0
        paranoid = cfg.paranoid_checks
        faults = cfg.faults
        for run in self._runs_newest_first(self._read_state(snapshot)):
            if len(run) == 0:
                continue
            st.runs_touched_point += 1
            found, value, _ = run.point_get(int(key), st, use_bloom,
                                            cache=self.block_cache,
                                            paranoid=paranoid, faults=faults)
            if found:
                return value
        return None

    # The device routes are imported on first use, so a store that never
    # turns them on never imports jax.  The config flags are re-read on every
    # call, so toggling them on a live store takes effect.  A device path
    # that fails raises: there is no silent numpy fallback.

    # Each route calls its entry by name on ``repro.kernels.ops`` at call
    # time, so a wrapper installed on the module (a profiler's, a test's)
    # sees every call, and charges the entry's wall time to the calling
    # thread's IOStats.

    def _bloom_probe_fn(self):
        """The device batched-probe route (``kernels.ops.bloom_probe_filter``)
        when ``use_pallas_bloom`` is on, else None (numpy probes).  Each
        call counts ``probe_calls`` and ``probe_ns``, and ``probe_uploads``
        when it had to upload the filter's bitset to the device."""
        if not self.config.use_pallas_bloom:
            return None
        from repro.kernels import ops
        hub = self._stats

        def probe(bf, keys):
            t0 = time.perf_counter_ns()
            resident = bf.device_bits is not None
            out = ops.bloom_probe_filter(bf, keys)
            st = hub.local()
            st.probe_calls += 1
            if not resident and bf.device_bits is not None:
                st.probe_uploads += 1
            st.probe_ns += time.perf_counter_ns() - t0
            return out
        return probe

    def _timed_entry(self, name: str):
        """``kernels.ops.<name>`` with its wall time charged to
        ``IOStats.entry_ns``."""
        from repro.kernels import ops
        hub = self._stats

        def call(*args, **kwargs):
            t0 = time.perf_counter_ns()
            out = getattr(ops, name)(*args, **kwargs)
            hub.local().entry_ns += time.perf_counter_ns() - t0
            return out
        return call

    def _bloom_hash_fn(self):
        """The device filter-*build* hash route, sharing the
        ``use_pallas_bloom`` toggle with the probe route: flush and
        compaction rebuild output filters from one device-side hash pass
        (``kernels.ops.bloom_build_hashes``) that is bit-identical to the
        numpy family, so either backend may probe the result."""
        if not self.config.use_pallas_bloom:
            return None
        return self._timed_entry("bloom_build_hashes")

    def _pair_merge_fn(self):
        """The device merge-path compaction lane when ``use_pallas_merge``
        is on: every pairwise merge of the compaction ladder routes through
        ``kernels.ops.merge_runs_tiled`` (merge-path partition + bitonic
        network; compiled on TPU, interpreted on CPU).  Differentially tested
        bit-for-bit against the numpy ladder and ``merge_runs_scalar``."""
        if not self.config.use_pallas_merge:
            return None
        return self._timed_entry("merge_runs_tiled")

    def multi_get(self, keys: Sequence[int],
                  snapshot: Optional[Version] = None) -> List[Optional[bytes]]:
        """Batched point reads: semantically ``[get(k) for k in keys]``.

        The batch is resolved level by level: every still-pending key is
        bloom-probed against a run in one vectorized pass (optionally through
        the device probe, DESIGN.md §3) and located with one searchsorted
        over the run's fence-pointed key array.  Aggregate IOStats accounting
        is identical to the equivalent sequence of scalar ``get`` calls.
        """
        tel = self.config.telemetry
        t0 = time.perf_counter_ns()
        try:
            out = self._multi_get_impl(keys, snapshot)
        except CorruptionError as e:
            if tel is not None:
                tel.emit("corruption", run_id=e.run_id, block_id=e.block_id,
                         where="multi_get")
            raise
        dt = time.perf_counter_ns() - t0
        self._stats.local().multi_get_ns += dt
        if tel is not None:
            tel.record("multi_get", dt)
        return out

    def _multi_get_impl(self, keys: Sequence[int],
                        snapshot: Optional[Version] = None
                        ) -> List[Optional[bytes]]:
        st = self._stats.local()
        keys_arr = np.asarray(list(keys), dtype=KEY_DTYPE)
        n = int(keys_arr.size)
        st.point_reads += n
        results: List[Optional[bytes]] = [None] * n
        if n == 0:
            return results
        with span("lsm.multi_get", keys=n):
            pending = np.arange(n, dtype=np.int64)
            if snapshot is None:
                with span("lsm.memtable_probe", keys=n):
                    for mt in self._mem_sources():
                        if len(mt) == 0 or pending.size == 0:
                            continue
                        keep = []
                        for j in pending:
                            hit = mt.get(int(keys_arr[j]))
                            if hit is not None:
                                # the value, or None for a tombstone
                                results[int(j)] = hit[1]
                            else:
                                keep.append(int(j))
                        pending = np.asarray(keep, dtype=np.int64)
            cfg = self.config
            use_bloom = cfg.bits_per_key > 0
            paranoid = cfg.paranoid_checks
            faults = cfg.faults
            probe_fn = self._bloom_probe_fn()
            for run in self._runs_newest_first(self._read_state(snapshot)):
                if pending.size == 0:
                    break
                if len(run) == 0:
                    continue
                st.runs_touched_point += int(pending.size)
                found, values = run.point_get_batch(
                    keys_arr[pending], st, use_bloom, probe_fn,
                    cache=self.block_cache, paranoid=paranoid, faults=faults)
                if found.any():
                    for p in np.nonzero(found)[0]:
                        results[int(pending[p])] = values[int(p)]
                    pending = pending[~found]
        return results

    def seek(self, key: int, snapshot: Optional[Version] = None) -> Optional[int]:
        """Position a merging iterator at the first key >= key (db_bench Seek).

        Cost: one seek + one block read per run with a valid position.

        Tombstone handling is approximate (a cost probe, not a correctness
        surface — ``scan`` is): memtable entries are liveness-filtered but
        run entries are not, so a deleted key stops shadowing once its
        tombstone flushes.  In async mode that transition happens on the
        background worker's schedule rather than at an explicit ``flush``
        call; use ``scan``/``iterator`` where exact liveness matters."""
        tel = self.config.telemetry
        if tel is None:
            return self._seek_impl(key, snapshot)
        t0 = time.perf_counter_ns()
        out = self._seek_impl(key, snapshot)
        tel.record("seek", time.perf_counter_ns() - t0)
        return out

    def _seek_impl(self, key: int, snapshot: Optional[Version] = None
                   ) -> Optional[int]:
        st = self._stats.local()
        st.range_reads += 1
        best: Optional[int] = None
        # memtables BEFORE levels: the install protocol publishes the L0 run
        # first and pops the immutable memtable second, so this capture order
        # makes the race a benign duplicate, never a lost read (_mem_sources)
        mems = self._mem_sources() if snapshot is None else []
        if snapshot is None and self.config.use_range_views:
            view = self._view_fresh()
            if view is None and self._scheduler is None:
                view = self.refresh_range_view()
            if view is not None:
                st.view_scans += 1
                best = view.seek(int(key), st, self.block_cache)
                # same approximate-liveness memtable probe as the run walk
                return self._seek_mems(mems, int(key), best)
            st.view_fallbacks += 1
        for run in self._runs_newest_first(self._read_state(snapshot)):
            if len(run) == 0:
                continue
            st.runs_touched_range += 1
            st.seeks += 1
            i = run.seek_idx(int(key))
            if i < len(run):
                run._charge_block(run.block_of[i], st,
                                  self.block_cache,
                                  paranoid=self.config.paranoid_checks,
                                  faults=self.config.faults)
                k = int(run.keys[i])
                if best is None or k < best:
                    best = k
        return self._seek_mems(mems, int(key), best)

    @staticmethod
    def _seek_mems(mems, key: int, best: Optional[int]) -> Optional[int]:
        """``best`` lowered to each memtable's first live key >= ``key``.

        The memtable's first entry at or past ``key`` may be a tombstone
        with a live key after it; that live key can precede every run's
        candidate, so the tombstone is skipped, not taken as the
        memtable's answer."""
        for mt in mems:
            k = min((k for k, _, v in mt.snapshot_items(key)
                     if v is not None), default=None)
            if k is not None and (best is None or k < best):
                best = k
        return best

    def iterator(self, snapshot: Optional[Version] = None,
                 chunk: int = 512) -> MergingIterator:
        """A streaming merging iterator over the current (or snapshot) state.

        Holds one cursor per run + the memtable; see core.iterator for the
        merge and I/O-accounting semantics (DESIGN.md §3).  The iterator reads
        a frozen set of runs — writes/compactions after creation are not seen
        by run cursors (memtable updates may be, as in RocksDB iterators pin
        SSTs but here the memtable is shared; take a snapshot for isolation).
        """
        # memtables BEFORE levels (see seek): worst case a duplicate entry
        # with the same seq/value, never a lost read
        mems = self._mem_sources() if snapshot is None else None
        levels = self._read_state(snapshot)
        runs = [r for r in self._runs_newest_first(levels) if len(r)]
        return MergingIterator(runs, memtables=mems,
                               stats=self._stats.local(),
                               chunk=chunk, cache=self.block_cache)

    def scan(self, start_key: int, count: int,
             snapshot: Optional[Version] = None) -> List[Tuple[int, bytes]]:
        """Range read: first ``count`` live entries with key >= start_key.

        One seek per run positions a cursor; the merged stream then refills
        incrementally per run (no restart loop), charging each run the blocks
        it actually contributed — see core.iterator.

        With ``use_range_views`` (DESIGN.md §13) a live, *fresh* range view
        replaces all of that with one binary search + one sequential sweep
        + one batched gather per touched run.  A stale view (async churn
        between the last background rebuild and now) falls back to the
        merging iterator and counts ``view_fallbacks`` — the result is
        identical either way, only the cost differs.
        """
        tel = self.config.telemetry
        t0 = time.perf_counter_ns()
        with span("lsm.scan", count=count):
            out = self._scan_impl(start_key, count, snapshot)
        dt = time.perf_counter_ns() - t0
        self._stats.local().scan_ns += dt
        if tel is not None:
            tel.record("scan", dt)
        return out

    def _scan_impl(self, start_key: int, count: int,
                   snapshot: Optional[Version] = None
                   ) -> List[Tuple[int, bytes]]:
        st = self._stats.local()
        st.range_reads += 1
        if snapshot is None and self.config.use_range_views:
            # memtables BEFORE the view/levels capture (see seek): a racing
            # install contributes a benign duplicate, never a lost read
            mems = self._mem_sources()
            view = self._view_fresh()
            if view is None and self._scheduler is None:
                view = self.refresh_range_view()  # lazy in sync mode
            if view is not None:
                st.view_scans += 1
                mems = [m for m in mems if len(m)]   # empty => pure sweep
                mem_items = (combined_mem_items(mems, int(start_key))
                             if mems else [])
                return view.scan(int(start_key), count, mem_items,
                                 st, self.block_cache)
            st.view_fallbacks += 1
        it = self.iterator(snapshot)
        return it.scan(int(start_key), count)

    def scan_scalar(self, start_key: int, count: int,
                    snapshot: Optional[Version] = None
                    ) -> List[Tuple[int, bytes]]:
        """Reference range read (the pre-iterator seek-retry implementation).

        Kept as the differential-test oracle and the benchmarks' scalar
        baseline: slices ``count`` candidates from every run, sort-merges the
        python lists, and retries with a 4x larger window when a truncated
        run could still hide smaller keys.
        """
        st = self._stats.local()
        st.range_reads += 1
        # memtables BEFORE levels (see seek): a flush racing this capture
        # contributes a duplicate (same seq, same value — the (key, -seq)
        # merge keeps one), never a lost read
        mems = self._mem_sources() if snapshot is None else []
        levels = self._read_state(snapshot)
        runs = [r for r in self._runs_newest_first(levels) if len(r)]
        per_run_take = max(count, 1)
        while True:
            cand_k: List[np.ndarray] = []
            cand_s: List[np.ndarray] = []
            cand_v: List[List[Optional[bytes]]] = []
            # Results are only valid up to the smallest last-key among
            # truncated run slices (a run whose window ended may still hold
            # keys below another run's contributions).
            frontier: Optional[int] = None
            seek_positions = []
            for run in runs:
                i = run.seek_idx(int(start_key))
                seek_positions.append(i)
                k, s, l, v = run.slice_from(i, per_run_take)
                if i + per_run_take < len(run) and len(k):
                    fk = int(k[-1])
                    frontier = fk if frontier is None else min(frontier, fk)
                cand_k.append(k)
                cand_s.append(s)
                cand_v.append([None if l[j] == TOMBSTONE_LEN else bytes(v[j, :l[j]])
                               for j in range(len(k))])
            mem_items: List[Tuple[int, int, Optional[bytes]]] = []
            for mt in mems:
                # seq numbers resolve duplicates across the rotation queue
                # inside _merge_candidates' (key, -seq) sort
                mem_items.extend(mt.scan(int(start_key)))
            merged = self._merge_candidates(cand_k, cand_s, cand_v, mem_items)
            live = [(k, v) for k, v in merged if v is not None and
                    (frontier is None or k <= frontier)][:count]
            if len(live) >= count or frontier is None:
                # Account I/O for the final pass only (the retry loop models
                # an iterator that would have kept reading anyway).
                end_key = live[-1][0] if live else None
                for run, i in zip(runs, seek_positions):
                    st.runs_touched_range += 1
                    st.seeks += 1
                    if i >= len(run):
                        continue
                    if end_key is None:
                        consumed_end = i + 1
                    else:
                        consumed_end = int(np.searchsorted(
                            run.keys, np.uint64(end_key), side="right"))
                        consumed_end = max(consumed_end, i + 1)
                    st.blocks_read += run.blocks_spanned(i, consumed_end)
                return live
            per_run_take *= 4

    @staticmethod
    def _merge_candidates(cand_k, cand_s, cand_v, mem_items):
        ks: List[int] = []
        ss: List[int] = []
        vs: List[Optional[bytes]] = []
        for k_arr, s_arr, v_list in zip(cand_k, cand_s, cand_v):
            ks.extend(int(x) for x in k_arr)
            ss.extend(int(x) for x in s_arr)
            vs.extend(v_list)
        for k, s, v in mem_items:
            ks.append(k)
            ss.append(s)
            vs.append(v)
        order = sorted(range(len(ks)), key=lambda i: (ks[i], -ss[i]))
        out: List[Tuple[int, Optional[bytes]]] = []
        last_key = None
        for i in order:
            if ks[i] != last_key:
                out.append((ks[i], vs[i]))
                last_key = ks[i]
        return out

    # ----------------------------------------------------------- snapshots
    def get_snapshot(self) -> Version:
        """Acquire a reader reference on the current version.

        Thin wrapper over the manifest's *refcounted* pins: snapshot reads
        stay valid across any number of later flushes/compactions until the
        matching ``release_snapshot``; if several readers snapshot the same
        version, it stays pinned until the last one releases.  The
        read-and-pin is atomic under the manifest mutex, so snapshots taken
        while background compaction churns can never pin a version whose
        runs a concurrent GC already freed.
        """
        return self.manifest.pin_current()

    def release_snapshot(self, snapshot: Version) -> None:
        """Drop one reader reference (see ``get_snapshot``)."""
        if not self.manifest.unpin(snapshot.version_id):
            return  # other readers still hold the version: nothing can free
        with self._maint_lock:
            self.manifest.gc()
            if self.block_cache is not None:
                # Runs kept alive only by the released snapshot may be gone.
                self.block_cache.retain(self.storage.ids())

    # ------------------------------------------------------------ recovery
    def crash(self):
        """Simulate process crash: volatile state is lost.

        Async mode: the scheduler aborts the in-flight job at its next safe
        point and drops all queued work *before* the volatile wipe, so no
        half-applied compaction, pinned input version, or orphaned cache
        entry survives (see ``CompactionScheduler.abort_and_drain``).  The
        immutable-memtable queue's WAL segments are durable (fully fsynced
        at rotation) and stay for ``recover`` to replay; the memtable dicts
        themselves are process state and are rebuilt from those segments.
        """
        if self._scheduler is not None:
            self._scheduler.abort_and_drain()
        f = self.config.faults
        self.wal.crash(f)
        for imm in self._imm:
            imm.wal.crash()   # fully synced at rotation: keeps every byte
        self.manifest.crash(f)
        self.memtable.clear()

    def recover(self):
        """Rebuild volatile state from the durable manifest + WAL(s).

        Async mode adds the rotated-but-unflushed WAL segments: they are
        consolidated (oldest first) ahead of the active WAL into one log —
        segment concatenation is record concatenation — so replay order
        equals write order and a *second* crash before the next rotation
        still recovers everything.  The scheduler survives recovery idle
        (its queue was drained by ``crash``) and resumes on the next
        rotation.

        Integrity (DESIGN.md §16.2): the manifest tail is checksum-verified
        (corrupt edits are popped back to the last good version — each was
        itself a durable prefix), WAL replay stops at the first bad frame
        and the log is truncated there, and every recovered run is scrubbed
        *regardless of* ``paranoid_checks`` — a bad block raises
        :class:`CorruptionError` so corruption is never served silently.
        Recovery also clears degraded mode: the failed pipeline's state was
        volatile.
        """
        tel = self.config.telemetry
        v, popped = self.manifest.recover_current()
        if popped and tel is not None:
            tel.emit("corruption", run_id=-1, block_id=-1, where="manifest",
                     popped_versions=popped)
        self._levels = v.runs(self.storage)
        self._max_level = v.max_level
        self._seq = v.last_seq
        self._degraded = None
        self._bg_failure_surfaced = False
        if self.block_cache is not None:
            # DRAM contents did not survive the crash; reload the pin set
            # from the recovered L0 (charged — these are real device reads)
            # while the unpinned cache refills on demand.
            self.block_cache.clear()
            with self._maint_lock:
                self.pinned_l0.repin(self._levels[0],
                                     stats=self._stats.local())
        # Drop bytes past the last checksum-valid WAL frame before replay:
        # a corrupt frame must not linger in the live log (new appends
        # would land after it and be unreachable to the next replay).
        wal_dropped = self.wal.repair()
        if wal_dropped and tel is not None:
            tel.emit("corruption", run_id=-1, block_id=-1, where="wal",
                     dropped_bytes=wal_dropped)
        # Post-crash every surviving WAL byte is durable (crash truncated
        # each segment to its watermark), so consolidation + replay rebuilds
        # the memtable and advances _seq; with an empty immutable queue this
        # is exactly the old single-WAL replay.
        replayed = self._consolidate_imm_wal()
        if tel is not None:
            tel.emit("wal_replay", records=replayed,
                     bytes=len(self.wal._buf), dropped_bytes=wal_dropped)
        report = self.scrub()
        for r in report:
            if r["bad_blocks"]:
                raise CorruptionError(r["run_id"], r["bad_blocks"][0],
                                      where="recovery scrub")

    def scrub(self) -> List[dict]:
        """Verify every run's block checksums; one report dict per run.

        Each entry carries ``run_id``, ``level``, ``entries``, ``blocks``
        and ``bad_blocks`` (empty list == clean).  Emits a ``scrub``
        telemetry event (plus one ``corruption`` event per dirty run) but
        does not raise — callers decide (recovery raises, operators may
        quarantine).
        """
        tel = self.config.telemetry
        t0 = time.perf_counter_ns() if tel is not None else 0
        report: List[dict] = []
        levels = self._levels
        for li, lvl in enumerate(levels):
            for run in lvl:
                bad = run.verify()
                report.append({"run_id": run.run_id, "level": li,
                               "entries": len(run), "blocks": run.n_blocks,
                               "bad_blocks": bad})
                if bad and tel is not None:
                    tel.emit("corruption", run_id=run.run_id,
                             block_id=int(bad[0]), where="scrub",
                             bad_blocks=len(bad))
        if tel is not None:
            tel.record("scrub", time.perf_counter_ns() - t0)
            tel.emit("scrub", runs=len(report),
                     bad_runs=sum(1 for r in report if r["bad_blocks"]))
        return report

    # ------------------------------------- cross-shard migration (§15)
    # Three primitives used by ShardedLSMStore rebalancing.  All of them
    # assume the caller holds the facade write gate and has quiesced this
    # store (no foreground writers, scheduler drained) — except
    # strip_to_range, which recovery also calls with a replayed (in-range
    # by invariant) memtable.

    def export_range(self, lo: int, hi: int):
        """Columns of every stored entry with ``lo <= key < hi``.

        Returns ``(keys, seqs, vlens, vals)`` with duplicates *retained*
        (one row per surviving physical entry, any level) so the importer's
        ``build_run`` dedup keeps exactly the newest version per key, or
        ``None`` when the range holds nothing.  Requires an empty memtable
        (the facade flushes before migrating) so runs are the whole store.
        """
        assert len(self.memtable) == 0 and not self._imm, \
            "export_range requires a flushed, quiesced store"
        lo64 = np.uint64(lo)
        ks, ss, ls, vs, vmax = [], [], [], [], 0
        for run in self._runs_newest_first(self._levels):
            if len(run) == 0:
                continue
            i0 = int(np.searchsorted(run.keys, lo64, side="left"))
            i1 = (len(run) if hi >= 1 << 64 else
                  int(np.searchsorted(run.keys, np.uint64(hi), side="left")))
            if i0 >= i1:
                continue
            k, s, l, v = run.slice_from(i0, i1 - i0)
            v2 = v if v.ndim == 2 else v.reshape(len(k), 0)
            ks.append(k); ss.append(s); ls.append(l); vs.append(v2)
            vmax = max(vmax, v2.shape[1])
        if not ks:
            return None
        vs = [v if v.shape[1] == vmax
              else np.pad(v, ((0, 0), (0, vmax - v.shape[1])))
              for v in vs]
        return (np.concatenate(ks), np.concatenate(ss),
                np.concatenate(ls), np.concatenate(vs))

    def import_migrated_run(self, run: SortedRun) -> None:
        """Install a migrated run as newest-L0 and commit it durably.

        The facade guarantees the run's key range is disjoint from
        everything this store currently holds (it is becoming the owner),
        so L0 placement cannot shadow or be shadowed incorrectly; the seq
        max-bump keeps every *future* local write newer than the imports.
        """
        if len(run) == 0:
            return
        f = self.config.faults
        if f is not None:
            f.check("migration_import")  # before any mutation: a failed
                                         # import leaves this store untouched
        self._seq = max(self._seq, int(run.seqs.max()))
        levels = [list(lvl) for lvl in self._levels]
        levels[0].append(run)          # newest-last, like flush
        self._levels = levels          # COW publish
        st = self._stats.local()
        st.blocks_written += -(-run.data_bytes // self.config.block_size)
        self._commit()

    def strip_to_range(self, lo: int, hi: int) -> int:
        """Drop every stored entry outside ``[lo, hi)``; return the count.

        Runs wholly outside are dropped; straddling runs are rebuilt from
        their in-range slice (already unique+sorted).  Commits only when
        something changed, so post-recovery clipping of an untouched store
        is a no-op.  The memtable is left alone: the facade only writes
        in-range keys under the routing that is durably logged *before* it
        becomes visible, so replayed memtable contents are in-range by
        invariant.
        """
        f = self.config.faults
        if f is not None:
            f.check("migration_strip")   # before any mutation: the donor
                                         # keeps its (already-copied) range
        lo64 = np.uint64(lo)
        dropped = 0
        changed = False
        levels: List[List[SortedRun]] = []
        for li, lvl in enumerate(self._levels):
            out = []
            for run in lvl:
                if len(run) == 0:
                    out.append(run)
                    continue
                i0 = int(np.searchsorted(run.keys, lo64, side="left"))
                i1 = (len(run) if hi >= 1 << 64 else
                      int(np.searchsorted(run.keys, np.uint64(hi),
                                          side="left")))
                if i0 == 0 and i1 == len(run):
                    out.append(run)
                    continue
                changed = True
                dropped += len(run) - (i1 - i0)
                if i0 >= i1:
                    continue                      # wholly outside: drop
                k, s, l, v = run.slice_from(i0, i1 - i0)
                st = self._stats.local()
                nr = build_run(k, s, l, v,
                               bits_per_key=self._bits_for_level(li),
                               assume_unique_sorted=True,
                               block_size=self.config.block_size,
                               key_bytes=self.config.key_bytes,
                               hash_fn=self._bloom_hash_fn())
                st.blocks_written += -(-nr.data_bytes
                                       // self.config.block_size)
                out.append(nr)
            levels.append(out)
        if changed:
            self._levels = levels          # COW publish: stale range views
            self._commit()                 # self-invalidate on levels_ref
        return dropped

    # ---------------------------------------------------------------- info
    def cache_summary(self) -> dict:
        """Memory-subsystem health: hit rate, charged bytes, residency."""
        if self.block_cache is None:
            return dict(enabled=False, hit_rate=0.0, hits=0, misses=0,
                        evictions=0, charged_bytes=0, pinned_bytes=0,
                        pinned_l0_runs=0)
        c = self.block_cache
        return dict(enabled=True, hit_rate=c.hit_rate(), hits=c.hits,
                    misses=c.misses, evictions=c.evictions,
                    charged_bytes=c.charged_bytes,
                    pinned_bytes=c.pinned_bytes,
                    pinned_l0_runs=len(self.pinned_l0.pinned_run_ids))

    def level_summary(self) -> List[dict]:
        out = []
        for i, lvl in enumerate(self._levels):
            cap = (self.policy.capacity(i, self._max_level,
                                        self.config.base_level_bytes)
                   if i >= 1 else None)
            out.append(dict(level=i, runs=len(lvl),
                            entries=sum(len(r) for r in lvl),
                            bytes=sum(r.data_bytes for r in lvl),
                            capacity=cap))
        return out

    @property
    def num_levels_in_use(self) -> int:
        return self._max_level

    @property
    def total_entries(self) -> int:
        # memtables BEFORE levels (see _mem_sources): a racing install can
        # double-count an in-flight flush, never drop it
        mems = self._mem_sources()
        levels = self._levels
        return sum(len(r) for lvl in levels for r in lvl) \
            + sum(len(mt) for mt in mems)

    def _live_profile(self) -> Tuple[int, int]:
        """(live entry count, live logical bytes) of the newest versions.

        One vectorized pass: concatenate every source's keys newest-first
        (memtable, then runs in read order), stable-argsort, and keep the
        first occurrence of each key — the newest version, since stable
        sorting preserves concatenation order within equal keys.  Replaces
        the per-run ``np.isin`` against an ever-growing seen-set (quadratic
        in the number of runs x entries).
        """
        parts_k: List[np.ndarray] = []
        parts_vl: List[np.ndarray] = []
        for mt in self._mem_sources():   # active, then immutables newest 1st
            # consistent point-in-time copy (the active memtable may be
            # racing the writer thread; see Memtable.snapshot_items)
            items = mt.snapshot_items()
            if items:
                parts_k.append(np.fromiter((k for k, _, _ in items),
                                           KEY_DTYPE, len(items)))
                parts_vl.append(np.fromiter(
                    (TOMBSTONE_LEN if v is None else len(v)
                     for _, _, v in items), np.int64, len(items)))
        for run in self._runs_newest_first(self._levels):
            if len(run):
                parts_k.append(run.keys)
                parts_vl.append(run.vlens.astype(np.int64))
        if not parts_k:
            return 0, 0
        K = np.concatenate(parts_k)
        VL = np.concatenate(parts_vl)
        order = np.argsort(K, kind="stable")
        Ks = K[order]
        first = np.empty(Ks.size, dtype=bool)
        first[0] = True
        np.not_equal(Ks[1:], Ks[:-1], out=first[1:])
        win_vl = VL[order[first]]
        live = win_vl != TOMBSTONE_LEN
        n_live = int(np.count_nonzero(live))
        logical = int(np.sum(win_vl[live])) + n_live * self.config.key_bytes
        return n_live, logical

    def total_live_entries(self) -> int:
        """Logical entry count (newest versions only, tombstones excluded)."""
        return self._live_profile()[0]

    def _space_profile(self) -> Tuple[int, int]:
        """(physical bytes stored, logical live bytes) — the two terms of
        space amplification, exposed separately so the sharded facade can
        sum shards before dividing (a mean of per-shard ratios is wrong
        when shard sizes differ)."""
        mems = self._mem_sources()      # memtables BEFORE levels, as above
        phys = sum(r.data_bytes for lvl in self._levels for r in lvl) \
            + sum(mt.size_bytes for mt in mems)
        return phys, self._live_profile()[1]

    def space_amplification(self) -> float:
        """Physical bytes stored / logical bytes of the live newest versions
        (RocksDB's definition; 1.0 when nothing is live)."""
        phys, logical = self._space_profile()
        if logical == 0:
            return 1.0
        return phys / logical

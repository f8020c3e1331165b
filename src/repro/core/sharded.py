"""Sharded keyspace: N independent LSMStores behind one facade (DESIGN.md §12).

PR 4's determinism turnstile serializes one background job per store, so a
single tree can never use more than one core of background compaction.  The
standard route to multi-core scale (the partitioning survey in Luo & Carey;
RocksDB column families / CockroachDB ranges) is to *range-partition* the key
space into N fully independent trees:

``ShardedLSMStore``
    Order-preserving splitters (``shards - 1`` ascending uint64 bounds; key k
    lives in the first shard whose splitter exceeds it) route every key to
    exactly one inner :class:`LSMStore`.  Each shard owns its WAL + memtable,
    its Manifest/RunStorage, and its own ``CompactionScheduler`` — background
    flush/compaction runs genuinely in parallel across shards, bounded by a
    *shared worker budget* (one semaphore sized ``compaction_workers``, so N
    shards never oversubscribe the machine).  The facade presents the entire
    single-store API: batched ops are split by ONE vectorized
    ``np.searchsorted`` against the splitters and fanned out per shard;
    cross-shard ``scan``/``seek`` exploit the order-preserving partition —
    shard i's keys all precede shard i+1's, so a range read is a
    shard-ordered concatenation, not a merge.

Dynamic rebalancing (DESIGN.md §15)
    Static splitters collapse under skew: a hotspot piles every op into one
    shard while its siblings idle.  With ``rebalance_interval_ops > 0`` the
    facade tracks per-shard routed ops in a decaying window, detects
    imbalance (max/mean share ≥ ``rebalance_ratio``) at write and
    compaction/quiesce boundaries, and re-derives the splitters as
    load-weighted key quantiles over the shards' own runs — splitting hot
    shards and merging cold neighbours in one step.  Data moves by
    **cross-shard run migration**: quiesce, export each shard's
    leaving-range slice, rebuild it as L0 runs in the destination (durably
    committed), log + publish the new routing, then strip each source to
    its new range.  Readers never block: routing lives in one immutable
    ``_Routing`` object swapped by reference; a reader captures it,
    computes, and retries iff the reference moved mid-read (seqlock
    flavor).  Snapshots carry the routing they were taken under, and their
    manifest pins keep pre-migration runs alive, so snapshot reads are
    never retried and survive any number of rebalances.

Shared memory subsystem
    All shards share one budgeted :class:`BlockCache`: each shard reads
    through a namespaced ``BlockCacheView`` with a ``cache_bytes / N`` slice
    (admission pressure evicts only the owning namespace's cold entries) and
    a ``pin_l0_bytes / N`` DRAM-resident L0 slice.  Cache keys are
    namespaced by shard id and ``retain``/repin/clear are namespace-scoped,
    so one shard's post-commit invalidation can never evict (or alias) a
    sibling's live blocks.  A rebalance re-slices the per-namespace budgets
    load-proportionally (with a 1/(4N) floor), so a merged cold shard hands
    its idle cache back to the hot half of the keyspace; namespaces are
    never renumbered — migrated runs get fresh run-ids in the destination's
    storage, so their blocks key under the destination's namespace and the
    source's strip-commit ``retain`` drops the dead ones.

Differential contract
    The plain single store (or ``shards=1``) is the retained oracle: for any
    op sequence, every read (``get``/``multi_get``/``scan``/``seek``) returns
    byte-identical results, because each key's ops land on one shard in
    program order and shard ranges are disjoint.  ``shards=1`` is bit-for-bit
    the plain store (same flush boundaries, same seqs, same bloom bits).
    With ``shards>1`` the per-shard trees are smaller — sequence numbers are
    per-shard and levels are shallower (that depth reduction, plus parallel
    background work, is the speedup) — so cross-shard equality is defined on
    read *results*, not run bytes.  Rebalancing preserves it: a migrated
    key's entire version history lives in exactly one shard before and
    after the move (imports are deduped newest-wins from the quiesced
    source; the destination owned nothing in the moved range, so dropping
    collapsed tombstones loses nothing live).

Concurrency
    The facade inherits the engine's single-writer/multi-reader discipline:
    one foreground thread writes (each shard still sees a single writer);
    readers are lock-free per shard.  Rebalancing runs on a foreground
    thread under the write gate — never on a scheduler worker, whose
    ``on_idle`` hook only *flags* imbalance (running it there would
    deadlock: the migration quiesces that very scheduler).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from bisect import bisect_left, bisect_right
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .cache import BlockCache, BlockCacheView
from .engine import LSMConfig, LSMStore
from .manifest import Version
from .run import build_run
from .scheduler import CompactJob, WorkerBudget
from .telemetry import span
from .tuner import TunerStep
from .types import KEY_DTYPE, IOStats, StatsHub

_KEY_SPACE_END = 1 << 64
_HIST_B = 32                 # buckets per shard in the load histogram (§15)


def uniform_splitters(shards: int, key_space: int = 1 << 64
                      ) -> Tuple[int, ...]:
    """``shards - 1`` ascending bounds splitting ``[0, key_space)`` evenly.

    The default (full uint64 space) is right for hashed key schemes
    (AutumnKVCache chain hashes, YCSB's scrambled keys); dense sequential
    key ranges should pass their own ``key_space``.
    """
    return tuple(key_space * (i + 1) // shards for i in range(shards - 1))


class _Routing:
    """One immutable routing epoch: the splitters plus their derived forms.

    Readers capture a single reference, compute against it, then validate
    ``facade._routing is r`` — a mid-read migration swaps the reference
    (always to a fresh object), so a torn read (source already stripped /
    destination not yet routed) is detected and retried.  The writer swaps
    it only under the facade write gate, *after* durably logging the new
    splitters, which is what makes crash recovery unambiguous.
    """

    __slots__ = ("lst", "arr", "epoch", "n")

    def __init__(self, splitters: Sequence[int], epoch: int = 0):
        self.lst = [int(x) for x in splitters]
        self.arr = np.asarray(self.lst, dtype=KEY_DTYPE)
        self.epoch = int(epoch)
        self.n = len(self.lst) + 1

    def shard_of(self, key: int) -> int:
        return bisect_right(self.lst, int(key))

    def split(self, keys_arr: np.ndarray) -> np.ndarray:
        """Vectorized shard assignment: one searchsorted for the batch."""
        return np.searchsorted(self.arr, keys_arr, side="right")

    def bounds(self, si: int) -> Tuple[int, int]:
        """Shard ``si``'s owned key range ``[lo, hi)`` (hi may be 2**64)."""
        lo = self.lst[si - 1] if si > 0 else 0
        hi = self.lst[si] if si < self.n - 1 else _KEY_SPACE_END
        return lo, hi


@dataclasses.dataclass(frozen=True)
class ShardedSnapshot:
    """One pinned :class:`Version` per shard, in shard order, plus the
    routing epoch the pins were taken under — snapshot reads route with
    *their* splitters, and the pins keep pre-migration runs alive, so a
    snapshot survives any number of rebalances unchanged."""
    versions: Tuple[Version, ...]
    routing: Optional[_Routing] = None


class ShardedLSMStore:
    """Range-partitioned facade over ``config.shards`` independent stores.

    Construct via :func:`make_store` (returns a plain :class:`LSMStore`
    when ``config.shards <= 1``).  All shards share the facade's *live*
    ``LSMConfig`` object, so runtime toggles (``use_pallas_bloom``,
    ``slowdown_trigger``/``stall_trigger``, the rebalance knobs) keep
    reaching every shard with no per-shard plumbing; construction-time
    fields that must differ per shard (cache/pin budgets, worker counts)
    are overridden before the shared object is installed.
    """

    def __init__(self, config: Optional[LSMConfig] = None):
        self.config = config or LSMConfig(shards=2)
        n = max(1, int(self.config.shards))
        splitters = self.config.shard_splitters
        if splitters is None:
            splitters = uniform_splitters(n)
        splitters = [int(s) for s in splitters]
        if len(splitters) != n - 1:
            raise ValueError(
                f"need {n - 1} splitters for {n} shards, got {len(splitters)}")
        if splitters != sorted(set(splitters)):
            raise ValueError("splitters must be strictly ascending")
        # Routing epoch 0 + its durable log.  The log mirrors the WAL's
        # fsync discipline except routing commits sync immediately (they
        # are rare); crash() truncates to the synced watermark and
        # recover() restores the last durable epoch.
        self._routing = _Routing(splitters, epoch=0)
        self._routing_log: List[Tuple[int, ...]] = [tuple(splitters)]
        self._routing_synced = 1
        # Shared worker budget: at most `compaction_workers` background jobs
        # in flight across ALL shards (each shard still runs its own
        # one-job-at-a-time determinism turnstile).
        self._budget = None
        if self.config.async_compaction:
            # resizable: the online tuner's worker-reallocation actuator
            # retargets it at quiesce boundaries (DESIGN.md §17)
            self._budget = WorkerBudget(
                max(1, int(self.config.compaction_workers)))
        shard_cfg = dataclasses.replace(
            self.config, shards=1, shard_splitters=None,
            cache_bytes=0, pin_l0_bytes=0,   # cache is shared, attached below
            compaction_workers=1,            # 1 worker thread per shard pool
            tuner=None)                      # facade drives the one tuner;
                                             # shards must not double-drive
        self.shards: List[LSMStore] = [
            LSMStore(dataclasses.replace(shard_cfg),
                     scheduler_budget=self._budget, scheduler_offset=i)
            for i in range(n)]
        # Facade write gate: serializes snapshot acquisition against
        # facade-level writes (put/delete/batch/flush) AND rebalancing.
        # Without it a ``get_snapshot`` racing a cross-shard ``write_batch``
        # can pin shard 0 before the batch and shard 1 after it — a *torn*
        # snapshot that no single-store snapshot could ever expose.  RLock
        # because the batch entry points nest (``put_batch`` ->
        # ``write_batch``).  The single-writer discipline makes the gate
        # uncontended in every existing workload; only a concurrent
        # snapshot taker (or a rebalance) ever waits.
        self._write_gate = threading.RLock()
        # Per-shard load accounting (DESIGN.md §15).  _load is the decaying
        # trigger window (reset on rebalance, halved on each non-triggering
        # check so stale skew ages out); _load_total is cumulative for
        # reporting.  Plain-int bumps: racy under concurrent readers,
        # intentionally — load is a heuristic and the lock-free read path
        # must never take a lock.
        self._load = [0] * n
        self._load_total = [0] * n
        # Per-shard key-space histogram over the same decaying window: 32
        # buckets spanning the shard's current range.  This is the "cheap
        # per-shard load summary" that lets _derive_splitters cut at the
        # *measured* within-shard distribution — without it the derivation
        # assumes even spread and chases a concentrated hot range through
        # several geometric half-step migrations instead of one.  Reset
        # whenever the routing (and so the bucket geometry) changes.
        self._load_hist = [np.zeros(_HIST_B) for _ in range(n)]
        self._ops_since_check = 0
        self._rebalance_needed = False
        self._in_rebalance = False
        # The facade's own counters (routing, rebalances), one slot per
        # thread as in the shards; ``stats`` merges them with the shards'.
        self._stats = StatsHub()
        for si, s in enumerate(self.shards):
            # Live-config sharing: runtime toggles on the facade's config
            # reach every shard.  Construction-only fields (memtable size,
            # worker count, cache budgets) were already consumed above.
            s.config = self.config
            if s._scheduler is not None:
                # imbalance detection at compaction/quiesce boundaries:
                # the drained-queue hook only sets a flag (see _on_shard_idle)
                s._scheduler.on_idle = self._on_shard_idle
        self.block_cache: Optional[BlockCache] = None
        if self.config.cache_bytes > 0 or self.config.pin_l0_bytes > 0:
            self._build_shared_cache()
        # Online tuning (DESIGN.md §17): the facade is the tuner's single
        # driver (shard configs carried tuner=None at construction, so the
        # shards' own write paths never tick it); same cheap armed-counter
        # trigger shape as rebalancing.
        self._tuner = self.config.tuner
        self._tune_ops = 0
        self._tune_armed = False
        self._tune_prev_shard_stats: Optional[List[IOStats]] = None
        if self._tuner is not None:
            self._tuner.bind(self)

    # ------------------------------------------------------------ partition
    @property
    def _splitters(self) -> np.ndarray:
        return self._routing.arr

    @property
    def _splitters_list(self) -> List[int]:
        return self._routing.lst

    @property
    def splitters(self) -> Tuple[int, ...]:
        """The current routing bounds (moves when a rebalance lands)."""
        return tuple(self._routing.lst)

    def _shard_of(self, key: int) -> int:
        return self._routing.shard_of(key)

    def _split(self, keys_arr: np.ndarray) -> np.ndarray:
        """Vectorized shard assignment: one searchsorted for the batch."""
        return self._routing.split(keys_arr)

    def _note_ops(self, si: int, k: int = 1) -> None:
        self._load[si] += k
        self._load_total[si] += k
        self._ops_since_check += k

    def _note_key(self, si: int, key: int) -> None:
        """Scalar load note incl. the key-space histogram bucket."""
        self._note_ops(si)
        lo, hi = self._routing.bounds(si)
        b = int((key - lo) * _HIST_B / (hi - lo))
        h = self._load_hist[si]
        h[b if 0 <= b < _HIST_B else _HIST_B - 1] += 1.0

    def _note_keys(self, si: int, keys_arr: np.ndarray) -> None:
        """Batched load note: one bincount feeds the histogram.

        Racy-benign like the scalar counters (reads note without the
        gate); the histogram is a trigger heuristic, never a correctness
        input."""
        self._note_ops(si, int(keys_arr.size))
        lo, hi = self._routing.bounds(si)
        b = ((keys_arr.astype(np.float64) - lo)
             * (_HIST_B / float(hi - lo))).astype(np.int64)
        np.clip(b, 0, _HIST_B - 1, out=b)
        self._load_hist[si] += np.bincount(b, minlength=_HIST_B)

    # ---------------------------------------------------------------- cache
    def _build_shared_cache(self) -> None:
        """One budgeted BlockCache, one namespaced view + L0 slice per shard."""
        cfg = self.config
        n = len(self.shards)
        self.block_cache = BlockCache(cfg.cache_bytes, cfg.cache_policy)
        self.block_cache.telemetry = cfg.telemetry
        per_cache = cfg.cache_bytes // n
        per_pin = cfg.pin_l0_bytes // n
        for i, s in enumerate(self.shards):
            s.attach_cache(BlockCacheView(self.block_cache, i, per_cache),
                           per_pin)

    def configure_cache(self, cache_bytes: int, pin_l0_bytes: int = 0,
                        policy: Optional[str] = None) -> None:
        """(Re)build the shared memory subsystem on a live facade.

        Mirrors ``LSMStore.configure_cache``: replaces any existing cache
        (contents dropped), slices the budgets ``1/N`` per shard, and
        repins every shard's current L0 (charged).  Zeros detach.
        """
        self.config.cache_bytes = int(cache_bytes)
        self.config.pin_l0_bytes = int(pin_l0_bytes)
        if policy is not None:
            self.config.cache_policy = policy
        if cache_bytes <= 0 and pin_l0_bytes <= 0:
            self.block_cache = None
            for s in self.shards:
                s.block_cache = None
                s.pinned_l0 = None
            return
        self._build_shared_cache()

    # ------------------------------------------------------------- writes
    def put(self, key: int, value: bytes) -> None:
        with self._write_gate:
            si = self._routing.shard_of(key)
            self.shards[si].put(key, value)
            self._note_key(si, key)
        self._maybe_rebalance()
        if self._tuner is not None:
            self._maybe_tune(1)

    def delete(self, key: int) -> None:
        with self._write_gate:
            si = self._routing.shard_of(key)
            self.shards[si].delete(key)
            self._note_key(si, key)
        self._maybe_rebalance()
        if self._tuner is not None:
            self._maybe_tune(1)

    def put_batch(self, keys, values) -> None:
        """Batched puts, split per shard by one vectorized searchsorted.

        A broadcast value (one ``bytes`` for every key) splits entirely in
        numpy — no per-element Python indexing on the ingest hot path."""
        if isinstance(values, (bytes, bytearray)):
            keys_arr = np.asarray(keys, dtype=KEY_DTYPE)
            val = bytes(values)
            with self._write_gate:
                sids = self._routing.split(keys_arr)
                for si in np.unique(sids):
                    sel = keys_arr[sids == si]
                    self.shards[int(si)].put_batch(sel.tolist(), val)
                    self._note_keys(int(si), sel)
            self._maybe_rebalance()
            if self._tuner is not None:
                self._maybe_tune(int(keys_arr.size))
            return
        self.write_batch(zip(keys, values))

    def delete_batch(self, keys) -> None:
        self.write_batch((k, None) for k in keys)

    def write_batch(self, ops: Iterable[Tuple[int, Optional[bytes]]]) -> None:
        """Batched puts + deletes: one searchsorted assigns every op its
        shard; each shard then ingests its sub-batch through its own
        vectorized ``write_batch`` lane.  Per-key op order is preserved
        (the split is a stable partition), so the final state equals the
        single-store oracle's for the same sequence.
        """
        pairs = list(ops)
        if not pairs:
            return
        keys_arr = np.fromiter((int(k) for k, _ in pairs), KEY_DTYPE,
                               len(pairs))
        with self._write_gate:
            # split under the gate: routing must not move between
            # assignment and the per-shard writes
            sids = self._routing.split(keys_arr)
            for si in np.unique(sids):
                idx = np.nonzero(sids == si)[0]
                self.shards[int(si)].write_batch(pairs[int(j)] for j in idx)
                self._note_keys(int(si), keys_arr[idx])
        self._maybe_rebalance()
        if self._tuner is not None:
            self._maybe_tune(len(pairs))

    def flush(self) -> None:
        with self._write_gate:
            for s in self.shards:
                s.flush()
        self._maybe_rebalance()
        if self._tuner is not None:
            self._maybe_tune(0)

    def fsync_wal(self) -> None:
        """Durability barrier on every shard's active WAL."""
        for s in self.shards:
            s.fsync_wal()

    # -------------------------------------------------------------- reads
    def _shard_snap(self, snapshot: Optional[ShardedSnapshot], si: int
                    ) -> Optional[Version]:
        return None if snapshot is None else snapshot.versions[si]

    def _snap_routing(self, snapshot: ShardedSnapshot) -> _Routing:
        r = snapshot.routing
        return r if r is not None else self._routing

    def get(self, key: int,
            snapshot: Optional[ShardedSnapshot] = None) -> Optional[bytes]:
        if snapshot is not None:
            si = self._snap_routing(snapshot).shard_of(key)
            return self.shards[si].get(key, snapshot=snapshot.versions[si])
        while True:
            r = self._routing
            si = r.shard_of(key)
            out = self.shards[si].get(key)
            if self._routing is r:   # no migration landed mid-read
                self._note_key(si, key)
                return out

    def multi_get(self, keys: Sequence[int],
                  snapshot: Optional[ShardedSnapshot] = None
                  ) -> List[Optional[bytes]]:
        """Batched point reads: one searchsorted splits the wave, each
        shard resolves its sub-batch with its own vectorized ``multi_get``,
        and results scatter back to the callers' positions.

        Charges the facade's routing counters: the call's wall time, the
        part of it outside the shard calls (``route_ns``), the keys, the
        largest shard's part of them and the shard calls made."""
        t0 = time.perf_counter_ns()
        keys_arr = np.asarray(list(keys), dtype=KEY_DTYPE)
        n = int(keys_arr.size)
        if n == 0:
            return []
        shard_ns = 0
        with span("lsm.route", keys=n):
            while True:
                r = self._routing if snapshot is None \
                    else self._snap_routing(snapshot)
                results, ns, hot, calls = self._multi_get_routed(
                    r, keys_arr, snapshot)
                shard_ns += ns
                if snapshot is not None or self._routing is r:
                    break
        dt = time.perf_counter_ns() - t0
        st = self._stats.local()
        st.sharded_multi_gets += 1
        st.sharded_multi_get_ns += dt
        st.route_ns += dt - shard_ns
        st.routed_keys += n
        st.hot_shard_keys += hot
        st.route_shards += r.n
        st.shard_calls += calls
        return results

    def _multi_get_routed(self, r: _Routing, keys_arr: np.ndarray,
                          snapshot: Optional[ShardedSnapshot]
                          ) -> Tuple[List[Optional[bytes]], int, int, int]:
        """One routed attempt: the answers, the nanoseconds the shards'
        own ``multi_get`` timers charged, the largest shard's key count
        and the number of shard calls."""
        results: List[Optional[bytes]] = [None] * int(keys_arr.size)
        sids = r.split(keys_arr)
        shard_ns = hot = calls = 0
        for si in np.unique(sids):
            si = int(si)
            idx = np.nonzero(sids == si)[0]
            shard = self.shards[si]
            timer = shard._stats.local()
            before = timer.multi_get_ns
            sub = shard.multi_get(keys_arr[idx],
                                  snapshot=self._shard_snap(snapshot, si))
            shard_ns += timer.multi_get_ns - before
            hot = max(hot, int(idx.size))
            calls += 1
            for j, v in zip(idx, sub):
                results[int(j)] = v
            if snapshot is None:
                self._note_keys(si, keys_arr[idx])
        return results, shard_ns, hot, calls

    def seek(self, key: int,
             snapshot: Optional[ShardedSnapshot] = None) -> Optional[int]:
        """First key >= key across shards: because the partition is
        order-preserving, the first shard (in range order) with any
        in-range result holds the global minimum."""
        if snapshot is not None:
            return self._seek_routed(self._snap_routing(snapshot), key,
                                     snapshot)
        while True:
            r = self._routing
            got = self._seek_routed(r, key, None)
            if self._routing is r:
                return got

    def _seek_routed(self, r: _Routing, key: int,
                     snapshot: Optional[ShardedSnapshot]) -> Optional[int]:
        for si in range(r.shard_of(key), len(self.shards)):
            lo, hi = r.bounds(si)
            got = self.shards[si].seek(max(int(key), lo),
                                       snapshot=self._shard_snap(snapshot, si))
            if got is not None and got < hi:
                return got
        return None

    def scan(self, start_key: int, count: int,
             snapshot: Optional[ShardedSnapshot] = None
             ) -> List[Tuple[int, bytes]]:
        """Range read: shard-ordered concatenation of per-shard scans (no
        cross-shard merge needed — shard i's keys all precede shard i+1's).
        Byte-identical to the single-store oracle's ``scan``/``scan_scalar``.
        """
        return self._scan_impl(start_key, count, snapshot, scalar=False)

    def scan_scalar(self, start_key: int, count: int,
                    snapshot: Optional[ShardedSnapshot] = None
                    ) -> List[Tuple[int, bytes]]:
        """Reference range read through every shard's ``scan_scalar``."""
        return self._scan_impl(start_key, count, snapshot, scalar=True)

    def _scan_impl(self, start_key: int, count: int,
                   snapshot: Optional[ShardedSnapshot], scalar: bool
                   ) -> List[Tuple[int, bytes]]:
        if snapshot is not None:
            return self._scan_routed(self._snap_routing(snapshot), start_key,
                                     count, snapshot, scalar)
        while True:
            r = self._routing
            out = self._scan_routed(r, start_key, count, None, scalar)
            if self._routing is r:
                return out

    def _scan_routed(self, r: _Routing, start_key: int, count: int,
                     snapshot: Optional[ShardedSnapshot], scalar: bool
                     ) -> List[Tuple[int, bytes]]:
        out: List[Tuple[int, bytes]] = []
        for si in range(r.shard_of(int(start_key)), len(self.shards)):
            need = count - len(out)
            if need <= 0:
                break
            lo, hi = r.bounds(si)
            shard = self.shards[si]
            fn = shard.scan_scalar if scalar else shard.scan
            part = fn(max(int(start_key), lo), need,
                      snapshot=self._shard_snap(snapshot, si))
            if part and part[-1][0] >= hi:
                # mid-migration only: clip entries the captured routing
                # assigns to a later shard.  Results are sorted, so every
                # in-range entry precedes the clipped tail — the kept
                # prefix is complete and the next shard continues it.
                keys = [k for k, _ in part]
                part = part[:bisect_left(keys, hi)]
            out.extend(part)
        return out[:count]

    # ----------------------------------------------------------- snapshots
    def get_snapshot(self) -> ShardedSnapshot:
        """Pin every shard's current version atomically w.r.t. facade writes.

        Two mechanisms make the pinned tuple a point-in-time cut instead of
        a torn one:

        1. The facade **write gate**: acquisition holds the same lock every
           facade write path (and a rebalance) takes, so a concurrent
           cross-shard ``write_batch``/``flush``/migration is either
           entirely before or entirely after the snapshot — never
           half-visible.  (Pinning shard 0, losing the CPU to a writer that
           lands on shards 0 *and* 1, then pinning shard 1 was exactly the
           torn interleaving.)
        2. **Pin-validate-retry** against background installs: after
           pinning all shards, each shard's current version id is re-read;
           if any shard installed a version mid-acquisition (async flush or
           compaction on a worker thread), the pins are released and the
           tuple is re-taken.  Installs are rate-limited by real merge
           work, so the seqlock-style loop settles immediately in practice.

        The snapshot also captures the routing it was taken under (stable
        here — the gate excludes migrations): its reads route with those
        splitters forever, and the pins keep any since-migrated runs alive
        in their original shard.

        Remaining async-mode caveat (documented, not defended): snapshots
        see only *installed* versions, never memtables, and each shard's
        background flush runs on its own schedule — so the halves of an
        already-acked batch can *enter* snapshot visibility at different
        times.  The gate guarantees the snapshot never splits a facade
        write's acquisition; quiesce (or sync mode) before snapshotting
        when cross-shard batch atomicity of *visibility* is required.
        """
        with self._write_gate:
            while True:
                pins = tuple(s.get_snapshot() for s in self.shards)
                if all(p.version_id == s.manifest.current().version_id
                       for s, p in zip(self.shards, pins)):
                    return ShardedSnapshot(pins, self._routing)
                tel = self.config.telemetry
                if tel is not None:
                    tel.emit("snapshot_retry", shards=len(self.shards))
                for s, p in zip(self.shards, pins):
                    s.release_snapshot(p)

    def release_snapshot(self, snapshot: ShardedSnapshot) -> None:
        for s, v in zip(self.shards, snapshot.versions):
            s.release_snapshot(v)

    # ---------------------------------------------------------- rebalancing
    def _on_shard_idle(self) -> None:
        """Scheduler-worker hook at a drained-queue boundary: flag only.

        A worker thread must never *run* the rebalance — the migration
        quiesces that worker's own scheduler, which would deadlock — so the
        hook just records that the window looks skewed; the next foreground
        write (or ``wait_for_quiesce``) consumes the flag.
        """
        cfg = self.config
        iv = cfg.rebalance_interval_ops
        if iv <= 0 or self._in_rebalance or self._ops_since_check < iv:
            return
        loads = self._load
        tot = sum(loads)
        if tot and max(loads) * len(loads) >= cfg.rebalance_ratio * tot:
            self._rebalance_needed = True

    def _maybe_rebalance(self) -> bool:
        """Write-boundary trigger: cheap flag/counter test, full check at
        most every ``rebalance_interval_ops`` routed ops."""
        cfg = self.config
        if cfg.rebalance_interval_ops <= 0 or self._in_rebalance:
            return False
        if not self._rebalance_needed \
                and self._ops_since_check < cfg.rebalance_interval_ops:
            return False
        return self.rebalance_now()

    def arm_rebalancing(self, interval_ops: int,
                        ratio: Optional[float] = None) -> None:
        """Enable (or retune) automatic rebalancing on a live facade.

        Resets the load window.  The intended use is bulk-load-then-serve:
        a sequential preload looks maximally skewed to the windowed tracker
        (every sorted wave lands in one shard), so load with
        ``rebalance_interval_ops=0`` and arm once the serving phase starts.
        """
        with self._write_gate:
            self.config.rebalance_interval_ops = int(interval_ops)
            if ratio is not None:
                self.config.rebalance_ratio = float(ratio)
            self._load = [0] * len(self.shards)
            self._load_hist = [np.zeros(_HIST_B)
                               for _ in range(len(self.shards))]
            self._ops_since_check = 0
            self._rebalance_needed = False

    def rebalance_now(self, force: bool = False) -> bool:
        """Evaluate the load window and rebalance if it is skewed (or
        ``force``).  Returns True iff a migration landed."""
        return self._rebalance(None, force)

    def rebalance_to(self, splitters: Sequence[int]) -> bool:
        """Migrate to an explicit splitter vector (tests / operators).

        Same protocol as the automatic path, skipping derivation."""
        lst = [int(x) for x in splitters]
        if len(lst) != len(self.shards) - 1:
            raise ValueError(
                f"need {len(self.shards) - 1} splitters, got {len(lst)}")
        if lst != sorted(set(lst)):
            raise ValueError("splitters must be strictly ascending")
        return self._rebalance(lst, True)

    def _rebalance(self, target: Optional[List[int]], force: bool) -> bool:
        if self._in_rebalance:       # reentrancy (quiesce inside migration)
            return False
        with self._write_gate:
            if self._in_rebalance:
                return False
            self._in_rebalance = True
            try:
                self._rebalance_needed = False
                self._ops_since_check = 0
                loads = list(self._load)
                tot = sum(loads)
                n = len(self.shards)
                ratio = (max(loads) * n / tot) if tot else 1.0
                if not force and ratio < self.config.rebalance_ratio:
                    # decay the window so stale skew ages out
                    self._load = [v // 2 for v in loads]
                    self._load_hist = [h * 0.5 for h in self._load_hist]
                    return False
                with span("lsm.rebalance", imbalance=round(ratio, 3)):
                    return self._rebalance_to(target, loads, ratio)
            finally:
                self._in_rebalance = False

    def _rebalance_to(self, target: Optional[List[int]],
                      loads: List[int], ratio: float) -> bool:
        """The migration protocol (gate held, ``_in_rebalance`` set).

        Order is the crash-safety argument (DESIGN.md §15): (1) quiesce —
        memtables become runs, schedulers drain; (2) build + durably commit
        import runs in every destination; (3) append the new splitters to
        the durable routing log, then publish the reader-visible routing
        swap; (4) strip each source to its new range (durable per shard).
        A crash before (3) recovers the old routing and the recovery clip
        drops the committed imports — exact pre-migration state; a crash
        after (3) recovers the new routing and the clip finishes the
        source cleanup — exact post-migration state.
        """
        t0 = time.perf_counter_ns()
        n = len(self.shards)
        # (1) quiesce: the migration operates on a settled, run-only tree
        for s in self.shards:
            s.flush()
        for s in self.shards:
            if not s.wait_for_quiesce(timeout=120.0):
                return False     # nothing mutated yet: clean abort
        old = self._routing
        new_lst = target if target is not None \
            else self._derive_splitters(loads)
        if new_lst is None or list(new_lst) == old.lst:
            self._load = [v // 2 for v in loads]
            self._load_hist = [h * 0.5 for h in self._load_hist]
            return False
        new = _Routing(new_lst, old.epoch + 1)
        tel = self.config.telemetry
        if tel is not None:
            tel.emit("rebalance_start", epoch=new.epoch,
                     imbalance=round(ratio, 3), window_ops=int(sum(loads)))
        if self._budget is not None:
            self._budget.acquire()   # migration rides the worker budget —
        try:                         # acquired AFTER quiesce (a drained
            # pipeline holds no permit; permit-then-quiesce deadlocks
            # at budget=1)
            moves, moved = self._install_imports(old, new)      # (2)
            self._commit_routing(new)                           # (3)
            self._cleanup_sources(new)                          # (4)
        finally:
            if self._budget is not None:
                self._budget.release()
        if tel is not None:
            for si in range(n):
                ol, oh = old.bounds(si)
                nl, nh = new.bounds(si)
                if (nl, nh) == (ol, oh):
                    continue
                if nl >= ol and nh <= oh:
                    tel.emit("shard_split", shard=si, lo=nl, hi=nh)
                elif nl <= ol and nh >= oh:
                    tel.emit("shard_merge", shard=si, lo=nl, hi=nh)
                else:                # slid sideways: shrank one side,
                    tel.emit("shard_shift", shard=si, lo=nl, hi=nh)  # grew the other
        self._reassign_cache_budgets(loads)
        # the moved data lands as L0 runs and the stripped sources may be
        # under-shaped: reshape in the background (no-op when shaped; sync
        # mode compacts inline to stay the deterministic oracle)
        for s in self.shards:
            if s._scheduler is not None:
                s._scheduler.submit(CompactJob())
            else:
                s._compact_until_quiet()
        self._stats.local().rebalances += 1
        self._load = [0] * n
        self._load_hist = [np.zeros(_HIST_B) for _ in range(n)]
        dur = time.perf_counter_ns() - t0
        if tel is not None:
            tel.record("rebalance", dur)
            tel.emit("rebalance_end", epoch=new.epoch, moves=moves,
                     entries=moved, t0=t0, dur_ns=dur)
        return True

    def _derive_splitters(self, loads: List[int]) -> Optional[List[int]]:
        """Load-weighted key quantiles over the shards' stored keys.

        Each shard's unique key set (stride-subsampled when huge) carries
        its window load distributed by the shard's key-space histogram —
        keys in hot buckets weigh more, so a concentrated hot range is cut
        at its *measured* median in one step instead of being chased
        through several even-spread half-migrations.  The global cumsum is
        cut at i/n of total weight, which simultaneously splits hot shards
        and merges cold neighbours.  Returns None when there is no data
        (or no usable cut).
        """
        n = len(self.shards)
        routing = self._routing
        keys_parts: List[np.ndarray] = []
        w_parts: List[np.ndarray] = []
        for si, s in enumerate(self.shards):
            runs = [r for lvl in s._levels for r in lvl if len(r)]
            if not runs:
                continue
            if len(runs) == 1:
                k = runs[0].keys
            else:
                k = np.unique(np.concatenate([r.keys for r in runs]))
            stride = max(1, k.size // 65536)
            if stride > 1:
                k = k[::stride]
            keys_parts.append(k)
            # bucket each sampled key, spread the bucket's observed load
            # over its keys; smooth with 1/8 of a uniform mass so buckets
            # the window never touched still get a floor (and a shard with
            # an empty histogram degrades to the even-spread weighting)
            lo, hi = routing.bounds(si)
            h = self._load_hist[si]
            b = ((k.astype(np.float64) - lo)
                 * (_HIST_B / float(hi - lo))).astype(np.int64)
            np.clip(b, 0, _HIST_B - 1, out=b)
            wb = h + max(float(h.sum()), 1.0) / (_HIST_B * 8.0)
            wb *= (loads[si] + 1.0) / wb.sum()
            cnt = np.maximum(np.bincount(b, minlength=_HIST_B), 1)
            w_parts.append(wb[b] / cnt[b])
        if not keys_parts:
            return None
        K = np.concatenate(keys_parts)   # sorted: shard ranges are disjoint
        W = np.concatenate(w_parts)
        cum = np.cumsum(W)
        targets = float(cum[-1]) * np.arange(1, n) / n
        idx = np.minimum(np.searchsorted(cum, targets), K.size - 1)
        out: List[int] = []
        prev = -1
        for c in K[idx]:
            c = int(c)
            if c <= prev:            # enforce strictly ascending
                c = prev + 1
            out.append(c)
            prev = c
        if out[-1] >= _KEY_SPACE_END:
            return None              # fix-up ran off the key space
        return out

    def _install_imports(self, old: _Routing, new: _Routing
                         ) -> Tuple[int, int]:
        """Step (2): durably commit every leaving-range slice into its new
        owner as a fresh L0 run (deduped newest-wins; whole-key tombstones
        collapse — the destination owned nothing in the moved range, so
        nothing live is shadowed)."""
        tel = self.config.telemetry
        moves = moved = 0
        for si, s in enumerate(self.shards):
            ol, oh = old.bounds(si)
            nl, nh = new.bounds(si)
            # what shard si gives away = its old range minus its new range:
            # at most a low-side and a high-side interval
            for lo, hi in ((ol, min(oh, nl)), (max(ol, nh), oh)):
                if lo >= hi:
                    continue
                with span("lsm.migrate_export", shard=si):
                    cols = s.export_range(lo, hi)
                if cols is None:
                    continue
                k, sq, vl, vv = cols
                dest_ids = new.split(k)
                for dj in np.unique(dest_ids):
                    dj = int(dj)
                    mask = dest_ids == dj
                    dst = self.shards[dj]
                    with span("lsm.migrate_import", src=si, dst=dj,
                              entries=int(mask.sum())):
                        run = build_run(k[mask], sq[mask], vl[mask],
                                        vv[mask],
                                        bits_per_key=dst._bits_for_level(0),
                                        drop_tombstones=True,
                                        block_size=self.config.block_size,
                                        key_bytes=self.config.key_bytes,
                                        hash_fn=dst._bloom_hash_fn())
                        if len(run) == 0:
                            continue     # the slice was all tombstones
                        dst.import_migrated_run(run)
                    moves += 1
                    moved += len(run)
                    if tel is not None:
                        tel.emit("run_migrate", src=si, dst=dj,
                                 entries=len(run), bytes=run.data_bytes)
        self._stats.local().migrated_entries += moved
        return moves, moved

    def _commit_routing(self, new: _Routing) -> None:
        """Step (3): durable intent first (the log append is fsynced
        immediately — routing changes are rare), then the reader-visible
        reference swap.  Everything written after this point routes — and
        is WAL-logged — under the new splitters, which is the invariant
        recovery's range clip relies on."""
        self._routing_log.append(tuple(new.lst))
        self._routing_synced = len(self._routing_log)
        self._routing = new

    def _cleanup_sources(self, new: _Routing) -> None:
        """Step (4): drop each shard's moved-away entries (durable per
        shard; a crash part-way is finished by recovery's clip)."""
        for si, s in enumerate(self.shards):
            lo, hi = new.bounds(si)
            with span("lsm.strip", shard=si):
                s.strip_to_range(lo, hi)

    def _reassign_cache_budgets(self, loads: List[int]) -> None:
        """Re-slice the shared cache load-proportionally (1/(4N) floor).

        A merged cold shard hands its idle budget back to the hot range;
        namespaces never renumber, so no entries are invalidated — only
        the admission budgets move."""
        if self.block_cache is None or self.config.cache_bytes <= 0:
            return
        total = self.config.cache_bytes
        n = len(self.shards)
        base = (sum(loads) + n) // (3 * n) + 1   # floor ≈ 1/(4N) share
        w = [ld + base for ld in loads]
        wsum = sum(w)
        budgets = [total * wi // wsum for wi in w]
        budgets[max(range(n), key=lambda i: w[i])] += total - sum(budgets)
        for i, s in enumerate(self.shards):
            if s.block_cache is not None:
                s.block_cache.budget_bytes = budgets[i]
            self.block_cache.set_ns_budget(i, budgets[i])

    # --------------------------------------------- online tuning (§17)
    def _shards_idle(self) -> bool:
        """True at a facade-wide compaction-chain boundary (sync shards
        are always at one)."""
        return all(s._scheduler is None or s._scheduler.idle()
                   for s in self.shards)

    def _maybe_tune(self, k: int = 0) -> None:
        """Write-boundary tuning trigger (the ``_maybe_rebalance`` shape):
        count routed ops, arm at ``interval_ops``, fire at the first
        all-shards-idle boundary."""
        tun = self._tuner
        self._tune_ops += k
        if not self._tune_armed:
            if self._tune_ops < tun.interval_ops:
                return
            self._tune_armed = True
        if not self._shards_idle():
            return
        self._tune_ops = 0
        self._tune_armed = False
        with self._write_gate:
            tun.tick(self)

    def apply_tuning(self) -> Optional[TunerStep]:
        """Run one tuner tick now iff every shard is at a boundary — the
        facade twin of ``LSMStore.apply_tuning`` (DESIGN.md §17).  Taken
        under the write gate so a concurrent snapshot can never observe a
        half-applied actuation."""
        tun = self._tuner
        if tun is None or not self._shards_idle():
            return None
        self._tune_ops = 0
        self._tune_armed = False
        with self._write_gate:
            return tun.tick(self)

    def compact_to_shape(self, timeout: Optional[float] = 600.0) -> int:
        """Maintenance reshape across shards (``LSMStore.compact_to_shape``):
        drain every shard, then fold each shard's tree to its (re)tuned
        policy's predicted level count.  Foreground, under the write gate —
        the explicit maintenance window after a policy retune widened the
        capacity schedule.  Returns total maintenance merges."""
        with self._write_gate:
            if not self.wait_for_quiesce(timeout):
                return 0
            return sum(s.compact_to_shape() for s in self.shards)

    def retune_policy(self, *, T: Optional[float] = None,
                      c: Optional[float] = None) -> None:
        """Swap every shard's policy to a same-family one with new knobs
        (tuner actuator); future compaction targets only, trees never
        rewritten."""
        cfg = self.config
        if T is not None:
            cfg.T = float(T)
        if c is not None:
            cfg.c = float(c)
        for s in self.shards:
            s.policy = s.policy.retuned(T=cfg.T, c=cfg.c)

    def resize_worker_budget(self, n: int) -> bool:
        """Retarget the shared worker-budget semaphore (tuner actuator).
        Shrinks only land when the permits are free — apply_tuning calls
        this at an all-idle boundary, where they are."""
        if self._budget is None:
            return False
        ok = self._budget.resize(n)
        if ok:
            self.config.compaction_workers = self._budget.size
        return ok

    def set_cache_split(self, pin_l0_bytes: int) -> None:
        """Facade twin of ``LSMStore.set_cache_split``: move budget between
        the shared cache and the per-shard pinned-L0 slices at constant
        total memory.  Gentle — the shared cache evicts down in place and
        each namespace budget rescales proportionally (preserving any
        miss-weighted skew the budget rule has built up); no contents are
        dropped wholesale."""
        if self.block_cache is None:
            return
        cfg = self.config
        total = cfg.cache_bytes + cfg.pin_l0_bytes
        pin = max(0, min(int(pin_l0_bytes), total))
        cache = total - pin
        scale = cache / cfg.cache_bytes if cfg.cache_bytes > 0 else 0.0
        cfg.cache_bytes = cache
        cfg.pin_l0_bytes = pin
        self.block_cache.resize(cache)
        n = len(self.shards)
        per_pin = pin // n
        for s in self.shards:
            v = s.block_cache
            if v is not None:
                v.resize(int(v.budget_bytes * scale) if scale > 0
                         else cache // n)
            if s.pinned_l0 is not None:
                s.pinned_l0.pin_l0_bytes = per_pin
                with s._maint_lock:
                    s.pinned_l0.repin(s._levels[0], stats=s._stats.local())

    def _get_pin_frac(self) -> float:
        total = self.config.cache_bytes + self.config.pin_l0_bytes
        return self.config.pin_l0_bytes / total if total else 0.0

    def _set_pin_frac(self, v: float) -> None:
        total = self.config.cache_bytes + self.config.pin_l0_bytes
        self.set_cache_split(int(total * float(v)))

    def _tuning_actuators(self):
        """Facade knob set: level ratios fan out to every shard; pressure
        and worker knobs act on the shared config/budget."""
        acts = {
            "c": (lambda: self.shards[0].policy.c,
                  lambda v: self.retune_policy(c=v)),
            "T": (lambda: self.shards[0].policy.T,
                  lambda v: self.retune_policy(T=v)),
        }
        if self.config.async_compaction:
            acts["slowdown_trigger"] = (
                lambda: self.config.slowdown_trigger,
                lambda v: setattr(self.config, "slowdown_trigger", int(v)))
        if self._budget is not None:
            acts["compaction_workers"] = (lambda: self._budget.size,
                                          self.resize_worker_budget)
        if self.block_cache is not None and self.config.cache_bytes \
                + self.config.pin_l0_bytes > 0:
            acts["pin_frac"] = (self._get_pin_frac, self._set_pin_frac)
        return acts

    def _tuning_rules(self, window, stats_delta) -> None:
        """Rule-based actuation the tuner runs every tick (no hill-climb):
        shift shared-cache namespace budgets toward hit-rate-starved
        shards.  Same floor/weighting shape as the rebalance-time
        ``_reassign_cache_budgets``, but weighted by each shard's *window*
        cache misses (the starvation signal) instead of routed ops."""
        if self.block_cache is None or self.config.cache_bytes <= 0:
            return
        cur = [s.stats for s in self.shards]
        prev = self._tune_prev_shard_stats
        self._tune_prev_shard_stats = cur
        if prev is None:
            return
        misses = [c.delta(p).cache_miss_blocks
                  for c, p in zip(cur, prev)]
        if sum(misses) <= 0:
            return
        total = self.config.cache_bytes
        n = len(self.shards)
        base = (sum(misses) + n) // (3 * n) + 1   # floor ≈ 1/(4N) share
        w = [m + base for m in misses]
        wsum = sum(w)
        budgets = [total * wi // wsum for wi in w]
        budgets[max(range(n), key=lambda i: w[i])] += total - sum(budgets)
        for i, s in enumerate(self.shards):
            if s.block_cache is not None:
                s.block_cache.resize(budgets[i])
            else:
                self.block_cache.set_ns_budget(i, budgets[i])

    # ------------------------------------------------------------ recovery
    def crash(self) -> None:
        """Whole-store crash: every shard aborts its background pipeline and
        loses volatile state; each shard's fsynced WAL segments + durable
        manifest survive independently, as does the synced prefix of the
        routing log."""
        for s in self.shards:
            s.crash()
        del self._routing_log[self._routing_synced:]

    def recover(self) -> None:
        """Recover every shard (durable manifest + consolidated WAL replay),
        restore the last durable routing, and clip each shard to its routed
        range — which atomically resolves a crash mid-migration to either
        the exact pre-migration state (routing commit didn't land: the
        clip drops the already-committed import copies) or the exact
        post-migration state (it did: the clip finishes the source
        cleanup).  Replayed WAL/memtable contents are always in-range
        w.r.t. the recovered routing, because writes only ever route under
        a routing that was durably logged first."""
        routing = _Routing(self._routing_log[-1],
                           epoch=len(self._routing_log) - 1)
        self._routing = routing
        for si, s in enumerate(self.shards):
            s.recover()
            lo, hi = routing.bounds(si)
            s.strip_to_range(lo, hi)
        self._load = [0] * len(self.shards)
        self._load_hist = [np.zeros(_HIST_B) for _ in range(len(self.shards))]
        self._ops_since_check = 0
        self._rebalance_needed = False

    def close(self) -> None:
        """Drain and stop every shard's background workers (each shard then
        serves on the synchronous, state-equivalent path)."""
        err = None
        for s in self.shards:
            try:
                s.close()
            except BaseException as e:   # close every shard before raising
                err = err or e
        if err is not None:
            raise err

    def wait_for_quiesce(self, timeout: Optional[float] = None) -> bool:
        """Block until every shard's background pipeline drains.

        A quiesce is also a rebalance boundary: if the drained window is
        skewed past the trigger, the migration runs here (foreground
        thread, gate taken inside) and its reshaping jobs are drained
        within the same deadline — after a True return the facade is both
        settled *and* balanced w.r.t. the closed window."""
        deadline = None if timeout is None else time.monotonic() + timeout
        ok = self._drain_shards(deadline)
        if ok and not self._in_rebalance and self._maybe_rebalance():
            ok = self._drain_shards(deadline)
        if ok and self._tuner is not None and self._tune_armed:
            self.apply_tuning()
        return ok

    def _drain_shards(self, deadline: Optional[float]) -> bool:
        ok = True
        for s in self.shards:
            left = None if deadline is None \
                else max(0.0, deadline - time.monotonic())
            ok = s.wait_for_quiesce(left) and ok
        return ok

    # ------------------------------------------------- integrity (§16)
    @property
    def degraded(self) -> bool:
        """True when any shard is read-only after persistent background
        failure.  Degradation is per-shard: writes routed to a degraded
        shard raise ``StoreDegradedError`` while every other shard keeps
        accepting writes, and reads keep serving everywhere."""
        return any(s.degraded for s in self.shards)

    def degraded_shards(self) -> List[int]:
        """Indices of read-only shards (empty list == fully writable)."""
        return [si for si, s in enumerate(self.shards) if s.degraded]

    def scrub(self) -> List[dict]:
        """Verify block checksums across every shard's runs; per-run report
        dicts (shard-tagged) in shard order — the facade twin of
        ``LSMStore.scrub``."""
        report: List[dict] = []
        for si, s in enumerate(self.shards):
            for r in s.scrub():
                r["shard"] = si
                report.append(r)
        return report

    # ---------------------------------------------------------------- info
    @property
    def stats(self) -> IOStats:
        """The facade's own counters summed with every shard's (a fresh
        fieldwise-summed ``IOStats`` — use ``snapshot()``/``delta()`` on it
        as usual)."""
        return IOStats.merge([self._stats.merged()]
                             + [s.stats for s in self.shards])

    @property
    def rebalances(self) -> int:
        """Rebalances whose migration landed."""
        return self._stats.merged().rebalances

    @property
    def migrated_entries(self) -> int:
        """Entries those rebalances moved across shards."""
        return self._stats.merged().migrated_entries

    @property
    def shard_stats(self) -> List[dict]:
        """Per-shard ``IOStats.to_dict()``, in shard order — the raw
        per-shard sensor block behind ``shard_load_summary``."""
        return [s.stats.to_dict() for s in self.shards]

    def shard_load_ops(self) -> List[int]:
        """Cumulative facade ops (reads + writes) routed per shard.
        Benchmarks diff two calls to get a window's imbalance."""
        return list(self._load_total)

    def shard_load_summary(self) -> List[dict]:
        """Cheap per-shard load/pressure summary: routed-op share, live
        bytes, and the stall/write counters rebalancing decisions read."""
        n = len(self.shards)
        tot = sum(self._load_total) or 1
        out = []
        for si, s in enumerate(self.shards):
            lo, hi = self._routing.bounds(si)
            st = s.stats
            phys, _ = s._space_profile()
            out.append(dict(shard=si, lo=lo, hi=hi,
                            ops=self._load_total[si],
                            op_share=self._load_total[si] / tot,
                            window_ops=self._load[si],
                            live_bytes=phys,
                            entries=s.total_entries,
                            wal_appends=st.wal_appends,
                            point_reads=st.point_reads,
                            range_reads=st.range_reads,
                            stall_ns=st.stall_ns))
        return out

    @property
    def telemetry(self):
        """The facade's (and, by live-config sharing, every shard's)
        Telemetry — one object aggregates all shards' histograms/events."""
        return self.config.telemetry

    @property
    def num_levels_in_use(self) -> int:
        return max(s.num_levels_in_use for s in self.shards)

    @property
    def total_entries(self) -> int:
        return sum(s.total_entries for s in self.shards)

    def total_live_entries(self) -> int:
        return sum(s.total_live_entries() for s in self.shards)

    def space_amplification(self) -> float:
        phys = logical = 0
        for s in self.shards:
            p, lg = s._space_profile()
            phys += p
            logical += lg
        return phys / logical if logical else 1.0

    def level_summary(self) -> List[dict]:
        """Per-level aggregate across shards (capacities summed)."""
        out: List[dict] = []
        for s in self.shards:
            for d in s.level_summary():
                i = d["level"]
                while len(out) <= i:
                    out.append(dict(level=len(out), runs=0, entries=0,
                                    bytes=0, capacity=None))
                out[i]["runs"] += d["runs"]
                out[i]["entries"] += d["entries"]
                out[i]["bytes"] += d["bytes"]
                if d["capacity"] is not None:
                    out[i]["capacity"] = (out[i]["capacity"] or 0) \
                        + d["capacity"]
        return out

    def cache_summary(self) -> dict:
        """Shared-cache health: one hit rate, global charged bytes, and the
        number of DRAM-resident L0 runs across all shards."""
        if self.block_cache is None:
            return dict(enabled=False, hit_rate=0.0, hits=0, misses=0,
                        evictions=0, charged_bytes=0, pinned_bytes=0,
                        pinned_l0_runs=0)
        c = self.block_cache
        return dict(enabled=True, hit_rate=c.hit_rate(), hits=c.hits,
                    misses=c.misses, evictions=c.evictions,
                    charged_bytes=c.charged_bytes,
                    pinned_bytes=c.pinned_bytes,
                    pinned_l0_runs=sum(
                        len(s.pinned_l0.pinned_run_ids) for s in self.shards
                        if s.pinned_l0 is not None))


def make_store(config: Optional[LSMConfig] = None):
    """The store factory every call site uses: a plain :class:`LSMStore`
    for ``shards <= 1`` (the retained bit-for-bit oracle path), a
    :class:`ShardedLSMStore` facade otherwise — the ``LSMConfig.shards``
    knob is the only thing a caller changes."""
    config = config or LSMConfig()
    if config.shards <= 1:
        return LSMStore(config)
    return ShardedLSMStore(config)

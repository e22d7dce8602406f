"""Three-tier server model store (paper Fig. 1 + Algorithm 1 server side).

Levels: "global" (one model), "cluster" (one per cluster key, keys are
namespaced e.g. "loc:2" / "ori:1"), and client-side "local" models which
never touch the server.  ``handle_model_update`` implements the server
update handler with per-model locking (lines 19-25 of Algorithm 1).

Batched mode (``batch_aggregation=True``): clients enqueue updates without
blocking on the model lock; a drain step folds every queued update for a
model into one ``coalesced_aggregate`` call — at most one N-way weighted
sum (one Pallas kernel launch with ``use_pallas=True``) per drained batch
instead of one full parameter pass per update.  Semantics are identical to
the sequential fold (see ``coalesced_aggregate``).

Secure mode (``masker`` attached): clients submit masked weighted deltas via
``submit_secure`` and ``drain_secure`` folds one full round at a time — the
pairwise masks cancel inside the fused N-way sum, with seed-reconstruction
recovery for members that dropped mid-round (see
``repro.privacy.secure_agg``).

Sharded mode (``ShardedModelStore``): the cluster is FedCCL's natural unit
of server parallelism, so the store partitions its models into K independent
shards — cluster key -> shard by a stable crc32 hash, each shard with its
own queue locks, hot-path stats, and (in the threaded runtime) its own drain
worker.  Submits and drains against different *shards* share no lock: the
registry is copy-on-write (reads are lock-free), queue locks are per record
or per shard slice, and stats are bucketed per shard.
The one model every client touches, the global model, is sharded at the
queue: submits land round-robin on per-shard slices of the global queue and
a drain folds them **two-level** — per-shard coalesced partials reduced by a
sample-weighted cross-shard merge.  Equivalence to the flat Algorithm-2
telescoped fold is structural: the convex coefficient of every queued update
depends only on the metadata sequence in arrival order, so the plan
(``plan_coalesce``) is computed once over the seq-sorted concatenation of
the shard slices and only the parameter *sums* are partitioned, which
commutes exactly (see ``two_level_coalesced_aggregate``).  Secure rounds are
never split across shards: a model's full-round fold stays on its owning
shard, because pairwise masks only cancel inside one fused sum.

Process-sharded mode (``ProcessShardedModelStore``): the same K-shard
topology with every shard promoted to a worker **process**
(``repro.core.server_proc``) — submits cross per-shard msgpack SPSC queues,
cluster folds run inside the workers, and the global model merges via a
cross-server plan/partial/merge split of the identical two-level algebra.
The parent journals every update until its fold is acked, so crashed or
stuck workers are respawned and replayed without losing updates or
double-counting rounds.  See the class docstring for the full design.
"""

from __future__ import annotations

import bisect
import itertools
import threading
import zlib
from collections import deque
from dataclasses import dataclass, replace

from repro.core import server_proc, transport
from repro.core.fetch import WireCache, serve_fetch
from repro.core.aggregation import (
    AggregationConfig,
    ModelMeta,
    UpdateDelta,
    aggregate_models,
    chunked_convex_reduce,
    coalesced_aggregate,
    multi_aggregate,
    plan_coalesce,
    secure_coalesced_aggregate,
    two_level_coalesced_aggregate,
)
from repro.core.server_proc import (
    delta_from_wire,
    delta_to_wire,
    meta_from_wire,
    meta_to_wire,
)
from repro.obs import clock
from repro.obs.record import current_trace, maybe_span, trace_scope

GLOBAL_KEY = "__global__"


def stable_shard(key: str, n_shards: int) -> int:
    """Legacy modulo cluster-key -> shard map (crc32, never Python's
    randomized ``hash``).  Kept for reference and the property tests that
    contrast it with the ring: the modulo map reassigns ~all keys when K
    changes, which is exactly why routing now goes through ``HashRing``.
    Never consult this for live routing — ownership can move at runtime
    (``migrate_cluster``), and only ``HashRing.shard_of`` carries the
    overrides + epoch (docs/ELASTICITY.md; fedlint FED404)."""
    if key == GLOBAL_KEY:
        return 0
    return zlib.crc32(str(key).encode()) % n_shards


class HashRing:
    """Consistent-hash ring with explicit ownership epochs — the routing
    authority shared by every sharded topology (docs/ELASTICITY.md).

    Each shard owns ``vnodes`` points on a 32-bit ring at the stable crc32
    positions of ``"s{shard}:{vnode}"`` (never Python's randomized
    ``hash``), so the base assignment is a pure function of (key, K,
    vnodes) — reproducible across threads, processes, restarts and
    ``PYTHONHASHSEED``.  Growing or shrinking K moves only ~1/K of the
    keys (the minimal-movement property the modulo map lacks; see
    ``tests/test_hash_ring.py``).

    Live migration overlays the ring with an **override table**: one
    ``assign(key, dst)`` call atomically bumps the monotone ownership
    ``epoch`` and records ``key -> (dst, epoch)``.  The overrides dict is
    copy-on-write (replaced wholesale under ``_lock``, never mutated in
    place), so the submit hot path reads routing with zero locks.  The
    global model always routes to shard 0 and never migrates — its fold
    is parent-owned in every topology.
    """

    def __init__(self, n_shards: int, vnodes: int = 64):
        self.n_shards = max(int(n_shards), 1)
        self.vnodes = max(int(vnodes), 1)
        points = sorted(
            (zlib.crc32(f"s{shard}:{v}".encode()), shard)
            for shard in range(self.n_shards) for v in range(self.vnodes))
        self._hashes = [h for h, _ in points]
        self._points = [s for _, s in points]
        self._lock = threading.Lock()
        self._overrides: dict[str, tuple[int, int]] = {}  # key -> (dst, ep)
        self.epoch = 0

    def owner(self, key: str) -> int:
        """Pure ring position of a key — ignores migration overrides.
        Routing callers must use ``shard_of`` instead (fedlint FED404)."""
        if key == GLOBAL_KEY:
            return 0
        i = bisect.bisect_right(self._hashes, zlib.crc32(str(key).encode()))
        return self._points[i % len(self._points)]

    def shard_of(self, key: str) -> int:
        """Current owner: the override table first (lock-free copy-on-write
        read), the ring position otherwise."""
        if key == GLOBAL_KEY:
            return 0
        # fedlint: unlocked-ok(copy-on-write dict swapped wholesale under _lock)
        ov = self._overrides.get(str(key))
        return ov[0] if ov is not None else self.owner(key)

    def assign(self, key: str, dst: int) -> int:
        """Move a key's ownership to ``dst``; returns the bumped epoch.
        This is the fence point of a migration: the instant the new
        overrides dict is published, every later ``shard_of`` routes to
        the new owner."""
        key = str(key)
        dst = int(dst)
        if key == GLOBAL_KEY:
            raise ValueError("the global model is parent-owned and never "
                             "migrates")
        if not 0 <= dst < self.n_shards:
            raise ValueError(f"destination shard {dst} out of range "
                             f"[0, {self.n_shards})")
        with self._lock:
            self.epoch += 1
            updated = dict(self._overrides)
            updated[key] = (dst, self.epoch)
            self._overrides = updated          # atomic reference swap
            return self.epoch

    def overrides(self) -> dict:
        """Snapshot of the override table (``{key: (dst, epoch)}``) — what
        seed blobs ship so respawned ex-owners still answer redirects."""
        # fedlint: unlocked-ok(copy-on-write overrides snapshot read)
        return self._overrides


@dataclass(frozen=True)
class PendingUpdate:
    """One client update queued for a later coalesced drain."""

    params: object
    meta: ModelMeta
    delta: UpdateDelta
    # start of its ``enqueue`` span (repro.obs.clock), 0 = telemetry off;
    # the drain that folds it records ``fold start - enqueued_ns``
    enqueued_ns: int = 0


@dataclass(frozen=True)
class PendingSecureUpdate:
    """One masked client update awaiting its round's secure drain."""

    client_id: str
    round_id: int
    masked_delta: object     # s_i * privatized_delta_i + pairwise masks
    delta: UpdateDelta
    submitted_ns: int = 0    # submit stamp, as PendingUpdate.enqueued_ns


class ModelRecord:
    """One stored model.  (params, meta) live in a single tuple swapped by
    one reference assignment, so lock-free snapshot reads can never observe
    new params with old meta (or vice versa) mid-aggregation."""

    def __init__(self, params, meta: ModelMeta = None):
        self._state = (params, meta if meta is not None else ModelMeta())
        self.lock = threading.Lock()
        # pending updates awaiting a coalesced drain; guarded by pending_lock
        # so enqueues never block behind an in-flight aggregation holding
        # `lock`
        self.pending: deque = deque()
        self.pending_lock = threading.Lock()
        # rounds popped by an in-flight drain but not yet reflected in meta;
        # guarded by pending_lock so `effective_round` readers always see
        # pop-and-register / swap-and-retire as single atomic steps
        self.inflight_rounds: int = 0
        # secure-aggregation rounds: round_id -> [PendingSecureUpdate];
        # guarded by pending_lock as well
        self.secure_pending: dict[int, list] = {}

    @property
    def params(self):
        return self._state[0]

    @property
    def meta(self) -> ModelMeta:
        return self._state[1]

    def swap(self, params, meta: ModelMeta):
        self._state = (params, meta)

    def snapshot(self):
        return self._state


# ------------------------------------------------------ record-level drains
# Shared by ModelStore and ShardedModelStore (per-cluster records are drained
# identically in both; only the global tier differs).  Callers hold rec.lock.

def _drain_record_once(rec: ModelRecord, max_coalesce: int,
                       agg_cfg: AggregationConfig, tel=None,
                       route: str = "host", key: str = ""):
    """Pop and fold one coalesced batch; returns the CoalesceResult or None.

    The two pending_lock critical sections keep ``effective_round`` readers
    consistent mid-drain: the pop registers the batch's rounds as in-flight
    in the same section that removes them from the queue, and the publish
    swaps meta and retires them in one section — a reader holding
    pending_lock can never see the batch in neither place.
    """
    with rec.pending_lock:
        take = min(len(rec.pending), max_coalesce)
        batch = [rec.pending.popleft() for _ in range(take)]
        rounds = sum(u.delta.rounds for u in batch)
        rec.inflight_rounds += rounds
    if not batch:
        return None
    base_round = rec.meta.round
    ups = [(u.params, u.meta, u.delta) for u in batch]
    try:
        args = {"key": key, "n": len(batch)}
        with maybe_span(tel, "fold", current_trace(), args,
                        hist=f"drain_fold_ns_{route}") as sp:
            if sp is not None:
                args["waits"] = _queue_waits(
                    sp.t0, [u.enqueued_ns for u in batch])
            res = coalesced_aggregate(rec.params, rec.meta, ups, agg_cfg)
    except BaseException:
        # a malformed update must not strand the batch: put it back at the
        # queue head (FIFO preserved) and retire the in-flight rounds so
        # effective_round stays truthful, then surface the error
        with rec.pending_lock:
            rec.pending.extendleft(reversed(batch))
            rec.inflight_rounds -= rounds
        raise
    if tel is not None:
        _observe_waits(tel, args["waits"])
        tel.metrics.histogram("coalesce_batch").observe(len(batch))
        stale = tel.metrics.histogram("staleness_at_fold")
        # telescoped staleness: ``ModelMeta.accumulate`` advances ``round``
        # additively by each delta's rounds, so measuring every update
        # against base + rounds-folded-before-it is independent of chunk
        # boundaries — the histogram is identical across every topology's
        # drains of the same FIFO schedule (test_store_equivalence)
        cum = 0
        for u in batch:
            stale.observe(max(0, base_round + cum - u.meta.round))
            cum += u.delta.rounds
    with rec.pending_lock:
        rec.swap(res.params, res.meta)
        rec.inflight_rounds -= rounds
    return res


def _queue_waits(start_ns: int, stamps) -> list:
    """Each update's wait in its queue: from its enqueue (a secure update's
    submit) stamp to ``start_ns``, the start of the fold that takes it.
    An unstamped update (queued with telemetry off) has no wait."""
    return [start_ns - t for t in stamps if t]


def _observe_waits(tel, waits) -> None:
    """A fold's queue waits, into the operators' ``queue_wait_ns``."""
    hist = tel.metrics.histogram("queue_wait_ns")
    for w in waits:
        hist.observe(w)


def _drain_secure_record(rec: ModelRecord, key: str, round_id: int,
                         expected_ids, masker, agg_cfg: AggregationConfig,
                         tel=None) -> tuple[int, int]:
    """Fold one secure round on one record; returns (folded, recovered).
    With telemetry on, a round with updates is one ``secure_fold`` span."""
    with rec.pending_lock:
        batch = rec.secure_pending.pop(round_id, [])
    if not batch:
        return 0, 0
    args = {"key": key, "n": len(batch)}
    with maybe_span(tel, "secure_fold", current_trace(), args,
                    hist="secure_round_ns") as sp:
        if sp is not None:
            args["waits"] = _queue_waits(
                sp.t0, [u.submitted_ns for u in batch])
        try:
            submitted = {u.client_id for u in batch}
            missing = sorted(set(expected_ids) - submitted)
            correction = None
            if missing:
                if masker is None:
                    raise RuntimeError(
                        "secure round has dropouts but no masker is attached "
                        "for seed reconstruction")
                correction = masker.reconstruct(
                    rec.params, missing, sorted(submitted), round_id, key)
            res = secure_coalesced_aggregate(
                rec.params, rec.meta,
                [(u.masked_delta, u.delta) for u in batch],
                agg_cfg, correction)
        except BaseException:
            # don't strand the round: restore it so a later retry can fold it
            with rec.pending_lock:
                rec.secure_pending[round_id] = \
                    batch + rec.secure_pending.get(round_id, [])
            raise
        with rec.pending_lock:
            rec.swap(res.params, res.meta)
    if tel is not None:
        _observe_waits(tel, args["waits"])
    return len(batch), len(missing)


class _RegistryBase:
    """Shared model-registry plumbing for both store flavors.

    The registry is **copy-on-write**: ``_records`` is only ever replaced
    wholesale (never mutated in place) under ``_registry_lock``, so readers
    — the submit hot path, snapshot fetches, drain-worker sweeps — take no
    lock at all; they read whatever consistent dict reference is current.
    ``ensure_cluster`` (Predict & Evolve joins mid-run) is the only writer.
    """

    def __init__(self, init_params, cluster_keys=()):
        self._registry_lock = threading.Lock()     # writers only (COW swap)
        records = {GLOBAL_KEY: ModelRecord(init_params)}
        for key in cluster_keys:
            records[str(key)] = ModelRecord(init_params)
        self._records: dict[str, ModelRecord] = records
        # read-tier serving cache: canonical wire bytes per (key, version),
        # shared by fetch_wire() across every store flavor (repro.core.fetch)
        self._wire_cache = WireCache()

    # ------------------------------------------------------------------ keys
    @staticmethod
    def _key(level: str, cluster_key: str | None) -> str:
        if level == "global":
            return GLOBAL_KEY
        assert cluster_key is not None, "cluster level requires a key"
        return str(cluster_key)

    def model_key(self, level: str, cluster_key: str | None = None) -> str:
        """Public (level, cluster_key) -> storage-key mapping — the string
        clients and the masker must agree on when deriving round masks."""
        return self._key(level, cluster_key)

    def _record(self, key: str) -> ModelRecord:
        """Lock-free registry read off the current copy-on-write snapshot."""
        # _records is swapped wholesale under _registry_lock and never
        # mutated in place, so a bare read observes one atomic snapshot.
        # fedlint: unlocked-ok(copy-on-write registry snapshot read)
        rec = self._records.get(key)
        if rec is None:
            # fedlint: unlocked-ok(copy-on-write registry snapshot read)
            known = sorted(k for k in self._records if k != GLOBAL_KEY)
            raise KeyError(
                f"no model registered for cluster key {key!r} "
                f"(known cluster keys: {known})")
        return rec

    def ensure_cluster(self, cluster_key: str, init_params=None):
        """Predict & Evolve: a newly formed cluster gets a model seeded from
        the current global model (immediate specialization base)."""
        key = str(cluster_key)
        with self._registry_lock:
            if key not in self._records:
                seed = init_params if init_params is not None else \
                    self._records[GLOBAL_KEY].params
                updated = dict(self._records)
                updated[key] = ModelRecord(seed)
                self._records = updated            # atomic reference swap

    def keys(self):
        # fedlint: unlocked-ok(copy-on-write registry snapshot read)
        return [k for k in self._records if k != GLOBAL_KEY]

    # -------------------------------------------------------------- protocol
    def request_model(self, level: str, cluster_key: str | None = None):
        """RequestModel — snapshot read (no model lock needed for consistency;
        the paper's clients read whatever the latest aggregated state is)."""
        return self._record(self._key(level, cluster_key)).snapshot()

    def fetch_wire(self, level: str, cluster_key: str | None = None,
                   held=None):
        """Parent-served conditional fetch: ``(result, payload, meta_wire)``
        with the same semantics as a shard server's ``fetch`` reply
        (``repro.core.fetch.serve_fetch``) — not-modified ack when the
        client's held ``[samples, epochs, round]`` version is current, a
        lossless compressed delta when the held version is still cached,
        else the full canonical msgpack snapshot.  Serialization is cached
        per version, so repeat fetches of an unchanged model never re-pack
        (the fix for the process-topology fetch regression: the old path
        re-serialized the identical mirror on every fetch)."""
        params, meta = self.request_model(level, cluster_key)
        meta_w = meta_to_wire(meta)
        kind, payload = serve_fetch(self._wire_cache,
                                    self._key(level, cluster_key),
                                    params, meta_w, held)
        return kind, payload, meta_w

    # ------------------------------------------------------------- inspection
    def meta(self, level: str, cluster_key: str | None = None) -> ModelMeta:
        return self._record(self._key(level, cluster_key)).meta

    def params(self, level: str, cluster_key: str | None = None):
        return self._record(self._key(level, cluster_key)).params


class _SubmitStats:
    """Submit-side (hot-path) counters behind their own lock.  ``ModelStore``
    bills every key to one sink; ``ShardedModelStore`` gives each shard its
    own, so submitters to different shards never serialize on bookkeeping."""

    __slots__ = ("lock", "n_updates", "n_fast_path", "n_lock_waits",
                 "n_enqueued", "max_queue_depth")

    def __init__(self):
        self.lock = threading.Lock()
        self.n_updates = 0        # direct-path (non-batched) aggregations
        self.n_fast_path = 0
        self.n_lock_waits = 0
        self.n_enqueued = 0
        self.max_queue_depth = 0

    def count_lock_wait(self):
        with self.lock:
            self.n_lock_waits += 1

    def count_direct(self, fast: bool):
        with self.lock:
            self.n_updates += 1
            if fast:
                self.n_fast_path += 1

    def count_enqueue(self):
        # callers count BEFORE publishing to the queue: a concurrent drain
        # may fold the update the instant it becomes visible, and
        # `updates <= enqueued` must hold for every agg_stats() snapshot
        with self.lock:
            self.n_enqueued += 1

    def count_enqueue_many(self, n: int):
        # batched flavor of count_enqueue: same count-before-publish rule,
        # one lock round trip for the whole batch (submit_many hot path)
        with self.lock:
            self.n_enqueued += n

    def observe_depth(self, depth: int):
        with self.lock:
            if depth > self.max_queue_depth:
                self.max_queue_depth = depth

    def snapshot(self) -> tuple:
        """One consistent read: (updates, fast_path, lock_waits, enqueued,
        max_depth)."""
        with self.lock:
            return (self.n_updates, self.n_fast_path, self.n_lock_waits,
                    self.n_enqueued, self.max_queue_depth)


class _StoreBase(_RegistryBase):
    """Submit paths and per-record drains shared by both store flavors.

    The flavors genuinely disagree on exactly two things: which submit-side
    stats sink a model key bills to (``_submit_stats``) and how the global
    tier queues/drains.  Everything else — the direct update path,
    pending/secure enqueues, per-record coalesced drains, secure full-round
    drains, and the drain-side counters — lives here once, so the
    lock-ordering and count-before-publish invariants cannot drift between
    the flavors."""

    def __init__(self, init_params, cluster_keys=(),
                 agg_cfg: AggregationConfig = AggregationConfig(),
                 batch_aggregation: bool = False, max_coalesce: int = 16,
                 masker=None, drain_timeout_s: float = 30.0,
                 telemetry=None):
        super().__init__(init_params, cluster_keys)
        self.agg_cfg = agg_cfg
        # telemetry sink (repro.obs.record.Telemetry) or None = off; the
        # hot paths pay one attribute check when disabled
        self._tel = telemetry
        self._route = "pallas" if agg_cfg.use_pallas else "host"
        self._submit_seq = itertools.count()   # trace-sampling counter
        self.batch_aggregation = batch_aggregation
        self.max_coalesce = max(int(max_coalesce), 1)
        # bounded-drain deadline (FedCCLConfig.drain_timeout_s): worker-reply
        # waits in the process store and drain-worker joins in the threaded
        # runtime; expiries are counted (``drain_timeouts`` in agg_stats())
        # instead of silently returning partial drains
        self.drain_timeout_s = float(drain_timeout_s)
        # secure aggregation: a repro.privacy.secure_agg.PairwiseMasker (its
        # presence switches both runtimes to full-round secure drains)
        self.masker = masker
        # monotone round-id base carried across runtime runs — pair masks are
        # derived from (pair, round_id, model_key), so round ids must never
        # repeat for one masker or masks would be reused (and cancellable
        # across runs by an observer)
        self.secure_round_offset = 0
        # drain-side counters (cold path: one touch per batch, not per
        # submit) behind a store-level lock
        self._drain_lock = threading.Lock()
        self._n_drain_updates = 0
        self._n_drain_fast_path = 0
        self.n_drain_batches = 0
        self.n_drained = 0                     # updates consumed by drains
        self.n_secure_rounds = 0               # secure drains performed
        self.n_secure_recoveries = 0           # dropped clients recovered
        self.n_drain_timeouts = 0              # bounded-drain deadline misses

    # ----------------------------------------------------------- flavor hooks
    def _submit_stats(self, key: str) -> _SubmitStats:
        """The submit-side stats sink the given model key bills to."""
        raise NotImplementedError

    def _all_submit_stats(self) -> list:
        """Every submit-side sink, for the aggregate counter properties."""
        raise NotImplementedError

    def _count_drain(self, folded: int, fast: int,
                     secure: bool = False, recovered: int = 0,
                     batches: int = 1):
        with self._drain_lock:
            self._n_drain_updates += folded
            self._n_drain_fast_path += fast
            self.n_drain_batches += batches
            self.n_drained += folded
            if secure:
                self.n_secure_rounds += 1
                self.n_secure_recoveries += recovered

    def _count_drain_timeout(self, shard: int | None = None):
        """Record a bounded-drain deadline miss.  ``shard`` attributes the
        expiry to one worker where the topology has them (the process/TCP
        store overrides this to keep per-shard counts — see
        ``agg_stats()["shard_drain_timeouts"]``)."""
        with self._drain_lock:
            self.n_drain_timeouts += 1

    # ---------------------------------- aggregate counters (drain + submit)
    # Each property takes `_drain_lock` for the drain half and reads every
    # submit sink through its locked `snapshot()` tuple
    # (updates, fast_path, lock_waits, enqueued, max_depth) — a bare
    # `s.n_updates` would read the counter mid-increment from another
    # thread (fedlint FED101; regression:
    # test_counter_properties_consistent_under_concurrency).
    @property
    def n_updates(self) -> int:
        with self._drain_lock:
            drain = self._n_drain_updates
        return drain + sum(s.snapshot()[0] for s in self._all_submit_stats())

    @property
    def n_fast_path(self) -> int:
        with self._drain_lock:
            drain = self._n_drain_fast_path
        return drain + sum(s.snapshot()[1] for s in self._all_submit_stats())

    @property
    def n_lock_waits(self) -> int:
        return sum(s.snapshot()[2] for s in self._all_submit_stats())

    @property
    def n_enqueued(self) -> int:
        return sum(s.snapshot()[3] for s in self._all_submit_stats())

    @property
    def max_queue_depth(self) -> int:
        # default=0: a store whose flavor reports no submit sinks (or one
        # inspected before its shards exist) must read as empty, not raise
        return max((s.snapshot()[4] for s in self._all_submit_stats()),
                   default=0)

    # -------------------------------------------------------------- protocol
    def handle_model_update(self, level: str, cluster_key: str | None,
                            updated_params, updated_meta: ModelMeta,
                            delta: UpdateDelta, *, blocking: bool = True) -> bool:
        """HandleModelUpdate (Algorithm 1 lines 19-25): lock the one model
        being updated, aggregate, store, release.  Returns False if
        ``blocking=False`` and the lock was busy (client retries later).

        In batched mode the update is enqueued instead (never blocks, always
        accepted); a later drain folds the whole queue at once.

        With telemetry on, every Nth submit (``trace_sample_n``) mints a
        trace id held in thread-local scope for the duration of the call —
        downstream enqueues, inline folds and wire frames pick it up via
        ``current_trace()``, which is what chains one submit's spans across
        process/TCP boundaries (docs/OBSERVABILITY.md).
        """
        tel = self._tel
        if tel is None:
            return self._handle_update(level, cluster_key, updated_params,
                                       updated_meta, delta, blocking=blocking)
        n = next(self._submit_seq)
        trace = (n + 1) if tel.sampled(n) else 0
        with tel.span("submit", trace, {"level": level},
                      hist="submit_latency_ns"), trace_scope(trace):
            return self._handle_update(level, cluster_key, updated_params,
                                       updated_meta, delta, blocking=blocking)

    def _handle_update(self, level: str, cluster_key: str | None,
                       updated_params, updated_meta: ModelMeta,
                       delta: UpdateDelta, *, blocking: bool = True) -> bool:
        if self.batch_aggregation:
            self.enqueue_update(level, cluster_key, updated_params,
                                updated_meta, delta)
            return True
        key = self._key(level, cluster_key)
        rec = self._record(key)
        st = self._submit_stats(key)
        if not rec.lock.acquire(blocking=blocking):
            st.count_lock_wait()
            return False
        try:
            fast = (self.agg_cfg.sequential_fast_path
                    and updated_meta.round == rec.meta.round + 1)
            rec.swap(*aggregate_models(
                rec.params, rec.meta, updated_params, updated_meta, delta,
                self.agg_cfg))
            st.count_direct(fast)
        finally:
            rec.lock.release()
        return True

    # ------------------------------------------------------- batched updates
    def _enqueue_record(self, key: str, upd: PendingUpdate) -> int:
        rec = self._record(key)
        st = self._submit_stats(key)
        st.count_enqueue()          # before publish — see _SubmitStats
        tel = self._tel
        args = {"key": key}
        with maybe_span(tel, "enqueue", current_trace(), args) as sp:
            if sp is not None:
                upd = replace(upd, enqueued_ns=sp.t0)
            with rec.pending_lock:
                rec.pending.append(upd)
                depth = len(rec.pending)
            st.observe_depth(depth)
            if sp is not None:
                tel.metrics.histogram("queue_depth").observe(depth)
                args["depth"] = depth
        return depth

    def enqueue_update(self, level: str, cluster_key: str | None,
                       updated_params, updated_meta: ModelMeta,
                       delta: UpdateDelta) -> int:
        """Queue an update for a later coalesced drain; returns queue depth."""
        return self._enqueue_record(
            self._key(level, cluster_key),
            PendingUpdate(updated_params, updated_meta, delta))

    def submit_many(self, level: str, cluster_key: str | None,
                    updates) -> int:
        """Batched submit entry point for replay drivers (the scenario
        engine, ``repro.scenario``): ``updates`` is an iterable of
        ``(params, meta, delta)`` triples that all target one model.

        In batched mode the whole list is appended under a single
        queue-lock/stats round trip per destination queue (the per-client
        protocol overhead — one lock pair, one telemetry touch per update —
        is what dominates at 10^5 simulated clients; the fold semantics are
        identical to N ``enqueue_update`` calls in the same order).  In
        direct mode it degrades to sequential ``_handle_update`` calls.
        Returns the deepest queue touched (0 for the direct path)."""
        ups = updates if isinstance(updates, list) else list(updates)
        if not ups:
            return 0
        tel = self._tel
        with maybe_span(tel, "submit_many", current_trace(),
                        {"level": level, "n": len(ups)}):
            if self.batch_aggregation:
                depth = self._enqueue_many(level, cluster_key, ups)
            else:
                for p, m, d in ups:
                    self._handle_update(level, cluster_key, p, m, d)
                depth = 0
        if tel is not None:
            tel.metrics.histogram("submit_batch").observe(len(ups))
        return depth

    def _enqueue_many(self, level: str, cluster_key: str | None,
                      ups) -> int:
        """Flavor hook behind ``submit_many``: publish a list of
        ``(params, meta, delta)`` triples to the destination queue(s).
        The base path covers every record-queued key (flat store, and the
        sharded store's cluster tier — ``_submit_stats`` routes the batch
        to the owning shard's sink)."""
        stamp = self._enqueue_stamp()
        return self._enqueue_record_many(
            self._key(level, cluster_key),
            [PendingUpdate(p, m, d, stamp) for p, m, d in ups])

    def _enqueue_stamp(self) -> int:
        """A batched enqueue's stamp for each of its updates (0 = telemetry
        off), as ``_enqueue_record`` stamps one update."""
        return clock.monotonic_ns() if self._tel is not None else 0

    def _enqueue_record_many(self, key: str, pend: list) -> int:
        rec = self._record(key)
        st = self._submit_stats(key)
        st.count_enqueue_many(len(pend))   # before publish — see _SubmitStats
        with rec.pending_lock:
            rec.pending.extend(pend)
            depth = len(rec.pending)
        st.observe_depth(depth)
        tel = self._tel
        if tel is not None:
            tel.metrics.histogram("queue_depth").observe(depth)
        return depth

    def pending_depth(self, level: str, cluster_key: str | None = None) -> int:
        rec = self._record(self._key(level, cluster_key))
        with rec.pending_lock:
            return len(rec.pending)

    def effective_round(self, level: str, cluster_key: str | None = None) -> int:
        """Server round *including* queued-but-undrained updates (each
        pending update advances the round by ``delta.rounds`` once drained).
        This is the round an update enqueued right now would be measured
        against — the staleness reference for batched mode.

        ``inflight_rounds`` covers the drain window between popping a batch
        and swapping the aggregated meta in: without it a reader could see
        the batch in neither the queue nor the meta and watch the effective
        round regress mid-drain (latent race surfaced by the equivalence
        harness; see ``_drain_record_once``)."""
        rec = self._record(self._key(level, cluster_key))
        with rec.pending_lock:
            queued = sum(u.delta.rounds for u in rec.pending)
            return rec.meta.round + queued + rec.inflight_rounds

    def _drain_record(self, key: str) -> int:
        """Fold all queued updates for one record, ``max_coalesce`` at a
        time, into single N-way aggregations; returns updates folded."""
        rec = self._record(key)
        drained = 0
        while True:
            # model lock first so concurrent drains stay FIFO; enqueues only
            # touch pending_lock and keep flowing while we aggregate
            with rec.lock:
                res = _drain_record_once(rec, self.max_coalesce, self.agg_cfg,
                                         self._tel, self._route, key)
            if res is None:
                return drained
            # `res` is a drain-local CoalesceResult whose field name
            # collides with the lock-guarded _SubmitStats.n_fast_path.
            # fedlint: unlocked-ok(local CoalesceResult, not shared state)
            self._count_drain(res.n_folded, res.n_fast_path)
            drained += res.n_folded

    # ---------------------------------------------------- secure aggregation
    def submit_secure(self, level: str, cluster_key: str | None,
                      client_id: str, round_id: int, masked_delta,
                      delta: UpdateDelta) -> int:
        """Queue one masked update for its round's secure drain.  The server
        never aggregates these individually — only ``drain_secure`` folds a
        full round, inside which the pairwise masks cancel."""
        key = self._key(level, cluster_key)
        rec = self._record(key)
        st = self._submit_stats(key)
        st.count_enqueue()          # before publish — see _SubmitStats
        stamp = self._enqueue_stamp()
        with rec.pending_lock:
            bucket = rec.secure_pending.setdefault(round_id, [])
            bucket.append(PendingSecureUpdate(client_id, round_id,
                                              masked_delta, delta, stamp))
            depth = len(bucket)
        st.observe_depth(depth)
        return depth

    def drain_secure(self, level: str, cluster_key: str | None,
                     round_id: int, expected_ids) -> int:
        """Fold one secure round into a single fused N-way sum.

        ``expected_ids`` is the round's full member set; members that never
        submitted (dropouts) are recovered by reconstructing their stray
        pairwise masks from the pair seeds and subtracting them inside the
        same sum.  Returns the number of updates folded.
        """
        key = self._key(level, cluster_key)
        rec = self._record(key)
        with rec.lock:
            folded, recovered = _drain_secure_record(
                rec, key, round_id, expected_ids, self.masker, self.agg_cfg,
                self._tel)
        if not folded:
            return 0
        self._count_drain(folded, 0, secure=True, recovered=recovered)
        return folded

    # ------------------------------------------------------------- inspection
    def coalesce_factor(self) -> float:
        """Mean queued-updates-per-drain — 1.0 means no batching benefit.

        Takes ``_drain_lock`` so the ratio is computed from one consistent
        (drained, batches) pair; `agg_stats()` holds the (non-reentrant)
        lock already and computes the same ratio inline from its snapshot
        (regression: test_coalesce_factor_locked_and_consistent)."""
        with self._drain_lock:
            if not self.n_drain_batches:
                return 0.0
            return self.n_drained / self.n_drain_batches

    def sync_mirrors(self) -> int:
        """Mirror-staleness barrier.  In-thread stores hold the models
        directly, so there is nothing to sync (always 0); the process/TCP
        store overrides this to pull lazily-synced params from its workers
        (``FedCCLConfig.mirror_sync_every``)."""
        return 0

    # ------------------------------------------------------------- telemetry
    @property
    def telemetry(self):
        """The store's ``repro.obs.record.Telemetry`` sink (None = off)."""
        return self._tel

    def telemetry_dump(self) -> dict:
        """Multi-site telemetry dump — ``{"sites": [...]}``, the shape every
        ``repro.obs.export`` exporter consumes.  In-thread stores record at
        one site; the process/TCP store overrides this to append one site
        per worker (the ``obsdump`` wire command)."""
        if self._tel is None:
            return {"sites": []}
        return {"sites": [self._tel.dump()]}


class ModelStore(_StoreBase):
    """Thread-safe store for global + cluster models: one submit-side stats
    sink, flat drains (the global tier is just another record)."""

    def __init__(self, init_params, cluster_keys=(),
                 agg_cfg: AggregationConfig = AggregationConfig(),
                 batch_aggregation: bool = False, max_coalesce: int = 16,
                 masker=None, drain_timeout_s: float = 30.0,
                 telemetry=None):
        super().__init__(init_params, cluster_keys, agg_cfg,
                         batch_aggregation, max_coalesce, masker,
                         drain_timeout_s, telemetry)
        self._submit = _SubmitStats()

    def _submit_stats(self, key: str) -> _SubmitStats:
        return self._submit

    def _all_submit_stats(self) -> list:
        return [self._submit]

    def drain(self, level: str, cluster_key: str | None = None) -> int:
        """Fold all queued updates for one model, `max_coalesce` at a time,
        into single N-way aggregations.  Returns number of updates folded."""
        return self._drain_record(self._key(level, cluster_key))

    def drain_all(self) -> int:
        total = self.drain("global")
        for key in self.keys():
            total += self.drain("cluster", key)
        return total

    def migrate_cluster(self, cluster_key: str, dst_shard: int) -> int:
        raise RuntimeError(
            "the flat ModelStore has no shards to migrate between — use a "
            "sharded topology (server_shards / server_processes / "
            "server_hosts)")

    def agg_stats(self) -> dict:
        """Single-store flavor of the cross-topology ``agg_stats`` surface
        (the sharded/process/TCP flavors add shard, respawn, mirror-sync
        and wire-byte counters on top of these shared keys —
        ``drain_timeouts`` included, which those flavors also attribute
        per shard)."""
        # snapshot order matters: drain counters FIRST, then the submit sink
        # as one locked read.  Enqueues are counted before publish and folds
        # happen after it, so any fold visible in the drain snapshot has its
        # enqueue visible in the (later) submit snapshot — every snapshot
        # keeps updates <= enqueued and fast_path_frac <= 1 (regression:
        # test_agg_stats_consistent_snapshot_under_drains)
        with self._drain_lock:
            drain_updates = self._n_drain_updates
            drain_fast = self._n_drain_fast_path
            drain_batches = self.n_drain_batches
            # inline (not coalesce_factor(): it takes this non-reentrant
            # lock) from the same snapshot, so the ratio is consistent
            coalesce = (self.n_drained / drain_batches) if drain_batches \
                else 0.0
            secure_rounds = self.n_secure_rounds
            secure_recoveries = self.n_secure_recoveries
            drain_timeouts = self.n_drain_timeouts
        direct, fast, lock_waits, enqueued, max_depth = self._submit.snapshot()
        updates = drain_updates + direct
        out = {
            "updates": updates,
            "fast_path_frac": (drain_fast + fast) / max(updates, 1),
            "lock_waits": lock_waits,
            "enqueued": enqueued,
            "drain_batches": drain_batches,
            "max_queue_depth": max_depth,
            "coalesce_factor": coalesce,
            "drain_timeouts": drain_timeouts,
        }
        if self.masker is not None:
            out["secure_rounds"] = secure_rounds
            out["secure_recoveries"] = secure_recoveries
        return out


# =========================================================================
# Sharded store: per-cluster shards, two-level global fold
# =========================================================================


class _Shard:
    """One independent server slice: its slice of the global pending queue
    plus its own stats.  Cluster records owned by the shard keep their
    per-record queues; the shard only decides *which drain worker* sweeps
    them and which stats bucket counts them."""

    __slots__ = ("idx", "lock", "global_pending", "stats")

    def __init__(self, idx: int):
        self.idx = idx
        self.lock = threading.Lock()
        # FIFO slice of the global queue: (seq, PendingUpdate)
        self.global_pending: deque = deque()
        self.stats = _SubmitStats()


class ShardedModelStore(_StoreBase):
    """``ModelStore`` semantics partitioned into K independent shards.

    Cluster models are assigned to shards by a consistent-hash ring
    (``HashRing`` — stable crc32 vnode points, never Python's randomized
    ``hash``), so the base assignment is reproducible across processes and
    restarts, K changes move only ~1/K of the keys, and live migration
    (``migrate_cluster``) overlays epoch-stamped ownership overrides
    without a restart (docs/ELASTICITY.md).  Submits to different clusters
    touch only their record's queue lock and their shard's stats lock (the
    registry itself is copy-on-write, read lock-free; so is the ring's
    override table); global submits are struck round-robin across
    per-shard queue slices carrying a monotone arrival ``seq``.

    ``drain_global`` folds all queued global slices two-level: one
    ``plan_coalesce`` walk over the seq-sorted concatenation fixes every
    update's telescoped convex coefficient (identical to the flat fold's),
    then each shard's members are reduced to a convex partial and a
    sample-weighted cross-shard merge reassembles the exact flat sum — see
    ``two_level_coalesced_aggregate`` for the equivalence argument, and
    ``tests/test_store_equivalence.py`` for the harness that checks it
    against the sequential fold, the flat drain, and both runtimes.

    Secure aggregation stays model-local (masks only cancel inside one fused
    full-round sum), so ``drain_secure`` runs unchanged on the owning
    shard's record — a dropout in one shard's round can never touch another
    shard's state.
    """

    def __init__(self, init_params, cluster_keys=(),
                 agg_cfg: AggregationConfig = AggregationConfig(),
                 n_shards: int = 4, batch_aggregation: bool = False,
                 max_coalesce: int = 16, masker=None,
                 drain_timeout_s: float = 30.0, ring_vnodes: int = 64,
                 telemetry=None):
        self.n_shards = max(int(n_shards), 1)
        super().__init__(init_params, cluster_keys, agg_cfg,
                         batch_aggregation, max_coalesce, masker,
                         drain_timeout_s, telemetry)
        self.ring = HashRing(self.n_shards, ring_vnodes)
        self.n_cluster_migrations = 0       # under the shared _drain_lock
        self._shards = [_Shard(i) for i in range(self.n_shards)]
        self._gseq = itertools.count()      # global-queue arrival order
        # two-level fold instrumentation (under the shared _drain_lock)
        self.n_global_drains = 0
        self.n_global_partials = 0          # shard partials fed to merges

    # ------------------------------------------------------------------ keys
    def _submit_stats(self, key: str) -> _SubmitStats:
        return self._shards[self.shard_of(key)].stats

    def _all_submit_stats(self) -> list:
        return [s.stats for s in self._shards]

    def shard_of(self, key: str) -> int:
        """Current cluster-key -> shard owner — the consistent-hash ring
        plus any live-migration overrides (``HashRing.shard_of``)."""
        return self.ring.shard_of(key)

    def ownership_epoch(self) -> int:
        """Monotone epoch bumped by every ``migrate_cluster`` — the
        staleness version for routing caches (``FetchClient``)."""
        # fedlint: unlocked-ok(monotone int; torn read returns a valid epoch)
        return self.ring.epoch

    def migrate_cluster(self, cluster_key: str, dst_shard: int) -> int:
        """Move one cluster model to another shard; returns the new
        ownership epoch.  Thread shards share the parent's records, so the
        flip is pure routing: holding ``rec.lock`` fences in-flight drains
        (drain beats take it per fold), and the next beat's
        ``shard_cluster_keys`` sweep picks the key up on its new shard."""
        key = self._key("cluster", cluster_key)
        rec = self._record(key)              # unknown cluster -> KeyError
        tel = self._tel
        t0 = clock.monotonic_ns() if tel is not None else 0
        with rec.lock:
            epoch = self.ring.assign(key, int(dst_shard))
        with self._drain_lock:
            self.n_cluster_migrations += 1
        if tel is not None:
            tel.metrics.counter("cluster_migrations").inc()
            tel.event("migrate", t0, clock.monotonic_ns() - t0,
                      current_trace(),
                      {"key": key, "dst": int(dst_shard), "epoch": epoch})
        return epoch

    def shard_cluster_keys(self, shard: int):
        """Cluster keys owned by one shard (that shard's drain beat)."""
        # fedlint: unlocked-ok(copy-on-write registry snapshot read)
        return [k for k in self._records
                if k != GLOBAL_KEY and self.shard_of(k) == shard]

    # ------------------------------------------------------- batched updates
    def enqueue_update(self, level: str, cluster_key: str | None,
                       updated_params, updated_meta: ModelMeta,
                       delta: UpdateDelta) -> int:
        upd = PendingUpdate(updated_params, updated_meta, delta)
        key = self._key(level, cluster_key)
        if key != GLOBAL_KEY:
            return self._enqueue_record(key, upd)
        # global tier: strike a round-robin shard slice instead of the
        # record's own queue
        seq = next(self._gseq)
        sh = self._shards[seq % self.n_shards]
        sh.stats.count_enqueue()    # before publish — see _SubmitStats
        tel = self._tel
        args = {"key": GLOBAL_KEY}
        with maybe_span(tel, "enqueue", current_trace(), args) as sp:
            if sp is not None:
                upd = replace(upd, enqueued_ns=sp.t0)
            with sh.lock:
                sh.global_pending.append((seq, upd))
                depth = len(sh.global_pending)
            sh.stats.observe_depth(depth)
            if sp is not None:
                tel.metrics.histogram("queue_depth").observe(depth)
                args["depth"] = depth
        return depth

    def _enqueue_many(self, level: str, cluster_key: str | None,
                      ups) -> int:
        key = self._key(level, cluster_key)
        if key != GLOBAL_KEY:
            return super()._enqueue_many(level, cluster_key, ups)
        # global tier: scatter the batch round-robin across shard slices in
        # one pass, preserving arrival seq order (the two-level fold sorts
        # by seq, so the fold is identical to N single enqueues)
        per: list[list] = [[] for _ in range(self.n_shards)]
        stamp = self._enqueue_stamp()
        for p, m, d in ups:
            seq = next(self._gseq)
            per[seq % self.n_shards].append(
                (seq, PendingUpdate(p, m, d, stamp)))
        tel = self._tel
        depth = 0
        for sh, items in zip(self._shards, per, strict=True):
            if not items:
                continue
            sh.stats.count_enqueue_many(len(items))  # before publish
            with sh.lock:
                sh.global_pending.extend(items)
                d2 = len(sh.global_pending)
            sh.stats.observe_depth(d2)
            depth = max(depth, d2)
            if tel is not None:
                tel.metrics.histogram("queue_depth").observe(d2)
        return depth

    def pending_depth(self, level: str, cluster_key: str | None = None) -> int:
        if self._key(level, cluster_key) == GLOBAL_KEY:
            total = 0
            for sh in self._shards:
                with sh.lock:
                    total += len(sh.global_pending)
            return total
        return super().pending_depth(level, cluster_key)

    def effective_round(self, level: str, cluster_key: str | None = None) -> int:
        """Round including queued *and* in-flight (popped, not yet merged)
        updates — same staleness reference as ``ModelStore.effective_round``.
        For the global tier the shard slices are summed under the record's
        pending_lock, which every global drain also holds while popping, so
        readers never catch a drain between pop and publish."""
        key = self._key(level, cluster_key)
        if key != GLOBAL_KEY:
            return super().effective_round(level, cluster_key)
        rec = self._record(key)
        with rec.pending_lock:
            queued = 0
            for sh in self._shards:
                with sh.lock:
                    queued += sum(u.delta.rounds
                                  for _, u in sh.global_pending)
            return rec.meta.round + queued + rec.inflight_rounds

    # ------------------------------------------------------------ drains
    def drain(self, level: str, cluster_key: str | None = None) -> int:
        key = self._key(level, cluster_key)
        if key == GLOBAL_KEY:
            return self.drain_global()
        return self._drain_record(key)

    def drain_global(self) -> int:
        """Two-level global fold: pop every shard slice (seq-tagged), plan
        once over the seq-sorted concatenation, reduce per-shard partials,
        merge sample-weighted.  One call drains the whole global queue; the
        per-shard partial sums are arity-bounded by ``max_coalesce``."""
        rec = self._record(GLOBAL_KEY)
        with rec.lock:
            with rec.pending_lock:
                popped, batches, seqs, total_rounds = [], [], [], 0
                for sh in self._shards:
                    with sh.lock:
                        items = list(sh.global_pending)
                        sh.global_pending.clear()
                    popped.append(items)
                    seqs.append([s for s, _ in items])
                    batches.append([(u.params, u.meta, u.delta)
                                    for _, u in items])
                    total_rounds += sum(u.delta.rounds for _, u in items)
                rec.inflight_rounds += total_rounds
            n = sum(len(b) for b in batches)
            if n == 0:
                with rec.pending_lock:
                    rec.inflight_rounds -= total_rounds
                return 0
            tel = self._tel
            try:
                args = {"key": GLOBAL_KEY, "n": n}
                with maybe_span(tel, "fold", current_trace(), args,
                                hist=f"drain_fold_ns_{self._route}") as sp:
                    if sp is not None:
                        args["waits"] = _queue_waits(
                            sp.t0, [u.enqueued_ns for items in popped
                                    for _, u in items])
                    res = two_level_coalesced_aggregate(
                        rec.params, rec.meta, batches, self.agg_cfg,
                        seqs=seqs, max_width=self.max_coalesce)
            except BaseException:
                # restore the popped slices (seq tags and stamps intact,
                # FIFO per shard) and retire the in-flight rounds before
                # surfacing
                with rec.pending_lock:
                    for sh, items in zip(self._shards, popped, strict=True):
                        with sh.lock:
                            sh.global_pending.extendleft(reversed(items))
                    rec.inflight_rounds -= total_rounds
                raise
            if tel is not None:
                _observe_waits(tel, args["waits"])
                tel.metrics.histogram("coalesce_batch").observe(n)
                stale = tel.metrics.histogram("staleness_at_fold")
                base_round = rec.meta.round
                # seq order == arrival order == the flat store's FIFO, so
                # the telescoped staleness per update matches the flat
                # drain's exactly (see _drain_record_once)
                cum = 0
                for _, m, d in sorted(
                        (s, u[1], u[2])
                        for sq, b in zip(seqs, batches, strict=True)
                        for s, u in zip(sq, b, strict=True)):
                    stale.observe(max(0, base_round + cum - m.round))
                    cum += d.rounds
            with rec.pending_lock:
                rec.swap(res.params, res.meta)
                rec.inflight_rounds -= total_rounds
        with self._drain_lock:
            self._n_drain_updates += n
            self._n_drain_fast_path += res.n_fast_path
            self.n_drain_batches += 1
            self.n_drained += n
            self.n_global_drains += 1
            self.n_global_partials += res.n_partials
        return n

    def drain_shard(self, shard: int) -> int:
        """One drain worker's beat: every cluster model owned by the shard.
        The global queue is drained separately (``drain_global``) because
        its two-level fold spans all shards' slices."""
        total = 0
        for key in self.shard_cluster_keys(shard):
            total += self._drain_record(key)
        return total

    def drain_all(self) -> int:
        total = self.drain_global()
        for shard in range(self.n_shards):
            total += self.drain_shard(shard)
        return total

    def agg_stats(self) -> dict:
        with self._drain_lock:
            migrations = self.n_cluster_migrations
        return _sharded_agg_stats(self, self._shards,
                                  # fedlint: unlocked-ok(monotone epoch stat)
                                  extra={"ownership_epoch": self.ring.epoch,
                                         "cluster_migrations": migrations})


def _sharded_agg_stats(store, shards, extra: dict | None = None) -> dict:
    """Shared agg_stats assembly for the sharded store flavors (thread
    shards, process workers and TCP workers expose the same counter
    layout; the process/TCP store passes its flavor extras — ``transport``,
    ``respawns``, ``mirror_syncs``, per-worker ``shard_drain_timeouts``,
    ``wire_tx_bytes``/``wire_rx_bytes`` — through ``extra``).  Secure-round
    counters aggregate worker-local folds: each secure round runs entirely
    on the model's owning shard/worker, and only the counted totals land
    here.

    Snapshot order matters: drain counters FIRST, then each shard's
    counters as one locked read.  Enqueues are counted before publish
    and folds happen after it, so any fold visible in the drain
    snapshot has its enqueue visible in the (later) shard snapshots —
    every snapshot keeps updates <= enqueued and fast_path_frac <= 1.
    """
    with store._drain_lock:
        drain_updates = store._n_drain_updates
        drain_fast = store._n_drain_fast_path
        drain_batches = store.n_drain_batches
        drain = {
            "drain_batches": drain_batches,
            # inline (not coalesce_factor(): it takes this non-reentrant
            # lock) from the same snapshot, so the ratio is consistent
            "coalesce_factor": (store.n_drained / drain_batches)
            if drain_batches else 0.0,
            "global_drains": store.n_global_drains,
            "global_partials": store.n_global_partials,
            "secure_rounds": store.n_secure_rounds,
            "secure_recoveries": store.n_secure_recoveries,
            "drain_timeouts": store.n_drain_timeouts,
        }
    updates, fast, lock_waits, enqueued, max_depth = 0, 0, 0, 0, 0
    shard_enqueued = []
    for s in shards:
        u, f, lw, enq, depth = s.stats.snapshot()
        updates += u
        fast += f
        lock_waits += lw
        enqueued += enq
        max_depth = max(max_depth, depth)
        shard_enqueued.append(enq)
    updates += drain_updates
    fast += drain_fast
    out = {
        "updates": updates,
        "fast_path_frac": fast / max(updates, 1),
        "lock_waits": lock_waits,
        "enqueued": enqueued,
        "drain_batches": drain["drain_batches"],
        "max_queue_depth": max_depth,
        "coalesce_factor": drain["coalesce_factor"],
        "drain_timeouts": drain["drain_timeouts"],
        "shards": store.n_shards,
        "global_drains": drain["global_drains"],
        "global_partials": drain["global_partials"],
        "shard_enqueued": shard_enqueued,
    }
    if extra:
        out.update(extra)
    if store.masker is not None:
        out["secure_rounds"] = drain["secure_rounds"]
        out["secure_recoveries"] = drain["secure_recoveries"]
    return out


# =========================================================================
# Process-sharded store: shard servers as worker processes
# =========================================================================


class _JournalEntry:
    """One unacked update the parent still owns.  ``raw`` is the exact wire
    message sent to the worker, so a respawn replays it byte-for-byte.
    ``custody`` marks global updates whose payload a ``gpop`` reply has
    already handed back to the parent — replay must skip those or the
    in-flight two-level fold would double-count them."""

    __slots__ = ("kind", "key", "rounds", "raw", "custody")

    def __init__(self, kind: str, key: str, rounds: int, raw: bytes):
        self.kind = kind          # "sub" | "gsub" | "secure"
        self.key = key
        self.rounds = rounds
        self.raw = raw
        self.custody = False


class _ProcShard:
    """Parent-side bookkeeping for one worker process: its transport handle,
    submit stats, and the journal of unacked updates (the crash-replay
    source of truth).  ``rpc_lock`` serializes replying commands (and
    respawns) per worker; ``journal_lock`` is the leaf lock guarding the
    journal, the per-key pending counters, and handle puts (so a respawn's
    replay can never interleave with a half-published submit)."""

    __slots__ = ("idx", "stats", "handle", "rpc_lock", "journal",
                 "journal_lock", "pending_counts", "pending_rounds",
                 "secure_counts", "outbox", "dirty", "deferred",
                 "replicas", "replica_pushes", "replica_drops")

    def __init__(self, idx: int):
        self.idx = idx
        self.stats = _SubmitStats()
        self.handle = None
        self.replicas: list = []          # read-replica transports (TCP)
        self.replica_pushes = 0           # mirror pushes delivered
        self.replica_drops = 0            # pushes skipped (replica down)
        self.rpc_lock = threading.RLock()
        self.journal: dict[int, _JournalEntry] = {}     # seq -> entry
        self.journal_lock = threading.Lock()
        self.pending_counts: dict[str, int] = {}        # key -> unacked subs
        self.pending_rounds: dict[str, int] = {}        # key -> their rounds
        self.secure_counts: dict[tuple, int] = {}       # (key, round) -> n
        self.outbox: list = []                          # unflushed raw msgs
        # lazy mirror sync (mirror_sync_every > 1): keys whose worker-side
        # params are ahead of the parent mirror (meta-only acks received),
        # and the drain stats deferred until their params land — both
        # guarded by journal_lock
        self.dirty: set[str] = set()
        self.deferred: dict[str, list] = {}   # key -> [folded, fast, batches]


class ProcessShardedModelStore(_StoreBase):
    """``ShardedModelStore`` semantics with every shard promoted to a worker
    **process** — aggregation escapes the GIL and scales with cores.

    Topology: the parent keeps the authoritative registry (all reads —
    ``request_model``/``meta``/``params`` — stay parent-local snapshots,
    zero IPC) plus a per-shard **journal** of unacked updates; each worker
    owns working copies of its shard's cluster models, their pending queues
    and secure-round buckets, and its slice of the global queue.  Submits
    msgpack-serialize the update once (the checkpoint codec) and land on the
    shard's SPSC command queue without blocking; drain RPCs make the worker
    fold with the identical ``coalesced_aggregate`` and ship the folded
    ``(params, meta)`` back, which the parent swaps into its mirror and acks
    against the journal in one atomic step.

    The global model folds by a **cross-server two-level merge**: the
    parent snapshots every worker's seq-tagged slice metadata (``gmeta``),
    runs the unchanged ``plan_coalesce`` over the seq-sorted concatenation
    (the flat Algorithm-2 telescoped coefficients), each worker reduces its
    own members to one convex partial (``greduce`` via the unchanged
    ``multi_aggregate`` — only K partials ever cross process boundaries,
    not N updates), and a mass-weighted merge reassembles the exact flat
    sum — the same algebra ``two_level_coalesced_aggregate`` uses for
    thread shards, distributed (see ``tests/test_store_equivalence.py``).

    Crash safety: a worker that dies or misses the ``drain_timeout_s``
    deadline is respawned from the parent mirrors and its journal replayed.
    Updates are acked only after their fold's result is applied parent-side,
    and folds are deterministic, so a crash anywhere in the submit->fold->
    reply pipeline neither loses updates nor double-counts rounds (heavy
    kill-mid-round test in ``tests/test_process_store.py``).  Timeouts are
    surfaced as ``drain_timeouts`` in ``agg_stats()``.

    Secure aggregation stays model-local per server process: a cluster
    model's full-round masked fold (and its dropout seed-reconstruction)
    runs entirely inside the owning worker; the parent-owned global model
    folds its secure rounds parent-locally.

    ``inprocess=True`` swaps the spawned processes for the deterministic
    in-process emulation (same messages, same codec, same ``ShardWorker``
    logic) — what ``runtime_sim`` uses so schedules stay bit-reproducible.

    ``server_hosts=["host:port", ...]`` promotes the workers to **separate
    hosts**: instead of spawning, the parent connects to one standalone
    shard server (``repro.launch.shard_server``) per entry over TCP
    (length-prefixed msgpack frames — ``repro.core.transport``, normative
    spec in ``docs/WIRE_PROTOCOL.md``) and seeds it over the wire.  The
    fold algebra, journal crash recovery (now covering connection loss:
    reconnect, re-seed, replay — idempotent via the worker's seq
    dedup set), and drain-timeout accounting carry over unchanged.

    ``mirror_sync_every=N`` (lazy mirror sync) cuts reply bandwidth for
    all remote flavors: workers ship full params only every Nth drain
    reply per model and ack with seq-stamped metadata otherwise.  Dirty
    mirrors are re-synced by an explicit ``sync_mirrors()`` barrier, which
    the read paths (``request_model``/``params``/``meta``), checkpointing
    (``save_store``) and ``close`` invoke per dirty key — parent mirrors
    are provably never stale when read.  Folded-but-unsynced updates stay
    journaled, so a crash between syncs replays and refolds them from the
    last synced mirror (nothing is lost, nothing double-counted — their
    stats are deferred until their params land).
    """

    # drains are scatter-gather beats: the threaded runtime runs ONE pump
    # thread calling drain_all() instead of one thread per shard (the
    # parallelism lives in the workers; extra parent threads only add GIL
    # convoy on the submit hot path)
    scatter_drains = True

    def __init__(self, init_params, cluster_keys=(),
                 agg_cfg: AggregationConfig = AggregationConfig(),
                 n_shards: int = 4, batch_aggregation: bool = True,
                 max_coalesce: int = 16, masker=None,
                 drain_timeout_s: float = 30.0, inprocess: bool = False,
                 server_hosts=None, mirror_sync_every: int = 1,
                 ring_vnodes: int = 64, telemetry=None):
        if server_hosts:
            # one worker per remote server; addresses fix the shard count.
            # Read-replica syntax: "owner:port|replica:port|..." — the
            # first address owns the shard (submits, drains, secure
            # rounds); the rest mirror it for read fan-out (the parent
            # pushes folded params, fetch clients round-robin across all)
            owners, replicas = [], []
            for h in server_hosts:
                parts = [p for p in
                         (s.strip() for s in str(h).split("|")) if p]
                owners.append(transport.parse_host(parts[0]))
                replicas.append([transport.parse_host(p)
                                 for p in parts[1:]])
            self.server_hosts = owners
            self.replica_hosts = replicas if any(replicas) else None
            n_shards = len(self.server_hosts)
        else:
            self.server_hosts = None
            self.replica_hosts = None
        self.n_shards = max(int(n_shards), 1)
        super().__init__(init_params, cluster_keys, agg_cfg,
                         batch_aggregation, max_coalesce, masker,
                         drain_timeout_s, telemetry)
        self.inprocess = bool(inprocess) and self.server_hosts is None
        self.mirror_sync_every = max(int(mirror_sync_every), 1)
        self.ring = HashRing(self.n_shards, ring_vnodes)
        self.n_cluster_migrations = 0     # under the shared _drain_lock
        self._gseq = itertools.count()
        self.n_global_drains = 0
        self.n_global_partials = 0
        self.n_respawns = 0
        self.n_mirror_syncs = 0           # explicit sync RPCs issued
        self.n_shard_drain_timeouts = [0] * self.n_shards
        self._closed = False
        self._proc_shards = [_ProcShard(i) for i in range(self.n_shards)]
        for sh in self._proc_shards:
            sh.handle = self._make_handle(sh.idx)
            if self.replica_hosts:
                # replicas are seeded exactly like the owner (same blob =
                # same starting mirrors); they then receive only `mirror`
                # pushes, never submits or drains
                for addr in self.replica_hosts[sh.idx]:
                    sh.replicas.append(transport.TcpWorkerHandle(
                        sh.idx, self._seed_blob(sh.idx), addr,
                        connect_timeout=max(self.drain_timeout_s, 10.0)))

    # --------------------------------------------------------------- lifecycle
    def _make_handle(self, shard_idx: int) -> transport.Transport:
        blob = self._seed_blob(shard_idx)
        if self.server_hosts is not None:
            return transport.TcpWorkerHandle(
                shard_idx, blob, self.server_hosts[shard_idx],
                connect_timeout=max(self.drain_timeout_s, 10.0))
        cls = (server_proc.InprocessWorkerHandle if self.inprocess
               else server_proc.ProcessWorkerHandle)
        return cls(shard_idx, blob)

    def _seed_blob(self, shard_idx: int) -> bytes:
        recs = []
        for key in self.shard_cluster_keys(shard_idx):
            # fedlint: unlocked-ok(copy-on-write registry snapshot read)
            params, meta = self._records[key].snapshot()
            recs.append((key, params, meta))
        tcfg = ({"sample_n": self._tel.sample_n}
                if self._tel is not None else None)
        # every worker learns where migrated-away keys live, so respawned
        # ex-owners keep answering redirects instead of erroring unknown
        migrated = {key: [dst, ep]
                    for key, (dst, ep) in self.ring.overrides().items()
                    if dst != shard_idx}
        return server_proc.make_seed_blob(recs, self.max_coalesce,
                                          self.agg_cfg, self.masker,
                                          self.mirror_sync_every, tcfg,
                                          # fedlint: unlocked-ok(monotone epoch; seed built under rpc_lock)
                                          epoch=self.ring.epoch,
                                          migrated=migrated)

    def close(self, timeout: float | None = None):
        """Stop every worker with a bounded join (terminate/kill fallback;
        TCP sessions end and the remote servers return to accepting).
        Syncs dirty mirrors first, so post-close reads see the freshest
        folded state.  Idempotent; pending-but-undrained updates stay
        journaled parent-side (they were never acked), so closing loses no
        federation state that a checkpoint of the mirrors would not
        capture."""
        if self._closed:
            return
        try:
            self.sync_mirrors()
        except BaseException:
            pass                  # a dead worker's folds are replay-covered
        self._closed = True
        t = self.drain_timeout_s if timeout is None else float(timeout)
        for sh in self._proc_shards:
            with sh.rpc_lock:
                try:
                    sh.handle.stop(min(t, 10.0))
                except BaseException:
                    sh.handle.discard()
                for h in sh.replicas:
                    try:
                        h.stop(min(t, 10.0))
                    except BaseException:
                        h.discard()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def worker_spawns(self) -> list:
        """Per-shard spawn counts (1 = never respawned) — respawn-path
        observability for tests and ``agg_stats``."""
        return [sh.handle.spawns for sh in self._proc_shards]

    def _debug_kill_worker(self, shard: int):
        """Crash injection (tests): SIGKILL the worker / poison the
        emulation.  The next drain touching the shard detects and respawns."""
        self._proc_shards[shard].handle.kill()

    # ------------------------------------------------------------------ keys
    def _submit_stats(self, key: str) -> _SubmitStats:
        return self._proc_shards[self.shard_of(key)].stats

    def _all_submit_stats(self) -> list:
        return [s.stats for s in self._proc_shards]

    def shard_of(self, key: str) -> int:
        """Same ring assignment as ``ShardedModelStore.shard_of`` — the
        two sharded topologies are drop-in replacements for each other."""
        return self.ring.shard_of(key)

    def ownership_epoch(self) -> int:
        """Monotone epoch bumped by every ``migrate_cluster`` — the
        staleness version for routing caches (``FetchClient``)."""
        # fedlint: unlocked-ok(monotone int; torn read returns a valid epoch)
        return self.ring.epoch

    def shard_cluster_keys(self, shard: int):
        # fedlint: unlocked-ok(copy-on-write registry snapshot read)
        return [k for k in self._records
                if k != GLOBAL_KEY and self.shard_of(k) == shard]

    def ensure_cluster(self, cluster_key: str, init_params=None):
        key = str(cluster_key)
        with self._registry_lock:
            if key in self._records:
                return
            seed = (init_params if init_params is not None
                    else self._records[GLOBAL_KEY].params)
            updated = dict(self._records)
            updated[key] = ModelRecord(seed)
            self._records = updated
        # command-queue FIFO makes the worker register the model before any
        # subsequently submitted update for it; a respawn between the
        # registry swap and this put re-seeds from the registry (idempotent)
        while True:
            idx = self.shard_of(key)
            sh = self._proc_shards[idx]
            with sh.journal_lock:
                if self.shard_of(key) != idx:
                    continue    # migration fenced this key mid-publish
                raw = server_proc.packb(["ensure", key, seed,
                                         self.ring.epoch])
                self._outbox_put(sh, raw)
            break
        for h in sh.replicas:       # replicas must serve the key too
            if h.alive():
                h.put(raw)

    # ------------------------------------------------------- submit paths
    def _handle_update(self, level: str, cluster_key: str | None,
                       updated_params, updated_meta: ModelMeta,
                       delta: UpdateDelta, *, blocking: bool = True) -> bool:
        # every update crosses a process boundary, so the store is
        # queue-based even in "direct" mode: a non-batched config folds
        # synchronously right after the enqueue (a coalesced fold of each
        # single update — identical Algorithm-2 semantics)
        self.enqueue_update(level, cluster_key, updated_params, updated_meta,
                            delta)
        if not self.batch_aggregation:
            self.drain(level, cluster_key)
        return True

    def enqueue_update(self, level: str, cluster_key: str | None,
                       updated_params, updated_meta: ModelMeta,
                       delta: UpdateDelta) -> int:
        key = self._key(level, cluster_key)
        seq = next(self._gseq)
        tel = self._tel
        trace = current_trace() if tel is not None else 0
        t0 = clock.monotonic_ns() if tel is not None else 0
        if key == GLOBAL_KEY:
            # global tier: strike a round-robin worker slice (the two-level
            # fold is seq-sorted, so slice assignment is semantically free;
            # the global model is parent-owned and never migrates)
            sh = self._proc_shards[seq % self.n_shards]
            raw = server_proc.packb(
                ["gsub", seq, updated_params, meta_to_wire(updated_meta),
                 delta_to_wire(delta)])
            sh.stats.count_enqueue()    # before publish — see _SubmitStats
            with sh.journal_lock:
                sh.journal[seq] = _JournalEntry("gsub", key, delta.rounds,
                                                raw)
                sh.pending_counts[key] = sh.pending_counts.get(key, 0) + 1
                sh.pending_rounds[key] = \
                    sh.pending_rounds.get(key, 0) + delta.rounds
                depth = sh.pending_counts[key]
                self._outbox_put(sh, raw)
        else:
            self._record(key)          # unknown cluster -> KeyError, as flat
            meta_w = meta_to_wire(updated_meta)
            delta_w = delta_to_wire(delta)
            while True:
                idx = self.shard_of(key)
                sh = self._proc_shards[idx]
                sh.stats.count_enqueue()  # before publish — see _SubmitStats
                with sh.journal_lock:
                    if self.shard_of(key) != idx:
                        # a migration fenced this key between the route
                        # read and the journal lock: reroute (the journal
                        # move holds both journal locks, so entries
                        # published here can never be missed)
                        continue
                    raw = server_proc.packb(
                        ["sub", seq, key, updated_params, meta_w, delta_w,
                         self.ring.epoch])
                    sh.journal[seq] = _JournalEntry("sub", key, delta.rounds,
                                                    raw)
                    sh.pending_counts[key] = sh.pending_counts.get(key, 0) + 1
                    sh.pending_rounds[key] = \
                        sh.pending_rounds.get(key, 0) + delta.rounds
                    depth = sh.pending_counts[key]
                    self._outbox_put(sh, raw)
                break
        sh.stats.observe_depth(depth)
        if tel is not None:
            tel.metrics.histogram("queue_depth").observe(depth)
            args = {"key": key, "depth": depth}
            if trace:
                # the wire seq links this submit to the worker-side fold
                # event that consumes it (its args carry the batch's seqs),
                # since outbox batching means the *frame* that ships the
                # update may carry another call's trace context
                args["seq"] = seq
            tel.event("enqueue", t0, clock.monotonic_ns() - t0, trace, args)
        return depth

    def _enqueue_many(self, level: str, cluster_key: str | None,
                      ups) -> int:
        # every update must be journaled individually (respawn replay is
        # per-entry), so the batch win here is the outbox: FLUSH_N submits
        # coalesce into one wire frame regardless of entry point
        depth = 0
        for p, m, d in ups:
            depth = self.enqueue_update(level, cluster_key, p, m, d)
        return depth

    def pending_depth(self, level: str, cluster_key: str | None = None) -> int:
        key = self._key(level, cluster_key)
        if key == GLOBAL_KEY:
            total = 0
            for sh in self._proc_shards:
                with sh.journal_lock:
                    total += sh.pending_counts.get(GLOBAL_KEY, 0)
            return total
        sh = self._proc_shards[self.shard_of(key)]
        with sh.journal_lock:
            return sh.pending_counts.get(key, 0)

    def effective_round(self, level: str, cluster_key: str | None = None) -> int:
        """Same staleness reference as the in-thread stores.  The journal
        holds every queued *and* in-flight (popped by a worker fold, not yet
        acked) update, and acks land in the same ``journal_lock`` section
        that swaps the folded meta in — readers can never watch the round
        count regress mid-drain."""
        key = self._key(level, cluster_key)
        rec = self._record(key)
        if key == GLOBAL_KEY:
            with rec.pending_lock:
                queued = 0
                for sh in self._proc_shards:
                    with sh.journal_lock:
                        queued += sh.pending_rounds.get(GLOBAL_KEY, 0)
                return rec.meta.round + queued
        sh = self._proc_shards[self.shard_of(key)]
        with sh.journal_lock:
            return rec.meta.round + sh.pending_rounds.get(key, 0)

    # ---------------------------------------------------------------- drains
    @staticmethod
    def _ack(sh: _ProcShard, seqs):
        """Retire acked journal entries.  Caller holds ``sh.journal_lock``
        and has already applied the fold result they correspond to."""
        for seq in seqs:
            e = sh.journal.pop(seq, None)
            if e is None:
                continue
            if e.kind in ("sub", "gsub"):
                sh.pending_counts[e.key] = sh.pending_counts.get(e.key, 1) - 1
                sh.pending_rounds[e.key] = \
                    sh.pending_rounds.get(e.key, e.rounds) - e.rounds

    def _respawn(self, sh: _ProcShard):
        """Replace a dead/stuck worker: ``Transport.restart`` resets it from
        the parent mirrors (fresh process for the spawned flavor; reconnect
        + re-seed for TCP — a supervisor-restarted server on the same
        address is picked up transparently), then the journal is replayed
        in seq order (parent-custody global entries skipped — their payload
        is already in the in-flight fold's hands; the worker's seq
        held-seq dedup makes the replay idempotent if some messages survived).
        Folded-but-unsynced entries (lazy mirror sync) are still journaled,
        so the replay refolds them from the last synced mirror — their
        deferred stats are dropped here and recounted by the refold.
        Caller holds ``sh.rpc_lock``."""
        with sh.journal_lock:
            sh.outbox = []     # journaled (subs) or registry-derived (ensure)
            sh.dirty.clear()   # reseeded worker == mirror: nothing stale
            sh.deferred.clear()
            sh.handle.restart(self._seed_blob(sh.idx))
            for seq in sorted(sh.journal):
                e = sh.journal[seq]
                if not e.custody:
                    self._outbox_put(sh, e.raw)
            self._flush_outbox(sh)
        with self._drain_lock:
            self.n_respawns += 1

    # extra reply allowance for the first command after a respawn: a fresh
    # worker pays a cold interpreter + jax import before its first fold
    SPAWN_ALLOWANCE_S = 60.0

    # submits coalesce into one queue message per shard: the per-message
    # transport cost (queue wakeups, pipe round trips) dominates marginal
    # bytes, so batching widens the submit pipe ~FLUSH_N-fold.  Every RPC
    # flushes first, which keeps command-queue FIFO semantics intact.
    FLUSH_N = 8

    def _flush_outbox(self, sh: _ProcShard):
        """Ship the shard's buffered fire-and-forget messages as one batch.
        Caller holds ``sh.journal_lock`` (the outbox's lock)."""
        if not sh.outbox:
            return
        if len(sh.outbox) == 1:
            sh.handle.put(sh.outbox[0])
        else:
            sh.handle.put(server_proc.packb(["batch", sh.outbox]))
        sh.outbox = []

    def _outbox_put(self, sh: _ProcShard, raw: bytes):
        """Buffer one fire-and-forget message, flushing at the batch
        threshold.  Caller holds ``sh.journal_lock``."""
        sh.outbox.append(raw)
        if len(sh.outbox) >= self.FLUSH_N:
            self._flush_outbox(sh)

    def _exchange(self, sh: _ProcShard, raw: bytes,
                  timeout: float | None = None):
        """Send one replying command and decode its reply, with crash and
        timeout handling: on ``WorkerUnavailable`` the worker is respawned
        (journal replay) and the command retried once.  Caller holds
        ``sh.rpc_lock``."""
        timeout = self.drain_timeout_s if timeout is None else timeout
        for attempt in (0, 1):
            try:
                return server_proc.unpackb(sh.handle.rpc(raw, timeout))
            except server_proc.WorkerUnavailable as e:
                if isinstance(e, server_proc.WorkerTimeout):
                    self._count_drain_timeout(sh.idx)
                self._respawn(sh)
                timeout = self.drain_timeout_s + self.SPAWN_ALLOWANCE_S
                if attempt:
                    raise RuntimeError(
                        f"shard {sh.idx} worker unavailable even after "
                        f"respawn: {e}") from e

    @staticmethod
    def _check_error(sh: _ProcShard, reply):
        if reply[0] == "error":
            raise RuntimeError(
                f"shard {sh.idx} worker error on {reply[1]!r}: {reply[2]}")

    def _rpc(self, sh: _ProcShard, raw: bytes, on_reply):
        """One replying worker command.  ``on_reply`` runs inside the
        critical section so its acks/custody marks are visible before any
        later respawn could replay the entries it consumed."""
        with sh.rpc_lock:
            with sh.journal_lock:
                self._flush_outbox(sh)
            reply = self._exchange(sh, raw)
            self._check_error(sh, reply)
            return on_reply(reply)

    def _scatter_gather(self, raws, on_reply) -> list:
        """Broadcast one replying command per worker, then gather — the K
        folds run truly concurrently while the parent waits once.  This is
        the process-pool drain beat: one parent thread, K busy workers
        (per-shard pump threads would serialize on the parent's GIL
        instead).  ``raws`` is one bytes command for all shards or a
        per-shard list.  Holds every shard's rpc_lock (acquired in index
        order) across the exchange; per-shard crashes respawn and retry
        that shard alone.  Returns ``on_reply(sh, reply)`` per shard."""
        if isinstance(raws, bytes):
            raws = [raws] * self.n_shards
        if self.inprocess:
            # the emulation dispatches inline — scatter degenerates to a
            # deterministic sequential sweep over the single-shard RPC path
            return [self._rpc(sh, raw, lambda reply, sh=sh: on_reply(sh, reply))
                    for sh, raw in zip(self._proc_shards, raws, strict=True)]
        for sh in self._proc_shards:
            sh.rpc_lock.acquire()
        try:
            for sh, raw in zip(self._proc_shards, raws, strict=True):
                with sh.journal_lock:
                    self._flush_outbox(sh)
                sh.handle.put(raw)               # scatter: no waiting yet
            out = []
            for sh, raw in zip(self._proc_shards, raws, strict=True):
                try:
                    reply = server_proc.unpackb(
                        sh.handle.rpc_recv(self.drain_timeout_s))
                except server_proc.WorkerUnavailable as e:
                    if isinstance(e, server_proc.WorkerTimeout):
                        self._count_drain_timeout(sh.idx)
                    self._respawn(sh)
                    reply = self._exchange(        # journal replayed
                        sh, raw,
                        self.drain_timeout_s + self.SPAWN_ALLOWANCE_S)
                self._check_error(sh, reply)
                out.append(on_reply(sh, reply))
            return out
        finally:
            for sh in self._proc_shards:
                sh.rpc_lock.release()

    def _push_replicas(self, sh: _ProcShard, key: str, params, meta_w):
        """Best-effort mirror push to the shard's read replicas after an
        authoritative mirror swap (fire-and-forget ``mirror`` op).  A dead
        replica drops pushes (fetch clients fail over to the owner or the
        parent) and gets a throttled reconnect attempt — ``restart``
        re-seeds it from the parent mirrors, which resyncs every key it
        missed.  Callers hold ``sh.rpc_lock`` (reply application), so the
        counters need no extra lock; never called under ``journal_lock``."""
        if not sh.replicas:
            return
        raw = server_proc.packb(["mirror", key, params, meta_w])
        for h in sh.replicas:
            if h.alive():
                h.put(raw)
                sh.replica_pushes += 1
                continue
            sh.replica_drops += 1
            if sh.replica_drops % 32 == 1:
                try:
                    h.restart(self._seed_blob(sh.idx))
                    h.put(raw)
                    sh.replica_pushes += 1
                except BaseException:
                    h.discard()

    def _apply_drained(self, sh: _ProcShard, reply) -> int:
        _, key, folded, fast, batches, acked, params, meta_w = reply
        if not folded:
            return 0
        rec = self._record(key)
        if params is None:
            # meta-only (provisional) ack — lazy mirror sync: the fold
            # happened worker-side but its params ship with a later reply
            # (or the sync_mirrors barrier).  Keep the entries journaled
            # (a crash replays + refolds them from the last synced
            # mirror), mark the mirror dirty, and defer the drain stats so
            # the refold can't double-count them.
            with sh.journal_lock:
                sh.dirty.add(key)
                d = sh.deferred.setdefault(key, [0, 0, 0])
                d[0] += folded
                d[1] += fast
                d[2] += batches
            return folded
        with sh.journal_lock:
            rec.swap(params, meta_from_wire(meta_w))
            self._ack(sh, acked)     # flushes earlier provisional acks too
            sh.dirty.discard(key)
            dfolded, dfast, dbatches = sh.deferred.pop(key, (0, 0, 0))
        self._push_replicas(sh, key, params, meta_w)
        self._count_drain(folded + dfolded, fast + dfast,
                          batches=batches + dbatches)
        return folded

    def drain(self, level: str, cluster_key: str | None = None) -> int:
        key = self._key(level, cluster_key)
        if key == GLOBAL_KEY:
            return self.drain_global()
        sh = self._proc_shards[self.shard_of(key)]
        return self._rpc(sh, server_proc.packb(["drain", key]),
                         lambda reply: self._apply_drained(sh, reply))

    def _apply_shard_beat(self, sh: _ProcShard, reply) -> int:
        """Apply one ``shard_drained`` reply: per-key folded states swapped
        into the mirrors and acked.  Shared by the single-shard drain and
        the scatter-gather ``drain_all`` beat."""
        total = 0
        for per_key in reply[1]:
            total += self._apply_drained(sh, ["drained"] + list(per_key))
        return total

    def drain_shard(self, shard: int) -> int:
        """One drain beat for a whole worker: every cluster model it owns,
        folded worker-side in one RPC round trip."""
        sh = self._proc_shards[shard]
        return self._rpc(sh, server_proc.packb(["drain_shard"]),
                         lambda reply: self._apply_shard_beat(sh, reply))

    def _abort_global_drain(self):
        """Undo a half-done cross-server merge: clear custody so the
        journal is authoritative again, then respawn every worker — fresh
        queues discard any stale half-gathered replies, and the journal
        replay restores each slice exactly (nothing was acked)."""
        for sh in self._proc_shards:
            with sh.journal_lock:
                for e in sh.journal.values():
                    e.custody = False
            with sh.rpc_lock:
                self._respawn(sh)

    def drain_global(self) -> int:
        """Cross-server two-level global merge, distributed: the parent
        scatter-gathers each server's slice *metadata* (``gmeta``), runs
        the unchanged ``plan_coalesce`` over the seq-sorted concatenation
        to fix every update's flat telescoped coefficient, then each
        worker reduces its own members to one convex partial (``greduce``
        — params never cross a process boundary individually, only K
        partials do), and a mass-weighted K-way merge reassembles the
        exact flat Algorithm-2 sum.  Same algebra as the thread-sharded
        ``two_level_coalesced_aggregate``, with the partial reduction
        running on the servers instead of the parent."""
        rec = self._record(GLOBAL_KEY)
        with rec.lock:
            # phase 1 — plan over metas (read-only snapshot of the slices)
            metas = self._scatter_gather(server_proc.packb(["gmeta"]),
                                         lambda sh, reply: reply[1])
            flat = sorted((int(it[0]), k, meta_from_wire(it[1]),
                           delta_from_wire(it[2]))
                          for k, items in enumerate(metas) for it in items)
            n = len(flat)
            if n == 0:
                return 0
            tel = self._tel
            t0 = clock.monotonic_ns() if tel is not None else 0
            plan = plan_coalesce(rec.meta, [(m, d) for _, _, m, d in flat],
                                 self.agg_cfg)
            by_shard: dict[int, list] = {k: [] for k in range(self.n_shards)}
            for (seq, k, _, _), w in zip(flat, plan.weights[1:], strict=True):
                by_shard[k].append([seq, w])
            try:
                # phase 2 — per-server partial reduction; custody marks the
                # reduced entries so a concurrent respawn cannot replay
                # them while the merge is in flight
                def collect(sh, reply):
                    with sh.journal_lock:
                        for seq in reply[1]:
                            e = sh.journal.get(int(seq))
                            if e is not None:
                                e.custody = True
                    return reply
                raws = [server_proc.packb(["greduce", by_shard[k]])
                        for k in range(self.n_shards)]
                replies = self._scatter_gather(raws, collect)
                acked = [[int(s) for s in reply[1]] for reply in replies]
                partials = [(reply[3], reply[2]) for reply in replies
                            if reply[3] is not None and reply[2] > 0.0]
                base_w = plan.weights[0]
                entries = (([(rec.params, base_w)] if base_w != 0.0 else [])
                           + partials)
                if not entries:
                    new_params = rec.params
                else:
                    entries = chunked_convex_reduce(entries,
                                                    self.max_coalesce,
                                                    self.agg_cfg)
                    new_params = (entries[0][0] if len(entries) == 1 else
                                  multi_aggregate([p for p, _ in entries],
                                                  [m for _, m in entries],
                                                  self.agg_cfg))
            except BaseException:
                self._abort_global_drain()
                raise
            if tel is not None:
                dur = clock.monotonic_ns() - t0
                tel.metrics.histogram(
                    f"drain_fold_ns_{self._route}").observe(dur)
                tel.metrics.histogram("coalesce_batch").observe(n)
                stale = tel.metrics.histogram("staleness_at_fold")
                base_round = rec.meta.round
                # parent-side only: the workers' greduce partials observe
                # nothing for the global tier, or every update would be
                # counted twice.  ``flat`` is seq-sorted, so the telescoped
                # staleness matches the flat store's (see _drain_record_once)
                cum = 0
                for _, _, m, d in flat:
                    stale.observe(max(0, base_round + cum - m.round))
                    cum += d.rounds
                tel.event("merge", t0, dur, current_trace(),
                          {"key": GLOBAL_KEY, "n": n,
                           "partials": len(partials)})
            with rec.pending_lock:
                rec.swap(new_params, plan.meta)
                for sh, sq in zip(self._proc_shards, acked, strict=True):
                    with sh.journal_lock:
                        self._ack(sh, sq)
        with self._drain_lock:
            self._n_drain_updates += n
            self._n_drain_fast_path += plan.n_fast_path
            self.n_drain_batches += 1
            self.n_drained += n
            self.n_global_drains += 1
            self.n_global_partials += len(partials)
        return n

    def drain_all(self) -> int:
        """One full drain beat: the cross-server global merge, then one
        ``drain_shard`` broadcast — every worker folds its cluster queues
        concurrently while the parent gathers (the threaded runtime's
        process-pool pump calls exactly this in a loop)."""
        total = self.drain_global()
        total += sum(self._scatter_gather(server_proc.packb(["drain_shard"]),
                                          self._apply_shard_beat))
        return total

    # ---------------------------------------------------- lazy mirror sync
    def _apply_synced(self, sh: _ProcShard, reply) -> int:
        """Apply one ``synced`` reply: swap each shipped (params, meta)
        into the mirror, retire the accumulated provisional acks, and
        release the deferred drain stats — the mirror is authoritative for
        those keys again."""
        n = 0
        for key, acked, params, meta_w in reply[1]:
            rec = self._record(key)
            with sh.journal_lock:
                rec.swap(params, meta_from_wire(meta_w))
                self._ack(sh, acked)
                sh.dirty.discard(key)
                counts = sh.deferred.pop(key, None)
            self._push_replicas(sh, key, params, meta_w)
            if counts:
                self._count_drain(counts[0], counts[1], batches=counts[2])
            n += 1
        return n

    def _sync_shard(self, sh: _ProcShard) -> int:
        with self._drain_lock:
            self.n_mirror_syncs += 1
        tel = self._tel
        if tel is None:
            return self._rpc(sh, server_proc.packb(["sync"]),
                             lambda reply: self._apply_synced(sh, reply))
        with tel.span("mirror_sync", current_trace(), {"shard": sh.idx}):
            return self._rpc(sh, server_proc.packb(["sync"]),
                             lambda reply: self._apply_synced(sh, reply))

    def fetch_endpoints(self):
        """Read-tier serving addresses per shard — replicas first, the
        shard owner last — or ``None`` when the workers are not reachable
        over TCP (spawned/inprocess flavors serve reads parent-side).
        ``repro.core.fetch.FetchClient`` round-robins over each list."""
        if self.server_hosts is None:
            return None
        out = []
        for sh in self._proc_shards:
            addrs = (list(self.replica_hosts[sh.idx])
                     if self.replica_hosts else [])
            addrs.append(self.server_hosts[sh.idx])
            out.append(addrs)
        return out

    def _sync_key(self, key: str):
        """Read barrier for one model: if its mirror is dirty (lazy mirror
        sync), pull the worker's params before the read.  Clean keys — and
        the parent-owned global model — cost one set lookup.

        Audit note (stale-read window): a provisional (meta-only) ack and
        a concurrent read race on ``sh.dirty``.  Both sides take
        ``journal_lock``, so exactly two interleavings exist: the reader
        checks after ``_apply_drained`` marked the key (mark visible →
        barrier syncs, fresh read), or before (the ack is still being
        applied, so the read linearizes ahead of it — indistinguishable
        from the drain reply still being in flight, the same lag eager
        ``mirror_sync_every=1`` has between a worker fold and the parent
        swap).  There is NO window where a visible dirty mark is skipped,
        which is the invariant the barrier promises and
        ``test_process_store.py`` pins with a timed-thread regression
        test (reads started after the ack application returns must
        observe the fold)."""
        if self.mirror_sync_every <= 1 or key == GLOBAL_KEY or self._closed:
            return
        sh = self._proc_shards[self.shard_of(key)]
        with sh.journal_lock:
            if key not in sh.dirty:
                return
        self._sync_shard(sh)

    def sync_mirrors(self) -> int:
        """Barrier: flush every worker's folded-but-unshipped params into
        the parent mirrors.  After it returns, every mirror reflects every
        fold whose drain reply the parent has processed — the invariant
        the read paths, ``save_store`` and ``close`` rely on.  Returns the
        number of models synced (0 when ``mirror_sync_every`` is 1: every
        drain reply already ships params)."""
        if self.mirror_sync_every <= 1 or self._closed:
            return 0
        synced = 0
        for sh in self._proc_shards:
            with sh.journal_lock:
                dirty = bool(sh.dirty)
            if dirty:
                synced += self._sync_shard(sh)
        return synced

    # ------------------------------------------------- reads (sync barrier)
    def request_model(self, level: str, cluster_key: str | None = None):
        self._sync_key(self._key(level, cluster_key))
        return super().request_model(level, cluster_key)

    def params(self, level: str, cluster_key: str | None = None):
        self._sync_key(self._key(level, cluster_key))
        return super().params(level, cluster_key)

    def meta(self, level: str, cluster_key: str | None = None) -> ModelMeta:
        self._sync_key(self._key(level, cluster_key))
        return super().meta(level, cluster_key)

    # ---------------------------------------------------- secure aggregation
    def submit_secure(self, level: str, cluster_key: str | None,
                      client_id: str, round_id: int, masked_delta,
                      delta: UpdateDelta) -> int:
        key = self._key(level, cluster_key)
        if key == GLOBAL_KEY:
            # the parent owns the global model, so its secure rounds stay
            # parent-local — model-local per server, like every other model
            return super().submit_secure(level, cluster_key, client_id,
                                         round_id, masked_delta, delta)
        self._record(key)
        seq = next(self._gseq)
        bucket = (key, int(round_id))
        delta_w = delta_to_wire(delta)
        while True:
            idx = self.shard_of(key)
            sh = self._proc_shards[idx]
            sh.stats.count_enqueue()    # before publish — see _SubmitStats
            with sh.journal_lock:
                if self.shard_of(key) != idx:
                    continue    # migration fenced this key — reroute
                raw = server_proc.packb(
                    ["ssub", seq, key, int(round_id), str(client_id),
                     masked_delta, delta_w, self.ring.epoch])
                sh.journal[seq] = _JournalEntry("secure", key, delta.rounds,
                                                raw)
                sh.secure_counts[bucket] = sh.secure_counts.get(bucket, 0) + 1
                depth = sh.secure_counts[bucket]
                self._outbox_put(sh, raw)
            break
        sh.stats.observe_depth(depth)
        return depth

    def drain_secure(self, level: str, cluster_key: str | None,
                     round_id: int, expected_ids) -> int:
        key = self._key(level, cluster_key)
        if key == GLOBAL_KEY:
            return super().drain_secure(level, cluster_key, round_id,
                                        expected_ids)
        sh = self._proc_shards[self.shard_of(key)]

        def apply(reply):
            _, _, folded, recovered, acked, params, meta_w = reply
            if not folded:
                return 0
            rec = self._record(key)
            with sh.journal_lock:
                rec.swap(params, meta_from_wire(meta_w))
                # secure replies always ship params, flushing any earlier
                # provisional acks for the key along with them
                self._ack(sh, acked)
                sh.secure_counts.pop((key, int(round_id)), None)
                sh.dirty.discard(key)
                counts = sh.deferred.pop(key, None)
            self._push_replicas(sh, key, params, meta_w)
            if counts:
                self._count_drain(counts[0], counts[1], batches=counts[2])
            self._count_drain(folded, 0, secure=True, recovered=recovered)
            return folded

        return self._rpc(
            sh, server_proc.packb(["sdrain", key, int(round_id),
                                   [str(i) for i in expected_ids]]), apply)

    # ---------------------------------------------------- cluster migration
    def migrate_cluster(self, cluster_key: str, dst_shard: int) -> int:
        """Live-migrate one cluster model to another worker; returns the
        new ownership epoch (docs/ELASTICITY.md is the normative spec).

        Protocol (under both workers' rpc locks, index order): sync any
        provisional acks so the journal holds exactly the worker's pending
        seqs, **fence** by flipping the ring override (new submits route
        and journal to the new owner from that instant), flush the old
        owner's outbox (pre-fence stragglers reach it ahead of the export
        — command-queue FIFO), move the key's journal entries + counters
        to the new owner's shard, then ``mig_export`` (the old worker pops
        the record, ships params + pending + secure buckets and tombstones
        the key) and ``mig_install`` (the new worker installs, skipping
        seqs its held-dedup already has — the idempotence that makes every
        crash-retry safe).  Finally ``mig_redirects`` collects messages
        the old worker parked for the migrated key (submits that raced
        the fence) and re-delivers them to the new owner, where held-seq
        dedup drops any duplicate.  Any failure after the journal move
        degrades to ``_respawn(dst)``: the parent mirror + moved journal
        are the source of truth, so a fresh seed + replay completes the
        migration."""
        key = self._key("cluster", cluster_key)
        rec = self._record(key)              # unknown cluster -> KeyError
        dst_i = int(dst_shard)
        if not 0 <= dst_i < self.n_shards:
            raise ValueError(f"destination shard {dst_i} out of range "
                             f"[0, {self.n_shards})")
        src_i = self.shard_of(key)
        if src_i == dst_i:
            # fedlint: unlocked-ok(monotone int; no-op returns current epoch)
            return self.ring.epoch           # already owned by dst: no-op
        src, dst = self._proc_shards[src_i], self._proc_shards[dst_i]
        first, second = (src, dst) if src_i < dst_i else (dst, src)
        tel = self._tel
        t0 = clock.monotonic_ns() if tel is not None else 0
        with first.rpc_lock, second.rpc_lock:
            if tel is None:
                epoch = self._migrate_locked(key, rec, src, dst)
            else:
                with tel.span("migrate", current_trace(),
                              {"key": key, "src": src_i, "dst": dst_i}):
                    epoch = self._migrate_locked(key, rec, src, dst)
        with self._drain_lock:
            self.n_cluster_migrations += 1
        if tel is not None:
            tel.metrics.counter("cluster_migrations").inc()
            tel.event("migrate", t0, clock.monotonic_ns() - t0,
                      current_trace(),
                      {"key": key, "src": src_i, "dst": dst_i,
                       "epoch": epoch})
        return epoch

    def _migrate_locked(self, key: str, rec: ModelRecord, src: _ProcShard,
                        dst: _ProcShard) -> int:
        """The fence -> ship -> ack -> replay body of ``migrate_cluster``.
        Caller holds both shards' rpc locks (index order)."""
        # 1. flush provisional (lazy-sync) acks: afterwards the journal
        # holds exactly the seqs the src worker still queues for this key,
        # so the export blob and the moved journal describe the same set
        if self.mirror_sync_every > 1:
            with src.journal_lock:
                dirty = key in src.dirty
            if dirty:
                self._sync_shard(src)
        # 2. fence + flip: from this instant every submit routes (and
        # journals) to dst, stamped with the bumped epoch
        epoch = self.ring.assign(key, dst.idx)
        # 3. pre-fence stragglers in the outbox reach the src worker ahead
        # of the export (command-queue FIFO)
        with src.journal_lock:
            self._flush_outbox(src)
        # 4. move the key's journal entries + pending counters to dst: the
        # journal is the crash-replay source of truth, so after this step
        # a dst respawn alone completes the migration
        a, b = (src, dst) if src.idx < dst.idx else (dst, src)
        with a.journal_lock, b.journal_lock:
            for seq in [s for s, e in src.journal.items() if e.key == key]:
                dst.journal[seq] = src.journal.pop(seq)
            if key in src.pending_counts:
                dst.pending_counts[key] = dst.pending_counts.get(key, 0) + \
                    src.pending_counts.pop(key)
                dst.pending_rounds[key] = dst.pending_rounds.get(key, 0) + \
                    src.pending_rounds.pop(key, 0)
            for bkt in [b for b in src.secure_counts if b[0] == key]:
                dst.secure_counts[bkt] = dst.secure_counts.get(bkt, 0) + \
                    src.secure_counts.pop(bkt)
            if key in src.dirty:          # empty after step 1; defensive
                src.dirty.discard(key)
                dst.dirty.add(key)
            d = src.deferred.pop(key, None)
            if d is not None:
                dd = dst.deferred.setdefault(key, [0, 0, 0])
                for i in range(3):
                    dd[i] += d[i]
        # 5. export: src pops the record, ships its state, tombstones the
        # key.  A None blob means src was respawned mid-export (its fresh
        # seed, post-flip, excludes the key) — fall back to reseeding dst,
        # whose seed blob now includes the key from the parent mirror and
        # whose journal replay delivers the moved entries.
        try:
            reply = self._exchange(src, server_proc.packb(
                ["mig_export", key, epoch, dst.idx]))
            self._check_error(src, reply)
            state = reply[2]
        except BaseException:
            # a deferred submit-path error surfaced on the export: clear
            # BOTH workers to the journaled truth before re-raising, so
            # the half-moved key cannot be folded twice
            self._respawn(src)
            self._respawn(dst)
            raise
        if state is None:
            self._respawn(dst)
        else:
            try:
                reply = self._exchange(dst, server_proc.packb(
                    ["mig_install", key, epoch, state]))
                self._check_error(dst, reply)
            except BaseException:
                # journal + mirror are authoritative; a fresh dst seed +
                # replay completes the migration
                self._respawn(dst)
        # 6. re-deliver submits the src worker parked for migrated keys
        # (stragglers that raced the fence); dst's held-seq dedup makes a
        # duplicate delivery (e.g. one also covered by a replay) a no-op
        try:
            reply = self._exchange(src, server_proc.packb(["mig_redirects"]))
            self._check_error(src, reply)
            redirected = reply[1]
        except BaseException:
            redirected = []   # a respawned src parked nothing; any moved
            #                   entries were already delivered by replay
        if redirected:
            with dst.journal_lock:
                for raw in redirected:
                    self._outbox_put(dst, raw)
        # 7. the new owner's read replicas serve the key from the parent
        # mirror until the next fold pushes a fresher one
        params, meta = rec.snapshot()
        self._push_replicas(dst, key, params, meta_to_wire(meta))
        return epoch

    # ------------------------------------------------------------- inspection
    def _count_drain_timeout(self, shard: int | None = None):
        """Deadline misses are attributed per worker here: one stuck host
        must be findable without grepping logs (the runbook in
        ``docs/OPERATIONS.md`` keys on ``shard_drain_timeouts``)."""
        with self._drain_lock:
            self.n_drain_timeouts += 1
            if shard is not None:
                self.n_shard_drain_timeouts[shard] += 1

    def transport_kind(self) -> str:
        if self.server_hosts is not None:
            return "tcp"
        return "inprocess" if self.inprocess else "process"

    def wire_bytes(self) -> tuple[int, int]:
        """(tx, rx) payload bytes across every worker transport — the
        bytes-on-wire metric (``benchmarks/multiproc_store.py``)."""
        tx = sum(sh.handle.tx_bytes for sh in self._proc_shards)
        rx = sum(sh.handle.rx_bytes for sh in self._proc_shards)
        for sh in self._proc_shards:
            tx += sum(h.tx_bytes for h in sh.replicas)
            rx += sum(h.rx_bytes for h in sh.replicas)
        return tx, rx

    def telemetry_dump(self) -> dict:
        """Parent site plus one site per live worker, fetched over the
        worker transport (the ``obsdump`` command — docs/WIRE_PROTOCOL.md).
        A worker that cannot reply is skipped: its rings died with it, and
        the respawned worker's telemetry restarts empty (which is also why
        journal replay can never double-count spans — only the surviving
        session's events are ever dumped).  Wire-byte and dirty-mirror
        gauges are stamped at dump time."""
        if self._tel is None:
            return {"sites": []}
        tx, rx = self.wire_bytes()
        gauge = self._tel.metrics.gauge
        gauge("wire_tx_bytes").set(tx)
        gauge("wire_rx_bytes").set(rx)
        dirty = 0
        for sh in self._proc_shards:
            with sh.journal_lock:
                dirty += len(sh.dirty)
        gauge("dirty_mirrors").set(dirty)
        sites = [self._tel.dump()]
        if self._closed:
            return {"sites": sites}
        raw = server_proc.packb(["obsdump"])
        for sh in self._proc_shards:
            try:
                dump = self._rpc(sh, raw, lambda reply: reply[1])
            except BaseException:
                continue
            if dump is not None:
                sites.append(dump)
        return {"sites": sites}

    def agg_stats(self) -> dict:
        tx, rx = self.wire_bytes()
        with self._drain_lock:
            extra = {"processes": 0 if self.inprocess else self.n_shards,
                     "transport": self.transport_kind(),
                     "respawns": self.n_respawns,
                     "mirror_syncs": self.n_mirror_syncs,
                     "shard_drain_timeouts":
                         list(self.n_shard_drain_timeouts),
                     "wire_tx_bytes": tx,
                     "wire_rx_bytes": rx,
                     "replicas": sum(len(sh.replicas)
                                     for sh in self._proc_shards),
                     "replica_pushes": sum(sh.replica_pushes
                                           for sh in self._proc_shards),
                     "replica_drops": sum(sh.replica_drops
                                          for sh in self._proc_shards),
                     "ownership_epoch": self.ring.epoch,
                     "cluster_migrations": self.n_cluster_migrations}
        return _sharded_agg_stats(self, self._proc_shards, extra)

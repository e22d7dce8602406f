"""Shard servers as worker processes — the multi-*server* aggregation tier.

``ProcessShardedModelStore`` (``repro.core.store``) promotes each shard of
the sharded server to an OS **process** so aggregation escapes the GIL: the
parent serializes submits onto per-shard SPSC command queues (producer: the
parent, guarded; consumer: the one worker), each worker owns its shard's
cluster models + pending queues and folds them with the exact same
``coalesced_aggregate`` the in-thread stores use, and drain RPCs ship the
folded ``(params, meta)`` back for the parent's authoritative mirror.

This module holds the pieces that must be importable from a spawned child:

  * the **wire codec** — msgpack with the checkpoint array ext codec
    (``repro.checkpoint.msgpack_ckpt.packb``/``unpackb``), so the update
    payloads crossing process boundaries use the identical format models are
    checkpointed in;
  * ``ShardWorker`` — the executable shard-server logic, transport-agnostic:
    the spawned main loop drives it in real mode, the standalone TCP server
    (``repro.launch.shard_server``) drives it across hosts, and the
    deterministic in-process emulation (used by ``runtime_sim`` and the fast
    tests) calls it synchronously through the same serialized messages;
  * ``ProcessWorkerHandle`` / ``InprocessWorkerHandle`` — two of the three
    parent-side ``repro.core.transport.Transport`` flavors (the TCP flavor
    lives in ``repro.core.transport``): ``put`` (fire-and-forget submit),
    ``rpc`` (command awaiting one reply, with bounded timeout + liveness
    checks), ``restart``/``kill``/``stop``.

Crash safety is the *parent's* job (see the store's journal): workers are
intentionally stateless beyond their working copies — every update a worker
holds is journaled in the parent until the drain that folded it is acked, so
a killed worker is respawned from the parent's mirrors and its journal
replayed without losing updates or double-counting rounds.  Replays are
idempotent: submits carry a monotone per-store ``seq`` and the worker drops
any seq it already holds (``held``), so a replay racing a
message that DID arrive (TCP reconnects) cannot double-apply it.

Lazy mirror sync (``mirror_sync_every`` in the seed blob): drain replies
ship the folded params only every Nth reply per model and ack with
seq-stamped metadata otherwise; the accumulated acks ride along with the
next params-carrying reply (or an explicit ``sync`` command — the
``sync_mirrors()`` barrier).  See ``docs/WIRE_PROTOCOL.md`` for the
normative message-by-message semantics.

Elastic membership (wire v4, ``docs/ELASTICITY.md``): cluster ownership
lives on a consistent-hash ring with explicit epochs, and live migration
ships a cluster's fold state between workers via the ``mig_export`` /
``mig_install`` / ``mig_redirects`` commands.  Workers tombstone
migrated-away keys and answer replying ops on them with a ``redirect``
naming the new owner; submits that race a fence park worker-side and are
replayed (new owner) or redirected (old owner) — held-seq dedup makes
every such re-delivery idempotent.
"""

from __future__ import annotations

import multiprocessing as mp
import queue as _queue
import threading
from collections import deque

from repro.checkpoint.msgpack_ckpt import packb
from repro.checkpoint.msgpack_ckpt import unpackb_np as unpackb
from repro.core.fetch import WireCache, serve_fetch
from repro.core.transport import (      # noqa: F401  (re-exported: the
    Transport,                          # exceptions predate transport.py and
    WorkerTimeout,                      # are imported from here by old code)
    WorkerUnavailable,
)
from repro.launch.device import host_worker_main
from repro.obs import clock
from repro.obs.record import Telemetry, current_trace

# commands that produce exactly one reply; everything else is fire-and-forget
REPLY_OPS = frozenset({"drain", "drain_shard", "gmeta", "greduce", "sdrain",
                       "sync", "ping", "obsdump", "stop", "fetch",
                       "mig_export", "mig_install", "mig_redirects"})


# ------------------------------------------------------------------ wire fmt

def meta_to_wire(meta) -> list:
    return [meta.samples_learned, meta.epochs_learned, meta.round]


def meta_from_wire(w):
    from repro.core.aggregation import ModelMeta

    return ModelMeta(int(w[0]), int(w[1]), int(w[2]))


def delta_to_wire(delta) -> list:
    return [delta.samples_learned, delta.epochs_learned, delta.rounds]


def delta_from_wire(w):
    from repro.core.aggregation import UpdateDelta

    return UpdateDelta(int(w[0]), int(w[1]), int(w[2]))


def make_seed_blob(shard_records, max_coalesce: int, agg_cfg,
                   masker, mirror_sync_every: int = 1,
                   telemetry=None, epoch: int = 0,
                   migrated=None) -> bytes:
    """Everything a fresh worker needs, in wire format: its owned cluster
    records, the fold config, the masker parameters (the masker must live
    worker-side — secure rounds are model-local per server process), the
    lazy-mirror-sync cadence, the telemetry config (``None`` = off,
    else ``{"sample_n": N}`` — the worker builds its own ``Telemetry``
    and ships it back via the ``obsdump`` command), the current ownership
    ``epoch``, and the ``migrated`` tombstone map (``key -> [dst, epoch]``
    for clusters this worker must redirect rather than serve — see
    docs/ELASTICITY.md)."""
    return packb({
        "records": [[key, params, meta_to_wire(meta)]
                    for key, params, meta in shard_records],
        "max_coalesce": int(max_coalesce),
        "agg": [bool(agg_cfg.use_pallas), bool(agg_cfg.sequential_fast_path)],
        "masker": (None if masker is None
                   else [int(masker.seed), float(masker.mask_scale)]),
        "sync_every": int(mirror_sync_every),
        "telemetry": telemetry,
        "epoch": int(epoch),
        "migrated": {str(k): [int(v[0]), int(v[1])]
                     for k, v in (migrated or {}).items()},
    })


# ------------------------------------------------------------------- worker

class ShardWorker:
    """One shard server's executable logic.

    Owns working copies of the shard's cluster models, their pending queues,
    their secure-round buckets, and the shard's slice of the global queue.
    Folds reuse ``coalesced_aggregate`` byte-for-byte with the in-thread
    stores, so the Algorithm-2 semantics cannot drift between topologies.
    The command path is single-threaded by construction (one consumer per
    SPSC queue), so it needs no locks.  The read path (wire v3) is the one
    concurrent entry point: ``fetch`` may be called from TCP read-session
    threads while the command session folds — it touches only each
    record's published ``snap`` tuple (swapped by a single reference
    assignment after every fold) and the internally-locked wire cache.
    """

    def __init__(self, shard_idx: int, seed_blob: bytes):
        from repro.core.aggregation import AggregationConfig

        blob = unpackb(seed_blob)
        self.idx = shard_idx
        self.max_coalesce = max(int(blob["max_coalesce"]), 1)
        self.sync_every = max(int(blob.get("sync_every", 1)), 1)
        use_pallas, fast_path = blob["agg"]
        self.agg_cfg = AggregationConfig(use_pallas=use_pallas,
                                         sequential_fast_path=fast_path)
        self.masker = None
        if blob["masker"] is not None:
            from repro.privacy.secure_agg import PairwiseMasker

            seed, scale = blob["masker"]
            self.masker = PairwiseMasker(seed=seed, mask_scale=scale)
        tcfg = blob.get("telemetry")
        self.tel = (Telemetry(sample_n=int(tcfg.get("sample_n", 1)),
                              site=f"shard-{shard_idx}")
                    if tcfg else None)
        self._route = "pallas" if use_pallas else "host"
        # key -> {"params", "meta", "pending": deque[(seq, p, m, d)],
        #         "secure": {round_id: [(seq, client_id, masked, delta)]},
        #         "unsynced": [seqs folded but not yet shipped with params],
        #         "drains": replies since the last params-carrying one}
        self.records: dict[str, dict] = {}
        self.wire_cache = WireCache()
        for key, params, meta_w in blob["records"]:
            self._ensure(key, params, meta_from_wire(meta_w))
        self.gslice: deque = deque()       # (seq, params, meta, delta)
        # elastic membership (docs/ELASTICITY.md): the highest ownership
        # epoch this worker has observed, and the tombstone map for
        # clusters migrated away — replying ops on a tombstoned key answer
        # ["redirect", key, dst, epoch] instead of serving stale state
        self.epoch = int(blob.get("epoch", 0))
        self.migrated: dict[str, tuple[int, int]] = {
            str(k): (int(v[0]), int(v[1]))
            for k, v in (blob.get("migrated") or {}).items()}
        # submits that raced a migration fence: messages for keys this
        # worker does not serve (tombstoned, or not yet installed) park
        # here in arrival order; ``mig_install`` replays the installed
        # key's parked messages, ``mig_redirects`` hands the rest back to
        # the parent for re-delivery to the new owner (held-seq dedup on
        # the receiving side makes a duplicate delivery a no-op)
        self.parked: list[tuple[str, bytes]] = []
        # replay dedup: seqs this worker currently HOLDS (queued, not yet
        # folded).  A journal replay racing messages that already arrived
        # (TCP reconnects) redelivers exactly the unacked entries, so a
        # duplicate is a submit whose seq is still held — drop it.  NOT a
        # watermark: concurrent submitters can publish a shard's seqs
        # slightly out of order (seq is allocated before the outbox lock),
        # and a failed submit never enters the set, so its replay is
        # re-attempted.  Seqs leave on fold, keeping the set bounded by
        # queue depth; a fresh seed resets it with the state it described.
        self.held: set[int] = set()
        # errors raised by fire-and-forget commands (which must not emit
        # unpaired replies) are deferred and surfaced as the error reply of
        # the NEXT replying command — never swallowed: the journaled update
        # they stranded stays unacked, so a silent drop here would inflate
        # effective_round/pending_depth forever
        self.pending_errors: list[str] = []

    def _ensure(self, key: str, params, meta=None):
        from repro.core.aggregation import ModelMeta

        if key not in self.records:
            rec = {"params": params,
                   "meta": meta if meta is not None else ModelMeta(),
                   "pending": deque(), "secure": {},
                   "unsynced": [], "drains": 0}
            self._publish(rec)
            self.records[key] = rec

    @staticmethod
    def _publish(rec):
        """Swap the record's read-path snapshot: one reference assignment,
        so concurrent ``fetch`` callers see (params, meta) move atomically
        and never a half-updated pair."""
        rec["snap"] = (rec["params"], meta_to_wire(rec["meta"]))

    def _is_replay_dup(self, seq: int) -> bool:
        """True if this submit seq is already held and must be dropped as
        a replay duplicate.  The caller registers the seq only after the
        apply succeeds: a submit that errored never entered worker state,
        so its replay must be re-attempted, not swallowed."""
        return seq in self.held

    def _serves(self, key: str) -> bool:
        """True if this worker currently owns ``key``'s fold state.  False
        during a migration race: either the key was migrated away
        (tombstoned) or it is migrating *in* and ``mig_install`` has not
        landed yet — both park the message instead of serving it."""
        return key in self.records and key not in self.migrated

    def _park(self, key: str, msg):
        """Hold a submit that raced a migration fence; re-serialized so
        replay/redirect re-delivers the exact original bytes."""
        self.parked.append((key, packb(msg)))
        if self.tel is not None:
            self.tel.metrics.counter("parked_submits").inc()
        return None

    # --------------------------------------------------------------- dispatch
    def handle(self, msg):
        """One decoded command -> reply tuple (or None for fire-and-forget).
        The real worker main loop and the in-process emulation both route
        every message through here, after the identical codec round trip."""
        op = msg[0]
        if op in REPLY_OPS and self.pending_errors:
            errs = "; ".join(self.pending_errors)
            self.pending_errors = []
            return ["error", op, f"deferred submit-path errors: {errs}"]
        if op == "batch":
            # one queue message carrying many fire-and-forget commands: the
            # parent coalesces submits per shard because the per-message
            # transport cost (queue wakeups + pipe round trips) dwarfs the
            # marginal bytes — see ProcessShardedModelStore._flush_outbox.
            # One poison item must not strand its batchmates: per-item
            # errors are deferred, the rest of the batch still lands.
            for raw in msg[1]:
                try:
                    self.handle(unpackb(raw))
                except BaseException as e:
                    self.pending_errors.append(
                        f"batch-item: {type(e).__name__}: {e}")
            return None
        if op == "sub":
            _, seq, key, params, meta_w, delta_w, _epoch = msg
            if not self._serves(key):
                return self._park(key, msg)
            if not self._is_replay_dup(int(seq)):
                self.records[key]["pending"].append(
                    (seq, params, meta_from_wire(meta_w),
                     delta_from_wire(delta_w)))
                self.held.add(int(seq))
            return None
        if op == "gsub":
            _, seq, params, meta_w, delta_w = msg
            if not self._is_replay_dup(int(seq)):
                self.gslice.append((seq, params, meta_from_wire(meta_w),
                                    delta_from_wire(delta_w)))
                self.held.add(int(seq))
            return None
        if op == "ssub":
            _, seq, key, round_id, client_id, masked, delta_w, _epoch = msg
            if not self._serves(key):
                return self._park(key, msg)
            if not self._is_replay_dup(int(seq)):
                bucket = self.records[key]["secure"].setdefault(
                    int(round_id), [])
                bucket.append((seq, client_id, masked,
                               delta_from_wire(delta_w)))
                self.held.add(int(seq))
            return None
        if op == "ensure":
            _, key, params, _epoch = msg
            if key in self.migrated:
                return self._park(key, msg)
            self._ensure(key, params)
            return None
        if op == "fetch":
            return self.fetch(msg[1], msg[2] if len(msg) > 2 else None)
        if op == "mirror":
            _, key, params, meta_w = msg
            if key in self.migrated:
                return None      # stale push that raced the fence: drop
            self._mirror(key, params, meta_w)
            return None
        if op == "mig_export":
            return self._mig_export(msg[1], int(msg[2]), int(msg[3]))
        if op == "mig_install":
            return self._mig_install(msg[1], int(msg[2]), msg[3])
        if op == "mig_redirects":
            return self._mig_redirects()
        if op == "drain":
            return self._drain_key(msg[1])
        if op == "drain_shard":
            out = []
            for key in self.records:
                r = self._drain_key(key)
                if r[0] == "error":
                    return r           # fold error fails the whole beat
                out.append(r[1:])
            return ["shard_drained", out]
        if op == "gmeta":
            # metadata snapshot of the global slice — the cheap half of the
            # cross-server merge (the parent plans over metas; params stay
            # here until greduce folds them into one partial)
            return ["gmetas", [[seq, meta_to_wire(m), delta_to_wire(d)]
                               for seq, _, m, d in self.gslice]]
        if op == "greduce":
            return self._greduce(msg[1])
        if op == "sdrain":
            _, key, round_id, expected_ids = msg
            return self._drain_secure(key, int(round_id), expected_ids)
        if op == "sync":
            # the sync_mirrors() barrier: ship params + accumulated acks
            # for every model with meta-only (provisional) acks outstanding
            out = []
            for key, rec in self.records.items():
                if not rec["unsynced"]:
                    continue
                acked, rec["unsynced"], rec["drains"] = rec["unsynced"], [], 0
                out.append([key, acked, rec["params"],
                            meta_to_wire(rec["meta"])])
            return ["synced", out]
        if op == "obsdump":
            # telemetry snapshot: the worker's metrics + event rings, with
            # its own wall/monotonic anchor so the parent can merge every
            # site onto one timeline (repro.obs.export)
            return ["obsdumped",
                    self.tel.dump() if self.tel is not None else None]
        if op == "ping":
            import jax

            return ["pong", self.idx, sorted(self.records),
                    jax.default_backend()]
        raise ValueError(f"unknown worker op {op!r}")

    # -------------------------------------------------------------- read path
    def fetch(self, key: str, held=None):
        """Serve one read-tier conditional fetch (wire v3).

        The ONLY worker entry point that is safe to call concurrently with
        the command session: it reads the record's published ``snap``
        tuple and the internally-locked wire cache, never the mutable fold
        state.  ``held`` is the client's ``[samples, epochs, round]``
        version or ``None``; the reply's ``result`` discriminator is
        ``FETCH_FULL`` / ``FETCH_NOT_MODIFIED`` / ``FETCH_DELTA``.
        A tombstoned key answers a redirect naming the new owner."""
        mig = self.migrated.get(key)
        if mig is not None:
            return ["redirect", key, mig[0], mig[1]]
        rec = self.records.get(key)
        snap = rec.get("snap") if rec is not None else None
        if snap is None:
            raise KeyError(f"shard {self.idx} does not serve {key!r}")
        params, meta_w = snap
        tel = self.tel
        t0 = clock.monotonic_ns() if tel is not None else 0
        kind, payload = serve_fetch(self.wire_cache, key, params, meta_w,
                                    held)
        if tel is not None:
            name = ("full", "not_modified", "delta")[kind]
            tel.metrics.counter(f"fetch_{name}").inc()
            tel.metrics.histogram("fetch_serve_ns").observe(
                clock.monotonic_ns() - t0)
            if payload is not None:
                tel.metrics.histogram("fetch_reply_bytes").observe(
                    len(payload))
        return ["fetched", key, kind, payload, meta_w]

    def _mirror(self, key: str, params, meta_w):
        """Replica state push: overwrite this server's copy of a model it
        mirrors for read fan-out.  Replicas never receive submits or
        drains — the shard owner folds, the parent pushes the folded
        mirror here, read sessions serve it."""
        self._ensure(key, params, meta_from_wire(meta_w))
        rec = self.records[key]
        rec["params"], rec["meta"] = params, meta_from_wire(meta_w)
        self._publish(rec)

    # -------------------------------------------------------------- migration
    def _mig_export(self, key: str, epoch: int, dst: int):
        """Ship one cluster's complete fold state to its new owner and
        tombstone the key (docs/ELASTICITY.md §3).  A ``None`` state means
        this worker no longer holds the record — it was respawned after
        the ring flipped, so its fresh seed excluded the key; the parent
        then completes the migration by reseeding the destination
        instead."""
        self.epoch = max(self.epoch, int(epoch))
        rec = self.records.pop(key, None)
        if rec is None:
            return ["mig_state", key, None]
        self.migrated[key] = (int(dst), int(epoch))
        state = {
            "params": rec["params"],
            "meta": meta_to_wire(rec["meta"]),
            "pending": [[seq, p, meta_to_wire(m), delta_to_wire(d)]
                        for seq, p, m, d in rec["pending"]],
            "secure": [[rid, [[seq, cid, masked, delta_to_wire(d)]
                              for seq, cid, masked, d in bucket]]
                       for rid, bucket in rec["secure"].items()],
            "unsynced": list(rec["unsynced"]),
            "drains": int(rec["drains"]),
        }
        shipped = {int(s) for s, _, _, _ in rec["pending"]}
        for bucket in rec["secure"].values():
            shipped.update(int(s) for s, _, _, _ in bucket)
        self.held.difference_update(shipped)
        return ["mig_state", key, state]

    def _mig_install(self, key: str, epoch: int, state):
        """Install a migrated cluster as the new owner.  Idempotent under
        the parent's exchange-retry: seqs the held-dedup set already has
        (a respawn's journal replay delivered them first) are skipped, and
        the params overwrite equals the parent-mirror seed the respawn
        used, so a second install changes nothing."""
        self.epoch = max(self.epoch, int(epoch))
        self.migrated.pop(key, None)
        params = state["params"]
        meta = meta_from_wire(state["meta"])
        self._ensure(key, params, meta)
        rec = self.records[key]
        rec["params"], rec["meta"] = params, meta
        self._publish(rec)
        n_shipped = 0
        for seq, p, m_w, d_w in state.get("pending", []):
            if int(seq) in self.held:
                continue
            rec["pending"].append((seq, p, meta_from_wire(m_w),
                                   delta_from_wire(d_w)))
            self.held.add(int(seq))
            n_shipped += 1
        for rid, bucket in state.get("secure", []):
            dst_bucket = rec["secure"].setdefault(int(rid), [])
            for seq, cid, masked, d_w in bucket:
                if int(seq) in self.held:
                    continue
                dst_bucket.append((seq, cid, masked, delta_from_wire(d_w)))
                self.held.add(int(seq))
                n_shipped += 1
        rec["unsynced"].extend(int(s) for s in state.get("unsynced", []))
        rec["drains"] = max(rec["drains"], int(state.get("drains", 0)))
        self._replay_parked(key)
        return ["mig_installed", key, n_shipped]

    def _replay_parked(self, key: str):
        """Re-dispatch messages parked for a key that just installed,
        in arrival order — after the shipped pending queue, preserving
        the submit FIFO across the migration."""
        mine, rest = [], []
        for k, raw in self.parked:
            (mine if k == key else rest).append((k, raw))
        self.parked = rest
        for _, raw in mine:
            self.handle(unpackb(raw))

    def _mig_redirects(self):
        """Hand back the raw messages parked for migrated-away keys so the
        parent re-delivers them to the new owner; parked messages for keys
        still migrating *in* stay parked."""
        out, keep = [], []
        for k, raw in self.parked:
            (out if k in self.migrated else keep).append((k, raw))
        self.parked = keep
        return ["redirected", [raw for _, raw in out]]

    # ----------------------------------------------------------------- drains
    def _drain_key(self, key: str):
        """Fold every pending update for one model, ``max_coalesce`` at a
        time — the worker-side twin of ``_drain_record_once`` loops.  On a
        fold error the popped batch is restored at the queue head so the
        journaled updates stay consistent with the worker's queue.

        Lazy mirror sync: only every ``sync_every``-th non-empty reply per
        model carries the folded params; the others ack with seq-stamped
        metadata (the parent keeps the entries journaled as
        folded-but-unsynced and marks its mirror dirty).  A params-carrying
        reply flushes ALL accumulated acks, so the parent's full ack and
        mirror swap stay one atomic step."""
        from repro.core.aggregation import coalesced_aggregate

        mig = self.migrated.get(key)
        if mig is not None:
            return ["redirect", key, mig[0], mig[1]]
        rec = self.records[key]
        tel = self.tel
        folded = fast = batches = 0
        acked: list[int] = []
        # staleness-at-fold telescoping: ``base + cum`` is the round the
        # model WOULD have reached folding strictly sequentially, so the
        # per-update observation is independent of drain chunk boundaries —
        # the cross-topology parity invariant (docs/OBSERVABILITY.md)
        base_round = rec["meta"].round
        cum_rounds = 0
        while rec["pending"]:
            take = min(len(rec["pending"]), self.max_coalesce)
            batch = [rec["pending"].popleft() for _ in range(take)]
            t0 = clock.monotonic_ns() if tel is not None else 0
            try:
                res = coalesced_aggregate(
                    rec["params"], rec["meta"],
                    [(p, m, d) for _, p, m, d in batch], self.agg_cfg)
            except BaseException as e:
                rec["pending"].extendleft(reversed(batch))
                return ["error", key, f"{type(e).__name__}: {e}"]
            if tel is not None:
                dur = clock.monotonic_ns() - t0
                tel.metrics.histogram(
                    f"drain_fold_ns_{self._route}").observe(dur)
                tel.metrics.histogram("coalesce_batch").observe(len(batch))
                stale = tel.metrics.histogram("staleness_at_fold")
                for _, _, m, d in batch:
                    stale.observe(max(0, base_round + cum_rounds - m.round))
                    cum_rounds += d.rounds
                tel.event("worker.fold", t0, dur, current_trace(),
                          {"key": key, "n": len(batch),
                           "seqs": [int(s) for s, _, _, _ in batch]})
            rec["params"], rec["meta"] = res.params, res.meta
            self._publish(rec)
            folded += res.n_folded
            fast += res.n_fast_path
            batches += 1
            acked.extend(seq for seq, _, _, _ in batch)
            self.held.difference_update(int(s) for s, _, _, _ in batch)
        if not folded:
            return ["drained", key, 0, 0, 0, [], None, None]
        rec["unsynced"].extend(acked)
        rec["drains"] += 1
        if self.sync_every > 1 and rec["drains"] < self.sync_every:
            return ["drained", key, folded, fast, batches, acked,
                    None, meta_to_wire(rec["meta"])]
        if tel is not None:
            # mirror-sync age: how many drain replies this params-carrying
            # reply had accumulated (1 = eager sync, ~sync_every when lazy)
            tel.metrics.histogram("mirror_sync_lag").observe(rec["drains"])
        full_acked, rec["unsynced"], rec["drains"] = rec["unsynced"], [], 0
        return ["drained", key, folded, fast, batches, full_acked,
                rec["params"], meta_to_wire(rec["meta"])]

    def _greduce(self, pairs):
        """Reduce this server's slice members to one convex partial.

        ``pairs`` is ``[[seq, weight], ...]`` — the planned telescoped
        coefficients (``plan_coalesce`` run parent-side over every server's
        metas) for exactly the seqs of the parent's gmeta snapshot.  The
        selected members leave the slice (newer arrivals stay for the next
        drain); the nonzero-weight survivors fold through the unchanged
        ``multi_aggregate``, whose internal normalization makes the result
        the convex partial ``sum_i (w_i / W) p_i`` with mass ``W = sum w_i``
        — the parent's mass-weighted merge of partials then reassembles the
        exact flat Algorithm-2 sum (same algebra as
        ``two_level_coalesced_aggregate``, distributed)."""
        from repro.core.aggregation import (
            chunked_convex_reduce,
            multi_aggregate,
        )

        want = {int(s): float(w) for s, w in pairs}
        keep = deque()
        take = []
        for item in self.gslice:
            (take if item[0] in want else keep).append(item)
        entries = [(p, want[seq]) for seq, p, _, _ in take
                   if want[seq] != 0.0]
        partial, mass = None, 0.0
        if entries:
            try:
                # arity-bounded exactly like the thread-sharded fold: every
                # fused sum stays <= max_coalesce wide, so the worker's jit
                # cache sees only the warm power-of-two buckets
                entries = chunked_convex_reduce(entries, self.max_coalesce,
                                                self.agg_cfg)
                partial = (entries[0][0] if len(entries) == 1 else
                           multi_aggregate([p for p, _ in entries],
                                           [m for _, m in entries],
                                           self.agg_cfg))
            except BaseException as e:
                return ["error", "greduce", f"{type(e).__name__}: {e}"]
            mass = float(sum(m for _, m in entries))
        self.gslice = keep
        self.held.difference_update(int(s) for s, _, _, _ in take)
        return ["gpartial", [seq for seq, _, _, _ in take], mass, partial]

    def _drain_secure(self, key: str, round_id: int, expected_ids):
        """Model-local secure full-round fold: pairwise masks cancel inside
        one fused sum that never leaves this worker; dropouts are recovered
        from the worker's own masker (seed reconstruction)."""
        from repro.core.aggregation import secure_coalesced_aggregate

        mig = self.migrated.get(key)
        if mig is not None:
            return ["redirect", key, mig[0], mig[1]]
        rec = self.records[key]
        batch = rec["secure"].pop(round_id, [])
        if not batch:
            return ["sdrained", key, 0, 0, [], None, None]
        t0 = clock.monotonic_ns() if self.tel is not None else 0
        try:
            submitted = {cid for _, cid, _, _ in batch}
            missing = sorted(set(expected_ids) - submitted)
            correction = None
            if missing:
                if self.masker is None:
                    raise RuntimeError(
                        "secure round has dropouts but no masker is attached "
                        "for seed reconstruction")
                correction = self.masker.reconstruct(
                    rec["params"], missing, sorted(submitted), round_id, key)
            res = secure_coalesced_aggregate(
                rec["params"], rec["meta"],
                [(masked, d) for _, _, masked, d in batch],
                self.agg_cfg, correction)
        except BaseException as e:
            rec["secure"][round_id] = batch + rec["secure"].get(round_id, [])
            return ["error", key, f"{type(e).__name__}: {e}"]
        if self.tel is not None:
            dur = clock.monotonic_ns() - t0
            self.tel.metrics.histogram("secure_round_ns").observe(dur)
            self.tel.event("worker.secure_fold", t0, dur, current_trace(),
                           {"key": key, "n": len(batch),
                            "missing": len(missing)})
        rec["params"], rec["meta"] = res.params, res.meta
        self._publish(rec)
        self.held.difference_update(int(s) for s, _, _, _ in batch)
        # secure replies always carry params (full-round folds are the sync
        # points of secure mode) and therefore flush any accumulated lazy
        # acks — the shipped params already include those earlier folds
        acked = rec["unsynced"] + [seq for seq, _, _, _ in batch]
        rec["unsynced"], rec["drains"] = [], 0
        return ["sdrained", key, len(batch), len(missing), acked,
                rec["params"], meta_to_wire(rec["meta"])]


def worker_main(shard_idx: int, cmd_q, rsp_q, seed_blob: bytes):
    """Spawned shard-server entry point: decode, dispatch, reply.  Errors on
    fire-and-forget commands must not produce unpaired replies (RPC pairing
    is positional), so they are deferred into ``pending_errors`` and become
    the error reply of the next replying command."""
    worker = ShardWorker(shard_idx, seed_blob)
    while True:
        raw = cmd_q.get()
        msg = unpackb(raw)
        op = msg[0]
        if op == "stop":
            rsp_q.put(packb(["stopped", shard_idx]))
            return
        try:
            reply = worker.handle(msg)
        except BaseException as e:
            reply = ["error", op, f"{type(e).__name__}: {e}"]
            if op not in REPLY_OPS:
                worker.pending_errors.append(f"{op}: {type(e).__name__}: {e}")
        if op in REPLY_OPS:
            rsp_q.put(packb(reply))


# ----------------------------------------------------------------- transports

class ProcessWorkerHandle(Transport):
    """Parent-side endpoint of one spawned shard server.

    ``cmd_q`` is SPSC in spirit: many parent threads may ``put`` (mp.Queue
    is thread-safe and buffers through its feeder thread, so submits never
    block on a busy worker), exactly one worker consumes.  Replying
    commands pair positionally, so callers serialize them per shard (the
    store's ``_ProcShard.rpc_lock``).
    """

    def __init__(self, shard_idx: int, seed_blob: bytes):
        self.idx = shard_idx
        self.spawns = 0
        # tx_bytes has two writer populations — fire-and-forget put()
        # callers (outbox flushers under the shard's journal lock) and
        # rpc() callers (under the shard's rpc lock) — so the increment
        # needs its own lock, like TcpWorkerHandle._send_lock (regression:
        # test_handle_tx_bytes_exact_under_concurrent_puts).  rx_bytes has
        # a single writer population (rpc-lock holders).
        self._send_lock = threading.Lock()
        self.tx_bytes = 0
        self.rx_bytes = 0
        self._ctx = mp.get_context("spawn")   # fork-after-jax is unsafe
        self._start(seed_blob)

    def _start(self, seed_blob: bytes):
        self.cmd_q = self._ctx.Queue()
        self.rsp_q = self._ctx.Queue()
        # the worker folds on the host CPU backend: the parent owns the chip
        self.proc = self._ctx.Process(
            target=host_worker_main,
            args=(self.idx, self.cmd_q, self.rsp_q, seed_blob),
            daemon=True, name=f"fedccl-shard-{self.idx}")
        self.proc.start()
        self.spawns += 1

    def put(self, raw: bytes):
        with self._send_lock:
            self.tx_bytes += len(raw)
        self.cmd_q.put(raw)

    def rpc(self, raw: bytes, timeout: float) -> bytes:
        """Send one replying command and await its reply.  Caller holds
        the shard's rpc lock."""
        with self._send_lock:
            self.tx_bytes += len(raw)
        self.cmd_q.put(raw)
        return self.rpc_recv(timeout)

    def rpc_recv(self, timeout: float) -> bytes:
        """Await one reply for an already-sent command (the scatter half of
        a scatter-gather drain sends first, gathers later), polling
        liveness: a dead worker raises ``WorkerUnavailable`` immediately
        instead of burning the whole deadline; a live-but-silent one raises
        ``WorkerTimeout`` at the deadline.  Caller holds the shard's rpc
        lock."""
        deadline = clock.monotonic() + timeout
        while True:
            remaining = deadline - clock.monotonic()
            try:
                reply = self.rsp_q.get(timeout=max(min(remaining, 0.2), 0.01))
                self.rx_bytes += len(reply)
                return reply
            except _queue.Empty:
                if not self.proc.is_alive():
                    raise WorkerUnavailable(
                        f"shard worker {self.idx} died "
                        f"(exitcode {self.proc.exitcode})") from None
                if remaining <= 0:
                    raise WorkerTimeout(
                        f"shard worker {self.idx} missed the {timeout:.1f}s "
                        f"drain deadline") from None

    def restart(self, seed_blob: bytes):
        """Replace a dead/stuck worker with a fresh one on fresh queues
        (stale buffered commands and unpaired replies die with the old
        pair).  Caller replays the journal right after."""
        self.discard()
        self._start(seed_blob)

    def alive(self) -> bool:
        return self.proc.is_alive()

    def kill(self):
        """SIGKILL — the crash-injection hook used by the respawn tests."""
        self.proc.kill()
        self.proc.join(5.0)

    def discard(self):
        """Tear down without ceremony: the worker is dead, stuck, or being
        replaced — SIGKILL works even on a SIGSTOPped process, where a
        polite SIGTERM would sit queued behind the stop forever."""
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(5.0)
        for q in (self.cmd_q, self.rsp_q):
            q.close()
            q.cancel_join_thread()

    def stop(self, timeout: float):
        """Graceful bounded shutdown; escalates to terminate/kill.
        Caller holds the shard's rpc lock."""
        try:
            reply = unpackb(self.rpc(packb(["stop"]), timeout))
            assert reply[0] == "stopped"
            self.proc.join(timeout)
        except WorkerUnavailable:
            pass
        finally:
            self.discard()


class InprocessWorkerHandle(Transport):
    """Deterministic in-process emulation of a shard server — the transport
    ``runtime_sim`` and the fast test matrix use.  Every message still round
    trips the wire codec and dispatches through the identical
    ``ShardWorker.handle``, so the only thing the emulation removes is the
    OS process (and with it, nondeterministic scheduling).  Byte counters
    count the serialized payloads, so reply-bandwidth tests (lazy mirror
    sync) run deterministically without sockets."""

    def __init__(self, shard_idx: int, seed_blob: bytes):
        self.idx = shard_idx
        self.spawns = 0
        # same two-writer-population story as ProcessWorkerHandle: put()
        # (journal-lock holders) and rpc() (rpc-lock holders) both bump
        # tx_bytes, so the counter gets its own lock
        self._send_lock = threading.Lock()
        self.tx_bytes = 0
        self.rx_bytes = 0
        # a real worker's command queue serializes every message; the
        # emulation dispatches inline, so this lock plays the queue's role
        # (ShardWorker itself is single-threaded by design)
        self._dispatch_lock = threading.Lock()
        self._start(seed_blob)

    def _start(self, seed_blob: bytes):
        self.worker = ShardWorker(self.idx, seed_blob)
        self._dead = False
        self.spawns += 1

    def put(self, raw: bytes):
        if self._dead:
            return                      # a dead worker's queue eats messages
        with self._send_lock:
            self.tx_bytes += len(raw)
        msg = unpackb(raw)
        try:
            with self._dispatch_lock:
                self.worker.handle(msg)
        except BaseException as e:      # deferred, like worker_main
            if msg[0] in REPLY_OPS:
                raise
            self.worker.pending_errors.append(
                f"{msg[0]}: {type(e).__name__}: {e}")

    def rpc_recv(self, timeout: float) -> bytes:
        raise NotImplementedError(
            "the in-process emulation dispatches inline; scatter-gather "
            "degenerates to sequential rpc() calls")

    def rpc(self, raw: bytes, timeout: float) -> bytes:
        """Dispatch one replying command inline.  Caller holds the shard's
        rpc lock (which is what keeps ``rx_bytes`` single-writer)."""
        if self._dead:
            raise WorkerUnavailable(
                f"shard worker {self.idx} died (in-process emulation)")
        with self._send_lock:
            self.tx_bytes += len(raw)
        msg = unpackb(raw)
        try:
            with self._dispatch_lock:
                reply = self.worker.handle(msg)
        except BaseException as e:      # mirror worker_main's error envelope
            reply = ["error", msg[0], f"{type(e).__name__}: {e}"]
        out = packb(reply)
        self.rx_bytes += len(out)
        return out

    def restart(self, seed_blob: bytes):
        self._start(seed_blob)

    def alive(self) -> bool:
        return not self._dead

    def kill(self):
        self._dead = True
        self.worker = None

    def discard(self):
        self.kill()

    def stop(self, timeout: float):
        self.kill()

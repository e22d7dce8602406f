"""FedCCL facade: wires clustering, store, protocol, continual learning and

a runtime into one object — the library's main entry point.

    fed = FedCCL(FedCCLConfig(...), init_params, train_fn)
    fed.setup(client_specs)          # pre-training DBSCAN clustering
    fed.run(rounds=5)                # async training (simulated or threaded)
    keys, params = fed.join(new_spec)  # Predict & Evolve for a new client
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from repro.core.aggregation import AggregationConfig
from repro.core.clustering import IncrementalDBSCAN
from repro.core.fetch import FetchClient
from repro.core.predict_evolve import ClusterSpace, PredictEvolve
from repro.core.protocol import Client, ClientSpec
from repro.core.runtime_sim import AsyncSimRuntime
from repro.core.runtime_threaded import AsyncThreadedRuntime
from repro.core.store import (
    ModelStore,
    ProcessShardedModelStore,
    ShardedModelStore,
)
from repro.obs.export import metrics_json, prometheus_text, write_perfetto
from repro.obs.record import Telemetry, maybe_span
from repro.privacy.accountant import RDPAccountant
from repro.privacy.dp import DPConfig, DPPrivatizer
from repro.privacy.secure_agg import PairwiseMasker


@dataclass(frozen=True)
class ClusterSpaceConfig:
    name: str                       # must match a static_features key
    eps: float
    min_samples: int = 3
    metric: str = "euclidean"


@dataclass(frozen=True)
class FedCCLConfig:
    spaces: tuple = (
        ClusterSpaceConfig("loc", eps=150.0, min_samples=3, metric="haversine"),
        ClusterSpaceConfig("ori", eps=25.0, min_samples=3, metric="cyclic"),
    )
    ewc_lambda: float = 0.0          # continual-learning anchor strength
    runtime: str = "sim"             # "sim" | "threaded"
    seed: int = 0
    dropout_prob: float = 0.0        # client-unavailability resilience knob
    use_pallas_agg: bool = False
    batch_aggregation: bool = False  # coalescing server path (queue + drain)
    max_coalesce: int = 16           # max queued updates folded per drain
    # server sharding: 0 = single ModelStore; K >= 1 = ShardedModelStore with
    # K per-cluster shards (per-shard drain workers in the threaded runtime,
    # two-level global fold — see repro.core.store.ShardedModelStore)
    server_shards: int = 0
    # multi-process federation server: K >= 1 promotes each shard to a
    # worker *process* (ProcessShardedModelStore — submits cross per-shard
    # msgpack queues, drains fold off-GIL in the workers, the global model
    # merges two-level in the parent).  Takes precedence over server_shards.
    # The sim runtime uses the deterministic in-process emulation; the
    # threaded runtime spawns real workers with crash detection + respawn.
    server_processes: int = 0
    # multi-host federation server: "host:port" addresses of standalone
    # shard servers (repro.launch.shard_server) — one worker per entry,
    # reached over the framed-msgpack TCP transport (docs/WIRE_PROTOCOL.md)
    # instead of spawning local processes.  Takes precedence over
    # server_processes/server_shards; len(server_hosts) fixes the shard
    # count.  Crash recovery carries over: a lost connection reconnects,
    # re-seeds and replays the journal (idempotent by update seq).
    # Read replicas: an entry may list extra addresses separated by "|"
    # ("owner:9701|replica:9711") — the first address owns the shard
    # (submits/drains), the rest mirror it for read fan-out (the parent
    # pushes folded params; fetch clients round-robin across all).
    server_hosts: tuple = ()
    # read tier: serve model fetches (FedCCL.model_for / fetcher.fetch)
    # from the shard servers over read-only TCP sessions instead of the
    # parent mirrors — seq-conditional (not-modified acks and compressed
    # deltas against the client's held version), with automatic parent
    # fallback for the global tier, non-TCP topologies, and unreachable
    # servers.  See docs/ARCHITECTURE.md (read tier) and
    # docs/WIRE_PROTOCOL.md §4.7.
    fetch_from_workers: bool = False
    # lazy mirror sync (process/TCP stores): workers ship full params only
    # every Nth drain reply per model and ack with seq-stamped metadata
    # otherwise — cuts reply bandwidth ~N-fold on the drain path.  Reads,
    # checkpoints and shutdown re-sync dirty mirrors through the
    # store.sync_mirrors() barrier, so served snapshots are never stale.
    # 1 = every reply ships params (the eager default).
    mirror_sync_every: int = 1
    # ---- elastic membership (docs/ELASTICITY.md) --------------------------
    # virtual nodes per shard on the consistent-hash ownership ring the
    # sharded/process/TCP stores route cluster keys with.  More vnodes =
    # smoother key balance across shards; the ring points are stable
    # crc32 hashes, so placement never depends on PYTHONHASHSEED.
    ring_vnodes: int = 64
    # automatic rebalance policy for FedCCL.rebalance(): None = manual
    # only (FedCCL.migrate_cluster); "load" migrates the hottest cluster
    # off the most-enqueued shard onto the least-enqueued one whenever
    # the hot shard carries more than rebalance_hot_ratio times the cold
    # shard's submits (per-shard agg_stats load).
    rebalance_policy: str | None = None
    # hot/cold submit-count ratio that triggers a "load" rebalance; at or
    # below the threshold rebalance() is a no-op
    rebalance_hot_ratio: float = 2.0
    # bounded drain deadline: worker-reply waits in the process store and
    # drain-worker joins in the threaded runtime; expiries surface as
    # agg_stats()["drain_timeouts"] instead of silent partial drains
    # (per-worker attribution in agg_stats()["shard_drain_timeouts"] for
    # the process/TCP topologies)
    drain_timeout_s: float = 30.0
    # ---- privacy subsystem (repro.privacy) --------------------------------
    dp_clip: float | None = None  # L2 clip of update deltas; None = DP off
    dp_noise_multiplier: float = 1.0 # noise std = multiplier * dp_clip
    secure_agg: bool = False         # pairwise-mask secure aggregation
    target_delta: float = 1e-5       # delta for (epsilon, delta) reporting
    # pair-mask std; 0.0 = unmasked parity baseline.  Must be set on the
    # order of n_samples * dp_clip to actually hide the weighted deltas —
    # see the magnitude caveat in repro.privacy.secure_agg
    secure_mask_scale: float = 1.0
    # ---- telemetry (repro.obs) --------------------------------------------
    # True wires a Telemetry sink through the store (and, for the
    # process/TCP topologies, into every worker): submit/enqueue/fold spans
    # in per-thread ring buffers plus log-bucketed latency, queue-depth and
    # staleness histograms, read back via FedCCL.metrics_report() and
    # write_trace() — see docs/OBSERVABILITY.md.  Off = zero-cost (stores
    # hold None and hot paths pay one attribute check).
    telemetry: bool = False
    # trace-sample every Nth submit: 1 = every submit gets a cross-process
    # span chain; larger N thins the flow arrows (metrics and events are
    # always recorded when telemetry is on)
    trace_sample_n: int = 1


class FedCCL:
    def __init__(self, cfg: FedCCLConfig, init_params, train_fn):
        self.cfg = cfg
        self.train_fn = train_fn
        self.masker = (PairwiseMasker(seed=cfg.seed,
                                      mask_scale=cfg.secure_mask_scale)
                       if cfg.secure_agg else None)
        self.accountant = (RDPAccountant(target_delta=cfg.target_delta)
                           if cfg.dp_clip is not None else None)
        agg_cfg = AggregationConfig(use_pallas=cfg.use_pallas_agg)
        # every span is also a profiler annotation, on the device trace's
        # clock; the facade reaches the sink through self.store alone
        tel = (Telemetry(sample_n=cfg.trace_sample_n,
                         annotate=jax.profiler.TraceAnnotation)
               if cfg.telemetry else None)
        if cfg.server_hosts:
            self.store = ProcessShardedModelStore(
                init_params, agg_cfg=agg_cfg,
                server_hosts=list(cfg.server_hosts),
                batch_aggregation=cfg.batch_aggregation,
                max_coalesce=cfg.max_coalesce, masker=self.masker,
                drain_timeout_s=cfg.drain_timeout_s,
                mirror_sync_every=cfg.mirror_sync_every,
                ring_vnodes=cfg.ring_vnodes, telemetry=tel)
        elif cfg.server_processes > 0:
            self.store = ProcessShardedModelStore(
                init_params, agg_cfg=agg_cfg, n_shards=cfg.server_processes,
                batch_aggregation=cfg.batch_aggregation,
                max_coalesce=cfg.max_coalesce, masker=self.masker,
                drain_timeout_s=cfg.drain_timeout_s,
                mirror_sync_every=cfg.mirror_sync_every,
                ring_vnodes=cfg.ring_vnodes,
                inprocess=(cfg.runtime == "sim"), telemetry=tel)
        elif cfg.server_shards > 0:
            self.store = ShardedModelStore(
                init_params, agg_cfg=agg_cfg, n_shards=cfg.server_shards,
                batch_aggregation=cfg.batch_aggregation,
                max_coalesce=cfg.max_coalesce, masker=self.masker,
                drain_timeout_s=cfg.drain_timeout_s,
                ring_vnodes=cfg.ring_vnodes, telemetry=tel)
        else:
            self.store = ModelStore(
                init_params, agg_cfg=agg_cfg,
                batch_aggregation=cfg.batch_aggregation,
                max_coalesce=cfg.max_coalesce, masker=self.masker,
                drain_timeout_s=cfg.drain_timeout_s, telemetry=tel)
        self.spaces = [
            ClusterSpace(s.name, IncrementalDBSCAN(s.eps, s.min_samples, s.metric))
            for s in cfg.spaces]
        self.pe = PredictEvolve(self.spaces, self.store)
        self.clients: list[Client] = []
        # client-id index for model_for: registration keeps it in sync, so
        # serving stays O(1) in fleet size (the list is the ordered public
        # view; the dict is the lookup path)
        self._clients_by_id: dict[str, Client] = {}
        self._init_params = init_params
        self._runtime = None
        # read tier (cfg.fetch_from_workers): a FetchClient serves
        # model_for/fetch worker-side when the store exposes TCP endpoints,
        # parent-side (with the conditional wire cache) otherwise
        self.fetcher = (FetchClient(self.store, telemetry=tel)
                        if cfg.fetch_from_workers else None)

    def _make_privatizer(self, client_id: str, index: int):
        if self.cfg.dp_clip is None:
            return None
        return DPPrivatizer(
            DPConfig(clip=self.cfg.dp_clip,
                     noise_multiplier=self.cfg.dp_noise_multiplier,
                     use_pallas=self.cfg.use_pallas_agg),
            client_id=client_id, seed=self.cfg.seed + 2000 + index,
            accountant=self.accountant)

    # ----------------------------------------------------------------- setup
    def setup(self, specs: list[ClientSpec]) -> dict[str, list[str]]:
        """Registers the first clients, each local model seeded with the
        initial parameters, which the facade then lets go: a large model's
        initial copy is freed once every tier has moved on from it.  Runs
        once; later clients come in through ``join``."""
        if self._init_params is None:
            raise RuntimeError("setup runs once; later clients join through "
                               "join()")
        assignments = self.pe.bootstrap(specs)
        for i, spec in enumerate(specs):
            c = Client(spec=spec,
                       cluster_keys=assignments[spec.client_id],
                       train_fn=self.train_fn,
                       ewc_lambda=self.cfg.ewc_lambda,
                       rng=np.random.default_rng(self.cfg.seed + 1000 + i),
                       privatizer=self._make_privatizer(spec.client_id, i))
            c.local_params = self._init_params
            self.clients.append(c)
            self._clients_by_id[spec.client_id] = c
        self._init_params = None
        return assignments

    # ------------------------------------------------------------------- run
    def run(self, rounds: int = 1):
        if self.cfg.runtime == "threaded":
            rt = AsyncThreadedRuntime(self.clients, self.store, rounds)
            rt.run()
            self._runtime = rt
            return self.store.agg_stats()
        rt = AsyncSimRuntime(self.clients, self.store, seed=self.cfg.seed,
                             dropout_prob=self.cfg.dropout_prob)
        rt.run(rounds)
        self._runtime = rt
        return rt.stats()

    def shutdown(self):
        """Release server resources: a process-sharded store stops its
        worker processes with a bounded join (no-op for in-thread stores).
        Model state stays readable — the parent keeps authoritative
        mirrors of every tier."""
        if self.fetcher is not None:
            self.fetcher.close()
        close = getattr(self.store, "close", None)
        if close is not None:
            close()

    # ------------------------------------------------- elastic membership
    def migrate_cluster(self, cluster_key: str, dst_shard: int) -> int:
        """Manually move one cluster model to another shard/worker (live —
        no restart, no lost updates; docs/ELASTICITY.md).  Returns the new
        ownership epoch."""
        migrate = getattr(self.store, "migrate_cluster", None)
        if migrate is None:
            raise RuntimeError(
                "this topology's store has no migrate_cluster; pick a "
                "sharded topology (server_shards / server_processes / "
                "server_hosts)")
        return migrate(cluster_key, dst_shard)

    def rebalance(self) -> list[tuple[str, int, int]]:
        """Apply ``FedCCLConfig.rebalance_policy`` once; returns the
        migrations performed as ``(cluster_key, dst_shard, epoch)``.

        Policy ``"load"``: read per-shard submit counts from
        ``agg_stats()["shard_enqueued"]``; when the hottest shard carries
        more than ``rebalance_hot_ratio`` times the coldest shard's
        submits, migrate the hot shard's deepest-queued cluster to the
        cold shard.  ``None`` (the default) never migrates — rebalancing
        stays a manual ``migrate_cluster`` call."""
        policy = self.cfg.rebalance_policy
        if policy is None:
            return []
        if policy != "load":
            raise ValueError(f"unknown rebalance_policy {policy!r} "
                             "(expected None or 'load')")
        stats = self.store.agg_stats()
        enqueued = stats.get("shard_enqueued")
        if not enqueued or len(enqueued) < 2:
            return []
        hot = max(range(len(enqueued)), key=lambda i: enqueued[i])
        cold = min(range(len(enqueued)), key=lambda i: enqueued[i])
        if hot == cold or (enqueued[hot] <=
                           self.cfg.rebalance_hot_ratio
                           * max(enqueued[cold], 1)):
            return []
        keys = self.store.shard_cluster_keys(hot)
        if not keys:
            return []
        key = max(keys, key=lambda k: self.store.pending_depth("cluster", k))
        epoch = self.store.migrate_cluster(key, cold)
        return [(key, cold, epoch)]

    # ----------------------------------------------------- Predict & Evolve
    def join(self, spec: ClientSpec) -> tuple[list[str], object]:
        """New client: immediate specialized model, then becomes participant.
        A ``join`` span with telemetry on (``join.cluster`` and
        ``join.model`` inside)."""
        with maybe_span(self.store.telemetry, "join",
                        args={"client": spec.client_id}):
            keys, params = self.pe.join(spec)
            idx = len(self.clients)
            c = Client(spec=spec, cluster_keys=keys, train_fn=self.train_fn,
                       ewc_lambda=self.cfg.ewc_lambda,
                       rng=np.random.default_rng(self.cfg.seed + 5000 + idx),
                       privatizer=self._make_privatizer(spec.client_id,
                                                        3000 + idx))
            c.local_params = params
            self.clients.append(c)
            self._clients_by_id[spec.client_id] = c
        return keys, params

    # --------------------------------------------------------------- privacy
    def privacy_report(self) -> dict:
        """(epsilon, delta) budgets and secure-aggregation round accounting
        for the run so far (see ``repro.privacy``).

        Topology-independent by construction: the report reads the store's
        aggregate secure counters, which every flavor maintains identically
        — on the sharded store each secure round folds on the model's
        owning shard, and on the process/TCP stores it folds **inside the
        owning worker** (masks and dropout seed-reconstruction never cross
        the wire; only the counted totals come back in drain replies).
        ``secure_agg.rounds`` therefore counts full-round folds across all
        workers, and ``dropout_recoveries`` the worker-local seed
        reconstructions.  Pair with ``store.agg_stats()`` for the
        operational side (per-shard ``drain_timeouts``, respawns, wire
        bytes) — see docs/OPERATIONS.md."""
        report = {
            "dp": {
                "enabled": self.cfg.dp_clip is not None,
                "clip": self.cfg.dp_clip,
                "noise_multiplier": self.cfg.dp_noise_multiplier,
                "target_delta": self.cfg.target_delta,
            },
            "secure_agg": {
                "enabled": self.cfg.secure_agg,
                "rounds": self.store.n_secure_rounds,
                "dropout_recoveries": self.store.n_secure_recoveries,
            },
        }
        if self.accountant is not None:
            report["per_client"] = self.accountant.client_report()
            report["per_model"] = self.accountant.model_report()
        return report

    # ------------------------------------------------------------ telemetry
    def metrics_report(self, fmt: str = "json"):
        """Merged cross-site telemetry (``FedCCLConfig.telemetry=True``).

        ``fmt="json"`` returns a dict — counters, gauges, and
        p50/p95/p99/mean/max summaries per log-bucketed histogram
        (``submit_latency_ns``, ``drain_fold_ns_host``/``_pallas``,
        ``queue_depth``, ``staleness_at_fold``, ...).  ``fmt="prometheus"``
        returns the text exposition page for a scrape endpoint.  Sites are
        the parent plus every worker (pulled over the wire via ``obsdump``);
        metric names/units are catalogued in docs/OBSERVABILITY.md."""
        dump = self.store.telemetry_dump()
        if fmt == "prometheus":
            return prometheus_text(dump)
        if fmt != "json":
            raise ValueError(f"unknown metrics format {fmt!r} "
                             "(expected 'json' or 'prometheus')")
        return metrics_json(dump)

    def write_trace(self, path) -> None:
        """Write the run's span chains as Chrome trace-event JSON —
        loadable in Perfetto (ui.perfetto.dev) or chrome://tracing.  One
        Perfetto process per telemetry site; sampled submits draw flow
        arrows across the parent -> worker process/TCP boundary."""
        write_perfetto(self.store.telemetry_dump(), path)

    # ------------------------------------------------------------- inference
    def _serve_params(self, level: str, key: str | None = None):
        """One served read: through the fetch client when the read tier is
        on (worker-served where the topology allows, conditional either
        way), else a parent-mirror snapshot."""
        if self.fetcher is not None:
            return self.fetcher.fetch(level, key)[0]
        return self.store.params(level, key)

    def model_for(self, client_id: str, level: str = "auto"):
        """The parameters served to one client and the tier they came from.
        With telemetry on, a ``serve.read`` span (tier choice and store
        read) for the profiler and the ``serve_read_ns`` histogram."""
        with maybe_span(self.store.telemetry, "serve.read", ring=False,
                        hist="serve_read_ns"):
            client = self._clients_by_id.get(client_id)
            if client is None:
                known = sorted(self._clients_by_id)
                shown = ", ".join(repr(k) for k in known[:8])
                if len(known) > 8:
                    shown += f", ... ({len(known)} clients total)"
                raise KeyError(f"unknown client_id {client_id!r}; "
                               f"known clients: [{shown}]")
            if level == "local":
                return client.local_params, "local"
            if level == "global":
                return self._serve_params("global"), "global"
            if level.startswith("cluster"):
                if ":" in level:
                    key = level.split(":", 1)[1]
                elif client.cluster_keys:
                    key = client.cluster_keys[0]
                else:
                    # noise client (DBSCAN label -1): no cluster model exists,
                    # fall back to the global tier instead of crashing
                    return self._serve_params("global"), "global"
                return self._serve_params("cluster", key), f"cluster:{key}"
            return self.pe.choose_inference_model(
                client, serve=self._serve_params)

"""Three-tier store, Algorithm 1 protocol, and both async runtimes."""


import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import ModelMeta, UpdateDelta
from repro.core.fedccl import ClusterSpaceConfig, FedCCL, FedCCLConfig
from repro.core.protocol import ClientSpec
from repro.core.store import ModelStore


def scalar_train_fn(params, dataset, rng, anchor):
    target, n = dataset
    w = params["w"]
    for _ in range(3):
        g = w - target
        if anchor is not None:
            g = g + anchor.lam * (w - anchor.anchor["w"])
        w = w - 0.3 * g
    return {"w": w}, n, 3


def make_fed(runtime="sim", n_per_group=3, rounds=3, seed=0):
    cfg = FedCCLConfig(
        spaces=(ClusterSpaceConfig("loc", eps=100.0, min_samples=2,
                                   metric="haversine"),),
        ewc_lambda=0.05, runtime=runtime, seed=seed)
    fed = FedCCL(cfg, {"w": jnp.zeros(())}, scalar_train_fn)
    rng = np.random.default_rng(seed)
    specs = []
    for i in range(n_per_group):
        specs.append(ClientSpec(
            f"a{i}", {"loc": np.array([48.2 + rng.normal(0, .2),
                                       16.4 + rng.normal(0, .2)])},
            (+1.0, 100), speed=rng.uniform(.5, 2)))
    for i in range(n_per_group):
        specs.append(ClientSpec(
            f"b{i}", {"loc": np.array([52.5 + rng.normal(0, .2),
                                       13.4 + rng.normal(0, .2)])},
            (-1.0, 100), speed=rng.uniform(.5, 2)))
    fed.setup(specs)
    return fed


def test_store_levels_and_locking():
    store = ModelStore({"w": jnp.zeros(())}, cluster_keys=["c0"])
    p, m = store.request_model("global")
    assert m.round == 0
    ok = store.handle_model_update("cluster", "c0", {"w": jnp.ones(())},
                                   ModelMeta(10, 1, 1), UpdateDelta(10, 1, 1))
    assert ok
    assert store.meta("cluster", "c0").round == 1
    # non-blocking update while lock held -> rejected
    rec = store._records["c0"]
    rec.lock.acquire()
    ok = store.handle_model_update("cluster", "c0", {"w": jnp.ones(())},
                                   ModelMeta(10, 1, 2), UpdateDelta(10, 1, 1),
                                   blocking=False)
    rec.lock.release()
    assert not ok and store.n_lock_waits == 1


def test_clusters_specialize_and_global_averages():
    fed = make_fed(rounds=3)
    fed.run(rounds=4)
    keys = sorted(fed.store.keys())
    vals = [float(fed.store.params("cluster", k)["w"]) for k in keys]
    assert len(keys) == 2
    assert max(vals) > 0.8 and min(vals) < -0.8        # specialized
    # global averages the two opposing groups (both at +-1): clearly inside
    assert abs(float(fed.store.params("global")["w"])) < 0.6


def test_sim_runtime_is_deterministic():
    r1 = make_fed(seed=7)
    r2 = make_fed(seed=7)
    s1 = r1.run(rounds=3)
    s2 = r2.run(rounds=3)
    assert s1 == s2
    assert float(r1.store.params("global")["w"]) == \
        float(r2.store.params("global")["w"])


def test_sim_staleness_occurs():
    fed = make_fed()
    stats = fed.run(rounds=4)
    assert stats["mean_staleness"] > 0     # true async interleaving
    assert 0 < stats["fast_path_frac"] < 1


def test_sim_runtime_frees_a_submitted_job(monkeypatch):
    """A submit event holds a job's fetched snapshot and trained update only
    until the update is submitted: at a client's global training (the last
    of its event) every model an earlier training was given or made is
    dead, or still held by the store, a client's local tier or a job not
    yet trained."""
    import weakref

    from repro.core import fedccl as fedccl_mod
    from repro.core.runtime_sim import AsyncSimRuntime

    runtimes = []

    class Recording(AsyncSimRuntime):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runtimes.append(self)

    monkeypatch.setattr(fedccl_mod, "AsyncSimRuntime", Recording)
    made, calls, extra, checked = [], {}, [], [0]
    fed = None

    def train_fn(params, dataset, rng, anchor):
        k = calls[id(dataset)] = calls.get(id(dataset), 0) + 1
        if k % 3 == 0:          # local, cluster, global: the global job
            store = fed.store
            held = [store.params("global")["w"]]
            held += [store.params("cluster", c)["w"] for c in store.keys()]
            held += [c.local_params["w"] for c in fed.clients]
            held += [job[2]["w"] for ev in runtimes[-1]._heap
                     for job in (ev.payload or ())]
            held.append(params["w"])
            alive = [r() for r in made if r() is not None]
            extra.append(sum(not any(a is h for h in held) for a in alive))
            checked[0] += 1
        out = {"w": params["w"] + 1.0}
        made.extend([weakref.ref(params["w"]), weakref.ref(out["w"])])
        return out, 10, 1

    cfg = FedCCLConfig(
        spaces=(ClusterSpaceConfig("loc", eps=100.0, min_samples=2,
                                   metric="haversine"),),
        ewc_lambda=0.05, runtime="sim", seed=3)
    fed = FedCCL(cfg, {"w": jnp.zeros(())}, train_fn)
    fed.setup([ClientSpec(f"s{i}", {"loc": np.array([48.2 + 0.01 * i,
                                                     16.4])},
                          (i,), speed=1.0 + 0.3 * i) for i in range(4)])
    fed.run(rounds=3)
    assert checked[0] == 12
    assert extra == [0] * 12


def test_setup_lets_the_initial_parameters_go():
    """setup seeds every local model with the initial parameters and keeps
    no reference of its own: once each tier has moved on they are freed.
    It runs once; a second call says how clients come in later."""
    import weakref

    fed = make_fed()
    init = weakref.ref(fed.clients[0].local_params["w"])
    assert init() is not None
    fed.run(rounds=1)
    assert init() is None
    with pytest.raises(RuntimeError, match="join"):
        fed.setup([ClientSpec("late", {"loc": np.array([48.2, 16.4])},
                              (1.0, 10))])


def test_dropout_resilience():
    cfg = FedCCLConfig(
        spaces=(ClusterSpaceConfig("loc", eps=100.0, min_samples=2,
                                   metric="haversine"),),
        seed=3, dropout_prob=0.3)
    fed = FedCCL(cfg, {"w": jnp.zeros(())}, scalar_train_fn)
    rng = np.random.default_rng(3)
    fed.setup([ClientSpec(f"c{i}", {"loc": np.array([48.2 + rng.normal(0, .1),
                                                     16.4 + rng.normal(0, .1)])},
                          (1.0, 50)) for i in range(4)])
    stats = fed.run(rounds=3)
    # all clients eventually complete their rounds despite dropouts
    assert stats["updates"] >= 4 * 3 * 2   # (cluster+global) per round


def test_threaded_runtime_consistency():
    fed = make_fed(runtime="threaded")
    fed.run(rounds=2)
    total_rounds = fed.store.meta("global").round
    assert total_rounds == 6 * 2           # every update serialized by lock
    samples = fed.store.meta("global").samples_learned
    assert samples == 6 * 2 * 100          # n_clients * rounds * delta(n=100)


def test_model_for_noise_client_falls_back_to_global():
    fed = make_fed()
    fed.run(rounds=2)
    # outlier joins as DBSCAN noise: cluster_keys == []
    keys, _ = fed.join(ClientSpec(
        "outlier", {"loc": np.array([0.0, 0.0])}, (0.0, 10)))
    assert keys == []
    params, tag = fed.model_for("outlier", level="cluster")
    assert tag == "global"
    np.testing.assert_allclose(np.asarray(params["w"]),
                               np.asarray(fed.store.params("global")["w"]))
    # explicit key still works for noise clients
    any_key = fed.store.keys()[0]
    _, tag = fed.model_for("outlier", level=f"cluster:{any_key}")
    assert tag == f"cluster:{any_key}"


def test_model_for_unknown_client_raises_keyerror():
    fed = make_fed()
    with pytest.raises(KeyError, match="nope"):
        fed.model_for("nope")


class _NoScan(list):
    """A clients list that detonates on iteration/containment — proof that
    the serving path uses the id index, not an O(N) scan."""

    def __iter__(self):
        raise AssertionError("model_for scanned the clients list")

    def __contains__(self, item):
        raise AssertionError("model_for scanned the clients list")


def test_model_for_is_indexed_not_scanned():
    """Regression: ``model_for`` used to linear-scan ``self.clients`` per
    call — O(N) per inference request.  It must go through the client-id
    dict (kept in sync by ``setup``/``join``), so serving stays O(1)."""
    fed = make_fed()
    fed.run(rounds=1)
    orig = fed.clients
    fed.clients = _NoScan(orig)
    params, tag = fed.model_for("a0", level="local")
    assert tag == "local" and params is not None
    _, tag = fed.model_for("a0", level="global")
    assert tag == "global"
    # join() keeps the index in sync too
    fed.clients = orig
    fed.join(ClientSpec("late", {"loc": np.array([48.2, 16.4])}, (1.0, 10)))
    fed.clients = _NoScan(orig)
    assert fed.model_for("late", level="local")[1] == "local"


def test_model_for_auto_routes_through_read_tier():
    """Regression: the default ``level="auto"`` path delegated to
    ``PredictEvolve.choose_inference_model``, which read the store
    directly — bypassing the fetch client the explicit levels use.  With
    the read tier on, every served level must go through the fetcher."""
    fed = make_fed()
    fed.run(rounds=1)
    calls = []

    def spy_serve(level, key=None):
        calls.append((level, key))
        return fed.store.params(level, key)

    fed._serve_params = spy_serve
    _, tag = fed.model_for("a0")                 # auto -> first cluster
    assert tag.startswith("cluster:")
    assert calls == [("cluster", tag.split(":", 1)[1])]
    _, tag = fed.model_for("a0", level="global")
    assert tag == "global" and calls[-1] == ("global", None)


def test_model_for_unknown_client_error_is_truncated():
    """Regression: the KeyError used to enumerate the ENTIRE fleet in its
    message — megabytes of text at realistic fleet sizes.  It must show a
    bounded prefix plus the total count."""
    fed = make_fed(n_per_group=10)          # 20 clients
    with pytest.raises(KeyError) as ei:
        fed.model_for("nope")
    msg = str(ei.value)
    assert "20 clients total" in msg
    assert msg.count("'a") + msg.count("'b") <= 8
    assert "'a0'" in msg                    # still actionable


def test_predict_evolve_join():
    fed = make_fed()
    fed.run(rounds=3)
    keys, params = fed.join(ClientSpec(
        "new", {"loc": np.array([52.55, 13.45])}, (-1.0, 50)))
    assert keys and keys[0].startswith("loc:")
    # immediately specialized: matches its cluster's sign
    assert float(params["w"]) < -0.5
    # outlier joins as noise -> global model
    keys2, params2 = fed.join(ClientSpec(
        "outlier", {"loc": np.array([0.0, 0.0])}, (0.0, 10)))
    assert keys2 == []


def test_coalesce_factor_locked_and_consistent():
    """``coalesce_factor()`` takes ``_drain_lock`` so the ratio comes from
    one consistent (drained, batches) pair, and ``agg_stats()`` — which
    already holds the non-reentrant lock — computes the same ratio inline
    instead of deadlocking on a nested ``coalesce_factor()`` call
    (fedlint FED101 fallout; see docs/INVARIANTS.md)."""
    store = ModelStore({"w": jnp.zeros(())}, cluster_keys=["c0"],
                       batch_aggregation=True, max_coalesce=8)
    for i in range(6):
        store.enqueue_update("cluster", "c0", {"w": jnp.ones(())},
                             ModelMeta(10, 1, i + 1), UpdateDelta(10, 1, 1))
    assert store.drain("cluster", "c0") == 6
    assert store.coalesce_factor() == pytest.approx(6.0)

    # run agg_stats on a thread so a regression to a nested
    # coalesce_factor() call (self-deadlock on the non-reentrant
    # _drain_lock) fails the test instead of hanging the suite
    out = {}
    t = threading.Thread(target=lambda: out.update(store.agg_stats()),
                         daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "agg_stats() deadlocked on _drain_lock"
    assert out["coalesce_factor"] == pytest.approx(store.coalesce_factor())


def test_counter_properties_consistent_under_concurrency():
    """The aggregate counter properties read the drain half under
    ``_drain_lock`` and every submit sink through its locked
    ``snapshot()`` tuple — never a bare mid-increment attribute read
    (fedlint FED101 fallout).  Concurrent readers must observe
    monotonically non-decreasing totals and the exact final count."""
    store = ModelStore({"w": jnp.zeros(())}, cluster_keys=["c0"])
    n_writers, per_writer = 4, 25
    stop = threading.Event()
    errors = []

    def reader():
        last = -1
        while not stop.is_set():
            n = store.n_updates
            if n < last:
                errors.append(f"n_updates regressed: {n} < {last}")
                return
            last = n
            # companion counters must stay readable mid-churn (their
            # values race n_updates, so only the read itself is asserted)
            _ = store.n_fast_path

    def writer():
        for _ in range(per_writer):
            store.handle_model_update(
                "cluster", "c0", {"w": jnp.ones(())},
                ModelMeta(10, 1, 1), UpdateDelta(10, 1, 1))

    readers = [threading.Thread(target=reader) for _ in range(2)]
    writers = [threading.Thread(target=writer) for _ in range(n_writers)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert errors == []
    assert store.n_updates == n_writers * per_writer
    assert store.n_fast_path <= store.n_updates
    assert store.n_lock_waits == 0      # blocking submits never bail

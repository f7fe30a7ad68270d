"""Telemetry layer tests: registry units, merge semantics, hub
aggregation, disabled-mode no-ops, courier round-trip, and the
multiprocess acceptance run.

Structure mirrors the layer itself: pure-python registry/merge tests
first (no repro machinery), then the hub + pusher, then the courier
RPC boundary, then full runs through ``run_experiment`` /
``run_distributed_experiment``.
"""
import json
import pickle
import time

import numpy as np
import pytest

from repro.telemetry import (HUB_INTERFACE, NULL_METRIC, Counter, Gauge,
                             Histogram, MetricRegistry, MetricsHub,
                             MetricsPusher, WorkerTelemetry, format_report,
                             merge_snapshots, quantile, span,
                             strip_reservoirs)
from repro.telemetry import registry as _registry


@pytest.fixture
def telemetry_state():
    """Restore the process-global registry to its import-time state so
    tests that configure() it can't leak into the rest of the suite."""
    yield
    _registry.unconfigure()


# ------------------------------------------------------------ registry units
def test_quantile_matches_numpy():
    rng = np.random.default_rng(0)
    values = sorted(rng.normal(size=257).tolist())
    for q in (0.0, 0.25, 0.5, 0.95, 0.99, 1.0):
        assert quantile(values, q) == pytest.approx(
            np.percentile(values, q * 100), rel=1e-9)


def test_quantile_edge_cases():
    assert np.isnan(quantile([], 0.5))
    assert quantile([3.0], 0.99) == 3.0


def test_histogram_exact_when_under_reservoir():
    h = Histogram("h", max_samples=512)
    for v in range(1, 101):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["sum"] == pytest.approx(5050.0)
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    assert snap["mean"] == pytest.approx(50.5)
    assert snap["p50"] == pytest.approx(np.percentile(range(1, 101), 50))
    assert snap["p95"] == pytest.approx(np.percentile(range(1, 101), 95))
    assert snap["p99"] == pytest.approx(np.percentile(range(1, 101), 99))


def test_histogram_reservoir_bounds_memory_keeps_exact_stats():
    h = Histogram("h", max_samples=64)
    for v in range(10_000):
        h.observe(float(v))
    snap = h.snapshot()
    # count/sum/min/max are exact regardless of sampling
    assert snap["count"] == 10_000
    assert snap["min"] == 0.0 and snap["max"] == 9999.0
    assert len(snap["reservoir"]) == 64
    # the uniform sample keeps quantiles honest (loose statistical bound)
    assert 3000 < snap["p50"] < 7000


def test_empty_histogram_snapshot():
    assert Histogram("h").snapshot() == {"type": "histogram", "count": 0}


def test_counter_and_gauge():
    c = Counter("c")
    c.inc()
    c.inc(5)
    assert c.snapshot() == {"type": "counter", "value": 6}
    g = Gauge("g")
    g.set(3)
    g.set(2.5)
    assert g.snapshot() == {"type": "gauge", "value": 2.5}


def test_registry_returns_same_metric_and_rejects_type_conflicts():
    reg = MetricRegistry(enabled=True)
    assert reg.counter("a/b") is reg.counter("a/b")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("a/b")


def test_registry_probes():
    reg = MetricRegistry(enabled=True)
    reg.probe("pool", lambda: {"held": 3, "free": 5})
    reg.probe("bad", lambda: 1 / 0)            # raising probe is skipped
    reg.probe("mixed", lambda: {"ok": 1.5, "label": "nope"})
    snap = reg.snapshot()
    assert snap["pool/held"] == {"type": "gauge", "value": 3.0}
    assert snap["pool/free"] == {"type": "gauge", "value": 5.0}
    assert snap["mixed/ok"]["value"] == 1.5
    assert not any(k.startswith("bad") for k in snap)
    assert "mixed/label" not in snap


def test_registry_probe_prefix_collision_dedupes():
    reg = MetricRegistry(enabled=True)
    reg.probe("engine", lambda: {"x": 1})
    reg.probe("engine", lambda: {"x": 2})
    snap = reg.snapshot()
    assert snap["engine/x"]["value"] == 1.0
    assert snap["engine#2/x"]["value"] == 2.0


def test_timer_observes_milliseconds():
    """A span times its block in milliseconds into ``<name>_ms``."""
    reg = MetricRegistry(enabled=True)
    with reg.span("h"):
        time.sleep(0.01)
    snap = reg.histogram("h_ms").snapshot()
    assert snap["count"] == 1
    assert snap["min"] >= 5.0   # ms, not seconds


def test_enabled_span_records_exact_count_and_sum(monkeypatch):
    reg = MetricRegistry(enabled=True)
    ticks = iter([10.0, 10.25, 20.0, 20.5])
    monkeypatch.setattr(_registry.time, "monotonic", lambda: next(ticks))
    for step in range(2):
        with reg.span("learner/update", step=step):
            pass
    snap = reg.histogram("learner/update_ms").snapshot()
    assert snap["count"] == 2
    assert snap["sum"] == 750.0
    assert (snap["min"], snap["max"]) == (250.0, 500.0)


def test_disabled_span_never_reads_the_clock(monkeypatch, telemetry_state):
    def boom(*_):
        raise AssertionError("a disabled span touched the clock or jax")

    monkeypatch.setattr(_registry.time, "monotonic", boom)
    monkeypatch.setattr(_registry, "_annotation", boom)
    for reg in (MetricRegistry(enabled=False), _registry.get_registry()):
        with reg.span("learner/input", step=3) as s:
            pass
        assert s is _registry._NULL_SPAN
        assert reg.snapshot() == {}
    with span("engine/decode", rows=2) as s:
        pass
    assert s is _registry._NULL_SPAN


def test_span_looks_enablement_up_when_called(telemetry_state):
    """Components built before ``configure`` still record once it runs."""
    with span("variables/fetch"):
        pass
    _registry.configure(enabled=True)
    with span("variables/fetch"):
        pass
    assert _registry.snapshot()["variables/fetch_ms"]["count"] == 1


def test_span_appears_in_the_profiler_trace(tmp_path, telemetry_state):
    import glob

    import jax
    from jax.profiler import ProfileData
    _registry.configure(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("learner/update", step=7):
            jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(ev.name, dict(ev.stats), ev.duration_ns)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("repro/")]
    assert len(events) == 1
    name, ids, duration_ns = events[0]
    assert (name, ids) == ("repro/learner/update", {"step": 7})
    assert 0 < duration_ns
    assert _registry.snapshot()["learner/update_ms"]["count"] == 1


# ----------------------------------------------------------- disabled mode
def test_disabled_registry_is_noop():
    reg = MetricRegistry(enabled=False)
    c = reg.counter("c")
    h = reg.histogram("h")
    g = reg.gauge("g")
    # all three are the shared falsy null — hot paths skip clock reads
    assert c is NULL_METRIC and h is NULL_METRIC and g is NULL_METRIC
    assert not c and not h and not g
    c.inc()
    g.set(1.0)
    h.observe(2.0)
    with reg.span("h"):
        pass
    reg.probe("pool", lambda: {"x": 1})
    assert reg.snapshot() == {}


def test_global_registry_disabled_until_configured(telemetry_state):
    _registry.unconfigure()
    assert not _registry.enabled()
    assert not _registry.is_configured()
    assert _registry.histogram("x") is NULL_METRIC
    _registry.configure(enabled=True, node="test")
    assert _registry.enabled()
    assert _registry.node_name() == "test"
    assert _registry.histogram("x")
    # configure() always starts fresh: no leakage between runs
    _registry.histogram("x").observe(1.0)
    _registry.configure(enabled=True, node="test2")
    assert _registry.snapshot() == {}


# ------------------------------------------------------------------- merge
def test_merge_counters_sum():
    merged = merge_snapshots({
        "a": {"events": {"type": "counter", "value": 3}},
        "b": {"events": {"type": "counter", "value": 4}},
    })
    assert merged["events"] == {"type": "counter", "value": 7, "nodes": 2}


def test_merge_gauges_mean_min_max():
    merged = merge_snapshots({
        "a": {"size": {"type": "gauge", "value": 10.0}},
        "b": {"size": {"type": "gauge", "value": 30.0}},
    })
    assert merged["size"]["mean"] == 20.0
    assert merged["size"]["min"] == 10.0
    assert merged["size"]["max"] == 30.0


def test_merge_histograms_recomputes_quantiles_from_reservoirs():
    h1, h2 = Histogram("h"), Histogram("h")
    for v in range(100):
        h1.observe(float(v))
    for v in range(100, 300):
        h2.observe(float(v))
    merged = merge_snapshots({"a": {"h": h1.snapshot()},
                              "b": {"h": h2.snapshot()}})["h"]
    combined = list(range(300))
    assert merged["count"] == 300
    assert merged["min"] == 0.0 and merged["max"] == 299.0
    # true cross-node quantiles, NOT the average of per-node percentiles
    assert merged["p50"] == pytest.approx(np.percentile(combined, 50))
    assert merged["p95"] == pytest.approx(np.percentile(combined, 95))
    avg_of_p50s = (h1.snapshot()["p50"] + h2.snapshot()["p50"]) / 2
    assert merged["p50"] != pytest.approx(avg_of_p50s)


def test_merge_skips_conflicting_types_and_handles_empty():
    merged = merge_snapshots({
        "a": {"m": {"type": "counter", "value": 1},
              "h": {"type": "histogram", "count": 0}},
        "b": {"m": {"type": "gauge", "value": 2.0},
              "h": {"type": "histogram", "count": 0}},
    })
    assert "m" not in merged
    assert merged["h"] == {"type": "histogram", "count": 0, "nodes": 2}


def test_strip_reservoirs():
    h = Histogram("h")
    h.observe(1.0)
    stripped = strip_reservoirs({"h": h.snapshot()})
    assert "reservoir" not in stripped["h"]
    assert stripped["h"]["count"] == 1


# --------------------------------------------------------------------- hub
def _snapshot_with(events: int) -> dict:
    reg = MetricRegistry(enabled=True)
    reg.counter("events").inc(events)
    reg.histogram("lat_ms").observe(float(events))
    return reg.snapshot()


def test_hub_aggregates_and_keeps_latest_per_node():
    hub = MetricsHub()
    hub.push("actor/0", _snapshot_with(5))
    hub.push("actor/1", _snapshot_with(7))
    hub.push("actor/0", _snapshot_with(10))   # supersedes the first push
    snap = hub.snapshot()
    assert sorted(snap["nodes"]) == ["actor/0", "actor/1"]
    assert snap["num_nodes"] == 2 and snap["num_pushes"] == 3
    assert snap["merged"]["events"]["value"] == 17
    assert snap["merged"]["lat_ms"]["count"] == 2
    assert hub.nodes() == ["actor/0", "actor/1"]
    assert hub.num_pushes() == 3
    report = hub.report()
    assert "2 node(s)" in report and "events" in report
    assert format_report(snap) == report


def test_hub_jsonl_export(tmp_path):
    path = tmp_path / "telemetry.jsonl"
    hub = MetricsHub(jsonl_path=str(path))
    hub.push("a", _snapshot_with(1))
    hub.push("b", _snapshot_with(2))
    hub.stop()
    records = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["node"] for r in records] == ["a", "b"]
    for r in records:
        assert r["metrics"]["events"]["type"] == "counter"
        assert "reservoir" not in r["metrics"]["lat_ms"]
    # stop() is idempotent and the hub stays readable afterwards
    hub.stop()
    assert hub.snapshot()["num_nodes"] == 2


def test_pusher_pushes_periodically_and_flushes_on_stop(telemetry_state):
    _registry.configure(enabled=True, node="w")
    _registry.counter("events").inc(3)
    hub = MetricsHub()
    pusher = MetricsPusher(hub, "w", period_s=0.02).start()
    deadline = time.time() + 5.0
    while hub.num_pushes() < 2 and time.time() < deadline:
        time.sleep(0.01)
    assert hub.num_pushes() >= 2
    _registry.counter("events").inc(1)
    pusher.stop()
    # the final flush after the loop exits captured the last increment
    assert hub.snapshot()["merged"]["events"]["value"] == 4
    pushes = hub.num_pushes()
    time.sleep(0.06)
    assert hub.num_pushes() == pushes   # really stopped


def test_worker_telemetry_install(telemetry_state):
    hub = MetricsHub()
    # already-configured process (local launcher): install is a no-op
    _registry.configure(enabled=True, node="services")
    assert WorkerTelemetry(hub, "actor/0").install() is None
    assert _registry.node_name() == "services"
    # fresh process (spawn child): install configures + starts a pusher
    _registry.unconfigure()
    pusher = WorkerTelemetry(hub, "actor/1", period_s=0.02).install()
    assert pusher is not None
    assert _registry.is_configured() and _registry.enabled()
    assert _registry.node_name() == "actor/1"
    _registry.counter("events").inc(2)
    pusher.stop()
    assert hub.snapshot()["merged"]["events"]["value"] == 2


# ------------------------------------------------------- courier round-trip
def test_hub_courier_roundtrip(telemetry_state):
    from repro.distributed.courier import serve

    hub = MetricsHub()
    server, handle = serve(hub, interface=HUB_INTERFACE, name="telemetry/hub")
    try:
        # client-side instrumentation: RPCs made while telemetry is on
        # show up as courier/client metrics in THIS process's registry
        _registry.configure(enabled=True, node="test")
        reg = MetricRegistry(enabled=True)
        h = reg.histogram("lat_ms")
        for v in range(100):
            h.observe(float(v))
        reg.counter("events").inc(7)
        handle.push("worker/0", reg.snapshot())
        handle.push("worker/1", reg.snapshot())

        snap = handle.snapshot()
        assert sorted(snap["nodes"]) == ["worker/0", "worker/1"]
        merged = snap["merged"]
        assert merged["events"]["value"] == 14
        # reservoirs crossed the wire intact: merged count doubles and
        # the stripped wire-format summary keeps its quantiles
        assert merged["lat_ms"]["count"] == 200
        assert merged["lat_ms"]["p50"] == pytest.approx(
            np.percentile(range(100), 50))
        assert "reservoir" not in merged["lat_ms"]
        assert handle.nodes() == ["worker/0", "worker/1"]
        assert handle.num_pushes() == 2

        # both RPC sides of the push were themselves measured
        local = _registry.snapshot()
        client_lat = local["courier/client/telemetry/hub/push/latency_ms"]
        server_lat = local["courier/server/telemetry/hub/push/latency_ms"]
        assert client_lat["count"] >= 2 and server_lat["count"] >= 2
        assert local["courier/client/telemetry/hub/push/bytes_sent"][
            "value"] > 0

        # WorkerTelemetry pickles with the remote handle inside — the
        # exact payload the multiprocess launcher ships to spawn children
        wt = pickle.loads(pickle.dumps(
            WorkerTelemetry(handle, "actor/0", period_s=0.02)))
        assert wt.node == "actor/0"
        wt.hub.push("actor/0", reg.snapshot())
        assert handle.num_pushes() == 3
    finally:
        server.stop()


# ----------------------------------------------------------- full-run paths
def test_run_experiment_telemetry_extras(telemetry_state, tmp_path):
    from conftest import make_dqn_catch_config
    from repro.experiments import run_experiment

    jsonl = tmp_path / "run.jsonl"
    config = make_dqn_catch_config(
        seed=0, num_episodes=4, eval_episodes=0, min_replay_size=20,
        samples_per_insert=2.0, batch_size=8,
        telemetry=True, telemetry_jsonl=str(jsonl))
    result = run_experiment(config)
    tel = result.extras["telemetry"]
    assert sorted(tel["nodes"]) == ["local"]
    merged = tel["merged"]
    # replay instrumentation: block-time histograms + occupancy probe
    assert merged["replay/insert_block_ms"]["count"] > 0
    assert merged["replay/size"]["mean"] > 0   # merged gauges: mean/min/max
    assert jsonl.exists() and jsonl.read_text().strip()


def test_run_experiment_telemetry_off_by_default(telemetry_state):
    from conftest import make_dqn_catch_config
    from repro.experiments import run_experiment

    config = make_dqn_catch_config(
        seed=0, num_episodes=2, eval_episodes=0, min_replay_size=20,
        samples_per_insert=2.0, batch_size=8)
    result = run_experiment(config)
    assert "telemetry" not in result.extras
    assert not _registry.enabled()


# ------------------------------------------- multiprocess acceptance (slow)
@pytest.mark.slow
def test_multiprocess_telemetry_acceptance(telemetry_state, tmp_path):
    """Acceptance: a multiprocess DQN-on-Catch run with ``telemetry=True``
    produces a merged snapshot with courier RPC latency histograms, replay
    per-shard occupancy, and inference batch-occupancy stats from >= 3
    distinct worker nodes."""
    from conftest import make_dqn_catch_config
    from repro.experiments import run_distributed_experiment

    jsonl = tmp_path / "telemetry.jsonl"
    config = make_dqn_catch_config(
        seed=0, eval_episodes=0, num_replay_shards=2,
        min_replay_size=30, samples_per_insert=2.0, batch_size=8,
        launcher="multiprocess", inference="server",
        telemetry=True, telemetry_push_period_s=0.2,
        telemetry_jsonl=str(jsonl))
    result = run_distributed_experiment(config, num_actors=2,
                                        max_actor_steps=600,
                                        timeout_s=240)
    tel = result.extras["telemetry"]
    nodes = set(tel["nodes"])
    assert {"actor/0", "actor/1", "services"} <= nodes
    assert tel["num_pushes"] >= len(nodes)
    merged = tel["merged"]

    # courier RPC tracing: client side (from the actor children) and
    # server side (parent-resident services) both measured the hot edges
    client_lat = [n for n in merged
                  if n.startswith("courier/client/") and
                  n.endswith("/latency_ms")]
    server_lat = [n for n in merged
                  if n.startswith("courier/server/") and
                  n.endswith("/latency_ms")]
    assert client_lat and server_lat
    sel = merged["courier/client/inference/select_action/latency_ms"]
    assert sel["count"] > 0 and sel["p95"] >= sel["p50"] > 0
    assert merged[
        "courier/client/inference/select_action/bytes_sent"]["value"] > 0

    # replay per-shard occupancy + block-time histograms
    for shard in ("replay/shard_0", "replay/shard_1"):
        assert merged[f"{shard}/size"]["mean"] > 0
        assert merged[f"{shard}/insert_block_ms"]["count"] > 0

    # inference batching: queue waits and batch occupancy on the server
    assert merged["inference/batch_occupancy"]["count"] > 0
    assert 0.0 < merged["inference/batch_occupancy"]["mean"] <= 1.0
    assert merged["inference/queue_wait_ms"]["count"] > 0
    assert merged["inference/server/requests"]["mean"] > 0

    # per-node attribution: actor children report their client latencies
    for actor in ("actor/0", "actor/1"):
        node_metrics = tel["nodes"][actor]
        assert any(n.startswith("courier/client/") for n in node_metrics)

    # JSONL export captured pushes from multiple nodes
    records = [json.loads(line) for line in
               jsonl.read_text().splitlines()]
    assert {"actor/0", "actor/1", "services"} <= {r["node"]
                                                  for r in records}

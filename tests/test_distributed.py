"""Launcher conformance suite + courier RPC layer.

The same ``Program`` graph must behave identically on every registered
backend (``local`` threads, ``multiprocess`` OS processes): graph
resolution through handle edges, fail-fast on worker death, stop/join
idempotence, join-timeout reporting, and handle pickling degradation
(in-memory ``Handle`` -> courier ``RemoteHandle``).  Worker/service classes
here are module-level so the multiprocess backend can pickle them into
spawn children.
"""
import pickle
import threading
import time

import pytest

from repro.distributed import (JoinTimeout, Launcher, LauncherBase,
                               RemoteError, RemoteHandle, WorkerErrors,
                               get_launcher, register_launcher, serve)
from repro.distributed.program import Handle, Program, Replica

BACKENDS = ["local", "multiprocess"]

# Generous: spawn children pay interpreter startup (~1-2s each).
JOIN_S = 60


# --------------------------------------------------------------- node types
class Source:
    def __init__(self, value=41):
        self.value = value

    def get(self):
        return self.value


class Sink:
    """Service the workers report into (the parent cannot reach into a child
    process to read a worker attribute, so conformance tests observe worker
    effects through a service node)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def put(self, value):
        with self._lock:
            self._items.append(value)

    def items(self):
        with self._lock:
            return list(self._items)


class Bridge:
    """Worker: one read from source, one write to sink, exit."""

    def __init__(self, source, sink, offset=1):
        # the key Launchpad property: source/sink may be Handles, courier
        # RemoteHandles, or the objects; this code cannot tell.
        self.source = source
        self.sink = sink
        self.offset = offset

    def run(self):
        self.sink.put(self.source.get() + self.offset)


class Exploder:
    def __init__(self, message="boom"):
        self.message = message

    def run(self):
        raise ValueError(self.message)


class Spinner:
    """Worker: loop until stopped, reporting liveness through the sink."""

    def __init__(self, sink=None):
        self.sink = sink
        self._stop = threading.Event()

    def run(self):
        while not self._stop.is_set():
            if self.sink is not None:
                self.sink.put(1)
            self._stop.wait(0.01)

    def stop(self):
        self._stop.set()


class PlatformReporter:
    """Worker: reports the JAX platforms its process is pinned to."""

    def __init__(self, sink):
        self.sink = sink

    def run(self):
        import jax
        self.sink.put(jax.config.jax_platforms)


class Stubborn:
    """Worker that ignores stop requests (for join-timeout reporting)."""

    def run(self):
        time.sleep(120)


# Fake learners driving LearnerReplicaWorker through the conformance suite
# (module-level for the multiprocess backend's pickling).
class TickLearner:
    """Minimal learner: state is a float scalar; step() bumps it."""

    def __init__(self, value=0.0, step_s=0.0):
        import jax.numpy as jnp
        self.state = jnp.asarray(value, jnp.float32)
        self.step_s = step_s

    def step(self):
        import jax.numpy as jnp
        if self.step_s:
            time.sleep(self.step_s)
        self.state = self.state + jnp.asarray(1.0, jnp.float32)
        return {}

    def get_variables(self, names=()):
        return [float(self.state)]


class ExplodingLearner(TickLearner):
    def __init__(self, blow_at=3):
        super().__init__()
        self.blow_at = blow_at

    def step(self):
        if float(self.state) >= self.blow_at:
            raise ValueError("replica-boom")
        return super().step()


class SleepyLearner(TickLearner):
    """step() sleeps long enough to straggle past a short join timeout."""

    def __init__(self):
        super().__init__(step_s=8.0)


def _cleanup(launcher):
    """Best-effort teardown for tests that leave stubborn runners behind."""
    launcher.stop()
    for proc in getattr(launcher, "processes", {}).values():
        if proc.is_alive():
            proc.terminate()
            proc.join(timeout=10)


# ------------------------------------------------------- conformance suite
@pytest.mark.parametrize("backend", BACKENDS)
def test_graph_resolution_through_handles(backend):
    prog = Program()
    sink = prog.add_node("sink", Sink, role="service",
                         interface=("put", "items"))
    src = prog.add_node("source", Source, 41, role="service",
                        interface=("get",))
    prog.add_node("bridge", Bridge, src, sink, role="worker")
    launcher = get_launcher(backend)(prog).launch()
    launcher.join(timeout=JOIN_S)
    assert prog.resolve("sink").items() == [42]


@pytest.mark.parametrize("backend", BACKENDS)
def test_replicated_workers(backend):
    """num_replicas expands a worker node into a pool; Replica args give
    each member its own value."""
    prog = Program()
    sink = prog.add_node("sink", Sink, role="service",
                         interface=("put", "items"))
    src = prog.add_node("source", Source, 100, role="service",
                        interface=("get",))
    handles = prog.add_node("bridge", Bridge, src, sink,
                            Replica(lambda i: i), role="worker",
                            num_replicas=3)
    assert [h.node_name for h in handles] == ["bridge/0", "bridge/1",
                                              "bridge/2"]
    launcher = get_launcher(backend)(prog).launch()
    launcher.join(timeout=JOIN_S)
    assert sorted(prog.resolve("sink").items()) == [100, 101, 102]


@pytest.mark.parametrize("backend", BACKENDS)
def test_fail_fast_on_worker_death(backend):
    """The first worker failure stops every sibling; join surfaces it."""
    prog = Program()
    sink = prog.add_node("sink", Sink, role="service",
                         interface=("put", "items"))
    prog.add_node("spinner", Spinner, sink, role="worker")
    prog.add_node("exploder", Exploder, "boom", role="worker")
    launcher = get_launcher(backend)(prog).launch()
    with pytest.raises(Exception) as exc_info:
        launcher.join(timeout=JOIN_S)
    assert "boom" in str(exc_info.value)
    assert launcher.should_stop()
    # spinner observed the fail-fast stop and exited (no timeout needed)
    assert not isinstance(exc_info.value, (JoinTimeout, WorkerErrors)) \
        or all(not isinstance(e, JoinTimeout)
               for e in getattr(exc_info.value, "errors", []))


@pytest.mark.parametrize("backend", BACKENDS)
def test_all_worker_failures_aggregate(backend):
    """Multiple failures arrive as one WorkerErrors — none dropped."""
    prog = Program()
    prog.add_node("a", Exploder, "boom-a", role="worker")
    prog.add_node("b", Exploder, "boom-b", role="worker")
    launcher = get_launcher(backend)(prog).launch()
    # Fail-fast may classify the second death as shutdown-noise only for
    # user stops; two genuine explosions must both surface.
    with pytest.raises(Exception) as exc_info:
        launcher.join(timeout=JOIN_S)
    err = exc_info.value
    messages = (" ".join(str(e) for e in err.errors)
                if isinstance(err, WorkerErrors) else str(err))
    assert "boom-a" in messages and "boom-b" in messages


@pytest.mark.parametrize("backend", BACKENDS)
def test_stop_join_idempotent(backend):
    prog = Program()
    sink = prog.add_node("sink", Sink, role="service",
                         interface=("put", "items"))
    prog.add_node("spinner", Spinner, sink, role="worker")
    launcher = get_launcher(backend)(prog).launch()
    deadline = time.time() + JOIN_S
    while not prog.resolve("sink").items() and time.time() < deadline:
        time.sleep(0.02)
    assert prog.resolve("sink").items(), "spinner never ran"
    launcher.stop()
    launcher.stop()
    launcher.join(timeout=JOIN_S)
    launcher.join(timeout=JOIN_S)
    assert launcher.should_stop()


@pytest.mark.parametrize("backend", BACKENDS)
def test_join_timeout_names_stragglers(backend):
    prog = Program()
    prog.add_node("stubborn", Stubborn, role="worker")
    launcher = get_launcher(backend)(prog).launch()
    time.sleep(0.3 if backend == "local" else 3.0)   # let the child boot
    launcher.stop()
    with pytest.raises(JoinTimeout) as exc_info:
        launcher.join(timeout=0.5)
    assert "stubborn" in exc_info.value.node_names
    # process backends reap the straggler instead of leaking it
    for proc in getattr(launcher, "processes", {}).values():
        assert not proc.is_alive()
    _cleanup(launcher)


@pytest.mark.parametrize("backend", BACKENDS)
def test_handle_pickling_roundtrip(backend):
    """A handle crossing a process boundary degrades to a courier
    RemoteHandle with identical call syntax — and survives re-pickling."""
    prog = Program()
    handle = prog.add_node("source", Source, 41, role="service",
                           interface=("get",))
    launcher = get_launcher(backend)(prog).launch()
    try:
        launcher.serve("source")   # idempotent (multiprocess already did)
        remote = pickle.loads(pickle.dumps(handle))
        assert isinstance(remote, RemoteHandle)
        assert remote.get() == 41
        # RemoteHandle itself round-trips (its socket never pickles)
        remote2 = pickle.loads(pickle.dumps(remote))
        assert remote2.get() == 41
        # the declared interface survives the boundary
        with pytest.raises(AttributeError):
            remote.value
    finally:
        launcher.stop()
        launcher.join(timeout=JOIN_S)


def test_multiprocess_workers_are_pinned_to_cpu(monkeypatch):
    """One process per chip: a worker child runs JAX on the CPU even when
    the parent's environment names no platform (on a TPU host the parent
    holds the chip)."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    prog = Program()
    sink = prog.add_node("sink", Sink, role="service",
                         interface=("put", "items"))
    prog.add_node("reporter", PlatformReporter, sink, role="worker")
    launcher = get_launcher("multiprocess")(prog).launch()
    launcher.join(timeout=JOIN_S)
    assert prog.resolve("sink").items() == ["cpu"]


# ----------------------------------------- learner-replica node conformance
def _replica_program(learners, average_period=2, max_steps=6):
    """The multi-learner node shape ``make_distributed_agent`` emits:
    ``learner/replica_i`` run+serve hybrids around a shared
    ``learner/param_server`` rendezvous."""
    from repro.learners import (PARAM_SERVER_INTERFACE, LearnerReplicaWorker,
                                ParameterServer)
    prog = Program()
    server = ParameterServer(len(learners), average_period)
    prog.add_node("learner/param_server", lambda: server, role="service",
                  interface=PARAM_SERVER_INTERFACE)
    handles = []
    for i, learner in enumerate(learners):
        worker = LearnerReplicaWorker(learner, server, i, average_period,
                                      max_steps=max_steps)
        handles.append(prog.add_node(f"learner/replica_{i}",
                                     lambda w=worker: w, role="service",
                                     interface=("get_variables",)))
    return prog, server, handles


@pytest.mark.parametrize("backend", BACKENDS)
def test_learner_replica_nodes_step_and_average(backend):
    """Replica nodes run as run+serve hybrids on every backend: both step
    to max_steps, rendezvous at the param server, and serve exactly their
    declared interface."""
    prog, server, handles = _replica_program(
        [TickLearner(0.0), TickLearner(4.0)], average_period=2, max_steps=6)
    launcher = get_launcher(backend)(prog).launch()
    try:
        launcher.join(timeout=JOIN_S)
    finally:
        launcher.stop()
    assert server.rounds == 3          # 6 steps / period 2
    # averaging pulled the two streams together: both replicas converged to
    # the shared mean trajectory
    v0 = prog.resolve("learner/replica_0").get_variables()[0]
    v1 = prog.resolve("learner/replica_1").get_variables()[0]
    assert v0 == v1
    # interface enforcement on the replica handle
    assert handles[0].get_variables() == [v0]
    with pytest.raises(AttributeError):
        handles[0].learner
    with pytest.raises(AttributeError):
        handles[0].run


@pytest.mark.parametrize("backend", BACKENDS)
def test_learner_replica_death_fails_fast(backend):
    """One replica dying stops its siblings (the survivor is released from
    the averaging barrier instead of waiting forever) and join surfaces
    the error."""
    prog, server, _ = _replica_program(
        [ExplodingLearner(blow_at=3), TickLearner(0.0)],
        average_period=2, max_steps=100)
    launcher = get_launcher(backend)(prog).launch()
    with pytest.raises(Exception) as exc_info:
        launcher.join(timeout=JOIN_S)
    assert "replica-boom" in str(exc_info.value)
    assert launcher.should_stop()
    assert server.stopped


@pytest.mark.parametrize("backend", BACKENDS)
def test_join_timeout_names_straggler_replica(backend):
    """A replica stuck inside a long learner step is named by JoinTimeout."""
    prog, _, _ = _replica_program([SleepyLearner()], average_period=100,
                                  max_steps=1)
    launcher = get_launcher(backend)(prog).launch()
    time.sleep(0.3)                     # let the replica enter its step
    launcher.stop()
    with pytest.raises(JoinTimeout) as exc_info:
        launcher.join(timeout=0.5)
    assert "learner/replica_0" in exc_info.value.node_names
    _cleanup(launcher)


def test_unserved_handle_refuses_to_pickle():
    prog = Program()
    handle = prog.add_node("source", Source, role="service")
    with pytest.raises(pickle.PicklingError):
        pickle.dumps(handle)


# ------------------------------------------------------------ program graph
def test_duplicate_node_rejected():
    prog = Program()
    prog.add_node("a", Source)
    with pytest.raises(ValueError):
        prog.add_node("a", Source)


def test_bad_role_rejected():
    prog = Program()
    with pytest.raises(ValueError):
        prog.add_node("a", Source, role="supervisor")
    with pytest.raises(ValueError):
        prog.add_node("b", Source, role="worker", is_worker=True)


def test_is_worker_compat_spelling():
    prog = Program()
    prog.add_node("w", Spinner, is_worker=True)
    assert prog.node("w").role == "worker"
    assert prog.node("w").is_worker


def test_handle_dereference_is_lazy_and_cached():
    prog = Program()
    calls = []

    def factory():
        calls.append(1)
        return Source(1)

    h = prog.add_node("s", factory)
    assert not calls
    assert h.get() == 1
    assert h.get() == 1
    assert len(calls) == 1


def test_handle_enforces_declared_interface():
    prog = Program()
    h = prog.add_node("s", Source, role="service", interface=("get",))
    assert h.get() == 41
    with pytest.raises(AttributeError):
        h.value


def test_launcher_registry():
    assert get_launcher("local").backend == "local"
    assert get_launcher("multiprocess").backend == "multiprocess"
    with pytest.raises(ValueError, match="unknown launcher"):
        get_launcher("fleet-of-zeppelins")

    class DummyLauncher(LauncherBase):
        backend = "dummy-test"

        def launch(self):
            return self

    register_launcher("dummy-test", DummyLauncher)
    try:
        assert get_launcher("dummy-test") is DummyLauncher
        assert issubclass(DummyLauncher, Launcher)
    finally:
        from repro.distributed import launchers as launchers_lib
        launchers_lib._LAUNCHERS.pop("dummy-test", None)


# ----------------------------------------------------------------- courier
def test_courier_call_args_kwargs():
    class Calc:
        def mul(self, a, b=2):
            return a * b

    server, handle = serve(Calc(), name="calc")
    try:
        assert handle.mul(3) == 6
        assert handle.mul(3, b=5) == 15
        assert handle.call("mul", 4, b=4) == 16
    finally:
        server.stop()


def test_courier_preserves_exception_type():
    class Flaky:
        def blow(self):
            raise KeyError("missing-thing")

    server, handle = serve(Flaky(), name="flaky")
    try:
        with pytest.raises(KeyError, match="missing-thing"):
            handle.blow()
        # the connection survives a remote exception
        with pytest.raises(KeyError):
            handle.blow()
    finally:
        server.stop()


def test_courier_unpicklable_exception_becomes_remote_error():
    class Cursed(RuntimeError):
        def __init__(self):
            super().__init__("cursed")
            self.lock = threading.Lock()    # unpicklable payload

    class Target:
        def blow(self):
            raise Cursed()

    server, handle = serve(Target(), name="cursed")
    try:
        with pytest.raises(RemoteError, match="Cursed"):
            handle.blow()
    finally:
        server.stop()


def test_courier_server_enforces_interface():
    server, _ = serve(Source(7), interface=("get",), name="src")
    try:
        # bypass the client-side allowlist: the server still refuses
        sneaky = RemoteHandle(server.address, name="src", interface=None,
                              authkey=server.authkey)
        assert sneaky.get() == 7
        with pytest.raises(AttributeError, match="interface"):
            sneaky.call("value")
    finally:
        server.stop()


def test_courier_rejects_unauthenticated_connections():
    """The unpickling server must not accept frames from arbitrary local
    processes: connections without the authkey are refused before any
    payload is read."""
    server, handle = serve(Source(7), interface=("get",), name="src")
    try:
        intruder = RemoteHandle(server.address, name="src",
                                interface=("get",), authkey=b"wrong-key")
        with pytest.raises(ConnectionError, match="authentication"):
            intruder.get()
        keyless = RemoteHandle(server.address, name="src",
                               interface=("get",))
        with pytest.raises(ConnectionError, match="authentication"):
            keyless.get()
        assert handle.get() == 7      # the real client still works
    finally:
        server.stop()


class _TwoArgError(Exception):
    """Pickles via dumps but fails to REconstruct on loads (multi-arg
    __init__ with single-arg args tuple)."""

    def __init__(self, limit, used):
        super().__init__(f"quota {used}/{limit}")
        self.limit, self.used = limit, used


def test_courier_unreconstructable_exception_becomes_remote_error():
    class Target:
        def blow(self):
            raise _TwoArgError(10, 11)

    server, handle = serve(Target(), name="quota")
    try:
        with pytest.raises(RemoteError, match="_TwoArgError"):
            handle.blow()
    finally:
        server.stop()


def test_courier_unpicklable_response_becomes_remote_error():
    """A result that fails to pickle must answer as an error frame, not
    silently kill the connection."""
    class Target:
        def get_lock(self):
            return threading.Lock()

        def get_value(self):
            return 7

    server, handle = serve(Target(), name="locky")
    try:
        with pytest.raises(RemoteError, match="could not be pickled"):
            handle.get_lock()
        assert handle.get_value() == 7    # the connection survives
    finally:
        server.stop()


def test_courier_rate_limiter_timeout_crosses_the_wire():
    """Shutdown-noise classification depends on remote errors keeping their
    type: a RateLimiterTimeout raised server-side must re-raise as itself."""
    from repro.replay.rate_limiter import RateLimiterTimeout

    class Table:
        def insert(self):
            raise RateLimiterTimeout("stopped")

    server, handle = serve(Table(), name="table")
    try:
        with pytest.raises(RateLimiterTimeout):
            handle.insert()
    finally:
        server.stop()


# ------------------------------------------------------- variable satellite
def test_variable_server_empty_names_returns_all():
    from repro.core import VariableServer
    server = VariableServer(policy=[1, 2], critic=[3])
    assert server.get_variables(()) == [[1, 2], [3]]
    assert server.get_variables() == [[1, 2], [3]]
    assert server.get_variables(("critic",)) == [[3]]


def test_variable_source_served_over_courier():
    from repro.core import VariableClient, VariableServer
    from repro.core.variable import serve_variable_source
    vs = VariableServer(policy=[1, 2, 3])
    server, handle = serve_variable_source(vs)
    try:
        client = VariableClient(handle)
        assert client.params == [1, 2, 3]
        vs.publish("policy", [4])
        client.update(wait=True)
        assert client.params == [4]
        # empty names over RPC: all published variables
        assert handle.get_variables(()) == [[4]]
        with pytest.raises(AttributeError):
            handle.publish("policy", [5])   # not in the served interface
    finally:
        server.stop()


class _CountingSource:
    def __init__(self):
        self.fetches = 0

    def get_variables(self, names=()):
        self.fetches += 1
        return [[self.fetches]]


def test_variable_client_no_initial_double_fetch():
    """params populated by the property accessor must not be re-fetched by
    the immediately following update(wait=False)."""
    from repro.core import VariableClient
    source = _CountingSource()
    client = VariableClient(source, update_period=1)
    assert client.params == [1]
    assert source.fetches == 1
    client.update(wait=False)          # just fetched: deduped
    assert source.fetches == 1
    client.update(wait=False)          # cadence resumes (period=1)
    assert source.fetches == 2


def test_variable_client_period_still_honoured():
    from repro.core import VariableClient
    source = _CountingSource()
    client = VariableClient(source, update_period=5)
    for _ in range(10):
        client.update()
    # fetch on first call (no params yet) + every 5th call
    assert source.fetches == 3
    client.update(wait=True)
    assert source.fetches == 4


def test_variable_fetch_span_counts_fetches_not_updates():
    from repro.core import VariableClient
    from repro.telemetry import registry
    reg = registry.configure(enabled=True)
    try:
        source = _CountingSource()
        client = VariableClient(source, update_period=5)
        for _ in range(10):
            client.update()
        client.update(wait=True)
        assert source.fetches == 4
        assert reg.histogram("variables/fetch_ms").count == 4
    finally:
        registry.unconfigure()


# --------------------------------------------- multiprocess learning smoke
@pytest.mark.slow
def test_multiprocess_dqn_on_catch_learning_smoke():
    """Acceptance: the UNCHANGED DQNBuilder trains on Catch with actors in
    separate OS processes, pulling weights via the courier-served learner
    and feeding replay (sharded, to exercise shard service nodes) over
    courier RPC."""
    from conftest import make_dqn_catch_config
    from repro.experiments import run_distributed_experiment

    config = make_dqn_catch_config(
        seed=0, eval_episodes=20, num_replay_shards=2,
        launcher="multiprocess")
    result = run_distributed_experiment(config, num_actors=2,
                                        max_actor_steps=4000,
                                        timeout_s=240,
                                        with_evaluator=True)
    counts = result.counts
    assert counts.get("actor_steps", 0) >= 4000, counts
    assert result.learner_steps > 50
    assert result.extras["launcher"] == "multiprocess"
    assert result.extras["inserts"] > result.extras["min_size_to_sample"]
    assert result.extras["samples"] > 0
    # SPI accounting still holds across the RPC boundary (loose bound:
    # shards cross min-size thresholds independently)
    assert 1.0 < result.extras["spi_effective"] < 8.0
    # the remote evaluator reported through its service node
    assert len(result.extras["evaluator_returns"]) >= 1
    # sharded replay: both shard services saw inserts
    per_shard = result.extras["replay"]["per_shard"]
    assert len(per_shard) == 2 and all(s["inserts"] > 0 for s in per_shard)
    # learning: greedy eval beats the random-policy floor on Catch
    assert result.final_eval_return is not None
    assert result.final_eval_return > -0.6

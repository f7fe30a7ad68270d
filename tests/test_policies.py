"""repro.policies: incremental KV-cache decode parity, slot lifecycle,
serving, and (slow) learning on Catch under both launchers."""
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import make_environment_spec
from repro.envs import Catch
from repro.policies import (CacheSlotsExhausted, KVCachePool, PolicyEngine,
                            TransformerInferenceServer,
                            TransformerPolicyBuilder, TransformerPolicyConfig,
                            network)
from repro.policies.actors import _WindowBuffer

WINDOW = 4


def _cfg(**kw):
    base = dict(num_layers=1, d_model=32, num_heads=2, num_kv_heads=1,
                head_dim=16, d_ff=64, window=WINDOW, epsilon=0.0,
                backend="jnp", sequence_length=10, period=10, batch_size=8,
                min_replay_size=10, samples_per_insert=0.0)
    base.update(kw)
    return TransformerPolicyConfig(**base)


def _builder(cfg=None, seed=0):
    spec = make_environment_spec(Catch(seed=0))
    return TransformerPolicyBuilder(spec, cfg or _cfg(), seed=seed)


def _params(builder, seed=0):
    obs_dim = int(np.prod(builder.spec.observations.shape))
    return network.init(jax.random.key(seed), builder.arch, obs_dim,
                        builder.num_actions)


def _oracle_q(params, builder, window, length):
    """Full-sequence recompute Q at the newest real frame."""
    q = network.q_sequence(params, builder.arch,
                           jnp.asarray(window).reshape(1, WINDOW, -1))[0]
    return np.asarray(q[max(length - 1, 0)])


# ===================================================================== parity
@pytest.mark.parametrize("backend", ["jnp", "ref"])
def test_incremental_decode_matches_full_recompute(backend):
    """Acceptance: prefill + incremental decode through the ring cache ==
    full-sequence recompute, including past the ring wrap (T > window)."""
    builder = _builder(_cfg(backend=backend))
    params = _params(builder)
    obs_shape = builder.spec.observations.shape
    engine = PolicyEngine(builder.arch, obs_shape, builder.num_actions,
                          num_slots=1, epsilon=0.0, backend=backend)
    buf = _WindowBuffer(WINDOW, obs_shape)
    buf.reset()
    rng = np.random.RandomState(1)
    for t in range(3 * WINDOW):           # wraps the ring twice
        buf.push(rng.rand(*obs_shape).astype(np.float32))
        window = buf.window_array()
        act = engine.select(params, ["env0"], window[None], [buf.t])[0]
        expected = int(np.argmax(_oracle_q(params, builder, window,
                                           min(t + 1, WINDOW))))
        assert act == expected, f"step {t} ({backend}): {act} != {expected}"
    stats = engine.stats()
    assert stats["prefill_batches"] == 1      # one prefill, then pure decode
    assert stats["decode_rows"] == 3 * WINDOW - 1


def test_mid_episode_reprefill_equivalence():
    """A slot rebuilt mid-episode from its window (the stale-cache path)
    continues with the same actions as one that decoded incrementally."""
    builder = _builder()
    params = _params(builder)
    obs_shape = builder.spec.observations.shape
    rng = np.random.RandomState(2)
    frames = [rng.rand(*obs_shape).astype(np.float32) for _ in range(10)]

    def run(reprefill_at):
        engine = PolicyEngine(builder.arch, obs_shape, builder.num_actions,
                              num_slots=1, epsilon=0.0, backend="jnp")
        buf = _WindowBuffer(WINDOW, obs_shape)
        buf.reset()
        acts = []
        for t, f in enumerate(frames):
            buf.push(f)
            if t == reprefill_at:
                engine.pool.invalidate_all()   # forces the prefill path
            acts.append(engine.select(params, ["env0"],
                                      buf.window_array()[None], [buf.t])[0])
        return acts

    assert run(reprefill_at=None) == run(reprefill_at=6)


def test_batched_rows_mix_prefill_and_decode():
    """One select() call can carry fresh episodes (prefill) and continuing
    ones (decode); every row must match its own oracle."""
    builder = _builder()
    params = _params(builder)
    obs_shape = builder.spec.observations.shape
    engine = PolicyEngine(builder.arch, obs_shape, builder.num_actions,
                          num_slots=3, epsilon=0.0, backend="jnp")
    rng = np.random.RandomState(3)
    bufs = [_WindowBuffer(WINDOW, obs_shape) for _ in range(3)]
    for b in bufs:
        b.reset()
    for t in range(8):
        if t == 5:
            bufs[1].reset()        # env1 starts a new episode mid-batch
        windows, positions = [], []
        for b in bufs:
            b.push(rng.rand(*obs_shape).astype(np.float32))
            windows.append(b.window_array())
            positions.append(b.t)
        acts = engine.select(params, ["e0", "e1", "e2"],
                             np.stack(windows), positions)
        for i, b in enumerate(bufs):
            length = min(b.t + 1, WINDOW)
            expected = int(np.argmax(_oracle_q(params, builder, windows[i],
                                               length)))
            assert acts[i] == expected, f"t={t} env{i}"


# ============================================================ slot lifecycle
def test_pool_recycle_on_episode_end():
    builder = _builder()
    pool = KVCachePool(builder.arch, num_slots=2)
    a = pool.acquire("a")
    b = pool.acquire("b")
    assert pool.held() == 2 and a.index != b.index
    pool.release("a")
    assert pool.held() == 1
    c = pool.acquire("c")               # recycles a's slot
    assert c.index == a.index
    assert c.pos == -1 and c.cache_pos == -1


def test_pool_exhaustion_backpressure():
    builder = _builder()
    pool = KVCachePool(builder.arch, num_slots=1, timeout_s=0.05)
    pool.acquire("a")
    t0 = time.monotonic()
    with pytest.raises(CacheSlotsExhausted):
        pool.acquire("b")
    assert time.monotonic() - t0 >= 0.04   # it actually waited


def test_pool_reaps_idle_slots_of_dead_clients():
    """Churn tolerance (repro.resilience): a worker that dies without
    releasing leaks its slots only until pool pressure triggers the idle
    reaper — a live episode touches its slot every step and is spared."""
    builder = _builder()
    pool = KVCachePool(builder.arch, num_slots=2, timeout_s=0.05,
                       reap_idle_s=0.1)
    dead = pool.acquire("dead-client")
    pool.acquire("live-client")
    time.sleep(0.15)                       # both slots now look idle...
    pool.lookup("live-client")             # ...but the live one is touched
    fresh = pool.acquire("fresh-client")   # pressure: reaps only the dead
    assert fresh.index == dead.index
    assert pool.stats["reaped"] == 1
    assert pool.lookup("dead-client") is None
    assert pool.lookup("live-client") is not None
    assert pool.held() == 2


def test_pool_reaping_disabled_keeps_backpressure():
    builder = _builder()
    pool = KVCachePool(builder.arch, num_slots=1, timeout_s=0.05,
                       reap_idle_s=None)
    pool.acquire("a")
    time.sleep(0.15)
    with pytest.raises(CacheSlotsExhausted):
        pool.acquire("b")
    assert pool.stats["reaped"] == 0
    assert pool.stats["exhausted_waits"] == 1

    # a blocked acquire unblocks as soon as a slot frees
    got = {}

    def late_release():
        time.sleep(0.05)
        pool.release("a")

    thread = threading.Thread(target=late_release)
    thread.start()
    got["slot"] = pool.acquire("b", timeout=2.0)
    thread.join()
    assert got["slot"].key == "b"


def test_pool_invalidate_all_marks_slots_stale():
    builder = _builder()
    pool = KVCachePool(builder.arch, num_slots=2)
    slot = pool.acquire("a")
    slot.pos = 5
    generation = pool.generation
    pool.invalidate_all()
    assert pool.generation == generation + 1
    assert slot.generation == generation      # now stale
    assert pool.held() == 1                   # still held, must re-prefill


def test_engine_weight_refresh_invalidates_cache():
    """New params object identity => every live slot re-prefills (stale-
    cache rejection after an InferenceServer weight refresh)."""
    builder = _builder()
    obs_shape = builder.spec.observations.shape
    engine = PolicyEngine(builder.arch, obs_shape, builder.num_actions,
                          num_slots=1, epsilon=0.0, backend="jnp")
    params1 = _params(builder, seed=0)
    params2 = jax.tree.map(lambda x: x, params1)    # same values, new object
    buf = _WindowBuffer(WINDOW, obs_shape)
    buf.reset()
    rng = np.random.RandomState(4)
    for t in range(3):
        buf.push(rng.rand(*obs_shape).astype(np.float32))
        engine.select(params1, ["env0"], buf.window_array()[None], [buf.t])
    assert engine.stats()["prefill_batches"] == 1
    buf.push(rng.rand(*obs_shape).astype(np.float32))
    act = engine.select(params2, ["env0"], buf.window_array()[None],
                        [buf.t])[0]
    stats = engine.stats()
    assert stats["cache_invalidations"] == 1
    assert stats["prefill_batches"] == 2      # the refresh forced a prefill
    # identical weights => the re-prefilled answer matches the oracle
    expected = int(np.argmax(_oracle_q(params1, builder,
                                       buf.window_array(), WINDOW)))
    assert act == expected


@pytest.fixture
def telemetry_on():
    from repro.telemetry import registry
    yield registry.configure(enabled=True)
    registry.unconfigure()


def test_engine_counts_parameter_bytes_sent_to_the_device(telemetry_on):
    """Every prefill or decode call with host-numpy weights copies all of
    them to the device; with device arrays it copies none."""
    builder = _builder()
    obs_shape = builder.spec.observations.shape
    engine = PolicyEngine(builder.arch, obs_shape, builder.num_actions,
                          num_slots=4, epsilon=0.0, backend="jnp")
    device_params = _params(builder)
    host_params = jax.tree.map(np.asarray, device_params)
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(host_params))
    copied = telemetry_on.counter("engine/param_bytes_to_device")
    windows = np.zeros((2, WINDOW) + obs_shape, np.float32)
    engine.select(host_params, ["a"], windows[:1], [0])          # prefill
    assert copied.value == nbytes
    engine.select(host_params, ["a"], windows[:1], [1])          # decode
    assert copied.value == 2 * nbytes
    engine.select(host_params, ["a", "b"], windows, [2, 0])      # both
    assert copied.value == 4 * nbytes
    engine.select(device_params, ["a", "b"], windows, [3, 1])
    engine.select(device_params, ["a", "b"], windows, [4, 2])
    assert copied.value == 4 * nbytes
    snap = telemetry_on.snapshot()
    stats = engine.stats()
    assert snap["engine/prefill_ms"]["count"] == stats["prefill_batches"]
    assert snap["engine/decode_ms"]["count"] == stats["decode_batches"]
    assert stats["prefill_batches"] + stats["decode_batches"] == 6


def _prefilled_learner(builder):
    """The policy learner over a replay holding a few Catch episodes."""
    table = builder.make_replay()
    adder = builder.make_adder(table)
    env = Catch(seed=1)
    rng = np.random.RandomState(1)
    while table.size() < builder.cfg.batch_size:
        step = env.reset()
        adder.add_first(step)
        while not step.last():
            action = np.int32(rng.randint(builder.num_actions))
            step = env.step(action)
            adder.add(action, step)
    return builder.make_learner(builder.make_dataset(table),
                                priority_update_cb=table.update_priorities)


def test_learner_step_spans_split_the_step(tmp_path, telemetry_on):
    """Input, update, priorities and metrics: one span each per step, in
    that order, none overlapping another."""
    import glob

    from jax.profiler import ProfileData
    learner = _prefilled_learner(_builder())
    learner.step()                                # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            learner.step()
    finally:
        jax.profiler.stop_trace()
    phases = ["input", "update", "priorities", "metrics"]
    snap = telemetry_on.snapshot()
    assert [snap[f"learner/{p}_ms"]["count"] for p in phases] == [4] * 4
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                    dict(ev.stats)["step"])
                   for plane in ProfileData.from_file(path).planes
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("repro/learner/"))
    assert [(name, step) for _, _, name, step in spans] == [
        (f"repro/learner/{p}", step) for step in (1, 2, 3) for p in phases]
    assert all(end <= nxt for (_, end, _, _), (nxt, _, _, _)
               in zip(spans, spans[1:]))


# ================================================================== serving
class _FakeSource:
    """get_variables handing out a fresh params OBJECT each bump()."""

    def __init__(self, params):
        self._params = params

    def bump(self):
        self._params = jax.tree.map(lambda x: x, self._params)

    def get_variables(self, names=("policy",)):
        return [self._params for _ in names]


def test_transformer_inference_server_roundtrip():
    builder = _builder()
    policy = builder.make_policy(evaluation=True)
    engine = policy.make_engine(num_slots=4)
    source = _FakeSource(_params(builder))
    server = TransformerInferenceServer(engine, source, max_batch_size=4,
                                        max_wait_ms=1.0, update_period=1)
    try:
        assert server.window() == WINDOW
        obs_shape = builder.spec.observations.shape
        rng = np.random.RandomState(5)
        bufs = [_WindowBuffer(WINDOW, obs_shape) for _ in range(2)]
        for b in bufs:
            b.reset()
        for t in range(WINDOW + 2):
            for b in bufs:
                b.push(rng.rand(*obs_shape).astype(np.float32))
            windows = np.stack([b.window_array() for b in bufs])
            positions = np.asarray([b.t for b in bufs])
            actions = server.select_action(windows, positions, "client-1")
            assert actions.shape == (2,)
        stats = server.stats()
        assert stats["requests"] == WINDOW + 2
        assert stats["rows"] == 2 * (WINDOW + 2)
        assert stats["pool_held_slots"] == 2

        # weight refresh (update_period=1: every batch refetches; bump makes
        # the fetch return a NEW object) => cache invalidation + re-prefill
        source.bump()
        for b in bufs:
            b.push(rng.rand(*obs_shape).astype(np.float32))
        windows = np.stack([b.window_array() for b in bufs])
        positions = np.asarray([b.t for b in bufs])
        server.select_action(windows, positions, "client-1")
        assert server.stats()["cache_invalidations"] >= 1

        # release frees the client's slots
        server.release("client-1")
        assert server.stats()["pool_held_slots"] == 0
    finally:
        server.stop()


def test_server_batch_spans_nest_fetches_and_engine_calls(telemetry_on):
    """One ``inference/batch`` span per server batch; the weight fetches
    and the engine's calls inside it are counted where they happen."""
    builder = _builder()
    engine = builder.make_policy(evaluation=True).make_engine(num_slots=4)
    params = jax.tree.map(np.asarray, _params(builder))
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    source = _FakeSource(params)
    server = TransformerInferenceServer(engine, source, max_batch_size=2,
                                        max_wait_ms=1.0, update_period=2)
    obs_shape = builder.spec.observations.shape
    try:
        for t in range(5):
            server.select_action(np.zeros((2, WINDOW) + obs_shape,
                                          np.float32), np.full(2, t), "c")
        stats = server.stats()
    finally:
        server.stop()
    snap = telemetry_on.snapshot()
    calls = stats["prefill_batches"] + stats["decode_batches"]
    assert stats["batches"] == 5
    assert snap["inference/batch_ms"]["count"] == 5
    assert snap["variables/fetch_ms"]["count"] == 3   # batches 1, 2 and 4
    assert snap["engine/param_bytes_to_device"]["value"] == calls * nbytes
    assert "inference/batch_rows" not in snap
    assert snap["inference/batch_occupancy"]["count"] == 5


def test_server_rejects_new_requests_after_stop():
    from repro.distributed.courier import CourierClosed
    builder = _builder()
    policy = builder.make_policy(evaluation=True)
    server = TransformerInferenceServer(policy.make_engine(num_slots=2),
                                        _FakeSource(_params(builder)),
                                        max_batch_size=2)
    server.stop()
    with pytest.raises(CourierClosed):
        server.select_action(np.zeros((1, WINDOW, 10, 5), np.float32),
                             np.zeros((1,), np.int64), "c")


# ============================================================ learning (slow)
@pytest.mark.slow
def test_transformer_policy_learns_catch():
    """Acceptance: TransformerPolicyBuilder trains DQN-style on Catch
    through run_experiment (single process, local KV-cache decode)."""
    from conftest import make_transformer_catch_config
    from repro.experiments import run_experiment

    config = make_transformer_catch_config(seed=0, num_episodes=250,
                                           eval_every=0, eval_episodes=20)
    result = run_experiment(config)
    assert result.learner_steps > 0
    early = np.mean(result.train_returns[:30])
    final = result.final_eval_return
    assert np.isfinite(final)
    assert final > early, (f"no improvement: eval {final:.2f} vs "
                           f"early-train {early:.2f}")


@pytest.mark.slow
def test_transformer_policy_server_inference_local_launcher():
    """Acceptance: inference='server' on the local launcher — actors RPC the
    TransformerInferenceServer, which runs continuous-batching KV decode."""
    from conftest import make_transformer_catch_config
    from repro.experiments import run_distributed_experiment

    config = make_transformer_catch_config(
        seed=0, launcher="local", inference="server", num_envs_per_actor=2)
    result = run_distributed_experiment(config, num_actors=2,
                                        max_actor_steps=400, timeout_s=120)
    assert result.counts["actor_steps"] > 0
    assert result.learner_steps > 0
    stats = result.extras["inference"]
    assert stats["decode_rows"] > stats["prefill_rows"] > 0
    assert stats["batches"] > 0


@pytest.mark.slow
def test_transformer_policy_server_inference_multiprocess_launcher():
    """Acceptance: the same config crosses process boundaries — windows over
    courier RPC, cache slots keyed per remote client."""
    from conftest import make_transformer_catch_config
    from repro.experiments import run_distributed_experiment

    config = make_transformer_catch_config(
        seed=0, launcher="multiprocess", inference="server",
        num_envs_per_actor=2)
    result = run_distributed_experiment(config, num_actors=2,
                                        max_actor_steps=300, timeout_s=400)
    assert result.counts["actor_steps"] > 0
    stats = result.extras["inference"]
    assert stats["decode_rows"] > 0 and stats["prefill_rows"] > 0

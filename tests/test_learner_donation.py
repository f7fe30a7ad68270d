"""JaxLearner's donated step: the target network and the optimizer state of
the state a step is given are written over in place; params and the step
counter are not, so readers in other threads keep working."""
import sys
import threading

import numpy as np
import pytest

import jax

from repro.core import make_environment_spec
from repro.envs import Catch
from repro.learners import (LearnerReplicaWorker, MultiLearner,
                            ParameterServer)
from repro.replay.dataset import ReplaySample, SampleInfo


def _dqn_learner(batches, seed=0):
    from repro.agents.dqn import DQNBuilder, DQNConfig
    cfg = DQNConfig(min_replay_size=8, samples_per_insert=0.0, batch_size=8,
                    n_step=1, prioritized=False)
    builder = DQNBuilder(make_environment_spec(Catch(seed=0)), cfg,
                         seed=seed)
    return builder.make_learner(iter(batches))


def _dqn_batches(num_batches, batch_size=8, seed=0):
    from repro.core.types import Transition
    rng = np.random.RandomState(seed)
    batches = []
    for _ in range(num_batches):
        data = Transition(
            observation=rng.rand(batch_size, 10, 5).astype(np.float32),
            action=rng.randint(0, 3, size=batch_size).astype(np.int32),
            reward=rng.randn(batch_size).astype(np.float32),
            discount=np.ones(batch_size, np.float32),
            next_observation=rng.rand(batch_size, 10, 5).astype(np.float32))
        info = SampleInfo(np.arange(batch_size, dtype=np.int64),
                          np.full(batch_size, 1.0 / 64))
        batches.append(ReplaySample(info, data))
    return batches


def _policy_learner():
    """The transformer policy's learner at tiny widths over a replay
    holding a few Catch episodes."""
    from repro.policies import TransformerPolicyBuilder, TransformerPolicyConfig
    cfg = TransformerPolicyConfig(
        num_layers=1, d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
        d_ff=64, window=4, epsilon=0.0, backend="jnp", sequence_length=10,
        period=10, batch_size=8, min_replay_size=10, samples_per_insert=0.0)
    builder = TransformerPolicyBuilder(make_environment_spec(Catch(seed=0)),
                                       cfg, seed=0)
    table = builder.make_replay()
    adder = builder.make_adder(table)
    env = Catch(seed=1)
    rng = np.random.RandomState(1)
    while table.size() < cfg.batch_size:
        step = env.reset()
        adder.add_first(step)
        while not step.last():
            action = np.int32(rng.randint(builder.num_actions))
            step = env.step(action)
            adder.add(action, step)
    return builder.make_learner(builder.make_dataset(table),
                                priority_update_cb=table.update_priorities)


def _make(agent):
    return _dqn_learner(_dqn_batches(40)) if agent == "dqn" \
        else _policy_learner()


def _donated(state):
    return jax.tree.leaves((state.target_params, state.opt_state))


def _kept(state):
    return jax.tree.leaves((state.params, state.steps))


@pytest.mark.parametrize("agent", ["dqn", "policy"])
def test_step_donates_target_and_optimizer_state_only(agent):
    learner = _make(agent)
    learner.step()
    before = learner.state
    learner.step()
    assert all(leaf.is_deleted() for leaf in _donated(before))
    assert not any(leaf.is_deleted() for leaf in _kept(before))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(
        learner.state))


@pytest.mark.parametrize("agent", ["dqn", "policy"])
def test_compiled_update_aliases_the_donated_bytes(agent):
    learner = _make(agent)
    sample = next(learner._iterator)
    compiled = learner._donating_update.lower(*learner.state,
                                              sample).compile()
    donated = sum(leaf.nbytes for leaf in _donated(learner.state))
    assert donated > 0
    assert compiled.memory_analysis().alias_size_in_bytes == donated


def test_donated_bytes_gauge_set_on_the_first_step():
    from repro.telemetry import registry
    reg = registry.configure(enabled=True)
    try:
        learner = _dqn_learner(_dqn_batches(4))
        donated = sum(leaf.nbytes for leaf in _donated(learner.state))
        learner.step()
        learner.step()
        assert reg.snapshot()["learner/donated_bytes"]["value"] == donated
    finally:
        registry.unconfigure()


def test_target_that_is_the_params_steps():
    learner = _dqn_learner(_dqn_batches(4))
    state = learner.state
    learner.state = state._replace(target_params=state.params)
    for _ in range(3):
        learner.step()
    assert int(learner.state.steps) == 3
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(learner.state.params))


def test_one_state_set_into_two_learners_both_step():
    first = _dqn_learner(_dqn_batches(4, seed=1))
    second = _dqn_learner(_dqn_batches(4, seed=2))
    shared = first.state
    first.state = shared
    second.state = shared
    for _ in range(2):
        first.step()
        second.step()
    assert int(first.state.steps) == int(second.state.steps) == 2


def test_a_wrapped_update_may_hand_back_its_state():
    """A wrapper set in place of ``_update`` runs the program that donates
    nothing, so a state it keeps or returns stays whole."""
    learner = _dqn_learner(_dqn_batches(4))
    update = learner._update

    def same_state(state, sample):
        _, metrics, priorities = update(state, sample)
        return state, metrics, priorities

    learner._update = same_state
    start = jax.device_get(learner.state)
    for _ in range(3):
        learner.step()
    jax.tree.map(np.testing.assert_array_equal,
                 jax.device_get(learner.state), start)


def test_readers_in_other_threads_while_the_learner_steps():
    learner = _dqn_learner(_dqn_batches(31))
    learner.step()                                # compile outside the loop
    errors, snapshots, stop = [], [], threading.Event()

    def read():
        try:
            while not stop.is_set():
                learner.get_variables()
                snapshots.append(learner.snapshot_state())
                int(learner.state.steps)
        except Exception as e:   # reported below, in the test's thread
            errors.append(e)

    readers = [threading.Thread(target=read) for _ in range(3)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for reader in readers:
            reader.start()
        for _ in range(30):
            learner.step()
    finally:
        stop.set()
        for reader in readers:
            reader.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not any(reader.is_alive() for reader in readers)
    assert not errors, errors
    assert snapshots
    for snap in snapshots:
        assert int(snap.steps) == int(snap.opt_state.step)
    assert int(learner.state.steps) == 31


def test_multi_learner_merges_every_step_into_donating_replicas():
    multi = MultiLearner(
        [_dqn_learner(_dqn_batches(10, seed=1)),
         _dqn_learner(_dqn_batches(10, seed=2))], average_period=1)
    for _ in range(20):      # 10 merges, each set into both replicas
        multi.step()
    assert multi.param_server.rounds == 10
    snap = multi.snapshot_state()
    assert int(snap.steps) == 10
    jax.tree.map(np.testing.assert_allclose, snap,
                 jax.device_get(multi.state))


def test_multi_learner_polls_while_replica_nodes_step():
    """The run loop's polls of a fleet whose replicas step in their own
    threads: the step count, the served params and a snapshot."""
    replicas = [_dqn_learner(_dqn_batches(21, seed=i)) for i in (1, 2)]
    for replica in replicas:
        replica.step()                            # compile outside the race
    server = ParameterServer(2, 1)
    workers = [LearnerReplicaWorker(replica, server, i, average_period=1,
                                    max_steps=20)
               for i, replica in enumerate(replicas)]
    multi = MultiLearner(replicas, average_period=1, param_server=server,
                         workers=workers)
    errors = []

    def run(worker):
        try:
            worker.run()
        except Exception as e:   # reported below, in the test's thread
            errors.append(e)

    threads = [threading.Thread(target=run, args=(w,)) for w in workers]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    polls = 0
    try:
        for thread in threads:
            thread.start()
        while any(thread.is_alive() for thread in threads):
            assert 1 <= multi.steps <= 21
            multi.get_variables()
            polls += 1
        snap = multi.snapshot_state()
    finally:
        for worker in workers:
            worker.stop()
        for thread in threads:
            thread.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not errors, errors
    assert polls > 0
    assert multi.steps == int(snap.steps) == 21


def test_replica_state_dict_is_a_host_snapshot():
    learner = _dqn_learner(_dqn_batches(4))
    worker = LearnerReplicaWorker(learner, max_steps=2)
    worker.run()
    saved = worker.state_dict()
    assert all(isinstance(leaf, np.ndarray)
               for leaf in jax.tree.leaves(saved["learner_state"]))
    assert int(saved["learner_state"].steps) == saved["steps_taken"] == 2


class _PushThenPull:
    """An async parameter service that keeps every push and whose pull is
    down, or has no blend to give."""

    def __init__(self, pull_fails):
        self.pull_fails = pull_fails
        self.pushed = []

    def push(self, replica_id, state, step):
        self.pushed.append(state)

    def pull(self):
        if self.pull_fails:
            raise ConnectionError("down")
        return None


@pytest.mark.parametrize("pull_fails", [True, False],
                         ids=["pull_fails", "pull_gives_nothing"])
def test_async_push_stays_whole_when_the_pull_fails(pull_fails):
    learner = _dqn_learner(_dqn_batches(4))
    service = _PushThenPull(pull_fails)
    worker = LearnerReplicaWorker(learner, service, average_period=1,
                                  max_steps=3, sync_mode="async")
    worker.run()
    assert len(service.pushed) == 3
    for state in service.pushed:
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(state))

"""Offline RL (§2.6/§3.7): train BC and offline DQN from a fixed dataset —
no actors, just a learner + dataset, then an evaluator.

BC goes through the experiments API (``BCBuilder`` is an offline
``AgentBuilder``: no adder, dataset pre-loaded into the replay table);
the offline-DQN section applies the DQN *learner* directly to the same
dataset — the paper's point that learners are reusable outside the
actor/replay loop.

  PYTHONPATH=src python examples/offline_bc.py
"""
import jax
import numpy as np

from repro.adders import NStepTransitionAdder
from repro.agents import bc as bc_lib
from repro.agents import dqn as dqn_lib
from repro.compile_cache import enable_compile_cache
from repro.core import EnvironmentLoop, FeedForwardActor, VariableClient, make_environment_spec
from repro.envs import Catch
from repro.experiments import ExperimentConfig, run_offline_experiment
from repro.replay import MinSize, Table, Uniform, dataset_from_list


def collect(episodes=120, seed=0):
    env = Catch(seed=seed)
    table = Table("data", 1 << 20, Uniform(0), MinSize(1))
    adder = NStepTransitionAdder(table, 1, 0.99)
    for _ in range(episodes):
        ts = env.reset()
        adder.add_first(ts)
        while not ts.last():
            board = ts.observation
            ball = int(np.argmax(board[:-1].max(axis=0)))
            paddle = int(np.argmax(board[-1]))
            a = int(1 + np.sign(ball - paddle))
            ts = env.step(a)
            adder.add(a, ts)
    return [table._items[k].data for k in table._order]


def evaluate(learner, policy, episodes=25):
    actor = FeedForwardActor(policy, VariableClient(learner))
    loop = EnvironmentLoop(Catch(seed=123), actor)
    return np.mean([loop.run_episode()["episode_return"]
                    for _ in range(episodes)])


def main():
    enable_compile_cache()
    items = collect()
    print(f"dataset: {len(items)} transitions from an expert policy")

    # BC through the offline experiments path
    config = ExperimentConfig(
        builder_factory=lambda spec: bc_lib.BCBuilder(
            spec, items, bc_lib.BCConfig(), seed=0),
        environment_factory=lambda seed: Catch(seed=seed),
        seed=0,
        eval_episodes=25,
    )
    result = run_offline_experiment(config, num_learner_steps=400)
    print(f"BC learner steps {result.learner_steps}  "
          f"eval return {result.final_eval_return:+.2f}")

    # offline double-DQN: the same learner module, fed the fixed dataset
    spec = make_environment_spec(Catch(seed=0))
    qcfg = dqn_lib.DQNConfig(prioritized=False)
    qlearner = dqn_lib.make_learner(spec, qcfg, dataset_from_list(items, 64),
                                    jax.random.key(1))
    for i in range(400):
        m = qlearner.step()
    print(f"offline DQN final loss {m['loss']:.4f}  "
          f"eval return {evaluate(qlearner, dqn_lib.make_eval_policy(spec, qcfg)):+.2f}")


if __name__ == "__main__":
    main()

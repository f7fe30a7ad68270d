"""Quickstart: the Acme pattern in a dozen lines — declare an experiment
(builder factory + environment factory), run it, watch DQN learn Catch.

The same ``ExperimentConfig`` drives every execution mode: swap
``run_experiment`` for ``run_distributed_experiment(config, num_actors=4)``
and the identical builder runs as a Launchpad-lite program instead
(see examples/distributed_dqn_catch.py).

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.agents.dqn import DQNBuilder, DQNConfig
from repro.compile_cache import enable_compile_cache
from repro.envs import Catch
from repro.experiments import ExperimentConfig, run_experiment


def main():
    enable_compile_cache()
    config = ExperimentConfig(
        builder_factory=lambda spec: DQNBuilder(
            spec, DQNConfig(min_replay_size=50, samples_per_insert=0.0,
                            batch_size=32, n_step=1, epsilon=0.2), seed=0),
        environment_factory=lambda seed: Catch(seed=seed),
        seed=1,
        num_episodes=250,
        eval_every=50,
        eval_episodes=20,
    )
    result = run_experiment(config)

    for steps, ret in result.eval_returns:
        print(f"actor_steps {steps:5d}  eval_return {ret:+.2f}")
    assert np.mean(result.train_returns[-50:]) > 0, \
        "agent should have learned catch"
    print("quickstart OK")


if __name__ == "__main__":
    main()

"""End-to-end continuous-control driver: D4PG (distributional critic) on
pendulum swingup from raw features — the paper's Fig 5 workhorse, run
through the experiments API.

  PYTHONPATH=src python examples/train_d4pg_pendulum.py
"""
import numpy as np

from repro.agents.continuous import ContinuousBuilder, ContinuousConfig
from repro.compile_cache import enable_compile_cache
from repro.envs import PendulumSwingup
from repro.experiments import ExperimentConfig, run_experiment

EPISODE_LEN = 150


def main():
    enable_compile_cache()
    cfg = ContinuousConfig(algo="d4pg", hidden=64, batch_size=64,
                           min_replay_size=300, samples_per_insert=0.0,
                           n_step=3, sigma=0.3, vmin=0.0,
                           vmax=float(EPISODE_LEN), num_atoms=31,
                           target_update_period=50)
    config = ExperimentConfig(
        builder_factory=lambda spec: ContinuousBuilder(spec, cfg, seed=2),
        environment_factory=lambda seed: PendulumSwingup(
            seed=seed, episode_len=EPISODE_LEN),
        seed=1,
        num_episodes=80,
        eval_every=20,
        eval_episodes=5,
    )
    result = run_experiment(config)

    rets = result.train_returns
    for ep in range(9, len(rets), 10):
        print(f"episode {ep + 1:3d}  return {rets[ep]:6.1f}  "
              f"avg10 {np.mean(rets[max(ep - 9, 0):ep + 1]):6.1f} "
              f"/ {EPISODE_LEN}")
    print(f"final eval return: {result.final_eval_return:6.1f}")
    print("done; learner steps:", result.learner_steps)


if __name__ == "__main__":
    main()

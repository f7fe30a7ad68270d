"""Distributed agent (Fig 4 of the paper): N actor nodes + a learner node +
a rate-limited replay service, launched on a Launchpad-lite program graph —
from the SAME ExperimentConfig a single-process run would use.  The
execution backend is a config field: ``--launcher multiprocess`` places each
actor in its own OS process with courier RPC edges, no other change.

  PYTHONPATH=src python examples/distributed_dqn_catch.py --actors 4
  PYTHONPATH=src python examples/distributed_dqn_catch.py \
      --actors 4 --replay-shards 4 --prefetch 4   # sharded replay service
  PYTHONPATH=src python examples/distributed_dqn_catch.py \
      --actors 4 --launcher multiprocess          # one process per actor
  PYTHONPATH=src python examples/distributed_dqn_catch.py \
      --actors 4 --learner-replicas 2             # one learner per shard,
                                                  # parameter averaging

Factories are module-level (not lambdas): process-crossing backends pickle
them into the spawned actor processes.
"""
import argparse
import functools

from repro.agents.dqn import DQNBuilder, DQNConfig
from repro.compile_cache import enable_compile_cache
from repro.envs import Catch
from repro.experiments import ExperimentConfig, run_distributed_experiment


def make_builder(spec, cfg: DQNConfig):
    return DQNBuilder(spec, cfg, seed=0)


def make_env(seed: int):
    return Catch(seed=seed)


def main():
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--actors", type=int, default=4)
    p.add_argument("--actor-steps", type=int, default=6000)
    p.add_argument("--replay-shards", type=int, default=1,
                   help="replay shards (one replay node per shard)")
    p.add_argument("--prefetch", type=int, default=0,
                   help="learner prefetch queue depth in batches")
    p.add_argument("--launcher", default="local",
                   choices=["local", "multiprocess"],
                   help="execution backend: threads, or one OS process "
                        "per actor with courier RPC edges")
    p.add_argument("--learner-replicas", type=int, default=None,
                   help="learner replicas, one per replay shard, merged by "
                        "parameter averaging (actors still see one logical "
                        "learner)")
    p.add_argument("--average-period", type=int, default=None,
                   help="per-replica SGD steps between averaging rounds")
    args = p.parse_args()

    cfg = DQNConfig(min_replay_size=100, samples_per_insert=8.0,
                    batch_size=32, n_step=1, epsilon=0.15)
    config = ExperimentConfig(
        builder_factory=functools.partial(make_builder, cfg=cfg),
        environment_factory=make_env,
        seed=0,
        max_actor_steps=args.actor_steps,
        eval_episodes=30,
        num_replay_shards=args.replay_shards,
        prefetch_size=args.prefetch,
        launcher=args.launcher,
        num_learner_replicas=args.learner_replicas,
        learner_average_period=args.average_period,
    )
    print(f"launching [{args.launcher}]: {args.actors} actors + learner "
          f"+ replay[{args.replay_shards} shard(s)] "
          f"(SPI target {cfg.samples_per_insert}, "
          f"prefetch {args.prefetch})")
    result = run_distributed_experiment(config, num_actors=args.actors,
                                        timeout_s=300)

    ex = result.extras
    print(f"actor_steps={result.counts.get('actor_steps', 0):6.0f} "
          f"learner_steps={result.learner_steps:5d} "
          f"inserts={ex['inserts']} samples={ex['samples']} "
          f"spi_effective={ex['spi_effective']:.1f}")
    print(f"eval return over 30 episodes: {result.final_eval_return:+.2f}")
    if "replay" in ex:
        for shard in ex["replay"]["per_shard"]:
            print(f"  {shard['name']}: size={shard['size']} "
                  f"inserts={shard['inserts']} samples={shard['samples']}")
    if "learners" in ex:
        lrn = ex["learners"]
        print(f"  learners: {lrn['num_replicas']} replica(s), "
              f"{lrn['rounds']} averaging round(s) "
              f"(period {lrn['average_period']}), per-replica steps "
              f"{lrn['per_replica_steps']}")


if __name__ == "__main__":
    main()

"""Compile the transformer-policy learner's step for one TPU v5e chip that
is described, not attached, and print what the compiler made of it.

    PYTHONPATH=src JAX_PLATFORMS=cpu python scripts/aot_learner_step.py \\
        benchmarks/chip/configs/yi-6b-policy-w16.json \\
        benchmarks/chip/traffic/r2d2.json [--donate learner|none]

``--donate learner`` (the default) compiles the program ``JaxLearner.step``
runs; ``none`` compiles the same update with nothing donated.  It prints one
JSON line: ``memory_analysis``, the cost analysis's flops and bytes accessed
(a loop body counts once, whatever its trip count), the number of ``while``
loops, and the instructions XLA's rematerialisation pass recomputes to fit
the program in the chip's memory (names holding ``.remat``).  Nothing runs
and no weight is made: the state and the batch are shapes only, so this
says nothing about time, only about memory headroom and what the compiler
did.  The TPU compiler ships with jaxlib; only one process at a time may
load it.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")

import jax  # noqa: E402
import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"),
                os.path.join(ROOT, "benchmarks", "chip")]

from repro.core import make_environment_spec  # noqa: E402
from repro.envs import Catch  # noqa: E402
from repro.policies import (TransformerPolicyBuilder,  # noqa: E402
                            TransformerPolicyConfig)

MEMORY_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
                 "alias_size_in_bytes", "temp_size_in_bytes",
                 "generated_code_size_in_bytes")


def _sample(builder, env, sequences: int):
    """One batch from a replay holding ``sequences`` sequences of ``env``'s
    episodes under random actions (the batch's shapes are what count)."""
    table = builder.make_replay()
    adder = builder.make_adder(table)
    rng = np.random.RandomState(0)
    while table.size() < sequences:
        step = env.reset()
        adder.add_first(step)
        while not step.last():
            action = np.int32(rng.randint(builder.num_actions))
            step = env.step(action)
            adder.add(action, step)
    return next(builder.make_dataset(table))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("config", help="a benchmarks/chip/configs/*.json")
    parser.add_argument("traffic", help="a benchmarks/chip/traffic/*.json")
    parser.add_argument("--donate", choices=("learner", "none"),
                        default="learner")
    args = parser.parse_args(argv)
    from harness import load_json, policy_kwargs
    config, traffic = load_json(args.config), load_json(args.traffic)

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    env = Catch(*config["observation_shape"], seed=0)
    cfg = TransformerPolicyConfig(**policy_kwargs(config, traffic))
    builder = TransformerPolicyBuilder(make_environment_spec(env), cfg)
    sample = _sample(builder, env, max(cfg.min_replay_size // cfg.period, 1))
    learners = []

    def state():
        learners.append(builder.make_learner(iter(())))
        return learners[0].state

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            np.shape(x), x.dtype, sharding=one_chip), tree)

    state_shapes = on_chip(jax.eval_shape(state))
    sample_shapes = on_chip(sample)
    learner = learners[0]
    if args.donate == "learner":
        lowered = learner._donating_update.lower(*state_shapes,
                                                 sample_shapes)
    else:
        update_fn = learner._plain_update.__wrapped__
        lowered = jax.jit(update_fn).lower(state_shapes, sample_shapes)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    remat = re.findall(r"%([\w.\-]*\.remat[\w.\-]*) = ", hlo)
    print(json.dumps({
        "donate": args.donate,
        "device_kind": topo.devices[0].device_kind,
        "memory": {k: getattr(memory, k) for k in MEMORY_FIELDS},
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "while_loops": len(re.findall(r" while\(", hlo)),
        "remat": sorted(remat),
    }))


if __name__ == "__main__":
    main()

"""Readings that set the limits of ``correct``, on the chip: for each seed,
one run of the cell with the program's numbers beside the control's (the
reference in bfloat16 in the program's place) and the planted faults', and
the ``correct`` the harness gives each of them against the cell's limits
(``verdicts``: the program's must be true, the control's and each fault's
false).

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 5 [--diagnose]

Runs every seed in this one process.  ``--witness`` also answers the
checked rows whose served action is not the reference's best with the
program's own policy oracle (``TransformerPolicy``: the full forward over the
window the actor sent) and counts where it sides with the reference.
``--diagnose`` also counts, for each
of the learner's first three steps, the positions where the double-Q
action the program's own forward picks at XLA's default precision differs
from the reference's at ``highest``, and the loss the reference gives when
it takes the program-precision action instead of its own.  Prints one JSON
line per seed.
"""
from __future__ import annotations

import time

import argparse
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))


def _program_arch(family, config: dict, num_actions: int):
    """The program's own ``ArchConfig`` for the configuration."""
    from repro.policies import network
    return network.make_arch(
        types.SimpleNamespace(**family.policy_fields(config)), num_actions)


def diagnose(keep: dict, hp: dict, config: dict, family) -> dict:
    """Double-Q action flips between XLA's default precision (the program's
    forward) and ``highest`` (the reference's), step by step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import checks
    from repro.policies import network

    arch = keep["arch"]
    arch_prog = _program_arch(family, config, arch.num_actions)
    q_prog = jax.jit(lambda p, o: network.q_sequence(p, arch_prog, o))
    q_ref = jax.jit(family.q_values, static_argnums=(1, 3))
    batches = [checks._batch(s) for s in keep["recorded"]["batches"]]
    start = family.make_params(keep["seeds"]["weights"], arch)
    params = start
    zeros = jax.tree.map(jnp.zeros_like, start)
    opt = (jnp.zeros((), jnp.float32), zeros, zeros)
    hp_items = tuple(sorted(hp.items()))
    out = []
    for k, batch in enumerate(batches):
        obs = jnp.asarray(batch["observation"].reshape(
            batch["observation"].shape[0], batch["observation"].shape[1], -1))
        qp = np.asarray(q_prog(params, obs))
        qr = np.asarray(q_ref(params, arch, obs, jnp.float32))
        qt = np.asarray(q_ref(start, arch, obs, jnp.float32))
        live = (batch["mask"][:, :-1] * batch["discount"][:, :-1]) > 0
        flip = (qp[:, 1:].argmax(-1) != qr[:, 1:].argmax(-1)) & live
        gap = np.sort(qr[:, 1:], -1)
        margin = (gap[..., -1] - gap[..., -2])[live]
        best_p, best_r = qp[:, 1:].argmax(-1), qr[:, 1:].argmax(-1)
        # the jump a flip makes in the target: target-net value difference
        jump = np.abs(_pick(qt[:, 1:], best_p) - _pick(qt[:, 1:], best_r))
        loss_prog_action = _loss_with_action(qr, qt, best_p, batch, hp,
                                             arch)
        out.append({"step": k + 1, "live_positions": int(live.sum()),
                    "flips": int(flip.sum()),
                    "median_margin": float(np.median(margin)),
                    "q_abs_err_p99": float(
                        np.quantile(np.abs(qp - qr), 0.99)),
                    "flip_target_jumps": jump[flip].tolist()[:20],
                    "ref_loss_with_program_action": loss_prog_action})
        params, opt, _, _, _ = family.sgd_step(
            params, start, opt, arch, hp_items, jnp.float32, batch)
    return out


def witness(keep: dict, config: dict, family) -> dict:
    """The program's own window oracle on the served rows the reference
    finds not best: how many it answers with the reference's best."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.policies import network

    arch = keep["arch"]
    checked = keep["checked"]
    rows = np.flatnonzero(checked["ok"][checked["req_of_row"]])
    windows = checked["window"][rows]
    lengths = np.minimum(checked["position"][rows] + 1, arch.window)
    params = family.make_params(keep["seeds"]["weights"], arch)
    q_ref = np.asarray(jax.jit(family.last_q, static_argnums=(1, 4))(
        params, arch, jnp.asarray(windows), jnp.asarray(lengths, jnp.int32),
        jnp.float32))
    wrong = q_ref.argmax(-1) != checked["action"][rows]
    if not wrong.any():
        return {"rows": int(rows.size), "not_best": 0}
    arch_prog = _program_arch(family, config, arch.num_actions)
    q_oracle = np.asarray(jax.jit(
        lambda p, o: network.q_sequence(p, arch_prog, o))(
            params, jnp.asarray(windows[wrong])))
    oracle = q_oracle[np.arange(int(wrong.sum())), lengths[wrong] - 1]
    return {"rows": int(rows.size), "not_best": int(wrong.sum()),
            "positions": checked["position"][rows][wrong].tolist(),
            "oracle_sides_with_reference": int(
                (oracle.argmax(-1) == q_ref[wrong].argmax(-1)).sum())}


def _pick(q, actions):
    import numpy as np
    return np.take_along_axis(q, actions[..., None].astype(np.int64),
                              -1)[..., 0]


def _loss_with_action(q, q_target, best, batch, hp, arch):
    """The reference's loss with the double-Q action given."""
    import numpy as np
    T = q.shape[1]
    rewards = batch["reward"].astype(np.float64)
    discounts = batch["discount"].astype(np.float64) * hp["discount"]
    y = rewards[:, :-1] + discounts[:, :-1] * _pick(q_target[:, 1:], best)
    q_taken = _pick(q[:, :-1], batch["action"][:, :-1])
    starts = batch["start_of_episode"][:, :1].astype(np.float64)
    full = (np.arange(T - 1) >= arch.window - 1)[None]
    valid = batch["mask"][:, :-1] * np.clip(starts + full, 0, 1)
    td = (y - q_taken) * valid
    w = (1.0 / np.maximum(batch["probabilities"], 1e-12)) \
        ** hp["importance_beta"]
    w = w / w.max()
    return float(0.5 * np.sum(w[:, None] * td * td) / max(valid.sum(), 1.0))


def verdicts(readings: dict, limits: dict) -> dict:
    """The ``correct`` the harness would give the program, the control and
    each fault, each with its own numbers in the program's place; a number
    a reading lacks keeps the program's."""
    labels = {k.rsplit(".", 1)[0] for k in readings if "." in k}
    out = {}
    for label in sorted(labels):
        values = {n: readings.get(f"{label}.{n}",
                                  readings.get(f"program.{n}"))
                  for n in limits}
        out[label] = all(v is not None and v <= limits[n]
                         for n, v in values.items())
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--diagnose", action="store_true")
    parser.add_argument("--witness", action="store_true")
    args = parser.parse_args()
    root = os.path.dirname(os.path.dirname(HERE))
    sys.path[:0] = [HERE, os.path.join(root, "src")]
    import harness
    bench = harness.load_benchmark(root)
    cell, config, traffic, limits = harness.resolve_cell(bench, args.workload)
    family = harness.load_family(config["family"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        keep = {}
        result = harness.run_cell(
            cell, config, traffic, limits, bench, seed, args.seconds, False,
            process_start_ns=time.monotonic_ns(), controls=True, keep=keep,
            log=lambda m: print(m, file=sys.stderr, flush=True))
        line = {"workload": args.workload, "seed": seed,
                "correct": result["correct"], "metrics": result["metrics"],
                "memory_peak_bytes": result["device"]["memory_peak_bytes"],
                "compared": result["compared"],
                "verdicts": verdicts(result["controls"], limits["limits"]),
                "readings": result["controls"]}
        if args.diagnose:
            line["diagnosis"] = diagnose(keep, traffic["learner"], config,
                                         family)
        if args.witness:
            line["witness"] = witness(keep, config, family)
        print(json.dumps(line), flush=True)
        del keep


if __name__ == "__main__":
    main()

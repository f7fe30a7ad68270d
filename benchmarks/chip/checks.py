"""The comparison that decides ``correct``: what the timed path produced
against the plain reference of the configuration's model family
(``families/<family>.py``, passed in as ``family``), run after the
program's state is freed.

Learner: the first three steps the window's own learner took, on the
batches it drew.  Compared are each step's loss, the first gradient as the
optimizer received it (its first moment after step 1, divided by
``1 - beta1``), the weights' change after the three steps, and the
priorities each step handed to the replay.  Gradients and changes are
compared per leaf, as the gap between the two norms over the larger of the
reference leaf's norm and the median leaf's norm; the worst leaf counts.
Leaves whose reference gradient is below a thousandth of the median leaf's
move under Adam by round-off alone and are left out of the change.
Priorities are compared per sampled sequence, as the gap over the larger
of the reference's priority and the step's median; the worst sequence
counts, and a sequence the program sent no priority for reads as 0.
``prio_missing`` counts the sampled sequences of the three steps that the
program sent no priority for.

Serving: every row the inference server answered for the set-up's checked
episodes (served with the seed's weights), each served action against the
reference's Q-values for the window the actor sent: the gap by which the
served action's value lies below the reference's best, over the median
spread of the reference's Q-values across actions.  The widest gap counts.
The traffic acts greedily, so every served action is checkable.

With ``controls=True`` the same numbers are also read for the control
(the reference in the program's place, in ``CONTROL_DTYPE``) and for the
faults a reference can plant (half of the batch left out, the mean over
the rest; a served action altered), reported under ``control.*`` and
``fault.*``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CONTROL_DTYPE = jnp.bfloat16
BETA1 = 0.9
ROUNDOFF_SHARE = 1e-3
SERVE_BLOCK = 1024


def _leaves(tree) -> dict:
    return {jax.tree_util.keystr(path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64)))


def _norms(tree) -> dict:
    return {k: _norm(v) for k, v in _leaves(tree).items()}


def leaf_gaps(program: dict, ref: dict, keep=None) -> dict:
    """Per leaf: |norm - reference norm| over max(reference norm, median
    reference norm)."""
    keys = [k for k in ref if keep is None or k in keep]
    median = float(np.median([ref[k] for k in keys]))
    return {k: abs(program[k] - ref[k]) / max(ref[k], median, 1e-30)
            for k in keys}


def norm_gap(program: dict, ref: dict, keep=None) -> float:
    """The worst leaf's gap."""
    return max(leaf_gaps(program, ref, keep).values())


def prio_gap(program: dict, ref_keys, ref_prios) -> float:
    """Worst sequence: |priority - reference| over max(reference, median
    reference); ``program`` maps key -> priority, a missing key reads 0."""
    ref = np.asarray(ref_prios, np.float64)
    median = float(np.median(ref))
    got = np.asarray([program.get(int(k), 0.0) for k in ref_keys],
                     np.float64)
    return float((np.abs(got - ref) / np.maximum(np.maximum(ref, median),
                                                 1e-30)).max())


def _batch(sample) -> dict:
    data = dict(sample.data)
    data["probabilities"] = np.asarray(sample.info.probabilities, np.float32)
    return data


def _half(batch: dict) -> dict:
    rows = batch["observation"].shape[0] // 2
    return {k: v[:rows] for k, v in batch.items()}


def _learner_numbers(losses, grad_norms, change_norms, priorities,
                     ref) -> dict:
    out = {f"loss_{i + 1}": abs(p - r) / max(abs(r), 1e-30)
           for i, (p, r) in enumerate(zip(losses, ref["losses"]))}
    out["prio_missing"] = 0
    for i, (keys, prios) in enumerate(ref["priorities"]):
        sent = priorities[i] if i < len(priorities) else {}
        out[f"prio_{i + 1}"] = prio_gap(sent, keys, prios)
        out["prio_missing"] += sum(int(k) not in sent for k in keys)
    out["grad_1"] = norm_gap(grad_norms, ref["grad"])
    if ref["moved"]:
        out["change_3"] = norm_gap(change_norms, ref["change"],
                                   keep=ref["moved"])
    return out


def check_learner(family, arch, hp: dict, weights_seed: int,
                  recorded: dict, controls: bool = False):
    """(compared numbers, extra readings) for the learner."""
    batches = [_batch(s) for s in recorded["batches"]]
    keys = [np.asarray(s.info.keys) for s in recorded["batches"]]
    start = family.make_params(weights_seed, arch)
    start_host = _leaves(jax.device_get(start))
    losses, grad, after, prios = family.train_three(start, arch, hp,
                                                    batches)
    grad_norms = _norms(grad)
    median = float(np.median(list(grad_norms.values())))
    change = {k: v - start_host[k] for k, v in _leaves(after).items()}
    ref = {
        "losses": losses, "grad": grad_norms,
        "change": {k: _norm(v) for k, v in change.items()},
        "priorities": list(zip(keys, prios)),
    }
    ref["moved"] = {k for k, n in grad_norms.items()
                    if n >= ROUNDOFF_SHARE * median
                    and ref["change"][k] > 0}
    program_change = {k: _norm(v - start_host[k])
                      for k, v in _leaves(recorded["params_after"]).items()}
    program_grad = {k: n / (1 - BETA1)
                    for k, n in _norms(recorded["first_moment"]).items()}
    program_prios = [dict(zip(np.asarray(k).tolist(),
                              np.asarray(p, np.float64).tolist()))
                     for k, p in recorded["priorities"]]
    numbers = _learner_numbers(recorded["losses"], program_grad,
                               program_change, program_prios, ref)
    grad_gaps = leaf_gaps(program_grad, ref["grad"])
    extra = {"reference_losses": losses,
             "program_losses": list(recorded["losses"]),
             "left_out_of_change": sorted(set(grad_norms) - ref["moved"]),
             "grad_1_worst_leaves": sorted(grad_gaps.items(),
                                           key=lambda kv: -kv[1])[:3]}
    if controls:
        for label, dtype, data in (("control", CONTROL_DTYPE, batches),
                                   ("fault.half_batch", jnp.float32,
                                    [_half(b) for b in batches])):
            c_losses, c_grad, c_after, c_prios = family.train_three(
                start, arch, hp, data, dtype)
            c_change = {k: _norm(v - start_host[k])
                        for k, v in _leaves(c_after).items()}
            c_prios = [dict(zip(k[:len(p)].tolist(),
                                np.asarray(p, np.float64).tolist()))
                       for k, p in zip(keys, c_prios)]
            for name, value in _learner_numbers(
                    c_losses, _norms(c_grad), c_change, c_prios,
                    ref).items():
                extra[f"{label}.{name}"] = value
    del start
    return numbers, extra


def check_serve(family, arch, weights_seed: int, requests: dict,
                controls: bool = False):
    """(compared numbers, extra readings) for the served actions."""
    rows = np.flatnonzero(requests["ok"][requests["req_of_row"]]) \
        if requests["req_of_row"].size else np.zeros(0, np.int64)
    if rows.size == 0:
        return {"serve_gap": None}, {"serve_rows": 0}
    params = family.make_params(weights_seed, arch)
    windows = requests["window"][rows]
    lengths = np.minimum(requests["position"][rows] + 1, arch.window)
    served = requests["action"][rows]
    q_ref, q_ctl = [], []
    last = jax.jit(family.last_q, static_argnums=(1, 4))
    every = jax.jit(family.q_values, static_argnums=(1, 3))
    for lo in range(0, rows.size, SERVE_BLOCK):
        w = jnp.asarray(windows[lo:lo + SERVE_BLOCK])
        n = jnp.asarray(lengths[lo:lo + SERVE_BLOCK], jnp.int32)
        if controls:
            q_ref.append(np.asarray(every(params, arch, w, jnp.float32)))
            q_ctl.append(np.asarray(every(params, arch, w, CONTROL_DTYPE)))
        else:
            q_ref.append(np.asarray(last(params, arch, w, n, jnp.float32)))
    del params
    q_ref = np.concatenate(q_ref)
    if controls:
        q_all, q_ctl = q_ref, np.concatenate(q_ctl)
        q_ref = q_all[np.arange(rows.size), lengths - 1]
    scale = float(np.median(q_ref.max(-1) - q_ref.min(-1)))
    gaps = q_ref.max(-1) - q_ref[np.arange(rows.size), served]
    numbers = {"serve_gap": float(gaps.max()) / scale}
    wrong = gaps > 0
    extra = {"serve_rows": int(rows.size),
             "serve_rows_not_best": int(wrong.sum()),
             "serve_positions_not_best": sorted(
                 requests["position"][rows][wrong].tolist()),
             "serve_q_scale": scale}
    if controls:
        valid = np.arange(arch.window)[None, :] < lengths[:, None]
        best = q_all.max(-1)
        picked = np.take_along_axis(q_all, q_ctl.argmax(-1)[..., None],
                                    -1)[..., 0]
        extra["control.serve_gap"] = float(((best - picked) * valid).max()
                                           / scale)
        altered = (served + 1) % arch.num_actions
        extra["fault.altered_action.serve_gap"] = float(
            (q_ref.max(-1) - q_ref[np.arange(rows.size), altered]).max()
            / scale)
    return numbers, extra

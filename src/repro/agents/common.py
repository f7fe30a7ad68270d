"""Shared learner scaffolding for all agents."""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.interfaces import Learner
from repro.telemetry import registry as _telemetry


class LearnerState(NamedTuple):
    params: Any
    target_params: Any
    opt_state: Any
    steps: jax.Array
    extra: Any = ()


class JaxLearner(Learner):
    """Generic learner: pulls batches from an iterator, applies a jitted SGD
    step, publishes weights, accumulates learner walltime (§4.2 — persists
    through checkpoints).

    The step donates the target network and the optimizer state: the new
    ones are written over the old, so XLA has their memory for the step.
    A state read through ``state`` is therefore valid only until the next
    step; a thread other than the stepping one reads ``snapshot_state()``
    (or ``get_variables``: params are never donated)."""

    def __init__(self, state: LearnerState, update_fn, iterator: Iterator,
                 priority_update_cb: Optional[Callable] = None):
        self._state = _owned(state)
        # ``_update`` is the step's program as seen from outside, ``(state,
        # sample)``, donating nothing: a wrapper set in its place may keep
        # or hand back the state it was given.  While it is the learner's
        # own, ``step`` runs the donating program instead.
        self._update = self._plain_update = jax.jit(update_fn)

        def update(params, target_params, opt_state, steps, extra, sample):
            return update_fn(LearnerState(params, target_params, opt_state,
                                          steps, extra), sample)

        # target and optimizer state donated; params are not, since
        # get_variables reads them from other threads while the step runs
        self._donating_update = jax.jit(update, donate_argnums=(1, 2))
        # held from the update's dispatch to the new state's assignment, so
        # snapshot_state never reads buffers a dispatch is donating
        self._lock = threading.Lock()
        self._iterator = iterator
        self._priority_cb = priority_update_cb
        self._walltime = 0.0
        self._num_steps = 0   # this object's steps: the spans' ids
        self._metrics: Dict[str, float] = {}

    @property
    def state(self) -> LearnerState:
        return self._state

    @state.setter
    def state(self, s: LearnerState):
        self._state = _owned(s)

    @property
    def steps(self) -> int:
        """The state's step count: never donated, so any thread may read
        it."""
        return int(self._state.steps)

    def snapshot_state(self) -> LearnerState:
        """A host copy of the whole state, safe to take from any thread."""
        with self._lock:
            return jax.device_get(self._state)

    @property
    def learner_walltime(self) -> float:
        return self._walltime

    def step(self) -> Dict[str, float]:
        # four spans that split the step without overlap, so a device idle
        # gap falls in exactly one phase: input, update, priorities, metrics
        step = self._num_steps
        self._num_steps += 1
        with _telemetry.span("learner/input", step=step):
            sample = next(self._iterator)
        donating = self._update is self._plain_update
        if donating and step == 0:
            donated = (self._state.target_params, self._state.opt_state)
            _telemetry.gauge("learner/donated_bytes").set(
                sum(leaf.nbytes for leaf in jax.tree.leaves(donated)))
        with _telemetry.span("learner/update", step=step):
            t0 = time.time()
            with self._lock:
                if donating:
                    self._state, metrics, priorities = \
                        self._donating_update(*self._state, sample)
                else:
                    self._state, metrics, priorities = self._update(
                        self._state, sample)
            jax.block_until_ready(priorities if priorities is not None
                                  else metrics)
            self._walltime += time.time() - t0
        if self._priority_cb is not None and priorities is not None:
            with _telemetry.span("learner/priorities", step=step):
                self._priority_cb(np.asarray(sample.info.keys),
                                  np.asarray(priorities))
        # ONE host transfer for all metrics + the step counter (a float(v)
        # per entry is a separate blocking device sync each).
        with _telemetry.span("learner/metrics", step=step):
            host_metrics, steps = jax.device_get((metrics, self._state.steps))
        self._metrics = {k: float(v) for k, v in host_metrics.items()}
        self._metrics["learner_steps"] = float(steps)
        self._metrics["learner_walltime"] = self._walltime
        return self._metrics

    def get_variables(self, names: Sequence[str] = ("policy",)):
        return [jax.tree.map(np.asarray, self._state.params)
                for _ in (names or ("policy",))]


def _owned(state: LearnerState) -> LearnerState:
    """``state`` with its donated parts in buffers of their own: a target
    that is the params, or one state set into two learners, would otherwise
    be donated under another reader."""
    target_params, opt_state = jax.tree.map(
        jnp.copy, (state.target_params, state.opt_state))
    return state._replace(target_params=target_params, opt_state=opt_state)


def importance_weights(probs: jax.Array, beta: float = 0.6) -> jax.Array:
    """PER importance-sampling weights, max-normalized (Schaul et al. 2015)."""
    w = (1.0 / jnp.maximum(probs.astype(jnp.float32), 1e-12)) ** beta
    return w / jnp.max(w)

"""Shared learner scaffolding for all agents."""
from __future__ import annotations

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
    through checkpoints)."""

    def __init__(self, state: LearnerState, update_fn, iterator: Iterator,
                 priority_update_cb: Optional[Callable] = None):
        self._state = state
        # NOTE: no donation here — actors snapshot params from another
        # thread (get_variables) and donation would delete buffers under
        # them.  The large-model train steps (repro.launch.steps) donate.
        self._update = jax.jit(update_fn)
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
        self._state = s

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
        with _telemetry.span("learner/update", step=step):
            t0 = time.time()
            self._state, metrics, priorities = self._update(self._state,
                                                            sample)
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


def fresh_copy(tree):
    """Deep-copy a pytree's buffers (so params/target_params can both be
    donated without aliasing the same buffer twice)."""
    return jax.tree.map(jnp.copy, tree)


def importance_weights(probs: jax.Array, beta: float = 0.6) -> jax.Array:
    """PER importance-sampling weights, max-normalized (Schaul et al. 2015)."""
    w = (1.0 / jnp.maximum(probs.astype(jnp.float32), 1e-12)) ** beta
    return w / jnp.max(w)

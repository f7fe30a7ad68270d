"""Sequence double-DQN learning for the transformer policy.

The learner's forward pass is ``network.q_sequence`` — FULL-sequence
recompute over replayed (B, T) observation windows with the same banded
(``sliding_window``) attention the acting path evaluates incrementally
through the KV cache, so learner and actor compute the same function.

Objective: R2D2-style double Q-learning with 1-step-within-sequence
targets, prioritized by a max/mean mix of |TD|.  Positions whose attention
context would differ from acting (a mid-episode sequence's first
``window - 1`` steps see a truncated window) are masked out of the loss.
"""
from __future__ import annotations

from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.agents.common import JaxLearner, LearnerState, importance_weights
from repro.core.types import EnvironmentSpec
from repro.policies import network
from repro.replay.dataset import ReplaySample


def make_learner(spec: EnvironmentSpec, cfg, iterator: Iterator, rng_key,
                 priority_update_cb=None) -> JaxLearner:
    num_actions = spec.actions.num_values
    obs_dim = int(np.prod(spec.observations.shape)) or 1
    arch = network.make_arch(cfg, num_actions)
    opt = optim.adam(cfg.learning_rate, clip=40.0)
    params = network.init(rng_key, arch, obs_dim, num_actions)
    state = LearnerState(params, params, opt.init(params),
                         jnp.zeros((), jnp.int32))

    def loss_fn(params, target_params, sample: ReplaySample):
        seq = sample.data
        obs = seq["observation"].astype(jnp.float32)           # (B, T, ...)
        B, T = obs.shape[:2]
        obs = obs.reshape(B, T, -1)
        actions = seq["action"].astype(jnp.int32)
        rewards = seq["reward"].astype(jnp.float32)
        discounts = seq["discount"].astype(jnp.float32) * cfg.discount
        mask = seq["mask"].astype(jnp.float32)

        q = network.q_sequence(params, arch, obs)              # (B, T, A)
        q_target = network.q_sequence(target_params, arch, obs)
        # double Q with 1-step-within-sequence targets
        a_star = jnp.argmax(q[:, 1:], axis=-1)
        next_v = jnp.take_along_axis(q_target[:, 1:],
                                     a_star[..., None], -1)[..., 0]
        y = rewards[:, :-1] + discounts[:, :-1] * \
            jax.lax.stop_gradient(next_v)
        q_taken = jnp.take_along_axis(q[:, :-1],
                                      actions[:, :-1][..., None], -1)[..., 0]

        # acting-parity mask: a sequence that does NOT start at an episode
        # start has its first window-1 steps attend a truncated context the
        # actor never sees — drop them from the loss (burn-in analogue).
        start = seq["start_of_episode"][:, :1].astype(jnp.float32)   # (B, 1)
        t_idx = jnp.arange(T - 1, dtype=jnp.float32)[None, :]
        full_ctx = (t_idx >= cfg.window - 1).astype(jnp.float32)
        context_ok = jnp.clip(start + full_ctx, 0.0, 1.0)
        valid = mask[:, :-1] * context_ok
        td = (y - q_taken) * valid

        w = importance_weights(jnp.asarray(sample.info.probabilities),
                               cfg.importance_beta)
        loss = 0.5 * jnp.sum(w[:, None] * jnp.square(td)) / jnp.maximum(
            jnp.sum(valid), 1.0)
        abs_td = jnp.abs(td)
        prio = cfg.priority_eta * jnp.max(abs_td, axis=1) + \
            (1 - cfg.priority_eta) * jnp.mean(abs_td, axis=1)
        return loss, prio

    def update(state: LearnerState, sample: ReplaySample):
        (loss, prio), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, state.target_params, sample)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optim.apply_updates(state.params, updates)
        steps = state.steps + 1
        target = optim.periodic_update(params, state.target_params, steps,
                                       cfg.target_update_period)
        return (LearnerState(params, target, opt_state, steps),
                {"loss": loss}, prio)

    return JaxLearner(state, update, iterator,
                      priority_update_cb=priority_update_cb)

"""R2D2 (§3.2): recurrent replay distributed DQN.

Sequences (with stored initial LSTM state + burn-in prefix), double
Q-learning over fixed-length sequences, prioritized by a convex combination
of mean and max absolute TD errors, n-step bootstrap targets.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.agents.common import JaxLearner, LearnerState, importance_weights
from repro.builders import AgentBuilder, BuilderOptions
from repro.core.types import EnvironmentSpec
from repro.networks.lstm import LSTMNetwork, LSTMState
from repro.networks.mlp import flatten_obs
from repro.replay.dataset import ReplaySample


@dataclasses.dataclass
class R2D2Config:
    hidden: int = 64
    lstm_size: int = 64
    learning_rate: float = 1e-3
    discount: float = 0.99
    sequence_length: int = 16
    period: int = 8                  # overlapping sequences
    burn_in: int = 4
    batch_size: int = 32
    target_update_period: int = 100
    epsilon: float = 0.1
    min_replay_size: int = 100
    max_replay_size: int = 50_000
    samples_per_insert: float = 4.0
    priority_eta: float = 0.9        # max/mean TD mixing
    importance_beta: float = 0.6


def make_network(spec: EnvironmentSpec, cfg: R2D2Config) -> LSTMNetwork:
    num_actions = spec.actions.num_values
    net = LSTMNetwork((cfg.hidden,), cfg.lstm_size, num_actions)
    net.in_dim = int(np.prod(spec.observations.shape)) or 1
    return net


def make_learner(spec: EnvironmentSpec, cfg: R2D2Config, iterator: Iterator,
                 rng_key, priority_update_cb=None) -> JaxLearner:
    net = make_network(spec, cfg)
    opt = optim.adam(cfg.learning_rate, clip=40.0)
    params = net.init(rng_key, net.in_dim)
    state = LearnerState(params, params, opt.init(params),
                         jnp.zeros((), jnp.int32))
    num_actions = spec.actions.num_values

    def q_over_sequence(params, obs_tm, lstm_state):
        """obs_tm: (T, B, feat) -> (T, B, A)."""
        q, _ = net.unroll(params, obs_tm, lstm_state)
        return q

    def loss_fn(params, target_params, sample: ReplaySample):
        seq = sample.data
        obs = seq["observation"].astype(jnp.float32)           # (B, T, ...)
        B, T = obs.shape[:2]
        obs_tm = jnp.swapaxes(obs.reshape(B, T, -1), 0, 1)     # (T, B, feat)
        actions = jnp.swapaxes(seq["action"].astype(jnp.int32), 0, 1)
        rewards = jnp.swapaxes(seq["reward"].astype(jnp.float32), 0, 1)
        discounts = jnp.swapaxes(
            seq["discount"].astype(jnp.float32) * cfg.discount, 0, 1)
        mask = jnp.swapaxes(seq["mask"].astype(jnp.float32), 0, 1)

        # stored initial state ("stale state"), burn-in re-warms it
        init_state = LSTMState(jnp.zeros((B, cfg.lstm_size)),
                               jnp.zeros((B, cfg.lstm_size)))
        if cfg.burn_in > 0:
            burn = obs_tm[:cfg.burn_in]
            _, warm = net.unroll(params, burn, init_state)
            _, warm_t = net.unroll(target_params, burn, init_state)
            warm = jax.tree.map(jax.lax.stop_gradient, warm)
            warm_t = jax.tree.map(jax.lax.stop_gradient, warm_t)
        else:
            warm = warm_t = init_state
        obs_l = obs_tm[cfg.burn_in:]
        act_l = actions[cfg.burn_in:]
        rew_l = rewards[cfg.burn_in:]
        disc_l = discounts[cfg.burn_in:]
        mask_l = mask[cfg.burn_in:]

        q = q_over_sequence(params, obs_l, warm)               # (L, B, A)
        q_target = q_over_sequence(target_params, obs_l, warm_t)
        # double Q with 1-step-within-sequence targets
        a_star = jnp.argmax(q[1:], axis=-1)
        next_v = jnp.take_along_axis(q_target[1:], a_star[..., None], -1)[..., 0]
        y = rew_l[:-1] + disc_l[:-1] * jax.lax.stop_gradient(next_v)
        q_taken = jnp.take_along_axis(q[:-1], act_l[:-1][..., None], -1)[..., 0]
        td = (y - q_taken) * mask_l[:-1]

        w = importance_weights(jnp.asarray(sample.info.probabilities),
                               cfg.importance_beta)
        loss = 0.5 * jnp.sum(w[None, :] * jnp.square(td)) / jnp.maximum(
            jnp.sum(mask_l[:-1]), 1.0)
        abs_td = jnp.abs(td)
        prio = cfg.priority_eta * jnp.max(abs_td, axis=0) + \
            (1 - cfg.priority_eta) * jnp.mean(abs_td, axis=0)
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


def make_behavior_policy(spec: EnvironmentSpec, cfg: R2D2Config,
                         epsilon=None):
    net = make_network(spec, cfg)
    eps = cfg.epsilon if epsilon is None else epsilon

    def policy(params, key, obs, lstm_state):
        obs = flatten_obs(obs, spec.observations.shape)
        q, new_state = net.apply(params, obs, lstm_state)
        greedy = jnp.argmax(q[0])
        rand = jax.random.randint(key, (), 0, spec.actions.num_values)
        explore = jax.random.uniform(key) < eps
        return jnp.where(explore, rand, greedy).astype(jnp.int32), new_state

    return policy


class R2D2Builder(AgentBuilder):
    def __init__(self, spec: EnvironmentSpec, cfg: R2D2Config = None,
                 seed: int = 0):
        cfg = cfg or R2D2Config()
        super().__init__(BuilderOptions(
            variable_update_period=10,
            min_observations=cfg.min_replay_size,
            observations_per_step=max(float(cfg.period), 1.0),
            batch_size=cfg.batch_size))
        self.spec = spec
        self.cfg = cfg
        self.seed = seed

    def make_replay(self):
        from repro import replay as r
        cfg = self.cfg
        if cfg.samples_per_insert > 0:
            limiter = r.SampleToInsertRatio(
                cfg.samples_per_insert, cfg.min_replay_size // cfg.period + 1,
                error_buffer=max(2 * cfg.samples_per_insert * cfg.batch_size, 100))
        else:
            limiter = r.MinSize(max(cfg.min_replay_size // cfg.period, 1))
        return r.Table("replay", cfg.max_replay_size, r.Prioritized(), limiter)

    def make_adder(self, table):
        from repro.adders.sequence import SequenceAdder
        return SequenceAdder(table, self.cfg.sequence_length,
                             period=self.cfg.period, priority=100.0)

    def make_dataset(self, table):
        from repro.replay import as_iterator
        return as_iterator(table, self.cfg.batch_size)

    def make_learner(self, iterator, priority_update_cb=None):
        return make_learner(self.spec, self.cfg, iterator,
                            jax.random.key(self.seed),
                            priority_update_cb=priority_update_cb)

    def make_policy(self, evaluation: bool = False):
        return make_behavior_policy(self.spec, self.cfg,
                                    epsilon=0.0 if evaluation else None)

    def make_actor(self, policy, variable_client, adder, seed: int = 0):
        from repro.core import RecurrentActor
        net = make_network(self.spec, self.cfg)
        return RecurrentActor(policy, lambda: net.initial_state(1),
                              variable_client, adder, rng_seed=seed)

    def make_batched_actor(self, policy, variable_client, adders,
                           seed: int = 0):
        from repro.core import BatchedRecurrentActor
        net = make_network(self.spec, self.cfg)
        return BatchedRecurrentActor(policy, lambda: net.initial_state(1),
                                     variable_client, adders, rng_seed=seed)

"""IMPALA (§3.3): advantage actor-critic with V-trace off-policy correction.

Data flows through a FIFO queue (non-overlapping sequences, processed in
order) exactly as the paper describes; the V-trace recursion runs through the
pure-jnp ``kernels/ref.py`` scan (the Pallas ``vtrace_kernel`` does not
compile for the TPU).
The behaviour logits are stored by the actor as extras so the learner can
form the importance ratios.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.agents.common import JaxLearner, LearnerState, fresh_copy
from repro.builders import AgentBuilder, BuilderOptions
from repro.core.actors import (STEP_MOD, BatchedFeedForwardActor,
                               _folded_policy)
from repro.core.types import EnvironmentSpec
from repro.kernels import ref as kernels_ref
from repro.networks.mlp import flatten_obs, mlp_apply, mlp_init
from repro.replay.dataset import ReplaySample


@dataclasses.dataclass
class IMPALAConfig:
    hidden: int = 64
    learning_rate: float = 6e-4
    discount: float = 0.99
    sequence_length: int = 20
    batch_size: int = 16
    entropy_cost: float = 0.01
    baseline_cost: float = 0.5
    max_queue_size: int = 1000
    clip_rho: float = 1.0
    clip_c: float = 1.0


def make_network(spec: EnvironmentSpec, cfg: IMPALAConfig):
    num_actions = spec.actions.num_values
    in_dim = int(np.prod(spec.observations.shape)) or 1

    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "torso": mlp_init(k1, (in_dim, cfg.hidden, cfg.hidden)),
            "policy": mlp_init(k2, (cfg.hidden, num_actions)),
            "value": mlp_init(k3, (cfg.hidden, 1)),
        }

    def apply(params, obs):
        h = mlp_apply(params["torso"], obs, activate_final=True)
        return mlp_apply(params["policy"], h), mlp_apply(params["value"], h)[..., 0]

    return init, apply, in_dim, num_actions


def make_learner(spec: EnvironmentSpec, cfg: IMPALAConfig, iterator: Iterator,
                 rng_key) -> JaxLearner:
    init, apply, in_dim, num_actions = make_network(spec, cfg)
    opt = optim.adam(cfg.learning_rate, clip=40.0)
    params = init(rng_key)
    state = LearnerState(params, (), opt.init(params), jnp.zeros((), jnp.int32))

    def loss_fn(params, sample: ReplaySample):
        seq = sample.data                          # dict of (B, T, ...)
        obs = seq["observation"].astype(jnp.float32)
        B, T = obs.shape[:2]
        flat = obs.reshape(B * T, -1)
        logits, values = apply(params, flat)
        logits = logits.reshape(B, T, num_actions)
        values = values.reshape(B, T)
        actions = seq["action"].astype(jnp.int32)
        rewards = seq["reward"].astype(jnp.float32)
        discounts = seq["discount"].astype(jnp.float32) * cfg.discount
        mask = seq["mask"].astype(jnp.float32)
        behavior_logits = seq["behavior_logits"].astype(jnp.float32)

        # time-major, learner vs behaviour importance ratios
        def tm(x):
            return jnp.swapaxes(x, 0, 1)
        logp = jax.nn.log_softmax(logits)
        logp_a = jnp.take_along_axis(logp, actions[..., None], -1)[..., 0]
        blogp = jax.nn.log_softmax(behavior_logits)
        blogp_a = jnp.take_along_axis(blogp, actions[..., None], -1)[..., 0]
        rhos = jnp.exp(logp_a - blogp_a)

        # bootstrap: V(o_{t+1}) approximated by shifting values
        next_values = jnp.concatenate(
            [values[:, 1:], values[:, -1:]], axis=1)
        vs, pg_adv = kernels_ref.vtrace_ref(
            tm(values), tm(next_values), tm(rewards),
            tm(discounts), tm(jax.lax.stop_gradient(rhos)),
            clip_rho=cfg.clip_rho, clip_c=cfg.clip_c)
        vs, pg_adv = tm(vs), tm(pg_adv)

        m = mask
        pg_loss = -jnp.sum(logp_a * jax.lax.stop_gradient(pg_adv) * m) / jnp.sum(m)
        v_loss = 0.5 * jnp.sum(jnp.square(jax.lax.stop_gradient(vs) - values) * m) \
            / jnp.sum(m)
        probs = jax.nn.softmax(logits)
        entropy = -jnp.sum(jnp.sum(probs * logp, -1) * m) / jnp.sum(m)
        loss = pg_loss + cfg.baseline_cost * v_loss - cfg.entropy_cost * entropy
        return loss, {"loss": loss, "pg_loss": pg_loss, "v_loss": v_loss,
                      "entropy": entropy}

    def update(state: LearnerState, sample: ReplaySample):
        grads, metrics = jax.grad(loss_fn, has_aux=True)(state.params, sample)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optim.apply_updates(state.params, updates)
        return (LearnerState(params, (), opt_state, state.steps + 1),
                metrics, None)

    return JaxLearner(state, update, iterator)


def make_behavior_policy(spec: EnvironmentSpec, cfg: IMPALAConfig):
    _, apply, _, num_actions = make_network(spec, cfg)

    def policy(params, key, obs):
        obs = flatten_obs(obs, spec.observations.shape)
        logits, _ = apply(params, obs)
        action = jax.random.categorical(key, logits[0])
        return action.astype(jnp.int32), logits[0]

    return policy


class IMPALAActor:
    """Feed-forward actor that also records behaviour logits as extras."""

    def __init__(self, policy, variable_client, adder, rng_seed=0):
        self._policy = jax.jit(_folded_policy(policy))
        self._client = variable_client
        self._adder = adder
        self._key = jax.random.key(rng_seed)
        self._steps = 0
        self._last_logits = None

    def select_action(self, observation):
        action, logits = self._policy(self._client.params, self._key,
                                      self._steps, jnp.asarray(observation))
        self._steps = (self._steps + 1) % STEP_MOD
        self._last_logits = np.asarray(logits)
        return np.asarray(action)

    def observe_first(self, timestep):
        if self._adder:
            self._adder.add_first(timestep)

    def observe(self, action, next_timestep):
        if self._adder:
            self._adder.add(action, next_timestep,
                            extras={"behavior_logits": self._last_logits})

    def update(self, wait=False):
        self._client.update(wait)


class BatchedIMPALAActor(BatchedFeedForwardActor):
    """Vectorized IMPALA acting: one vmapped dispatch returns N (action,
    logits) pairs; each env's behaviour logits ride into its own adder."""

    def __init__(self, policy, variable_client, adders, rng_seed=0):
        super().__init__(policy, variable_client, adders, rng_seed=rng_seed)
        self._last_logits = None

    def select_action(self, observation):
        actions, logits = self._run_policy(observation)
        self._last_logits = np.asarray(logits)
        return np.asarray(actions)

    def observe(self, action, next_timestep, env_id: int = 0):
        adder = self._adder(env_id)
        if adder:
            adder.add(action, next_timestep,
                      extras={"behavior_logits": self._last_logits[env_id]})


class IMPALABuilder(AgentBuilder):
    def __init__(self, spec: EnvironmentSpec, cfg: IMPALAConfig = None,
                 seed: int = 0):
        cfg = cfg or IMPALAConfig()
        # near on-policy: sync weights every step; step the learner as soon
        # as the queue holds a full batch (the Agent's can_step guard
        # prevents blocking on a short queue).
        super().__init__(BuilderOptions(
            variable_update_period=1,
            min_observations=cfg.sequence_length * cfg.batch_size,
            observations_per_step=1.0,
            batch_size=cfg.batch_size))
        self.spec = spec
        self.cfg = cfg
        self.seed = seed

    def make_replay(self):
        from repro import replay as r
        return r.Table("queue", self.cfg.max_queue_size, r.Fifo(),
                       r.MinSize(self.cfg.batch_size))

    def make_adder(self, table):
        from repro.adders.sequence import SequenceAdder
        return SequenceAdder(table, self.cfg.sequence_length,
                             period=self.cfg.sequence_length)

    def make_dataset(self, table):
        from repro.replay import as_iterator
        return as_iterator(table, self.cfg.batch_size)

    def make_learner(self, iterator, priority_update_cb=None):
        return make_learner(self.spec, self.cfg, iterator,
                            jax.random.key(self.seed))

    def make_policy(self, evaluation: bool = False):
        return make_behavior_policy(self.spec, self.cfg)

    def make_actor(self, policy, variable_client, adder, seed: int = 0):
        return IMPALAActor(policy, variable_client, adder, rng_seed=seed)

    def make_batched_actor(self, policy, variable_client, adders,
                           seed: int = 0):
        return BatchedIMPALAActor(policy, variable_client, adders,
                                  rng_seed=seed)

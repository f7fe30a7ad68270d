"""DQN (§3.2): double Q-learning, n-step targets (via the adder), dueling
heads, prioritized replay with importance weighting — the paper's enhanced
("in the spirit of Rainbow") implementation."""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.agents.common import JaxLearner, LearnerState, importance_weights
from repro.builders import AgentBuilder, BuilderOptions
from repro.core.types import EnvironmentSpec
from repro.networks import heads as heads_lib
from repro.networks.mlp import flatten_obs, mlp_apply, mlp_init
from repro.replay.dataset import ReplaySample


@dataclasses.dataclass
class DQNConfig:
    hidden: int = 64
    dueling: bool = True
    learning_rate: float = 1e-3
    discount: float = 0.99
    n_step: int = 3
    target_update_period: int = 100
    epsilon: float = 0.1
    batch_size: int = 64
    min_replay_size: int = 200
    max_replay_size: int = 100_000
    samples_per_insert: float = 4.0
    importance_beta: float = 0.6
    prioritized: bool = True


def make_q_network(spec: EnvironmentSpec, cfg: DQNConfig):
    num_actions = spec.actions.num_values
    in_dim = int(np.prod(spec.observations.shape)) or 1

    def init(key):
        k1, k2 = jax.random.split(key)
        p = {"torso": mlp_init(k1, (in_dim, cfg.hidden, cfg.hidden))}
        if cfg.dueling:
            p["head"] = heads_lib.dueling_init(k2, cfg.hidden, cfg.hidden,
                                               num_actions)
        else:
            p["head"] = {"q": mlp_init(k2, (cfg.hidden, num_actions))}
        return p

    def apply(params, obs):
        h = mlp_apply(params["torso"], obs, activate_final=True)
        if cfg.dueling:
            return heads_lib.dueling_apply(params["head"], h)
        return mlp_apply(params["head"]["q"], h)

    return init, apply, in_dim, num_actions


def make_learner(spec: EnvironmentSpec, cfg: DQNConfig, iterator: Iterator,
                 rng_key, priority_update_cb=None) -> JaxLearner:
    init, apply, in_dim, num_actions = make_q_network(spec, cfg)
    opt = optim.adam(cfg.learning_rate, clip=40.0)
    params = init(rng_key)
    state = LearnerState(params, params, opt.init(params),
                         jnp.zeros((), jnp.int32))

    def loss_fn(params, target_params, sample: ReplaySample):
        t = sample.data
        obs = flatten_obs(t.observation, spec.observations.shape)
        next_obs = flatten_obs(t.next_observation, spec.observations.shape)
        q = apply(params, obs)
        q_next_online = apply(params, next_obs)
        q_next_target = apply(target_params, next_obs)
        a_star = jnp.argmax(q_next_online, axis=-1)
        next_v = jnp.take_along_axis(q_next_target, a_star[:, None], -1)[:, 0]
        y = t.reward + t.discount * jax.lax.stop_gradient(next_v)
        q_taken = jnp.take_along_axis(q, t.action[:, None].astype(jnp.int32),
                                      -1)[:, 0]
        td = y - q_taken
        if cfg.prioritized:
            w = importance_weights(jnp.asarray(sample.info.probabilities),
                                   cfg.importance_beta)
        else:
            w = jnp.ones_like(td)
        loss = 0.5 * jnp.mean(w * jnp.square(td))
        return loss, td

    def update(state: LearnerState, sample: ReplaySample):
        (loss, td), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, state.target_params, sample)
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optim.apply_updates(state.params, updates)
        steps = state.steps + 1
        target = optim.periodic_update(params, state.target_params, steps,
                                       cfg.target_update_period)
        new_state = LearnerState(params, target, opt_state, steps)
        priorities = jnp.abs(td)
        return new_state, {"loss": loss}, priorities

    return JaxLearner(state, update, iterator,
                      priority_update_cb=priority_update_cb if cfg.prioritized
                      else None)


def make_behavior_policy(spec: EnvironmentSpec, cfg: DQNConfig,
                         epsilon: Optional[float] = None):
    _, apply, _, num_actions = make_q_network(spec, cfg)
    eps = cfg.epsilon if epsilon is None else epsilon

    def policy(params, key, obs):
        obs = flatten_obs(obs, spec.observations.shape)
        q = apply(params, obs)[0]
        greedy = jnp.argmax(q)
        rand = jax.random.randint(key, (), 0, num_actions)
        explore = jax.random.uniform(key) < eps
        return jnp.where(explore, rand, greedy).astype(jnp.int32)

    return policy


def make_eval_policy(spec: EnvironmentSpec, cfg: DQNConfig):
    return make_behavior_policy(spec, cfg, epsilon=0.0)


class DQNBuilder(AgentBuilder):
    """Typed builder (repro.builders.AgentBuilder) for DQN."""

    def __init__(self, spec: EnvironmentSpec, cfg: DQNConfig = None,
                 seed: int = 0, spi_tolerance: float = None):
        from repro import replay as replay_lib
        cfg = cfg or DQNConfig()
        super().__init__(BuilderOptions(
            variable_update_period=10,
            min_observations=cfg.min_replay_size,
            observations_per_step=max(
                cfg.batch_size / cfg.samples_per_insert, 1.0)
            if cfg.samples_per_insert > 0 else 1.0,
            batch_size=cfg.batch_size))
        self.spec = spec
        self.cfg = cfg
        self.seed = seed
        self._replay_lib = replay_lib
        self.spi_tolerance = spi_tolerance

    def make_replay(self):
        r = self._replay_lib
        cfg = self.cfg
        tol = self.spi_tolerance
        if cfg.samples_per_insert > 0:
            limiter = r.SampleToInsertRatio(
                cfg.samples_per_insert, cfg.min_replay_size,
                error_buffer=tol if tol is not None
                else max(cfg.samples_per_insert * 2 * cfg.batch_size, 100.0))
        else:
            limiter = r.MinSize(cfg.min_replay_size)
        selector = r.Prioritized() if cfg.prioritized else r.Uniform(self.seed)
        return r.Table("replay", cfg.max_replay_size, selector, limiter)

    def make_adder(self, table):
        from repro.adders import NStepTransitionAdder
        return NStepTransitionAdder(table, self.cfg.n_step, self.cfg.discount,
                                    priority=100.0)

    def make_dataset(self, table):
        from repro.replay import as_iterator
        return as_iterator(table, self.cfg.batch_size)

    def make_learner(self, iterator, priority_update_cb=None):
        import jax
        return make_learner(self.spec, self.cfg, iterator,
                            jax.random.key(self.seed),
                            priority_update_cb=priority_update_cb)

    def make_policy(self, evaluation: bool = False):
        if evaluation:
            return make_eval_policy(self.spec, self.cfg)
        return make_behavior_policy(self.spec, self.cfg)

    def make_actor(self, policy, variable_client, adder, seed: int = 0):
        from repro.core import FeedForwardActor
        return FeedForwardActor(policy, variable_client, adder, rng_seed=seed)

"""Continuous-control actor-critic agents (§3.4): DDPG, D4PG, MPO, DMPO.

All four share: n-step transition replay (uniform sampling — the paper found
prioritization gives minimal benefit here), Gaussian exploration noise,
target networks.  They differ in the policy loss (deterministic PG vs MPO's
EM) and the critic (expected vs C51 distributional).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.agents.common import JaxLearner, LearnerState
from repro.builders import AgentBuilder, BuilderOptions
from repro.core.types import EnvironmentSpec
from repro.networks.heads import l2_project
from repro.networks.mlp import flatten_obs, mlp_apply, mlp_init
from repro.replay.dataset import ReplaySample


@dataclasses.dataclass
class ContinuousConfig:
    algo: str = "d4pg"            # ddpg | d4pg | mpo | dmpo
    hidden: int = 256
    policy_lr: float = 1e-3
    critic_lr: float = 1e-3
    discount: float = 0.99
    n_step: int = 5
    batch_size: int = 256
    min_replay_size: int = 1000
    max_replay_size: int = 1_000_000
    samples_per_insert: float = 32.0
    sigma: float = 0.2            # exploration noise
    target_update_period: int = 100
    # distributional critic
    num_atoms: int = 51
    vmin: float = 0.0
    vmax: float = 1000.0
    # mpo duals
    mpo_epsilon: float = 0.1
    mpo_eps_mean: float = 1e-2
    mpo_eps_std: float = 1e-5
    mpo_samples: int = 16


def _distributional(cfg):
    return cfg.algo in ("d4pg", "dmpo")


def _mpo_family(cfg):
    return cfg.algo in ("mpo", "dmpo")


def make_networks(spec: EnvironmentSpec, cfg: ContinuousConfig):
    obs_dim = int(np.prod(spec.observations.shape)) or 1
    act_dim = int(np.prod(spec.actions.shape)) or 1
    critic_out = cfg.num_atoms if _distributional(cfg) else 1
    policy_out = 2 * act_dim if _mpo_family(cfg) else act_dim

    def init(key):
        k1, k2 = jax.random.split(key)
        return {
            "policy": mlp_init(k1, (obs_dim, cfg.hidden, cfg.hidden, policy_out)),
            "critic": mlp_init(k2, (obs_dim + act_dim, cfg.hidden, cfg.hidden,
                                    critic_out)),
            "log_temp": jnp.zeros(()),          # MPO duals
            "log_alpha_mean": jnp.zeros(()),
            "log_alpha_std": jnp.zeros(()),
        }

    def policy_dist(params, obs):
        out = mlp_apply(params["policy"], obs)
        if _mpo_family(cfg):
            mean, raw = jnp.split(out, 2, axis=-1)
            return jnp.tanh(mean), jax.nn.softplus(raw) + 1e-3
        return jnp.tanh(out), None

    def critic(params, obs, act):
        h = jnp.concatenate([obs, act], axis=-1)
        out = mlp_apply(params["critic"], h)
        if _distributional(cfg):
            return out                           # logits over atoms
        return out[..., 0]

    return init, policy_dist, critic, obs_dim, act_dim


def make_learner(spec: EnvironmentSpec, cfg: ContinuousConfig,
                 iterator: Iterator, rng_key) -> JaxLearner:
    init, policy_dist, critic, obs_dim, act_dim = make_networks(spec, cfg)
    popt = optim.adam(cfg.policy_lr, clip=40.0)
    copt = optim.adam(cfg.critic_lr, clip=40.0)
    params = init(rng_key)
    opt_state = (popt.init(params), copt.init(params))
    state = LearnerState(params, params, opt_state,
                         jnp.zeros((), jnp.int32))
    atoms = jnp.linspace(cfg.vmin, cfg.vmax, cfg.num_atoms)

    def q_mean(params, obs, act):
        out = critic(params, obs, act)
        if _distributional(cfg):
            return jnp.sum(jax.nn.softmax(out, -1) * atoms, -1)
        return out

    def critic_loss(params, target_params, t, key):
        obs = flatten_obs(t.observation, spec.observations.shape)
        nobs = flatten_obs(t.next_observation, spec.observations.shape)
        act = t.action.reshape(obs.shape[0], -1)
        nmean, nstd = policy_dist(target_params, nobs)
        if nstd is not None:
            na = nmean + nstd * jax.random.normal(key, nmean.shape)
            na = jnp.clip(na, -1, 1)
        else:
            na = nmean
        if _distributional(cfg):
            target_logits = critic(target_params, nobs, na)
            target_p = jax.nn.softmax(target_logits, -1)
            z_target = t.reward[:, None] + t.discount[:, None] * atoms[None, :]
            proj = l2_project(z_target, target_p, atoms)
            logits = critic(params, obs, act)
            logp = jax.nn.log_softmax(logits, -1)
            loss = -jnp.mean(jnp.sum(jax.lax.stop_gradient(proj) * logp, -1))
        else:
            nq = critic(target_params, nobs, na)
            y = t.reward + t.discount * jax.lax.stop_gradient(nq)
            q = critic(params, obs, act)
            loss = 0.5 * jnp.mean(jnp.square(y - q))
        return loss

    def dpg_policy_loss(params, target_params, t):
        obs = flatten_obs(t.observation, spec.observations.shape)
        mean, _ = policy_dist(params, obs)
        q = q_mean(params, obs, mean)
        return -jnp.mean(q)

    def mpo_policy_loss(params, target_params, t, key):
        """Simplified MPO E/M steps with temperature + KL-alpha duals."""
        obs = flatten_obs(t.observation, spec.observations.shape)
        B = obs.shape[0]
        tmean, tstd = policy_dist(target_params, obs)
        k1, k2 = jax.random.split(key)
        samples = tmean[None] + tstd[None] * jax.random.normal(
            k1, (cfg.mpo_samples, B, act_dim))            # (S, B, A)
        samples = jnp.clip(samples, -1, 1)
        q = jax.vmap(lambda a: q_mean(target_params, obs, a))(samples)  # (S, B)
        temp = jnp.exp(params["log_temp"]) + 1e-8
        # E-step: weights + temperature dual loss
        logw = jax.nn.log_softmax(jax.lax.stop_gradient(q) / temp, axis=0)
        w = jnp.exp(logw)
        temp_loss = temp * (cfg.mpo_epsilon + jnp.mean(
            jax.nn.logsumexp(jax.lax.stop_gradient(q) / temp, axis=0)
            - jnp.log(cfg.mpo_samples)))
        # M-step: weighted max-likelihood under the online policy
        mean, std = policy_dist(params, obs)
        logp = -0.5 * jnp.sum(
            jnp.square((samples - mean[None]) / std[None])
            + 2 * jnp.log(std[None]), axis=-1)            # (S, B)
        ml_loss = -jnp.mean(jnp.sum(jax.lax.stop_gradient(w) * logp, axis=0))
        # decoupled KL regularization to the target policy
        kl_mean = jnp.mean(0.5 * jnp.sum(
            jnp.square((mean - tmean) / tstd), axis=-1))
        kl_std = jnp.mean(jnp.sum(
            jnp.log(std / tstd) + (jnp.square(tstd) /
                                   (2 * jnp.square(std))) - 0.5, axis=-1))
        a_mean = jnp.exp(params["log_alpha_mean"])
        a_std = jnp.exp(params["log_alpha_std"])
        alpha_mean_loss = a_mean * (cfg.mpo_eps_mean -
                                    jax.lax.stop_gradient(kl_mean))
        alpha_std_loss = a_std * (cfg.mpo_eps_std -
                                  jax.lax.stop_gradient(kl_std))
        policy_loss = ml_loss \
            + jax.lax.stop_gradient(a_mean) * kl_mean \
            + jax.lax.stop_gradient(a_std) * kl_std
        return policy_loss + temp_loss + alpha_mean_loss + alpha_std_loss

    def total_loss(params, target_params, t, key):
        k1, k2 = jax.random.split(key)
        cl = critic_loss(params, target_params, t, k1)
        if _mpo_family(cfg):
            pl = mpo_policy_loss(params, target_params, t, k2)
        else:
            pl = dpg_policy_loss(params, target_params, t)
        return cl + pl, {"critic_loss": cl, "policy_loss": pl}

    def update(state: LearnerState, sample: ReplaySample):
        t = sample.data
        key = jax.random.fold_in(jax.random.key(17), state.steps)
        grads, metrics = jax.grad(total_loss, has_aux=True)(
            state.params, state.target_params, t, key)
        p_opt, c_opt = state.opt_state
        pupd, p_opt = popt.update(grads, p_opt, state.params)
        params = optim.apply_updates(state.params, pupd)
        steps = state.steps + 1
        target = optim.periodic_update(params, state.target_params, steps,
                                       cfg.target_update_period)
        metrics["loss"] = metrics["critic_loss"] + metrics["policy_loss"]
        return (LearnerState(params, target, (p_opt, c_opt), steps),
                metrics, None)

    return JaxLearner(state, update, iterator)


def make_behavior_policy(spec: EnvironmentSpec, cfg: ContinuousConfig,
                         evaluation: bool = False):
    init, policy_dist, critic, obs_dim, act_dim = make_networks(spec, cfg)

    def policy(params, key, obs):
        obs = flatten_obs(obs, spec.observations.shape)
        mean, std = policy_dist(params, obs)
        a = mean[0]
        if not evaluation:
            noise = cfg.sigma if std is None else std[0]
            a = a + noise * jax.random.normal(key, a.shape)
        return jnp.clip(a, -1.0, 1.0)

    return policy


class ContinuousBuilder(AgentBuilder):
    def __init__(self, spec: EnvironmentSpec, cfg: ContinuousConfig = None,
                 seed: int = 0):
        cfg = cfg or ContinuousConfig()
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

    def make_replay(self):
        from repro import replay as r
        cfg = self.cfg
        if cfg.samples_per_insert > 0:
            limiter = r.SampleToInsertRatio(
                cfg.samples_per_insert, cfg.min_replay_size,
                error_buffer=max(2 * cfg.samples_per_insert * cfg.batch_size, 1000))
        else:
            limiter = r.MinSize(cfg.min_replay_size)
        return r.Table("replay", cfg.max_replay_size, r.Uniform(self.seed),
                       limiter)

    def make_adder(self, table):
        from repro.adders import NStepTransitionAdder
        return NStepTransitionAdder(table, self.cfg.n_step, self.cfg.discount)

    def make_dataset(self, table):
        from repro.replay import as_iterator
        return as_iterator(table, self.cfg.batch_size)

    def make_learner(self, iterator, priority_update_cb=None):
        return make_learner(self.spec, self.cfg, iterator,
                            jax.random.key(self.seed))

    def make_policy(self, evaluation: bool = False):
        return make_behavior_policy(self.spec, self.cfg, evaluation)

    def make_actor(self, policy, variable_client, adder, seed: int = 0):
        from repro.core import FeedForwardActor
        return FeedForwardActor(policy, variable_client, adder, rng_seed=seed)


def builder_for(algo: str, spec: EnvironmentSpec, seed: int = 0,
                **overrides) -> ContinuousBuilder:
    cfg = ContinuousConfig(algo=algo, **overrides)
    return ContinuousBuilder(spec, cfg, seed)

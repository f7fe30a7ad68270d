"""The ``dense`` family: a plain reference of the transformer Q-policy and
its sequence double-DQN learner, written from the published block
description (Llama-style pre-norm: RMSNorm, rotary attention with grouped KV
heads, SwiGLU MLP), and the operations its learner step needs.  It imports
nothing of the program under test.

The benchmark makes the weights here, from the seed, and hands them to the
program; the reference regenerates them from the same seed.  Everything runs
in float32, every matmul at ``highest`` precision.  ``dtype=jnp.bfloat16``
is the control, the precision one step below the configuration's float32:
the weights are held in bfloat16 (rounded again after every update) and
every matmul's operands are rounded to bfloat16.

The parameter tree has the layout the program consumes:
``obs_proj/{w,b}``, ``blocks/{ln1,ln2}/scale``, ``blocks/attn/{wq,wk,wv,wo}``,
``blocks/mlp/{w_gate,w_up,w_down}`` (each stacked over layers on axis 0),
``final_norm/scale`` and ``head``.

Operation counts are of the algorithm, not of an implementation: attention
counts each query against the keys it may see (the banded, causal window),
pad rows count nothing, and nothing recomputed is counted twice.  A
multiply and an add are two operations.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30


class Arch(NamedTuple):
    """The shapes and constants a configuration file states."""
    obs_dim: int
    num_actions: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    num_layers: int
    window: int
    rope_theta: float
    rms_norm_eps: float


def arch_from_config(config: dict) -> Arch:
    return Arch(
        obs_dim=int(np.prod(config["observation_shape"])),
        num_actions=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]),
        num_layers=int(config["num_hidden_layers"]),
        window=int(config["max_position_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
    )


def policy_fields(config: dict) -> dict:
    """The program's ``TransformerPolicyConfig`` fields for this model."""
    return dict(
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        window=config["max_position_embeddings"],
    )


# ------------------------------------------------------------------ weights
def _normal(key, shape, fan_in):
    return fan_in ** -0.5 * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)


def _make_params(key, arch: Arch):
    d, h, kv, hd, ff, n = (arch.d_model, arch.num_heads, arch.num_kv_heads,
                           arch.head_dim, arch.d_ff, arch.num_layers)
    ks = jax.random.split(key, 9)
    return {
        "obs_proj": {"w": _normal(ks[0], (arch.obs_dim, d), arch.obs_dim),
                     "b": jnp.zeros((d,), jnp.float32)},
        "blocks": {
            "ln1": {"scale": jnp.ones((n, d), jnp.float32)},
            "attn": {"wq": _normal(ks[1], (n, d, h, hd), d),
                     "wk": _normal(ks[2], (n, d, kv, hd), d),
                     "wv": _normal(ks[3], (n, d, kv, hd), d),
                     "wo": _normal(ks[4], (n, h, hd, d), h * hd)},
            "ln2": {"scale": jnp.ones((n, d), jnp.float32)},
            "mlp": {"w_gate": _normal(ks[5], (n, d, ff), d),
                    "w_up": _normal(ks[6], (n, d, ff), d),
                    "w_down": _normal(ks[7], (n, ff, d), ff)},
        },
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "head": _normal(ks[8], (d, arch.num_actions), d),
    }


def make_params(seed: int, arch: Arch):
    """The weights of a run, on the default device, in one jitted call."""
    fn = jax.jit(_make_params, static_argnums=1)
    return fn(jax.random.key(seed), arch)


# ------------------------------------------------------------------ forward
def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: (B, T, heads, hd), positions 0..T-1; rotate-half convention."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    angles = np.arange(x.shape[1])[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(angles), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _rounded(x, dtype):
    """``x`` rounded to ``dtype`` and held in float32."""
    if dtype == jnp.float32:
        return x
    return x.astype(dtype).astype(jnp.float32)


def q_values(params, arch: Arch, obs, dtype=jnp.float32):
    """Q-values at every position: obs (B, T, obs_dim) -> (B, T, A).

    Position t attends to positions t-window+1 .. t of its own row (causal,
    banded at the policy window).  Every matmul takes its operands rounded
    to ``dtype`` and multiplies them exactly, accumulating in float32; all
    else is float32."""

    def ein(spec, a, b):
        return jnp.einsum(spec, _rounded(a, dtype), _rounded(b, dtype),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    p = params
    x = ein("bti,id->btd", obs.astype(jnp.float32), p["obs_proj"]["w"]) \
        + p["obs_proj"]["b"]
    T = x.shape[1]
    t = np.arange(T)
    band = (t[:, None] >= t[None, :]) & (t[:, None] - t[None, :] < arch.window)
    group = arch.num_heads // arch.num_kv_heads
    for layer in range(arch.num_layers):
        blk = jax.tree.map(lambda a: a[layer], p["blocks"])
        h = _rmsnorm(x, blk["ln1"]["scale"], arch.rms_norm_eps)
        q = _rope(ein("btd,dhk->bthk", h, blk["attn"]["wq"]), arch.rope_theta)
        k = _rope(ein("btd,dhk->bthk", h, blk["attn"]["wk"]), arch.rope_theta)
        v = ein("btd,dhk->bthk", h, blk["attn"]["wv"])
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        s = ein("bqhk,bshk->bhqs", q, k) * arch.head_dim ** -0.5
        a = jax.nn.softmax(jnp.where(band[None, None], s, NEG_INF), axis=-1)
        o = ein("bhqs,bshk->bqhk", a, v)
        x = x + ein("bthk,hkd->btd", o, blk["attn"]["wo"])
        h = _rmsnorm(x, blk["ln2"]["scale"], arch.rms_norm_eps)
        g = ein("btd,df->btf", h, blk["mlp"]["w_gate"])
        u = ein("btd,df->btf", h, blk["mlp"]["w_up"])
        x = x + ein("btf,fd->btd", jax.nn.silu(g) * u, blk["mlp"]["w_down"])
    x = _rmsnorm(x, p["final_norm"]["scale"], arch.rms_norm_eps)
    return ein("btd,da->bta", x, p["head"])


def last_q(params, arch: Arch, windows, lengths, dtype=jnp.float32):
    """Q at each window's newest frame: windows (n, W, obs_dim) left-aligned,
    lengths (n,) -> (n, A)."""
    q = q_values(params, arch, windows, dtype)
    return jnp.take_along_axis(q, (lengths - 1)[:, None, None], axis=1)[:, 0]


# ------------------------------------------------------------------ learner
def td_loss(params, target_params, arch: Arch, hp: dict, batch: dict,
            dtype=jnp.float32):
    """R2D2-style sequence double-Q loss with 1-step targets inside each
    replayed sequence, prioritized-replay importance weights, and the
    acting-parity mask (a sequence that does not start an episode trains
    only where its window is full).  Returns (loss, priorities)."""
    obs = batch["observation"].astype(jnp.float32)
    B, T = obs.shape[:2]
    obs = obs.reshape(B, T, -1)
    actions = batch["action"].astype(jnp.int32)
    rewards = batch["reward"].astype(jnp.float32)
    discounts = batch["discount"].astype(jnp.float32) * hp["discount"]
    mask = batch["mask"].astype(jnp.float32)
    q = q_values(params, arch, obs, dtype)
    q_target = q_values(target_params, arch, obs, dtype)
    best = jnp.argmax(q[:, 1:], axis=-1)
    next_v = jnp.take_along_axis(q_target[:, 1:], best[..., None], -1)[..., 0]
    y = rewards[:, :-1] + discounts[:, :-1] * jax.lax.stop_gradient(next_v)
    q_taken = jnp.take_along_axis(q[:, :-1], actions[:, :-1, None], -1)[..., 0]
    starts = batch["start_of_episode"][:, :1].astype(jnp.float32)
    full = (np.arange(T - 1) >= arch.window - 1).astype(np.float32)[None]
    valid = mask[:, :-1] * jnp.clip(starts + full, 0.0, 1.0)
    td = (y - q_taken) * valid
    w = (1.0 / jnp.maximum(batch["probabilities"].astype(jnp.float32),
                           1e-12)) ** hp["importance_beta"]
    w = w / jnp.max(w)
    loss = 0.5 * jnp.sum(w[:, None] * td * td) / jnp.maximum(jnp.sum(valid),
                                                             1.0)
    abs_td = jnp.abs(td)
    eta = hp["priority_eta"]
    return loss, eta * abs_td.max(axis=1) + (1 - eta) * abs_td.mean(axis=1)


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def sgd_step(params, target_params, opt, arch, hp_items, dtype, batch):
    """One Adam step; the weights are held in ``dtype``."""
    hp = dict(hp_items)
    (loss, priorities), grads = jax.value_and_grad(td_loss, has_aux=True)(
        params, target_params, arch, hp, batch, dtype)
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    scale = jnp.minimum(1.0, hp["clip"] / (_global_norm(grads) + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    step, mu, nu = opt
    step = step + 1
    b1, b2 = 0.9, 0.999
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    lr = hp["learning_rate"]

    def apply(p, m, v):
        m_hat = m / (1 - b1 ** step)
        v_hat = v / (1 - b2 ** step)
        return p - lr * m_hat / (jnp.sqrt(v_hat) + 1e-8)

    params = jax.tree.map(lambda *a: _rounded(apply(*a), dtype), params, mu,
                          nu)
    return params, (step, mu, nu), loss, grads, priorities


def train_three(params, arch: Arch, hp: dict, batches, dtype=jnp.float32):
    """Three SGD steps (Adam, clipped at ``hp['clip']``) from ``params`` on
    the given batches, the target network held at the start weights.
    Returns (losses, first clipped gradient, params after the last step,
    each step's priorities)."""
    params = jax.tree.map(lambda p: _rounded(p, dtype), params)
    target = params
    zeros = jax.tree.map(jnp.zeros_like, params)
    opt = (jnp.zeros((), jnp.float32), zeros, zeros)
    hp_items = tuple(sorted(hp.items()))
    losses, first_grad, priorities = [], None, []
    for batch in batches:
        params, opt, loss, grads, prio = sgd_step(params, target, opt, arch,
                                                   hp_items, dtype, batch)
        losses.append(float(loss))
        priorities.append(np.asarray(prio))
        if first_grad is None:
            first_grad = jax.device_get(grads)
        del grads
    return losses, first_grad, jax.device_get(params), priorities


# ------------------------------------------------------------------ counts
def matmul_weights(arch: Arch) -> int:
    """Weights each token multiplies through: projection, blocks, Q head."""
    d, h, kv = arch.d_model, arch.num_heads, arch.num_kv_heads
    hd = arch.head_dim
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * arch.d_ff
    return arch.obs_dim * d + arch.num_layers * per_layer \
        + d * arch.num_actions


def attention_flops(arch: Arch, keys_seen: int) -> int:
    """Scores and weighted values over ``keys_seen`` (query, key) pairs,
    summed over layers and heads."""
    return 4 * arch.num_layers * arch.num_heads * arch.head_dim * keys_seen


def forward_flops(arch: Arch, tokens: int, keys_seen: int) -> int:
    return 2 * matmul_weights(arch) * tokens + attention_flops(arch, keys_seen)


def banded_keys(arch: Arch, length: int) -> int:
    """(query, key) pairs of one causal row of ``length`` positions banded
    at the policy window."""
    return sum(min(t + 1, arch.window) for t in range(length))


def learner_step_flops(arch: Arch, batch: int, seq_len: int) -> int:
    """One SGD step: the online forward, the target forward and the backward
    of the online network (twice its forward)."""
    fwd = forward_flops(arch, batch * seq_len, batch * banded_keys(arch,
                                                                  seq_len))
    return 4 * fwd

"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=None, scale=None):
    """q: (b, h, sq, d); k, v: (b, h, sk, d). Plain softmax attention."""
    sq, sk = q.shape[2], k.shape[2]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(sq)[:, None]
        ki = jnp.arange(sk)[None, :]
        mask = qi >= ki
        if window is not None:
            mask &= (qi - ki) < window
        scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def decode_attention_ref(q, k, v, lengths):
    """q: (b, h, d); k, v: (b, s, kv, d) with ``h % kv == 0`` (query head i
    reads KV head ``i // (h // kv)``); lengths: (b,) valid prefix lengths."""
    b, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, kv, h // kv, d).astype(jnp.float32)
    scores = jnp.einsum("bngd,bsnd->bngs", qg, k.astype(jnp.float32)) \
        * d ** -0.5
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngs,bsnd->bngd", p, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def ssd_scan_ref(x, dt, A, B, C, chunk):
    """Chunked SSD — delegates to the model's pure-jnp implementation.

    x: (b, s, h, p); dt: (b, s, h); A: (h,); B, C: (b, s, n).
    Returns (y (b, s, h, p) float32, final_state (b, h, n, p) float32).
    """
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(x, dt, A, B, C, chunk)


def vtrace_ref(values, next_values, rewards, discounts, rhos,
               clip_rho: float = 1.0, clip_c: float = 1.0):
    """V-trace targets (Espeholt et al. 2018), time-major (T, B) inputs.

    vs_t = V_t + delta_t + gamma_t * c_t * (vs_{t+1} - V_{t+1}),
    delta_t = clipped_rho_t * (r_t + gamma_t * V_{t+1} - V_t).
    """
    rho_c = jnp.minimum(rhos, clip_rho)
    cs = jnp.minimum(rhos, clip_c)
    deltas = rho_c * (rewards + discounts * next_values - values)

    def body(acc, inp):
        delta, disc, c, nv = inp
        acc = delta + disc * c * acc
        return acc, acc

    T = values.shape[0]
    _, diffs = jax.lax.scan(
        body, jnp.zeros_like(values[0]),
        (deltas, discounts, cs, next_values), reverse=True)
    vs = values + diffs
    # policy-gradient advantages use vs_{t+1}
    vs_next = jnp.concatenate([vs[1:], next_values[-1:]], axis=0)
    pg_adv = rho_c * (rewards + discounts * vs_next - values)
    return vs, pg_adv

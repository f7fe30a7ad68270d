"""Flash attention (forward) as a Pallas TPU kernel.

TPU-native adaptation: the grid is (batch, heads, q_blocks, kv_blocks) with
the kv dimension marked "arbitrary" (sequential) so the online-softmax
accumulators (m, l, acc) live in VMEM scratch across kv steps.  Block shapes
are MXU-aligned (multiples of 128 on the matmul dims); causal/sliding-window
masking is applied with 2-D iotas inside the kernel.

This is the TARGET kernel for TPU; on this CPU container it is validated with
``interpret=True`` against :func:`repro.kernels.ref.flash_attention_ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, window, bq, bk, nk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                             # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                          # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                 # (bq, 1)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0, ...] = (acc_ref[...] /
                            jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (b, h, sq, d); k, v: (b, h, sk, d) -> (b, h, sq, d)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    nq, nk = sq // bq, sk // bk
    scale = scale if scale is not None else d ** -0.5

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk)
    grid = (b, h, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, q_, k_: (b_, h_, k_, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, q_, k_: (b_, h_, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running denom l
            pltpu.VMEM((bq, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)

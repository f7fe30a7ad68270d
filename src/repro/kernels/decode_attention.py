"""Single-token decode attention (flash-decoding) as a Pallas TPU kernel.

The serve-path hot spot: one query token per row against a KV cache.
Grid: (batch, kv_heads, kv_blocks).  GQA is folded into the blocking: each
grid step takes the ``q_per_kv`` query heads that share one KV head as a
``(q_per_kv, d)`` tile, so the cache is read at its own ``kv_heads`` width
and never repeated.  The kv dimension is sequential with the online-softmax
partials (m, l, acc) in VMEM scratch, so the cache is streamed HBM->VMEM
exactly once.  Variable-length caches are handled with a per-row
``lengths`` vector, scalar-prefetched into SMEM, masking the tail block.

Blocks keep the TPU tiling rule: the trailing two dims of every block are
either whole array dims or multiples of (8, 128).  The cache is therefore
viewed head-major, ``(b, kv, s, d)``, so a block is a ``(bk, d)`` slab.

Validated with ``interpret=True`` against ``ref.decode_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def kv_block(length: int, block_k: int) -> int:
    """The kv block the kernel uses over a cache of ``length`` slots.

    A cache no longer than ``block_k`` is one whole block; a longer one must
    split into ``block_k`` blocks, with ``block_k`` a multiple of 8 (the
    sublane tiling).  Any other length is refused, naming the shape.
    """
    if length <= block_k:
        return length
    if length % block_k or block_k % 8:
        raise ValueError(
            f"decode_attention cannot block a cache of length {length} with "
            f"block_k={block_k}: the length must be <= block_k or a multiple "
            f"of it, and block_k a multiple of 8")
    return block_k


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale, bk, nk):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (g, d)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (g, bk)

    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < len_ref[b], s, NEG_INF)

    m_prev = m_ref[...]                               # (g, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q, k, v, lengths, *, block_k: int = 512,
                     interpret: bool = False):
    """q: (b, h, d); k, v: (b, s, kv, d) cache layout with ``h % kv == 0``
    (query head i reads KV head ``i // (h // kv)``); lengths: (b,) int32
    valid prefix lengths.  Returns (b, h, d)."""
    b, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    if h % kv:
        raise ValueError(f"{h} query heads do not group over {kv} KV heads")
    g = h // kv
    bk = kv_block(s, block_k)
    nk = s // bk
    kernel = functools.partial(_decode_kernel, scale=d ** -0.5, bk=bk, nk=nk)
    qg = q.reshape(b, kv, g, d)
    kt = jnp.swapaxes(k, 1, 2)                       # (b, kv, s, d)
    vt = jnp.swapaxes(v, 1, 2)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv, nk),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b_, j, k_, lens: (b_, j, 0, 0)),
                pl.BlockSpec((1, 1, bk, d), lambda b_, j, k_, lens: (b_, j, k_, 0)),
                pl.BlockSpec((1, 1, bk, d), lambda b_, j, k_, lens: (b_, j, k_, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda b_, j, k_, lens: (b_, j, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),     # running max m
                pltpu.VMEM((g, 1), jnp.float32),     # running denom l
                pltpu.VMEM((g, d), jnp.float32),     # output accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(b, h, d)

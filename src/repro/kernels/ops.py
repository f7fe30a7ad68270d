"""Jitted public wrappers around the Pallas kernels.

Every wrapper compiles the Mosaic kernel for the TPU unless the caller
passes ``interpret=True``, which runs the kernel body through the Pallas
interpreter on any backend (how the CPU tests check them against
``ref.py``).  Nothing here picks interpret mode by itself.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.ssd_scan import ssd_scan as _ssd
from repro.kernels.vtrace_kernel import vtrace as _vtrace


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, block_q=128,
                    block_k=128, interpret=False):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, lengths, *, block_k=512, interpret=False):
    return _decode(q, k, v, lengths, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk=256, interpret=False):
    return _ssd(x, dt, A, B, C, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("clip_rho", "clip_c", "block_b",
                                             "interpret"))
def vtrace(values, next_values, rewards, discounts, rhos, *, clip_rho=1.0,
           clip_c=1.0, block_b=128, interpret=False):
    return _vtrace(values, next_values, rewards, discounts, rhos,
                   clip_rho=clip_rho, clip_c=clip_c, block_b=block_b,
                   interpret=interpret)

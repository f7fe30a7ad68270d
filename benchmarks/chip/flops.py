"""Operations and bytes the policy's work needs, computed from shapes, and
the peak table they are divided by.

Counts are of the algorithm, not of an implementation: attention counts
each query against the keys it may see (the banded, causal window), pad
rows count nothing, and nothing recomputed is counted twice.  A multiply
and an add are two operations.
"""
from __future__ import annotations

import json
import os

from reference import Arch

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peak row for ``device_kind``; an unknown kind raises."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


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

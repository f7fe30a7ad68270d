"""GQA attention: chunked-causal training/prefill path + single-token decode.

The training/prefill path scans over query chunks (flash-style: never
materializes the full (S, S) score matrix) so that 32k-token prefill lowers
with O(S * chunk) live memory.  Supports RoPE, Qwen3 qk-norm, sliding-window
(banded) masking, and non-causal/cross attention for the Whisper encoder.

GQA K/V are stored with ``num_kv_heads`` (cache compression) and broadcast to
the full head count at compute time — the broadcast keeps every score tensor
laid out (batch, heads, q, k) so SPMD head-sharding propagates cleanly.

Positions are 1-D ``(seq,)`` — shared across the batch — on the training and
prefill paths; ``decode_attention`` additionally accepts per-row ``(b,)``
positions so continuous-batching servers can decode requests that are at
different depths of their episodes in ONE dispatch.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ArchConfig
from repro.sharding import shard

NEG_INF = -1e30


def attn_init(key, cfg: ArchConfig, dtype=jnp.float32):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.truncated_normal(ks[0], (d, h, hd), d ** -0.5, dtype),
        "wk": layers.truncated_normal(ks[1], (d, kv, hd), d ** -0.5, dtype),
        "wv": layers.truncated_normal(ks[2], (d, kv, hd), d ** -0.5, dtype),
        "wo": layers.truncated_normal(ks[3], (h, hd, d), (h * hd) ** -0.5, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(params, cfg: ArchConfig, x, positions, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qk_norm:
        q = layers.head_rmsnorm(params["q_norm"], q, cfg.rmsnorm_eps)
        k = layers.head_rmsnorm(params["k_norm"], k, cfg.rmsnorm_eps)
    if rope and cfg.rope_theta > 0:
        # positions: (s,) shared across the batch, or (b, s) per-row
        pos2d = positions if positions.ndim == 2 else positions[None, :]
        q = layers.apply_rope(q, pos2d, cfg.rope_theta)
        k = layers.apply_rope(k, pos2d, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "kv_seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "kv_seq", "kv_heads", "head_dim")
    return q, k, v


def _repeat_kv(k, q_per_kv: int):
    """(b, s, kv, hd) -> (b, s, h, hd), sharded on the full head axis."""
    if q_per_kv == 1:
        return k
    k = jnp.repeat(k, q_per_kv, axis=2)
    return shard(k, "batch", "kv_seq", "heads", "head_dim")


def _masked_softmax(scores, q_pos, k_pos, causal, window):
    """scores: (b, h, sq, sk); q_pos: (sq,), k_pos: (sk,)."""
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1)


def attention(params, cfg: ArchConfig, x, positions, *, causal=True,
              q_chunk: int = 1024, kv: Optional[tuple] = None):
    """Full-sequence attention. ``kv`` overrides K/V (cross-attention)."""
    q, k, v = _project_qkv(params, cfg, x, positions, rope=kv is None)
    if kv is not None:
        k, v = kv
    sq, sk = q.shape[1], k.shape[1]
    k_pos = positions if kv is None else jnp.arange(sk)
    window = cfg.sliding_window
    k = _repeat_kv(k, cfg.q_per_kv)
    v = _repeat_kv(v, cfg.q_per_kv)
    scale = cfg.head_dim ** -0.5

    # sequence-parallel attention: when the q_seq rule maps to a mesh axis
    # (heads not divisible by the model axis), shard query positions and
    # compute un-chunked — scores are (b, h, sq/P, sk), already small.
    # K/V are gathered to full sequence (replicated heads) and every score
    # tensor is pinned to q_seq — otherwise the einsum's two free dims both
    # want the model axis and the partitioner replicates the full (sq, sk)
    # matrix.
    from repro.sharding import current_rules
    rules = current_rules()
    seq_par = False
    if rules is not None:
        spec = rules.mesh_axes(("q_seq",), (sq,))
        if spec and spec[0] is not None:
            seq_par = True
            q = shard(q, "batch", "q_seq", "heads", "head_dim")
            k = shard(k, "batch", None, None, None)
            v = shard(v, "batch", None, None, None)
            q_chunk = sq

    def block(q_blk, pos_blk):
        scores = jnp.einsum("bqhk,bshk->bhqs", q_blk, k) * scale
        if seq_par:
            scores = shard(scores, "batch", None, "q_seq", None)
        else:
            scores = shard(scores, "batch", "heads", None, None)
        p = _masked_softmax(scores, pos_blk, k_pos, causal, window).astype(v.dtype)
        if seq_par:
            p = shard(p, "batch", None, "q_seq", None)
        out = jnp.einsum("bhqs,bshk->bqhk", p, v)
        if seq_par:
            return shard(out, "batch", "q_seq", "heads", "head_dim")
        return shard(out, "batch", None, "heads", "head_dim")

    if sq % q_chunk != 0:
        q_chunk = sq          # non-divisible (e.g. whisper's 1500 frames)
    if sq <= q_chunk:
        out = block(q, positions)
    else:
        n = sq // q_chunk
        qs = jnp.moveaxis(q.reshape(q.shape[0], n, q_chunk, *q.shape[2:]), 1, 0)
        ps = positions.reshape(n, q_chunk)
        out = jax.lax.map(lambda args: block(*args), (qs, ps))
        out = jnp.moveaxis(out, 0, 1).reshape(q.shape[0], sq, q.shape[2], q.shape[3])
    out = shard(out, "batch", "seq", "heads", "head_dim")
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


# ------------------------------------------------------------------ decode
def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """One layer's cache. Sliding-window archs use a ring buffer of size W."""
    length = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    shape = (batch, length, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def resolve_decode_backend(backend: str) -> str:
    """Resolve ``"auto"`` to a concrete decode backend.

    ``auto`` is the pallas flash-decoding kernel on a TPU and the pure-jnp
    ``kernels/ref.py`` oracle on every other backend (the kernel compiles
    only for the TPU).  On a TPU a cache the kernel cannot block raises
    from ``kernels.decode_attention.kv_block``; nothing falls back.
    """
    if backend == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "ref"
    if backend not in ("jnp", "kernel", "ref"):
        raise ValueError(f"unknown decode backend {backend!r}")
    return backend


def decode_attention(params, cfg: ArchConfig, x, cache, pos, *,
                     cross_kv: Optional[tuple] = None, backend: str = "jnp"):
    """One-token decode. x: (b, 1, d); pos: scalar int32 (current index) or
    ``(b,)`` int32 per-row positions (continuous batching: rows at different
    episode depths decoded in one dispatch).

    K is stored pre-RoPE'd.  Returns (out, new_cache).
    For ``cross_kv`` (whisper) the cache is passed through untouched.

    ``backend`` selects the score/softmax path once the cache is updated:
    ``"jnp"`` (grouped-GQA einsum), ``"kernel"`` (the pallas flash-decoding
    kernel — per-row valid prefix lengths, TPU only), ``"ref"`` (the
    pure-jnp ``kernels/ref.py`` oracle), or ``"auto"`` (kernel on TPU, ref
    elsewhere — see ``resolve_decode_backend``).
    """
    pos = jnp.asarray(pos, jnp.int32)
    vector_pos = pos.ndim == 1
    positions = pos[:, None] if vector_pos else jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, cfg, x, positions, rope=cross_kv is None)
    scale = cfg.head_dim ** -0.5

    def score_softmax_out(k, v, valid):
        # grouped GQA (no KV repeat): with sq == 1 every tensor here is tiny
        # except the cache itself, which is read exactly once.
        if k.dtype != q.dtype:      # quantized (e.g. f8) caches: upcast fuses
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
        b = q.shape[0]
        qg = q.reshape(b, 1, k.shape[2], cfg.q_per_kv, cfg.head_dim)
        scores = jnp.einsum("bqngh,bsnh->bngqs", qg, k) * scale
        if valid is not None:
            vshape = ((valid.shape[0], 1, 1, 1, -1) if valid.ndim == 2
                      else (1, 1, 1, 1, -1))
            scores = jnp.where(valid.reshape(vshape), scores, NEG_INF)
        p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(v.dtype)
        out = jnp.einsum("bngqs,bsnh->bqngh", p, v)
        out = out.reshape(b, 1, cfg.num_heads, cfg.head_dim)
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"])

    if cross_kv is not None:
        k, v = cross_kv
        return score_softmax_out(k, v, None), cache

    length = cache["k"].shape[1]
    slot = jnp.mod(pos, length) if cfg.sliding_window else pos
    if vector_pos:
        rows = jnp.arange(k_new.shape[0])
        k = cache["k"].at[rows, slot].set(k_new[:, 0].astype(cache["k"].dtype))
        v = cache["v"].at[rows, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    else:
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, 1)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, 1)
    new_cache = {"k": k, "v": v}

    backend = resolve_decode_backend(backend)
    if backend in ("kernel", "ref"):
        # Both kernels mask a VALID PREFIX per row.  That is exactly the
        # occupancy of our caches: a linear cache holds slots [0, pos] and a
        # full ring holds all L slots — min(pos+1, L) either way.  Ring
        # wraparound scrambles chronological order, but softmax attention is
        # permutation-invariant over the key set and K is stored post-RoPE,
        # so prefix masking stays correct after wrap.  Both read the cache
        # at its KV-head width (GQA grouped, no repeat).
        lengths = jnp.broadcast_to(jnp.minimum(pos + 1, length),
                                   (q.shape[0],)).astype(jnp.int32)
        kq, vq = k.astype(q.dtype), v.astype(q.dtype)
        if backend == "kernel":
            from repro.kernels import ops
            out_h = ops.decode_attention(q[:, 0], kq, vq, lengths)
        else:
            from repro.kernels import ref as kernels_ref
            out_h = kernels_ref.decode_attention_ref(q[:, 0], kq, vq, lengths)
        out = jnp.einsum("bhk,hkd->bd", out_h.astype(q.dtype),
                         params["wo"])[:, None]
        return out, new_cache

    slots = jnp.arange(length)
    pos_col = pos[:, None] if vector_pos else pos
    if cfg.sliding_window:
        # slot s holds token pos - ((pos - s) mod L); valid if that is >= 0
        token_idx = pos_col - jnp.mod(pos_col - slots, length)
        valid = token_idx >= 0
    else:
        valid = slots <= pos_col
    return score_softmax_out(k, v, valid), new_cache


def prefill_attention(params, cfg: ArchConfig, x, cache, positions,
                      lengths=None):
    """Batched prompt prefill THROUGH the decode cache: one call writes the
    whole prompt's K/V into slots [0, s) and returns full-sequence outputs.

    x: (b, s, d); positions: (s,) shared across rows (prompts are
    left-aligned at 0..s-1); lengths: optional (b,) valid prompt lengths —
    keys at or beyond a row's length are masked out (shorter prompts and
    zero-padded batch slots), though their outputs are still computed
    (callers read only positions < length).  Returns (out, new_cache).

    The prompt must fit the cache (s <= cache length): continuous-batching
    callers re-prefill from a bounded window rather than wrap mid-prompt.
    """
    s = x.shape[1]
    length = cache["k"].shape[1]
    if s > length:
        raise ValueError(f"prompt of {s} tokens exceeds cache length {length}")
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)
    k_cache = cache["k"].at[:, :s].set(k_new.astype(cache["k"].dtype))
    v_cache = cache["v"].at[:, :s].set(v_new.astype(cache["v"].dtype))
    new_cache = {"k": k_cache, "v": v_cache}

    k = _repeat_kv(k_new, cfg.q_per_kv)
    v = _repeat_kv(v_new, cfg.q_per_kv)
    scale = cfg.head_dim ** -0.5
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * scale
    mask = positions[:, None] >= positions[None, :]
    if cfg.sliding_window is not None:
        mask &= (positions[:, None] - positions[None, :]) < cfg.sliding_window
    mask = mask[None, None]                                # (1, 1, s, s)
    if lengths is not None:
        mask = mask & (positions[None, None, None, :]
                       < lengths[:, None, None, None])
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqs,bshk->bqhk", p, v)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), new_cache

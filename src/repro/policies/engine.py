"""The policy-serving engine: routing between prefill and incremental decode.

``select`` takes one batch of (episode key, observation window, episode
step) rows — from any mix of clients — and answers every row with an
eps-greedy action while keeping each episode's KV-cache slot current:

- a row whose slot is CURRENT (same weights generation, step exactly one
  past the slot's last step) takes the DECODE path: one token through the
  cache, optionally on the pallas ``decode_attention`` kernel;
- every other row (new episode, episode restart, dropped step, or weights
  refreshed since the slot was filled) takes the PREFILL path: its whole
  window is pushed through the cache in one batched call.

Both paths gather the group's slot rows from the pool's batched cache, run
ONE jitted call padded to a power-of-two bucket (pad rows ride the pool's
scratch slot), and scatter the updated rows back — continuous batching over
per-episode cache state.

Weight refresh detection is object identity on ``params`` (a
``VariableClient`` only rebinds ``.params`` when it actually fetched new
weights): a refresh bumps the pool generation, so every live slot
re-prefills before its next decode rather than mixing stale K/V into fresh
queries.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.actors import STEP_MOD
from repro.models.attention import resolve_decode_backend
from repro.models.config import ArchConfig
from repro.policies import network
from repro.policies.cache import KVCachePool
from repro.telemetry import registry as _telemetry


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class PolicyEngine:
    """Stateful transformer-policy evaluation over a ``KVCachePool``."""

    def __init__(self, arch: ArchConfig, obs_shape, num_actions: int, *,
                 num_slots: int, epsilon: float = 0.0,
                 backend: str = "auto", slot_timeout_s: float = 5.0,
                 rng_seed: int = 0, jit: bool = True):
        self.arch = arch
        self.window = arch.sliding_window
        self.obs_shape = tuple(obs_shape)
        self.obs_dim = int(np.prod(obs_shape)) or 1
        self.num_actions = num_actions
        self.epsilon = float(epsilon)
        self.pool = KVCachePool(arch, num_slots, timeout_s=slot_timeout_s)
        # resolved once, in the process that runs the engine, and reported
        # in stats() so a run says which decode path it took
        self.decode_backend = backend = resolve_decode_backend(backend)
        self._key = jax.random.key(rng_seed)
        self._step = 0
        self._last_params = None
        self._stats = {"prefill_rows": 0, "decode_rows": 0,
                       "prefill_batches": 0, "decode_batches": 0,
                       "cache_invalidations": 0, "stale_reprefills": 0}
        # Exported as gauges at snapshot time (no-op when telemetry is off);
        # covers slot utilization, prefill/decode ratio, re-prefill counts.
        _telemetry.probe("inference/engine", self.stats)

        eps = self.epsilon

        def eps_greedy(q, key, step, rows):
            key = jax.random.fold_in(key, step)
            keys = jax.random.split(key, rows)
            greedy = jnp.argmax(q, axis=-1).astype(jnp.int32)
            rand = jax.vmap(lambda k: jax.random.randint(
                k, (), 0, num_actions))(keys).astype(jnp.int32)
            explore = jax.vmap(lambda k: jax.random.uniform(k) < eps)(keys)
            return jnp.where(explore, rand, greedy)

        def prefill_fn(params, sub_cache, windows, lengths, key, step):
            obs = windows.reshape(windows.shape[0], windows.shape[1], -1)
            q, sub_cache = network.q_prefill(params, arch, sub_cache, obs,
                                             lengths)
            rows = jnp.arange(q.shape[0])
            q_last = q[rows, jnp.maximum(lengths - 1, 0)]
            return eps_greedy(q_last, key, step, q.shape[0]), sub_cache

        def decode_fn(params, sub_cache, obs, pos, key, step):
            obs = obs.reshape(obs.shape[0], -1)
            q, sub_cache = network.q_decode(params, arch, sub_cache, obs,
                                            pos, backend=backend)
            return eps_greedy(q, key, step, q.shape[0]), sub_cache

        self._prefill = jax.jit(prefill_fn) if jit else prefill_fn
        self._decode = jax.jit(decode_fn) if jit else decode_fn

    # ----------------------------------------------------------- the hot path
    def select(self, params, keys: Sequence, windows, positions) -> np.ndarray:
        """One action per row.

        keys: hashable per-episode identities; windows: (n, W, *obs_shape)
        float32, LEFT-aligned (oldest frame first) and zero-padded on the
        right; positions: (n,) int — the EPISODE step of each row's newest
        frame.  Returns (n,) int32 actions.
        """
        if params is not self._last_params:
            if self._last_params is not None:
                self.pool.invalidate_all()
                self._stats["cache_invalidations"] += 1
            self._last_params = params

        windows = np.asarray(windows, np.float32)
        positions = np.asarray(positions, np.int64)
        n = windows.shape[0]
        generation = self.pool.generation
        actions = np.zeros((n,), np.int32)

        prefill_rows: List[int] = []
        decode_rows: List[int] = []
        slots = []
        for i in range(n):
            slot = self.pool.lookup(keys[i])
            if (slot is not None and slot.generation == generation
                    and slot.pos >= 0 and positions[i] == slot.pos + 1):
                decode_rows.append(i)
            else:
                if slot is None:
                    slot = self.pool.acquire(keys[i])
                else:
                    # episode restart or stale cache: recycle in place
                    if slot.generation != generation:
                        self._stats["stale_reprefills"] += 1
                    self.pool.reset_slot(slot)
                prefill_rows.append(i)
            slots.append(slot)

        if prefill_rows:
            self._run_prefill(params, prefill_rows, slots, windows,
                              positions, actions)
        if decode_rows:
            self._run_decode(params, decode_rows, slots, windows,
                             positions, actions)
        return actions

    def _pad(self, indices: List[int], bucket: int) -> np.ndarray:
        scratch = self.pool.scratch_index
        return np.asarray(indices + [scratch] * (bucket - len(indices)),
                          np.int32)

    def _next_step(self) -> int:
        step = self._step
        self._step = (self._step + 1) % STEP_MOD
        return step

    def _run_prefill(self, params, rows, slots, windows, positions, actions):
        g = len(rows)
        bucket = _bucket(g)
        w = self.window
        lengths = np.ones((bucket,), np.int32)
        batch = np.zeros((bucket, w) + windows.shape[2:], np.float32)
        for j, i in enumerate(rows):
            lengths[j] = min(positions[i] + 1, w)
            batch[j] = windows[i]
        idx = self._pad([slots[i].index for i in rows], bucket)
        sub = self.pool.gather(idx)
        acts, sub = self._prefill(params, sub, jnp.asarray(batch),
                                  jnp.asarray(lengths), self._key,
                                  self._next_step())
        self.pool.scatter(idx, sub)
        acts = np.asarray(acts)
        for j, i in enumerate(rows):
            slot = slots[i]
            slot.pos = int(positions[i])
            slot.cache_pos = int(lengths[j]) - 1
            actions[i] = acts[j]
        self._stats["prefill_batches"] += 1
        self._stats["prefill_rows"] += g

    def _run_decode(self, params, rows, slots, windows, positions, actions):
        g = len(rows)
        bucket = _bucket(g)
        w = self.window
        obs = np.zeros((bucket,) + windows.shape[2:], np.float32)
        pos = np.zeros((bucket,), np.int32)
        for j, i in enumerate(rows):
            # newest frame of a left-aligned window
            obs[j] = windows[i, min(int(positions[i]), w - 1)]
            pos[j] = slots[i].cache_pos + 1
        idx = self._pad([slots[i].index for i in rows], bucket)
        sub = self.pool.gather(idx)
        acts, sub = self._decode(params, sub, jnp.asarray(obs),
                                 jnp.asarray(pos), self._key,
                                 self._next_step())
        self.pool.scatter(idx, sub)
        acts = np.asarray(acts)
        for j, i in enumerate(rows):
            slot = slots[i]
            slot.pos = int(positions[i])
            slot.cache_pos += 1
            actions[i] = acts[j]
        self._stats["decode_batches"] += 1
        self._stats["decode_rows"] += g

    # ------------------------------------------------------------- lifecycle
    def release(self, key):
        self.pool.release(key)

    def release_client(self, client_id):
        self.pool.release_prefix(client_id)

    def stats(self) -> Dict[str, Any]:
        s = dict(self._stats)
        s.update({f"pool_{k}": v for k, v in self.pool.stats.items()})
        s["pool_held_slots"] = self.pool.held()
        s["pool_utilization"] = self.pool.held() / max(self.pool.num_slots, 1)
        s["prefill_decode_ratio"] = (s["prefill_rows"]
                                     / max(s["decode_rows"], 1))
        s["decode_backend"] = self.decode_backend
        return s

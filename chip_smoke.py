"""Chip smoke run: the RL main path end to end on one TPU chip.

Trains a transformer-policy DQN on Catch through
``run_distributed_experiment`` with the multiprocess launcher, central
inference (``inference="server"``) and two actor processes, at the body
widths of Yi-6B cut to two layers, from seeded random weights.  The parent
process alone holds the chip: the learner, replay and the
``TransformerInferenceServer`` run there, and the actors are weightless
``WindowedInferenceClientActor`` children pinned to the CPU.  The trained
parameters are then run through prefill and cached decode, with the decode
backend the server resolved, and the Q-values are checked against a
full-sequence reference at ``highest`` matmul precision.

    python chip_smoke.py

Exits non-zero, printing no result, unless JAX's first device is a TPU.  The
last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
NUM_ACTORS = 2
LEARNER_STEPS = 20
RUN_TIMEOUT_S = 600.0
REF_ROWS = 8
# The served path keeps XLA's default matmul precision, which on a TPU feeds
# float32 operands to the MXU as bfloat16 (relative rounding 2**-9 per
# operand); through two 4096-wide blocks that stays near 1% of the Q scale.
# The reference runs at "highest" precision.  A wrong mask, cache slot or
# GQA grouping moves Q by a sizeable fraction of its scale.
Q_TOL = 2e-2   # worst |Q - Q_ref|, as a fraction of max |Q_ref|


def policy_config():
    """The policy at Yi-6B's body widths (``repro/configs/yi_6b.py``).

    Depth is cut from 32 layers to 2: about 346 M parameters, so params,
    target params and Adam state take about 5.5 GB at float32.  RoPE keeps
    the policy network's base of 10,000; ``yi_6b.py`` does not state
    Yi-6B's ``rope_theta``.
    """
    from repro.configs import get_arch
    from repro.policies import TransformerPolicyConfig
    yi = get_arch("yi-6b")
    return TransformerPolicyConfig(
        num_layers=2, d_model=yi.d_model, num_heads=yi.num_heads,
        num_kv_heads=yi.num_kv_heads, head_dim=yi.head_dim, d_ff=yi.d_ff,
        min_replay_size=16, samples_per_insert=0.0, backend="auto")


class PolicyBuilderFactory:
    """Picklable ``spec -> TransformerPolicyBuilder``: actor children
    rebuild the builder from it."""

    def __init__(self, cfg):
        self.cfg = cfg

    def __call__(self, spec):
        from repro.policies import TransformerPolicyBuilder
        return TransformerPolicyBuilder(spec, self.cfg, seed=SEED)


def catch(seed):
    from repro.envs import Catch
    return Catch(seed=seed)


def check(ok: bool, message: str):
    if not ok:
        raise RuntimeError(message)


class CompileClock:
    """Sums JAX's backend compile events (persistent-cache reads included)
    and counts persistent-cache hits, across all threads."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


def train(cfg):
    """Phase 1: the distributed run through the normal entry point."""
    from repro.experiments import ExperimentConfig, run_distributed_experiment
    config = ExperimentConfig(
        builder_factory=PolicyBuilderFactory(cfg), environment_factory=catch,
        seed=SEED, eval_episodes=0, launcher="multiprocess",
        inference="server")
    t0 = time.time()
    result = run_distributed_experiment(
        config, num_actors=NUM_ACTORS, max_learner_steps=LEARNER_STEPS,
        timeout_s=RUN_TIMEOUT_S)
    stats = result.extras["inference"]
    print(f"run_seconds: {time.time() - t0}")
    print(f"learner_steps: {result.learner_steps}")
    print(f"actor_steps: {int(result.counts.get('actor_steps', 0))}")
    print(f"server_prefill_rows: {stats['prefill_rows']}")
    print(f"server_decode_rows: {stats['decode_rows']}")
    print(f"server_decode_backend: {stats['decode_backend']}")
    check(result.learner_steps >= LEARNER_STEPS,
          f"learner took {result.learner_steps} of {LEARNER_STEPS} steps "
          f"within {RUN_TIMEOUT_S} s")
    check(stats["decode_rows"] > 0, "the server decoded no rows")
    return result, stats["decode_backend"]


def check_q_values(builder, params, backend):
    """Phase 2: prefill, then cached decode past the ring wrap, against
    ``network.q_sequence`` at ``highest`` precision.  Returns the compiled
    decode step's HLO text."""
    import jax
    import numpy as np
    from repro.policies import network

    arch = builder.arch
    window = arch.sliding_window
    steps = 3 * window
    obs_dim = int(np.prod(builder.spec.observations.shape))
    rng = np.random.RandomState(SEED)
    obs = rng.rand(REF_ROWS, steps, obs_dim).astype(np.float32)
    prompt = 1 + np.arange(REF_ROWS, dtype=np.int32) % window  # per-row
    with jax.default_matmul_precision("highest"):
        q_ref = np.asarray(jax.jit(
            lambda p, o: network.q_sequence(p, arch, o))(params, obs))

    prefill = jax.jit(lambda p, c, o, n: network.q_prefill(p, arch, c, o, n))
    decode_fn = jax.jit(lambda p, c, o, pos: network.q_decode(
        p, arch, c, o, pos, backend=backend))
    cache = network.init_cache(arch, REF_ROWS)
    padded = obs[:, :window] * (np.arange(window)[None, :, None]
                                < prompt[:, None, None])
    q_pre, cache = prefill(params, cache, padded, prompt)
    q_pre = np.asarray(q_pre)
    diffs = [np.abs(q_pre[i, :prompt[i]] - q_ref[i, :prompt[i]]).max()
             for i in range(REF_ROWS)]
    rows = np.arange(REF_ROWS)
    decode = decode_fn.lower(params, cache, obs[:, 0], prompt).compile()
    for k in range(steps - int(prompt.max())):
        pos = prompt + k
        q_dec, cache = decode(params, cache, obs[rows, pos], pos)
        diffs.append(np.abs(np.asarray(q_dec) - q_ref[rows, pos]).max())
    worst = float(max(diffs))
    scale = float(np.abs(q_ref).max())
    print(f"q_ref_max_abs: {scale}")
    print(f"q_worst_abs_diff: {worst}")
    print(f"q_worst_rel_diff: {worst / scale}  (tolerance {Q_TOL})")
    check(worst <= Q_TOL * scale,
          f"served Q differs from the reference by {worst} "
          f"(> {Q_TOL} x {scale})")
    return decode.as_text()


def check_kernel(arch):
    """Phase 3: the decode kernel alone against the ``ref.py`` oracle, at
    the policy's head layout and a cache of 1024 slots."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    b, s = 8, 1024
    rng = np.random.RandomState(SEED + 1)
    q = jnp.asarray(rng.randn(b, arch.num_heads, arch.head_dim), jnp.float32)
    kv_shape = (b, s, arch.num_kv_heads, arch.head_dim)
    k = jnp.asarray(rng.randn(*kv_shape), jnp.float32)
    v = jnp.asarray(rng.randn(*kv_shape), jnp.float32)
    lengths = jnp.asarray(rng.randint(1, s + 1, b), jnp.int32)
    out = np.asarray(ops.decode_attention(q, k, v, lengths))
    with jax.default_matmul_precision("highest"):
        expected = np.asarray(jax.jit(ref.decode_attention_ref)(
            q, k, v, lengths))
    worst = float(np.abs(out - expected).max())
    scale = float(np.abs(expected).max())
    print(f"kernel_worst_abs_diff: {worst}  (ref max abs {scale})")
    check(worst <= Q_TOL * scale,
          f"decode kernel differs from ref by {worst} (> {Q_TOL} x {scale})")


def main():
    import jax
    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{device.platform!r} ({device.device_kind}); nothing ran")
    from repro.compile_cache import enable_compile_cache
    print(f"compile_cache_dir: {enable_compile_cache()}")
    clock = CompileClock()

    cfg = policy_config()
    result, backend = train(cfg)
    check(backend == "kernel",
          f"the server decoded with {backend!r}, not the Pallas kernel")
    hlo = check_q_values(result.builder, result.learner.state.params, backend)
    has_kernel = "tpu_custom_call" in hlo
    print(f"decode_step_has_tpu_custom_call: {has_kernel}")
    check(has_kernel, "the compiled decode step holds no Pallas kernel")
    check_kernel(result.builder.arch)

    stats = device.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(f"bytes_limit: {stats.get('bytes_limit')}")
    print(f"compile_seconds: {clock.seconds}  ({clock.compiles} compiles, "
          f"{clock.cache_hits} persistent-cache hits)")
    print(json.dumps({"ok": True,
                      "device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": len(devices)}}))


if __name__ == "__main__":
    main()

"""Batched transformer-policy serving (SEED-RL style actor inference):
load a reduced architecture from the assigned pool, prefill a prompt batch,
then decode tokens step-by-step through the KV/SSM cache — the same
``serve_step`` the multi-pod dry-run lowers for decode_32k / long_500k.

  PYTHONPATH=src python examples/serve_transformer_policy.py --arch qwen3-1.7b
  PYTHONPATH=src python examples/serve_transformer_policy.py --arch mamba2-780m
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import ARCHS, reduced
from repro.launch.steps import make_batched_prefill_step, make_serve_step
from repro.models import transformer


def main():
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen3-1.7b", choices=sorted(ARCHS))
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--decode-len", type=int, default=32)
    args = p.parse_args()

    cfg = reduced(ARCHS[args.arch])       # CPU-sized variant of the family
    print(f"arch={cfg.name} ({cfg.arch_type}) reduced to "
          f"{cfg.num_layers}L d{cfg.d_model}")
    rng = jax.random.key(0)
    params = transformer.init(rng, cfg, jnp.float32)

    b = args.batch
    max_len = args.prompt_len + args.decode_len
    cache = transformer.init_cache(cfg, b, max_len, jnp.float32)
    serve = jax.jit(make_serve_step(cfg))

    prompt = jax.random.randint(rng, (b, args.prompt_len), 0, cfg.vocab_size)
    if cfg.arch_type in ("dense", "moe"):
        # whole-prompt prefill through the cache in one jitted call
        prefill = jax.jit(make_batched_prefill_step(cfg))
        tok, logits, cache = prefill(params, cache, prompt)
    else:
        # ssm/hybrid/audio caches: step the decoder over the prompt
        for t in range(args.prompt_len):
            tok, logits, cache = serve(params, cache, prompt[:, t:t + 1],
                                       jnp.int32(t))
    # decode
    t0 = time.time()
    out = [tok]
    for i in range(args.decode_len - 1):
        tok, logits, cache = serve(params, cache, tok,
                                   jnp.int32(args.prompt_len + i))
        out.append(tok)
    dt = time.time() - t0
    tokens = jnp.concatenate(out, axis=1)
    print(f"decoded {tokens.shape} in {dt:.2f}s "
          f"({b * (args.decode_len - 1) / dt:.0f} tok/s on CPU)")
    print("sample:", tokens[0, :16].tolist())


if __name__ == "__main__":
    main()

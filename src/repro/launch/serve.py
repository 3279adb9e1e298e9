"""Serving launcher: batched prefill + decode over the geo mesh.

``python -m repro.launch.serve --arch <id> --prompt-len 64 --gen 32``

Runs a model (smoke-scale, or the full config with ``--full-config``)
end to end: batched synthetic prompts through ``prefill`` then greedy
``decode_step`` tokens.  Each phase is compiled ahead of its timed run,
so the printed times separate compilation from execution; ``main``
returns them with the generated tokens and the last logits.
"""

from __future__ import annotations

import argparse
import time


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="distilgpt2-82m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_smoke_config
    from repro.launch.batches import decode_step_input, synthetic_prompt_batch
    from repro.launch.compile_cache import CompileStats, enable_compile_cache
    from repro.models import decode_step, init_params, prefill

    cache_dir = enable_compile_cache()
    cfg = get_config(args.arch) if args.full_config else get_smoke_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    max_len = args.prompt_len + args.gen

    batch = synthetic_prompt_batch(cfg, key, args.batch, args.prompt_len)

    with CompileStats() as compiles:
        t0 = time.perf_counter()
        prefill_c = jax.jit(
            lambda pr, b: prefill(pr, b, cfg, max_len=max_len)
        ).lower(params, batch).compile()
        t_prefill_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        logits, cache = prefill_c(params, batch)
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0
        print(
            f"prefill: {args.batch}x{args.prompt_len} in {t_prefill:.3f}s "
            f"(compile {t_prefill_compile:.2f}s)"
        )

        tokens = jnp.argmax(logits, axis=-1)
        t0 = time.perf_counter()
        decode_c = jax.jit(
            lambda pr, tok, c, pos: decode_step(pr, tok, c, cfg, pos)
        ).lower(
            params, decode_step_input(cfg, key, tokens, args.batch, 0), cache,
            jnp.int32(args.prompt_len),
        ).compile()
        t_decode_compile = time.perf_counter() - t0
    generated = [tokens]
    t0 = time.perf_counter()
    for i in range(args.gen):
        pos = jnp.int32(args.prompt_len + i)
        step_in = decode_step_input(cfg, key, tokens, args.batch, i)
        logits, cache = decode_c(params, step_in, cache, pos)
        tokens = jnp.argmax(logits, axis=-1)
        generated.append(tokens)
    tokens.block_until_ready()
    t_decode = time.perf_counter() - t0
    toks_per_s = args.batch * args.gen / t_decode
    print(
        f"decode: {args.gen} steps in {t_decode:.3f}s ({toks_per_s:.1f} tok/s, "
        f"compile {t_decode_compile:.2f}s)"
    )
    out = jnp.stack(generated, axis=1)
    print(f"sample[0]: {out[0].tolist()}")
    print(f"{compiles.summary()} ({cache_dir})")
    return {
        "prefill_s": t_prefill,
        "prefill_compile_s": t_prefill_compile,
        "decode_s": t_decode,
        "decode_compile_s": t_decode_compile,
        "decode_tokens_per_s": toks_per_s,
        "tokens": out,
        "last_logits": logits,
        "compile_cache_hits": compiles.hits,
        "compile_cache_misses": compiles.misses,
    }


if __name__ == "__main__":
    main()

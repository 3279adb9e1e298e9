"""Serving cells: batched prefill and greedy decode in a closed loop.

The program's ``prefill`` (with ``max_len = prompt + gen``) and
``decode_step`` are jitted as ``repro.launch.serve`` jits them, and its
argmax runs as there, after each call.  The loop serves full batches of
``batch`` requests back to back: a prefill, then decode steps until each
request has ``gen_tokens`` tokens.  Every step's tokens are fetched to the
host, as a streaming server delivers them.  The window holds every batch
that starts within ``--seconds``.

* TTFT: from the start of a request's batch to its first token on the host.
* ITL: the gap between two consecutive tokens of a request on the host.

Every request of a batch shares its batch's TTFT and gaps, and every batch
holds ``batch`` requests, so a percentile over the batches is the same as
one over the requests.  Once the window has closed, a sample of its
requests drawn from the seed is compared with the reference, which runs
the whole prompt and the served tokens through a plain forward pass.
"""

from __future__ import annotations

import gc
import time
from typing import Callable, Dict

import numpy as np

from . import inputs, reference
from .drive_train import model_config, weights_fn
from .harness import log, memory_peak


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


class Server:
    """The program's prefill and decode, jitted as the serving launcher does."""

    def __init__(self, cfg, traffic: Dict, params):
        import jax

        from repro.models import decode_step, prefill

        self.B, self.P, self.G = traffic["batch"], traffic["prompt_len"], traffic["gen_tokens"]
        max_len = self.P + self.G
        self.params = params

        def serve_prefill(params, batch):
            return prefill(params, batch, cfg, max_len=max_len)

        def serve_decode(params, tokens, cache, position):
            return decode_step(params, tokens, cache, cfg, position)

        self.prefill, self.decode = jax.jit(serve_prefill), jax.jit(serve_decode)

    def batch(self, rows: np.ndarray):
        """Serve one batch; returns its tokens [B, G] and their host arrival times."""
        import jax
        import jax.numpy as jnp

        with jax.profiler.TraceAnnotation("bench.prefill"):
            logits, cache = self.prefill(self.params, {"tokens": jnp.asarray(rows)})
            tokens = jnp.argmax(logits, axis=-1)
            out = [np.asarray(tokens)]
        times = [time.perf_counter()]
        for i in range(self.G - 1):
            with jax.profiler.TraceAnnotation("bench.decode"):
                logits, cache = self.decode(self.params, tokens, cache, jnp.int32(self.P + i))
                tokens = jnp.argmax(logits, axis=-1)
                out.append(np.asarray(tokens))
            times.append(time.perf_counter())
        return np.stack(out, axis=1), times


def closed_loop(server: Server, prompts_of: Callable[[int], np.ndarray], seconds: float) -> Dict:
    """Serve batch after batch; every batch that starts within ``seconds``."""
    ttft, gaps, prompts, served = [], [], [], []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        rows = prompts_of(len(served))
        t_batch = time.perf_counter()
        tokens, times = server.batch(rows)
        ttft.append(times[0] - t_batch)
        gaps.append(np.diff(times))
        prompts.append(rows)
        served.append(tokens)
    return {"seconds": time.perf_counter() - t0, "ttft": np.asarray(ttft), "itl": np.concatenate(gaps),
            "prompts": np.concatenate(prompts), "served": np.concatenate(served)}


def sample(seed: int, loop: Dict, n: int):
    """The prompts and served tokens of ``n`` requests of the window, drawn from the seed."""
    rng = np.random.default_rng(inputs.seed_words(seed))
    pick = np.sort(rng.choice(len(loop["served"]), size=min(n, len(loop["served"])), replace=False))
    return loop["prompts"][pick], loop["served"][pick]


def run(spec: Dict, devs, *, seed: int, seconds: float, tracer, t_process: float, mark) -> Dict:
    from repro.launch.compile_cache import CompileStats

    t = spec["traffic"]
    cfg = model_config(spec["config"])
    B, P = t["batch"], t["prompt_len"]
    make_params = weights_fn(cfg)
    server = Server(cfg, t, make_params(seed))
    mark("weights")
    with CompileStats() as setup_compiles:
        for w in range(t["warmup_batches"]):
            server.batch(inputs.serve_batch(seed, "warmup", w, B, P, cfg.vocab_size))
    mark("warmup")

    # -- the window: a closed loop of full batches ------------------------------
    compiles = CompileStats()
    tracer.start()
    compiles.__enter__()
    setup_s = time.perf_counter() - t_process
    loop = closed_loop(server, lambda b: inputs.serve_batch(seed, "window", b, B, P, cfg.vocab_size), seconds)
    compiles.__exit__(None, None, None)
    tracer.stop()
    peak = memory_peak(devs)
    batches = len(loop["ttft"])
    window = {"seconds": loop["seconds"], "batches": batches, "requests": batches * B,
              "itl_mean_s": float(loop["itl"].mean())}
    del server
    gc.collect()

    # -- the reference, over a sample of the served requests ----------------------
    t_ref = time.perf_counter()
    prompts, served = sample(seed, loop, t["sample_requests"])
    ref_of = reference.lookup(spec["cell"]["config"])
    gaps, _ = ref_of.served_gaps(make_params(seed), prompts, served, spec["config"]["model"],
                                 rows_per_block=t["ref_rows_per_block"])
    numbers = {"token_gap": float(gaps.max())}
    info = {
        "setup_compiles": f"{setup_compiles.hits} hits / {setup_compiles.misses} misses, "
                          f"{setup_compiles.compile_s:.2f}s",
        "window_compiles": compiles.hits + compiles.misses,
        "batches": batches,
        "ttft_ms_max": f"{loop['ttft'].max() * 1e3:.3f}",
        "itl_ms_max": f"{loop['itl'].max() * 1e3:.3f}",
        "checked_tokens": int(gaps.size),
        "reference": ref_of.source,
        "reference_s": f"{time.perf_counter() - t_ref:.2f}",
    }
    log(f"window {batches * B} requests in {batches} batches, {loop['seconds']:.3f}s; set-up {setup_s:.2f}s")
    return {
        "end_to_end": {
            "setup_s": setup_s,
            "ttft_ms_p90": percentile(loop["ttft"], 90) * 1e3,
            "itl_ms_p95": percentile(loop["itl"], 95) * 1e3,
        },
        "window": window,
        "numbers": numbers,
        "attempted": batches * B,
        "failed": 0,
        "memory_peak_bytes": peak,
        "info": info,
    }

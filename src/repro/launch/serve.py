"""Serving launcher: continuous-batching decode over the paged KV cache,
with NDPage's flat block table or the two-level radix baseline.

On one TPU chip it serves an architecture at its published widths in
bf16, with random weights from a fixed seed (the repository ships no
weights):

  python -m repro.launch.serve --arch internlm2-1.8b [--kv-mode paged_radix]

``--local-smoke`` serves the architecture's reduced variant in f32 through
the same engine, which is what runs on a CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

#: the one-chip deployment: 8 concurrent sequences of up to 2048 tokens
#: in 16-token pages, prompts of 64-256 tokens, 32 generated tokens each
FULL = dict(max_batch=8, max_len=2048, page_size=16, requests=8,
            prompt_len=(64, 256), new_tokens=32)
#: the reduced CPU run of --local-smoke
SMOKE = dict(max_batch=4, max_len=96, page_size=8, requests=16,
             prompt_len=(4, 10), new_tokens=8)


def make_requests(n: int, vocab: int, prompt_len: Tuple[int, int],
                  new_tokens: int, seed: int = 0) -> List:
    """``n`` requests with prompts of uniform random length in
    ``prompt_len`` (inclusive) drawn from a seeded generator."""
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    lo, hi = prompt_len
    return [Request.build(i, rng.integers(1, vocab, rng.integers(lo, hi + 1)),
                          max_new_tokens=new_tokens) for i in range(n)]


def start(cfg, params, kv_mode: str | None, requests: Sequence, *,
          max_batch: int, max_len: int, page_size: int):
    """A :class:`ServeEngine` with ``requests`` queued; ``run()`` serves
    them.  ``kv_mode`` pins the block table (``paged_flat`` /
    ``paged_radix``); ``None`` lets occupancy choose."""
    from repro.serving import ServeEngine
    eng = ServeEngine(cfg, params, max_batch=max_batch, max_len=max_len,
                      page_size=page_size, table_mode=kv_mode)
    for r in requests:
        eng.submit(dataclasses.replace(r, generated=[]))
    return eng


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--kv-mode", default="paged_flat",
                    choices=["paged_flat", "paged_radix", "auto"])
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--local-smoke", action="store_true")
    args = ap.parse_args()

    import jax

    from repro import config as C
    from repro.models import init_params
    from repro.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = C.get_arch(args.arch)
    run = SMOKE if args.local_smoke else FULL
    if args.local_smoke:
        cfg = dataclasses.replace(C.smoke_variant(cfg), dtype="float32")
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit(
            f"serving {cfg.name} at full width needs a TPU, found "
            f"{jax.devices()[0].platform}; use --local-smoke on a CPU")
    params = init_params(cfg, jax.random.PRNGKey(0))
    reqs = make_requests(args.requests or run["requests"], cfg.vocab_size,
                         run["prompt_len"], run["new_tokens"])
    eng = start(cfg, params, None if args.kv_mode == "auto" else args.kv_mode,
                reqs, max_batch=run["max_batch"], max_len=run["max_len"],
                page_size=run["page_size"])
    done = eng.run()
    print(f"served {len(done)} requests; scheduler={eng.sched.stats}; "
          f"tcache={eng.sched.tcache.hit_rate:.2%}")


if __name__ == "__main__":
    main()

"""Batched serving example: greedy decoding against a ring-buffered KV cache
with throughput stats, driven through the Session API.

    python examples/serve_batch.py --arch h2o-danube-1.8b
"""
import argparse
import time

import jax

from repro.api import Session
from repro.runtime import enable_compile_cache
from repro.configs import get_config, list_archs
from repro.launch.serve import ServeStats
from repro.models import init_params


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()        # CPU-scale weights
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step")
    compiled = Session(cfg).default_plan(seq=args.prompt_len
                                         + args.new_tokens)
    bundle = compiled.serve()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0, cfg.vocab)

    t0 = time.perf_counter()
    out = bundle.generate(params, prompt, n_new=args.new_tokens)
    wall = time.perf_counter() - t0
    stats = ServeStats(tokens_generated=args.batch * args.new_tokens,
                       steps=args.prompt_len + args.new_tokens, wall_s=wall)
    print(f"arch          : {cfg.name}")
    print(f"generated     : {out.shape} "
          f"({stats.tokens_generated} new tokens)")
    print(f"throughput    : {stats.tok_per_s:,.1f} tok/s "
          f"(CPU, reduced config)")
    print(f"sample row    : {out[0].tolist()}")


if __name__ == "__main__":
    main()

"""Batched serving demo: continuous batching over decode slots (the
serving-side mirror of the paper's dynamic batched ARA -- converged work
leaves the batch, queued work enters, shapes stay fixed).

Run:  PYTHONPATH=src python examples/serve_lm.py --requests 8 --slots 3
"""

import argparse
import time

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import init_model
from repro.train import DecodeServer, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=True)
    print(f"initializing {cfg.name} ({cfg.param_count()/1e6:.1f}M params)")
    params = init_model(jax.random.PRNGKey(0), cfg)
    srv = DecodeServer(cfg, params, slots=args.slots, max_len=128)

    reqs = [Request(prompt=[1 + i, 2 + i, 3 + i], max_new_tokens=args.max_new,
                    temperature=0.0 if i % 2 == 0 else 0.8, rid=i)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    done = srv.run(reqs)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(c.tokens) for c in done)
    print(f"served {len(done)} requests / {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s) with {args.slots} slots")
    for c in sorted(done, key=lambda c: c.rid):
        print(f"  request {c.rid}: {c.tokens}")


if __name__ == "__main__":
    main()

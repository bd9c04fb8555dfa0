"""Serving launcher: drive the continuous-batching engine from the CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        [--requests 16] [--decode-slots 4] [--page-size 16] \
        [--max-len 256] [--max-new 32] [--seed 0] \
        [--kernel-backend xla|pallas|pallas_interpret]

Builds a reduced config of the named architecture, submits a seeded
batch of ragged requests (prompt lengths and generation budgets drawn
per request), streams tokens as the engine emits them, and reports the
drain throughput plus the serving compile invariant (one prefill
executable per prompt bucket, one decode executable total).  The
counterpart of ``repro.launch.train`` for the serving subsystem; for a
load sweep with latency percentiles and the static-batch comparison,
use ``benchmarks/bench_serve.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry as R
from repro.serving import GenerationRequest, ServingEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--decode-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kernel-backend", default=None,
                    choices=("xla", "pallas", "pallas_interpret"))
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-request completion lines")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch).reduced()
    if args.kernel_backend:
        cfg = dataclasses.replace(cfg,
                                  kernel_backend=args.kernel_backend)
    mode = R.serving_mode(cfg)
    if mode is None:
        raise SystemExit(
            f"arch {cfg.name} (arch_type={cfg.arch_type}, window="
            f"{cfg.sliding_window}) has no paged/state serving mode; "
            f"use train.serve.Server (examples/serve_decode.py falls "
            f"back automatically)")
    params = R.init_params(jax.random.PRNGKey(args.seed), cfg)
    eng = ServingEngine(cfg, params, decode_slots=args.decode_slots,
                        page_size=args.page_size, max_len=args.max_len)

    rng = np.random.default_rng(args.seed)
    max_prompt = max(args.max_len - args.max_new, 2)
    for _ in range(args.requests):
        s = int(rng.integers(2, max_prompt + 1))
        n = int(rng.integers(1, args.max_new + 1))
        eng.submit(GenerationRequest(
            prompt=rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32),
            max_new_tokens=n))

    print(f"arch={cfg.name} mode={mode} slots={args.decode_slots} "
          f"page_size={eng.page_size} pool={eng.pool.capacity} pages")
    t0 = time.time()
    n_tok = 0
    while not eng.done:
        for rid, _tok, fin in eng.step():
            n_tok += 1
            if fin and not args.quiet:
                res = eng.result(rid)
                print(f"  rid={rid} {res.finish_reason} "
                      f"prompt={res.prompt_len} new={len(res.tokens)}")
    dt = time.time() - t0
    print(f"{args.requests} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / max(dt, 1e-9):.1f} tok/s)")
    print(f"executables: prefill={eng.n_prefill_executables} "
          f"decode={eng.n_decode_executables} "
          f"(budget {eng.executable_budget}); "
          f"occupancy {eng.mean_occupancy():.2f}")
    assert eng.n_decode_executables == 1, "decode executable invariant"


if __name__ == "__main__":
    main()

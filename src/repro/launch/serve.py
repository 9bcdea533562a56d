"""Serving driver: batched generation with AFT-backed atomic weight refresh.

Loads the latest committed checkpoint for ``--run-id`` (written by
``repro.launch.train``) and serves batched greedy generations; the
background refresher hot-swaps weights whenever the trainer commits a newer
checkpoint — atomically, thanks to read-atomic isolation.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --workdir /tmp/aft-train --run-id train0 --requests 8
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.checkpoint import AftCheckpointer
from repro.core import AftCluster, ClusterConfig
from repro.models import Model
from repro.serve import ServeConfig, ServeEngine
from repro.storage.localfs import LocalFSStorage
from repro.storage.memory import MemoryStorage

from .compile_cache import use_compile_cache
from .train import reduced_preset


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "m100"])
    ap.add_argument("--storage", default="localfs",
                    choices=["memory", "localfs"])
    ap.add_argument("--workdir", default="/tmp/aft-train")
    ap.add_argument("--run-id", default="train0")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--refresh-every", type=float, default=1.0)
    args = ap.parse_args()
    use_compile_cache()

    cfg, _, _ = reduced_preset(args.arch, args.preset)
    model = Model(cfg)
    storage = (MemoryStorage() if args.storage == "memory"
               else LocalFSStorage(args.workdir))
    cluster = AftCluster(storage, ClusterConfig(num_nodes=2))
    try:
        ck = AftCheckpointer(cluster.client(), run_id=args.run_id)
        eng = ServeEngine(model, ck, ServeConfig(
            max_batch=args.requests,
            max_len=args.prompt_len + args.max_new + 1,
            refresh_every_s=args.refresh_every))
        if not eng.refresh_weights():
            print("[serve] no committed checkpoint found — run "
                  "repro.launch.train first")
            return 1
        print(f"[serve] weights @ step {eng.weights_step}")
        eng.start_refresher()

        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.requests, args.prompt_len)).tolist()
        t0 = time.time()
        outs = eng.generate(prompts, args.max_new)
        dt = time.time() - t0
        toks = args.requests * args.max_new
        print(f"[serve] {toks} tokens in {dt:.2f}s "
              f"({toks/dt:.1f} tok/s, batch={args.requests})")
        for i, o in enumerate(outs[:4]):
            print(f"  req{i}: {o[:16]}{'...' if len(o) > 16 else ''}")
        print(f"[serve] stats: {eng.stats}")
        eng.stop()
        return 0
    finally:
        cluster.stop()


if __name__ == "__main__":
    raise SystemExit(main())

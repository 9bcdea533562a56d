"""End-to-end fault-tolerant training driver.

Runs a real training loop (synthetic grammar corpus) with AFT-transactional
checkpointing.  On this CPU container the default preset is a reduced
config; ``--preset m100`` selects a ~100M-parameter variant of the chosen
architecture family (same code path the production mesh would run — the
dry-run/roofline tools cover the full configs).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --steps 100 --ckpt-every 20 --storage localfs --workdir /tmp/aft-run
  # crash/restart demo (exactly-once):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --steps 60 --crash-at 35 && \
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --steps 60
"""

from __future__ import annotations

import argparse
import json
import time

from repro.checkpoint import AftCheckpointer
from repro.core import AftCluster, ClusterConfig
from repro.models import Model, get_config
from repro.storage.localfs import LocalFSStorage
from repro.storage.memory import MemoryStorage
from repro.train import get_optimizer
from repro.train.data import data_for_model
from repro.train.loop import CrashInjected, Trainer, TrainerConfig

from .compile_cache import use_compile_cache


def make_storage(kind: str, workdir: str):
    if kind == "memory":
        return MemoryStorage()
    if kind == "localfs":
        return LocalFSStorage(workdir)
    raise ValueError(kind)


def reduced_preset(arch: str, preset: str):
    cfg = get_config(arch)
    if preset == "smoke":
        return cfg.reduced(), 8, 64
    if preset == "m100":
        # ~100M-param family member: wider/deeper than smoke, CPU-trainable
        return cfg.reduced(
            d_model=512, num_heads=8, num_kv_heads=4, d_ff=1408,
            vocab_size=min(cfg.vocab_size, 32000),
            pattern_repeats=max(1, min(8, 48 // max(1, len(cfg.pattern)))),
            head_dim=None, attn_q_chunk=128,
        ), 8, 256
    raise ValueError(preset)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "m100"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--storage", default="localfs",
                    choices=["memory", "localfs"])
    ap.add_argument("--workdir", default="/tmp/aft-train")
    ap.add_argument("--run-id", default="train0")
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--crash-at", type=int, default=-1,
                    help="inject a crash after this step (restart to resume)")
    ap.add_argument("--history-out", default="")
    args = ap.parse_args()
    use_compile_cache()

    cfg, batch, seq = reduced_preset(args.arch, args.preset)
    if args.batch:
        batch = args.batch
    if args.seq:
        seq = args.seq
    model = Model(cfg)
    from repro.models.params import count_params

    n_params = count_params(model.param_defs())
    print(f"[train] arch={args.arch} preset={args.preset} "
          f"params={n_params/1e6:.1f}M batch={batch} seq={seq}")

    storage = make_storage(args.storage, args.workdir)
    cluster = AftCluster(storage, ClusterConfig(num_nodes=args.nodes))
    try:
        ck = AftCheckpointer(cluster.client(), run_id=args.run_id)
        data = data_for_model(cfg, global_batch=batch, seq_len=seq)
        tcfg = TrainerConfig(
            total_steps=args.steps, ckpt_every=args.ckpt_every,
            log_every=args.log_every,
            crash_after_step=args.crash_at if args.crash_at >= 0 else None)
        trainer = Trainer(model, get_optimizer(args.optimizer, lr=args.lr),
                          data, ck, tcfg)
        t0 = time.time()
        try:
            hist = trainer.run()
        except CrashInjected as e:
            print(f"[train] CRASH INJECTED: {e} — restart this command to "
                  f"resume from the last committed checkpoint "
                  f"(step {ck.latest_step()})")
            return 0
        dt = time.time() - t0
        if not hist:
            print(f"[train] nothing to do — run already complete at step "
                  f"{ck.latest_step()}")
            return 0
        print(f"[train] done: {hist[-1]} ({dt:.1f}s)")
        steps_done = hist[-1]["step"] + 1 - hist[0]["step"]
        tok_s = batch * seq * steps_done / max(dt, 1e-9)
        print(f"[train] ~{tok_s:.0f} tokens/s on this host")
        if args.history_out:
            with open(args.history_out, "w") as f:
                json.dump(hist, f, indent=1)
        return 0
    finally:
        cluster.stop()


if __name__ == "__main__":
    raise SystemExit(main())

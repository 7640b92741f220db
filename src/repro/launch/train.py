"""End-to-end training driver (QAT BitNet) with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch bitnet-3b --reduced \
        --steps 200 --global-batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Features exercised end-to-end: synthetic sharded data pipeline, QAT train
step (STE ternary + absmax int8), grad accumulation, warmup-cosine AdamW,
step-level checkpoint/restart (atomic manifests), preemption handling
(SIGTERM → checkpoint + clean exit), straggler monitoring.
"""

from __future__ import annotations

import argparse
import importlib
import time

import jax
import numpy as np

from repro.checkpoint.store import (latest_step, load_checkpoint,
                                    save_checkpoint)
from repro.configs.base import get_config
from repro.data.pipeline import SyntheticDataset
from repro.distributed.fault_tolerance import (PreemptionHandler,
                                               StragglerMonitor)
from repro.models.transformer import init_params
from repro.training.optimizer import adamw_init
from repro.training.train import make_train_step

_REDUCED_MODULES = {
    "mixtral-8x22b": "mixtral_8x22b",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "whisper-small": "whisper_small",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "llava-next-34b": "llava_next_34b",
    "qwen1.5-32b": "qwen1_5_32b",
    "stablelm-1.6b": "stablelm_1_6b",
    "mistral-nemo-12b": "mistral_nemo_12b",
    "qwen1.5-110b": "qwen1_5_110b",
    "rwkv6-1.6b": "rwkv6_1_6b",
    "bitnet-3b": "bitnet_3b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}


def resolve_config(arch: str, reduced: bool):
    if reduced:
        mod = importlib.import_module(
            f"repro.configs.{_REDUCED_MODULES[arch]}")
        return mod.REDUCED
    return get_config(arch)


def train_loop(cfg, *, steps: int, global_batch: int, seq_len: int,
               ckpt_dir: str | None = None, ckpt_every: int = 50,
               n_micro: int = 1, peak_lr: float = 1e-3, seed: int = 0,
               log_every: int = 10, preemption: PreemptionHandler | None
               = None, resume: bool = True, hooks=None) -> dict:
    """Returns {"losses": [...], "last_step": n, "straggler": summary}."""
    data = SyntheticDataset(cfg, seq_len=seq_len, global_batch=global_batch,
                            seed=seed)
    params, _ = init_params(cfg, jax.random.PRNGKey(seed))
    opt_state = adamw_init(params)
    start = 0
    if ckpt_dir and resume and latest_step(ckpt_dir) is not None:
        (params, opt_state), start, _ = load_checkpoint(
            ckpt_dir, (params, opt_state))
        print(f"resumed from step {start}")

    step_fn = jax.jit(make_train_step(cfg, n_micro=n_micro, peak_lr=peak_lr,
                                      warmup=max(steps // 10, 1),
                                      total_steps=steps),
                      donate_argnums=(0, 1))
    monitor = StragglerMonitor()
    losses = []
    n = start
    for n in range(start, steps):
        t0 = time.time()
        batch = {k: jax.numpy.asarray(v) for k, v in data.batch(n).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        straggle = monitor.record(time.time() - t0)
        if n % log_every == 0:
            print(f"step {n:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e}"
                  + ("  [straggler]" if straggle else ""))
        if hooks:
            for h in hooks:
                h(n, params, opt_state, metrics)
        if ckpt_dir and (n + 1) % ckpt_every == 0:
            save_checkpoint(ckpt_dir, n + 1, (params, opt_state))
        if preemption is not None and preemption.preempted:
            if ckpt_dir:
                save_checkpoint(ckpt_dir, n + 1, (params, opt_state))
            print(f"preempted at step {n + 1}: checkpointed, exiting")
            break
    if ckpt_dir:
        save_checkpoint(ckpt_dir, n + 1, (params, opt_state))
    return {"losses": losses, "last_step": n + 1,
            "straggler": monitor.summary(), "params": params}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bitnet-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = resolve_config(args.arch, args.reduced)
    print(f"training {cfg.name} ({cfg.family}) for {args.steps} steps, "
          f"batch {args.global_batch} × seq {args.seq}")
    pre = PreemptionHandler()
    out = train_loop(cfg, steps=args.steps, global_batch=args.global_batch,
                     seq_len=args.seq, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every, n_micro=args.n_micro,
                     peak_lr=args.lr, seed=args.seed, preemption=pre)
    first = np.mean(out["losses"][:10])
    last = np.mean(out["losses"][-10:])
    print(f"loss {first:.4f} → {last:.4f} over {out['last_step']} steps")


if __name__ == "__main__":
    main()

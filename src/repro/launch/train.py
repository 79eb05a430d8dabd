"""Production training launcher: ``--arch <id> --shape train_4k``.

On real hardware this process runs once per host (jax.distributed
initializes from the cluster env); in this container it drives the same
code on the local device(s).  For the 256/512-chip compile-only check use
``repro.launch.dryrun`` instead.

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --steps 20 --seq-len 64 --batch 8

Exits non-zero when a loss is not finite or when the trainer restored a
checkpoint and replayed steps: a launch reports a clean run, not a
recovered one.
"""
from __future__ import annotations

import argparse
import math
import sys
from typing import List, Optional

from repro import compile_cache
from repro.configs import get_config, get_smoke_config
from repro.configs.base import InputShape, OptimizerConfig, RunConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.presets import make_run_config
from repro.runtime import Trainer


def run_config(arch: str, *, steps: int, ckpt_dir: str,
               shape: str = "train_4k", smoke: bool = False,
               seq_len: Optional[int] = None,
               batch: Optional[int] = None) -> RunConfig:
    """The launcher's run: the arch's preset for ``shape``, with the
    sequence and global batch optionally cut and a ``steps``-long
    schedule."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    run = make_run_config(arch, shape, model_config=cfg)
    if seq_len or batch:
        run = run.replace(shape=InputShape(
            "cli",
            seq_len=seq_len or run.shape.seq_len,
            global_batch=batch or run.shape.global_batch,
            kind="train"), microbatches=1)
    return run.replace(
        checkpoint_dir=ckpt_dir,
        optimizer=OptimizerConfig(total_steps=steps,
                                  warmup_steps=max(steps // 10, 1)))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args(argv)
    compile_cache.enable()

    run = run_config(args.arch, steps=args.steps, ckpt_dir=args.ckpt_dir,
                     shape=args.shape, smoke=args.smoke,
                     seq_len=args.seq_len, batch=args.batch)
    mesh = make_host_mesh(model=args.model_parallel)
    print(f"arch={run.model.name} "
          f"params={run.model.param_count() / 1e6:.1f}M "
          f"mesh={dict(mesh.shape)} batch={run.shape.global_batch} "
          f"seq={run.shape.seq_len}")
    trainer = Trainer(run, mesh=mesh)
    state = trainer.restore_or_init()
    state = trainer.train(state, args.steps, log_every=10)
    trainer.save(state, blocking=True)

    losses = [row["loss"] for row in trainer.metrics_log if "loss" in row]
    restored = sum(1 for row in trainer.metrics_log
                   if row.get("event") == "restored")
    finite = all(math.isfinite(x) for x in losses)
    print(f"done at step {state.step} losses={losses} restored={restored}")
    return 0 if finite and restored == 0 else 1


if __name__ == "__main__":
    sys.exit(main())

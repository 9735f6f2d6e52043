"""End-to-end LM training driver with fault tolerance.

Trains an assigned architecture (reduced config by default) for a few
hundred steps with periodic checkpointing, then kills and resumes mid-run to
demonstrate crash recovery. ``--full --arch xlstm-125m`` trains the real
125M-parameter config (slow on CPU; the same code path the dry-run validates
at 256/512 chips).

Run:  PYTHONPATH=src python examples/train_lm.py --arch xlstm-125m --steps 200
"""

import argparse
import tempfile

from repro.configs import get_arch, list_archs
from repro.configs.base import ShapeSpec
from repro.train import checkpoint as ckpt
from repro.train.data import data_iterator
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m", choices=list_archs())
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    shape = ShapeSpec("example", seq_len=args.seq_len, global_batch=args.batch,
                      kind="train")

    with tempfile.TemporaryDirectory() as tmp:
        tcfg = TrainerConfig(steps=args.steps, ckpt_every=max(args.steps // 4, 1),
                             ckpt_dir=tmp, lr=2e-3)

        # Phase 1: train to ~60% and "crash".
        stop_at = args.steps * 6 // 10
        t1 = Trainer(cfg, TrainerConfig(**{**tcfg.__dict__, "steps": stop_at}),
                     data_iterator(cfg, shape))
        t1.run(on_step=lambda s, m: s % 25 == 0 and print(
            f"[phase1] step {s:4d} loss {m['loss']:.4f}"))
        print(f"-- simulated failure at step {stop_at}; latest checkpoint: "
              f"step {ckpt.latest_step(tmp)}")

        # Phase 2: a fresh Trainer restores and finishes the run.
        t2 = Trainer(cfg, tcfg, data_iterator(cfg, shape))
        t2.run(on_step=lambda s, m: s % 25 == 0 and print(
            f"[phase2] step {s:4d} loss {m['loss']:.4f}"))

        first = t1.history[0]["loss"]
        last = t2.history[-1]["loss"]
        print(f"\nloss {first:.4f} -> {last:.4f} over {args.steps} steps "
              f"(resumed from step {stop_at}); "
              f"stragglers flagged: {len(t1.straggler_events) + len(t2.straggler_events)}")


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    main()

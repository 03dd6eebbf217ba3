"""End-to-end driver: train a ~135M-parameter LM (smollm-135m) with the
paper's group-sparse OT domain-alignment auxiliary loss.

The OT loss routes through the differentiable ``repro.ot.OTLayer`` façade
(exact Danskin gradients through the screened dual; docs/training.md), so
``--ot-solver stochastic`` swaps in the minibatch dual-ascent solver without
touching the training loop.

Full run (a few hundred steps on the real config — the assignment's e2e
driver; several hours on this CPU container):

  PYTHONPATH=src python examples/train_lm_ot.py --steps 300

Quick run (reduced model, ~2 min):

  PYTHONPATH=src python examples/train_lm_ot.py --quick

CI smoke (tiny model, a few steps; exits non-zero unless the training loss
strictly decreases):

  PYTHONPATH=src python examples/train_lm_ot.py --smoke

Demonstrates: deterministic data pipeline, AdamW + cosine schedule, remat,
crash-safe checkpointing (kill it mid-run and re-launch: it resumes), the
straggler watchdog, and the OT alignment loss solved with Algorithm 1.
"""
import argparse
import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs import get_config
from repro.configs.base import OptimizerConfig, TrainConfig
from repro.data.pipeline import SyntheticLM, SyntheticLMConfig
from repro.training.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model, few steps; exit 1 unless loss decreases")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ot_ckpt")
    ap.add_argument("--no-ot", action="store_true")
    ap.add_argument("--ot-solver", default="lbfgs",
                    choices=("lbfgs", "stochastic"),
                    help="dual solver for the OT alignment loss")
    ap.add_argument("--ot-grad-impl", default="screened",
                    choices=("dense", "screened", "pallas", "fused"),
                    help="gradient-oracle backend for the OT alignment loss")
    ap.add_argument("--dtype", default="float32",
                    help="param/compute dtype; float32 avoids slow bf16 "
                         "emulation on CPU (bf16 is the TPU deployment dtype)")
    args = ap.parse_args()

    cfg = get_config("smollm-135m")
    cfg = dataclasses.replace(cfg, param_dtype=args.dtype, compute_dtype=args.dtype)
    steps = args.steps
    if args.smoke:
        cfg = cfg.reduced(num_layers=2, d_model=64, d_ff=128, vocab_size=128)
        steps = min(steps, 8)
        args.batch, args.seq = 4, 32
    elif args.quick:
        cfg = cfg.reduced(num_layers=4, d_model=128, d_ff=256, vocab_size=1024)
        steps = min(steps, 40)

    tcfg = TrainConfig(
        optimizer=OptimizerConfig(lr=1e-3 if args.smoke else 6e-4,
                                  warmup_steps=max(steps // 10, 2 if args.smoke else 5),
                                  decay_steps=steps),
        steps=steps,
        log_every=1 if args.smoke else max(steps // 20, 1),
        checkpoint_every=max(steps // 4, 10),
        ot_align=not args.no_ot,
        ot_align_weight=0.05,
        ot_solver=args.ot_solver,
        ot_grad_impl=args.ot_grad_impl,
    )
    data = SyntheticLM(
        SyntheticLMConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, num_classes=8)
    )
    ckpt_dir = None if args.smoke else args.ckpt
    trainer = Trainer(cfg, tcfg, data, ckpt_dir=ckpt_dir)
    final = trainer.run()
    first = trainer.metrics_history[0] if trainer.metrics_history else {}
    print(f"\nce: {first.get('ce', float('nan')):.4f} -> {final.get('ce', float('nan')):.4f}"
          f"   (ot_distance: {final.get('ot_distance', 'n/a')})")

    if args.smoke:
        ok = final.get("loss", float("inf")) < first.get("loss", float("-inf"))
        print(f"smoke: loss {first.get('loss'):.4f} -> {final.get('loss'):.4f} "
              f"({'DECREASED' if ok else 'DID NOT DECREASE'})")
        if not ok:
            sys.exit(1)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

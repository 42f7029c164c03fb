"""End-to-end training driver: a ~100M-parameter LM for a few hundred steps
on the deterministic synthetic pipeline, with checkpointing/auto-resume and
the full trainer stack (the same code path the pod launcher uses).

    PYTHONPATH=src python examples/train_lm.py --steps 300 [--resume]

On this CPU container a step takes a few seconds; kill it mid-run and
re-invoke to watch auto-resume continue from the latest checkpoint.
"""

import argparse
import sys

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.config import (
    HOST_MESH,
    ModelConfig,
    OptimizerConfig,
    RunConfig,
    ShapeConfig,
)
from repro.data import DataConfig, make_pipeline
from repro.models.model import build_model
from repro.sharding.rules import Dist
from repro.train.trainer import Trainer

# ~100M params: 12L x 512d with a 32k vocab (embed 16.4M + blocks 44M + head 16.4M)
LM_100M = ModelConfig(
    name="lm_100m", family="dense", n_layers=12, d_model=512, n_heads=8,
    n_kv_heads=8, d_ff=2048, vocab_size=32_000, head_dim=64,
    remat="none", tie_embeddings=False,
)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    model = build_model(LM_100M)
    print(f"model: {model.n_params() / 1e6:.1f}M params")

    run = RunConfig(
        model=LM_100M,
        shape=ShapeConfig("example", args.seq, args.batch, "train"),
        mesh=HOST_MESH,
        optimizer=OptimizerConfig(lr=3e-4, warmup_steps=20,
                                  total_steps=args.steps, schedule="cosine"),
        micro_batches=2,
        checkpoint_dir=args.ckpt,
        checkpoint_every=50,
    )
    data = make_pipeline(DataConfig(
        vocab_size=LM_100M.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=0,
    ))

    trainer = Trainer(model=model, run=run, dist=Dist(), data=data, log_every=10)
    trainer.install_preemption_handler()
    if trainer.try_resume():
        print(f"resumed from step {trainer.step}")

    out = trainer.fit(args.steps)
    print(f"done: {out['steps']} steps, final loss {out['final_loss']:.4f}, "
          f"slow steps {out['slow_steps']}")
    for m in out["log"][-5:]:
        print(f"  step {m['step']:4d} loss {m['loss']:.4f} "
              f"({m['dt_s']*1e3:.0f} ms/step)")
    data.stop()


if __name__ == "__main__":
    main()

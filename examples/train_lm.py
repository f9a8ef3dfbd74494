"""End-to-end training driver (deliverable b): train a ~100M-parameter LM
for a few hundred steps with the full runtime stack — LSA-sliced trainer,
atomic checkpoints, replica voting, resumable data pipeline.

    PYTHONPATH=src python examples/train_lm.py --size 100m --steps 300
    PYTHONPATH=src python examples/train_lm.py --size 20m  --steps 200   # CPU-friendly
    PYTHONPATH=src python examples/train_lm.py --size tiny --steps 40    # smoke

Kill it mid-run and re-invoke with --resume: training continues byte-exactly
from the last checkpoint (the paper's stop-and-go).
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, RunConfig, ShapeConfig, TrainConfig
from repro.models import build_model
from repro.models.counting import param_count
from repro.resilience.checkpoint import CheckpointManager
from repro.resilience.voting import ReplicaVoter
from repro.train.data import pipeline_for
from repro.train.train_step import init_train_state, make_train_step
from repro.train.trainer import Trainer
from repro.launch.cache import enable_compile_cache

SIZES = {
    # ~104M params
    "100m": dict(num_layers=12, d_model=640, num_heads=10, num_kv_heads=10,
                 d_ff=2560, vocab_size=32000, seq=512, batch=8),
    # ~21M params — a few hundred steps complete in minutes on CPU
    "20m": dict(num_layers=8, d_model=320, num_heads=5, num_kv_heads=5,
                d_ff=1280, vocab_size=16000, seq=256, batch=8),
    "tiny": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                 d_ff=128, vocab_size=512, seq=64, batch=4),
}


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="20m", choices=list(SIZES))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/rexa_train_lm")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lsa", action="store_true", help="schedule via LSA")
    args = ap.parse_args(argv)

    s = SIZES[args.size]
    model_cfg = ModelConfig(
        name=f"lm-{args.size}", family="dense",
        num_layers=s["num_layers"], d_model=s["d_model"],
        num_heads=s["num_heads"], num_kv_heads=s["num_kv_heads"],
        d_ff=s["d_ff"], vocab_size=s["vocab_size"], dtype="float32",
    )
    shape = ShapeConfig("train", seq_len=s["seq"], global_batch=s["batch"], kind="train")
    tcfg = TrainConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps,
                       slice_steps=10, ckpt_every_slices=5, seed=0)
    run = RunConfig(model=model_cfg, shape=shape, train=tcfg)

    model = build_model(model_cfg)
    print(f"[train_lm] {model_cfg.name}: {param_count(model_cfg)/1e6:.1f}M params, "
          f"batch {s['batch']} x seq {s['seq']}")
    state = init_train_state(model, tcfg, jax.random.key(0))
    step = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
    pipe = pipeline_for(model_cfg, shape, seed=0)

    trainer = Trainer(
        run, step, state, pipe,
        ckpt=CheckpointManager(args.ckpt_dir, keep=2),
        voter=ReplicaVoter(n_replicas=1),
        put_batch=lambda b: {k: jnp.asarray(v) for k, v in b.items()},
    )
    if args.resume and trainer.restore():
        print(f"[train_lm] resumed at step {trainer.current_step()}")

    t0 = time.time()
    if args.lsa:
        trainer.run_slice(2)  # profile one mini-slice for LSA durations
        trainer.train_lsa(args.steps)
    else:
        while trainer.current_step() < args.steps:
            m = trainer.run_slice(
                min(tcfg.slice_steps, args.steps - trainer.current_step())
            )
            st = trainer.current_step()
            tok_s = s["batch"] * s["seq"] * tcfg.slice_steps / trainer.log.slice_times[-1]
            print(f"[train_lm] step {st:4d}  loss {m['loss']:.4f}  "
                  f"gnorm {m['grad_norm']:.2f}  {tok_s:,.0f} tok/s")
            if st % (tcfg.slice_steps * tcfg.ckpt_every_slices) == 0:
                trainer.save()
    trainer.save()
    print(f"[train_lm] {trainer.current_step()} steps in {time.time()-t0:.0f}s; "
          f"loss {trainer.log.losses[0]:.3f} -> {trainer.log.losses[-1]:.3f}; "
          f"checkpoints at {trainer.log.ckpt_steps}")


if __name__ == "__main__":
    main()

"""End-to-end LM training driver (example-scale): data pipeline with
chaotic-PRNG shuffling, microbatched train step, checkpoint/resume, straggler
watchdog — the full production loop at CPU-runnable size.

Run:    PYTHONPATH=src python examples/train_lm.py --steps 60
Resume: rerun the same command — it restarts from the latest checkpoint.

``--preset small`` is a ~100M-class config; the default ``tiny`` keeps the
example fast on CPU.  On TPU pods use repro.launch.train instead (same loop,
production mesh + sharding).
"""
import argparse
import dataclasses
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "src"))

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.data.pipeline import SyntheticLMDataset
from repro.train.loop import LoopConfig, run
from repro.train.optimizer import Adam, warmup_cosine
from repro.train.train_step import TrainStepConfig, init_train_state, make_train_step

PRESETS = {
    # ~8M params: fast on CPU
    "tiny": ModelConfig(name="tiny_lm", n_layers=4, d_model=256, n_heads=4,
                        n_kv_heads=2, d_ff=1024, vocab_size=4096,
                        remat=False, dtype="float32"),
    # ~100M params (llama3-family shape, the e2e-driver scale)
    "small": ModelConfig(name="small_lm", n_layers=12, d_model=768, n_heads=12,
                         n_kv_heads=4, d_ff=2048, vocab_size=32000,
                         remat=True, dtype="bfloat16"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="tiny")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="results/train_lm_ckpt")
    ap.add_argument("--chaotic-shuffle", action="store_true", default=True)
    args = ap.parse_args()

    cfg = PRESETS[args.preset]
    print(f"[train_lm] {cfg.name}: {cfg.n_params() / 1e6:.1f}M params")

    opt = Adam(lr=warmup_cosine(3e-4, 20, args.steps), clip_norm=1.0,
               weight_decay=0.01)
    state = init_train_state(cfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(
        cfg, opt, TrainStepConfig(num_microbatches=args.microbatches)))

    ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=args.seq,
                            global_batch=args.batch, seed=0,
                            use_chaotic_shuffle=args.chaotic_shuffle)
    batch_at = lambda i: {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}

    res = run(state, step, batch_at,
              LoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=20, log_every=5))
    first = res.metrics_history[0]["loss"] if res.metrics_history else float("nan")
    last = res.metrics_history[-1]["loss"] if res.metrics_history else float("nan")
    print(f"[train_lm] loss {first:.3f} -> {last:.3f} "
          f"(resumed_from={res.resumed_from}, stragglers={len(res.straggler_steps)})")
    assert last < first, "loss did not decrease"
    print("[train_lm] complete.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

# End-to-end LM training driver: forelem data pipeline → packed dataset →
# fault-tolerant chunked training (hybrid scheduling §III-A3) with
# checkpoint/restart and a simulated mid-run worker failure.
#
# Default config is CPU-sized (~8M params, 200 steps, a few minutes).
# ``--full`` selects a ~100M-param config (the deliverable scale — sized for
# real accelerators).
#
# Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200] [--full]
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.base import get_config, reduced_config
from repro.data.pipeline import PipelineConfig, ShardedLoader, build_dataset
from repro.models.transformer import Model
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.step import TrainSpec, make_train_step


def synth_corpus(n_docs: int, seed: int = 0):
    """Markov-ish synthetic text so the loss has learnable structure."""
    rng = np.random.default_rng(seed)
    vocab = [f"tok{i}" for i in range(512)]
    docs = []
    for _ in range(n_docs):
        n = int(rng.integers(16, 256))
        state = int(rng.integers(0, 512))
        words = []
        for _ in range(n):
            state = (state * 31 + int(rng.integers(0, 7))) % 512
            words.append(vocab[state])
        docs.append(" ".join(words))
    return docs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true", help="~100M-param config")
    ap.add_argument("--ckpt-dir", default="runs/ckpt_train_lm")
    ap.add_argument("--fail-at-step", type=int, default=-1, help="simulate worker failure")
    args = ap.parse_args()
    use_compile_cache()

    # --- data: the forelem pipeline ----------------------------------------
    print("building dataset through the forelem pipeline ...")
    docs = synth_corpus(3000)
    ds = build_dataset(docs, PipelineConfig(seq_len=args.seq, min_doc_tokens=8, vocab_size=1024))
    print(f"  {ds.n_docs} docs -> {len(ds)} packed rows, {ds.n_tokens} tokens, vocab {ds.vocab.size}")

    # --- model ----------------------------------------------------------------
    base = get_config("starcoder2-3b")
    if args.full:
        cfg = dataclasses.replace(base, n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                                  head_dim=64, d_ff=3072, vocab_size=ds.vocab.size, tie_embeddings=True)
    else:
        cfg = dataclasses.replace(reduced_config(base), n_layers=4, d_model=256, n_heads=8,
                                  n_kv_heads=4, head_dim=32, d_ff=1024, vocab_size=ds.vocab.size,
                                  window=args.seq, max_seq_len=args.seq)
    model = Model(cfg)
    print(f"  model: {model.n_params()/1e6:.1f}M params ({cfg.arch_id} family)")

    params = model.init_params(jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(lr_peak=3e-3, warmup_steps=20, total_steps=args.steps)
    opt_state = adamw_init(params)
    train_step = jax.jit(make_train_step(model, opt_cfg, TrainSpec(microbatches=1, remat=False)),
                         donate_argnums=(0, 1))

    loader = ShardedLoader(ds, global_batch=args.batch)
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)

    # restore if a checkpoint exists (restart-after-failure path)
    start_step = 0
    if ckpt.latest_step() is not None:
        start_step, (params, opt_state) = ckpt.restore((params, opt_state))
        print(f"  restored from checkpoint at step {start_step}")

    # --- training loop (one chunk of the hybrid schedule = ckpt interval) --
    t0 = time.time()
    losses = []
    chunk = 25  # static-schedule chunk size; dynamic level = this loop
    step = start_step
    while step < args.steps:
        chunk_end = min(step + chunk, args.steps)
        for s in range(step, chunk_end):
            if s == args.fail_at_step:
                print(f"  !! simulated worker failure at step {s} — restart from checkpoint")
                last = ckpt.latest_step() or 0
                last, (params, opt_state) = ckpt.restore((params, opt_state))
                step = last
                break
            batch = {k: jnp.asarray(v) for k, v in loader.batch(s).items()}
            params, opt_state, metrics = train_step(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if s % 20 == 0:
                print(f"  step {s:4d}  loss {losses[-1]:.4f}  lr {float(metrics['lr']):.2e}"
                      f"  gnorm {float(metrics['grad_norm']):.2f}")
        else:
            step = chunk_end
            ckpt.save(step, (params, opt_state), blocking=False)
            continue
        continue
    ckpt.wait()
    dt = time.time() - t0
    tok_s = (args.steps - start_step) * args.batch * args.seq / max(dt, 1e-9)
    print(f"\nfinal loss {losses[-1]:.4f} (from {losses[0]:.4f}); {tok_s:,.0f} tok/s on CPU")
    assert losses[-1] < losses[0], "loss did not improve"
    print("loss improved ✓  checkpoints in", args.ckpt_dir)


if __name__ == "__main__":
    main()

# Serving example: batched prefill + decode with KV cache (bf16 or int8),
# greedy/temperature sampling, simple request batcher.
#
# Run:  PYTHONPATH=src python examples/serve_lm.py [--batch 4] [--new 32]
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.base import get_config, reduced_config
from repro.models.transformer import Model, prefill_forward
from repro.serve.kvcache import cache_bytes, dequantize_kv, quantize_kv
from repro.serve.step import generate


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new", type=int, default=32)
    args = ap.parse_args()
    use_compile_cache()

    cfg = reduced_config(get_config(args.arch))
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    print(f"serving {args.arch} (reduced: {model.n_params()/1e6:.1f}M params)")

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(4, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)

    # --- batched generation ---------------------------------------------------
    t0 = time.time()
    res = generate(model, params, prompts, max_new_tokens=args.new)
    dt = time.time() - t0
    print(f"generated {args.batch}×{args.new} tokens in {dt:.1f}s "
          f"({args.batch*args.new/dt:.1f} tok/s incl. compile)")
    print("sample:", np.asarray(res.tokens[0, args.prompt_len:args.prompt_len+12]))

    # --- int8 KV cache (serve-memory optimization) ---------------------------
    _, cache = prefill_forward(params, {"tokens": prompts}, cfg)
    q = quantize_kv(cache)
    deq = dequantize_kv(q)
    b0, b1 = cache_bytes(cache), cache_bytes(q)
    # error on the k tensors
    def first_kv(tree):
        for leaf in jax.tree.leaves(tree):
            return leaf
    err = float(jnp.max(jnp.abs(
        jax.tree.leaves(cache)[0].astype(jnp.float32) - jax.tree.leaves(deq)[0].astype(jnp.float32))))
    print(f"int8 KV cache: {b0/1e6:.2f} MB -> {b1/1e6:.2f} MB ({b0/max(b1,1):.2f}x), max abs err {err:.4f}")


if __name__ == "__main__":
    main()

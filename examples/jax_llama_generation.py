"""Autoregressive generation with the Llama KV cache — decode throughput.

Inference counterpart of ``jax_llama_training.py``: prefill + lax.scan
decoding through the static-shape KV cache (``models.llama.generate``).
Random weights by default (throughput measurement; swap in an orbax
checkpoint via --checkpoint to decode from trained params,
``docs/inference.md``).

    python examples/jax_llama_generation.py --model 300m --prompt-len 128 \
        --max-new-tokens 256 --batch-size 8
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import (
    LLAMA_1B,
    LLAMA_300M,
    LLAMA_8B,
    LLAMA_TINY,
    MOE_SMALL,
    MOE_TINY,
    LlamaLM,
    MoeLM,
    generate,
)

# MoE configs decode through the same generate() (no-drop expert
# capacity — see models.moe_lm.MoeBlock).
CONFIGS = {"tiny": (LlamaLM, LLAMA_TINY), "300m": (LlamaLM, LLAMA_300M),
           "1b": (LlamaLM, LLAMA_1B), "8b": (LlamaLM, LLAMA_8B),
           "moe-tiny": (MoeLM, MOE_TINY), "moe-small": (MoeLM, MOE_SMALL)}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", choices=sorted(CONFIGS), default="300m")
    parser.add_argument("--prompt-len", type=int, default=128)
    parser.add_argument("--max-new-tokens", type=int, default=256)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--temperature", type=float, default=0.0)
    parser.add_argument("--checkpoint", default=None,
                        help="orbax checkpoint dir of model params")
    args = parser.parse_args()

    model_cls, cfg = CONFIGS[args.model]
    model = model_cls(cfg)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(
        0, cfg.vocab_size, (args.batch_size, args.prompt_len)), jnp.int32)

    if args.checkpoint:
        import orbax.checkpoint as ocp

        variables = ocp.PyTreeCheckpointer().restore(args.checkpoint)
    else:
        variables = model.init(jax.random.PRNGKey(0), prompt[:, :8])
    if model_cls is MoeLM:
        # Apply with params only: a stale init-time aux_loss collection
        # must not ride along (MoeLM docstring).
        variables = {"params": variables["params"]}

    kwargs = dict(max_new_tokens=args.max_new_tokens,
                  temperature=args.temperature,
                  rng=jax.random.PRNGKey(1))
    # First call compiles prefill + the scan; fetch a token as the barrier.
    out = generate(model, variables, prompt, **kwargs)
    int(out[0, -1])

    t0 = time.perf_counter()
    out = generate(model, variables, prompt, **kwargs)
    int(out[0, -1])
    dt = time.perf_counter() - t0

    new_tokens = args.batch_size * args.max_new_tokens
    label = args.model if model_cls is MoeLM else f"llama-{args.model}"
    print(f"{label} prompt={args.prompt_len} "
          f"b={args.batch_size}: "
          f"{new_tokens / dt:.0f} decode tokens/sec "
          f"({args.max_new_tokens / dt:.1f} tok/s/sequence), "
          f"sample ids {np.asarray(out[0, args.prompt_len:args.prompt_len + 8])}")


if __name__ == "__main__":
    main()

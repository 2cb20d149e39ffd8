"""Decoder-only (Llama-style) LM training benchmark.

BASELINE.json lists "Llama-3-8B — stress fused allreduce at LLM gradient
sizes" among the target configs; this script runs the same shape of workload
at any size:

    python examples/jax_llama_training.py --model tiny --seq-len 256
    python examples/jax_llama_training.py --model 1b --seq-len 2048

``--seq-parallel N`` shards the SEQUENCE over N chips (data x seq mesh):
ring attention rotates K/V blocks over ICI, RoPE gets each shard's global
positions, and the next-token loss shift crosses shard boundaries with one
ppermute — max context scales linearly with N.

    python examples/jax_llama_training.py --seq-len 8192 --seq-parallel 4
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import (LLAMA_1B, LLAMA_8B, LLAMA_300M, LLAMA_TINY,
                                LlamaLM, causal_lm_loss,
                                chunked_causal_lm_loss, sp_causal_lm_loss)
from horovod_tpu.ops.attention import make_attention_fn
from horovod_tpu.parallel import make_mesh
from horovod_tpu.parallel.sequence import ring_attention

CONFIGS = {"tiny": LLAMA_TINY, "300m": LLAMA_300M,
           "1b": LLAMA_1B, "8b": LLAMA_8B}


def build_train_step(model, tx, mesh, *, sp=1, s_local=None,
                     chunked_loss=0):
    """The jitted train step this script times — loss, gradients and
    ``hvd.DistributedOptimizer`` update inside ``jax.shard_map`` over
    ``mesh`` — as ``step(params, opt_state, ids) -> (params, opt_state,
    loss)`` (params and state donated). ``chip_smoke.py`` builds its
    Llama phase with this same function."""
    if sp > 1:
        def loss_fn(p, ids):
            idx = lax.axis_index("seq")
            positions = idx * s_local + jnp.arange(s_local)
            logits = model.apply({"params": p}, ids, positions=positions)
            return sp_causal_lm_loss(logits, ids, "seq")

        def train_step(p, s, ids):
            loss, grads = jax.value_and_grad(loss_fn)(p, ids)
            # Each seq shard holds its contribution to d(global loss)/dp:
            # sum over the axis; the optimizer then averages over data.
            grads = jax.tree.map(lambda g: lax.psum(g, "seq"), grads)
            updates, s = tx.update(grads, s, p)
            return optax.apply_updates(p, updates), s, hvd.allreduce(loss)

        in_specs = (P(), P(), P("data", "seq"))
    else:
        if chunked_loss:
            def loss_fn(p, ids):
                hidden = model.apply({"params": p}, ids, return_hidden=True)
                return chunked_causal_lm_loss(
                    hidden, p["lm_head"]["kernel"], ids,
                    num_chunks=chunked_loss)
        else:
            def loss_fn(p, ids):
                return causal_lm_loss(model.apply({"params": p}, ids), ids)

        def train_step(p, s, ids):
            loss, grads = jax.value_and_grad(loss_fn)(p, ids)
            updates, s = tx.update(grads, s, p)
            return optax.apply_updates(p, updates), s, hvd.allreduce(loss)

        in_specs = (P(), P(), P("data"))

    return jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=in_specs, out_specs=(P(), P(), P()),
        check_vma=False,
    ), donate_argnums=(0, 1))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", choices=list(CONFIGS), default="tiny")
    parser.add_argument("--seq-len", type=int, default=256)
    parser.add_argument("--batch-size", type=int, default=4,
                        help="per-chip batch")
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--no-flash", action="store_true")
    parser.add_argument("--seq-parallel", type=int, default=1,
                        help="shard the sequence over this many chips "
                             "(ring attention + global RoPE positions)")
    parser.add_argument("--remat", action="store_true",
                        help="jax.checkpoint each block: O(1)-layers live "
                             "activations for ~1/3 extra FLOPs (long "
                             "sequences past the no-remat HBM ceiling)")
    parser.add_argument("--optimizer", choices=["adamw", "sgd", "adafactor"],
                        default="adamw",
                        help="adafactor (factored second moments, the "
                             "classic TPU memory-lean optimizer) fits "
                             "models whose f32 Adam moments alone would "
                             "blow HBM — e.g. Llama-1B on one 16 GiB chip")
    parser.add_argument("--chunked-loss", type=int, default=0, metavar="K",
                        help="split the sequence into K chunks and apply "
                             "the lm_head + loss per chunk (LARGER K = "
                             "less peak HBM): the (B,S,V) logits never "
                             "materialize (pairs with --remat for the "
                             "longest single-chip sequences)")
    args = parser.parse_args()

    hvd.init()
    n = hvd.local_num_devices()
    cfg = CONFIGS[args.model]
    if args.remat:
        cfg = dataclasses.replace(cfg, remat=True)
    sp = args.seq_parallel
    if sp < 1 or n % sp or args.seq_len % sp:
        raise SystemExit(f"--seq-parallel {sp} must be >= 1 and divide both "
                         f"the device count ({n}) and --seq-len "
                         f"({args.seq_len})")
    dp = n // sp
    if args.chunked_loss and sp > 1:
        raise SystemExit("--chunked-loss applies to the single-sequence "
                         "path; under --seq-parallel the logits are already "
                         "sequence-sharded")

    if sp > 1:
        mesh = make_mesh({"data": dp, "seq": sp})
        ring_flash = False if args.no_flash else "auto"
        attention_fn = lambda q, k, v, m: ring_attention(  # noqa: E731
            q, k, v, axis_name="seq", causal=True, use_flash=ring_flash)
        # ring_attention takes grouped K/V directly: the ring rotates K/V
        # blocks, so GQA cuts the per-step ICI bytes to Hkv/H.
        attention_fn.supports_gqa = True
    else:
        mesh = hvd.parallel.mesh()
        # use_flash="auto": Pallas flash above FLASH_AUTO_MIN_SEQ, plain
        # XLA softmax below (faster at short seq; measured on v5e).
        attention_fn = None if args.no_flash else make_attention_fn(
            causal=True)
    model = LlamaLM(cfg, attention_fn=attention_fn)

    batch = args.batch_size * dp
    s_local = args.seq_len // sp
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, args.seq_len)), jnp.int32)

    # Init with a plain twin: attention_fn contributes no params, and the
    # ring kernel's axis name only exists inside the shard_map. Init at a
    # SHORT length — params are length-independent, and the twin's XLA
    # attention would materialize S^2 logits at full length (16 GiB at
    # S=16k: the init, not the train step, was the single-chip ceiling).
    init_len = min(s_local, 512)
    params = LlamaLM(cfg).init(jax.random.PRNGKey(0),
                               ids[:1, :init_len])["params"]
    inner_tx = {
        "adamw": lambda: optax.adamw(3e-4),
        "sgd": lambda: optax.sgd(0.1, momentum=0.9),
        "adafactor": lambda: optax.adafactor(3e-4),
    }[args.optimizer]()
    tx = hvd.DistributedOptimizer(inner_tx, axis_name="data")
    opt_state = tx.init(params)

    step = build_train_step(model, tx, mesh, sp=sp, s_local=s_local,
                            chunked_loss=args.chunked_loss)

    ids_s = jax.device_put(
        ids, hvd.parallel.data_sharding(mesh, *(("seq",) if sp > 1 else ())))
    params = hvd.parallel.replicate(params, mesh)
    opt_state = hvd.parallel.replicate(opt_state, mesh)

    params, opt_state, loss = step(params, opt_state, ids_s)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        params, opt_state, loss = step(params, opt_state, ids_s)
    float(loss)
    dt = time.perf_counter() - t0
    if hvd.rank() == 0:
        tok_per_sec = batch * args.seq_len * args.num_iters / dt
        print(f"llama-{args.model} seq={args.seq_len}: "
              f"{tok_per_sec:.0f} tokens/sec ({tok_per_sec / n:.0f}/chip), "
              f"loss={float(loss):.3f}")


if __name__ == "__main__":
    main()

"""b8 decode floor probe (round-5 verdict Weak #1).

``artifacts/decode_ceiling_r5.json`` left b8 decode at 68% of the
weights+cache roofline and ASSERTED the residual is "the while loop's
intrinsic per-iteration cost" without measuring it. This probe pins it:

1. **Minimal-body while loop** at the SAME iteration count as the decode
   scan (``--max-new-tokens`` - 1 = 255 by default): a ``lax.scan`` whose
   body is one elementwise op on a (batch,) carry. Its wall time IS the
   platform's fixed per-iteration cost (dispatch, loop bookkeeping,
   carry plumbing) with zero useful work — directly comparable to the
   per-step residual the r5 artifact attributes to the loop.
2. **Unrolled decode**: ``generate(..., unroll=k)`` replicates the scan
   body k tokens per while iteration (the KV cache takes one in-place
   row write per token either way), amortizing that fixed cost 1/k. If
   the floor hypothesis is right, b8 throughput rises toward the
   roofline as k grows; if it's wrong, unrolling moves nothing.

Writes ``artifacts/decode_floor.json``: either b8 >= 70% of the
roofline (unroll harvested the residual) or floor ~= residual (the
hypothesis is pinned, not asserted).

Run: python examples/decode_floor_probe.py --model 300m --batch-size 8
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def measure_empty_loop(iters: int, batch: int, reps: int = 5):
    """Median wall time of a jitted lax.scan of ``iters`` minimal-body
    steps: one (batch,) f32 add per step — the floor any same-length
    decode loop pays before doing useful work."""
    import statistics

    import jax
    import jax.numpy as jnp

    @jax.jit
    def loop(x):
        def body(c, _):
            return c + 1.0, ()
        out, _ = jax.lax.scan(body, x, None, length=iters)
        return out

    x = jnp.zeros((batch,), jnp.float32)
    float(loop(x)[0])  # compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(loop(x)[0])  # device fetch = sync barrier
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def measure_decode(model, variables, prompt, new_tokens: int, unroll: int,
                   reps: int = 3):
    """Median decode rate (tok/s) of ``generate`` at the given unroll."""
    import statistics

    import jax
    import numpy as np

    from horovod_tpu.models.llama import generate

    b = prompt.shape[0]
    out = generate(model, variables, prompt, max_new_tokens=new_tokens,
                   unroll=unroll)
    int(np.asarray(out)[0, -1])  # compile + settle
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = generate(model, variables, prompt,
                       max_new_tokens=new_tokens, unroll=unroll)
        int(np.asarray(out)[0, -1])
        rates.append(b * new_tokens / (time.perf_counter() - t0))
    return statistics.median(rates), out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="300m",
                    choices=["tiny", "300m", "1b"])
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=256)
    ap.add_argument("--unrolls", default="1,2,4")
    ap.add_argument("--roofline-tok-s", type=float, default=None,
                    help="weights+cache roofline for the config (r5 "
                    "artifact models b8 at ~9.3k tok/s on v5e); when "
                    "set, the artifact records pct_of_roofline")
    ap.add_argument("--serving", action="store_true",
                    help="also run the same b-request workload through "
                    "the hvd.serving continuous batcher (the floor's "
                    "first customer: the batcher amortizes exactly the "
                    "per-iteration cost this probe pins) and record the "
                    "amortized rate beside the bare rows")
    ap.add_argument("--out", default="artifacts/decode_floor.json")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.models import LLAMA_1B, LLAMA_300M, LLAMA_TINY, LlamaLM

    hvd.init()
    cfg = {"tiny": LLAMA_TINY, "300m": LLAMA_300M,
           "1b": LLAMA_1B}[args.model]
    model = LlamaLM(cfg)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch_size, args.prompt_len)),
        jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), prompt[:, :8])

    iters = args.max_new_tokens - 1
    floor_s = measure_empty_loop(iters, args.batch_size)
    floor_us_per_iter = 1e6 * floor_s / iters
    print(f"minimal-body loop: {iters} iters in {floor_s * 1e3:.2f} ms "
          f"({floor_us_per_iter:.1f} us/iter)", file=sys.stderr)

    rows = {}
    baseline = None
    for unroll in [int(u) for u in args.unrolls.split(",")]:
        rate, out = measure_decode(model, variables, prompt,
                                   args.max_new_tokens, unroll)
        if baseline is None:
            baseline = out
        else:
            mism = int(np.sum(np.asarray(baseline) != np.asarray(out)))
            if mism:
                print(f"WARNING: unroll={unroll} changed {mism} greedy "
                      "tokens (bf16 tie noise)", file=sys.stderr)
        rows[f"unroll{unroll}"] = round(rate, 1)
        print(f"decode b{args.batch_size} unroll={unroll}: "
              f"{rate:.0f} tok/s", file=sys.stderr)

    record = {
        "what": ("b8 decode floor probe: minimal-body lax.scan at the "
                 "decode iteration count pins the fixed per-iteration "
                 "platform cost; generate(unroll=k) amortizes it 1/k "
                 "(round-5 verdict Weak #1)"),
        "model": args.model, "batch": args.batch_size,
        "prompt_len": args.prompt_len,
        "max_new_tokens": args.max_new_tokens,
        "substrate": jax.default_backend(),
        "empty_loop_ms_total": round(floor_s * 1e3, 3),
        "empty_loop_us_per_iter": round(floor_us_per_iter, 2),
        "decode_tok_s": rows,
    }
    if args.serving:
        # The serving tier over the same workload: the probe's batch
        # becomes batch-size individual requests through the continuous
        # batcher — per-request arrivals, one shared decode loop. The
        # amortized rate lands beside the bare b8 floor rows so the
        # artifact answers "what does the batcher buy over bare
        # generate() at this batch" directly.
        from horovod_tpu.serving import ServingConfig
        from horovod_tpu.serving.engine import ServingEngine

        scfg = ServingConfig(
            max_batch=args.batch_size, block_size=16, num_blocks=0,
            queue_depth=max(2 * args.batch_size, 8),
            max_seq_len=args.prompt_len + args.max_new_tokens + 1)
        engine = ServingEngine(model, variables, config=scfg)
        handles = [engine.submit(np.asarray(prompt)[i],
                                 args.max_new_tokens)
                   for i in range(args.batch_size)]
        engine.run_until_idle()          # compile pass (unmeasured)
        for h in handles:
            h.result(timeout=0)
        # Drop the warmup engine's pools before the measured pass: two
        # fully-provisioned pools during measurement would double the
        # serving tier's HBM footprint (the module-level jit cache keeps
        # the compiled programs either way).
        engine.shutdown()
        del engine
        engine2 = ServingEngine(model, variables, config=scfg)
        t0 = time.perf_counter()
        handles = [engine2.submit(np.asarray(prompt)[i],
                                  args.max_new_tokens)
                   for i in range(args.batch_size)]
        engine2.run_until_idle()
        serving_s = time.perf_counter() - t0
        outs = [h.result(timeout=0) for h in handles]
        mism = sum(int(np.any(np.asarray(o)
                              != np.asarray(baseline)[i, args.prompt_len:]))
                   for i, o in enumerate(outs))
        if mism:
            print(f"WARNING: serving changed tokens in {mism} request(s) "
                  "(bf16 tie noise)", file=sys.stderr)
        st = engine2.stats()
        rate = args.batch_size * args.max_new_tokens / serving_s
        print(f"serving b{args.batch_size}: {rate:.0f} tok/s "
              f"({st['steps']} steps)", file=sys.stderr)
        # Compare against the first measured bare row — --unrolls need
        # not include 1.
        bare_key = next(iter(rows))
        record["serving"] = {
            "tok_s": round(rate, 1),
            "steps": st["steps"],
            "preemptions": st["preemptions"],
            "blocks_peak": st["blocks_peak"],
            f"vs_bare_{bare_key}": round(rate / rows[bare_key], 3),
        }
    if args.roofline_tok_s:
        record["roofline_tok_s"] = args.roofline_tok_s
        record["pct_of_roofline"] = {
            k: round(100.0 * v / args.roofline_tok_s, 1)
            for k, v in rows.items()}
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())

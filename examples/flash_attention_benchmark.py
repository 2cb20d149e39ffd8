"""Flash attention kernel micro-benchmark (forward and forward+backward).

Reproduces the README flash row and sweeps block sizes, so kernel changes
(e.g. the round-2 HBM→VMEM streaming rewrite) can be re-measured on
hardware with one command:

    python examples/flash_attention_benchmark.py                 # defaults
    python examples/flash_attention_benchmark.py --sweep         # block sweep
    python examples/flash_attention_benchmark.py --seq-len 32768 --batch 1
    python examples/flash_attention_benchmark.py --xla-reference # softmax path

Timing is dispatch-amortized: the kernel runs ``--iters`` times inside ONE
jitted ``lax.scan`` whose carry feeds each iteration (defeating
loop-invariant hoisting), and the single call is timed — a sub-millisecond
kernel is comparable to one host dispatch, so a naive Python loop over
``fn(q, k, v)`` would time the host as much as the MXU.

Prints one JSON line per configuration:
  {"metric": "flash_fwd_ms", "B":..,"S":..,"H":..,"D":..,
   "block_q":..,"block_k":..,"fwd_ms":..,"train_ms":..}
During a --sweep, a configuration that fails (e.g. a VMEM working set
beyond the chip's scoped limit) reports {"error": "vmem_oom"} and the
sweep continues; a single-config run re-raises so the failure is loud
(nonzero exit).

Off-TPU this runs the same kernel in Pallas interpreter mode — useful only
for correctness, the timings are meaningless there (a warning is printed).
"""

import argparse
import itertools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.ops.attention import (_fit_block, flash_attention,
                                       reference_attention)


def _best_call_s(callable_, reps=3):
    """Fastest wall-clock of ``reps`` calls (each call device-synced)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(callable_())
        best = min(best, time.perf_counter() - t0)
    return best


def scan_timer(fn, q, k, v, iters):
    """ms/iter for ``fn(q, k, v)``, dispatch-amortized: one jitted scan of
    ``iters`` dependent iterations, best of three timed calls, MINUS an
    empty-scan baseline timed the same way (one dispatch+fetch is a fixed
    cost — latency/iters of per-iter bias if not subtracted).
    ``fn`` must reduce its outputs to a scalar itself (sum over EVERY
    output it wants timed) — the scalar is the scan carry, so all of them
    stay live under XLA dead-code elimination."""

    def scanned(body_fn):
        @jax.jit
        def many(q, k, v):
            c, _ = lax.scan(lambda c, _: (body_fn(c, q, k, v), None),
                            jnp.float32(0.0), None, length=iters)
            return c
        return many

    # The carry perturbs q by an un-foldable ~0 so XLA can neither hoist
    # the (otherwise loop-invariant) body nor run iterations in parallel.
    many = scanned(lambda c, q, k, v: fn(q + (c * 1e-30).astype(q.dtype),
                                         k, v))
    # Baseline: same scan/dispatch/fetch structure, trivial body.
    empty = scanned(lambda c, q, k, v: c + 1.0)

    float(many(q, k, v))   # compile + device fetch as the barrier
    float(empty(q, k, v))
    timed = _best_call_s(lambda: many(q, k, v))
    base = _best_call_s(lambda: empty(q, k, v))
    return max(timed - base, 0.0) / iters * 1e3


def bench_config(b, s, h, d, block_q, block_k, iters, causal=True,
                 xla_reference=False):
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.randn(b, s, h, d).astype(np.float32) * 0.3, jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    if xla_reference:
        attn = lambda q, k, v: reference_attention(q, k, v, causal=causal)  # noqa: E731
    else:
        attn = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=causal, block_q=block_q, block_k=block_k)

    # Full-output sums as the timed scalar: every element of the forward
    # output (resp. of ALL THREE gradients) feeds the carry, so neither the
    # Pallas kernels nor the transparent-HLO reference path can be sliced
    # or partially dead-code-eliminated by XLA.
    def fwd(q, k, v):
        return attn(q, k, v).astype(jnp.float32).sum()

    def loss(q, k, v):
        return (attn(q, k, v).astype(jnp.float32) ** 2).sum()

    def train(q, k, v):
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return (dq.astype(jnp.float32).sum() + dk.astype(jnp.float32).sum()
                + dv.astype(jnp.float32).sum())

    return (scan_timer(fwd, q, k, v, iters),
            scan_timer(train, q, k, v, iters))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--seq-len", type=int, default=2048)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--block-q", type=int, default=512)
    parser.add_argument("--block-k", type=int, default=1024)
    parser.add_argument("--iters", type=int, default=150,
                        help="scan length per timed call; keep the scan's "
                        "total kernel time >> the 10-100 ms dispatch "
                        "overhead or the subtraction turns noisy")
    parser.add_argument("--sweep", action="store_true",
                        help="sweep block_q x block_k instead of one config")
    parser.add_argument("--xla-reference", action="store_true",
                        help="time the plain XLA softmax path instead")
    args = parser.parse_args()

    if jax.default_backend() != "tpu":
        print("warning: not on TPU — interpreter-mode timings are "
              "meaningless, use for correctness only")

    if args.sweep and not args.xla_reference:
        qs = [128, 256, 512]
        ks = [256, 512, 1024, 2048]
        configs = [(bq, bk) for bq, bk in itertools.product(qs, ks)
                   if bq <= args.seq_len and bk <= args.seq_len]
    else:
        # --xla-reference ignores block sizes: a sweep would re-time the
        # identical computation 12x and report a spurious block dependence.
        if args.sweep:
            print("note: --sweep has no effect with --xla-reference "
                  "(block sizes don't reach the XLA path); timing one "
                  "configuration", file=sys.stderr)
        configs = [(args.block_q, args.block_k)]

    # Report the EFFECTIVE blocks (the kernel clamps/halves requests that
    # don't divide the sequence) and dedupe configs that clamp to the same
    # kernel — a sweep must never record a config that was not actually run.
    effective = {(_fit_block(bq, args.seq_len),
                  _fit_block(bk, args.seq_len))
                 for bq, bk in configs}
    if not effective:
        sys.exit(f"no sweep block size fits --seq-len {args.seq_len}; "
                 "pass explicit --block-q/--block-k")

    metric = "xla_attn_fwd_ms" if args.xla_reference else "flash_fwd_ms"
    best = None
    for (bq, bk) in sorted(effective):
        rec = {"metric": metric, "B": args.batch, "S": args.seq_len,
               "H": args.heads, "D": args.head_dim, "block_q": bq,
               "block_k": bk}
        try:
            fwd_ms, train_ms = bench_config(
                args.batch, args.seq_len, args.heads, args.head_dim, bq, bk,
                args.iters, xla_reference=args.xla_reference)
        except Exception as e:  # noqa: BLE001 — sweep must survive OOM configs
            if not args.sweep:
                raise  # single-config runs must fail loudly (nonzero exit)
            msg = str(e)
            rec["error"] = ("vmem_oom" if "vmem" in msg.lower() else
                            type(e).__name__)
            # Raw (truncated) message too: the "vmem" substring match
            # would silently reclassify if Mosaic/Pallas reword the OOM
            # error — keep the sweep output diagnosable either way.
            rec["error_detail"] = msg[:200]
            print(json.dumps(rec), flush=True)
            continue
        rec.update(fwd_ms=round(fwd_ms, 3), train_ms=round(train_ms, 3))
        print(json.dumps(rec), flush=True)
        if best is None or fwd_ms < best[0]:
            best = (fwd_ms, bq, bk)
    if args.sweep and not args.xla_reference and best is not None:
        print(f"best fwd: {best[0]:.3f} ms at block_q={best[1]} "
              f"block_k={best[2]}")


if __name__ == "__main__":
    main()

"""MXU granule probe for head_dim=64 attention — the "head packing" question.

Round-4 review asked whether packing two d=64 heads into one MXU pass
(contraction 128 wide) can lift flash attention above its measured ~15%-of-
peak at d=64, or whether the shape is inherently charged at the 128 granule.

The mathematical frame first (measured below): two INDEPENDENT heads'
score products s_h = q_h @ k_h^T cannot share a dense 128-wide contraction
without either (a) block-diagonal zero padding — density 1/2, identical MAC
count to padding each d=64 contraction to 128 — or (b) the sum/difference
packing ([q1 q2]@[k1 k2]^T = s1+s2 and [q1 -q2]@[k1 k2]^T = s1-s2), which
needs TWO dense K=128 passes to recover two heads: again identical MAC
count to two padded passes. A systolic array charges dense MACs, so NO
packing can beat the per-head padded cost. Packing can therefore only win
if XLA's native d=64 dots cost MORE than one padded 128-pass each
(layout retiling, lane waste on (.., 64) arrays, per-op overhead).

So the probe measures, on the real chip:
  A. contraction sweep  — (M,K)@(K,N) bf16, K in {64,128,256,512}: is a
     K=64 dot charged ~K=128 (padding waste exists) or ~half (no waste)?
  B. output-width sweep — N in {64,128,256,512}: lane-granule charge.
  C. flash QK shapes in situ — batched (512,64)@(64,1024) at 2x batch vs
     (512,128)@(128,1024): equal useful FLOPs, direct d penalty readout.
  D. flash PV shapes in situ — batched (512,1024)@(1024,64) vs ..x128.
  E. sum/difference packed QK — the only dense packing that exists — timed
     against two native d=64 dots (prediction: no better; see frame above).
  F. end-to-end flash kernel, H8/D64 vs H4/D128 at B4 S2048 causal (equal
     FLOPs and equal model width 512): the full-kernel penalty, fwd+train.

Timing: dispatch-amortized lax.scan with value-fetch barrier and
empty-scan baseline subtraction (same method as
examples/flash_attention_benchmark.py — a naive per-call loop times the
host dispatch as much as the MXU).

Prints one JSON line per measurement and a final summary line; pipe to
artifacts/headpack_probe_r5.json via --json-out.
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.ops.attention import flash_attention

V5E_BF16_PEAK_TFS = 197.0


def _best_call_s(callable_, reps=5):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(callable_())
        best = min(best, time.perf_counter() - t0)
    return best


def scan_time_ms(body, args, iters=50, target_ms=150.0, max_iters=6000):
    """ms/iter of ``body(*args) -> scalar``, dispatch-amortized: one jitted
    scan of carry-dependent iterations, minus an empty-scan baseline.
    ``body`` must fold EVERY output it wants timed into the returned scalar
    (DCE-proof); the carry perturbs args[0] so XLA cannot hoist the
    loop-invariant body.

    Auto-calibrates the scan length so each timed call carries
    >= ``target_ms`` of device work — sub-ms kernels at short scan
    lengths sit inside the dispatch jitter and read as pure noise (an
    uncalibrated first cut of this probe measured 290% of peak)."""

    def build(n):
        def scanned(fn):
            @jax.jit
            def many(*a):
                c, _ = lax.scan(lambda c, _: (fn(c, *a), None),
                                jnp.float32(0.0), None, length=n)
                return c
            return many

        many = scanned(lambda c, *a: body(
            a[0] + (c * 1e-30).astype(a[0].dtype), *a[1:]))
        empty = scanned(lambda c, *a: c + 1.0)
        float(many(*args))   # compile + device fetch as the barrier
        float(empty(*args))
        return many, empty

    def measure(n, reps):
        many, empty = build(n)
        timed = _best_call_s(lambda: many(*args), reps)
        base = _best_call_s(lambda: empty(*args), reps)
        return max(timed - base, 0.0) / n * 1e3

    est = measure(iters, reps=2)
    need = max_iters if est <= 0 else int(target_ms / max(est, 1e-6)) + 1
    n = min(max(iters, need), max_iters)
    if n <= iters:
        return measure(iters, reps=5)
    return measure(n, reps=5)


def _rand(shape, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3,
                       jnp.bfloat16)


def tfs(flops, ms):
    return flops / (ms * 1e-3) / 1e12 if ms > 0 else float("inf")


def emit(rec, sink):
    print(json.dumps(rec), flush=True)
    sink.append(rec)


def part_a_contraction(out, iters):
    M = N = 4096
    for K in (64, 128, 256, 512):
        a, b = _rand((M, K)), _rand((K, N), seed=1)
        ms = scan_time_ms(
            lambda a, b: jnp.dot(a, b,
                                 preferred_element_type=jnp.float32).sum(),
            (a, b), iters)
        fl = 2 * M * N * K
        emit({"part": "A_contraction", "M": M, "K": K, "N": N,
              "ms": round(ms, 4), "tfs": round(tfs(fl, ms), 1),
              "pct_peak": round(100 * tfs(fl, ms) / V5E_BF16_PEAK_TFS, 1)},
             out)


def part_b_output(out, iters):
    M, K = 4096, 4096
    for N in (64, 128, 256, 512):
        a, b = _rand((M, K)), _rand((K, N), seed=1)
        ms = scan_time_ms(
            lambda a, b: jnp.dot(a, b,
                                 preferred_element_type=jnp.float32).sum(),
            (a, b), iters)
        fl = 2 * M * K * N
        emit({"part": "B_output_width", "M": M, "K": K, "N": N,
              "ms": round(ms, 4), "tfs": round(tfs(fl, ms), 1),
              "pct_peak": round(100 * tfs(fl, ms) / V5E_BF16_PEAK_TFS, 1)},
             out)


def _bmm(a, b):
    return lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                           preferred_element_type=jnp.float32)


def part_c_qk_shapes(out, iters):
    # Equal useful FLOPs: 32 heads at d=64 vs 16 heads at d=128.
    for (bh, d) in ((32, 64), (16, 128)):
        q, k = _rand((bh, 512, d)), _rand((bh, d, 1024), seed=1)
        ms = scan_time_ms(lambda q, k: _bmm(q, k).sum(), (q, k), iters)
        fl = 2 * bh * 512 * d * 1024
        emit({"part": "C_flash_qk", "bh": bh, "d": d, "ms": round(ms, 4),
              "tfs": round(tfs(fl, ms), 1),
              "pct_peak": round(100 * tfs(fl, ms) / V5E_BF16_PEAK_TFS, 1)},
             out)


def part_d_pv_shapes(out, iters):
    for (bh, d) in ((32, 64), (16, 128)):
        p, v = _rand((bh, 512, 1024)), _rand((bh, 1024, d), seed=1)
        ms = scan_time_ms(lambda p, v: _bmm(p, v).sum(), (p, v), iters)
        fl = 2 * bh * 512 * 1024 * d
        emit({"part": "D_flash_pv", "bh": bh, "d": d, "ms": round(ms, 4),
              "tfs": round(tfs(fl, ms), 1),
              "pct_peak": round(100 * tfs(fl, ms) / V5E_BF16_PEAK_TFS, 1)},
             out)


def part_e_sumdiff(out, iters):
    # Two native d=64 QK dots vs the sum/difference dense-128 packing that
    # recovers the same two score matrices: a = [q1 q2]@[k1 k2]^T,
    # b = [q1 -q2]@[k1 k2]^T, s1 = (a+b)/2, s2 = (a-b)/2.
    # q1/q2 ride STACKED as args[0] so the carry perturbation reaches both
    # dots — with q2 as a separate arg the q2@k2 product is loop-invariant
    # and XLA hoists it out of the scan (a first cut measured >peak).
    bh = 16  # pairs
    q12 = jnp.stack([_rand((bh, 512, 64)), _rand((bh, 512, 64), seed=1)])
    k1, k2 = _rand((bh, 64, 1024), seed=2), _rand((bh, 64, 1024), seed=3)

    def native(q12, k1, k2):
        return _bmm(q12[0], k1).sum() + _bmm(q12[1], k2).sum()

    def sumdiff(q12, k1, k2):
        qa = jnp.concatenate([q12[0], q12[1]], axis=2)  # (bh, 512, 128)
        qb = jnp.concatenate([q12[0], -q12[1]], axis=2)
        kp = jnp.concatenate([k1, k2], axis=1)          # (bh, 128, 1024)
        a = _bmm(qa, kp)
        b = _bmm(qb, kp)
        return (0.5 * (a + b)).sum() + (0.5 * (a - b)).sum()

    ms_n = scan_time_ms(native, (q12, k1, k2), iters)
    ms_p = scan_time_ms(sumdiff, (q12, k1, k2), iters)
    fl = 2 * (2 * bh) * 512 * 64 * 1024  # useful flops, both variants
    emit({"part": "E_sumdiff_pack", "variant": "native_2x_d64",
          "ms": round(ms_n, 4), "tfs": round(tfs(fl, ms_n), 1)}, out)
    emit({"part": "E_sumdiff_pack", "variant": "packed_dense128",
          "ms": round(ms_p, 4), "tfs": round(tfs(fl, ms_p), 1)}, out)


def part_g_pv_transposed(out, iters):
    # The PV product out = p @ v has a 64-lane output (Part B/D: charged at
    # the 128 granule, ~2x waste). Transposed, out^T = v^T @ p^T puts
    # block_q=512 on the lanes and d=64 on the temporal M axis — zero lane
    # padding IF short-M streams don't cost pipeline fill. Same useful
    # FLOPs as Part D's native rows; also the shape class of ALL THREE
    # backward-pass outputs (dq, dk, dv are (.., 64) too).
    for (bh, d) in ((32, 64), (16, 128)):
        vt, pt = _rand((bh, d, 1024)), _rand((bh, 1024, 512), seed=1)
        ms = scan_time_ms(lambda vt, pt: _bmm(vt, pt).sum(), (vt, pt), iters)
        fl = 2 * bh * 512 * 1024 * d
        emit({"part": "G_pv_transposed", "bh": bh, "d": d,
              "ms": round(ms, 4), "tfs": round(tfs(fl, ms), 1),
              "pct_peak": round(100 * tfs(fl, ms) / V5E_BF16_PEAK_TFS, 1)},
             out)


def part_f_flash_e2e(out, iters):
    B, S = 4, 2048
    for (h, d) in ((8, 64), (4, 128)):
        q, k, v = (_rand((B, S, h, d), seed=s) for s in (0, 1, 2))

        def fwd(q, k, v):
            return flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        def loss(q, k, v):
            return (flash_attention(q, k, v, causal=True).astype(
                jnp.float32) ** 2).sum()

        def train(q, k, v):
            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            return (dq.astype(jnp.float32).sum()
                    + dk.astype(jnp.float32).sum()
                    + dv.astype(jnp.float32).sum())

        ms_f = scan_time_ms(fwd, (q, k, v), iters)
        ms_t = scan_time_ms(train, (q, k, v), max(iters // 3, 10))
        # Causal useful flops ~ half of full S^2 (QK + PV, fwd).
        fl_fwd = 2 * (2 * B * h * S * S * d) / 2
        emit({"part": "F_flash_e2e", "H": h, "D": d, "B": B, "S": S,
              "fwd_ms": round(ms_f, 3), "train_ms": round(ms_t, 3),
              "fwd_tfs_useful": round(tfs(fl_fwd, ms_f), 1),
              "fwd_pct_peak": round(
                  100 * tfs(fl_fwd, ms_f) / V5E_BF16_PEAK_TFS, 1)}, out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--json-out", type=str, default=None)
    ap.add_argument("--parts", type=str, default="ABCDEFG")
    args = ap.parse_args()

    if jax.default_backend() != "tpu":
        print("warning: not on TPU — timings meaningless")

    out = []
    if "A" in args.parts:
        part_a_contraction(out, args.iters)
    if "B" in args.parts:
        part_b_output(out, args.iters)
    if "C" in args.parts:
        part_c_qk_shapes(out, args.iters)
    if "D" in args.parts:
        part_d_pv_shapes(out, args.iters)
    if "E" in args.parts:
        part_e_sumdiff(out, args.iters)
    if "F" in args.parts:
        part_f_flash_e2e(out, args.iters)
    if "G" in args.parts:
        part_g_pv_transposed(out, args.iters)

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"what": "d=64 MXU granule / head-packing probe",
                       "method": ("dispatch-amortized lax.scan, value-fetch "
                                  "barrier, empty-scan baseline subtracted, "
                                  "best of 5 calls"),
                       "peak_tfs_bf16": V5E_BF16_PEAK_TFS,
                       "rows": out}, f, indent=1)
        print(f"wrote {args.json_out}")


if __name__ == "__main__":
    main()

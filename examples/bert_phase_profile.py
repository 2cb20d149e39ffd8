"""BERT per-phase time accounting + MFU (round-5 verdict Weak #3).

BERT-base is a named BASELINE target (``BASELINE.md``) that last got a
throughput number in round 2 and never got the per-phase ceiling
treatment its sibling targets (ResNet 0.996x roofline, ViT 93% of
device-time bound) received. This harness re-measures the MLM training
step at the current tree, buckets every scheduled op by XLA provenance
(the ``vit_phase_profile`` method), and quotes MFU from the analytic
transformer FLOP count (never run on a chip: BERT MFU is not measured
on the current code).

Run: python examples/bert_phase_profile.py --model base --seq-len 128 \
         --batch-per-chip 128
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from horovod_tpu.utils.hlo_phases import (add_to_bucket, finalize_buckets,
                                          hlo_rows, newest_xplane)

# Ordered: first hit wins. Keys match the jax name-stack in hlo_stats'
# tf_op_name, e.g. "jit(step)/transpose(jvp(BertEncoder))/layer_3/
# attention/query/dot_general:".
PHASES = (
    ("attn_proj", ("/query/", "/key/", "/value/", "/out/")),
    ("attn_core", ("/attention/", "softmax", "flash")),
    ("mlp", ("/intermediate/", "/output/", "gelu", "/Dense_")),
    ("layernorm", ("LayerNorm", "layer_norm")),
    ("embed", ("embed", "one_hot", "position", "token_type")),
    ("head_loss", ("mlm", "logsumexp", "token_nll", "take_along")),
)


def classify(tf_op_name: str) -> str:
    for phase, keys in PHASES:
        if any(k in tf_op_name for k in keys):
            return phase
    return "other"


def train_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Analytic fwd+bwd FLOPs of one MLM step: 6 * 2ND matmul FLOPs
    (fwd = 2ND, bwd = 2x fwd) over the encoder + lm head, plus the
    attention O(S^2) term. N counts matmul params only (embeddings are
    gathers)."""
    h, L = cfg.hidden_size, cfg.num_layers
    inter = cfg.intermediate_size
    per_layer = 4 * h * h + 2 * h * inter      # qkv+out, mlp in/out
    matmul_params = L * per_layer + h * cfg.vocab_size
    tokens = batch * seq
    dense = 6.0 * tokens * matmul_params
    attn = 6.0 * 2.0 * L * batch * seq * seq * h  # scores + context, f+b
    return dense + attn


def capture(model_name: str, batch: int, seq: int, trace_dir: str,
            steps: int = 5, attention: str = "xla"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import (BERT_BASE, BERT_LARGE, BERT_TINY,
                                    BertEncoder, mlm_loss)

    hvd.init()
    cfg = {"base": BERT_BASE, "large": BERT_LARGE,
           "tiny": BERT_TINY}[model_name]
    attention_fn = None
    if attention == "flash":
        from horovod_tpu.ops.attention import make_attention_fn

        attention_fn = make_attention_fn(causal=False)
    model = BertEncoder(cfg, attention_fn=attention_fn)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    mask = jnp.ones((batch, seq), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids[:1],
                           deterministic=True)
    tx = optax.adamw(1e-4)
    state = tx.init(variables["params"])

    @jax.jit
    def step(p, s, ids, mask):
        def loss_fn(pp):
            logits = model.apply({"params": pp}, ids, attention_mask=mask,
                                 deterministic=True)
            return mlm_loss(logits, ids, mask)

        loss, g = jax.value_and_grad(loss_fn)(p)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    params = variables["params"]
    for _ in range(3):
        params, state, loss = step(params, state, ids, mask)
    float(loss)
    t0 = time.perf_counter()
    with hvd.profiler.trace(trace_dir):
        for _ in range(steps):
            params, state, loss = step(params, state, ids, mask)
        float(loss)
    wall = time.perf_counter() - t0
    seq_s = batch * steps / wall
    print(f"capture b{batch} s{seq}: {seq_s:.1f} seq/s during trace",
          file=sys.stderr)
    return newest_xplane(trace_dir), seq_s, cfg


def phase_table(xplane: str, steps: int = 5, dump: bool = False) -> dict:
    buckets = {}
    total = 0.0
    for row in hlo_rows(xplane):
        t_ms = row["self_ms"] / steps
        op = row["tf_op_name"]
        phase = classify(op)
        total += t_ms
        add_to_bucket(buckets, phase, t_ms, row)
        if dump and t_ms > 0.1:
            print(f"{phase:12s} {t_ms:6.2f}ms {row['bound_by']:8s} "
                  f"{op[:120]}", file=sys.stderr)
    return {"total_ms_per_step": round(total, 2),
            "phases": finalize_buckets(buckets)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="base",
                    choices=["base", "large", "tiny"])
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-per-chip", type=int, default=128)
    ap.add_argument("--attention", choices=["xla", "flash"], default="xla")
    ap.add_argument("--peak-tflops", type=float, default=197.0,
                    help="chip bf16 peak for the MFU quote (v5e: 197)")
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--dump", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    trace_dir = args.trace_dir or (
        f"/tmp/bert_trace_{args.model}_b{args.batch_per_chip}")
    xplane, seq_s, cfg = capture(args.model, args.batch_per_chip,
                                 args.seq_len, trace_dir,
                                 steps=args.steps,
                                 attention=args.attention)
    table = phase_table(xplane, steps=args.steps, dump=args.dump)
    flops = train_flops_per_step(cfg, args.batch_per_chip, args.seq_len)
    steps_per_s = seq_s / args.batch_per_chip
    mfu = flops * steps_per_s / (args.peak_tflops * 1e12)
    out = {"model": args.model, "seq_len": args.seq_len,
           "batch_per_chip": args.batch_per_chip,
           "attention": args.attention,
           "seq_per_s": round(seq_s, 1),
           "flops_per_step": flops,
           "mfu_pct": round(100.0 * mfu, 1),
           "peak_tflops": args.peak_tflops,
           "xplane": xplane, **table}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({k: (v if k != "phases" else {
        p: b["ms"] for p, b in v.items()}) for k, v in out.items()
        if k != "xplane"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""ViT per-phase time accounting (round-4 verdict item #2).

Traces the ViT training step on the real chip and buckets every scheduled
op's time into phases by XLA provenance — the same method that produced
``artifacts/moe_ceiling_r4.json`` (see ``examples/moe_phase_profile.py``).
The per-phase table decides whether ViT-S/16's MFU hides another lever
or is the configuration's structural ceiling.

Run: python examples/vit_phase_profile.py --model s16 --batch-per-chip 64
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from horovod_tpu.utils.hlo_phases import (add_to_bucket, finalize_buckets,
                                          hlo_rows, newest_xplane)

# Ordered: first hit wins. Keys match the jax name-stack in hlo_stats'
# tf_op_name, e.g. "jit(step)/transpose(jvp(VisionTransformer))/layer_3/
# SelfAttention_0/query/dot_general:".
PHASES = (
    ("attn_proj", ("/query/", "/key/", "/value/", "/out/")),
    ("attn_core", ("/SelfAttention_0/", "softmax", "flash")),
    ("mlp", ("/Dense_0/", "/Dense_1/", "gelu")),
    ("layernorm", ("LayerNorm", "final_norm")),
    ("patch_embed", ("patch_embed", "conv")),
    ("head_loss", ("/head/", "token_nll", "logsumexp", "while")),
)


def classify(tf_op_name: str) -> str:
    for phase, keys in PHASES:
        if any(k in tf_op_name for k in keys):
            return phase
    return "other"


def capture(model_name: str, batch: int, trace_dir: str,
            steps: int = 5) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import (VIT_B16, VIT_S16, VIT_TINY,
                                    VisionTransformer, classification_loss)

    hvd.init()
    cfg = {"b16": VIT_B16, "s16": VIT_S16, "tiny": VIT_TINY}[model_name]
    # Same step construction as examples/jax_vit_training.py (the
    # configuration the round-4 throughput rows were measured on), minus
    # the shard_map wrapper — single-chip provenance is easier to read and
    # the mesh is one device here anyway.
    model = VisionTransformer(cfg)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(
        batch, cfg.image_size, cfg.image_size, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, cfg.num_classes, size=(batch,)))
    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.ones((1, cfg.image_size, cfg.image_size, 3)),
        deterministic=True)
    tx = optax.adamw(1e-3)
    state = tx.init(variables)

    @jax.jit
    def step(v, s, xb, yb):
        def loss_fn(vv):
            return classification_loss(
                model.apply(vv, xb, deterministic=True), yb)

        loss, g = jax.value_and_grad(loss_fn)(v)
        u, s = tx.update(g, s, v)
        return optax.apply_updates(v, u), s, loss

    for _ in range(3):
        variables, state, loss = step(variables, state, x, y)
    float(loss)
    t0 = time.perf_counter()
    with hvd.profiler.trace(trace_dir):
        for _ in range(steps):
            variables, state, loss = step(variables, state, x, y)
        float(loss)
    wall = time.perf_counter() - t0
    print(f"capture b{batch}: {batch * steps / wall:.0f} img/s during trace",
          file=sys.stderr)
    return newest_xplane(trace_dir)


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
    return {"total_ms_per_step": round(total, 1),
            "phases": finalize_buckets(buckets)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="s16")
    ap.add_argument("--batch-per-chip", type=int, default=64)
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--xplane", default=None)
    ap.add_argument("--steps", type=int, default=5,
                    help="steps inside the trace; also the divisor turning "
                    "trace totals into per-step ms (pass the capture's "
                    "value when analyzing an existing --xplane)")
    ap.add_argument("--dump", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    trace_dir = args.trace_dir or (
        f"/tmp/vit_trace_{args.model}_b{args.batch_per_chip}")
    xplane = args.xplane or capture(args.model, args.batch_per_chip,
                                    trace_dir, steps=args.steps)
    table = phase_table(xplane, steps=args.steps, dump=args.dump)
    out = {"model": args.model, "batch_per_chip": args.batch_per_chip,
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

#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

Drives the framework's main path once, through the entry points a user
calls, in ONE process (a chip belongs to one process at a time):

  device             hvd.init() + jax.devices(): must be platform "tpu"
  train_resnet50     build_resnet50_step below: ResNet-50,
                     224^2, bf16, 128 images/chip, SGD-momentum through
                     hvd.DistributedOptimizer over hvd.parallel.mesh()
  train_llama300m    examples/jax_llama_training.py's own step
                     (build_train_step): LLAMA_300M, seq 1024, batch 8/chip,
                     AdamW — flash forward + both backward kernels (the
                     streamed path: seq 1024 is two query blocks)
  flash_one_tile     ops.attention's one-tile path at seq 512 against
                     reference_attention in float32: causal GQA at head
                     width 128, float32 with a key mask, causal sq != sk
  window_and_experts ops.attention's streamed kernels, two blocks a side:
                     under a causal band with a window (GQA at head width
                     128), plain causal at head width 64 and at 192 over
                     128, and under a key mask; and
                     parallel.moe.moe_apply_held with a share of the
                     experts at SmallThinker's widths, each against its
                     float32 reference, forward and gradients
  linear_attention   ops.linear_attention's chunked gated delta rule at
                     Olmo-Hybrid's head widths (15 heads, keys 96, values
                     192, chunks of 64) against the token-by-token
                     recurrence in float32, forward and all five gradients
  generate_llama300m models.llama.generate: contiguous decode kernel
  serve_llama300m    hvd.serving.serve: paged decode kernel, default
                     block_size

Every phase prints one line (seconds split into compile and run, and the
checks it made); then one JSON summary line (phases, cache, ``claim``);
the LAST stdout line is the result the driver reads, exactly
``{"ok": true, "device": {"platform", "kind", "count"}}`` with the device
as jax reports it. The exit code is 0 only if every phase passed — a
failed check raises, nothing catches, and no result line is printed.
The train phases compile ahead of time and report tracing + lowering
(``lower``, which no cache saves) apart from XLA's ``compile``; generate
and serve jit inside their entry points, so their ``compile`` is the first
call minus the second (tracing included).
With more than one device the two train phases run over the whole ``data``
mesh and also check placement, the all-reduce, bit-identical replicas and
(Llama) loss parity with one device; generate/serve run on one device.

    python chip_smoke.py                 # on the chip: the real sizes
    python chip_smoke.py --rehearse-cpu  # same code path, tiny sizes, CPU

Without a TPU the default invocation exits non-zero at once, naming the
platform it found, and prints no result. The rehearsal prints its summary
(``"rehearsal": true``) but no result line either: a CPU pass is not a
chip pass. Timings printed here are not records (the summary's ``claim``
is null).
"""

import argparse
import dataclasses
import importlib.metadata
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# One bf16 ulp (8 significand bits), relative: the tolerance unit for every
# comparison between two bf16 computations of the same quantity.
BF16_ULP = 2.0 ** -8


class SmokeFailure(AssertionError):
    """A phase's check did not hold (explicit raise: survives ``-O``)."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    resnet_batch_per_chip: int = 128
    resnet_image: int = 224
    resnet_steps: int = 8
    llama: str = "LLAMA_300M"
    seq_len: int = 1024
    lm_batch_per_chip: int = 8
    lm_steps: int = 4
    prompt_len: int = 128
    new_tokens: int = 32
    gen_batch: int = 8
    serve_prompts: tuple = (32, 96, 32, 96, 32, 96)
    serve_new: int = 24
    serve_max_seq: int = 256


# Tiny twin for --rehearse-cpu: same code path (seq 512 still takes the
# flash kernel, through the Pallas interpreter), sizes a CPU finishes.
REHEARSAL = Sizes(resnet_batch_per_chip=8, resnet_image=32, resnet_steps=5,
                  llama="LLAMA_TINY", seq_len=512, lm_batch_per_chip=1,
                  lm_steps=3, prompt_len=16, new_tokens=6, gen_batch=2,
                  serve_prompts=(8, 24, 8), serve_new=4, serve_max_seq=64)


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)
    return what


def _load(name, relpath):
    """Import an example of this checkout as a module."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _now():
    return time.perf_counter()


def _lower_and_compile(step, *args):
    """AOT-compile a jitted step, timing apart what a warm compile cache
    cannot save (tracing + lowering, Python work) and what it can (XLA's
    compile). Returns ``(compiled, lower_s, compile_s)``."""
    t0 = _now()
    lowered = step.lower(*args)
    t1 = _now()
    compiled = lowered.compile()
    return compiled, t1 - t0, _now() - t1


class _Stopwatch:
    """Calls a jitted function twice and keeps the seconds: the second
    call is ``run_s``, the first minus the second ``compile_s``. Returns
    the second call's leaves as float32 numpy arrays."""

    def __init__(self):
        self.compile_s = self.run_s = 0.0

    def __call__(self, fn, *args):
        import jax
        import numpy as np

        t0 = _now()
        jax.block_until_ready(fn(*args))
        t1 = _now()
        got = jax.block_until_ready(fn(*args))
        t2 = _now()
        self.run_s += t2 - t1
        self.compile_s += max((t1 - t0) - (t2 - t1), 0.0)
        return [np.asarray(x, np.float32) for x in jax.tree.leaves(got)]


def _rel_close(a, b, tol):
    return abs(a - b) <= tol * max(abs(a), abs(b))


def _mosaic_check(text, rehearsal, at_least=1):
    """'Compiled by Mosaic' read off the compiled program, not inferred
    from the backend name. The rehearsal runs the interpreter, which has
    no custom call to find — it reports that instead of passing."""
    n = text.count("tpu_custom_call")
    if rehearsal:
        return f"mosaic custom calls: {n} (interpreter rehearsal)"
    return check(n >= at_least, f"mosaic custom calls {n} >= {at_least}")


def _placement_checks(n, mesh, replicated, batch, compiled):
    """Multi-device only: nothing piled on device 0, and the step really
    reduces over every replica (an unbound axis would make hvd.allreduce
    the identity and leave gradients un-averaged)."""
    import jax

    from horovod_tpu.utils import comm_accounting

    devices = set(mesh.devices.flat)
    leaves = jax.tree.leaves(replicated)
    check(all(leaf.sharding.device_set == devices for leaf in leaves),
          "a replicated leaf does not live on every mesh device")
    shards = batch.addressable_shards
    check(len({s.device for s in shards}) == n
          and len({str(s.index) for s in shards}) == n,
          "batch is not split into one distinct shard per device")
    groups = [c.group_size for c in comm_accounting.collectives(compiled)
              if c.op == "all-reduce"]
    check(n in groups, f"no all-reduce over {n} replicas (sizes {groups})")
    return (f"{len(leaves)} leaves on {n} devices, batch on {n} shards, "
            f"{groups.count(n)} all-reduce over {n} replicas")


def _replicas_identical(tree):
    """Every device's copy of every leaf, compared bit for bit on the host."""
    import jax
    import numpy as np

    for leaf in jax.tree.leaves(tree):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        ref = copies[0].tobytes()
        if any(c.tobytes() != ref for c in copies[1:]):
            return False
    return True


def _cache_entries(cache_dir):
    from horovod_tpu.utils import compile_cache

    return compile_cache.entry_count(cache_dir) if cache_dir else 0


def phase_device(rehearsal):
    from horovod_tpu.utils import compile_cache

    # The rehearsal's CPU programs are not worth keeping (and would pad
    # the entry count the chip run reports).
    cache_dir = None if rehearsal else compile_cache.enable()
    import horovod_tpu as hvd

    t0 = _now()
    hvd.init()     # first backend touch: the library's own guarded init
    import jax
    import jaxlib

    dev = jax.devices()[0]
    init_s = _now() - t0
    want = "cpu" if rehearsal else "tpu"
    if dev.platform != want:
        sys.stderr.write(
            f"chip_smoke: needs platform {want!r}, jax found "
            f"{dev.platform!r} ({dev.device_kind} x{len(jax.devices())})\n")
        sys.exit(4)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    cache = {"dir": cache_dir, "entries_before": _cache_entries(cache_dir)}
    print(f"[device] platform={dev.platform} device_kind={dev.device_kind} "
          f"n_devices={device['count']} init={init_s:.1f}s "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"cache_dir={cache_dir} cache_entries={cache['entries_before']}",
          flush=True)
    return device, cache


def build_resnet50_step(batch_per_chip, image_size):
    """The framework's main path: ``hvd.init()`` -> ``hvd.parallel.mesh()``
    -> SGD-momentum through ``hvd.DistributedOptimizer`` inside
    ``jax.jit(jax.shard_map(...))``, ResNet-50 in bf16 on a fixed synthetic
    batch (the size arguments are the CPU rehearsal's seam).

    Returns ``(step, state, (x, y), mesh)`` with
    ``state = (params, batch_stats, opt_state)`` replicated over the mesh
    and the batch sharded along ``data``;
    ``step(*state, x, y) -> (*state, loss)`` donates the state."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import ResNet50

    hvd.init()
    n = hvd.local_num_devices()
    mesh = hvd.parallel.mesh()

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    batch = batch_per_chip * n
    # Feed activations in bf16: the model computes in bf16 anyway, and the
    # half-sized batch halves the first conv's HBM read. Cast on the HOST,
    # so shard_batch moves each shard straight to its own device instead
    # of staging the global batch on device 0 first.
    images_host = np.random.RandomState(0).rand(
        batch, image_size, image_size, 3).astype(jnp.bfloat16)
    labels_host = np.random.RandomState(1).randint(0, 1000, size=(batch,))

    # One program: eagerly, flax hands XLA a program an operation, and
    # few of a ResNet's repeat (22-30 s of 94 on a cold v5e). A stack of
    # like layers is the other way round: its eager programs repeat, and
    # the 300M Llama's init as one program took 6-11 s longer a phase.
    variables = jax.jit(model.init, static_argnames="train")(
        jax.random.PRNGKey(0), jnp.ones((1, image_size, image_size, 3)),
        train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    tx = hvd.DistributedOptimizer(
        optax.sgd(0.1, momentum=0.9), axis_name="data")
    opt_state = tx.init(params)

    def loss_fn(p, stats, x, y):
        logits, new_model_state = model.apply(
            {"params": p, "batch_stats": stats}, x, train=True,
            mutable=["batch_stats"])
        # Integer-label CE skips materialising a [B, 1000] one-hot in HBM
        # (~1.2% end-to-end on v5e).
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, new_model_state["batch_stats"]

    def train_step(p, stats, opt_state, x, y):
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, stats, x, y)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), new_stats, opt_state, loss

    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    ), donate_argnums=(0, 1, 2))

    x = hvd.parallel.shard_batch(images_host, mesh)
    y = hvd.parallel.shard_batch(labels_host, mesh)
    state = tuple(hvd.parallel.replicate(t, mesh)
                  for t in (params, batch_stats, opt_state))
    return step, state, (x, y), mesh


def phase_train_resnet50(sz, rehearsal):
    import math

    import jax

    step, state, (x, y), mesh = build_resnet50_step(
        sz.resnet_batch_per_chip, sz.resnet_image)
    n = mesh.size
    compiled, lower_s, compile_s = _lower_and_compile(step, *state, x, y)
    checks = []
    if n > 1:
        checks.append(_placement_checks(n, mesh, state, x, compiled))

    t0 = _now()
    losses = []
    for _ in range(sz.resnet_steps):
        *state, loss = compiled(*state, x, y)
        losses.append(float(loss))
    run_s = _now() - t0
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    checks.append(check(
        losses[-1] < losses[0],
        f"loss finite and falling {losses[0]:.3f}->{losses[-1]:.3f} "
        f"over {len(losses)} steps"))

    # Is block_until_ready a barrier on this runtime? One steady-state step
    # timed to block_until_ready, the next to a host fetch of the loss: if
    # the first returned early it would read far shorter than the second.
    t0 = _now()
    *state, loss = compiled(*state, x, y)
    jax.block_until_ready(loss)
    block_ms = (_now() - t0) * 1e3
    t0 = _now()
    *state, loss = compiled(*state, x, y)
    float(loss)
    fetch_ms = (_now() - t0) * 1e3
    checks.append(f"step to block_until_ready {block_ms:.1f} ms, "
                  f"to float(loss) {fetch_ms:.1f} ms")
    if n > 1:
        # params + momentum; BatchNorm statistics are per-device by design.
        checks.append(check(_replicas_identical((state[0], state[2])),
                            f"params bit-identical on {n} devices"))
    return {"lower_s": lower_s, "compile_s": compile_s, "run_s": run_s,
            "checks": checks, "step_block_ms": round(block_ms, 2),
            "step_fetch_ms": round(fetch_ms, 2)}


def phase_train_llama(sz, rehearsal):
    import math

    import jax
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import models
    from horovod_tpu.ops.attention import make_attention_fn

    example = _load("jax_llama_training", "examples/jax_llama_training.py")
    cfg = getattr(models, sz.llama)
    mesh = hvd.parallel.mesh()
    n = mesh.size
    # The example's --model 300m path: flash attention through the "auto"
    # adapter, AdamW(3e-4) through DistributedOptimizer, plain-twin init.
    model = models.LlamaLM(cfg, attention_fn=make_attention_fn(causal=True))
    reference = models.LlamaLM(cfg)     # reference_attention(causal=True)
    ids_host = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (sz.lm_batch_per_chip * n, sz.seq_len),
        dtype=np.int32)
    params0 = reference.init(jax.random.PRNGKey(0),
                             ids_host[:1, :min(sz.seq_len, 512)])["params"]
    tx = hvd.DistributedOptimizer(optax.adamw(3e-4), axis_name="data")
    opt_state = tx.init(params0)
    step = example.build_train_step(model, tx, mesh)

    ids = jax.device_put(ids_host, hvd.parallel.data_sharding(mesh))
    params = hvd.parallel.replicate(params0, mesh)
    opt_state = hvd.parallel.replicate(opt_state, mesh)

    compiled, lower_s, compile_s = _lower_and_compile(
        step, params, opt_state, ids)
    checks = [_mosaic_check(compiled.as_text(), rehearsal, at_least=3)]
    if n > 1:
        checks.append(_placement_checks(n, mesh, (params, opt_state), ids,
                                        compiled))

    # One-device forward losses on the initial params, one per per-chip
    # chunk of the same global batch (equal chunks: their mean IS the
    # global mean), with the flash model; chunk 0 also with
    # reference_attention.
    def fwd_loss(m):
        return jax.jit(lambda p, i: models.causal_lm_loss(
            m.apply({"params": p}, i), i))

    chunks = np.split(ids_host, n)
    loss_ref0 = float(fwd_loss(reference)(params0, chunks[0]))
    flash_fwd = fwd_loss(model)
    loss_flash = [float(flash_fwd(params0, c)) for c in chunks]
    del params0
    checks.append(check(
        _rel_close(loss_flash[0], loss_ref0, BF16_ULP),
        f"first-step loss with flash {loss_flash[0]:.5f} vs "
        f"reference_attention {loss_ref0:.5f} within 2^-8 relative"))

    t0 = _now()
    losses = []
    for _ in range(sz.lm_steps):
        params, opt_state, loss = compiled(params, opt_state, ids)
        losses.append(float(loss))
    run_s = _now() - t0
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    checks.append(check(
        losses[-1] < losses[0],
        f"loss finite and falling {losses[0]:.4f}->{losses[-1]:.4f} "
        f"over {len(losses)} steps"))
    loss_1dev = float(np.mean(loss_flash))
    checks.append(check(
        _rel_close(losses[0], loss_1dev, BF16_ULP),
        f"{n}-device first-step loss {losses[0]:.5f} vs one-device loss on "
        f"the same global batch {loss_1dev:.5f} within 2^-8 relative"))
    if n > 1:
        checks.append(check(_replicas_identical((params, opt_state)),
                            f"params bit-identical on {n} devices"))
    return {"lower_s": lower_s, "compile_s": compile_s, "run_s": run_s,
            "checks": checks}


def phase_flash_one_tile(sz, rehearsal):
    """The one-tile kernels (a sequence that fits one block a side) off
    BERT's shape, which the benchmark covers: forward and gradients
    against ``reference_attention`` on float32 copies at the highest
    matmul precision, beside the streamed kernels on the same inputs
    (explicit 128 blocks). A mis-lowered transpose or reduction gives an
    error of the order of the values; both paths share the MXU's
    rounding, so one tile may not be further off than a few ulps of the
    input dtype or twice the streamed error, whichever is larger, and
    never by more than 2^-5 of the largest value."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import attention
    from horovod_tpu.ops.attention import flash_attention, reference_attention

    shapes = [   # name, dtype, sq, sk, h, hkv, d, causal, key mask
        ("causal-gqa-d128", jnp.bfloat16, 512, 512, 8, 2, 128, True, False),
        ("f32-mask-d64", jnp.float32, 512, 512, 4, 4, 64, False, True),
        ("causal-sq256-sk512", jnp.bfloat16, 256, 512, 4, 4, 64, True, True),
        # BERT's heads: a grid step's block is four heads of the twelve.
        ("mask-h12-d64", jnp.bfloat16, 512, 512, 12, 12, 64, False, True),
    ]
    rng = np.random.RandomState(0)
    batch = 2
    checks, compile_s, run_s = [], 0.0, 0.0
    for name, dtype, sq, sk, h, hkv, d, causal, masked in shapes:
        def rand(*shape):
            return jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)

        q, k, v = rand(batch, sq, h, d), rand(batch, sk, hkv, d), \
            rand(batch, sk, hkv, d)
        w = rand(batch, sq, h, d)           # the cotangent on the output
        mask = (jnp.asarray(np.arange(sk)[None, :]
                            < rng.randint(sk - sq // 2, sk + 1, (batch, 1)))
                if masked else None)

        def out_and_grads(attn, q, k, v):
            def loss(q, k, v):
                out = attn(q, k, v, key_mask=mask, causal=causal)
                return jnp.sum(out.astype(jnp.float32)
                               * w.astype(jnp.float32)), out
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (out,) + grads

        def run(attn, *args):
            nonlocal compile_s, run_s
            fn = jax.jit(lambda *a: out_and_grads(attn, *a))
            names = set(re.findall(r"hvd_flash_\w+", fn.lower(*args).as_text(
                debug_info=True)))
            t0 = _now()
            jax.block_until_ready(fn(*args))
            t1 = _now()
            got = jax.block_until_ready(fn(*args))
            t2 = _now()
            run_s += t2 - t1
            compile_s += max((t1 - t0) - (t2 - t1), 0.0)
            return [np.asarray(x, np.float32) for x in got], names

        with jax.default_matmul_precision("highest"):
            want, _ = run(reference_attention,
                          *(x.astype(jnp.float32) for x in (q, k, v)))
        one, one_names = run(flash_attention, q, k, v)
        streamed, streamed_names = run(
            lambda *a, **kw: flash_attention(*a, block_q=128, block_k=128,
                                             **kw), q, k, v)
        # Both paths call their kernels by the same names; the shape rule
        # says which one the default blocks took.
        kernels = {"hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"}
        check(one_names == streamed_names == kernels
              and attention._one_tile_path(q, k, sq, sk)
              and not attention._one_tile_path(q, k, 128, 128),
              f"{name}: kernels {sorted(one_names)} / "
              f"{sorted(streamed_names)}, one tile by the shape rule")

        def worst(got):
            return max(float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
                       for g, r in zip(got, want))

        err_one, err_streamed = worst(one), worst(streamed)
        ulps = 4 * float(jnp.finfo(dtype).eps)
        checks.append(check(
            err_one <= min(max(ulps, 2 * err_streamed), 2.0 ** -5),
            f"{name}: out/dq/dk/dv within {err_one:.2e} of max|reference| "
            f"(streamed {err_streamed:.2e})"))
    return {"compile_s": compile_s, "run_s": run_s, "checks": checks}


def phase_window_and_experts(sz, rehearsal):
    """What PR 26 added to the step, off the benchmark's own shape: the
    streamed flash kernels with ``window`` (blocks the band skips, blocks
    its edges cross, blocks wholly inside), at the head widths the cells
    run beside 128 (64; q and k 192 over v 128) and under a key mask, each
    against ``reference_attention`` on float32 copies at the highest
    matmul precision (the tolerance is ``tests/``' for bfloat16 or
    tighter: ``attention_helpers`` holds gradients to 5e-2 of the
    largest value), and the dropless
    expert layer holding 4 of 16 experts (3 chosen a token, hidden 2560,
    expert width 768) against every held expert applied densely in
    float32, once as a seeded router spreads the tokens and once with
    every token forced onto the held experts (PR 27: nothing dropped
    where every sorted row belongs to a group), and that once more with
    the 4 held of 32 (PR 41: an eighth held, so the rows are walked in
    chunks, all of them live). Both in bf16: a few ulps of bf16 of the largest value, and
    never more than 2^-5 of it; the expert layer's count of what landed
    here must be the count of chosen ids that are held."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import attention
    from horovod_tpu.ops.attention import flash_attention, reference_attention
    from horovod_tpu.parallel.moe import grouped_gated_mlp, moe_apply_held

    rng = np.random.RandomState(1)
    checks, timed = [], _Stopwatch()

    def rand(*shape, scale=1.0, dtype=jnp.bfloat16):
        return jnp.asarray(scale * rng.randn(*shape).astype(np.float32),
                           dtype)

    def worst(got, want):
        return max(float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
                   for g, r in zip(got, want))

    tolerance = 2.0 ** -5

    # -- the streamed kernels: two default blocks a side, by band (a window
    # that crosses blocks off their borders), by head width (128, 64, q
    # and k 192 over v 128: a head is a band of that many rows of the
    # (B, heads * d, S) arrays) and under a key mask (a padded batch).
    seq = 256 if rehearsal else 2048
    blocks = dict(block_q=64, block_k=128) if rehearsal else {}
    kernels = {"hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"}
    rows = [   # name, query heads, K/V heads, q/k width, v width, window, mask
        ("window-d128", 8, 2, 128, 128, seq // 2 + seq // 8, False),
        ("causal-d64", 8, 2, 64, 64, None, False),
        ("causal-192-over-128", 4, 4, 192, 128, None, False),
        ("key-mask-d128", 8, 2, 128, 128, None, True),
    ]
    for name, h, hkv, d, dv, window, masked in rows:
        q, k, v, w = (rand(2, seq, h, d), rand(2, seq, hkv, d),
                      rand(2, seq, hkv, dv), rand(2, seq, h, dv))
        mask = (jnp.asarray(np.arange(seq)[None, :] < rng.randint(
            seq // 2, seq, (2, 1))) if masked else None)

        def attention_grads(attn, **kw):
            def fn(q, k, v):
                def loss(q, k, v):
                    out = attn(q, k, v, causal=True, window=window,
                               key_mask=mask, **kw)
                    return jnp.sum(out.astype(jnp.float32)
                                   * w.astype(jnp.float32)), out
                (_, out), grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
                return (out,) + grads
            return jax.jit(fn)

        flash = attention_grads(flash_attention, **blocks)
        names = set(re.findall(r"hvd_flash_\w+", flash.lower(q, k, v).as_text(
            debug_info=True)))
        check(names == kernels and not attention._one_tile_path(
            q, k, blocks.get("block_q", attention.FLASH_DEFAULT_BLOCK_Q),
            blocks.get("block_k", attention.FLASH_DEFAULT_BLOCK_K), v),
            f"{name}: kernels {sorted(names)}, streamed by the shape rule")
        with jax.default_matmul_precision("highest"):
            want = timed(attention_grads(reference_attention),
                         *(x.astype(jnp.float32) for x in (q, k, v)))
        err = worst(timed(flash, q, k, v), want)
        checks.append(check(
            err <= tolerance,
            f"streamed {name} ({h}/{hkv} heads, "
            f"{'window %d of ' % window if window else ''}{seq}): "
            f"out/dq/dk/dv within {err:.2e} of max|reference|"))

    # -- the expert layer with a share.
    tokens, hidden, width = (512, 128, 96) if rehearsal else (4096, 2560, 768)
    experts, held, chosen = 16, (4, 5, 6, 7), 3
    x = rand(tokens, hidden)
    logits = rand(tokens, experts, dtype=jnp.float32)
    # Float32 weights that bf16 holds exactly: the layer's cast of them is
    # then no rounding, and no gate changes sign between the two sides (a
    # ReLU's gradient is not continuous there).
    params = {name: rand(len(held), *shape, scale=shape[0] ** -0.5).astype(
        jnp.float32) for name, shape in (("w_gate", (hidden, width)),
                                         ("w_up", (hidden, width)),
                                         ("w_down", (width, hidden)))}
    target = rand(tokens, hidden)

    def held_layer(params, x, logits):
        def loss(params, x, logits):
            y, load = moe_apply_held(grouped_gated_mlp, params, x, logits,
                                     held, chosen)
            return jnp.sum(y.astype(jnp.float32)
                           * target.astype(jnp.float32)), (y, load)
        (_, (y, load)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(params, x, logits)
        return (y,) + grads, load

    def dense_layer(params, x, logits):
        def loss(params, x, logits):
            top, ids = jax.lax.top_k(logits, chosen)
            weights = jnp.zeros_like(logits).at[
                jnp.arange(tokens)[:, None], ids].set(
                jax.nn.softmax(top, axis=-1))[:, jnp.array(held)]
            mid = jax.nn.relu(jnp.einsum(
                "td,edf->etf", x, params["w_gate"])) * jnp.einsum(
                "td,edf->etf", x, params["w_up"])
            y = jnp.einsum("etd,te->td", jnp.einsum(
                "etf,efd->etd", mid, params["w_down"]), weights)
            return jnp.sum(y * target.astype(jnp.float32)), y
        (_, y), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(params, x, logits)
        return (y,) + grads

    dense_layer, held_layer = jax.jit(dense_layer), jax.jit(held_layer)
    # As the seeded router spreads them, a quarter lands here; with the
    # held experts' logits raised every assignment does. The same under a
    # router 32 wide: an eighth of the experts held, where the combine and
    # the gradients of both row movements walk the landed rows in chunks
    # (PR 41), and every chunk holds one.
    wide = rand(tokens, 2 * experts, dtype=jnp.float32)
    for what, logits in (("a router's spread", logits),
                         ("every token forced here",
                          logits.at[:, jnp.array(held)].add(20.0)),
                         ("every token forced here",
                          wide.at[:, jnp.array(held)].add(20.0))):
        with jax.default_matmul_precision("highest"):
            want = timed(dense_layer, params, x.astype(jnp.float32), logits)
        *got, load = timed(held_layer, params, x, logits)
        landed = int(np.isin(np.argsort(
            -np.asarray(logits), axis=-1)[:, :chosen], held).sum())
        check(int(load.sum()) == landed,
              f"experts: {int(load.sum())} assignments landed, {landed} "
              "chosen ids are held")
        err = worst(got, want)
        checks.append(check(
            err <= tolerance,
            f"experts {held} of {logits.shape[-1]}, {what}: {landed} of "
            f"{tokens * chosen} assignments here: y/dw/dx/dlogits within "
            f"{err:.2e} of max|reference|"))
    return {"compile_s": timed.compile_s, "run_s": timed.run_s,
            "checks": checks}


def phase_linear_attention(sz, rehearsal):
    """What PR 30 added to the step, off the benchmark's own shape: the
    chunked gated delta rule (``ops.linear_attention.gated_delta_rule``)
    at the published head widths, 15 heads with keys 96 and values 192
    wide, in bf16 with chunks of 64, against
    ``reference_gated_delta_rule`` (the recurrence token by token) on
    float32 copies at the highest matmul precision: the outputs, the
    state after the last token and the gradients of q, k, v, g and beta.
    q and k enter as a layer hands them over (L2-normed, q scaled by
    d_k^-1/2), the decays between e^-4 and 1, beta in (0, 2). bf16
    operands through the chunk's triangular system, its writes and the
    state: a few ulps of bf16 of the largest value, and never more than
    2^-5 of it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import linear_attention as la

    rng = np.random.RandomState(2)
    seq, heads, d_k, d_v = (160, 3, 96, 192) if rehearsal \
        else (2048, 15, 96, 192)

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    q = (la.l2_normalize(rand(1, seq, heads, d_k)) * d_k ** -0.5).astype(
        jnp.bfloat16)
    k = la.l2_normalize(rand(1, seq, heads, d_k)).astype(jnp.bfloat16)
    v = rand(1, seq, heads, d_v).astype(jnp.bfloat16)
    g = -jnp.exp(jnp.asarray(rng.uniform(
        -6.0, 1.4, (1, seq, heads)).astype(np.float32)))
    beta = 2.0 * jax.nn.sigmoid(2.0 * rand(1, seq, heads))
    target = rand(1, seq, heads, d_v)

    def with_gradients(rule):
        def fn(q, k, v, g, beta):
            def loss(*args):
                o, state = rule(*args, output_final_state=True)
                return jnp.sum(o.astype(jnp.float32) * target), (o, state)
            (_, (o, state)), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(q, k, v, g, beta)
            return (o, state) + grads
        return jax.jit(fn)

    timed = _Stopwatch()
    want = timed(with_gradients(la.reference_gated_delta_rule),
                 *(x.astype(jnp.float32) for x in (q, k, v)), g, beta)
    got = timed(with_gradients(la.gated_delta_rule), q, k, v, g, beta)
    errs = [float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
            for a, b in zip(got, want)]
    check(max(errs) <= 2.0 ** -5,
          "linear attention: o/state/dq/dk/dv/dg/dbeta within "
          + "/".join(f"{e:.1e}" for e in errs) + " of max|reference|")
    return dict(compile_s=timed.compile_s, run_s=timed.run_s, checks=[
        f"chunked delta rule, {heads} heads {d_k}/{d_v} wide over {seq} "
        f"tokens in chunks of 64: o/state/dq/dk/dv/dg/dbeta within "
        f"{max(errs):.2e} of max|reference|"])


def _lm_on_one_device(sz):
    """Model, variables and a prompt for the decode phases — on the
    default device (generate/serve are one-device paths; says which)."""
    import jax
    import numpy as np

    from horovod_tpu import models

    cfg = getattr(models, sz.llama)
    model = models.LlamaLM(cfg)
    prompt = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (sz.gen_batch, sz.prompt_len), dtype=np.int32)
    variables = model.init(jax.random.PRNGKey(0), prompt[:, :8])
    where = str(next(iter(jax.tree.leaves(variables)[0].devices())))
    return cfg, model, variables, jax.numpy.asarray(prompt), where


def phase_generate(sz, rehearsal):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import llama
    from horovod_tpu.utils.comm_accounting import decode_path_markers

    cfg, model, variables, prompt, where = _lm_on_one_device(sz)
    b, s = prompt.shape
    max_len = s + sz.new_tokens

    t0 = _now()
    out = llama.generate(model, variables, prompt, sz.new_tokens)
    np.asarray(out)
    first_s = _now() - t0
    t0 = _now()
    out = np.asarray(llama.generate(model, variables, prompt, sz.new_tokens))
    run_s = _now() - t0
    verdict = llama.LAST_DECODE_PATH
    checks = [check(verdict.path == "kernel",
                    f"classifier verdict {verdict.path!r} ({verdict.reason})"),
              check(out.shape == (b, max_len)
                    and (out[:, :s] == np.asarray(prompt)).all()
                    and (0 <= out).all() and (out < cfg.vocab_size).all(),
                    f"tokens {out.shape}: prompt kept, ids in vocab")]

    # The program generate() just ran, lowered again with the same static
    # arguments: its text must hold the Mosaic call and the kernel marker.
    text = llama._decode.lower(
        model, variables, prompt, jax.random.PRNGKey(0), jnp.float32(0.0),
        sz.new_tokens, max_len, True, verdict.path, verdict.mesh,
        verdict.head_axis, verdict.batch_axis, 1).compile().as_text()
    checks.append(_mosaic_check(text, rehearsal))
    marks = decode_path_markers(text)
    checks.append(check(
        marks["hvd.decode.kernel"] > 0 and marks["hvd.decode.einsum"] == 0,
        f"markers kernel={marks['hvd.decode.kernel']} "
        f"einsum={marks['hvd.decode.einsum']}"))

    # First decode-step logits, kernel path against the einsum path that
    # decode_kernel_disabled() selects (prefill + one single-token step, as
    # _decode_body traces them). One fresh function per path: the switch is
    # trace-time state, and a shared function would hit the jit cache and
    # compare the kernel with itself.
    def first_step_logits(path):
        def fn(v, p):
            with llama.decode_path_context(path):
                cache = llama.init_kv_cache(cfg, b, max_len)
                logits, cache = model.apply(v, p, cache=cache, cache_index=0)
                tok = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
                logits, _ = model.apply(v, tok[:, None], cache=cache,
                                        cache_index=s)
            return logits[:, -1].astype(jnp.float32)
        return jax.jit(fn)

    einsum_fn = first_step_logits("einsum")
    marks = decode_path_markers(
        einsum_fn.lower(variables, prompt).compile().as_text())
    check(marks["hvd.decode.einsum"] > 0 and marks["hvd.decode.kernel"] == 0,
          f"the comparison program did not take the einsum path: {marks}")
    got = np.asarray(first_step_logits("kernel")(variables, prompt))
    ref = np.asarray(einsum_fn(variables, prompt))
    err, scale = float(np.abs(got - ref).max()), float(np.abs(ref).max())
    checks.append(check(
        np.isfinite(got).all() and err <= 16 * BF16_ULP * scale,
        f"first decode-step logits vs einsum path: max|d|={err:.4f} <= "
        f"16 bf16 ulps of max|logit|={scale:.3f}"))
    return {"compile_s": max(first_s - run_s, 0.0), "run_s": run_s,
            "checks": checks, "device": where}


def phase_serve(sz, rehearsal):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.ops import decode_attention as da
    from horovod_tpu.serving import ServingConfig, engine as engine_mod
    from horovod_tpu.utils.comm_accounting import decode_path_markers

    cfg, model, variables, _, where = _lm_on_one_device(sz)
    # Default ServingConfig (block_size 16, 8 slots); only the position
    # budget is set, as the bench's serving row sets it.
    engine = hvd.serving.serve(model, variables,
                               ServingConfig(max_seq_len=sz.serve_max_seq))
    try:
        scfg = engine.config

        def round_trip(seed):
            rng = np.random.RandomState(seed)
            t0 = _now()
            handles = [engine.submit(rng.randint(0, cfg.vocab_size, (plen,)),
                                     sz.serve_new)
                       for plen in sz.serve_prompts]
            tokens = [h.result(timeout=900) for h in handles]
            return handles, tokens, _now() - t0

        # Two prompt lengths -> two prefill programs + one step program.
        _, _, first_s = round_trip(1)
        handles, tokens, run_s = round_trip(2)
        checks = [
            check(all(h.state == "finished" for h in handles)
                  and all(len(t) == sz.serve_new for t in tokens)
                  and all(0 <= x < cfg.vocab_size for t in tokens for x in t),
                  f"{len(handles)} requests finished with {sz.serve_new} "
                  "tokens each"),
            check(engine.decode_path.path == "kernel",
                  f"decode_path {engine.decode_path.path!r}"),
        ]
        stats = engine.stats()
        checks.append(check(stats["preemptions"] == 0
                            and stats["requests_finished"]
                            == 2 * len(sz.serve_prompts),
                            f"{stats['requests_finished']} finished, "
                            f"{stats['steps']} decode steps, 0 preemptions"))
    finally:
        engine.shutdown()

    # The engine's step program at the engine's own geometry, lowered from
    # shapes alone: Mosaic call + paged marker.
    head_dim = cfg.dim // cfg.num_heads
    f = cfg.num_kv_heads * head_dim
    slots = -(-scfg.max_seq_len // scfg.block_size)
    n_blocks = scfg.max_batch * slots + 1
    pool = jax.ShapeDtypeStruct((n_blocks, scfg.block_size, f), cfg.dtype)
    pools = {f"layer_{i}": {"k": pool, "v": pool}
             for i in range(cfg.num_layers)}
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    text = engine_mod._paged_step.lower(
        model, pools, variables, i32(scfg.max_batch), i32(scfg.max_batch),
        i32(scfg.max_batch, slots),
        jax.ShapeDtypeStruct((scfg.max_batch,), jnp.float32),
        jax.random.PRNGKey(0), all_greedy=True,
        path="kernel").compile().as_text()
    checks.append(_mosaic_check(text, rehearsal))
    marks = decode_path_markers(text)
    checks.append(check(
        marks["hvd.decode.paged"] > 0 and marks["hvd.decode.einsum"] == 0,
        f"markers paged={marks['hvd.decode.paged']} "
        f"einsum={marks['hvd.decode.einsum']}"))

    # Paged kernel against paged_gather_attention on the same pool: a
    # random pool of the engine's geometry, scattered tables, ragged lens.
    rng = np.random.RandomState(3)
    b = scfg.max_batch
    k_pool, v_pool = (jnp.asarray(rng.randn(n_blocks, scfg.block_size, f)
                                  * 0.5, cfg.dtype).at[0].set(0)
                      for _ in range(2))
    q = jnp.asarray(rng.randn(b, 1, cfg.num_heads, head_dim) * 0.5, cfg.dtype)
    lens = rng.randint(0, scfg.max_seq_len, (b,)).astype(np.int32)
    tables = np.zeros((b, slots), np.int32)
    order = rng.permutation(n_blocks - 1) + 1
    for i in range(b):
        used = lens[i] // scfg.block_size + 1
        tables[i, :used] = order[i * slots:i * slots + used]
    got = np.asarray(jax.jit(da.paged_decode_attention, static_argnums=5)(
        q, k_pool, v_pool, tables, lens, cfg.num_kv_heads), np.float32)
    ref = np.asarray(jax.jit(da.paged_gather_attention, static_argnums=5)(
        q, k_pool, v_pool, tables, lens, cfg.num_kv_heads), np.float32)
    err, scale = float(np.abs(got - ref).max()), float(np.abs(ref).max())
    checks.append(check(
        np.isfinite(got).all() and err <= 4 * BF16_ULP * scale,
        f"paged kernel vs paged_gather_attention: max|d|={err:.5f} <= "
        f"4 bf16 ulps of max|out|={scale:.3f}"))
    return {"compile_s": max(first_s - run_s, 0.0), "run_s": run_s,
            "checks": checks, "device": where}


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the same phases at tiny sizes on the CPU backend (Pallas "
             "interpreter) — a rehearsal, reported as such in the summary; "
             "never the default")
    args = parser.parse_args()
    rehearsal = args.rehearse_cpu
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"     # before jax is imported
    sz = REHEARSAL if rehearsal else Sizes()

    t_start = _now()
    device, cache = phase_device(rehearsal)
    phases = {}
    for name, fn in (("train_resnet50", phase_train_resnet50),
                     ("train_llama300m", phase_train_llama),
                     ("flash_one_tile", phase_flash_one_tile),
                     ("window_and_experts", phase_window_and_experts),
                     ("linear_attention", phase_linear_attention),
                     ("generate_llama300m", phase_generate),
                     ("serve_llama300m", phase_serve)):
        t0 = _now()
        info = fn(sz, rehearsal)        # raises on a failed check
        info["wall_s"] = _now() - t0
        where = f" device={info['device']}" if "device" in info else ""
        lower = f"lower={info['lower_s']:.1f}s " if "lower_s" in info else ""
        print(f"[{name}] ok wall={info['wall_s']:.1f}s {lower}"
              f"compile={info['compile_s']:.1f}s run={info['run_s']:.2f}s"
              f"{where} | " + "; ".join(info.pop("checks")), flush=True)
        phases[name] = {k: round(v, 3) if isinstance(v, float) else v
                        for k, v in info.items()}

    cache["entries_after"] = _cache_entries(cache["dir"])
    print(json.dumps({
        "rehearsal": rehearsal, "phases": phases, "cache": cache,
        "total_s": round(_now() - t_start, 1), "claim": None}), flush=True)
    if not rehearsal:
        # The driver's contract: the last stdout line, these keys only.
        print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

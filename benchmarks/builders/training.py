"""What the training builders share: seeded weights, the three checked
steps, the timed window with its run-ahead of two, the traced window, and
the comparison with the plain reference.

A training builder's ``build(config, traffic, mesh)`` returns a
:class:`Workbench` through the program's public API alone and places no
array; :func:`run` drives it. A builder of another kind (a server with a
load generator) brings a ``run`` of its own.
"""

import collections
import dataclasses
import math
import os
import shutil
import statistics
import time
from typing import Any, Callable

import numpy as np

from harness import compare, device as device_gate, hlo_text, manifest, \
    trace_reduce


@dataclasses.dataclass
class Workbench:
    """One configuration's training step, ready to lower.

    ``step(state, batch) -> (state, loss)`` is the jitted program with the
    state donated. ``weight_shapes`` is the tree of shapes the benchmark
    fills from the seed; ``init_state(weights)`` makes the step's state
    from it."""
    step: Callable
    weight_shapes: Any
    init_state: Callable
    weight_params: Callable     # weights -> the parameters among them
    params_of: Callable         # state -> its parameters
    first_gradient: Callable    # state after step one -> that gradient
    identical_of: Callable      # state -> what every replica holds alike
    batch_shapes: Any
    make_batch: Callable        # numpy Generator -> host batch (tuple)
    samples_per_step: int
    flops_per_step: float       # model FLOPs, recomputation not counted
    state_shardings: Any = None
    batch_shardings: Any = None

    def arg_shapes(self):
        """The step's arguments as shapes with their shardings: enough to
        lower and compile without placing an array."""
        import jax

        weights = self.weight_shapes
        state = jax.eval_shape(self.init_state, weights)

        def with_sharding(tree, sharding):
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=sharding), tree)

        return (with_sharding(state, self.state_shardings),
                with_sharding(self.batch_shapes, self.batch_shardings))


# ---------------------------------------------------------------- weights

def _leaf_name(path):
    return "/".join(str(getattr(k, "key", k)) for k in path)


def make_weights(shapes, key, rules):
    """The benchmark's own seeded weights, leaf by leaf inside one jitted
    call: a normal kernel or embedding (``he_normal``, or a fixed standard
    deviation), constant scales and biases, zero mean and unit variance
    for batch statistics. ``rules`` is the configuration's ``init``."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for i, (path, s) in enumerate(flat):
        kind = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if kind in ("kernel", "embedding"):
            std = rules[kind]
            if std == "he_normal":
                std = math.sqrt(2.0 / math.prod(s.shape[:-1]))
            leaf = std * jax.random.normal(k, s.shape, jnp.float32)
        elif kind == "scale":
            # A configuration may set some scales apart by a part of their
            # name, as the zero-initialised last norm of a residual block.
            value = next((v for part, v in rules.get(
                "scale_where", {}).items() if part in _leaf_name(path)),
                rules["scale"])
            leaf = jnp.full(s.shape, value, jnp.float32)
        elif kind == "var":
            leaf = jnp.ones(s.shape, jnp.float32)
        elif kind in ("bias", "mean"):
            leaf = jnp.full(s.shape, rules["bias"] if kind == "bias"
                            else 0.0, jnp.float32)
        else:
            raise ValueError(f"no init rule for leaf {_leaf_name(path)}")
        leaves.append(leaf.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def leaf_norms(tree):
    """name -> l2 norm, as one small array fetched once."""
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.jit(lambda leaves: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in leaves]))([x for _, x in flat])
    return dict(zip((_leaf_name(p) for p, _ in flat),
                    np.asarray(norms).tolist()))


def _loss_values(loss):
    """Every replica's own copy of the step's loss."""
    return [float(np.asarray(s.data)) for s in loss.addressable_shards]


# ------------------------------------------------------------- the window

def timed_window(call, state, batch, seconds, run_ahead=2):
    """Dispatch steps for ``seconds``, never syncing on the newest one:
    after dispatching step i block on the loss of step i - run_ahead and
    stamp the host clock. Starts on a drained device and ends when the
    last loss has arrived."""
    import jax

    annotate = jax.profiler.TraceAnnotation
    pending = collections.deque()
    stamps, losses, dispatch = [], [], []

    def land():
        with annotate("wait_loss"):
            losses.append(float(pending.popleft()))
        with annotate("stamp"):
            stamps.append(time.perf_counter())

    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with annotate("dispatch"):
            state, loss = call(state, batch)
        dispatch.append(time.perf_counter() - t0)
        pending.append(loss)
        if len(pending) > run_ahead:
            land()
        if time.perf_counter() - start >= seconds:
            break
    while pending:
        land()
    end = stamps[-1]
    return state, {"start": start, "end": end, "stamps": stamps,
                   "losses": losses, "dispatch": dispatch}


def percentile(values, q):
    """The q-th percentile by linear interpolation between order
    statistics."""
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


# ------------------------------------------------------------------- run

class CompileCounter:
    """Counts XLA backend compilations and persistent-cache hits through
    jax's own monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Program:
    """The one object of a run: the compiled step and what it was built
    from. Set-up drives it through the checked steps and hands the same
    object, with the same state, to the window."""
    bench: Workbench
    mesh: Any
    compiled: Any
    text: str
    weights: Callable       # key -> the seeded weights, one jitted call


def compile_program(cell, devices, build, spans):
    import jax

    import horovod_tpu as hvd

    mesh = hvd.parallel.make_mesh(devices=devices)
    hvd.parallel.set_mesh(mesh)
    bench = build(cell.config, cell.traffic, mesh)
    t0 = time.perf_counter()
    compiled = bench.step.lower(*bench.arg_shapes()).compile()
    spans["compile"] = time.perf_counter() - t0
    rules = cell.config["init"]
    weights = jax.jit(lambda k: make_weights(bench.weight_shapes, k, rules))
    return Program(bench, mesh, compiled, compiled.as_text(), weights)


def seeded_inputs(program, seed):
    """State and batch from the seed, placed by the program's own
    ``replicate`` and ``shard_batch``."""
    import jax

    import horovod_tpu as hvd

    key = jax.random.PRNGKey(seed)
    state = hvd.parallel.replicate(
        jax.jit(program.bench.init_state)(program.weights(key)),
        program.mesh)
    host_batch = program.bench.make_batch(np.random.default_rng(seed))
    batch = hvd.parallel.shard_batch(host_batch, program.mesh)
    return key, state, host_batch, batch


def checked_steps(program, state, batch, key, steps, keep_gradient=False):
    """The first ``steps`` steps through the window's own call and feed.
    Returns the state to go on from and what the comparison reads: each
    step's loss, the leaf norms of the first gradient as the optimizer
    got it, and of the parameters' change over the steps. With
    ``keep_gradient`` the first gradient itself is kept, on the host."""
    import jax

    bench = program.bench
    numbers = {"losses": []}
    for i in range(steps):
        state, loss = program.compiled(state, batch)
        numbers["losses"].append(statistics.fmean(_loss_values(loss)))
        if i == 0:
            # Kept on the host until the reference has run: the device is
            # the program's until then.
            first = jax.jit(bench.first_gradient)(state)
            numbers["first_gradient"] = leaf_norms(first)
            if keep_gradient:
                numbers["first_gradient_tree"] = jax.device_get(first)
    numbers["change"] = leaf_norms(jax.jit(
        lambda p, w: jax.tree.map(lambda a, b: a - b, p,
                                  bench.weight_params(w)))(
        bench.params_of(state), program.weights(key)))
    return state, numbers


def reference_numbers(cell, program, host_batch, key, steps,
                      precision="f32"):
    """The same steps by the plain reference from the same seeded weights
    and batch, replica by replica, in the shape of
    :func:`checked_steps`'s numbers. A ``precision`` below ``f32`` is the
    control: the reference put in the program's place."""
    import jax

    reference = manifest.load_module("reference", cell.config["reference"])
    params0 = program.bench.weight_params(program.weights(key))
    shards = [tuple(np.split(a, cell.chips)[r] for a in host_batch)
              for r in range(cell.chips)]
    losses, first, params = reference.follow(
        params0, shards, steps, cell.config, precision=precision)
    change = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))(
        params, params0)
    return {"losses": [statistics.fmean(step) for step in losses],
            "first_gradient": leaf_norms(first),
            "first_gradient_tree": first,
            "change": leaf_norms(change)}


def _is_matrix(leaf):
    return leaf.rsplit("/", 1)[-1] in ("kernel", "embedding")


def gaps(ours, reference):
    """Every number read, as a gap from the reference's; the reference
    file's limits say which of them are compared.

    Norms go leaf by leaf. The matrices (kernels and embeddings, all but a
    thousandth of the parameters) and the vectors (norm-layer scales,
    biases) are told apart, by the worst leaf and by the median leaf,
    because they behave apart: a vector's gradient is a sum that nearly
    cancels, and how far bf16 alone moves it depends on the seeded
    weights (PERF.md, section 6). The norm over all leaves counts both."""
    import jax

    numbers = {
        f"loss_step{i + 1}": compare.relative_gap(a, b)
        for i, (a, b) in enumerate(zip(ours["losses"], reference["losses"]))}
    for name, key in (("first_gradient", "first_gradient"),
                      ("param_change", "change")):
        by_leaf = compare.leaf_gaps(ours[key], reference[key])
        matrices = {leaf: gap for leaf, gap in by_leaf.items()
                    if _is_matrix(leaf)}
        numbers[f"{name}_worst_matrix"], leaf = compare.worst(matrices)
        print(f"[check] {name}_worst_matrix is {leaf}", flush=True)
        numbers[f"{name}_median_matrix"] = statistics.median(
            matrices.values())
        numbers[f"{name}_worst_vector"], _ = compare.worst(
            {leaf: gap for leaf, gap in by_leaf.items()
             if leaf not in matrices})
        numbers[f"{name}_global"] = compare.relative_gap(
            compare.global_norm(ours[key]),
            compare.global_norm(reference[key]))
    if "first_gradient_tree" not in ours:
        return numbers
    # The norm of the difference, which a lower precision moves in the
    # first order where it moves a norm only in the second.
    apart = leaf_norms(jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x - y, a, b))(ours["first_gradient_tree"],
                                   reference["first_gradient_tree"]))
    numbers["first_gradient_difference"] = (
        compare.global_norm(apart)
        / compare.global_norm(reference["first_gradient"]))
    floor = statistics.median(reference["first_gradient"].values())
    numbers["first_gradient_difference_worst_matrix"], leaf = compare.worst({
        leaf: norm / (max(reference["first_gradient"][leaf], floor)
                      or float("inf"))
        for leaf, norm in apart.items() if _is_matrix(leaf)})
    print(f"[check] first_gradient_difference_worst_matrix is {leaf}",
          flush=True)
    return numbers


def run(ctx, build):
    """One run of a training cell. ``ctx`` is the harness's: the cell, the
    seed, the seconds, whether to trace, the devices and the clock's
    zero. Returns what ``run.py`` prints."""
    import jax

    cell, spans = ctx["cell"], ctx["spans"]
    traffic = cell.traffic
    counter = CompileCounter()
    program = compile_program(cell, ctx["devices"], build, spans)
    bench, compiled = program.bench, program.compiled
    key, state, host_batch, batch = seeded_inputs(program, ctx["seed"])

    checks = {}
    if cell.chips > 1:
        checks.update(placement_checks(program.mesh, state, batch,
                                       program.text))
    steps = traffic["checked_steps"]
    reference = manifest.load_module("reference", cell.config["reference"])
    limits = (reference.REHEARSAL_LIMITS if cell.rehearsal
              else reference.LIMITS)
    state, ours = checked_steps(
        program, state, batch, key, steps,
        keep_gradient=any("difference" in name for name in limits))
    for _ in range(traffic["warmup_steps"]):
        state, loss = compiled(state, batch)
    jax.block_until_ready(loss)

    compiles_before = counter.compiles
    trace_dir = None
    if ctx["trace"]:
        trace_dir = os.path.join(ctx["out_dir"], "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)    # keep the newest
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the loop's own spans suffice
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        seconds = min(ctx["seconds"], traffic["trace_seconds"])
    else:
        seconds = ctx["seconds"]
    spans["setup"] = time.perf_counter() - ctx["t_start"]
    state, window = timed_window(compiled, state, batch, seconds)
    if ctx["trace"]:
        jax.profiler.stop_trace()
    compiled_in_window = counter.compiles - compiles_before
    memory_peak = device_gate.memory_peak_bytes(ctx["devices"])

    if cell.chips > 1:
        checks["replicas_not_bit_identical"] = float(
            not replicas_identical(bench.identical_of(state)))
    n_steps = len(window["stamps"])
    failed = sum(1 for v in window["losses"] if not math.isfinite(v))
    elapsed = window["end"] - window["start"]
    intervals = (np.diff(window["stamps"]) * 1e3).tolist()
    rate = n_steps * bench.samples_per_step / elapsed / cell.chips
    if not cell.rehearsal:
        print(f"[window] steps={n_steps} seconds={elapsed:.4f} "
              f"step_ms_median={statistics.median(intervals):.4f} "
              f"step_ms_p90_samples={len(intervals)} "
              f"model_tflops_per_s_per_chip="
              f"{rate * bench.flops_per_step / bench.samples_per_step / 1e12:.3f}",
              flush=True)
    print(f"[compile] in_window={compiled_in_window} "
          f"backend_compiles={counter.compiles} "
          f"cache_hits={counter.cache_hits}", flush=True)

    # The reference runs once the program's state is freed, so that the
    # peak above stays the program's. Its time is not set-up.
    del state, batch, loss, compiled
    program.compiled = None
    t0 = time.perf_counter()
    numbers = gaps(ours, reference_numbers(cell, program, host_batch, key,
                                           steps))
    spans["reference"] = time.perf_counter() - t0
    numbers.update(checks)
    numbers["compilations_in_window"] = float(compiled_in_window)
    limits = {**limits, **{name: 0.0 for name in checks},
              "compilations_in_window": 0.0}
    correct = compare.judge(numbers, limits)

    out = {
        "correct": bool(correct and failed == 0),
        "attempted": n_steps, "failed": failed,
        "end_to_end": {
            "setup_s": spans["setup"],
            "train_samples_per_s_per_chip": rate,
            "train_step_ms_p90": percentile(intervals, 90),
        },
        "memory_peak_bytes": memory_peak,
        "layer_inputs": {
            "spans": spans, "compiled_text": program.text,
            "window": window, "steps": n_steps, "bench": bench,
            "cell": cell, "memory_peak_bytes": memory_peak,
            "chips": cell.chips,
        },
    }
    if trace_dir:
        out["layer_inputs"]["trace"] = trace_reduce.load(
            trace_reduce.newest_xplane(trace_dir))
    return out


# -------------------------------------------- what only several chips have

def placement_checks(mesh, state, batch, text):
    """PR 21's four-chip checks as gaps with the limit 0: every leaf on
    every device, the batch on one distinct shard per device, and an
    all-reduce over all replicas in the compiled step."""
    import jax

    n = mesh.size
    devices = set(mesh.devices.flat)
    leaves = jax.tree.leaves(state)
    first = jax.tree.leaves(batch)[0]
    shards = first.addressable_shards
    groups = [c.group_size for c in hlo_text.collectives(text)
              if c.op == "all-reduce"]
    return {
        "leaves_not_on_every_device": float(sum(
            leaf.sharding.device_set != devices for leaf in leaves)),
        "batch_not_one_shard_per_device": float(
            len({s.device for s in shards}) != n
            or len({str(s.index) for s in shards}) != n),
        "no_all_reduce_over_all_replicas": float(n not in groups),
    }


def replicas_identical(tree):
    """Every device's copy of every leaf, compared bit for bit."""
    import jax

    for leaf in jax.tree.leaves(tree):
        copies = [np.asarray(s.data).tobytes()
                  for s in leaf.addressable_shards]
        if any(c != copies[0] for c in copies[1:]):
            return False
    return True

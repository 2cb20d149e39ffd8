"""One run of one benchmark cell: load, warm up, measure, check, print.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic,
builder, reference and per-layer readers are files found by the names
written there (see ``harness/manifest.py``). The last line of standard
output is the result: one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. A run that finds no TPU, or fewer chips than the cell asks
for, exits 3 with no result. ``--rehearse-cpu`` walks the same code at the
tiny sizes the configuration and traffic files give for it, on the CPU
with the kernels interpreted, and prints a line that says ``rehearsal``
and carries no device metric.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from harness import device as device_gate, manifest, trace_reduce  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny sizes on the CPU; never a result")
    return p.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    cell = manifest.Cell(args.workload, rehearsal=args.rehearse_cpu)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell.chips}").strip()

    try:
        import horovod_tpu as hvd
        from horovod_tpu.utils import compile_cache
    except ImportError as e:
        sys.stderr.write(f"benchmark: the program is not here: {e}\n")
        return 2

    spans = {}
    if not args.rehearse_cpu:
        # The rehearsal's CPU programs are not worth keeping.
        cache_dir = compile_cache.enable()
        entries_before = compile_cache.entry_count(cache_dir)
    t0 = time.perf_counter()
    hvd.init()
    spans["init"] = time.perf_counter() - t0
    devices, stamp = device_gate.acquire(cell.chips, args.rehearse_cpu)
    print(f"[device] {json.dumps(stamp)} cell={cell.name} "
          f"chips={cell.chips} seed={args.seed}", flush=True)

    out_dir = os.path.join(manifest.ROOT, ".bench_out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    builder = manifest.load_module("builders", cell.config["builder"])
    result = builder.run({
        "cell": cell, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "devices": devices, "spans": spans,
        "t_start": T_START, "out_dir": out_dir,
    })

    if not args.rehearse_cpu:
        spans["cache_entries_written"] = (
            compile_cache.entry_count(cache_dir) - entries_before)
    print("[spans] " + " ".join(f"{k}={v:.3f}" if isinstance(v, float)
                                else f"{k}={v}" for k, v in spans.items()),
          flush=True)
    inputs = result["layer_inputs"]
    inputs["stamp"] = stamp
    units = {m["name"]: m["unit"]
             for m in cell.end_to_end + cell.per_layer}
    if args.trace:
        values = {}
        for metric in cell.per_layer:
            reader = manifest.load_module("layer_metrics", metric["name"])
            value = reader.read(inputs)
            if value is not None:
                values[metric["name"]] = value
    else:
        values = {m["name"]: result["end_to_end"][m["name"]]
                  for m in cell.end_to_end}
    if not args.rehearse_cpu:
        print("[all] " + json.dumps(result["end_to_end"]), flush=True)

    line = {
        "correct": result["correct"],
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items()},
        "device": dict(stamp, memory_peak_bytes=result["memory_peak_bytes"]),
    }
    if args.rehearse_cpu:
        # A rehearsal proves the control flow. Its timings are the CPU's
        # and are not written under any metric's name.
        line = {"rehearsal": True, "correct": line["correct"],
                "attempted": line["attempted"], "failed": line["failed"],
                "metrics": {}, "device": stamp}
    elif args.trace:
        trace = inputs["trace"]
        line["device"]["busy_s"] = trace_reduce.mean_busy_ns(trace) / 1e9
        line["device"]["window_s"] = (
            inputs["window"]["end"] - inputs["window"]["start"])
        line["breakdown"] = {
            "device_ops": trace_reduce.top_operations(trace),
            "idle_gaps": trace_reduce.longest_gaps(trace),
        }
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Read, on the chip and in one process, the numbers the limits of
``correct`` are set from: for each seed the gaps between the program's
checked steps and the float32 reference, and the gaps of the control (the
reference put in the program's place, computed one precision below the
configuration's) from the same float32 reference.

    python benchmarks/tools/read_gaps.py --workload <cell> --seeds 1,2,3 \
        [--control fp8 --control-seeds 3]

Not part of a benchmark run. ``PERF.md`` section 2 records what it read.
"""

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from harness import device as device_gate, manifest  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", default="int8",
                   help="comma-separated precisions of the control")
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args()
    cell = manifest.Cell(args.workload, rehearsal=args.rehearse_cpu)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell.chips}")

    import horovod_tpu as hvd
    from horovod_tpu.utils import compile_cache
    from builders import training

    if not args.rehearse_cpu:
        compile_cache.enable()
    hvd.init()
    devices, stamp = device_gate.acquire(cell.chips, args.rehearse_cpu)
    print(f"[device] {json.dumps(stamp)}", flush=True)
    builder = manifest.load_module("builders", cell.config["builder"])
    program = training.compile_program(cell, devices, builder.build, {})
    steps = cell.traffic["checked_steps"]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        key, state, host_batch, batch = training.seeded_inputs(program, seed)
        state, ours = training.checked_steps(program, state, batch, key,
                                             steps, keep_gradient=True)
        del state, batch
        ref = training.reference_numbers(cell, program, host_batch, key,
                                         steps)
        print("[program] " + json.dumps(
            {"seed": seed, "losses": ours["losses"],
             **training.gaps(ours, ref)}), flush=True)
        for precision in args.control.split(",") * (i < args.control_seeds):
            control = training.reference_numbers(
                cell, program, host_batch, key, steps, precision=precision)
            print(f"[control {precision}] " + json.dumps(
                {"seed": seed, "losses": control["losses"],
                 **training.gaps(control, ref)}), flush=True)


if __name__ == "__main__":
    main()

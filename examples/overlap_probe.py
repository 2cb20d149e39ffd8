"""Backward-order bucket-scheduler overlap probe (rounds 12+16).

Spawns a real 2-rank native-engine job on this host and drives a
simulated backward pass — N gradient tensors produced one by one with a
fixed compute delay between productions — through two paths:

* **unbucketed**: wait for the full gradient set, then allreduce
  everything (the no-overlap baseline every naive data-parallel step
  implements);
* **bucketed**: ``hvd.BucketScheduler`` — with the round-16 pipelined
  engine the scheduler launches each gradient's allreduce eagerly as it
  is produced (the double-buffered wire thread keeps fused groups
  moving while later gradients are still packed), and the last backward
  bucket carries launch priority 1 so the optimizer-critical reduction
  jumps the queue (docs/overlap.md).

Reports the measured ``overlap_efficiency`` (fraction of the backward
window with at least one reduction in flight — the union formula shared
with ``utils.scaling_model``), both paths' step times, the scaling
model's PREDICTED overlap for the same schedule, the negotiation-vs-wire
stall split from the r13-calibrated control-plane model, and the
step-time delta vs the r12 serial-engine baseline artifact. Results are
bit-identical across paths (pinned by tests/test_wire_compression.py's
mp acceptance test); this probe is about WHEN collectives launch, never
what they compute.

A/B flags: ``--no-pipeline`` forces the serial engine
(``HOROVOD_PIPELINE=0`` — the r12 behavior), ``--no-priority`` drops the
last-bucket priority tag.

Writes ``artifacts/overlap_r16.json`` via ``--out``; the last stdout
line is a JSON summary.
"""

import argparse
import json
import os
import platform
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _free_port():
    from horovod_tpu.run.launch import _free_port as launcher_free_port

    return launcher_free_port()


def _parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tensors", type=int, default=16)
    p.add_argument("--tensor-mib", type=float, default=2.0)
    p.add_argument("--compute-ms", type=float, default=10.0,
                   help="simulated backward compute per produced gradient")
    p.add_argument("--bucket-mib", type=float, default=8.0)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--priority", dest="priority", action="store_true",
                   default=True,
                   help="tag the last backward bucket with launch "
                        "priority 1 (default)")
    p.add_argument("--no-priority", dest="priority", action="store_false")
    p.add_argument("--no-pipeline", action="store_true",
                   help="HOROVOD_PIPELINE=0 in the children: serial "
                        "fill->wire->copy-out engine, the r12 baseline")
    p.add_argument("--out", default=None, help="artifact JSON path")
    p.add_argument("--child", type=int, default=None, help=argparse.SUPPRESS)
    p.add_argument("--addrs", default=None, help=argparse.SUPPRESS)
    return p.parse_args(argv)


def child_main(args):
    os.environ["HOROVOD_RING_ADDRS"] = args.addrs
    os.environ.setdefault("HOROVOD_CYCLE_TIME", "1")
    if args.no_pipeline:
        os.environ["HOROVOD_PIPELINE"] = "0"
    from horovod_tpu.common.config import Config
    from horovod_tpu.common.topology import Topology
    from horovod_tpu.controller.bucket_scheduler import (
        BucketScheduler,
        partition_buckets,
    )
    from horovod_tpu.controller.native import NativeController

    rank, size = args.child, 2
    topo = Topology(rank=rank, size=size, local_rank=rank, local_size=size,
                    cross_rank=0, cross_size=1)
    ctl = NativeController(Config.from_env(), topo)
    n = int(args.tensor_mib * (1 << 20)) // 4
    grads = [np.random.RandomState(100 + i).randn(n).astype(np.float32)
             for i in range(args.tensors)]
    compute_s = args.compute_ms / 1e3
    bucket_bytes = int(args.bucket_mib * (1 << 20))
    # The last backward bucket — first needed by the optimizer — is known
    # ahead of time from the static plan; its members carry priority 1.
    priority_names = []
    if args.priority:
        plan = partition_buckets(
            [(f"grad.{i}", g.nbytes) for i, g in enumerate(grads)],
            bucket_bytes)
        if plan:
            priority_names = plan[-1].names

    def produce():
        # The simulated backward pass: one gradient materializes per
        # compute slice, in backward production order.
        for i, g in enumerate(grads):
            time.sleep(compute_s)
            yield f"grad.{i}", g

    def run_unbucketed():
        t0 = time.monotonic()
        ready = list(produce())  # full pytree first, then reduce
        handles = [(name, ctl.allreduce_async(g, average=True, name=name))
                   for name, g in ready]
        for _, h in handles:
            h.wait()
        return time.monotonic() - t0, None

    def run_bucketed():
        t0 = time.monotonic()
        sched = BucketScheduler(ctl, bucket_bytes=bucket_bytes,
                                priority_names=priority_names)
        sched.backward_started()
        for name, g in produce():
            sched.grad_ready(name, g)
        _, report = sched.finish()
        return time.monotonic() - t0, report

    # Warmup both paths (connections, fusion buffer, residual scratch).
    run_unbucketed()
    run_bucketed()
    un_times, bu_times, reports = [], [], []
    for _ in range(args.steps):
        t, _ = run_unbucketed()
        un_times.append(t)
        t, rep = run_bucketed()
        bu_times.append(t)
        reports.append(rep)
    if rank == 0:
        median = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
        rep = reports[bu_times.index(median(bu_times))]
        print("OVERLAP " + json.dumps({
            "unbucketed_step_ms": round(median(un_times) * 1e3, 2),
            "bucketed_step_ms": round(median(bu_times) * 1e3, 2),
            "pipeline": bool(ctl.pipeline_enabled),
            "report": rep,
        }), flush=True)
    ctl.shutdown()


def main(argv=None):
    args = _parse_args(argv)
    if args.child is not None:
        child_main(args)
        return
    from horovod_tpu.core import bindings

    if bindings.load() is None:
        raise SystemExit("native core unavailable (no toolchain)")
    addrs = ",".join(f"127.0.0.1:{_free_port()}" for _ in range(2))
    passthrough = ["--tensors", str(args.tensors), "--tensor-mib",
                   str(args.tensor_mib), "--compute-ms",
                   str(args.compute_ms), "--bucket-mib",
                   str(args.bucket_mib), "--steps", str(args.steps)]
    if args.no_pipeline:
        passthrough.append("--no-pipeline")
    if not args.priority:
        passthrough.append("--no-priority")
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", str(r),
         "--addrs", addrs] + passthrough,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for r in range(2)]
    outs = []
    for r, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            raise SystemExit(f"rank {r} hung")
        outs.append(out)
    for r, (proc, out) in enumerate(zip(procs, outs)):
        if proc.returncode != 0:
            sys.stderr.write(out)
            raise SystemExit(f"rank {r} failed (exit {proc.returncode})")
    payload = None
    for line in outs[0].splitlines():
        if line.startswith("OVERLAP "):
            payload = json.loads(line[len("OVERLAP "):])
    if payload is None:
        sys.stderr.write(outs[0])
        raise SystemExit("rank 0 produced no OVERLAP record")

    report = payload["report"]
    pipelined = bool(payload.get("pipeline"))
    # Model-vs-measured (ROADMAP item 4): rebuild the model's event
    # timeline from the measured schedule and compare its overlap
    # efficiency through the SAME union formula — the shared recipe in
    # scaling_model (the test suite pins the same path). The pipelined
    # engine gets the pipelined event model (launches no longer
    # serialized behind the previous bucket's copy-out).
    from horovod_tpu.utils.scaling_model import (
        BucketEvent,
        modeled_events_from_measured,
        overlap_efficiency_from_events,
        pipelined_modeled_events,
        stall_split_report,
    )

    window = report["compute_window_s"]
    if report.get("eager"):
        modeled = pipelined_modeled_events(report["events"], window)
    else:
        events = [BucketEvent(e["launch_s"], e["complete_s"])
                  for e in report["events"]]
        modeled = modeled_events_from_measured(events, window)
    predicted = overlap_efficiency_from_events(modeled, 0.0, window)

    # Negotiation-vs-wire stall split from the r13-calibrated control
    # plane (884us/rank-class negotiation, artifacts/simcluster_r13.json)
    # — names the owner of whatever overlap gap remains.
    stall_split = None
    cal_path = os.path.join(REPO, "artifacts", "simcluster_r13.json")
    if os.path.exists(cal_path):
        from horovod_tpu.utils.scaling_model import control_plane_from_artifact

        with open(cal_path) as f:
            cal = control_plane_from_artifact(json.load(f))
        stall_split = stall_split_report(report["events"], cal, n=2)

    summary = {
        "tensors": args.tensors,
        "tensor_mib": args.tensor_mib,
        "bucket_mib": args.bucket_mib,
        "compute_ms_per_tensor": args.compute_ms,
        "pipeline": pipelined,
        "priority": bool(args.priority),
        "unbucketed_step_ms": payload["unbucketed_step_ms"],
        "bucketed_step_ms": payload["bucketed_step_ms"],
        "speedup_bucketed": round(
            payload["unbucketed_step_ms"]
            / max(1e-9, payload["bucketed_step_ms"]), 3),
        "overlap_efficiency": report["overlap_efficiency"],
        "buckets": report["buckets"],
        "model_predicted_overlap_efficiency": round(predicted, 4),
        "model_vs_measured_abs_diff": round(
            abs(predicted - report["overlap_efficiency"]), 4),
    }
    if pipelined:
        summary["overlap_efficiency_pipelined"] = \
            report["overlap_efficiency"]
    if stall_split is not None:
        summary["stall_split"] = stall_split
    # Step-time delta vs the serial-engine r12 baseline artifact, when a
    # comparable run (same workload knobs) is on disk.
    r12_path = os.path.join(REPO, "artifacts", "overlap_r12.json")
    if os.path.exists(r12_path):
        with open(r12_path) as f:
            r12 = json.load(f)
        if all(r12.get(k) == summary[k] for k in
               ("tensors", "tensor_mib", "bucket_mib",
                "compute_ms_per_tensor")):
            summary["r12_baseline"] = {
                "bucketed_step_ms": r12["bucketed_step_ms"],
                "overlap_efficiency": r12["overlap_efficiency"],
            }
            summary["step_time_delta_ms_vs_r12"] = round(
                r12["bucketed_step_ms"] - payload["bucketed_step_ms"], 2)
    if args.out:
        artifact = {
            "what": ("Round-16 pipelined overlap: gradient allreduces "
                     "launch eagerly while the simulated backward pass "
                     "still runs, against the native engine's double-"
                     "buffered data plane with the last bucket priority-"
                     "tagged (2-rank, loopback). overlap_efficiency = "
                     "fraction of the backward window with >=1 reduction "
                     "in flight "
                     "(utils.scaling_model.overlap_efficiency_from_events "
                     "— model and measurement share the formula); "
                     "stall_split attributes complete-after-ready time to "
                     "negotiation vs wire via the r13-calibrated control-"
                     "plane model."),
            "round": 16,
            "cmd": "python examples/overlap_probe.py",
            "substrate": {
                "transport": "loopback TCP, shared cores",
                "host": platform.platform(),
                "cpus": os.cpu_count(),
                "honest_read": (
                    "Simulated backward (sleep per produced gradient): "
                    "the probe measures the SCHEDULER's overlap, not a "
                    "real model's. Reduction cost on loopback shares "
                    "CPUs with nothing here (the producer sleeps), so "
                    "overlap efficiency reads higher than a busy chip "
                    "would; the bucketed-vs-unbucketed step-time ratio "
                    "is the robust signal. Box pace swings +-20%."),
            },
            "median_step_report": report,
            **summary,
        }
        out_path = os.path.join(REPO, args.out) \
            if not os.path.isabs(args.out) else args.out
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {out_path}", file=sys.stderr)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()

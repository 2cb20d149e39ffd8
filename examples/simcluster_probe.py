"""Control-plane scaling probe on the simcluster harness (round 13).

Measures what ``utils/scaling_model.py`` used to assume: negotiation
step latency, elastic reshape time, and heartbeat-fanout cost per world
size, on 8–64 *logical* ranks multiplexed in this one process
(``horovod_tpu/sim``, docs/simcluster.md) — plus the round-12 overlap
model-vs-measured check re-run at 8 and 32 ranks instead of its
original 2-rank probe. Writes the full record (with the fitted
control-plane calibration and per-size model residuals) to ``--out``
and prints a one-line JSON summary.

Substrate honesty: loopback TCP, one shared GIL — these calibrate the
coordinator's per-rank walk costs (recv + HMAC + dispatch per wire),
not NIC latency; the record says so.

Usage::

    python examples/simcluster_probe.py --out artifacts/simcluster_r13.json
    python examples/simcluster_probe.py --sizes 8,16 --cycles 10  # quick
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--sizes", default="8,16,32,64",
                        help="comma-separated logical world sizes")
    parser.add_argument("--cycles", type=int, default=30,
                        help="measured steps per world size")
    parser.add_argument("--overlap-sizes", default="8,32",
                        help="world sizes for the overlap model check "
                             "('' to skip)")
    parser.add_argument("--out", default=None,
                        help="write the full JSON record here")
    args = parser.parse_args()

    from horovod_tpu.sim.measure import (
        measure_control_plane,
        run_overlap_probe,
    )

    sizes = [int(s) for s in args.sizes.split(",") if s.strip()]
    record = measure_control_plane(sizes, cycles=args.cycles)
    record["substrate"] = (
        "simcluster: in-process loopback TCP, multiplexed logical ranks, "
        "shared GIL — calibrates coordinator per-rank walk costs, not "
        "NIC latency (docs/simcluster.md)")
    record["overlap"] = {}
    overlap_sizes = [int(s) for s in args.overlap_sizes.split(",")
                     if s.strip()]
    for n in overlap_sizes:
        record["overlap"][str(n)] = run_overlap_probe(n)

    if args.out:
        out_dir = os.path.dirname(args.out)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1, sort_keys=True)
            f.write("\n")

    cal = record["calibration"]
    largest = str(max(sizes))
    summary = {
        "unit": "seconds",
        "world_sizes": record["world_sizes"],
        "negotiate_per_rank_us": round(
            cal["negotiation_per_rank_s"] * 1e6, 2),
        "reshape_per_rank_us": round(cal["reshape_per_rank_s"] * 1e6, 2),
        "heartbeat_per_rank_us": round(
            cal["heartbeat_per_rank_s"] * 1e6, 2),
        "negotiate_step_seconds_at_max": record["control_plane"][
            largest]["negotiate_step_seconds"],
        "overlap_model_diff": {
            n: row["model_vs_measured_diff"]
            for n, row in sorted(record["overlap"].items())},
        "artifact": args.out,
    }
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())

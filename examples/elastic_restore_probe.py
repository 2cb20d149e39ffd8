"""Elastic-restore flatness probe (ISSUE 15, docs/sharded-checkpoint.md).

Measures reshape-to-consistent-state time — ``hvd.elastic.State.restore()``
— on a real 3-rank elastic job at two model sizes >= 4x apart, for both
restore mechanisms:

* ``p2p`` (the default): rank 0 publishes tiny authority metadata
  (per-shard digests over the deterministic flat-leaf layout); survivors
  verify against their precomputed digest table and keep their LOCAL
  commit — zero model bytes cross the wire, so the time is dominated by
  two small object collectives + one in-memory materialization.
* ``broadcast`` (the r12 baseline, ``HOROVOD_ELASTIC_RESTORE=broadcast``):
  rank 0 re-broadcasts the whole committed pytree through the star.

The acceptance bar (ISSUE 15): across a >=4x model-size spread, the p2p
restore-time ratio stays <= 1.5x while the re-measured broadcast baseline
scales with the model. Loopback understates the broadcast cost a real NIC
would pay, so the recorded contrast is conservative.

Writes the full record to ``--out`` (artifacts/elastic_restore_r15.json);
the last stdout line is the JSON summary,
including the new ``hvd_elastic_restore_seconds`` histogram field.
"""

import argparse
import json
import os
import platform
import socket
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _child(args):
    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    sizes_mib = [args.small_mib, args.small_mib * args.factor]
    record = {"sizes_mib": sizes_mib, "ranks": size, "leaf_kinds": {}}
    for kind in ("jax", "numpy"):
        modes = {}
        for mode in ("p2p", "broadcast"):
            modes[mode] = {}
            for size_mib in sizes_mib:
                # 8 leaves of equal share: enough spread for a real
                # layout, few enough that per-leaf overhead is noise.
                n = int(size_mib * (1 << 20) / 4 / 8)
                # Identical on every rank, like a lockstep-trained
                # model: the survivor path this measures is
                # digest-match, not fetch.
                params = {f"w{i}": np.full(n, float(i), np.float32)
                          for i in range(8)}
                if kind == "jax":
                    params = {k: jnp.asarray(v)
                              for k, v in sorted(params.items())}
                state = hvd.elastic.State(step=0, params=params)
                os.environ["HOROVOD_ELASTIC_RESTORE"] = mode
                state.restore()  # warmup: installs the exchange
                time.sleep(0.2)  # let the digest precompute land (p2p)
                reps = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    state.restore()
                    reps.append(time.perf_counter() - t0)
                # The job-level restore time is the SLOWEST rank's.
                worst = [max(vals) for vals in zip(*hvd.allgather_object(
                    reps, name=f"probe.{kind}.{mode}.{size_mib}"))]
                modes[mode][str(size_mib)] = {
                    "median_s": float(np.median(worst)),
                    "p90_s": float(np.percentile(worst, 90)),
                    "reps": args.reps,
                }
                state.close()  # release the workers + pinned snapshot
        record["leaf_kinds"][kind] = modes
    os.environ["HOROVOD_ELASTIC_RESTORE"] = "p2p"
    if rank == 0:
        small, big = (str(s) for s in sizes_mib)
        for kind in ("jax", "numpy"):
            for mode in ("p2p", "broadcast"):
                m = record["leaf_kinds"][kind][mode]
                m["ratio"] = (m[big]["median_s"] / m[small]["median_s"]
                              if m[small]["median_s"] > 0 else None)
        snap = hvd.metrics.snapshot()
        hist = (snap.get("hvd_elastic_restore_seconds") or {}).get(
            "values") or []
        record["hvd_elastic_restore_seconds"] = (
            hist[0][1] if hist else {"count": 0})
        jax_ratio = record["leaf_kinds"]["jax"]["p2p"]["ratio"]
        record["acceptance"] = {
            "size_spread": args.factor,
            "p2p_ratio_max": 1.5,
            # The acceptance row is the jax pytree — this repo's
            # training states — where a digest-matched restore moves
            # and copies zero model bytes. numpy states pay one buffer
            # copy per restore (mutable in place; recorded beside it).
            "p2p_ratio_ok": jax_ratio is not None and jax_ratio <= 1.5,
        }
        print("PROBE_RESULT " + json.dumps(record), flush=True)
    hvd.shutdown()


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--ranks", type=int, default=3)
    parser.add_argument("--small-mib", type=float, default=4.0)
    parser.add_argument("--factor", type=int, default=4)
    parser.add_argument("--reps", type=int, default=15)
    parser.add_argument("--out", default=None)
    parser.add_argument("--child", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        _child(args)
        return 0

    addr = f"127.0.0.1:{_free_port()}"
    procs = []
    for rank in range(args.ranks):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.update({
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_RANK": str(rank),
            "HOROVOD_SIZE": str(args.ranks),
            "HOROVOD_LOCAL_RANK": str(rank),
            "HOROVOD_LOCAL_SIZE": str(args.ranks),
            "HOROVOD_CONTROLLER_ADDR": addr,
            "HOROVOD_ENGINE": "python",
            "HOROVOD_ELASTIC": "1",
            "HOROVOD_METRICS": "1",
            "HOROVOD_CYCLE_TIME": "1",
        })
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child",
             "--ranks", str(args.ranks),
             "--small-mib", str(args.small_mib),
             "--factor", str(args.factor), "--reps", str(args.reps)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outputs = []
    for rank, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            raise SystemExit(f"probe: rank {rank} hung")
        outputs.append(out)
        if proc.returncode != 0:
            sys.stderr.write(out)
            raise SystemExit(f"probe: rank {rank} failed "
                             f"(exit {proc.returncode})")
    record = None
    for line in outputs[0].splitlines():
        if line.startswith("PROBE_RESULT "):
            record = json.loads(line.split(" ", 1)[1])
    if record is None:
        sys.stderr.write(outputs[0])
        raise SystemExit("probe: rank 0 printed no result")
    record["substrate"] = {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "transport": "loopback TCP star (wire cost IS cpu cost here; "
                     "real NICs make the broadcast baseline strictly "
                     "worse)",
    }
    if args.out:
        out_path = os.path.join(REPO, args.out) \
            if not os.path.isabs(args.out) else args.out
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=1, sort_keys=True)
            f.write("\n")
    p2p = record["leaf_kinds"]["jax"]["p2p"]
    bc = record["leaf_kinds"]["jax"]["broadcast"]
    np_p2p = record["leaf_kinds"]["numpy"]["p2p"]
    print(json.dumps({
        "value": round(p2p["ratio"], 3) if p2p["ratio"] else None,
        "unit": "x restore-time growth over a "
                f"{record['acceptance']['size_spread']}x model spread "
                "(p2p, jax pytree; <=1.5 = flat)",
        "sizes_mib": record["sizes_mib"],
        "p2p_median_s": {k: v["median_s"] for k, v in sorted(p2p.items())
                         if isinstance(v, dict)},
        "broadcast_median_s": {k: v["median_s"]
                               for k, v in sorted(bc.items())
                               if isinstance(v, dict)},
        "broadcast_ratio": round(bc["ratio"], 3) if bc["ratio"] else None,
        "numpy_p2p_ratio": round(np_p2p["ratio"], 3)
        if np_p2p["ratio"] else None,
        "hvd_elastic_restore_seconds":
            record["hvd_elastic_restore_seconds"],
        "acceptance": record["acceptance"],
        "artifact": args.out,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

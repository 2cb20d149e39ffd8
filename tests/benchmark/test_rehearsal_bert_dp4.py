"""The ``bert-base-s512-dp4`` cell (PR 34) rehearsed on the CPU:
``run.py --rehearse-cpu`` end to end in a child process with a time limit
of its own, traced so that every per-layer reader of the cell runs, on
four virtual devices at the tiny sizes the configuration and traffic
files give (two sequences of 512 a replica through the interpreted flash
kernels). The placement checks and the bit-identical state come with
``chips`` 4; the rehearsal's last line says so and carries no device
metric. The readers' own tests are ``test_exchange.py``."""

import json
import os
import subprocess
import sys

from harness import manifest

CELL = "bert-base-s512-dp4"
RUN = os.path.join(manifest.BENCH_DIR, "run.py")


def test_rehearsal_runs_traced_on_four_devices_and_is_marked():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "2147483659",
         "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    result = json.loads(lines[-1])
    assert set(result) == {"rehearsal", "correct", "attempted", "failed",
                           "metrics", "device"}
    assert result["rehearsal"] is True and result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {}
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    # No CPU timing under any metric's name, anywhere in the output.
    m = manifest.load_manifest()
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    text = "\n".join(lines)
    assert not any(name in text for name in names)
    # Each number compared is printed beside its limit: the reference's
    # six, the three placement checks, the replicas' state and the
    # window's compilations, all at the limits the one-chip cell has.
    checks = {ln.split()[1]: ln for ln in lines
              if ln.startswith("[check] ") and " limit " in ln}
    assert all(ln.endswith(" ok") for ln in sorted(checks.values()))
    assert {"leaves_not_on_every_device", "batch_not_one_shard_per_device",
            "no_all_reduce_over_all_replicas", "replicas_not_bit_identical",
            "compilations_in_window", "loss_step3",
            "first_gradient_worst_matrix"} <= set(checks)
    # The record the program kept of the step's exchange, as the reader
    # of the bytes asked for printed it: the rehearsal model's 39 leaves
    # (16 a layer, the three embeddings, their norm's two and the head's
    # two), over four replicas.
    line = next(ln for ln in lines if ln.startswith("[exchange] "))
    record = json.loads(line[len("[exchange] "):])
    assert record["prefix"] == "DistributedOptimizer"
    assert (record["axis"], record["axis_size"]) == ("data", 4)
    assert record["bytes_wire"] == record["bytes_asked"] > 0
    assert record["wire_dtypes"] == {"float32": record["bytes_wire"]}
    assert record["leaves"] == 39 and record["average"] is True

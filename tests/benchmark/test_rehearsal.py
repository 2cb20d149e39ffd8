"""``run.py --rehearse-cpu`` end to end, each cell in a child process with
a time limit of its own: the same code as a chip run at the tiny sizes the
configuration and traffic files give, on the CPU. A rehearsal's last line
says so and carries no device metric."""

import json
import os
import subprocess
import sys

import pytest

from harness import manifest

RUN = os.path.join(manifest.BENCH_DIR, "run.py")


def _rehearse(cell, trace):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", "2147483659",
         "--seconds", "1", "--trace", str(trace), "--rehearse-cpu"],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.splitlines()


@pytest.mark.parametrize("cell,trace,devices", [
    ("resnet50-dp1", 0, 1),
    ("bert-base-s512-dp1", 1, 1),
    ("resnet50-dp4", 0, 4),
])
def test_rehearsal_runs_and_is_marked(cell, trace, devices):
    lines = _rehearse(cell, trace)
    result = json.loads(lines[-1])
    assert set(result) == {"rehearsal", "correct", "attempted", "failed",
                           "metrics", "device"}
    assert result["rehearsal"] is True and result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {}
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": devices}
    # No CPU timing under any metric's name, anywhere in the output.
    m = manifest.load_manifest()
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    text = "\n".join(lines)
    assert not any(name in text for name in names)
    # Each number compared is printed beside its limit.
    checks = [ln for ln in lines if ln.startswith("[check] ")
              and " limit " in ln]
    assert len(checks) >= 6 and all(ln.endswith(" ok") for ln in checks)


def test_without_the_rehearsal_flag_there_is_no_result_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "resnet50-dp1", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    assert "needs platform 'tpu'" in proc.stderr

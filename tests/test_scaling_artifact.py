"""Scaling-efficiency harness (examples/scaling_efficiency.py): the record
it prints must keep its shape — parseable JSON on the last line,
power-of-two sizes up to the device count, positive rates, efficiency
consistent with the rates. How the rates of virtual CPU devices that
share the host's cores compare with each other is the machine's load,
not the program's, and is not asserted (real numbers need chips)."""

import json
import os
import sys

from mp_harness import run_cmd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_scaling_harness_curve_shape():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = run_cmd(
        [sys.executable, os.path.join(REPO, "examples",
                                      "scaling_efficiency.py"),
         "--model", "mlp", "--steps", "5", "--warmup", "2",
         "--batch-per-chip", "32"],
        timeout=180, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    record = json.loads(out.stdout.strip().splitlines()[-1])

    assert record["metric"] == "scaling_efficiency"
    sizes = record["sizes"]
    assert sizes == [1, 2, 4, 8]
    rates = {int(k): v for k, v in record["img_sec"].items()}
    eff = {int(k): v for k, v in record["efficiency"].items()}
    assert all(rates[n] > 0 for n in sizes)
    # Efficiency must be rates-consistent...
    for n in sizes:
        expected = rates[n] / (n * rates[1])
        assert abs(eff[n] - expected) < 1e-3, (n, eff[n], expected)
    # ...and anchored at 1 for n=1.
    assert eff[1] == 1.0

"""Scaling-efficiency harness (examples/scaling_efficiency.py): the curve
artifact the driver archives each round must keep its shape — parseable
JSON, power-of-two sizes up to the device count, positive rates, efficiency
consistent with the rates and non-increasing in world size (on the shared-
core CPU box efficiency is ~1/n by construction; real numbers need chips)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_resnet50_roofline_artifact_coherent():
    """The shipped ceiling proof (examples/resnet50_roofline.py) must stay
    internally coherent: measured time sits between the optimistic
    max(flops,bytes) bound and the serial sum bound, and the batch matches
    what bench.py actually runs."""
    sys.path.insert(0, REPO)  # bench.py lives at the repo root
    import bench

    d = json.load(open(os.path.join(REPO, "artifacts",
                                    "resnet50_roofline_r4.json")))
    r = d["roofline"]
    assert r["max_bound_ms"] <= r["sum_bound_ms"]
    assert r["max_bound_ratio"] < 1.0
    # ceiling claim: within 10% of the serial two-resource bound
    assert 0.9 <= r["sum_bound_ratio"] <= 1.15, r["sum_bound_ratio"]
    assert d["batch_per_chip"] == bench.BATCH_PER_CHIP
    for row in r["top_ops"]:
        assert row["limiter"] in ("flops", "hbm")
        assert row["roofline_ratio"] is not None  # top ops all have time
        assert abs(max(row["t_flops_ms"], row["t_hbm_ms"])
                   - row["roofline_ratio"] * row["t_measured_ms"]) \
            < 0.02 * max(row["t_measured_ms"], 0.1)


def test_moe_ceiling_artifact_coherent():
    """Phase tables must be internally coherent: phases sum to the total,
    the MoE dispatch machinery stays under 10% of the step (the headline
    claim), and the device totals reproduce the round-3 throughput rows
    within the measured noise band."""
    d = json.load(open(os.path.join(REPO, "artifacts",
                                    "moe_ceiling_r4.json")))
    for cfg, (tok, r3_tok) in (("s1024_b8", (8 * 1024, 105_200)),
                               ("s512_b32", (32 * 512, 120_700))):
        t = dict(d["phase_ms_per_step"][cfg])
        total = t.pop("total")
        ssum = sum(v for v in t.values())
        assert abs(ssum - total) < 0.02 * total, (cfg, ssum, total)
        moe_overhead = (t["dispatch_combine"] + t["router"]
                        + t["route_sort"])
        assert moe_overhead / total < 0.10, (cfg, moe_overhead)
        tok_s = tok / (total / 1e3)
        assert abs(tok_s - r3_tok) / r3_tok < 0.12, (cfg, tok_s)


def test_scaling_harness_curve_shape():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "scaling_efficiency.py"),
         "--model", "mlp", "--steps", "5", "--warmup", "2",
         "--batch-per-chip", "32"],
        env=env, capture_output=True, text=True, timeout=900)
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
    # ...anchored at 1 for n=1, and non-increasing in n (true on real chips
    # up to noise and by construction on shared host cores).
    assert eff[1] == 1.0
    for a, b in zip(sizes, sizes[1:]):
        assert eff[b] <= eff[a] * 1.1, (a, b, eff)

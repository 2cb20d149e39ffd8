"""Launcher end-to-end: the reference CI smoke-runs `horovodrun -np 2`
(.buildkite/gen-pipeline.sh:101-133); same here via `python -m horovod_tpu.run`."""

import os
import sys

import pytest

from mp_harness import run_cmd
from mp_harness import run_launcher as _run_launcher

SCRIPT = (
    "import os; os.environ.setdefault('JAX_PLATFORMS','cpu');"
    "import jax; jax.config.update('jax_platforms','cpu');"
    "import numpy as np; import horovod_tpu as hvd; hvd.init();"
    "out = np.asarray(hvd.allreduce(np.ones(4,np.float32)*(hvd.rank()+1),"
    "average=True, name='launch.t'));"
    "expected = np.mean([r+1 for r in range(hvd.size())]);"
    "assert np.allclose(out, expected), out;"
    "print(f'rank {hvd.rank()} of {hvd.size()} ok'); hvd.shutdown()"
)


def test_launch_np2():
    res = _run_launcher(["-np", "2", sys.executable, "-c", SCRIPT])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "[0]: rank 0 of 2 ok" in res.stdout
    assert "[1]: rank 1 of 2 ok" in res.stdout


def test_metrics_urls_logged_at_startup(monkeypatch):
    """With HOROVOD_METRICS_PORT set, horovodrun prints each rank's
    resolved endpoint (port + rank offset) so operators never compute it
    by hand; --verbose adds the rank-0 cluster-view URL."""
    monkeypatch.setenv("HOROVOD_METRICS_PORT", "39500")
    res = _run_launcher(["-np", "2", "--verbose", sys.executable, "-c",
                         "print('ok')"], timeout=60)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "rank 0 metrics at http://127.0.0.1:39500/metrics" in res.stderr
    assert "rank 1 metrics at http://127.0.0.1:39501/metrics" in res.stderr
    assert "cluster view" in res.stderr
    assert ":39500/metrics" in res.stderr.split("cluster view", 1)[1]
    monkeypatch.setenv("HOROVOD_METRICS_PORT", "nonsense")
    res = _run_launcher(["-np", "1", sys.executable, "-c", "print('ok')"],
                        timeout=60)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ignoring unparseable HOROVOD_METRICS_PORT" in res.stderr


def test_trace_flag_produces_merged_trace_and_report(tmp_path):
    """horovodrun --trace DIR: ranks trace under DIR, rank 0 merges at
    shutdown, and the launcher points the operator at the artifacts.
    Since round 14 --trace no longer pins the python engine — this run
    rides the DEFAULT (native C++) engine's span source end-to-end."""
    import json

    trace_dir = tmp_path / "trace"
    res = _run_launcher(["-np", "2", "--trace", str(trace_dir),
                         sys.executable, "-c", SCRIPT])
    assert res.returncode == 0, res.stdout + res.stderr
    # The pin (and its stderr note) are gone: traced jobs keep the fast
    # path and the spans come from the engine the job actually selected.
    assert "HOROVOD_ENGINE=python" not in res.stderr
    assert "merged trace at" in res.stderr
    merged = trace_dir / "merged_trace.json"
    assert merged.exists(), res.stdout + res.stderr
    events = json.loads(merged.read_text())
    rows = {e["args"]["name"] for e in events
            if e.get("name") == "process_name"}
    assert rows >= {"rank 0", "rank 1"}
    report = json.loads((trace_dir / "straggler_report.json").read_text())
    assert report["collectives"] >= 1
    assert report["ranks"] == [0, 1]


def test_launch_failure_propagates():
    res = _run_launcher(
        ["-np", "2", sys.executable, "-c", "import sys; sys.exit(3)"])
    assert res.returncode == 3
    # Without --max-restarts there is no supervision: one attempt only.
    assert "restarting" not in res.stderr


def test_max_restarts_retries_until_success(tmp_path):
    """Supervision (elastic-lite): the job fails on restart epochs 0 and 1,
    succeeds on epoch 2; --max-restarts 3 must relaunch with
    HOROVOD_RESTART_EPOCH bumped each time and exit 0."""
    script = tmp_path / "flaky.py"
    script.write_text(
        "import os, sys\n"
        "epoch = int(os.environ['HOROVOD_RESTART_EPOCH'])\n"
        "print(f'attempt epoch={epoch}', flush=True)\n"
        "sys.exit(0 if epoch >= 2 else 17)\n")
    res = _run_launcher(["-np", "2", "--max-restarts", "3",
                         "--restart-backoff", "0.05",
                         sys.executable, str(script)])
    assert res.returncode == 0, res.stdout + res.stderr
    for epoch in (0, 1, 2):
        assert f"attempt epoch={epoch}" in res.stdout
    assert "restarting (attempt 1/3)" in res.stderr
    assert "restarting (attempt 2/3)" in res.stderr
    assert "HOROVOD_RESTART_EPOCH=2" in res.stderr


def test_max_restarts_exhausted_propagates_failure(tmp_path):
    script = tmp_path / "always_fails.py"
    script.write_text("import sys; sys.exit(9)\n")
    res = _run_launcher(["-np", "1", "--max-restarts", "1",
                         "--restart-backoff", "0.05",
                         sys.executable, str(script)])
    assert res.returncode == 9
    assert "restarting (attempt 1/1)" in res.stderr
    assert "giving up after 1 restart" in res.stderr


def test_restart_resumes_from_latest_checkpoint(tmp_path):
    """The restart-from-checkpoint contract end to end: epoch 0 saves
    ckpt_5 then crashes; epoch 1 resumes from it via restore_latest and
    finishes."""
    ckdir = tmp_path / "ckpts"
    script = tmp_path / "train.py"
    script.write_text(
        "import os, sys\n"
        "import numpy as np, jax.numpy as jnp\n"
        "import horovod_tpu as hvd\n"
        "from horovod_tpu.utils import (restart_epoch, restore_latest,\n"
        "                               save_checkpoint)\n"
        "hvd.init()\n"
        f"ckdir = {str(ckdir)!r}\n"
        "path, tree = restore_latest(ckdir, like={'step': jnp.zeros((), "
        "jnp.int32), 'w': jnp.zeros(4)})\n"
        "if tree is None:\n"
        "    assert restart_epoch() == 0\n"
        "    tree = {'step': jnp.int32(5), 'w': jnp.ones(4) * 2.5}\n"
        "    save_checkpoint(os.path.join(ckdir, 'ckpt_5'), tree)\n"
        "    sys.exit(13)  # simulated crash after the checkpoint\n"
        "assert restart_epoch() == 1, restart_epoch()\n"
        "assert int(tree['step']) == 5 and float(tree['w'][0]) == 2.5\n"
        "print(f'resumed step={int(tree[\"step\"])} "
        "epoch={restart_epoch()}', flush=True)\n"
        "hvd.shutdown()\n")
    res = _run_launcher(["-np", "1", "--max-restarts", "1",
                         "--restart-backoff", "0.05",
                         sys.executable, str(script)])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "resumed step=5 epoch=1" in res.stdout


def test_ssh_preflight_unreachable_host_fails_fast():
    from horovod_tpu.run.launch import ssh_preflight

    with pytest.raises(RuntimeError, match="ssh preflight failed"):
        ssh_preflight(["nonexistent-host-for-preflight-test.invalid"],
                      use_cache=False, timeout=3.0)


def test_ssh_preflight_cache(tmp_path, monkeypatch):
    import subprocess as sp

    from horovod_tpu.run import launch

    monkeypatch.setattr(launch, "_SSH_CACHE",
                        str(tmp_path / "ssh_cache.json"))
    calls = []

    def fake_run(cmd, **kwargs):
        calls.append(cmd)
        return sp.CompletedProcess(cmd, 0, stdout="", stderr="")

    monkeypatch.setattr(launch.subprocess, "run", fake_run)
    launch.ssh_preflight(["remote-a", "remote-b"])
    assert len(calls) == 2
    # Second launch within the TTL: cached, no ssh invocations.
    launch.ssh_preflight(["remote-a", "remote-b"])
    assert len(calls) == 2
    # Local hosts are never checked.
    launch.ssh_preflight(["localhost"])
    assert len(calls) == 2


def test_parse_hosts():
    from horovod_tpu.run import parse_hosts

    assert parse_hosts("a:2,b:2", 4) == [("a", 2), ("b", 2)]
    assert parse_hosts(None, 3) == [("localhost", 3)]
    with pytest.raises(ValueError, match="exceeds total slots"):
        parse_hosts("a:1", 2)


def test_nic_list_interfaces():
    from horovod_tpu.run.nic_discovery import list_interfaces
    pairs = list_interfaces()
    assert pairs, "must enumerate at least one IPv4 interface"
    for name, ip in pairs:
        assert ip.count(".") == 3
    # Loopback sorts last when a real NIC exists.
    if len(pairs) > 1:
        assert not pairs[0][1].startswith("127.")


def test_nic_ring_probe_three_hosts():
    """Three probe tasks stand in for three hosts (the reference test model:
    N ranks on one box). One of them runs through the ssh entry point
    (task_fn) as a real subprocess."""
    import threading

    from horovod_tpu.run.nic_discovery import (
        NICDriverService,
        run_probe_task,
    )

    driver = NICDriverService(3, timeout=60.0)
    addr = f"127.0.0.1:{driver.port}"
    results = {}

    def worker(i):
        results[i] = run_probe_task(i, addr)

    threads = [threading.Thread(target=worker, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    # Third task runs exactly as the launcher ships it to remote hosts:
    # the standalone script over stdin (`python -`), with NO repo on
    # PYTHONPATH — proving it needs no horovod_tpu install.
    import json

    import horovod_tpu.run.task_fn as task_fn_module
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    with open(task_fn_module.__file__) as script:
        proc = run_cmd(
            [sys.executable, "-", "2", addr],
            timeout=120, stdin=script, env=env)
    for t in threads:
        t.join(timeout=60)
    driver.close()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert set(results) == {0, 1}
    routable = results[0]["routable"]
    # Every "host" got an address its ring predecessor proved reachable.
    assert set(routable) == {0, 1, 2}
    # All tasks share one machine, so every interface worked on every link.
    assert results[0]["common_interfaces"]
    assert results[0] == results[1]
    # The standalone task prints the same answer as JSON on stdout.
    stdout_answer = json.loads(proc.stdout)
    assert stdout_answer["common_interfaces"] == \
        results[0]["common_interfaces"]


def test_nic_discovery_timeout_returns_error():
    from horovod_tpu.run.nic_discovery import NICDriverService, run_probe_task

    driver = NICDriverService(2, timeout=1.0)
    with pytest.raises(RuntimeError, match="registration timeout"):
        run_probe_task(0, f"127.0.0.1:{driver.port}")
    assert not driver.wait_done()
    driver.close()


def test_discover_routable_addrs_single_host_is_noop():
    from horovod_tpu.run.launch import discover_routable_addrs
    assert discover_routable_addrs(["localhost"], 22, "ab" * 32) is None


def test_version_flag():
    res = _run_launcher(["-v"])
    assert res.returncode == 0
    assert "horovod_tpu v" in res.stdout


def test_missing_np_still_errors():
    res = _run_launcher([sys.executable, "-c", "pass"])
    assert res.returncode != 0
    assert "-np" in res.stderr


def test_host_long_form_alias():
    # Reference spells the flag --host; both spellings must work.
    res = _run_launcher(["-np", "1", "--host", "localhost:1",
                         sys.executable, "-c", "print('ok-alias')"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ok-alias" in res.stdout

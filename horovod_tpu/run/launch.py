"""``horovodrun`` for TPU clusters.

Reference: ``horovod/run/run.py`` (489 lines) — parses ``-np``/``-H``, does an
ssh preflight, discovers routable NICs via driver/task TCP services, then
execs ``mpirun`` which fans out ranks via orted. On TPU none of the MPI
machinery exists; the launcher's jobs reduce to:

  1. mint a per-job HMAC secret and pick the coordinator address,
  2. for remote hosts: cached ssh preflight (reference ``run/run.py:46-102``),
  3. start one process per rank with the topology exported in env
     (``HOROVOD_RANK/SIZE/LOCAL_RANK/LOCAL_SIZE/CONTROLLER_ADDR/SECRET_KEY``),
  4. stream rank-prefixed output, propagate failures, kill stragglers.

Local ranks are direct children; remote hosts (``-H host:slots``) fan out
over ssh with the env inlined (the reference's ``-x VAR`` passthrough,
``run/run.py:462-480``). On a TPU pod slice you typically run one process
per host and let the SPMD tier drive all local chips.

A chip belongs to one process at a time, so every rank's relation to the
host's chips is decided here, never discovered by a failed init: a rank
that is alone on its host keeps the platform it inherited (it owns every
local chip); with ``--bind-chips`` local rank ``i`` owns exactly chip ``i``
(one-chip-per-process, the reference's one-GPU-per-rank model — see
:func:`chip_binding_env`); every other rank that shares a host is told it
owns none (``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..common import config as config_mod
from ..common.wire import make_secret
from .. import metrics

_m = None


def _launcher_metrics():
    global _m
    if _m is None:
        from types import SimpleNamespace

        _m = SimpleNamespace(restarts=metrics.counter(
            "hvd_launcher_restarts_total",
            "Supervised relaunches performed by horovodrun "
            "--max-restarts."))
    return _m


def parse_hosts(hosts: Optional[str], np_: int) -> List[Tuple[str, int]]:
    """Parse ``-H host1:2,host2:2`` (reference ``run/run.py:285-342``)."""
    if not hosts:
        return [("localhost", np_)]
    out = []
    for part in hosts.split(","):
        part = part.strip()
        if not part:
            continue
        host, _, slots = part.partition(":")
        out.append((host, int(slots) if slots else 1))
    total = sum(s for _, s in out)
    if total < np_:
        raise ValueError(
            f"-np {np_} exceeds total slots {total} in -H {hosts!r}")
    return out


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _derived_port(base: int, offset: int) -> int:
    """Map a base+offset onto a valid port regardless of where the ephemeral
    base landed (remote-host heuristic; override env vars if it clashes)."""
    return 20000 + (base + offset) % 40000


def _is_local(host: str) -> bool:
    return host in ("localhost", "127.0.0.1", socket.gethostname())


def chip_binding_env(local_rank: int) -> Dict[str, str]:
    """Environment that makes libtpu give this process exactly one chip,
    local chip ``local_rank``, as a complete one-chip topology of its own
    (no cross-process slice is formed: the ranks talk through the eager
    controller, not through ICI).

    Established on a four-chip v5e host with libtpu 0.0.34: naming the
    chip alone (``TPU_VISIBLE_CHIPS``, or the older ``TPU_VISIBLE_DEVICES``
    with ``TPU_PROCESS_BOUNDS``) lets one rank in and fails the other three
    on libtpu's multi-process lockfile; it is
    ``TPU_CHIPS_PER_PROCESS_BOUNDS`` that declares the process a subset of
    the host and allows several libtpu loads side by side."""
    return {
        "TPU_VISIBLE_CHIPS": str(local_rank),
        "TPU_VISIBLE_DEVICES": str(local_rank),    # the name before it
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def build_rank_env(base: Dict[str, str], rank: int, size: int,
                   local_rank: int, local_size: int, cross_rank: int,
                   cross_size: int, controller_addr: str, secret: str,
                   bind_chips: bool, spmd: bool = False,
                   restart_epoch: int = 0, elastic: bool = False,
                   min_ranks: int = 1, max_ranks: int = 0,
                   elastic_join: bool = False) -> Dict[str, str]:
    env = dict(base)
    env.update({
        "HOROVOD_RANK": str(rank),
        "HOROVOD_SIZE": str(size),
        "HOROVOD_LOCAL_RANK": str(local_rank),
        "HOROVOD_LOCAL_SIZE": str(local_size),
        "HOROVOD_CROSS_RANK": str(cross_rank),
        "HOROVOD_CROSS_SIZE": str(cross_size),
        "HOROVOD_SECRET_KEY": secret,
        # Supervision attempt number (--max-restarts): training scripts
        # key restart-vs-fresh on this (utils.checkpoint.restart_epoch()).
        "HOROVOD_RESTART_EPOCH": str(restart_epoch),
    })
    if elastic:
        # Elastic membership (docs/elastic.md): pin the python controller
        # engine (the ring data planes are fixed-membership) and scrub any
        # inherited ring endpoints so no rank tries to build one.
        env.update({
            "HOROVOD_ELASTIC": "1",
            "HOROVOD_ELASTIC_MIN_RANKS": str(min_ranks),
            "HOROVOD_ELASTIC_MAX_RANKS": str(max_ranks),
            "HOROVOD_ENGINE": "python",
        })
        for var in ("HOROVOD_RING_ADDRS", "HOROVOD_LOCAL_RING_ADDRS",
                    "HOROVOD_CROSS_RING_ADDRS"):
            env.pop(var, None)
        if elastic_join:
            env["HOROVOD_ELASTIC_JOIN"] = "1"
        else:
            # A fresh (rendezvous) rank must not inherit a stale join flag
            # from the launcher's own environment.
            env.pop("HOROVOD_ELASTIC_JOIN", None)
    else:
        env.pop("HOROVOD_ELASTIC", None)
        env.pop("HOROVOD_ELASTIC_JOIN", None)
    # Ranks we spawn watch their parent and die when orphaned (local: this
    # launcher; remote: the ssh session's shell). HOROVOD_PARENT_WATCHDOG=0
    # in the launcher's env opts out and is inherited via `base`.
    env.setdefault("HOROVOD_PARENT_WATCHDOG", "1")
    if spmd:
        # SPMD multi-host mode: ranks join the JAX distributed runtime and
        # every process sees the global device set; no eager controller.
        # Scrub any eager-tier endpoints inherited from the launcher's own
        # environment or the worker would also try to join a stale TCP ring.
        env.pop("HOROVOD_CONTROLLER_ADDR", None)
        env.pop("HOROVOD_RING_ADDRS", None)
        env.pop("HOROVOD_ENGINE", None)
        env["HOROVOD_SPMD_COORDINATOR"] = controller_addr
    else:
        env["HOROVOD_CONTROLLER_ADDR"] = controller_addr
    if bind_chips:
        env.update(chip_binding_env(local_rank))
    elif local_size > 1:
        # Several unbound ranks share this host: none of them may take the
        # chips (the first would win and the rest fail at init).
        env["JAX_PLATFORMS"] = "cpu"
    return env


_SSH_CACHE = os.path.expanduser("~/.horovod_tpu/ssh_preflight.json")
_SSH_CACHE_TTL_S = 300.0


def _boot_id() -> str:
    """Scope for on-disk monotonic stamps: CLOCK_MONOTONIC is only
    comparable within one boot, so the cache records which boot wrote it
    and entries from any other boot are discarded wholesale."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            return f.read().strip()
    except OSError:
        return "unknown-boot"


def ssh_preflight(hosts: List[str], ssh_port: int = 22,
                  use_cache: bool = True, timeout: float = 10.0) -> None:
    """Verify passwordless ssh to every remote host before fanning out
    (reference ``run/run.py:46-102``: threaded check with an on-disk cache
    so repeated launches skip it). Raises RuntimeError listing unreachable
    hosts; successes are cached for five minutes."""
    import json

    cache: Dict[str, float] = {}
    # Monotonic, not wall clock: an NTP step mid-TTL would expire (or
    # revive) entries spuriously. CLOCK_MONOTONIC is boot-relative and
    # only comparable within one boot, so the file carries the writing
    # boot's id and a mismatch discards it entirely (a pre-reboot stamp
    # can otherwise look in-TTL once uptime catches up). The 0 <= age
    # guard additionally drops stamps from the future within a boot.
    now = time.monotonic()
    boot = _boot_id()
    if use_cache:
        try:
            with open(_SSH_CACHE) as f:
                data = json.load(f)
            entries = (data.get("entries", {})
                       if data.get("boot_id") == boot else {})
            # Pre-boot_id cache files (a bare dict) hold wall-clock or
            # foreign-boot stamps: treat as empty, it's a 5-minute cache.
            cache = {h: t for h, t in entries.items()
                     if 0 <= now - t < _SSH_CACHE_TTL_S}
        except (OSError, ValueError, AttributeError):
            cache = {}

    # Cache key includes the port: success on 22 says nothing about 2222.
    def key(h):
        return f"{h}:{ssh_port}"

    to_check = [h for h in hosts if not _is_local(h) and key(h) not in cache]
    failures: Dict[str, str] = {}
    lock = threading.Lock()

    def check(host):
        try:
            res = subprocess.run(
                ["ssh", "-o", "StrictHostKeyChecking=no", "-o",
                 "BatchMode=yes", "-o", f"ConnectTimeout={int(timeout)}",
                 "-p", str(ssh_port), host, "true"],
                capture_output=True, text=True, timeout=timeout + 5)
            ok, msg = res.returncode == 0, (res.stderr or res.stdout).strip()
        except Exception as exc:  # missing ssh binary, subprocess timeout
            ok, msg = False, str(exc)
        with lock:
            if ok:
                cache[key(host)] = now
            else:
                failures[host] = msg

    # daemon=False on purpose: the preflight's join IS the launch gate.
    threads = [threading.Thread(target=check, args=(h,),
                                name=f"hvd-ssh-preflight-{h}", daemon=False)
               for h in to_check]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    if use_cache and cache:
        try:
            os.makedirs(os.path.dirname(_SSH_CACHE), exist_ok=True)
            with open(_SSH_CACHE, "w") as f:
                json.dump({"boot_id": boot, "entries": cache}, f)
        except OSError:
            pass
    if failures:
        detail = "; ".join(f"{h}: {msg or 'ssh failed'}"
                           for h, msg in sorted(failures.items()))
        raise RuntimeError(
            f"ssh preflight failed for {sorted(failures)} — passwordless "
            f"ssh is required for remote hosts ({detail})")


def discover_routable_addrs(hosts: List[str], ssh_port: int, secret: str,
                            timeout: float = 60.0) -> Optional[Dict[str, str]]:
    """Ring-probe every host's interfaces and return {host: routable_ip}
    (reference NIC discovery, ``run/run.py:105-256``): a probe task runs on
    each host (ssh for remote, a thread locally), dials every advertised
    interface of the next host, and the driver keeps, per host, an address
    its predecessor proved reachable. Returns None if discovery can't
    complete — callers fall back to the ``-H`` names."""
    from . import task_fn as task_fn_module
    from .nic_discovery import NICDriverService, list_interfaces, \
        run_probe_task

    if len(hosts) < 2:
        return None
    driver = NICDriverService(len(hosts), timeout=timeout)
    # Remote tasks dial every candidate concurrently; loopback is useless to
    # them (and could even connect to the WRONG host's bound port).
    candidates = [ip for _, ip in list_interfaces()
                  if not ip.startswith("127.")] \
        or [ip for _, ip in list_interfaces()]
    driver_addrs = ",".join(f"{ip}:{driver.port}" for ip in candidates)
    procs: List[Tuple[str, subprocess.Popen, List[str]]] = []
    threads: List[threading.Thread] = []
    thread_errors: List[str] = []
    try:
        for i, host in enumerate(hosts):
            if _is_local(host):
                def _local_probe(idx=i):
                    try:
                        run_probe_task(idx, f"127.0.0.1:{driver.port}")
                    except Exception as exc:  # checked by the poll loop
                        thread_errors.append(f"local probe {idx}: {exc}")

                t = threading.Thread(target=_local_probe,
                                     name=f"hvd-nic-probe-{i}", daemon=True)
                t.start()
                threads.append(t)
            else:
                # The standalone probe script rides ssh stdin (python -):
                # the remote host needs no horovod_tpu checkout and pays no
                # package import to enumerate its NICs.
                remote = (f"env HOROVOD_SECRET_KEY={shlex.quote(secret)} "
                          f"python3 - {i} {driver_addrs}")
                # close the script handle once Popen has dup'd it into the
                # child — otherwise one fd leaks per remote host per run.
                with open(task_fn_module.__file__) as script:
                    p = subprocess.Popen(
                        ["ssh", "-o", "StrictHostKeyChecking=no",
                         "-p", str(ssh_port), host, remote],
                        stdin=script,
                        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                        text=True)
                # Drain stderr continuously: a chatty remote interpreter
                # must not wedge on a full pipe mid-protocol.
                buf: List[str] = []
                threading.Thread(target=lambda p=p, b=buf: b.extend(
                    iter(p.stderr.readline, "")),
                    name=f"hvd-nic-stderr-{host}", daemon=True).start()
                procs.append((host, p, buf))
        # Poll instead of blocking: a probe that dies instantly (no remote
        # python3, auth failure) should fail the discovery now, with its
        # stderr, not after the full timeout.
        deadline = time.monotonic() + timeout
        while not driver.done():
            if thread_errors:
                sys.stderr.write(
                    f"horovodrun: NIC {thread_errors[0]}; falling back to "
                    "-H host names\n")
                return None
            for host, p, buf in procs:
                if p.poll() not in (None, 0):
                    err = "".join(buf).strip()
                    sys.stderr.write(
                        f"horovodrun: NIC probe on {host} exited with code "
                        f"{p.returncode}"
                        + (f": {err}" if err else "")
                        + "; falling back to -H host names\n")
                    return None
            if time.monotonic() > deadline:
                sys.stderr.write(
                    "horovodrun: NIC discovery timed out; falling back to "
                    "-H host names (override with --controller-addr / "
                    "HOROVOD_RING_ADDRS if unroutable)\n")
                return None
            time.sleep(0.1)
        routable = driver.routable_addrs()
        return {host: routable[i] for i, host in enumerate(hosts)
                if i in routable}
    finally:
        driver.close()
        for _, p, _ in procs:
            if p.poll() is None:
                p.terminate()


def _stream(prefix: str, pipe, out) -> None:
    for line in iter(pipe.readline, ""):
        out.write(f"{prefix}{line}")
        out.flush()
    pipe.close()


def run(args: argparse.Namespace) -> int:
    """Supervised launch: run the job, and on a non-zero exit tear it down,
    back off, and relaunch up to ``--max-restarts`` times with
    ``HOROVOD_RESTART_EPOCH`` bumped (elastic-lite: training scripts resume
    from their latest ``utils/checkpoint.py`` checkpoint — later Horovod
    solved this as Elastic Horovod; on TPU the supervisor restarts whole
    processes instead of rebuilding rings in place)."""
    max_restarts = getattr(args, "max_restarts", 0)
    backoff = max(0.0, getattr(args, "restart_backoff", 1.0))
    epoch = 0
    interrupted = threading.Event()

    def _exit(code: int) -> int:
        # The supervisor's registry/ring live in THIS process — no rank
        # ever exports them. A supervised run that restarted dumps its own
        # flight recorder so the restart history survives the terminal.
        # The launcher has no HOROVOD_RANK, so the dump lands on the bare
        # path (or a "{rank}" placeholder expands to "launcher") — never
        # clobbering a rank's postmortem.
        if epoch > 0:
            metrics.record_event("launcher_exit", exit_code=code,
                                 restarts=epoch)
            metrics.dump_flight_recorder("launcher_exit")
        return code

    while True:
        code = _run_attempt(args, restart_epoch=epoch,
                            interrupted=interrupted)
        if interrupted.is_set():
            # Operator-initiated teardown (SIGINT/SIGTERM) is not a fault;
            # never auto-restart over the operator's intent.
            return _exit(_finish_trace(args, code))
        if code == 0 or epoch >= max_restarts:
            if code != 0 and max_restarts > 0:
                sys.stderr.write(
                    f"horovodrun: giving up after {epoch} restart(s); "
                    f"final exit code {code}\n")
            return _exit(_finish_trace(args, code))
        epoch += 1
        delay = min(30.0, backoff * (2.0 ** (epoch - 1)))
        sys.stderr.write(
            f"horovodrun: job failed with exit code {code}; restarting "
            f"(attempt {epoch}/{max_restarts}) in {delay:.1f}s with "
            f"HOROVOD_RESTART_EPOCH={epoch}\n")
        # Event.wait, not time.sleep: a SIGINT during the backoff (the
        # still-installed handler sets `interrupted`) must cancel the
        # relaunch, not schedule one more multi-hour attempt.
        if interrupted.wait(delay):
            epoch -= 1  # cancelled during backoff: this restart never ran
            return _exit(_finish_trace(args, code))
        # Counted only once the backoff survives: a restart that was
        # cancelled mid-backoff must not appear in the restart history.
        if metrics.on():
            _launcher_metrics().restarts.inc()
            metrics.record_event("launcher_restart", epoch=epoch,
                                 exit_code=code)


def _finish_trace(args: argparse.Namespace, code: int) -> int:
    """Post-run trace hook for ``--trace``: rank 0 already merged on a
    clean shutdown; after a crash (or a kill) the per-rank files are
    still on disk, so merge whatever exists and point the operator at
    the artifacts either way. Never changes the exit code."""
    trace_dir = getattr(args, "trace", None)
    if not trace_dir:
        return code
    try:
        from .. import trace as trace_mod

        merged = os.path.join(trace_dir, trace_mod.MERGED_TRACE_FILE)
        report = os.path.join(trace_dir, trace_mod.REPORT_FILE)
        if not os.path.exists(merged):
            if not trace_mod.rank_trace_files(trace_dir):
                sys.stderr.write(
                    f"horovodrun: no per-rank traces under {trace_dir} to "
                    "merge\n")
                return code
            trace_mod.merge_trace_dir(trace_dir)
        if not os.path.exists(report):
            trace_mod.write_report(trace_dir, feed=False)
        sys.stderr.write(
            f"horovodrun: merged trace at {merged}; straggler report at "
            f"{report}\n")
    except Exception as exc:  # tracing must never fail the launch result
        sys.stderr.write(f"horovodrun: trace merge failed: {exc} "
                         "(retry with python -m horovod_tpu.tools."
                         f"straggler {trace_dir})\n")
    return code


def _run_attempt(args: argparse.Namespace, restart_epoch: int = 0,
                 interrupted: Optional[threading.Event] = None) -> int:
    hosts = parse_hosts(args.hosts, args.np)
    if getattr(args, "trace", None):
        # Cluster tracing (docs/tracing.md): every rank writes spans under
        # the shared dir; rank 0 merges at shutdown. BOTH eager engines
        # emit the same fixed phase vocabulary now — the native C++
        # engine stamps spans into its C ring and the controller drains
        # them (round 14) — so --trace no longer pins
        # HOROVOD_ENGINE=python; traced jobs keep the fast path.
        os.makedirs(args.trace, exist_ok=True)
        os.environ["HOROVOD_TRACE_DIR"] = args.trace
        if args.spmd:
            # Say so NOW, not via an empty directory at exit: spans come
            # from the eager controllers, not the SPMD tier.
            sys.stderr.write(
                "horovodrun: WARNING --trace has no span source under "
                "--spmd — collective spans come from the eager controller "
                "engines; expect no trace.rank*.json files "
                "(docs/tracing.md)\n")
    size = args.np
    secret = config_mod.secret_key_hex() or make_secret()
    coord_host = hosts[0][0]
    any_remote_host = any(not _is_local(h) for h, _ in hosts)
    host_ip: Dict[str, str] = {}
    if any_remote_host:
        ssh_preflight([h for h, _ in hosts], ssh_port=args.ssh_port,
                      use_cache=not args.disable_cache)
        # Skip the ring-probe only when every consumer of its result is
        # already overridden: the coordinator address explicitly, and the
        # ring addresses either absent entirely (SPMD mode) or explicitly —
        # including the hierarchical rings when those are requested.
        from ..common.config import _env_bool
        hier_requested = (_env_bool("HOROVOD_HIERARCHICAL_ALLREDUCE")
                          or _env_bool("HOROVOD_HIERARCHICAL_ALLGATHER"))
        hier_overridden = ("HOROVOD_LOCAL_RING_ADDRS" in os.environ
                           and "HOROVOD_CROSS_RING_ADDRS" in os.environ)
        all_overridden = bool(args.controller_addr) and (
            args.spmd or ("HOROVOD_RING_ADDRS" in os.environ
                          and (not hier_requested or hier_overridden)))
        if not args.disable_nic_discovery and not all_overridden:
            # Probe tasks and the driver authenticate with the job secret.
            os.environ["HOROVOD_SECRET_KEY"] = secret
            host_ip = discover_routable_addrs(
                [h for h, _ in hosts], args.ssh_port, secret) or {}
    def _public_host(host: str) -> str:
        """Address other hosts should dial for `host`: the ring-probed
        routable IP when discovery ran, else the -H name; local entries in
        mixed jobs need a reachable name, not loopback."""
        if _is_local(host):
            return (host_ip.get(host) or socket.gethostname()
                    if any_remote_host else "127.0.0.1")
        return host_ip.get(host, host)

    coord_host = _public_host(coord_host)
    coord_addr = args.controller_addr or f"{coord_host}:{_free_port()}"

    assignments = []  # (rank, host, local_rank, local_size, cross_rank)
    rank = 0
    for cross_rank, (host, slots) in enumerate(hosts):
        local = min(slots, size - rank)
        for lr in range(local):
            assignments.append((rank, host, lr, local, cross_rank))
            rank += 1
        if rank >= size:
            break

    # Telemetry endpoints: each rank serves /metrics at base + rank
    # (common/basics.py). Print the resolved URLs so operators never
    # compute the port offset by hand; rank 0's endpoint additionally
    # aggregates every worker's piggybacked snapshot (rank-labeled).
    metrics_base = config_mod.env_str("HOROVOD_METRICS_PORT")
    if metrics_base:
        try:
            base_port = int(metrics_base)
        except ValueError:
            base_port = 0
        if base_port > 0:
            for r, host, _, _, _ in assignments:
                sys.stderr.write(
                    f"horovodrun: rank {r} metrics at "
                    f"http://{_public_host(host)}:{base_port + r}/metrics\n")
            if args.verbose:
                sys.stderr.write(
                    "horovodrun: cluster view (every rank's series, "
                    "rank-labeled) at http://"
                    f"{_public_host(assignments[0][1])}:{base_port}"
                    "/metrics\n")
        else:
            sys.stderr.write(
                "horovodrun: ignoring unparseable HOROVOD_METRICS_PORT="
                f"{metrics_base!r}; metrics endpoints disabled\n")

    # Per-rank addresses for the native C++ ring data plane (eager tier only;
    # SPMD workers have no ring). Local-only jobs bind loopback with
    # verified-free ports; with remote hosts in play the local entries must
    # be reachable, so use the hostname and a common base port on remote
    # machines (override via HOROVOD_RING_ADDRS if the heuristic clashes).
    elastic = getattr(args, "elastic", False)
    ring_addrs_env = None
    if not args.spmd and not elastic:
        ring_base = _free_port()
        ring_addrs = []
        for r, host, _, _, _ in assignments:
            if _is_local(host):
                ring_addrs.append(f"{_public_host(host)}:{_free_port()}")
            else:
                ring_addrs.append(
                    f"{_public_host(host)}:{_derived_port(ring_base, r)}")
        ring_addrs_env = config_mod.ring_addrs() or ",".join(ring_addrs)

    # Per-group ring addresses for the two-level (hierarchical) data plane
    # (HOROVOD_HIERARCHICAL_ALLREDUCE/ALLGATHER): one ring inside each host
    # entry plus a ring of the entries' first ranks, so the flags can simply
    # be flipped on the training command. Exported only for homogeneous
    # layouts (every populated group the same size, >1): with mixed group
    # sizes the per-rank gate and count math would diverge across ranks and
    # the lockstep data phases would deadlock — those layouts stay on the
    # flat ring (the reference's homogeneity check serves the same purpose,
    # operations.cc:936-952).
    local_ring_by_rank: Dict[int, str] = {}
    cross_ring_env = None
    groups: Dict[int, list] = {}
    for a in assignments:
        groups.setdefault(a[4], []).append(a)
    group_sizes = {len(m) for m in groups.values()}
    if not args.spmd and not elastic and len(groups) > 1 and \
            group_sizes.issubset({
            max(group_sizes)}) and max(group_sizes) > 1:
        # Remote ports share ring_base with the flat ring, in disjoint
        # offset bands — flat [0, size), local [size, 2*size), cross
        # [2*size, 3*size) — so two rings can never be told to bind the
        # same port on one host.

        def _group_addr(host, offset):
            if _is_local(host):
                return f"{_public_host(host)}:{_free_port()}"
            return f"{_public_host(host)}:{_derived_port(ring_base, offset)}"

        cross_addrs = []
        for cr in sorted(groups):
            members = groups[cr]
            addrs = [_group_addr(host, size + r)
                     for r, host, _, _, _ in members]
            for r, _, _, _, _ in members:
                local_ring_by_rank[r] = ",".join(addrs)
            root_r, root_host = members[0][0], members[0][1]
            cross_addrs.append(_group_addr(root_host, 2 * size + root_r))
        cross_ring_env = ",".join(cross_addrs)
        if ("HOROVOD_LOCAL_RING_ADDRS" in os.environ) != \
                ("HOROVOD_CROSS_RING_ADDRS" in os.environ):
            sys.stderr.write(
                "horovodrun: only one of HOROVOD_LOCAL_RING_ADDRS/"
                "HOROVOD_CROSS_RING_ADDRS is set; ignoring it in favor of "
                "the launcher-computed hierarchical rings (set both to "
                "override)\n")

    procs: List[subprocess.Popen] = []
    threads = []
    failed = threading.Event()

    def spawn(rank, host, local_rank, local_size, cross_rank, join=False):
        # cross_size counts POPULATED groups: with -np smaller than the total
        # slots, trailing -H entries receive no ranks and must not count.
        env = build_rank_env(
            dict(os.environ), rank, size, local_rank, local_size,
            cross_rank, len(groups), coord_addr, secret, args.bind_chips,
            spmd=args.spmd, restart_epoch=restart_epoch, elastic=elastic,
            min_ranks=getattr(args, "min_ranks", 1),
            max_ranks=getattr(args, "max_ranks", 0), elastic_join=join)
        env["HOROVOD_START_TIMEOUT"] = str(args.start_timeout)
        if not args.spmd and not elastic:
            env["HOROVOD_RING_ADDRS"] = ring_addrs_env
            # A complete user-set hierarchical pair wins (build_rank_env
            # already inherited it); anything less gets the computed pair —
            # the two consumers (controller and native engine) require both,
            # so a half-set pair would silently fall back to the flat ring.
            if rank in local_ring_by_rank and cross_ring_env and \
                    not ("HOROVOD_LOCAL_RING_ADDRS" in os.environ
                         and "HOROVOD_CROSS_RING_ADDRS" in os.environ):
                env["HOROVOD_LOCAL_RING_ADDRS"] = local_ring_by_rank[rank]
                env["HOROVOD_CROSS_RING_ADDRS"] = cross_ring_env
        if _is_local(host):
            cmd = args.command
        else:
            # ssh fan-out with env inlined (reference run/run.py:462-485 via
            # mpirun -x; no orted here — ranks connect straight back to the
            # coordinator's TCP service).
            exports = " ".join(
                f"{k}={shlex.quote(v)}" for k, v in env.items()
                if k.startswith(("HOROVOD_", "TPU_", "JAX_", "PYTHONPATH")))
            remote = f"cd {shlex.quote(os.getcwd())} && env {exports} " + \
                " ".join(shlex.quote(c) for c in args.command)
            cmd = ["ssh", "-o", "StrictHostKeyChecking=no",
                   "-p", str(args.ssh_port), host, remote]
            env = dict(os.environ)
        proc = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, bufsize=1)
        procs.append(proc)
        t = threading.Thread(
            target=_stream, args=(f"[{rank}]: " if size > 1 else "",
                                  proc.stdout, sys.stdout),
            name=f"hvd-rank-stream-{rank}", daemon=True)
        t.start()
        threads.append(t)
        return proc

    for a in assignments:
        spawn(*a)

    def _terminate_all(signum=None, frame=None):
        if signum is not None and interrupted is not None:
            interrupted.set()  # operator signal: suppress supervised restart
        for p in procs:
            if p.poll() is None:
                p.terminate()

    signal.signal(signal.SIGINT, _terminate_all)
    signal.signal(signal.SIGTERM, _terminate_all)

    exit_code = 0
    assignment_by_rank = {a[0]: a for a in assignments}
    # Elastic (docs/elastic.md): a dead WORKER is respawned individually as
    # a joiner (the coordinator admits it at the next epoch boundary) up to
    # --elastic-respawns times per slot, instead of the whole job being
    # torn down; the job ends when the coordinator's process does.
    respawns_left = {a[0]: getattr(args, "elastic_respawns", 0)
                     for a in assignments if a[0] != 0}
    try:
        pending = [(a[0], procs[i]) for i, a in enumerate(assignments)]
        done = False
        while pending and not done:
            for rank_id, p in list(pending):
                rc = p.poll()
                if rc is None:
                    continue
                pending.remove((rank_id, p))
                if not elastic:
                    if rc != 0 and exit_code == 0:
                        exit_code = rc
                        sys.stderr.write(
                            f"horovodrun: rank {rank_id} exited with code "
                            f"{rc}; terminating remaining ranks\n")
                        failed.set()
                        _terminate_all()
                    continue
                if rank_id == 0:
                    # The coordinator IS the job in elastic mode: its exit
                    # (clean or not) ends the run; lingering workers and
                    # half-admitted joiners are torn down with it.
                    exit_code = rc
                    done = True
                    _terminate_all()
                    break
                if rc == 0 or interrupted is not None and interrupted.is_set():
                    continue  # graceful leave / operator teardown: no respawn
                if respawns_left.get(rank_id, 0) > 0:
                    respawns_left[rank_id] -= 1
                    sys.stderr.write(
                        f"horovodrun: rank {rank_id} exited with code {rc}; "
                        "respawning its slot as an elastic joiner "
                        f"({respawns_left[rank_id]} respawn(s) left)\n")
                    pending.append((
                        rank_id, spawn(*assignment_by_rank[rank_id],
                                       join=True)))
                else:
                    sys.stderr.write(
                        f"horovodrun: rank {rank_id} exited with code {rc}; "
                        "elastic respawn budget exhausted — continuing with "
                        "the survivors\n")
            if pending and not done:
                time.sleep(0.05)
    finally:
        _terminate_all()
        for t in threads:
            t.join(timeout=2.0)
    return exit_code


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="horovodrun",
        description="Launch a horovod_tpu job (TPU-native horovodrun: no "
                    "mpirun, no ssh preflight for local jobs).")
    from .. import __version__

    parser.add_argument("-np", "--num-proc", dest="np", type=int,
                        required=True,
                        help="total number of processes (ranks)")
    # argparse's version action exits during parse, before required-arg
    # validation, so plain `horovodrun -v` works (reference horovodrun -v).
    parser.add_argument("-v", "--version", action="version",
                        version=f"horovod_tpu v{__version__}")
    parser.add_argument("-H", "--hosts", "--host", default=None,
                        help="host1:slots,host2:slots (default: all local)")
    parser.add_argument("--controller-addr", default=None,
                        help="coordinator bind address host:port "
                             "(default: auto on rank-0 host)")
    parser.add_argument("--bind-chips", action="store_true",
                        help="give local rank i exactly local TPU chip i "
                             "(one-chip-per-rank model); without it, ranks "
                             "that share a host run with JAX_PLATFORMS=cpu")
    parser.add_argument("--spmd", action="store_true",
                        help="SPMD multi-host mode: ranks join the JAX "
                             "distributed runtime (one process per host, "
                             "global mesh over all chips); collectives run "
                             "inside jit over ICI/DCN instead of the eager "
                             "controller")
    parser.add_argument("-p", "--ssh-port", type=int, default=22,
                        help="ssh port for remote hosts (reference "
                             "horovodrun -p)")
    parser.add_argument("--start-timeout", type=int, default=600,
                        help="seconds to wait for all ranks to start and "
                             "rendezvous before aborting (reference "
                             "horovodrun --start-timeout)")
    parser.add_argument("--elastic", action="store_true",
                        help="elastic membership (docs/elastic.md): a dead "
                             "rank re-forms the job with the survivors at a "
                             "bumped membership epoch instead of aborting "
                             "it, dead worker slots are respawned "
                             "individually as joiners, and late workers "
                             "are admitted at epoch boundaries; pins the "
                             "python controller engine")
    parser.add_argument("--min-ranks", type=int, default=1,
                        help="elastic: abort (like a static job) if a "
                             "reshape would drop below this world size "
                             "(default 1)")
    parser.add_argument("--max-ranks", type=int, default=0,
                        help="elastic: park joiners beyond this world size "
                             "until a slot frees (default 0 = unbounded)")
    parser.add_argument("--elastic-respawns", type=int, default=3,
                        help="elastic: times each dead worker slot is "
                             "respawned as a joiner before the job simply "
                             "continues with the survivors (default 3)")
    parser.add_argument("--max-restarts", type=int, default=0,
                        help="on a non-zero rank exit, tear the job down "
                             "and relaunch up to N times with exponential "
                             "backoff and HOROVOD_RESTART_EPOCH bumped; "
                             "training scripts resume from their latest "
                             "checkpoint (elastic-lite; default 0 = no "
                             "restarts)")
    parser.add_argument("--restart-backoff", type=float, default=1.0,
                        help="base seconds for the exponential restart "
                             "backoff (doubles per restart, capped at 30s)")
    parser.add_argument("--trace", metavar="DIR", default=None,
                        help="cluster-wide distributed tracing: every rank "
                             "writes clock-anchored phase spans under DIR "
                             "(HOROVOD_TRACE_DIR) — under either eager "
                             "engine, native included; rank 0 merges them "
                             "into DIR/merged_trace.json with a straggler "
                             "report at shutdown (docs/tracing.md)")
    parser.add_argument("--disable-cache", action="store_true",
                        help="skip the ssh-preflight result cache "
                             "(reference horovodrun --disable-cache)")
    parser.add_argument("--disable-nic-discovery", action="store_true",
                        help="skip the interface ring-probe on multi-host "
                             "launches and dial the -H names directly")
    parser.add_argument("--verbose", action="store_true")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="training command")
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")
    if args.spmd and args.bind_chips:
        parser.error("--spmd and --bind-chips conflict: SPMD mode needs "
                     "every process to see all its host's chips")
    if args.spmd and args.elastic:
        parser.error("--spmd and --elastic conflict: the JAX distributed "
                     "runtime is a static world; elastic membership lives "
                     "in the eager controller tier")
    if args.elastic and args.min_ranks > args.np:
        parser.error(f"--min-ranks {args.min_ranks} exceeds -np {args.np}")
    if args.command[0] == "--":
        args.command = args.command[1:]
    return run(args)


if __name__ == "__main__":
    sys.exit(main())

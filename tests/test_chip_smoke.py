"""The chip bring-up contract, as far as a CPU box can hold it: the smoke
refuses to run without a TPU, the compile cache can be placed from outside
and is otherwise one fixed in-checkout path, backend acquisition failures
raise instead of being swallowed, the Pallas kernels refuse an unknown
backend instead of silently interpreting, and the launcher tells every
rank which devices are its own."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(code_or_args, env_extra=None, env_drop=()):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    for name in env_drop:
        env.pop(name, None)
    env.update(env_extra or {})
    args = (code_or_args if isinstance(code_or_args, list)
            else ["-c", code_or_args])
    return subprocess.Popen([sys.executable] + args, env=env, cwd=REPO,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


def _finish(proc):
    out, err = proc.communicate(timeout=120)
    return subprocess.CompletedProcess(proc.args, proc.returncode, out, err)


def _run(*args, **kwargs):
    return _finish(_spawn(*args, **kwargs))


def test_default_smoke_refuses_cpu_and_names_it():
    res = _run([os.path.join(REPO, "chip_smoke.py")])
    assert res.returncode != 0
    assert "needs platform 'tpu'" in res.stderr and "'cpu'" in res.stderr
    # No result line: nothing on stdout parses as the summary.
    assert '"ok"' not in res.stdout


_CACHE_CODE = (
    "import jax\n"
    "from horovod_tpu.utils import compile_cache\n"
    "print(compile_cache.enable())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")


# Parents that start chip-holding children import these; none may take
# the chip itself.
_IMPORT_CODE = (
    "import horovod_tpu, horovod_tpu.run.launch, bench, __graft_entry__\n"
    "from jax._src import xla_bridge\n"
    "print(xla_bridge.backends_are_initialized())\n")


def test_compile_cache_left_alone_when_placed_from_outside(tmp_path):
    placed = str(tmp_path / "placed")
    res = _run(_CACHE_CODE + _IMPORT_CODE,
               {"JAX_COMPILATION_CACHE_DIR": placed})
    assert res.returncode == 0, res.stderr
    returned, configured, min_secs, backends_up = res.stdout.split()
    # jax read the variable itself; the helper set nothing.
    assert returned == configured == placed
    assert float(min_secs) == 1.0           # jax's own default, untouched
    # ...and neither the helper nor importing the package, the launcher,
    # bench or the graft entry initialized a backend.
    assert backends_up == "False"


def test_compile_cache_default_is_one_fixed_in_checkout_path():
    procs = [_spawn(_CACHE_CODE, env_drop=("JAX_COMPILATION_CACHE_DIR",))
             for _ in range(2)]
    runs = [_finish(p) for p in procs]
    for res in runs:
        assert res.returncode == 0, res.stderr
    first, second = (res.stdout.split() for res in runs)
    assert first == second                  # two processes, one path
    returned, configured, min_secs = first
    assert returned == configured == os.path.join(REPO, ".jax_cache")
    assert float(min_secs) == 0.0           # every program is kept


def test_init_raises_when_backend_acquisition_fails(monkeypatch):
    """A real (non-injected) backend failure propagates out of hvd.init():
    no CPU fallback, no carrying on with zero devices."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.common import retry

    def boom():
        raise RuntimeError("libtpu: no chip for you")

    monkeypatch.setattr(jax, "local_device_count", boom)
    monkeypatch.setenv("HOROVOD_TPU_INIT_RETRIES", "2")
    monkeypatch.setenv("HOROVOD_TPU_INIT_BACKOFF", "0")
    with pytest.raises(retry.RetryError, match="no chip for you"):
        hvd.init()
    assert not hvd.is_initialized()


def test_auto_interpret_only_knows_tpu_and_cpu(monkeypatch):
    import jax

    from horovod_tpu.ops import attention

    assert attention._auto_interpret() is True      # this suite: cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention._auto_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        attention._auto_interpret()


def test_launcher_decides_every_ranks_devices():
    from horovod_tpu.run.launch import build_rank_env

    def env(local_rank, local_size, bind):
        return build_rank_env(
            {}, rank=local_rank, size=local_size, local_rank=local_rank,
            local_size=local_size, cross_rank=0, cross_size=1,
            controller_addr="127.0.0.1:1", secret="ab", bind_chips=bind)

    # Alone on its host: owns every chip, platform untouched.
    alone = env(0, 1, False)
    assert "JAX_PLATFORMS" not in alone and "TPU_VISIBLE_CHIPS" not in alone
    # Sharing a host unbound: told it owns none.
    assert env(1, 2, False)["JAX_PLATFORMS"] == "cpu"
    # Bound: exactly one chip each, each process a one-chip subset of the
    # host (without the per-process bounds libtpu admits only one rank).
    bound = [env(i, 4, True) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in bound] == ["0", "1", "2", "3"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_PROCESS_BOUNDS"] == "1,1,1"
               and "JAX_PLATFORMS" not in e for e in bound)

"""The chip bring-up contract, as far as a CPU box can hold it: the smoke
refuses to run without a TPU, the compile cache can be placed from outside
and is otherwise one fixed in-checkout path, backend acquisition failures
raise instead of being swallowed, the Pallas kernels refuse an unknown
backend instead of silently interpreting, and the launcher tells every
rank which devices are its own."""

import functools
import importlib.util
import os
import re
import subprocess
import sys

import pytest

from mp_harness import spawn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(code_or_args, env_extra=None, env_drop=()):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    for name in env_drop:
        env.pop(name, None)
    env.update(env_extra or {})
    args = (code_or_args if isinstance(code_or_args, list)
            else ["-c", code_or_args])
    return spawn([sys.executable] + args, env=env, cwd=REPO,
                 stderr=subprocess.PIPE)


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
    "import horovod_tpu, horovod_tpu.run.launch, __graft_entry__\n"
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
    # ...and neither the helper nor importing the package, the launcher
    # or the graft entry initialized a backend.
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


# The smoke's ResNet-50 step (``chip_smoke.build_resnet50_step``), lowered
# and not compiled or run, on a mesh of 1 and of 4 virtual CPU devices at
# the rehearsal's sizes: what the smoke asserts on the chip about placement
# and about the exchange, as far as the lowered program shows it.
@functools.lru_cache(maxsize=None)
def _lowered_resnet_step(n):
    import jax

    from horovod_tpu.parallel import make_mesh, set_mesh

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    set_mesh(make_mesh(devices=jax.devices()[:n]))
    step, state, (x, y), mesh = chip_smoke.build_resnet50_step(2, 32)
    text = step.lower(*state, x, y).as_text(debug_info=True)
    return text, state, (x, y), mesh


# Both meshes in tier-1 since the smoke draws its weights in one program
# (PR 45): 12 s a mesh, where the eager ResNet-50 init took 65.
@pytest.mark.parametrize("n", [1, 4])
def test_resnet_step_is_placed_as_the_smoke_asserts(n):
    import jax
    from jax.sharding import PartitionSpec as P

    _, state, (x, y), mesh = _lowered_resnet_step(n)
    devices = set(mesh.devices.flat)
    assert mesh.axis_names == ("data",) and len(devices) == n
    for leaf in jax.tree.leaves(state):
        assert leaf.sharding.spec == P()
        assert leaf.sharding.device_set == devices
    for batch in (x, y):
        assert batch.sharding.spec == P("data")
        shards = batch.addressable_shards
        assert len({s.device for s in shards}) == n
        assert len({str(s.index) for s in shards}) == n
        assert all(s.data.shape[0] * n == batch.shape[0] for s in shards)


@pytest.mark.parametrize("n,group", [
    (1, "dense<0> : tensor<1x1xi64>"), (4, "dense<[[0, 1, 2, 3]]>")])
def test_resnet_step_lowers_with_its_exchange(n, group):
    import jax

    text, state, _, _ = _lowered_resnet_step(n)
    # One all-reduce a gradient leaf, over every device of the mesh, each
    # named from inside the optimizer's ``hvd.exchange`` scope.
    leaves = len(jax.tree.leaves(state[0]))
    reduces = re.findall(r'"stablehlo\.all_reduce"[^\n]*', text)
    assert len(reduces) == leaves
    assert all(f"replica_groups = {group}" in line for line in reduces)
    scoped = re.findall(
        r'loc\("(?:[^"]*/)?hvd\.exchange/hvd\.allreduce\.[^"/]*/psum"', text)
    assert len(scoped) == leaves

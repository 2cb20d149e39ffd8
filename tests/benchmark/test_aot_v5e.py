"""Every cell's step compiles for a described TPU v5e 2x2 host at its real
size, with the Pallas kernels compiled by Mosaic: what the chip's compiler
would refuse costs no chip time here. Nothing runs, so nothing here is a
measurement.

One file, the topology described inside a module-scoped fixture (never at
import), no child process, the compile cache off around it: see the
on-chip-measurement guide, section 2."""

import numpy as np
import pytest

from harness import hlo_text, manifest

HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(cell_name, topo, monkeypatch):
    from jax.sharding import Mesh

    import horovod_tpu.ops.attention as attention

    # On the CPU backend the program would interpret its kernels; the step
    # is compiled for the chip, so steer it to the Mosaic branch here.
    monkeypatch.setattr(attention, "_auto_interpret", lambda: False)
    cell = manifest.Cell(cell_name)
    builder = manifest.load_module("builders", cell.config["builder"])
    mesh = Mesh(np.array(topo.devices[:cell.chips]), ("data",))
    bench = builder.build(cell.config, cell.traffic, mesh)
    return bench.step.lower(*bench.arg_shapes()).compile()


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("cell,mosaic_calls,all_reduces", [
    ("resnet50-dp1", 0, []),
    ("bert-base-s512-dp1", 36, []),
    ("resnet50-dp4", 0, [(102_212_768, 4)]),
    ("bert-base-s128-dp1", 0, []),
])
def test_step_compiles_for_v5e(cell, mosaic_calls, all_reduces, topo,
                               no_compile_cache, monkeypatch):
    compiled = _compile(cell, topo, monkeypatch)
    text = compiled.as_text()
    assert hlo_text.mosaic_calls(text) == mosaic_calls
    found = [(c.payload_bytes, c.group_size)
             for c in hlo_text.collectives(text) if c.op == "all-reduce"]
    assert found == all_reduces
    assert _device_bytes(compiled) < HBM_BYTES

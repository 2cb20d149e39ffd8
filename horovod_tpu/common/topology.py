"""Process/device topology discovery — the TPU-native replacement for MPI
communicator setup.

The reference derives rank/size from ``MPI_Comm_rank/size``, local rank from an
``MPI_Comm_split_type(SHARED)`` node communicator, and cross rank from an
``MPI_Comm_split(local_rank)`` (reference ``horovod/common/operations.cc:890-959``).
On TPU there is no mpirun: topology comes from the TPU runtime / JAX process
model, or from environment variables set by our launcher (``horovodrun``).

Precedence:
  1. ``HOROVOD_RANK``/``HOROVOD_SIZE`` (+``_LOCAL_RANK``/``_LOCAL_SIZE``) —
     set by our launcher; also accepts OpenMPI's ``OMPI_COMM_WORLD_*`` names
     for drop-in compatibility (the reference's tests read those,
     ``test/common.py:25-58``).
  2. JAX multi-host runtime: ``jax.process_index()`` / ``jax.process_count()``
     (one process per TPU host, the idiomatic pod-slice model).
  3. Single-process default: rank 0 of 1.

Note on semantics: a Horovod "rank" is one *process*. The reference runs one
process per GPU so rank==device; on TPU one process drives several chips and
intra-process data parallelism is expressed over the device mesh (see
``horovod_tpu.parallel``). ``num_devices``/``local_devices`` expose chip-level
topology alongside the process-level rank/size.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

from .config import env_str


def _first_env_int(names: Sequence[str]) -> Optional[int]:
    for name in names:
        val = env_str(name)
        if val is not None and val.strip():
            try:
                return int(val)
            except ValueError:
                pass
    return None


@dataclasses.dataclass(frozen=True)
class Topology:
    """Immutable view of the job topology, fixed at ``hvd.init()``.

    Mirrors the rank/size/local/cross ints kept in the reference's
    ``HorovodGlobalState`` (``horovod/common/global_state.h:60-75``).
    """

    rank: int
    size: int
    local_rank: int
    local_size: int
    cross_rank: int
    cross_size: int
    # Chip-level topology (TPU-only extension; 0 devices possible under
    # pure-CPU tests before JAX is imported).
    num_devices: int = 0
    local_num_devices: int = 0
    is_homogeneous: bool = True

    def validate(self) -> None:
        if not (0 <= self.rank < self.size):
            raise ValueError(f"rank {self.rank} out of range for size {self.size}")
        if not (0 <= self.local_rank < self.local_size):
            raise ValueError(
                f"local_rank {self.local_rank} out of range for local_size {self.local_size}"
            )


def _device_counts() -> Tuple[int, int]:
    """Total and per-process accelerator device counts from JAX, if importable."""
    try:
        import jax

        return jax.device_count(), jax.local_device_count()
    except Exception:  # pragma: no cover - jax always present in this image
        return 0, 0


def detect(ranks: Optional[Sequence[int]] = None) -> Topology:
    """Discover topology. ``ranks`` narrows the job to a subset, mirroring
    ``hvd.init(ranks)`` in the reference (``horovod/common/basics.py:29-55``).
    """
    rank = _first_env_int(["HOROVOD_RANK", "OMPI_COMM_WORLD_RANK", "PMI_RANK"])
    size = _first_env_int(["HOROVOD_SIZE", "OMPI_COMM_WORLD_SIZE", "PMI_SIZE"])
    if (rank is None) != (size is None):
        # Half-set launcher env is a misconfiguration, not a fallback case:
        # silently training as rank 0 of 1 on every host corrupts results.
        raise RuntimeError(
            "partially-set launcher environment: exactly one of rank/size is "
            f"present (rank={rank}, size={size}); set both HOROVOD_RANK and "
            "HOROVOD_SIZE (or neither, to use the JAX process model)")

    num_devices, local_num_devices = _device_counts()

    if rank is None:
        # No launcher env: fall back to the JAX process model.
        try:
            import jax

            rank = jax.process_index()
            size = jax.process_count()
        except Exception:  # pragma: no cover
            rank, size = 0, 1

    local_rank = _first_env_int(
        ["HOROVOD_LOCAL_RANK", "OMPI_COMM_WORLD_LOCAL_RANK"]
    )
    local_size = _first_env_int(
        ["HOROVOD_LOCAL_SIZE", "OMPI_COMM_WORLD_LOCAL_SIZE"]
    )
    if local_rank is None or local_size is None:
        # Single process per host (the TPU idiom) unless the launcher says
        # otherwise.
        local_rank, local_size = 0, 1

    cross_rank = _first_env_int(["HOROVOD_CROSS_RANK"])
    cross_size = _first_env_int(["HOROVOD_CROSS_SIZE"])
    if cross_rank is None or cross_size is None:
        # Homogeneous assumption: nodes all have local_size ranks. The
        # reference verifies homogeneity with an allgather of local sizes
        # (operations.cc:936-952); our launcher exports explicit CROSS_* vars
        # for heterogeneous layouts instead.
        cross_rank = rank // max(local_size, 1)
        cross_size = (size + local_size - 1) // max(local_size, 1)

    if ranks:
        ranks = list(ranks)
        if sorted(set(ranks)) != sorted(ranks):
            raise ValueError("init(ranks=...) must not contain duplicates")
        if rank in ranks:
            new_rank = ranks.index(rank)
            topo = Topology(
                rank=new_rank,
                size=len(ranks),
                local_rank=0,
                local_size=1,
                cross_rank=new_rank,
                cross_size=len(ranks),
                num_devices=num_devices,
                local_num_devices=local_num_devices,
            )
            topo.validate()
            return topo
        raise RuntimeError(
            f"process rank {rank} not in init(ranks={ranks}); reference "
            "semantics: non-member processes must not call horovod APIs "
            "(horovod/common/basics.py:44-55)"
        )

    topo = Topology(
        rank=rank,
        size=size,
        local_rank=local_rank,
        local_size=local_size,
        cross_rank=cross_rank,
        cross_size=cross_size,
        num_devices=num_devices,
        local_num_devices=local_num_devices,
    )
    topo.validate()
    return topo

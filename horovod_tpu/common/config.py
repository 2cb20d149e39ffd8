"""Runtime configuration for horovod_tpu.

The reference configures everything through environment variables read once at
startup inside ``BackgroundThreadLoop`` (reference ``horovod/common/operations.cc:987-1080``).
We keep the exact same variable names so operator muscle memory (and existing
launch scripts) carry over, and add ``HOROVOD_TPU_*`` variables for knobs that
only exist on TPU.

Unlike the reference, configuration is an explicit dataclass snapshot rather
than globals scattered through a god object: JAX programs are functional, and a
frozen config travels well through jit boundaries.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

# Defaults mirror reference horovod/common/operations.cc:1005 (64 MiB fusion
# threshold), :1013 (5 ms cycle time) and horovod/common/global_state.h:135
# (1024-entry response cache).
DEFAULT_FUSION_THRESHOLD_BYTES = 64 * 1024 * 1024
DEFAULT_CYCLE_TIME_MS = 5.0
DEFAULT_CACHE_CAPACITY = 1024
DEFAULT_STALL_CHECK_SECONDS = 60.0
DEFAULT_START_TIMEOUT_SECONDS = 120.0
# Liveness bound for the post-rendezvous control plane: a blocked recv that
# sees NO frame (not even a heartbeat) for this long declares the peer dead
# instead of hanging forever (the reference's timeout-less sockets could).
DEFAULT_COMM_TIMEOUT_SECONDS = 120.0


def start_timeout_seconds(
        default: float = DEFAULT_START_TIMEOUT_SECONDS) -> float:
    """THE parser for ``HOROVOD_START_TIMEOUT`` (reference horovodrun
    --start-timeout). Garbage and non-positive values fall back to
    ``default`` — every consumer (rendezvous accept/connect windows in
    ``controller/service.py``, ``jax.distributed.initialize`` in
    ``common/basics.py``) must agree, or the two planes time out at
    different moments and the slower one wins by hanging."""
    try:
        val = float(os.environ.get("HOROVOD_START_TIMEOUT", ""))
    except (ValueError, OverflowError):
        return default
    return val if val > 0 else default


def comm_timeout_seconds() -> float:
    """``HOROVOD_COMM_TIMEOUT_SECONDS``: per-recv liveness deadline on the
    eager control plane. 0 (or negative) disables the deadline entirely —
    the pre-fault-tolerance behavior."""
    val = _env_float("HOROVOD_COMM_TIMEOUT_SECONDS",
                     DEFAULT_COMM_TIMEOUT_SECONDS)
    return val if val > 0 else 0.0


def heartbeat_interval_seconds() -> float:
    """``HOROVOD_HEARTBEAT_INTERVAL_SECONDS``: idle-cycle heartbeat frame
    period (0 disables). Defaults to a quarter of the comm timeout capped
    at 10s, so a live-but-quiet peer always beats the deadline with slack
    for scheduler noise. With the deadline disabled entirely
    (HOROVOD_COMM_TIMEOUT_SECONDS=0) heartbeats default OFF too — nothing
    would consume them; the env var still forces them on if wanted."""
    timeout = comm_timeout_seconds()
    default = min(10.0, timeout / 4.0) if timeout else 0.0
    val = _env_float("HOROVOD_HEARTBEAT_INTERVAL_SECONDS", default)
    return val if val > 0 else 0.0


def ring_data_plane_enabled() -> bool:
    """True when the launcher exported per-rank ring addresses and the
    operator did not force the pure-Python star data plane. The single
    source of truth for both engine selection (basics.init) and the Python
    controller's ring construction — the predicate must be identical on
    every rank, and both sites must agree."""
    return bool(os.environ.get("HOROVOD_RING_ADDRS")) and \
        os.environ.get("HOROVOD_CPU_OPS", "ring") != "star"


def env_rank() -> Optional[int]:
    """``HOROVOD_RANK`` as Optional[int]; unset/empty/garbage -> None.
    THE parser for every consumer (metrics rank labels, flight-recorder
    paths, fault-plan rank filters) — they must agree on what a
    malformed launch environment means, and none of them may crash on
    it."""
    val = os.environ.get("HOROVOD_RANK")
    if val is None or not val.strip():
        return None
    try:
        return int(val)
    except ValueError:
        return None


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    """Raw environment string, unset -> ``default``. THE generic reader:
    every value read of the environment outside this module goes through
    an accessor here (enforced by hvdlint HVD003), so there is exactly
    one place that decides what unset/empty/garbage means per knob."""
    val = os.environ.get(name)
    return default if val is None else val


def env_size() -> Optional[int]:
    """``HOROVOD_SIZE`` as Optional[int]; unset/empty/garbage -> None
    (the :func:`env_rank` convention — the two must agree on what a
    malformed launch environment means)."""
    val = os.environ.get("HOROVOD_SIZE")
    if val is None or not val.strip():
        return None
    try:
        return int(val)
    except ValueError:
        return None


def engine() -> Optional[str]:
    """``HOROVOD_ENGINE`` (native/python), None when the launcher left
    the choice to :func:`ring_data_plane_enabled`. Every rank derives the
    same answer from the same launcher-exported env."""
    return os.environ.get("HOROVOD_ENGINE") or None


def controller_addr() -> Optional[str]:
    """``HOROVOD_CONTROLLER_ADDR``: the coordinator's TCP star endpoint,
    exported by horovodrun; None outside a launched eager job."""
    return os.environ.get("HOROVOD_CONTROLLER_ADDR") or None


def spmd_coordinator() -> Optional[str]:
    """``HOROVOD_SPMD_COORDINATOR``: jax.distributed coordinator address
    (horovodrun --spmd); None outside SPMD multi-host mode."""
    return os.environ.get("HOROVOD_SPMD_COORDINATOR") or None


def secret_key_hex() -> Optional[str]:
    """``HOROVOD_SECRET_KEY`` (hex), the per-job HMAC key minted by the
    launcher. None means the hermetic single-job default applies
    (``common/wire.job_secret``) — both wire implementations and the
    launcher must agree on that fallback."""
    return os.environ.get("HOROVOD_SECRET_KEY") or None


def ring_addrs() -> Optional[str]:
    """``HOROVOD_RING_ADDRS``: per-rank addresses for the native ring
    data plane (launcher-exported, identical on every rank)."""
    return os.environ.get("HOROVOD_RING_ADDRS") or None


def local_ring_addrs() -> Optional[str]:
    return os.environ.get("HOROVOD_LOCAL_RING_ADDRS") or None


def cross_ring_addrs() -> Optional[str]:
    return os.environ.get("HOROVOD_CROSS_RING_ADDRS") or None


# Wire dtypes the native ring can put f32 allreduce payloads on the wire
# as (docs/wire-compression.md); must match core.bindings.WIRE_DTYPE_CODES.
RING_WIRE_DTYPES = ("none", "bf16", "fp16", "int8")

# Default wire dtype per link class — RING_CHUNK_BYTES_BY_LINK's sibling
# table (docs/wire-compression.md). Fast intra-node/ICI links lose more
# to the cast kernels than the saved bytes buy back, so they default to
# the untouched f32 stream; DCN/TCP-class links are exactly where
# int8+error-feedback pays (the reference's cross-node hop,
# nccl_operations.cc:167-363).
RING_WIRE_DTYPE_BY_LINK = {
    "local": "none",
    "ici": "none",
    "tcp": "int8",
    "dcn": "int8",
}

# Default transfer-chunk bytes per link class (docs/wire-compression.md):
# loopback wants big chunks (syscall overhead dominates, no real wire to
# overlap with), plain TCP keeps the round-3 256 KiB sweet spot, DCN-class
# NICs amortize better at 512 KiB, ICI-class links are long-BDP pipes.
RING_CHUNK_BYTES_BY_LINK = {
    "local": 1 << 20,
    "tcp": 256 << 10,
    "dcn": 512 << 10,
    "ici": 2 << 20,
}


def ring_wire_dtype() -> str:
    """``HOROVOD_RING_WIRE_DTYPE``: on-the-wire representation of f32
    payloads in the native ring's allreduce data phases — ``bf16``/``fp16``
    halve every hop's bytes (accumulation stays f32), ``int8`` quarters
    them with per-block scales + error feedback (convergence contract in
    docs/wire-compression.md). Unset/garbage -> ``none``, which keeps the
    byte stream identical to the pre-round-10 ring. Must be identical on
    every rank (launcher-exported, like the other ring knobs)."""
    val = (os.environ.get("HOROVOD_RING_WIRE_DTYPE") or "").strip().lower()
    return val if val in RING_WIRE_DTYPES else "none"


def _link_class_env(name: str) -> Optional[str]:
    """A *_LINK_CLASS env value when valid, else None (garbage falls back
    to the caller's inference path, never crashes)."""
    val = (os.environ.get(name) or "").strip().lower()
    return val if val in RING_CHUNK_BYTES_BY_LINK else None


def local_ring_link_class() -> str:
    """``HOROVOD_LOCAL_RING_LINK_CLASS``: link class of the hierarchical
    plane's intra-node ring. Unset/garbage -> inferred from the
    launcher-exported local ring addresses (same-host ranks are loopback,
    hence ``local``); operators on ICI fabrics export it explicitly."""
    val = _link_class_env("HOROVOD_LOCAL_RING_LINK_CLASS")
    if val is not None:
        return val
    from ..run.nic_discovery import infer_link_class

    return infer_link_class(local_ring_addrs())


def cross_ring_link_class() -> str:
    """``HOROVOD_CROSS_RING_LINK_CLASS``: link class of the hierarchical
    plane's inter-node ring (the local roots' ring). Unset/garbage ->
    inferred from the cross ring addresses — anything spanning hosts is
    ``tcp``; known DCN fabrics export the class explicitly (the chunk
    table AND the wire-dtype table key off it)."""
    val = _link_class_env("HOROVOD_CROSS_RING_LINK_CLASS")
    if val is not None:
        return val
    from ..run.nic_discovery import infer_link_class

    return infer_link_class(cross_ring_addrs())


def _wire_dtype_for(env_name: str, link_class: str) -> str:
    """Shared resolver for the per-link wire dtypes: an explicit valid
    env value wins; unset/garbage falls back to the link-class default
    (``RING_WIRE_DTYPE_BY_LINK``), never to a crash."""
    val = (os.environ.get(env_name) or "").strip().lower()
    if val in RING_WIRE_DTYPES:
        return val
    return RING_WIRE_DTYPE_BY_LINK[link_class]


def ring_wire_dtype_local() -> str:
    """``HOROVOD_RING_WIRE_DTYPE_LOCAL``: on-the-wire representation of
    f32 allreduce payloads on the hierarchical plane's LOCAL (intra-node)
    ring. Default by link class: local/ici -> ``none`` (the fast hop —
    cast kernels cost more than the bytes they save), tcp/dcn -> ``int8``.
    Launcher-exported, identical on every rank (like every ring knob)."""
    return _wire_dtype_for("HOROVOD_RING_WIRE_DTYPE_LOCAL",
                           local_ring_link_class())


def ring_wire_dtype_cross() -> str:
    """``HOROVOD_RING_WIRE_DTYPE_CROSS``: wire dtype for the hierarchical
    plane's CROSS ring (local roots, the slow inter-node hop — exactly
    where int8+error-feedback pays most; docs/wire-compression.md).
    Default by link class: tcp/dcn -> ``int8``, local/ici -> ``none``."""
    return _wire_dtype_for("HOROVOD_RING_WIRE_DTYPE_CROSS",
                           cross_ring_link_class())


def ring_chunk_bytes() -> int:
    """``HOROVOD_RING_CHUNK_BYTES``: transfer-chunk size for the ring's
    reduce-while-receive sink and compress-ahead cursor (per-rank
    pipelining granularity only — the int8 wire format is anchored on
    fixed quant blocks, so ranks need not agree). 0 (default, and for
    garbage) means auto: the per-link-class table keyed by
    :func:`ring_link_class`, and the knob joins the GP autotuner's search
    when ``HOROVOD_AUTOTUNE`` is on. Explicit values pin the knob
    (excluded from the search, like every other fixed= override)."""
    return max(0, _env_int("HOROVOD_RING_CHUNK_BYTES", 0))


def ring_link_class() -> str:
    """``HOROVOD_RING_LINK_CLASS``: the flat ring's link class
    (local/tcp/dcn/ici), keying the default chunk table. Unset -> inferred
    from the launcher-exported ring addresses (``run.nic_discovery
    .infer_link_class``): loopback-only -> ``local``, anything spanning
    hosts -> ``tcp``; operators on known DCN/ICI fabrics export the class
    explicitly (or the launcher does, where NIC discovery identified
    one)."""
    val = (os.environ.get("HOROVOD_RING_LINK_CLASS") or "").strip().lower()
    if val in RING_CHUNK_BYTES_BY_LINK:
        return val
    from ..run.nic_discovery import infer_link_class

    return infer_link_class(ring_addrs())


def resolved_ring_chunk_bytes() -> int:
    """The chunk size the ring should start at: the explicit env value, or
    the link-class default. One resolver so the controller, the autotuner
    seeding, and the metrics gauge agree."""
    explicit = ring_chunk_bytes()
    if explicit:
        return explicit
    return RING_CHUNK_BYTES_BY_LINK[ring_link_class()]


# Default gradient-bucket size for the backward-order bucket scheduler
# (docs/overlap.md): big enough that per-bucket negotiation overhead
# amortizes, small enough that the first reduction launches while most of
# the backward pass is still running (the reference's fusion-buffer cycle
# achieves the same balance with its 64 MiB buffer + 5 ms cycle).
DEFAULT_BUCKET_BYTES = 8 * 1024 * 1024


def bucket_bytes() -> int:
    """``HOROVOD_BUCKET_BYTES``: size bound for the backward-order
    gradient buckets (controller/bucket_scheduler.py). 0 (default, and
    for garbage) means auto — the 8 MiB default, and the knob joins the
    GP autotuner's search when ``HOROVOD_AUTOTUNE`` is on. An explicit
    positive value pins the knob (``fixed=`` semantics, like
    HOROVOD_RING_CHUNK_BYTES)."""
    return max(0, _env_int("HOROVOD_BUCKET_BYTES", 0))


def resolved_bucket_bytes() -> int:
    """The bucket size the scheduler should start at: the explicit env
    value, or the default. One resolver so the scheduler, the autotuner
    seeding, and docs agree."""
    explicit = bucket_bytes()
    return explicit if explicit else DEFAULT_BUCKET_BYTES


def cpu_ops() -> str:
    """``HOROVOD_CPU_OPS``: "star" forces the pure-Python star data
    plane; anything else (default "ring") allows the native rings. Part
    of the per-rank-identical path-selection predicate
    (:func:`ring_data_plane_enabled`)."""
    return os.environ.get("HOROVOD_CPU_OPS", "ring")


def flight_recorder_path() -> Optional[str]:
    """``HOROVOD_FLIGHT_RECORDER``: crash-postmortem JSONL path (with
    ``{rank}``/``.rankN`` expansion applied by the recorder). None/blank
    disables — and, via ``metrics.on()``, setting it implicitly enables
    telemetry."""
    val = (os.environ.get("HOROVOD_FLIGHT_RECORDER") or "").strip()
    return val or None


def restart_epoch() -> int:
    """``HOROVOD_RESTART_EPOCH``: supervision attempt number, bumped by
    ``horovodrun --max-restarts`` per relaunch. 0 on the first launch,
    outside the launcher, and for garbage values (a malformed relaunch
    env must look like a fresh start, not crash resume logic)."""
    try:
        return max(0, int(os.environ.get("HOROVOD_RESTART_EPOCH", "0")))
    except ValueError:
        return 0


def tensorflow_custom_op_enabled() -> bool:
    """``HOROVOD_TENSORFLOW_CUSTOM_OP``: opt-out knob for the native TF
    custom-op data path. Historical semantics kept exactly: only the
    explicit negatives disable; unset and even empty mean enabled (NOT
    the ``_env_bool`` convention — existing launch scripts rely on
    it)."""
    return os.environ.get("HOROVOD_TENSORFLOW_CUSTOM_OP", "1") \
        .strip().lower() not in ("0", "false", "no", "off")


def log_level_name() -> str:
    """``HOROVOD_LOG_LEVEL`` lowercased, defaulting to "warning" — the
    one parser for both the early logging bootstrap
    (``hvd_logging.configure``) and ``Config.from_env``."""
    return os.environ.get("HOROVOD_LOG_LEVEL", "warning").lower()


def autotune_straggler_weight() -> float:
    """``HOROVOD_AUTOTUNE_STRAGGLER_WEIGHT``: how strongly the autotuner's
    objective discounts throughput for observed negotiation slack and
    coordinator recv-wait (docs/autotune.md). 0 restores the pure
    bytes/sec objective; negative/garbage values clamp to the default.
    Default 1.0 — with a healthy cluster both penalty terms are ~0, so
    the blend only bites when stragglers actually cost wall time."""
    val = _env_float("HOROVOD_AUTOTUNE_STRAGGLER_WEIGHT", 1.0)
    return val if val >= 0 else 1.0


def pipeline_enabled() -> bool:
    """``HOROVOD_PIPELINE``: the native engine's double-buffered data
    plane (docs/overlap.md) — a dedicated wire thread runs group N on
    the ring while the engine thread packs N+1 and copies out N-1.
    Default on; ``HOROVOD_PIPELINE=0`` is the escape hatch back to the
    serial fill->wire->copy-out stream (byte-identical results either
    way — the knob trades step time only). The engine also falls back
    to serial on the hierarchical allreduce plane, whose cross-hop
    scratch is shared."""
    return _env_bool("HOROVOD_PIPELINE", True)


def autotune_overlap_weight() -> float:
    """``HOROVOD_AUTOTUNE_OVERLAP_WEIGHT``: how strongly the autotuner's
    objective rewards measured backward/comm overlap (docs/autotune.md,
    docs/overlap.md). The blend multiplies the throughput score by
    ``1 + w * overlap_efficiency`` whenever the bucket scheduler has
    published a fresh overlap sample; 0 removes the term. Negative or
    garbage values clamp to the default 1.0."""
    val = _env_float("HOROVOD_AUTOTUNE_OVERLAP_WEIGHT", 1.0)
    return val if val >= 0 else 1.0


def doctor_cycles() -> int:
    """``HOROVOD_DOCTOR_CYCLES``: coordinator cycles between periodic
    cluster-doctor sweeps (the rank-0 log line + hvd_doctor_* gauges;
    docs/doctor.md). 0/negative disables the periodic sweep (the /doctor
    endpoint and offline CLI still work). Default 1000 — ~5s at the
    default 5 ms cycle time."""
    val = _env_int("HOROVOD_DOCTOR_CYCLES", 1000)
    return max(0, val)


def elastic_enabled() -> bool:
    """``HOROVOD_ELASTIC``: opt-in elastic membership (docs/elastic.md).
    When set, a dead rank triggers a coordinator-led reshape (survivors
    re-form at a bumped membership epoch) instead of a job-wide abort,
    and late worker hellos are admitted at the next epoch boundary.
    Unset, behavior is identical to the static fault-tolerance contract
    (docs/fault-tolerance.md)."""
    return _env_bool("HOROVOD_ELASTIC")


def elastic_join() -> bool:
    """``HOROVOD_ELASTIC_JOIN``: this worker is a late joiner — it sends
    a JOIN hello to a live coordinator and waits for its (rank, size,
    epoch) assignment instead of taking part in the initial rendezvous.
    Exported by ``horovodrun --elastic`` when it respawns a dead worker
    slot."""
    return _env_bool("HOROVOD_ELASTIC_JOIN")


def elastic_min_ranks() -> int:
    """``HOROVOD_ELASTIC_MIN_RANKS``: smallest world size an elastic
    reshape may re-form (coordinator included). Below it the job aborts
    exactly like the non-elastic path. Default 1 — the coordinator keeps
    going alone if it must."""
    return max(1, _env_int("HOROVOD_ELASTIC_MIN_RANKS", 1))


def elastic_max_ranks() -> int:
    """``HOROVOD_ELASTIC_MAX_RANKS``: largest world size joiners may grow
    the job to; joiners beyond it stay parked until a slot frees. 0 (the
    default) means unbounded."""
    return max(0, _env_int("HOROVOD_ELASTIC_MAX_RANKS", 0))


def elastic_ckpt_dir() -> Optional[str]:
    """``HOROVOD_CKPT_DIR``: directory for the continuous async sharded
    checkpoints (docs/sharded-checkpoint.md). When set, every
    ``hvd.elastic.State.commit()`` also hands this rank's shard of the
    committed pytree to the background ``hvd-ckpt-writer`` thread; the
    step loop never blocks on storage. Unset (the default), commits stay
    purely in-memory and the disk tier is off."""
    val = env_str("HOROVOD_CKPT_DIR")
    return val.strip() if val and val.strip() else None


def elastic_ckpt_keep() -> int:
    """``HOROVOD_CKPT_KEEP``: how many complete sharded-checkpoint steps
    the async writer retains on disk (older steps are pruned after a new
    one lands whole). Minimum/default 2 — the double buffer that makes a
    kill at ANY rename point leave a complete previous step visible."""
    return max(2, _env_int("HOROVOD_CKPT_KEEP", 2))


def elastic_restore_mode() -> str:
    """``HOROVOD_ELASTIC_RESTORE``: how ``hvd.elastic.State.restore()``
    re-establishes consistent state after a reshape (docs/elastic.md).
    ``p2p`` (the default under elastic membership) keeps digest-matching
    survivors' local commits and scatters only the missing shards over
    surviving owners; ``broadcast`` forces the legacy rank-0 whole-pytree
    re-broadcast (the bench baseline). Garbage falls back to p2p."""
    val = (env_str("HOROVOD_ELASTIC_RESTORE") or "").strip().lower()
    return "broadcast" if val == "broadcast" else "p2p"


def autotune_priors() -> str:
    """``HOROVOD_AUTOTUNE_PRIORS``: where the GP autotuner's initial
    bucket/chunk configuration comes from (docs/autotune.md, round 17).
    ``capacity`` seeds the first probed configuration from the capacity
    planner's calibrated recommendation for this world size
    (``utils.scaling_model.recommend_autotune_seeds`` over the artifact
    named by :func:`capacity_calibration_path`); anything else — the
    default ``off`` — keeps the resolver defaults. Explicit env pins
    (HOROVOD_BUCKET_BYTES / HOROVOD_RING_CHUNK_BYTES) always win over
    the prior."""
    val = (env_str("HOROVOD_AUTOTUNE_PRIORS") or "").strip().lower()
    return "capacity" if val == "capacity" else "off"


def capacity_calibration_path() -> Optional[str]:
    """``HOROVOD_CAPACITY_CALIBRATION``: path to a control-plane
    calibration artifact (the ``control_plane`` + ``model_vs_measured``
    JSON shape the sim measurement rig writes). Arms the
    ``capacity_headroom`` doctor rule and the ``capacity`` autotune
    priors; unset (default) both stand down — a fleet without a
    calibrated model has nothing honest to compare against."""
    val = env_str("HOROVOD_CAPACITY_CALIBRATION")
    return val.strip() if val and val.strip() else None


def metrics_window_seconds() -> float:
    """``HOROVOD_METRICS_WINDOW_SECONDS``: how long one rolling
    telemetry window lasts (docs/metrics.md). The rank-0 window roller
    delta-snapshots the cluster view at this cadence into a bounded
    ring (last 32 windows), feeding the windowed doctor rules and the
    live calibration re-fit (docs/capacity.md). Garbage/non-positive
    falls back to the default 30s."""
    val = _env_float("HOROVOD_METRICS_WINDOW_SECONDS", 30.0)
    return val if val > 0 else 30.0


def capacity_refit_windows() -> int:
    """``HOROVOD_CAPACITY_REFIT_WINDOWS``: telemetry windows between
    live-calibration re-fits (docs/capacity.md) — every N completed
    windows rank 0 re-fits the control-plane curves from the windowed
    histograms and, when ``HOROVOD_CAPACITY_LIVE_DIR`` is set, rewrites
    ``capacity_live.json``. Minimum/garbage clamps to 1; default 8."""
    val = _env_int("HOROVOD_CAPACITY_REFIT_WINDOWS", 8)
    return max(1, val)


def capacity_live_dir() -> Optional[str]:
    """``HOROVOD_CAPACITY_LIVE_DIR``: directory where rank 0 persists
    ``capacity_live.json`` — the live re-fit of the control-plane
    calibration in the exact ``capacity_r17.json`` schema, stamped
    ``"source": "live"`` (docs/capacity.md). Written on every
    ``HOROVOD_CAPACITY_REFIT_WINDOWS``-th window and at shutdown.
    Unset (default): the live re-fit stays in memory only."""
    val = env_str("HOROVOD_CAPACITY_LIVE_DIR")
    return val.strip() if val and val.strip() else None


def serving_max_batch() -> int:
    """``HOROVOD_SERVING_MAX_BATCH``: decode-batch slots in the serving
    engine — the most sequences one continuous-batching decode step
    carries (docs/serving.md). Garbage/non-positive falls back to the
    default 8 (the b8 decode floor the batcher exists to amortize)."""
    val = _env_int("HOROVOD_SERVING_MAX_BATCH", 8)
    return val if val > 0 else 8


def serving_block_size() -> int:
    """``HOROVOD_SERVING_BLOCK_SIZE``: KV-cache page size in token
    positions. Default 16 — on real models the flat head width is a
    128-lane multiple, so a 16-row block is one bf16 Mosaic tile."""
    val = _env_int("HOROVOD_SERVING_BLOCK_SIZE", 16)
    return val if val > 0 else 16


def serving_num_blocks() -> int:
    """``HOROVOD_SERVING_NUM_BLOCKS``: physical KV pool capacity in
    blocks (the null block is extra). 0 (default) = fully provisioned —
    every decode slot can hold a max-length sequence, so preemption is
    impossible; operators lower it to oversubscribe HBM and let
    preemption-by-recompute absorb the tail."""
    return max(0, _env_int("HOROVOD_SERVING_NUM_BLOCKS", 0))


def serving_queue_depth() -> int:
    """``HOROVOD_SERVING_QUEUE_DEPTH``: admission bound — submissions
    beyond this many WAITING requests are rejected loudly
    (``hvd.serving.RejectedError``) instead of queueing without bound."""
    val = _env_int("HOROVOD_SERVING_QUEUE_DEPTH", 128)
    return val if val > 0 else 128


def serving_max_seq_len() -> int:
    """``HOROVOD_SERVING_MAX_SEQ_LEN``: per-sequence position budget
    (prompt + generated) in the serving engine. 0 (default) = the
    model's own ``max_seq_len``."""
    return max(0, _env_int("HOROVOD_SERVING_MAX_SEQ_LEN", 0))


def serving_prefix_cache() -> bool:
    """``HOROVOD_SERVING_PREFIX_CACHE``: copy-on-write prefix sharing on
    the paged KV pool (docs/serving.md). Default ON — per-request tokens
    are bit-identical with it on or off (the pinned parity contract), so
    the knob exists for A/B measurement and paranoia, not correctness."""
    return _env_bool("HOROVOD_SERVING_PREFIX_CACHE", True)


def serving_prefix_capacity() -> int:
    """``HOROVOD_SERVING_PREFIX_CAPACITY``: most blocks the prefix index
    may hold references to (its LRU bound). 0 (default) = no dedicated
    bound — cold entries are released only under pool pressure, which is
    the right default because cached pages are free until somebody
    needs the blocks."""
    return max(0, _env_int("HOROVOD_SERVING_PREFIX_CAPACITY", 0))


def router_replicas() -> int:
    """``HOROVOD_ROUTER_REPLICAS``: engine replicas ``hvd.serving.fleet``
    spins up when the caller does not pass an explicit count. Default 2
    — the smallest fleet where replica death is a reshape instead of an
    outage."""
    val = _env_int("HOROVOD_ROUTER_REPLICAS", 2)
    return val if val > 0 else 2


def router_affinity() -> bool:
    """``HOROVOD_ROUTER_AFFINITY``: prefix-affinity placement — requests
    whose first whole page matches a prefix recently routed somewhere
    follow it there (that replica's prefix cache is warm for them).
    Default ON; off = pure least-loaded."""
    return _env_bool("HOROVOD_ROUTER_AFFINITY", True)


def router_retries() -> int:
    """``HOROVOD_ROUTER_RETRIES``: times the router replays one request
    on another replica after its serving replica died mid-flight (the
    recompute path: greedy decoding is deterministic, so the replay's
    tokens are identical and already-streamed ones are skipped). Beyond
    it the failure surfaces to the caller."""
    return max(0, _env_int("HOROVOD_ROUTER_RETRIES", 2))


def fault_plan_raw() -> Optional[str]:
    """``HOROVOD_FAULT_PLAN``: inline JSON or ``@file`` reference for the
    deterministic fault-injection plan; None/blank disables."""
    val = os.environ.get("HOROVOD_FAULT_PLAN")
    if not val or not val.strip():
        return None
    return val


def _env_bool(name: str, default: bool = False) -> bool:
    val = os.environ.get(name)
    if val is None:
        return default
    return val.strip().lower() not in ("", "0", "false", "no", "off")


def _env_float(name: str, default: float) -> float:
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    try:
        return float(val)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    val = os.environ.get(name)
    if val is None or not val.strip():
        return default
    try:
        return int(val)
    except ValueError:
        return default


@dataclasses.dataclass(frozen=True)
class Config:
    """Snapshot of all runtime knobs, read from the environment at init().

    Env-variable names intentionally match the reference (SURVEY.md §5
    "Config / flag system") so scripts written for the reference keep working.
    """

    # Tensor Fusion (reference operations.cc:1005): fused buffers up to this
    # many bytes are reduced in one collective.
    fusion_threshold_bytes: int = DEFAULT_FUSION_THRESHOLD_BYTES
    # Background controller tick, ms (reference operations.cc:1013).
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS
    # Response-cache entries (reference global_state.h:135); 0 disables.
    cache_capacity: int = DEFAULT_CACHE_CAPACITY
    # Two-level (ICI-within-slice / DCN-across-slices) collectives, the TPU
    # analogue of reference NCCLHierarchicalAllreduce (nccl_operations.cc:167).
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    # Chrome-trace timeline output path (reference operations.cc:986-996).
    timeline_filename: Optional[str] = None
    timeline_mark_cycles: bool = False
    # Cluster-wide distributed tracing (docs/tracing.md): every rank
    # writes clock-anchored phase spans under this directory; rank 0
    # merges them (+ straggler report) at clean shutdown. TPU-era
    # extension — the reference timeline is per-rank only.
    trace_dir: Optional[str] = None
    # Stall detection (reference operations.cc:688-769).
    stall_check_disable: bool = False
    stall_check_seconds: float = DEFAULT_STALL_CHECK_SECONDS
    stall_shutdown_seconds: float = 0.0  # 0 = never force shutdown
    # Liveness: per-recv control-plane deadline (0 = no deadline) and idle
    # heartbeat period (0 = no heartbeats). See docs/fault-tolerance.md.
    comm_timeout_seconds: float = DEFAULT_COMM_TIMEOUT_SECONDS
    heartbeat_interval_seconds: float = 10.0
    # Autotuner (reference parameter_manager.cc).
    autotune: bool = False
    autotune_log: Optional[str] = None
    # Logging level name: trace/debug/info/warning/error/fatal.
    log_level: str = "warning"
    log_hide_timestamp: bool = False
    # TPU-only: dtype used on the wire for fused allreduce ("float32",
    # "bfloat16"). bfloat16 halves ICI bytes; reference's closest analogue is
    # fp16 Compression (torch/compression.py:45-74).
    tpu_reduction_dtype: Optional[str] = None

    @staticmethod
    def from_env() -> "Config":
        timeline = os.environ.get("HOROVOD_TIMELINE") or None
        autotune_log = os.environ.get("HOROVOD_AUTOTUNE_LOG") or None
        return Config(
            fusion_threshold_bytes=_env_int(
                "HOROVOD_FUSION_THRESHOLD", DEFAULT_FUSION_THRESHOLD_BYTES
            ),
            cycle_time_ms=_env_float("HOROVOD_CYCLE_TIME", DEFAULT_CYCLE_TIME_MS),
            cache_capacity=_env_int("HOROVOD_CACHE_CAPACITY", DEFAULT_CACHE_CAPACITY),
            hierarchical_allreduce=_env_bool("HOROVOD_HIERARCHICAL_ALLREDUCE"),
            hierarchical_allgather=_env_bool("HOROVOD_HIERARCHICAL_ALLGATHER"),
            timeline_filename=timeline,
            timeline_mark_cycles=_env_bool("HOROVOD_TIMELINE_MARK_CYCLES"),
            trace_dir=(os.environ.get("HOROVOD_TRACE_DIR") or "").strip()
            or None,
            stall_check_disable=_env_bool("HOROVOD_STALL_CHECK_DISABLE"),
            stall_check_seconds=_env_float(
                "HOROVOD_STALL_CHECK_TIME_SECONDS", DEFAULT_STALL_CHECK_SECONDS
            ),
            stall_shutdown_seconds=_env_float(
                "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", 0.0
            ),
            comm_timeout_seconds=comm_timeout_seconds(),
            heartbeat_interval_seconds=heartbeat_interval_seconds(),
            autotune=_env_bool("HOROVOD_AUTOTUNE"),
            autotune_log=autotune_log,
            log_level=os.environ.get("HOROVOD_LOG_LEVEL", "warning").lower(),
            log_hide_timestamp=_env_bool("HOROVOD_LOG_HIDE_TIME"),
            tpu_reduction_dtype=os.environ.get("HOROVOD_TPU_REDUCTION_DTYPE") or None,
        )

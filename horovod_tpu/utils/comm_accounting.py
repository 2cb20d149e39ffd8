"""Communication-volume accounting from compiled HLO.

The reference anchors its scaling story on measured allreduce bus
bandwidth (``/root/reference/docs/benchmarks.md:5-34``). On one real chip
we cannot measure multi-chip wire time, but the compiled program tells us
exactly WHAT will move: every XLA collective and its payload bytes are
static in the HLO. This module parses them and provides the ring-model
theory to pin them against — the hardware-free scaling evidence that
replaces a meaningless 1-core wall-clock curve
(``tests/test_comm_volume.py``, ``artifacts/comm_volume_r3.json``).

Wire-byte model (ring algorithms, the ICI/NCCL standard):

* all-reduce of ``B`` bytes over ``n`` devices: each device sends (and
  receives) ``2 (n-1)/n * B`` — reduce-scatter half + all-gather half.
* reduce-scatter / all-gather alone: ``(n-1)/n * B`` each (``B`` = the
  FULL pre-scatter / post-gather payload).
* collective-permute (ring hop): each device sends its shard once.
* all-to-all of ``B`` bytes: ``(n-1)/n * B`` leaves each device.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List

from ..common import profiler

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# e.g. "f32[1024,8]" or "bf16[8]{0}" inside an HLO op signature.
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                  "collective-permute", "all-to-all")


@dataclasses.dataclass
class Collective:
    op: str             # HLO opcode (all-reduce, ...)
    payload_bytes: int  # summed result-shape bytes (full logical payload)
    group_size: int     # devices per replica group (1 = unknown/whole)


def _typed_entries(sig: str) -> List[tuple]:
    """(dtype, dims, bytes) per array in an HLO signature string."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(sig):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((dtype, dims, n * _DTYPE_BYTES[dtype]))
    return out


def _shape_entries(sig: str) -> List[int]:
    return [b for _, _, b in _typed_entries(sig)]


def _operand_count(line: str, open_paren: int) -> int:
    """Number of comma-separated operands in the call parens opening at
    ``open_paren`` (depth-aware; 0 for an empty list)."""
    depth, i, commas = 1, open_paren + 1, 0
    start = i
    while i < len(line) and depth:
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "," and depth == 1:
            commas += 1
        i += 1
    return 0 if not line[start:i - 1].strip() else commas + 1


# "{{0,1,2,3},{4,5,6,7}}" (explicit) or "[2,4]<=[8]" (iota: 2 groups x 4).
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def async_result_entries(line: str, opcode: str, ents: List[tuple],
                         open_paren: int) -> List[tuple]:
    """Result-half entries of an async ``X-start`` tuple: strip
    collective-permute's trailing u32[] context scalars, then drop as
    many leading entries as the op has operands (parsed from the call
    parens); even-halving is the fallback when parsing fails. Shared by
    :func:`collectives` and ``utils.overlap``."""
    if opcode.startswith("collective-permute"):
        while ents and ents[-1][1] == "" and ents[-1][0] in ("u32", "s32"):
            ents.pop()
    k = _operand_count(line, open_paren)
    if 0 < k < len(ents):
        return ents[k:]
    if len(ents) % 2 == 0:
        return ents[len(ents) // 2:]
    return ents


_INDEX_COMMENT_RE = re.compile(r"/\*index=\d+\*/")


def collectives(compiled) -> List[Collective]:
    """Parse a ``jax`` compiled object (``jit(f).lower(...).compile()``)
    into its collective ops. Payload = the op's RESULT shape bytes (for
    reduce-scatter: the scattered shard; for all-gather: the gathered
    full array; for all-reduce: the reduced array — matching each op's
    logical output). Each op carries its replica-group size parsed from
    the HLO, so multi-axis programs (dcn x ici) bill each collective at
    its own ring length."""
    out = []
    for line in compiled.as_text().splitlines():
        # Tuples past five elements carry "/*index=5*/" position comments;
        # their "=" would end the result-signature match below and a
        # combined (many-operand) collective would go uncounted.
        s = line.strip()
        if "/*index=" in s:
            s = _INDEX_COMMENT_RE.sub("", s)
        # "%name = f32[...] all-reduce(...)" — opcode follows the result
        # signature; skip -start/-done pairs' duplicate (count -start).
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^=]*?)\s*"
                     r"(all-reduce|reduce-scatter|all-gather|"
                     r"collective-permute|all-to-all)"
                     r"(-start|-done)?\(", s)
        if not m:
            continue
        if m.group(3) == "-done":
            continue
        if m.group(3) == "-start":
            entries = [b for _, _, b in async_result_entries(
                s, m.group(2) + m.group(3), _typed_entries(m.group(1)),
                m.end() - 1)]
        else:
            entries = _shape_entries(m.group(1))
        out.append(Collective(m.group(2), sum(entries), _group_size(s)))
    return out


def count_by_op(colls: List[Collective]) -> Dict[str, int]:
    c: Dict[str, int] = {}
    for x in colls:
        c[x.op] = c.get(x.op, 0) + 1
    return c


def payload_by_op(colls: List[Collective]) -> Dict[str, int]:
    c: Dict[str, int] = {}
    for x in colls:
        c[x.op] = c.get(x.op, 0) + x.payload_bytes
    return c


# ---------------------------------------------------------------------------
# Decode-path attribution.

#: The ``jax.named_scope`` labels ``models.llama._cached_attention`` wraps
#: each decode path in. They survive compilation as HLO op metadata
#: (``op_name="jit(..)/../hvd.decode.kernel_tp/.."``) — so a compiled
#: decode program PROVES which path it traced, independent of any
#: Python-side record (``models.llama.LAST_DECODE_PATH`` is the cheap
#: twin). The same labels show up as ``tf_op_name`` prefixes in profiler
#: traces, so phase tables attribute attention time per path too.
DECODE_PATH_MARKERS = tuple(profiler.decode_scope(path)
                            for path in profiler.DECODE_PATHS)


def decode_path_markers(compiled_or_text) -> Dict[str, int]:
    """Count each decode-path scope marker in compiled HLO (pass a
    ``jit(f).lower(...).compile()`` object or its ``as_text()``). A
    decode program that really runs the shard_mapped kernel shows
    ``kernel_tp`` > 0 and ``einsum`` == 0; the blanket fallback shows the
    reverse — the bench's TP-decode row asserts exactly that."""
    text = (compiled_or_text if isinstance(compiled_or_text, str)
            else compiled_or_text.as_text())
    return {m: len(re.findall(re.escape(m) + r"(?!\w)", text))
            for m in DECODE_PATH_MARKERS}


# ---------------------------------------------------------------------------
# Kernel attribution.

_MOSAIC_CALL = "tpu_custom_call"
_KERNEL_WORDS = {kernel: re.compile(r"(?<![\w.])" + re.escape(kernel)
                                    + r"(?![\w.])")
                 for kernel in profiler.KERNELS}


def mosaic_calls_by_kernel(program_or_text) -> Dict[str, int]:
    """Count a program's Mosaic custom calls by the ``pallas_call``'s
    ``name=`` (``profiler.KERNELS``; a call of none of them, such as a
    product XLA hands to Mosaic itself, under ``"(unnamed)"``). Pass a
    ``jit(f).lower(...)`` (StableHLO: the call carries ``kernel_name =
    "hvd_flash_fwd"``), its ``.compile()`` (HLO: the call's ``op_name``
    ends ``.../hvd_flash_fwd/pallas_call``) or either's ``as_text()``. A
    step whose blocks are checkpointed (``models.decoder.rematerialised``)
    shows as many ``hvd_flash_fwd`` as ``hvd_flash_bwd_dq``: the forward
    kernel is not called again in the backward pass."""
    text = (program_or_text if isinstance(program_or_text, str)
            else program_or_text.as_text())
    found = dict.fromkeys(profiler.KERNELS + ("(unnamed)",), 0)
    for line in text.splitlines():
        if _MOSAIC_CALL not in line:
            continue
        found[next((kernel for kernel in profiler.KERNELS
                    if _KERNEL_WORDS[kernel].search(line)),
                   "(unnamed)")] += 1
    return found


# ---------------------------------------------------------------------------
# Ring-model wire bytes (per device, send direction).


def ring_allreduce_bytes(n: int, payload: int) -> float:
    return 2 * (n - 1) / n * payload


def ring_reduce_scatter_bytes(n: int, payload: int) -> float:
    return (n - 1) / n * payload


def ring_all_gather_bytes(n: int, payload: int) -> float:
    return (n - 1) / n * payload


def wire_bytes_per_device(colls: List[Collective],
                          default_n: int) -> float:
    """Ring-model send bytes per device for a compiled step. Each
    collective is billed at its own parsed replica-group size;
    ``default_n`` covers ops whose groups could not be parsed."""
    total = 0.0
    for x in colls:
        n = x.group_size if x.group_size > 1 else default_n
        if x.op == "all-reduce":
            total += ring_allreduce_bytes(n, x.payload_bytes)
        elif x.op == "reduce-scatter":
            # Result is the shard: full payload = shard * n.
            total += ring_reduce_scatter_bytes(n, x.payload_bytes * n)
        elif x.op == "all-gather":
            total += ring_all_gather_bytes(n, x.payload_bytes)
        elif x.op == "collective-permute":
            total += x.payload_bytes
        elif x.op == "all-to-all":
            total += (n - 1) / n * x.payload_bytes
    return total

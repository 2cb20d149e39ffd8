"""What a compiled program's text says: its collectives and its Mosaic
calls. ``collectives`` is a copy of the program's
``horovod_tpu.utils.comm_accounting.collectives`` (sound since PR 21's
regex fix), kept here so that no later PR can move the yardstick.
"""

import dataclasses
import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INDEX_COMMENT_RE = re.compile(r"/\*index=\d+\*/")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_COLLECTIVE_RE = re.compile(
    r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^=]*?)\s*"
    r"(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)"
    r"(-start|-done)?\(")


@dataclasses.dataclass
class Collective:
    op: str             # HLO opcode
    payload_bytes: int  # summed result-shape bytes
    group_size: int     # devices per replica group (1 = unknown or whole)


def _typed_entries(sig):
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


def _operand_count(line, open_paren):
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


def _group_size(line):
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def _async_result_entries(line, opcode, ents, open_paren):
    """Result half of an async ``X-start`` tuple (operands come first)."""
    if opcode.startswith("collective-permute"):
        while ents and ents[-1][1] == "" and ents[-1][0] in ("u32", "s32"):
            ents.pop()
    k = _operand_count(line, open_paren)
    if 0 < k < len(ents):
        return ents[k:]
    if len(ents) % 2 == 0:
        return ents[len(ents) // 2:]
    return ents


def collectives(text):
    """Every collective of a compiled program's text, ``-start``/``-done``
    pairs counted once, each with its payload and replica-group size."""
    out = []
    for line in text.splitlines():
        s = line.strip()
        if "/*index=" in s:
            # Tuples past five elements carry position comments whose "="
            # would end the result-signature match.
            s = _INDEX_COMMENT_RE.sub("", s)
        m = _COLLECTIVE_RE.match(s)
        if not m or m.group(3) == "-done":
            continue
        entries = _typed_entries(m.group(1))
        if m.group(3) == "-start":
            entries = _async_result_entries(
                s, m.group(2) + m.group(3), entries, m.end() - 1)
        out.append(Collective(m.group(2), sum(b for _, _, b in entries),
                              _group_size(s)))
    return out


def mosaic_calls(text):
    """Number of Pallas kernels Mosaic compiled into the program."""
    return len(re.findall(r'custom_call_target="tpu_custom_call"', text))

"""From a profiler trace to intervals, and from intervals to numbers.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it with nothing but jax. :func:`load` keeps, per device plane
(``/device:TPU:<n>``), the events of its ``XLA Ops`` line (one event per
executed HLO operation, start and duration in nanoseconds; the ``Steps``,
``XLA Modules`` and ``Async XLA Ops`` lines repeat that time and are
left out) and the host's ``TraceAnnotation`` spans, all on the profiler's
one clock. Everything else is interval
arithmetic, checked on a recorded trace in
``tests/benchmark/test_trace_reduce.py``.
"""

import dataclasses
import glob
import gzip
import json
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all")


@dataclasses.dataclass
class Trace:
    """``devices``: device plane name -> [(name, start_ns, duration_ns)] of
    its operations, by start. ``host``: the same triples for the host's
    annotated spans. ``opcodes``: operation name -> HLO opcode.
    ``kernels``: names of the operations that are Mosaic custom calls."""
    devices: dict
    host: list
    opcodes: dict
    kernels: set

    def to_json(self):
        return {"devices": self.devices, "host": self.host,
                "opcodes": self.opcodes, "kernels": sorted(self.kernels)}

    @classmethod
    def from_json(cls, data):
        return cls({k: [tuple(e) for e in v]
                    for k, v in data["devices"].items()},
                   [tuple(e) for e in data["host"]], data["opcodes"],
                   set(data["kernels"]))

    def head(self, events_per_device):
        """The first events of every device and the host spans that end
        before the last of them does: a trace short enough to keep."""
        devices = {k: v[:events_per_device]
                   for k, v in self.devices.items()}
        end = max(s + d for v in devices.values() for _, s, d in v)
        names = {n for v in devices.values() for n, _, _ in v}
        return Trace(devices,
                     [e for e in self.host if e[1] + e[2] <= end],
                     {n: o for n, o in self.opcodes.items() if n in names},
                     self.kernels & names)


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# On this runtime an operation's event is named by its whole HLO
# instruction: "%fusion.14 = (f32[256]{...}, ...) fusion(...), kind=...".
_INSTRUCTION_RE = re.compile(r"^%?([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")


def parse_operation(text):
    """``(name, opcode, is_mosaic_kernel)`` of a traced operation."""
    m = _INSTRUCTION_RE.match(text)
    if not m:
        return text, "", False
    return (m.group(1), m.group(2),
            m.group(2) == "custom-call" and "tpu_custom_call" in text)


def load(path, host_span_names=("dispatch", "wait_loss", "stamp")):
    """Read an ``.xplane.pb`` (or a recorded ``.json``/``.json.gz`` of
    :meth:`Trace.to_json`)."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return Trace.from_json(json.load(f))
    if path.endswith(".json"):
        with open(path) as f:
            return Trace.from_json(json.load(f))
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, opcodes, kernels = {}, [], {}, set()
    parsed = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = devices.setdefault(plane.name, [])
                for ev in line.events:
                    text = ev.name
                    if text not in parsed:
                        parsed[text] = parse_operation(text)
                        name, opcode, kernel = parsed[text]
                        opcodes[name] = opcode
                        if kernel:
                            kernels.add(name)
                    events.append((parsed[text][0], int(ev.start_ns),
                                   int(ev.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host_span_names:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
    for events in devices.values():
        events.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return Trace(devices, host, opcodes, kernels)


# -------------------------------------------------------------- intervals

def union(intervals):
    """Sorted, disjoint ``[start, end)`` covering the same points."""
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [tuple(i) for i in out]


def total(intervals):
    return sum(end - start for start, end in intervals)


def subtract(intervals, holes):
    """The part of the disjoint ``intervals`` outside the disjoint
    ``holes``."""
    out = []
    holes = list(holes)
    for start, end in intervals:
        at = start
        for h0, h1 in holes:
            if h1 <= at or h0 >= end:
                continue
            if h0 > at:
                out.append((at, h0))
            at = max(at, h1)
        if at < end:
            out.append((at, end))
    return out


def spans(events, keep=lambda name: True):
    return union((s, s + d) for name, s, d in events if keep(name))


def collective_names(trace):
    """Names of the traced operations that are collectives, async halves
    (``-start``, ``-done``) included."""
    return {name for name, opcode in trace.opcodes.items()
            if opcode.removesuffix("-start").removesuffix("-done")
            in COLLECTIVE_OPCODES}


# ---------------------------------------------------------------- numbers

def busy_ns(events):
    """Nanoseconds in which at least one operation ran."""
    return total(spans(events))


def mean_busy_ns(trace):
    """:func:`busy_ns`, averaged over the devices."""
    busy = [busy_ns(events) for events in trace.devices.values()]
    return sum(busy) / len(busy)


def gaps(events):
    """``[(start, end)]`` in which no operation ran, between the first
    operation and the last."""
    busy = spans(events)
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]


def exposed_ns(events, keep):
    """Time of the operations ``keep`` selects during which no other
    operation ran on that device."""
    return total(subtract(spans(events, keep),
                          spans(events, lambda n: not keep(n))))


def top_operations(trace, n=10):
    """``[[name, seconds]]`` of the operations that took most device time,
    averaged over the devices, numbered suffixes kept."""
    sums = {}
    for events in trace.devices.values():
        for name, _, d in events:
            sums[name] = sums.get(name, 0) + d
    k = max(len(trace.devices), 1)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def longest_gaps(trace, n=10):
    """``[[what the host was doing, seconds]]`` for the longest idle gaps
    of the busiest-gapped device: the host span that covers most of each
    gap, or ``unattributed``."""
    worst = max(trace.devices.values(),
                key=lambda e: max((b - a for a, b in gaps(e)), default=0),
                default=[])
    ranked = sorted(gaps(worst), key=lambda g: g[0] - g[1])[:n]
    out = []
    for start, end in ranked:
        best, cover = "unattributed", 0
        for name, s, d in trace.host:
            c = min(end, s + d) - max(start, s)
            if c > cover:
                best, cover = name, c
        out.append([best, (end - start) / 1e9])
    return out

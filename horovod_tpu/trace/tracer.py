"""Per-rank collective span writer for the cluster trace.

Unlike ``common/timeline.py`` (rank 0 only, one chrome "process" per
tensor, a timebase private to the process), every rank writes its own
``trace.rank<N>.json`` here, and every span carries two things that make
the files mergeable:

* a ``clock_sync`` metadata event recording the wall-clock anchor of the
  file's (monotonic) timebase, so timestamps can be rebased onto any
  other rank's clock given an offset table (``trace/clock.py``);
* the collective **sequence id** the coordinator assigned to the fused
  op (``args.seq``), identical on every rank, so the merge can correlate
  "rank 2's execute span for seq 417" with everyone else's.

Phase vocabulary is FIXED — ``enqueue``/``negotiate``/``fuse``/
``execute``/``done`` — enforced here at emit time and by the source lint
in ``tests/test_metrics_lint.py``; ad-hoc phase strings would break the
merge's straggler attribution and every downstream dashboard.

Spans are buffered in memory (a few dicts per executed collective —
far below the event rate the Timeline's writer thread exists for) and
written as one JSON array at close; overflow beyond
``HOROVOD_TRACE_MAX_EVENTS`` drops-with-count like the timeline.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

from ..analysis.lockorder import make_lock
from ..common.config import _env_int

# The fixed phase vocabulary: one chrome "thread" per phase per rank.
# PHASES is the collective pipeline (what the controller emits, what the
# merge's straggler attribution consumes); SERVING_PHASES is the serving
# engine's iteration loop (schedule / prefill / decode, written to its
# own ``trace.serving.rank<N>.json`` — deliberately NOT matched by the
# merge's rank-file pattern, so serving spans never pollute collective
# straggler attribution). ALL_PHASES is the writer's legal set; both
# sub-vocabularies stay fixed and lint-enforced
# (tests/test_metrics_lint.py). New entries append — tids are
# positional and pinned by the merge golden file.
PHASES = ("enqueue", "negotiate", "fuse", "execute", "done")
SERVING_PHASES = ("schedule", "prefill", "decode")
ALL_PHASES = PHASES + SERVING_PHASES

DEFAULT_MAX_EVENTS = 1 << 20

TRACE_FILE_FMT = "trace.rank{rank}.json"
MERGED_TRACE_FILE = "merged_trace.json"
OFFSETS_FILE = "clock_offsets.json"
REPORT_FILE = "straggler_report.json"


def rank_trace_path(trace_dir: str, rank: int) -> str:
    return os.path.join(trace_dir, TRACE_FILE_FMT.format(rank=rank))


class TraceWriter:
    """Buffered span writer for one rank. Thread-safe; close() is
    idempotent (the shutdown trace exchange and the controller's
    failure-path cleanup may both reach it)."""

    def __init__(self, path: str, rank: int,
                 max_events: Optional[int] = None):
        self._path = path
        self.rank = int(rank)
        self._mono0 = time.monotonic()
        self._wall0 = time.time()  # hvdlint: disable=HVD004 (anchor)
        self._max = max_events if max_events is not None else max(
            1024, _env_int("HOROVOD_TRACE_MAX_EVENTS", DEFAULT_MAX_EVENTS))
        self._lock = make_lock("trace.writer")
        self._events: list = []
        self._dropped = 0
        self._closed = False

    # -- emit ---------------------------------------------------------------

    def span(self, phase: str, t0: float, t1: float, seq: Optional[int] = None,
             op: Optional[str] = None, **args) -> None:
        """One complete ("X") event. ``t0``/``t1`` are ``time.monotonic()``
        stamps from this process; they are stored relative to the file's
        monotonic origin, which the ``clock_sync`` anchor ties to wall
        time."""
        if phase not in ALL_PHASES:
            raise ValueError(
                f"unknown trace phase {phase!r}; the vocabulary is fixed: "
                f"{ALL_PHASES}")
        a = dict(args)
        if seq is not None:
            a["seq"] = int(seq)
        if op is not None:
            a["op"] = op
        event = {
            "name": phase,
            "ph": "X",
            "pid": self.rank,
            # One chrome thread per phase: overlapping spans of DIFFERENT
            # phases (enqueue of op B during execute of op A) land on
            # separate tracks instead of mis-nesting.
            "tid": ALL_PHASES.index(phase) + 1,
            "ts": int(round((t0 - self._mono0) * 1e6)),
            "dur": max(0, int(round((t1 - t0) * 1e6))),
            "args": a,
        }
        with self._lock:
            if self._closed:
                return
            if len(self._events) >= self._max:
                self._dropped += 1
                return
            self._events.append(event)

    # -- lifecycle ----------------------------------------------------------

    def _metadata(self) -> list:
        meta = [{
            # The anchor that makes this file mergeable: absolute wall
            # clock at the monotonic origin (ts == 0), plus the rank.
            "name": "clock_sync", "ph": "M", "pid": self.rank,
            "args": {"wall_anchor": self._wall0,
                     "monotonic_origin": self._mono0,
                     "rank": self.rank},
        }, {
            "name": "process_name", "ph": "M", "pid": self.rank,
            "args": {"name": f"rank {self.rank}"},
        }, {
            "name": "process_sort_index", "ph": "M", "pid": self.rank,
            "args": {"sort_index": self.rank},
        }]
        for i, phase in enumerate(ALL_PHASES):
            meta.append({"name": "thread_name", "ph": "M", "pid": self.rank,
                         "tid": i + 1, "args": {"name": phase}})
        return meta

    def close(self) -> Optional[str]:
        """Write the file (metadata + spans + trailer); returns the path,
        or None if a prior close already wrote it."""
        with self._lock:
            if self._closed:
                return None
            self._closed = True
            events = self._events
            self._events = []
            dropped = self._dropped
        out = self._metadata() + events
        out.append({"name": "trace_end", "ph": "M", "pid": self.rank,
                    "args": {"dropped_events": dropped,
                             "events": len(events)}})
        # Written beside its name and moved onto it: a reader that waits
        # for the file to exist (rank 0's merge at shutdown) never finds a
        # half-written one.
        tmp = self._path + ".tmp"
        with open(tmp, "w") as f:
            for i, ev in enumerate(out):
                f.write(("[\n" if i == 0 else ",\n") + json.dumps(ev))
            f.write("\n]\n")
        os.replace(tmp, self._path)
        return self._path

    @property
    def path(self) -> str:
        return self._path

    def read_bytes(self) -> bytes:
        """The written file's bytes (for the shutdown push over the
        wire). Empty when close() hasn't produced a file."""
        try:
            with open(self._path, "rb") as f:
                return f.read()
        except OSError:
            return b""

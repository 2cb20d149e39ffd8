"""Cut a profiler trace down to its first operations and write it in the
reduction's own recorded form (``.json.gz``), small enough to keep as a
test's fixture.

    python benchmarks/tools/trace_trim.py <dir or .xplane.pb> <events per device> <out.json.gz>
"""

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace_reduce  # noqa: E402


def main():
    path, events, out = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    if os.path.isdir(path):
        path = trace_reduce.newest_xplane(path)
    trace = trace_reduce.load(path).head(events)
    with gzip.open(out, "wt") as f:
        json.dump(trace.to_json(), f, separators=(",", ":"))
    print(f"{out}: {os.path.getsize(out)} bytes, "
          f"{ {k: len(v) for k, v in trace.devices.items()} } operations, "
          f"{len(trace.host)} host spans")


if __name__ == "__main__":
    main()

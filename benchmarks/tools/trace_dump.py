"""Print what a profiler trace holds, to be read by hand before (and
after) code is written against it: every plane, its lines, how many
events each has, the stats an event carries, and a line's first events.

    python benchmarks/tools/trace_dump.py <dir or .xplane.pb> [events]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace_reduce  # noqa: E402


def main():
    from jax.profiler import ProfileData

    path = sys.argv[1]
    show = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    if os.path.isdir(path):
        path = trace_reduce.newest_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:show]:
                stats = {k: (str(v)[:60]) for k, v in ev.stats}
                print(f"    {ev.name[:70]!r} start={ev.start_ns:.0f} "
                      f"dur={ev.duration_ns:.0f} {stats}")


if __name__ == "__main__":
    main()

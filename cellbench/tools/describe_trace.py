"""Print what a profiler trace holds: planes, lines, event counts and the
first events of each line. Look at one trace by hand before trusting the
reduction in ``cellbench/trace.py``.

    python3 cellbench/tools/describe_trace.py <dir or .xplane.pb>
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from cellbench import trace  # noqa: E402


def main() -> None:
    from jax.profiler import ProfileData

    path = sys.argv[1]
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:4]:
                print(f"      {e.name[:90]!r} start {e.start_ns:.0f} "
                      f"dur {e.duration_ns:.0f} {dict(e.stats)}"[:300])


if __name__ == "__main__":
    main()

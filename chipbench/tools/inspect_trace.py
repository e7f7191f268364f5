"""Print the device ops of a profiler trace, the most time first, with
their stats, and the harness spans it holds.

    python chipbench/tools/inspect_trace.py <trace dir> [top]
"""
import os
import sys
from collections import defaultdict

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]

from chipbench import trace as tr  # noqa: E402


def main(argv):
    path = tr.newest_xplane(argv[1])
    top = int(argv[2]) if len(argv) > 2 else 40
    import jax
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        print("plane", repr(plane.name),
              [(line.name, sum(1 for _ in line.events)) for line in plane.lines][:12])
    ops, spans = tr.load(path)
    secs = defaultdict(float)
    detail = {}
    for o in ops:
        secs[o.name] += (o.end - o.start) * 1e-9
        detail.setdefault(o.name, o.detail)
    print(f"{path}: {len(ops)} device ops, {len(spans)} harness spans, "
          f"devices {sorted({o.device for o in ops})}")
    for name, s in sorted(secs.items(), key=lambda kv: -kv[1])[:top]:
        print(f"{s:12.6f} s  {name}  |  {detail[name][:300]}")
    names = defaultdict(int)
    for sp in spans:
        names[sp.name] += 1
    print("spans:", dict(names))


if __name__ == "__main__":
    main(sys.argv)

#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, and each line's top events.

    python3 benchmark/tools/trace_dump.py <file.xplane.pb> [events per line]
"""
import sys
from jax.profiler import ProfileData


def main(path: str, k: int = 12) -> None:
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            total: dict = {}
            n = 0
            for e in line.events:
                n += 1
                rec = total.setdefault(e.name, [0.0, 0])
                rec[0] += e.duration_ns
                rec[1] += 1
            print(f"  LINE {line.name!r}: {n} events, {len(total)} names")
            for name, (ns, cnt) in sorted(total.items(),
                                          key=lambda kv: -kv[1][0])[:k]:
                print(f"     {ns / 1e6:12.3f} ms  x{cnt:<7d} {name[:110]}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)

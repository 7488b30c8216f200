"""Print the shape of a profiler trace: its planes, their lines, the
number of events on each and the commonest event names.

    python3 bench/tools/trace_dump.py <file.xplane.pb>
"""
import collections
import sys


def main(path: str) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            t = [(e.start_ns, e.duration_ns) for e in evs[:2]]
            print(f"  LINE {line.name!r} events={len(evs)} first={t}")
            for name, c in names.most_common(8):
                print(f"      {c:7d} {name[:120]}")


if __name__ == "__main__":
    main(sys.argv[1])

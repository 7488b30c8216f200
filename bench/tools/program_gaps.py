"""Print where a serving trace's device idle time goes, from the
program's spans (``bench/program_spans.py``): for each host thread, the
device idle seconds by the thread's innermost program or ``bench.*``
span at the time; each span's median self time; and the median of each
flush phase over the flushes wholly inside the stretch.

    python3 bench/tools/program_gaps.py <file.xplane.pb>
"""
from __future__ import annotations

import pathlib
import statistics
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def main(path: str) -> int:
    from bench import program_spans as ps

    t = ps.reduce_file(path)
    if t is None:
        print("no program flush or no device plane in the trace")
        return 1
    idle = t.idle_s([t.stretch])
    print(f"stretch {t.window_s:.6f} s, device idle {idle:.6f} s "
          f"({idle / t.window_s:.4f}); device_idle_host "
          f"{t.idle_host_share():.4f}")
    for line, acc in t.idle_by_span().items():
        print(f"thread {line}: device idle by innermost span")
        for owner, v in sorted(acc.items(), key=lambda kv: -kv[1]):
            if v > 0:
                print(f"  {owner:28s} {v:.6f} s  {v / idle:7.2%} of idle")
    print("span self time over the spans wholly inside the stretch")
    for name, xs in sorted(t.self_times().items()):
        print(f"  {name:28s} n={len(xs):6d}  median "
              f"{statistics.median(xs) * 1e3:.4f} ms  max "
              f"{max(xs) * 1e3:.4f} ms")
    fl = t.flushes()
    print(f"flushes wholly inside the stretch: {len(fl)}")
    for k in ps.PHASES:
        if fl:
            print(f"  {k:28s} median "
                  f"{statistics.median(f[k] for f in fl) * 1e3:.4f} ms")
    spans = [sum(1 for _ in f.walk()) for r in t.batcher() for f in r.walk()
             if f.name == ps.FLUSH]
    if spans:
        print(f"spans per flush (bench.* included): median "
              f"{statistics.median(spans)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

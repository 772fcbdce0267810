"""Benchmark runner — one module per paper table. Prints CSV lines
``name,...metrics`` and a summary. Usage: python -m benchmarks.run [tables]
"""
from __future__ import annotations

import sys
import time


TABLES = ("accuracy", "ablation", "adaround", "time", "approx_precision",
          "kernels", "reload", "serving")


def main() -> None:
    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    names = sys.argv[1:] or list(TABLES)
    t00 = time.time()
    for name in names:
        mod = __import__(f"bench_{name}")
        print(f"### bench_{name} " + "#" * 40, flush=True)
        t0 = time.time()
        mod.run(report=lambda s: print(s, flush=True))
        print(f"### bench_{name} done in {time.time()-t0:.1f}s", flush=True)
    print(f"### all benches done in {time.time()-t00:.1f}s")


if __name__ == '__main__':
    main()

"""Readings of the comparison that decides ``correct``, for setting its
limits: the program's, and the control's, over many seeds in one
process.

    python bench/control.py --workload <name> --seconds <s> --seeds 1 2 3

For each seed the cell is set up and driven through a window of
``--seconds`` as a benchmark run does; then every number the comparison
can be decided on is printed for the program (``"who": "program"``)
and for the control: the plain reference computed in the precision
below the configuration's, put in the program's place.
One JSON object per line.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as harness  # noqa: E402

from bench.lib import catalog  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    root = harness.ROOT
    bench = catalog.benchmark(root)
    bench_dir = root / "bench"
    entry = catalog.cell(bench, args.workload)
    harness.check_chips(entry["chips"], catalog.peaks(bench_dir))
    harness.enable_cache(root / ".bench_cache")
    drv = catalog.driver(catalog.traffic(entry["traffic"], bench_dir)["driver"],
                         bench_dir)
    for seed in args.seeds:
        t0 = time.perf_counter()
        cell = drv.setup(catalog.spec(bench, args.workload, seed, bench_dir))
        cell.window(args.seconds)
        cell.release()
        for who in ("program", "control"):
            line = {"workload": entry["name"], "seed": seed, "who": who,
                    **{name: value for name, value, _ in
                       cell.checks(who == "control")},
                    "elapsed_s": time.perf_counter() - t0}
            print(json.dumps(line), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration, traffic, driver and per-layer metrics are
found by name under ``bench/`` (``bench/lib/catalog.py``).  A run:

  1. refuses to start unless JAX's first device is a TPU whose
     ``device_kind`` is in ``bench/peaks.json`` and at least the cell's
     chips are present (exit 2, no result line);
  2. sets up: builds the system under test from the seed, compiles it
     (through the persistent compilation cache in ``.bench_cache/jax``
     inside the checkout) and drives it through its warm-up.  ``setup_s``
     runs from process start to the end of the warm-up;
  3. measures for ``--seconds``: ``--trace 0`` reports the cell's
     end-to-end metrics; ``--trace 1`` records a profiler trace of the
     window (at most the traffic's ``trace_seconds``) and reports the
     per-layer metrics read from it;
  4. reads the peak device memory, frees the program's state, and
     compares what the timed path produced with the configuration's
     plain reference.  Each number compared is printed beside its limit,
     as the last lines of standard error and under the result's last
     key, ``checks``.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import catalog  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no accelerator of a known kind, or too few chips."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def check_chips(chips: int, peaks: dict) -> dict:
    """The first device's entry in the peaks table; raises ``NoChip``
    unless it is a TPU of a listed kind and ``chips`` are present."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX's first device is {dev.platform!r}, not a TPU")
    if dev.device_kind not in peaks:
        raise NoChip(f"device kind {dev.device_kind!r} is not in "
                     f"bench/peaks.json")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips present, the cell needs {chips}")
    return peaks[dev.device_kind]


def enable_cache(cache_dir: Path) -> str:
    import jax

    d = cache_dir / "jax"
    d.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(d))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(d)


def memory_peak(chips: int, program_bytes: int | None) -> int:
    """The peak on the fullest chip: the runtime's ``peak_bytes_in_use``,
    or the largest compiled program's footprint where that is larger
    (the runtime counter leaves out a program's temp on this stack)."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return max(peak, int(program_bytes or 0))


def fmt_checks(checks: list[tuple[str, float, float]]) -> dict:
    return {name: {"value": value, "limit": limit}
            for name, value, limit in checks}


def passes(checks: list[tuple[str, float, float]]) -> bool:
    return all(math.isfinite(v) and v <= lim for _, v, lim in checks)


def run(args: argparse.Namespace, root: Path = ROOT,
        require_chip: bool = True) -> dict:
    """One run of one cell; returns the result object."""
    bench = catalog.benchmark(root)
    bench_dir = root / "bench"
    entry = catalog.cell(bench, args.workload)
    peaks_table = catalog.peaks(bench_dir)

    import jax

    if require_chip:
        peak = check_chips(entry["chips"], peaks_table)
    else:
        peak = next(iter(peaks_table.values()))
    dev = jax.devices()[0]
    cache = enable_cache(root / ".bench_cache")

    from repro.launch.compile_cache import CompileCounter

    spec = catalog.spec(bench, args.workload, args.seed, bench_dir)
    trf = spec.traffic
    drv = catalog.driver(trf["driver"], bench_dir)
    with CompileCounter() as cc:
        cell = drv.setup(spec)
    setup_s = time.perf_counter() - T_START
    log(f"setup setup_s={setup_s} cache_dir={cache} "
        + " ".join(f"{k}={v}" for k, v in cc.summary().items()))

    e2e = catalog.cell_metrics(bench, entry["name"], "end_to_end")
    layer = catalog.cell_metrics(bench, entry["name"], "per_layer")
    out: dict = {}
    with CompileCounter() as cw:
        if args.trace:
            seconds = min(args.seconds, float(trf.get("trace_seconds",
                                                      args.seconds)))
            tdir = root / ".bench_cache" / "trace" / entry["name"]
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench_window"):
                    res = cell.window(seconds)
            finally:
                jax.profiler.stop_trace()
            from bench.lib import trace as tracelib

            t0 = time.perf_counter()
            tr = tracelib.load(tracelib.find_xplane(str(tdir)))
            counts = dict(res["counts"], peaks=peak, chips=entry["chips"])
            metrics = {}
            for m in layer:
                v = catalog.metric(m["name"], bench_dir).read(tr, counts)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            out["busy_s"] = tr.busy_s()
            out["window_s"] = tr.window_s
            out["breakdown"] = {"device_ops": tr.top_ops(10),
                                "idle_gaps": tr.idle_gaps(10)}
            log(f"trace window_s={tr.window_s} busy_s={tr.busy_s()} "
                f"reduce_s={time.perf_counter() - t0}")
        else:
            res = cell.window(args.seconds)
            metrics = {}
            for m in e2e:
                if m["name"] == "setup_s":
                    v = setup_s
                else:
                    v = res["e2e"][m["name"]]
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if cw.misses or cw.hits:
        log(f"WARNING: {cw.misses + cw.hits} programs compiled or loaded "
            f"inside the measured window")
    log("window " + " ".join(f"{k}={v}" for k, v in res["e2e"].items()))

    mem = memory_peak(entry["chips"], cell.program_bytes())
    cell.release()
    checks = cell.checks()
    for name, value, limit in checks:
        print(f"[check] {name} = {value} (limit {limit})", file=sys.stderr,
              flush=True)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": entry["chips"], "memory_peak_bytes": mem}
    if args.trace:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["window_s"]
    result = {
        "correct": passes(checks) and res["failed"] == 0,
        "attempted": res["attempted"],
        "failed": res["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        result["breakdown"] = out["breakdown"]
    result["checks"] = fmt_checks(checks)
    return result


def parse(argv: list[str] | None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        log(f"no result: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Record the small chip trace that tests/bench/test_bench_phases.py reads.

    python tests/bench/record_trace.py tests/bench/data/ant_collect_scoped_v5e.xplane.pb

Runs on one TPU chip.  Two calls of a 4-step random collect (Ant-v3,
256 lanes, 64 per recv, the ``device`` engine), traced under the
harness's ``bench_window`` annotation with its ``collect_call`` and
``wait_last_call`` spans, then one read of the pool's counters, as the
sim driver reads them at the end of its window.  The program's phase
scopes and the pool's host spans land in the trace; the file is about
1 MB.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main(out: str) -> int:
    import jax

    import repro
    from repro.core.xla_loop import build_random_collect_fn

    if jax.devices()[0].platform != "tpu":
        print("no TPU: nothing recorded", file=sys.stderr)
        return 2
    pool = repro.make("Ant-v3", num_envs=256, batch_size=64,
                      engine="device", seed=0)
    key = jax.random.PRNGKey(0)
    ps, ts = pool.reset(key)
    collect = build_random_collect_fn(pool, num_steps=4)
    for i in range(2):                        # compile and warm up
        ps, ts, _, _ = collect(ps, None, ts, jax.random.fold_in(key, i))
    jax.block_until_ready((ps, ts))
    pool.stats(ps)
    with tempfile.TemporaryDirectory() as tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench_window"):
                for i in range(2):
                    with jax.profiler.TraceAnnotation("collect_call"):
                        ps, ts, _, _ = collect(ps, None, ts,
                                               jax.random.fold_in(key, 9 + i))
                with jax.profiler.TraceAnnotation("wait_last_call"):
                    jax.block_until_ready((ps, ts))
                pool.stats(ps)
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        shutil.copy(max(paths, key=os.path.getmtime), out)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

"""Record the small TPU trace that ``test_bench_trace.py`` reads.

    python3 tests/bench/record_trace.py OUT.xplane.pb
    gzip -9 -c OUT.xplane.pb > tests/bench/data/tpu_small.xplane.pb.gz

On a TPU, from the root of a checkout. Two ``fit`` calls of a tiny MTGC
federation (the CIFAR CNN on 8x8 images, 2 groups of 2 clients, batch 4)
inside the benchmark's own host spans, with a 50 ms sleep between them
inside the window and outside any ``fit`` span: a known idle gap.
"""
from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT), str(Path(__file__).parent)]

import jax  # noqa: E402

from bench import run  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench_cells import tiny_cell  # noqa: E402
from repro.api import fit  # noqa: E402

SLEEP_S = 0.05


def main(out: str) -> int:
    run.check_devices(1)
    cell = tiny_cell("cnn-cifar10.full")
    _, engine, data, state, _ = run.set_up(cell, 7)
    state, data, _ = run.drive_checked(cell, engine, data, state)
    tdir = ROOT / ".bench_trace_record"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for i in range(2):
            with jax.profiler.TraceAnnotation(tr.FIT_SPAN):
                state, hz = fit(engine, data, 1, state=state)
            data = hz.data
            if i == 0:
                time.sleep(SLEEP_S)
        with jax.profiler.TraceAnnotation(tr.SYNC_SPAN):
            jax.block_until_ready(state)
    jax.profiler.stop_trace()
    shutil.copy(next(tdir.rglob("*.xplane.pb")), out)
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

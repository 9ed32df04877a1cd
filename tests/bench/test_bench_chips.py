"""A cell that asks for four chips, run through the harness on four virtual
CPU devices: ``lm-tiny`` with ``chips: 4`` (``data/lm-tiny/four_chips.json``),
not a cell of ``BENCHMARK.json``. The run compares as on one chip and its
result line reads every chip's memory.

The devices are fixed when XLA starts, so the run is a child process with
``--xla_force_host_platform_device_count=4``:

    python3 tests/bench/test_bench_chips.py <dir>   # prints the result line
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def test_four_chip_cell_is_correct_and_reads_every_chip(tmp_path):
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        flags + " --xla_force_host_platform_device_count=4").strip(),
        PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT), str(HERE)]))
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"], proc.stderr[-4000:]
    device = result["device"]
    assert device["count"] == 4
    # The CPU reports no memory: one entry a chip, each None.
    assert device["memory_peak_bytes_per_chip"] == [None] * 4
    assert device["memory_peak_bytes"] is None


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT), str(HERE)]
    from bench import run
    from bench_cells import LM_WORKLOAD, lm_checkout, tiny_cell

    import jax

    assert len(jax.devices()) == 4, jax.devices()
    root = lm_checkout(Path(sys.argv[1]), four_chips=True)
    cell = tiny_cell(LM_WORKLOAD, root)
    assert cell.workload["chips"] == 4
    result, lines = run.run_cell(cell, 2**31 + 41, 0.3, trace=False,
                                 require_tpu=False)
    print("\n".join(lines), file=sys.stderr)
    print(json.dumps(result))

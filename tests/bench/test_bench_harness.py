"""The harness off the chip: it refuses to measure without a TPU, refuses to
run without the program, and its comparison fails runs whose timed path is
broken underneath, and the lower-precision control.

The fault tests skip only the harness's look for a chip and drive the rest
of a run (set-up, checked calls, window, reference, comparison) at a tiny
size, against the cell's own limits.
"""
from __future__ import annotations

import json
import shutil
import subprocess
import sys

import jax
import pytest

from bench import check, run
from bench_cells import (WORKLOADS, allow_cpu_peaks, control_cell,
                         plant_fault, tiny_cell)


def test_refuses_without_a_tpu_and_names_the_platform(capsys):
    rc = run.main(["--workload", "cnn-cifar10.full", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "needs a TPU" in err and "'cpu'" in err


def test_check_devices_counts_chips():
    with pytest.raises(run.NoAccelerator, match="platform 'cpu'"):
        run.check_devices(1)


def test_refuses_in_a_checkout_without_the_program(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "cnn-cifar10.full",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload, monkeypatch, tmp_path):
    allow_cpu_peaks(monkeypatch)
    cell = tiny_cell(workload)
    result, lines = run.run_cell(cell, 2**31 + 9, 0.3, trace=True,
                                 require_tpu=False, trace_dir=tmp_path)
    assert result["correct"], lines
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(cell.limits) <= set(check.NUMBERS)
    assert result["attempted"] >= 1 and result["failed"] == 0
    json.dumps(result)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    allow_cpu_peaks(monkeypatch)
    cell = tiny_cell(workload)
    plant_fault(monkeypatch, fault, cell)
    result, lines = run.run_cell(cell, 1, 0.2, trace=False, require_tpu=False)
    assert not result["correct"], lines


@pytest.mark.parametrize("workload", WORKLOADS)
def test_bfloat16_control_is_not_correct(workload):
    """The reference computed in bfloat16, the precision below the
    configuration's float32, in the program's place, held to the cell's
    limits, at the size its ``tiny.json`` gives the control (the CNN at its
    published widths, the ResNet cut)."""
    cell = control_cell(workload)
    seed = 1
    fed, _, _, _, x0 = run.set_up(cell, seed)
    ref = run.reference_readout(cell, seed, fed, x0)
    control = run.reference_readout(cell, seed, fed, x0, dtype="bfloat16")
    correct, checks = check.judge(check.readings(x0, control, ref),
                                  cell.limits)
    assert not correct, checks


@pytest.mark.parametrize("workload", WORKLOADS)
def test_y_left_out_of_the_local_step_is_not_correct(workload):
    """The reference with y left out of the local step (y itself still
    updated), in the program's place, held to the cell's limits. y is 0
    through the first round, so only the numbers read after the last
    checked call can see it."""
    cell = tiny_cell(workload)
    seed = 3
    fed, _, _, _, x0 = run.set_up(cell, seed)
    ref = run.reference_readout(cell, seed, fed, x0)
    faulty = run.reference_readout(cell, seed, fed, x0, drop_y=True)
    read = check.readings(x0, faulty, ref)
    assert read["z"] == read["y"] == 0.0, read
    correct, checks = check.judge(read, cell.limits)
    assert not correct, checks


def test_device_peak_counts_the_programs_reserved_scratch():
    assert run.device_peak_bytes([{"peak_bytes_in_use": 3,
                                   "peak_bytes_reserved": 4}]) == 7
    assert run.device_peak_bytes([{"peak_bytes_in_use": 3}]) == 3
    assert run.device_peak_bytes([{}]) is None


def test_device_peak_reads_the_fullest_chip():
    """Four chips' ``memory_stats()``: the fullest counts, in use plus
    reserved, whichever chip it is."""
    stats = [{"peak_bytes_in_use": 9, "peak_bytes_reserved": 1},
             {"peak_bytes_in_use": 6, "peak_bytes_reserved": 8},
             {"peak_bytes_in_use": 12},
             {"peak_bytes_in_use": 2, "peak_bytes_reserved": 2}]
    assert run.device_peak_bytes(stats) == 14
    assert [run.chip_peak_bytes(s) for s in stats] == [10, 14, 12, 4]
    assert run.device_peak_bytes(stats[:1] + [{}] * 3) == 10
    assert run.device_peak_bytes([{}] * 4) is None


def test_compile_cache_stays_in_the_checkout(monkeypatch, tmp_path):
    """A cache directory set for the machine is not used: another checkout
    could share it and hand this one its executables with its own scope
    names."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    try:
        assert run.enable_compile_cache() == str(run.ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(run.CACHE_DIR)
    finally:
        for n, v in before.items():
            jax.config.update(n, v)

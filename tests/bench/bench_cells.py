"""Shared pieces of the benchmark's tests: cells cut to a size a CPU test
holds, and a peak table entry for the CPU so a traced run can be driven
off the chip."""
from __future__ import annotations

from bench import peaks, run

# Each configuration at a tiny size: same code paths, few clients, small
# images and widths.
TINY = {
    "cnn-cifar10": {"image_shape": [8, 8, 3], "levels": [2, 2]},
    "resnet18gn-cifar100": {"image_shape": [8, 8, 3], "levels": [2, 2],
                            "widths": [8, 16], "blocks_per_stage": 1,
                            "gn_groups": 4},
}
WORKLOADS = ("cnn-cifar10.full", "resnet18gn-cifar100.full")


def tiny_cell(workload: str) -> run.Cell:
    """``workload`` from ``BENCHMARK.json`` with its configuration and
    traffic cut to a CPU test's size; its limits are the cell's own."""
    cell = run.load_cell(workload)
    cell.config.update(TINY[cell.workload["config"]])
    cell.traffic.update(batch=4, samples_per_client=40)
    return cell


def allow_cpu_peaks(monkeypatch) -> None:
    """Lets ``run_cell`` look up peaks for the CPU, so that a test can
    drive a traced run off the chip."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))

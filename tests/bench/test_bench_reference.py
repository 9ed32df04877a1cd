"""The plain reference against the engine at a tiny size on the CPU.

On the CPU both run in float32 throughout, so the program's first checked
rounds and the reference's agree to round-off: a reference that drifted from
the algorithm the engine runs would read far above these bounds.
"""
from __future__ import annotations

import numpy as np
import pytest

from bench import check, feed, run
from bench_cells import WORKLOADS, program_and_reference_loss, tiny_cell


@pytest.mark.parametrize("workload", WORKLOADS)
def test_reference_forward_matches_program_model(workload):
    got, want = program_and_reference_loss(tiny_cell(workload))
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_reference_rounds_match_engine(workload):
    cell = tiny_cell(workload)
    seed = 2**31 + 77
    fed, engine, data, state, x0 = run.set_up(cell, seed)
    _, _, program = run.drive_checked(cell, engine, data, state)
    ref = run.reference_readout(cell, seed, fed, x0)
    read = check.readings(x0, program, ref)
    assert read["loss"] < 1e-5, read
    assert read["grad"] < 1e-5, read
    assert read["delta"] < 1e-4, read
    assert read["grad_still"] == 0


def test_feed_repeats_the_packers_rows():
    """The rows the reference trains on are the rows the program packed."""
    cell = tiny_cell("cnn-cifar10.full")
    seed = 91
    fed, engine, data, _, _ = run.set_up(cell, seed)
    rows = feed.shard_rows(fed.indices, cell.traffic["shards"], 5,
                           cell.traffic["batch"], feed.pack_rng(seed))
    np.testing.assert_array_equal(np.asarray(data.arrays["x"]), fed.x[rows])
    np.testing.assert_array_equal(np.asarray(data.arrays["y"]), fed.y[rows])


def test_same_seed_same_inputs_and_large_seeds():
    cfg = {"levels": [2, 3], "image_shape": [4, 4, 3], "num_classes": 10}
    traffic = {"samples_per_client": 30, "noise": 1.0,
               "partition": "both_noniid", "alpha": 0.5, "min_per_client": 4}
    big = 2**31 + 2**20 + 5
    a = feed.make_federation(cfg, traffic, big)
    b = feed.make_federation(cfg, traffic, big)
    c = feed.make_federation(cfg, traffic, big + 1)
    np.testing.assert_array_equal(a.x, b.x)
    assert not np.array_equal(a.x, c.x)
    assert a.x.shape == (180, 48) and a.x.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(feed.jax_key(big, "weights")),
                                  np.asarray(feed.jax_key(big, "weights")))


def test_round_shards_follow_the_driver_draw():
    """``round_shards`` names the shards the program's driver selects: the
    reference's first round, fed from it, matches the program's."""
    cell = tiny_cell("cnn-cifar10.full")
    cell.traffic["checked_calls"] = 1
    seed = 5
    fed, engine, data, state, x0 = run.set_up(cell, seed)
    _, _, program = run.drive_checked(cell, engine, data, state)
    ref = run.reference_readout(cell, seed, fed, x0)
    np.testing.assert_allclose(program.losses, ref.losses, rtol=1e-5)

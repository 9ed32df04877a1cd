"""Each configuration's FLOP count against a count by hand, its weights
against its published parameter count, the corrected update's work, and the
peaks table."""
from __future__ import annotations

import functools

import jax
import numpy as np
import pytest

from bench import rooflines, run
from bench.peaks import PEAKS, peaks_for

# conv1 32*32 * 5*5*3 * 32 = 2,457,600 MACs; conv2 on the pooled 16x16:
# 16*16 * 5*5*32 * 64 = 13,107,200; fc1 (8*8*64 = 4,096) x 512 = 2,097,152;
# out 512 x 10 = 5,120. 17,667,072 MACs, 2 FLOPs each.
CNN_FORWARD_FLOPS = 2 * (2_457_600 + 13_107_200 + 2_097_152 + 5_120)

# stem 32*32 * 9*3 * 64 = 1,769,472; stage 1 (32x32, 64 -> 64): 4 convs of
# 32*32 * 9*64*64 = 37,748,736; each later stage at a quarter of the
# positions and twice the width: c1 18,874,368 + c2 37,748,736 + a 1x1
# projection 2,097,152 + a second block of 2 x 37,748,736 = 134,217,728;
# head 512 x 100 = 51,200.
RESNET_FORWARD_FLOPS = 2 * (1_769_472 + 4 * 37_748_736 + 3 * 134_217_728
                            + 51_200)

# Published parameter counts of the two models at these sizes.
PARAMS = {"cnn-cifar10": 2_156_490, "resnet18gn-cifar100": 11_223_140}


@pytest.mark.parametrize("workload,flops", [
    ("cnn-cifar10.full", CNN_FORWARD_FLOPS),
    ("resnet18gn-cifar100.full", RESNET_FORWARD_FLOPS),
], ids=["cnn", "resnet"])
def test_forward_flops_match_hand_count(workload, flops):
    cell = run.load_cell(workload)
    assert cell.model.forward_flops(cell.config) == flops


def test_round_flops_counts_every_active_sample():
    cell = run.load_cell("cnn-cifar10.full")
    # 3 x forward, 50 samples a step, 10 local steps, 100 clients.
    assert run.rounds_flops(cell) == 3 * CNN_FORWARD_FLOPS * 50 * 10 * 100


@pytest.mark.parametrize("workload", ["cnn-cifar10.full",
                                      "resnet18gn-cifar100.full"])
def test_weights_have_published_size_and_program_layout(workload):
    cell = run.load_cell(workload)
    shapes = jax.eval_shape(functools.partial(cell.ref.init_weights,
                                              cell.config),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert n == PARAMS[cell.workload["config"]]
    # build_engine refuses weights the program's model does not take.
    assert run.build_engine(cell).spec.levels == tuple(cell.config["levels"])


def test_update_work_matches_hand_count():
    """The corrected local step of the CNN at 10 x 10 clients: 100 replicas
    read x, g, z and write x; 10 groups read y; 4 bytes a number."""
    n = PARAMS["cnn-cifar10"]
    assert rooflines.update_bytes(n, 10, 10) == 3_536_643_600
    assert rooflines.update_bytes(n, 10, 10) == 4 * n * (4 * 100 + 10)
    assert rooflines.update_bytes(n, 10, 10, itemsize=2) == 1_768_321_800
    assert rooflines.update_flops(n, 10, 10) == 862_596_000


def test_peaks_table_is_keyed_by_device_kind():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        peaks_for("TPU v9 imaginary")
    assert "cpu" not in PEAKS

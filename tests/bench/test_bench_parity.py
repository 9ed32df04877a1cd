"""The cells that classify rows read as they did before the harness took
causal LMs: at seed 0 on the tiny CNN and ResNet cells, the first round's
batches, the weights, and the plain reference's losses, global models and
corrections over the checked rounds hash to what the harness gave when
these hashes were recorded.

XLA on the CPU sums in an order that depends on how many cores it sees, so
the readout runs in a child process held to one core; the batches and
weights come from NumPy and JAX's counter-based generator and do not
depend on it.

    python3 tests/bench/test_bench_parity.py   # prints the readout's hashes
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

PINNED = {
    "cnn-cifar10.full": {
        "batch": "ae62bcf995c0a9d61959647c8b98af3af3f7e9e0a6cc8a13bb023844761ac5ba",
        "x0": "4452dd15b6b9c987a79c11958cb06b494d3d047915d13a250ab41640da282d4f",
        "losses": "a2288a1216df2ba936c60c7dcb61d9b5900c2c5f38c0126c740b81ce31f73735",
        "first": "f5dd79daacf0bdf7d7f006c34a0bd6b7b36b16080ec39183e357c8e6e7e3bf46",
        "last": "7291a675d90eb1b044f407dd69df2389eb071c6458e43e6a46a72d6f477d7b6f",
        "corrections": "f0646a27322ad9389a9088ac7757cb3b8acc285bbe423995d6c8e16a8d7959c6",
        "corrections_last": "b2a9cc000b86a55cff352f53e018532a24878c39633cd6396b218223e149be75",
    },
    "resnet18gn-cifar100.full": {
        "batch": "0309912d5873318abacf7922a5e1520529cc50b93867cb8f7e89b95714b96235",
        "x0": "40e51edabd44849870408dff3d23d8ef024ef437eec92e71e95647829e88e070",
        "losses": "498b4b5df0ce9d3e3f5945d39ac12b42fcab5713b9d03b1688b8ee251cc4906a",
        "first": "7e1e89f0ff193e9a050743da74e685f579edd3514b96c51416e7eb03e30219c5",
        "last": "5736a437f849c2379a182bd5e6e15d7df7b89081ebbb74558f98f5a6fa001975",
        "corrections": "0c389d1f50a21c563933d0f62264f192a6ffb7913b6efd84453b9c36f5895e44",
        "corrections_last": "9a5a99a1eb8e0304c87a7ec878953f8eb8dfea24682f8e1e059e3c14291e0441",
    },
}
PARTS = tuple(PINNED["cnn-cifar10.full"])


def digest(obj) -> str:
    """sha256 over a nest of dicts (by sorted key), sequences, floats (by
    ``repr``) and arrays (dtype, shape and bytes)."""
    import numpy as np

    h = hashlib.sha256()

    def walk(o):
        if isinstance(o, dict):
            for k in sorted(o):
                h.update(str(k).encode())
                walk(o[k])
        elif isinstance(o, (list, tuple)):
            for v in o:
                walk(v)
        elif isinstance(o, float):
            h.update(repr(o).encode())
        else:
            a = np.ascontiguousarray(np.asarray(o))
            h.update(f"{a.dtype.str}{a.shape}".encode())
            h.update(a.tobytes())

    walk(obj)
    return h.hexdigest()


def readout_hashes() -> dict:
    """The hashes of ``PARTS`` for every workload of ``PINNED``, from the
    harness as it is."""
    import jax

    from bench import feed, run
    from bench_cells import tiny_cell

    out = {}
    for workload in PINNED:
        cell = tiny_cell(workload)
        cfg, traffic, seed = cell.config, cell.traffic, 0
        G, K = cfg["levels"]
        sched = traffic["spec"]["schedule"]
        E, H = sched["group_rounds"], sched["local_steps"]
        fed = feed.make_federation(cfg, traffic, seed)
        arrays, rows = feed.packed_slots(fed, traffic, H, feed.pack_rng(seed))
        sids = feed.round_shards(feed.jax_key(seed, "select"), 1, E, G, K,
                                 traffic["shards"])
        # One microbatch a local step: drop its axis, [E, H, G, K, B, ...].
        batch = {k: v[:, :, :, :, 0] for k, v in
                 feed.round_batches(arrays, rows, sids[0]).items()}
        x0 = jax.device_get(jax.jit(functools.partial(
            cell.ref.init_weights, cfg))(feed.jax_key(seed, "weights")))
        ref = run.reference_readout(cell, seed, fed, x0)
        out[workload] = {
            "batch": digest(batch), "x0": digest(x0),
            "losses": digest(ref.losses), "first": digest(ref.first),
            "last": digest(ref.last), "corrections": digest(ref.corrections),
            "corrections_last": digest(ref.corrections_last)}
    return out


@pytest.fixture(scope="module")
def hashes():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(ROOT / "src"), str(ROOT), str(HERE)]))
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve())], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("part", PARTS)
@pytest.mark.parametrize("workload", tuple(PINNED))
def test_reads_as_recorded(hashes, workload, part):
    assert hashes[workload][part] == PINNED[workload][part]


if __name__ == "__main__":
    # One core, before anything starts XLA's thread pools.
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
    sys.path[:0] = [str(ROOT / "src"), str(ROOT), str(HERE)]
    print(json.dumps(readout_hashes()))

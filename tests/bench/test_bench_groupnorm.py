"""``step.groupnorm_ms``, the device time under the packed GroupNorm's
scope ``groupnorm``: the ResNet cell's round names both GroupNorm passes
with it inside ``client_step``, and the reader reads nothing where no op
carries it (the CNN, which has no GroupNorm)."""
from __future__ import annotations

import re
from pathlib import Path

import pytest

from bench import run
from bench import trace as tr
from bench_cells import tiny_cell

SCOPE = "groupnorm"
SCOPED = Path(__file__).parent / "data" / "tpu_scopes.xplane.pb.gz"


def _reader():
    return run.load_module(run.BENCH / "metrics" / "step.groupnorm_ms.py",
                           "test_groupnorm_metric")


def _op_paths(workload):
    """The ``op_name`` of every instruction of ``workload``'s compiled
    one-round chunk at the tiny size: what a trace's ``tf_op`` reads."""
    cell = tiny_cell(workload)
    _, engine, data, state, _ = run.set_up(cell, 11)
    hlo = engine.lower_chunk(data, state=state, chunk=1).hlo
    return re.findall(r'op_name="([^"]*)"', hlo)


def _one_us_each(paths, rounds=2):
    """A traced run whose ops, one a path, take 1 us each in turn."""
    ops = [tr.Op(f"%op.{i} = f32[] add()", 1e3 * i, 1e3 * (i + 1), path)
           for i, path in enumerate(paths)]
    red = tr.Reduced((0.0, 1e3 * len(ops)), {0: ops}, [])
    return run.TracedRun(red, rounds, 1.0, {}, 1)


@pytest.fixture(scope="module")
def resnet_paths():
    return _op_paths("resnet18gn-cifar100.full")


def test_both_groupnorm_passes_carry_the_scope(resnet_paths):
    named = [p for p in resnet_paths if tr.on_path(p, SCOPE)]
    forward = [p for p in named if "transpose(" not in p]
    backward = [p for p in named if "transpose(" in p]
    assert forward and backward
    assert all(tr.on_path(p, "client_step") for p in named)
    # Both sums of the backward are under the scope.
    assert [p for p in backward if p.endswith("/reduce_sum")]


def test_reader_counts_the_scoped_ops(resnet_paths):
    got = _reader().read(_one_us_each(resnet_paths, rounds=2))
    named = sum(tr.on_path(p, SCOPE) for p in resnet_paths)
    assert got == pytest.approx(1e3 * named * 1e-6 / 2)
    assert 0 < got < 1e3 * len(resnet_paths) * 1e-6 / 2


def test_reader_reads_nothing_on_the_cnn():
    assert not any(tr.on_path(p, SCOPE)
                   for p in _op_paths("cnn-cifar10.full"))
    # A CNN trace recorded on a TPU v5e, with the round's scopes.
    traced = run.TracedRun(tr.reduce_file(SCOPED, 1), 2, 1.0, {}, 1)
    assert traced.trace.scope_seconds("client_step") is not None
    assert _reader().read(traced) is None

"""The trace reduction on a small trace recorded on a TPU v5e and committed
(``data/tpu_small.xplane.pb.gz``, made by ``record_trace.py``): busy union,
idle share, idle gaps by host activity, and the copy rule of
``layout.copy_ms``."""
from __future__ import annotations

import re
from pathlib import Path

import numpy as np
import pytest

from bench import run
from bench import trace as tr

TRACE = Path(__file__).parent / "data" / "tpu_small.xplane.pb.gz"
SLEEP_S = 0.05     # record_trace.py's sleep between its two fit calls


def _metric(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py",
                           "test_metric_" + re.sub(r"\W", "_", name))


@pytest.fixture(scope="module")
def raw():
    """The device ops and host spans read straight from the file."""
    pd = tr.load_profile(TRACE)
    ops, spans = [], {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((ev.name, ev.start_ns, ev.duration_ns))
                elif ev.name.startswith("bench."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return ops, spans


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_file(TRACE, 1)


def test_window_is_the_runs_own_span(raw, reduced):
    _, spans = raw
    assert reduced.window == spans["bench.window"][0]
    assert len(spans["bench.fit"]) == 2
    assert 0.05 < reduced.window_s < 5.0


def test_busy_union_against_a_dense_count(raw, reduced):
    """Busy time by marking every microsecond some op covers."""
    ops, _ = raw
    lo, hi = reduced.window
    covered = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for name, start, dur in ops:
        if tr.opcode(name) in tr.CONTAINERS:
            continue
        a = int(max(start - lo, 0) // 1000)
        b = int(np.ceil(max(min(start + dur, hi) - lo, 0) / 1000))
        covered[a:b] = True
    assert reduced.busy_s == pytest.approx(covered.sum() * 1e-6, abs=2e-4)
    assert 0 < reduced.busy_s < reduced.window_s


def test_containers_are_left_out(raw, reduced):
    ops, _ = raw
    whiles = [n for n, _, _ in ops if tr.opcode(n) == "while"]
    assert whiles, "the recorded rounds run their scans as while loops"
    assert not any(tr.opcode(o.name) in tr.CONTAINERS
                   for o in reduced.ops[0])


def test_idle_share_and_the_planted_gap(reduced):
    idle = _metric("device.idle_share").read(
        run.TracedRun(reduced, 2, 1.0, {"bf16_flops_per_s": 1.0}, 1))
    assert idle == pytest.approx(
        100 * (1 - reduced.busy_s / reduced.window_s))
    # The sleep between the two fit calls is idle, and the host was in the
    # window span and in no fit span then.
    gaps = tr.breakdown(reduced)["idle_gaps"]
    assert gaps[0][0] == tr.WINDOW_SPAN
    assert gaps[0][1] >= SLEEP_S
    assert idle >= 100 * SLEEP_S / reduced.window_s


def test_copy_rule(reduced):
    metric = _metric("layout.copy_ms")
    names = reduced.op_seconds()
    counted = {n for n in names if metric.is_format(n)}
    assert counted, "the flat state's repacks are data formatting"
    assert all(tr.opcode(n) in metric.FORMAT_OPS or tr.opcode(n) == "fusion"
               for n in counted)
    assert not any(tr.opcode(n) in ("convolution", "dot") for n in counted)
    assert not any("convolution" in n.partition(" = ")[0] for n in counted)
    want = sum(names[n] for n in counted) * 1e3 / 2
    got = metric.read(run.TracedRun(reduced, 2, 1.0, {}, 1))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("text,op,inst", [
    ("%copy.321 = f32[10,10,50]{2,1,0:T(8,128)} copy(f32[10,10,50]{2,1,0} "
     "%maximum_bitcast_fusion.6)", "copy", "copy"),
    ("%while.38 = (s32[]{:T(128)}, f32[10,2]{1,0}) while((s32[], f32[10,2]) "
     "%tuple.1), condition=%c, body=%b", "while", "while"),
    ("%constant_dynamic-slice_fusion.2 = f32[4]{0} fusion(f32[8]{0} %p), "
     "kind=kLoop, calls=%fc", "fusion", "constant_dynamic-slice_fusion"),
    ("%copy-start.30 = (s32[200]{0}, s32[200]{0}, u32[]{:S(2)}) "
     "copy-start(s32[200]{0} %reshape.168)", "copy-start", "copy-start"),
    ("jit_run_chunk(2537001274057112721)", "jit_run_chunk(2537001274057112721)",
     "jit_run_chunk(2537001274057112721)"),
])
def test_instruction_text_parsing(text, op, inst):
    assert tr.opcode(text) == op
    assert tr.instruction(text) == inst


@pytest.mark.parametrize("text,fmt", [
    ("%reshape.254 = f32[10,10,4096,512]{3,2,1,0} reshape(f32[10,10,2097152] "
     "%slice.190)", True),
    ("%dynamic-update-slice.21 = f32[10,10,2156490]{2,1,0} "
     "dynamic-update-slice(%a, %b, %c)", True),
    ("%constant_dynamic-slice_fusion.2 = f32[4]{0} fusion(%p), kind=kLoop", True),
    ("%maximum_bitcast_fusion.5 = f32[10,50]{1,0} fusion(%a), kind=kLoop", False),
    ("%fusion.208 = f32[50,16]{1,0} fusion(%a, %b), kind=kOutput", False),
    ("%select-and-scatter.5 = f32[10]{0} select-and-scatter(%a, %b, %c)", False),
])
def test_copy_rule_on_instructions(text, fmt):
    assert _metric("layout.copy_ms").is_format(text) is fmt


def test_interval_arithmetic():
    ivs = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert tr.merge(ivs) == [(0, 20), (30, 40)]
    assert tr.busy_ns(ivs, 8, 38) == 12 + 8
    assert tr.gaps(ivs, 0, 50) == [(20, 30), (40, 50)]
    assert tr.gaps(ivs, 12, 33) == [(20, 30)]

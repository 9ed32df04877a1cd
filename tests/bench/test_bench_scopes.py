"""The split of a traced window by the program's named scopes and host
spans (``bench/scopes.py``, ``bench/xspace.py``), on two traces recorded
on a TPU v5e by ``record_trace.py``: ``tpu_small`` from a program without
scopes or spans, ``tpu_scopes`` from one with them."""
from __future__ import annotations

import hashlib
import json
import re
from pathlib import Path

import pytest

from bench import run, scopes, xspace
from bench import trace as tr

DATA = Path(__file__).parent / "data"
SMALL = DATA / "tpu_small.xplane.pb.gz"
SCOPED = DATA / "tpu_scopes.xplane.pb.gz"
SLEEP_S = 0.05     # record_trace.py's sleep between its two fit calls
ENGINE_SCOPES = ("client_step", "local_update", "state_repack", "group_agg",
                 "global_agg")


def _metric(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py",
                           "test_scopes_metric_" + re.sub(r"\W", "_", name))


@pytest.mark.parametrize("tf_op,want", [
    ("jit(run_chunk)/while/body/client_step/vmap(vmap(jvp()))/mul:",
     "client_step"),
    ("jit(f)/while/body/vmap(vmap(client_step))/add:", "client_step"),
    ("jit(f)/transpose(jvp(client_step))/dot_general:", "client_step"),
    ("jit(f)/local_update/state_repack/slice:", "state_repack"),
    ("jit(f)/group_agg/while/body/global_agg/sub:", "global_agg"),
    ("jit(f)/group_agg/sub:;jit(f)/global_agg/sub:", "group_agg"),
    ("jit(f)/client_steps/add:", None),
    ("jit(f)/my.client_step/add:", None),
    ("jit(run_chunk)/while/body/closed_call/while:", None),
    ("", None),
], ids=["plain", "vmapped", "transposed", "nested", "nested_deep",
        "joined", "longer_word", "dotted_word", "outside", "empty"])
def test_scope_of_a_path(tf_op, want):
    assert scopes.scope_of(tf_op, ENGINE_SCOPES) == want


def test_wire_reader_agrees_with_profile_data():
    """Both readers give the device ops in one order, by one name, at one
    time (ProfileData cuts to whole nanoseconds)."""
    pd = tr.load_profile(SMALL)
    events = [ev for plane in pd.planes if plane.name == "/device:TPU:0"
              for line in plane.lines if line.name == tr.OPS_LINE
              for ev in line.events]
    (plane,) = xspace.read_planes(SMALL, tr.DEVICE_PLANE.match, [tr.OPS_LINE])
    raw = plane.lines[0].events
    assert len(raw) == len(events) > 1000
    for ev, r in zip(events, raw):
        assert plane.metadata[r.metadata_id][0] == ev.name
        assert abs(r.start_ns - ev.start_ns) < 1.0
        assert abs(r.duration_ps / 1e3 - ev.duration_ns) < 1.0
    stats = {k for _, (_, st) in plane.metadata.items() for k in st}
    assert {"tf_op", "hlo_category", "source"} <= stats


def test_accepted_readers_read_as_before():
    """The benchmark's three metrics and the breakdown's ops on
    ``tpu_small``, as its reader gave them when the trace was recorded."""
    red = tr.reduce_file(SMALL, 1)
    r = run.TracedRun(red, 2, 5.3e12, {"bf16_flops_per_s": 1.97e14}, 1)
    assert _metric("device.idle_share").read(r) == 97.16771112076066
    assert _metric("round.mfu").read(r) == 82.02724475255766
    assert _metric("layout.copy_ms").read(r) == 0.5167385000000001
    assert red.busy_s == 0.0018578860000000002
    ops = json.dumps(tr.breakdown(red)["device_ops"]).encode()
    assert hashlib.sha256(ops).hexdigest() == (
        "7cc7c53af0b8b4f9a7b5b6892e527bd17f5f93a5cd5ea2e337c4629da8f6f422")


def test_a_program_without_scopes_splits_into_the_remainder():
    split = scopes.split_file(SMALL, 1)
    red = tr.reduce_file(SMALL, 1)
    assert split.reduced.window == red.window
    assert [o.name for o in split.reduced.ops[0]] == [o.name for o in red.ops[0]]
    sec = split.scope_seconds(ENGINE_SCOPES)
    assert set(sec) == {None}
    assert sec[None] == pytest.approx(sum(red.op_seconds().values()))
    assert split.driver_host_s() is None


@pytest.fixture(scope="module")
def scoped():
    return scopes.split_file(SCOPED, 1)


def test_scope_seconds_against_a_dense_count(scoped):
    """Each op's scope found by another rule (the rightmost whole-word
    name on its first path) and its time summed from the raw events."""
    lo, hi = scoped.reduced.window
    (plane,) = xspace.read_planes(SCOPED, tr.DEVICE_PLANE.match, [tr.OPS_LINE])
    want = {}
    for ev in plane.lines[0].events:
        name, stats = plane.metadata[ev.metadata_id]
        end = ev.start_ns + ev.duration_ps / 1e3
        if tr.opcode(name) in tr.CONTAINERS or end <= lo or ev.start_ns >= hi:
            continue
        path = str(stats.get("tf_op", "")).split(";")[0]
        found = [(m.start(), m.group(1)) for m in re.finditer(
            r"(?<![\w.\-])(" + "|".join(scopes.declared_scopes())
            + r")(?![\w.\-])", path)]
        scope = max(found)[1] if found else None
        want[scope] = want.get(scope, 0.0) + ev.duration_ps * 1e-12
    got = scoped.scope_seconds(scopes.declared_scopes())
    assert set(got) == set(want)
    assert set(ENGINE_SCOPES) <= set(got)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=2e-3, abs=2e-6), k
    assert sum(got.values()) == pytest.approx(
        sum(scoped.reduced.op_seconds().values()))


def test_planted_gap_is_the_benchmarks(scoped):
    gaps = tr.breakdown(scoped.reduced)["idle_gaps"]
    assert gaps[0][0] == tr.WINDOW_SPAN
    assert gaps[0][1] >= SLEEP_S


def test_gaps_inside_fit_are_the_programs(scoped):
    red = scoped.reduced
    fits = [(s, e) for n, s, e in red.spans if n == tr.FIT_SPAN]
    assert len(fits) == 2
    idle = tr.gaps([(o.start_ns, o.end_ns) for o in red.ops[0]], *red.window)
    inside = [(s, e) for s, e in idle if e - s > 1e5
              and any(fs <= (s + e) / 2 < fe for fs, fe in fits)]
    assert inside, "the tiny rounds wait for the host inside fit"
    for s, e in inside:
        assert tr.host_activity(red, (s + e) / 2).startswith("repro."), (s, e)


def test_driver_host_time_is_fit_less_its_fetches(scoped):
    red = scoped.reduced
    fits = [(s, e) for n, s, e in red.spans if n == scopes.FIT_SPAN]
    fetches = [(s, e) for n, s, e in red.spans if n == scopes.FETCH_SPAN]
    assert len(fits) == len(fetches) == 2
    assert all(fs <= s < e <= fe for (fs, fe), (s, e) in zip(fits, fetches))
    host = scoped.driver_host_s()
    assert 0 < host < sum(e - s for s, e in fits) * 1e-9
    assert host == pytest.approx(
        sum((fe - fs) - (e - s) for (fs, fe), (s, e) in zip(fits, fetches))
        * 1e-9)


def test_summary_line(scoped):
    out = scopes.summary(scoped)
    assert out["rounds"] == 2
    assert set(ENGINE_SCOPES) <= set(out["scope_ms"])
    assert sum(out["scope_ms"].values()) + out["unscoped_ms"] == \
        pytest.approx(1e3 * sum(scoped.reduced.op_seconds().values()) / 2)
    assert len(out["unscoped_top"]) <= 5
    assert out["driver_host_ms"] > 0
    json.dumps(out)


# The benchmark's readers of the program's names, and the scope each reads.
SCOPE_METRICS = {"step.fwd_bwd_ms": "client_step", "update.ms": "local_update",
                 "pack.repack_ms": "state_repack", "agg.group_ms": "group_agg",
                 "agg.global_ms": "global_agg"}


@pytest.mark.parametrize("metric,scope", SCOPE_METRICS.items())
def test_scope_reader_is_the_splits_number(scoped, metric, scope):
    run_ = run.TracedRun(tr.reduce_file(SCOPED, 1), 2, 1.0, {}, 1)
    want = scopes.summary(scoped)["scope_ms"][scope]
    assert _metric(metric).read(run_) == pytest.approx(want, rel=1e-9)


def test_driver_reader_is_the_splits_number(scoped):
    run_ = run.TracedRun(tr.reduce_file(SCOPED, 1), 2, 1.0, {}, 1)
    want = scopes.summary(scoped)["driver_host_ms"]
    assert _metric("driver.host_ms").read(run_) == pytest.approx(want,
                                                                 rel=1e-9)


def test_engine_scopes_are_disjoint_on_the_trace(scoped):
    """No op lies under two of the round's scopes, so an op counted for
    every scope on its path and one counted for its innermost agree."""
    for op in scoped.reduced.ops[0]:
        assert sum(tr.on_path(op.path, s) for s in ENGINE_SCOPES) <= 1, op.path
    inner = scoped.scope_seconds(ENGINE_SCOPES)
    for s in ENGINE_SCOPES:
        assert scoped.reduced.scope_seconds(s) == pytest.approx(inner[s])


def test_readers_of_names_read_nothing_without_them():
    run_ = run.TracedRun(tr.reduce_file(SMALL, 1), 2, 1.0, {}, 1)
    for metric in [*SCOPE_METRICS, "driver.host_ms"]:
        assert _metric(metric).read(run_) is None, metric


def test_reduced_keeps_paths_and_program_spans_apart():
    red = tr.reduce_file(SCOPED, 1)
    assert all(n.startswith(tr.RUN_PREFIX) for n, _, _ in red.spans)
    assert {n for n, _, _ in red.program_spans} >= {
        "repro.fit", "repro.dispatch", "repro.fetch"}
    assert sum(bool(o.path) for o in red.ops[0]) > 0.9 * len(red.ops[0])


@pytest.mark.parametrize("tf_op,name,want", [
    ("jit(f)/local_update/state_repack/slice:", "local_update", True),
    ("jit(f)/local_update/state_repack/slice:", "state_repack", True),
    ("jit(f)/transpose(jvp(client_step))/dot_general:", "client_step", True),
    ("jit(f)/client_step/clients_packed/conv:", "clients_packed", True),
    ("jit(f)/client_steps/add:", "client_step", False),
    ("jit(f)/my.client_step/add:", "client_step", False),
    ("jit(f)/group_agg/sub:;jit(f)/global_agg/sub:", "global_agg", False),
    ("", "client_step", False),
], ids=["outer", "inner", "transposed", "nested_name", "longer_word",
        "dotted_word", "second_path", "empty"])
def test_a_scope_anywhere_on_the_path(tf_op, name, want):
    assert tr.on_path(tf_op, name) is want

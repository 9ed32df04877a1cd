"""A causal LM as a cell made of new files alone (``data/lm-tiny``): token
traffic, the plain next-token reference on both two-level backends, the
faults and the control that its comparison must catch, and the draws the
reference repeats.

``lm_checkout`` copies this checkout's ``bench/`` and adds the
``lm-tiny`` configuration, its traffic, limits and a reader of the
program's ``attn`` scope as new files, with new ``BENCHMARK.json`` entries;
nothing of the harness is edited.
"""
from __future__ import annotations

import functools
import json

import jax
import numpy as np
import pytest

from bench import check, feed, reference, run
from bench_cells import (allow_cpu_peaks, lm_cell, lm_checkout, plant_fault,
                         program_and_reference_loss)

BACKENDS = {"simulator": ("simulator", None), "sharded_a2": ("sharded", 2)}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lm_checkout(tmp_path_factory.mktemp("checkout"))


def test_new_files_make_a_cell(root):
    cell = lm_cell(root)
    assert cell.causal_lm and cell.config["task"] == "causal_lm"
    assert [name for name, _, _ in cell.per_layer] == ["lm.attn_ms"]
    for name in ("run.py", "feed.py", "reference.py", "trace.py"):
        assert (root / "bench" / name).read_bytes() == \
            (run.BENCH / name).read_bytes()
    # The program's model takes the reference's weights.
    assert run.build_engine(cell).spec.levels == (2, 2)


@pytest.mark.parametrize("backend", BACKENDS)
def test_sound_run_is_correct(root, backend, monkeypatch, tmp_path):
    allow_cpu_peaks(monkeypatch)
    cell = lm_cell(root, *BACKENDS[backend])
    result, lines = run.run_cell(cell, 2**31 + 13, 0.3, trace=True,
                                 require_tpu=False, trace_dir=tmp_path)
    assert result["correct"], lines
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(cell.limits)
    assert result["attempted"] >= 1 and result["failed"] == 0
    json.dumps(result)


@pytest.mark.parametrize("backend", BACKENDS)
def test_reference_rounds_match_engine(root, backend):
    """On the CPU both run in float32: the microbatched sharded round and
    the simulator's agree with the reference to round-off."""
    cell = lm_cell(root, *BACKENDS[backend])
    seed = 2**31 + 77
    fed, engine, data, state, x0 = run.set_up(cell, seed)
    _, _, program = run.drive_checked(cell, engine, data, state)
    read = check.readings(x0, program, run.reference_readout(cell, seed, fed,
                                                             x0))
    for name in ("loss", "grad", "delta", "z", "y", "z_last", "y_last"):
        assert read[name] < 1e-5, (name, read[name])
    assert read["grad_still"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_broken_timed_path_is_not_correct(root, backend, fault, monkeypatch):
    allow_cpu_peaks(monkeypatch)
    cell = lm_cell(root, *BACKENDS[backend])
    plant_fault(monkeypatch, fault, cell)
    result, lines = run.run_cell(cell, 3, 0.2, trace=False, require_tpu=False)
    assert not result["correct"], lines


@pytest.mark.parametrize("kind", ["bfloat16", "drop_y"])
def test_control_and_dropped_y_are_not_correct(root, kind):
    """The reference in bfloat16, and the reference with y left out of the
    local step, each in the program's place, against the limits."""
    cell = lm_cell(root)
    seed = 5
    fed, _, _, _, x0 = run.set_up(cell, seed)
    ref = run.reference_readout(cell, seed, fed, x0)
    other = run.reference_readout(
        cell, seed, fed, x0,
        **({"dtype": "bfloat16"} if kind == "bfloat16" else {"drop_y": True}))
    read = check.readings(x0, other, ref)
    if kind == "drop_y":
        assert read["z"] == read["y"] == 0.0, read
    correct, checks = check.judge(read, cell.limits)
    assert not correct, checks


@pytest.mark.parametrize("backend", BACKENDS)
def test_host_state_reference_reads_as_the_whole_state_path(root, backend,
                                                            monkeypatch):
    """The per-client reference that keeps the round's state on the host
    reads as the whole-state device path it replaced: step losses, the
    global models after every round and the z and y leaf norms.

    Each client step is the same jitted client computation (alone, not a
    ``lax.map`` body), each aggregation the same jitted means and
    corrections (one leaf at a time), and host-device copies are exact, so
    where XLA lowers the lone client step as it lowers the map's body, as
    on the CPU, they agree bit for bit. A chip may lower the two apart and
    differ in the last bits: 1e-5 relative. The bfloat16 control reads
    gaps a hundred times wider."""
    cell = lm_cell(root, *BACKENDS[backend])
    seed = 2**31 + 19
    fed, _, _, _, x0 = run.set_up(cell, seed)
    host = run.reference_readout(cell, seed, fed, x0)
    control = run.reference_readout(cell, seed, fed, x0, dtype="bfloat16")
    monkeypatch.setattr(reference, "run_rounds", functools.partial(
        reference.run_rounds, host_state=False))
    whole = run.reference_readout(cell, seed, fed, x0)
    np.testing.assert_array_equal(host.losses, whole.losses)
    for part in ("first", "last"):
        for a, b in zip(jax.tree.leaves(getattr(host, part)),
                        jax.tree.leaves(getattr(whole, part))):
            np.testing.assert_array_equal(a, b)
    assert host.corrections == whole.corrections
    assert host.corrections_last == whole.corrections_last
    gap = np.abs(control.losses - whole.losses) / np.abs(whole.losses)
    assert gap.max() > 100 * 1e-5, gap.max()


def test_host_state_reference_holds_one_client_on_the_device(root,
                                                            monkeypatch):
    """Between client steps the device holds the same bytes at G x K = 4
    and 16: the client's new x and its group's y, within one client's x, z,
    y and gradient. Sampled each time a client's new x comes back to the
    host; arrays alive before the call are not counted."""
    cell = lm_cell(root)
    cfg = cell.config
    loss = functools.partial(cell.ref.loss, cfg)
    params = jax.device_get(cell.ref.init_weights(cfg, jax.random.PRNGKey(0)))
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    samples = []
    to_host = reference._to_host

    def sampled(tree):
        if isinstance(tree, dict):            # a client's new x
            samples[-1].append(sum(
                a.nbytes for a in jax.live_arrays()
                if id(a) not in before and not a.is_deleted()))
        return to_host(tree)

    monkeypatch.setattr(reference, "_to_host", sampled)
    rng = np.random.default_rng(0)
    for G, K in ((2, 2), (4, 4)):
        toks = rng.integers(0, cfg["vocab_size"], (2, 2, G, K, 1, 2, 33),
                            dtype=np.int32)
        batch = {"tokens": toks[..., :-1], "targets": toks[..., 1:]}
        alive = jax.live_arrays()
        before = {id(a) for a in alive}
        samples.append([])
        reference.run_rounds(loss, params, [batch], levels=(G, K), lr=0.05,
                             group_rounds=2, local_steps=2, per_client=True)
        del alive
        assert len(samples[-1]) == 2 * 2 * G * K
    assert max(samples[0]) <= 4 * n_bytes + 4096, (samples, n_bytes)
    assert set(samples[0]) == set(samples[1]), samples


@pytest.mark.parametrize("backend", BACKENDS)
def test_token_windows_repeat_the_packers_windows(root, backend):
    cell = lm_cell(root, *BACKENDS[backend])
    seed = 2**31 + 2**20 + 3
    fed, _, data, _, _ = run.set_up(cell, seed)
    steps = cell.traffic["spec"]["schedule"]["local_steps"] * cell.microbatches
    want = feed.token_windows(fed.streams, cell.traffic["shards"], steps,
                              cell.traffic["batch"], cell.traffic["seq_len"],
                              feed.pack_rng(seed))
    assert want["tokens"].shape == (2, 2, 2, steps, 2, 32)
    for name in ("tokens", "targets"):
        np.testing.assert_array_equal(np.asarray(data.arrays[name]),
                                      want[name])
    np.testing.assert_array_equal(want["tokens"][..., 1:],
                                  want["targets"][..., :-1])


def test_round_batches_split_steps_into_microbatches(root):
    cell = lm_cell(root, "sharded", 2)
    seed = 11
    fed = feed.make_federation(cell.config, cell.traffic, seed)
    arrays, rows = feed.packed_slots(fed, cell.traffic, 4, feed.pack_rng(seed))
    sids = np.zeros((2, 2, 2), int)
    b = feed.round_batches(arrays, rows, sids, microbatches=2)
    assert b["tokens"].shape == (2, 2, 2, 2, 2, 2, 32)    # [E, H, G, K, A, B, T]
    # Local step h's microbatch a is step-batch h * A + a of the shard.
    np.testing.assert_array_equal(b["tokens"][0, 1, 1, 0, 1],
                                  arrays["tokens"][rows[1, 0, 0, 3]])


def test_token_streams_from_the_seed():
    cfg = {"task": "causal_lm", "levels": [2, 3], "vocab_size": 300,
           "eos_token_id": 7}
    traffic = {"tokens_per_client": 5000, "doc_len_median": 40,
               "doc_len_sigma": 1.2, "domains": 5, "partition": "both_noniid",
               "alpha": 0.5}
    big = 2**31 + 2**20 + 5
    a = feed.make_federation(cfg, traffic, big)
    b = feed.make_federation(cfg, traffic, big)
    c = feed.make_federation(cfg, traffic, big + 1)
    for g in range(2):
        for k in range(3):
            s = a.streams[g][k]
            assert s.shape == (5000,) and s.dtype == np.int32
            assert s.min() >= 0 and s.max() < 300
            np.testing.assert_array_equal(s, b.streams[g][k])
            assert not np.array_equal(s, c.streams[g][k])
            # Documents end in the end-of-document id; lengths vary widely.
            ends = np.flatnonzero(s == 7)
            lens = np.diff(ends)
            assert len(ends) > 50 and lens.max() > 4 * np.median(lens)


def test_domain_mixtures_skew_at_both_levels():
    """Small alpha: each group's mixture leans on few domains, groups differ,
    and clients differ from their group; large alpha: all near uniform."""
    def tv(p, q):
        return 0.5 * np.abs(p - q).sum(-1)

    rng = np.random.default_rng(0)
    gm, cm = feed.domain_mixtures(rng, 8, 8, 8, 0.1)
    assert gm.shape == (8, 8) and cm.shape == (8, 8, 8)
    np.testing.assert_allclose(cm.sum(-1), 1.0)
    assert np.mean(gm.max(-1)) > 0.6
    assert np.mean([tv(gm[i], gm[j]) for i in range(8)
                    for j in range(i)]) > 0.6
    assert np.mean(tv(cm, gm[:, None])) > 0.3
    gm, cm = feed.domain_mixtures(rng, 8, 8, 8, 100.0)
    assert np.max(tv(gm, np.full(8, 1 / 8))) < 0.1
    assert np.max(tv(cm, gm[:, None])) < 0.1


def test_forward_flops_and_round_flops(root):
    """The forward of one 32-token sequence by hand: per layer and position
    q 64*64 + k, v 2 * 64*32 + o 64*64 + MLP 3 * 64*128 = 36,864 MACs; the
    scores and weighted values 2 * 4*16 * 528 causal pairs = 67,584 a
    layer; the unembedding 64*512 a position."""
    cell = lm_cell(root)
    macs = 2 * (32 * 36_864 + 67_584) + 32 * 64 * 512
    assert cell.model.forward_flops(cell.config, seq_len=32) == 2 * macs
    # 4 clients, E=2 x H=2 local steps, 2 sequences a step (x 2 microbatches).
    assert run.rounds_flops(cell) == 3.0 * 2 * macs * 4 * 4 * 2
    assert run.rounds_flops(lm_cell(root, "sharded", 2)) == \
        3.0 * 2 * macs * 4 * 4 * 2 * 2


def test_reference_loss_matches_program_loss(root):
    got, want = program_and_reference_loss(lm_cell(root))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_reference_refuses_what_it_does_not_implement():
    from bench import reference

    sched = {"group_rounds": 2, "local_steps": 2}
    assert reference.supports({"algorithm": "mtgc", "lr": 0.1,
                               "schedule": sched})
    assert reference.supports({"algorithm": "mtgc", "lr": 0.1,
                               "backend": "sharded",
                               "schedule": dict(sched, microbatches=4)})
    assert not reference.supports({"algorithm": "mtgc", "lr": 0.1,
                                   "schedule": dict(sched, microbatches=4)})
    assert not reference.supports({"algorithm": "mtgc", "lr": 0.1,
                                   "backend": "multilevel",
                                   "schedule": sched})
    assert not reference.supports({"algorithm": "mtgc", "lr": 0.1,
                                   "schedule": dict(sched,
                                                    group_rounds=[2, 3])})
    assert not reference.supports({"algorithm": "mtgc", "lr": 0.1,
                                   "client_participation": 0.5,
                                   "schedule": sched})
    assert not reference.supports({"algorithm": "fedavg", "lr": 0.1,
                                   "schedule": sched})

"""Compiled horizon driver (core/driver.py) vs the per-round loop.

The driver's contract is *bit-exact* equivalence: scanning T rounds of
(on-device batch selection + round function) inside one donated jit must
reproduce exactly what T single-round dispatches produce from the same
state and the same packed dataset. Gated here for all six algorithms x
{tree, flat} state x {full, uniform} participation, for chunked dispatch
(including the T % chunk remainder), and for the sharded production round.
Donation itself is asserted by checking the input buffers are invalidated.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ALGORITHMS,
    HFLConfig,
    PackedBatches,
    as_tree,
    hfl_init,
    make_global_round,
    make_round_step,
    pack_client_shards,
    run_rounds,
    select_round,
)

from test_mtgc_engine import D, quad_loss

G, K, E, H, T = 2, 3, 2, 2, 5


def _donation_supported() -> bool:
    f = jax.jit(lambda x: x + 1.0, donate_argnums=0)
    x = jnp.ones((8,))
    f(x)
    return x.is_deleted()


needs_donation = pytest.mark.skipif(
    not _donation_supported(),
    reason="buffer donation unsupported on this backend")


def make_data(S=4, seed=0, key=1, microbatches=None):
    """Packed quadratic data: per-(client, shard, step) (a, b) pairs."""
    rng = np.random.default_rng(seed)
    steps = H * (microbatches or 1)
    shape = (G, K, S, steps, D)
    arrays = {
        "a": jnp.asarray(rng.normal(size=shape).astype(np.float32) + 2.0),
        "b": jnp.asarray(rng.normal(size=shape).astype(np.float32)),
    }
    return PackedBatches(arrays, jax.random.PRNGKey(key), E, H, microbatches)


def _loop(round_fn, state, data, rounds=T):
    step = make_round_step(round_fn, donate=False)
    mets = []
    for _ in range(rounds):
        state, data, m = step(state, data)
        mets.append(m)
    stacked = jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                           *mets)
    return state, data, stacked


def _assert_bitexact(state_a, state_b, metrics_a, metrics_b, fields, tag):
    for name in fields:
        a, b = getattr(state_a, name), getattr(state_b, name)
        if a is None or b is None:  # dyn: carried for FedDyn only
            assert a is None and b is None, f"{tag}.{name}"
            continue
        np.testing.assert_array_equal(
            np.asarray(as_tree(a)["w"]), np.asarray(as_tree(b)["w"]),
            err_msg=f"{tag}.{name}")
    for name in metrics_a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(metrics_a, name)),
            np.asarray(getattr(metrics_b, name)),
            err_msg=f"{tag}.metrics.{name}")


# ----------------------------------------------- driver vs per-round loop


@pytest.mark.parametrize("algo", ALGORITHMS)
@pytest.mark.parametrize("flat", [False, True], ids=["tree", "flat"])
@pytest.mark.parametrize("participation", ["full", "uniform"])
def test_driver_matches_loop(algo, flat, participation):
    kw = dict(num_groups=G, clients_per_group=K, local_steps=H,
              group_rounds=E, lr=0.05, algorithm=algo, prox_mu=0.1,
              feddyn_alpha=0.1, use_flat_state=flat)
    if participation == "uniform":
        kw.update(client_participation=0.5, group_participation=0.75,
                  participation_mode="uniform")
    cfg = HFLConfig(**kw)
    rf = make_global_round(quad_loss, cfg)

    state_l, data_l, metrics_l = _loop(
        rf, hfl_init({"w": jnp.zeros(D)}, cfg), make_data())
    state_d, data_d, hz = run_rounds(
        rf, hfl_init({"w": jnp.zeros(D)}, cfg), make_data(), T, donate=False)

    tag = f"{algo}/{'flat' if flat else 'tree'}/{participation}"
    _assert_bitexact(state_l, state_d, metrics_l, hz.metrics,
                     ("params", "z", "y", "dyn"), tag)
    # Both rng streams advanced identically (participation + selection).
    np.testing.assert_array_equal(np.asarray(state_l.rng),
                                  np.asarray(state_d.rng))
    np.testing.assert_array_equal(np.asarray(data_l.rng),
                                  np.asarray(data_d.rng))


def test_chunked_matches_unchunked():
    """chunk=2 over T=5 (chunks of 2, 2, and a remainder of 1) is bit-exact
    against the single whole-horizon dispatch."""
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc")
    rf = make_global_round(quad_loss, cfg)
    state_u, _, hz_u = run_rounds(
        rf, hfl_init({"w": jnp.zeros(D)}, cfg), make_data(), T, donate=False)
    state_c, _, hz_c = run_rounds(
        rf, hfl_init({"w": jnp.zeros(D)}, cfg), make_data(), T, chunk=2,
        donate=False)
    _assert_bitexact(state_u, state_c, hz_u.metrics, hz_c.metrics,
                     ("params", "z", "y"), "chunked")
    assert np.asarray(hz_c.metrics.loss).shape[0] == T
    # Oversized / zero chunk both mean "whole horizon".
    state_o, _, _ = run_rounds(
        rf, hfl_init({"w": jnp.zeros(D)}, cfg), make_data(), T, chunk=99,
        donate=False)
    np.testing.assert_array_equal(
        np.asarray(as_tree(state_o.params)["w"]),
        np.asarray(as_tree(state_u.params)["w"]))


def test_eval_fn_cadence_and_values():
    """eval_fn fires at eval_every multiples plus the final round, inside the
    compiled scan, and sees the post-round state."""
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc")
    rf = make_global_round(quad_loss, cfg)

    def eval_fn(prev, state):
        return {"pmean": jnp.mean(as_tree(state.params)["w"]),
                "round": state.round}

    state, data, hz = run_rounds(
        rf, hfl_init({"w": jnp.zeros(D)}, cfg), make_data(), T, chunk=2,
        eval_every=2, eval_fn=eval_fn, donate=False)
    np.testing.assert_array_equal(hz.eval_rounds, [2, 4, 5])
    np.testing.assert_array_equal(np.asarray(hz.evals["round"]), [2, 4, 5])

    # Cross-check values against the per-round loop.
    state_l, data_l = hfl_init({"w": jnp.zeros(D)}, cfg), make_data()
    step = make_round_step(rf, donate=False)
    want = []
    for t in range(T):
        state_l, data_l, _ = step(state_l, data_l)
        if (t + 1) % 2 == 0 or t == T - 1:
            want.append(float(jnp.mean(as_tree(state_l.params)["w"])))
    np.testing.assert_array_equal(np.asarray(hz.evals["pmean"]),
                                  np.asarray(want, np.float32))


# ------------------------------------------------------------- donation


@needs_donation
def test_run_rounds_donates_state_buffers():
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc")
    rf = make_global_round(quad_loss, cfg)
    state = hfl_init({"w": jnp.zeros(D)}, cfg)
    bufs = [leaf for f in ("params", "z", "y", "dyn")
            for leaf in jax.tree.leaves(getattr(state, f))]
    state2, _, _ = run_rounds(rf, state, make_data(), 2)
    assert all(b.is_deleted() for b in bufs)
    assert not any(b.is_deleted() for b in jax.tree.leaves(state2.params))


@needs_donation
def test_round_step_donates_state_buffers():
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc")
    rf = make_global_round(quad_loss, cfg)
    state = hfl_init({"w": jnp.zeros(D)}, cfg)
    buf = jax.tree.leaves(state.params)[0]
    step = make_round_step(rf)
    state, _, _ = step(state, make_data())
    assert buf.is_deleted()


# ------------------------------------------- packed data + selection layout


def test_select_round_gathers_whole_client_shards():
    """Every [e, :, g, k] block of the selected batches is one of client
    (g, k)'s own packed shards, taken whole."""
    S = 5
    base = (np.arange(G)[:, None, None, None] * 1000
            + np.arange(K)[None, :, None, None] * 100
            + np.arange(S)[None, None, :, None] * 10
            + np.arange(H)[None, None, None, :])
    data = PackedBatches({"v": jnp.asarray(base, jnp.float32)},
                         jax.random.PRNGKey(3), E, H, None)
    out = np.asarray(select_round(data, jax.random.PRNGKey(7))["v"])
    assert out.shape == (E, H, G, K)
    for e in range(E):
        for g in range(G):
            for k in range(K):
                block = out[e, :, g, k]
                s = (block[0] - g * 1000 - k * 100) / 10
                assert s == int(s) and 0 <= s < S, block
                np.testing.assert_array_equal(
                    block, base[g, k, int(s)].astype(np.float32))


def test_select_round_microbatched_layout():
    A, S = 2, 3
    steps = H * A
    arrays = {"x": jnp.arange(G * K * S * steps * 4, dtype=jnp.float32)
              .reshape(G, K, S, steps, 4)}
    data = PackedBatches(arrays, jax.random.PRNGKey(0), E, H, A)
    out = select_round(data, jax.random.PRNGKey(1))["x"]
    assert out.shape == (E, H, A, G, K, 4)
    # [H, A] must be the steps axis split in order: microbatch a of step h
    # is packed step h * A + a.
    flat = np.asarray(out).reshape(E, steps, G, K, 4)
    back = PackedBatches(arrays, jax.random.PRNGKey(0), E, steps, None)
    np.testing.assert_array_equal(
        flat, np.asarray(select_round(back, jax.random.PRNGKey(1))["x"]))


def test_pack_client_shards_draws_from_client_pools():
    rng = np.random.default_rng(0)
    n = 64
    idx = [[np.arange(g * K * 8 + k * 8, g * K * 8 + k * 8 + 8)
            for k in range(K)] for g in range(G)]
    x = np.arange(n, dtype=np.float32)    # feature == global sample index
    y = np.arange(n, dtype=np.int32)
    data = pack_client_shards({"x": x, "y": y}, idx, group_rounds=E,
                              local_steps=H, batch_size=3, shards=4, rng=rng,
                              key=jax.random.PRNGKey(0))
    assert data.num_shards == 4
    xs = np.asarray(data.arrays["x"])
    assert xs.shape == (G, K, 4, H, 3)
    np.testing.assert_array_equal(xs, np.asarray(data.arrays["y"]))
    for g in range(G):
        for k in range(K):
            assert set(xs[g, k].ravel().astype(int)) <= set(idx[g][k])


def test_packed_batches_is_a_pytree():
    data = make_data()
    leaves = jax.tree.leaves(data)
    assert len(leaves) == 3      # a, b, rng
    mapped = jax.tree.map(lambda x: x, data)
    assert isinstance(mapped, PackedBatches)
    assert (mapped.group_rounds, mapped.local_steps, mapped.microbatches) == \
        (E, H, None)


# --------------------------------------------------- chunk-runner caching


def test_chunk_runner_cached_per_round_fn_and_collectable():
    """Repeated run_rounds with the same round function reuse one compiled
    runner (no retrace); dropping the round function releases the runner
    (the old identity-keyed lru_cache kept dead executables pinned)."""
    import gc
    import weakref

    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc")
    base = make_global_round(quad_loss, cfg)
    traces = []

    def rf(state, batches):
        traces.append(1)
        return base(state, batches)

    run_rounds(rf, hfl_init({"w": jnp.zeros(D)}, cfg), make_data(), 2,
               donate=False)
    assert len(traces) == 1 and len(rf.__chunk_runners__) == 1
    runner = rf.__chunk_runners__[(None, False)]
    run_rounds(rf, hfl_init({"w": jnp.zeros(D)}, cfg), make_data(), 2,
               donate=False)
    # Cache hit: same runner object, no second trace.
    assert len(traces) == 1
    assert rf.__chunk_runners__[(None, False)] is runner

    ref = weakref.ref(runner)
    del runner, rf
    gc.collect()
    assert ref() is None, "dead round fn still pins its compiled runner"


def test_chunk_runner_distinct_eval_fns_get_distinct_runners():
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc")
    rf = make_global_round(quad_loss, cfg)

    def ev1(prev, state):
        return {"v": state.round}

    def ev2(prev, state):
        return {"v": state.round + 1}

    for ev in (ev1, ev2, ev1):
        run_rounds(rf, hfl_init({"w": jnp.zeros(D)}, cfg), make_data(), 2,
                   eval_fn=ev, donate=False)
    assert len(rf.__chunk_runners__) == 2

    # Fresh eval closures per call must not grow the cache without bound.
    from repro.core.driver import _RUNNERS_PER_FN
    evs = []  # keep ids alive so every closure is a distinct live key
    for _ in range(_RUNNERS_PER_FN + 3):
        evs.append(lambda prev, state: {"v": state.round})
        run_rounds(rf, hfl_init({"w": jnp.zeros(D)}, cfg), make_data(), 2,
                   eval_fn=evs[-1], donate=False)
    assert len(rf.__chunk_runners__) <= _RUNNERS_PER_FN


# --------------------------------------------------- sharded round parity


def test_driver_matches_loop_sharded_round():
    """The production round (launch.train) under the driver's microbatched
    layout: loop vs compiled horizon, bit-exact."""
    from repro.launch.train import make_sharded_round, sharded_init

    A = 2
    rf = make_sharded_round(quad_loss, E=E, H=H, lr=0.05)
    rounds = 3

    state_l, data_l, metrics_l = _loop(
        rf, sharded_init({"w": jnp.zeros(D)}, G, K),
        make_data(microbatches=A), rounds=rounds)
    state_d, data_d, hz = run_rounds(
        rf, sharded_init({"w": jnp.zeros(D)}, G, K),
        make_data(microbatches=A), rounds, chunk=2, donate=False)

    _assert_bitexact(state_l, state_d, metrics_l, hz.metrics,
                     ("params", "z", "y"), "sharded")
    np.testing.assert_array_equal(np.asarray(data_l.rng),
                                  np.asarray(data_d.rng))

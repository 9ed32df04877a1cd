"""Participation-weighting semantics: unbiasedness gates, engine/sharded
parity, and the empty-group freeze guard.

The statistical gates run Monte-Carlo batches of *whole engine rounds* --
R trajectories with independent mask streams vmapped into one compiled
horizon (``run_rounds`` over a vmapped round function) -- and compare the
disseminated global aggregate against the exact full-participation
reference on the same deterministic quadratic data:

* one group round per global round (E=1), synced start: the masked global
  aggregate under ``inverse_prob`` is *exactly* unbiased, so its MC error
  is pure noise shrinking ~1/sqrt(R);
* multi-round MTGC (E=2, T=4): the realized-count estimator's denominator
  noise feeds the z/y corrections and compounds into a systematic bias
  many sigma above the MC noise, which ``inverse_prob`` cuts by ~3x on the
  same seed set.

All seeds are fixed, so the gates are deterministic; thresholds carry wide
margins relative to the measured values. The MC harness itself lives in
``benchmarks.fig_participation`` (the same code that emits the
BENCH_participation.json CI artifact), so the gated statistic and the
published numbers measure the same estimator readout by construction.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.fig_participation import (
    full_participation_reference,
    mc_participation_aggregates,
)
from repro.core import (
    ALGORITHMS,
    HFLConfig,
    as_tree,
    hfl_init,
    make_global_round,
    round_masks,
    run_rounds,
)
from repro.core import multilevel as ml
from repro.launch.train import make_sharded_round, sharded_init

from test_mtgc_engine import D, make_batches, quad_loss


def _mc_aggregates(weighting, *, E, H, T, R):
    # traj_key pinned: the gate thresholds below were calibrated on it.
    return mc_participation_aggregates(weighting, E=E, H=H, T=T, R=R,
                                       traj_key=7)


_full_reference = full_participation_reference


# --------------------------------------------------- statistical gates


def test_inverse_prob_unbiased_single_timescale():
    """E=1 from a synced start: each client's local trajectory is mask-
    independent, so the Horvitz-Thompson aggregate is exactly unbiased --
    the MC error of its mean is pure noise, shrinking ~1/sqrt(R)."""
    R = 2048
    agg, ok = _mc_aggregates("inverse_prob", E=1, H=2, T=1, R=R)
    full = _full_reference(E=1, H=2, T=1)[0]
    a = agg[0]
    assert ok[0].all()  # p=0.5 over 24 clients: empty rounds are ~1e-8

    errs = {}
    for r in (128, 512, 2048):
        errs[r] = np.linalg.norm(a[:r].mean(axis=0) - full)
    # Analytic MC noise floor for the full batch: sqrt(sum_d var_d / R).
    se = np.sqrt((a.var(axis=0) / R).sum())
    assert errs[2048] < 4.0 * se, (errs, se)
    # ~1/sqrt(R): 16x the samples should shrink the error ~4x; require 2x.
    assert errs[2048] < 0.5 * errs[128], errs


def test_none_bias_compounds_and_inverse_prob_reduces_it():
    """Multi-round MTGC (E=2, T=4): realized-count weighting accumulates a
    systematic bias far above the MC noise; inverse_prob cuts it well below
    half on the same seeds (measured ~3.8x at large R; the HT trajectory
    distribution is heavy-tailed, so the gate uses R large enough for its
    mean-norm to stabilize; see BENCH_participation.json)."""
    R, T = 1536, 4
    full = _full_reference(E=2, H=2, T=T)[T - 1]
    bias, se = {}, {}
    for w in ("none", "inverse_prob"):
        agg, ok = _mc_aggregates(w, E=2, H=2, T=T, R=R)
        a = agg[T - 1][ok[T - 1]]
        bias[w] = np.linalg.norm(a.mean(axis=0) - full)
        se[w] = np.sqrt((a.var(axis=0) / len(a)).sum())
    # 'none' is measurably biased: many sigma above its noise floor.
    assert bias["none"] > 8.0 * se["none"], (bias, se)
    # inverse_prob's compounded bias is at most ~half of none's.
    assert bias["inverse_prob"] < 0.55 * bias["none"], (bias, se)


# ------------------------------------------- exactness / coincidence gates


def test_full_participation_bitexact_with_weighting_enabled():
    """C=1 compiles the weighting machinery out entirely: the program is
    bit-for-bit the unweighted engine for every algorithm."""
    Gs, Ks, E, H = 2, 3, 2, 2
    _, _, batches = make_batches(Gs, Ks, E, H, seed=5)
    jb = jax.tree.map(jnp.asarray, batches)
    for algo in ALGORITHMS:
        kw = dict(num_groups=Gs, clients_per_group=Ks, local_steps=H,
                  group_rounds=E, lr=0.05, algorithm=algo, prox_mu=0.1,
                  feddyn_alpha=0.1)
        st0 = hfl_init({"w": jnp.zeros(D)}, HFLConfig(**kw))
        s_plain, _ = jax.jit(make_global_round(quad_loss, HFLConfig(**kw)))(
            st0, jb)
        s_w, _ = jax.jit(make_global_round(
            quad_loss,
            HFLConfig(**kw, participation_weighting="inverse_prob")))(st0, jb)
        for name in ("params", "z", "y", "dyn"):
            a, b = getattr(s_plain, name), getattr(s_w, name)
            if a is None or b is None:  # dyn: carried for FedDyn only
                assert a is None and b is None, f"{algo}.{name}"
                continue
            np.testing.assert_array_equal(
                np.asarray(as_tree(a)["w"]), np.asarray(as_tree(b)["w"]),
                err_msg=f"{algo}.{name}")


@pytest.mark.parametrize("algo", ["mtgc", "hfedavg"])
def test_fixed_mode_weightings_coincide(algo):
    """Under 'fixed' sampling the realized count equals the expected count,
    so both weightings compute the identical program output."""
    Gs, Ks, E, H = 2, 4, 2, 2
    _, _, batches = make_batches(Gs, Ks, E, H, seed=9)
    jb = jax.tree.map(jnp.asarray, batches)
    outs = {}
    for w in ("none", "inverse_prob"):
        cfg = HFLConfig(num_groups=Gs, clients_per_group=Ks, local_steps=H,
                        group_rounds=E, lr=0.05, algorithm=algo,
                        client_participation=0.5, group_participation=0.5,
                        participation_mode="fixed",
                        participation_weighting=w)
        st = hfl_init({"w": jnp.zeros(D)}, cfg)
        rf = jax.jit(make_global_round(quad_loss, cfg))
        for _ in range(3):
            st, _ = rf(st, jb)
        outs[w] = st
    for name in ("params", "z", "y"):
        np.testing.assert_allclose(
            np.asarray(as_tree(getattr(outs["none"], name))["w"]),
            np.asarray(as_tree(getattr(outs["inverse_prob"], name))["w"]),
            rtol=1e-6, atol=1e-7, err_msg=name)


def test_multilevel_fixed_mode_weightings_coincide():
    dims, periods, lr = (2, 2, 3), (8, 4, 2), 0.05
    rng = np.random.default_rng(12)
    sh = (8,) + dims + (D,)
    batches = {"a": jnp.asarray(rng.normal(size=sh).astype(np.float32) + 2.0),
               "b": jnp.asarray(rng.normal(size=sh).astype(np.float32))}
    outs = {}
    for w in ("none", "inverse_prob"):
        st = ml.multilevel_init({"w": jnp.zeros(D)}, dims)
        rf = jax.jit(ml.make_multilevel_round(
            quad_loss, dims, periods, lr, participation=(0.5, 1.0, 0.5),
            participation_mode="fixed", participation_weighting=w))
        for _ in range(3):
            st, losses = rf(st, batches)
        outs[w] = st
        assert np.isfinite(np.asarray(losses)).all()
    np.testing.assert_allclose(np.asarray(outs["none"].params["w"]),
                               np.asarray(outs["inverse_prob"].params["w"]),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("weighting", ["none", "inverse_prob"])
def test_multilevel_two_level_matches_engine_under_partial(weighting):
    """M=2 multilevel under uniform partial participation reproduces the
    two-level engine replica-for-replica for both weightings (same rng =>
    same masks; the key schedules coincide). In particular the multilevel
    hierarchy must apply the HT denominator only at estimation steps --
    re-aggregating already-disseminated values is recovery and must be
    count-normalized (regression: a fixed denominator there rescales the
    model by realized/expected count)."""
    Gs, Ks, E, H, lr = 2, 3, 2, 2, 0.05
    _, _, batches = make_batches(Gs, Ks, E, H, seed=17)
    jb = jax.tree.map(jnp.asarray, batches)
    mb = {k: v.reshape((E * H,) + v.shape[2:]) for k, v in jb.items()}

    cfg = HFLConfig(num_groups=Gs, clients_per_group=Ks, local_steps=H,
                    group_rounds=E, lr=lr, algorithm="mtgc",
                    client_participation=0.5, group_participation=0.75,
                    participation_mode="uniform",
                    participation_weighting=weighting, use_flat_state=False)
    key = jax.random.PRNGKey(13)
    st2 = hfl_init({"w": jnp.zeros(D)}, cfg, rng=key)
    rf2 = jax.jit(make_global_round(quad_loss, cfg))
    stM = ml.multilevel_init({"w": jnp.zeros(D)}, (Gs, Ks), rng=key)
    rfM = jax.jit(ml.make_multilevel_round(
        quad_loss, (Gs, Ks), (E * H, H), lr,
        participation=(0.75, 0.5), participation_mode="uniform",
        participation_weighting=weighting))
    for _ in range(3):
        st2, _ = rf2(st2, jb)
        stM, _ = rfM(stM, mb)
        np.testing.assert_allclose(
            np.asarray(stM.params["w"]),
            np.asarray(as_tree(st2.params)["w"]),
            rtol=1e-5, atol=1e-6)
        # nu_1 is the engine's y (same update, same gating).
        np.testing.assert_allclose(
            np.asarray(stM.nus[0]["w"]),
            np.asarray(as_tree(st2.y)["w"]),
            rtol=1e-5, atol=1e-6)


def test_multilevel_inverse_prob_freezes_inactive_subtree():
    """The frozen-subtree invariant survives HT weighting (uniform mode)."""
    from repro.core import participation as pp

    dims, periods, lr = (2, 2, 2), (8, 4, 2), 0.05
    rng = np.random.default_rng(13)
    a = rng.normal(size=dims + (D,)).astype(np.float32) + 2.0
    b = rng.normal(size=dims + (D,)).astype(np.float32)
    batches = {
        "a": jnp.asarray(np.broadcast_to(a, (8,) + a.shape).copy()),
        "b": jnp.asarray(np.broadcast_to(b, (8,) + b.shape).copy()),
    }
    st = ml.multilevel_init({"w": jnp.zeros(D)}, dims)
    rf = jax.jit(ml.make_multilevel_round(
        quad_loss, dims, periods, lr, participation=(0.5, 1.0, 1.0),
        participation_mode="fixed", participation_weighting="inverse_prob"))
    for _ in range(3):
        mkey, _ = jax.random.split(st.rng)
        keys = jax.random.split(mkey, 3)
        m1 = np.asarray(pp.sample_axis_mask(keys[0], (2,), 0.5, "fixed"))
        off = int(np.argmin(m1))
        p0 = np.asarray(st.params["w"])
        nu0 = np.asarray(st.nus[0]["w"])
        st, losses = rf(st, batches)
        np.testing.assert_array_equal(np.asarray(st.params["w"])[off], p0[off])
        np.testing.assert_array_equal(np.asarray(st.nus[0]["w"])[off], nu0[off])
        assert not np.allclose(np.asarray(st.params["w"])[1 - off],
                               p0[1 - off])
        assert np.isfinite(np.asarray(losses)).all()


# --------------------------------------------------- empty-group freeze


def _empty_group_seed(cfg, want_empty=0, tries=256):
    """A PRNG seed whose round-0 draw leaves group ``want_empty`` with no
    active clients while the other group has at least one."""
    for s in range(tries):
        masks, _ = round_masks(jax.random.PRNGKey(s), cfg)
        cm = np.asarray(masks.client)
        if cm[want_empty].sum() == 0 and cm[1 - want_empty].sum() > 0:
            return s
    raise AssertionError("no seed found")


@pytest.mark.parametrize("weighting", ["none", "inverse_prob"])
@pytest.mark.parametrize("algo", ALGORITHMS)
def test_all_empty_group_round_freezes_group_bitexact(algo, weighting):
    """A reachable group whose Bernoulli client draws all came up empty
    keeps params, z, y and dyn bit-exactly frozen -- proving the
    tree_masked_mean empty-slice fallback value is never observed under
    either weighting (it exists only to keep the program NaN-free)."""
    Gs, Ks, E, H = 2, 3, 2, 2
    _, _, batches = make_batches(Gs, Ks, E, H, seed=3)
    jb = jax.tree.map(jnp.asarray, batches)
    cfg = HFLConfig(num_groups=Gs, clients_per_group=Ks, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm=algo, prox_mu=0.1,
                    feddyn_alpha=0.1, client_participation=0.02,
                    participation_mode="uniform",
                    participation_weighting=weighting)
    seed = _empty_group_seed(cfg)
    key = jax.random.PRNGKey(seed)
    # Start from a post-round-like state with nonzero corrections so a
    # spurious update cannot hide as 0 == 0.
    warm_cfg = HFLConfig(num_groups=Gs, clients_per_group=Ks, local_steps=H,
                         group_rounds=E, lr=0.05, algorithm=algo, prox_mu=0.1,
                         feddyn_alpha=0.1)
    st = hfl_init({"w": jnp.zeros(D)}, warm_cfg)
    st, _ = jax.jit(make_global_round(quad_loss, warm_cfg))(st, jb)
    st = st._replace(rng=key)

    # dyn is carried for FedDyn only.
    before = {name: np.asarray(as_tree(getattr(st, name))["w"]).copy()
              for name in ("params", "z", "y", "dyn")
              if getattr(st, name) is not None}
    st2, m = jax.jit(make_global_round(quad_loss, cfg))(st, jb)
    assert np.isfinite(np.asarray(m.loss)).all()
    for name in ("params", "z", "dyn"):
        if name not in before:
            assert getattr(st2, name) is None, f"{algo}/{weighting}.{name}"
            continue
        np.testing.assert_array_equal(
            np.asarray(as_tree(getattr(st2, name))["w"])[0],
            before[name][0], err_msg=f"{algo}/{weighting}.{name}")
    np.testing.assert_array_equal(
        np.asarray(as_tree(st2.y)["w"])[0], before["y"][0],
        err_msg=f"{algo}/{weighting}.y")


# --------------------------------------------------- sharded round parity


@pytest.mark.parametrize("weighting", ["none", "inverse_prob"])
@pytest.mark.parametrize("flat", [False, True], ids=["tree", "flat"])
def test_sharded_partial_matches_engine(weighting, flat):
    """The production round under partial participation computes exactly
    the simulator engine, state-for-state, for both weightings and both
    state layouts (same rng => same masks; Bernoulli client + group
    sampling)."""
    Gs, Ks, E, H, lr = 2, 3, 2, 2, 0.05
    _, _, batches = make_batches(Gs, Ks, E, H, seed=21)
    jb = jax.tree.map(jnp.asarray, batches)
    pb = {k: v[:, :, None] for k, v in jb.items()}
    kw = dict(client_participation=0.5, group_participation=0.75,
              participation_mode="uniform", participation_weighting=weighting)

    cfg = HFLConfig(num_groups=Gs, clients_per_group=Ks, local_steps=H,
                    group_rounds=E, lr=lr, algorithm="mtgc",
                    use_flat_state=False, **kw)
    key = jax.random.PRNGKey(3)
    st_c = hfl_init({"w": jnp.zeros(D)}, cfg, rng=key)
    rf_c = jax.jit(make_global_round(quad_loss, cfg))
    st_p = sharded_init({"w": jnp.zeros(D)}, Gs, Ks, rng=key,
                        use_flat_state=flat)
    rf_p = jax.jit(make_sharded_round(quad_loss, E=E, H=H, lr=lr, **kw))
    for _ in range(4):
        st_c, m_c = rf_c(st_c, jb)
        st_p, m_p = rf_p(st_p, pb)
    for name in ("params", "z", "y"):
        np.testing.assert_allclose(
            np.asarray(as_tree(getattr(st_p, name))["w"]),
            np.asarray(as_tree(getattr(st_c, name))["w"]),
            rtol=1e-5, atol=1e-6, err_msg=name)
    # Same masks were drawn on both sides (rng streams advanced in lockstep).
    np.testing.assert_array_equal(np.asarray(st_p.rng), np.asarray(st_c.rng))
    np.testing.assert_allclose(float(m_p.participation),
                               float(m_c.participation), rtol=1e-6)


def test_sharded_partial_fused_matches_unfused():
    """The fused Pallas path (interpret off-TPU) applies the participation
    mask in-register identically to the where-gated reference."""
    Gs, Ks, E, H, lr = 2, 3, 2, 2, 0.05
    _, _, batches = make_batches(Gs, Ks, E, H, seed=22)
    pb = {k: jnp.asarray(v[:, :, None]) for k, v in batches.items()}
    kw = dict(client_participation=0.5, participation_mode="uniform",
              participation_weighting="inverse_prob")
    key = jax.random.PRNGKey(5)
    states = {}
    for fused, flat in ((False, False), (True, False), (True, True)):
        st = sharded_init({"w": jnp.zeros(D)}, Gs, Ks, rng=key,
                          use_flat_state=flat)
        rf = jax.jit(make_sharded_round(
            quad_loss, E=E, H=H, lr=lr, use_fused_update=fused,
            fused_mode="interpret" if fused else None, **kw))
        for _ in range(3):
            st, _ = rf(st, pb)
        states[(fused, flat)] = st
    for combo in ((True, False), (True, True)):
        for name in ("params", "z", "y"):
            np.testing.assert_allclose(
                np.asarray(as_tree(getattr(states[combo], name))["w"]),
                np.asarray(as_tree(getattr(states[(False, False)], name))["w"]),
                rtol=1e-5, atol=1e-6, err_msg=f"{combo}/{name}")


def test_sharded_partial_requires_rng():
    rf = make_sharded_round(quad_loss, E=1, H=1, lr=0.1,
                            client_participation=0.5)
    st = sharded_init({"w": jnp.zeros(D)}, 2, 2)  # rng=None
    batches = {"a": jnp.ones((1, 1, 1, 2, 2, D)),
               "b": jnp.ones((1, 1, 1, 2, 2, D))}
    with pytest.raises(ValueError, match="rng"):
        rf(st, batches)


def test_sharded_full_participation_ignores_rng_default():
    """Default (full participation) rounds still run on rng-less states --
    the pre-weighting construction path keeps working."""
    Gs, Ks, E, H = 2, 2, 1, 2
    _, _, batches = make_batches(Gs, Ks, E, H, seed=23)
    pb = {k: jnp.asarray(v[:, :, None]) for k, v in batches.items()}
    st = sharded_init({"w": jnp.zeros(D)}, Gs, Ks)
    assert st.rng is None
    rf = jax.jit(make_sharded_round(quad_loss, E=E, H=H, lr=0.05))
    st, m = rf(st, pb)
    assert float(m.participation) == 1.0
    assert np.isfinite(np.asarray(m.loss)).all()


def test_driver_runs_sharded_partial_round():
    """The compiled horizon drives the masked production round; loop vs
    scan bit-exact (the participation rng lives in the donated state)."""
    from test_driver import _assert_bitexact, _loop, make_data

    rf = make_sharded_round(quad_loss, E=2, H=2, lr=0.05,
                            client_participation=0.5,
                            participation_weighting="inverse_prob")

    def init():
        return sharded_init({"w": jnp.zeros(D)}, 2, 3,
                            rng=jax.random.PRNGKey(11))

    state_l, data_l, metrics_l = _loop(rf, init(), make_data(microbatches=2),
                                       rounds=3)
    state_d, data_d, hz = run_rounds(rf, init(), make_data(microbatches=2),
                                     3, chunk=2, donate=False)
    _assert_bitexact(state_l, state_d, metrics_l, hz.metrics,
                     ("params", "z", "y"), "sharded-partial")
    np.testing.assert_array_equal(np.asarray(state_l.rng),
                                  np.asarray(state_d.rng))

"""Partial-participation subsystem: masks, masked means, frozen state,
full-participation exactness, and the fused-update wiring."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    HFLConfig,
    as_tree,
    hfl_init,
    make_global_round,
    round_masks,
    sample_hfl_masks,
)
from repro.core import multilevel as ml
from repro.core import participation as pp
from repro.core import tree as tu
from repro.data.partition import sample_round_batches

from test_mtgc_engine import D, make_batches, quad_loss


# ------------------------------------------------------ masked-mean helpers


@settings(max_examples=20, deadline=None)
@given(g=st.integers(1, 4), k=st.integers(1, 5), trail=st.integers(1, 7))
def test_masked_mean_all_ones_equals_mean(g, k, trail):
    rng = np.random.default_rng(g * 31 + k * 7 + trail)
    a = {"w": jnp.asarray(rng.normal(size=(g, k, trail)), jnp.float32),
         "b": {"c": jnp.asarray(rng.normal(size=(g, k)), jnp.float32)}}
    ones = jnp.ones((g, k), jnp.float32)
    got = tu.tree_masked_mean(a, ones, axis=1)
    want = tu.tree_mean(a, axis=1)
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(want["w"]),
                               rtol=1e-7, atol=1e-7)
    np.testing.assert_allclose(np.asarray(got["b"]["c"]),
                               np.asarray(want["b"]["c"]), rtol=1e-7, atol=1e-7)


def test_masked_mean_ignores_masked_entries():
    """Masked-out replicas cannot poison the aggregate -- not even with NaN."""
    x = np.ones((2, 3, 4), np.float32)
    x[:, 2] = np.nan  # frozen replica holding garbage
    mask = jnp.asarray([[1, 1, 0], [1, 0, 0]], jnp.float32)
    got = tu.tree_masked_mean({"w": jnp.asarray(x)}, mask, axis=1)["w"]
    np.testing.assert_allclose(np.asarray(got), 1.0)


def test_masked_mean_empty_slice_is_exact_zero():
    """An all-masked slice recovers exact zeros -- even when the frozen
    (masked-out) entries it would have read are non-finite. Screened
    aggregation relies on this: a group whose every contribution was
    screened must not emit NaN into the (gated, unobserved) aggregate."""
    raw = np.random.default_rng(0).normal(size=(2, 3, 4)).astype(np.float32)
    raw[0, 1] = np.nan  # garbage in the empty slice's masked-out entries
    x = jnp.asarray(raw)
    mask = jnp.asarray([[0, 0, 0], [1, 1, 0]], jnp.float32)
    got = np.asarray(tu.tree_masked_mean({"w": x}, mask, axis=1)["w"])
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[0], np.zeros((4,), np.float32))
    np.testing.assert_allclose(got[1], raw[1, :2].mean(axis=0), rtol=1e-6)


def test_tree_select_keeps_frozen_bits():
    a = {"w": jnp.full((2, 2, 3), jnp.nan)}
    b = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(2, 2, 3)),
                          jnp.float32)}
    mask = jnp.asarray([[1, 0], [0, 1]], jnp.float32)
    out = np.asarray(tu.tree_select(mask, a, b)["w"])
    assert np.isnan(out[0, 0]).all() and np.isnan(out[1, 1]).all()
    np.testing.assert_array_equal(out[0, 1], np.asarray(b["w"])[0, 1])


# ----------------------------------------------------------- mask sampling


def test_fixed_count_is_nearest_half_up():
    """Nearest-count semantics: half-up tie-break, never banker's rounding
    (round(0.5 * 5) == 2 would under-sample), never zero."""
    assert pp.fixed_count(0.5, 5) == 3      # the banker's-rounding trap
    assert pp.fixed_count(0.5, 13) == 7
    assert pp.fixed_count(0.5, 4) == 2
    assert pp.fixed_count(0.5, 2) == 1
    assert pp.fixed_count(0.3, 5) == 2      # 1.5 rounds up
    assert pp.fixed_count(0.7, 5) == 4      # 3.5 rounds up
    assert pp.fixed_count(0.1, 5) == 1
    assert pp.fixed_count(0.01, 3) == 1     # never zero
    assert pp.fixed_count(1.0, 7) == 7
    assert pp.fixed_count(1.0 - 1e-9, 7) == 7


def test_inclusion_prob_modes():
    assert pp.inclusion_prob(0.5, 4, "uniform") == 0.5
    assert pp.inclusion_prob(1.0, 4, "uniform") == 1.0
    assert pp.inclusion_prob(0.5, 5, "fixed") == pytest.approx(3 / 5)
    assert pp.inclusion_prob(1.0, 5, "fixed") == 1.0
    with pytest.raises(ValueError):
        pp.inclusion_prob(0.5, 4, "bogus")


def test_sample_axis_mask_frac_one_vs_almost_one():
    """frac=1.0 short-circuits to ones without consuming randomness; an
    epsilon below 1.0 must still produce all-ones masks in both modes
    (fixed: fixed_count == n; uniform: the f32 threshold rounds to 1.0)."""
    key = jax.random.PRNGKey(0)
    shape = (3, 5)
    exact = pp.sample_axis_mask(key, shape, 1.0, "fixed")
    np.testing.assert_array_equal(np.asarray(exact), 1.0)
    for mode in ("uniform", "fixed"):
        almost = pp.sample_axis_mask(key, shape, 1.0 - 1e-9, mode)
        np.testing.assert_array_equal(np.asarray(almost), 1.0, err_msg=mode)


def test_host_and_engine_masks_agree_under_weighting():
    """round_masks host/device agreement survives the weighting config
    field: the host-derived mask still names exactly the frozen replicas
    of an inverse_prob uniform-sampling round."""
    G, K, E, H = 3, 4, 2, 2
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc",
                    client_participation=0.5, group_participation=0.75,
                    participation_mode="uniform",
                    participation_weighting="inverse_prob")
    _, _, batches = make_batches(G, K, E, H, seed=33)
    state = hfl_init({"w": jnp.zeros(D)}, cfg)
    rf = jax.jit(make_global_round(quad_loss, cfg))
    for _ in range(3):
        masks, _ = round_masks(state.rng, cfg)
        cm = np.asarray(masks.client)
        prev = np.asarray(as_tree(state.params)["w"])
        state, m = rf(state, jax.tree.map(jnp.asarray, batches))
        cur = np.asarray(as_tree(state.params)["w"])
        np.testing.assert_array_equal(cur[cm == 0], prev[cm == 0])
        if cm.sum():
            assert not np.allclose(cur[cm == 1], prev[cm == 1])
        np.testing.assert_allclose(float(m.participation), cm.mean(),
                                   rtol=1e-6)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), g=st.integers(1, 5), k=st.integers(1, 6),
       frac=st.sampled_from([0.25, 0.5, 0.75]))
def test_fixed_mode_counts_are_exact(seed, g, k, frac):
    masks = sample_hfl_masks(jax.random.PRNGKey(seed), g, k, frac, 1.0,
                             mode="fixed")
    counts = np.asarray(masks.client).sum(axis=1)
    np.testing.assert_array_equal(counts, pp.fixed_count(frac, k))
    assert np.asarray(masks.group).sum() == g


def test_group_mask_gates_clients():
    masks = sample_hfl_masks(jax.random.PRNGKey(7), 6, 4, 1.0, 0.5,
                             mode="fixed")
    gm = np.asarray(masks.group)
    cm = np.asarray(masks.client)
    assert (cm[gm == 0] == 0).all()
    assert (cm[gm == 1] == 1).all()
    assert gm.sum() == pp.fixed_count(0.5, 6)


def test_host_and_engine_masks_agree():
    """round_masks(state.rng, cfg) reproduces exactly the masks the jitted
    round consumes: a group frozen on the host view is frozen in the state."""
    G, K, E, H = 4, 3, 2, 2
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc",
                    client_participation=0.5, group_participation=0.5,
                    participation_mode="fixed")
    _, _, batches = make_batches(G, K, E, H, seed=31)
    state = hfl_init({"w": jnp.zeros(D)}, cfg)
    rf = jax.jit(make_global_round(quad_loss, cfg))
    for _ in range(3):
        masks, _ = round_masks(state.rng, cfg)
        cm = np.asarray(masks.client)
        prev = np.asarray(as_tree(state.params)["w"])
        state, m = rf(state, jax.tree.map(jnp.asarray, batches))
        cur = np.asarray(as_tree(state.params)["w"])
        np.testing.assert_array_equal(cur[cm == 0], prev[cm == 0])
        assert not np.allclose(cur[cm == 1], prev[cm == 1])
        np.testing.assert_allclose(float(m.participation), cm.mean(), rtol=1e-6)


# --------------------------------------------------- engine under partial C


def test_zero_participation_group_freezes_y_and_params():
    """A group that sits out a round keeps y_j, z, and every client frozen."""
    G, K, E, H = 2, 3, 2, 2
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc",
                    group_participation=0.5, participation_mode="fixed")
    _, _, batches = make_batches(G, K, E, H, seed=41)
    state = hfl_init({"w": jnp.zeros(D)}, cfg)
    rf = jax.jit(make_global_round(quad_loss, cfg))
    for _ in range(4):
        masks, _ = round_masks(state.rng, cfg)
        gm = np.asarray(masks.group)
        assert gm.sum() == 1  # fixed mode: exactly one of two groups
        off = int(np.argmin(gm))
        y0 = np.asarray(as_tree(state.y)["w"])
        z0 = np.asarray(as_tree(state.z)["w"])
        p0 = np.asarray(as_tree(state.params)["w"])
        state, _ = rf(state, jax.tree.map(jnp.asarray, batches))
        np.testing.assert_array_equal(np.asarray(as_tree(state.y)["w"])[off], y0[off])
        np.testing.assert_array_equal(np.asarray(as_tree(state.z)["w"])[off], z0[off])
        np.testing.assert_array_equal(np.asarray(as_tree(state.params)["w"])[off], p0[off])


def test_gradient_init_keeps_empty_group_y_frozen():
    """A reachable group whose Bernoulli client draws all came up empty must
    keep its y frozen even under correction_init='gradient' (round 0)."""
    from unittest import mock

    G, K, E, H = 2, 3, 1, 1
    _, _, batches = make_batches(G, K, E, H, seed=51)
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc",
                    correction_init="gradient", client_participation=0.5)

    def crafted(key, shape, frac, mode):
        if shape == (G,):
            return jnp.ones(shape, jnp.float32)          # both groups live
        return jnp.asarray([[0, 0, 0], [1, 1, 0]], jnp.float32)

    with mock.patch.object(pp, "sample_axis_mask", crafted):
        rf = jax.jit(make_global_round(quad_loss, cfg))
        state = hfl_init({"w": jnp.zeros(D)}, cfg)
        state2, _ = rf(state, jax.tree.map(jnp.asarray, batches))
    np.testing.assert_array_equal(np.asarray(as_tree(state2.y)["w"])[0],
                                  np.asarray(as_tree(state.y)["w"])[0])
    assert not np.allclose(np.asarray(as_tree(state2.params)["w"])[1, :2],
                           np.asarray(as_tree(state.params)["w"])[1, :2])


def test_partial_invariants_over_participants():
    """Sec. 3.2 invariants restricted to participants: the z increments sum
    to zero over each group's active clients, y stays zero-mean over the
    groups that have ever participated jointly... the per-round increment
    does."""
    G, K, E, H = 3, 4, 2, 3
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.1, algorithm="mtgc",
                    client_participation=0.5)
    _, _, batches = make_batches(G, K, E, H, seed=42)
    state = hfl_init({"w": jnp.zeros(D)}, cfg)
    rf = jax.jit(make_global_round(quad_loss, cfg))
    for _ in range(3):
        masks, _ = round_masks(state.rng, cfg)
        cm = np.asarray(masks.client)[..., None]
        y_prev = np.asarray(as_tree(state.y)["w"])
        state, m = rf(state, jax.tree.map(jnp.asarray, batches))
        # z was re-zeroed for participants, then summed increments cancel
        zsum = (np.asarray(as_tree(state.z)["w"]) * cm).sum(axis=1)
        np.testing.assert_allclose(zsum, 0.0, atol=1e-4)
        # y increments cancel over the groups active this round
        gact = (cm.sum(1) > 0).astype(np.float32)
        dy = (np.asarray(as_tree(state.y)["w"]) - y_prev) * gact
        np.testing.assert_allclose(dy.sum(axis=0), 0.0, atol=1e-4)
        assert np.isfinite(np.asarray(m.loss)).all()


def test_full_participation_config_matches_masked_all_ones():
    """C=1.0 compiles the pre-change program; the masked path fed all-ones
    masks must agree with it to float precision on params, z and y."""
    from unittest import mock

    G, K, E, H = 2, 3, 2, 2
    _, _, batches = make_batches(G, K, E, H, seed=5)
    jb = jax.tree.map(jnp.asarray, batches)
    for algo in ("mtgc", "hfedavg", "local_corr", "group_corr", "fedprox",
                 "feddyn"):
        kw = dict(num_groups=G, clients_per_group=K, local_steps=H,
                  group_rounds=E, lr=0.05, algorithm=algo, prox_mu=0.1,
                  feddyn_alpha=0.1)
        st0 = hfl_init({"w": jnp.zeros(D)}, HFLConfig(**kw))
        rf_full = jax.jit(make_global_round(quad_loss, HFLConfig(**kw)))
        s_full, _ = rf_full(st0, jb)
        with mock.patch.object(
                pp, "sample_axis_mask",
                lambda key, shape, frac, mode: jnp.ones(shape, jnp.float32)):
            rf_ones = jax.jit(make_global_round(
                quad_loss, HFLConfig(**kw, client_participation=0.5)))
            s_ones, _ = rf_ones(st0, jb)
        for name in ("params", "z", "y", "dyn"):
            a, b = getattr(s_full, name), getattr(s_ones, name)
            if a is None or b is None:  # dyn: carried for FedDyn only
                assert a is None and b is None, f"{algo}.{name}"
                continue
            np.testing.assert_allclose(
                np.asarray(as_tree(a)["w"]), np.asarray(as_tree(b)["w"]),
                rtol=1e-6, atol=1e-6, err_msg=f"{algo}.{name}")


def test_partial_mtgc_still_trains():
    G, K, E, H = 2, 4, 3, 4
    cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                    group_rounds=E, lr=0.05, algorithm="mtgc",
                    client_participation=0.5, participation_mode="fixed")
    _, _, batches = make_batches(G, K, E, H, seed=6)
    state = hfl_init({"w": jnp.zeros(D)}, cfg)
    rf = jax.jit(make_global_round(quad_loss, cfg))
    first_step = last = None
    for _ in range(20):
        state, m = rf(state, jax.tree.map(jnp.asarray, batches))
        if first_step is None:
            first_step = float(np.asarray(m.loss)[0, 0])  # loss at x ~ 0
        last = float(np.asarray(m.loss).mean())
    # mean loss at the heterogeneous optimum is positive: check the drop
    # from the untrained model, not convergence to zero
    assert np.isfinite(last) and last < 0.6 * first_step, (first_step, last)


# ------------------------------------------------------- multilevel engine


def test_multilevel_participation_none_equals_all_ones_fractions():
    dims, periods, lr = (2, 2), (4, 2), 0.05
    _, _, b4 = make_batches(2, 2, 2, 2, seed=11)
    mb = {k: jnp.asarray(v.reshape((4,) + v.shape[2:])) for k, v in b4.items()}
    st0 = ml.multilevel_init({"w": jnp.zeros(D)}, dims)
    rf_none = jax.jit(ml.make_multilevel_round(quad_loss, dims, periods, lr))
    rf_ones = jax.jit(ml.make_multilevel_round(
        quad_loss, dims, periods, lr, participation=(1.0, 1.0)))
    s1, l1 = rf_none(st0, mb)
    s2, l2 = rf_ones(st0, mb)
    np.testing.assert_array_equal(np.asarray(s1.params["w"]),
                                  np.asarray(s2.params["w"]))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_multilevel_partial_freezes_inactive_subtree():
    dims, periods, lr = (2, 2, 2), (8, 4, 2), 0.05
    rng = np.random.default_rng(12)
    a = rng.normal(size=dims + (D,)).astype(np.float32) + 2.0
    b = rng.normal(size=dims + (D,)).astype(np.float32)
    batches = {
        "a": jnp.asarray(np.broadcast_to(a, (8,) + a.shape).copy()),
        "b": jnp.asarray(np.broadcast_to(b, (8,) + b.shape).copy()),
    }
    st = ml.multilevel_init({"w": jnp.zeros(D)}, dims)
    rf = jax.jit(ml.make_multilevel_round(
        quad_loss, dims, periods, lr, participation=(0.5, 1.0, 1.0),
        participation_mode="fixed"))
    for _ in range(3):
        # replicate the engine's level-1 mask on the host
        mkey, _ = jax.random.split(st.rng)
        keys = jax.random.split(mkey, 3)
        m1 = np.asarray(pp.sample_axis_mask(keys[0], (2,), 0.5, "fixed"))
        off = int(np.argmin(m1))
        p0 = np.asarray(st.params["w"])
        nu0 = np.asarray(st.nus[0]["w"])
        st, losses = rf(st, batches)
        np.testing.assert_array_equal(np.asarray(st.params["w"])[off], p0[off])
        np.testing.assert_array_equal(np.asarray(st.nus[0]["w"])[off], nu0[off])
        assert not np.allclose(np.asarray(st.params["w"])[1 - off], p0[1 - off])
        assert np.isfinite(np.asarray(losses)).all()


# ------------------------------------------------------- fused local update


@pytest.mark.parametrize("partial_c", [1.0, 0.5])
def test_fused_update_matches_tree_map_path(partial_c):
    G, K, E, H = 2, 3, 2, 3
    _, _, batches = make_batches(G, K, E, H, seed=8)
    jb = jax.tree.map(jnp.asarray, batches)
    outs = {}
    for fused in (False, True):
        cfg = HFLConfig(num_groups=G, clients_per_group=K, local_steps=H,
                        group_rounds=E, lr=0.05, algorithm="mtgc",
                        client_participation=partial_c, use_fused_update=fused)
        state = hfl_init({"w": jnp.zeros(D)}, cfg)
        rf = jax.jit(make_global_round(quad_loss, cfg))
        for _ in range(2):
            state, _ = rf(state, jb)
        outs[fused] = state
    for name in ("params", "z", "y"):
        np.testing.assert_allclose(
            np.asarray(as_tree(getattr(outs[False], name))["w"]),
            np.asarray(as_tree(getattr(outs[True], name))["w"]),
            rtol=1e-5, atol=1e-6, err_msg=name)


def test_fused_update_rejected_for_non_mtgc():
    # ValueError, not AssertionError: config checks must survive python -O.
    cfg = HFLConfig(algorithm="fedprox", use_fused_update=True)
    with pytest.raises(ValueError):
        make_global_round(quad_loss, cfg)


# ----------------------------------------------------------- data pipeline


def test_round_batches_skip_inactive_clients():
    rng = np.random.default_rng(0)
    data_x = rng.normal(size=(200, 5)).astype(np.float32) + 10.0  # never zero
    data_y = rng.integers(0, 3, size=(200,)).astype(np.int64)
    idx = [[np.arange(100), np.arange(100)],
           [np.arange(100, 200), np.arange(100, 200)]]
    mask = np.asarray([[1, 0], [0, 1]], np.float32)
    out = sample_round_batches(data_x, data_y, idx, rng, group_rounds=2,
                               local_steps=3, batch_size=4, client_mask=mask)
    assert (out["x"][:, :, 0, 1] == 0).all() and (out["x"][:, :, 1, 0] == 0).all()
    assert (out["x"][:, :, 0, 0] != 0).all() and (out["x"][:, :, 1, 1] != 0).all()

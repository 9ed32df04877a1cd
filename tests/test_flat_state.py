"""Flat-buffer state (core/packer.py) vs the pytree reference path.

Three layers of evidence:

* pack/unpack round-trip property tests on ragged-leaf, mixed-dtype trees
  with arbitrary leading topology axes;
* engine parity: flat and tree states must agree (allclose, rtol 1e-5) on
  every state field *and* every metric after 3 global rounds, for all six
  algorithms and for partial participation under both sampling modes;
* multilevel + fused-kernel parity for the same 3-round protocol.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    ALGORITHMS,
    FlatBuffers,
    HFLConfig,
    as_tree,
    hfl_init,
    make_global_round,
    make_packer,
)
from repro.core import multilevel as ml

from test_mtgc_engine import D, make_batches, quad_loss

# ----------------------------------------------------- pack/unpack round trip


def _ragged_tree(rng, shapes, dtypes):
    leaves = [jnp.asarray(rng.normal(size=s) * 3, d) for s, d in zip(shapes, dtypes)]
    return {"a": leaves[0], "nest": {"b": leaves[1], "c": (leaves[2], leaves[3])}}


@settings(max_examples=25, deadline=None)
@given(
    s0=st.tuples(st.integers(1, 5)),
    s1=st.tuples(st.integers(1, 4), st.integers(1, 6)),
    s2=st.tuples(st.integers(1, 3), st.integers(1, 3), st.integers(1, 4)),
    lead=st.sampled_from([(), (3,), (2, 4)]),
    mixed=st.booleans(),
)
def test_pack_unpack_roundtrip_ragged(s0, s1, s2, lead, mixed):
    rng = np.random.default_rng(sum(s0) + sum(s1) + sum(s2) + len(lead))
    dtypes = ([jnp.float32, jnp.bfloat16, jnp.float32, jnp.int32] if mixed
              else [jnp.float32] * 4)
    tpl = _ragged_tree(rng, [s0, s1, s2, ()], dtypes)
    packer = make_packer(tpl)
    tree = jax.tree.map(lambda x: jnp.broadcast_to(x, lead + x.shape), tpl)
    flat = packer.flatten(tree)
    assert flat.lead_shape == lead
    # one contiguous buffer per dtype, sizes add up
    total = sum(x.size for x in jax.tree.leaves(tpl))
    assert sum(b.shape[-1] for b in flat.bufs.values()) == total
    back = packer.unflatten(flat)
    for want, got in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert want.dtype == got.dtype and want.shape == got.shape
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_flat_buffers_ride_through_jit_scan_and_grad():
    tpl = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
           "b": jnp.ones((4,), jnp.float32)}
    packer = make_packer(tpl)
    fb = packer.flatten(tpl)

    doubled = jax.jit(lambda t: jax.tree.map(lambda x: 2 * x, t))(fb)
    assert isinstance(doubled, FlatBuffers) and doubled.packer == packer

    def body(c, _):
        return jax.tree.map(lambda x: x + 1, c), 0
    scanned, _ = jax.lax.scan(body, fb, jnp.arange(3))
    assert isinstance(scanned, FlatBuffers)

    g = jax.grad(lambda t: sum(jnp.sum(b ** 2) for b in t.bufs.values()))(fb)
    assert isinstance(g, FlatBuffers)
    np.testing.assert_allclose(np.asarray(g.bufs["float32"]),
                               2 * np.asarray(fb.bufs["float32"]))


def test_as_tree_is_identity_on_pytrees():
    t = {"w": jnp.zeros(3)}
    assert as_tree(t) is t


# ----------------------------------------------------------- engine parity


def _run_engine(cfg, batches, rounds=3):
    state = hfl_init({"w": jnp.zeros(D)}, cfg)
    rf = jax.jit(make_global_round(quad_loss, cfg))
    metrics = None
    for _ in range(rounds):
        state, metrics = rf(state, batches)
    return state, metrics


def _assert_state_parity(st_tree, st_flat, m_tree, m_flat, tag):
    for name in ("params", "z", "y", "dyn"):
        a, b = getattr(st_tree, name), getattr(st_flat, name)
        if a is None or b is None:  # dyn: carried for FedDyn only
            assert a is None and b is None, f"{tag}.{name}"
            continue
        np.testing.assert_allclose(
            np.asarray(a["w"]), np.asarray(as_tree(b)["w"]),
            rtol=1e-5, atol=1e-6, err_msg=f"{tag}.{name}")
    for name in m_tree._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(m_tree, name)),
            np.asarray(getattr(m_flat, name)),
            rtol=1e-5, atol=1e-6, err_msg=f"{tag}.metrics.{name}")


@pytest.mark.parametrize("algo", ALGORITHMS)
def test_flat_matches_tree_all_algorithms(algo):
    G, K, E, H = 2, 3, 2, 3
    _, _, batches = make_batches(G, K, E, H, seed=61)
    jb = jax.tree.map(jnp.asarray, batches)
    kw = dict(num_groups=G, clients_per_group=K, local_steps=H,
              group_rounds=E, lr=0.05, algorithm=algo, prox_mu=0.1,
              feddyn_alpha=0.1)
    st_t, m_t = _run_engine(HFLConfig(use_flat_state=False, **kw), jb)
    st_f, m_f = _run_engine(HFLConfig(use_flat_state=True, **kw), jb)
    assert isinstance(st_f.params, FlatBuffers)
    assert not isinstance(st_t.params, FlatBuffers)
    _assert_state_parity(st_t, st_f, m_t, m_f, algo)


@pytest.mark.parametrize("algo", ["mtgc", "hfedavg", "feddyn"])
@pytest.mark.parametrize("mode", ["uniform", "fixed"])
def test_flat_matches_tree_partial_participation(algo, mode):
    G, K, E, H = 3, 4, 2, 3
    _, _, batches = make_batches(G, K, E, H, seed=62)
    jb = jax.tree.map(jnp.asarray, batches)
    kw = dict(num_groups=G, clients_per_group=K, local_steps=H,
              group_rounds=E, lr=0.05, algorithm=algo, feddyn_alpha=0.1,
              client_participation=0.5, group_participation=0.75,
              participation_mode=mode)
    # identical state.rng streams -> identical masks on both paths
    st_t, m_t = _run_engine(HFLConfig(use_flat_state=False, **kw), jb)
    st_f, m_f = _run_engine(HFLConfig(use_flat_state=True, **kw), jb)
    _assert_state_parity(st_t, st_f, m_t, m_f, f"{algo}/{mode}")


def test_flat_matches_tree_gradient_init():
    G, K, E, H = 2, 3, 2, 2
    _, _, batches = make_batches(G, K, E, H, seed=63)
    jb = jax.tree.map(jnp.asarray, batches)
    kw = dict(num_groups=G, clients_per_group=K, local_steps=H,
              group_rounds=E, lr=0.05, algorithm="mtgc",
              correction_init="gradient")
    st_t, m_t = _run_engine(HFLConfig(use_flat_state=False, **kw), jb)
    st_f, m_f = _run_engine(HFLConfig(use_flat_state=True, **kw), jb)
    _assert_state_parity(st_t, st_f, m_t, m_f, "gradient-init")


@pytest.mark.parametrize("partial_c", [1.0, 0.5])
def test_flat_fused_kernel_matches_tree(partial_c):
    """The batched Pallas call (interpret mode off-TPU) over the whole flat
    model, participation mask folded in, equals the per-leaf tree path."""
    G, K, E, H = 2, 3, 2, 2
    _, _, batches = make_batches(G, K, E, H, seed=64)
    jb = jax.tree.map(jnp.asarray, batches)
    kw = dict(num_groups=G, clients_per_group=K, local_steps=H,
              group_rounds=E, lr=0.05, algorithm="mtgc",
              client_participation=partial_c)
    st_t, m_t = _run_engine(HFLConfig(use_flat_state=False, **kw), jb)
    st_f, m_f = _run_engine(
        HFLConfig(use_flat_state=True, use_fused_update=True, **kw), jb)
    _assert_state_parity(st_t, st_f, m_t, m_f, f"fused/{partial_c}")


# -------------------------------------------------------- multilevel parity


@pytest.mark.parametrize("participation", [None, (1.0, 0.5, 0.5)])
def test_multilevel_flat_matches_tree(participation):
    dims, periods, lr = (2, 2, 3), (12, 4, 2), 0.05
    rng = np.random.default_rng(65)
    batches = {
        "a": jnp.asarray(rng.normal(size=(periods[0],) + dims + (D,)),
                         jnp.float32) + 2.0,
        "b": jnp.asarray(rng.normal(size=(periods[0],) + dims + (D,)),
                         jnp.float32),
    }
    rf = jax.jit(ml.make_multilevel_round(quad_loss, dims, periods, lr,
                                          participation=participation))
    st_t = ml.multilevel_init({"w": jnp.zeros(D)}, dims)
    st_f = ml.multilevel_init({"w": jnp.zeros(D)}, dims, use_flat_state=True)
    for _ in range(3):
        st_t, l_t = rf(st_t, batches)
        st_f, l_f = rf(st_f, batches)
    np.testing.assert_allclose(np.asarray(as_tree(st_f.params)["w"]),
                               np.asarray(st_t.params["w"]),
                               rtol=1e-5, atol=1e-6)
    for m in range(len(dims)):
        np.testing.assert_allclose(np.asarray(as_tree(st_f.nus[m])["w"]),
                                   np.asarray(st_t.nus[m]["w"]),
                                   rtol=1e-5, atol=1e-6, err_msg=f"nu{m}")
    np.testing.assert_allclose(np.asarray(l_f), np.asarray(l_t),
                               rtol=1e-5, atol=1e-6)

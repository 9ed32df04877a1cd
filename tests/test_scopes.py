"""The MTGC round names its layers for a profiler trace: the device scopes
reach the compiled program's ``op_name`` metadata in every state layout,
and ``fit`` writes its host spans on the profiler's clock."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.specs import abstract_data
from repro.api import ExperimentSpec, RoundSchedule, build, fit
from repro.launch.hlo_analysis import BUCKETS

ENGINE_SCOPES = ("client_step", "local_update", "state_repack", "group_agg",
                 "global_agg")
DIM = 6


def two_leaf_loss(params, batch):
    """Two parameter leaves, so that the flat state's repacks are real
    slices and concatenations."""
    r = batch["a"] * params["w"] + params["v"].sum(axis=1) - batch["b"]
    return 0.5 * jnp.sum(r * r)


def _params():
    return {"w": jnp.linspace(0.1, 0.6, DIM, dtype=jnp.float32),
            "v": jnp.ones((DIM, 2), jnp.float32)}


def _engine(**kw):
    spec = ExperimentSpec(levels=(2, 3), algorithm="mtgc",
                          schedule=RoundSchedule(group_rounds=2, local_steps=2),
                          **kw).validate()
    return build(spec, two_leaf_loss)


def test_scopes_are_declared_once():
    assert set(ENGINE_SCOPES) <= set(BUCKETS)
    assert len(set(BUCKETS)) == len(BUCKETS)


@pytest.mark.parametrize("mode", [
    {},                                   # flat, unfused: the default
    {"fusion": "fused"},                  # flat, Pallas (interpret off-TPU)
    {"state_layout": "tree"},
], ids=["flat", "flat_fused", "tree"])
def test_every_scope_reaches_the_compiled_program(mode):
    engine = _engine(**mode)
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          _params())
    hlo = engine.lower_chunk(abstract_data(engine, dim=DIM),
                             params=params).hlo
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ENGINE_SCOPES:
        if scope == "state_repack" and mode.get("state_layout") == "tree":
            continue        # a tree state has no flat buffers to repack
        assert any(re.search(rf"(^|[/(]){scope}([/)]|$)", n) for n in names), \
            scope
    if mode.get("state_layout") == "tree":
        assert not any("state_repack" in n for n in names)


def _host_events(trace_dir):
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    assert len(files) == 1
    pd = jax.profiler.ProfileData.from_file(str(files[0]))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.")]


def test_fit_writes_its_host_spans(tmp_path):
    engine = _engine()
    rng = np.random.default_rng(0)
    n = 8
    arrays = {"a": rng.normal(size=(6 * n, DIM)).astype(np.float32),
              "b": rng.normal(size=(6 * n, DIM)).astype(np.float32)}
    idx = [[np.arange(n) + n * (3 * g + k) for k in range(3)]
           for g in range(2)]
    data = engine.pack_arrays(arrays, idx, batch_size=2, shards=2,
                              rng=np.random.default_rng(1),
                              key=jax.random.PRNGKey(2))
    state, hz = fit(engine, data, 1, params=_params())      # compiles
    jax.profiler.start_trace(str(tmp_path))
    try:
        state, hz = fit(engine, hz.data, 1, state=state)
    finally:
        jax.profiler.stop_trace()
    spans = _host_events(tmp_path)
    by = {name: [(s, e) for n, s, e in spans if n == name]
          for name in ("repro.fit", "repro.dispatch", "repro.fetch")}
    assert all(len(v) == 1 for v in by.values()), spans
    (fs, fe), (ds, de), (ts, te) = (by[k][0] for k in by)
    assert fs <= ds < de <= ts < te <= fe

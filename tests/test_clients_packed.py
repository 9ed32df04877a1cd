"""The conv models' client-packed step against the vmapped per-client step.

``models/small.py`` gives the CNN and ResNet-GN a packed apply that runs
every client at once with the activations laid out ``[B, H, W, C*ch]``;
``core/engine.py`` ``_client_grads`` takes it where the loss offers one and
vmaps every other loss over ``[G, K]``. Evidence:

* model level: packed losses and gradients equal the vmapped per-client
  ``value_and_grad`` (float32 tolerance), for C in {1, 4}, GroupNorm with
  more groups than channels, and a stride-2 block with a projection;
* engine level: one round with the packed loss equals the round with the
  attribute stripped, over tree, flat and fused layouts, full and partial
  participation and ``correction_init='gradient'``; a loss without a packed
  form traces the vmapped program exactly;
* structure: the packed step holds no activation-sized transpose but the
  input's (and the CNN's fc1 flatten), where vmap's rules put dozens.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.engine as engine_mod
from repro.api import ExperimentSpec, RoundSchedule, build
from repro.models import small
from repro.models.small import cnn, lstm, make_loss, mlp, resnet_gn

B = 7                  # a batch size no width, kernel or class count shares
IMAGE = (12, 12, 3)    # spatial sizes 12, 6, 3: no client count or width


def _model(name, **kw):
    if name == "cnn":
        return cnn(10, IMAGE)
    return resnet_gn(10, IMAGE, **kw)


def _stacked(init, lead, seed=0):
    n = int(np.prod(lead))
    params = jax.vmap(init)(jax.random.split(jax.random.PRNGKey(seed), n))
    return jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), params)


def _batch(lead, seed=1, classes=10):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    return {"x": jax.random.normal(kx, lead + (B,) + IMAGE, jnp.float32),
            "y": jax.random.randint(ky, lead + (B,), 0, classes)}


def _vmapped(loss, lead):
    vg = jax.value_and_grad(loss)
    for _ in lead:
        vg = jax.vmap(vg)
    return vg


def _packed(loss):
    def total(p, b):
        losses = loss.packed(p, b)
        return jnp.sum(losses), losses

    def vg(p, b):
        (_, losses), g = jax.value_and_grad(total, has_aux=True)(p, b)
        return losses, g
    return vg


def _assert_grads_close(got, want):
    """Leafwise, within float32 rounding of the gradient's own scale: a
    bias ahead of a GroupNorm of one channel per group has a true gradient
    of 0, and both sides read rounding noise there."""
    scale = max(float(jnp.max(jnp.abs(w))) for w in jax.tree.leaves(want))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert g.shape == w.shape, jax.tree_util.keystr(path)
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


MODELS = {
    "cnn": ("cnn", {}),
    # Two stages: a stride-2 first block with a 1x1 projection shortcut.
    "resnet": ("resnet", {"widths": (8, 16), "blocks_per_stage": 1,
                          "gn_groups": 4}),
    # 8 groups over 4 and 8 channels: GroupNorm's min(groups, c) branch.
    "resnet_groups_gt_ch": ("resnet", {"widths": (4, 8),
                                       "blocks_per_stage": 1,
                                       "gn_groups": 8}),
    # A stride-2 block without a projection (equal widths) and two blocks
    # a stage.
    "resnet_deep": ("resnet", {"widths": (8, 8), "blocks_per_stage": 2,
                               "gn_groups": 2}),
}


@pytest.mark.parametrize("lead", [(1,), (4,), (2, 2)], ids=str)
@pytest.mark.parametrize("model", list(MODELS))
def test_packed_loss_and_grads_match_vmapped(model, lead):
    name, kw = MODELS[model]
    init, apply = _model(name, **kw)
    loss = make_loss(apply)
    params, batch = _stacked(init, lead), _batch(lead)
    want_l, want_g = jax.jit(_vmapped(loss, lead))(params, batch)
    got_l, got_g = jax.jit(_packed(loss))(params, batch)
    assert got_l.shape == lead
    np.testing.assert_allclose(got_l, want_l, rtol=1e-5, atol=1e-6)
    _assert_grads_close(got_g, want_g)


def test_packed_logits_match_per_client_apply():
    init, apply = _model("resnet", **MODELS["resnet"][1])
    lead = (3,)
    params, batch = _stacked(init, lead), _batch(lead)
    got = jax.jit(apply.packed)(params, batch["x"])
    want = jax.jit(jax.vmap(apply))(params, batch["x"])
    assert got.shape == lead + (B, 10)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_only_the_conv_models_have_a_packed_form():
    assert hasattr(make_loss(_model("cnn")[1]), "packed")
    assert hasattr(make_loss(_model("resnet")[1]), "packed")
    assert not hasattr(make_loss(mlp(10, 12)[1]), "packed")
    assert not hasattr(make_loss(lstm(11, hidden=8, embed=4)[1]), "packed")


# ------------------------------------------------------------- engine level

G, K, E, H = 2, 2, 2, 2


def _stripped(loss):
    """The same loss without its packed form: the engine vmaps it."""
    return lambda params, batch: loss(params, batch)


def _round_inputs(init, seed=3, lead=(E, H, G, K)):
    params = init(jax.random.PRNGKey(seed))
    return params, _batch(lead, seed=seed + 1)


ENGINE_CASES = {
    "flat": {},
    "flat_fused": {"fusion": "fused"},
    "tree": {"state_layout": "tree"},
    "tree_fused": {"state_layout": "tree", "fusion": "fused"},
    "partial": {"client_participation": 0.5},
    "partial_tree": {"client_participation": 0.5, "state_layout": "tree"},
    "gradient_init": {"correction_init": "gradient"},
    "gradient_init_partial": {"correction_init": "gradient",
                              "client_participation": 0.5},
}


@pytest.mark.parametrize("case", list(ENGINE_CASES))
def test_packed_round_matches_vmapped_round(case):
    init, apply = _model("resnet", **MODELS["resnet"][1])
    loss = make_loss(apply)
    spec = ExperimentSpec(levels=(G, K), algorithm="mtgc", lr=0.05,
                          schedule=RoundSchedule(group_rounds=E, local_steps=H),
                          **ENGINE_CASES[case]).validate()
    params, batches = _round_inputs(init)
    out = {}
    for name, fn in (("packed", loss), ("vmapped", _stripped(loss))):
        engine = build(spec, fn)
        state = engine.init(params, jax.random.PRNGKey(7))
        state, metrics = jax.jit(engine.round_fn)(state, batches)
        out[name] = (engine.global_model(state), state, metrics)
    (gp, sp, mp), (gv, sv, mv) = out["packed"], out["vmapped"]
    np.testing.assert_allclose(mp.loss, mv.loss, rtol=1e-5, atol=1e-6)
    _assert_grads_close(gp, gv)
    for field in ("params", "z", "y"):
        _assert_grads_close(getattr(sp, field), getattr(sv, field))


def _parent_client_grads(loss_fn, params, batch):
    """The client step as it was before the packed path: every loss
    vmapped over [G, K]."""
    vg = jax.value_and_grad(loss_fn)
    with jax.named_scope("client_step"):
        return jax.vmap(jax.vmap(vg))(params, batch)


def _mlp_case():
    init, apply = mlp(10, 12, hidden=16)
    params = init(jax.random.PRNGKey(0))
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    batches = {"x": jax.random.normal(kx, (E, H, G, K, B, 12)),
               "y": jax.random.randint(ky, (E, H, G, K, B), 0, 10)}
    return make_loss(apply), params, batches


def _lstm_case():
    init, apply = lstm(11, hidden=8, embed=4)
    params = init(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (E, H, G, K, B, 6), 0, 11)
    return make_loss(apply), params, {"x": tok, "y": tok}


@pytest.mark.parametrize("case", [_mlp_case, _lstm_case],
                         ids=["mlp", "lstm"])
@pytest.mark.parametrize("layout", ["flat", "tree"])
def test_loss_without_packed_form_traces_the_vmapped_program(
        case, layout, monkeypatch):
    loss, params, batches = case()
    spec = ExperimentSpec(levels=(G, K), algorithm="mtgc",
                          schedule=RoundSchedule(group_rounds=E, local_steps=H),
                          state_layout=layout).validate()

    def jaxpr():
        engine = build(spec, loss)
        state = engine.init(params, jax.random.PRNGKey(7))
        return str(jax.make_jaxpr(engine.round_fn)(state, batches))

    now = jaxpr()
    monkeypatch.setattr(engine_mod, "_client_grads", _parent_client_grads)
    assert now == jaxpr()


@pytest.mark.parametrize("model,packed", [("cnn", True), ("cnn", False),
                                          ("mlp", False)])
def test_clients_packed_scope_names_the_packed_ops_only(model, packed):
    if model == "mlp":
        loss, params, batches = _mlp_case()
    else:
        init, apply = _model(model)
        loss = make_loss(apply)
        params, batches = _round_inputs(init)
        if not packed:
            loss = _stripped(loss)
    spec = ExperimentSpec(levels=(G, K), algorithm="mtgc",
                          schedule=RoundSchedule(group_rounds=E, local_steps=H)
                          ).validate()
    engine = build(spec, loss)
    state = engine.init(params, jax.random.PRNGKey(7))
    hlo = jax.jit(engine.round_fn).lower(state, batches).as_text(
        dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', hlo)
    in_step = [n for n in names if "client_step" in n]
    assert in_step
    tagged = [n for n in in_step if "clients_packed" in n]
    if packed:
        assert tagged
        assert not [n for n in names
                    if "clients_packed" in n and "client_step" not in n]
    else:
        assert not [n for n in names if "clients_packed" in n]


# ---------------------------------------------------------------- structure


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            subs = p if isinstance(p, (list, tuple)) else (p,)
            for sub in subs:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def _activation_transposes(loss, init, lead=(G, K)):
    """Transposes in the client step's jaxpr whose operand has the batch and
    a spatial axis: an activation's (B is no width, kernel or class count,
    so a parameter never has it; a pooled feature has no spatial axis)."""
    params = jax.eval_shape(lambda: _stacked(init, lead))
    batch = jax.eval_shape(lambda: _batch(lead))
    step = jax.make_jaxpr(
        lambda p, b: engine_mod._client_grads(loss, p, b))(params, batch)
    return [e.invars[0].aval.shape for e in _eqns(step.jaxpr)
            if e.primitive.name == "transpose"
            and B in e.invars[0].aval.shape
            and {IMAGE[0], IMAGE[0] // 2, IMAGE[0] // 4}
            & set(e.invars[0].aval.shape)]


@pytest.mark.parametrize("model,allowed,vmapped", [
    # The input's one move to [B, H, W, C*c].
    ("resnet", 1, 10),
    # ... and fc1's per-client flatten to [C, B, F], forward and backward.
    ("cnn", 3, 6),
])
def test_packed_step_has_no_activation_relayouts(model, allowed, vmapped):
    kw = {"widths": (8, 16), "blocks_per_stage": 1} if model == "resnet" else {}
    init, apply = _model(model, **kw)
    loss = make_loss(apply)
    packed = _activation_transposes(loss, init)
    assert len(packed) == allowed, packed
    assert packed[0] == (G * K, B) + IMAGE      # the input
    # The same model through vmap's batching rules: a relayout around
    # every bias add and GroupNorm, forward and backward.
    assert len(_activation_transposes(_stripped(loss), init)) >= vmapped


# ------------------------------------------------- GroupNorm's backward


def _two_pass_groupnorm(p, x, groups):
    """The packed GroupNorm in the two-pass form, for autodiff: group mean
    of the H, W means, then the group mean of (x - mu)^2."""
    n = p["scale"].shape[0]
    bsz, _, _, nc = x.shape
    g = min(groups, nc // n)

    def group_mean(t):
        t = t.reshape(bsz, n * g, -1)
        t = jnp.broadcast_to(t.mean(axis=-1, keepdims=True), t.shape)
        return t.reshape(bsz, nc)[:, None, None, :]

    d = x - group_mean(x.mean(axis=(1, 2)))
    var = group_mean(jnp.square(d).mean(axis=(1, 2)))
    return (d * jax.lax.rsqrt(var + 1e-5) * p["scale"].reshape(-1)
            + p["bias"].reshape(-1))


def _gn_vjp(fn, p, x, dy, groups):
    _, vjp = jax.vjp(lambda x, p: fn(p, x, groups), x, p)
    dx, dp = vjp(dy)
    return {"dx": dx, "d_scale": dp["scale"], "d_bias": dp["bias"]}


GN_CASES = [(offset, n, c, groups) for offset in (0, 10, 1000)
            for n, c, groups in ((1, 8, 4), (4, 8, 4), (4, 4, 8))]


@pytest.mark.parametrize("offset,n,c,groups", GN_CASES, ids=[
    f"offset{o}-C{n}-c{c}-g{g}" for o, n, c, g in GN_CASES])
def test_groupnorm_vjp_is_as_precise_as_two_pass_autodiff(offset, n, c,
                                                          groups):
    """The packed GroupNorm's analytic VJP in float32 against float64
    autodiff of the two-pass form, on inputs whose mean lies ``offset``
    standard deviations from 0: each of dx, d_scale and d_bias is no
    further from float64 than float32 autodiff of the same form, with
    1.5x slack. A one-pass variance (E[x^2] - E[x]^2) or a backward that
    reduces dy x - mu sum dy loses several digits at offset 1000."""
    ks = jax.random.split(jax.random.PRNGKey(offset + 10 * n + c), 4)
    shape = (B, IMAGE[0], IMAGE[1], n * c)
    std = 2.0
    x = std * (jax.random.normal(ks[0], shape) + offset)
    dy = jax.random.normal(ks[1], shape)
    p = {"scale": 1.0 + 0.5 * jax.random.normal(ks[2], (n, c)),
         "bias": jax.random.normal(ks[3], (n, c))}
    got = _gn_vjp(small._groupnorm_packed, p, x, dy, groups)
    autodiff = _gn_vjp(_two_pass_groupnorm, p, x, dy, groups)
    with jax.enable_x64(True):
        up = lambda t: jnp.asarray(np.asarray(t, np.float64))
        want = _gn_vjp(_two_pass_groupnorm, jax.tree.map(up, p), up(x),
                       up(dy), groups)
        want = jax.tree.map(np.asarray, want)
    assert want["dx"].dtype == np.float64
    for leaf in ("dx", "d_scale", "d_bias"):
        assert got[leaf].dtype == jnp.float32
        ref = np.linalg.norm(want[leaf])
        ours = np.linalg.norm(np.asarray(got[leaf], np.float64)
                              - want[leaf]) / ref
        theirs = np.linalg.norm(np.asarray(autodiff[leaf], np.float64)
                                - want[leaf]) / ref
        assert ours <= 1.5 * theirs, (leaf, ours, theirs)


def _walk(op):
    for region in op.regions:
        for block in region.blocks:
            for inner in block.operations:
                yield inner
                yield from _walk(inner.operation)


def _activation_sized(shape):
    """An activation's shape: the batch and a spatial axis (B is no width,
    and a per-image sum ``[B, C*c]`` has no spatial axis)."""
    return B in shape and bool({IMAGE[0], IMAGE[0] // 2} & set(shape))


def test_groupnorm_gradients_come_from_per_image_sums():
    """On the lowered StableHLO of the packed ResNet's ``value_and_grad``:
    every GroupNorm scale and bias gradient is a reduction of a ``[B,
    C*c]`` array of per-image sums, not of an activation; each GroupNorm
    reduces activations 5 times (H, W means of x and (x - mu)^2 forward;
    sums of dy, dy (x - mu) and x - mu backward; autodiff made 6, two of
    them over B, H and W), all under the ``groupnorm`` scope; the other
    activation reductions are the conv biases' gradients and the pool."""
    from jax._src.lib.mlir import ir

    init, apply = _model("resnet", **MODELS["resnet"][1])
    loss = make_loss(apply)
    lead = (G, K)
    params = jax.eval_shape(lambda: _stacked(init, lead))
    batch = jax.eval_shape(lambda: _batch(lead))
    module = jax.jit(jax.value_and_grad(
        lambda p, b: jnp.sum(loss.packed(p, b)))).lower(
            params, batch).compiler_ir("stablehlo")
    main = next(op for op in module.body.operations
                if ir.StringAttr(op.attributes["sym_name"]).value == "main")
    outs = list(main.regions[0].blocks[0].operations[-1].operands)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert len(outs) == 1 + len(leaves)        # the loss, then the gradient
    gn_leaves = 0
    for (path, _), value in zip(leaves, outs[1:]):
        name = jax.tree_util.keystr(path)
        if "gn" not in name:
            continue
        gn_leaves += 1
        op = value.owner
        while op.name in ("stablehlo.reshape", "stablehlo.convert"):
            op = op.operands[0].owner
        assert op.name == "stablehlo.reduce", name
        operand = tuple(ir.RankedTensorType(op.operands[0].type).shape)
        assert operand == (B, G * K * 8 * (2 if "s1" in name else 1)), (
            name, operand)

    reductions = [op for op in _walk(module.operation)
                  if op.operation.name == "stablehlo.reduce"
                  and _activation_sized(tuple(ir.RankedTensorType(
                      op.operands[0].type).shape))]
    scoped = [op for op in reductions
              if re.search(r"(?<![\w.\-])groupnorm(?![\w.\-])",
                           str(op.location))]
    backward = [op for op in scoped if "transpose(" in str(op.location)]
    norms = gn_leaves // 2
    assert norms == 5        # stem, and gn1 and gn2 of both blocks
    assert len(scoped) - len(backward) == 2 * norms
    assert len(backward) == 3 * norms
    convs = 6                # stem, c1 and c2 of both blocks, a projection
    assert len(reductions) - len(scoped) == convs + 1

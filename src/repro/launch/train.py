"""Production MTGC training round (sharded, microbatched).

This is Algorithm 1 restructured for the multi-pod mesh: the same update
equations as ``core.engine`` (which the tests cross-check against a pure
oracle), but with

* grad accumulation over A microbatch chunks inside every local step
  (big models / long sequences do not fit a full per-client batch),
* state stacked [G, K, ...] and sharded over (group, client) with each
  replica ZeRO-3/Megatron-sharded over (fsdp, model),
* the group-global correction ``y`` kept at [G, ...] (never materialized
  per client: it broadcasts into the update via a unit axis),
* group aggregation -> all-reduce over ``client`` every H steps; global
  aggregation -> all-reduce over ``group`` (x ``pod``) every E*H steps,
* optionally (``use_fused_update``) the corrected local step runs through
  the fused Pallas ``mtgc_update`` kernel -- the microbatch mean ``g/A``,
  the corrections and the AXPY stream through VMEM in one pass instead of
  three parameter-sized HBM round-trips,
* optionally (``sharded_init(..., use_flat_state=True)``) the state lives
  in contiguous flat buffers (core/packer.py): the round detects the
  layout at trace time, repacks tree views once per group round for the
  gradient loop, folds ``z + y`` into one precomputed correction tensor,
  and runs aggregations / z / y updates as whole-model ops. Combined with
  ``use_fused_update`` the local step is a single batched Pallas call over
  the entire flat model. Flat states require params and corrections in one
  dtype (no ``correction_dtype``).

Partial participation (``client_participation`` / ``group_participation``
on :func:`make_sharded_round`) threads the same per-round ``[G]`` / ``[G,
K]`` masks as the simulator engine through the production round: masks are
drawn from ``state.rng`` exactly like ``core.participation.round_masks``
(host pipelines and the jitted round agree on who participates), inactive
replicas keep their params/z frozen via the same ``where`` gating the
fused Pallas kernel applies in-register, aggregations become masked means
with the engine's weighting semantics (``participation_weighting="none" |
"inverse_prob"``; see core/participation.py), and y updates fire only for
groups with an active client. Masks are data: the program shape -- and
under GSPMD the collective schedule -- is unchanged, inactive clients'
contributions folding to no-ops inside the same all-reduces, and with
full participation (the default) the masked machinery is compiled out
bit-for-bit. Parity with the simulator engine under partial participation
is gated in tests/test_weighting.py.

Under GSPMD this lowers to exactly the paper's two-timescale collective
schedule; local steps generate zero cross-client traffic.

K here is the *materialized cohort*, not necessarily the population: with
``ExperimentSpec.population`` set, ``core.population`` holds P >> K
virtual clients' corrections in a host store and gathers/scatters each
sampled cohort through this unchanged round between driver chunks
(``--population`` / ``--cohort-size`` / ``--client-state`` on this CLI).

Also used as the lowering target of the train_4k dry-run.

The CLI is one ``repro.api`` client: its experiment flags are generated
from the ``ExperimentSpec`` CLI table (``repro.api.add_spec_args``; this
entry point pins ``backend="sharded"`` and ``microbatches=1``), and
training runs through ``build``/``fit`` over ``core/driver.py``: the
token stream is packed into per-client shard blocks and uploaded once,
every round's batches are gathered on device, and the state buffers are
donated through each dispatch (tree and flat layouts alike). ``--chunk
N`` compiles N global rounds into a single scan dispatch (``run_rounds``;
default 1 = one donated dispatch per round, 0 = the whole horizon as one
dispatch). Chunking does not change numerics (driver parity is gated in
tests/test_driver.py) -- it bounds how much work one dispatch commits to
while amortizing dispatch overhead and returning metrics one transfer per
chunk.

CLI (example, small-enough-for-CPU config):
    PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \
        --smoke --rounds 2 --chunk 2
"""
from __future__ import annotations

import argparse
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import tree as tu
from repro.core.packer import FlatBuffers, is_flat, make_packer
from repro.core.participation import inclusion_prob, sample_hfl_masks

PyTree = Any


class ShardedHFLState(NamedTuple):
    params: PyTree   # [G, K, ...] per-client replicas
    z: PyTree        # [G, K, ...] client->group corrections
    y: PyTree        # [G, ...]    group->global corrections
    rng: jax.Array | None = None  # participation sampling key (None = full)
    round: jax.Array | None = None  # window counter (async cadences only)
    snap: PyTree | None = None   # [G, ...] last-downloaded global per group
    glob: PyTree | None = None   # [...]    last global model (delay comp.)
    dl: jax.Array | None = None  # [G] realized downloads (timeout faults + async)
    efc: PyTree | None = None    # [G, K, ...] client-link error-feedback residuals
    efg: PyTree | None = None    # [G, ...]    group-link error-feedback residuals


class ShardedMetrics(NamedTuple):
    loss: jax.Array          # [E, H] mean loss per local step (active clients)
    grad_norm: jax.Array     # scalar, last step
    z_norm: jax.Array
    y_norm: jax.Array
    participation: jax.Array  # fraction of clients active this round
    screened: jax.Array      # count of screened contributions (0 undefended)
    comm_bytes: jax.Array    # scalar modeled upload bytes on the wire this round


def sharded_init(params0: PyTree, G: int, K: int,
                 *, use_flat_state: bool = False,
                 correction_dtype=None,
                 rng: jax.Array | None = None,
                 round_counter: bool = False,
                 staleness_snapshots: bool = False,
                 fault_download: bool = False,
                 ef_client: bool = False,
                 ef_group: bool = False) -> ShardedHFLState:
    """Stacked per-client state. ``correction_dtype`` stores z/y in a
    narrower dtype (bf16) -- a beyond-paper memory optimization; the update
    math still runs in the params' dtype. Incompatible with flat states
    (one contiguous buffer per dtype requires params and corrections to
    share it). ``rng`` seeds per-round participation sampling; required by
    rounds built with partial participation, ignored otherwise.

    ``round_counter`` carries the window counter async report cadences are
    derived from; ``staleness_snapshots`` adds the per-group download
    snapshots (``snap``/``glob``) delay-compensated async rounds need (see
    core/staleness.py); ``fault_download`` carries the realized-download
    mask group-timeout faults under an async schedule need
    (core/faults.py); ``ef_client`` / ``ef_group`` carry the per-link
    error-feedback residuals compressed uploads accumulate
    (core/compression.py) -- always in the params' dtype, since they
    store upload-delta error, not corrections. All default off: the sync
    state is unchanged."""
    rnd = jnp.zeros((), jnp.int32) if round_counter else None
    dl = jnp.ones((G,), jnp.float32) if fault_download else None
    if use_flat_state:
        if correction_dtype is not None:
            raise ValueError(
                "flat state packs params and corrections into one buffer "
                "per dtype; correction_dtype needs the tree layout")
        packer = make_packer(params0)
        flat0 = packer.flatten(params0)
        stacked = FlatBuffers(
            {k: jnp.broadcast_to(b, (G, K) + b.shape) for k, b in flat0.bufs.items()},
            packer,
        )
        snap = glob = None
        if staleness_snapshots:
            glob = flat0
            snap = FlatBuffers(
                {k: jnp.broadcast_to(b, (G,) + b.shape)
                 for k, b in flat0.bufs.items()},
                packer,
            )
        return ShardedHFLState(
            params=stacked, z=packer.zeros((G, K)), y=packer.zeros((G,)),
            rng=rng, round=rnd, snap=snap, glob=glob, dl=dl,
            efc=packer.zeros((G, K)) if ef_client else None,
            efg=packer.zeros((G,)) if ef_group else None,
        )
    stacked = jax.tree.map(lambda x: jnp.broadcast_to(x, (G, K) + x.shape), params0)
    cdt = correction_dtype
    z0 = jax.tree.map(lambda x: jnp.zeros(x.shape, cdt or x.dtype), stacked)
    y0 = jax.tree.map(lambda x: jnp.zeros((G,) + x.shape, cdt or x.dtype), params0)
    snap = glob = None
    if staleness_snapshots:
        # jnp.array copies: glob must not alias the caller's params, or
        # the driver's donated scans would delete them out from under it.
        glob = jax.tree.map(jnp.array, params0)
        snap = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (G,) + x.shape), params0)
    efc0 = (jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), stacked)
            if ef_client else None)
    efg0 = (jax.tree.map(lambda x: jnp.zeros((G,) + x.shape, x.dtype), params0)
            if ef_group else None)
    return ShardedHFLState(params=stacked, z=z0, y=y0, rng=rng,
                           round=rnd, snap=snap, glob=glob, dl=dl,
                           efc=efc0, efg=efg0)


def make_sharded_round(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    *, E: int, H: int, lr: float, algorithm: str = "mtgc",
    use_fused_update: bool = False,
    fused_mode: str | None = None,
    client_participation: float = 1.0,
    group_participation: float = 1.0,
    participation_mode: str = "uniform",
    participation_weighting: str = "none",
) -> Callable[[ShardedHFLState, PyTree], tuple[ShardedHFLState, ShardedMetrics]]:
    """One MTGC global round. batches: leaves [E, H, A, G, K, chunk, ...].

    .. deprecated::
        ``make_sharded_round`` is the legacy constructor; new code should
        declare an ``ExperimentSpec(backend="sharded")`` and use
        ``repro.api.build(spec, loss_fn)`` -- this shim delegates to that
        adapter. (The returned round function reads ``(G, K)`` from the
        state it is traced with, so the spec's ``levels`` do not shape
        it -- only ``build().init`` consumes them.)

    ``algorithm``: "mtgc" | "hfedavg" (corrections off -> the paper's
    baseline, same schedule).  ``use_fused_update``
    routes the corrected step (mtgc only) through the fused Pallas kernel;
    ``fused_mode`` overrides the backend dispatch ("auto" resolves to the
    compiled kernel on TPU and the jnp oracle elsewhere; "interpret" runs
    the kernel body op-by-op for CPU validation). The returned function
    adapts at trace time to flat or pytree states (``sharded_init``'s
    ``use_flat_state``); narrow corrections (``sharded_init``'s
    ``correction_dtype``) are cast to f32 inside the update either way.

    ``client_participation`` / ``group_participation`` < 1 enable the
    engine's partial-participation semantics on the production round:
    per-round masks drawn from ``state.rng`` (``sharded_init(...,
    rng=...)``; same key schedule as ``core.participation.round_masks``),
    frozen inactive replicas, masked aggregations under
    ``participation_weighting`` ("none" realized-count | "inverse_prob"
    Horvitz-Thompson), and gated z/y updates -- matching ``core.engine``
    state-for-state (tests/test_weighting.py). The participation mask rides
    into the fused Pallas kernel in-register.
    """
    import warnings

    from repro.core.api import ExperimentSpec, RoundSchedule, build

    warnings.warn(
        "make_sharded_round is deprecated: declare an "
        "ExperimentSpec(backend='sharded') and use "
        "repro.api.build(spec, loss_fn)", DeprecationWarning, stacklevel=2)

    spec = ExperimentSpec(
        schedule=RoundSchedule(group_rounds=E, local_steps=H),
        algorithm=algorithm,
        lr=lr,
        backend="sharded",
        state_layout="tree",  # the round adapts to the state at trace time
        fusion="fused" if use_fused_update else "none",
        fused_mode=fused_mode,
        client_participation=client_participation,
        group_participation=group_participation,
        participation_mode=participation_mode,
        participation_weighting=participation_weighting,
    )
    return build(spec, loss_fn).round_fn


def _build_sharded_round(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    *, E: int, H: int, lr: float, algorithm: str = "mtgc",
    use_fused_update: bool = False,
    fused_mode: str | None = None,
    client_participation: float = 1.0,
    group_participation: float = 1.0,
    participation_mode: str = "uniform",
    participation_weighting: str = "none",
    plan=None,
    faults=None,
    defense=None,
    compression=None,
) -> Callable[[ShardedHFLState, PyTree], tuple[ShardedHFLState, ShardedMetrics]]:
    """The real production-round builder behind ``repro.api``'s adapter.

    See :func:`make_sharded_round` (the delegating shim) for the full
    semantics; parameters and the returned contract are identical.

    ``plan`` (a ``core.staleness.StalenessPlan``) switches the round into
    async group-round mode: ``E`` becomes the padded loop length
    ``max(E_g)``, the static per-group iteration mask composes with the
    participation freeze/recover machinery (and rides into the fused
    Pallas kernel in-register exactly like the client mask), and the
    global aggregation becomes the staleness-aware merge of the groups
    reporting this window -- identical semantics to the simulator engine's
    async path (see core/engine.py and core/staleness.py). ``plan=None``
    traces the legacy sync program bit for bit.

    ``faults`` / ``defense`` (``core.faults.FaultPlan`` /
    ``DefensePlan``) inject per-round crash / timeout / corrupted-upload
    faults and screen/clip uploads before aggregation -- identical
    semantics to the simulator engine's fault path (see core/faults.py).
    Disabled (or None) plans trace the legacy program, bit for bit.

    ``compression`` (``core.compression.CompressionPlan``) quantizes /
    sparsifies the upload deltas at both aggregation links, with
    per-link error-feedback residuals carried in the state
    (``sharded_init(..., ef_client=..., ef_group=...)``) and the modeled
    ``comm_bytes`` metric -- identical semantics to the simulator
    engine's compression seam (see core/compression.py): compress ->
    corrupt -> screen, so the defense sees the dequantized bytes and a
    screened contribution never pollutes a residual.
    """
    use_corr = algorithm == "mtgc"
    if algorithm not in ("mtgc", "hfedavg"):
        raise ValueError(f"unknown sharded algorithm {algorithm!r} "
                         "(choose 'mtgc' or 'hfedavg')")
    if use_fused_update and not use_corr:
        raise ValueError("use_fused_update fuses exactly g/A + z + y: mtgc only")
    if participation_mode not in ("uniform", "fixed"):
        raise ValueError(f"unknown participation mode {participation_mode!r}")
    if participation_weighting not in ("none", "inverse_prob"):
        raise ValueError(
            f"unknown participation weighting {participation_weighting!r}")
    if not (0.0 < client_participation <= 1.0
            and 0.0 < group_participation <= 1.0):
        raise ValueError("participation fractions must be in (0, 1], got "
                         f"{client_participation}/{group_participation}")
    if use_fused_update:
        from repro.kernels import ops as kops
    fmode = fused_mode or "auto"
    partial = client_participation < 1.0 or group_participation < 1.0
    ht = partial and participation_weighting == "inverse_prob"
    faults = faults if (faults is not None and faults.enabled) else None
    defense = defense if (defense is not None and defense.enabled) else None
    fault_mode = faults is not None
    defended = defense is not None
    if fault_mode:
        faults.validate()
        f_crash = faults.crash_rate > 0
        f_timeout = faults.timeout_rate > 0
        f_corrupt = faults.corrupt_rate > 0
    else:
        f_crash = f_timeout = f_corrupt = False
    if defended:
        defense.validate()
    if fault_mode or defended:
        from repro.core import faults as _flt
    comp = compression if (compression is not None
                           and compression.enabled) else None
    comp_mode = comp is not None
    if comp_mode:
        comp.validate()
        if plan is not None:
            raise ValueError(
                "compressed uploads under an async schedule are not "
                "supported yet: stale reports would need their own "
                "residual timeline (see ROADMAP)")
    # Imported unconditionally: the comm_bytes metric is reported whether
    # or not a plan is active.
    from repro.core import compression as _cmp
    comp_c = comp_mode and comp.client_mode != "none"
    comp_g = comp_mode and comp.group_mode != "none"
    ef_c = comp_mode and comp.ef_client
    ef_g = comp_mode and comp.ef_group
    comp_stoch = comp_mode and comp.stochastic
    c_noise = comp_mode and comp.client_mode == "int8_stochastic"
    # Compression kernels ride the same dispatch knob as the fused update.
    comp_dispatch = fmode if use_fused_update else "ref"
    vg = jax.vmap(jax.vmap(jax.value_and_grad(loss_fn)))  # over [G, K]
    async_mode = plan is not None
    if async_mode:
        if plan.e_pad != E:
            raise ValueError(f"E must be the padded loop length "
                             f"max(E_g)={plan.e_pad}, got {E}")
        em_all = jnp.asarray(plan.iteration_mask())              # [E_pad, G]
        dw = jnp.asarray(plan.discount_weights())                # [G]
        e_eff = jnp.asarray(plan.effective_rounds, jnp.float32)  # [G]

    def round_fn(state: ShardedHFLState, batches: PyTree):
        x, z, y = state.params, state.z, state.y
        flat = is_flat(x)
        packer = x.packer if flat else None
        G, K = jax.tree.leaves(x)[0].shape[:2]

        if partial:
            if state.rng is None:
                raise ValueError(
                    "partial participation draws per-round masks from the "
                    "state: build it with sharded_init(..., rng=key)")
            # Identical key schedule to core.participation.round_masks, so
            # host pipelines and the jitted round agree on the masks.
            mkey, rng = jax.random.split(state.rng)
            masks = sample_hfl_masks(
                mkey, G, K, client_participation, group_participation,
                participation_mode)
            cmask, gmask = masks.client, masks.group       # [G, K], [G]
            cdenom = (inclusion_prob(client_participation, K,
                                     participation_mode) * K if ht else None)
            gdenom = (inclusion_prob(group_participation, G,
                                     participation_mode) * G if ht else None)
        else:
            cmask = None
            cdenom = gdenom = None
            rng = state.rng

        if fault_mode:
            if rng is None:
                raise ValueError(
                    "fault injection draws per-round masks from the state: "
                    "build it with sharded_init(..., rng=key)")
            # Fault draw AFTER the participation draw, off the same carried
            # stream: the zero-fault rng stream is untouched.
            fm, rng = _flt.fault_masks(rng, faults, G, K)
            if f_crash:
                alive = 1.0 - fm.crash
                cmask = alive if cmask is None else cmask * alive
            if f_timeout:
                tm_keep = 1.0 - fm.timeout                 # [G]
        if comp_stoch:
            if rng is None:
                raise ValueError(
                    "stochastic compression draws rounding noise from the "
                    "state: build it with sharded_init(..., rng=key)")
            # Compression draw AFTER the participation and fault draws,
            # off the same carried stream: deterministic plans leave the
            # stream untouched.
            ckey, rng = jax.random.split(rng)
            kc, kg = jax.random.split(ckey)
        if (fault_mode or defended) and cmask is None:
            cmask = jnp.ones((G, K), jnp.float32)
        masked = cmask is not None
        if masked:
            n_active = jnp.maximum(jnp.sum(cmask), 1.0)

        if async_mode:
            if plan.num_groups != G:
                raise ValueError(f"staleness plan covers {plan.num_groups} "
                                 f"groups, state has {G}")
            if plan.needs_round_counter and state.round is None:
                raise ValueError(
                    "this async schedule derives report cadences from the "
                    "window counter: build the state with "
                    "sharded_init(..., round_counter=True) (repro.api.build "
                    "does this for you)")
            t = state.round if state.round is not None else 0
            rep = plan.report_mask(t)                      # [G]
            fresh = plan.fresh_mask(t)                     # [G]
            if f_timeout:
                if state.dl is None:
                    raise ValueError(
                        "group-timeout faults under an async schedule carry "
                        "the realized-download mask in the state: build it "
                        "with sharded_init(..., fault_download=True) "
                        "(repro.api.build does this for you)")
                rep = rep * tm_keep
                fresh = state.dl

        if use_corr:
            # Alg. 1 line 3 (with the experimental zero init of footnote 2):
            # the client-group correction restarts every global round --
            # for participants only; frozen clients keep their z. Only y
            # persists across rounds. Async: restarts per report *cycle*
            # (only groups starting from a fresh download reset).
            if async_mode:
                zmask = (fresh[:, None] * cmask if masked
                         else jnp.broadcast_to(fresh[:, None], (G, K)))
                z = tu.tree_select(zmask, tu.tree_zeros_like(z), z)
            else:
                z0 = tu.tree_zeros_like(z)
                z = tu.tree_select(cmask, z0, z) if masked else z0

        def step_loss_mean(lsum_gk, inv_a, am, n_act):
            """Scalar step loss from the per-client sums over A chunks."""
            lpc = lsum_gk * inv_a
            if defended:
                # Screen not-yet-healed corrupted clients out of the metric
                # (their uploads are screened; see core/engine.py).
                w = am * jnp.isfinite(lpc).astype(jnp.float32)
                return (jnp.sum(jnp.where(w != 0, lpc, 0))
                        / jnp.maximum(jnp.sum(w), 1.0))
            if am is not None:
                return jnp.sum(jnp.where(am != 0, lpc, 0)) / n_act
            return jnp.mean(lpc)

        def step_grad_norm(g, inv_a, am):
            if defended:
                w = am * _flt.all_finite_mask(g, 2)
                return tu.tree_masked_sq_norm(g, w) * inv_a * inv_a
            if am is not None:
                return tu.tree_masked_sq_norm(g, am) * inv_a * inv_a
            return tu.tree_sq_norm(g) * inv_a * inv_a

        def accum_grads(x_t, batch_h):
            """Per-client summed loss [G, K] + summed grads over the A
            microbatch chunks."""
            def accum(acc, batch_a):
                gsum, lsum = acc
                loss, g = vg(x_t, batch_a)
                return (tu.tree_add(gsum, g), lsum + loss), None

            A = jax.tree.leaves(batch_h)[0].shape[0]
            (g, lsum), _ = jax.lax.scan(
                accum,
                (tu.tree_zeros_like(x_t), jnp.zeros((G, K), jnp.float32)),
                batch_h,
            )
            return g, lsum, 1.0 / A

        def local_step(carry, batch_h, am, n_act):
            # batch_h leaves: [A, G, K, chunk, ...]
            x, z, y = carry
            g, lsum, inv_a = accum_grads(x, batch_h)
            if use_corr and use_fused_update:
                # Fused AXPY through VMEM: g/A + z + y and the update in one
                # pass (kernels/mtgc_update.py). The [G, K, n]-layout kernel
                # broadcasts y across clients via its block index map, so y
                # is never materialized per client even per leaf -- and the
                # participation/iteration mask gates frozen replicas
                # in-register.
                def fused_leaf(xi, gi, zi, yi):
                    Gl, Kl = xi.shape[:2]
                    out = kops.mtgc_update_flat(
                        xi.reshape(Gl, Kl, -1), gi.reshape(Gl, Kl, -1),
                        zi.reshape(Gl, Kl, -1), yi.reshape(Gl, -1),
                        am, lr=lr, g_scale=inv_a, mode=fmode)
                    return out.reshape(xi.shape)

                x = jax.tree.map(fused_leaf, x, g, z, y)
            elif use_corr:
                x_new = jax.tree.map(
                    lambda xi, gi, zi, yi: xi - lr * (
                        gi * inv_a + zi.astype(gi.dtype) + yi[:, None].astype(gi.dtype)
                    ),
                    x, g, z, y,
                )
                x = tu.tree_select(am, x_new, x) if am is not None else x_new
            else:
                x_new = jax.tree.map(lambda xi, gi: xi - lr * gi * inv_a, x, g)
                x = tu.tree_select(am, x_new, x) if am is not None else x_new
            return (x, z, y), (step_loss_mean(lsum, inv_a, am, n_act),
                               step_grad_norm(g, inv_a, am))

        def local_phase_flat(x, z, y, batch_e, am, n_act):
            """H local steps on a flat state, repacking at the phase edge.

            z/y are constant inside the phase: their sum collapses into one
            precomputed correction tensor (non-fused) or feeds the single
            batched Pallas call over the whole flat model (fused); the
            participation gate folds into the same expression.
            """
            if use_corr and use_fused_update:
                def step(xf, batch_h):
                    g, lsum, inv_a = accum_grads(packer.unflatten(xf), batch_h)
                    gf = packer.flatten(g)
                    xf = FlatBuffers(
                        {k: kops.mtgc_update_flat(
                            xf.bufs[k], gf.bufs[k], z.bufs[k], y.bufs[k],
                            am, lr=lr, g_scale=inv_a, mode=fmode)
                         for k in xf.bufs},
                        packer,
                    )
                    return xf, (step_loss_mean(lsum, inv_a, am, n_act),
                                step_grad_norm(gf, inv_a, am))

                return jax.lax.scan(step, x, batch_e)

            corr_t = (packer.unflatten(
                jax.tree.map(lambda zb, yb: zb + yb[:, None], z, y))
                if use_corr else None)

            def step(x_t, batch_h):
                g, lsum, inv_a = accum_grads(x_t, batch_h)
                if use_corr:
                    x_new = jax.tree.map(
                        lambda xi, gi, ci: xi - lr * (gi * inv_a + ci),
                        x_t, g, corr_t)
                else:
                    x_new = jax.tree.map(
                        lambda xi, gi: xi - lr * gi * inv_a, x_t, g)
                if am is not None:
                    x_t = jax.tree.map(
                        lambda xn, xi: jnp.where(
                            tu.expand_mask(am, xn) != 0, xn, xi),
                        x_new, x_t)
                else:
                    x_t = x_new
                return x_t, (step_loss_mean(lsum, inv_a, am, n_act),
                             step_grad_norm(g, inv_a, am))

            x_t, out = jax.lax.scan(step, packer.unflatten(x), batch_e)
            return packer.flatten(x_t), out

        def group_round(carry, inp):
            # batch_e leaves: [H, A, G, K, chunk, ...]
            x, z, y, efc = carry
            if async_mode:
                # Iteration liveness joins the participation mask: a
                # straggler past its E_g rounds this window is frozen
                # exactly like an unsampled client, so aggregation, z
                # update and dissemination below need no further gating.
                batch_e, em = inp
                am = (em[:, None] * cmask if masked
                      else jnp.broadcast_to(em[:, None], (G, K)))
                n_act = jnp.maximum(jnp.sum(am), 1.0)
            else:
                if c_noise:
                    batch_e, ek = inp
                else:
                    batch_e = inp
                    ek = None
                am = cmask if masked else None
                n_act = n_active if masked else None
            x_start = x  # phase-start model: upload deltas are vs this
            if flat:
                x, (losses, gnorm) = local_phase_flat(x, z, y, batch_e,
                                                      am, n_act)
            else:
                (x, z, y), (losses, gnorm) = jax.lax.scan(
                    lambda c, b: local_step(c, b, am, n_act), (x, z, y),
                    batch_e)
            # Upload view: compression first -- the wire carries the
            # dequantized delta, so corruption faults rewrite (and the
            # defense screens) exactly what the group server would
            # reconstruct; clean/frozen clients keep their exact bits
            # either way (where-selects, never arithmetic).
            x_end = x
            if comp_c:
                delta = tu.tree_sub(x, x_start)
                u = tu.tree_add(delta, efc) if ef_c else delta
                deq = _cmp.roundtrip(
                    u, mode=comp.client_mode, lead_ndim=2,
                    frac=comp.topk_frac, key=ek, dispatch=comp_dispatch)
                x_cmp = tu.tree_add(x_start, deq)
                x = tu.tree_select(am, x_cmp, x) if am is not None else x_cmp
            if f_corrupt:
                x = _flt.corrupt_uploads(x_start, x, fm.corrupt * am, faults)
            if defended:
                x, ok = _flt.screen_and_clip(x_start, x, defense)
                smask = am * ok
                scr = jnp.sum(am) - jnp.sum(smask)
            else:
                smask = am
            # Correction-state view: z is client-side state, updated from
            # the client's *own* local model plus the received broadcast --
            # the error-feedback residual re-applied on the wire must never
            # enter z (released residual mass fed back through the
            # correction destabilizes EF). Uncompressed, the wire view is
            # the local model and the legacy program is untouched.
            x_loc = x
            if comp_c:
                x_loc = x_end
                if f_corrupt:
                    x_loc = _flt.corrupt_uploads(x_start, x_loc,
                                                 fm.corrupt * am, faults)
            if ef_c:
                # Residual carries forward only for contributions that
                # entered the aggregate: a screened or inactive client
                # leaves its error-feedback state untouched.
                err = tu.tree_sub(u, deq)
                efc = (tu.tree_select(smask, err, efc)
                       if smask is not None else err)
            with jax.named_scope("group_agg"):
                # Group aggregation: mean over (active, surviving) clients;
                # under inverse_prob the masked sum divides by the expected
                # count.
                xbar = (tu.tree_masked_mean(x, smask, axis=1, denom=cdenom)
                        if smask is not None else tu.tree_mean(x, axis=1))
            if use_corr:
                # z_i += (x_{i,H} - xbar_j) / (H * lr)   (Alg. 1 line 9)
                # Gated on the screen mask: screened contributions never
                # integrate into the correction state.
                z_new = jax.tree.map(
                    lambda zi, xe, xb: (
                        zi.astype(jnp.float32)
                        + (xe.astype(jnp.float32) - xb[:, None].astype(jnp.float32)) / (H * lr)
                    ).astype(zi.dtype),
                    z, x_loc, xbar,
                )
                z = tu.tree_select(smask, z_new, z) if smask is not None else z_new
            # dissemination: every active client restarts from its group
            # model; frozen clients keep their params. Under the defense,
            # screened-but-active clients also download (healing) -- unless
            # the whole group was screened (hardened zero mean), in which
            # case its active clients revert to the phase-start model so a
            # screened upload never survives into the global recovery mean
            # (x_start is bit-identical to x for frozen clients).
            xbar_b = jax.tree.map(
                lambda xb, xi: jnp.broadcast_to(xb[:, None], xi.shape), xbar, x
            )
            if smask is None:
                x = xbar_b
            elif defended:
                has_srv = (jnp.sum(smask, axis=1) > 0).astype(jnp.float32)
                x = tu.tree_select(am * has_srv[:, None], xbar_b, x_start)
            else:
                x = tu.tree_select(am, xbar_b, x)
            out = (losses, gnorm, scr) if defended else (losses, gnorm)
            return (x, z, y, efc), out

        if ef_c:
            if state.efc is None:
                raise ValueError(
                    "client-link error feedback carries per-client "
                    "residuals in the state: build it with "
                    "sharded_init(..., ef_client=True) (repro.api.build "
                    "does this for you)")
            efc = state.efc
        else:
            efc = None
        if async_mode:
            scan_xs = (batches, em_all)
        elif c_noise:
            scan_xs = (batches, jax.random.split(kc, E))
        else:
            scan_xs = batches
        (x, z, y, efc), scan_out = jax.lax.scan(
            group_round, (x, z, y, efc), scan_xs)
        if defended:
            losses, gnorms, scrs = scan_out
            screened = jnp.sum(scrs)
        else:
            losses, gnorms = scan_out
            screened = jnp.zeros((), jnp.float32)

        # --- global aggregation + y update (Alg. 1 lines 10-11) ----------
        if ef_g:
            if state.efg is None:
                raise ValueError(
                    "group-link error feedback carries per-group residuals "
                    "in the state: build it with sharded_init(..., "
                    "ef_group=True) (repro.api.build does this for you)")
            efg = state.efg
        else:
            efg = None

        def compress_group(xbar_j, gref, gact):
            """Group -> global link: compress each group's aggregate delta
            vs its round-start reference; inactive groups keep their exact
            (unused) report bits."""
            gdelta = tu.tree_sub(xbar_j, gref)
            ug = tu.tree_add(gdelta, efg) if ef_g else gdelta
            deqg = _cmp.roundtrip(ug, mode=comp.group_mode, lead_ndim=1,
                                  frac=comp.topk_frac,
                                  key=kg if comp_stoch else None,
                                  dispatch=comp_dispatch)
            xbar_c = tu.tree_add(gref, deqg)
            if gact is not None:
                xbar_c = tu.tree_select(gact, xbar_c, xbar_j)
            return xbar_c, ug, deqg

        if async_mode:
            # Staleness-aware merge of the groups reporting this window:
            # same semantics as the simulator engine's async path (see
            # core/engine.py and core/staleness.py), f32 math for narrow
            # correction dtypes.
            if masked:
                gact = (jnp.sum(cmask, axis=1) > 0).astype(jnp.float32)
                gup = jnp.sum(rep * gact)   # reports actually sent
                with jax.named_scope("global_agg"):
                    xbar_j = tu.tree_masked_mean(x, cmask, axis=1)
                if defended and defense.screen_nonfinite:
                    # Backstop group-level screen before the merge.
                    gfin = _flt.all_finite_mask(xbar_j, 1)
                    screened = screened + jnp.sum(
                        cmask * ((gact * (1.0 - gfin))[:, None]))
                    gact = gact * gfin
                obs = rep * gact
            else:
                xbar_j = jax.tree.map(lambda xi: xi[:, 0], x)
                obs = rep
                gup = jnp.sum(rep)
            if plan.needs_snapshots:
                if state.snap is None or state.glob is None:
                    raise ValueError(
                        "staleness='delay_compensated' carries per-group "
                        "download snapshots in the state: build it with "
                        "sharded_init(..., staleness_snapshots=True) "
                        "(repro.api.build does this for you)")
                xbar_used = jax.tree.map(
                    lambda xj, gl, sn: xj + (jnp.expand_dims(gl, 0) - sn),
                    xbar_j, state.glob, state.snap)
            else:
                xbar_used = xbar_j

            w = rep * dw
            if partial and ht:
                wsum = w * gmask
                sup = wsum * gact
                den = (gdenom / G) * jnp.sum(w)
            elif masked:
                wsum = w * gact
                sup = wsum
                den_raw = jnp.sum(wsum)
                den = jnp.where(den_raw > 0, den_raw, 1.0)
            else:
                wsum = w
                sup = wsum
                den = jnp.sum(w)

            def _stale_merge(v):
                live = tu.expand_mask(sup, v) != 0
                return jnp.sum(
                    jnp.where(live, v, 0) * tu.expand_mask(wsum, v),
                    axis=0) / den

            with jax.named_scope("global_agg"):
                xbar = jax.tree.map(_stale_merge, xbar_used)
        elif masked and (fault_mode or defended or comp_g):
            # The recovery/estimation split opened up so timeouts, the
            # group-level finite screen and the compressed report compose
            # into the estimation path (identical to the simulator
            # engine's fault path).
            with jax.named_scope("global_agg"):
                xbar_j = tu.tree_masked_mean(x, cmask, axis=1)
                gact = (jnp.sum(cmask, axis=1) > 0).astype(jnp.float32)
                if f_timeout:
                    gact = gact * tm_keep
                gup = jnp.sum(gact)   # reports actually sent (pre-screen)
                if comp_g:
                    # Reference the group server and the global server
                    # share: the participating replicas' round-start mean.
                    gref = tu.tree_masked_mean(state.params, cmask, axis=1)
                    xbar_srv = xbar_j  # group's own (pre-wire) aggregate
                    xbar_j, ug, deqg = compress_group(xbar_j, gref, gact)
                if defended and defense.screen_nonfinite:
                    gfin = _flt.all_finite_mask(xbar_j, 1)
                    screened = screened + jnp.sum(
                        cmask * ((gact * (1.0 - gfin))[:, None]))
                    gact = gact * gfin
                if ht:
                    xbar_j0 = jax.tree.map(
                        lambda v: jnp.where(
                            tu.expand_mask(gact, v) != 0, v, 0), xbar_j)
                    xbar = tu.tree_masked_mean(xbar_j0, gmask, axis=0,
                                               denom=gdenom)
                else:
                    xbar = tu.tree_masked_mean(xbar_j, gact, axis=0)
        elif partial:
            with jax.named_scope("global_agg"):
                # Same recovery-then-estimate aggregate as the simulator
                # engine (tree_group_global_mean), keeping the two round
                # builders in lockstep for the parity gates.
                xbar_j, xbar, gact = tu.tree_group_global_mean(
                    x, cmask, gmask if ht else None, gdenom)
            gup = jnp.sum(gact)
        else:
            xbar_j = jax.tree.map(lambda xi: xi[:, 0], x)    # clients equal
            gup = jnp.float32(G)
            if comp_g:
                gref = jax.tree.map(lambda xi: xi[:, 0], state.params)
                xbar_srv = xbar_j  # group's own (pre-wire) aggregate
                xbar_j, ug, deqg = compress_group(xbar_j, gref, None)
            with jax.named_scope("global_agg"):
                xbar = tu.tree_mean(xbar_j, axis=0)
        if ef_g:
            # Gated on the FINAL estimation mask (post timeout + screen):
            # a screened or timed-out report never pollutes the residual.
            errg = tu.tree_sub(ug, deqg)
            efg = tu.tree_select(gact, errg, efg) if masked else errg
        if use_corr:
            if async_mode:
                # y_j += (report_j - xbar) / (H * E_j * r_j * lr): a
                # reporting group ran E_j * r_j group rounds since its
                # download. The policy discount dw weights the merge only
                # -- the y tracking update runs at full rate (see
                # core/staleness.py).
                coef = 1.0 / (e_eff * H * lr)                         # [G]
                y_new = jax.tree.map(
                    lambda yj, xj, xg: (
                        yj.astype(jnp.float32)
                        + tu.expand_mask(coef, yj)
                        * (xj.astype(jnp.float32)
                           - jnp.expand_dims(xg.astype(jnp.float32), 0))
                    ).astype(yj.dtype),
                    y, xbar_used, xbar,
                )
                y = tu.tree_select(obs, y_new, y)
            else:
                # Like z above, y is group-server-side state: it updates
                # from the group's own aggregate (pre-wire), never from
                # the dequantized view carrying the EF residual.
                y_src = xbar_srv if comp_g else xbar_j
                y_new = jax.tree.map(
                    lambda yj, xj, xg: (
                        yj.astype(jnp.float32)
                        + (xj.astype(jnp.float32) - xg.astype(jnp.float32)) / (H * E * lr)
                    ).astype(yj.dtype),
                    y, y_src, xbar,
                )
                y = tu.tree_select(gact, y_new, y) if masked else y_new
        x_glob = jax.tree.map(
            lambda xg: jnp.broadcast_to(xg, (G, K) + xg.shape), xbar
        )
        if async_mode:
            if fault_mode or defended:
                # No download from a window that aggregated nothing (every
                # report screened/timed out: hardened exact-zero merge).
                any_obs = (jnp.sum(obs) > 0).astype(jnp.float32)
                dmask = rep[:, None] * cmask * any_obs
            elif masked:
                # Only reporting groups download; stragglers keep their
                # mid-cycle replicas.
                dmask = rep[:, None] * cmask
            else:
                dmask = jnp.broadcast_to(rep[:, None], (G, K))
            x = tu.tree_select(dmask, x_glob, x)
        else:
            if fault_mode or defended:
                # Timed-out groups miss the download too; no one downloads
                # a global mean with zero surviving groups.
                any_g = (jnp.sum(gact) > 0).astype(jnp.float32)
                dm = cmask * any_g
                if f_timeout:
                    dm = dm * tm_keep[:, None]
                x = tu.tree_select(dm, x_glob, x)
            elif masked:
                x = tu.tree_select(cmask, x_glob, x)
            else:
                x = x_glob

        snap, glob = state.snap, state.glob
        if async_mode and plan.needs_snapshots:
            any_obs = (jnp.sum(obs) > 0).astype(jnp.float32)
            snap = tu.tree_select(
                obs, jax.tree.map(
                    lambda xg, sn: jnp.broadcast_to(
                        jnp.expand_dims(xg, 0), sn.shape), xbar, snap),
                snap)
            glob = tu.tree_select(any_obs, xbar, glob)
        dl = state.dl
        if async_mode and f_timeout:
            # Realized downloads this window (rep already excludes timed-out
            # groups): next round's freshness for the z re-init.
            dl = rep * any_obs
        new_round = None if state.round is None else state.round + 1
        # Bytes on the wire: uploads *sent* this round (screened uploads
        # were transmitted; crashed/unsampled clients and timed-out groups
        # sent nothing), priced by core/compression.py's wire model.
        if async_mode:
            n_up_c = (jnp.sum(em_all[:, :, None] * cmask[None]) if masked
                      else jnp.sum(em_all) * K)
        else:
            n_up_c = (E * jnp.sum(cmask) if masked
                      else jnp.float32(E * G * K))
        comm = _cmp.round_comm_bytes(state.params, comp, n_up_c, gup)
        metrics = ShardedMetrics(
            loss=losses,
            grad_norm=gnorms[-1, -1],
            z_norm=tu.tree_sq_norm(z) / (G * K),
            y_norm=tu.tree_sq_norm(y) / G,
            participation=(jnp.sum(cmask) / (G * K)) if masked
            else jnp.ones((), jnp.float32),
            screened=screened,
            comm_bytes=comm,
        )
        return ShardedHFLState(params=x, z=z, y=y, rng=rng, round=new_round,
                               snap=snap, glob=glob, dl=dl,
                               efc=efc if ef_c else state.efc,
                               efg=efg if ef_g else state.efg), metrics

    return round_fn


# --------------------------------------------------------------------- CLI


def setup_run(arch: str, spec, *, smoke: bool, seq: int, batch: int,
              seed: int, shards: int):
    """Model, engine and packed token data for one ``fit`` of ``arch``.

    ``smoke`` trains ``cfg.reduced()`` (2 layers, d<=512) in place of the
    published widths. Returns ``(cfg, params, engine, data)``.
    """
    import numpy as np

    from repro.configs import get_arch
    from repro.core.api import build
    from repro.data.lm import make_lm_tokens
    from repro.models.transformer import build_model

    cfg = get_arch(arch)
    if smoke:
        cfg = cfg.reduced()
    bundle = build_model(cfg)
    rng = np.random.default_rng(seed)
    toks, _ = make_lm_tokens(rng, cfg.vocab_size, 200_000)
    params = bundle.init(jax.random.PRNGKey(seed))
    engine = build(spec, bundle.loss)
    data = engine.pack_tokens(
        toks, batch_size=batch, seq_len=seq, shards=shards,
        rng=rng, key=jax.random.PRNGKey(seed + 1))
    return cfg, params, engine, data


def main() -> None:
    from repro.core.api import (
        ExperimentSpec,
        RoundSchedule,
        add_spec_args,
        fit,
        spec_from_args,
    )
    from repro.launch.compile_cache import enable_compile_cache

    # Spec flags (--levels/--E/--H/--algorithm/--lr/--state-layout/...) are
    # generated from repro.api's one declarative CLI table; this entry
    # point pins backend="sharded" and microbatches=1 and only hand-keeps
    # the flags that are not ExperimentSpec fields.
    defaults = ExperimentSpec(
        backend="sharded", lr=0.05, state_layout="tree",
        schedule=RoundSchedule(group_rounds=2, local_steps=2, microbatches=1))
    ap = argparse.ArgumentParser(description=__doc__)
    add_spec_args(ap, defaults=defaults, exclude=("backend",))
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (2 layers, d<=512)")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=1,
                    help="global rounds per compiled scan dispatch "
                         "(core/driver.py run_rounds); 0 = the whole "
                         "horizon as one dispatch")
    ap.add_argument("--shards", type=int, default=8,
                    help="packed batch blocks per client uploaded once "
                         "(on-device batch selection)")
    args = ap.parse_args()
    enable_compile_cache()

    spec = spec_from_args(args, defaults=defaults, backend="sharded",
                          microbatches=1)
    cfg, params, engine, data = setup_run(
        args.arch, spec, smoke=args.smoke, seq=args.seq, batch=args.batch,
        seed=args.seed, shards=args.shards)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] arch={cfg.name} params={n_params/1e6:.1f}M "
          f"algo={spec.algorithm}")
    if spec.population is not None:
        G, K = spec.levels
        if spec.client_state == "stateful":
            # Segment-table arithmetic (Packer.state_bytes): the host
            # store holds [G, P] correction rows, the device only [G, K].
            from repro.core.packer import make_packer
            per_client = make_packer(params).state_bytes()
            nfields = len(engine.population_fields)
            print(f"[train] population={spec.population}/group cohort={K} "
                  f"store={G * spec.population * per_client * nfields/1e6:.1f}"
                  f"MB host, device corrections "
                  f"{G * K * per_client * nfields/1e6:.1f}MB")
        else:
            print(f"[train] population={spec.population}/group cohort={K} "
                  "stateless (no store)")
    state, hz = fit(
        engine, data, args.rounds, params=params,
        rng=(jax.random.PRNGKey(args.seed + 2)
             if not spec.full_participation else None),
        chunk=args.chunk)

    for t in range(args.rounds):
        print(f"round {t}: loss {float(hz.metrics.loss[t].mean()):.4f} "
              f"z^2 {float(hz.metrics.z_norm[t]):.3e} "
              f"y^2 {float(hz.metrics.y_norm[t]):.3e}")


if __name__ == "__main__":
    main()

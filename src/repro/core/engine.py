"""The hierarchical-FL round engine (paper Algorithm 1, generalized).

One *global round* ``t`` is the engine's unit of work -- a single jittable
program (though no longer the largest one: ``core/driver.py`` lifts whole
training horizons over this round function into one compiled
scan-over-rounds with donated state buffers and on-device batch
selection; the round function itself is driver-agnostic):

    for e in range(E):                 # lax.scan over group rounds
        for h in range(H):             # lax.scan over local steps
            g_i   = grad F_i(x_i, xi)                  # vmapped over [G, K]
            x_i  -= lr * (g_i + z_i + y_j [+ prox/dyn terms])
        group aggregation + z update (Alg. 1, lines 8-9)
    global aggregation + y update     (Alg. 1, lines 10-11)

All per-client state is stacked with leading axes ``[G, K, ...]`` so the same
engine runs (a) as a CPU simulator for the paper's experiments and (b) under
GSPMD with the leading axes sharded over the (group, client) mesh axes, where
the group/global aggregations lower to hierarchical all-reduces.

Baselines are the same engine with corrections toggled off (HFedAvg), one
correction only (local / group correction, Fig. 4), or with FedProx / FedDyn
gradient modifiers (Fig. 3).

Partial participation (beyond the paper, the regime where correction
methods are stress-tested): when ``cfg.client_participation`` /
``cfg.group_participation`` < 1, per-round 0/1 masks are drawn from
``state.rng`` (see ``core.participation``); inactive clients keep their
params and corrections frozen, every aggregation becomes a masked mean, and
``z``/``y`` updates fire only for participants. Masks are data, not
structure -- the scans and the jitted program shape are unchanged. With
full participation the masked machinery is compiled out entirely, so the
default path is bit-for-bit the paper engine.

``cfg.participation_weighting`` picks the masked-mean estimator:
``"none"`` divides by the realized participant count (the subpopulation
mean), ``"inverse_prob"`` divides by the expected count (Horvitz-Thompson
-- group client-means by ``inclusion_prob(C_k) * K``, the global
group-mean by ``inclusion_prob(C_g) * G`` over *reachable* groups, with a
reachable-but-empty group legitimately contributing zero). The same
denominators flow into the z/y control-variable updates and the
``correction_init='gradient'`` means, so the averages the corrections
track stay unbiased under Bernoulli sampling instead of compounding the
count randomness across both timescales (tests/test_weighting.py). State
gating is weighting-independent: frozen replicas stay frozen, y updates
still fire only for groups with at least one active client.

Flat state (``cfg.use_flat_state``, default on): ``hfl_init`` packs params,
``z`` and ``dyn`` into contiguous ``[G, K, N]`` buffers (one per dtype) and
``y`` into ``[G, N]`` (see ``core.packer``); the round function detects the
layout at trace time from the state itself. Every aggregation, correction
update, drift norm and dissemination then runs as a single whole-model op
instead of per-leaf dispatch. The gradient hot loop still consumes tree
views -- ``packer.unflatten`` produces them once per *local phase* (not per
step, so the hot loop pays no repack traffic), the phase constants z and y
unpack once at the phase boundary (y deliberately kept ``[G, N]``, a
factor K smaller than the replicas, broadcasting per step), and the
participation ``where`` folds into the same fused update expression. With
``use_fused_update`` the local step becomes a single batched Pallas call
over the entire flat model (mask folded in, ``y`` broadcast by the kernel's
index map; kernels/mtgc_update.py) -- the TPU path. Flat/tree parity is
enforced by tests/test_flat_state.py; models are untouched either way.

Cohort shapes: the round reads ``G, K`` from the state's leading axes at
trace time, never from a global registry -- so ``K`` need not be the whole
client population. ``core.population`` exploits exactly this: it keeps a
host-side store of per-client corrections for ``P >> K`` virtual clients
and swaps each sampled cohort's rows in and out of the same ``[G, K,
...]`` state between driver chunks, leaving this round function byte-for-
byte unchanged.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import tree as tu
from repro.core.config import HFLConfig
from repro.core.packer import FlatBuffers, as_tree, is_flat, make_packer
from repro.core.participation import inclusion_prob, round_masks

PyTree = Any


class HFLState(NamedTuple):
    """State carried between global rounds.

    params: [G, K, ...]  per-client models (all equal right after a round
                         under full participation; frozen replicas keep
                         stale params under partial participation).
    z:      [G, K, ...]  client->group correction (zeros when unused).
    y:      [G, ...]     group->global correction (zeros when unused).
    dyn:    [G, K, ...]  FedDyn gradient memory -- only carried for
                         ``algorithm="feddyn"``; None otherwise (no
                         pytree leaves: a dead G*K*N buffer would cost
                         as much device memory as the replicas).
    rng:    PRNG key for stochastic batching / participation sampling.
    round:  global round counter t.
    snap:   [G, ...]     global model each group last downloaded -- only
                         carried for delay-compensated async rounds
                         (``hfl_init(..., staleness_snapshots=True)``);
                         None otherwise (no pytree leaves).
    glob:   [...]        the last aggregated global model, paired with
                         ``snap`` (None otherwise).
    dl:     [G]          realized-download mask: which groups actually
                         downloaded at the end of the last window -- only
                         carried when group-timeout faults meet an async
                         schedule (``hfl_init(..., fault_download=True)``),
                         where the static fresh cadence no longer predicts
                         downloads; None otherwise (no pytree leaves).
    efc:    [G, K, ...]  client-link error-feedback residual -- only
                         carried when a ``CompressionPlan`` with error
                         feedback compresses the client->group uploads
                         (``hfl_init(..., ef_client=True)``); None
                         otherwise (no pytree leaves).
    efg:    [G, ...]     group-link error-feedback residual, likewise
                         (``hfl_init(..., ef_group=True)``).
    """

    params: PyTree
    z: PyTree
    y: PyTree
    dyn: PyTree
    rng: jax.Array
    round: jax.Array
    snap: PyTree | None = None
    glob: PyTree | None = None
    dl: jax.Array | None = None
    efc: PyTree | None = None
    efg: PyTree | None = None


class RoundMetrics(NamedTuple):
    loss: jax.Array          # [E, H] mean training loss per local step
    client_drift: jax.Array  # [E] mean ||x_i - xbar_j||^2 at group agg
    group_drift: jax.Array   # scalar mean ||xbar_j - xbar||^2 at global agg
    z_norm: jax.Array        # scalar mean ||z||^2 after the round
    y_norm: jax.Array        # scalar mean ||y||^2 after the round
    participation: jax.Array  # scalar fraction of clients active this round
    screened: jax.Array      # scalar count of screened contributions (0 undefended)
    comm_bytes: jax.Array    # scalar modeled upload bytes on the wire this round


def hfl_init(params0: PyTree, cfg: HFLConfig, rng: jax.Array | None = None,
             *, staleness_snapshots: bool = False,
             fault_download: bool = False, ef_client: bool = False,
             ef_group: bool = False) -> HFLState:
    """Broadcast a single model to every client and zero the corrections.

    With ``cfg.use_flat_state`` the state leaves are contiguous flat
    buffers (FlatBuffers; see core/packer.py) rather than model pytrees --
    recover tree views with ``packer.as_tree`` / ``FlatBuffers.to_tree``.

    ``staleness_snapshots`` additionally carries the per-group download
    snapshots (``snap``/``glob``) that delay-compensated async rounds need
    (core/staleness.py); both start at the initial model, so the first
    compensation is exactly zero.

    ``fault_download`` carries the realized-download mask ``dl`` that
    group-timeout faults under an async schedule need (core/faults.py);
    every group starts fresh (all ones -- matching the static
    ``fresh_mask`` at t=0).

    ``ef_client`` / ``ef_group`` carry the zero-initialized per-link
    error-feedback residuals (``efc`` [G, K, ...] / ``efg`` [G, ...])
    that a ``CompressionPlan`` with ``error_feedback=True`` accumulates
    (core/compression.py).
    """
    G, K = cfg.num_groups, cfg.clients_per_group
    rng = jax.random.PRNGKey(0) if rng is None else rng
    dl = jnp.ones((G,), jnp.float32) if fault_download else None
    feddyn = cfg.algorithm == "feddyn"
    if cfg.use_flat_state:
        packer = make_packer(params0)
        flat0 = packer.flatten(params0)
        params = FlatBuffers(
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
        return HFLState(
            params=params,
            z=packer.zeros((G, K)),
            y=packer.zeros((G,)),
            dyn=packer.zeros((G, K)) if feddyn else None,
            rng=rng,
            round=jnp.zeros((), jnp.int32),
            snap=snap,
            glob=glob,
            dl=dl,
            efc=packer.zeros((G, K)) if ef_client else None,
            efg=packer.zeros((G,)) if ef_group else None,
        )
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (G, K) + x.shape), params0
    )
    y0 = jax.tree.map(lambda x: jnp.zeros((G,) + x.shape, x.dtype), params0)
    snap = glob = None
    if staleness_snapshots:
        # jnp.array copies: glob must not alias the caller's params, or
        # the driver's donated scans would delete them out from under it.
        glob = jax.tree.map(jnp.array, params0)
        snap = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (G,) + x.shape), params0)
    return HFLState(
        params=stacked,
        z=tu.tree_zeros_like(stacked),
        y=y0,
        dyn=tu.tree_zeros_like(stacked) if feddyn else None,
        rng=rng,
        round=jnp.zeros((), jnp.int32),
        snap=snap,
        glob=glob,
        dl=dl,
        efc=tu.tree_zeros_like(stacked) if ef_client else None,
        efg=tu.tree_zeros_like(y0) if ef_group else None,
    )


def _client_grads(loss_fn: Callable, params: PyTree, batch: PyTree):
    """(loss, grad) of the local loss, vmapped over the [G, K] leading axes."""
    vg = jax.value_and_grad(loss_fn)
    with jax.named_scope("client_step"):
        return jax.vmap(jax.vmap(vg))(params, batch)


def make_global_round(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    cfg: HFLConfig,
) -> Callable[[HFLState, PyTree], tuple[HFLState, RoundMetrics]]:
    """Build the jittable global-round function for ``cfg.algorithm``.

    .. deprecated::
        ``make_global_round`` is the legacy constructor; new code should
        declare an ``ExperimentSpec(backend="simulator")`` and use
        ``repro.api.build(spec, loss_fn)`` -- this shim delegates to that
        adapter, so both paths are the same program.

    ``loss_fn(params, batch) -> scalar`` is a single-client loss; the engine
    vmaps it over the [G, K] axes. ``batches`` passed to the returned function
    must have leaves shaped ``[E, H, G, K, ...]`` (one batch per local step
    per client).

    The returned function adapts at trace time to the state layout it is
    given: a flat state (from ``hfl_init`` under ``cfg.use_flat_state``)
    runs the flat hot path, a pytree state runs the per-leaf reference
    path; ``loss_fn`` always sees model pytrees.
    """
    import warnings

    from repro.core.api import ExperimentSpec, build

    warnings.warn(
        "make_global_round is deprecated: declare an "
        "ExperimentSpec(backend='simulator') and use "
        "repro.api.build(spec, loss_fn)", DeprecationWarning, stacklevel=2)
    return build(ExperimentSpec.from_hfl_config(cfg), loss_fn).round_fn


def _build_global_round(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    cfg: HFLConfig,
    plan=None,
    faults=None,
    defense=None,
    compression=None,
) -> Callable[[HFLState, PyTree], tuple[HFLState, RoundMetrics]]:
    """The real round builder behind ``repro.api``'s simulator adapter.

    ``plan`` (a ``core.staleness.StalenessPlan``) switches the round into
    async group-round mode: batches carry ``e_pad = max(E_g)`` group rounds
    per global round ("window"), the static per-group iteration mask gates
    stragglers' dead iterations exactly like a participation mask, and the
    global aggregation becomes a staleness-aware merge of the groups
    reporting this window (report cadence, discount weights and delay
    compensation all from the plan -- see core/staleness.py). With
    ``plan=None`` (the uniform sync schedule) the traced program is the
    legacy round, bit for bit.

    ``faults`` (a ``core.faults.FaultPlan``) injects per-round crash /
    timeout / corrupted-upload faults drawn from the state rng *after* the
    participation draw (the zero-fault rng stream is untouched);
    ``defense`` (a ``core.faults.DefensePlan``) screens/clips uploads
    before any aggregate or correction update sees them. A disabled (or
    None) plan traces the legacy program, bit for bit.

    ``compression`` (a ``core.compression.CompressionPlan``) compresses
    the client->group and/or group->global uploads at the same seam the
    corruption faults and the defense use -- compression first, so
    faults corrupt and the defense screens the *dequantized* upload --
    with optional per-link error-feedback residuals carried in the state
    (``efc``/``efg``). A disabled (or None) plan traces the legacy
    program, bit for bit, and consumes no rng keys.
    """
    cfg.validate()
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
        if cfg.correction_init != "zero":
            raise ValueError(
                "fault injection / screened aggregation require "
                "correction_init='zero' (the gradient init has no "
                "screened analogue)")
        if cfg.server_lr != 1.0:
            raise ValueError(
                "fault injection / screened aggregation require "
                "server_lr=1.0")
        from repro.core import faults as _flt
    comp = compression if (compression is not None
                           and compression.enabled) else None
    comp_mode = comp is not None
    if comp_mode:
        comp.validate()
        if plan is not None:
            raise ValueError(
                "compressed uploads under an async schedule are not "
                "supported yet (the staleness merge would need per-window "
                "residual bookkeeping; see ROADMAP)")
        if cfg.correction_init != "zero":
            raise ValueError(
                "compressed uploads require correction_init='zero' (the "
                "gradient init has no compressed analogue)")
        if cfg.server_lr != 1.0:
            raise ValueError("compressed uploads require server_lr=1.0")
    # Imported unconditionally: the comm_bytes metric is reported (at the
    # uncompressed wire size) whether or not a plan is active.
    from repro.core import compression as _cmp
    comp_c = comp_mode and comp.client_mode != "none"
    comp_g = comp_mode and comp.group_mode != "none"
    ef_c = comp_mode and comp.ef_client
    ef_g = comp_mode and comp.ef_group
    comp_stoch = comp_mode and comp.stochastic
    c_noise = comp_c and comp.client_mode == "int8_stochastic"
    algo = cfg.algorithm
    use_z = algo in ("mtgc", "local_corr")
    use_y = algo in ("mtgc", "group_corr")
    use_prox = algo == "fedprox"
    use_dyn = algo == "feddyn"
    if algo not in ("mtgc", "hfedavg", "local_corr", "group_corr", "fedprox", "feddyn"):
        raise ValueError(f"unknown algorithm {algo!r}")

    G, K, H, E = cfg.num_groups, cfg.clients_per_group, cfg.local_steps, cfg.group_rounds
    lr = cfg.lr
    partial = not cfg.full_participation
    async_mode = plan is not None
    if async_mode:
        if plan.num_groups != G:
            raise ValueError(f"staleness plan covers {plan.num_groups} "
                             f"groups, config has {G}")
        if plan.e_pad != E:
            raise ValueError(f"cfg.group_rounds must be the padded loop "
                             f"length max(E_g)={plan.e_pad}, got {E}")
        if cfg.correction_init != "zero":
            raise ValueError(
                "async group rounds require correction_init='zero' (the "
                "gradient init has no per-cycle analogue)")
        if cfg.server_lr != 1.0:
            raise ValueError("async group rounds require server_lr=1.0")
        # Static plan constants, captured by the traced round as literals.
        em_all = jnp.asarray(plan.iteration_mask())              # [E_pad, G]
        dw = jnp.asarray(plan.discount_weights())                # [G]
        e_eff = jnp.asarray(plan.effective_rounds, jnp.float32)  # [G]
    # Horvitz-Thompson denominators (expected active counts per level);
    # None = realized-count weighting.
    ht = partial and cfg.participation_weighting == "inverse_prob"
    cdenom = (inclusion_prob(cfg.client_participation, K,
                             cfg.participation_mode) * K if ht else None)
    gdenom = (inclusion_prob(cfg.group_participation, G,
                             cfg.participation_mode) * G if ht else None)
    use_fused = cfg.use_fused_update
    if use_fused:
        from repro.kernels import ops as kops
        fused_mode = "pallas" if jax.default_backend() == "tpu" else "interpret"
    # Compression rides the same fusion knob: a fused spec runs the batched
    # quantize kernels (interpret off-TPU, so the pallas_call contract is
    # auditable on CPU), an unfused spec the bit-identical jnp reference.
    comp_dispatch = (("pallas" if jax.default_backend() == "tpu"
                      else "interpret") if use_fused else "ref")

    def global_round(state: HFLState, batches: PyTree) -> tuple[HFLState, RoundMetrics]:
        x, z, y, dyn = state.params, state.z, state.y, state.dyn
        flat = is_flat(state.params)
        packer = state.params.packer if flat else None

        if partial:
            masks, rng = round_masks(state.rng, cfg)
            cmask = masks.client                              # [G, K]
            gmask = masks.group                               # [G]
        else:
            cmask = None
            rng = state.rng

        if fault_mode:
            # Fault draw AFTER the participation draw, off the same carried
            # stream: the zero-fault stream (and trajectory) is untouched.
            fm, rng = _flt.fault_masks(rng, faults, G, K)
            if f_crash:
                # A crashed client is frozen exactly like an unsampled one.
                alive = 1.0 - fm.crash
                cmask = alive if cmask is None else cmask * alive
            if f_timeout:
                tm_keep = 1.0 - fm.timeout                    # [G]
        if comp_stoch:
            # Compression-noise draw AFTER the participation and fault
            # draws, off the same carried stream; deterministic modes
            # (bf16/topk) consume no keys, so their rng stream -- and
            # trajectory -- matches the uncompressed run's exactly.
            ckey, rng = jax.random.split(rng)
            kc, kg = jax.random.split(ckey)
        if (fault_mode or defended) and cmask is None:
            # Force the masked machinery on so screens/faults have a mask
            # to compose with even under full participation.
            cmask = jnp.ones((G, K), jnp.float32)
        masked = cmask is not None
        if masked:
            n_active = jnp.maximum(jnp.sum(cmask), 1.0)

        if async_mode:
            # Per-window report/fresh masks from the carried round counter
            # (constant ones when every cadence is 1, i.e. policy "sync").
            rep = plan.report_mask(state.round)               # [G]
            fresh = plan.fresh_mask(state.round)              # [G]
            if f_timeout:
                # A timed-out group misses its report window; the static
                # fresh cadence no longer predicts downloads, so freshness
                # comes from the carried realized-download mask instead.
                if state.dl is None:
                    raise ValueError(
                        "group-timeout faults under an async schedule carry "
                        "the realized-download mask in the state: build it "
                        "with hfl_init(..., fault_download=True) "
                        "(repro.api.build does this for you)")
                rep = rep * tm_keep
                fresh = state.dl

        def step_loss_mean(loss, am, n_act):
            if defended:
                # A corrupted client that has not healed yet (downloaded a
                # clean model) produces a non-finite loss while its upload
                # is screened -- keep the loss metric (and the guarded
                # horizon's divergence predicate) meaningful by screening
                # the metric the same way.
                w = am * jnp.isfinite(loss).astype(jnp.float32)
                return (jnp.sum(jnp.where(w != 0, loss, 0))
                        / jnp.maximum(jnp.sum(w), 1.0))
            if am is not None:
                return jnp.sum(jnp.where(am != 0, loss, 0)) / n_act
            return jnp.mean(loss)

        def local_phase_tree(x, z, y, dyn, anchor, batches_eh, am, n_act):
            """H local SGD steps (Alg. 1, lines 6-7). batches_eh: [H, G, K, ...]."""
            y_b = tu.tree_broadcast_to_axis(y, 1, K)  # [G, K, ...]

            def step(carry, batch):
                x = carry
                loss, g = _client_grads(loss_fn, x, batch)
                with jax.named_scope("local_update"):
                    if use_fused:
                        # Hot-spot AXPY fused through VMEM (Alg. 1 line 7).
                        x_new = jax.tree.map(
                            lambda xi, gi, zi, yi: kops.mtgc_update(
                                xi, gi, zi, yi, lr=lr, mode=fused_mode),
                            x, g, z, y_b,
                        )
                    else:
                        # Corrected direction: g + z + y (MTGC); baselines
                        # toggle terms.
                        d = g
                        if use_z:
                            d = tu.tree_add(d, z)
                        if use_y:
                            d = tu.tree_add(d, y_b)
                        if use_prox:
                            d = jax.tree.map(
                                lambda di, xi, ai: di + cfg.prox_mu * (xi - ai),
                                d, x, anchor)
                        if use_dyn:
                            d = jax.tree.map(
                                lambda di, mi, xi, ai: di - mi + cfg.feddyn_alpha * (xi - ai),
                                d, dyn, x, anchor,
                            )
                        x_new = jax.tree.map(lambda xi, di: xi - lr * di, x, d)
                    if am is not None:
                        x = tu.tree_select(am, x_new, x)
                    else:
                        x = x_new
                return x, step_loss_mean(loss, am, n_act)

            x, losses = jax.lax.scan(step, x, batches_eh)
            return x, losses

        def local_phase_flat(x, z, y, dyn, anchor, batches_eh, am, n_act):
            """Flat local phase: repack at the phase boundary, never per step.

            z and y are constant for the whole phase, so they unpack once
            here (y kept at its [G, ...] shape -- a factor K smaller than
            the replicas -- and broadcast per step, unlike the sharded
            round which pre-sums z + y at full [G, K] size); the
            participation gate folds into the same fused update expression
            (no separate parameter-sized ``tree_select`` pass).
            """
            if use_fused:
                # One batched Pallas call over the entire flat model per
                # step: y stays [G, N] (broadcast by the kernel index map)
                # and the mask is applied in-register.
                def step(xf, batch):
                    loss, g = _client_grads(loss_fn, packer.unflatten(xf), batch)
                    gf = packer.flatten(g)
                    with jax.named_scope("local_update"):
                        xf = FlatBuffers(
                            {k: kops.mtgc_update_flat(
                                xf.bufs[k], gf.bufs[k], z.bufs[k], y.bufs[k],
                                am, lr=lr, mode=fused_mode)
                             for k in xf.bufs},
                            packer,
                        )
                    return xf, step_loss_mean(loss, am, n_act)

                return jax.lax.scan(step, x, batches_eh)

            # Unpack the phase constants once ([G, N] y stays a factor K
            # smaller than the replicas until it broadcasts in-kernel).
            z_t = z.to_tree() if use_z else None
            y_t = y.to_tree() if use_y else None
            anchor_t = anchor.to_tree() if (use_prox or use_dyn) else None
            dyn_t = dyn.to_tree() if use_dyn else None

            def step(x_t, batch):
                loss, g = _client_grads(loss_fn, x_t, batch)

                def upd(xi, gi, *rest):
                    it = iter(rest)
                    d = gi
                    if use_z:
                        d = d + next(it)
                    if use_y:
                        d = d + jnp.expand_dims(next(it), 1)
                    if use_prox or use_dyn:
                        ai = next(it)
                    if use_prox:
                        d = d + cfg.prox_mu * (xi - ai)
                    if use_dyn:
                        d = d - next(it) + cfg.feddyn_alpha * (xi - ai)
                    x_new = xi - lr * d
                    if am is not None:
                        return jnp.where(tu.expand_mask(am, x_new) != 0, x_new, xi)
                    return x_new

                extra = [t for t, used in ((z_t, use_z), (y_t, use_y),
                                           (anchor_t, use_prox or use_dyn),
                                           (dyn_t, use_dyn)) if used]
                with jax.named_scope("local_update"):
                    x_t = jax.tree.map(upd, x_t, g, *extra)
                return x_t, step_loss_mean(loss, am, n_act)

            x_t, losses = jax.lax.scan(step, packer.unflatten(x), batches_eh)
            return packer.flatten(x_t), losses

        local_phase = local_phase_flat if flat else local_phase_tree

        def group_round(carry, inp):
            """One group round e: local phase + group aggregation (lines 5-9)."""
            x, z, y, dyn, anchor, efc = carry
            if async_mode:
                # Iteration liveness joins the participation mask: a
                # straggler past its E_g rounds this window is frozen
                # exactly like an unsampled client (mask data, static
                # shape), so the group mean, z update and dissemination
                # below need no further gating.
                batches_eh, em = inp
                am = (em[:, None] * cmask if masked
                      else jnp.broadcast_to(em[:, None], (G, K)))
                n_act = jnp.maximum(jnp.sum(am), 1.0)
            else:
                if c_noise:
                    batches_eh, ek = inp
                else:
                    batches_eh = inp
                    ek = None
                am = cmask if masked else None
                n_act = n_active if masked else None
            x_end, losses = local_phase(x, z, y, dyn, anchor, batches_eh,
                                        am, n_act)

            with jax.named_scope("group_agg"):
                # Upload view: compression first -- the wire carries the
                # dequantized delta, so corruption faults then rewrite (and
                # the defense screens) exactly what the group server would
                # reconstruct; clean/frozen clients keep their exact bits
                # either way (where-selects, never arithmetic).
                x_up = x_end
                if comp_c:
                    delta = tu.tree_sub(x_end, x)
                    u = tu.tree_add(delta, efc) if ef_c else delta
                    deq = _cmp.roundtrip(
                        u, mode=comp.client_mode, lead_ndim=2,
                        frac=comp.topk_frac, key=ek, dispatch=comp_dispatch)
                    x_cmp = tu.tree_add(x, deq)
                    x_up = (tu.tree_select(am, x_cmp, x_end)
                            if am is not None else x_cmp)
                if f_corrupt:
                    x_up = _flt.corrupt_uploads(x, x_up, fm.corrupt * am, faults)
                if defended:
                    x_up, ok = _flt.screen_and_clip(x, x_up, defense)
                    smask = am * ok
                    scr = jnp.sum(am) - jnp.sum(smask)
                    n_srv = jnp.maximum(jnp.sum(smask), 1.0)
                else:
                    smask = am
                    n_srv = n_act
                # Correction-state view: z is client-side state -- the client
                # updates it from its *own* local model plus the broadcast it
                # receives -- so the error-feedback residual re-applied on the
                # wire must never enter z (feeding released residual mass back
                # through the correction destabilizes EF). Uncompressed, the
                # wire view is the local model and the legacy program is
                # untouched, screening and clipping included.
                x_loc = x_up
                if comp_c:
                    x_loc = x_end
                    if f_corrupt:
                        x_loc = _flt.corrupt_uploads(x, x_loc, fm.corrupt * am,
                                                     faults)
                if ef_c:
                    # Residual carries forward only for contributions that
                    # entered the aggregate: a screened or inactive client
                    # leaves its error-feedback state untouched.
                    err = tu.tree_sub(u, deq)
                    efc = (tu.tree_select(smask, err, efc)
                           if smask is not None else err)

                # Group aggregation (line 8): xbar_j = mean over (active,
                # surviving) clients (realized-count or expected-count
                # denominator per weighting).
                if smask is not None:
                    xbar = tu.tree_masked_mean(x_up, smask, axis=1,
                                               denom=cdenom)            # [G, ...]
                else:
                    xbar = tu.tree_mean(x_up, axis=1)                   # [G, ...]
                xbar_b = tu.tree_broadcast_to_axis(xbar, 1, K)          # [G, K, ...]

                diff = tu.tree_sub(x_up, xbar_b)
                if smask is not None:
                    drift = tu.tree_masked_sq_norm(diff, smask) / n_srv
                else:
                    drift = tu.tree_sq_norm(diff) / (G * K)

                # Client-group correction update (line 9):
                #   z_i += (x_{i,H} - xbar_j) / (H * lr)
                # Gated on the screen mask: a screened contribution never
                # integrates into the correction state.
                if use_z:
                    z_new = jax.tree.map(
                        lambda zi, xe, xb: zi + (xe - xb) / (H * lr), z, x_loc, xbar_b
                    )
                    z = tu.tree_select(smask, z_new, z) if smask is not None else z_new
                # Model dissemination: every active client restarts from the
                # group model; inactive clients stay frozen. Under the defense,
                # active-but-screened clients also download -- that is what
                # heals a corrupted client -- unless the group has no surviving
                # contribution at all (its hardened mean is an exact, unusable
                # zero), in which case the group's active clients revert to
                # their group-round start model: a screened upload must never
                # survive in a replica, or the global recovery mean would
                # integrate it anyway (`x` still holds the round-start
                # replicas here; for frozen clients it is bit-identical to
                # x_up, so only the fully-screened case changes).
                if smask is None:
                    x = xbar_b
                elif defended:
                    has_srv = (jnp.sum(smask, axis=1) > 0).astype(jnp.float32)
                    x = tu.tree_select(am * has_srv[:, None], xbar_b, x)
                else:
                    x = tu.tree_select(am, xbar_b, x_up)
                out = (losses, drift, scr) if defended else (losses, drift)
                return (x, z, y, dyn, anchor, efc), out

        # --- Round initialization (lines 2-4) ---------------------------
        # Group model init is implicit: params enter equal across clients.
        if use_z:
            if cfg.correction_init == "zero":
                # Footnote 2: experiments initialize z = 0 each round
                # (participants only -- frozen clients keep their z).
                if async_mode:
                    # Generalized per report cycle: only groups starting
                    # from a fresh download reset; mid-cycle stragglers
                    # keep accumulating z across windows.
                    zmask = (fresh[:, None] * cmask if masked
                             else jnp.broadcast_to(fresh[:, None], (G, K)))
                    z = tu.tree_select(zmask, tu.tree_zeros_like(z), z)
                else:
                    z0 = tu.tree_zeros_like(z)
                    z = tu.tree_select(cmask, z0, z) if masked else z0
            else:
                # Theoretical init (line 3): z_i = -g_i + mean_group g_i,
                # evaluated with the first local batch xi_{i,0}^{t,0}.
                b00 = jax.tree.map(lambda b: b[0, 0], batches)
                _, g0 = _client_grads(loss_fn, as_tree(x), b00)
                if flat:
                    g0 = packer.flatten(g0)
                if partial:
                    g0m = tu.tree_broadcast_to_axis(
                        tu.tree_masked_mean(g0, cmask, axis=1, denom=cdenom),
                        1, K)
                    z = tu.tree_select(cmask, tu.tree_sub(g0m, g0), z)
                else:
                    g0m = tu.tree_broadcast_to_axis(tu.tree_mean(g0, axis=1), 1, K)
                    z = tu.tree_sub(g0m, g0)
        if use_y and cfg.correction_init == "gradient":
            is_first = state.round == 0
            if partial:
                # Gate on actual activity, not mere reachability: a group
                # whose client draws all came up empty must keep y frozen
                # and stay out of the global mean (its masked group mean
                # would fall back to garbage batches).
                gact0 = (jnp.sum(cmask, axis=1) > 0).astype(jnp.float32)

            def grad_init_y(y):
                b00 = jax.tree.map(lambda b: b[0, 0], batches)
                _, g0 = _client_grads(loss_fn, as_tree(x), b00)
                if flat:
                    g0 = packer.flatten(g0)
                if partial:
                    gj = tu.tree_masked_mean(g0, cmask, axis=1,
                                             denom=cdenom)         # [G, ...]
                    gg = (tu.tree_masked_mean(gj, gmask, axis=0, denom=gdenom)
                          if ht else
                          tu.tree_masked_mean(gj, gact0, axis=0))  # [...]
                else:
                    gj = tu.tree_mean(g0, axis=1)                  # [G, ...]
                    gg = tu.tree_mean(gj, axis=0)                  # [...]
                return jax.tree.map(lambda gjj, ggg: ggg - gjj, gj, gg)

            y_init = grad_init_y(y)
            if partial:
                y_init = tu.tree_select(gact0, y_init, y)
            y = jax.tree.map(
                lambda yg, yo: jnp.where(is_first, yg, yo), y_init, y
            )

        anchor = x  # group-round-start model (FedProx / FedDyn reference)

        # Error-feedback residuals ride the scan carry; disabled links
        # carry None (zero pytree leaves -- the traced program is the
        # legacy one, bit for bit).
        efc = state.efc if ef_c else None
        if ef_c and efc is None:
            raise ValueError(
                "client-link error feedback carries per-client residuals "
                "in the state: build it with hfl_init(..., ef_client=True) "
                "(repro.api.build does this for you)")

        # --- E group rounds (lines 5-9) ---------------------------------
        # Async windows scan the padded e_pad = max(E_g) iterations and
        # feed the static per-group iteration mask alongside the batches;
        # stochastic client compression feeds one noise key per group round.
        if async_mode:
            scan_xs = (batches, em_all)
        elif c_noise:
            scan_xs = (batches, jax.random.split(kc, E))
        else:
            scan_xs = batches
        if flat:
            # y, dyn and anchor are constant across the E group rounds:
            # close over them instead of threading parameter-sized flat
            # buffers through the scan carry (loop-invariant constants
            # instead of per-iteration carry traffic).
            def group_round_flat(carry, inp):
                xc, zc, ec = carry
                (xc, zc, _, _, _, ec), out = group_round(
                    (xc, zc, y, dyn, anchor, ec), inp)
                return (xc, zc, ec), out

            (x, z, efc), scan_out = jax.lax.scan(
                group_round_flat, (x, z, efc), scan_xs)
        else:
            (x, z, y, dyn, _, efc), scan_out = jax.lax.scan(
                group_round, (x, z, y, dyn, anchor, efc), scan_xs
            )
        if defended:
            losses, drifts, scrs = scan_out
            screened = jnp.sum(scrs)
        else:
            losses, drifts = scan_out
            screened = jnp.zeros((), jnp.float32)

        # --- Global aggregation (line 10) --------------------------------
        with jax.named_scope("global_agg"):
            efg = state.efg if ef_g else None
            if ef_g and efg is None:
                raise ValueError(
                    "group-link error feedback carries per-group residuals in "
                    "the state: build it with hfl_init(..., ef_group=True) "
                    "(repro.api.build does this for you)")

            def compress_group(xbar_j, gref, gact):
                """Compress each group's report delta against its round-start
                model -- the reference both ends of the link share -- and
                where-select so non-reporting groups' recovered means keep
                their exact bits. Returns (xbar_j', u, deq) for the EF carry.
                """
                gdelta = tu.tree_sub(xbar_j, gref)
                ug = tu.tree_add(gdelta, efg) if ef_g else gdelta
                deqg = _cmp.roundtrip(
                    ug, mode=comp.group_mode, lead_ndim=1,
                    frac=comp.topk_frac, key=kg if comp_stoch else None,
                    dispatch=comp_dispatch)
                xbar_c = tu.tree_add(gref, deqg)
                if gact is not None:
                    xbar_c = tu.tree_select(gact, xbar_c, xbar_j)
                return xbar_c, ug, deqg

            if async_mode:
                # Staleness-aware merge of the groups reporting this window:
                # reports enter a weighted mean -- report cadence (rep) x policy
                # weight (dw) x the participation estimator -- and non-reporting
                # groups neither upload nor download (see core/staleness.py).
                if masked:
                    gact = (jnp.sum(cmask, axis=1) > 0).astype(jnp.float32)
                    gup = jnp.sum(rep * gact)  # reports actually sent (pre-screen)
                    # Recovery, not estimation: active replicas of group j all
                    # hold the disseminated xbar_j from its last live iteration.
                    xbar_j = tu.tree_masked_mean(x, cmask, axis=1)
                    if defended and defense.screen_nonfinite:
                        # Backstop group-level screen: a recovered report that
                        # still carries non-finite bits never enters the merge
                        # (counts every active client it would have spoken for).
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
                            "hfl_init(..., staleness_snapshots=True) "
                            "(repro.api.build does this for you)")
                    # First-order delay compensation: shift a stale report by
                    # the global progress its group missed since it last
                    # downloaded (glob - snap_g; exactly zero for fresh groups).
                    xbar_used = jax.tree.map(
                        lambda xj, gl, sn: xj + (jnp.expand_dims(gl, 0) - sn),
                        xbar_j, state.glob, state.snap)
                else:
                    xbar_used = xbar_j

                w = rep * dw                        # [G] deterministic weights
                if partial and ht:
                    # Horvitz-Thompson over reachable groups composed with the
                    # deterministic report/policy weights: an empty reachable
                    # report contributes an exact zero while the denominator
                    # stays the expected reporting mass.
                    wsum = w * gmask
                    sup = wsum * gact
                    den = (gdenom / G) * jnp.sum(w)
                elif masked:
                    wsum = w * gact
                    sup = wsum
                    den_raw = jnp.sum(wsum)
                    den = jnp.where(den_raw > 0, den_raw, 1.0)
                else:
                    # >= 1 always: the pace-setting group (r_g = 1) reports
                    # every window at full weight.
                    wsum = w
                    sup = wsum
                    den = jnp.sum(w)

                def _stale_merge(v):
                    live = tu.expand_mask(sup, v) != 0
                    return jnp.sum(
                        jnp.where(live, v, 0) * tu.expand_mask(wsum, v),
                        axis=0) / den

                xbar = jax.tree.map(_stale_merge, xbar_used)
                gdrift = tu.tree_masked_sq_norm(
                    tu.tree_sub(xbar_j, tu.tree_broadcast_to_axis(xbar, 0, G)), obs
                ) / jnp.maximum(jnp.sum(obs), 1.0)
            elif masked and (fault_mode or defended or comp_g):
                # The legacy recovery/estimation split of tree_group_global_mean,
                # opened up so group-timeout faults, the group-level finite
                # screen and group-link compression can compose into the
                # estimation mask between the two stages (recovery over active
                # replicas is unchanged).
                xbar_j = tu.tree_masked_mean(x, cmask, axis=1)
                gact = (jnp.sum(cmask, axis=1) > 0).astype(jnp.float32)
                if f_timeout:
                    # A timed-out group misses the global exchange entirely:
                    # no upload, no y update, no download -- frozen this round.
                    gact = gact * tm_keep
                gup = jnp.sum(gact)  # reports actually sent (pre-screen)
                if comp_g:
                    # Compression happens at the upload, i.e. after the
                    # timeout composition (a timed-out group never sent bytes,
                    # so its residual must not advance) and before the finite
                    # screen (the backstop screens the dequantized report).
                    gref = tu.tree_masked_mean(state.params, cmask, axis=1)
                    xbar_srv = xbar_j  # group server's own (pre-wire) aggregate
                    xbar_j, ug, deqg = compress_group(xbar_j, gref, gact)
                if defended and defense.screen_nonfinite:
                    gfin = _flt.all_finite_mask(xbar_j, 1)
                    screened = screened + jnp.sum(
                        cmask * ((gact * (1.0 - gfin))[:, None]))
                    gact = gact * gfin
                if ht:
                    xbar_j0 = jax.tree.map(
                        lambda v: jnp.where(tu.expand_mask(gact, v) != 0, v, 0),
                        xbar_j)
                    xbar = tu.tree_masked_mean(xbar_j0, gmask, axis=0,
                                               denom=gdenom)
                else:
                    xbar = tu.tree_masked_mean(xbar_j, gact, axis=0)
                gdrift = tu.tree_masked_sq_norm(
                    tu.tree_sub(xbar_j, tu.tree_broadcast_to_axis(xbar, 0, G)), gact
                ) / jnp.maximum(jnp.sum(gact), 1.0)
            elif partial:
                # A group with zero sampled clients never feeds the y update or
                # dissemination of its own replicas (gact gating). Under
                # realized-count weighting it is also renormalized out of the
                # global mean; under inverse_prob every *reachable* group enters
                # the Horvitz-Thompson sum, an empty one contributing zero --
                # see tree_group_global_mean for the recovery/estimation split.
                xbar_j, xbar, gact = tu.tree_group_global_mean(
                    x, cmask, gmask if ht else None, gdenom)
                gup = jnp.sum(gact)
                gdrift = tu.tree_masked_sq_norm(
                    tu.tree_sub(xbar_j, tu.tree_broadcast_to_axis(xbar, 0, G)), gact
                ) / jnp.maximum(jnp.sum(gact), 1.0)
            else:
                xbar_j = jax.tree.map(lambda xi: xi[:, 0], x)   # [G, ...] (clients equal)
                gup = jnp.float32(G)
                if comp_g:
                    gref = jax.tree.map(lambda xi: xi[:, 0], state.params)
                    xbar_srv = xbar_j  # group server's own (pre-wire) aggregate
                    xbar_j, ug, deqg = compress_group(xbar_j, gref, None)
                xbar = tu.tree_mean(xbar_j, axis=0)             # [...]
                gdrift = tu.tree_sq_norm(
                    tu.tree_sub(xbar_j, tu.tree_broadcast_to_axis(xbar, 0, G))
                ) / G

            if ef_g:
                # Gated on the FINAL estimation mask (post-timeout, post-
                # screen): only a report that entered the merge advances the
                # group's residual.
                errg = tu.tree_sub(ug, deqg)
                efg = tu.tree_select(gact, errg, efg) if masked else errg

            # Group-global correction update (line 11):
            #   y_j += (xbar_j^{t,E} - xbar^{t+1}) / (H * E * lr)
            if use_y:
                if async_mode:
                    # Per report cycle: a reporting group ran E_g * r_g group
                    # rounds since its last download. The policy discount dw
                    # applies to the *merge* only -- y is a tracking estimator
                    # and must update at full rate, or a transient y decays
                    # geometrically (factor 1 - dw/G per report) and its bias
                    # dominates the trajectory (see core/staleness.py).
                    coef = 1.0 / (e_eff * H * lr)                         # [G]
                    xbar_g = tu.tree_broadcast_to_axis(xbar, 0, G)
                    y_new = jax.tree.map(
                        lambda yj, xj, xg: yj + tu.expand_mask(coef, yj) * (xj - xg),
                        y, xbar_used, xbar_g)
                    y = tu.tree_select(obs, y_new, y)
                else:
                    # Like z above, y is group-server-side state: it updates
                    # from the group's own aggregate (pre-wire), never from
                    # the dequantized view carrying the EF residual.
                    y_src = xbar_srv if comp_g else xbar_j
                    y_new = jax.tree.map(
                        lambda yj, xj, xg: yj + (xj - xg) / (H * E * lr), y, y_src, xbar
                    )
                    y = tu.tree_select(gact, y_new, y) if masked else y_new

            # FedDyn gradient-memory update (per client, after its local work).
            if use_dyn:
                dyn_new = jax.tree.map(
                    lambda mi, xi, ai: mi - cfg.feddyn_alpha * (xi - ai), dyn, x, anchor
                )
                dyn = tu.tree_select(cmask, dyn_new, dyn) if masked else dyn_new

            # Dissemination: active clients restart from the (server-lr) global
            # model; frozen clients keep what they have.
            if cfg.server_lr != 1.0:
                if partial:
                    # No stored global model under partial participation: anchor
                    # the server step on the mean over all replicas.
                    prev = tu.tree_mean(state.params, axis=(0, 1))
                else:
                    prev = jax.tree.map(lambda xi: xi[0, 0], state.params)
                xbar = jax.tree.map(lambda p, xb: p + cfg.server_lr * (xb - p), prev, xbar)
            x_glob = jax.tree.map(
                lambda xg: jnp.broadcast_to(xg, (G, K) + xg.shape), xbar
            )
            if async_mode:
                if fault_mode or defended:
                    # Reporting groups download only when the window actually
                    # aggregated something: with the defense decoupling "has
                    # active clients" from "entered the merge", a window whose
                    # every report was screened must not disseminate its
                    # hardened (exact-zero) merge.
                    any_obs = (jnp.sum(obs) > 0).astype(jnp.float32)
                    dmask = rep[:, None] * cmask * any_obs
                elif masked:
                    # Only reporting groups download; stragglers keep their
                    # mid-cycle replicas (that lag is exactly what makes their
                    # next report stale).
                    dmask = rep[:, None] * cmask
                else:
                    dmask = jnp.broadcast_to(rep[:, None], (G, K))
                x = tu.tree_select(dmask, x_glob, x)
            else:
                if fault_mode or defended:
                    # Timed-out groups miss the download too (frozen), and no
                    # one downloads a global mean with zero surviving groups.
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
                # Reporting groups record the global model they just
                # downloaded; the server records it as the latest global
                # (guarded: a window where every reporter came up empty under
                # partial participation aggregates nothing).
                any_obs = (jnp.sum(obs) > 0).astype(jnp.float32)
                snap = tu.tree_select(
                    obs, tu.tree_broadcast_to_axis(xbar, 0, G), snap)
                glob = tu.tree_select(any_obs, xbar, glob)

            dl = state.dl
            if async_mode and f_timeout:
                # Realized downloads this window (rep already excludes timed-out
                # groups): next round's freshness for the z re-init.
                dl = rep * any_obs

        # Bytes on the wire: every upload actually sent this round counts
        # (screened uploads spent their bytes; crashed/unsampled clients
        # and timed-out groups sent none).
        if async_mode:
            n_up_c = (jnp.sum(em_all[:, :, None] * cmask[None])
                      if masked else jnp.sum(em_all) * K)
        else:
            n_up_c = (E * jnp.sum(cmask) if masked
                      else jnp.float32(E * G * K))
        comm = _cmp.round_comm_bytes(state.params, comp, n_up_c, gup)

        metrics = RoundMetrics(
            loss=losses,
            client_drift=drifts,
            group_drift=gdrift,
            z_norm=tu.tree_sq_norm(z) / (G * K),
            y_norm=tu.tree_sq_norm(y) / G,
            participation=(jnp.sum(cmask) / (G * K)) if masked
            else jnp.ones((), jnp.float32),
            screened=screened,
            comm_bytes=comm,
        )
        new_state = HFLState(
            params=x, z=z, y=y, dyn=dyn, rng=rng, round=state.round + 1,
            snap=snap, glob=glob, dl=dl,
            efc=efc if ef_c else state.efc,
            efg=efg if ef_g else state.efg,
        )
        return new_state, metrics

    return global_round


def global_model(state: HFLState) -> PyTree:
    """The current global model xbar (all clients are equal between rounds).

    Under partial participation frozen replicas may hold stale params, so
    index a client that certainly received the last dissemination is not
    statically known; callers tracking the exact global model under partial
    participation should average active replicas via the round's masks.
    Between full-participation rounds every replica is the global model.
    Flat states are unpacked back into the model tree.
    """
    return as_tree(jax.tree.map(lambda x: x[0, 0], state.params))

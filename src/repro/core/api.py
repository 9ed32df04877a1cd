"""One front door for every experiment: ``repro.api``.

The paper's MTGC algorithm is one algorithm, but this repo grew three
divergent constructor stacks for it -- ``make_global_round`` (the
simulator engine), ``make_multilevel_round`` (Appendix E, M levels) and
``make_sharded_round`` (the production microbatched round) -- each with
its own init, state type and kwarg sprawl. :class:`ExperimentSpec` is the
single declarative surface over all of them: topology, schedule,
algorithm, participation, state layout, fusion and backend in one frozen
dataclass; :func:`build` turns a spec into an :class:`Engine` (a uniform
``init`` / ``round_fn`` / ``global_model`` / packing adapter over the
existing engines) and :func:`fit` drives any engine through the compiled
horizon driver (``core.driver``) without the caller ever touching packing
internals.

Quickstart (the 60-second version; see examples/quickstart.py)::

    from repro import api
    spec = api.ExperimentSpec(
        levels=(4, 5), algorithm="mtgc", lr=0.1,
        schedule=api.RoundSchedule(group_rounds=4, local_steps=5))
    engine = api.build(spec, loss_fn)
    data = engine.pack_arrays({"x": X, "y": Y}, client_index_pools,
                              batch_size=32, rng=np.random.default_rng(0),
                              key=jax.random.PRNGKey(1))
    state, horizon = api.fit(engine, data, 30, params=model_params,
                             eval_every=5, eval_fn=my_eval_fn)
    model = engine.global_model(state)

Backends share semantics, not just shape: ``build(spec)`` for the same
algorithm/topology/participation is state-for-state identical to the
legacy constructors (tests/test_api_conformance.py), and the legacy
constructors themselves are now thin shims over this module, so every
pre-existing parity/oracle test gates the redesign.

**Async group rounds** land through the hook :class:`RoundSchedule`
reserved for them: ``group_rounds`` accepts a per-group tuple
``(E_1, ..., E_G)`` -- heterogeneous edges run at their own pace -- and
``ExperimentSpec.staleness`` picks what the global aggregation does with
groups that report late (:data:`STALENESS_POLICIES`: ``"sync"`` every
group reports each window with its own E_g rounds of work; ``"naive"``
stale reports merge at full weight; ``"discount"`` down-weights a report
by ``1/(1+staleness)``; ``"delay_compensated"`` shifts it by the global
progress the group missed). ``max_staleness`` bounds how late a report
may be (groups beyond it are force-synced). All of it is implemented in
the simulator and sharded engines behind :func:`build` -- no new
constructor stack -- via static iteration masks over a padded
``max(E_g)`` inner loop (core/staleness.py); the uniform/sync
configuration stays bit-for-bit the legacy program
(tests/test_async_rounds.py)::

    spec = api.ExperimentSpec(
        levels=(3, 4),
        schedule=api.RoundSchedule(group_rounds=(4, 2, 1), local_steps=5),
        staleness="discount", max_staleness=3)

The multilevel backend keeps requiring a uniform schedule (validated up
front).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import HFLConfig
from repro.core.driver import (
    GuardReport,
    GuardSpec,
    Horizon,
    LoweredChunk,
    PackedBatches,
    pack_client_shards,
    pack_lm_shards,
    run_rounds,
    trace_chunk,
)
from repro.core.compression import COMPRESSION_MODES, CompressionPlan
from repro.core.faults import DefensePlan, FAULT_KINDS, FaultPlan
from repro.core.packer import as_tree
from repro.core.population import (
    PopulationStore,
    population_fields,
    run_population_rounds,
    stateless_round,
)
from repro.core.staleness import STALENESS_POLICIES

PyTree = Any

ALGORITHMS = ("mtgc", "hfedavg", "local_corr", "group_corr", "fedprox", "feddyn")
BACKENDS = ("simulator", "multilevel", "sharded")
LAYOUTS = ("tree", "flat")
FUSIONS = ("none", "fused")
CLIENT_STATES = ("stateful", "stateless")

# Which algorithms each backend implements (the simulator engine is the
# paper's full baseline zoo; the production round keeps the two deployed
# ones; the M-level engine is MTGC by construction).
BACKEND_ALGORITHMS = {
    "simulator": ALGORITHMS,
    "multilevel": ("mtgc",),
    "sharded": ("mtgc", "hfedavg"),
}


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise ValueError(msg)


@dataclasses.dataclass(frozen=True)
class RoundSchedule:
    """When each timescale fires, declared once for every backend.

    group_rounds: E -- group aggregations per global round. A scalar, or a
        per-group tuple ``(E_1, ..., E_G)`` (length ``levels[0]``): a
        non-uniform tuple enables async group rounds -- each group runs its
        own E_g inside a padded ``max(E_g)`` window, and
        ``ExperimentSpec.staleness`` picks the stale-report policy
        (simulator and sharded backends; the multilevel backend requires a
        uniform schedule).
    local_steps: H -- local SGD steps per group round.
    microbatches: A -- gradient-accumulation chunks per local step; only
        meaningful on the sharded backend (None elsewhere).
    periods: explicit M-level aggregation periods ``(P_1 > ... > P_M)``
        for the multilevel backend; for a two-level topology they default
        to ``(E * H, H)``.
    """

    group_rounds: int | tuple[int, ...] = 2
    local_steps: int = 5
    microbatches: int | None = None
    periods: tuple[int, ...] | None = None

    def __post_init__(self):
        if isinstance(self.group_rounds, (list, tuple)):
            object.__setattr__(self, "group_rounds",
                               tuple(int(e) for e in self.group_rounds))
        if self.periods is not None:
            object.__setattr__(self, "periods",
                               tuple(int(p) for p in self.periods))

    @property
    def is_uniform(self) -> bool:
        """True when every group runs the same number of group rounds."""
        if isinstance(self.group_rounds, tuple):
            return all(e == self.group_rounds[0] for e in self.group_rounds)
        return True

    @property
    def uniform_group_rounds(self) -> int:
        """E as a scalar; raises for non-uniform (async) schedules --
        callers that can handle the padded loop use
        :attr:`max_group_rounds` instead."""
        if isinstance(self.group_rounds, tuple):
            first = self.group_rounds[0]
            _require(self.is_uniform,
                     "this code path needs a uniform group-round schedule "
                     f"(got {self.group_rounds}); async per-group schedules "
                     "run through the padded max(E_g) loop "
                     "(max_group_rounds)")
            return first
        return int(self.group_rounds)

    @property
    def max_group_rounds(self) -> int:
        """The padded inner-loop length max(E_g) -- what one global round's
        batches carry; equals E for uniform schedules."""
        if isinstance(self.group_rounds, tuple):
            return max(self.group_rounds)
        return int(self.group_rounds)

    def level_periods(self, num_levels: int) -> tuple[int, ...]:
        """Aggregation periods for an ``num_levels``-deep topology."""
        if self.periods is not None:
            return self.periods
        E, H = self.uniform_group_rounds, self.local_steps
        _require(num_levels == 2,
                 f"a {num_levels}-level topology needs explicit "
                 "schedule.periods (group_rounds/local_steps only define "
                 "the two-level schedule)")
        return (E * H, H)

    def validate(self, levels: tuple[int, ...]) -> "RoundSchedule":
        gr = self.group_rounds
        if isinstance(gr, tuple):
            _require(len(gr) == levels[0],
                     f"per-group group_rounds needs one entry per group: "
                     f"{len(gr)} entries for {levels[0]} groups")
            _require(all(e >= 1 for e in gr), f"group_rounds must be >= 1: {gr}")
        else:
            _require(gr >= 1, f"group_rounds must be >= 1, got {gr}")
        _require(self.local_steps >= 1,
                 f"local_steps must be >= 1, got {self.local_steps}")
        _require(self.microbatches is None or self.microbatches >= 1,
                 f"microbatches must be None or >= 1, got {self.microbatches}")
        if self.periods is not None:
            _require(self.is_uniform,
                     "explicit schedule.periods (the multilevel backend) "
                     "require a uniform group-round schedule, got "
                     f"group_rounds={self.group_rounds}")
            _require(len(self.periods) == len(levels),
                     f"one period per level: {len(self.periods)} periods for "
                     f"{len(levels)} levels")
            for a, b in zip(self.periods, self.periods[1:]):
                _require(a > b and a % b == 0,
                         f"periods must nest (P_m > P_m+1, divisible): "
                         f"{self.periods}")
            # periods are authoritative: an explicitly different E/H would
            # be silently ignored, so reject the conflict. Field defaults
            # count as "unset" (you can't declare periods without them).
            derived = (self.periods[0] // self.periods[-1], self.periods[-1])
            given = (self.uniform_group_rounds, self.local_steps)
            defaults = (RoundSchedule.group_rounds, RoundSchedule.local_steps)
            _require(given == derived or given == defaults,
                     f"schedule.periods={self.periods} implies "
                     f"(group_rounds, local_steps)={derived}, which "
                     f"conflicts with the explicit {given}; set periods "
                     "alone or keep them consistent")
        return self


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """Everything that defines one HFL experiment, in one place.

    levels: topology dims -- ``(G, K)`` for the two-level engines, or the
        full ``(N_1, ..., N_M)`` tree for the multilevel backend.
    schedule: the :class:`RoundSchedule` (E / H / microbatches / periods).
    algorithm: one of :data:`ALGORITHMS` (backend support varies; see
        :data:`BACKEND_ALGORITHMS`).
    backend: "simulator" (``core.engine``), "multilevel"
        (``core.multilevel``) or "sharded" (``launch.train``).
    state_layout: "flat" packs state into contiguous ``[*dims, N]``
        buffers (``core.packer``); "tree" keeps model pytrees.
    fusion: "fused" routes the MTGC local step through the Pallas kernel.
    fused_mode: sharded-backend kernel dispatch override
        ("auto" | "pallas" | "interpret"); None = backend default.
    correction_dtype: narrow (e.g. "bfloat16") z/y storage -- sharded
        backend, tree layout only.
    client_participation / group_participation / participation_mode /
    participation_weighting: exactly ``HFLConfig``'s semantics.
    level_participation: per-level live-uplink fractions for M-level
        topologies (overrides the two scalar fractions there).
    staleness: stale-report policy for async (non-uniform) group-round
        schedules, one of :data:`STALENESS_POLICIES` -- "sync" (every group
        reports each window; the only policy valid with uniform rounds),
        "naive" (stale reports merge at full weight), "discount"
        (1/(1+staleness) weighting) or "delay_compensated" (reports are
        shifted by the global progress the group missed). See
        core/staleness.py.
    max_staleness: bound on report staleness -- groups whose cadence would
        exceed it are force-synced; requires an async (non-"sync") policy.
    population: virtual clients per group. ``levels[1]`` stays the compiled
        cohort shape; each driver chunk samples that many clients from the
        population, gathers their persistent corrections out of a host-side
        :class:`~repro.core.population.PopulationStore` and scatters them
        back -- device memory and round time scale with the cohort, not the
        population (``core.population``). ``population == levels[1]``
        materializes everyone (bit-exact vs. the plain path); larger
        populations require full participation (cohort sampling *is* the
        participation mechanism) and a uniform sync schedule.
    cohort_size: declarative alias for the compiled cohort shape; when set
        it must equal ``levels[1]`` (the single authoritative topology) and
        requires ``population``.
    client_state: "stateful" (default) persists per-client corrections in
        the population store; "stateless" zero-initializes them every round
        -- the large-cohort FL assumption -- and needs no store at all.
    faults: a :class:`~repro.core.faults.FaultPlan` -- deterministic
        per-round fault injection (client crashes, group timeouts,
        corrupted uploads) drawn from the state rng after the
        participation draw, so the zero-fault stream is untouched.
        None / all-zero rates trace the legacy program bit-for-bit.
        Two-level simulator/sharded backends only.
    defense: a :class:`~repro.core.faults.DefensePlan` -- screened
        aggregation (non-finite and norm screening of per-client deltas,
        optional norm clipping) applied at the upload boundary; screened
        contributions never enter aggregates or the z/y corrections, and
        the per-round ``screened`` metric counts them.
    compression: a :class:`~repro.core.compression.CompressionPlan` --
        per-link quantized/sparsified uploads (client->group and
        group->global independently: bf16 | int8_stochastic | topk) with
        optional error-feedback residuals carried in the state, applied
        at the same upload boundary the faults/defense use (compress ->
        corrupt -> screen). Every engine reports the modeled per-round
        ``comm_bytes`` metric whether or not a plan is set. Two-level
        simulator/sharded backends, sync schedules only.
    """

    levels: tuple[int, ...] = (2, 2)
    schedule: RoundSchedule = RoundSchedule()
    algorithm: str = "mtgc"
    lr: float = 0.1
    backend: str = "simulator"
    state_layout: str = "flat"
    fusion: str = "none"
    fused_mode: str | None = None
    correction_init: str = "zero"
    prox_mu: float = 0.0
    feddyn_alpha: float = 0.0
    server_lr: float = 1.0
    client_participation: float = 1.0
    group_participation: float = 1.0
    level_participation: tuple[float, ...] | None = None
    participation_mode: str = "uniform"
    participation_weighting: str = "none"
    correction_dtype: str | None = None
    staleness: str = "sync"
    max_staleness: int | None = None
    population: int | None = None
    cohort_size: int | None = None
    client_state: str = "stateful"
    faults: FaultPlan | None = None
    defense: DefensePlan | None = None
    compression: CompressionPlan | None = None

    def __post_init__(self):
        object.__setattr__(self, "levels", tuple(int(n) for n in self.levels))
        if self.level_participation is not None:
            object.__setattr__(self, "level_participation",
                               tuple(float(p) for p in self.level_participation))

    # ------------------------------------------------------------ checks

    def validate(self) -> "ExperimentSpec":
        _require(len(self.levels) >= 2,
                 f"levels needs at least (groups, clients), got {self.levels}")
        _require(all(n >= 1 for n in self.levels),
                 f"every topology dim must be >= 1: {self.levels}")
        _require(self.backend in BACKENDS,
                 f"unknown backend {self.backend!r} (choose from {BACKENDS})")
        _require(self.algorithm in ALGORITHMS,
                 f"unknown algorithm {self.algorithm!r} "
                 f"(choose from {ALGORITHMS})")
        _require(self.algorithm in BACKEND_ALGORITHMS[self.backend],
                 f"algorithm {self.algorithm!r} is not implemented by the "
                 f"{self.backend!r} backend "
                 f"(supported: {BACKEND_ALGORITHMS[self.backend]})")
        _require(len(self.levels) == 2 or self.backend == "multilevel",
                 f"{len(self.levels)}-level topologies need "
                 f"backend='multilevel', got {self.backend!r}")
        self.schedule.validate(self.levels)
        _require(self.schedule.microbatches is None
                 or self.backend == "sharded",
                 "schedule.microbatches is a sharded-backend knob")
        if self.backend == "multilevel":
            self.schedule.level_periods(len(self.levels))

        # Async group rounds: contradictory combos are rejected up front.
        _require(self.staleness in STALENESS_POLICIES,
                 f"unknown staleness policy {self.staleness!r} "
                 f"(choose from {STALENESS_POLICIES})")
        uniform = self.schedule.is_uniform
        _require(uniform or self.backend != "multilevel",
                 "non-uniform group_rounds (async group rounds) are a "
                 "two-level feature: the multilevel backend requires a "
                 "uniform schedule")
        _require(self.staleness == "sync" or not uniform,
                 f"staleness={self.staleness!r} is a no-op with uniform "
                 "group_rounds: stale reports only arise when groups run "
                 "different round counts -- set a per-group tuple or drop "
                 "the policy")
        _require(self.max_staleness is None or self.staleness != "sync",
                 "max_staleness bounds async reporting; it needs a non-"
                 "'sync' staleness policy")
        _require(self.max_staleness is None or self.max_staleness >= 1,
                 f"max_staleness must be None or >= 1, "
                 f"got {self.max_staleness}")
        _require(uniform or self.correction_init == "zero",
                 "async group rounds require correction_init='zero' (the "
                 "gradient init has no per-cycle analogue)")
        _require(uniform or self.server_lr == 1.0,
                 "async group rounds require server_lr=1.0")

        _require(self.state_layout in LAYOUTS,
                 f"unknown state_layout {self.state_layout!r} "
                 f"(choose from {LAYOUTS})")
        _require(self.fusion in FUSIONS,
                 f"unknown fusion {self.fusion!r} (choose from {FUSIONS})")
        _require(self.fusion == "none" or self.algorithm == "mtgc",
                 "fusion='fused' fuses exactly g + z + y: mtgc only")
        _require(self.fusion == "none" or self.backend != "multilevel",
                 "the multilevel backend has no fused-kernel path")
        _require(self.fused_mode is None or self.backend == "sharded",
                 "fused_mode overrides the sharded backend's kernel dispatch")
        _require(self.correction_dtype is None
                 or (self.backend == "sharded" and self.state_layout == "tree"),
                 "correction_dtype (narrow z/y storage) exists only on the "
                 "sharded backend's tree layout")

        _require(self.correction_init in ("zero", "gradient"),
                 f"correction_init must be 'zero' or 'gradient', "
                 f"got {self.correction_init!r}")
        _require(self.correction_init == "zero" or self.backend == "simulator",
                 "correction_init='gradient' is a simulator-engine feature")
        for name in ("prox_mu", "feddyn_alpha"):
            _require(getattr(self, name) == 0.0 or self.backend == "simulator",
                     f"{name} only affects the simulator engine's "
                     "fedprox/feddyn algorithms")
        _require(self.server_lr == 1.0 or self.backend == "simulator",
                 "server_lr is a simulator-engine knob")

        for name in ("client_participation", "group_participation"):
            frac = getattr(self, name)
            _require(0.0 < frac <= 1.0,
                     f"{name} must be in (0, 1], got {frac}")
        _require(self.participation_mode in ("uniform", "fixed"),
                 f"participation_mode must be 'uniform' or 'fixed', "
                 f"got {self.participation_mode!r}")
        _require(self.participation_weighting in ("none", "inverse_prob"),
                 f"participation_weighting must be 'none' or 'inverse_prob', "
                 f"got {self.participation_weighting!r}")
        if self.level_participation is not None:
            _require(self.backend == "multilevel",
                     "level_participation is a multilevel-backend knob; "
                     "two-level backends use client_/group_participation")
            _require(len(self.level_participation) == len(self.levels),
                     "one participation fraction per level: "
                     f"{len(self.level_participation)} for "
                     f"{len(self.levels)} levels")
            _require(all(0.0 < p <= 1.0 for p in self.level_participation),
                     f"participation fractions must be in (0, 1]: "
                     f"{self.level_participation}")

        # Virtual population: contradictory combos are rejected up front.
        _require(self.client_state in CLIENT_STATES,
                 f"unknown client_state {self.client_state!r} "
                 f"(choose from {CLIENT_STATES})")
        _require(self.cohort_size is None or self.population is not None,
                 "cohort_size describes the sampled cohort of a virtual "
                 "population; set population too")
        _require(self.client_state == "stateful" or self.population is not None,
                 "client_state='stateless' is a virtual-population contract; "
                 "set population (the materialized engines are stateful by "
                 "construction)")
        if self.population is not None:
            _require(self.population >= 1,
                     f"population must be >= 1, got {self.population}")
            _require(len(self.levels) == 2,
                     "a virtual population is two-level (groups x clients); "
                     f"got levels={self.levels}")
            _require(self.backend != "multilevel",
                     "the multilevel backend has no cohort gather/scatter "
                     "path; use the simulator or sharded backend")
            _require(self.cohort_size is None
                     or self.cohort_size == self.levels[1],
                     f"cohort_size ({self.cohort_size}) must equal levels[1] "
                     f"({self.levels[1]}), the compiled cohort shape -- "
                     "levels stays the single authoritative topology")
            _require(self.population >= self.levels[1],
                     f"population ({self.population}) must be >= the cohort "
                     f"levels[1] ({self.levels[1]}): a cohort larger than "
                     "the population cannot be sampled without replacement")
        if self.virtual_population:
            _require(self.full_participation,
                     "a virtual population (population > levels[1]) samples "
                     "its cohort from the store -- that *is* the "
                     "participation mechanism; in-round partial "
                     "participation would freeze slots whose occupants "
                     "change between chunks. Keep client_/group_"
                     "participation at 1.0")
            _require(self.schedule.is_uniform and self.staleness == "sync",
                     "virtual populations require a uniform sync schedule: "
                     "async per-group cadences assume slot occupants "
                     "persist across windows (follow-up work)")

        # Fault tolerance: contradictory combos are rejected up front.
        if self.faults is not None:
            self.faults.validate()
        if self.defense is not None:
            self.defense.validate()
        if self.fault_mode or self.defended:
            _require(self.backend != "multilevel",
                     "fault injection / screened aggregation are two-level "
                     "features (simulator and sharded backends); the "
                     "multilevel backend is follow-up work")
            _require(self.population is None,
                     "fault injection with a virtual population is follow-up "
                     "work: screened slots would need store-side healing")
            _require(self.correction_init == "zero",
                     "fault injection / screened aggregation require "
                     "correction_init='zero' (the gradient init has no "
                     "crash-consistent analogue)")
            _require(self.server_lr == 1.0,
                     "fault injection / screened aggregation require "
                     "server_lr=1.0")

        # Compressed uploads: contradictory combos are rejected up front.
        if self.compression is not None:
            self.compression.validate()
        if self.compressed:
            _require(self.backend != "multilevel",
                     "compressed uploads are a two-level feature (simulator "
                     "and sharded backends); per-level plans for the "
                     "multilevel backend are follow-up work")
            _require(self.staleness == "sync" and self.schedule.is_uniform,
                     "compressed uploads under an async schedule are not "
                     "supported yet: stale reports would need their own "
                     "residual timeline (see ROADMAP)")
            _require(self.correction_init == "zero",
                     "compressed uploads require correction_init='zero' "
                     "(the gradient init predates the upload seam)")
            _require(self.server_lr == 1.0,
                     "compressed uploads require server_lr=1.0")
            if self.compression.error_feedback:
                _require(self.client_state == "stateful",
                         "error feedback is per-client persistent state; "
                         "client_state='stateless' contradicts it -- set "
                         "CompressionPlan(error_feedback=False)")
                _require(self.population is None,
                         "error feedback with a virtual population is "
                         "follow-up work: per-client residuals would need "
                         "store-side gather/scatter like z; set "
                         "CompressionPlan(error_feedback=False)")
            else:
                _require(self.population is None
                         or self.compression.client_mode == "none",
                         "client-link compression with a virtual population "
                         "is follow-up work (the cohort seam predates the "
                         "upload seam)")
        return self

    # ------------------------------------------------- config conversion

    @property
    def full_participation(self) -> bool:
        if self.level_participation is not None:
            return all(p >= 1.0 for p in self.level_participation)
        return (self.client_participation >= 1.0
                and self.group_participation >= 1.0)

    @property
    def fault_mode(self) -> bool:
        """True when the spec injects any faults."""
        return self.faults is not None and self.faults.enabled

    @property
    def defended(self) -> bool:
        """True when screened aggregation is active."""
        return self.defense is not None and self.defense.enabled

    @property
    def compressed(self) -> bool:
        """True when any upload link carries a non-trivial compressor."""
        return self.compression is not None and self.compression.enabled

    @property
    def virtual_population(self) -> bool:
        """True when the population exceeds the materialized cohort --
        cohort draws then actually sample (``population == levels[1]`` is
        the degenerate everyone-materialized case)."""
        return self.population is not None and self.population > self.levels[1]

    def participation_by_level(self) -> tuple[float, ...]:
        """Per-level live-uplink fractions for the multilevel engine."""
        if self.level_participation is not None:
            return self.level_participation
        # Two-level semantics: level 0 = group uplinks, deepest = clients.
        return ((self.group_participation,)
                + (1.0,) * (len(self.levels) - 2)
                + (self.client_participation,))

    def staleness_plan(self):
        """The :class:`~repro.core.staleness.StalenessPlan` this spec's
        schedule implies, or None for the uniform sync schedule (the
        engines then take their legacy code path untouched)."""
        from repro.core.staleness import make_plan

        return make_plan(self.schedule.group_rounds, self.levels[0],
                         self.staleness, self.max_staleness)

    def to_hfl_config(self) -> HFLConfig:
        """The equivalent two-level ``HFLConfig`` (simulator engine).

        ``group_rounds`` is the padded loop length ``max(E_g)`` -- exactly
        E for uniform schedules; per-group counts live in the staleness
        plan, not the legacy config.
        """
        _require(len(self.levels) == 2,
                 f"HFLConfig is two-level; spec has levels={self.levels}")
        return HFLConfig(
            num_groups=self.levels[0],
            clients_per_group=self.levels[1],
            local_steps=self.schedule.local_steps,
            group_rounds=self.schedule.max_group_rounds,
            lr=self.lr,
            algorithm=self.algorithm,
            correction_init=self.correction_init,
            prox_mu=self.prox_mu,
            feddyn_alpha=self.feddyn_alpha,
            server_lr=self.server_lr,
            client_participation=self.client_participation,
            group_participation=self.group_participation,
            participation_mode=self.participation_mode,
            participation_weighting=self.participation_weighting,
            use_fused_update=self.fusion == "fused",
            use_flat_state=self.state_layout == "flat",
        )

    @classmethod
    def from_hfl_config(cls, cfg: HFLConfig,
                        backend: str = "simulator") -> "ExperimentSpec":
        return cls(
            levels=(cfg.num_groups, cfg.clients_per_group),
            schedule=RoundSchedule(group_rounds=cfg.group_rounds,
                                   local_steps=cfg.local_steps),
            algorithm=cfg.algorithm,
            lr=cfg.lr,
            backend=backend,
            state_layout="flat" if cfg.use_flat_state else "tree",
            fusion="fused" if cfg.use_fused_update else "none",
            correction_init=cfg.correction_init,
            prox_mu=cfg.prox_mu,
            feddyn_alpha=cfg.feddyn_alpha,
            server_lr=cfg.server_lr,
            client_participation=cfg.client_participation,
            group_participation=cfg.group_participation,
            participation_mode=cfg.participation_mode,
            participation_weighting=cfg.participation_weighting,
        )


# ------------------------------------------------------------------ engine


LossFn = Callable[[PyTree, PyTree], jax.Array]


@runtime_checkable
class Engine(Protocol):
    """What every backend looks like behind :func:`build`.

    spec: the validated :class:`ExperimentSpec` this engine realizes.
    round_fn: ``(state, batches) -> (state, metrics)`` consuming the
        driver batch layout (what ``select_round`` emits for this spec);
        jit-friendly and driver-ready.
    metric_fields: names of the metrics NamedTuple fields ``round_fn``
        returns -- always includes ``"loss"``.
    """

    spec: ExperimentSpec
    round_fn: Callable[[PyTree, PyTree], tuple[PyTree, Any]]
    metric_fields: tuple[str, ...]

    def init(self, params: PyTree, rng: jax.Array | None = None) -> PyTree:
        """Broadcast one model into this backend's round state."""
        ...

    def global_model(self, state: PyTree) -> PyTree:
        """The current global model as a plain model pytree."""
        ...


class _EngineBase:
    """Shared packing plumbing; subclasses adapt one legacy engine each."""

    def __init__(self, spec: ExperimentSpec, loss_fn: LossFn):
        self.spec = spec
        self.loss_fn = loss_fn
        self.round_fn = self._build_round_fn()
        if spec.client_state == "stateless":
            # Wrap once at build time: the driver's chunk-runner cache
            # keys on the round function's identity.
            self.round_fn = stateless_round(self.round_fn, ("z", "dyn"))

    @property
    def population_fields(self) -> tuple[str, ...]:
        """State fields the population store persists for this spec."""
        return population_fields(self.spec.algorithm)

    def init_population(self, state: PyTree) -> PopulationStore:
        """A zeroed host store for ``spec.population`` virtual clients,
        seeded from ``state``'s current correction rows (identity mapping
        into rows ``[0, K)``)."""
        _require(self.spec.population is not None,
                 "init_population needs spec.population set")
        _require(self.spec.client_state == "stateful",
                 "stateless clients keep no per-client state; no store "
                 "exists to initialize")
        return PopulationStore.from_state(
            state, self.spec.population, self.population_fields)

    # Subclasses set these to the driver-layout (E, H) of one round.
    # Async schedules pack the padded max(E_g) axis: stragglers' dead
    # iterations draw shards that the iteration mask then gates out.
    @property
    def _pack_rounds(self) -> int:
        return self.spec.schedule.max_group_rounds

    @property
    def _pack_steps(self) -> int:
        return self.spec.schedule.local_steps

    @property
    def _pack_microbatches(self) -> int | None:
        return None

    def pack_arrays(self, data_arrays: dict[str, np.ndarray], indices: list,
                    *, batch_size: int, shards: int = 16,
                    rng: np.random.Generator, key: jax.Array) -> PackedBatches:
        """Pack a partitioned array dataset for :func:`fit` (uploads once)."""
        _require(_index_depth(indices) == len(self.spec.levels),
                 f"index nesting depth {_index_depth(indices)} does not "
                 f"match levels={self.spec.levels}")
        return pack_client_shards(
            data_arrays, indices, group_rounds=self._pack_rounds,
            local_steps=self._pack_steps, batch_size=batch_size,
            shards=shards, microbatches=self._pack_microbatches,
            rng=rng, key=key)

    def pack_tokens(self, tokens: np.ndarray, *, batch_size: int,
                    seq_len: int, shards: int = 8,
                    rng: np.random.Generator, key: jax.Array) -> PackedBatches:
        """Pack an LM token stream for :func:`fit` (two-level backends)."""
        _require(len(self.spec.levels) == 2,
                 "token packing is two-level; use pack_arrays with nested "
                 "index pools for deeper trees")
        G, K = self.spec.levels
        return pack_lm_shards(
            tokens, num_groups=G, clients_per_group=K,
            group_rounds=self._pack_rounds, local_steps=self._pack_steps,
            batch_size=batch_size, seq_len=seq_len, shards=shards,
            microbatches=self._pack_microbatches, rng=rng, key=key)

    def abstract_state(self, params: PyTree) -> PyTree:
        """ShapeDtypeStructs of this engine's round state, zero allocation.

        ``params`` may itself be abstract -- ``init`` (and the flat-layout
        packer behind it) is traced with ``jax.eval_shape``, so nothing is
        materialized. The rng passed to ``init`` is a concrete throwaway
        key: only its shape/dtype survive into the abstract state.
        """
        return jax.eval_shape(
            lambda p: self.init(p, jax.random.PRNGKey(0)), params)

    def lower_chunk(
        self,
        data: PackedBatches,
        *,
        params: PyTree | None = None,
        state: PyTree | None = None,
        chunk: int = 2,
        eval_fn=None,
        donate: bool = True,
        compile: bool = True,
    ) -> LoweredChunk:
        """Trace + lower (+ compile) this engine's driver chunk, no execution.

        The static-analysis front door (``repro.analysis`` and ``python -m
        repro.launch.audit`` audit the lowered artifacts this returns).
        ``data`` leaves may be ``jax.ShapeDtypeStruct``s with the packed
        driver layout (``[*levels, S, steps, ...]`` plus the microbatch
        axis on the sharded backend); pass either an abstract ``state`` or
        the ``params`` to derive one from via :meth:`abstract_state`.
        """
        if state is None:
            _require(params is not None,
                     "lower_chunk needs `state` or `params` to trace over")
            state = self.abstract_state(params)
        return trace_chunk(self.round_fn, state, data, chunk,
                           eval_fn=eval_fn, donate=donate, compile=compile)

    def retry_round_fn(self, retry: int):
        """Round function for guarded-horizon retry ``retry`` (>= 1).

        When the spec has a norm screen, each retry rebuilds the round
        with ``screen_norm * retry_widen ** retry`` -- the screen catches
        exponentially more on every retry, so a chunk that diverged
        because a corrupted-but-finite delta slipped under the threshold
        converges on replay. Otherwise the original round is retried
        as-is (the re-split rng alone changes the fault draw). Rebuilt
        rounds are cached per retry level so the driver's chunk-runner
        cache (keyed on function identity) is not thrashed.
        """
        spec = self.spec
        if (retry <= 0 or spec.defense is None
                or spec.defense.screen_norm is None):
            return self.round_fn
        cache = getattr(self, "_retry_round_fns", None)
        if cache is None:
            cache = self._retry_round_fns = {}
        if retry not in cache:
            widened = dataclasses.replace(
                spec.defense,
                screen_norm=(spec.defense.screen_norm
                             * spec.defense.retry_widen ** retry))
            rebuilt = build(dataclasses.replace(spec, defense=widened),
                            self.loss_fn)
            cache[retry] = rebuilt.round_fn
        return cache[retry]

    def participation_masks(self, rng: jax.Array):
        """(masks, next_rng) the round derives from a pre-round state rng.

        Exactly the draw the two-level round functions make internally
        (``core.participation.round_masks``' key schedule), so eval
        closures can pick an active replica without rebuilding a legacy
        ``HFLConfig`` from the spec.
        """
        from repro.core.participation import sample_hfl_masks

        _require(len(self.spec.levels) == 2,
                 "participation_masks is two-level; the multilevel backend "
                 "draws hierarchical chain masks internally")
        mkey, next_rng = jax.random.split(rng)
        masks = sample_hfl_masks(
            mkey, *self.spec.levels, self.spec.client_participation,
            self.spec.group_participation, self.spec.participation_mode)
        return masks, next_rng


def _index_depth(indices) -> int:
    depth = 0
    node = indices
    while isinstance(node, (list, tuple)):
        depth += 1
        node = node[0]
    return depth


class SimulatorEngine(_EngineBase):
    """The paper engine (``core.engine``) behind the uniform surface."""

    def _build_round_fn(self):
        from repro.core import engine as _engine
        self._cfg = self.spec.to_hfl_config().validate()
        self._plan = self.spec.staleness_plan()
        from repro.core.engine import RoundMetrics
        self.metric_fields = RoundMetrics._fields
        return _engine._build_global_round(self.loss_fn, self._cfg,
                                           plan=self._plan,
                                           faults=self.spec.faults,
                                           defense=self.spec.defense,
                                           compression=self.spec.compression)

    def init(self, params: PyTree, rng: jax.Array | None = None) -> PyTree:
        from repro.core.engine import hfl_init
        spec = self.spec
        comp = spec.compression if spec.compressed else None
        if rng is None and (spec.fault_mode
                            or (comp is not None and comp.stochastic)):
            # Fault masks -- and stochastic rounding noise -- draw from
            # the state rng stream.
            rng = jax.random.PRNGKey(0)
        snaps = self._plan is not None and self._plan.needs_snapshots
        # The download-freshness carry only exists where it is consumed:
        # async schedules with timeout faults.
        dl = (spec.fault_mode and spec.faults.timeout_rate > 0
              and self._plan is not None)
        return hfl_init(params, self._cfg, rng, staleness_snapshots=snaps,
                        fault_download=dl,
                        ef_client=comp is not None and comp.ef_client,
                        ef_group=comp is not None and comp.ef_group)

    def global_model(self, state: PyTree) -> PyTree:
        from repro.core.engine import global_model
        if self._plan is not None:
            # Only a cadence-1 group's replicas are guaranteed fresh
            # between async windows; the legacy reader takes [0, 0].
            g = self._plan.fastest_group
            return as_tree(jax.tree.map(lambda x: x[g, 0], state.params))
        return global_model(state)


class MultiLevelMetrics(NamedTuple):
    """Metrics contract of the multilevel backend (losses only)."""

    loss: jax.Array  # [P_1] mean training loss per local step


class MultiLevelEngine(_EngineBase):
    """Appendix E's M-level engine (``core.multilevel``) as an Engine.

    ``round_fn`` consumes the driver layout ``[E, H, *dims, ...]`` (with
    ``E * H = P_1``) and merges the two leading axes into the legacy
    ``[P_1, *dims, ...]`` contract; the raw legacy-layout function stays
    available as ``legacy_round_fn`` for the delegating shim.
    """

    def _build_round_fn(self):
        from repro.core import multilevel as _ml
        spec = self.spec
        dims = spec.levels
        periods = spec.schedule.level_periods(len(dims))
        participation = (None if spec.full_participation
                         else spec.participation_by_level())
        self.legacy_round_fn = _ml._build_multilevel_round(
            self.loss_fn, dims, periods, spec.lr,
            participation=participation,
            participation_mode=spec.participation_mode,
            participation_weighting=spec.participation_weighting)
        self.metric_fields = MultiLevelMetrics._fields
        E, H = self._pack_rounds, self._pack_steps
        raw = self.legacy_round_fn

        def round_fn(state, batches):
            merged = jax.tree.map(
                lambda b: b.reshape((E * H,) + b.shape[2:]), batches)
            state, losses = raw(state, merged)
            return state, MultiLevelMetrics(loss=losses)

        return round_fn

    @property
    def _pack_rounds(self) -> int:
        periods = self.spec.schedule.level_periods(len(self.spec.levels))
        return periods[0] // periods[-1]

    @property
    def _pack_steps(self) -> int:
        return self.spec.schedule.level_periods(len(self.spec.levels))[-1]

    def init(self, params: PyTree, rng: jax.Array | None = None) -> PyTree:
        from repro.core.multilevel import multilevel_init
        return multilevel_init(params, self.spec.levels, rng,
                               use_flat_state=self.spec.state_layout == "flat")

    def global_model(self, state: PyTree) -> PyTree:
        from repro.core.multilevel import multilevel_global_model
        return multilevel_global_model(state)


class ShardedEngine(_EngineBase):
    """The production microbatched round (``launch.train``) as an Engine."""

    def _build_round_fn(self):
        from repro.launch import train as _train
        spec = self.spec
        self._plan = spec.staleness_plan()
        self.metric_fields = _train.ShardedMetrics._fields
        return _train._build_sharded_round(
            self.loss_fn, E=spec.schedule.max_group_rounds,
            H=spec.schedule.local_steps, lr=spec.lr,
            algorithm=spec.algorithm,
            use_fused_update=spec.fusion == "fused",
            fused_mode=spec.fused_mode,
            client_participation=spec.client_participation,
            group_participation=spec.group_participation,
            participation_mode=spec.participation_mode,
            participation_weighting=spec.participation_weighting,
            plan=self._plan, faults=spec.faults, defense=spec.defense,
            compression=spec.compression)

    @property
    def _pack_microbatches(self) -> int:
        return self.spec.schedule.microbatches or 1

    def init(self, params: PyTree, rng: jax.Array | None = None) -> PyTree:
        from repro.launch.train import sharded_init
        G, K = self.spec.levels
        comp = self.spec.compression if self.spec.compressed else None
        if rng is None and (not self.spec.full_participation
                            or self.spec.virtual_population
                            or self.spec.fault_mode
                            or (comp is not None and comp.stochastic)):
            # Virtual populations draw their cohorts -- fault plans their
            # masks, stochastic compressors their rounding noise -- from
            # the state rng even under (mandatory) full in-round
            # participation.
            rng = jax.random.PRNGKey(0)
        dtype = (None if self.spec.correction_dtype is None
                 else jnp.dtype(self.spec.correction_dtype))
        plan = self._plan
        dl = (self.spec.fault_mode and self.spec.faults.timeout_rate > 0
              and plan is not None)
        return sharded_init(
            params, G, K,
            use_flat_state=self.spec.state_layout == "flat",
            correction_dtype=dtype, rng=rng,
            round_counter=plan is not None and plan.needs_round_counter,
            staleness_snapshots=plan is not None and plan.needs_snapshots,
            fault_download=dl,
            ef_client=comp is not None and comp.ef_client,
            ef_group=comp is not None and comp.ef_group)

    def global_model(self, state: PyTree) -> PyTree:
        # Under async schedules only a cadence-1 group holds the fresh
        # global model between windows.
        g = 0 if self._plan is None else self._plan.fastest_group
        return as_tree(jax.tree.map(lambda x: x[g, 0], state.params))


_ENGINES = {
    "simulator": SimulatorEngine,
    "multilevel": MultiLevelEngine,
    "sharded": ShardedEngine,
}


def build(spec: ExperimentSpec, loss_fn: LossFn) -> Engine:
    """Validate ``spec`` and construct its backend :class:`Engine`.

    ``loss_fn(params, batch) -> scalar`` is the single-client loss; every
    backend vmaps it over its topology axes exactly as the legacy
    constructors did.
    """
    spec = spec.validate()
    return _ENGINES[spec.backend](spec, loss_fn)


@functools.partial(jax.profiler.annotate_function, name="repro.fit")
def fit(
    engine: Engine,
    data: PackedBatches,
    T: int,
    *,
    state: PyTree | None = None,
    params: PyTree | None = None,
    rng: jax.Array | None = None,
    chunk: int | None = None,
    eval_every: int = 1,
    eval_fn: Callable[[PyTree, PyTree], PyTree] | None = None,
    donate: bool = True,
    population_store: PopulationStore | None = None,
    overlap: bool = True,
    guard: GuardSpec | bool | None = None,
    checkpoint_every: int | None = None,
    checkpoint_path: str | None = None,
    resume: bool = False,
) -> tuple[PyTree, Horizon]:
    """Train ``T`` global rounds through the compiled horizon driver.

    Pass either a ready ``state`` (to continue a run) or the initial model
    ``params`` (plus an optional ``rng`` for participation sampling) --
    :func:`fit` then composes ``engine.init`` + ``core.driver.run_rounds``
    (donated chunked scans, on-device batch selection, in-scan eval at the
    ``eval_every`` cadence) and returns the final state with the stacked
    :class:`Horizon`. ``data`` comes from ``engine.pack_arrays`` /
    ``engine.pack_tokens``; callers never touch packing internals.

    To continue a horizon, pass the previous call's ``horizon.data`` (the
    packed dataset with its selection rng advanced) together with
    ``state=...`` -- reusing the original ``data`` object would replay the
    finished horizon's shard draws::

        state, hz = fit(engine, data, 10, params=params)
        state, hz = fit(engine, hz.data, 10, state=state)   # rounds 11-20

    With ``spec.population`` set and stateful clients, :func:`fit` routes
    through ``core.population.run_population_rounds`` instead: each chunk
    gathers the sampled cohort's corrections from a host-side
    :class:`PopulationStore` (auto-created via ``engine.init_population``
    unless ``population_store`` is passed -- pass ``horizon.population``
    to continue a run) and scatters them back, with the transfers
    overlapped against device compute unless ``overlap=False``. The store
    rides back on ``horizon.population``.

    ``guard`` (a :class:`GuardSpec`, or ``True`` for the defaults) makes
    the horizon self-heal: each driver chunk is snapshotted, checked for
    divergence and rolled back + retried with a re-split rng (see
    ``core.driver.GuardSpec``). Unless the spec overrides it, retries run
    ``engine.retry_round_fn`` -- the defense norm screen tightens by
    ``retry_widen ** retry`` on each attempt. ``horizon.guard`` reports
    the rollbacks/retries taken.

    ``checkpoint_every=N`` with ``checkpoint_path=dir`` autosaves the
    state (and the data selection rng) at every driver chunk boundary
    that is a multiple of N rounds (``chunk`` defaults to N so boundaries
    align), via ``repro.checkpoint``. ``resume=True`` restores the latest
    checkpoint in ``checkpoint_path`` (if any) and runs only the
    remaining ``T - step`` rounds -- bit-exact with the uninterrupted run
    (tests/test_checkpoint.py).

    A profiler trace shows each call as the host span ``repro.fit``, with
    the driver's ``repro.dispatch`` and ``repro.fetch`` inside it.
    """
    if state is None:
        _require(params is not None,
                 "fit() needs either state=... or params=... to start from")
        state = engine.init(params, rng)
    if checkpoint_every is not None or resume:
        _require(checkpoint_path is not None,
                 "checkpoint autosave/resume needs checkpoint_path=")
    if checkpoint_every is not None:
        _require(checkpoint_every >= 1,
                 f"checkpoint_every must be >= 1, got {checkpoint_every}")
        if chunk is None:
            chunk = checkpoint_every
    if guard is True:
        guard = GuardSpec()
    if guard is not None and guard.round_fn_for_retry is None \
            and hasattr(engine, "retry_round_fn"):
        guard = guard._replace(round_fn_for_retry=engine.retry_round_fn)

    spec = getattr(engine, "spec", None)
    if (spec is not None and spec.population is not None
            and spec.client_state == "stateful"):
        _require(guard is None and checkpoint_every is None and not resume,
                 "guarded horizons and checkpoint autosave are "
                 "materialized-path features; the population "
                 "gather/scatter loop is follow-up work")
        store = (population_store if population_store is not None
                 else engine.init_population(state))
        state, _, horizon = run_population_rounds(
            engine.round_fn, state, store, data, T, chunk=chunk,
            eval_every=eval_every, eval_fn=eval_fn, donate=donate,
            overlap=overlap)
        return state, horizon

    from repro import checkpoint as _ckpt

    start = 0
    if resume:
        step = _ckpt.latest_step(checkpoint_path)
        if step is not None:
            like = {"state": state, "data_rng": np.asarray(data.rng)}
            restored = _ckpt.restore(checkpoint_path, step, like)
            state = restored["state"]
            data = data.replace_rng(jnp.asarray(restored["data_rng"]))
            start = step
            _require(start < T,
                     f"checkpoint at round {start} >= T={T}: nothing left "
                     "to resume")

    on_chunk = None
    if checkpoint_every is not None:
        def on_chunk(done, st, da):
            rounds = start + done
            if rounds % checkpoint_every == 0 or rounds == T:
                _ckpt.save(checkpoint_path, rounds,
                           {"state": st, "data_rng": np.asarray(da.rng)})

    state, _, horizon = run_rounds(
        engine.round_fn, state, data, T - start, chunk=chunk,
        eval_every=eval_every, eval_fn=eval_fn, donate=donate,
        guard=guard, on_chunk=on_chunk)
    return state, horizon


# ------------------------------------------------------------------- CLI


@dataclasses.dataclass(frozen=True)
class CliFlag:
    """One row of the declarative spec<->argparse table.

    ``optional`` rows default to None on the parser and are skipped by
    :func:`spec_from_args` when unset -- for flags that *override* another
    row's field only when given (``--group-rounds`` over ``--E``) or whose
    spec default is genuinely None (``--max-staleness``).
    """

    field: str                     # ExperimentSpec field ("schedule.x" ok)
    flag: str                      # e.g. "--client-participation"
    help: str
    type: Callable = str
    choices: tuple | None = None
    nargs: str | None = None
    optional: bool = False

    @property
    def dest(self) -> str:
        return self.flag.lstrip("-").replace("-", "_")


def _parse_group_rounds(s: str) -> tuple[int, ...]:
    """'4,2,1' -> (4, 2, 1) -- the --group-rounds argparse type."""
    return tuple(int(part) for part in s.split(","))


#: The one table the CLIs are generated from: every entry maps one
#: ExperimentSpec (or RoundSchedule) field to one argparse flag. Adding a
#: spec knob here surfaces it on every entry point at once.
CLI_FLAGS: tuple[CliFlag, ...] = (
    CliFlag("levels", "--levels", "topology dims, e.g. --levels 2 2 (G K)",
            type=int, nargs="+"),
    CliFlag("schedule.group_rounds", "--E",
            "group aggregations per global round", type=int),
    CliFlag("schedule.group_rounds", "--group-rounds",
            "per-group async round counts, comma-separated (e.g. 4,2,1); "
            "overrides --E", type=_parse_group_rounds, optional=True),
    CliFlag("schedule.local_steps", "--H",
            "local SGD steps per group round", type=int),
    CliFlag("algorithm", "--algorithm", "HFL algorithm",
            choices=ALGORITHMS),
    CliFlag("lr", "--lr", "client learning rate", type=float),
    CliFlag("backend", "--backend", "round engine implementation",
            choices=BACKENDS),
    CliFlag("state_layout", "--state-layout",
            "state storage: contiguous flat buffers or model pytrees",
            choices=LAYOUTS),
    CliFlag("fusion", "--fusion",
            "route the MTGC local step through the fused Pallas kernel",
            choices=FUSIONS),
    CliFlag("client_participation", "--client-participation",
            "fraction of each group's clients sampled per round",
            type=float),
    CliFlag("group_participation", "--group-participation",
            "fraction of groups reachable per round", type=float),
    CliFlag("participation_mode", "--participation-mode",
            "Bernoulli draws or exact counts", choices=("uniform", "fixed")),
    CliFlag("participation_weighting", "--weighting",
            "masked-aggregation weighting: realized count or inverse "
            "inclusion probability (Horvitz-Thompson)",
            choices=("none", "inverse_prob")),
    CliFlag("staleness", "--staleness-policy",
            "stale-report policy for async (non-uniform) group rounds",
            choices=STALENESS_POLICIES),
    CliFlag("max_staleness", "--max-staleness",
            "bound on report staleness; groups beyond it are force-synced",
            type=int, optional=True),
    CliFlag("population", "--population",
            "virtual clients per group, backed by the host-side population "
            "store; device state stays cohort-shaped", type=int,
            optional=True),
    CliFlag("cohort_size", "--cohort-size",
            "sampled cohort per group -- must equal levels[1], the compiled "
            "shape (declarative alias; requires --population)", type=int,
            optional=True),
    CliFlag("client_state", "--client-state",
            "stateful persists per-client corrections in the population "
            "store; stateless zero-inits them every round (no store)",
            choices=CLIENT_STATES),
    CliFlag("faults.crash_rate", "--fault-crash",
            "per-(round, client) crash probability -- a crashed client "
            "does no local work and uploads nothing", type=float,
            optional=True),
    CliFlag("faults.timeout_rate", "--fault-timeout",
            "per-(round, group) timeout probability -- the group misses "
            "the global exchange", type=float, optional=True),
    CliFlag("faults.corrupt_rate", "--fault-corrupt",
            "per-(round, client) corrupted-upload probability", type=float,
            optional=True),
    CliFlag("faults.corrupt_kind", "--fault-kind",
            "corrupted-upload payload: nan/inf poison or a norm-exploded "
            "delta", choices=FAULT_KINDS, optional=True),
    CliFlag("defense.screen_norm", "--screen-norm",
            "screen out client deltas whose L2 norm exceeds this",
            type=float, optional=True),
    CliFlag("defense.clip_norm", "--clip-norm",
            "clip surviving client deltas to this L2 norm", type=float,
            optional=True),
    CliFlag("defense.screen_nonfinite", "--screen-nonfinite",
            "screen out non-finite client uploads (1, the plan default; "
            "0 disables)", type=int, optional=True),
    CliFlag("compression.client_mode", "--compress-client",
            "client->group upload compressor",
            choices=COMPRESSION_MODES, optional=True),
    CliFlag("compression.group_mode", "--compress-group",
            "group->global upload compressor",
            choices=COMPRESSION_MODES, optional=True),
    CliFlag("compression.error_feedback", "--error-feedback",
            "carry per-link error-feedback residuals (1, the plan "
            "default; 0 disables)", type=int, optional=True),
    CliFlag("compression.topk_frac", "--topk-frac",
            "fraction of entries a topk link keeps per upload",
            type=float, optional=True),
)

#: Constructors for the nested spec fields CLI rows may target with a
#: dotted ``field`` -- used when the spec default for that field is None.
_NESTED_FIELDS = {"schedule": RoundSchedule, "faults": FaultPlan,
                  "defense": DefensePlan, "compression": CompressionPlan}


def _spec_get(spec: ExperimentSpec, field: str):
    obj = spec
    for part in field.split("."):
        obj = getattr(obj, part)
    return obj


def add_spec_args(parser, *, defaults: ExperimentSpec | None = None,
                  exclude: tuple[str, ...] = ()) -> None:
    """Generate argparse flags for :class:`ExperimentSpec` from the table.

    ``defaults`` seeds each flag's default (so entry points can ship their
    own baseline spec); ``exclude`` drops fields an entry point pins
    (e.g. ``launch.train`` pins ``backend='sharded'``).
    """
    defaults = defaults or ExperimentSpec()
    for row in CLI_FLAGS:
        if row.field in exclude or row.flag in exclude:
            continue
        if row.optional:
            default, kwargs = None, dict(help=row.help)
        else:
            default = _spec_get(defaults, row.field)
            kwargs = dict(help=f"{row.help} (default: {default})")
        if row.choices is not None:
            kwargs["choices"] = row.choices
        else:
            kwargs["type"] = row.type
        if row.nargs is not None:
            kwargs["nargs"] = row.nargs
            kwargs["type"] = row.type
        parser.add_argument(row.flag, default=default, dest=row.dest, **kwargs)


def spec_from_args(args, *, defaults: ExperimentSpec | None = None,
                   **overrides) -> ExperimentSpec:
    """Build the :class:`ExperimentSpec` an argparse namespace describes.

    ``overrides`` (field=value, including ``schedule_*`` shortcuts like
    ``microbatches=1``) win over CLI values -- entry points use them to pin
    backend-specific fields that are not exposed as flags.

    Dotted rows (``schedule.x``, ``faults.x``, ``defense.x``) update the
    nested dataclass via ``dataclasses.replace``; a nested field whose
    spec default is None (no fault plan configured) is constructed from
    its defaults the first time one of its flags is given, so
    ``--fault-crash 0.05`` alone yields a full :class:`FaultPlan`.
    """
    defaults = defaults or ExperimentSpec()
    spec_kw: dict[str, Any] = {}
    nested_kw: dict[str, dict[str, Any]] = {}
    for row in CLI_FLAGS:
        if not hasattr(args, row.dest):
            continue
        value = getattr(args, row.dest)
        if row.optional and value is None:
            continue
        target, _, sub = row.field.partition(".")
        if sub:
            nested_kw.setdefault(target, {})[sub] = value
        else:
            spec_kw[target] = value
    for name, value in overrides.items():
        if name in ("group_rounds", "local_steps", "microbatches", "periods"):
            nested_kw.setdefault("schedule", {})[name] = value
        else:
            spec_kw[name] = value
    for target, kw in nested_kw.items():
        base = getattr(defaults, target)
        if base is None:
            base = _NESTED_FIELDS[target]()
        spec_kw[target] = dataclasses.replace(base, **kw)
    return dataclasses.replace(defaults, **spec_kw)


__all__ = [
    "ALGORITHMS",
    "BACKENDS",
    "BACKEND_ALGORITHMS",
    "CLIENT_STATES",
    "CLI_FLAGS",
    "COMPRESSION_MODES",
    "CliFlag",
    "CompressionPlan",
    "DefensePlan",
    "Engine",
    "ExperimentSpec",
    "FAULT_KINDS",
    "FUSIONS",
    "FaultPlan",
    "GuardReport",
    "GuardSpec",
    "Horizon",
    "LAYOUTS",
    "LoweredChunk",
    "MultiLevelEngine",
    "MultiLevelMetrics",
    "PackedBatches",
    "PopulationStore",
    "RoundSchedule",
    "STALENESS_POLICIES",
    "ShardedEngine",
    "SimulatorEngine",
    "add_spec_args",
    "build",
    "fit",
    "run_population_rounds",
    "spec_from_args",
]

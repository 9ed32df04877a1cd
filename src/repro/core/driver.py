"""Whole-horizon compiled training: scan-over-rounds with donated buffers.

``core.engine`` (and ``launch.train``) compile *one global round* into a
single XLA program; every entry point then drives it from a Python host
loop -- one dispatch per round with host-side batch packing in between. At
paper scale (T = 100 global rounds, G*K = 100 clients) that loop pays
per-round dispatch latency, a host->device transfer of every batch, and a
host sync for the metrics of every round; and without donation each round
briefly holds two copies of the parameter-sized state buffers.

This module lifts the loop onto the device:

* **Packed dataset** (:class:`PackedBatches`): for every client, ``shards``
  pre-formed blocks of ``steps = H * max(A, 1)`` step-batches are sampled
  once on the host and uploaded once -- leaves ``[G, K, S, steps, B, ...]``.
  Each round then draws ``[E, G, K]`` shard indices from a dedicated data
  PRNG key and gathers its batches *on device* (:func:`select_round`); the
  host never packs or transfers batches again.
* **Compiled horizon** (:func:`run_rounds`): ``chunk`` global rounds run as
  one ``jax.lax.scan`` inside a single ``jax.jit`` with the state argument
  donated (``donate_argnums``), so the round-to-round state hand-off reuses
  the input buffers instead of holding two parameter-sized copies, and T
  rounds cost ceil(T / chunk) dispatches. Per-round metrics come back
  stacked, one transfer per chunk. Scan lowers to a while loop, so compile
  time is independent of ``chunk``; chunking exists to bound how much work
  a single dispatch commits to (progress visibility, interruptibility) --
  the remainder chunk triggers at most one extra compile.
* **Per-round fallback** (:func:`make_round_step`): the same select + round
  step as a single donated dispatch, for host loops that need per-round
  control. ``run_rounds`` over the same :class:`PackedBatches` is bit-exact
  against this loop (gated by tests/test_driver.py).

Evaluation stays compiled: ``eval_fn(prev_state, state)`` runs inside the
scan under ``jax.lax.cond``, gated by a per-round boolean mask computed on
the host from ``eval_every`` (plus the final round), so eval work is only
spent on the rounds that report. ``prev_state`` is the pre-round state --
under partial participation its ``rng`` re-derives the round's masks (see
``core.participation``), e.g. to pick an active replica to evaluate.

The driver is layout- and engine-agnostic: ``round_fn`` may be any
``(state, batches) -> (state, metrics)`` function (simulator engine, tree
or flat state, or the sharded production round -- set ``microbatches`` for
its ``[E, H, A, G, K, ...]`` batch layout), and the participation RNG stays
where it always was, inside the engine state.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


class PackedBatches:
    """A once-uploaded, device-resident training dataset for the driver.

    arrays: pytree whose leaves are ``[G, K, S, steps, ...]`` -- ``S``
        pre-sampled blocks per client, each holding ``steps`` step-batches
        (``steps = local_steps * max(microbatches, 1)``). Deeper topologies
        (the M-level engine) carry all their client axes up front:
        ``[*dims, S, steps, ...]`` with ``topo_ndim = len(dims)``.
    rng: PRNG key advanced one split per round for shard selection.
    group_rounds / local_steps / microbatches: static layout of one round.
        ``microbatches=None`` emits engine-layout batches ``[E, H, G, K,
        ...]``; an integer emits the sharded microbatched layout
        ``[E, H, A, G, K, ...]``. A per-group ``group_rounds`` tuple
        (async schedules) packs its padded maximum.
    topo_ndim: how many leading leaf axes index the client topology
        (2 for the two-level engines; M for an M-level tree, where the
        selected batches come back ``[E, H, *dims, ...]``).

    Registered as a pytree (children: arrays + rng; the layout is static
    aux data), so it can cross ``jit`` boundaries whole.
    """

    __slots__ = ("arrays", "rng", "group_rounds", "local_steps",
                 "microbatches", "topo_ndim")

    def __init__(self, arrays: PyTree, rng: jax.Array,
                 group_rounds: int | tuple[int, ...],
                 local_steps: int, microbatches: int | None = None,
                 topo_ndim: int = 2):
        self.arrays = arrays
        self.rng = rng
        if isinstance(group_rounds, (list, tuple)):
            # Async per-group schedules pack the padded max(E_g) axis;
            # stragglers' dead iterations draw shards that the engines'
            # iteration mask then gates out of every aggregate.
            group_rounds = max(int(e) for e in group_rounds)
        self.group_rounds = int(group_rounds)
        self.local_steps = int(local_steps)
        self.microbatches = None if microbatches is None else int(microbatches)
        self.topo_ndim = int(topo_ndim)

    @property
    def num_shards(self) -> int:
        return jax.tree.leaves(self.arrays)[0].shape[self.topo_ndim]

    def replace_rng(self, rng: jax.Array) -> "PackedBatches":
        return PackedBatches(self.arrays, rng, self.group_rounds,
                             self.local_steps, self.microbatches,
                             self.topo_ndim)

    def __repr__(self) -> str:
        shapes = [tuple(x.shape) for x in jax.tree.leaves(self.arrays)]
        return (f"PackedBatches(E={self.group_rounds}, H={self.local_steps}, "
                f"A={self.microbatches}, leaves={shapes})")


def _packed_flatten(pb: PackedBatches):
    return ((pb.arrays, pb.rng),
            (pb.group_rounds, pb.local_steps, pb.microbatches, pb.topo_ndim))


def _packed_unflatten(aux, children) -> PackedBatches:
    arrays, rng = children
    return PackedBatches(arrays, rng, *aux)


jax.tree_util.register_pytree_node(PackedBatches, _packed_flatten,
                                   _packed_unflatten)


def select_round(data: PackedBatches, key: jax.Array) -> PyTree:
    """Gather one global round of batches from the packed shards, on device.

    Draws one shard index per (group round, client) -- ``[E, *dims]`` -- and
    gathers the corresponding blocks, so a round's batch tensor never exists
    on the host. Returns leaves ``[E, H, *dims, ...]`` (``microbatches is
    None``) or ``[E, H, A, *dims, ...]``; ``dims`` is ``(G, K)`` for the
    two-level engines and the full topology for deeper trees
    (``data.topo_ndim`` leading axes).
    """
    E, H, A = data.group_rounds, data.local_steps, data.microbatches
    lead = jax.tree.leaves(data.arrays)[0].shape[:data.topo_ndim]
    S = jax.tree.leaves(data.arrays)[0].shape[data.topo_ndim]
    P = int(np.prod(lead))
    # One draw per (round, client); the flat reshape leaves the bit stream
    # identical to the historical [E, G, K] draw.
    sid = jax.random.randint(key, (E,) + lead, 0, S).reshape(E, P)

    def gather(leaf):
        flat = leaf.reshape((P,) + leaf.shape[data.topo_ndim:])
        sel = flat[jnp.arange(P)[None, :], sid]      # [E, P, steps, ...]
        sel = jnp.moveaxis(sel, 2, 1)                # [E, steps, P, ...]
        sel = sel.reshape(sel.shape[:2] + lead + sel.shape[3:])
        if A is None:
            return sel                               # steps == H
        return sel.reshape((E, H, A) + sel.shape[2:])

    return jax.tree.map(gather, data.arrays)


def pack_client_shards(
    data_arrays: dict[str, np.ndarray],
    indices: list,
    *,
    group_rounds: int | tuple[int, ...],
    local_steps: int,
    batch_size: int,
    shards: int = 16,
    microbatches: int | None = None,
    rng: np.random.Generator,
    key: jax.Array,
) -> PackedBatches:
    """Pack a partitioned array dataset (``data.partition``) for the driver.

    For every client, pre-samples ``shards`` blocks of ``steps x batch``
    examples (with replacement, like ``sample_round_batches``) from its
    index pool -- once, on the host -- and uploads the gathered features as
    ``[G, K, S, steps, B, ...]`` device arrays. Per-round batch variety then
    comes from on-device shard selection: each group round draws one of the
    ``S`` blocks per client, so ``shards`` bounds how many distinct blocks a
    client can see across the horizon (host memory scales with it; 16 is
    plenty for the paper's schedules).

    ``indices`` is the per-client index-pool nesting: ``[G][K]`` lists of
    arrays for the two-level engines, or ``[N_1][N_2]...[N_M]`` for an
    M-level topology -- the nesting depth becomes ``topo_ndim`` and the
    packed leaves carry all topology axes up front (``[*dims, S, steps,
    B, ...]``). Clients draw in row-major order either way, so the
    two-level case is bit-identical to the historical packing.
    """
    steps = local_steps * (microbatches or 1)

    def draw(node):
        if isinstance(node, (list, tuple)):
            return np.stack([draw(child) for child in node])
        return rng.choice(node, size=(shards, steps, batch_size), replace=True)

    sel = draw(indices)                              # [*dims, S, steps, B]
    topo_ndim = sel.ndim - 3
    arrays = {name: jnp.asarray(arr[sel]) for name, arr in data_arrays.items()}
    return PackedBatches(arrays, key, group_rounds, local_steps, microbatches,
                         topo_ndim)


def pack_lm_shards(
    tokens: np.ndarray | list,
    *,
    num_groups: int,
    clients_per_group: int,
    group_rounds: int | tuple[int, ...],
    local_steps: int,
    batch_size: int,
    seq_len: int,
    shards: int = 8,
    microbatches: int | None = None,
    rng: np.random.Generator,
    key: jax.Array,
) -> PackedBatches:
    """Pack a token stream (``data.lm``) for the driver.

    Samples random ``seq_len`` windows (next-token targets shifted by one,
    exactly like ``lm_batches``) into ``{"tokens", "targets"}`` blocks of
    shape ``[G, K, S, steps, B, seq_len]``, uploaded once.

    ``tokens`` is either one shared stream (every client samples from it,
    the historical behaviour, draw-for-draw identical) or a ``[G][K]``
    nesting of per-client streams (e.g. domain-skewed shards) -- each
    client then samples windows from its own stream.
    """
    G, K = num_groups, clients_per_group
    steps = local_steps * (microbatches or 1)

    def windows(stream, size):
        stream = np.asarray(stream)
        starts = rng.integers(0, len(stream) - seq_len - 1, size=size)
        win = starts[..., None] + np.arange(seq_len)
        return stream[win].astype(np.int32), stream[win + 1].astype(np.int32)

    if isinstance(tokens, np.ndarray):
        toks, targs = windows(tokens, (G, K, shards, steps, batch_size))
    else:
        per_client = [[windows(tokens[g][k], (shards, steps, batch_size))
                       for k in range(K)] for g in range(G)]
        toks = np.stack([[per_client[g][k][0] for k in range(K)]
                         for g in range(G)])
        targs = np.stack([[per_client[g][k][1] for k in range(K)]
                          for g in range(G)])
    arrays = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(targs)}
    return PackedBatches(arrays, key, group_rounds, local_steps, microbatches)


RoundFn = Callable[[PyTree, PyTree], tuple[PyTree, PyTree]]


def make_round_step(round_fn: RoundFn, *, donate: bool = True):
    """One (on-device select + global round) as a single jitted dispatch.

    Returns ``step(state, data) -> (state, data, metrics)``. With ``donate``
    (default) the state argument's buffers are donated to the call, so the
    loop never holds two copies of the ``[G, K, N]`` state -- the caller
    must not reuse the state object it passed in. The per-round driver:
    what ``run_rounds`` compiles into its scan, kept as the host-loop
    building block (and the parity baseline for the compiled horizon).
    """

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def _step(state, data: PackedBatches):
        key, rng = jax.random.split(data.rng)
        state, metrics = round_fn(state, select_round(data, key))
        return state, rng, metrics

    def step(state, data: PackedBatches):
        state, rng, metrics = _step(state, data)
        return state, data.replace_rng(rng), metrics

    return step


class Horizon(NamedTuple):
    """Stacked results of a multi-round driver run.

    metrics: the round function's metrics, stacked -- leaves ``[T, ...]``.
    evals: ``eval_fn`` outputs at the evaluated rounds -- leaves
        ``[len(eval_rounds), ...]`` -- or None when no ``eval_fn`` was given.
    eval_rounds: 1-based global round indices that were evaluated
        (multiples of ``eval_every`` plus the final round).
    data: the :class:`PackedBatches` with its selection rng advanced past
        this horizon -- continue training from it (``repro.api.fit`` hands
        it back so a continued run draws fresh shard indices instead of
        replaying the finished horizon's).
    population: the host-side ``PopulationStore`` when the run trained a
        virtual client population (``core.population``), with every cohort's
        corrections scattered back -- None for materialized runs.
    guard: a :class:`GuardReport` when the run was guarded
        (``run_rounds(..., guard=...)``); None otherwise.
    """

    metrics: Any
    evals: Any | None
    eval_rounds: np.ndarray
    data: Any | None = None
    population: Any | None = None
    guard: Any | None = None


class GuardSpec(NamedTuple):
    """Self-healing horizon policy for ``run_rounds(..., guard=...)``.

    Before each chunk dispatch the driver snapshots the state (and the
    data rng) to the host; after the chunk it checks the divergence
    predicate below, and on divergence rolls the chunk back and retries it
    with a re-split rng -- up to ``max_retries`` times, then raises
    ``RuntimeError``. Divergence is:

    * any non-finite value in the chunk's ``metrics.loss``, or
    * (``check_state``) a non-finite value in the state's correction /
      global leaves -- the ``z`` / ``y`` / ``dyn`` / ``glob`` fields when
      the state has them, every leaf otherwise. ``params`` is deliberately
      NOT checked: under fault injection a frozen replica legitimately
      carries non-finite bits until its next download heals it, without
      ever entering an aggregate (see core/faults.py) -- or
    * the chunk's final-round mean loss exceeding ``loss_spike`` times the
      last accepted chunk's (losses assumed nonnegative; the first chunk
      has no reference and only the finiteness checks apply).

    ``round_fn_for_retry(attempt)`` (attempt >= 1) supplies the round
    function for retries -- e.g. one rebuilt with a tighter screen
    threshold (``DefensePlan.retry_widen``; ``repro.api.fit`` wires the
    engine's ``retry_round_fn`` here). None retries the original.

    The per-chunk snapshot + divergence sync serializes the async dispatch
    pipeline once per chunk -- bench_faults.py gates the zero-fault
    overhead under 10% per round.
    """

    max_retries: int = 2
    loss_spike: float = 10.0
    check_state: bool = True
    round_fn_for_retry: Callable[[int], RoundFn] | None = None


class GuardReport(NamedTuple):
    """What the guarded horizon did: how many chunks were rolled back at
    least once, and the total retry attempts across the run."""

    rollbacks: int
    retries: int


_GUARD_FIELDS = ("z", "y", "dyn", "glob")


def _guard_leaves(state: PyTree) -> list:
    """The leaves the guard's state check covers (see GuardSpec)."""
    picked = [getattr(state, f) for f in _GUARD_FIELDS
              if getattr(state, f, None) is not None]
    return jax.tree.leaves(picked if picked else state)


def _finite_chunk(state: PyTree, losses, check_state: bool) -> bool:
    ok = np.isfinite(np.asarray(losses)).all()
    if ok and check_state:
        # Reduce on device: each leaf costs one scalar transfer instead of
        # pulling the whole state to host every chunk.
        for leaf in _guard_leaves(state):
            if (hasattr(leaf, "dtype")
                    and jnp.issubdtype(leaf.dtype, jnp.floating)
                    and not bool(jnp.isfinite(leaf).all())):
                return False
    return bool(ok)


def _host_snapshot(tree: PyTree) -> PyTree:
    """Host copies of every leaf (syncs; survives donation of the device
    buffers)."""
    return jax.tree.map(np.asarray, tree)


def _fold_retry(rng, salt: int):
    return jax.random.fold_in(rng, np.uint32(salt))


_RUNNERS_PER_FN = 8


def _build_chunk_runner(round_fn: RoundFn, eval_fn, donate: bool):
    """Build the jitted scan-over-rounds chunk executor."""

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def run_chunk(state, data: PackedBatches, eval_mask: jax.Array):
        def body(carry, do_eval):
            state, rng = carry
            key, rng = jax.random.split(rng)
            prev = state
            state, metrics = round_fn(state, select_round(data, key))
            if eval_fn is None:
                return (state, rng), (metrics,)
            shapes = jax.eval_shape(eval_fn, prev, state)
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
            ev = jax.lax.cond(do_eval, eval_fn, lambda p, s: zeros, prev, state)
            return (state, rng), (metrics, ev)

        (state, rng), outs = jax.lax.scan(body, (state, data.rng), eval_mask)
        return (state, rng) + outs

    return run_chunk


def _chunk_runner(round_fn: RoundFn, eval_fn, donate: bool):
    """Fetch (or build) the chunk executor for this (round_fn, eval_fn).

    The runner is cached *on the round function object itself*, so its
    lifetime is exactly the round function's: repeated ``run_rounds`` calls
    with the same functions (chunked horizons, benchmark reps) reuse the
    compiled executable instead of re-tracing, and when the caller drops
    the round function (e.g. a benchmark sweep building one per combo) the
    executable -- and whatever arrays its closures captured -- become
    collectable with it. A global cache keyed on identity (the previous
    ``lru_cache``) instead kept up to ``maxsize`` dead round functions and
    their executables pinned; keying on a semantic config signature would
    alias distinct closures (two round fns with equal configs but different
    captured loss/eval state must not share a runner).

    Within one round function, runners are keyed by ``(id(eval_fn),
    donate)``; the runner strongly references its ``eval_fn``, so the id
    cannot be recycled while the entry lives. The per-fn cache is bounded
    (FIFO eviction at ``_RUNNERS_PER_FN``) so a long-lived round function
    driven with fresh eval closures per call cannot accumulate executables
    without limit. Callables that reject attribute assignment (e.g. bound
    methods) just get a fresh runner per call -- correct, merely uncached.
    """
    try:
        cache = round_fn.__chunk_runners__
    except AttributeError:
        try:
            round_fn.__chunk_runners__ = cache = {}
        except AttributeError:
            return _build_chunk_runner(round_fn, eval_fn, donate)
    key = (None if eval_fn is None else id(eval_fn), bool(donate))
    runner = cache.get(key)
    if runner is None:
        while len(cache) >= _RUNNERS_PER_FN:
            cache.pop(next(iter(cache)))
        cache[key] = runner = _build_chunk_runner(round_fn, eval_fn, donate)
    return runner


class LoweredChunk(NamedTuple):
    """Compiled-but-never-executed artifacts of one driver chunk.

    What the static auditor (``repro.analysis``) inspects: the AOT trace
    of the chunk runner over fully abstract inputs -- jaxpr for
    primitive-level invariants (fusion contract, no host callbacks in
    loop bodies), optimized HLO for donation aliases / f64 / cost
    budgets, and the abstract in/out states for dtype contracts. Nothing
    here ever touched device data beyond compilation.

    traced / lowered / compiled: the ``jax.jit(...).trace -> lower ->
        compile`` chain (``compiled`` is None when ``trace_chunk`` was
        asked not to compile).
    state: the abstract (ShapeDtypeStruct) input state the runner was
        traced over; ``out_state`` is the matching output state.
    data: the abstract :class:`PackedBatches` it was traced over.
    donate: whether the state argument was donated.
    """

    traced: Any
    lowered: Any
    compiled: Any
    state: PyTree
    data: PackedBatches
    donate: bool

    @property
    def jaxpr(self):
        return self.traced.jaxpr

    @property
    def hlo(self) -> str:
        """Optimized (post-layout, post-fusion) HLO text."""
        return self.compiled.as_text()

    @property
    def out_state(self) -> PyTree:
        """Abstract output state (run_chunk returns ``(state, rng, ...)``)."""
        return self.traced.out_info[0]


def trace_chunk(
    round_fn: RoundFn,
    state: PyTree,
    data: PackedBatches,
    chunk: int = 2,
    *,
    eval_fn: Callable[[PyTree, PyTree], PyTree] | None = None,
    donate: bool = True,
    compile: bool = True,
) -> LoweredChunk:
    """Trace + lower (+ compile) one ``chunk``-round dispatch, no execution.

    ``state`` and ``data`` leaves may be ``jax.ShapeDtypeStruct``s (build
    them with ``jax.eval_shape``); the AOT path never allocates them. Uses
    the same cached runner as :func:`dispatch_chunk`, so a subsequent
    identical trace must hit the jit tracing cache -- the retrace gate in
    ``repro.analysis`` is built on exactly this property.
    """
    runner = _chunk_runner(round_fn, eval_fn, donate)
    mask = jax.ShapeDtypeStruct((int(chunk),), jnp.bool_)
    traced = runner.trace(state, data, mask)
    lowered = traced.lower()
    compiled = lowered.compile() if compile else None
    return LoweredChunk(traced, lowered, compiled, state, data, bool(donate))


def dispatch_chunk(
    round_fn: RoundFn,
    state: PyTree,
    data: PackedBatches,
    eval_mask: np.ndarray,
    *,
    eval_fn: Callable[[PyTree, PyTree], PyTree] | None = None,
    donate: bool = True,
) -> tuple[PyTree, PackedBatches, PyTree, PyTree | None]:
    """Dispatch one compiled ``len(eval_mask)``-round chunk, without syncing.

    The building block ``run_rounds`` (and ``core.population``'s
    gather/scatter loop) iterates: fetches the cached chunk runner for
    ``(round_fn, eval_fn, donate)`` and fires it. JAX dispatch is
    asynchronous, so the returned ``(state, data, metrics, evals)`` are
    futures -- the host is free to do work (e.g. population-store gather /
    scatter) while the device scans the chunk; only touching the results
    with ``np.asarray`` blocks. With ``donate`` the input state's buffers
    are consumed.

    The host span ``repro.dispatch`` (argument ``rounds``, the chunk's
    round count) covers fetching the runner through the enqueue, so a
    trace shows a recompile as a long span.
    """
    with jax.profiler.TraceAnnotation("repro.dispatch",
                                      rounds=len(eval_mask)):
        runner = _chunk_runner(round_fn, eval_fn, donate)
        out = runner(state, data, jnp.asarray(eval_mask))
    state, rng = out[0], out[1]
    evals = out[3] if eval_fn is not None else None
    return state, data.replace_rng(rng), out[2], evals


def eval_mask_for_chunk(done: int, n: int, T: int, eval_every: int) -> np.ndarray:
    """Per-round eval booleans for rounds ``done+1 .. done+n`` of ``T``.

    True at multiples of ``eval_every`` plus the final round -- the single
    definition both drivers share so their eval cadences cannot drift.
    """
    return np.array([(done + i + 1) % eval_every == 0 or done + i + 1 == T
                     for i in range(n)])


def run_rounds(
    round_fn: RoundFn,
    state: PyTree,
    data: PackedBatches,
    T: int,
    *,
    chunk: int | None = None,
    eval_every: int = 1,
    eval_fn: Callable[[PyTree, PyTree], PyTree] | None = None,
    donate: bool = True,
    guard: GuardSpec | None = None,
    on_chunk: Callable[[int, PyTree, PackedBatches], None] | None = None,
) -> tuple[PyTree, PackedBatches, Horizon]:
    """Run ``T`` global rounds as ceil(T / chunk) compiled dispatches.

    Each dispatch scans ``chunk`` rounds of (on-device batch selection +
    ``round_fn``) with the state buffers donated, and returns that chunk's
    metrics stacked -- one device->host transfer per chunk instead of per
    round. ``chunk=None`` (or 0) compiles the whole horizon into a single
    dispatch; a remainder ``T % chunk`` costs at most one extra compile
    (scan lowers to a while loop, so compile time does not grow with
    ``chunk``).

    ``eval_fn(prev_state, state) -> pytree`` runs inside the scan under
    ``lax.cond`` at rounds ``eval_every, 2*eval_every, ..., T`` --
    ``prev_state`` is the pre-round state, whose ``rng`` re-derives the
    round's participation masks when a caller needs them for evaluation.

    With ``donate`` (default) the caller's ``state`` (and each intermediate
    chunk state) is consumed: its buffers are invalidated and reused for
    the output state, halving driver peak state memory. Pass
    ``donate=False`` to keep the input alive.

    With ``guard`` (a :class:`GuardSpec`) the horizon self-heals: each
    chunk is snapshotted before dispatch and rolled back + retried with a
    re-split rng when it diverges (see GuardSpec for the predicate), and
    the returned Horizon carries a :class:`GuardReport`. ``on_chunk(done,
    state, data)`` fires after every accepted chunk -- ``repro.api.fit``
    hooks checkpoint autosave here.

    Returns ``(state, data, Horizon)`` -- ``data`` carries the advanced
    selection rng so horizons can be continued. The host's wait for the
    chunks' metrics is the host span ``repro.fetch``.
    """
    assert T >= 1 and eval_every >= 1
    if chunk is not None and chunk < 0:
        raise ValueError(f"chunk must be None or >= 0, got {chunk}")
    chunk = T if not chunk else min(int(chunk), T)

    mets, evs, masks = [], [], []
    done = 0
    loss_ref = None
    rollbacks = retries = 0
    while done < T:
        n = min(chunk, T - done)
        mask = eval_mask_for_chunk(done, n, T, eval_every)
        if guard is None:
            state, data, metrics, ev = dispatch_chunk(
                round_fn, state, data, mask, eval_fn=eval_fn, donate=donate)
        else:
            state, data, metrics, ev, loss_ref, rb, rt = _guarded_chunk(
                round_fn, state, data, mask, guard,
                eval_fn=eval_fn, donate=donate, done=done, loss_ref=loss_ref)
            rollbacks += rb
            retries += rt
        mets.append(metrics)
        if eval_fn is not None:
            evs.append(ev)
        masks.append(mask)
        done += n
        if on_chunk is not None:
            on_chunk(done, state, data)

    def _cat(*xs):
        return np.concatenate([np.asarray(x) for x in xs])

    mask_all = np.concatenate(masks)
    eval_rounds = np.nonzero(mask_all)[0] + 1
    evals = None
    with jax.profiler.TraceAnnotation("repro.fetch"):
        metrics = jax.tree.map(_cat, *mets)
        if eval_fn is not None:
            evals = jax.tree.map(lambda *xs: _cat(*xs)[mask_all], *evs)
    report = GuardReport(rollbacks, retries) if guard is not None else None
    return state, data, Horizon(metrics, evals, eval_rounds, data, None, report)


def _guarded_chunk(
    round_fn: RoundFn,
    state: PyTree,
    data: PackedBatches,
    eval_mask: np.ndarray,
    guard: GuardSpec,
    *,
    eval_fn: Callable[[PyTree, PyTree], PyTree] | None,
    donate: bool,
    done: int,
    loss_ref: float | None,
):
    """One snapshot / dispatch / check / maybe-rollback cycle.

    Returns ``(state, data, metrics, evals, new_loss_ref, rolled_back,
    retries_used)``. The snapshot is taken to host memory BEFORE dispatch
    because donation consumes the input buffers; a retry replays the chunk
    from the snapshot with ``attempt`` folded into the state and data rngs
    so a different participation / fault draw is realized.
    """
    snap_state = _host_snapshot(state)
    snap_rng = np.asarray(data.rng)
    attempt = 0
    while True:
        if attempt > 0:
            salt = done * (guard.max_retries + 1) + attempt
            state = jax.tree.map(jnp.asarray, snap_state)
            if getattr(state, "rng", None) is not None and hasattr(state, "_replace"):
                state = state._replace(rng=_fold_retry(jnp.asarray(state.rng), salt))
            data = data.replace_rng(_fold_retry(jnp.asarray(snap_rng), salt))
            rf = (guard.round_fn_for_retry(attempt)
                  if guard.round_fn_for_retry is not None else round_fn)
        else:
            rf = round_fn
        state, data, metrics, ev = dispatch_chunk(
            rf, state, data, eval_mask, eval_fn=eval_fn, donate=donate)

        losses = getattr(metrics, "loss", None)
        if losses is None:
            raise ValueError(
                "guarded run_rounds needs a `loss` field in the round "
                "metrics to detect divergence")
        losses = np.asarray(losses)
        ok = _finite_chunk(state, losses, guard.check_state)
        final = float(np.mean(losses[-1])) if ok else np.inf
        if ok and loss_ref is not None and loss_ref > 0.0:
            ok = final <= guard.loss_spike * loss_ref
        if ok:
            return (state, data, metrics, ev, final,
                    int(attempt > 0), attempt)
        if attempt >= guard.max_retries:
            raise RuntimeError(
                f"guarded horizon diverged at rounds {done + 1}.."
                f"{done + len(eval_mask)} and exhausted "
                f"{guard.max_retries} retries (last final-round loss "
                f"{final}, reference {loss_ref})")
        attempt += 1

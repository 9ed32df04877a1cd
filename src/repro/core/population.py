"""Virtual client population: O(cohort) device state for million-client HFL.

Every engine materializes its per-client state as ``[G, K, ...]`` device
buffers, so K -- the number of clients -- is a compile-time shape bounded
by device memory. Production FL is the opposite regime: a server *samples*
a small cohort from a huge population each round. This module decouples
the two: K stays the compiled cohort shape, while the population lives in
a host-side store holding only what genuinely persists per client -- the
multi-timescale corrections ``z`` (and FedDyn's gradient memory ``dyn``).
Per-client ``params`` need no store: every participant re-downloads the
global model at dissemination, so a client entering a cohort starts from
the current global model plus its persistent correction.

The store reuses the :class:`~repro.core.packer.Packer` segment table: per
persistent field, one contiguous numpy buffer per dtype with leading axes
``[G, P]`` (``P`` virtual clients per group). Each driver chunk then runs

    gather -> fused round(s) -> scatter

gather the sampled cohort's rows into the existing flat ``[G, K, N]``
device buffer, dispatch the unchanged compiled chunk, scatter the updated
rows back. With ``overlap=True`` the host half double-buffers against the
device half: JAX dispatch is asynchronous, so while the device scans a
chunk the host draws the *next* cohort and pre-gathers its rows, then
after syncing scatters the finished cohort and patches only the staged
rows the two cohorts share -- the gather/scatter cost hides behind
compute (measured in ``benchmarks/bench_population.py``).

Cohort draws follow the ``round_masks`` key discipline (split the state
rng once per draw, fold per group) -- except in the degenerate
``population == cohort`` case, where every client is materialized, no
draw happens, and the rng is left untouched: the cohort path is then
bit-exact against the materialized engines (gated in
tests/test_population.py).

Stateless clients (``client_state="stateless"``) need no store at all:
:func:`stateless_round` zero-initializes the persistent fields at every
round boundary, the assumption large-cohort FL systems make.

Front door: set ``ExperimentSpec.population`` / ``cohort_size`` /
``client_state`` and ``repro.api.fit`` routes through
:func:`run_population_rounds` automatically.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tree as tu
from repro.core.driver import (
    Horizon,
    PackedBatches,
    RoundFn,
    dispatch_chunk,
    eval_mask_for_chunk,
)
from repro.core.packer import FlatBuffers, Packer, is_flat, make_packer

PyTree = Any

HostBuffers = dict[str, dict[str, np.ndarray]]  # field -> dtype key -> [G,P,N]


def population_fields(algorithm: str) -> tuple[str, ...]:
    """Which state fields persist per client for this algorithm.

    ``z`` (the client->group correction) persists for every correction
    algorithm; FedDyn additionally carries its per-client gradient memory
    ``dyn``. Fields absent from a given state type (the sharded state has
    no ``dyn``) are skipped at store construction.
    """
    return ("z", "dyn") if algorithm == "feddyn" else ("z",)


def draw_cohort(key: jax.Array, num_groups: int, population: int,
                cohort: int) -> np.ndarray:
    """Sample one cohort: ``[G, cohort]`` distinct client ids per group.

    One subkey per group (same fold discipline as ``round_masks``), each
    drawing ``cohort`` ids from ``population`` without replacement.
    """
    keys = jax.random.split(key, num_groups)
    return np.stack([
        np.asarray(jax.random.choice(k, population, (cohort,), replace=False))
        for k in keys
    ])


class PopulationStore:
    """Host-side per-client persistent state for ``P`` virtual clients/group.

    data: per persistent field, one contiguous numpy buffer per dtype with
        shape ``[G, P, N_dtype]`` -- the ``Packer`` segment table of the
        corresponding state field, with the cohort axis widened to the
        population. New clients start at zero, exactly like a freshly
        initialized materialized state.
    packers / flat: per-field segment table and whether the *state* holds
        that field as :class:`FlatBuffers` (gathers then install buffers
        directly) or as a template tree (gathers unflatten through the
        table).

    Registered as a pytree whose leaves are the numpy buffers, so
    ``checkpoint.save`` / ``restore`` round-trip a ``{"state": ...,
    "population": store}`` tree with no special casing; unflattening
    coerces leaves back to host numpy so in-place scatter keeps working
    on a restored store.
    """

    __slots__ = ("fields", "num_groups", "population", "packers", "flat",
                 "data")

    def __init__(self, fields: tuple[str, ...], num_groups: int,
                 population: int, packers: dict[str, Packer],
                 flat: dict[str, bool], data: HostBuffers):
        self.fields = tuple(fields)
        self.num_groups = int(num_groups)
        self.population = int(population)
        self.packers = dict(packers)
        self.flat = dict(flat)
        self.data = data

    @classmethod
    def from_state(cls, state: PyTree, population: int,
                   fields: tuple[str, ...] = ("z",)) -> "PopulationStore":
        """Build a zeroed store matching ``state``'s persistent fields.

        ``state`` is any engine state whose ``fields`` carry ``[G, K,
        ...]`` leading axes (FlatBuffers or tree layout); fields the state
        type lacks (or holds as None) are dropped.
        """
        present = tuple(f for f in fields
                        if getattr(state, f, None) is not None)
        if not present:
            raise ValueError(
                f"state has none of the persistent fields {fields!r}")
        packers: dict[str, Packer] = {}
        flat: dict[str, bool] = {}
        num_groups = None
        for f in present:
            value = getattr(state, f)
            if is_flat(value):
                packers[f] = value.packer
                flat[f] = True
                lead = value.lead_shape
            else:
                leaves = jax.tree.leaves(value)
                template = jax.tree.map(lambda x: x[0, 0], value)
                packers[f] = make_packer(template)
                flat[f] = False
                lead = leaves[0].shape[:2]
            if len(lead) != 2:
                raise ValueError(
                    f"field {f!r} needs [G, K, ...] leading axes, got lead "
                    f"shape {lead}")
            num_groups = lead[0]
            if population < lead[1]:
                raise ValueError(
                    f"population ({population}) < materialized cohort "
                    f"({lead[1]})")
        data: HostBuffers = {
            f: {key: np.zeros((num_groups, population, n), np.dtype(key))
                for key, n in packers[f].buffer_sizes}
            for f in present
        }
        store = cls(present, num_groups, population, packers, flat, data)
        # Seed rows [0, K) from the state's current values (identity
        # mapping): a fresh state scatters zeros (no-op), while a resumed
        # mid-training state keeps its corrections instead of having them
        # silently zeroed by the first cohort install. The store is
        # authoritative from here on.
        cohort = store.cohort_of(state)
        idx = np.broadcast_to(np.arange(cohort), (num_groups, cohort))
        store.scatter(idx, store.extract(state))
        return store

    # -------------------------------------------------- host <-> device

    @functools.partial(jax.profiler.annotate_function,
                       name="repro.population.gather")
    def gather(self, idx: np.ndarray) -> HostBuffers:
        """Copy the cohort rows ``idx [G, K]`` out of the store (host).

        The host span ``repro.population.gather`` (and
        ``repro.population.scatter`` on :meth:`scatter`) is for operators of
        a population run, which no benchmark cell makes yet: a profiler
        trace then shows how much of the host's copying the device's work
        hides."""
        rows = np.arange(self.num_groups)[:, None]
        return {
            f: {key: buf[rows, idx] for key, buf in bufs.items()}
            for f, bufs in self.data.items()
        }

    @functools.partial(jax.profiler.annotate_function,
                       name="repro.population.scatter")
    def scatter(self, idx: np.ndarray, host_vals: HostBuffers) -> None:
        """Write the cohort rows back into the store, in place."""
        rows = np.arange(self.num_groups)[:, None]
        for f, bufs in host_vals.items():
            for key, arr in bufs.items():
                self.data[f][key][rows, idx] = arr

    def refresh(self, staged: HostBuffers, idx_new: np.ndarray,
                idx_old: np.ndarray) -> None:
        """Re-read staged rows that ``idx_old``'s scatter just updated.

        The overlapped driver pre-gathers the next cohort while the device
        is still training the current one; rows shared between the two
        cohorts are stale in that staging copy. Patch exactly those rows
        from the (now freshly scattered) store, in place.
        """
        for g in range(self.num_groups):
            stale = np.isin(idx_new[g], idx_old[g])
            if not stale.any():
                continue
            rows = idx_new[g][stale]
            for f, bufs in staged.items():
                for key, arr in bufs.items():
                    arr[g, stale] = self.data[f][key][g, rows]

    def install(self, state: PyTree, staged: HostBuffers) -> PyTree:
        """Replace the state's persistent fields with staged cohort rows."""
        updates = {}
        for f in self.fields:
            bufs = {key: jnp.asarray(arr) for key, arr in staged[f].items()}
            value = FlatBuffers(bufs, self.packers[f])
            updates[f] = value if self.flat[f] else value.to_tree()
        return state._replace(**updates)

    def extract(self, state: PyTree) -> HostBuffers:
        """Pull the persistent fields off the device (blocks until ready)."""
        out: HostBuffers = {}
        for f in self.fields:
            value = getattr(state, f)
            if not self.flat[f]:
                value = self.packers[f].flatten(value)
            out[f] = {key: np.asarray(buf) for key, buf in value.bufs.items()}
        return out

    # -------------------------------------------------------- reporting

    def cohort_of(self, state: PyTree) -> int:
        """The materialized cohort size K of this state's leading axes."""
        value = getattr(state, self.fields[0])
        lead = (value.lead_shape if is_flat(value)
                else jax.tree.leaves(value)[0].shape[:2])
        return int(lead[1])

    def state_bytes(self) -> int:
        """Host bytes of the full ``[G, P]`` population store."""
        return sum(
            self.packers[f].state_bytes((self.num_groups, self.population))
            for f in self.fields
        )

    def device_bytes(self, cohort: int) -> int:
        """Device bytes of the persistent fields at cohort size K."""
        return sum(
            self.packers[f].state_bytes((self.num_groups, cohort))
            for f in self.fields
        )

    def size_report(self, cohort: int | None = None) -> dict[str, Any]:
        """Segment-table size breakdown, host store vs device cohort."""
        report: dict[str, Any] = {
            "num_groups": self.num_groups,
            "population": self.population,
            "fields": {
                f: self.packers[f].size_report(
                    (self.num_groups, self.population))
                for f in self.fields
            },
            "host_bytes": self.state_bytes(),
        }
        if cohort is not None:
            report["cohort"] = int(cohort)
            report["device_bytes"] = self.device_bytes(cohort)
        return report

    def __repr__(self) -> str:
        return (f"PopulationStore(G={self.num_groups}, P={self.population}, "
                f"fields={self.fields}, bytes={self.state_bytes()})")


def _store_flatten_with_keys(store: PopulationStore):
    children = []
    for f in store.fields:
        for key in sorted(store.data[f]):
            path = jax.tree_util.DictKey(f"{f}.{key}")
            children.append((path, store.data[f][key]))
    aux = (store.fields, store.num_groups, store.population,
           tuple(sorted(store.packers.items())),
           tuple(sorted(store.flat.items())),
           tuple((f, tuple(sorted(store.data[f]))) for f in store.fields))
    return tuple(children), aux


def _store_flatten(store: PopulationStore):
    children, aux = _store_flatten_with_keys(store)
    return tuple(c for _, c in children), aux


def _store_unflatten(aux, children) -> PopulationStore:
    fields, num_groups, population, packers, flat, keys = aux
    it = iter(children)
    # np.asarray: restored leaves may arrive as device arrays; the store
    # must stay host numpy for in-place scatter.
    data = {f: {key: np.asarray(next(it)) for key in dtkeys}
            for f, dtkeys in keys}
    return PopulationStore(fields, num_groups, population, dict(packers),
                           dict(flat), data)


jax.tree_util.register_pytree_with_keys(
    PopulationStore, _store_flatten_with_keys, _store_unflatten,
    _store_flatten,
)


def stateless_round(round_fn: RoundFn,
                    fields: tuple[str, ...] = ("z", "dyn")) -> RoundFn:
    """Zero the persistent per-client fields at every round boundary.

    The stateless-client contract (``client_state="stateless"``): a cohort
    member arrives with no memory of earlier rounds, so ``z`` (and
    ``dyn``) start from zero each round and no population store is needed
    -- corrections act purely within-round. Fields the state lacks (or
    holds as None) pass through untouched. The wrapper is built once per
    engine so the driver's chunk-runner cache keys on a stable identity.
    """

    def wrapped(state, batches):
        resets = {
            f: tu.tree_zeros_like(getattr(state, f))
            for f in fields if getattr(state, f, None) is not None
        }
        return round_fn(state._replace(**resets), batches)

    return wrapped


def run_population_rounds(
    round_fn: RoundFn,
    state: PyTree,
    store: PopulationStore,
    data: PackedBatches,
    T: int,
    *,
    chunk: int | None = None,
    eval_every: int = 1,
    eval_fn: Callable[[PyTree, PyTree], PyTree] | None = None,
    donate: bool = True,
    overlap: bool = True,
) -> tuple[PyTree, PackedBatches, Horizon]:
    """``run_rounds`` over a virtual population: gather -> chunk -> scatter.

    Per driver chunk: draw a cohort of K (the state's materialized shape)
    from the store's P virtual clients per group, gather its persistent
    rows into the device state, dispatch the compiled chunk, scatter the
    updated rows back. A cohort is held fixed *within* a chunk (its rounds
    share one gather/scatter), so ``chunk`` trades cohort refresh rate
    against amortized transfer cost exactly as it already trades dispatch
    overhead.

    With ``overlap`` (default) the next cohort's draw + gather runs while
    the device scans the current chunk, and only the rows the consecutive
    cohorts share are re-read after the scatter -- the double-buffered
    path whose overhead ``benchmarks/bench_population.py`` gates under
    30% of round time. ``overlap=False`` is the strictly sequential
    baseline (bit-exact against the overlapped path; gated in
    tests/test_population.py).

    Degenerate ``P == K`` runs materialize everyone: no draws, rng
    untouched, bit-exact against ``run_rounds`` on the same round_fn.

    Returns ``(state, data, Horizon)`` with ``Horizon.population`` set to
    the store (mutated in place; returned for symmetry with ``data``).
    """
    assert T >= 1 and eval_every >= 1
    if chunk is not None and chunk < 0:
        raise ValueError(f"chunk must be None or >= 0, got {chunk}")
    chunk = T if not chunk else min(int(chunk), T)

    G, P = store.num_groups, store.population
    K = store.cohort_of(state)
    full = P == K
    rng = getattr(state, "rng", None)
    if not full and rng is None:
        raise ValueError(
            "virtual-population cohort draws need state.rng; initialize the "
            "state with an rng key")

    def draw() -> np.ndarray:
        nonlocal rng
        if full:
            return np.broadcast_to(np.arange(K), (G, K))
        ckey, rng = jax.random.split(rng)
        return draw_cohort(ckey, G, P, K)

    idx = draw()
    state = store.install(state, store.gather(idx))

    mets, evs, masks = [], [], []
    done = 0
    while done < T:
        n = min(chunk, T - done)
        mask = eval_mask_for_chunk(done, n, T, eval_every)
        state, data, metrics, ev = dispatch_chunk(
            round_fn, state, data, mask, eval_fn=eval_fn, donate=donate)
        done += n
        # The dispatch above is asynchronous: everything between here and
        # extract() runs on the host while the device scans the chunk.
        idx_next = staged_next = None
        if done < T:
            idx_next = draw()
            if overlap:
                staged_next = store.gather(idx_next)
        host_vals = store.extract(state)        # sync point
        store.scatter(idx, host_vals)
        if idx_next is not None:
            if overlap:
                store.refresh(staged_next, idx_next, idx)
            else:
                staged_next = store.gather(idx_next)
            state = store.install(state, staged_next)
            idx = idx_next
        mets.append(metrics)
        if eval_fn is not None:
            evs.append(ev)
        masks.append(mask)

    if not full:
        state = state._replace(rng=rng)

    def _cat(*xs):
        return np.concatenate([np.asarray(x) for x in xs])

    metrics = jax.tree.map(_cat, *mets)
    mask_all = np.concatenate(masks)
    eval_rounds = np.nonzero(mask_all)[0] + 1
    evals = None
    if eval_fn is not None:
        evals = jax.tree.map(lambda *xs: _cat(*xs)[mask_all], *evs)
    return state, data, Horizon(metrics, evals, eval_rounds, data, store)

"""The comparison that decides a run's ``correct``.

A run drives its first ``checked_calls`` ``fit`` calls through the same
compiled program as its window, and the plain reference (``reference.py``)
trains the same global rounds from the same weights on the same batches.
``readings`` works out every number of ``NUMBERS``; a cell compares those
that its limits file (``bench/limits/<workload>.json``) names, each against
its own limit, and prints the others:

* ``loss``: the widest relative gap between the program's and the
  reference's mean client loss, over every local step of the checked rounds;
* ``grad``: the change of the global model over the first call, the update
  the global model gets; by the worst leaf, the gap between the program's
  and the reference's norm of it;
* ``delta``: the change of the global model over all checked rounds, by the
  worst leaf in the same way;
* ``z``, ``y``: the corrections in the state after the first call, by the
  worst leaf; ``z_last``, ``y_last``: the same after the last call, when y
  has entered the local steps of the rounds after the first.

A leaf's gap is measured against the reference's norm of that leaf or of the
median leaf, whichever is larger. Leaves that the reference moves by less
than a thousandth of the median leaf (moved by round-off alone) are left out.
"""
from __future__ import annotations

import jax
import numpy as np

# The numbers a cell's limits may name (``bench/limits/<workload>.json``).
LEAF_NUMBERS = ("grad", "delta", "z", "y", "z_last", "y_last")
NUMBERS = ("loss", "loss_first") + tuple(
    n + sfx for n in LEAF_NUMBERS for sfx in ("", "_median"))
# A leaf the reference moves by less than this share of the median leaf's
# move is left out of the norm comparisons.
STILL_LEAF = 1e-3


def leaf_norms(tree, base=None) -> dict[str, float]:
    """Norm of every leaf of ``tree - base`` (of ``tree`` without a base),
    keyed by the leaf's path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    bases = [None] * len(flat) if base is None else jax.tree.leaves(base)
    out = {}
    for (path, leaf), b in zip(flat, bases):
        a = np.asarray(leaf, np.float64)
        out[jax.tree_util.keystr(path)] = float(
            np.linalg.norm(a if b is None else a - np.asarray(b, np.float64)))
    return out


def norm_gaps(program: dict, ref: dict) -> tuple[list[str], np.ndarray,
                                                  np.ndarray]:
    """Per leaf: the gap between the program's and the reference's norm,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger.

    Returns ``(names, gaps, kept)``; ``kept`` is False for leaves that the
    reference moves by round-off alone, whose gap reads 0.
    """
    names = list(ref)
    pn = np.array([program[n] for n in names])
    rn = np.array([ref[n] for n in names])
    med = float(np.median(rn))
    kept = rn >= STILL_LEAF * med
    gaps = np.where(kept, np.abs(pn - rn) / np.maximum(rn, med), 0.0)
    return names, np.where(np.isnan(gaps), np.inf, gaps), kept


def loss_gaps(program: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Relative gap of every local step's mean loss, ``[R * E * H]``."""
    program = np.asarray(program, np.float64).reshape(ref.shape)
    gaps = np.abs(program - ref) / np.abs(ref)
    return np.where(np.isfinite(gaps), gaps, np.inf).ravel()


def readings(x0, program, ref) -> dict:
    """Every number the comparison can hold a ``program`` readout to,
    against the reference's readout, with the detail behind them. A readout
    has ``losses``, ``first``, ``last``, ``corrections`` and
    ``corrections_last`` (``run.Readout``).

    ``loss``: the widest per-step loss gap; ``loss_first``: the first local
    step's (the forward pass at the initial weights). By the worst leaf
    (``<name>``) and by the median leaf (``<name>_median``): ``grad``, the
    change of the global model over the first call; ``delta``, its change
    over all checked calls; ``z`` and ``y``, the corrections in the state
    after the first call; ``z_last`` and ``y_last``, after the last.
    """
    steps = loss_gaps(program.losses, ref.losses)
    out = {"loss": float(np.max(steps)), "loss_first": float(steps[0]),
           "loss_steps": steps.tolist()}
    for key, p, r in (
            ("grad", leaf_norms(program.first, x0), leaf_norms(ref.first, x0)),
            ("delta", leaf_norms(program.last, x0), leaf_norms(ref.last, x0)),
            ("z", program.corrections["z"], ref.corrections["z"]),
            ("y", program.corrections["y"], ref.corrections["y"]),
            ("z_last", program.corrections_last["z"],
             ref.corrections_last["z"]),
            ("y_last", program.corrections_last["y"],
             ref.corrections_last["y"])):
        names, gaps, kept = norm_gaps(p, r)
        i = int(np.argmax(gaps))
        out[key] = float(gaps[i])
        out[f"{key}_median"] = float(np.median(gaps[kept]))
        out[f"{key}_leaf"] = names[i]
        out[f"{key}_leaves"] = dict(zip(names, gaps.tolist()))
        out[f"{key}_still"] = int(np.sum(~kept))
    return out


def judge(read: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the numbers that
    ``limits`` names: correct when each is finite and at most its limit."""
    checks = {n: {"value": read[n], "limit": limits[n]} for n in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks

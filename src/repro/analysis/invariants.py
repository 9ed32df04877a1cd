"""Structural invariants of the lowered driver chunk, checked statically.

Every check here consumes a :class:`repro.core.driver.LoweredChunk` --
the AOT trace of ``run_chunk(state, data, eval_mask)`` over abstract
inputs -- and returns :class:`Finding`s instead of raising, so the audit
CLI can report the whole matrix in one run:

* **donation** -- every leaf of the donated state argument must appear in
  the compiled executable's ``input_output_alias`` table. The state is
  argument 0 of ``run_chunk``, so its flattened leaves are exactly
  parameters ``0 .. n_leaves-1`` of the entry computation; a leaf missing
  from the alias table means XLA kept an extra parameter-sized copy live
  across the chunk (the regression the PR 3 donation win guards against).
* **host-sync** -- no host callback / infeed / outfeed primitive inside a
  ``while``/``scan``/``cond`` body: one host round-trip per scanned round
  serializes the whole async dispatch pipeline.
* **f64** -- no double-precision anywhere in the optimized HLO. jax
  disables x64 by default, but a stray numpy scalar in a weak-typed
  position can still promote through, doubling state bytes silently.
* **correction dtype** -- ``spec.correction_dtype`` honored end-to-end:
  the correction leaves (``z``/``y``) of both the abstract *input* state
  and the traced *output* state carry the narrow dtype, so a cast back to
  f32 anywhere in the round cannot round-trip unnoticed.
* **fusion contract** -- a fused spec lowers to exactly the expected
  ``pallas_call`` count in the jaxpr (one per correction buffer); an
  unfused spec lowers to exactly zero.
"""
from __future__ import annotations

import dataclasses
import re

import jax

# Primitives that force a device->host->device round trip when they
# appear inside a compiled loop body.
HOST_SYNC_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed",
})

# jax prints the alias table on the HloModule header line:
#   input_output_alias={ {0}: (0, {}, may-alias), ... }, entry_computation...
# Entries nest braces ({output_index}: (param, {tuple_index}, kind)), so the
# table is extracted by brace matching, not regex.
_ALIAS_PARAM_RE = re.compile(r"\(\s*(\d+)\s*,")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One audit violation (or skip note, when ``severity == "note"``)."""

    case: str
    check: str
    message: str
    severity: str = "error"

    def __str__(self) -> str:
        return f"[{self.case}] {self.check}: {self.message}"


def iter_jaxprs(jaxpr, _inside_loop: bool = False):
    """Yield ``(eqn, inside_loop)`` over a jaxpr and all sub-jaxprs.

    ``inside_loop`` is True once the walk has descended through a
    ``while``/``scan``/``cond`` body (anything re-executed or branch-
    selected at runtime).
    """
    for eqn in jaxpr.eqns:
        yield eqn, _inside_loop
        inside = _inside_loop or eqn.primitive.name in (
            "while", "scan", "cond")
        for sub in _sub_jaxprs(eqn):
            yield from iter_jaxprs(sub, inside)


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for item in (val if isinstance(val, (list, tuple)) else (val,)):
            inner = getattr(item, "jaxpr", None)
            if inner is not None:
                yield inner
            elif hasattr(item, "eqns"):
                yield item


def count_primitive(jaxpr, name: str) -> int:
    return sum(1 for eqn, _ in iter_jaxprs(jaxpr)
               if eqn.primitive.name == name)


def aliased_parameters(hlo: str) -> set[int]:
    """Entry-parameter numbers appearing in the input_output_alias table."""
    for line in hlo.splitlines():
        start = line.find("input_output_alias={")
        if start < 0:
            continue
        i = line.index("{", start)
        depth = 0
        for j in range(i, len(line)):
            depth += line[j] == "{"
            depth -= line[j] == "}"
            if depth == 0:
                body = line[i + 1: j]
                return {int(p) for p in _ALIAS_PARAM_RE.findall(body)}
    return set()


def check_donation(case: str, lc) -> list[Finding]:
    """Every donated state leaf must alias an output buffer."""
    if not lc.donate:
        return [Finding(case, "donation",
                        "runner traced with donate=False", "note")]
    n_state = len(jax.tree.leaves(lc.state))
    aliased = aliased_parameters(lc.hlo)
    missing = sorted(set(range(n_state)) - aliased)
    if not missing:
        return []
    leaves = jax.tree.leaves(lc.state)
    descr = ", ".join(
        f"param {i} ({leaves[i].dtype}{list(leaves[i].shape)})"
        for i in missing)
    return [Finding(case, "donation",
                    f"{len(missing)}/{n_state} donated state leaves have no "
                    f"input-output alias: {descr}")]


def check_host_sync(case: str, lc) -> list[Finding]:
    """No callback/infeed/outfeed primitive inside a loop body."""
    out = []
    for eqn, inside in iter_jaxprs(lc.jaxpr):
        if inside and eqn.primitive.name in HOST_SYNC_PRIMITIVES:
            out.append(Finding(
                case, "host-sync",
                f"{eqn.primitive.name} inside a compiled loop body "
                "(one host round-trip per scanned round)"))
    return out


def check_no_f64(case: str, lc) -> list[Finding]:
    """No f64/c128 in the optimized HLO (jaxpr checked too, for location)."""
    out = []
    for eqn, _ in iter_jaxprs(lc.jaxpr):
        for var in eqn.outvars:
            dt = getattr(getattr(var, "aval", None), "dtype", None)
            if dt is not None and str(dt) in ("float64", "complex128"):
                out.append(Finding(
                    case, "f64",
                    f"primitive {eqn.primitive.name} produces {dt}"))
    hits = len(re.findall(r"\bf64\[", lc.hlo))
    hits += len(re.findall(r"\bc128\[", lc.hlo))
    if hits and not out:
        out.append(Finding(case, "f64",
                           f"{hits} f64/c128 shapes in optimized HLO"))
    return out


def _correction_leaves(state) -> list:
    picked = [getattr(state, f) for f in ("z", "y")
              if getattr(state, f, None) is not None]
    return jax.tree.leaves(picked)


def check_correction_dtype(case: str, lc, spec) -> list[Finding]:
    """z/y leaves carry ``spec.correction_dtype`` on the way in AND out."""
    want = spec.correction_dtype
    if want is None:
        return []
    out = []
    for side, state in (("input", lc.state), ("output", lc.out_state)):
        leaves = _correction_leaves(state)
        if not leaves:
            out.append(Finding(case, "correction-dtype",
                               f"{side} state has no z/y leaves to check"))
            continue
        bad = sorted({str(x.dtype) for x in leaves if str(x.dtype) != want})
        if bad:
            out.append(Finding(
                case, "correction-dtype",
                f"{side} state z/y leaves are {bad}, spec says {want!r}"))
    return out


def check_fusion(case: str, lc, expected: int) -> list[Finding]:
    """Exactly ``expected`` pallas_call sites in the traced jaxpr."""
    got = count_primitive(lc.jaxpr, "pallas_call")
    if got == expected:
        return []
    kind = "fused" if expected else "unfused"
    return [Finding(case, "fusion",
                    f"{kind} spec lowered to {got} pallas_call sites, "
                    f"expected {expected}")]


def check_retrace(case: str, engine, state, data, chunk: int = 2) -> list[Finding]:
    """Tracing the chunk runner twice over identical abstract shapes must
    hit the jit tracing cache the second time -- a miss means something in
    the round closure defeats caching (unhashable static arg, fresh
    closure identity per call) and every driver chunk would re-trace.
    """
    from jax._src import test_util as jtu

    engine.lower_chunk(data, state=state, chunk=chunk, compile=False)  # warm
    with jtu.count_jit_tracing_cache_miss() as misses:
        engine.lower_chunk(data, state=state, chunk=chunk, compile=False)
    n = misses()
    if n == 0:
        return []
    return [Finding(case, "retrace",
                    f"identical abstract re-trace missed the jit tracing "
                    f"cache {n} times (expected 0)")]


def run_invariants(case, lc) -> list[Finding]:
    """All per-program invariant checks for one audited case."""
    out = []
    out += check_donation(case.name, lc)
    out += check_host_sync(case.name, lc)
    out += check_no_f64(case.name, lc)
    out += check_correction_dtype(case.name, lc, case.spec)
    out += check_fusion(case.name, lc, case.fused_leaves)
    return out

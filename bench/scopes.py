"""Split a traced window by the program's own names: device time by named
scope, host time by the program's spans.

    python3 -m bench.scopes TRACE.xplane.pb[.gz] [--rounds N] [--chips C]

The program names the layers of an MTGC round with ``jax.named_scope``
(``repro.launch.hlo_analysis.BUCKETS``: ``client_step``, ``local_update``,
``state_repack``, ``group_agg``, ``global_agg``) and writes host spans on
the profiler's clock (``repro.fit``, ``repro.dispatch``, ``repro.fetch``).
The window, the ops counted, their times and their ``tf_op`` paths are
``trace.py``'s. Here an op belongs to the innermost declared scope on its
path, so the scopes and the remainder add up to its op seconds. The
result, per round of the window (by default one a ``bench.fit`` span, as
the benchmark's cells run ``fit`` one round at a time), is one JSON
object:

- ``scope_ms``: device ms of each scope that some op falls under;
- ``unscoped_ms`` and ``unscoped_top``: device ms under no scope, and its
  five largest ``tf_op`` paths;
- ``driver_host_ms``: host ms inside ``repro.fit`` spans not covered by
  ``repro.fetch`` (the host work of ``fit`` that the device waits for);
- ``idle_gaps``: the longest idle gaps of chip 0, each named by the
  innermost host span, the benchmark's or the program's, open at its
  middle.

The benchmark's per-layer metrics read the same names through
``trace.Reduced.scope_seconds`` (an op counts for every scope on its path)
and ``span_self_s``; this tool is for looking at a whole window.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import re
import sys
from pathlib import Path

from bench import trace as tr

FIT_SPAN = "repro.fit"
FETCH_SPAN = "repro.fetch"


def scope_of(tf_op: str, names) -> str | None:
    """The innermost of ``names`` on the name-stack path ``tf_op``, or None.

    A name matches as a whole word inside a path component: JAX writes a
    scope entered under a transform as ``vmap(vmap(client_step))`` or
    ``transpose(jvp(client_step))``. Of two paths joined by ``;`` the first
    is read.
    """
    path = tf_op.split(";", 1)[0]
    for component in reversed(path.split("/")):
        words = [w for w in re.findall(r"[\w.\-]+", component) if w in names]
        if words:
            return words[-1]
    return None


def declared_scopes() -> tuple[str, ...]:
    """The scope names the program's ``jax.named_scope``s use."""
    from repro.launch.hlo_analysis import BUCKETS

    return tuple(BUCKETS)


@dataclasses.dataclass
class Split:
    """One traced window split by scope and by host span."""

    reduced: tr.Reduced   # trace.py's window and ops; spans of both kinds

    def path_seconds(self) -> dict[str, float]:
        """Device seconds of the ops by ``tf_op`` path, averaged over the
        chips."""
        tot = collections.Counter()
        for ops in self.reduced.ops.values():
            for op in ops:
                tot[op.path] += (op.end_ns - op.start_ns) * 1e-9
        return {k: v / max(len(self.reduced.ops), 1) for k, v in tot.items()}

    def scope_seconds(self, names) -> dict[str | None, float]:
        """Device seconds under each of ``names`` (an op counts for its
        innermost scope only) and, under ``None``, under none of them."""
        tot = collections.Counter()
        for path, sec in self.path_seconds().items():
            tot[scope_of(path, names)] += sec
        return dict(tot)

    def driver_host_s(self) -> float | None:
        """Host seconds in the window's ``repro.fit`` spans outside their
        ``repro.fetch`` spans; None where the window holds no such span."""
        return self.reduced.span_self_s(FIT_SPAN, FETCH_SPAN)


def split_file(path: str | Path, chips: int) -> Split:
    """Read one ``.xplane.pb`` (or ``.xplane.pb.gz``): ``trace.py``'s
    reduction, with the host spans of both kinds in ``spans`` so that an
    idle gap is named by the innermost span of either."""
    red = tr.reduce_file(path, chips)
    return Split(dataclasses.replace(red, spans=red.spans + red.program_spans))


def summary(split: Split, rounds: int | None = None, top: int = 5) -> dict:
    """The per-round split of the window (see the module's docstring)."""
    red = split.reduced
    lo, hi = red.window
    if rounds is None:
        rounds = sum(1 for n, s, e in red.spans
                     if n == tr.FIT_SPAN and lo <= s and e <= hi)
    if rounds <= 0:
        raise ValueError("a window of no rounds has no split per round")
    names = declared_scopes()
    scoped = split.scope_seconds(names)
    rest = scoped.pop(None, 0.0)
    paths = sorted(((p, sec) for p, sec in split.path_seconds().items()
                    if scope_of(p, names) is None), key=lambda kv: -kv[1])
    host = split.driver_host_s()
    return {
        "rounds": rounds,
        "window_ms": 1e3 * red.window_s / rounds,
        "busy_ms": 1e3 * red.busy_s / rounds,
        "scope_ms": {k: 1e3 * v / rounds for k, v in sorted(scoped.items())},
        "unscoped_ms": 1e3 * rest / rounds,
        "unscoped_top": [[p, 1e3 * s / rounds] for p, s in paths[:top]],
        "driver_host_ms": None if host is None else 1e3 * host / rounds,
        "idle_gaps": tr.breakdown(red)["idle_gaps"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    print(json.dumps(summary(split_file(args.trace, args.chips), args.rounds)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

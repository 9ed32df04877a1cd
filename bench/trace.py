"""From a profiler trace of the measured window to busy time, op times and gaps.

The run wraps its window in host spans of its own (``WINDOW_SPAN`` around
the whole window, ``FIT_SPAN`` around each ``fit`` call, ``SYNC_SPAN``
around the final wait for the device), and ``jax.profiler`` writes them,
with the device's operations, into one ``.xplane.pb`` on one clock.

On a TPU, each chip is a plane ``/device:TPU:<n>``. Its line ``XLA Ops``
holds one event per executed HLO instruction, named by the instruction's
text (``%fusion.12 = f32[...] fusion(...), kind=kLoop, ...``). Control-flow
instructions (``while``, ``conditional``, ``call``) are events too and span
the instructions they run, so they are left out here: busy time is the
union of the other instructions' intervals, and an instruction's time is
its own. The line ``Async XLA Ops`` (DMAs in flight beside the compute) is
not read.

``ProfileData`` gives each op's time but not its name-stack path, which the
op's metadata holds as ``tf_op`` (``jit(run_chunk)/while/body/client_step/
vmap(vmap(jvp()))/mul:``); ``xspace.py`` reads that from the same file, so
a per-layer metric can take the device time under any ``jax.named_scope``
of the program (``Reduced.scope_seconds``). The program's own host spans
(``repro.*``) are kept too, apart from the run's.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
FIT_SPAN = "bench.fit"
SYNC_SPAN = "bench.sync"
RUN_PREFIX = "bench."        # the run's own host spans
PROGRAM_PREFIX = "repro."    # the program's host spans

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = frozenset({"while", "conditional", "call"})


def opcode(text: str) -> str:
    """The opcode of an ``XLA Ops`` event's instruction text.

    ``%copy.3 = f32[8]{0} copy(...)`` gives ``copy``; a tuple-shaped
    result (``= (f32[...], ...) while(...)``) is skipped whole. Text that
    is not an instruction is its own opcode.
    """
    _, sep, rest = text.partition(" = ")
    if not sep:
        return text
    rest = rest.lstrip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.lstrip().split("(", 1)[0].strip()


def instruction(text: str) -> str:
    """The instruction's name without its ``%`` and its number:
    ``%fusion.12 = ...`` gives ``fusion``."""
    name = text.partition(" = ")[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def on_path(tf_op: str, name: str) -> bool:
    """Whether scope ``name`` lies anywhere on the name-stack path ``tf_op``.

    A name matches as a whole word inside a path component: JAX writes a
    scope entered under a transform as ``vmap(vmap(client_step))`` or
    ``transpose(jvp(client_step))``; ``my.client_step`` and
    ``client_steps`` are other names. Of two paths joined by ``;`` the
    first is read.
    """
    path = tf_op.split(";", 1)[0]
    return re.search(r"(?<![\w.\-])" + re.escape(name) + r"(?![\w.\-])",
                     path) is not None


@dataclasses.dataclass
class Op:
    name: str        # instruction text
    start_ns: float
    end_ns: float
    path: str = ""   # the op's name-stack path (``tf_op``)


@dataclasses.dataclass
class Reduced:
    """One traced window, on the trace's own clock (nanoseconds)."""

    window: tuple[float, float]
    ops: dict[int, list[Op]]                # chip -> its ops in the window
    spans: list[tuple[str, float, float]]   # the run's own host spans
    program_spans: list[tuple[str, float, float]] = dataclasses.field(
        default_factory=list)               # the program's host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        lo, hi = self.window
        per_chip = [busy_ns([(o.start_ns, o.end_ns) for o in ops], lo, hi)
                    for ops in self.ops.values()]
        return sum(per_chip) / len(per_chip) * 1e-9 if per_chip else 0.0

    def op_seconds(self) -> dict[str, float]:
        """Each instruction's device seconds in the window, averaged over
        the chips."""
        tot = collections.Counter()
        for ops in self.ops.values():
            for o in ops:
                tot[o.name] += (o.end_ns - o.start_ns) * 1e-9
        return {k: v / max(len(self.ops), 1) for k, v in tot.items()}

    def scope_seconds(self, name: str) -> float | None:
        """Device seconds of the ops that have scope ``name`` anywhere on
        their path, averaged over the chips; None where no op has it.

        Anywhere, not innermost: a scope that a later change nests inside
        this one leaves its reading as it was."""
        tot, found = 0.0, False
        for ops in self.ops.values():
            for o in ops:
                if on_path(o.path, name):
                    tot += (o.end_ns - o.start_ns) * 1e-9
                    found = True
        return tot / max(len(self.ops), 1) if found else None

    def span_self_s(self, outer: str, inner: str) -> float | None:
        """Host seconds of the window's ``outer`` program spans that no
        ``inner`` program span covers; None where the window holds no
        ``outer`` span."""
        lo, hi = self.window
        spans = self.program_spans
        outs = [(s, e) for n, s, e in spans if n == outer and lo <= s and e <= hi]
        if not outs:
            return None
        ins = [(s, e) for n, s, e in spans if n == inner]
        return 1e-9 * sum((e - s) - busy_ns(ins, s, e) for s, e in outs)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]``."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merge(intervals))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, cur = [], lo
    for s, e in merge(intervals):
        if e <= lo or s >= hi:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def load_profile(path: str | Path):
    """A ``jax.profiler.ProfileData`` from an ``.xplane.pb`` file, or from
    one compressed with gzip (``.gz``)."""
    import gzip

    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def op_paths(path: str | Path) -> dict[str, list[tuple[str, str]]]:
    """``(instruction text, tf_op)`` of every ``XLA Ops`` event of each
    device plane, in the file's order (the order ``ProfileData`` gives)."""
    from bench import xspace

    out = {}
    for plane in xspace.read_planes(path, DEVICE_PLANE.match, [OPS_LINE]):
        out[plane.name] = [
            (plane.metadata[ev.metadata_id][0],
             str(plane.metadata[ev.metadata_id][1].get("tf_op", "")))
            for line in plane.lines for ev in line.events]
    return out


def reduce_file(path: str | Path, chips: int) -> Reduced:
    """Read one ``.xplane.pb`` (or ``.xplane.pb.gz``) into a
    :class:`Reduced` window."""
    named = op_paths(path)
    pd = load_profile(path)
    spans, program, ops = [], [], collections.defaultdict(list)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                events = list(line.events)
                if [n for n, _ in named[plane.name]] != [e.name for e in events]:
                    raise ValueError(f"{path}: {plane.name}'s ops read "
                                     "differently by the two readers")
                for ev, (_, tf_op) in zip(events, named[plane.name]):
                    if opcode(ev.name) not in CONTAINERS:
                        ops[int(m.group(1))].append(
                            Op(ev.name, ev.start_ns,
                               ev.start_ns + ev.duration_ns, tf_op))
            elif plane.name == "/host:CPU":
                for ev in line.events:
                    span = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name.startswith(RUN_PREFIX):
                        spans.append(span)
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        program.append(span)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} {WINDOW_SPAN!r} spans")
    lo, hi = windows[0]
    chip_ops = {c: [o for o in ops.get(c, []) if o.end_ns > lo and o.start_ns < hi]
                for c in range(chips)}
    return Reduced((lo, hi), chip_ops, spans, program)


def reduce_dir(trace_dir: str | Path, chips: int) -> Reduced:
    """Reduce the one trace that ``jax.profiler`` wrote under ``trace_dir``."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"{trace_dir}: {len(files)} .xplane.pb files")
    return reduce_file(files[0], chips)


def host_activity(red: Reduced, t: float) -> str:
    """The innermost run span open at time ``t`` (``none`` outside all)."""
    best, width = "none", float("inf")
    for name, s, e in red.spans:
        if s <= t < e and e - s < width:
            best, width = name, e - s
    return best


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device instructions that took most time, and the longest idle
    gaps of chip 0 with what the host was doing at their middle."""
    ops = sorted(red.op_seconds().items(), key=lambda kv: -kv[1])[:top]
    chip = red.ops[min(red.ops)] if red.ops else []
    idle = gaps([(o.start_ns, o.end_ns) for o in chip], *red.window)
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[_short(n), s] for n, s in ops],
        "idle_gaps": [[host_activity(red, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in idle[:top]],
    }


def _short(text: str, width: int = 160) -> str:
    """An instruction's text, cut to ``width`` characters."""
    return text if len(text) <= width else text[:width - 3] + "..."

"""Run one cell of the chip benchmark and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell (``BENCHMARK.json``'s ``workloads``)
names a configuration and a traffic mix; the run

1. checks for the chips the cell asks for (no TPU, or too few: exit 3, no
   result);
2. sets up: makes the data from the seed (``feed.py``), the weights on the
   device in one jitted call (the configuration's ``reference.py``), builds
   the engine through ``repro.api.build`` and packs the data with the
   engine's ``pack_arrays`` (classification) or ``pack_tokens`` (a
   configuration whose ``task`` is ``causal_lm``);
3. drives the engine's first ``checked_calls`` ``fit`` calls, the first of
   which compiles, and keeps what the comparison needs from them;
4. measures: repeats ``fit`` calls of ``rounds_per_call`` rounds, each
   continuing from the last one's state and ``horizon.data``, until
   ``--seconds`` have passed, then waits for the device. ``--trace 1``
   records a profiler trace of this window;
5. reads the peak of device memory (buffers in use and the scratch the
   compiled programs reserve) on the fullest of the cell's chips, frees the
   program's state, trains the checked rounds again with the plain
   reference and compares (``check.py``).

The last line of standard output is one JSON object; the numbers compared,
each beside its limit, are the last lines of standard error and the last
key of that object.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, NamedTuple  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str):
    """Import a file by its path (names may hold ``-`` and ``.``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _modname(kind: str, name: str) -> str:
    return f"bench_{kind}_" + "".join(c if c.isalnum() else "_" for c in name)


class Cell:
    """One workload with everything the harness found for it by name."""

    def __init__(self, workload: dict, config: dict, model, ref,
                 traffic: dict, limits: dict, per_layer: list):
        self.workload = workload
        self.config = config
        self.model = model            # the program's model and FLOP count
        self.ref = ref                # the plain reference and weights
        self.traffic = traffic
        self.limits = limits
        self.per_layer = per_layer    # [(name, unit, reader module)]

    @property
    def causal_lm(self) -> bool:
        """Whether the configuration is a causal LM trained on token
        streams (``"task": "causal_lm"``); else it classifies rows."""
        return self.config.get("task") == "causal_lm"

    @property
    def microbatches(self) -> int:
        """A, the microbatches of a local step: the sharded backend's
        ``schedule.microbatches`` (1 where unset), 1 elsewhere."""
        spec = self.traffic["spec"]
        if spec.get("backend", "simulator") != "sharded":
            return 1
        return spec["schedule"].get("microbatches") or 1


def load_cell(name: str, benchmark: dict | None = None,
              root: Path = ROOT) -> Cell:
    """Resolve workload ``name`` of ``BENCHMARK.json`` to its files, in the
    checkout at ``root``."""
    bench = root / "bench"
    if benchmark is None:
        benchmark = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in benchmark["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in benchmark["configs"]}[wl["config"]]
    cfg_file = root / cfg_entry["file"]
    cfg_dir = cfg_file.parent
    per_layer = [
        (m["name"], m["unit"],
         load_module(bench / "metrics" / f"{m['name']}.py",
                     _modname("metric", m["name"])))
        for m in benchmark["per_layer"]
        if name in m.get("workloads", [name])]
    return Cell(
        wl, json.loads(cfg_file.read_text()),
        load_module(cfg_dir / "model.py", _modname("model", wl["config"])),
        load_module(cfg_dir / "reference.py", _modname("ref", wl["config"])),
        json.loads((bench / "traffic" / f"{wl['traffic']}.json").read_text()),
        json.loads((bench / "limits" / f"{name}.json").read_text()),
        per_layer)


def check_devices(chips: int):
    """The TPU devices of this run; raises :class:`NoAccelerator` naming
    what JAX found instead."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found platform {platform!r} "
                            f"with {len(devices)} device(s)")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)} {devices[0].device_kind!r}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache in ``CACHE_DIR``, inside this
    checkout, keeping every program, so that a run after the first compiles
    nothing.

    Never a directory that another checkout shares, so it overrides
    ``JAX_COMPILATION_CACHE_DIR``: JAX's cache key leaves out op metadata,
    so a shared cache can hand this checkout another's executable with the
    other's scope names, which the per-layer metrics read."""
    import jax

    path = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts the executables JAX builds (compiled or loaded from the
    persistent cache) while it is on."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == self.EVENT:
            self.count += 1


def build_engine(cell: Cell):
    """The engine of the cell's configuration and traffic, through
    ``repro.api.build``; no implementation knob is pinned."""
    import jax

    from repro.api import ExperimentSpec, RoundSchedule, build

    cfg, spec = cell.config, dict(cell.traffic["spec"])
    schedule = RoundSchedule(**spec.pop("schedule"))
    loss_fn, program_init = cell.model.program_loss(cfg)
    # The program's model and the reference's weights must have one layout.
    ours = jax.eval_shape(functools.partial(cell.ref.init_weights, cfg),
                          jax.random.PRNGKey(0))
    theirs = jax.eval_shape(program_init, jax.random.PRNGKey(0))
    if jax.tree.structure(ours) != jax.tree.structure(theirs) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(ours),
                                               jax.tree.leaves(theirs))):
        raise ValueError(f"{cfg['name']}: the program's model does not take "
                         "the reference's weight layout")
    spec = ExperimentSpec(levels=tuple(cfg["levels"]), schedule=schedule,
                          **spec)
    return build(spec, loss_fn)


def rounds_flops(cell: Cell) -> float:
    """Model FLOPs of one global round: forward + backward (3x the forward)
    of every sample of every active client's local steps. A sample is a
    row; for a causal LM, one sequence of the traffic's ``seq_len``."""
    G, K = cell.config["levels"]
    sched = cell.traffic["spec"]["schedule"]
    samples = G * K * sched["group_rounds"] * sched["local_steps"] \
        * cell.microbatches * cell.traffic["batch"]
    if cell.causal_lm:
        per_sample = cell.model.forward_flops(
            cell.config, seq_len=cell.traffic["seq_len"])
    else:
        per_sample = cell.model.forward_flops(cell.config)
    return 3.0 * per_sample * samples


class TracedRun:
    """What a per-layer metric's reader gets: the reduced trace and the
    counts of the traced window."""

    def __init__(self, trace, rounds: int, flops_per_round: float,
                 peaks: dict, chips: int):
        self.trace = trace
        self.rounds = rounds
        self.flops_per_round = flops_per_round
        self.peaks = peaks
        self.chips = chips


class Readout(NamedTuple):
    """What the comparison reads of a run of the checked rounds."""

    losses: Any   # [R, E, H] mean client loss of every local step
    first: Any    # the global model after the first checked call
    last: Any     # the global model after the last checked call
    corrections: dict       # {"z"|"y": {leaf: norm}} after the first call
    corrections_last: dict  # the same after the last call


def set_up(cell: Cell, seed: int, phases: dict | None = None):
    """Data, engine, packed data and state of one run, from the seed.

    Returns ``(fed, engine, data, state, x0)``; ``x0`` is a host copy of
    the initial weights. ``phases`` gets the seconds of each part."""
    import jax

    from bench import feed

    phases = {} if phases is None else phases
    cfg, traffic = cell.config, cell.traffic
    t = time.perf_counter()
    fed = feed.make_federation(cfg, traffic, seed)
    phases["data"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = build_engine(cell)
    params = jax.jit(functools.partial(cell.ref.init_weights, cfg))(
        feed.jax_key(seed, "weights"))
    phases["engine_weights"] = time.perf_counter() - t
    t = time.perf_counter()
    if cell.causal_lm:
        data = engine.pack_tokens(
            fed.streams, batch_size=traffic["batch"],
            seq_len=traffic["seq_len"], shards=traffic["shards"],
            rng=feed.pack_rng(seed), key=feed.jax_key(seed, "select"))
    else:
        data = engine.pack_arrays(
            {"x": fed.x, "y": fed.y}, fed.indices,
            batch_size=traffic["batch"], shards=traffic["shards"],
            rng=feed.pack_rng(seed), key=feed.jax_key(seed, "select"))
    state = engine.init(params)
    x0 = jax.device_get(params)
    phases["pack_init"] = time.perf_counter() - t
    return fed, engine, data, state, x0


def drive_checked(cell: Cell, engine, data, state):
    """The first ``checked_calls`` ``fit`` calls, as the window makes them
    (the first compiles). Returns ``(state, data, Readout)``."""
    import jax
    import numpy as np

    from repro.api import fit

    rpc = cell.traffic["rounds_per_call"]
    losses = []
    for i in range(cell.traffic["checked_calls"]):
        state, hz = fit(engine, data, rpc, state=state)
        data = hz.data
        losses.append(np.asarray(hz.metrics.loss))
        if i == 0:
            first = jax.device_get(engine.global_model(state))
            corrections = correction_norms(state)
    last = jax.device_get(engine.global_model(state))
    return state, data, Readout(np.concatenate(losses), first, last,
                                corrections, correction_norms(state))


def chip_peak_bytes(stats: dict) -> int | None:
    """The most memory one device held: the peak of the buffers in use plus
    the peak that the compiled programs reserved for their scratch (on a
    TPU the temporaries of a program are reserved apart from the buffers in
    use). None where the device reports neither (the CPU)."""
    if "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def device_peak_bytes(stats: list[dict]) -> int | None:
    """The most memory the run held on its fullest device, from each
    device's ``memory_stats()``; None where no device reports it."""
    peaks = [p for p in map(chip_peak_bytes, stats) if p is not None]
    return max(peaks, default=None)


def correction_norms(state) -> dict:
    """Norm of every leaf of the state's corrections z and y, in one jitted
    call, so that no tree view of the flat state is made on the device
    (it would add to the peak the run reports)."""
    import jax

    @jax.jit
    def norms(tree):
        tree = tree.to_tree() if hasattr(tree, "to_tree") else tree
        return jax.tree.map(jax.numpy.linalg.norm, tree)

    out = {}
    for name in ("z", "y"):
        flat, _ = jax.tree_util.tree_flatten_with_path(
            norms(getattr(state, name)))
        out[name] = {jax.tree_util.keystr(path): float(v) for path, v in flat}
    return out


def reference_readout(cell: Cell, seed: int, fed, x0, *, dtype=None,
                      precision: str | None = None,
                      batch_fraction: float = 1.0,
                      drop_y: bool = False) -> Readout:
    """The plain reference over the checked rounds, on the run's batches,
    in the configuration's precision (``dtype``, ``matmul_precision``)
    unless ``dtype`` or ``precision`` says otherwise."""
    from bench import feed, reference

    cfg, traffic = cell.config, cell.traffic
    G, K = cfg["levels"]
    sched = traffic["spec"]["schedule"]
    E, H, A = sched["group_rounds"], sched["local_steps"], cell.microbatches
    rpc = traffic["rounds_per_call"]
    rounds = traffic["checked_calls"] * rpc
    arrays, rows = feed.packed_slots(fed, traffic, H * A, feed.pack_rng(seed))
    sids = feed.round_shards(feed.jax_key(seed, "select"), rounds, E, G, K,
                             traffic["shards"])
    batches = [feed.round_batches(arrays, rows, sids[r], A)
               for r in range(rounds)]
    if cell.causal_lm:
        loss = functools.partial(cell.ref.loss, cfg)
    else:
        loss = functools.partial(reference.classification_loss,
                                 functools.partial(cell.ref.forward, cfg))
    losses, models, corrections = reference.run_rounds(
        loss, x0, batches, levels=(G, K), lr=traffic["spec"]["lr"],
        group_rounds=E, local_steps=H, per_client=cell.causal_lm,
        dtype=dtype or cfg["dtype"],
        precision=precision or cfg["matmul_precision"],
        batch_fraction=batch_fraction, drop_y=drop_y)
    return Readout(losses, models[rpc - 1], models[-1],
                   corrections[rpc - 1], corrections[-1])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t0: float = _T0, require_tpu: bool = True,
             trace_dir: Path = TRACE_DIR) -> tuple[dict, list]:
    """One run of ``cell``: returns ``(result, check_lines)``. A traced run
    writes its trace under ``trace_dir`` and deletes it once read."""
    import jax
    import numpy as np

    from bench import check, reference
    from bench import trace as tr
    from bench.peaks import peaks_for
    from repro.api import fit

    chips = cell.workload["chips"]
    devices = check_devices(chips) if require_tpu else jax.devices()[:chips]
    dev = devices[0]
    if not reference.supports(cell.traffic["spec"]):
        raise ValueError(f"the plain reference does not implement traffic "
                         f"{cell.workload['traffic']!r}")
    counter = CompileCounter()
    rpc = cell.traffic["rounds_per_call"]

    phases = {"start": time.perf_counter() - t0}
    fed, engine, data, state, x0 = set_up(cell, seed, phases)
    t = time.perf_counter()
    state, data, program = drive_checked(cell, engine, data, state)
    jax.block_until_ready(state)
    phases["checked_calls"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t0

    # ---- the measured window -------------------------------------------
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    rounds = failed = 0
    counter.on = True
    start = time.perf_counter()
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        while True:
            with jax.profiler.TraceAnnotation(tr.FIT_SPAN):
                state, hz = fit(engine, data, rpc, state=state)
            data = hz.data
            losses = np.asarray(hz.metrics.loss).reshape(rpc, -1)
            failed += int(np.sum(~np.isfinite(losses).all(axis=1)))
            rounds += rpc
            if time.perf_counter() - start >= seconds:
                break
        with jax.profiler.TraceAnnotation(tr.SYNC_SPAN):
            jax.block_until_ready(state)
    window_s = time.perf_counter() - start
    counter.on = False
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = tr.reduce_dir(trace_dir, chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = device_peak_bytes(stats)
    del state, data, hz, engine
    gc.collect()

    # ---- the plain reference over the checked rounds -------------------
    t = time.perf_counter()
    ref = reference_readout(cell, seed, fed, x0)
    phases["reference"] = time.perf_counter() - t
    read = check.readings(x0, program, ref)
    correct, checks = check.judge(read, cell.limits)
    correct = correct and failed == 0

    # ---- the result line -------------------------------------------------
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes,
              "memory_peak_bytes_per_chip": [chip_peak_bytes(c)
                                             for c in stats]}
    if trace:
        run = TracedRun(reduced, rounds, rounds_flops(cell),
                        peaks_for(dev.device_kind), chips)
        metrics = {}
        for name, unit, reader in cell.per_layer:
            value = reader.read(run)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    else:
        metrics = {"round_s": {"value": window_s / rounds, "unit": "s"}}
        if peak_bytes is not None:    # a TPU reports it; the CPU does not
            metrics["peak_hbm_gib"] = {"value": peak_bytes / 2**30,
                                       "unit": "GiB"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result = {"correct": correct, "attempted": rounds, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = tr.breakdown(reduced)
    lines = ["seconds: " + ", ".join(f"{k} {v:.3f}" for k, v in phases.items()),
             f"rounds in window {rounds}, compiles in window {counter.count}, "
             "worst leaves: " + ", ".join(
                 f"{k} {read[k + '_leaf']}" for k in check.LEAF_NUMBERS)]
    lines += [f"memory_stats {d.id}: " + ", ".join(
        f"{k} {v}" for k, v in sorted(c.items()))
        for d, c in zip(devices, stats)]
    lines.append("not compared: " + ", ".join(
        f"{n} {read[n]:.6g}" for n in check.NUMBERS if n not in checks))
    lines += [f"{n} {c['value']!r} limit {c['limit']!r}"
              for n, c in checks.items()]
    if trace:
        by_op = {}
        for name, sec in reduced.op_seconds().items():
            op = tr.opcode(name)
            by_op[op] = by_op.get(op, 0.0) + sec
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:12]
        lines.insert(0, "device seconds by opcode: " + ", ".join(
            f"{op} {sec:.4f}" for op, sec in top))
    result["checks"] = checks
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program at {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    cell = load_cell(args.workload)
    try:
        check_devices(cell.workload["chips"])
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    enable_compile_cache()
    result, lines = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

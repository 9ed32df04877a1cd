#!/usr/bin/env python3
"""Run the MTGC training path once on one TPU chip and check what comes out.

    python3 chip_smoke.py [--seed N]

It needs a TPU: on any other platform it exits non-zero before it runs
anything. All phases run in this one process, through ``repro.api``'s
``build``/``fit``:

* ``sim_fused`` -- the paper's cross-device setting (Sec. 5.1) at full
  width: the McMahan CNN (2,156,490 parameters) on CIFAR-10-shaped
  synthetic images, G=10 groups x K=10 clients, batch 50, E=2 group rounds
  of H=5 local steps, 3 global rounds, flat state with the fused Pallas
  update. The same spec with ``fusion="none"`` is the reference.
* ``sim_partial_compressed`` -- the same, with half the clients sampled per
  round (the masked update kernel) and compressed uploads: int8 with
  stochastic rounding on the client link, top-k on the group link, error
  feedback on both (the quantize kernels). Checked the same way.
* ``sharded`` -- the ``launch/train.py`` path (sharded backend, fused, tree
  layout) on glm4-9b at ``cfg.reduced()`` width for 2 rounds: a check that
  the second implementation of the round runs on the chip, not a real size.

Before it trains, each run lowers and compiles its chunk with
``Engine.lower_chunk`` and requires the expected number of compiled Mosaic
kernels (``tpu_custom_call``) in the optimized HLO: a fused run that fell
back to the interpreter or to the jnp reference has none. Losses and z/y
norms must be finite. Compile seconds, seconds per global round (timed to
``block_until_ready``) and peak device bytes are printed as smoke numbers;
they are not benchmark numbers. The last line of the output is the JSON
result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import numpy as np

from repro.api import CompressionPlan, ExperimentSpec, RoundSchedule, build, fit
from repro.data.partition import partition
from repro.data.synthetic import make_classification
from repro.launch import train
from repro.launch.compile_cache import enable_compile_cache
from repro.models.small import cnn, make_loss

LR = 0.01
# Dirichlet concentration of the label split; at 0.1 some of the 100
# clients get fewer than 8 of the 20,000 samples.
ALPHA = 0.5
LM_ARCH = "glm4-9b"


@dataclasses.dataclass(frozen=True)
class SimPhase:
    """One simulator phase: its spec fields, the compiled Mosaic kernels a
    fused chunk holds, and the parity bounds."""

    spec: dict
    # The flat update (one call: the CNN's one f32 buffer), plus one per
    # kernel-backed compressed upload link.
    kernels: int
    loss_rtol: float  # per global round, on the mean training loss
    disp_rtol: float  # ||x_fused - x_ref|| / ||x_ref - x_0||, all replicas


SIM_PHASES = {
    # Fused and unfused runs compute the same f32 update, but Mosaic and
    # XLA may round it differently (the order of g + z + y, a multiply-add
    # contracted or not): at most a few ulps per element per step, carried
    # through E*H*T = 30 SGD steps of a smooth map. A wrong mask, a dropped
    # correction term or a client updated with another's y moves the
    # displacement from the initial model by tens of percent.
    "sim_fused": SimPhase({}, kernels=1, loss_rtol=1e-3, disp_rtol=1e-3),
    # int8 stochastic rounding and the top-k threshold are discontinuous: a
    # one-ulp difference moves an entry that sits on a rounding boundary by
    # a whole quantization step, or across the threshold, and error
    # feedback carries it on. On the CPU at G=K=2 (this phase otherwise
    # unchanged), a one-ulp difference in the int8 scale division moved the
    # displacement by 4.5% and the loss by 1.1e-3. At G=K=2 on 8x8 images,
    # broken kernels moved it by 26% (top-k threshold read from the next
    # row), 108% (mask ignored) and 900% (int8 scale from the next row).
    "sim_partial_compressed": SimPhase(
        dict(client_participation=0.5,
             compression=CompressionPlan(client_mode="int8_stochastic",
                                         group_mode="topk", topk_frac=0.1,
                                         error_feedback=True)),
        kernels=3, loss_rtol=1e-2, disp_rtol=0.15),
}


@dataclasses.dataclass(frozen=True)
class SimSize:
    """A simulator phase's shape; the defaults are the paper's Sec. 5.1
    cross-device setting (benchmarks/common.py ``BenchSetup.paper``)."""

    levels: tuple = (10, 10)
    image_shape: tuple = (32, 32, 3)
    batch: int = 50
    samples: int = 20_000
    group_rounds: int = 2
    local_steps: int = 5
    rounds: int = 3


@dataclasses.dataclass(frozen=True)
class LMSize:
    """The sharded phase's shape: ``launch/train.py``'s defaults, with 2
    packed batch blocks per client in place of 8."""

    levels: tuple = (2, 2)
    seq: int = 128
    batch: int = 2
    rounds: int = 2
    shards: int = 2


@dataclasses.dataclass
class Run:
    """One trained run: what the phase checks and prints."""

    kernels: int          # tpu_custom_call sites in the compiled chunk
    compile_s: float      # lower + compile of the one-round chunk
    first_round_s: float  # first fit() round, to block_until_ready
    round_s: float        # mean of the later rounds, to block_until_ready
    losses: np.ndarray    # [T] mean training loss per global round
    x0: list              # initial model, one array per state buffer
    x: list               # final replicas [G, K, ...], one per buffer


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def count_kernels(hlo: str) -> int:
    """Compiled Mosaic kernel call sites in optimized HLO text."""
    return hlo.count('custom_call_target="tpu_custom_call"')


def train_run(engine, data, params, rounds: int, rng=None) -> Run:
    """Lower the chunk, then ``fit`` one round and ``rounds - 1`` more."""
    state = engine.init(params, rng)
    x0 = [np.asarray(b[0, 0]) for b in jax.tree.leaves(state.params)]
    t0 = time.perf_counter()
    lc = engine.lower_chunk(data, state=state, chunk=1)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    state, hz1 = fit(engine, data, 1, state=state, chunk=1)
    jax.block_until_ready(state)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, hz2 = fit(engine, hz1.data, rounds - 1, state=state, chunk=1)
    jax.block_until_ready(state)
    round_s = (time.perf_counter() - t0) / (rounds - 1)

    for field in ("loss", "z_norm", "y_norm"):
        vals = [np.asarray(getattr(h.metrics, field)) for h in (hz1, hz2)]
        _check(all(np.isfinite(v).all() for v in vals),
               f"non-finite {field}: {vals}")
    losses = np.concatenate([np.asarray(h.metrics.loss) for h in (hz1, hz2)])
    return Run(count_kernels(lc.hlo), compile_s, first_s, round_s,
               losses.reshape(rounds, -1).mean(axis=1), x0,
               [np.asarray(b) for b in jax.tree.leaves(state.params)])


def parity(fused: Run, ref: Run) -> dict:
    """Relative loss and displacement differences of fused against ref."""
    loss_rel = float(np.max(np.abs(fused.losses - ref.losses)
                            / np.abs(ref.losses)))
    diff = moved = 0.0
    for xf, xr, x0 in zip(fused.x, ref.x, ref.x0):
        diff += float(np.sum(np.square(xf - xr, dtype=np.float64)))
        moved += float(np.sum(np.square(xr - x0, dtype=np.float64)))
    return {"loss_rel": loss_rel, "disp_rel": (diff / moved) ** 0.5}


def sim_phase(name: str, size: SimSize = SimSize(), seed: int = 0) -> dict:
    """Train one simulator phase fused and unfused; return both runs and
    their parity numbers."""
    G, K = size.levels
    rng = np.random.default_rng(seed)
    # Images stay flat [n, H*W*C] on the device: the CNN reshapes them to
    # its image shape itself, while a packed [..., 32, 32, 3] array pads
    # its 3 channels to 128 lanes (several GB at this size).
    ds = make_classification(rng, num_samples=size.samples, num_classes=10,
                             dim=int(np.prod(size.image_shape)))
    idx = partition(ds.y, G, K, mode="both_noniid", alpha=ALPHA, seed=seed)
    init, apply = cnn(10, size.image_shape)
    params = init(jax.random.PRNGKey(seed))
    phase = SIM_PHASES[name]
    partial = phase.spec.get("client_participation", 1.0) < 1.0
    runs, data = {}, None
    for fusion in ("fused", "none"):
        spec = ExperimentSpec(
            levels=size.levels,
            schedule=RoundSchedule(group_rounds=size.group_rounds,
                                   local_steps=size.local_steps),
            algorithm="mtgc", lr=LR, state_layout="flat",
            fusion=fusion, **phase.spec)
        engine = build(spec, make_loss(apply))
        if data is None:
            # One packed dataset for both runs: fit() never donates it.
            data = engine.pack_arrays(
                {"x": ds.x, "y": ds.y}, idx, batch_size=size.batch,
                shards=1, rng=rng,
                key=jax.random.PRNGKey(seed + 1))
        runs[fusion] = train_run(
            engine, data, params, size.rounds,
            rng=jax.random.PRNGKey(seed + 2) if partial else None)
    return {"runs": runs, "parity": parity(runs["fused"], runs["none"])}


def sharded_phase(size: LMSize = LMSize(), seed: int = 0) -> dict:
    """The ``launch/train.py`` path, fused, tree layout, reduced width."""
    spec = ExperimentSpec(
        levels=size.levels, backend="sharded", lr=0.05, state_layout="tree",
        fusion="fused",
        schedule=RoundSchedule(group_rounds=2, local_steps=2, microbatches=1))
    _, params, engine, data = train.setup_run(
        LM_ARCH, spec, smoke=True, seq=size.seq, batch=size.batch,
        seed=seed, shards=size.shards)
    return {"run": train_run(engine, data, params, size.rounds),
            "leaves": len(jax.tree.leaves(params))}


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _report(phase: str, label: str, run: Run, expected: int) -> None:
    print(f"[smoke] {phase} {label}: tpu_custom_call={run.kernels} "
          f"(expected {expected}) compile_s={run.compile_s:.3f} "
          f"first_round_s={run.first_round_s:.3f} "
          f"round_s={run.round_s:.4f} losses={run.losses.tolist()} "
          f"peak_bytes_in_use_so_far={_peak_bytes()}", flush=True)
    _check(run.kernels == expected,
           f"{phase} {label}: {run.kernels} tpu_custom_call sites in the "
           f"compiled chunk, expected {expected}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"[smoke] device_kind={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache}", flush=True)
    print("[smoke] numbers below are smoke numbers, not benchmark numbers",
          flush=True)

    for name, phase in SIM_PHASES.items():
        out = sim_phase(name, seed=args.seed)
        _report(name, "fused", out["runs"]["fused"], phase.kernels)
        _report(name, "unfused", out["runs"]["none"], 0)
        par = out["parity"]
        print(f"[smoke] {name} parity: loss_rel={par['loss_rel']:.3e} "
              f"(tol {phase.loss_rtol:g}) disp_rel={par['disp_rel']:.3e} "
              f"(tol {phase.disp_rtol:g})", flush=True)
        _check(par["loss_rel"] <= phase.loss_rtol
               and par["disp_rel"] <= phase.disp_rtol,
               f"{name}: fused and unfused runs disagree: {par}")

    print(f"[smoke] sharded: {LM_ARCH} at cfg.reduced() width -- a check that "
          "the launch/train.py round runs on the chip, not a real size",
          flush=True)
    out = sharded_phase(seed=args.seed)
    # The tree-layout fused round updates each parameter leaf with its own
    # kernel call.
    _report("sharded", "fused", out["run"], out["leaves"])

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

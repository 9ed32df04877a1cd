"""Shared pieces of the benchmark's tests: the cells of ``BENCHMARK.json``
cut to the size a CPU test holds (each configuration's ``tiny.json``), a
checkout with one more cell made of new files alone (``lm-tiny``, a causal
LM), and a peak table entry for the CPU so a traced run can be driven off
the chip."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import numpy as np

from bench import peaks, run

BENCHMARK = json.loads((run.ROOT / "BENCHMARK.json").read_text())
WORKLOADS = tuple(w["name"] for w in BENCHMARK["workloads"])

# A causal-LM configuration, its traffic and limits, and its entries for
# BENCHMARK.json, laid out as they would arrive under bench/.
LM_TINY = Path(__file__).parent / "data" / "lm-tiny"
LM_WORKLOAD = "lm-tiny.tokens"


def _tiny(cell: run.Cell, root: Path = run.ROOT) -> dict:
    benchmark = json.loads((root / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in benchmark["configs"]}[
        cell.workload["config"]]
    return json.loads((root / entry["file"]).with_name("tiny.json")
                      .read_text())


def tiny_cell(workload: str, root: Path = run.ROOT) -> run.Cell:
    """``workload`` from the checkout at ``root`` with its configuration
    and traffic cut to a CPU test's size (the configuration's
    ``tiny.json``); its limits are the cell's own."""
    cell = run.load_cell(workload, root=root)
    tiny = _tiny(cell, root)
    cell.config.update(tiny["config"])
    cell.traffic.update(tiny["traffic"])
    return cell


def control_cell(workload: str, root: Path = run.ROOT) -> run.Cell:
    """``tiny_cell`` at the larger size at which a CPU test holds the
    bfloat16 control (``tiny.json``'s ``control``)."""
    cell = tiny_cell(workload, root)
    control = _tiny(cell, root)["control"]
    cell.config.update(control["config"])
    cell.traffic.update(control["traffic"])
    return cell


def lm_checkout(root: Path, four_chips: bool = False) -> Path:
    """A checkout at ``root``: this one's ``bench/`` and ``BENCHMARK.json``
    plus the ``lm-tiny`` cell, added as a new configuration arrives: new
    files under ``bench/configs``, ``bench/traffic``, ``bench/limits`` and
    ``bench/metrics``, and new ``BENCHMARK.json`` entries. With
    ``four_chips`` the cell asks for four chips (``four_chips.json``)."""
    shutil.copytree(run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic", "limits", "metrics"):
        for f in (LM_TINY / sub).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                dest = root / "bench" / f.relative_to(LM_TINY)
                if dest.exists():
                    raise FileExistsError(f"lm-tiny would edit {dest}")
                dest.parent.mkdir(parents=True, exist_ok=True)
                shutil.copy(f, dest)
    benchmark = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    added = json.loads((LM_TINY / "benchmark.json").read_text())
    if four_chips:
        added.update(json.loads((LM_TINY / "four_chips.json").read_text()))
    for key, entries in added.items():
        benchmark[key] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(benchmark, indent=1))
    return root


def lm_cell(root: Path, backend: str = "simulator",
            microbatches: int | None = None) -> run.Cell:
    """The ``lm-tiny`` cell of ``lm_checkout(root)``, on ``backend``."""
    cell = tiny_cell(LM_WORKLOAD, root)
    spec = cell.traffic["spec"]
    if backend != "simulator":
        spec["backend"] = backend
    if microbatches is not None:
        spec["schedule"]["microbatches"] = microbatches
    return cell


def program_and_reference_loss(cell: run.Cell) -> tuple:
    """The program's loss and the plain reference's on one random batch of
    ``cell``'s kind, from the reference's weights."""
    cfg = cell.config
    params = cell.ref.init_weights(cfg, jax.random.PRNGKey(3))
    loss_fn, _ = cell.model.program_loss(cfg)
    if cell.causal_lm:
        toks = jax.random.randint(jax.random.PRNGKey(4), (3, 33), 0,
                                  cfg["vocab_size"])
        batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
        return loss_fn(params, batch), cell.ref.loss(cfg, params, batch)
    x = jax.random.normal(jax.random.PRNGKey(4),
                          (6, int(np.prod(cfg["image_shape"]))))
    y = jax.random.randint(jax.random.PRNGKey(5), (6,), 0, cfg["num_classes"])
    logp = jax.nn.log_softmax(cell.ref.forward(cfg, params, x), axis=-1)
    want = -np.mean(np.asarray(logp)[np.arange(6), np.asarray(y)])
    return loss_fn(params, {"x": x, "y": y}), want


def _frozen_build(orig):
    """``repro.api.build`` whose round returns the state it was given."""
    def build(spec, loss_fn):
        engine = orig(spec, loss_fn)
        round_fn = engine.round_fn

        def frozen(state, batches):
            _, metrics = round_fn(state, batches)
            return state, metrics

        engine.round_fn = frozen
        return engine
    return build


def _half(batch):
    return jax.tree.map(lambda b: b[:, :, : b.shape[2] // 2], batch)


def _half_batch_grads(orig):
    """The simulator's clients' gradients on the first half of each batch
    (``[G, K, B, ...]``), the mean taken over that half."""
    def client_grads(loss_fn, params, batch):
        return orig(loss_fn, params, _half(batch))
    return client_grads


def _half_batch_build(orig):
    """``repro.api.build`` whose client loss sees the first half of its
    batch's rows only, the mean taken over that half: the sharded round
    takes its gradients of the loss itself."""
    def build(spec, loss_fn):
        def half_loss(params, batch):
            return loss_fn(params, jax.tree.map(
                lambda b: b[: b.shape[0] // 2], batch))
        return orig(spec, half_loss)
    return build


def plant_fault(monkeypatch, fault: str, cell: run.Cell) -> None:
    """Break the timed path of ``cell``'s engine underneath the harness:
    ``state_unchanged``, a round that returns its state unchanged;
    ``half_batch``, half of every batch left out."""
    import repro.api
    import repro.core.engine as engine_mod

    if fault == "state_unchanged":
        monkeypatch.setattr(repro.api, "build", _frozen_build(repro.api.build))
    elif cell.traffic["spec"].get("backend", "simulator") == "sharded":
        monkeypatch.setattr(repro.api, "build",
                            _half_batch_build(repro.api.build))
    else:
        monkeypatch.setattr(engine_mod, "_client_grads",
                            _half_batch_grads(engine_mod._client_grads))


def allow_cpu_peaks(monkeypatch) -> None:
    """Lets ``run_cell`` look up peaks for the CPU, so that a test can
    drive a traced run off the chip."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))

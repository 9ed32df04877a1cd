"""Plain MTGC global rounds: the reference the program's rounds are held to.

Algorithm 1 of the MTGC paper (arXiv 2409.18448) with its experiments'
choice of z = 0 at the start of every global round, written directly in
``jax.numpy`` from the paper and independent of ``repro``. One global round
of G groups x K clients:

    z_i = 0
    for e in 1..E:
        for h in 1..H:   x_i <- x_i - lr * (grad F_i(x_i; batch) + z_i + y_j)
        xbar_j = mean_k x_i;   z_i <- z_i + (x_i - xbar_j) / (H lr);   x_i <- xbar_j
    xbar = mean_j xbar_j;      y_j <- y_j + (xbar_j - xbar) / (H E lr);  x_i <- xbar

The clients' gradients run group by group (``lax.map`` over groups, ``vmap``
over a group's clients), so the reference needs about a G-th of the
activations the program holds. ``dtype`` is the precision everything is
held and computed in; float32 runs under ``default_matmul_precision
(precision)``: ``"default"``, the precision a configuration states (one
bfloat16 pass per product on a TPU, float32 on a CPU), or ``"highest"``
(float32 products on a TPU), a second reading for the calibration. Two
planted faults, for the calibration of the limits: ``batch_fraction < 1``
trains each client on the first part of every batch only, and ``drop_y``
leaves y out of the local step (y is still updated).
"""
from __future__ import annotations

import contextlib
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def supports(spec: dict) -> bool:
    """Whether the reference implements a traffic mix's ``spec`` section."""
    plain = {"algorithm", "lr", "schedule"}
    return spec.get("algorithm") == "mtgc" and set(spec) <= plain


def _loss(forward: Callable, p, x, y):
    logits = forward(p, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def run_rounds(forward: Callable, params0: dict, batches: list[dict], *,
               levels: tuple[int, int], lr: float, group_rounds: int,
               local_steps: int, dtype=jnp.float32,
               precision: str = "default",
               batch_fraction: float = 1.0, drop_y: bool = False
               ) -> tuple[np.ndarray, list[dict], list[dict]]:
    """Train ``len(batches)`` global rounds from ``params0``.

    ``batches[r]`` holds round r's ``{"x": [E, H, G, K, B, D], "y": [E, H,
    G, K, B]}`` host arrays. Returns the mean client loss of every local
    step, ``[R, E, H]`` float64, the global model after every round as
    host float64 trees, and after every round the norm of each leaf of the
    corrections z (of the round's last group round) and y.
    """
    G, K = levels
    E, H = group_rounds, local_steps
    dt = jnp.dtype(dtype)
    precision = (jax.default_matmul_precision(precision)
                 if dt == jnp.float32 else contextlib.nullcontext())
    cast = lambda t: jax.tree.map(lambda a: jnp.asarray(a, dt), t)

    def client(p, z, y, bx, by):
        loss, g = jax.value_and_grad(lambda q: _loss(forward, q, bx, by))(p)
        if drop_y:
            y = jax.tree.map(jnp.zeros_like, y)
        new = jax.tree.map(lambda pi, gi, zi, yi: pi - lr * (gi + zi + yi),
                           p, g, z, y)
        return new, loss

    @jax.jit
    def local_step(x, z, y, bx, by):
        if batch_fraction < 1.0:
            keep = max(1, int(bx.shape[2] * batch_fraction))
            bx, by = bx[:, :, :keep], by[:, :, :keep]

        def group(args):
            xg, zg, yg, bxg, byg = args
            return jax.vmap(client, in_axes=(0, 0, None, 0, 0))(
                xg, zg, yg, bxg, byg)

        x, loss = jax.lax.map(group, (x, z, y, bx.astype(dt), by))
        return x, jnp.mean(loss.astype(jnp.float32))

    @jax.jit
    def group_aggregate(x, z):
        xbar = jax.tree.map(lambda a: jnp.mean(a, axis=1, keepdims=True), x)
        z = jax.tree.map(lambda zi, xi, xb: zi + (xi - xb) / (H * lr),
                         z, x, xbar)
        x = jax.tree.map(lambda xb: jnp.broadcast_to(xb, (G, K) + xb.shape[2:]),
                         xbar)
        return x, z

    @jax.jit
    def global_aggregate(x, y):
        xj = jax.tree.map(lambda a: a[:, 0], x)            # clients are equal
        xbar = jax.tree.map(lambda a: jnp.mean(a, axis=0), xj)
        y = jax.tree.map(lambda yj, a, b: yj + (a - b) / (H * E * lr),
                         y, xj, xbar)
        x = jax.tree.map(lambda b: jnp.broadcast_to(b, (G, K) + b.shape), xbar)
        return x, y, xbar

    with precision:
        p0 = cast(params0)
        x = jax.tree.map(lambda a: jnp.broadcast_to(a, (G, K) + a.shape), p0)
        y = jax.tree.map(lambda a: jnp.zeros((G,) + a.shape, dt), p0)
        losses, models, corrections = [], [], []
        for rb in batches:
            z = jax.tree.map(jnp.zeros_like, x)
            for e in range(E):
                for h in range(H):
                    x, loss = local_step(x, z, y, jnp.asarray(rb["x"][e, h]),
                                         jnp.asarray(rb["y"][e, h]))
                    # One step at a time: a loop that runs ahead holds
                    # every queued step's new replicas on the device.
                    losses.append(float(loss))
                x, z = group_aggregate(x, z)
            x, y, xbar = global_aggregate(x, y)
            models.append(jax.tree.map(
                lambda a: np.asarray(a, np.float64), xbar))
            corrections.append({"z": _norms(z), "y": _norms(y)})
        losses = np.asarray(losses, np.float64)
    return losses.reshape(len(batches), E, H), models, corrections


def _norms(tree) -> dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(
        leaf.astype(jnp.float32))) for path, leaf in flat}

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

A client's local step takes the gradient of the configuration's loss
(``loss(params, batch)``, a mean over the batch's rows) over its A
microbatches of B rows: the mean of the A microbatch gradients, which is
the gradient of the mean over the A*B rows. Classification merges the
microbatches into one batch and runs the clients group by group
(``lax.map`` over groups, ``vmap`` over a group's clients), so the
reference needs about a G-th of the activations the program holds.

With ``per_client`` (causal LMs) the clients run one at a time, each over
its microbatches, and the state of the round stays in host memory: every
replica's x_i and z_i and every group's y_j, in the run's precision. A local
step puts one client's x_i, z_i and y_j on the device, runs one jitted call
that takes x_i's buffer for the new x_i, and brings x_i back; moving float32
or bfloat16 between host and device is exact. The group and global
aggregations run leaf by leaf on the device, through the same jitted means
and corrections as the whole-state path. So the device holds one client's
x, z, y, gradient and accumulator and one microbatch's activations, whatever
G x K; an aggregation holds one leaf of every replica. The whole-state path
with ``per_client`` (``host_state=False``) is the one the host path is
tested against.

``dtype`` is the precision everything is held and computed in; float32
runs under ``default_matmul_precision(precision)``: ``"default"``, the precision a configuration states (one
bfloat16 pass per product on a TPU, float32 on a CPU), or ``"highest"``
(float32 products on a TPU), a second reading for the calibration. Two
planted faults, for the calibration of the limits: ``batch_fraction < 1``
trains each client on the first part of every batch only, and ``drop_y``
leaves y out of the local step (y is still updated).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


BACKENDS = ("simulator", "sharded")
SCHEDULE = frozenset({"group_rounds", "local_steps", "microbatches"})


def supports(spec: dict) -> bool:
    """Whether the reference implements a traffic mix's ``spec`` section:
    MTGC at full participation with uncompressed uploads, on either
    two-level backend, with a uniform schedule; microbatches (the sharded
    backend's gradient accumulation) change no arithmetic of the round."""
    plain = {"algorithm", "lr", "schedule", "backend"}
    backend = spec.get("backend", "simulator")
    sched = spec.get("schedule", {})
    return (spec.get("algorithm") == "mtgc" and set(spec) <= plain
            and backend in BACKENDS and set(sched) <= SCHEDULE
            and isinstance(sched.get("group_rounds", 1), int)
            and (sched.get("microbatches") is None or backend == "sharded"))


def classification_loss(forward: Callable, p, batch: dict):
    """Softmax cross-entropy of ``forward(p, x)`` against the labels ``y``,
    the mean over the rows."""
    logits = forward(p, batch["x"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=-1))


def run_rounds(loss: Callable, params0: dict, batches: list[dict], *,
               levels: tuple[int, int], lr: float, group_rounds: int,
               local_steps: int, per_client: bool = False,
               host_state: bool = True,
               dtype=jnp.float32, precision: str = "default",
               batch_fraction: float = 1.0, drop_y: bool = False
               ) -> tuple[np.ndarray, list[dict], list[dict]]:
    """Train ``len(batches)`` global rounds from ``params0``.

    ``batches[r]`` holds round r's named host arrays, each ``[E, H, G, K,
    A, B, ...]`` (A microbatches of B rows a local step). ``loss(params,
    batch)`` is one client's loss on one batch of rows. With
    ``per_client``, ``host_state`` keeps the round's state in host memory
    and one client's on the device. Returns the mean client
    loss of every local step, ``[R, E, H]`` float64, the global model after
    every round as host float64 trees, and after every round the norm of
    each leaf of the corrections z (of the round's last group round) and y.
    """
    G, K = levels
    E, H = group_rounds, local_steps
    dt = jnp.dtype(dtype)
    precision = (jax.default_matmul_precision(precision)
                 if dt == jnp.float32 else contextlib.nullcontext())
    cast = lambda t: jax.tree.map(lambda a: jnp.asarray(a, dt), t)
    # Inputs in the run's precision; labels and token ids as they are.
    cast_in = lambda b: {k: a.astype(dt) if jnp.issubdtype(a.dtype,
                                                            jnp.floating)
                         else a for k, a in b.items()}

    def grad_of(p, b):
        """(loss, gradient) of one client: the mean over its microbatches
        (leading axis of ``b``)."""
        def micro(acc, bm):
            lv, g = jax.value_and_grad(loss)(p, bm)
            return (acc[0] + lv.astype(jnp.float32),
                    jax.tree.map(jnp.add, acc[1], g)), None

        A = jax.tree.leaves(b)[0].shape[0]
        init = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        (lsum, gsum), _ = jax.lax.scan(micro, init, b)
        return lsum / A, jax.tree.map(lambda g: g / A, gsum)

    def client(p, z, y, b):
        if per_client:
            lv, g = grad_of(p, b)
        else:
            lv, g = jax.value_and_grad(loss)(p, b)
        if drop_y:
            y = jax.tree.map(jnp.zeros_like, y)
        new = jax.tree.map(lambda pi, gi, zi, yi: pi - lr * (gi + zi + yi),
                           p, g, z, y)
        return new, lv

    def cut(b, rows_axis):
        """The first ``batch_fraction`` of every batch's rows."""
        if batch_fraction < 1.0:
            n = jax.tree.leaves(b)[0].shape[rows_axis]
            keep = max(1, int(n * batch_fraction))
            b = {k: jax.lax.slice_in_dim(a, 0, keep, axis=rows_axis)
                 for k, a in b.items()}
        return cast_in(b)

    @jax.jit
    def local_step(x, z, y, b):
        b = cut(b, 3)
        if per_client:
            def group(args):
                xg, zg, yg, bg = args
                return jax.lax.map(lambda a: client(a[0], a[1], yg, a[2]),
                                   (xg, zg, bg))
        else:
            # The microbatches merged: [G, K, A*B, ...].
            b = {k: a.reshape(a.shape[:2] + (-1,) + a.shape[4:])
                 for k, a in b.items()}

            def group(args):
                xg, zg, yg, bg = args
                return jax.vmap(client, in_axes=(0, 0, None, 0))(
                    xg, zg, yg, bg)

        x, lv = jax.lax.map(group, (x, z, y, b))
        return x, jnp.mean(lv.astype(jnp.float32))

    @functools.partial(jax.jit, donate_argnums=0)
    def client_step(x, z, y, b):
        """One local step of one client; ``b`` holds its ``[A, B, ...]``
        microbatches, and ``x``'s buffers take the new x."""
        return client(x, z, y, cut(b, 1))

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

    def device_rounds():
        p0 = cast(params0)
        x = jax.tree.map(lambda a: jnp.broadcast_to(a, (G, K) + a.shape), p0)
        y = jax.tree.map(lambda a: jnp.zeros((G,) + a.shape, dt), p0)
        for rb in batches:
            z = jax.tree.map(jnp.zeros_like, x)
            for e in range(E):
                for h in range(H):
                    x, lv = local_step(x, z, y, {
                        k: jnp.asarray(a[e, h]) for k, a in rb.items()})
                    # One step at a time: a loop that runs ahead holds
                    # every queued step's new replicas on the device.
                    losses.append(float(lv))
                x, z = group_aggregate(x, z)
            x, y, xbar = global_aggregate(x, y)
            models.append(jax.tree.map(
                lambda a: np.asarray(a, np.float64), xbar))
            corrections.append({"z": _norms(z), "y": _norms(y)})

    def host_rounds():
        # Leaves of the state, [G, K, ...] (x, z) and [G, ...] (y), on the
        # host in the run's precision.
        p0, tree = jax.tree.flatten(params0)
        p0 = [np.array(jnp.asarray(a, dt)) for a in p0]
        x = [np.broadcast_to(a, (G, K) + a.shape).copy() for a in p0]
        y = [np.zeros((G,) + a.shape, dt) for a in p0]
        at = lambda leaves, *i: jax.device_put(
            jax.tree.unflatten(tree, [a[i] for a in leaves]))
        for rb in batches:
            z = [np.zeros_like(a) for a in x]
            for e in range(E):
                for h in range(H):
                    lv = np.empty((G, K), np.float32)
                    for g in range(G):
                        yg = at(y, g)
                        for k in range(K):
                            new, lv[g, k] = client_step(
                                at(x, g, k), at(z, g, k), yg,
                                {n: a[e, h, g, k] for n, a in rb.items()})
                            for a, v in zip(x, _to_host(new)):
                                a[g, k] = v
                            # Dropped before the next step or aggregation:
                            # the device holds one client's state at a time.
                            del new
                        del yg
                    losses.append(float(jnp.mean(lv)))
                for i in range(len(x)):
                    x[i], z[i] = _to_host(group_aggregate(x[i], z[i]))
            xbar = []
            for i in range(len(x)):
                x[i], y[i], xb = _to_host(global_aggregate(x[i], y[i]))
                xbar.append(np.asarray(xb, np.float64))
            models.append(jax.tree.unflatten(tree, xbar))
            corrections.append({"z": _norms(jax.tree.unflatten(tree, z)),
                                "y": _norms(jax.tree.unflatten(tree, y))})

    losses, models, corrections = [], [], []
    with precision:
        (host_rounds if per_client and host_state else device_rounds)()
    losses = np.asarray(losses, np.float64)
    return losses.reshape(len(batches), E, H), models, corrections


def _to_host(tree) -> list[np.ndarray]:
    """The leaves of a device tree as writable host arrays."""
    return [np.array(a) for a in jax.tree.leaves(tree)]


def _norms(tree) -> dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): float(jnp.linalg.norm(
        leaf.astype(jnp.float32))) for path, leaf in flat}

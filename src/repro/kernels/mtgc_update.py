"""Fused MTGC local update: ``x <- x - lr * (g + z + y)`` (Alg. 1 line 7).

This is the paper's per-iteration hot-spot: a 4-operand AXPY executed
H*E times per round on every parameter element of every client replica.
Unfused, XLA emits three binary ops -> up to 3 extra HBM round-trips of the
parameter-sized intermediates. The kernel streams all four operands through
VMEM once (arithmetic intensity is fixed at ~0.75 flop/byte, so HBM
bandwidth is the ceiling and fusion is the whole win).

Two entry points:

* :func:`mtgc_update` -- one (equal-shape) leaf at a time. Layout: operands
  are flattened and tiled to (ROWS, 128) lanes -- the TPU vector layout --
  with a (block_rows, 128) VMEM block per grid step (default 1024x128xf32
  x 5 buffers = 2.6 MB of VMEM); the correction sum runs in f32 regardless
  of the storage dtype (z/y may be bf16 under the beyond-paper
  low-precision-correction option).

* :func:`mtgc_update_flat` -- the whole model at once over the contiguous
  flat-state layout (core/packer.py): x/g/z are ``[G, K, N]``, ``y`` stays
  ``[G, N]`` and is broadcast across clients *by the block index map* (never
  materialized per client), and an optional ``[G, K]`` participation mask is
  folded into the update in-register -- eliminating the parameter-sized
  ``tree_select`` HBM pass per local step. One lane-padding for the entire
  model instead of one per leaf.

``g_scale`` folds the microbatch-accumulation mean (``g / A`` on the
sharded path) into the same pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_BLOCK_ROWS = 1024


def _kernel(lr, g_scale, x_ref, g_ref, z_ref, y_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    d = (g_ref[...].astype(jnp.float32) * g_scale
         + z_ref[...].astype(jnp.float32)
         + y_ref[...].astype(jnp.float32))
    o_ref[...] = (x - lr * d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("lr", "g_scale", "block_rows",
                                             "interpret"))
def mtgc_update(x, g, z, y, *, lr: float, g_scale: float = 1.0,
                block_rows: int = DEFAULT_BLOCK_ROWS, interpret: bool = False):
    """Fused corrected update over arbitrary-shaped (equal-shape) arrays."""
    shape, dtype = x.shape, x.dtype
    n = x.size
    rows = -(-n // LANE)
    rows_p = -(-rows // block_rows) * block_rows
    pad = rows_p * LANE - n

    def prep(a):
        a = a.reshape(-1)
        if pad:
            a = jnp.pad(a, (0, pad))
        return a.reshape(rows_p, LANE)

    xs = [prep(a) for a in (x, g, z, y)]
    grid = (rows_p // block_rows,)
    out = pl.pallas_call(
        functools.partial(_kernel, float(lr), float(g_scale)),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
                  for _ in range(4)],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, LANE), dtype),
        interpret=interpret,
        name="mtgc_update",
    )(*xs)
    return out.reshape(-1)[:n].reshape(shape)


def _flat_kernel(lr, g_scale, x_ref, g_ref, z_ref, y_ref, m_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    d = (g_ref[...].astype(jnp.float32) * g_scale
         + z_ref[...].astype(jnp.float32)
         + y_ref[...].astype(jnp.float32))
    x_new = x - lr * d
    if m_ref is not None:
        x_new = jnp.where(m_ref[0, 0, 0] != 0, x_new, x)
    o_ref[...] = x_new.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("lr", "g_scale", "block_rows",
                                             "interpret"))
def mtgc_update_flat(x, g, z, y, mask=None, *, lr: float, g_scale: float = 1.0,
                     block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret: bool = False):
    """Whole-model fused update over flat buffers.

    x, g, z: [G, K, N]; y: [G, N] (broadcast over clients via the index
    map); mask: optional [G, K] 0/1 participation gate -- frozen replicas
    keep their exact bits. Returns the updated [G, K, N] buffer.
    """
    G, K, n = x.shape
    dtype = x.dtype
    rows = -(-n // LANE)
    # Clamp the block to the (8-row aligned) model size so small models do
    # not pay a 1024-row pad; one pad for the entire model either way.
    br = min(block_rows, -(-rows // 8) * 8)
    rows_p = -(-rows // br) * br
    pad = rows_p * LANE - n

    def prep(a, lead):
        a = a.reshape(lead + (n,))
        if pad:
            a = jnp.pad(a, [(0, 0)] * len(lead) + [(0, pad)])
        return a.reshape(lead + (rows_p, LANE))

    xs, gs, zs = (prep(a, (G * K,)) for a in
                  (x.reshape(G * K, n), g.reshape(G * K, n), z.reshape(G * K, n)))
    ys = prep(y, (G,))
    grid = (G * K, rows_p // br)
    ck_spec = pl.BlockSpec((1, br, LANE), lambda i, j: (i, j, 0))
    in_specs = [ck_spec, ck_spec, ck_spec,
                pl.BlockSpec((1, br, LANE), lambda i, j: (i // K, j, 0))]
    operands = [xs, gs, zs, ys]
    if mask is not None:
        # The per-row scalar is an (R, 1, 1) operand with a (1, 1, 1)
        # block: Mosaic needs a block's last two dims to be multiples of
        # (8, 128) or equal to the array's.
        in_specs.append(pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0)))
        operands.append(mask.reshape(G * K, 1, 1).astype(jnp.float32))
        kern = functools.partial(_flat_kernel, float(lr), float(g_scale))
    else:
        kern = functools.partial(
            lambda lr_, gs_, x_, g_, z_, y_, o_: _flat_kernel(
                lr_, gs_, x_, g_, z_, y_, None, o_),
            float(lr), float(g_scale))
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, br, LANE), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((G * K, rows_p, LANE), dtype),
        interpret=interpret,
        name="mtgc_update_flat",
    )(*operands)
    return out.reshape(G * K, rows_p * LANE)[:, :n].reshape(G, K, n)

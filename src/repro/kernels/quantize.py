"""Batched upload-compression kernels over the flat row layout.

Both kernels consume a batch of per-row upload vectors -- ``u: [R, N]``
where a row is one client's (or one group's) whole-model delta in the
contiguous flat layout (core/packer.py) -- plus one per-row scalar
(quantization scale or top-k threshold) fed as an ``(R, 1, 1)`` operand
with a ``(1, 1, 1)`` block -- the idiom the fused MTGC kernel uses for
participation masks, and the one the TPU compiler accepts -- so
the scalar is read once per grid row and the quantize -> dequantize
round trip happens entirely in-register: the int8 payload is never
materialized in HBM (bytes on the wire are accounted analytically in
``core/compression.py``).

* :func:`int8_roundtrip` -- stochastic rounding to int8 and back:
  ``q = clip(floor(u / scale + noise), -127, 127)``, ``deq = q * scale``
  with ``noise ~ U[0, 1)`` drawn outside the kernel from the carried
  state rng (an explicit operand keeps pallas/interpret/ref bit-exact).
  With ``scale = amax(|row|) / 127`` the clip never binds; it guards the
  zero-row ``scale = 1`` fallback.

* :func:`topk_mask` -- magnitude sparsification: keep entries with
  ``|u| >= thresh`` (the per-row k-th largest magnitude, computed outside
  via ``jax.lax.top_k``), zero the rest. Ties at the threshold are all
  kept, so the realized density can exceed k/N by the tie count.

Layout matches ``mtgc_update_flat``: rows flatten to (rows, 128) lanes,
block rows clamp to the 8-aligned model size, one lane-pad for the whole
batch. Padding lanes are zero in every operand, and both kernel bodies
map zero inputs to zero outputs (``floor(0 + noise) = 0`` for
``noise < 1``), so the pad never leaks into real lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
DEFAULT_BLOCK_ROWS = 1024


def _geometry(n: int, block_rows: int):
    rows = -(-n // LANE)
    br = min(block_rows, -(-rows // 8) * 8)
    rows_p = -(-rows // br) * br
    return br, rows_p, rows_p * LANE - n


def _prep(a, R: int, n: int, rows_p: int, pad: int):
    a = a.reshape(R, n)
    if pad:
        a = jnp.pad(a, [(0, 0), (0, pad)])
    return a.reshape(R, rows_p, LANE)


def _int8_kernel(u_ref, n_ref, s_ref, o_ref):
    scale = s_ref[0, 0, 0]
    u = u_ref[...].astype(jnp.float32)
    q = jnp.floor(u / scale + n_ref[...].astype(jnp.float32))
    q = jnp.clip(q, -127.0, 127.0)
    o_ref[...] = (q * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def int8_roundtrip(u, scale, noise, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                   interpret: bool = False):
    """Stochastic int8 quantize + dequantize. u/noise: [R, N]; scale: [R]."""
    R, n = u.shape
    dtype = u.dtype
    br, rows_p, pad = _geometry(n, block_rows)
    us = _prep(u, R, n, rows_p, pad)
    ns = _prep(noise, R, n, rows_p, pad)
    grid = (R, rows_p // br)
    row_spec = pl.BlockSpec((1, br, LANE), lambda i, j: (i, j, 0))
    out = pl.pallas_call(
        _int8_kernel,
        grid=grid,
        in_specs=[row_spec, row_spec,
                  pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((R, rows_p, LANE), dtype),
        interpret=interpret,
        name="int8_roundtrip",
    )(us, ns, scale.reshape(R, 1, 1).astype(jnp.float32))
    return out.reshape(R, rows_p * LANE)[:, :n]


def _topk_kernel(u_ref, t_ref, o_ref):
    thresh = t_ref[0, 0, 0]
    u = u_ref[...]
    o_ref[...] = jnp.where(jnp.abs(u) >= thresh, u,
                           jnp.zeros_like(u)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def topk_mask(u, thresh, *, block_rows: int = DEFAULT_BLOCK_ROWS,
              interpret: bool = False):
    """Keep entries with |u| >= per-row thresh, zero the rest. u: [R, N]."""
    R, n = u.shape
    dtype = u.dtype
    br, rows_p, pad = _geometry(n, block_rows)
    us = _prep(u, R, n, rows_p, pad)
    grid = (R, rows_p // br)
    row_spec = pl.BlockSpec((1, br, LANE), lambda i, j: (i, j, 0))
    out = pl.pallas_call(
        _topk_kernel,
        grid=grid,
        in_specs=[row_spec, pl.BlockSpec((1, 1, 1), lambda i, j: (i, 0, 0))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((R, rows_p, LANE), dtype),
        interpret=interpret,
        name="topk_mask",
    )(us, thresh.reshape(R, 1, 1).astype(dtype))
    return out.reshape(R, rows_p * LANE)[:, :n]

"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode and the jnp references accept block shapes that the TPU
compiler refuses, so every kernel on the training path is compiled here
at real width against a described (not attached) ``v5e:2x2`` topology.
Nothing runs; a compile that passes proves only that Mosaic accepts the
kernel. Widths: the paper's cross-device CNN (``models/small.py``
``cnn_cifar`` at 32x32x3, N = 2,156,490 parameters) on its Sec. 5.1
topology of G=10 groups x K=10 clients.

The topology is described inside a module-scoped fixture, never at
import time: only one process at a time may load the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.mtgc_update import mtgc_update, mtgc_update_flat
from repro.kernels.quantize import int8_roundtrip, topk_mask

G, K, N = 10, 10, 2_156_490


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; return the optimized HLO."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
def test_mtgc_update_flat_compiles(one_chip, masked):
    big = _sds((G, K, N), jnp.float32, one_chip)
    y = _sds((G, N), jnp.float32, one_chip)
    if masked:
        mask = _sds((G, K), jnp.float32, one_chip)
        fn = lambda x, g, z, y, m: mtgc_update_flat(x, g, z, y, m, lr=0.05)
        hlo = _compile(fn, big, big, big, y, mask)
    else:
        fn = lambda x, g, z, y: mtgc_update_flat(x, g, z, y, lr=0.05)
        hlo = _compile(fn, big, big, big, y)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("corr_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16_zy"])
def test_mtgc_update_leaf_compiles(one_chip, corr_dtype):
    x = _sds((N,), jnp.float32, one_chip)
    zy = _sds((N,), corr_dtype, one_chip)
    fn = lambda x, g, z, y: mtgc_update(x, g, z, y, lr=0.05)
    assert "tpu_custom_call" in _compile(fn, x, x, zy, zy)


@pytest.mark.parametrize("rows", [G * K, G], ids=["client_link", "group_link"])
def test_int8_roundtrip_compiles(one_chip, rows):
    u = _sds((rows, N), jnp.float32, one_chip)
    scale = _sds((rows,), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compile(int8_roundtrip, u, scale, u)


@pytest.mark.parametrize("rows", [G * K, G], ids=["client_link", "group_link"])
def test_topk_mask_compiles(one_chip, rows):
    u = _sds((rows, N), jnp.float32, one_chip)
    thresh = _sds((rows,), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compile(topk_mask, u, thresh)

"""Plain forward pass of the CIFAR CNN, built from the configuration alone.

conv5x5 (SAME) + bias, ReLU, 2x2 max pool; the same with the second width;
flatten; dense + ReLU; dense to the class logits. It imports nothing of the
program. ``init_weights`` makes the run's weights (He-normal weights, zero
biases) in the tree layout the program's model takes, so one set of weights
feeds both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_weights(cfg: dict, key: jax.Array) -> dict:
    h, w, c = cfg["image_shape"]
    c1, c2 = cfg["conv_channels"]
    k = cfg["conv_kernel"]
    ks = jax.random.split(key, 4)

    def he(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5

    flat = (h // 4) * (w // 4) * c2
    return {
        "c1": {"w": he(ks[0], (k, k, c, c1), k * k * c),
               "b": jnp.zeros((c1,), jnp.float32)},
        "c2": {"w": he(ks[1], (k, k, c1, c2), k * k * c1),
               "b": jnp.zeros((c2,), jnp.float32)},
        "f1": {"w": he(ks[2], (flat, cfg["fc_hidden"]), flat),
               "b": jnp.zeros((cfg["fc_hidden"],), jnp.float32)},
        "out": {"w": he(ks[3], (cfg["fc_hidden"], cfg["num_classes"]),
                        cfg["fc_hidden"]),
                "b": jnp.zeros((cfg["num_classes"],), jnp.float32)},
    }


def _conv(x, p):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _pool2(x):
    n, h, w, c = x.shape
    return x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(cfg: dict, p: dict, x: jax.Array) -> jax.Array:
    """Logits ``[B, classes]`` of flat images ``x [B, H*W*C]``."""
    h, w, c = cfg["image_shape"]
    x = x.reshape(x.shape[0], h, w, c)
    x = _pool2(jnp.maximum(_conv(x, p["c1"]), 0))
    x = _pool2(jnp.maximum(_conv(x, p["c2"]), 0))
    x = x.reshape(x.shape[0], -1)
    x = jnp.maximum(x @ p["f1"]["w"] + p["f1"]["b"], 0)
    return x @ p["out"]["w"] + p["out"]["b"]

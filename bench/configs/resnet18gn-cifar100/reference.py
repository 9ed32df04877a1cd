"""Plain forward pass of ResNet-18 with GroupNorm, from the configuration alone.

CIFAR ResNet-18 (He et al. 2016): a 3x3 stem conv, then per stage
``blocks_per_stage`` basic blocks (conv3x3-GN-ReLU-conv3x3-GN, added to the
shortcut, ReLU), the first block of every later stage with stride 2; a 1x1
projection conv (no norm) on the shortcut where the width changes; global
average pool; dense to the class logits. Every norm is a GroupNorm of
``gn_groups`` groups with a per-channel scale and bias (eps 1e-5). It
imports nothing of the program. ``init_weights`` makes the run's weights
(He-normal convs and dense, zero biases, unit GN scales) in the tree layout
the program's model takes, so one set of weights feeds both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GN_EPS = 1e-5


def _blocks(cfg: dict):
    """``(name, cin, width, stride)`` of every basic block, in order."""
    cin = cfg["widths"][0]
    for s, width in enumerate(cfg["widths"]):
        for b in range(cfg["blocks_per_stage"]):
            yield f"s{s}b{b}", cin, width, 2 if (b == 0 and s > 0) else 1
            cin = width


def init_weights(cfg: dict, key: jax.Array) -> dict:
    c = cfg["image_shape"][2]
    w0 = cfg["widths"][0]
    blocks = list(_blocks(cfg))
    ks = iter(jax.random.split(key, 2 + 3 * len(blocks)))

    def conv(k, cin, cout):
        w = jax.random.normal(next(ks), (k, k, cin, cout), jnp.float32)
        return {"w": w * (2.0 / (k * k * cin)) ** 0.5,
                "b": jnp.zeros((cout,), jnp.float32)}

    def gn(ch):
        return {"scale": jnp.ones((ch,), jnp.float32),
                "bias": jnp.zeros((ch,), jnp.float32)}

    p = {"stem": conv(3, c, w0), "stem_gn": gn(w0)}
    for name, cin, width, _ in blocks:
        blk = {"c1": conv(3, cin, width), "gn1": gn(width),
               "c2": conv(3, width, width), "gn2": gn(width)}
        if cin != width:
            blk["proj"] = conv(1, cin, width)
        p[name] = blk
    last, classes = cfg["widths"][-1], cfg["num_classes"]
    w = jax.random.normal(next(ks), (last, classes), jnp.float32)
    p["out"] = {"w": w * (2.0 / last) ** 0.5,
                "b": jnp.zeros((classes,), jnp.float32)}
    return p


def _conv(x, p, stride=1):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _group_norm(x, p, groups):
    n, h, w, c = x.shape
    xg = x.reshape(n, h, w, groups, c // groups)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = jnp.square(xg - mean).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + GN_EPS)
    return xg.reshape(n, h, w, c) * p["scale"] + p["bias"]


def forward(cfg: dict, p: dict, x: jax.Array) -> jax.Array:
    """Logits ``[B, classes]`` of flat images ``x [B, H*W*C]``."""
    h, w, c = cfg["image_shape"]
    groups = cfg["gn_groups"]
    x = x.reshape(x.shape[0], h, w, c)
    x = jax.nn.relu(_group_norm(_conv(x, p["stem"]), p["stem_gn"], groups))
    for name, cin, width, stride in _blocks(cfg):
        blk = p[name]
        y = jax.nn.relu(_group_norm(_conv(x, blk["c1"], stride), blk["gn1"],
                                    groups))
        y = _group_norm(_conv(y, blk["c2"]), blk["gn2"], groups)
        sc = _conv(x, blk["proj"], stride) if cin != width else x
        x = jax.nn.relu(y + sc)
    x = x.mean(axis=(1, 2))
    return x @ p["out"]["w"] + p["out"]["b"]

"""The paper's own model zoo (Sec. 5.1), in pure JAX.

* MLP: 2 hidden layers x 200 units + softmax  (EMNIST-L / Fashion-MNIST)
* CNN: the McMahan et al. CIFAR CNN            (CIFAR-10 / CINIC-10)
* ResNet-GN: ResNet with GroupNorm in place of BatchNorm (CIFAR-100);
  depth is configurable (the paper uses ResNet-18; smoke tests shrink it)
* LSTM: char-level LSTM (Shakespeare)

Each factory returns ``(init_fn(rng) -> params, apply_fn(params, x) -> logits)``.
Models are plain pytrees -- no framework dependency -- so the HFL engine can
stack them ``[G, K, ...]`` and run every client at once.

The conv models (CNN, ResNet-GN) also carry a *client-packed* apply,
``apply.packed(params, x)``: params stacked over leading client axes
``lead`` (the engine's ``[G, K]``; C clients in all), inputs ``[*lead, B,
...]``, logits ``[*lead, B, classes]``. Inside it the activations stay
``[B, H, W, C*ch]`` (client-major within the channels) from input to head:
each conv is one grouped conv over the C clients, and bias, GroupNorm,
ReLU, pools and residual adds act on that layout as it is.
``make_loss`` passes it on as ``loss.packed``, which the engine's client
step (``core/engine.py`` ``_client_grads``) takes in place of vmapping the
per-client loss over ``[G, K]``; vmap's per-op batching rules would move
the client axis between the channels and the front at every op. The MLPs
and the LSTM have no packed apply, and the engine vmaps them.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

Init = Callable[[jax.Array], dict]
Apply = Callable[[dict, jax.Array], jax.Array]


def _dense(rng, n_in, n_out, scale=None):
    scale = scale if scale is not None else (2.0 / n_in) ** 0.5
    w = scale * jax.random.normal(rng, (n_in, n_out), jnp.float32)
    return {"w": w, "b": jnp.zeros((n_out,), jnp.float32)}


def mlp(num_classes: int, input_dim: int, hidden: int = 200) -> Tuple[Init, Apply]:
    def init(rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "l1": _dense(k1, input_dim, hidden),
            "l2": _dense(k2, hidden, hidden),
            "out": _dense(k3, hidden, num_classes),
        }

    def apply(p, x):
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ p["l1"]["w"] + p["l1"]["b"])
        x = jax.nn.relu(x @ p["l2"]["w"] + p["l2"]["b"])
        return x @ p["out"]["w"] + p["out"]["b"]

    return init, apply


def deep_mlp(num_classes: int, input_dim: int, hidden: int = 32,
             depth: int = 48) -> Tuple[Init, Apply]:
    """Deep, narrow MLP: ``depth`` hidden layers of ``hidden`` units.

    The leaf-rich stress model for the round engines: per-parameter work is
    tiny while the leaf count is ~``2 * depth``, so per-leaf dispatch and
    trace cost dominate -- exactly the regime the flat-state hot path
    (core/packer.py) collapses. Used by benchmarks/bench_round.py.
    """

    def init(rng):
        ks = jax.random.split(rng, depth + 2)
        p = {"in": _dense(ks[0], input_dim, hidden)}
        for i in range(depth):
            p[f"h{i:03d}"] = _dense(ks[i + 1], hidden, hidden)
        p["out"] = _dense(ks[-1], hidden, num_classes)
        return p

    def apply(p, x):
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ p["in"]["w"] + p["in"]["b"])
        for i in range(depth):
            x = jax.nn.relu(x @ p[f"h{i:03d}"]["w"] + p[f"h{i:03d}"]["b"])
        return x @ p["out"]["w"] + p["out"]["b"]

    return init, apply


def _conv(rng, kh, kw, cin, cout):
    scale = (2.0 / (kh * kw * cin)) ** 0.5
    return {
        "w": scale * jax.random.normal(rng, (kh, kw, cin, cout), jnp.float32),
        "b": jnp.zeros((cout,), jnp.float32),
    }


def _apply_conv(p, x, stride=1, padding="SAME"):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + p["b"]


def _clients(v, per_client_ndim):
    """``(lead, C)`` of a leaf stacked over leading client axes ``lead``."""
    lead = v.shape[:v.ndim - per_client_ndim]
    return lead, math.prod(lead)


def _pack_input(x, lead, image_shape):
    """``[*lead, B, ...]`` client inputs -> one ``[B, H, W, C*c]`` image batch,
    each client's channels together (the one input-sized move)."""
    h, w, c = image_shape
    n = math.prod(lead)
    bsz = x.shape[len(lead)]
    x = jnp.moveaxis(x.reshape(n, bsz, h, w, c), 0, 3)
    return x.reshape(bsz, h, w, n * c)


def _apply_conv_packed(p, x, stride=1, padding="SAME"):
    """Every client's conv as one grouped conv: ``x`` is ``[B, H, W, C*cin]``
    and ``p`` is stacked over the clients; the output is ``[B, H', W',
    C*cout]`` in the same client-major channel order."""
    _, n = _clients(p["w"], 4)
    kh, kw, cin, cout = p["w"].shape[-4:]
    w = p["w"].reshape(n, kh, kw, cin, cout)
    w = jnp.moveaxis(w, 0, 3).reshape(kh, kw, cin, n * cout)
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=n,
    )
    return y + p["b"].reshape(-1)


def _dense_packed(p, x):
    """Per-client dense layer: ``x`` ``[*lead, B, in]`` -> ``[*lead, B,
    out]``, batched over the clients' own axes. Not over one merged ``[C,
    out]`` axis: with a few classes the TPU compiler then lays the bias out
    with the clients along the lanes, and copies the whole flat ``[G, K,
    N]`` state into that tiling to slice it."""
    return jnp.einsum("...bi,...io->...bo", x, p["w"]) + p["b"][..., None, :]


def _max_pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def cnn(num_classes: int, image_shape=(8, 8, 1)) -> Tuple[Init, Apply]:
    """McMahan-style CNN: conv5x32 - pool - conv5x64 - pool - fc512 - fc."""
    h, w, c = image_shape

    def init(rng):
        ks = jax.random.split(rng, 4)
        flat = (h // 4) * (w // 4) * 64
        return {
            "c1": _conv(ks[0], 5, 5, c, 32),
            "c2": _conv(ks[1], 5, 5, 32, 64),
            "f1": _dense(ks[2], flat, 512),
            "out": _dense(ks[3], 512, num_classes),
        }

    def apply(p, x):
        x = x.reshape(x.shape[0], h, w, c)
        x = jax.nn.relu(_apply_conv(p["c1"], x))
        x = _max_pool(x)
        x = jax.nn.relu(_apply_conv(p["c2"], x))
        x = _max_pool(x)
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ p["f1"]["w"] + p["f1"]["b"])
        return x @ p["out"]["w"] + p["out"]["b"]

    def apply_packed(p, x):
        lead, n = _clients(p["c1"]["b"], 1)
        x = _pack_input(x, lead, image_shape)
        x = _max_pool(jax.nn.relu(_apply_conv_packed(p["c1"], x)))
        x = _max_pool(jax.nn.relu(_apply_conv_packed(p["c2"], x)))
        # fc1 reads each client's (h, w, c) flatten: one move to [C, B, F].
        bsz, hh, ww, nc = x.shape
        x = jnp.moveaxis(x.reshape(bsz, hh, ww, n, nc // n), 3, 0)
        x = jax.nn.relu(_dense_packed(p["f1"], x.reshape(lead + (bsz, -1))))
        return _dense_packed(p["out"], x)

    apply.packed = apply_packed
    return init, apply


def _groupnorm(p, x, groups):
    n, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(n, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + 1e-5)
    x = xg.reshape(n, h, w, c)
    return x * p["scale"] + p["bias"]


def _groupnorm_packed(p, x, groups):
    """GroupNorm of each client on the packed ``[B, H, W, C*c]`` layout,
    without reshaping the activation: statistics reduce over H and W first,
    then over each group's channels on the small ``[B, C*c]`` array, and
    broadcast back. The variance is two-pass, as ``jnp.var``'s: the mean of
    (x - mu)^2. Differentiated by ``_gn_packed``'s analytic backward."""
    _, n = _clients(p["scale"], 1)
    g = min(groups, x.shape[-1] // n)
    return _gn_packed(x, p["scale"].reshape(-1), p["bias"].reshape(-1), n * g)


def _group_mean(t, ng):
    """``[B, n*c]`` -> each channel the mean over its group's channels."""
    s = t.reshape(t.shape[0], ng, -1)
    return jnp.broadcast_to(s.mean(axis=-1, keepdims=True), s.shape).reshape(
        t.shape)


def _gn_packed_fwd(x, scale, bias, ng):
    """Output and residuals ``(x, mu, rstd, scale)``; ``mu`` and ``rstd``
    are ``[B, n*c]``, each channel its group's."""
    with jax.named_scope("groupnorm"):
        mu = _group_mean(x.mean(axis=(1, 2)), ng)
        d = x - mu[:, None, None, :]
        var = _group_mean(jax.lax.square(d).mean(axis=(1, 2)), ng)
        rstd = jax.lax.rsqrt(var + 1e-5)
        y = d * rstd[:, None, None, :] * scale + bias
    return y, (x, mu, rstd, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gn_packed(x, scale, bias, ng):
    return _gn_packed_fwd(x, scale, bias, ng)[0]


def _gn_packed_bwd(ng, res, dy):
    """Analytic backward. The per-image sums s1 = sum_HW dy, s2 = sum_HW
    dy d and s3 = sum_HW d, with d = x - mu, read only dy and x; the scale
    and bias gradients and dx's group terms follow from them on ``[B,
    n*c]``, and one elementwise pass gives dx. It reduces dy d, never
    dy x - mu s1, and keeps s3, the rounding mean of d that autodiff of the
    two-pass form carries into dx: so it is as precise as that form."""
    x, mu, rstd, scale = res
    with jax.named_scope("groupnorm"):
        hw = x.shape[1] * x.shape[2]
        d = x - mu[:, None, None, :]
        s1 = dy.sum(axis=(1, 2))
        s2 = (dy * d).sum(axis=(1, 2))
        s3 = d.sum(axis=(1, 2))
        a = _group_mean(scale * s1, ng) / hw            # mean of dy scale
        b = _group_mean(scale * rstd * s2, ng) / hw     # of dy scale xhat
        c = rstd * (a - rstd * b * _group_mean(s3, ng) / hw)
        dx = (rstd[:, None, None, :] * scale * dy - c[:, None, None, :]
              - (rstd * rstd * b)[:, None, None, :] * d)
        return dx, (rstd * s2).sum(axis=0), s1.sum(axis=0)


_gn_packed.defvjp(_gn_packed_fwd, _gn_packed_bwd)


def _gn_params(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def resnet_gn(
    num_classes: int,
    image_shape=(8, 8, 3),
    widths=(16, 32, 64),
    blocks_per_stage: int = 2,
    gn_groups: int = 8,
) -> Tuple[Init, Apply]:
    """ResNet with GroupNorm (paper CIFAR-100 config modulo width/depth)."""
    h, w, c = image_shape

    def init(rng):
        ks = iter(jax.random.split(rng, 4 + 6 * len(widths) * blocks_per_stage))
        p = {"stem": _conv(next(ks), 3, 3, c, widths[0]), "stem_gn": _gn_params(widths[0])}
        cin = widths[0]
        for s, width in enumerate(widths):
            for b in range(blocks_per_stage):
                blk = {
                    "c1": _conv(next(ks), 3, 3, cin, width),
                    "gn1": _gn_params(width),
                    "c2": _conv(next(ks), 3, 3, width, width),
                    "gn2": _gn_params(width),
                }
                if cin != width:
                    blk["proj"] = _conv(next(ks), 1, 1, cin, width)
                p[f"s{s}b{b}"] = blk
                cin = width
        p["out"] = _dense(next(ks), cin, num_classes)
        return p

    def apply(p, x):
        x = x.reshape(x.shape[0], h, w, c)
        x = jax.nn.relu(_groupnorm(p["stem_gn"], _apply_conv(p["stem"], x), gn_groups))
        cin = widths[0]
        for s, width in enumerate(widths):
            for b in range(blocks_per_stage):
                blk = p[f"s{s}b{b}"]
                stride = 2 if (b == 0 and s > 0) else 1
                y = jax.nn.relu(
                    _groupnorm(blk["gn1"], _apply_conv(blk["c1"], x, stride), gn_groups))
                y = _groupnorm(blk["gn2"], _apply_conv(blk["c2"], y), gn_groups)
                sc = x if "proj" not in blk else _apply_conv(blk["proj"], x, stride)
                if stride != 1 and "proj" not in blk:
                    sc = sc[:, ::2, ::2, :]
                x = jax.nn.relu(y + sc)
                cin = width
        x = x.mean(axis=(1, 2))
        return x @ p["out"]["w"] + p["out"]["b"]

    def apply_packed(p, x):
        lead, n = _clients(p["stem"]["b"], 1)

        def conv_gn(pc, pg, x, stride=1):
            return _groupnorm_packed(pg, _apply_conv_packed(pc, x, stride),
                                     gn_groups)

        x = jax.nn.relu(conv_gn(p["stem"], p["stem_gn"],
                                _pack_input(x, lead, image_shape)))
        for s in range(len(widths)):
            for b in range(blocks_per_stage):
                blk = p[f"s{s}b{b}"]
                stride = 2 if (b == 0 and s > 0) else 1
                y = jax.nn.relu(conv_gn(blk["c1"], blk["gn1"], x, stride))
                y = conv_gn(blk["c2"], blk["gn2"], y)
                sc = x if "proj" not in blk else _apply_conv_packed(
                    blk["proj"], x, stride)
                if stride != 1 and "proj" not in blk:
                    sc = sc[:, ::2, ::2, :]
                x = jax.nn.relu(y + sc)
        bsz, nc = x.shape[0], x.shape[-1]
        x = x.mean(axis=(1, 2)).reshape(bsz, n, nc // n)
        return _dense_packed(p["out"], jnp.moveaxis(x, 0, 1).reshape(
            lead + (bsz, -1)))

    apply.packed = apply_packed
    return init, apply


def lstm(vocab: int, hidden: int = 128, embed: int = 32) -> Tuple[Init, Apply]:
    """Char-LSTM for next-token prediction (paper Shakespeare config)."""

    def init(rng):
        ks = jax.random.split(rng, 4)
        return {
            "emb": 0.02 * jax.random.normal(ks[0], (vocab, embed), jnp.float32),
            "wx": _dense(ks[1], embed, 4 * hidden),
            "wh": _dense(ks[2], hidden, 4 * hidden, scale=(1.0 / hidden) ** 0.5),
            "out": _dense(ks[3], hidden, vocab),
        }

    def apply(p, x):
        # x: [B, T] int tokens -> logits [B, T, vocab]
        e = p["emb"][x]                       # [B, T, E]
        B = x.shape[0]
        h0 = jnp.zeros((B, p["wh"]["w"].shape[0]), jnp.float32)
        c0 = jnp.zeros_like(h0)

        def step(carry, et):
            h, c = carry
            gates = et @ p["wx"]["w"] + p["wx"]["b"] + h @ p["wh"]["w"] + p["wh"]["b"]
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        _, hs = jax.lax.scan(step, (h0, c0), e.transpose(1, 0, 2))
        hs = hs.transpose(1, 0, 2)            # [B, T, H]
        return hs @ p["out"]["w"] + p["out"]["b"]

    return init, apply


def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def make_loss(apply: Apply) -> Callable[[dict, dict], jax.Array]:
    """Standard classification / next-token loss over {'x','y'} batches.

    Where ``apply`` has a client-packed form (``apply.packed``), the loss
    carries one too: ``loss.packed(params, batch)`` takes params stacked
    over leading client axes ``lead`` and batch leaves ``[*lead, B, ...]``,
    and returns each client's mean loss, ``[*lead]``."""

    def loss(params, batch):
        logits = apply(params, batch["x"])
        return softmax_xent(logits, batch["y"])

    packed = getattr(apply, "packed", None)
    if packed is not None:
        def packed_loss(params, batch):
            logp = jax.nn.log_softmax(packed(params, batch["x"]), axis=-1)
            nll = -jnp.take_along_axis(logp, batch["y"][..., None], axis=-1)
            return nll.mean(axis=(-2, -1))

        loss.packed = packed_loss
    return loss


def jit_accuracy(apply: Apply, x: jax.Array, y: jax.Array):
    """Jit-traceable eval accuracy over the full (x, y) set: ``params ->
    scalar``.

    The traceable counterpart of :func:`accuracy` (which streams batches on
    the host and cannot be jitted): meant to be traced *inside* an already
    jitted program, e.g. the horizon driver's ``eval_fn`` (core/driver.py).
    Standalone callers should wrap it in ``jax.jit`` themselves and need
    the whole eval set to fit in one forward pass.
    """

    def acc(params) -> jax.Array:
        pred = jnp.argmax(apply(params, x), axis=-1)
        return jnp.mean((pred == y).astype(jnp.float32))

    return acc


def accuracy(apply: Apply, params, x, y, batch: int = 512) -> float:
    """Streaming eval accuracy."""
    n = x.shape[0]
    correct = 0
    for i in range(0, n, batch):
        logits = apply(params, x[i : i + batch])
        pred = jnp.argmax(logits, -1)
        yy = y[i : i + batch]
        correct += int((pred == yy).sum())
    return correct / y.size

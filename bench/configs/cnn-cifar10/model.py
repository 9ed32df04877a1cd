"""The CIFAR CNN as the program builds it, and its FLOP count."""
from __future__ import annotations


def program_loss(cfg: dict):
    """``(loss_fn, init_fn)`` of ``repro.models.small.cnn`` at ``cfg``'s sizes."""
    from repro.models.small import cnn, make_loss

    init, apply = cnn(cfg["num_classes"], tuple(cfg["image_shape"]))
    return make_loss(apply), init


def forward_flops(cfg: dict) -> int:
    """Forward FLOPs of one sample: 2 x the multiply-adds of each conv and
    dense layer. Biases, ReLUs and pools are not counted."""
    h, w, c = cfg["image_shape"]
    c1, c2 = cfg["conv_channels"]
    k = cfg["conv_kernel"]
    macs = (h * w * k * k * c * c1                                  # conv1
            + (h // 2) * (w // 2) * k * k * c1 * c2                  # conv2
            + (h // 4) * (w // 4) * c2 * cfg["fc_hidden"]            # fc1
            + cfg["fc_hidden"] * cfg["num_classes"])                 # out
    return 2 * macs

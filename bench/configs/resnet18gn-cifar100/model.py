"""ResNet-18 with GroupNorm as the program builds it, and its FLOP count."""
from __future__ import annotations


def program_loss(cfg: dict):
    """``(loss_fn, init_fn)`` of ``repro.models.small.resnet_gn`` at
    ``cfg``'s sizes."""
    from repro.models.small import make_loss, resnet_gn

    init, apply = resnet_gn(cfg["num_classes"], tuple(cfg["image_shape"]),
                            widths=tuple(cfg["widths"]),
                            blocks_per_stage=cfg["blocks_per_stage"],
                            gn_groups=cfg["gn_groups"])
    return make_loss(apply), init


def forward_flops(cfg: dict) -> int:
    """Forward FLOPs of one sample: 2 x the multiply-adds of each conv
    (stem, block convs, projection shortcuts) and of the dense head. Norms,
    biases, ReLUs, residual adds and the pool are not counted."""
    h, w, c = cfg["image_shape"]
    widths = cfg["widths"]
    macs = h * w * 9 * c * widths[0]                                 # stem
    cin = widths[0]
    for s, width in enumerate(widths):
        for b in range(cfg["blocks_per_stage"]):
            if b == 0 and s > 0:
                h, w = h // 2, w // 2
            macs += h * w * 9 * cin * width                          # c1
            macs += h * w * 9 * width * width                        # c2
            if cin != width:
                macs += h * w * cin * width                          # proj
            cin = width
    macs += cin * cfg["num_classes"]                                 # out
    return 2 * macs

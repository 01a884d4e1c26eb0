"""ResNet (basic blocks, CIFAR stem) from `stage_planes`, `blocks_per_stage`,
`image_size`, `image_channels`, `num_classes`."""

from __future__ import annotations


def forward_macs_per_image(config: dict, traffic: dict = None) -> int:
    size, cin = int(config["image_size"]), int(config["image_channels"])
    macs = size * size * 9 * cin * 64  # 3x3 stem, stride 1
    cin = 64
    for stage, (planes, n) in enumerate(zip(config["stage_planes"],
                                            config["blocks_per_stage"])):
        for i in range(n):
            stride = 2 if (stage > 0 and i == 0) else 1
            size //= stride
            macs += size * size * 9 * cin * planes      # conv1
            macs += size * size * 9 * planes * planes   # conv2
            if stride != 1 or cin != planes:
                macs += size * size * cin * planes      # 1x1 shortcut
            cin = planes
    return macs + cin * int(config["num_classes"])


def train_flops_per_item(config: dict, traffic: dict = None) -> float:
    """Forward and backward: three times the forward's multiply-adds."""
    return 3 * 2 * forward_macs_per_image(config)

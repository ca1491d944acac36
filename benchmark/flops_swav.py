"""Operations of SwAV on a ResNet trunk, computed from shapes — beside
``flops.py`` (ALBERT) and the decoders' ``flops_*.py``.

Model FLOPs are convolutions and matmuls only, backward = 2x forward,
recomputation not counted: every convolution's 2·k²·C_in·C_out·H_out·W_out
at each crop size (the bottleneck trunk as ``models/resnet.py`` builds it —
a 7x7 stem at stride 2, a 3x3 max pool at stride 2, stages of 1x1 reduce ->
3x3 -> 1x1 expand at 4x the stage's width, the stride on the 3x3 of a
stage's first block and on its 1x1 shortcut projection), then the projection
MLP and the prototypes on the pooled features of every crop. Batch norm,
ReLU, pooling, the L2 normalisation, Sinkhorn and the loss are element-wise
or reductions and count nothing.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

# bottleneck blocks a stage, by the configuration's ``trunk_blocks``:
# ResNet-50's layout and the program's tiny preset's
STAGE_SIZES = {16: (3, 4, 6, 3), 4: (1, 1, 1, 1)}


def _out(size: int, kernel: int, stride: int) -> int:
    """Output side of a convolution or pool padded by ``kernel // 2``."""
    return (size + 2 * (kernel // 2) - kernel) // stride + 1


def trunk_convolutions(
    stage_sizes: Sequence[int], width: int, size: int, channels: int = 3,
) -> List[Tuple[str, int, int, int, int]]:
    """(name, kernel side, C_in, C_out, output side) of every convolution of
    the trunk on one square image of ``size``."""
    side = _out(size, 7, 2)
    convs = [("stem_conv", 7, channels, width, side)]
    side = _out(side, 3, 2)  # the max pool
    c_in = width
    for stage, blocks in enumerate(stage_sizes):
        features = width * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            name = f"stage{stage}_block{block}"
            out = _out(side, 3, stride)
            convs.append((f"{name}/reduce", 1, c_in, features, side))
            convs.append((f"{name}/conv3x3", 3, features, features, out))
            convs.append((f"{name}/expand", 1, features, 4 * features, out))
            if stride != 1 or c_in != 4 * features:
                convs.append((f"{name}/proj", 1, c_in, 4 * features, out))
            side, c_in = out, 4 * features
    return convs


def trunk_macs_per_image(stage_sizes: Sequence[int], width: int,
                         size: int) -> float:
    """Multiply-adds of the trunk's convolutions on one image of ``size``
    (ResNet-50 at 224: 4.09 G, the figure the literature quotes)."""
    return float(sum(
        k * k * c_in * c_out * side * side
        for _name, k, c_in, c_out, side in trunk_convolutions(
            stage_sizes, width, size
        )
    ))


def head_macs_per_crop(width: int, proj_hidden_dim: int, proj_out_dim: int,
                       num_prototypes: int) -> float:
    """Multiply-adds of the projection MLP (trunk features -> hidden -> out)
    and the prototypes on ONE crop's pooled features."""
    features = width * 8 * 4  # the last stage's width x the expansion
    return float(
        features * proj_hidden_dim + proj_hidden_dim * proj_out_dim
        + proj_out_dim * num_prototypes
    )


def swav_parts_flops_per_sample(config: Dict) -> Dict[str, float]:
    """Forward FLOPs of one SAMPLE (an image's ``sum(crop_counts)`` crops) by
    part; ``config``: the configuration file (``sizes``, ``crop_sizes``,
    ``crop_counts``)."""
    sizes = config["sizes"]
    stages = STAGE_SIZES[sizes["trunk_blocks"]]
    crops = list(zip(config["crop_sizes"], config["crop_counts"]))
    if sum(count for _size, count in crops) != sizes["num_crops"]:
        raise ValueError("crop_counts do not add up to sizes['num_crops']")
    parts = {
        f"trunk_{size}": 2.0 * count * trunk_macs_per_image(
            stages, sizes["width"], size
        )
        for size, count in crops
    }
    parts["head"] = 2.0 * sizes["num_crops"] * head_macs_per_crop(
        sizes["width"], sizes["proj_hidden_dim"], sizes["proj_out_dim"],
        sizes["num_prototypes"],
    )
    return parts


def swav_train_flops_per_sample(config: Dict) -> float:
    """Model FLOPs of one forward + backward sample (backward = 2x
    forward)."""
    return 3.0 * sum(swav_parts_flops_per_sample(config).values())

"""``flops_swav.py`` against the figure the literature quotes for ResNet-50
and against a count walked off the program's own model: every ``kernel`` of
``models/swav.py``'s parameter tree times the positions of the output its
module produced, at the tiny preset — a changed model cannot leave the
count behind — and the reducer that reads it."""
import json
import math
import os
import types

import pytest

from benchmark import flops_swav
from benchmark.reducers import swav_mfu

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config():
    with open(os.path.join(HERE, "configs", "swav_rn50.json")) as f:
        return json.load(f)


def test_resnet50_by_the_literature_and_by_hand():
    stages = flops_swav.STAGE_SIZES[16]
    macs = flops_swav.trunk_macs_per_image(stages, 64, 224)
    # 4.09 GMAC with the 2048 x 1000 classifier (2.05 M), which SwAV drops
    assert macs == pytest.approx(4.09e9, rel=0.02)
    convs = flops_swav.trunk_convolutions(stages, 64, 224)
    assert len(convs) == 1 + 3 * 16 + 4  # stem, three a block, a shortcut a stage
    assert convs[0] == ("stem_conv", 7, 3, 64, 112)
    assert convs[1] == ("stage0_block0/reduce", 1, 64, 64, 56)
    # the stride sits on the 3x3 of a stage's first block and on its shortcut
    by_name = {name: rest for name, *rest in convs}
    assert by_name["stage1_block0/reduce"] == [1, 256, 128, 56]
    assert by_name["stage1_block0/conv3x3"] == [3, 128, 128, 28]
    assert by_name["stage1_block0/proj"] == [1, 256, 512, 28]
    assert by_name["stage3_block2/expand"] == [1, 512, 2048, 7]
    # a 96 crop: 48 after the stem, 24, 12, 6, 3 — (96 / 224)² of the work
    small = flops_swav.trunk_macs_per_image(stages, 64, 96)
    assert small == pytest.approx(macs * (96 / 224) ** 2, rel=1e-9)
    config = _config()
    parts = flops_swav.swav_parts_flops_per_sample(config)
    assert parts["trunk_224"] == 2 * 2 * macs
    assert parts["trunk_96"] == 2 * 6 * small
    assert parts["head"] == 2 * 8 * (2048 * 2048 + 2048 * 128 + 128 * 3000)
    total = flops_swav.swav_train_flops_per_sample(config)
    assert total == 3 * sum(parts.values())
    assert total == pytest.approx(76.3e9, rel=1e-3)  # 25.4 GFLOP forward
    with pytest.raises(ValueError):
        flops_swav.swav_parts_flops_per_sample(dict(config, crop_counts=[2, 5]))


def test_the_count_is_the_programs_own_model_walked():
    import jax
    import jax.numpy as jnp

    from dedloc_tpu.models.swav import SwAVConfig, SwAVModel

    cfg = SwAVConfig.tiny()
    images, sizes, counts = 2, (32, 16), (2, 2)
    crops = [
        jnp.zeros((images * count, size, size, 3))
        for size, count in zip(sizes, counts)
    ]
    model = SwAVModel(cfg)

    def shapes(rng):
        variables = model.init(rng, crops, True)
        _out, state = model.apply(
            variables, crops, True, capture_intermediates=True,
            mutable=["batch_stats", "intermediates"],
        )
        return variables["params"], state["intermediates"]

    params, outputs = jax.eval_shape(shapes, jax.random.PRNGKey(0))
    walked = 0
    kernels = 0
    for path, kernel in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [k.key for k in path]
        if keys[-1] != "kernel":
            continue
        kernels += 1
        calls = outputs
        for key in keys[:-1]:
            calls = calls[key]
        for out in calls["__call__"]:  # the trunk runs once a crop size
            assert out.shape[-1] == kernel.shape[-1]
            walked += math.prod(kernel.shape) * math.prod(out.shape[:-1])
    assert kernels == 1 + 3 * 4 + 4 + 3  # the trunk's, the head's three
    config = {
        "sizes": {
            "width": cfg.trunk.width,
            "trunk_blocks": sum(cfg.trunk.stage_sizes),
            "proj_hidden_dim": cfg.proj_dims[1],
            "proj_out_dim": cfg.proj_dims[2],
            "num_prototypes": cfg.num_prototypes[0],
            "num_crops": cfg.num_crops,
        },
        "crop_sizes": list(sizes), "crop_counts": list(counts),
    }
    assert tuple(cfg.trunk.stage_sizes) == flops_swav.STAGE_SIZES[4]
    assert cfg.proj_dims[0] == cfg.trunk.out_features
    forward = sum(flops_swav.swav_parts_flops_per_sample(config).values())
    assert forward == 2.0 * walked / images
    # the cell's configuration names the program's ResNet-50 preset
    rn50 = SwAVConfig()
    sizes50 = _config()["sizes"]
    assert tuple(rn50.trunk.stage_sizes) == flops_swav.STAGE_SIZES[
        sizes50["trunk_blocks"]
    ]
    assert (rn50.trunk.width, rn50.proj_dims[1], rn50.proj_dims[2],
            rn50.num_prototypes[0], rn50.num_crops) == (
        sizes50["width"], sizes50["proj_hidden_dim"], sizes50["proj_out_dim"],
        sizes50["num_prototypes"], sizes50["num_crops"],
    )


def test_the_reducer_reads_the_accumulate_program_alone():
    ms = 1e6  # ns
    role = types.SimpleNamespace(
        PROGRAMS={"accumulate": "step"},
        microbatch_rows_per_device=lambda args: args,
    )

    def run(trace):
        return types.SimpleNamespace(
            trace=trace, config=_config(), args=128, role=role,
            device_kind="TPU v5 lite",
            program=lambda name: role.PROGRAMS[name],
        )

    trace = {"/device:TPU:0": {
        "XLA Modules": [("jit_step(1)", 0, 199 * ms),
                        ("jit_step(1)", 2000 * ms, 199 * ms),
                        ("jit_guarded_apply_step(2)", 3000 * ms, 5 * ms)],
        "XLA Ops": [],
    }}
    # 76.3 GFLOP x 128 images over 199 ms over 197 TFLOP/s
    assert swav_mfu.reduce(run(trace), {}) == pytest.approx(
        100 * 76.303e9 * 128 / 0.199 / 197e12, rel=1e-4
    )
    assert 0 < swav_mfu.reduce(run(trace), {}) < 100
    # no trace, or a trace without the program: nothing, never a 0
    assert swav_mfu.reduce(run(None), {}) is None
    bare = {"/device:TPU:0": {
        "XLA Modules": [("jit_other(1)", 0, ms)], "XLA Ops": [],
    }}
    assert swav_mfu.reduce(run(bare), {}) is None

"""The two float32 references against the roles' own loss and gradients, at
the tiny sizes on the CPU (the configuration's ``rehearse_tolerance``). The
same comparison runs at the published widths on the chip inside every
benchmark run, on the configuration's one fixed check seed; here two seeds."""
import json
import os

import pytest

from benchmark.roles import swav as swav_role
from benchmark.roles import trainer as trainer_role

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name, seed=None):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        config = json.load(f)
    if seed is not None:
        config["check"]["seed"] = seed
    return config


def _tiny_args(role, config):
    cell = {"name": "test", "flags": {}}
    return role.parse(role.build_argv(
        config, cell, 0, 0, "/tmp/unused", "", False, True
    ))


@pytest.mark.parametrize("seed", [0, 3])
def test_albert_reference_matches_role(seed):
    config = _config("albert_large_s512", seed)
    result = trainer_role.reference_check(
        config, _tiny_args(trainer_role, config), rehearse=True
    )
    assert result["ok"], result
    # and the check can fail: a reference with a different depth is far off
    assert result["grad_rel_l2"] > 0.0


@pytest.mark.parametrize("seed", [0, 3])
def test_swav_reference_matches_role(seed):
    config = _config("swav_rn50", seed)
    result = swav_role.reference_check(
        config, _tiny_args(swav_role, config), rehearse=True
    )
    assert result["ok"], result
    # the recipe's step (bf16 trunk) is compared too, gradients included
    assert result["recipe"]["grad_cosine"] > 0.5, result
    assert result["recipe_head"]["grad_cosine"] > 0.9, result


def test_gradient_direction_and_norm_bounds():
    """What the SwAV recipe's gradient is held to where bf16 leaves no
    relative L2 worth bounding: a cosine floor and a two-sided norm ratio.
    By hand: g = (3, 4), |g| = 5; -g has cosine -1; 2g has ratio 2; (4, 3)
    has cosine 24/25 and ratio 1."""
    import numpy as np

    from benchmark.roles.common import compare_with_reference

    g = {"w": np.array([3.0, 4.0])}
    bounds = {"grad_cosine_min": 0.9, "grad_norm_ratio_max": 1.25}

    def check(role):
        return compare_with_reference(1.0, {"w": np.array(role)}, 1.0, g, bounds)

    assert check([3.0, 4.0])["ok"]
    turned = check([4.0, 3.0])
    assert turned["ok"] and turned["grad_cosine"] == pytest.approx(0.96)
    assert turned["grad_norm_ratio"] == pytest.approx(1.0)
    assert not check([-3.0, -4.0])["ok"]  # the wrong sign
    assert not check([6.0, 8.0])["ok"]  # twice the norm
    assert not check([1.5, 2.0])["ok"]  # half the norm
    assert not check([float("nan"), 4.0])["ok"]


def test_albert_check_catches_a_wrong_model():
    """One layer fewer in the reference is a different function: the
    comparison must say so (guards against a tolerance that passes anything)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import albert as reference
    from benchmark.roles.common import compare_with_reference
    from dedloc_tpu.roles.common import (
        build_model, drop_collator_keys, synthetic_mlm_batches,
    )

    config = _config("albert_large_s512")
    cfg, model = build_model("tiny", "fused_ln", "flash")
    batch = drop_collator_keys(next(synthetic_mlm_batches(cfg, 2, 64, 0)))
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 64), jnp.int32)
    )["params"]

    def loss(layers):
        return jax.value_and_grad(lambda p: reference.loss_fn(
            p, batch, layers, cfg.num_attention_heads, cfg.layer_norm_eps
        ))(params)

    (full, g_full), (short, g_short) = loss(2), loss(1)
    result = compare_with_reference(
        short, g_short, full, g_full, config["check"]["tolerance"]
    )
    assert not result["ok"], result

"""SwAV (Caron et al., 2020) loss on a ResNet-50 trunk in plain ``jax.numpy``,
float32.

Forward: each resolution group of crops through the trunk (7x7/2 stem conv,
BN, ReLU, 3x3/2 max-pool, bottleneck stages 1x1 → 3x3 → 1x1(x4) with a
projected shortcut where the shape changes, global average pool), features
concatenated in crop order, the projection MLP (Linear → BN → ReLU → Linear),
L2 normalisation, one bias-free prototype layer. Batch norm uses the BATCH
statistics (training mode, biased variance). Loss: for each assignment crop
(the two 224-pixel views) the Sinkhorn-Knopp codes of its scores (3
iterations, epsilon 0.05, no gradient through the codes) are the targets of
every OTHER crop's softmax(scores / 0.1); averaged over crops and views. The
queue is not engaged (the recipe's first 15 epochs).

Reads the parameter tree the program trains; imports nothing from
``dedloc_tpu``. Callers run it under ``default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 1e-5


def _conv(x, kernel, stride):
    k = kernel.shape[0]
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), [(k // 2, k // 2)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _bn(x, p):
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean), axis=axes)
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def _conv_bn(x, p, stride=1, relu=True):
    y = _bn(_conv(x, p["conv"]["kernel"], stride), p["bn"])
    return jax.nn.relu(y) if relu else y


def _trunk(images, p, stage_sizes):
    x = jax.nn.relu(_bn(_conv(images, p["stem_conv"]["kernel"], 2),
                        p["stem_bn"]))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)],
    )
    for stage, blocks in enumerate(stage_sizes):
        for block in range(blocks):
            bp = p[f"stage{stage}_block{block}"]
            stride = 2 if stage > 0 and block == 0 else 1
            y = _conv_bn(x, bp["reduce"])
            y = _conv_bn(y, bp["conv3x3"], stride)
            y = _conv_bn(y, bp["expand"], relu=False)
            if "proj" in bp:
                x = _conv_bn(x, bp["proj"], stride, relu=False)
            x = jax.nn.relu(x + y)
    return jnp.mean(x, axis=(1, 2))


def sinkhorn(scores, iters, epsilon):
    n, k = scores.shape
    q = jnp.exp(scores / epsilon - jnp.max(scores / epsilon)).T
    q = q / jnp.sum(q)
    for _ in range(iters):
        q = q / (k * jnp.sum(q, axis=1, keepdims=True))
        q = q / (n * jnp.sum(q, axis=0, keepdims=True))
    q = q / jnp.sum(q, axis=0, keepdims=True)
    return jax.lax.stop_gradient(q.T)


def loss_fn(params, crops, stage_sizes=(3, 4, 6, 3), num_crops=8,
            crops_for_assign=(0, 1), temperature=0.1, epsilon=0.05,
            sinkhorn_iters=3):
    """``crops``: one [count*B, S, S, 3] float32 array per resolution group,
    crops stacked in crop order (the program's multicrop layout)."""
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    feats = jnp.concatenate(
        [_trunk(c.astype(jnp.float32), params["trunk"], stage_sizes)
         for c in crops], axis=0,
    )
    head = params["head"]
    x = feats @ head["proj0"]["kernel"] + head["proj0"]["bias"]
    x = jax.nn.relu(_bn(x, head["proj_bn0"]))
    x = x @ head["proj1"]["kernel"] + head["proj1"]["bias"]
    x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    scores = x @ head["prototypes0"]["kernel"]

    bs = scores.shape[0] // num_crops
    per_crop = [scores[i * bs:(i + 1) * bs] for i in range(num_crops)]
    total = 0.0
    for crop_id in crops_for_assign:
        codes = sinkhorn(per_crop[crop_id], sinkhorn_iters, epsilon)
        others = [p for p in range(num_crops) if p != crop_id]
        crop_loss = 0.0
        for p in others:
            logp = jax.nn.log_softmax(per_crop[p] / temperature, axis=1)
            crop_loss -= jnp.mean(jnp.sum(codes * logp, axis=1))
        total += crop_loss / len(others)
    return total / len(crops_for_assign)

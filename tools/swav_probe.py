"""SwAV sustained-run harness: texture dataset generation + linear probe.

Two subcommands around ``python -m dedloc_tpu.roles.swav``:

``generate``
    Render a class-structured JPEG dataset (oriented sinusoidal gratings:
    class = (orientation, frequency); per-image random phase, colour mix,
    contrast and pixel noise). Unlike a colour-mean fixture, a RANDOM
    trunk's pooled features do not trivially separate these classes, so the
    linear-probe delta between a trained and a random trunk measures what
    SwAV pretraining actually learned. Layout: ``<out>/class_<k>/*.jpg``
    (the class-subdir layout ``image_folder_multicrop_batches`` accepts).

``probe``
    Load the newest SwAV checkpoint from ``--checkpoint_dir``, extract
    frozen eval-mode trunk features for a held-out deterministic split of
    the same texture distribution, train the linear classifier
    (finetune/linear_probe.py — the vissl extract+linear protocol), and
    print one JSON line with trained vs random-trunk top-1.

The round-4 sustained run (BASELINE.md):

    python tools/swav_probe.py generate --out /root/corpus/swav_images
    python -m dedloc_tpu.roles.swav \
        --dht.experiment_prefix swav_r4 \
        --training.image_folder /root/corpus/swav_images \
        --training.per_device_batch_size 16 \
        --optimizer.target_batch_size 16 \
        --training.learning_rate 0.15 --training.warmup_steps 200 \
        --training.total_steps 2500 --training.max_local_steps 2500 \
        --training.queue_length 3840 --training.queue_start_step 400 \
        --training.save_steps 250 \
        --training.output_dir /root/corpus/swav_r4_out
    python tools/swav_probe.py probe \
        --checkpoint_dir /root/corpus/swav_r4_out
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def texture_image(
    rng: np.random.Generator,
    orientation: float,
    frequency: float,
    size: int,
) -> np.ndarray:
    """One grating image [size, size, 3] in [0, 255] for a (orientation,
    frequency) class, with per-image nuisance randomness."""
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    phase = rng.uniform(0, 2 * np.pi)
    angle = orientation + rng.normal(0, 0.05)
    carrier = np.sin(
        2 * np.pi * frequency * (np.cos(angle) * xx + np.sin(angle) * yy)
        + phase
    )
    contrast = rng.uniform(0.6, 1.0)
    base = rng.uniform(0.25, 0.75, size=3)  # random colour mix per image
    tint = rng.uniform(-0.25, 0.25, size=3)
    img = base[None, None, :] + contrast * 0.5 * carrier[..., None] * (
        0.6 + tint[None, None, :]
    )
    img += rng.normal(0, 0.04, img.shape)
    return (np.clip(img, 0, 1) * 255).astype(np.uint8)


def class_params(num_classes: int):
    """(orientation, frequency) grid: num_classes/2 orientations x 2 freqs."""
    n_orient = max(1, num_classes // 2)
    out = []
    for k in range(num_classes):
        orient = (k % n_orient) * np.pi / n_orient
        freq = 6.0 if k < n_orient else 14.0
        out.append((orient, freq))
    return out


def generate(args) -> None:
    from PIL import Image

    params = class_params(args.classes)
    rng = np.random.default_rng(args.seed)
    for k, (orient, freq) in enumerate(params):
        d = os.path.join(args.out, f"class_{k:02d}")
        os.makedirs(d, exist_ok=True)
        for i in range(args.per_class):
            arr = texture_image(rng, orient, freq, args.size)
            Image.fromarray(arr).save(
                os.path.join(d, f"img_{i:04d}.jpg"), quality=90
            )
    print(json.dumps({
        "generated": args.classes * args.per_class,
        "classes": args.classes, "size": args.size, "out": args.out,
    }))


def _labeled_split(num_classes: int, per_class: int, size: int, seed: int):
    """Deterministic held-out labelled images (NOT from the training files —
    fresh draws of the same distribution, the probe's train/eval data)."""
    params = class_params(num_classes)
    rng = np.random.default_rng(seed)
    images, labels = [], []
    for k, (orient, freq) in enumerate(params):
        for _ in range(per_class):
            images.append(
                texture_image(rng, orient, freq, size).astype(np.float32)
                / 255.0
            )
            labels.append(k)
    order = rng.permutation(len(images))
    return (
        np.stack(images)[order],
        np.asarray(labels, np.int32)[order],
    )


def probe(args) -> None:
    import jax

    from dedloc_tpu.finetune.linear_probe import (
        extract_features,
        run_linear_probe,
        swav_trunk_apply,
    )
    from dedloc_tpu.models.swav import SwAVConfig, SwAVModel
    from dedloc_tpu.utils.checkpoint import load_latest_checkpoint

    cfg = SwAVConfig(queue_length=0)
    model = SwAVModel(cfg)
    images, labels = _labeled_split(
        args.classes, args.probe_per_class, args.probe_size, args.seed + 777
    )
    n_train = int(0.8 * len(images))

    def probe_for(params, batch_stats, tag):
        feats = extract_features(
            swav_trunk_apply(model, params, batch_stats), images,
            batch_size=args.batch_size,
        )
        result = run_linear_probe(
            feats[:n_train], labels[:n_train],
            feats[n_train:], labels[n_train:],
            num_classes=args.classes,
        )
        return {f"{tag}_{k}": v for k, v in result.items()}

    # random-init baseline: what the probe can do with an UNtrained trunk
    rng = jax.random.PRNGKey(args.seed)
    init_crops = [np.zeros((2, 64, 64, 3), np.float32)]
    variables = model.init(rng, init_crops, True)
    out = {"checkpoint_dir": args.checkpoint_dir}
    out.update(probe_for(
        variables["params"], variables["batch_stats"], "random_trunk"
    ))

    loaded = load_latest_checkpoint(args.checkpoint_dir)
    assert loaded is not None, f"no checkpoint under {args.checkpoint_dir}"
    step, tree, _meta = loaded
    out["checkpoint_step"] = step
    # checkpoints hold tree_to_named((params, batch_stats)) — rebuild via
    # the same naming template
    from dedloc_tpu.utils.checkpoint import named_to_tree

    template = jax.device_get((variables["params"], variables["batch_stats"]))
    params, batch_stats = named_to_tree(tree, template)
    out.update(probe_for(params, batch_stats, "trained_trunk"))
    print(json.dumps(out))


def main() -> None:
    p = argparse.ArgumentParser()
    sub = p.add_subparsers(dest="cmd", required=True)
    g = sub.add_parser("generate")
    g.add_argument("--out", required=True)
    g.add_argument("--classes", type=int, default=24)
    g.add_argument("--per_class", type=int, default=120)
    g.add_argument("--size", type=int, default=224)
    g.add_argument("--seed", type=int, default=0)
    q = sub.add_parser("probe")
    q.add_argument("--checkpoint_dir", required=True)
    q.add_argument("--classes", type=int, default=24)
    q.add_argument("--probe_per_class", type=int, default=40)
    q.add_argument("--probe_size", type=int, default=128)
    q.add_argument("--batch_size", type=int, default=64)
    q.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    if args.cmd == "generate":
        generate(args)
    else:
        probe(args)


if __name__ == "__main__":
    main()

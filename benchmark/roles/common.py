"""What every role adapter shares: flags to argv, and the comparison of the
role's loss and gradients with the plain reference."""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def flags_to_argv(*flag_dicts: Dict[str, object]) -> List[str]:
    """Later dicts override earlier ones."""
    merged: Dict[str, object] = {}
    for flags in flag_dicts:
        merged.update(flags or {})
    return [str(x) for item in merged.items() for x in item]


def build_argv(config, cell, peer: int, seed: int, workdir: str,
               initial_peers: str, trace: bool, rehearse: bool) -> List[str]:
    """The role's CLI defaults, then the configuration's flags, the cell's,
    (rehearsal overrides,) and last what the harness itself
    needs: loopback DHT, the seed, a scratch directory, telemetry when traced."""
    harness = {
        "--dht.experiment_prefix": f"bench_{cell['name']}",
        "--dht.listen_host": "127.0.0.1",
        # ONE model per collaboration: every peer builds the same weights from
        # --seed (a joining peer adopts the collaboration's state); the data
        # differs per peer (the adapters seed it from --seed and the index)
        "--training.seed": seed,
        "--training.save_steps": 0,
        "--training.output_dir": f"{workdir}/peer{peer}",
    }
    if initial_peers:
        harness["--dht.initial_peers"] = initial_peers
    if trace:
        harness.update({
            "--telemetry.enabled": "true",
            "--telemetry.event_log_path": f"{workdir}/events_peer{peer}.jsonl",
        })
    layers = [config["flags"], cell.get("flags")]
    if rehearse:
        layers += [config.get("rehearse_flags"), cell.get("rehearse_flags")]
    return flags_to_argv(*layers, harness)


def compare_with_reference(role_loss, role_grads, ref_loss, ref_grads,
                           tolerance: Dict[str, float]) -> Dict[str, object]:
    """Relative loss error; relative L2 error, cosine and norm ratio of the
    whole gradient; the worst relative L2 error and the worst norm ratio
    (or its inverse) over the leaves that carry at least 1% of the gradient's
    norm (tiny leaves are all rounding). Each key of ``tolerance`` bounds one
    of them: ``loss_rel``, ``grad_rel_l2`` and ``leaf_rel_l2`` from above,
    ``grad_cosine_min`` from below, ``grad_norm_ratio_max`` and
    ``leaf_norm_ratio_max`` the ratio and its inverse. Every number is
    finite or the check fails."""
    import jax

    role_leaves = [np.asarray(x, np.float64) for x in jax.tree.leaves(role_grads)]
    ref_leaves = [np.asarray(x, np.float64) for x in jax.tree.leaves(ref_grads)]
    ref_norm = float(np.sqrt(sum(np.sum(x * x) for x in ref_leaves)))
    role_norm = float(np.sqrt(sum(np.sum(x * x) for x in role_leaves)))
    dot = float(sum(np.sum(a * b) for a, b in zip(role_leaves, ref_leaves)))
    diff_norm = float(np.sqrt(sum(
        np.sum((a - b) ** 2) for a, b in zip(role_leaves, ref_leaves)
    )))
    worst_leaf, worst_leaf_ratio = 0.0, 1.0
    for a, b in zip(role_leaves, ref_leaves):
        leaf_norm = float(np.sqrt(np.sum(b * b)))
        if leaf_norm >= 0.01 * ref_norm:
            worst_leaf = max(
                worst_leaf, float(np.sqrt(np.sum((a - b) ** 2))) / leaf_norm
            )
            leaf_ratio = float(np.sqrt(np.sum(a * a))) / leaf_norm
            worst_leaf_ratio = max(
                worst_leaf_ratio, leaf_ratio, 1.0 / max(leaf_ratio, 1e-30)
            )
    role_loss, ref_loss = float(role_loss), float(ref_loss)
    measured = {
        "loss_rel": abs(role_loss - ref_loss) / max(abs(ref_loss), 1e-12),
        "grad_rel_l2": diff_norm / max(ref_norm, 1e-30),
        "worst_leaf_rel_l2": worst_leaf,
        "worst_leaf_norm_ratio": worst_leaf_ratio,
        "grad_cosine": dot / max(role_norm * ref_norm, 1e-30),
        "grad_norm_ratio": role_norm / max(ref_norm, 1e-30),
    }
    ratio = measured["grad_norm_ratio"]
    within = {
        "loss_rel": lambda t: measured["loss_rel"] <= t,
        "grad_rel_l2": lambda t: measured["grad_rel_l2"] <= t,
        "leaf_rel_l2": lambda t: worst_leaf <= t,
        "grad_cosine_min": lambda t: measured["grad_cosine"] >= t,
        "grad_norm_ratio_max": lambda t: 1.0 / t <= ratio <= t,
        "leaf_norm_ratio_max": lambda t: worst_leaf_ratio <= t,
    }
    finite = bool(np.isfinite([role_loss, ref_loss, *measured.values()]).all())
    return {
        "ok": bool(finite and all(within[k](t) for k, t in tolerance.items())),
        "role_loss": role_loss,
        "reference_loss": ref_loss,
        **measured,
        "tolerance": tolerance,
    }

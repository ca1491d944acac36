"""Flat-segment LAMB / LARS: the optimizer math over ONE flat buffer.

The averaging path already lives on a flat fp32 vector (``TreeLayout``,
``averaging/partition.py``): every peer flattens its gradient tree into one
buffer, ships it, and unflattens the averaged result. The optimizer apply,
however, historically re-entered tree-land — per-leaf moment updates,
per-leaf norm reductions, a host round-trip per leaf when the averaged
result came back. This module closes the loop: the full LAMB/LARS update —
moments, debias, weight decay, per-layer trust ratios — computed directly
on the flat buffer, with per-layer reductions expressed as SEGMENT
reductions over the layout's contiguous spans.

Numerics: the math is the SAME code as the tree chain (``lamb_moments`` /
``adam_direction`` / ``trust_ratio_scale`` from ``optim/lamb.py`` — a flat
vector is a one-leaf pytree), so the only differences are reduction order
(a span reduce sums the same elements as the per-leaf norm, but XLA may
re-associate differently) and the clip/decay mask expansion. Equivalence vs
the per-leaf optax chain is locked by ``tests/test_optim.py`` to 25-step
agreement within a documented float32 bound.

These adapters are consumed by ``parallel.train_step.make_flat_apply_step``,
which keeps the OPTAX TREE STATE as the persistent ``opt_state`` (so
checkpoints, peer state sync and ZeRO layouts are untouched) and converts
tree<->flat inside the one fused jit.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dedloc_tpu.optim.lamb import (
    adam_direction,
    lamb_moments,
    trust_ratio_scale,
)


def spec_spans(
    spec: Sequence[Tuple[str, Tuple[int, ...], np.dtype]]
) -> List[Tuple[int, int]]:
    """Contiguous (offset, size) spans of each spec entry in the flat
    buffer — the segment boundaries every per-layer reduction uses."""
    spans = []
    offset = 0
    for _name, shape, _dtype in spec:
        size = int(np.prod(shape)) if shape else 1
        spans.append((offset, size))
        offset += size
    return spans


def segment_sumsq(flat: jnp.ndarray, spans) -> jnp.ndarray:
    """Per-segment sum of squares over the flat buffer: one slice-reduce
    per contiguous span (XLA fuses the slices; no gather/scatter and no
    O(N) segment-id constant). Empty spans contribute 0."""
    parts = [
        jnp.vdot(flat[o:o + s], flat[o:o + s]).real if s else jnp.float32(0.0)
        for o, s in spans
    ]
    return jnp.stack([jnp.asarray(p, jnp.float32) for p in parts])


def expand_segments(
    per_segment: jnp.ndarray, spans, total: int
) -> jnp.ndarray:
    """Broadcast a [num_segments] vector back to the flat [total] buffer
    (inverse of a segment reduction): one broadcast per contiguous span,
    concatenated. The spans are static, so this must NOT go through
    ``jnp.repeat``: its index arithmetic (a cumsum over ``total`` elements)
    is all-constant, and XLA constant-folds it at compile time — 145 s on
    the CPU and 1,357 s on a v5e for ALBERT-large's 17.8M-element buffer
    (PR 21 chip run)."""
    assert sum(s for _o, s in spans) == total, (spans, total)
    parts = [
        jnp.broadcast_to(per_segment[i], (s,))
        for i, (_o, s) in enumerate(spans) if s
    ]
    return (
        jnp.concatenate(parts) if parts
        else jnp.zeros((0,), per_segment.dtype)
    )


class FlatLamb:
    """The full ``optim.lamb.lamb`` chain ([clip] -> moments+decay -> trust
    -> lr) over one flat fp32 buffer.

    ``decay_flags`` / ``spans`` follow the TreeLayout spec order (sorted
    names). ``update`` is pure and jit-friendly; moments stay flat vectors
    between calls only inside the enclosing jit — the persistent state
    remains the tree chain's (see ``make_flat_apply_step``).
    """

    def __init__(
        self,
        spec,
        decay_flags: Sequence[bool],
        learning_rate: optax.ScalarOrSchedule,
        b1: float = 0.9,
        b2: float = 0.999,
        eps: float = 1e-6,
        weight_decay: float = 0.01,
        clamp_value: float = 10000.0,
        debias: bool = True,
        max_grad_norm: Optional[float] = None,
        sign_flags: Optional[Sequence[bool]] = None,
        sign_step: float = 0.001,
    ) -> None:
        self.spans = spec_spans(spec)
        self.total = sum(s for _o, s in self.spans)
        self.decay_flags = np.asarray(list(decay_flags), np.float32)
        assert len(self.decay_flags) == len(self.spans)
        # spans stepped by the sign of their gradient instead
        # (``optim.lamb.sign_stepped``, the per-leaf twin); none: this
        # class's program is what it was
        self.sign_flags = np.asarray(
            list(sign_flags) if sign_flags is not None
            else [False] * len(self.spans), bool,
        )
        assert len(self.sign_flags) == len(self.spans)
        self.sign_step = float(sign_step)
        self.learning_rate = learning_rate
        self.b1, self.b2, self.eps = b1, b2, eps
        self.weight_decay = float(weight_decay)
        self.clamp_value = float(clamp_value)
        self.debias = bool(debias)
        self.max_grad_norm = max_grad_norm

    def _lr(self, sched_count):
        if callable(self.learning_rate):
            return self.learning_rate(sched_count)
        return jnp.asarray(self.learning_rate, jnp.float32)

    def update(
        self,
        flat_grads: jnp.ndarray,
        flat_params: jnp.ndarray,
        flat_mu: jnp.ndarray,
        flat_nu: jnp.ndarray,
        count: jnp.ndarray,
        sched_count: jnp.ndarray,
    ):
        """One LAMB step on flat buffers. Returns
        (flat_updates, new_flat_mu, new_flat_nu, new_count) where
        ``flat_updates`` is the DELTA to add to the params (lr folded in,
        descent-negated — optax ``apply_updates`` convention)."""
        g = flat_grads
        signed = None
        if self.sign_flags.any():
            signed = expand_segments(
                jnp.asarray(self.sign_flags), self.spans, self.total
            )
            g = jnp.where(signed, 0.0, g)  # out of the clip's norm too
        if self.max_grad_norm is not None:
            # optax.clip_by_global_norm semantics on the flat buffer: the
            # global norm IS the one vdot
            g_norm = jnp.sqrt(jnp.vdot(g, g).real)
            g = jnp.where(
                g_norm < self.max_grad_norm, g,
                (g / g_norm) * self.max_grad_norm,
            )
        mu, nu, mu_hat, nu_hat, count = lamb_moments(
            g, flat_mu, flat_nu, count, self.b1, self.b2, self.debias
        )
        adam_step = adam_direction(mu_hat, nu_hat, self.eps)
        if self.weight_decay > 0.0:
            decay = expand_segments(
                jnp.asarray(self.decay_flags), self.spans, self.total
            )
            adam_step = adam_step + self.weight_decay * decay * flat_params
        # per-layer trust ratios as segment reductions over the flat buffer
        w_norm = jnp.sqrt(segment_sumsq(flat_params, self.spans))
        u_norm = jnp.sqrt(segment_sumsq(adam_step, self.spans))
        ratio = trust_ratio_scale(w_norm, u_norm, self.clamp_value)
        trusted = adam_step * expand_segments(ratio, self.spans, self.total)
        lr = self._lr(sched_count)
        updates = -lr * trusted
        if signed is not None:
            updates = jnp.where(
                signed, -self.sign_step * jnp.sign(flat_grads), updates
            )
        return updates, mu, nu, count


class FlatLars:
    """The full ``optim.lars.lars`` LARC-style update over one flat fp32
    buffer: per-layer local LR from segment norms, momentum folded in.
    ``excluded_flags`` marks spans the trust adaptation skips (plain SGD)."""

    def __init__(
        self,
        spec,
        excluded_flags: Sequence[bool],
        learning_rate: optax.ScalarOrSchedule,
        momentum: float = 0.9,
        weight_decay: float = 1e-6,
        trust_coefficient: float = 0.001,
        eps: float = 1e-8,
        clip: bool = True,
    ) -> None:
        self.spans = spec_spans(spec)
        self.total = sum(s for _o, s in self.spans)
        self.excluded_flags = np.asarray(list(excluded_flags), np.float32)
        assert len(self.excluded_flags) == len(self.spans)
        self.learning_rate = learning_rate
        self.momentum = float(momentum)
        self.weight_decay = float(weight_decay)
        self.trust_coefficient = float(trust_coefficient)
        self.eps = float(eps)
        self.clip = bool(clip)

    def _lr(self, sched_count):
        if callable(self.learning_rate):
            return self.learning_rate(sched_count)
        return jnp.asarray(self.learning_rate, jnp.float32)

    def update(
        self,
        flat_grads: jnp.ndarray,
        flat_params: jnp.ndarray,
        flat_momentum: jnp.ndarray,
        sched_count: jnp.ndarray,
    ):
        """One LARS step on flat buffers. Returns
        (flat_updates, new_flat_momentum) — updates are the delta to add
        to the params (the new momentum, per the reference LARC wrap)."""
        lr = self._lr(sched_count)
        g = flat_grads + self.weight_decay * flat_params
        w_norm = jnp.sqrt(segment_sumsq(flat_params, self.spans))
        g_norm = jnp.sqrt(segment_sumsq(g, self.spans))
        local_lr = self.trust_coefficient * w_norm / (g_norm + self.eps)
        if self.clip:
            local_lr = (
                jnp.minimum(local_lr / jnp.maximum(lr, 1e-12), 1.0) * lr
            )
        else:
            local_lr = local_lr * lr
        local_lr = jnp.where((w_norm > 0) & (g_norm > 0), local_lr, lr)
        # excluded spans take the plain -lr * g step (apex LARC skip list)
        excl = expand_segments(
            jnp.asarray(self.excluded_flags), self.spans, self.total
        )
        per_elem_lr = expand_segments(local_lr, self.spans, self.total)
        scaled = -(excl * lr + (1.0 - excl) * per_elem_lr) * g
        new_mom = self.momentum * flat_momentum + scaled
        return new_mom, new_mom


def tree_flags(mask_tree, template, spec_names: Sequence[str]) -> List[bool]:
    """Per-spec-entry boolean flags from a per-leaf mask pytree (e.g.
    ``albert_weight_decay_mask``), reordered into the sorted-name spec
    order the flat buffer uses."""
    flat = jax.tree_util.tree_flatten_with_path(template)[0]
    mask_leaves = jax.tree.leaves(
        mask_tree, is_leaf=lambda x: isinstance(x, bool)
    )
    by_name = {}
    for i, ((path, _leaf), flag) in enumerate(zip(flat, mask_leaves)):
        name = jax.tree_util.keystr(path) or f"leaf{i}"
        by_name[name] = bool(flag)
    return [by_name[name] for name in spec_names]

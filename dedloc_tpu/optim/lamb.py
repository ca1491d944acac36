"""LAMB optimizer (layer-wise adaptive moments) in optax style.

Capability parity with the reference recipe (albert/run_trainer.py:73-100):
torch_optimizer.Lamb(lr=..., betas=(0.9, 0.999), eps=1e-6, weight_decay=0.01,
clamp_value=10000, debias=True) with weight decay excluded for bias and
LayerNorm parameters. Implemented as composable optax gradient transforms so
the whole update runs inside the jitted train step (no host round-trip).

``scale_by_lamb`` and the full ``lamb`` chain share ONE implementation of
the Adam moments / debias / trust-ratio math (the helpers below) — the two
used to carry inline near-copies, and the flat-segment formulation
(``optim/flat.py``) adds a third consumer: any drift between them would be
a silent numerics bug, so the math lives in exactly one place. The helpers
are written with ``jax.tree.map`` so they work unchanged on parameter
PYTREES and on the one-leaf flat-buffer form.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import chex
import jax
import jax.numpy as jnp
import optax


class ScaleByLambState(NamedTuple):
    count: chex.Array
    mu: optax.Updates
    nu: optax.Updates


def lamb_moments(
    updates, mu, nu, count, b1: float, b2: float, debias: bool
) -> Tuple[Any, Any, Any, Any, chex.Array]:
    """One Adam moment step: returns (mu, nu, mu_hat, nu_hat, count+1).

    ``mu_hat``/``nu_hat`` carry the (optional) bias correction; with
    ``debias=False`` they alias the raw moments. Structure-agnostic: the
    arguments may be parameter pytrees or single flat vectors.
    """
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, updates)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, updates)
    count = count + 1
    if debias:
        c = count.astype(jnp.float32)
        mu_hat = jax.tree.map(lambda m: m / (1 - b1 ** c), mu)
        nu_hat = jax.tree.map(lambda v: v / (1 - b2 ** c), nu)
    else:
        mu_hat, nu_hat = mu, nu
    return mu, nu, mu_hat, nu_hat, count


def adam_direction(mu_hat, nu_hat, eps: float):
    """m / (sqrt(v) + eps), leaf-wise."""
    return jax.tree.map(lambda m, v: m / (jnp.sqrt(v) + eps), mu_hat, nu_hat)


def trust_ratio_scale(
    w_norm: jnp.ndarray, u_norm: jnp.ndarray, clamp_value: float
) -> jnp.ndarray:
    """The LAMB layer-wise trust ratio from precomputed norms:
    ``min(||w||, clamp_value) / ||u||`` where both norms are positive,
    else 1.0 (torch_optimizer.Lamb ``clamp_value`` semantics)."""
    w_norm = jnp.minimum(w_norm, clamp_value)
    return jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)


def apply_trust_ratio(w, u, clamp_value: float):
    """Per-leaf trust-ratio scaling of update ``u`` against params ``w``."""
    w_norm = jnp.linalg.norm(w.astype(jnp.float32))
    u_norm = jnp.linalg.norm(u.astype(jnp.float32))
    return u * trust_ratio_scale(w_norm, u_norm, clamp_value)


def scale_by_lamb(
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    clamp_value: float = 10000.0,
    debias: bool = True,
) -> optax.GradientTransformation:
    """Adam moments + layer-wise trust ratio with weight-norm clamp.

    The trust ratio is ``min(||w||, clamp_value) / ||adam_update||``, matching
    torch_optimizer.Lamb's ``clamp_value`` semantics.
    """

    def init_fn(params):
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        return ScaleByLambState(count=jnp.zeros([], jnp.int32), mu=mu, nu=nu)

    def update_fn(updates, state, params):
        assert params is not None, "lamb requires params"
        mu, nu, mu_hat, nu_hat, count = lamb_moments(
            updates, state.mu, state.nu, state.count, b1, b2, debias
        )
        adam_step = adam_direction(mu_hat, nu_hat, eps)
        updates = jax.tree.map(
            lambda w, u: apply_trust_ratio(w, u, clamp_value),
            params, adam_step,
        )
        return updates, ScaleByLambState(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


def albert_weight_decay_mask(params) -> Any:
    """True where weight decay applies: everything except biases and
    LayerNorm/embedding-LN scale/bias (reference: run_trainer.py:78-87
    no_decay = ["bias", "LayerNorm.weight"])."""

    def decide(path, _):
        names = [p.key for p in path if hasattr(p, "key")]
        joined = "/".join(names).lower()
        if names and names[-1] == "bias":
            return False
        if "layernorm" in joined or "layer_norm" in joined:
            return False
        return True

    return jax.tree_util.tree_map_with_path(decide, params)


def lamb(
    learning_rate: optax.ScalarOrSchedule,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    clamp_value: float = 10000.0,
    debias: bool = True,
    weight_decay_mask: Optional[Callable] = albert_weight_decay_mask,
    max_grad_norm: Optional[float] = None,
    sign_step_mask: Optional[Callable] = None,
    sign_step: float = 0.001,
) -> optax.GradientTransformation:
    """Full LAMB chain: [clip] -> moments+decay -> trust ratio -> lr.

    Weight decay is added to the adam update BEFORE the trust ratio (the
    torch_optimizer.Lamb formulation the reference trains with).

    ``sign_step_mask`` (params -> tree of bools, from the model table as
    ``weight_decay_mask`` is): the leaves it marks are not LAMB's — see
    ``sign_stepped``.
    """
    # Decay must enter before the trust-ratio scaling, so we fold it into the
    # update inside a custom wrapper around the shared scale_by_lamb math.
    inner = scale_by_lamb(b1, b2, eps, clamp_value, debias)

    def init_fn(params):
        return inner.init(params)

    def update_fn(updates, state, params):
        # the same moments -> +wd*param -> trust ordering as scale_by_lamb,
        # through the SAME helpers — only the weight-decay insertion differs
        mu, nu, mu_hat, nu_hat, count = lamb_moments(
            updates, state.mu, state.nu, state.count, b1, b2, debias
        )
        adam_step = adam_direction(mu_hat, nu_hat, eps)

        if weight_decay > 0.0:
            mask = (
                weight_decay_mask(params)
                if callable(weight_decay_mask)
                else jax.tree.map(lambda _: True, params)
            )
            adam_step = jax.tree.map(
                lambda u, w, m: u + weight_decay * w if m else u,
                adam_step,
                params,
                mask,
                is_leaf=lambda x: x is None,
            )

        updates = jax.tree.map(
            lambda w, u: apply_trust_ratio(w, u, clamp_value),
            params, adam_step,
        )
        new_state = ScaleByLambState(count=count, mu=mu, nu=nu)
        return updates, new_state

    chain = [optax.GradientTransformation(init_fn, update_fn)]
    if max_grad_norm is not None:
        chain.insert(0, optax.clip_by_global_norm(max_grad_norm))
    chain.append(
        optax.scale_by_learning_rate(learning_rate)  # negates for descent
    )
    if sign_step_mask is None:
        return optax.chain(*chain)
    return sign_stepped(optax.chain(*chain), sign_step_mask, sign_step)


def sign_stepped(
    inner: optax.GradientTransformation, mask: Callable, step: float,
) -> optax.GradientTransformation:
    """``inner`` for every leaf but those ``mask(params)`` marks: a marked
    leaf moves by ``-step · sign(g)`` — no moments, trust ratio, decay or
    learning-rate schedule — and ``inner`` sees zeros in its place, so it
    adds nothing to a global-norm clip. (The leaf's "gradient" is a
    statistic that rides the gradient's paths: an expert layer's load
    excess, stepped as DeepSeek-V3's bias rule steps it.) The state is
    ``inner``'s own."""

    def update_fn(updates, state, params):
        marked = mask(params)
        inner_updates, state = inner.update(
            jax.tree.map(
                lambda g, m: jnp.zeros_like(g) if m else g, updates, marked
            ),
            state, params,
        )
        return jax.tree.map(
            lambda u, g, m: (-step * jnp.sign(g)).astype(u.dtype) if m else u,
            inner_updates, updates, marked,
        ), state

    return optax.GradientTransformation(inner.init, update_fn)

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.optim import (
    lamb,
    lars,
    albert_weight_decay_mask,
    linear_warmup_linear_decay,
    linear_warmup_cosine_annealing,
)


def _rosenbrock_params():
    return {"w": jnp.array([1.5, 1.5]), "bias": jnp.array([0.5])}


def test_lamb_minimizes_quadratic():
    params = {"dense": {"kernel": jnp.array([[2.0, -3.0]]), "bias": jnp.array([1.0])}}
    target = {"dense": {"kernel": jnp.array([[0.5, 0.5]]), "bias": jnp.array([0.0])}}

    def loss(p):
        return sum(
            jnp.sum((a - b) ** 2)
            for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(target))
        )

    tx = lamb(1e-1, weight_decay=0.0)
    state = tx.init(params)

    @jax.jit
    def step(p, s):
        g = jax.grad(loss)(p)
        u, s = tx.update(g, s, p)
        import optax

        return optax.apply_updates(p, u), s

    l0 = float(loss(params))
    for _ in range(100):
        params, state = step(params, state)
    assert float(loss(params)) < l0 * 1e-2


def test_lamb_weight_decay_mask():
    params = {
        "encoder": {
            "layernorm": {"scale": jnp.ones(3), "bias": jnp.zeros(3)},
            "ffn": {"kernel": jnp.ones((3, 3)), "bias": jnp.zeros(3)},
        }
    }
    mask = albert_weight_decay_mask(params)
    assert mask["encoder"]["ffn"]["kernel"] is True
    assert mask["encoder"]["ffn"]["bias"] is False
    assert mask["encoder"]["layernorm"]["scale"] is False
    assert mask["encoder"]["layernorm"]["bias"] is False


def _undecayed(mask):
    return sorted(
        "/".join(k.key for k in path)
        for path, decayed in jax.tree_util.tree_leaves_with_path(mask)
        if not decayed
    )


def test_albert_mask_knows_no_other_models_names():
    """The family-neutral rule is bias + LayerNorm and nothing else: a
    module that happens to be called ``norm`` keeps its decay, and
    ALBERT's own undecayed set is the reference recipe's, leaf for leaf."""
    from dedloc_tpu.roles.common import build_model

    mask = albert_weight_decay_mask(
        {"norm": {"kernel": jnp.ones((2, 2)), "weight": jnp.ones(2)}}
    )
    assert mask == {"norm": {"kernel": True, "weight": True}}

    _cfg, model = build_model("tiny")
    ids = jnp.zeros((1, 8), jnp.int32)
    params = jax.eval_shape(
        lambda r: model.init(r, ids, ids, ids)["params"],
        jax.random.PRNGKey(0),
    )
    block = "albert/encoder/layer/block"
    assert _undecayed(albert_weight_decay_mask(params)) == sorted(
        [f"{block}/{m}/bias" for m in (
            "attention/dense", "attention/key", "attention/query",
            "attention/value", "attention/layernorm", "ffn", "ffn_output",
            "layernorm",
        )]
        + [f"{block}/attention/layernorm/scale", f"{block}/layernorm/scale"]
        + ["albert/embedding_projection/bias",
           "albert/embeddings_layernorm/bias",
           "albert/embeddings_layernorm/scale", "albert/pooler/bias",
           "mlm_dense/bias", "mlm_layernorm/bias", "mlm_layernorm/scale",
           "sop_classifier/bias"]
    )


def test_each_family_supplies_its_own_mask():
    """``build_optimizer`` takes the mask from the model table: Ouro's
    RMSNorm weights (stacked [L, H], the final ``norm`` among them) and the
    gate's bias go undecayed, every matrix decays."""
    from dedloc_tpu.roles.common import build_model, model_family

    assert model_family("large").weight_decay_mask is albert_weight_decay_mask
    _cfg, model = build_model("ouro_tiny")
    params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    block = "model/layers/block"
    assert _undecayed(
        model_family("ouro_tiny").weight_decay_mask(params)
    ) == sorted(
        [f"{block}/{n}/weight" for n in (
            "input_layernorm", "input_layernorm_2",
            "post_attention_layernorm", "post_attention_layernorm_2",
        )]
        + ["model/norm/weight", "model/early_exit_gate/bias"]
    )


def test_lamb_trust_ratio_clamp():
    """Huge params: ||w|| must be clamped at clamp_value in the trust ratio."""
    params = {"w": jnp.full((10,), 1e6)}
    tx = lamb(1.0, weight_decay=0.0, clamp_value=10.0)
    state = tx.init(params)
    g = {"w": jnp.ones((10,))}
    u, _ = tx.update(g, state, params)
    # trust ratio = min(||w||, 10)/||step||; adam step ~= sign ⇒ ||step||~sqrt(10)
    assert float(jnp.linalg.norm(u["w"])) <= 10.0 + 1e-3


def test_lars_minimizes_quadratic():
    params = {"kernel": jnp.array([3.0, -2.0])}

    def loss(p):
        return jnp.sum(p["kernel"] ** 2)

    tx = lars(0.5, momentum=0.9, weight_decay=0.0, trust_coefficient=0.01)
    state = tx.init(params)
    import optax

    l0 = float(loss(params))
    for _ in range(200):
        g = jax.grad(loss)(params)
        u, state = tx.update(g, state, params)
        params = optax.apply_updates(params, u)
    assert float(loss(params)) < l0 * 1e-2


def _spec_and_flags(params, mask_fn=None):
    """TreeLayout spec (sorted keystr names) + per-span mask flags for the
    flat adapters, mirroring what the collaborative optimizer derives."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    named = {
        jax.tree_util.keystr(p): np.asarray(leaf) for p, leaf in flat
    }
    spec = [
        (name, named[name].shape, np.dtype(np.float32))
        for name in sorted(named)
    ]
    if mask_fn is None:
        return spec, [True] * len(spec)
    from dedloc_tpu.optim.flat import tree_flags

    return spec, tree_flags(mask_fn(params), params, [n for n, _, _ in spec])


def _flatten_sorted(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    named = {
        jax.tree_util.keystr(p): np.asarray(leaf, np.float32)
        for p, leaf in flat
    }
    return np.concatenate(
        [named[n].reshape(-1) for n in sorted(named)]
    ) if named else np.zeros(0, np.float32)


def test_flat_lamb_matches_tree_chain_over_25_steps():
    """The flat-segment LAMB (optim/flat.py) must agree with the per-leaf
    optax chain over a 25-step trajectory. Documented bound: float32
    reduction-order only — per-span slice reductions vs per-leaf norms —
    so a few ulps relative, asserted at 1e-5 relative after 25 steps."""
    from dedloc_tpu.optim.flat import FlatLamb

    rng = np.random.default_rng(3)
    params = {
        "dense": {
            "kernel": jnp.asarray(rng.standard_normal((5, 4)), jnp.float32),
            "bias": jnp.asarray(rng.standard_normal((4,)), jnp.float32),
        },
        "layernorm": {"scale": jnp.ones((5,))},
        "scalar": jnp.asarray(0.5, jnp.float32),
    }
    sched = lambda c: 0.01 * (1.0 + 0.05 * c.astype(jnp.float32))  # noqa: E731
    tx = lamb(sched, weight_decay=0.01, max_grad_norm=1.0)
    spec, flags = _spec_and_flags(params, albert_weight_decay_mask)
    ftx = FlatLamb(spec, flags, sched, weight_decay=0.01, max_grad_norm=1.0)

    import optax

    tree_params = params
    tree_state = tx.init(params)
    flat_params = jnp.asarray(_flatten_sorted(params))
    from dedloc_tpu.optim.lamb import ScaleByLambState

    mu = jnp.zeros_like(flat_params)
    nu = jnp.zeros_like(flat_params)
    count = jnp.zeros([], jnp.int32)
    sched_count = jnp.zeros([], jnp.int32)
    for i in range(25):
        r = np.random.default_rng(50 + i)
        grads = jax.tree.map(
            lambda p: jnp.asarray(
                r.standard_normal(p.shape), jnp.float32
            ),
            tree_params,
        )
        updates, tree_state = tx.update(grads, tree_state, tree_params)
        tree_params = optax.apply_updates(tree_params, updates)
        flat_grads = jnp.asarray(_flatten_sorted(grads))
        delta, mu, nu, count = ftx.update(
            flat_grads, flat_params, mu, nu, count, sched_count
        )
        sched_count = sched_count + 1
        flat_params = flat_params + delta
    ref = _flatten_sorted(jax.device_get(tree_params))
    np.testing.assert_allclose(
        np.asarray(flat_params), ref, rtol=1e-5, atol=1e-7
    )
    # the moments agree too (single source of truth: lamb_moments)
    inner = tree_state[1] if isinstance(tree_state, tuple) else tree_state
    if not isinstance(inner, ScaleByLambState):
        inner = next(
            s for s in jax.tree_util.tree_leaves(
                tree_state, is_leaf=lambda x: isinstance(x, ScaleByLambState)
            ) if isinstance(s, ScaleByLambState)
        )
    np.testing.assert_allclose(
        np.asarray(mu), _flatten_sorted(jax.device_get(inner.mu)),
        rtol=1e-5, atol=1e-7,
    )


def test_flat_lars_matches_tree_chain_over_25_steps():
    from dedloc_tpu.optim.flat import FlatLars

    rng = np.random.default_rng(5)
    params = {
        "conv": jnp.asarray(rng.standard_normal((3, 3, 2)), jnp.float32),
        "bn": {"scale": jnp.ones((3,))},
    }
    import optax

    tx = lars(0.3, momentum=0.9, weight_decay=1e-4, trust_coefficient=0.01)
    spec, _ = _spec_and_flags(params)
    ftx = FlatLars(
        spec, [False] * len(spec), 0.3, momentum=0.9, weight_decay=1e-4,
        trust_coefficient=0.01,
    )
    tree_params = params
    tree_state = tx.init(params)
    flat_params = jnp.asarray(_flatten_sorted(params))
    mom = jnp.zeros_like(flat_params)
    sched_count = jnp.zeros([], jnp.int32)
    for i in range(25):
        r = np.random.default_rng(80 + i)
        grads = jax.tree.map(
            lambda p: jnp.asarray(r.standard_normal(p.shape), jnp.float32),
            tree_params,
        )
        updates, tree_state = tx.update(grads, tree_state, tree_params)
        tree_params = optax.apply_updates(tree_params, updates)
        delta, mom = ftx.update(
            jnp.asarray(_flatten_sorted(grads)), flat_params, mom,
            sched_count,
        )
        sched_count = sched_count + 1
        flat_params = flat_params + delta
    np.testing.assert_allclose(
        np.asarray(flat_params),
        _flatten_sorted(jax.device_get(tree_params)),
        rtol=1e-5, atol=1e-7,
    )


def test_scale_by_lamb_and_lamb_share_moment_math():
    """The dedupe contract: scale_by_lamb and the full lamb() chain (with
    decay off) produce IDENTICAL updates — they now run through the same
    lamb_moments/adam_direction/apply_trust_ratio helpers, so any drift
    between them is a regression."""
    from dedloc_tpu.optim.lamb import scale_by_lamb

    rng = np.random.default_rng(9)
    params = {"w": jnp.asarray(rng.standard_normal((6, 3)), jnp.float32)}
    grads = {"w": jnp.asarray(rng.standard_normal((6, 3)), jnp.float32)}
    inner = scale_by_lamb()
    chain = lamb(1.0, weight_decay=0.0)
    s1 = inner.init(params)
    s2 = chain.init(params)
    u1, _ = inner.update(grads, s1, params)
    u2, _ = chain.update(grads, s2, params)
    # the chain negates via scale_by_learning_rate(1.0)
    np.testing.assert_array_equal(
        np.asarray(u1["w"]), -np.asarray(u2["w"])
    )


def test_linear_schedule():
    s = linear_warmup_linear_decay(1.0, warmup_steps=10, total_steps=110)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1.0) < 1e-6
    assert abs(float(s(60)) - 0.5) < 1e-6
    assert float(s(110)) == 0.0


def test_cosine_schedule():
    s = linear_warmup_cosine_annealing(1.0, warmup_steps=10, total_steps=110)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1.0) < 1e-2
    assert float(s(110)) < 1e-6


def _sign_mask(params):
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key == "load_bias", params
    )


def _sign_step_setup():
    rng = np.random.default_rng(4)
    params = {
        "dense": {"kernel": jnp.asarray(rng.standard_normal((5, 4)), jnp.float32)},
        "router": {"load_bias": jnp.asarray(rng.standard_normal((6,)), jnp.float32)},
        "norm": {"weight": jnp.ones((5,))},
    }
    decay = lambda p: jax.tree_util.tree_map_with_path(  # noqa: E731
        lambda path, _: path[-1].key == "kernel", p
    )
    return params, decay


def _random_like(tree, seed, scale=1.0):
    r = np.random.default_rng(seed)
    return jax.tree.map(
        lambda p: jnp.asarray(scale * r.standard_normal(p.shape), jnp.float32),
        tree,
    )


def test_sign_step_rule_per_leaf_and_flat_agree():
    """A leaf the model table marks is stepped by ``-gamma * sign(g)`` —
    exactly ±gamma or 0, no moments, trust ratio, decay or schedule — in the
    per-leaf chain and in its flat twin alike; every other leaf moves as
    under plain LAMB (to reduction order)."""
    import optax

    from dedloc_tpu.optim.flat import FlatLamb, tree_flags

    params, decay = _sign_step_setup()
    gamma = 0.001
    sched = lambda c: 0.01 * (1.0 + 0.05 * c.astype(jnp.float32))  # noqa: E731
    tx = lamb(sched, weight_decay=0.01, max_grad_norm=1.0,
              weight_decay_mask=decay, sign_step_mask=_sign_mask,
              sign_step=gamma)
    spec, flags = _spec_and_flags(params, decay)
    names = [n for n, _, _ in spec]
    ftx = FlatLamb(
        spec, flags, sched, weight_decay=0.01, max_grad_norm=1.0,
        sign_flags=tree_flags(_sign_mask(params), params, names),
        sign_step=gamma,
    )
    tree_params, tree_state = params, tx.init(params)
    flat_params = jnp.asarray(_flatten_sorted(params))
    mu = nu = jnp.zeros_like(flat_params)
    count = sched_count = jnp.zeros([], jnp.int32)
    for i in range(10):
        grads = _random_like(tree_params, 70 + i)
        # an exactly balanced expert: the sign rule leaves it where it is
        grads["router"]["load_bias"] = grads["router"]["load_bias"].at[2].set(0.0)
        before = tree_params["router"]["load_bias"]
        updates, tree_state = tx.update(grads, tree_state, tree_params)
        tree_params = optax.apply_updates(tree_params, updates)
        moved = np.asarray(tree_params["router"]["load_bias"] - before)
        want = -gamma * np.sign(np.asarray(grads["router"]["load_bias"]))
        np.testing.assert_allclose(moved, want, atol=1e-7)
        assert moved[2] == 0.0
        delta, mu, nu, count = ftx.update(
            jnp.asarray(_flatten_sorted(grads)), flat_params, mu, nu, count,
            sched_count,
        )
        sched_count = sched_count + 1
        flat_params = flat_params + delta
    np.testing.assert_allclose(
        np.asarray(flat_params), _flatten_sorted(jax.device_get(tree_params)),
        rtol=1e-5, atol=1e-7,
    )


@pytest.mark.parametrize("path", ["per_leaf", "flat", "solo_mean"])
def test_sign_stepped_leaves_stay_out_of_the_clip(path):
    """The marked leaf carries a statistic, not a gradient: whatever it
    holds, the clip's norm — and so every other leaf's update — is what it
    is without it, in the per-leaf chain, the flat twin and the solo
    boundary's fused mean + clip."""
    import optax

    from dedloc_tpu.collaborative import optimizer
    from dedloc_tpu.optim.flat import FlatLamb, tree_flags

    params, decay = _sign_step_setup()
    grads = _random_like(params, 9, scale=3.0)  # norm well over the clip
    loud = jax.tree.map(jnp.copy, grads)
    loud["router"]["load_bias"] = 1e3 * jnp.ones((6,))
    if path == "per_leaf":
        tx = lamb(0.01, max_grad_norm=1.0, weight_decay_mask=decay,
                  sign_step_mask=_sign_mask)
        outs = [tx.update(g, tx.init(params), params)[0] for g in (grads, loud)]
        kernels = [o["dense"]["kernel"] for o in outs]
    elif path == "flat":
        spec, flags = _spec_and_flags(params, decay)
        names = [n for n, _, _ in spec]
        ftx = FlatLamb(
            spec, flags, 0.01, max_grad_norm=1.0,
            sign_flags=tree_flags(_sign_mask(params), params, names),
        )
        flat = jnp.asarray(_flatten_sorted(params))
        zero, count = jnp.zeros_like(flat), jnp.zeros([], jnp.int32)
        kernels = [
            ftx.update(jnp.asarray(_flatten_sorted(g)), flat, zero, zero,
                       count, count)[0][:20]  # "['dense']['kernel']" sorts first
            for g in (grads, loud)
        ]
    else:
        exempt = tuple(jax.tree.leaves(_sign_mask(params)))
        outs = [
            optimizer._fused_mean_clip(g, 1, 1.0, exempt=exempt)
            for g in (grads, loud)
        ]
        kernels = [o["dense"]["kernel"] for o in outs]
        # the statistic itself is not scaled by the clip
        np.testing.assert_array_equal(
            outs[1]["router"]["load_bias"], loud["router"]["load_bias"]
        )
        norm = np.sqrt(sum(
            float(jnp.sum(x * x)) for x, skip in
            zip(jax.tree.leaves(outs[1]), exempt) if not skip
        ))
        assert norm == pytest.approx(1.0, rel=1e-5)
    np.testing.assert_array_equal(np.asarray(kernels[0]), np.asarray(kernels[1]))

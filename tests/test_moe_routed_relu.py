"""The routed layer with ReLU-gated experts (ReGLU) and the softmax-over-
the-chosen router (``parallel/moe.py``: ``routed_experts(activation="relu")``,
``route_top_k_softmax``): forward and backward of the hand-written tile loop
against autodiff of a dense loop over the held experts, with and without
gradient sinks, and the SiLU path unmoved beside it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.parallel.moe import (
    ACTIVATIONS,
    route_top_k_softmax,
    routed_experts,
)

T, H, F, E, K = 48, 16, 8, 16, 3
NAMES = ("gate", "up", "down")


def _layer(seed=0):
    r = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        x=jax.random.normal(r[0], (T, H)),
        router=jax.random.normal(r[1], (H, E)),
        gate=jax.random.normal(r[2], (E, H, F)) * 0.3,
        up=jax.random.normal(r[3], (E, H, F)) * 0.3,
        down=jax.random.normal(r[4], (E, F, H)) * 0.3,
    )


def _dense(p, choice, weights, held, act):
    y = jnp.zeros_like(p["x"])
    for e in range(held[0], held[0] + held[1]):
        mine = jnp.sum(jnp.where(choice == e, weights, 0.0), axis=-1)
        y = y + mine[:, None] * (
            (act(p["x"] @ p["gate"][e]) * (p["x"] @ p["up"][e]))
            @ p["down"][e]
        )
    return y


def test_softmax_over_the_chosen_logits():
    p = _layer()
    logits = p["x"] @ p["router"]
    choice, weights = route_top_k_softmax(logits, K)
    assert choice.shape == weights.shape == (T, K)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    order = np.argsort(-np.asarray(logits), axis=-1)[:, :K]
    np.testing.assert_array_equal(np.sort(choice, -1), np.sort(order, -1))
    picked = np.take_along_axis(np.asarray(logits), np.asarray(choice), -1)
    want = np.exp(picked - picked.max(-1, keepdims=True))
    np.testing.assert_allclose(
        weights, want / want.sum(-1, keepdims=True), rtol=1e-5
    )
    # the weights carry a gradient to the logits; the choice does not move
    d = jax.grad(lambda x: jnp.sum(route_top_k_softmax(x, K)[1][:, 0]))(logits)
    assert float(jnp.max(jnp.abs(d))) > 0.0


@pytest.mark.parametrize("sinks", [False, True], ids=["plain", "sinks"])
@pytest.mark.parametrize("held", [(0, 16), (4, 4), (15, 1)])
@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_forward_and_backward_against_a_dense_loop(activation, held, sinks):
    p = _layer(3)
    choice, weights = route_top_k_softmax(p["x"] @ p["router"], K)
    lo, n = held
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    assert ACTIVATIONS[activation][0] is act

    def routed(q, grad_sinks=None):
        return routed_experts(
            q["x"], choice, weights, *(q[m][lo:lo + n] for m in NAMES), held,
            tile=8, grad_sinks=grad_sinks, activation=activation,
        )

    y, stats = routed(p)
    np.testing.assert_allclose(
        y, _dense(p, choice, weights, held, act), atol=1e-5, rtol=1e-5
    )
    assert float(stats["dropped_slots"]) == 0.0

    want = jax.grad(lambda q: jnp.sum(jnp.sin(
        _dense(dict(p, **q), choice, weights, held, act)
    )))({m: p[m] for m in ("x",) + NAMES})
    if not sinks:
        got = jax.grad(lambda q: jnp.sum(jnp.sin(routed(dict(p, **q))[0])))(
            {m: p[m] for m in ("x",) + NAMES}
        )
        for m in NAMES:
            np.testing.assert_allclose(
                got[m][lo:lo + n], want[m][lo:lo + n], atol=1e-5, rtol=1e-5
            )
    else:
        start = tuple(
            jnp.full(p[m][lo:lo + n].shape, 0.25, jnp.float32) for m in NAMES
        )
        (got, d_sinks), stats = jax.grad(
            lambda q, s: (lambda out: (jnp.sum(jnp.sin(out[0])), out[1]))(
                routed(dict(p, **q), s)
            ), (0, 1), has_aux=True,
        )({m: p[m] for m in ("x",) + NAMES}, start)
        assert float(stats["grad_sink_leaves"]) == 3.0
        for m, d in zip(NAMES, d_sinks):
            assert float(jnp.max(jnp.abs(got[m][lo:lo + n]))) == 0.0
            np.testing.assert_allclose(
                d - 0.25, want[m][lo:lo + n], atol=1e-5, rtol=1e-5
            )
    np.testing.assert_allclose(got["x"], want["x"], atol=1e-5, rtol=1e-5)


def test_the_two_activations_differ():
    p = _layer(4)
    choice, weights = route_top_k_softmax(p["x"] @ p["router"], K)
    outs = [
        routed_experts(p["x"], choice, weights, p["gate"], p["up"], p["down"],
                       (0, E), tile=8, activation=a)[0]
        for a in ("relu", "silu")
    ]
    assert float(jnp.max(jnp.abs(outs[0] - outs[1]))) > 0.05
    with pytest.raises(KeyError):
        routed_experts(p["x"], choice, weights, p["gate"], p["up"], p["down"],
                       (0, E), tile=8, activation="gelu")


def _routing(engages):
    """A fixed choice [T, K]: with ``engages`` 40 tokens put held expert 5
    first (five tiles of 8: a bulk iteration of four and a tail), without
    it no held expert draws four tiles."""
    t = np.arange(T)
    if engages:
        first = np.where(t < 40, 5, 4)
        rest = [6 + t % 2, 12 + t % 4]
    else:
        first = 4 + t % 4
        rest = [8 + t % 4, 12 + t % 4]
    return jnp.asarray(np.stack([first] + rest, -1), jnp.int32)


@pytest.mark.parametrize("engages", [True, False],
                         ids=["bulk_engages", "tails_alone"])
@pytest.mark.parametrize("sinks", [False, True], ids=["plain", "sinks"])
@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_the_run_length_walk_against_a_dense_loop(
    activation, sinks, engages
):
    """Forward, dx, the ROUTER weights' gradient (through the slots'
    weights) and the three held matrices' gradients, where the walk takes
    bulk iterations of four tiles and where it never does."""
    p = _layer(6)
    choice = _routing(engages)
    lo, n = held = (4, 4)
    act = ACTIVATIONS[activation][0]
    trained = ("x", "router") + NAMES

    def weights_of(q):  # a softmax over the chosen logits
        picked = jnp.take_along_axis(q["x"] @ q["router"], choice, axis=-1)
        return jax.nn.softmax(picked, axis=-1)

    def routed(q, grad_sinks=None):
        return routed_experts(
            q["x"], choice, weights_of(q), *(q[m][lo:lo + n] for m in NAMES),
            held, tile=8, grad_sinks=grad_sinks, activation=activation,
            run_tiles=4,
        )

    y, stats = routed(p)
    np.testing.assert_allclose(
        y, _dense(p, choice, weights_of(p), held, act), atol=1e-5, rtol=1e-5
    )
    assert float(stats["dropped_slots"]) == 0.0
    assert float(stats["bulk_row_share"]) == pytest.approx(
        32 / 96 if engages else 0.0
    )
    want = jax.grad(lambda q: jnp.sum(jnp.sin(
        _dense(dict(p, **q), choice, weights_of(dict(p, **q)), held, act)
    )))({m: p[m] for m in trained})
    start = tuple(
        jnp.full(p[m][lo:lo + n].shape, 0.25, jnp.float32) for m in NAMES
    ) if sinks else None
    (got, d_sinks), stats = jax.grad(
        lambda q, s: (lambda out: (jnp.sum(jnp.sin(out[0])), out[1]))(
            routed(dict(p, **q), s)
        ), (0, 1), has_aux=True, allow_int=True,
    )({m: p[m] for m in trained}, start)
    assert float(stats["grad_sink_leaves"]) == (3.0 if sinks else 0.0)
    for i, m in enumerate(NAMES):
        held_grad = d_sinks[i] - 0.25 if sinks else got[m][lo:lo + n]
        np.testing.assert_allclose(
            held_grad, want[m][lo:lo + n], atol=1e-5, rtol=1e-5
        )
        if sinks:
            assert float(jnp.max(jnp.abs(got[m][lo:lo + n]))) == 0.0
    assert float(jnp.max(jnp.abs(want["router"]))) > 0.0
    for m in ("x", "router"):
        np.testing.assert_allclose(got[m], want[m], atol=1e-5, rtol=1e-5)

"""The routed layer with UN-gated experts (``parallel/moe.py``:
``routed_experts(..., gate=None, activation="relu2")`` — down_e(relu(up_e
x)²), two matrices an expert): forward and backward of the hand-written
tile loop against autodiff of a dense loop over the held experts, with and
without gradient sinks (two of them); the bulk and the tail walk; and a
GATED caller's program unchanged by the new argument."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.parallel.moe import (
    PLAIN_ACTIVATIONS,
    route_top_k,
    routed_experts,
)

T, H, F, E, K = 48, 16, 8, 16, 3
NAMES = ("up", "down")


def _layer(seed=0):
    r = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        x=jax.random.normal(r[0], (T, H)),
        router=jax.random.normal(r[1], (H, E)),
        gate=jax.random.normal(r[2], (E, H, F)) * 0.3,
        up=jax.random.normal(r[3], (E, H, F)) * 0.3,
        down=jax.random.normal(r[4], (E, F, H)) * 0.3,
    )


def _route(p):
    return route_top_k(jax.nn.sigmoid(p["x"] @ p["router"]), None, K, 2.5)


def _dense(p, choice, weights, held):
    y = jnp.zeros_like(p["x"])
    for e in range(held[0], held[0] + held[1]):
        mine = jnp.sum(jnp.where(choice == e, weights, 0.0), axis=-1)
        y = y + mine[:, None] * (
            jnp.square(jax.nn.relu(p["x"] @ p["up"][e])) @ p["down"][e]
        )
    return y


@pytest.mark.parametrize("sinks", [False, True], ids=["plain", "sinks"])
@pytest.mark.parametrize("held", [(0, 16), (4, 4), (15, 1)])
def test_forward_and_backward_against_a_dense_loop(held, sinks):
    p = _layer(3)
    choice, weights = _route(p)
    lo, n = held

    def routed(q, grad_sinks=None):
        return routed_experts(
            q["x"], choice, weights, None,
            *(q[m][lo:lo + n] for m in NAMES), held, tile=8,
            grad_sinks=grad_sinks, activation="relu2",
        )

    y, stats = routed(p)
    np.testing.assert_allclose(
        y, _dense(p, choice, weights, held), atol=1e-5, rtol=1e-5
    )
    assert float(stats["dropped_slots"]) == 0.0
    leaves = {m: p[m] for m in ("x",) + NAMES}
    want = jax.grad(lambda q: jnp.sum(jnp.sin(
        _dense(dict(p, **q), choice, weights, held)
    )))(leaves)
    if not sinks:
        got = jax.grad(
            lambda q: jnp.sum(jnp.sin(routed(dict(p, **q))[0]))
        )(leaves)
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
        )(leaves, start)
        assert float(stats["grad_sink_leaves"]) == 2.0
        for m, d in zip(NAMES, d_sinks):
            assert float(jnp.max(jnp.abs(got[m][lo:lo + n]))) == 0.0
            np.testing.assert_allclose(
                d - 0.25, want[m][lo:lo + n], atol=1e-5, rtol=1e-5
            )
    np.testing.assert_allclose(got["x"], want["x"], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("run_tiles", [1, 2, 4])
def test_every_walk_gives_the_dense_loops_answer(run_tiles):
    p = _layer(5)
    t = np.arange(T)
    # expert 5 draws five tiles of 8: bulk iterations and a tail
    choice = jnp.asarray(np.stack(
        [np.where(t < 40, 5, 4), 6 + t % 2, 12 + t % 4], -1
    ), jnp.int32)
    weights = _route(p)[1]
    y, stats = routed_experts(
        p["x"], choice, weights, None, p["up"], p["down"], (0, E), tile=8,
        activation="relu2", run_tiles=run_tiles,
    )
    np.testing.assert_allclose(
        y, _dense(p, choice, weights, (0, E)), atol=1e-5, rtol=1e-5
    )
    assert float(stats["bulk_row_share"]) > 0.0


def test_the_form_and_the_activation_go_together():
    p = _layer(4)
    choice, weights = _route(p)
    assert set(PLAIN_ACTIVATIONS) == {"relu2"}
    with pytest.raises(KeyError):  # a gate's activation without a gate
        routed_experts(p["x"], choice, weights, None, p["up"], p["down"],
                       (0, E), tile=8, activation="silu")
    with pytest.raises(KeyError):  # and the other way round
        routed_experts(p["x"], choice, weights, p["gate"], p["up"], p["down"],
                       (0, E), tile=8, activation="relu2")
    plain = routed_experts(p["x"], choice, weights, None, p["up"], p["down"],
                           (0, E), tile=8, activation="relu2")[0]
    relu_gated = routed_experts(p["x"], choice, weights, p["up"], p["up"],
                                p["down"], (0, E), tile=8,
                                activation="relu")[0]
    # relu(u) · u IS relu(u)²: the two forms meet where gate = up
    np.testing.assert_allclose(plain, relu_gated, atol=1e-5, rtol=1e-5)


def test_a_gated_callers_program_has_no_trace_of_the_new_form():
    """The jaxpr of a gated call and of its gradient: three weight-gradient
    dots a loop body, a SiLU, no square — what it was before the loop
    learned a second form; the un-gated one has two and a square."""
    p = _layer(6)
    choice, weights = _route(p)

    def gated(q):
        return jnp.sum(routed_experts(
            q["x"], choice, weights, q["gate"], q["up"], q["down"], (0, E),
            tile=8,
        )[0])

    def plain(q):
        return jnp.sum(routed_experts(
            q["x"], choice, weights, None, q["up"], q["down"], (0, E),
            tile=8, activation="relu2",
        )[0])

    gated_text = str(jax.make_jaxpr(jax.grad(gated))(p))
    plain_text = str(jax.make_jaxpr(jax.grad(plain))(p))
    assert "logistic" in gated_text and "logistic" not in plain_text
    assert "square" not in gated_text and "square" in plain_text
    # matmuls of the forward and backward bodies (bulk + tail loops each):
    # gated 3 + (3 replayed + 1 + 2 + 3), un-gated 2 + (2 + 1 + 1 + 2)
    assert gated_text.count("dot_general") == 2 * (3 + 9)
    assert plain_text.count("dot_general") == 2 * (2 + 6)

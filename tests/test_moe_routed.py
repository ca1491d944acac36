"""The dropless top-k routed layer (``parallel/moe.py``): the choice takes
the bias and the weights do not; nothing is dropped however skewed the
router; absent experts contribute nothing; the tile plan puts every valid
(token, slot) pair in a row of its own expert's tiles; the load statistic
leaves the backward as the bias's cotangent; handed gradient sinks, the
backward sums the held matrices' gradients into them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.parallel.moe import (
    _tile_plan,
    expert_load,
    route_top_k,
    routed_experts,
    with_load_cotangent,
)

T, H, F, E, K = 48, 16, 8, 16, 3


def _layer(seed=0):
    r = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        x=jax.random.normal(r[0], (T, H)),
        router=jax.random.normal(r[1], (H, E)),
        gate=jax.random.normal(r[2], (E, H, F)) * 0.3,
        up=jax.random.normal(r[3], (E, H, F)) * 0.3,
        down=jax.random.normal(r[4], (E, F, H)) * 0.3,
    )


def _dense(p, choice, weights, held):
    """Σ over the held experts, each applied to every token (no sort)."""
    y = jnp.zeros_like(p["x"])
    for e in range(held[0], held[0] + held[1]):
        mine = jnp.sum(jnp.where(choice == e, weights, 0.0), axis=-1)
        y = y + mine[:, None] * (
            (jax.nn.silu(p["x"] @ p["gate"][e]) * (p["x"] @ p["up"][e]))
            @ p["down"][e]
        )
    return y


def _routed(p, choice, weights, held, tile=8):
    lo, n = held
    return routed_experts(
        p["x"], choice, weights, p["gate"][lo:lo + n], p["up"][lo:lo + n],
        p["down"][lo:lo + n], held, tile=tile,
    )


def test_the_bias_enters_the_choice_and_not_the_weights():
    scores = jax.nn.sigmoid(_layer()["x"] @ _layer()["router"])
    bias = jnp.zeros((E,)).at[5].set(10.0)  # expert 5 wins every token
    choice, weights = route_top_k(scores, bias, K, 2.448)
    assert bool(jnp.all(jnp.any(choice == 5, axis=-1)))
    plain_choice, _ = route_top_k(scores, jnp.zeros((E,)), K, 2.448)
    assert not bool(jnp.all(jnp.any(plain_choice == 5, axis=-1)))
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    np.testing.assert_allclose(
        weights, picked / picked.sum(-1, keepdims=True) * 2.448, rtol=1e-6
    )
    np.testing.assert_allclose(weights.sum(-1), 2.448, rtol=1e-6)


@pytest.mark.parametrize("held", [(0, 16), (4, 4), (15, 1)])
def test_matches_a_dense_loop_over_the_held_experts(held):
    p = _layer()
    scores = jax.nn.sigmoid(p["x"] @ p["router"])
    choice, weights = route_top_k(scores, jnp.zeros((E,)), K, 2.448)
    y, stats = _routed(p, choice, weights, held)
    np.testing.assert_allclose(
        y, _dense(p, choice, weights, held), atol=1e-5, rtol=1e-5
    )
    assert float(stats["dropped_slots"]) == 0.0
    inside = (choice >= held[0]) & (choice < held[0] + held[1])
    assert float(stats["local_slot_share"]) == pytest.approx(
        float(jnp.mean(inside))
    )


def test_dropless_when_every_token_goes_to_one_expert():
    """A router so skewed that all T tokens choose expert 2 (and two more):
    a capacity-bound layer would drop most of them; here expert 2 takes all
    T rows and ``moe.dropped_slots`` reads 0."""
    p = _layer(1)
    scores = jax.nn.sigmoid(p["x"] @ p["router"])
    choice, weights = route_top_k(
        scores, jnp.zeros((E,)).at[2].set(100.0), K, 2.448
    )
    assert bool(jnp.all(choice[:, 0] == 2))
    for held in ((2, 1), (0, 4)):
        y, stats = _routed(p, choice, weights, held)
        assert float(stats["dropped_slots"]) == 0.0
        np.testing.assert_allclose(
            y, _dense(p, choice, weights, held), atol=1e-5, rtol=1e-5
        )
    row_slot, tile_expert, tiles, dropped, _ = _tile_plan(choice, (2, 1), 8)
    assert int(jnp.sum(row_slot >= 0)) == T and int(tiles) == T // 8
    assert int(dropped) == 0


def test_absent_experts_contribute_nothing():
    p = _layer(2)
    scores = jax.nn.sigmoid(p["x"] @ p["router"])
    choice, weights = route_top_k(scores, jnp.zeros((E,)), K, 2.448)
    held = (8, 4)
    y, stats = _routed(p, choice, weights, held)
    # a token none of whose choices is held gets exactly zero
    untouched = ~jnp.any((choice >= 8) & (choice < 12), axis=-1)
    assert bool(jnp.any(untouched))
    assert float(jnp.max(jnp.abs(y[untouched]))) == 0.0
    # and the absent experts' matrices do not matter: none is even passed;
    # the held ones' gradients are those of the dense loop
    def loss(fn):
        return lambda q: jnp.sum(jnp.sin(fn(dict(p, **q))))

    names = ("x", "gate", "up", "down")
    got = jax.grad(loss(lambda q: _routed(q, choice, weights, held)[0]))(
        {n: p[n] for n in names}
    )
    want = jax.grad(loss(lambda q: _dense(q, choice, weights, held)))(
        {n: p[n] for n in names}
    )
    for n in names:
        np.testing.assert_allclose(got[n], want[n], atol=2e-5, rtol=1e-4)
        if n != "x":
            assert float(jnp.max(jnp.abs(got[n][:8]))) == 0.0


def test_weights_gradient_reaches_the_router():
    p = _layer(3)

    def through(fn):
        def loss(router):
            scores = jax.nn.sigmoid(p["x"] @ router)
            choice, weights = route_top_k(scores, jnp.zeros((E,)), K, 2.448)
            return jnp.sum(jnp.sin(fn(p, choice, weights, (0, 8))))
        return jax.grad(loss)(p["router"])

    np.testing.assert_allclose(
        through(lambda *a: _routed(*a)[0]), through(_dense),
        atol=2e-5, rtol=1e-4,
    )


def test_tile_plan_rows_belong_to_their_tiles_expert():
    choice = jnp.asarray(
        np.random.default_rng(0).integers(0, E, (T, K)), jnp.int32
    )
    held, tile = (4, 6), 8
    row_slot, tile_expert, tiles, dropped, _ = _tile_plan(choice, held, tile)
    row_slot, tile_expert = np.asarray(row_slot), np.asarray(tile_expert)
    flat = np.asarray(choice).reshape(-1)
    valid = (flat >= 4) & (flat < 10)
    placed = row_slot[row_slot >= 0]
    assert sorted(placed) == sorted(np.nonzero(valid)[0]) and int(dropped) == 0
    for row, slot in enumerate(row_slot):
        if slot >= 0:
            assert row < int(tiles) * tile
            assert flat[slot] - 4 == tile_expert[row // tile]


def test_load_statistic_is_the_bias_cotangent():
    choice = jnp.asarray(
        np.random.default_rng(1).integers(0, E, (T, K)), jnp.int32
    )
    load = expert_load(choice, E)
    assert float(load.sum()) == pytest.approx(1.0)
    np.testing.assert_allclose(
        load, np.bincount(np.asarray(choice).reshape(-1), minlength=E) / (T * K)
    )
    x = jnp.ones((4, 3))
    gx, gb = jax.grad(
        lambda x, b: 7.0 * jnp.sum(with_load_cotangent(x, b, load) ** 2),
        (0, 1),
    )(x, jnp.zeros((E,)))
    np.testing.assert_allclose(gx, 14.0 * x)  # x passes through untouched
    # whatever reaches x, the bias receives load − mean load, unscaled
    np.testing.assert_allclose(gb, load - load.mean(), atol=1e-8)


def _skewed_choice():
    """Of the held experts 4..7: expert 5 takes 20 rows (three tiles of 8),
    4 and 7 ten each (two), 6 none; the other slots go to absent experts."""
    t = np.arange(T)
    first = np.where(t < 20, 5, np.where(t < 30, 4, np.where(t < 40, 7, 0)))
    return jnp.asarray(np.stack([first, 8 + t % 4, 12 + t % 4], -1), jnp.int32)


def _relative(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
def test_held_gradients_accumulate_over_two_micro_batches(sink, dtype):
    """A float32 accumulator over two micro-batches: ``acc + d`` in a pass
    of its own (no sink), or handed to the loop as its sinks, whose
    cotangent is then the new accumulator while the matrices' own is zero.
    In float32 both are the dense loop's sums to 1e-6; in bfloat16 the sink
    keeps the float32 sum the other path rounds."""
    held, names = (4, 4), ("gate", "up", "down")
    choice, weights = _skewed_choice(), jnp.full((T, K), 0.7)
    _, tile_expert, tiles, _, _ = _tile_plan(choice, held, 8)
    assert np.bincount(
        np.asarray(tile_expert)[:int(tiles)], minlength=4
    ).tolist() == [2, 3, 0, 2]
    start = {
        n: 0.5 + jnp.zeros(_layer()[n][4:8].shape, jnp.float32) for n in names
    }
    acc, plain, want = dict(start), dict(start), dict(start)
    for seed in (0, 1):
        p = _layer(seed)
        p = {n: v.astype(dtype).astype(jnp.float32) for n, v in p.items()}
        dense = jax.grad(
            lambda q: jnp.sum(jnp.sin(_dense(dict(p, **q), choice, weights, held)))
        )({n: p[n] for n in names})
        want = {n: want[n] + dense[n][4:8] for n in names}

        def loss(mats, sinks):
            y, stats = routed_experts(
                p["x"].astype(dtype), choice, weights,
                *(mats[n].astype(dtype) for n in names), held, tile=8,
                grad_sinks=sinks,
            )
            return jnp.sum(jnp.sin(y)), stats

        mats = {n: p[n][4:8] for n in names}
        d_mats, stats = jax.grad(loss, has_aux=True)(mats, None)
        plain = {n: plain[n] + d_mats[n] for n in names}
        assert float(stats["dropped_slots"]) == 0.0
        assert float(stats["grad_sink_leaves"]) == 0.0
        if sink:
            (d_mats, d_sinks), stats = jax.grad(loss, (0, 1), has_aux=True)(
                mats, tuple(acc[n] for n in names)
            )
            assert all(float(jnp.max(jnp.abs(d_mats[n]))) == 0.0 for n in names)
            assert all(d.dtype == jnp.float32 for d in d_sinks)
            assert float(stats["dropped_slots"]) == 0.0
            assert float(stats["grad_sink_leaves"]) == 3.0
            acc = dict(zip(names, d_sinks))
        else:
            acc = plain
    exact = dtype == jnp.float32
    for n in names:
        # the expert no tile reached keeps what the accumulator held
        assert float(jnp.max(jnp.abs(acc[n][2] - start[n][2]))) == 0.0
        assert _relative(acc[n], want[n]) < (1e-6 if exact else 1e-2)
        # against the pass of its own: the same sums, but for its rounding
        # of each micro-batch's gradient to the compute dtype
        assert _relative(acc[n], plain[n]) < (1e-6 if exact else 2.0 ** -8)


# ---------------------------------------------------- the run-length walk

TILE, BULK = 8, 4  # the tiny configs' tile; tiles a bulk iteration: 32 rows
RUNS = [BULK, 2, 3]  # tiles a bulk iteration, the walks tried


def _choice_of_sizes(sizes, first=2):
    """One slot a token: ``sizes[i]`` tokens choose held expert first + i,
    five more an absent one."""
    experts = np.concatenate(
        [np.full(n, first + i) for i, n in enumerate(sizes)] + [np.zeros(5)]
    )
    rng = np.random.default_rng(len(experts))
    return jnp.asarray(rng.permutation(experts)[:, None], jnp.int32)


def _walked_tiles(walk, tile, m):
    """[(first tile, tiles)] of every bulk, then every tail iteration."""
    assert len(walk) == 2
    iterations = []
    for (starts, count), n in zip(walk, (m, 1)):
        assert starts.dtype == jnp.int32
        iterations += [(int(s), n) for s in np.asarray(starts)[:int(count)]]
    assert all(start % tile == 0 for start, _n in iterations)
    return [(start // tile, n) for start, n in iterations]


@pytest.mark.parametrize("m", RUNS)
@pytest.mark.parametrize("sizes", [
    (0, 9, 0), (1, 9, 1), (TILE - 1, 9, TILE - 1), (TILE, 9, TILE),
    (BULK * TILE - 1, 9, BULK * TILE - 1), (BULK * TILE, 9, BULK * TILE),
    (BULK * TILE + 1, 9, BULK * TILE + 1),
    (3 * BULK * TILE + 2 * TILE, 9, 3 * BULK * TILE + 2 * TILE),
    (0, 1, TILE - 1, TILE, BULK * TILE - 1, BULK * TILE, BULK * TILE + 1,
     3 * BULK * TILE + 2 * TILE),
    (200,),
], ids=["empty", "one", "tile-1", "tile", "bulk-1", "bulk", "bulk+1",
        "three_bulks_two_tails", "every_size", "all_to_one_expert"])
def test_the_walks_iterations_cover_every_tile_in_use_once(sizes, m):
    choice = _choice_of_sizes(sizes)
    held = (2, len(sizes))
    row_slot, tile_expert, tiles, dropped, (walk, bulk_rows) = _tile_plan(
        choice, held, TILE, m
    )
    tile_expert, tiles = np.asarray(tile_expert), int(tiles)
    padded = [-(-n // TILE) for n in sizes]
    assert tiles == sum(padded) and int(dropped) == 0
    assert int(jnp.sum(row_slot >= 0)) == sum(sizes)
    # static bounds: R / (m · tile) bulk iterations and never more than
    # R / tile tails
    (bulk_starts, bulk_count), (tail_starts, tail_count) = walk
    assert bulk_starts.shape[0] == row_slot.shape[0] // (m * TILE)
    assert tail_starts.shape[0] <= row_slot.shape[0] // TILE
    covered = np.zeros(len(tile_expert), int)
    for first, n in _walked_tiles(walk, TILE, m):
        covered[first:first + n] += 1
        # an iteration never spans two experts
        assert len(set(tile_expert[first:first + n])) == 1
    assert covered[:tiles].tolist() == [1] * tiles
    assert not covered[tiles:].any()
    assert int(bulk_count) == sum(p // m for p in padded)
    assert int(tail_count) == sum(p % m for p in padded)
    assert int(bulk_count) * m + int(tail_count) == tiles
    # a group's bulk iterations come first and hold its first rows
    assert int(bulk_rows) == sum(
        min(n, p // m * m * TILE) for n, p in zip(sizes, padded)
    )


def test_a_bulk_of_one_tile_is_the_single_size_walk():
    choice = _choice_of_sizes((40, 3, 0, 17))
    _, tile_expert, tiles, _, (walk, bulk_rows) = _tile_plan(
        choice, (2, 4), TILE, 1
    )
    assert walk[1][0].shape == (0,)  # no tail loop
    assert [t for t, _n in _walked_tiles(walk, TILE, 1)] == list(
        range(int(tiles))
    )
    assert int(bulk_rows) == 60  # every row, by the definition


def _two_micro_batches(m, activation, dtype, choice):
    """The sinks after two micro-batches walked at ``m`` tiles a bulk
    iteration, what the loop returned beside them (y, dx, d weights) in the
    second, and its stats."""
    names, held = ("gate", "up", "down"), (4, 4)
    weights = jnp.full(choice.shape, 0.7)
    sinks = tuple(
        0.5 + jnp.zeros(_layer()[n][4:8].shape, jnp.float32) for n in names
    )
    for seed in (0, 1):
        p = _layer(seed)

        def loss(x, w, s):
            y, stats = routed_experts(
                x.astype(dtype), choice, w,
                *(p[n][4:8].astype(dtype) for n in names), held, tile=TILE,
                grad_sinks=s, activation=activation, run_tiles=m,
            )
            return jnp.sum(jnp.sin(y)), (y, stats)

        (dx, dw, sinks), (y, stats) = jax.grad(
            loss, (0, 1, 2), has_aux=True
        )(p["x"], weights, sinks)
    return sinks, (y, dx, dw), stats


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("m,share", [
    (BULK, 32 / 96), (2, 64 / 96), (3, 72 / 96),
])
def test_the_run_length_walk_equals_the_single_size_walk(
    m, share, activation, dtype
):
    """A bulk iteration contracts a weight gradient over m · 8 rows in one
    float32-accumulating dot where the single-size walk adds m partial sums
    in float32: the same sums in another order, over two micro-batches into
    the same sinks."""
    t = np.arange(T)
    first = np.where(t < 40, 5, 4)  # expert 5: five tiles, expert 4: one
    choice = jnp.asarray(np.stack([first, 6 + t % 2, 12 + t % 4], -1),
                         jnp.int32)  # experts 6 and 7: three tiles each
    runs, runs_out, runs_stats = _two_micro_batches(
        m, activation, dtype, choice
    )
    single, single_out, single_stats = _two_micro_batches(
        1, activation, dtype, choice
    )
    assert float(runs_stats["bulk_row_share"]) == pytest.approx(share)
    assert float(runs_stats["dropped_slots"]) == 0.0
    assert float(single_stats["dropped_slots"]) == 0.0
    # the operands are the same bits either way; only the order of the
    # float32 sums differs
    for got, want in zip(runs + runs_out, single + single_out):
        assert got.dtype == want.dtype
        assert _relative(got.astype(jnp.float32),
                         want.astype(jnp.float32)) < 2e-6


@pytest.mark.parametrize("sizes,share", [
    ((9, 9, 9, 9), 0.0),  # two tiles each
    ((BULK * TILE - 1, BULK * TILE - 8, 3, 0), 31 / 58),  # 4 tiles; 3; 1
    ((BULK * TILE, 1, 0, 0), 32 / 33),
    ((3 * BULK * TILE + 2 * TILE, 0, 0, 0), 96 / 112),
    ((0, 0, 0, 0), 0.0),
], ids=["never", "a_padded_bulk", "an_exact_bulk", "three_bulks", "no_rows"])
def test_bulk_row_share_is_the_real_rows_the_bulk_iterations_take(
    sizes, share
):
    choice = _choice_of_sizes(sizes, first=4)
    p = _layer(5)
    x = jnp.tile(p["x"], (1 + choice.shape[0] // T, 1))[:choice.shape[0]]
    y, stats = routed_experts(
        x, choice, jnp.ones(choice.shape), p["gate"][4:8], p["up"][4:8],
        p["down"][4:8], (4, 4), tile=TILE, run_tiles=BULK,
    )
    assert float(stats["bulk_row_share"]) == pytest.approx(share)
    assert float(stats["dropped_slots"]) == 0.0
    assert float(stats["local_slot_share"]) == pytest.approx(
        sum(sizes) / choice.shape[0]
    )
    np.testing.assert_allclose(
        y, _dense(dict(p, x=x), choice, jnp.ones(choice.shape), (4, 4)),
        atol=1e-5, rtol=1e-5,
    )

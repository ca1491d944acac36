"""The held experts' compute-dtype matrices as an INPUT of ``accumulate_step``
(``parallel/train_step._StepWithComputeCopies``; ``models/decoder.
COMPUTE_COPIES``): the step with the copies equals the step that casts in
place bit for bit, the copies are cast once per set of weights and never kept
past one, and a loss with nothing marked builds the program it always did."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dedloc_tpu.models.decoder import EXPERT_LEAVES
from dedloc_tpu.parallel.train_step import (
    TrainState,
    _StepWithComputeCopies,
    make_accumulate_step,
    make_guarded_apply_step,
    zeros_like_grads,
)
from dedloc_tpu.roles.common import (
    build_loss_fn,
    build_model,
    drop_collator_keys,
    model_family,
)

# (size, depth): kanana-2's expert layers under ONE scan, LFM2's dense layer +
# a whole period under ``scan_periods``' scan (its cell's cut), the others'
# first layers unrolled — a compile each, so no deeper than each path needs
EXPERT_DECODERS = [
    ("kanana2_tiny", 0), ("lfm2_tiny", 5), ("smallthinker_tiny", 2),
    ("sdar_tiny", 2), ("laguna_tiny", 3),
]
BUILDS = "moe.compute_copy_builds"


def _case(size, batches=2, layers=0):
    """(params, that many micro-batches, the table's loss) of a tiny model
    (``layers``: its depth, 0 the size's own)."""
    cfg, model = build_model(size, num_hidden_layers=layers)
    source = model_family(size).synthetic_batches(cfg, 2, 32, 0)
    drawn = [
        jax.tree.map(jnp.asarray, drop_collator_keys(next(source)))
        for _ in range(batches)
    ]
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32)
    )["params"]
    return params, drawn, build_loss_fn(model)


def _cast_in_place(loss_fn):
    """The same loss and sinks, the marked leaves cast by the layer itself."""
    return dataclasses.replace(loss_fn, compute_dtype=None)


def _run(step, params, batches):
    acc, n = zeros_like_grads(params), jnp.zeros([], jnp.int32)
    for i, batch in enumerate(batches):
        acc, n, metrics = step(params, acc, n, batch, jax.random.PRNGKey(i))
    return jax.device_get((acc, metrics))


def _assert_same_bits(got, want):
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)
    ):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


def _held_casts(lowered_text, params):
    """float32 -> bf16 ``convert``s over a held matrix's shape (a layer's,
    or the scanned stack's) in a lowered module."""
    shapes = {
        "x".join(map(str, leaf.shape[-3:]))
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if path[-1].key in EXPERT_LEAVES
    }
    return sum(
        len(re.findall(
            rf"stablehlo\.convert .*tensor<(?:\d+x)*{shape}xf32>\) -> "
            rf"tensor<(?:\d+x)*{shape}xbf16>", lowered_text,
        )) for shape in shapes
    )


@pytest.mark.parametrize(
    "size,layers", EXPERT_DECODERS, ids=[size for size, _ in EXPERT_DECODERS]
)
def test_the_step_with_copies_equals_the_in_place_casts_bit_for_bit(
    size, layers
):
    """Two micro-batches of one set of weights: every accumulator leaf and
    every metric of the role's step equal the step whose layers cast the
    held matrices themselves; ``remat.kept_bytes`` too; ONE run of the cast
    program; three copies a routed layer, as many as sinks."""
    params, batches, loss_fn = _case(size, layers=layers)
    step = make_accumulate_step(loss_fn)
    assert isinstance(step, _StepWithComputeCopies)
    in_place = make_accumulate_step(_cast_in_place(loss_fn))
    assert not isinstance(in_place, _StepWithComputeCopies)
    acc, metrics = _run(step, params, batches)
    want_acc, want_metrics = _run(in_place, params, batches)
    copied = metrics.pop("moe.compute_copy_leaves")
    assert copied == metrics["moe.grad_sink_leaves"] > 0 and copied % 3 == 0
    assert want_metrics.pop("moe.compute_copy_leaves") == 0.0
    _assert_same_bits(acc, want_acc)
    _assert_same_bits(metrics, want_metrics)
    assert step.gauges == in_place.gauges
    assert step.gauges["remat.kept_bytes"] > 0
    assert step.counters == {BUILDS: 1} and in_place.counters == {}


@pytest.fixture(scope="module")
def kanana():
    """kanana2_tiny (its expert layers under ONE ``nn.scan``): params, three
    batches, the role's step and the in-place one, each traced once."""
    params, batches, loss_fn = _case("kanana2_tiny", batches=3)
    return params, batches, make_accumulate_step(loss_fn), (
        make_accumulate_step(_cast_in_place(loss_fn))
    )


def _applied(params, grads):
    """A real ``guarded_apply_step`` on ``params`` (donated, as the role's)."""
    tx = optax.sgd(0.5)
    state, ok = make_guarded_apply_step(tx)(
        TrainState.create(params, tx), grads
    )
    return state.params, bool(ok)


def _new_weights_by(kind, params, step, batches):
    fresh = jax.tree.map(jnp.copy, params)  # ``params`` itself stays whole
    if kind == "apply":
        grads = _run(step, fresh, batches[:1])[0]
        new, ok = _applied(fresh, grads)
        assert ok
    elif kind == "rollback":
        grads = jax.tree.map(lambda p: jnp.full_like(p, jnp.nan), fresh)
        new, ok = _applied(fresh, grads)
        assert not ok  # the values it had, in arrays of their own
        _assert_same_bits(jax.device_get(new), jax.device_get(params))
    else:  # a state that came from elsewhere (a download, a checkpoint)
        new = jax.tree_util.tree_map_with_path(
            lambda path, p: jnp.asarray(
                np.asarray(p) * (1.5 if path[-1].key in EXPERT_LEAVES else 1)
            ), fresh,
        )
    return new


@pytest.mark.parametrize("kind", ["apply", "rollback", "replaced_state"])
def test_new_weights_rebuild_the_copies_and_the_same_weights_do_not(
    kanana, kind
):
    params, batches, step, in_place = kanana
    new = _new_weights_by(kind, params, step, batches)
    # the old set is let go BEFORE the cast program builds the next
    cast, seen = step._cast, []

    def casting(marked):
        seen.append((step._copies, step._sources))
        return cast(marked)

    step._cast = casting
    try:
        before = step.counters[BUILDS]
        acc, metrics = _run(step, new, batches)  # three micro-batches
        assert step.counters[BUILDS] == before + 1 and seen == [(None, ())]
        step(
            new, zeros_like_grads(new), jnp.zeros([], jnp.int32), batches[0],
            jax.random.PRNGKey(0),
        )
        assert step.counters[BUILDS] == before + 1
    finally:
        step._cast = cast
    # stale copies would be the OLD weights' (but after a rollback)
    want_acc, want_metrics = _run(in_place, new, batches)
    metrics.pop("moe.compute_copy_leaves")
    want_metrics.pop("moe.compute_copy_leaves")
    _assert_same_bits(acc, want_acc)
    _assert_same_bits(metrics, want_metrics)


def test_a_scanned_stack_takes_its_copies_stacked(kanana):
    """kanana-2's expert layers are one scan: a leaf holds every layer's
    matrix on axis 0, the copies ride the scan the same way (``decoder.
    scan_layers``) and no float32 matrix is cast inside the program."""
    params, batches, step, in_place = kanana
    _run(step, params, batches[:1])
    held = params["layers"]["block"]["mlp"]
    copies = step._copies["layers"]["block"]["mlp"]
    assert sorted(copies) == sorted(EXPERT_LEAVES)
    assert step._copies.keys() == {"layers"}  # nothing but the marked
    for name in EXPERT_LEAVES:
        assert held[name].ndim == 4 and held[name].shape[0] == 2  # layers
        assert copies[name].shape == held[name].shape
        assert copies[name].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            copies[name], held[name].astype(jnp.bfloat16)
        )
    operands = (
        params, zeros_like_grads(params), jnp.zeros([], jnp.int32),
        batches[0], jax.random.PRNGKey(0),
    )
    assert _held_casts(step.lower(*operands).as_text(), params) == 0
    # forward + the layer's remat replay, three matrices each
    assert _held_casts(in_place.lower(*operands).as_text(), params) == 6


@pytest.mark.parametrize("size", ["lfm2_tiny", "sdar_tiny"])
def test_lower_takes_abstract_arguments(size):
    """What the benchmark's scratch analysis and ``tools/tpu_aot.py`` do:
    ``.lower`` on ``jax.eval_shape``'s trees — the inner six-argument
    program, the copies' shapes derived from ``params``, named as the trace
    and the compile events find it, and no held matrix cast inside."""
    cfg, model = build_model(size, num_hidden_layers=3)
    loss_fn = build_loss_fn(model)
    params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((2, 32), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    batch = drop_collator_keys(
        next(model_family(size).synthetic_batches(cfg, 2, 32, 0))
    )
    operands = (
        params, jax.eval_shape(zeros_like_grads, params),
        jax.ShapeDtypeStruct((), jnp.int32), batch, jax.random.PRNGKey(0),
    )
    step = make_accumulate_step(loss_fn)
    lowered = step.lower(*operands)
    text = lowered.as_text()
    assert "module @jit_accumulate_step" in text
    assert _held_casts(text, params) == 0
    assert step.counters == {BUILDS: 0} and step._copies is None
    in_place = make_accumulate_step(_cast_in_place(loss_fn))
    assert _held_casts(in_place.lower(*operands).as_text(), params) > 0
    assert step.gauges == in_place.gauges  # ``remat.kept_bytes``, per trace
    assert step.gauges["remat.kept_bytes"] > 0
    marked = [
        leaf for path, leaf in jax.tree_util.tree_leaves_with_path(params)
        if path[-1].key in EXPERT_LEAVES
    ]
    copies = [x for x in jax.tree.leaves(lowered.in_avals)
              if x.dtype == jnp.bfloat16]
    assert sorted(x.shape for x in copies) == sorted(x.shape for x in marked)


@pytest.mark.parametrize("size", ["tiny", "ouro_tiny"])
def test_a_loss_with_nothing_marked_builds_the_program_it_was(size):
    """ALBERT and Ouro mark no leaf: what comes back is the jitted
    five-argument function itself, with no copies to own — the module
    ``test_lfm2_moe_role.py`` holds to the hand-written step, text for text."""
    cfg, model = build_model(size)
    loss_fn = build_loss_fn(model)
    assert not hasattr(loss_fn, "compute_dtype")
    step = make_accumulate_step(loss_fn)
    assert not isinstance(step, _StepWithComputeCopies)
    assert step.counters == {} and step.gauges == {}
    batch = drop_collator_keys(
        next(model_family(size).synthetic_batches(cfg, 2, 32, 0))
    )
    params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((2, 32), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    operands = (
        params, jax.eval_shape(zeros_like_grads, params),
        jax.ShapeDtypeStruct((), jnp.int32), batch, jax.random.PRNGKey(0),
    )
    taken = jax.tree.leaves(step.lower(*operands).in_avals)
    assert len(taken) == len(jax.tree.leaves(operands))  # nothing beside them
    assert step.gauges["remat.kept_bytes"] > 0  # read off that trace


def test_a_mesh_takes_no_copies():
    """Under a data mesh there are no sinks, so no copies either: the plain
    five-argument program, its layers casting as they always did."""
    from jax.sharding import Mesh

    params, batches, loss_fn = _case("kanana2_tiny", batches=1)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    step = make_accumulate_step(loss_fn, mesh=mesh)
    assert not isinstance(step, _StepWithComputeCopies)
    _acc, metrics = _run(step, params, batches)
    assert float(metrics["moe.compute_copy_leaves"]) == 0.0
    assert float(metrics["moe.grad_sink_leaves"]) == 0.0

"""What the decoders' tests share (``tests/test_<family>_model.py`` and
``tests/test_<family>_role.py``; not a test file): the perturbation away
from the initialiser, the gradient comparisons, ONE cached case a
(family, overrides) — the tiny model, its seeded parameters and batch, and
the model's own loss and gradients on them, computed once a process — and
the assertions that read the same in every family's file. A family's file
binds them to its row (``Family``) and keeps what is the model's own.

The cache holds VALUES (configs, arrays), never jitted callables: the
executables behind them go with ``jax.clear_caches()``
(``tests/conftest.py``) and nothing here is compiled twice for it."""
import collections
import dataclasses
import functools
import json
import types
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.models.decoder import BIAS, EXPERT_LEAVES


def perturbed(params, seed=2, scale=0.1):
    """``params`` away from the initialiser's symmetry: norms off 1, biases
    off 0 by more than neighbouring scores differ, every matrix of the size
    at which a different function shows."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + scale * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)
    ])


def worst_leaf(got, want):
    """The largest relative L2 distance of a leaf of ``got`` from its leaf
    of ``want``."""
    worst = 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        norm = float(jnp.linalg.norm(b))
        if norm > 0:
            worst = max(worst, float(jnp.linalg.norm(a - b)) / norm)
    return worst


def bias_leaves(tree):
    """The correction-bias leaves of ``tree``, as numpy arrays."""
    return [
        np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if path[-1].key == BIAS
    ]


def without_bias(tree):
    """``tree`` with its correction-bias leaves zeroed: there the model's
    'gradient' is the load statistic, which is compared on its own."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if path[-1].key == BIAS else x, tree
    )


def model_grads(loss, model, params, batch):
    """``((loss, metrics), grads)`` of ``loss(model, params, batch)``."""
    return jax.jit(jax.value_and_grad(
        lambda p: loss(model, p, batch), has_aux=True
    ))(params)


def reference_grads(reference, kwargs, params, batch, choices=None):
    """``((loss, out), grads)`` of ``reference.forward`` under matmul
    precision 'highest', routed by ``choices`` where given."""
    def loss(p, choices):
        with jax.default_matmul_precision("highest"):
            out = reference.forward(p, batch, choices=choices, **kwargs)
        return out["loss"], out

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params, choices)


def _every_leaf(tree):
    return tree


def token_batch(cfg, seq):
    """Two seeded rows of ``seq`` tokens and their next tokens."""
    rows = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, seq + 1)
    ).astype(np.int32)
    return {"input_ids": jnp.asarray(rows[:, :-1]),
            "labels": jnp.asarray(rows[:, 1:])}


@dataclasses.dataclass(frozen=True, eq=False)
class Family:
    """A family's row: what its file binds the shared code to."""

    tiny: Callable  # Config.tiny
    module: type
    loss: Callable  # loss(model, params, batch) -> (loss, metrics)
    reference: types.ModuleType
    reference_kwargs: Callable  # (cfg, **changes) -> reference.forward's
    loss_tol: float
    leaf_tol: float
    seq: int = 64
    batch: Callable = token_batch  # (cfg, seq) -> the model's batch
    # the gradient leaves that are compared as gradients
    comparable: Callable = _every_leaf


Case = collections.namedtuple("Case", "cfg model params batch")
# how often the model's own gradients were computed, by (family, overrides)
OWN_COMPUTED = collections.Counter()


@functools.lru_cache(maxsize=None)
def _case(family, seq, overrides):
    cfg = family.tiny(dtype=jnp.float32, **dict(overrides))
    model = family.module(cfg)
    batch = family.batch(cfg, seq)
    params = perturbed(
        model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    )
    return Case(cfg, model, params, batch)


@functools.lru_cache(maxsize=None)
def _own(family, seq, overrides):
    OWN_COMPUTED[family, seq, overrides] += 1
    _cfg, model, params, batch = _case(family, seq, overrides)
    return model_grads(family.loss, model, params, batch)


@functools.lru_cache(maxsize=None)
def _reference_own(family, seq, overrides):
    cfg, _model, params, batch = _case(family, seq, overrides)
    return reference_grads(
        family.reference, family.reference_kwargs(cfg), params, batch
    )


def _key(family, seq, overrides):
    return family, seq or family.seq, tuple(sorted(overrides.items()))


def case(family, seq=None, **overrides):
    """``(cfg, model, params, batch)`` of the family's tiny model in
    float32 under ``overrides``: built once a process."""
    return _case(*_key(family, seq, overrides))


def own(family, seq=None, **overrides):
    """``((loss, metrics), grads)``: the model's own result on ``case``,
    computed once a process."""
    return _own(*_key(family, seq, overrides))


def reference_own(family, seq=None, **overrides):
    """``((loss, out), grads)``: the reference's result on ``case`` as the
    config describes it, routed by its own choices; once a process."""
    return _reference_own(*_key(family, seq, overrides))


# ------------------------------------- the assertions of the model files


def check_model_matches_reference(family, seq=None, **overrides):
    """Float32 on both sides: the choices agree exactly, nothing is forced;
    loss and every gradient leaf within the family's tolerances; no slot
    dropped; the held experts' share of the slots. Returns what the
    family's own assertions read: ``(cfg, metrics, grads, ref, ref_grads)``."""
    cfg = case(family, seq, **overrides).cfg
    (loss, metrics), grads = own(family, seq, **overrides)
    (ref_loss, ref), ref_grads = reference_own(family, seq, **overrides)
    np.testing.assert_array_equal(metrics["moe.choice"], ref["choice"])
    assert abs(float(loss) - float(ref_loss)) <= (
        family.loss_tol * float(ref_loss)
    )
    assert worst_leaf(
        family.comparable(grads), family.comparable(ref_grads)
    ) <= family.leaf_tol
    assert float(metrics["moe.dropped_slots"]) == 0.0
    shards = cfg.expert_shard[1]
    assert abs(
        float(metrics["moe.local_slot_share"]) - 1.0 / shards
    ) < (0.0 if shards == 1 else 0.15) + 1e-6
    return cfg, metrics, grads, ref, ref_grads


def check_a_different_function_fails(family, changes, given_choices=True):
    """A reference that reads the config another way is far off the
    model's gradients on the default case — given the model's routing
    (``given_choices``), so that what differs is the function alone.
    Returns ``(metrics, ref)`` for a family's further assertions."""
    cfg, _model, params, batch = case(family)
    (_loss, metrics), grads = own(family)
    (_ref_loss, ref), ref_grads = reference_grads(
        family.reference, family.reference_kwargs(cfg, **changes), params,
        batch, choices=metrics["moe.choice"] if given_choices else None,
    )
    off = worst_leaf(family.comparable(grads), family.comparable(ref_grads))
    assert off > 100 * family.leaf_tol, off
    return metrics, ref


def check_the_model_under_overrides(family, seq=None, **overrides):
    """The model under ``overrides`` — the flash kernels, in interpreter
    mode — against the reference routed by the model's choices: the loss
    and every gradient leaf. Returns the model's metrics."""
    cfg, _model, params, batch = case(family, seq, **overrides)
    (loss, metrics), grads = own(family, seq, **overrides)
    (ref_loss, _ref), ref_grads = reference_grads(
        family.reference, family.reference_kwargs(cfg), params, batch,
        choices=metrics["moe.choice"],
    )
    assert abs(float(loss) - float(ref_loss)) <= (
        family.loss_tol * float(ref_loss)
    )
    assert worst_leaf(
        family.comparable(grads), family.comparable(ref_grads)
    ) <= family.leaf_tol
    return metrics


def check_the_choices_differ(metrics, ref):
    assert np.mean(
        np.asarray(metrics["moe.choice"]) != np.asarray(ref["choice"])
    ) > 0.05


def check_reference_routed_by_given_choices(family):
    """Routed by the program's choices the reference reproduces its own
    result (the chip check routes it so)."""
    cfg, _model, params, batch = case(family)
    (loss, out), _ = reference_own(family)
    (again, _), _ = reference_grads(
        family.reference, family.reference_kwargs(cfg), params, batch,
        choices=out["choice"],
    )
    assert float(loss) == pytest.approx(float(again), rel=1e-6)


def check_the_routed_shares_add_up(family, layer, routed, inputs, whole,
                                   experts, shares=8):
    """One layer's FFN: the routed parts that the ``shares`` shares compute
    (``routed(share_cfg)`` the module, each told its share and given its
    slice of ``layer``'s ``experts`` leaves) are ``whole["routed"]``, the
    uncut reference's — with no shared expert nothing is computed alike on
    every chip but the router, whose choices agree."""
    total, local = 0.0, 0.0
    for index in range(shares):
        share = family.tiny(dtype=jnp.float32, expert_shard=(index, shares))
        first, held = share.held_experts
        mine = dict(layer, **{
            name: layer[name][first:first + held] for name in experts
        })
        y, routing = routed(share).apply({"params": mine}, *inputs)
        total = total + y
        local += float(routing["local_slot_share"])
        np.testing.assert_array_equal(routing["choice"], whole["choice"])
        assert float(routing["dropped_slots"]) == 0.0
    assert local == pytest.approx(1.0, abs=1e-6)
    want = whole["routed"].reshape(inputs[0].shape)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=2e-5)
    # and no share alone is the layer
    assert float(jnp.max(jnp.abs(y - want))) > 1e-3


# -------------------------------------- the assertions of the role files


def trainer_args(tmp_path, model_size, argv=()):
    """A solo tiny trainer's arguments: rows of 32, two micro-batches of two
    a boundary."""
    base = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", model_size,
        "--training.seq_length", "32",
        "--training.per_device_batch_size", "2",
        "--training.gradient_accumulation_steps", "2",
        "--training.warmup_steps", "2",
        "--training.total_steps", "50",
        "--training.output_dir", str(tmp_path / "out"),
        "--averager.averaging_expiration", "1.0",
        "--averager.min_refresh_period", "0.1",
        "--averager.default_refresh_period", "0.3",
    ]
    return parse_config(CollaborationArguments, base + list(argv))


def run_tiny_trainer(tmp_path, model_size, argv=(), max_local_steps=9):
    """The tiny preset through ``run_trainer`` with telemetry on: at least
    two global steps, at least two stepped records, finite losses. Returns
    ``(state, stepped records, every step record)``."""
    from dedloc_tpu.roles.trainer import run_trainer

    events = tmp_path / "events.jsonl"
    state = run_trainer(trainer_args(tmp_path, model_size, [
        "--optimizer.target_batch_size", "8",
        "--training.max_local_steps", str(max_local_steps),
        "--telemetry.enabled", "true",
        "--telemetry.event_log_path", str(events),
        *argv,
    ]))
    assert int(state.step) >= 2
    log = [json.loads(line) for line in events.read_text().splitlines()]
    records = [e for e in log if e.get("event") == "step.record"]
    stepped = [e for e in records if e.get("stepped")]
    assert len(stepped) >= 2
    assert all(np.isfinite([rec["loss"] for rec in stepped if "loss" in rec]))
    return state, stepped, records


def check_routing_records(stepped, shard, expert_layers, slack=0.25):
    """Every stepped record: no slot dropped, a load gauge an expert layer
    and none beyond, the held share of the slots, three sink leaves a
    layer."""
    count = int(shard.split("/")[1])
    for rec in stepped:
        assert rec["moe.dropped_slots"] == 0.0
        assert all(
            rec[f"moe.load_max_over_mean.{i}"] >= 1.0
            for i in range(1, expert_layers + 1)
        )
        assert f"moe.load_max_over_mean.{expert_layers + 1}" not in rec
        assert rec["moe.local_slot_share"] == pytest.approx(
            1.0 / count, abs=0.0 if count == 1 else slack
        )
        assert rec["moe.grad_sink_leaves"] == 3.0 * expert_layers


def check_kept_bytes_is_the_shapes(stepped, family, policy, params,
                                   model_size, **cut):
    """The remat policy's counter ``remat.kept_bytes`` on every record is
    what ``stash_bytes`` reads from the shapes alone, under the family's
    default ``policy``."""
    from dedloc_tpu.parallel.train_step import stash_bytes
    from dedloc_tpu.roles.common import build_loss_fn, build_model

    cfg, model = build_model(model_size, **cut)
    assert cfg.remat_policy == policy
    kept = stash_bytes(
        build_loss_fn(model), params,
        next(family.synthetic_batches(cfg, 2, 32, 0)), jax.random.PRNGKey(0),
    )
    assert {rec["remat.kept_bytes"] for rec in stepped} == {float(kept)}
    return cfg


def _two_micro_batches(accumulate, params, batches):
    """``(accumulator, last metrics)`` after ``accumulate`` over
    ``batches`` from zeros."""
    from dedloc_tpu.parallel.train_step import zeros_like_grads

    acc, n = zeros_like_grads(params), jnp.zeros([], jnp.int32)
    for i, batch in enumerate(batches):
        acc, n, metrics = accumulate(
            params, acc, n, batch, jax.random.PRNGKey(i)
        )
    return acc, metrics


def sink_case(size, **overrides):
    """(model, params, two batches, the table's loss) of a tiny decoder."""
    from dedloc_tpu.roles.common import build_loss_fn, build_model

    cfg, model = build_model(size, **overrides)
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (2, 2, 32), 0, cfg.vocab_size
    )
    params = model.init(jax.random.PRNGKey(0), ids[0])["params"]
    batches = [{"input_ids": x, "labels": jnp.roll(x, -1, 1)} for x in ids]
    return model, params, batches, build_loss_fn(model)


def check_accumulate_step_leaves_expert_gradients_in_the_accumulator(
    params, batches, loss_fn, sink_leaves, expert_leaves
):
    """``make_accumulate_step(build_loss_fn(model))`` — the call the role
    and the benchmark make — hands the accumulator's expert leaves to the
    tile loop: over two micro-batches they hold the float32 sums the plain
    step rounds to bf16 first, every other leaf is the plain step's
    exactly. ``sink_leaves`` layers' leaves in ``expert_leaves`` leaves of
    the tree."""
    from dedloc_tpu.parallel.train_step import (
        GradSinkLoss,
        make_accumulate_step,
    )

    assert isinstance(loss_fn, GradSinkLoss)
    sunk, metrics = _two_micro_batches(
        make_accumulate_step(loss_fn), params, batches
    )
    plain, plain_metrics = _two_micro_batches(
        make_accumulate_step(loss_fn.loss), params, batches
    )
    assert float(metrics["moe.grad_sink_leaves"]) == sink_leaves
    assert float(plain_metrics["moe.grad_sink_leaves"]) == 0.0
    assert float(metrics["loss"]) == float(plain_metrics["loss"])
    seen = 0
    for (path, got), want in zip(
        jax.tree_util.tree_leaves_with_path(sunk), jax.tree.leaves(plain)
    ):
        if path[-1].key in EXPERT_LEAVES:
            seen += 1
            apart = float(np.linalg.norm(got - want) / np.linalg.norm(want))
            assert 0.0 < apart < 2.0 ** -8, (path, apart)
        else:
            np.testing.assert_array_equal(got, want, err_msg=str(path))
    assert seen == expert_leaves

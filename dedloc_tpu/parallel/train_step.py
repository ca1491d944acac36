"""pjit train-step builders: local accumulation vs global apply.

The collaborative loop (SURVEY.md §3.1) splits one "step" into two phases with
different cadences, so we compile them separately:

  accumulate — per micro-batch: forward/backward under jit, grads summed into
               a persistent accumulator (donated). Sharded batch ⇒ the grad
               mean rides an ICI psum inserted by XLA. Runs constantly.
  apply      — once per GLOBAL optimizer step, on (possibly peer-averaged)
               gradients: optimizer update + LR schedule by global step.

``make_local_train_step`` fuses both (scan over micro-batches) for the
single-peer / CI path — capability of the plain HF Trainer loop with
gradient_accumulation_steps (albert/arguments.py:109).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import chex
import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dedloc_tpu.utils.logging import get_logger

logger = get_logger(__name__)


class TrainState(struct.PyTreeNode):
    """Model + optimizer state keyed by the GLOBAL collaboration step.

    ``step`` mirrors ``collaboration_state.optimizer_step`` in the reference
    (consumed by the swav loss at standard_train_step.py:153).
    """

    step: chex.Array
    params: Any
    opt_state: Any

    @classmethod
    def create(cls, params, tx: optax.GradientTransformation) -> "TrainState":
        return cls(
            step=jnp.zeros([], jnp.int32),
            params=params,
            opt_state=tx.init(params),
        )


LossFn = Callable[..., Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]]


@dataclasses.dataclass(frozen=True)
class GradSinkLoss:
    """A loss whose backward can leave some leaves' gradients IN the float32
    accumulator: ``loss(params, batch, rng, grad_sinks=None)`` and
    ``sink_mask`` (a parameter-shaped tree -> tree of bools). Handed the
    accumulator's marked leaves as ``grad_sinks`` (a nested dict, branches
    without one dropped) and differentiated with respect to them too, the
    sinks' cotangent is ``sink + gradient`` and the marked parameters' own
    gradient zero. Called without them it is any other loss.

    ``compute_dtype`` (the model's, ``cfg.dtype``; None: the loss takes no
    copies): the loss also takes ``compute_copies``, the marked leaves of
    ``params`` already cast to it, and then casts none of them itself — the
    marked parameters' gradient is zero under sinks, so nothing flows back
    through that cast and it need not be part of what is differentiated."""

    loss: Callable
    sink_mask: Callable
    compute_dtype: Optional[Any] = None

    def __call__(self, params, batch, rng, **beside):
        # ``grad_sinks=``, ``compute_copies=``: whichever the step hands over
        return self.loss(params, batch, rng, **beside)


def zeros_like_grads(params):
    return jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)


def _residual_bytes(backward, arguments) -> int:
    """Bytes of the leaves of ``backward`` (what ``jax.vjp`` returned: a
    pytree of the residuals its forward kept) that are none of
    ``arguments``' own leaves."""
    held = {id(x) for x in jax.tree.leaves(arguments)}
    kept = {
        id(x): x for x in jax.tree.leaves(backward)
        if isinstance(x, jax.Array) and id(x) not in held
    }
    return sum(x.size * x.dtype.itemsize for x in kept.values())


def stash_bytes(loss_fn: LossFn, params, batch, rng) -> int:
    """Bytes the forward of ONE micro-batch hands its backward besides the
    arguments: the residuals of ``jax.vjp`` over ``loss_fn`` with respect to
    ``params`` — under a layer remat policy the layer inputs and what the
    policy keeps (``models/remat.remat_policy_object``), plus what lies
    outside the remat'd layers (position tables, the final norm, the head's
    chunks). From the shapes alone: ``params`` and ``batch`` may be
    ``jax.ShapeDtypeStruct`` trees, nothing is allocated or run. It counts
    what JAX's backward READS; which of it XLA keeps in HBM and which it
    fuses into a producer is the compiler's (``memory_analysis``). The
    accumulate step reads the same number off its own trace
    (``make_accumulate_step``: ``step.gauges``)."""
    found = {}

    def forward(params, batch, rng):
        _, backward, _ = jax.vjp(
            lambda p: loss_fn(p, batch, rng), params, has_aux=True
        )
        found["bytes"] = _residual_bytes(backward, (params, batch, rng))

    jax.eval_shape(forward, params, batch, rng)
    return found["bytes"]


def _marked_subtree(tree, mask):
    """The nested dict of ``tree``'s leaves where ``mask`` (a tree of bools
    of its structure) is True."""
    marked: Dict[str, Any] = {}
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for (path, leaf), keep in zip(leaves, jax.tree.leaves(mask)):
        if keep:
            node = marked
            for key in path[:-1]:
                node = node.setdefault(key.key, {})
            node[path[-1].key] = leaf
    return marked


def _noting_cotangents(sinks, reached: set):
    """``sinks`` behind identities whose backward notes in ``reached``, while
    the gradient is traced, the path of each leaf a cotangent comes back to.
    A leaf that no module read has none, and its rule never runs."""

    def through(path, leaf):
        @jax.custom_vjp
        def identity(x):
            return x

        def noted(_, cotangent):
            reached.add(path)
            return (cotangent,)

        identity.defvjp(lambda x: (x, None), noted)
        return identity(leaf)

    return jax.tree_util.tree_map_with_path(through, sinks)


def make_accumulate_step(
    loss_fn: LossFn,
    mesh: Optional[Mesh] = None,
    seq_axis: Optional[str] = None,
    seq_length: Optional[int] = None,
    param_sharding: Optional[Any] = None,
) -> Callable:
    """Build jitted (params, grad_acc, n_acc, batch, rng) -> (grad_acc', n_acc', metrics).

    grad_acc holds the running SUM of per-micro-batch mean gradients; n_acc
    counts micro-batches so the caller can normalize before averaging/apply.
    The accumulator is donated: it lives in device memory across calls, so the
    host<->device traffic per micro-batch is just the batch itself.

    ``seq_axis``/``seq_length``: for sequence-parallel (ring-attention)
    meshes, batch leaves whose second dim is the sequence get constrained to
    P("data", seq_axis) at step entry, so inter-layer activations PROPAGATE
    seq-sharded and ring attention's in_specs match with zero per-layer
    relayout — and non-attention activations are S/n per device, the full
    O(S/n) memory win, not just the score matrix's.

    A ``GradSinkLoss`` on ONE device (no ``mesh``: under a data mesh the
    gradient is a mean over devices and the accumulator is not) is handed
    the accumulator's marked leaves and returns them summed: for those the
    new accumulator is the sink's cotangent, no ``a + g`` pass of its own.
    A marked leaf that no module read would come back zero: tracing the step
    raises on one.

    Where that loss states its ``compute_dtype``, the marked leaves of
    ``params`` in that dtype are an INPUT of the device program, not a value
    every micro-batch's forward and remat replay recompute: what is returned
    (``_StepWithComputeCopies``, the same call, ``.lower`` and ``.gauges``)
    owns them, casts them in a jitted program of its own
    (``expert_compute_copies``) when ``params``' marked leaves are not the
    arrays it cast last — once a global step: an apply, a NaN rollback and a
    state download all yield new arrays, nothing else does — and runs the
    six-argument ``accumulate_step`` on them. Any other loss, and any loss on
    a mesh, builds the five-argument program it always did.

    ``step.gauges`` (a dict, filled when the step is traced): ``remat.
    kept_bytes`` — the bytes one micro-batch's forward hands its backward
    besides the step's arguments (``stash_bytes``: the mechanism's counter
    of the model's layer remat policy), read off the trace itself.
    ``step.counters`` (a dict of running totals; empty without copies):
    ``moe.compute_copy_builds`` — runs of the cast program.
    """
    sink_mask = getattr(loss_fn, "sink_mask", None) if mesh is None else None
    copy_dtype = None if sink_mask is None else getattr(
        loss_fn, "compute_dtype", None
    )
    # host-side readings of the step's last TRACE (no output of the program):
    # on the returned step as ``step.gauges``, empty until it is traced
    gauges: Dict[str, float] = {}

    def accumulate(params, copies, grad_acc, n_acc, batch, rng):
        if mesh is not None and seq_axis is not None:
            def _constrain(x):
                if x.ndim >= 2 and seq_length and x.shape[1] == seq_length:
                    spec = P("data", seq_axis)
                else:
                    spec = P("data")
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, spec)
                )

            batch = jax.tree.map(_constrain, batch)
        # the accumulator's leaves that the loss's backward sums into
        # itself: none for a plain loss, whose step is the ``a + g`` it was
        sinks = {} if sink_mask is None else _marked_subtree(
            grad_acc, sink_mask(grad_acc)
        )
        reached: set = set()

        def loss(params, sinks):
            if not sinks:
                return loss_fn(params, batch, rng)
            beside = {"grad_sinks": _noting_cotangents(sinks, reached)}
            if copies is not None:
                beside["compute_copies"] = copies
            return loss_fn(params, batch, rng, **beside)

        # ``jax.value_and_grad`` in its two halves (the same program), so
        # that the trace can read what the forward keeps for the backward
        value, backward, metrics = jax.vjp(loss, params, sinks, has_aux=True)
        kept = _residual_bytes(
            backward, (params, copies, grad_acc, batch, rng)
        )
        gauges["remat.kept_bytes"] = float(kept)
        # once a trace, beside whatever the caller logs next
        logger.info(f"accumulate_step traced: kept_bytes={kept}")
        grads, sunk = backward(jnp.ones_like(value))
        sunk = dict(jax.tree_util.tree_flatten_with_path(sunk)[0])
        unread = [
            jax.tree_util.keystr(path) for path in sunk if path not in reached
        ]
        if unread:  # its cotangent is zero: the accumulated sum would be lost
            raise ValueError(
                f"marked as gradient sinks, read by no module: {unread}"
            )
        grad_acc = jax.tree_util.tree_map_with_path(
            lambda path, a, g: (
                sunk[path] if path in sunk else a + g.astype(jnp.float32)
            ),
            grad_acc, grads,
        )
        return grad_acc, n_acc + 1, metrics

    # jitted programs carry stable names: a trace, an IR dump or a compile
    # event finds "accumulate_step" after any refactor
    if copy_dtype is None:
        def accumulate_step(params, grad_acc, n_acc, batch, rng):
            return accumulate(params, None, grad_acc, n_acc, batch, rng)
    else:
        def accumulate_step(params, compute_copies, grad_acc, n_acc, batch,
                            rng):
            return accumulate(
                params, compute_copies, grad_acc, n_acc, batch, rng
            )

        return _StepWithComputeCopies(
            jax.jit(accumulate_step, donate_argnums=(2, 3)), sink_mask,
            copy_dtype, gauges,
        )

    kwargs = dict(donate_argnums=(1, 2))
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        # tensor parallelism: params (and the param-shaped grad accumulator)
        # carry the Megatron-style layout; XLA inserts the ICI collectives
        p_sh = param_sharding if param_sharding is not None else repl
        # seq-parallel: leave the batch sharding UNSPECIFIED so the per-leaf
        # layout committed by put_batch (seq dims over seq_axis) flows in
        # as-is; the in-step constraint above is then a no-op safety net
        # instead of an every-micro-batch reshard
        data = None if seq_axis is not None else NamedSharding(mesh, P("data"))
        kwargs.update(
            in_shardings=(p_sh, p_sh, repl, data, repl),
            out_shardings=(p_sh, repl, repl),
        )
    step = jax.jit(accumulate_step, **kwargs)
    step.gauges, step.counters = gauges, {}
    return step


class _StepWithComputeCopies:
    """``make_accumulate_step``'s step for a loss that takes compute-dtype
    copies of its marked leaves: ``(params, grad_acc, n_acc, batch, rng)``
    as any other, ``.lower`` and ``.gauges`` too, around ``inner``, the
    jitted six-argument ``accumulate_step`` (the copies second).

    It keeps the copies and the arrays they were cast from. A call compares
    ``params``' marked leaves with those BY IDENTITY (a reference each: an
    ``id()`` can be another array's after a collection) and, where one
    differs, lets the old copies go, THEN casts the new ones — two sets never
    stand side by side — in ``expert_compute_copies``, a jitted program of
    ONE signature, first build and rebuilds alike (a second one, say a
    donated previous set, would compile after the first global step).
    ``counters['moe.compute_copy_builds']`` counts its runs: one a set of
    weights. Between two applies the copies stay resident (2 bytes a held
    element)."""

    def __init__(self, inner, mask, dtype, gauges):
        self._inner, self._mask, self._dtype = inner, mask, dtype
        self.gauges = gauges
        self.counters = {"moe.compute_copy_builds": 0}
        self._sources: Tuple = ()
        self._copies = None
        # where ``params``' marked leaves lie among its leaves, for the
        # treedef it was read off: the mask walks every path (0.3 ms at 74
        # leaves), a call only flattens
        self._treedef, self._marked = None, ()

        def expert_compute_copies(marked):
            return jax.tree.map(lambda w: w.astype(dtype), marked)

        self._cast = jax.jit(expert_compute_copies)

    def __call__(self, params, grad_acc, n_acc, batch, rng):
        leaves, treedef = jax.tree.flatten(params)
        if self._treedef is None or treedef != self._treedef:
            self._treedef, self._marked = treedef, tuple(
                i for i, keep in enumerate(
                    jax.tree.leaves(self._mask(params))
                ) if keep
            )
        sources = tuple(leaves[i] for i in self._marked)
        if len(sources) != len(self._sources) or any(
            new is not old for new, old in zip(sources, self._sources)
        ):
            self._sources, self._copies = (), None
            self._copies = self._cast(self._marked_of(params))
            self._sources = sources
            self.counters["moe.compute_copy_builds"] += 1
        return self._inner(params, self._copies, grad_acc, n_acc, batch, rng)

    def _marked_of(self, params):
        return _marked_subtree(params, self._mask(params))

    def lower(self, params, grad_acc, n_acc, batch, rng):
        """The inner program lowered, the copies' shapes (and placement)
        derived from ``params``: abstract arguments do."""
        copies = jax.tree.map(
            lambda w: jax.ShapeDtypeStruct(
                w.shape, self._dtype, sharding=getattr(w, "sharding", None)
            ),
            self._marked_of(params),
        )
        return self._inner.lower(params, copies, grad_acc, n_acc, batch, rng)


def make_apply_step(
    tx: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    opt_state_sharding: Optional[Any] = None,
    param_sharding: Optional[Any] = None,
) -> Callable:
    """Build jitted (state, mean_grads) -> state'. Runs once per global step.

    ``opt_state_sharding`` (a NamedSharding pytree from
    ``parallel.zero.opt_state_shardings``) keeps optimizer moments sharded
    ZeRO-style across updates; ``param_sharding`` keeps params (and the
    incoming mean grads) in their tensor-parallel layout. GSPMD inserts
    whatever movement the elementwise update needs.
    """

    def apply_step(state: TrainState, grads) -> TrainState:
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt_state
        )

    kwargs = dict(donate_argnums=(0,))
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        p_sh = param_sharding if param_sharding is not None else repl
        if opt_state_sharding is not None or param_sharding is not None:
            state_sh = TrainState(
                step=repl, params=p_sh,
                opt_state=opt_state_sharding
                if opt_state_sharding is not None else repl,
            )
            kwargs.update(
                in_shardings=(state_sh, p_sh), out_shardings=state_sh
            )
        else:
            kwargs.update(in_shardings=(repl, repl), out_shardings=repl)
    return jax.jit(apply_step, **kwargs)


def _all_finite(tree) -> jnp.ndarray:
    """Fused all-finite reduce over a pytree (or a single flat buffer) —
    traced INSIDE a jit, unlike the standalone ``params_are_finite`` whose
    host ``bool()`` readback costs a device sync per call."""
    finite = jnp.array(True)
    for leaf in jax.tree.leaves(tree):
        finite &= jnp.all(jnp.isfinite(leaf))
    return finite


def make_guarded_apply_step(
    tx: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    opt_state_sharding: Optional[Any] = None,
    param_sharding: Optional[Any] = None,
    post_apply: Optional[Callable[["TrainState"], "TrainState"]] = None,
) -> Callable:
    """``make_apply_step`` with the NaN guard FUSED into the jit: returns
    jitted (state, mean_grads) -> (state', ok).

    The collaborative optimizer's rollback used to cost a full
    ``jax.numpy.copy`` of (step, params, opt_state) before every apply
    (donation eats the inputs) plus a host-synced ``params_are_finite``
    readback. Here the all-finite reduce and the ``jnp.where`` rollback run
    inside the same jitted program: non-finite params select the pre-apply
    buffers leaf-wise, no extra HBM snapshot, no host round-trip — ``ok``
    comes back as a device scalar the caller may fetch asynchronously.
    ``post_apply`` (e.g. SwAV prototype re-normalization) is folded in
    BEFORE the finite check, preserving the legacy ordering (a post-apply
    that produces non-finite params also rolls back).
    """

    def guarded_apply_step(state: TrainState, grads):
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt_state
        )
        if post_apply is not None:
            new_state = post_apply(new_state)
        ok = _all_finite(new_state.params)
        # roll back exactly what the legacy host-side guard restored —
        # (step, params, opt_state); auxiliary fields (e.g. SwAV batch
        # stats) keep their post-apply values, as before
        guarded = new_state.replace(
            step=jnp.where(ok, new_state.step, state.step),
            params=jax.tree.map(
                lambda n, o: jnp.where(ok, n, o),
                new_state.params, state.params,
            ),
            opt_state=jax.tree.map(
                lambda n, o: jnp.where(ok, n, o),
                new_state.opt_state, state.opt_state,
            ),
        )
        return guarded, ok

    kwargs = dict(donate_argnums=(0,))
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        p_sh = param_sharding if param_sharding is not None else repl
        if opt_state_sharding is not None or param_sharding is not None:
            state_sh = TrainState(
                step=repl, params=p_sh,
                opt_state=opt_state_sharding
                if opt_state_sharding is not None else repl,
            )
            kwargs.update(
                in_shardings=(state_sh, p_sh), out_shardings=(state_sh, repl)
            )
        else:
            kwargs.update(in_shardings=(repl, repl), out_shardings=(repl, repl))
    return jax.jit(guarded_apply_step, **kwargs)


def _replace_opt_states(state, replacements):
    """Rebuild an optax (possibly chained/nested-tuple) opt_state with the
    given per-TYPE replacements applied; unknown member states pass through
    untouched. ``replacements`` maps state type -> replacement callable."""
    for typ, fn in replacements.items():
        if isinstance(state, typ):
            return fn(state)
    if isinstance(state, tuple) and not hasattr(state, "_fields"):
        return tuple(_replace_opt_states(s, replacements) for s in state)
    return state


def _find_opt_state(state, typ):
    if isinstance(state, typ):
        return state
    if isinstance(state, tuple) and not hasattr(state, "_fields"):
        for s in state:
            found = _find_opt_state(s, typ)
            if found is not None:
                return found
    return None


def make_flat_apply_step(
    flat_tx: Any,
    spec,
    post_apply: Optional[Callable[["TrainState"], "TrainState"]] = None,
    from_tree: bool = False,
) -> Callable:
    """Fused FLAT apply: jitted (state, flat_mean_grads) -> (state', ok).

    ``flat_tx`` is an ``optim.flat.FlatLamb`` / ``FlatLars`` adapter and
    ``spec`` the TreeLayout spec (sorted names) the flat gradient buffer
    follows — the SAME spec the averaging wire uses, so the averaged result
    device_puts as ONE buffer and feeds the apply with no per-leaf host
    work. Inside the one jit: params and moments are flattened onto the
    layout (pure relayout, fused by XLA), the whole LAMB/LARS update runs
    as segment reductions over the flat buffer, the all-finite NaN guard
    reduces over the new flat params in one pass, and the ``jnp.where``
    rollback selects pre-apply buffers on failure. The persistent
    ``opt_state`` stays the optax TREE state (checkpoints / peer state
    sync / schema fingerprints unchanged); moments only take their flat
    form transiently inside the jit. Donation end-to-end: the state's
    buffers alias their successors (see the donate note at the bottom).

    ``from_tree=True`` builds the same program taking a params-shaped
    gradient TREE instead of the flat buffer (the solo fast path, where
    gradients never left the device and were never flattened).

    Single-mesh only: sharded layouts keep the per-leaf chain
    (``make_guarded_apply_step``) — GSPMD wants the tree structure.
    """
    from dedloc_tpu.optim.flat import FlatLamb, FlatLars
    from dedloc_tpu.optim.lamb import ScaleByLambState
    from dedloc_tpu.optim.lars import LarsState

    names = [name for name, _shape, _dtype in spec]
    shapes = [shape for _name, shape, _dtype in spec]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]

    def _tree_order(template):
        """Permutation: position in spec (sorted names) per tree leaf."""
        flat = jax.tree_util.tree_flatten_with_path(template)[0]
        leaf_names = [
            jax.tree_util.keystr(path) or f"leaf{i}"
            for i, (path, _leaf) in enumerate(flat)
        ]
        index = {n: i for i, n in enumerate(names)}
        if sorted(leaf_names) != sorted(names):
            raise ValueError(
                "flat apply spec does not match the parameter tree"
            )
        return [index[n] for n in leaf_names], leaf_names

    def _flatten(tree, order):
        leaves = jax.tree.leaves(tree)
        by_spec = [None] * len(leaves)
        for leaf, pos in zip(leaves, order):
            by_spec[pos] = leaf.astype(jnp.float32).reshape(-1)
        return jnp.concatenate(by_spec) if by_spec else jnp.zeros(
            (0,), jnp.float32
        )

    def _unflatten_like(flat, template, order):
        leaves, treedef = jax.tree_util.tree_flatten(template)
        offsets = np.cumsum([0] + sizes)
        # the barrier keeps XLA from fusing each 1-D slice with its reshape:
        # left to itself, XLA:TPU (libtpu 0.0.34) spends ~20 minutes
        # compiling the 32 slice+reshape pairs of ALBERT-large's 17.8M
        # buffer (any subset that holds the 1024x4096, 4096x1024 and 1024x2
        # leaves together does it); barred, the same program compiles in
        # seconds, for one extra copy of each leaf per global step
        chunks = jax.lax.optimization_barrier([
            flat[offsets[pos]:offsets[pos] + sizes[pos]] for pos in order
        ])
        out = [
            chunk.reshape(leaf.shape).astype(leaf.dtype)
            for chunk, leaf in zip(chunks, leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def flat_apply_step(state: TrainState, grads):
        order, _ = _tree_order(state.params)
        flat_grads = _flatten(grads, order) if from_tree else grads
        flat_params = _flatten(state.params, order)
        sched = _find_opt_state(state.opt_state, optax.ScaleByScheduleState)
        sched_count = (
            sched.count if sched is not None else jnp.zeros([], jnp.int32)
        )
        if isinstance(flat_tx, FlatLamb):
            inner = _find_opt_state(state.opt_state, ScaleByLambState)
            assert inner is not None, "flat LAMB needs a lamb() opt_state"
            updates, new_mu, new_nu, new_count = flat_tx.update(
                flat_grads, flat_params,
                _flatten(inner.mu, order), _flatten(inner.nu, order),
                inner.count, sched_count,
            )
            replacements = {
                ScaleByLambState: lambda s: ScaleByLambState(
                    count=new_count,
                    mu=_unflatten_like(new_mu, s.mu, order),
                    nu=_unflatten_like(new_nu, s.nu, order),
                ),
                optax.ScaleByScheduleState: lambda s: (
                    optax.ScaleByScheduleState(count=s.count + 1)
                ),
            }
        elif isinstance(flat_tx, FlatLars):
            inner = _find_opt_state(state.opt_state, LarsState)
            assert inner is not None, "flat LARS needs a lars() opt_state"
            updates, new_mom = flat_tx.update(
                flat_grads, flat_params,
                _flatten(inner.momentum, order), sched_count,
            )
            replacements = {
                LarsState: lambda s: LarsState(
                    momentum=_unflatten_like(new_mom, s.momentum, order)
                ),
                optax.ScaleByScheduleState: lambda s: (
                    optax.ScaleByScheduleState(count=s.count + 1)
                ),
            }
        else:  # pragma: no cover - guarded by the caller
            raise TypeError(f"unsupported flat optimizer {type(flat_tx)!r}")
        new_flat_params = flat_params + updates
        new_state = state.replace(
            step=state.step + 1,
            params=_unflatten_like(new_flat_params, state.params, order),
            opt_state=_replace_opt_states(state.opt_state, replacements),
        )
        if post_apply is not None:
            new_state = post_apply(new_state)
            ok = _all_finite(new_state.params)
        else:
            # one fused reduce over the flat buffer
            ok = jnp.all(jnp.isfinite(new_flat_params))
        guarded = new_state.replace(
            step=jnp.where(ok, new_state.step, state.step),
            params=jax.tree.map(
                lambda n, o: jnp.where(ok, n, o),
                new_state.params, state.params,
            ),
            opt_state=jax.tree.map(
                lambda n, o: jnp.where(ok, n, o),
                new_state.opt_state, state.opt_state,
            ),
        )
        return guarded, ok

    # donation end-to-end applies to the STATE (params/moments alias their
    # successors in-place). The incoming gradient buffer/tree is consumed
    # by the relayout but has no same-shaped output to alias — declaring
    # it donated would only emit the unusable-donation warning.
    return jax.jit(flat_apply_step, donate_argnums=(0,))


def make_local_train_step(
    loss_fn: LossFn,
    tx: optax.GradientTransformation,
    grad_accum_steps: int = 1,
    mesh: Optional[Mesh] = None,
) -> Callable:
    """Single-peer fused step: scan over micro-batches, then optimizer apply.

    batch leaves must have shape [grad_accum_steps, per_step_batch, ...].
    """

    def train_step(state: TrainState, batch, rng):
        def micro(carry, mb):
            grad_acc, r = carry
            r, sub = jax.random.split(r)
            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, mb, sub
            )
            grad_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / grad_accum_steps,
                grad_acc,
                grads,
            )
            return (grad_acc, r), metrics

        (grads, _), metrics = jax.lax.scan(
            micro, (zeros_like_grads(state.params), rng), batch
        )
        metrics = jax.tree.map(lambda m: m.mean(), metrics)
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=new_params, opt_state=new_opt_state
        )
        return new_state, metrics

    kwargs = dict(donate_argnums=(0,))
    if mesh is not None:
        repl = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P(None, "data"))
        kwargs.update(
            in_shardings=(repl, data, repl), out_shardings=(repl, repl)
        )
    return jax.jit(train_step, **kwargs)


@jax.jit
def params_are_finite(params) -> jnp.ndarray:
    """All-finite check over a pytree (reference: CollaborativeCallback.
    params_are_finite, albert/run_trainer.py:181-186). Used by the NaN-guard
    rollback in the collaborative wrapper."""
    leaves = jax.tree.leaves(params)
    finite = jnp.array(True)
    for leaf in leaves:
        finite &= jnp.all(jnp.isfinite(leaf))
    return finite

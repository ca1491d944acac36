"""Compile a cell's device programs for a TPU v5e WITHOUT a chip.

libtpu's compile-only client runs the real XLA:TPU and Mosaic compilers on
this CPU-only box (``tools/tpu_aot.py`` does the same for ALBERT's three
one-device programs). This script covers what the benchmark's cells add:

    python benchmark/aot.py albert_accumulate --batch 12 48 96
    python benchmark/aot.py albert_mesh2          # accumulate + guarded apply, 2 devices
    python benchmark/aot.py swav_accumulate swav_guarded_apply swav_flat_apply
    python benchmark/aot.py                       # all of them

One JSON line per program: compile seconds and the compiler's own memory
analysis (bytes per device). Compile seconds come from the real compilers on
a different host: leads, not chip numbers. Nothing runs, so this says nothing
about speed or results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dedloc_tpu.utils.backend import lowering_for_tpu

SEQ = 512


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _albert(batch, mesh=None):
    from dedloc_tpu.core.config import CollaborationArguments, parse_config
    from dedloc_tpu.parallel.train_step import TrainState
    from dedloc_tpu.roles.common import build_model, build_optimizer

    args = parse_config(CollaborationArguments, [])
    cfg, model = build_model("large", "fused_ln", "flash", mesh=mesh)
    tx = build_optimizer(args)
    state = jax.eval_shape(
        lambda r: TrainState.create(
            model.init(r, jnp.zeros((batch, SEQ), jnp.int32))["params"], tx
        ),
        jax.random.PRNGKey(0),
    )
    return cfg, model, tx, state


def _albert_batch(cfg, batch):
    from dedloc_tpu.roles.common import (
        drop_collator_keys,
        synthetic_mlm_batches,
    )

    return drop_collator_keys(next(synthetic_mlm_batches(cfg, batch, SEQ, 0)))


def albert_accumulate(topo, opts):
    """ALBERT-large accumulate_step on one device at each --batch."""
    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )
    from dedloc_tpu.roles.common import build_loss_fn

    one = SingleDeviceSharding(topo.devices[0])
    for batch in opts.batch:
        cfg, model, _tx, state = _albert(batch)
        grads = jax.eval_shape(zeros_like_grads, state.params)
        yield f"albert_accumulate[b={batch}]", make_accumulate_step(
            build_loss_fn(model)
        ).lower(*_abstract(
            (state.params, grads, jnp.zeros([], jnp.int32),
             _albert_batch(cfg, batch), jax.random.PRNGKey(0)),
            one,
        ))


def albert_mesh2(topo, opts):
    """A 2-device slice peer: accumulate_step, _fused_mean_clip's partner
    grad_flat_prepare is host-driven; the apply is guarded_apply_step."""
    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        make_guarded_apply_step,
        zeros_like_grads,
    )
    from dedloc_tpu.roles.common import build_loss_fn

    mesh = Mesh(np.array(topo.devices[:2]), ("data",))
    repl, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    for batch in opts.batch:
        cfg, model, tx, state = _albert(batch * 2, mesh=mesh)
        grads = jax.eval_shape(zeros_like_grads, state.params)
        yield f"albert_mesh2_accumulate[b={batch}x2]", make_accumulate_step(
            build_loss_fn(model), mesh=mesh
        ).lower(
            _abstract(state.params, repl), _abstract(grads, repl),
            _abstract(jnp.zeros([], jnp.int32), repl),
            _abstract(_albert_batch(cfg, batch * 2), data),
            _abstract(jax.random.PRNGKey(0), repl),
        )
    yield "albert_mesh2_guarded_apply", make_guarded_apply_step(
        tx, mesh=mesh
    ).lower(_abstract(state, repl), _abstract(grads, repl))


def _swav(batch):
    from dedloc_tpu.core.config import SwAVCollaborationArguments, parse_config
    from dedloc_tpu.parallel.train_step import TrainState
    from dedloc_tpu.roles.swav import build_swav

    args = parse_config(SwAVCollaborationArguments, [])
    cfg, spec, model, tx = build_swav(args)
    crops = [
        jax.ShapeDtypeStruct((count * batch, size, size, spec.channels),
                             jnp.float32)
        for size, count in zip(spec.sizes, spec.counts)
    ]
    variables = jax.eval_shape(
        lambda r, c: model.init(r, c, True), jax.random.PRNGKey(0), crops
    )
    state = jax.eval_shape(
        lambda p: TrainState.create(p, tx), variables["params"]
    )
    return args, cfg, spec, model, tx, crops, variables, state


def swav_accumulate(topo, opts):
    """SwAV ResNet-50 accumulate (jit name ``step``) at --swav-batch."""
    from dedloc_tpu.models.swav import make_swav_accumulate_step
    from dedloc_tpu.parallel.train_step import zeros_like_grads

    one = SingleDeviceSharding(topo.devices[0])
    _a, cfg, spec, model, _tx, crops, variables, state = _swav(opts.swav_batch)
    grads = jax.eval_shape(zeros_like_grads, state.params)
    fn = make_swav_accumulate_step(
        model, cfg, num_crop_groups=len(spec.sizes)
    )
    args = _abstract(
        (state.params, variables["batch_stats"], None, grads,
         jnp.zeros([], jnp.int32), crops, jnp.zeros([], jnp.int32)),
        one,
    )
    yield f"swav_accumulate[b={opts.swav_batch}]", fn.lower(*args, False)


def swav_guarded_apply(topo, opts):
    """The per-leaf LARS apply a solo SwAV peer runs, with the prototype
    re-normalisation folded in."""
    from dedloc_tpu.models.swav import make_prototype_post_apply
    from dedloc_tpu.parallel.train_step import (
        make_guarded_apply_step,
        zeros_like_grads,
    )

    one = SingleDeviceSharding(topo.devices[0])
    *_rest, tx, _crops, _vars, state = _swav(8)
    grads = jax.eval_shape(zeros_like_grads, state.params)
    yield "swav_guarded_apply", make_guarded_apply_step(
        tx, post_apply=make_prototype_post_apply()
    ).lower(*_abstract((state, grads), one))


def swav_flat_apply(topo, opts):
    """The fused flat LARS apply a networked SwAV peer runs (no cell runs it
    yet; PR 21's 20-minute compile was ALBERT's twin of this program)."""
    from dedloc_tpu.averaging.device_flat import named_device_leaves
    from dedloc_tpu.models.swav import make_prototype_post_apply
    from dedloc_tpu.parallel.train_step import make_flat_apply_step
    from dedloc_tpu.roles.swav import _build_flat_lars_factory

    one = SingleDeviceSharding(topo.devices[0])
    args, *_rest, state = _swav(8)
    spec = [
        (name, tuple(leaf.shape), np.dtype(np.float32))
        for name, leaf in sorted(named_device_leaves(state.params))
    ]
    total = sum(int(np.prod(shape)) if shape else 1 for _n, shape, _d in spec)
    flat_tx = _build_flat_lars_factory(args.training)(spec, state.params)
    yield f"swav_flat_apply[leaves={len(spec)}]", make_flat_apply_step(
        flat_tx, spec, post_apply=make_prototype_post_apply()
    ).lower(*_abstract(
        (state, jax.ShapeDtypeStruct((total,), jnp.float32)), one
    ))


PROGRAMS = {
    fn.__name__: fn
    for fn in (albert_accumulate, albert_mesh2, swav_accumulate,
               swav_guarded_apply, swav_flat_apply)
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("programs", nargs="*", default=list(PROGRAMS))
    parser.add_argument("--batch", type=int, nargs="+", default=[12])
    parser.add_argument("--swav-batch", type=int, default=128)
    opts = parser.parse_args(argv)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    for name in opts.programs:
        with lowering_for_tpu():  # Mosaic kernels, not the interpreter
            lowered = list(PROGRAMS[name](topo, opts))
        for label, low in lowered:
            start = time.perf_counter()
            compiled = low.compile()
            seconds = time.perf_counter() - start
            mem = compiled.memory_analysis()
            print(json.dumps({
                "program": label,
                "device_kind": topo.devices[0].device_kind,
                "compile_s": round(seconds, 2),
                "tpu_custom_calls": low.as_text().count("tpu_custom_call"),
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

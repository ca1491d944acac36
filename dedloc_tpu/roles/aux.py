"""Auxiliary peer: donates bandwidth to averaging, contributes no gradients.

Capability parity with albert/run_aux.py:206-263 — a CPU-only peer that
joins averaging groups with zero weight every 0.5 s
(``CollaborativeOptimizer(auxiliary=True, allow_state_sharing=False)`` +
``step_aux()`` loop). It hosts bandwidth-weighted spans during the group
reduce-scatter, which speeds up rounds for slow GPU/TPU peers.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from dedloc_tpu.collaborative.optimizer import CollaborativeOptimizer
from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.roles.common import (
    build_authorizer,
    build_dht,
    build_model,
    build_optimizer,
    single_device_attention_impl,
)
from dedloc_tpu.utils.backend import ensure_compile_cache, pin_cpu
from dedloc_tpu.utils.checkpoint import tree_to_named
from dedloc_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _local_template(args: CollaborationArguments):
    """Gradient shapes from the local model config — the offline fallback
    when no state provider is live yet (shape-only)."""
    impl = single_device_attention_impl(args.training.attention_impl)
    cfg, model = build_model(
        args.training.model_size,
        args.training.remat_policy,
        impl,
        args.training.vocab_size,
        num_hidden_layers=args.training.num_hidden_layers,
    )
    seq = min(args.training.seq_length, cfg.max_position_embeddings)
    params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, seq), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    return {
        k: np.zeros(v.shape, np.float32)
        for k, v in tree_to_named(params).items()
    }


def run_aux(
    args: CollaborationArguments,
    poll_interval: float = 0.5,
    max_iterations: int = 0,
) -> int:
    """Returns the number of averaging rounds joined (for tests).

    The gradient-shape template SELF-BOOTSTRAPS from a live state provider
    (run_aux.py:243-263 capability: the aux learns the model from the
    collaboration, not from the caller); the local model config is only the
    fallback while nobody shares state yet."""
    pin_cpu()  # donates bandwidth, never computes on the chip
    ensure_compile_cache()
    # gated runs: aux peers need envelopes too (leaders reject unsigned
    # joins; gated joiners reject unsigned leader replies)
    authorizer, authority_public_key = build_authorizer(args)
    tx = build_optimizer(args)
    # gated: record-sign with the token key, so the signed subkey digests
    # to this peer's verified identity (ledger binding, roles/common.py)
    dht, _public_key = build_dht(
        args,
        private_key=(
            authorizer.local_private_key if authorizer is not None else None
        ),
    )
    logger.info(f"aux peer DHT listening on {dht.port}")
    # swarm telemetry (--telemetry.*, docs/observability.md): an aux donor's
    # join failures / allreduce stragglers are exactly the events operators
    # need when a donor silently loses every matchmaking race
    from dedloc_tpu.roles.common import configure_role_telemetry

    _tele, tele_close = configure_role_telemetry(args, _public_key)
    opt = CollaborativeOptimizer(
        tx,
        dht,
        prefix=args.dht.experiment_prefix,
        target_batch_size=args.optimizer.target_batch_size,
        batch_size_lead=args.optimizer.batch_size_lead,
        bandwidth=args.averager.bandwidth,
        compression=args.averager.compression,
        chunk_size=args.averager.chunk_size,
        # the whole swarm must share one hierarchy: an aux donor without
        # the plan would advertise into the flat scope nobody else forms
        topology_plan=args.averager.topology_plan or None,
        # and it must follow live re-plans for the same reason (unless the
        # operator pinned a manual plan — pin = opt-out, docs/fleet.md)
        plan_follow=(
            args.averager.plan_follow and not args.averager.topology_plan
        ),
        plan_refresh_period=args.averager.plan_refresh_period,
        target_group_size=args.averager.target_group_size,
        averaging_expiration=args.averager.averaging_expiration,
        averaging_timeout=args.averager.averaging_timeout,
        listen_port=args.averager.listen_port,
        auxiliary=True,
        advertised_host=args.dht.advertised_host or None,
        allow_state_sharing=False,
        authorizer=authorizer,
        authority_public_key=authority_public_key,
        verbose=True,
    )
    rounds = iterations = 0
    template = fallback = None
    try:
        while True:
            if template is None:
                # self-bootstrap keeps retrying until a provider appears —
                # a late-started aux needs no model knowledge at all
                template = opt.bootstrap_aux_template(timeout=10.0)
                if template is not None:
                    logger.info(
                        f"bootstrapped gradient template from a state "
                        f"provider ({len(template)} tensors)"
                    )
            current = template
            if current is None:
                # nobody shares state yet: derive shapes locally so the
                # collaboration's very first rounds still get bandwidth
                if fallback is None:
                    fallback = _local_template(args)
                    logger.info(
                        "no state provider yet; using local model shapes"
                    )
                current = fallback
            if opt.step_aux(current):
                rounds += 1
                logger.info(f"joined averaging round (total {rounds})")
            iterations += 1
            if max_iterations and iterations >= max_iterations:
                break
            time.sleep(poll_interval)
    finally:
        tele_close()
        opt.shutdown()
        dht.shutdown()
    return rounds


def main(argv=None) -> None:
    run_aux(parse_config(CollaborationArguments, argv))


if __name__ == "__main__":
    main()

"""Test env: force an 8-device virtual CPU mesh BEFORE jax import.

This is how multi-chip shardings are validated without hardware
(SURVEY.md environment notes): XLA's CPU backend executes the same
sharded programs + collectives the TPU path compiles to.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def sim_swarm():
    """Factory for simulated swarms on the discrete-event engine
    (docs/simulator.md): ``engine, swarm = sim_swarm(n=32, seed=0)`` gives
    ``n`` spawned peers on a virtual-clock loop; drive scenarios with
    ``engine.run(coro)``. Teardown (swarm shutdown + engine close) is
    handled here, so a simulated-topology test is ~3 lines::

        engine, swarm = sim_swarm(32)
        report = engine.run(my_scenario(swarm))
        assert report["whatever"]
    """
    from dedloc_tpu.simulator.engine import SimEngine
    from dedloc_tpu.simulator.network import LinkSpec, SimNetwork
    from dedloc_tpu.simulator.swarm import SimSwarm

    made = []

    def make(n=16, seed=0, link=None, spawn=True, **swarm_kwargs):
        # construct everything and REGISTER for teardown before entering
        # the engine: once __enter__ installs the process-global frozen
        # DHT clock, any failure (bad kwargs, a failing spawn) must still
        # reach the teardown loop, or the frozen clock leaks into every
        # later test in the session
        engine = SimEngine(seed=seed)
        network = SimNetwork(
            seed=seed, default_link=link or LinkSpec(latency_s=0.002)
        )
        swarm = SimSwarm(network, seed=seed, **swarm_kwargs)
        made.append((engine, swarm))
        engine.__enter__()
        if spawn:
            engine.run(swarm.spawn(n))
        return engine, swarm

    yield make
    for engine, swarm in reversed(made):
        try:
            if not engine.loop.is_closed():
                engine.run(swarm.shutdown())
        finally:
            engine.close()

"""Test env: force an 8-device virtual CPU mesh BEFORE jax import.

This is how multi-chip shardings are validated without hardware
(SURVEY.md environment notes): XLA's CPU backend executes the same
sharded programs + collectives the TPU path compiles to.
"""
import gc
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# ONE persistent compile cache for every process of the suite, from its
# first compile, under jax's own file lock.
# ``utils/backend.ensure_compile_cache`` points a process at
# ``<checkout>/.jax_cache`` at its first role test, so six xdist workers
# used to read and write that directory at once, and jax's ``LRUCache.put``
# is a bare ``write_bytes`` (no temporary file, no rename) that another
# process's ``get`` can read half-written. With a maximum size set, ``get``
# and ``put`` take the directory's ``.lockfile``; the size (a whole run
# writes 0.1 GB) is what bounds the directory over many PRs. A directory of
# the suite's own, because an entry written by a process WITHOUT the size
# has no ``-atime`` file beside it, and every later ``put`` under the lock
# would fail on it. The directory outlives the run: a second run in the
# same checkout loads what the first compiled (CHANGES.md, PR 55: 6,809 ->
# 4,645 test-seconds). The helper honours both variables untouched.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache", "tier1",
))
os.environ.setdefault("JAX_COMPILATION_CACHE_MAX_SIZE", str(4 * 2**30))

import numpy as np
import pytest

# ``vm.max_map_count`` is 65,530 here; two model files bring a process to
# 20,000 mappings
MAPPINGS_BEFORE_RELEASE = 20_000


@pytest.fixture(autouse=True, scope="module")
def release_compiled_programs():
    """Behind each module, a worker that holds many executables lets them
    go: every XLA:CPU executable keeps memory mappings of its own, a whole
    run peaks at 12-56 thousand a worker, and the compile that crosses
    ``vm.max_map_count`` aborts the worker in whichever test came last. The
    only ``jax.clear_caches()`` of the suite; what the tests cache are
    values (``decoder_cases.py``), so nothing is compiled twice for it."""
    yield
    with open("/proc/self/maps") as maps:
        mappings = sum(1 for _line in maps)
    if mappings > MAPPINGS_BEFORE_RELEASE:
        import jax

        jax.clear_caches()
        gc.collect()


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

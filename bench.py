"""Headline benchmark: ALBERT-large pretraining throughput on one chip.

Measures samples/sec of the full jitted train step (forward, backward, grad
accumulation, LAMB) on ALBERT-large at seq_length 512 — the reference's
canonical per-peer workload (albert/arguments.py:104-121: per-device batch 4 ×
grad_accum 2, fp16, LAMB lr 1.76e-3). On TPU we run the same recipe with a
larger per-chip micro-batch (bf16 compute, scan-shared layers, remat), since a
TPU chip replaces a whole T4 GPU peer.

Baseline anchor: the reference peer is a T4 (g4dn.2xlarge, AWS_runner.ipynb).
A T4 running ALBERT-large seq-512 MLM+SOP fp16 sustains ≈10 samples/sec
(≈0.9 TFLOP/sample forward+backward against ≈9 effective TFLOP/s) — the same
arithmetic the DeDLOC paper's fleet sizing implies. vs_baseline is measured
samples/sec divided by that 10 samples/sec/peer anchor.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

# the one peak table (used for the MFU report, model FLOPs / peak — NOT for
# throughput measurement): a TPU it does not list raises, the CPU gets 0.0
from dedloc_tpu.telemetry.steps import chip_peak_tflops
from dedloc_tpu.utils.backend import ensure_compile_cache

T4_BASELINE_SAMPLES_PER_SEC = 10.0


def device_bench_setup(name: str) -> bool:
    """Gate for the modes that measure the accelerator. Returns ``tiny``
    (DEDLOC_BENCH_TINY=1: a CPU-sized smoke of the code path, reported
    under a smoke metric name). At the real size a non-TPU backend is an
    error: a CPU rate must never print under a per-chip metric name."""
    tiny = os.environ.get("DEDLOC_BENCH_TINY", "") == "1"
    platform = jax.devices()[0].platform
    if not tiny and platform != "tpu":
        raise SystemExit(
            f"bench.py {name}: this mode measures a TPU chip, but JAX "
            f"landed on {platform!r}. Run it on the chip, or set "
            "DEDLOC_BENCH_TINY=1 for a smoke of the code path (reported "
            "under a smoke metric name, never as a per-chip rate)."
        )
    return tiny


def albert_train_flops_per_sample(cfg, seq: int, max_pred: int) -> float:
    """Analytic MODEL FLOPs for one fwd+bwd sample (matmuls only, the MXU
    work; remat recompute is intentionally excluded — MFU measures useful
    FLOPs against peak, so recompute shows up as lower MFU, matching the
    convention of the scaling-book / PaLM appendix)."""
    h, i, s = cfg.hidden_size, cfg.intermediate_size, seq
    e, v = cfg.embedding_size, cfg.vocab_size
    per_token_layer = (
        8 * h * h  # QKV + attention-output projections
        + 4 * h * s  # QK^T scores + attention-weighted values
        + 4 * h * i  # FFN in + out
    )
    fwd = cfg.num_hidden_layers * per_token_layer * s
    fwd += 2 * e * h * s  # factorized embedding projection
    fwd += max_pred * 2 * (h * e + e * v)  # gathered MLM head
    fwd += 2 * h * 2  # SOP head (negligible)
    return 3.0 * fwd  # bwd = 2x fwd matmul FLOPs


def run_codec() -> None:
    """Reproducible wire-path bench (DEDLOC_BENCH=codec): serialize +
    deserialize the ALBERT-large param tree (~17.8M fp32 params, matching
    what a peer actually ships per averaging round) through the fp16+CRC32C
    wire codec (native/wirecodec.cpp with numpy fallback). Baseline anchor:
    round-1 measured 102 ms serialize on the same-sized tree (BASELINE.md)."""
    from dedloc_tpu.core.serialization import (
        CompressionType,
        deserialize_tree,
        serialize_tree,
    )

    rng = np.random.default_rng(0)
    # ALBERT-large's tensors: embeddings + factorized proj + the one shared
    # layer + pooler + MLM head ≈ 17.8M params (full tree is 17.97M)
    tree = {
        "word_embeddings": rng.standard_normal((30000, 128)).astype(np.float32),
        "position_embeddings": rng.standard_normal((512, 128)).astype(np.float32),
        "token_type_embeddings": rng.standard_normal((2, 128)).astype(np.float32),
        "embedding_projection": rng.standard_normal((128, 1024)).astype(np.float32),
        "attn_qkv": rng.standard_normal((3, 1024, 1024)).astype(np.float32),
        "attn_out": rng.standard_normal((1024, 1024)).astype(np.float32),
        "ffn_in": rng.standard_normal((1024, 4096)).astype(np.float32),
        "ffn_out": rng.standard_normal((4096, 1024)).astype(np.float32),
        "pooler": rng.standard_normal((1024, 1024)).astype(np.float32),
        "mlm_dense": rng.standard_normal((1024, 128)).astype(np.float32),
        "mlm_bias": rng.standard_normal((30000,)).astype(np.float32),
    }
    n_params = sum(int(v.size) for v in tree.values())
    blob = serialize_tree(tree, CompressionType.FLOAT16)  # warm the codec
    ser = des = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        blob = serialize_tree(tree, CompressionType.FLOAT16)
        ser = min(ser, time.perf_counter() - t0)
        t0 = time.perf_counter()
        deserialize_tree(blob)
        des = min(des, time.perf_counter() - t0)
    print(json.dumps({
        "metric": "wirecodec_fp16_serialize_ms",
        "value": round(ser * 1e3, 2),
        "unit": "ms",
        "vs_baseline": round(102.0 / (ser * 1e3), 3),
        "deserialize_ms": round(des * 1e3, 2),
        "n_params": n_params,
        "wire_mb": round(len(blob) / 2**20, 1),
    }))


def run_allreduce_pipeline() -> None:
    """Wire-path bench (DEDLOC_BENCH=allreduce_pipeline): a full multi-peer
    group all-reduce over localhost RPC — matchmaking excluded, so the
    number tracks the averaging WIRE PATH (chunk streaming + compression +
    eager reduce), not the codec in isolation (DEDLOC_BENCH=codec) and not
    group formation.

    Reports one JSON line with (a) wire bytes per round at each compression
    level and (b) round wall time for the chunk-streamed pipeline vs the
    monolithic-span wire format under a simulated volunteer link (per-peer
    serialized uplink: fixed per-message latency + bandwidth-proportional
    transmission — the regime DeDLOC targets). vs_baseline is the
    pipeline's speedup over the monolithic path on the same link.
    """
    import asyncio

    import numpy as np

    from dedloc_tpu.averaging.allreduce import GroupAllReduce
    from dedloc_tpu.core.serialization import CompressionType
    from dedloc_tpu.dht.protocol import RPCClient, RPCServer

    tiny = os.environ.get("DEDLOC_BENCH_TINY", "") == "1"
    # DEDLOC_BENCH_TIMING=0 skips the link-simulation half (part b below):
    # the wire-bytes half is deterministic and cheap, the timing half costs
    # seconds of simulated uplink sleeps — tier-1's contract test only
    # asserts the former
    timing = os.environ.get("DEDLOC_BENCH_TIMING", "1") != "0"
    n_peers = 3
    # bandwidth-weighted spans, the DeDLOC fleet shape: a big-pipe donor
    # (an aux-style peer) hosts most of the vector, so its SERVE leg is the
    # round's long pole — exactly where streaming reduced chunks back while
    # the scatter is still inbound pays off. Symmetric groups barely gain
    # (every uplink carries scatter+serve either way).
    peer_bandwidths = [1.0, 1.0, 8.0]
    if tiny:
        dim, chunk, rounds = 524_288, 65_536, 2  # 2 MB fp32
        bandwidth, latency = 8e6, 0.3e-3
    else:
        dim, chunk, rounds = 4_194_304, 131_072, 3  # 16 MB fp32
        bandwidth, latency = 25e6, 1e-3

    class LinkSim:
        """Per-peer serialized uplink: one transmission at a time, each
        costing latency + nbytes/bandwidth. Loopback RPC underneath stays
        real — this only adds the volunteer-link wait."""

        def __init__(self, n):
            self.locks = [asyncio.Lock() for _ in range(n)]

        async def transmit(self, peer, nbytes):
            async with self.locks[peer]:
                await asyncio.sleep(latency + nbytes / bandwidth)

    class MeteredClient(RPCClient):
        """Counts averaging wire bytes and (optionally) simulates the link."""

        def __init__(self, me, port_to_peer, wire, link=None):
            super().__init__(request_timeout=60.0)
            self._me = me
            self._port_to_peer = port_to_peer
            self._wire = wire
            self._link = link

        async def call(self, endpoint, method, args=None, timeout=None):
            if method == "avg.part" and args and args.get("data") is not None:
                nbytes = len(args["data"])
                self._wire["bytes"] += nbytes
                if self._link is not None:
                    await self._link.transmit(self._me, nbytes)
            reply = await super().call(endpoint, method, args, timeout)
            if method == "avg.get_reduced":
                nbytes = len(reply["data"])
                self._wire["bytes"] += nbytes
                if self._link is not None:
                    # the reduced chunk rides the HOST's uplink
                    await self._link.transmit(
                        self._port_to_peer[endpoint[1]], nbytes
                    )
            return reply

    async def one_round(compression, chunk_size, link_enabled, round_id):
        rng = np.random.default_rng(0)
        vectors = [
            rng.standard_normal(dim).astype(np.float32)
            for _ in range(n_peers)
        ]
        servers, clients, reducers = [], [], []
        wire = {"bytes": 0}
        link = LinkSim(n_peers) if link_enabled else None
        for i in range(n_peers):
            server = RPCServer("127.0.0.1", 0)
            await server.start()
            servers.append(server)
        port_to_peer = {s.port: i for i, s in enumerate(servers)}
        endpoints = [("127.0.0.1", s.port) for s in servers]
        for i in range(n_peers):
            client = MeteredClient(i, port_to_peer, wire, link)
            clients.append(client)
            reducers.append(
                GroupAllReduce(
                    client, servers[i], compression=compression,
                    timeout=120.0, chunk_size=chunk_size,
                )
            )
        try:
            t0 = time.perf_counter()
            await asyncio.gather(
                *(
                    reducers[i].run(
                        round_id, i, vectors[i], 1.0, endpoints,
                        peer_bandwidths,
                    )
                    for i in range(n_peers)
                )
            )
            wall = time.perf_counter() - t0
        finally:
            for c in clients:
                await c.close()
            for s in servers:
                await s.stop()
        return wall, wire["bytes"]

    async def bench():
        # (a) wire bytes per round, per compression level (no link sim)
        wire_bytes = {}
        loopback_wall = float("inf")
        for level in (
            CompressionType.NONE, CompressionType.FLOAT16,
            CompressionType.UINT8,
        ):
            wall, nbytes = await one_round(
                level, chunk, False, f"wb-{level.value}"
            )
            wire_bytes[level.value] = nbytes
            if level is CompressionType.FLOAT16:
                loopback_wall = wall

        # (b) chunk-streamed pipeline vs monolithic spans on the same
        # simulated link (float16, the shipped default)
        if not timing:
            return wire_bytes, loopback_wall, 0.0, 0.0
        pipelined = monolithic = float("inf")
        for r in range(rounds):
            wall, _ = await one_round(
                CompressionType.FLOAT16, chunk, True, f"pipe-{r}"
            )
            pipelined = min(pipelined, wall)
            wall, _ = await one_round(
                CompressionType.FLOAT16, 0, True, f"mono-{r}"
            )
            monolithic = min(monolithic, wall)
        return wire_bytes, loopback_wall, pipelined, monolithic

    wire_bytes, loopback_wall, pipelined, monolithic = asyncio.run(bench())
    # effective rate: raw fp32 gradient bytes averaged per second of round
    # wall time, per peer (the number a volunteer's step budget feels);
    # with the link sim skipped it reflects the bare loopback round
    effective = dim * 4 / (pipelined if timing else loopback_wall)
    print(json.dumps({
        "metric": "allreduce_pipeline_effective_bytes_per_sec",
        "value": round(effective, 1),
        "unit": "bytes/sec",
        # speedup of the chunk-streamed pipeline over the monolithic-span
        # wire format under the same per-message-latency link (0.0 when the
        # timing half was skipped via DEDLOC_BENCH_TIMING=0)
        "vs_baseline": round(monolithic / pipelined, 3) if timing else 0.0,
        "wire_bytes_per_round": wire_bytes,
        "pipelined_wall_ms": round(pipelined * 1e3, 2),
        "monolithic_wall_ms": round(monolithic * 1e3, 2),
        "peers": n_peers,
        "vector_bytes": dim * 4,
        "chunk_elems": chunk,
    }))


def run_grad_pipeline() -> None:
    """Boundary-seam bench (DEDLOC_BENCH=grad_pipeline): the gradient
    device->host seam at an averaging boundary — legacy per-leaf
    ``device_get`` + host ``TreeLayout.flatten_into`` vs the device-resident
    flat pipeline (``averaging/device_flat.py``: fused on-device
    flatten+mean+quantize, chunked async D2H, decode-only host leg) — over
    the ALBERT-large gradient tree (~17.9M fp32 params, the tree a peer
    actually ships per round).

    Reports (a) D2H bytes per boundary for each path (deterministic — the
    tier-1 contract half; under fp16/uint8 wire formats the pipeline moves
    2-4x fewer bytes because quantization happens BEFORE the transfer) and
    (b) best-of wall to contribution-ready on the host
    (DEDLOC_BENCH_TIMING=0 skips). vs_baseline is legacy wall / pipeline
    wall — meaningful on a real device-to-host link where bytes dominate; on
    a CPU backend both paths are memcpy-bound and the ratio hovers near 1.
    ``DEDLOC_BENCH_COMPRESSION`` picks the wire format (default float16).
    """
    import jax.numpy as jnp

    from dedloc_tpu.averaging.device_flat import DeviceFlatPipeline
    from dedloc_tpu.averaging.partition import TreeLayout
    from dedloc_tpu.utils.checkpoint import tree_to_named

    tiny = os.environ.get("DEDLOC_BENCH_TINY", "") == "1"
    timing = os.environ.get("DEDLOC_BENCH_TIMING", "1") != "0"
    compression = os.environ.get("DEDLOC_BENCH_COMPRESSION", "float16")
    rng = np.random.default_rng(0)
    scale = 0.01 if tiny else 1.0

    def t(*shape):
        shape = tuple(max(1, int(d * scale)) for d in shape)
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32)
        )

    # the ALBERT-large gradient tree shape (run_codec's tree, as grads)
    tree = {
        "word_embeddings": t(30000, 128),
        "position_embeddings": t(512, 128),
        "token_type_embeddings": t(2, 128),
        "embedding_projection": t(128, 1024),
        "attn_qkv": t(3, 1024, 1024) if not tiny else t(3, 32, 32),
        "attn_out": t(1024, 1024),
        "ffn_in": t(1024, 4096),
        "ffn_out": t(4096, 1024),
        "pooler": t(1024, 1024),
        "mlm_dense": t(1024, 128),
        "mlm_bias": t(30000),
    }
    n_micro = 16
    n_params = sum(int(v.size) for v in jax.tree.leaves(tree))

    def legacy_boundary():
        mean = jax.tree.map(lambda g: g / n_micro, tree)
        named = tree_to_named(mean)  # per-leaf device_get
        layout = TreeLayout.for_tree(named)
        return layout.flatten_into(named)

    pipe = DeviceFlatPipeline.for_tree(tree, compression=compression)

    def pipeline_boundary():
        fetch = pipe.fetch(tree, n=n_micro, use_ef=False)
        return fetch, fetch.result().flat

    # warm both paths (jit compile, buffer alloc)
    legacy_flat = legacy_boundary()
    fetch, pipe_flat = pipeline_boundary()
    np.testing.assert_allclose(pipe_flat, legacy_flat, atol=1e-2)
    legacy_bytes = n_params * 4  # fp32 over the seam, per-leaf
    pipeline_bytes = fetch.wire_bytes

    legacy_wall = pipe_wall = float("inf")
    iters = 1 if tiny else 3
    if timing:
        for _ in range(iters):
            t0 = time.perf_counter()
            legacy_boundary()
            legacy_wall = min(legacy_wall, time.perf_counter() - t0)
            t0 = time.perf_counter()
            pipeline_boundary()
            pipe_wall = min(pipe_wall, time.perf_counter() - t0)

    print(json.dumps({
        "metric": "grad_pipeline_d2h_bytes_per_boundary",
        "value": pipeline_bytes,
        "unit": "bytes",
        # byte reduction is the load-bearing, hardware-independent number;
        # the wall ratio below only speaks on a real device link
        "vs_baseline": round(legacy_bytes / pipeline_bytes, 3),
        "compression": compression,
        "n_params": n_params,
        "legacy_d2h_bytes": legacy_bytes,
        "legacy_wall_ms": (
            round(legacy_wall * 1e3, 2) if timing else 0.0
        ),
        "pipeline_wall_ms": (
            round(pipe_wall * 1e3, 2) if timing else 0.0
        ),
        "chunks": len(pipe.bounds),
    }))


def run_checkpoint_restore() -> None:
    """Swarm-checkpoint restore bench (DEDLOC_BENCH=checkpoint_restore):
    bootstrap bytes + wall for a joiner restoring the collaboration state,
    1-provider monolithic blob vs N-provider sharded
    (dedloc_tpu/checkpointing) — the availability cliff this subsystem
    removes: the blob path downloads everything from ONE peer's uplink,
    the sharded path spreads distinct shards across every announcing
    provider.

    Link model: per-provider serialized uplink (fixed per-message latency +
    bandwidth-proportional transmission), the same volunteer-link shape as
    the allreduce_pipeline bench; DEDLOC_BENCH_TIMING=0 skips the link-sim
    sleeps and reports only the deterministic byte/provider accounting
    (tier-1's contract half). vs_baseline is monolithic wall / sharded wall
    on the same link — ~N for N equal providers.
    """
    import asyncio
    import hashlib

    import numpy as np

    from dedloc_tpu.checkpointing import (
        CheckpointAnnouncement,
        build_manifest,
        shard_bytes,
        sharded_restore,
    )
    from dedloc_tpu.core.serialization import (
        CompressionType,
        serialize_array,
        serialize_tree,
    )
    from dedloc_tpu.dht.protocol import RPCClient, RPCServer

    tiny = os.environ.get("DEDLOC_BENCH_TINY", "") == "1"
    timing = os.environ.get("DEDLOC_BENCH_TIMING", "1") != "0"
    n_providers = int(os.environ.get("DEDLOC_BENCH_PROVIDERS", "4"))
    if tiny:
        dim, shard_elems = 262_144, 32_768  # 1 MB fp32, 8 shards
        bandwidth, latency = 8e6, 0.3e-3
    else:
        dim, shard_elems = 8_388_608, 1_048_576  # 32 MB fp32, 8 shards
        bandwidth, latency = 25e6, 1e-3

    rng = np.random.default_rng(0)
    tree = {"flat/params": rng.standard_normal(dim).astype(np.float32)}
    metadata = {"step": 1000, "local_step": 1000}
    manifest, flat = build_manifest(tree, 1000, shard_size=shard_elems,
                                    metadata=metadata)
    blob = serialize_tree(tree, CompressionType.NONE)
    blob_digest = hashlib.sha256(blob).digest()

    class LinkSim:
        """One serialized uplink per provider (allreduce_pipeline's model)."""

        def __init__(self, n):
            self.locks = [asyncio.Lock() for _ in range(n)]

        async def transmit(self, provider, nbytes):
            async with self.locks[provider]:
                await asyncio.sleep(latency + nbytes / bandwidth)

    class MeteredClient(RPCClient):
        """Counts restore wire bytes; reply payloads ride the serving
        provider's simulated uplink."""

        def __init__(self, port_to_provider, wire, link=None):
            super().__init__(request_timeout=120.0)
            self._port_to_provider = port_to_provider
            self._wire = wire
            self._link = link

        async def call(self, endpoint, method, args=None, timeout=None):
            reply = await super().call(endpoint, method, args, timeout)
            payload = None
            if method == "ckpt.shard":
                payload = reply["data"]
            elif method == "ckpt.manifest":
                payload = reply["manifest"]
            elif method == "state.get":
                payload = reply["state"]
            if payload is not None:
                self._wire["bytes"] += len(payload)
                if self._link is not None:
                    await self._link.transmit(
                        self._port_to_provider[endpoint[1]], len(payload)
                    )
            return reply

    async def start_providers(n):
        servers = []

        async def get_manifest(peer, args):
            return {"manifest": manifest.to_bytes()}

        async def get_shard(peer, args):
            index = int(args["index"])
            raw = shard_bytes(flat, manifest, index)
            return {
                "index": index,
                "data": serialize_array(
                    np.frombuffer(raw, dtype=np.float32), CompressionType.NONE
                ),
            }

        async def get_state(peer, args):
            return {"state": blob, "checksum": blob_digest}

        for _ in range(n):
            server = RPCServer("127.0.0.1", 0)
            server.register("ckpt.manifest", get_manifest)
            server.register("ckpt.shard", get_shard)
            server.register("state.get", get_state)
            await server.start()
            servers.append(server)
        return servers

    async def bench():
        servers = await start_providers(n_providers)
        port_to_provider = {s.port: i for i, s in enumerate(servers)}
        endpoints = [("127.0.0.1", s.port) for s in servers]
        try:
            # monolithic: the whole blob from provider 0's uplink
            mono_wire = {"bytes": 0}
            client = MeteredClient(
                port_to_provider, mono_wire,
                LinkSim(n_providers) if timing else None,
            )
            t0 = time.perf_counter()
            reply = await client.call(endpoints[0], "state.get", {})
            assert hashlib.sha256(reply["state"]).digest() == blob_digest
            mono_wall = time.perf_counter() - t0
            await client.close()

            # sharded: distinct shards from every provider in parallel
            shard_wire = {"bytes": 0}
            client = MeteredClient(
                port_to_provider, shard_wire,
                LinkSim(n_providers) if timing else None,
            )
            anns = [
                CheckpointAnnouncement(
                    step=manifest.step, manifest_digest=manifest.digest(),
                    num_shards=manifest.num_shards, endpoint=list(ep),
                )
                for ep in endpoints
            ]
            t0 = time.perf_counter()
            _meta, restored, _m = await sharded_restore(
                client, anns, parallelism=n_providers * 2, retries=1,
            )
            shard_wall = time.perf_counter() - t0
            np.testing.assert_array_equal(
                restored["flat/params"], tree["flat/params"]
            )
            await client.close()
            return mono_wall, mono_wire["bytes"], shard_wall, \
                shard_wire["bytes"]
        finally:
            for s in servers:
                await s.stop()

    mono_wall, mono_bytes, shard_wall, shard_bytes_total = asyncio.run(bench())
    print(json.dumps({
        "metric": "checkpoint_restore_sharded_bytes_per_sec",
        "value": round(manifest.total_bytes / shard_wall, 1),
        "unit": "bytes/sec",
        # sharded restore speedup over the single-provider blob on the same
        # per-provider-uplink link model (0.0 when timing was skipped)
        "vs_baseline": round(mono_wall / shard_wall, 3) if timing else 0.0,
        "state_bytes": manifest.total_bytes,
        "num_shards": manifest.num_shards,
        "monolithic": {"providers": 1, "wire_bytes": mono_bytes,
                       "wall_ms": round(mono_wall * 1e3, 2)},
        "sharded": {"providers": n_providers,
                    "wire_bytes": shard_bytes_total,
                    "wall_ms": round(shard_wall * 1e3, 2)},
    }))


def run_swav() -> None:
    """SwAV ResNet-50 step bench (DEDLOC_BENCH=swav): the full jitted
    multicrop train step — trunk fwd/bwd over 2x224 + 6x96 crops, prototypes
    head, sinkhorn assignment in the loss, LARS update, prototype
    re-normalization (swav_1node_resnet_submit.yaml recipe).

    MFU uses XLA's own executed-FLOP count for the compiled step (convs
    dominate; an analytic count would re-derive ResNet-50 conv by conv).
    vs_baseline anchors on the SwAV paper's own wall-clock: 800 epochs of
    ImageNet-1k on 64 V100s in ~50 h => ~88 samples/s per V100 peer."""
    from dedloc_tpu.models.swav import (
        SwAVConfig,
        SwAVModel,
        SwAVQueue,
        SwAVTrainState,
        make_swav_train_step,
    )
    from dedloc_tpu.optim import lars

    V100_SWAV_SAMPLES_PER_SEC = 88.0
    tiny = device_bench_setup("swav")
    if tiny:
        cfg = SwAVConfig.tiny()
        sizes, counts = (32, 16), (2, 2)
        batch, iters = 4, 2
    else:
        cfg = SwAVConfig(queue_length=3840)
        sizes, counts = (224, 96), (2, 6)
        # throughput saturates by B=128 (365/510/591/608 samples/s at
        # B=16/32/64/128 on v5e, 2026-07-30)
        batch = int(os.environ.get("DEDLOC_BENCH_BATCH", "128"))
        iters = 5

    model = SwAVModel(cfg)
    rng = jax.random.PRNGKey(0)
    crops = [
        jax.random.normal(
            jax.random.PRNGKey(i), (count * batch, size, size, 3),
            jnp.float32,
        )
        for i, (size, count) in enumerate(zip(sizes, counts))
    ]
    variables = model.init(rng, crops, True)
    tx = lars(learning_rate=0.6, momentum=0.9, weight_decay=1e-6)
    state = jax.jit(
        lambda p, bn: SwAVTrainState(
            step=jnp.zeros([], jnp.int32),
            params=p,
            batch_stats=bn,
            opt_state=tx.init(p),
            queue=SwAVQueue.create(cfg, jax.random.PRNGKey(1))
            if cfg.queue_length else None,
        )
    )(variables["params"], variables["batch_stats"])
    step = make_swav_train_step(model, cfg, tx)

    state, metrics = step(state, crops, False)
    float(metrics["loss"])  # warm-up: compile + one executed step

    best = float("inf")
    for block in range(3):
        start = time.perf_counter()
        for _ in range(iters):
            state, metrics = step(state, crops, False)
        float(metrics["loss"])
        best = min(best, time.perf_counter() - start)
    samples_per_sec = iters * batch / best

    result = {
        "metric": (
            "swav_tiny_smoke_samples_per_sec" if tiny
            else "swav_resnet50_train_samples_per_sec_per_chip"
        ),
        "value": round(samples_per_sec, 3),
        "unit": "samples/sec",
        # a CPU smoke has no anchor
        "vs_baseline": 1.0 if tiny else round(
            samples_per_sec / V100_SWAV_SAMPLES_PER_SEC, 3
        ),
    }
    if not tiny:
        analysis = step.lower(state, crops, False).compile().cost_analysis()
        flops_step = float(analysis["flops"])
        result["mfu"] = round(
            samples_per_sec * flops_step / batch
            / (chip_peak_tflops() * 1e12), 4
        )
        result["model_tflops_per_sample"] = round(
            flops_step / batch / 1e12, 4
        )
        result["chip"] = jax.devices()[0].device_kind
    print(json.dumps(result))


def run_longctx() -> None:
    """Long-context bench (DEDLOC_BENCH=longctx): ALBERT-large fwd+bwd at
    S=16,384 on ONE chip via the Pallas flash kernel — the length dense
    attention cannot even allocate at (BASELINE.md feasibility row, now a
    reproducible number). Reports tokens/sec; vs_baseline is against the
    reference's fixed S=512 capability (albert/arguments.py:110): it has NO
    long-context path, so the anchor is this workload's own S=512 rate and
    the ratio shows the cost of 32x longer context."""
    from dedloc_tpu.data.mlm import max_predictions_for
    from dedloc_tpu.models.albert import (
        AlbertConfig,
        AlbertForPreTraining,
        albert_pretraining_loss_gathered,
    )

    tiny = device_bench_setup("longctx")
    seq = 1024 if tiny else int(os.environ.get("DEDLOC_BENCH_SEQ", "16384"))
    per_step = 1
    # the smoke keeps dense attention: the interpreted flash kernel is too
    # slow for CI at S=1024
    impl = "dense" if tiny else "flash"
    cfg = (AlbertConfig.tiny if tiny else AlbertConfig.large)(
        remat_policy="dots_no_batch_attn" if impl == "flash" else "dots_no_batch",
        attention_impl=impl,
        max_position_embeddings=seq,
    )
    max_pred = max_predictions_for(seq)
    model = AlbertForPreTraining(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((per_step, seq), jnp.int32)
    )["params"]

    def loss_fn(p, b, r):
        mlm, sop = model.apply({"params": p}, b["input_ids"],
                               b["attention_mask"],
                               mlm_positions=b["mlm_positions"])
        return albert_pretraining_loss_gathered(
            mlm, sop, b["mlm_label_ids"], b["mlm_weights"], b["sop_labels"])[0]

    host = np.random.default_rng(0)
    batch = {
        "input_ids": jnp.asarray(host.integers(
            5, cfg.vocab_size, (per_step, seq)).astype(np.int32)),
        "attention_mask": jnp.ones((per_step, seq), jnp.int32),
        "mlm_positions": jnp.zeros((per_step, max_pred), jnp.int32),
        "mlm_label_ids": jnp.zeros((per_step, max_pred), jnp.int32),
        "mlm_weights": jnp.ones((per_step, max_pred), jnp.float32),
        "sop_labels": jnp.zeros((per_step,), jnp.int32),
    }
    grad = jax.jit(jax.grad(loss_fn))
    g = grad(params, batch, jax.random.PRNGKey(1))
    float(jax.tree.leaves(g)[0].ravel()[0])  # warm-up: compile + one step

    iters = 2 if tiny else 3
    best = float("inf")
    for block in range(3):
        start = time.perf_counter()
        for _ in range(iters):
            g = grad(params, batch, jax.random.PRNGKey(2))
        float(jax.tree.leaves(g)[0].ravel()[0])
        best = min(best, time.perf_counter() - start)
    tokens_per_sec = iters * per_step * seq / best
    result = {
        "metric": (
            f"albert_tiny_longctx_s{seq}_smoke_tokens_per_sec" if tiny
            else f"albert_large_longctx_s{seq}_fwdbwd_tokens_per_sec"
        ),
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
    }
    if tiny:
        result["vs_baseline"] = 1.0  # CPU smoke: no meaningful anchor
    else:
        # the S=512 recipe sustains 99.45 samples/s x 512 tokens
        # (BASELINE.md round-3 headline); the ratio is the cost of 32x
        # longer context under O(S^2) attention FLOPs
        result["vs_baseline"] = round(tokens_per_sec / (99.45 * 512), 4)
    print(json.dumps(result))


# the 1,000-peer mixed acceptance scenario's wall on this box class BEFORE
# the virtual-time engine overhaul (timer wheel + sharded dispatch + lazy
# hydration + DHT lookup cache) — the sim_engine bench's vs_baseline anchor
# (SIMBENCH_r01.json records the pre/post pair)
_PRE_OVERHAUL_MIXED1000_WALL_S = 21.765


def run_sim_engine() -> None:
    """Virtual-time engine bench (DEDLOC_BENCH=sim_engine): the 1,000-peer
    mixed scenario at its DEFAULT spec — exactly what ``tools/swarm_sim.py
    --scenario mixed --peers 1000 --seed 0`` runs, so the trajectory stays
    comparable to the pre-overhaul measurement of the same command —
    end-to-end on the discrete-event engine: one core, zero real sleeping.
    The headline metric is timer events scheduled per wall second — the
    engine's dispatch throughput, which is exactly what the timer wheel /
    sharded dispatch / lazy hydration work moves. The event count is a
    deterministic function of (seed, spec), so events/sec isolates engine
    wall cost from workload drift, and it is higher-is-better as
    tools/bench_gate.py requires (wall seconds would gate backwards).
    vs_baseline is the pre-overhaul wall for this command on the same box
    class over this run's wall: the engine speedup. Unless
    DEDLOC_BENCH_TIMING=0, the record also carries the 10,000-peer diurnal
    point (the planet-scale proof: 10k peers over 24 virtual hours in well
    under a minute of wall).

    DEDLOC_BENCH_TINY=1 shrinks the roster for a CI smoke; the metric name
    carries the roster size so a smoke never gates against the full run.
    """
    import resource

    from dedloc_tpu.simulator import scenarios as S
    from dedloc_tpu.simulator.engine import SIM_EPOCH

    tiny = os.environ.get("DEDLOC_BENCH_TINY", "") == "1"
    timing = os.environ.get("DEDLOC_BENCH_TIMING", "1") != "0"
    peers = 100 if tiny else 1000
    spec = {"scenario": "mixed", "peers": peers, "seed": 0}
    run = S.ScenarioRun(spec)
    wall0 = time.perf_counter()
    with run.engine:
        run.engine.run(S.SCENARIOS["mixed"](run), timeout=36000.0)
        events = run.engine.clock.sleeper_stats()["scheduled_total"]
        virtual_s = run.engine.clock.offset - SIM_EPOCH
        run.engine.run(run.swarm.shutdown())
    run.engine.close()
    wall = time.perf_counter() - wall0

    result = {
        "metric": f"sim_mixed{peers}_timer_events_per_wall_sec",
        "value": round(events / wall, 1),
        "unit": "events/sec",
        "wall_s": round(wall, 3),
        "virtual_s": round(virtual_s, 3),
        "events_scheduled": events,
        "peak_rss_mb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1
        ),
        "vs_baseline": (
            1.0 if tiny  # smoke roster: no comparable pre-overhaul anchor
            else round(_PRE_OVERHAUL_MIXED1000_WALL_S / wall, 2)
        ),
    }
    if timing and not tiny:
        d = S.run_scenario({"scenario": "diurnal", "peers": 10000, "seed": 0})
        result["diurnal_10k"] = {
            "wall_s": d["wall_s"],
            "virtual_s": d["virtual_s"],
            "peak_online": d["diurnal"]["peak_online"],
            "get_success": d["diurnal"]["get_success"],
        }
    print(json.dumps(result))


def run_serving() -> None:
    """Serving-plane bench (DEDLOC_BENCH=serving): the ISSUE 20 acceptance
    scenario — a 1,000-peer fleet, 16 experts x 3 replicas, 8 gateways,
    a bursty 400-request trace with 6 expert hosts killed mid-trace — on
    the virtual-time engine. The headline is requests resolved per WALL
    second (higher-is-better, as tools/bench_gate.py requires): the
    request count is fixed by the spec, so the metric isolates the
    serving plane's Python cost (discovery parse, candidate ranking,
    hedged dispatch, telemetry) from workload drift. p99 latency and the
    fall-through rate ride along as SLO context — p99 is VIRTUAL time
    (the simulated fleet's latency), wall is the box's cost to simulate
    it.

    DEDLOC_BENCH_TINY=1 shrinks the fleet for a CI smoke; the metric name
    carries the roster size so a smoke never gates against the full run.
    """
    import resource

    from dedloc_tpu.simulator import scenarios as S

    tiny = os.environ.get("DEDLOC_BENCH_TINY", "") == "1"
    peers = 40 if tiny else 1000
    spec = {
        "scenario": "serving", "peers": peers, "seed": 0,
        "experts": 4 if tiny else 16,
        "hosts_per_expert": 2 if tiny else 3,
        "gateways": 2 if tiny else 8,
        "requests": 40 if tiny else 400,
        "burst": 4 if tiny else 8,
        "tokens": 16, "hidden": 8,
        "kill_hosts": 0 if tiny else 6, "kill_at_frac": 0.5,
    }
    wall0 = time.perf_counter()
    report = S.run_scenario(spec)
    wall = time.perf_counter() - wall0
    serving = report["serving"]
    print(json.dumps({
        "metric": f"serving{peers}_requests_per_wall_sec",
        "value": round(serving["completed"] / wall, 1),
        "unit": "requests/sec",
        "wall_s": round(wall, 3),
        "virtual_s": report["virtual_s"],
        "requests": serving["requests"],
        "served": serving["served"],
        "wedged": serving["wedged"],
        "fall_through_rate": serving["fall_through_rate"],
        "latency_p50_s": serving["latency_p50_s"],
        "latency_p99_s": serving["latency_p99_s"],
        "load_skew": serving["load_skew"],
        "peak_rss_mb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1
        ),
    }))


def main() -> None:
    ensure_compile_cache()
    if os.environ.get("DEDLOC_BENCH") == "codec":
        run_codec()
        return
    if os.environ.get("DEDLOC_BENCH") == "allreduce_pipeline":
        run_allreduce_pipeline()
        return
    if os.environ.get("DEDLOC_BENCH") == "grad_pipeline":
        run_grad_pipeline()
        return
    if os.environ.get("DEDLOC_BENCH") == "checkpoint_restore":
        run_checkpoint_restore()
        return
    if os.environ.get("DEDLOC_BENCH") == "swav":
        run_swav()
        return
    if os.environ.get("DEDLOC_BENCH") == "longctx":
        run_longctx()
        return
    if os.environ.get("DEDLOC_BENCH") == "sim_engine":
        run_sim_engine()
        return
    if os.environ.get("DEDLOC_BENCH") == "serving":
        run_serving()
        return
    from dedloc_tpu.models.albert import (
        AlbertConfig,
        AlbertForPreTraining,
        albert_pretraining_loss_gathered,
    )
    from dedloc_tpu.optim import lamb
    from dedloc_tpu.parallel.train_step import TrainState, make_local_train_step

    tiny = device_bench_setup("albert")
    # the Pallas flash kernel beats XLA's dense attention on the full remat'd
    # train step (~86 vs ~77 samples/s on a v5e, builder-measured 2026-07,
    # before PR 21); the CPU smoke keeps the dense path, where the kernel
    # would run interpreted
    impl = "dense" if tiny else "flash"
    # measurement overrides (remat sweep for BASELINE.md). Round-3 recipe
    # change: default policy dots_no_batch -> dots_no_batch_attn and block
    # length 5 -> 10 iters (see BASELINE.md round-3 notes for both the old-
    # and new-methodology numbers so rounds stay comparable).
    # Round-4 recipe: fused add+LN Pallas kernel + the fused_ln remat policy,
    # micro-batch 12 (the B sweep's sweet spot — small enough that XLA stops
    # inserting remat-compression copies, large enough to feed the MXU;
    # 8/10/14/16/24/32 all measured slower, BASELINE.md round-4 notes) and
    # 16 accumulation micro-batches per jitted step: the ~10 ms of per-step
    # plumbing (donated-state shuffling + LAMB apply) amortizes over 8x the
    # samples vs accum 2 (108.4 -> 112.3 samples/s; accum 32 adds only +0.4
    # more). Production-honest: one optimizer step at target_batch_size 4096
    # accumulates far more than 16 micro-batches per chip.
    remat = os.environ.get("DEDLOC_BENCH_REMAT", "fused_ln")
    from dedloc_tpu.models.albert import fused_ln_for_policy

    fused_ln = fused_ln_for_policy(remat)
    per_step_env = int(os.environ.get("DEDLOC_BENCH_BATCH", "0"))
    # flash-kernel tile sweep knob (perf probes; 512 is the shipped recipe)
    attn_block = int(os.environ.get("DEDLOC_BENCH_ATTN_BLOCK", "512"))
    if tiny:  # CI smoke on CPU
        cfg = AlbertConfig.tiny(remat_policy=remat, attention_impl=impl,
                                fused_ln=fused_ln)
        accum, per_step, seq, iters = 2, 4, 64, 3
    else:
        cfg = AlbertConfig.large(remat_policy=remat, attention_impl=impl,
                                 fused_ln=fused_ln,
                                 attention_block_size=attn_block)
        # iters per block: one scalar readback per block
        accum, per_step, seq, iters = 16, 12, 512, 10
    if per_step_env:
        per_step = per_step_env
    accum_env = int(os.environ.get("DEDLOC_BENCH_ACCUM", "0"))
    if accum_env:
        accum = accum_env
    # gathered masked-position MLM head: vocab projection only where labels
    # exist (~15% of positions) — the TPU-native layout
    from dedloc_tpu.data.mlm import max_predictions_for

    max_pred = max_predictions_for(seq)

    model = AlbertForPreTraining(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((per_step, seq), jnp.int32))["params"]
    tx = lamb(learning_rate=1.76e-3, weight_decay=0.01)
    state = jax.jit(lambda p: TrainState.create(p, tx))(params)

    def loss_fn(params, batch, rng):
        mlm_logits, sop_logits = model.apply(
            {"params": params},
            batch["input_ids"],
            batch["attention_mask"],
            mlm_positions=batch["mlm_positions"],
        )
        return albert_pretraining_loss_gathered(
            mlm_logits,
            sop_logits,
            batch["mlm_label_ids"],
            batch["mlm_weights"],
            batch["sop_labels"],
        )

    host = np.random.default_rng(0)
    ids = host.integers(5, cfg.vocab_size, (accum, per_step, seq)).astype(np.int32)
    labelled = host.random((accum, per_step, seq)) < 0.15
    labelled &= np.cumsum(labelled, axis=2) <= max_pred
    positions = np.zeros((accum, per_step, max_pred), np.int32)
    label_ids = np.zeros((accum, per_step, max_pred), np.int32)
    weights = np.zeros((accum, per_step, max_pred), np.float32)
    for a in range(accum):
        for i in range(per_step):
            idx = np.flatnonzero(labelled[a, i])
            positions[a, i, : len(idx)] = idx
            label_ids[a, i, : len(idx)] = ids[a, i, idx]
            weights[a, i, : len(idx)] = 1.0
    batch = {
        "input_ids": jnp.asarray(ids),
        "attention_mask": jnp.ones((accum, per_step, seq), jnp.int32),
        "mlm_positions": jnp.asarray(positions),
        "mlm_label_ids": jnp.asarray(label_ids),
        "mlm_weights": jnp.asarray(weights),
        "sop_labels": jnp.asarray(host.integers(0, 2, (accum, per_step)), jnp.int32),
    }

    train_step = make_local_train_step(loss_fn, tx, grad_accum_steps=accum)

    # Warmup: compile + one executed step (the scalar readback waits for it).
    state, metrics = train_step(state, batch, jax.random.PRNGKey(1))
    float(metrics["loss"])

    # Steady-state throughput: steps chain on-device (donated state), one
    # scalar readback per BLOCK, best of three blocks.
    best = float("inf")
    for block in range(3):
        start = time.perf_counter()
        for i in range(iters):
            state, metrics = train_step(
                state, batch, jax.random.PRNGKey(2 + block * iters + i)
            )
        float(metrics["loss"])
        best = min(best, time.perf_counter() - start)

    samples_per_sec = iters * accum * per_step / best
    result = {
        "metric": (
            "albert_tiny_smoke_samples_per_sec" if tiny
            else "albert_large_train_samples_per_sec_per_chip"
        ),
        "value": round(samples_per_sec, 3),
        "unit": "samples/sec",
        # a CPU smoke has no anchor
        "vs_baseline": 1.0 if tiny else round(
            samples_per_sec / T4_BASELINE_SAMPLES_PER_SEC, 3
        ),
    }
    if not tiny:
        flops = albert_train_flops_per_sample(cfg, seq, max_pred)
        result["mfu"] = round(
            samples_per_sec * flops / (chip_peak_tflops() * 1e12), 4
        )
        result["model_tflops_per_sample"] = round(flops / 1e12, 4)
        result["chip"] = jax.devices()[0].device_kind
    print(json.dumps(result))


if __name__ == "__main__":
    main()

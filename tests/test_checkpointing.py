"""Swarm checkpointing tests: manifests, the content-addressed shard store,
the DHT catalog schema, the multi-peer fetcher over loopback RPC, and the
fault-injected end-to-end restore acceptance scenario.

Test policy (memory/tier1-timing-budget.md): every tier-1 test here rides
loopback with TINY trees (tens of elements, shard_size single digits); the
only real-DHT scenarios are the acceptance test and its fallback sibling,
kept to 3 in-process peers like tests/test_averaging.py's state-sharing
tests.
"""
import asyncio
import hashlib
import os

import numpy as np
import pytest

from dedloc_tpu.checkpointing import (
    CheckpointAnnouncement,
    CheckpointManifest,
    RestoreFailed,
    ShardStore,
    assemble_tree,
    build_manifest,
    catalog_key,
    fetch_shards,
    load_sharded_checkpoint,
    parse_announcements,
    save_sharded_checkpoint,
    select_target,
    shard_bytes,
    sharded_restore,
    verify_shard,
)
from dedloc_tpu.core.serialization import (
    CompressionType,
    pack_obj,
    serialize_array,
)
from dedloc_tpu.dht.protocol import RPCClient, RPCServer

pytestmark = pytest.mark.checkpointing


def _tree(rng, n=19):
    return {
        "b/w": rng.standard_normal((3, 4)).astype(np.float32),
        "a/k": rng.standard_normal((n,)).astype(np.float32),
        "c": np.array(2.5, np.float32),
    }


# ---------------------------------------------------------------- manifests


def test_manifest_roundtrip_bit_identical(rng):
    tree = _tree(rng)
    manifest, flat = build_manifest(tree, step=7, shard_size=4)
    assert manifest.num_shards == -(-manifest.total_size // 4)
    shards = {
        i: verify_shard(manifest, i, shard_bytes(flat, manifest, i))
        for i in range(manifest.num_shards)
    }
    out = assemble_tree(manifest, shards)
    assert set(out) == set(tree)
    for k in tree:
        # bit-identical, not allclose: fp32 roundtrips exactly
        np.testing.assert_array_equal(out[k], np.asarray(tree[k]))
        assert out[k].dtype == tree[k].dtype


def test_manifest_serialization_and_digest_stable(rng):
    manifest, _flat = build_manifest(_tree(rng), step=3, shard_size=8)
    clone = CheckpointManifest.from_bytes(manifest.to_bytes())
    assert clone == manifest
    assert clone.digest() == manifest.digest()


def test_manifest_refuses_unrepresentable_leaf():
    # int64 past 2**24 does not roundtrip through fp32 — must be refused at
    # BUILD time, not discovered as corruption at restore time
    tree = {"ok": np.ones((4,), np.float32),
            "ctr": np.array([2**24 + 1], np.int64)}
    with pytest.raises(ValueError, match="roundtrip"):
        build_manifest(tree, step=0, shard_size=4)


def test_manifest_allows_exactly_representable_ints(rng):
    tree = {"w": rng.standard_normal((6,)).astype(np.float32),
            "step": np.array([12345], np.int64)}
    manifest, flat = build_manifest(tree, step=1, shard_size=4)
    shards = {
        i: verify_shard(manifest, i, shard_bytes(flat, manifest, i))
        for i in range(manifest.num_shards)
    }
    out = assemble_tree(manifest, shards)
    assert out["step"].dtype == np.int64
    np.testing.assert_array_equal(out["step"], tree["step"])


def test_manifest_validate_rejects_bad_geometry(rng):
    manifest, _ = build_manifest(_tree(rng), step=1, shard_size=4)
    broken = CheckpointManifest(
        step=manifest.step, shard_size=manifest.shard_size,
        total_size=manifest.total_size,
        spec=manifest.spec,
        shard_digests=manifest.shard_digests[:-1],  # one missing
        metadata={},
    )
    with pytest.raises(ValueError, match="shards"):
        broken.validate()
    with pytest.raises(ValueError):
        CheckpointManifest.from_bytes(pack_obj({"v": 99}))


def test_verify_shard_rejects_truncation_and_bitflip(rng):
    manifest, flat = build_manifest(_tree(rng), step=1, shard_size=8)
    raw = shard_bytes(flat, manifest, 0)
    with pytest.raises(ValueError, match="bytes"):
        verify_shard(manifest, 0, raw[:-4])
    flipped = bytearray(raw)
    flipped[0] ^= 0xFF
    with pytest.raises(ValueError, match="sha256"):
        verify_shard(manifest, 0, bytes(flipped))


# -------------------------------------------------------------- shard store


def test_store_save_load_roundtrip(rng, tmp_path):
    tree = _tree(rng)
    save_sharded_checkpoint(str(tmp_path), tree, step=11, shard_size=4,
                            metadata={"step": 11})
    loaded = load_sharded_checkpoint(str(tmp_path))
    assert loaded is not None
    step, out, meta = loaded
    assert step == 11 and meta["step"] == 11
    for k in tree:
        np.testing.assert_array_equal(out[k], np.asarray(tree[k]))


def test_store_dedupes_unchanged_shards(rng, tmp_path):
    """Content addressing: a shard identical between steps is stored ONCE."""
    tree = _tree(rng)
    save_sharded_checkpoint(str(tmp_path), tree, step=1, shard_size=4,
                            keep=None)
    store = ShardStore(str(tmp_path))
    first = set(os.listdir(store.shard_dir))
    save_sharded_checkpoint(str(tmp_path), tree, step=2, shard_size=4,
                            keep=None)
    assert set(os.listdir(store.shard_dir)) == first
    assert store.manifest_steps() == [1, 2]


def test_store_drops_corrupt_cached_shard(rng, tmp_path):
    manifest = save_sharded_checkpoint(str(tmp_path), _tree(rng), step=5,
                                       shard_size=4)
    store = ShardStore(str(tmp_path))
    digest = manifest.shard_digests[0]
    path = store._shard_path(digest)
    with open(path, "wb") as f:
        f.write(b"garbage")
    assert store.get_shard(digest) is None  # dropped, not adopted
    assert not os.path.exists(path)
    assert load_sharded_checkpoint(str(tmp_path)) is None  # incomplete now


def test_store_gc_rotates_manifests_and_shards(rng, tmp_path):
    trees = [_tree(rng), _tree(rng), _tree(rng)]
    for step, tree in enumerate(trees):
        save_sharded_checkpoint(str(tmp_path), tree, step=step, shard_size=4,
                                keep=2)
    store = ShardStore(str(tmp_path))
    assert store.manifest_steps() == [1, 2]
    # every shard on disk is referenced by a kept manifest
    referenced = set()
    for step in (1, 2):
        referenced.update(
            d.hex() + ".bin" for d in store.load_manifest(step).shard_digests
        )
    assert set(os.listdir(store.shard_dir)) == referenced
    # keep=None keeps everything
    save_sharded_checkpoint(str(tmp_path), _tree(rng), step=9, shard_size=4,
                            keep=None)
    assert store.manifest_steps() == [1, 2, 9]


def test_store_gc_sweeps_orphan_tmp_files(rng, tmp_path):
    """*.tmp files orphaned by a write killed between mkstemp and os.replace
    are swept (age-guarded: a fresh tmp from an in-flight put survives)."""
    save_sharded_checkpoint(str(tmp_path), _tree(rng), step=1, shard_size=4)
    store = ShardStore(str(tmp_path))
    stale = os.path.join(store.shard_dir, "orphanAAAA.tmp")
    fresh = os.path.join(str(tmp_path), "inflightBBBB.tmp")
    for path in (stale, fresh):
        with open(path, "wb") as f:
            f.write(b"partial")
    os.utime(stale, (0, 0))  # crashed long ago
    store.gc(keep=2)
    assert not os.path.exists(stale)
    assert os.path.exists(fresh)


def test_store_latest_manifest_skips_corrupt_newest(rng, tmp_path):
    save_sharded_checkpoint(str(tmp_path), _tree(rng), step=1, shard_size=4,
                            keep=None)
    save_sharded_checkpoint(str(tmp_path), _tree(rng), step=2, shard_size=4,
                            keep=None)
    with open(os.path.join(str(tmp_path), "manifest-2.bin"), "wb") as f:
        f.write(b"\x00trunc")
    store = ShardStore(str(tmp_path))
    assert store.latest_manifest().step == 1
    loaded = load_sharded_checkpoint(str(tmp_path))
    assert loaded is not None and loaded[0] == 1


# ------------------------------------------------------------------ catalog



def _wait_for_catalog(dht, name, min_entries=1, timeout=15.0):
    """Deflake helper: catalog announcements are published fire-and-forget,
    so a fast joiner can start restoring before its own DHT view holds the
    record(s) and (correctly) fall back to the blob path — tests asserting
    WHICH path carried the restore must wait for the announcement first."""
    import time as _time

    from dedloc_tpu.checkpointing.catalog import catalog_key

    deadline = _time.time() + timeout
    while _time.time() < deadline:
        entry = dht.get(catalog_key(name), latest=True)
        if (
            entry is not None
            and hasattr(entry.value, "items")
            and len(list(entry.value.items())) >= min_entries
        ):
            return
        _time.sleep(0.05)
    raise AssertionError(
        f"catalog for {name!r} never showed {min_entries} announcement(s)"
    )


def _announcement(step=4, num_shards=5, port=1234, shards=None, digest=None):
    return CheckpointAnnouncement(
        step=step,
        manifest_digest=digest or hashlib.sha256(b"m").digest(),
        num_shards=num_shards,
        endpoint=["127.0.0.1", port],
        shards=shards,
    )


def test_announcement_schema_rejects_malformed():
    with pytest.raises(ValueError):
        _announcement(step=-1)
    with pytest.raises(ValueError):
        _announcement(digest=b"short")
    with pytest.raises(ValueError):
        _announcement(shards=[0, 5], num_shards=5)  # out of range
    with pytest.raises(ValueError):
        _announcement(shards=[])  # empty list must be None
    with pytest.raises(ValueError):
        CheckpointAnnouncement(
            step=1, manifest_digest=hashlib.sha256(b"m").digest(),
            num_shards=1, endpoint=["host"],  # not [host, port]
        )


def test_catalog_schema_enforced_at_dht_boundary():
    """The checkpoint_catalog record rides the SAME validator chain as the
    metrics bus: a malformed announcement is rejected at the storing node."""
    from dedloc_tpu.collaborative.metrics import make_validators
    from dedloc_tpu.dht.validation import CompositeValidator, DHTRecord

    validators, _pk = make_validators("exp")
    chain = CompositeValidator(validators)
    key = catalog_key("exp").encode()

    def record(value):
        return DHTRecord(key, b"peer-1", pack_obj(value), 10.0)

    good = _announcement().model_dump()
    assert chain.validate(record(good))
    bad = dict(good, manifest_digest=b"short")
    assert not chain.validate(record(bad))
    assert not chain.validate(record({"junk": 1}))


def test_select_target_prefers_deepest_step_then_majority():
    d1, d2 = hashlib.sha256(b"one").digest(), hashlib.sha256(b"two").digest()
    anns = [
        _announcement(step=4, digest=d1, port=1),
        _announcement(step=9, digest=d1, port=2),
        _announcement(step=9, digest=d1, port=3),
        _announcement(step=9, digest=d2, port=4),  # lone divergent manifest
    ]
    step, digest, providers = select_target(anns)
    assert step == 9 and digest == d1
    assert {a.endpoint[1] for a in providers} == {2, 3}
    assert select_target([]) is None


def test_parse_announcements_skips_own_and_malformed():
    good = _announcement().model_dump()
    items = [
        (b"me", good),
        (b"other", good),
        (b"broken", {"step": "NaN"}),
        (b"junk", "not a dict"),
    ]
    out = parse_announcements(items, own_subkeys=(b"me",))
    assert len(out) == 1
    assert out[0].endpoint == ["127.0.0.1", 1234]


# --------------------------------------------------- fetcher (loopback RPC)


async def _shard_providers(manifest, flat, holders):
    """N fake providers over loopback RPC; ``holders[i]`` is the set of
    shard indices provider i serves (None = all). Returns (endpoints,
    servers, serve_counts)."""
    servers, endpoints = [], []
    counts = [0] * len(holders)

    def make_handlers(i, held):
        async def get_manifest(peer, args):
            return {"manifest": manifest.to_bytes()}

        async def get_shard(peer, args):
            index = int(args["index"])
            if held is not None and index not in held:
                raise KeyError(f"provider {i} does not hold shard {index}")
            counts[i] += 1
            raw = shard_bytes(flat, manifest, index)
            return {
                "index": index,
                "data": serialize_array(
                    np.frombuffer(raw, dtype=np.float32), CompressionType.NONE
                ),
            }

        return get_manifest, get_shard

    for i, held in enumerate(holders):
        server = RPCServer("127.0.0.1", 0)
        get_manifest, get_shard = make_handlers(i, held)
        server.register("ckpt.manifest", get_manifest)
        server.register("ckpt.shard", get_shard)
        await server.start()
        servers.append(server)
        endpoints.append(("127.0.0.1", server.port))
    return endpoints, servers, counts


def test_fetch_spreads_shards_across_providers(rng):
    async def run():
        manifest, flat = build_manifest(_tree(rng, n=29), step=1, shard_size=4)
        assert manifest.num_shards >= 6
        endpoints, servers, counts = await _shard_providers(
            manifest, flat, [None, None, None]
        )
        client = RPCClient(request_timeout=10.0)
        try:
            providers = [(ep, None) for ep in endpoints]
            shards = await fetch_shards(client, manifest, providers,
                                        parallelism=4, retries=0)
            assemble_tree(manifest, shards)  # complete and verified
            # round-robin: with 2x more shards than providers, every
            # provider's uplink carried some of the restore
            assert all(c > 0 for c in counts), counts
            assert sum(counts) == manifest.num_shards
        finally:
            await client.close()
            for s in servers:
                await s.stop()

    asyncio.run(run())


def test_fetch_respects_partial_holders(rng):
    async def run():
        manifest, flat = build_manifest(_tree(rng, n=29), step=1, shard_size=4)
        n = manifest.num_shards
        low = frozenset(range(n // 2))
        high = frozenset(range(n // 2, n))
        endpoints, servers, counts = await _shard_providers(
            manifest, flat, [low, high]
        )
        client = RPCClient(request_timeout=10.0)
        try:
            providers = [(endpoints[0], low), (endpoints[1], high)]
            shards = await fetch_shards(client, manifest, providers,
                                        parallelism=4, retries=0)
            tree = assemble_tree(manifest, shards)
            assert set(tree) == {"b/w", "a/k", "c"}
            assert counts[0] == len(low) and counts[1] == len(high)

            # a shard nobody announces fails the restore cleanly
            with pytest.raises(RestoreFailed, match="no provider"):
                await fetch_shards(client, manifest,
                                   [(endpoints[0], low)], retries=0)
        finally:
            await client.close()
            for s in servers:
                await s.stop()

    asyncio.run(run())


def test_fetch_resumes_from_local_store(rng, tmp_path):
    """Shards already verified on disk are NOT refetched — a restore killed
    mid-flight resumes where it stopped."""

    async def run():
        manifest, flat = build_manifest(_tree(rng, n=29), step=1, shard_size=4)
        store = ShardStore(str(tmp_path))
        prefetched = manifest.num_shards // 2
        for i in range(prefetched):
            store.put_shard(manifest.shard_digests[i],
                            shard_bytes(flat, manifest, i))
        endpoints, servers, counts = await _shard_providers(
            manifest, flat, [None]
        )
        client = RPCClient(request_timeout=10.0)
        try:
            shards = await fetch_shards(
                client, manifest, [(endpoints[0], None)],
                parallelism=2, retries=0, store=store,
            )
            assert sum(counts) == manifest.num_shards - prefetched
            assemble_tree(manifest, shards)  # complete
            # and everything fetched was persisted for the NEXT resume
            assert store.missing_shards(manifest) == []
        finally:
            await client.close()
            for s in servers:
                await s.stop()

    asyncio.run(run())


def test_fully_cached_restore_counts_resumed(rng, tmp_path):
    """A restore satisfied ENTIRELY from the local cache still reports its
    shards as resumed (the best-case resume, not zero)."""
    from dedloc_tpu.telemetry.registry import Telemetry

    async def run():
        manifest, flat = build_manifest(_tree(rng, n=29), step=1, shard_size=4)
        store = ShardStore(str(tmp_path))
        for i, digest in enumerate(manifest.shard_digests):
            store.put_shard(digest, shard_bytes(flat, manifest, i))
        tele = Telemetry(peer="joiner")
        client = RPCClient(request_timeout=10.0)
        try:
            shards = await fetch_shards(
                client, manifest, [], store=store, telemetry_registry=tele,
            )
            assemble_tree(manifest, shards)  # complete, zero wire traffic
            n = manifest.num_shards
            assert tele.counter("ckpt.shards_resumed").value == n
            assert tele.counter("ckpt.shards_fetched").value == 0
        finally:
            await client.close()

    asyncio.run(run())


def test_restore_cache_rotates_across_steps(rng, tmp_path):
    """Repeated restores at new steps do not grow the shard cache without
    bound: a completed restore records its manifest and gc keeps the newest
    two manifests' shards."""

    async def run():
        manifests = []
        for step in (1, 2, 3):
            manifest, flat = build_manifest(
                _tree(rng, n=29), step=step, shard_size=4
            )
            manifests.append(manifest)
            endpoints, servers, _counts = await _shard_providers(
                manifest, flat, [None]
            )
            client = RPCClient(request_timeout=10.0)
            try:
                anns = [CheckpointAnnouncement(
                    step=step, manifest_digest=manifest.digest(),
                    num_shards=manifest.num_shards,
                    endpoint=list(endpoints[0]),
                )]
                await sharded_restore(
                    client, anns, parallelism=2, retries=0,
                    store=ShardStore(str(tmp_path)),
                )
            finally:
                await client.close()
                for s in servers:
                    await s.stop()
        store = ShardStore(str(tmp_path))
        assert store.manifest_steps() == [2, 3]
        assert store.missing_shards(manifests[0])  # step-1 shards collected
        for kept in manifests[1:]:
            assert store.missing_shards(kept) == []

    asyncio.run(run())


def test_fetch_retries_corrupt_shard_from_other_provider(rng):
    """A provider serving a corrupt shard costs one per-shard retry, not the
    restore: verification fails, the fetcher re-pulls from the other peer."""
    from dedloc_tpu.telemetry.registry import Telemetry

    async def run():
        manifest, flat = build_manifest(_tree(rng, n=29), step=1, shard_size=4)
        evil_server = RPCServer("127.0.0.1", 0)

        async def evil_manifest(peer, args):
            return {"manifest": manifest.to_bytes()}

        async def evil_shard(peer, args):
            index = int(args["index"])
            raw = bytearray(shard_bytes(flat, manifest, index))
            raw[0] ^= 0xFF  # always corrupt
            return {
                "index": index,
                "data": serialize_array(
                    np.frombuffer(bytes(raw), dtype=np.float32),
                    CompressionType.NONE,
                ),
            }

        evil_server.register("ckpt.manifest", evil_manifest)
        evil_server.register("ckpt.shard", evil_shard)
        await evil_server.start()
        endpoints, servers, _counts = await _shard_providers(
            manifest, flat, [None]
        )
        client = RPCClient(request_timeout=10.0)
        tele = Telemetry(peer="joiner")
        try:
            providers = [
                (("127.0.0.1", evil_server.port), None),
                (endpoints[0], None),
            ]
            shards = await fetch_shards(
                client, manifest, providers, parallelism=2, retries=2,
                backoff=0.01, telemetry_registry=tele,
            )
            tree = assemble_tree(manifest, shards)
            assert set(tree) == {"b/w", "a/k", "c"}
            assert tele.counter("ckpt.verify_failures").value >= 1
            # verify failures are NOT double-counted as transport failures
            # (docs/observability.md keeps the two disjoint); no transport
            # fault was injected here, so fetch_failures stays 0
            assert tele.counter("ckpt.fetch_failures").value == 0
            assert tele.counter("ckpt.shards_fetched").value == (
                manifest.num_shards
            )
        finally:
            await client.close()
            await evil_server.stop()
            for s in servers:
                await s.stop()

    asyncio.run(run())


def test_sharded_restore_picks_swarm_majority(rng):
    """End-to-end fetcher pipeline off announcements: the lone peer
    announcing a divergent manifest at the same step is outvoted."""

    async def run():
        manifest, flat = build_manifest(_tree(rng, n=29), step=6, shard_size=4)
        endpoints, servers, _counts = await _shard_providers(
            manifest, flat, [None, None]
        )
        client = RPCClient(request_timeout=10.0)
        try:
            anns = [
                CheckpointAnnouncement(
                    step=6, manifest_digest=manifest.digest(),
                    num_shards=manifest.num_shards, endpoint=list(ep),
                )
                for ep in endpoints
            ] + [
                CheckpointAnnouncement(
                    step=6, manifest_digest=hashlib.sha256(b"fork").digest(),
                    num_shards=3, endpoint=["127.0.0.1", 9],
                )
            ]
            metadata, tree, got = await sharded_restore(
                client, anns, parallelism=4, retries=0
            )
            assert got.digest() == manifest.digest()
            assert set(tree) == {"b/w", "a/k", "c"}
        finally:
            await client.close()
            for s in servers:
                await s.stop()

    asyncio.run(run())


# ------------------------------------- end-to-end restore (acceptance test)


def _swarm(n, prefix, shard_size=8, cache_dirs=None):
    """1 root + n-1 joined DHTs with averagers; caller shuts down."""
    from dedloc_tpu.averaging import DecentralizedAverager
    from dedloc_tpu.dht import DHT
    from dedloc_tpu.telemetry.registry import Telemetry

    dhts, avgs, teles = [], [], []
    for i in range(n):
        kwargs = {"listen_host": "127.0.0.1"}
        if dhts:
            kwargs["initial_peers"] = [dhts[0].get_visible_address()]
        dhts.append(DHT(start=True, **kwargs))
        teles.append(Telemetry(peer=f"peer{i}"))
        avgs.append(
            DecentralizedAverager(
                dhts[i], prefix, listen_host="127.0.0.1",
                checkpoint_shard_size=shard_size,
                checkpoint_fetch_parallelism=4,
                checkpoint_dir=(cache_dirs[i] if cache_dirs else None),
                state_sync_retries=3, state_sync_backoff=0.05,
                telemetry_registry=teles[i],
            )
        )
    return dhts, avgs, teles


def _shutdown(dhts, avgs):
    for a in avgs:
        a.shutdown()
    for d in dhts:
        d.shutdown()


def test_fault_injected_multi_peer_restore(rng, tmp_path):
    """ISSUE 5 acceptance: a joiner completes a sharded restore although one
    provider dies mid-fetch and one shard fails its checksum once; the
    restored tree is bit-identical to the source."""
    from dedloc_tpu.testing.faults import FaultSchedule

    tree = {
        "layer/w": rng.standard_normal((8, 8)).astype(np.float32),
        "layer/b": rng.standard_normal((8,)).astype(np.float32),
        "head": rng.standard_normal((17,)).astype(np.float32),
    }
    dhts, avgs, teles = _swarm(
        3, "accept", shard_size=8,
        cache_dirs=[None, None, str(tmp_path / "cache")],
    )
    provider_a, provider_b, joiner = avgs
    try:
        for provider in (provider_a, provider_b):
            provider.set_shared_state(tree, {"step": 42, "local_step": 42})
            provider.publish_state_provider(expiration=60.0)

        # deflake: wait until the joiner's own DHT view holds BOTH
        # announcements before starting the restore under faults (a
        # half-propagated catalog would show provider A as the only
        # announcer and correctly fall back to blob when A dies)
        _wait_for_catalog(dhts[2], "accept", min_entries=2)

        served_a = {"n": 0}

        def a_dies_mid_fetch(ctx):
            if ctx["method"] != "ckpt.shard":
                return False
            if ctx.get("port") != provider_a.server.port:
                return False
            served_a["n"] += 1
            return served_a["n"] > 1  # serves ONE shard, then dies

        corrupted = {"n": 0}

        def b_corrupts_once(ctx):
            # the truncate fault rides the averager's ckpt.shard reply;
            # scope it to provider B so A's death stays the only A-fault
            if corrupted["n"]:
                return False
            corrupted["n"] += 1
            return True

        with FaultSchedule(seed=0) as schedule:
            schedule.inject("rpc.server.dispatch", "drop", times=-1,
                            match=a_dies_mid_fetch)
            schedule.inject("checkpoint.shard_get", "truncate", times=1,
                            fraction=0.5, match=b_corrupts_once)
            result = joiner.load_state_from_peers(timeout=30.0)

        assert result is not None, "restore failed outright"
        metadata, restored = result
        assert metadata["step"] == 42
        assert set(restored) == set(tree)
        for k in tree:
            np.testing.assert_array_equal(restored[k], tree[k])

        tele = teles[2]
        assert tele.counter("ckpt.restores").value == 1, (
            "restore fell back to the blob path"
        )
        assert tele.counter("ckpt.verify_failures").value >= 1
        assert tele.counter("ckpt.fetch_failures").value >= 1
        fired_points = {p for p, _ctx in schedule.fired}
        assert "rpc.server.dispatch" in fired_points  # A really died
        assert "checkpoint.shard_get" in fired_points  # B really corrupted
        # the ckpt.restore span recorded a successful sharded restore
        spans = [e for e in tele.events if e["event"] == "ckpt.restore"]
        assert spans and spans[-1]["ok"] and spans[-1]["mode"] == "sharded"
        # resumable-store by-product: every shard is now cached locally
        store = ShardStore(str(tmp_path / "cache"))
        manifest = provider_b._sharded_state_sync()[0]
        assert store.missing_shards(manifest) == []
    finally:
        _shutdown(dhts, avgs)


def test_unshardable_state_build_failure_is_cached(monkeypatch):
    """A snapshot that cannot roundtrip the fp32 layout fails the sharded
    build ONCE per snapshot — the publish cadence / ckpt RPCs must not pay
    a full-state flatten (plus a warning) on every retry."""
    import threading

    from dedloc_tpu.averaging import averager as averager_mod
    from dedloc_tpu.averaging.averager import DecentralizedAverager

    calls = {"n": 0}

    def failing_build(*args, **kwargs):
        calls["n"] += 1
        raise ValueError("leaf not representable in fp32")

    monkeypatch.setattr(averager_mod, "manifest_of_flat", failing_build)
    # the state-sharing half of an averager, without its DHT
    self = DecentralizedAverager.__new__(DecentralizedAverager)
    self.checkpoint_shard_size = 4
    self._state_lock = threading.Lock()
    self._state_sets, self._shared_state, self._state_generation = [], None, 0
    self._shared_state_blob = self._sharded_state = None
    self._sharded_state_error = None
    assert self.set_shared_state(
        {"p": np.arange(4, dtype=np.float32)}, {"step": 1}
    )
    for _ in range(3):
        with pytest.raises(ValueError, match="not representable"):
            self._sharded_state_sync()
    assert calls["n"] == 1  # built once, cached failure re-raised after
    # a NEW snapshot clears the cached failure and builds again
    assert self.set_shared_state(
        {"p": np.arange(5, dtype=np.float32)}, {"step": 2}
    )
    with pytest.raises(ValueError):
        self._sharded_state_sync()
    assert calls["n"] == 2


def test_joiner_falls_back_to_blob_when_catalog_empty(rng):
    """Providers predating (or opting out of) sharded serving: the joiner's
    sharded-first preference degrades to the full-blob ladder, not a
    failure. Bare averagers default shard_size to 0, so the PROVIDERS here
    never announce a catalog record."""
    from dedloc_tpu.averaging import DecentralizedAverager
    from dedloc_tpu.dht import DHT
    from dedloc_tpu.telemetry.registry import Telemetry

    root = DHT(start=True, listen_host="127.0.0.1")
    d2 = DHT(start=True, listen_host="127.0.0.1",
             initial_peers=[root.get_visible_address()])
    provider = DecentralizedAverager(root, "fallback",
                                     listen_host="127.0.0.1")
    tele = Telemetry(peer="joiner")
    joiner = DecentralizedAverager(
        d2, "fallback", listen_host="127.0.0.1",
        checkpoint_shard_size=8, telemetry_registry=tele,
    )
    tree = {"p": np.arange(7, dtype=np.float32)}
    try:
        provider.set_shared_state(tree, {"step": 5})
        provider.publish_state_provider()
        result = joiner.load_state_from_peers(timeout=20.0)
        assert result is not None
        metadata, restored = result
        assert metadata["step"] == 5
        np.testing.assert_array_equal(restored["p"], tree["p"])
        assert tele.counter("ckpt.restores").value == 0  # blob path used
    finally:
        provider.shutdown(); joiner.shutdown()
        d2.shutdown(); root.shutdown()


def test_sharded_restore_preferred_over_blob(rng):
    """When the catalog IS populated, the sharded path carries the restore
    (ckpt.restores == 1) and serves counters tick on the provider side."""
    dhts, avgs, teles = _swarm(2, "prefer", shard_size=4)
    provider, joiner = avgs
    tree = {"w": rng.standard_normal((13,)).astype(np.float32)}
    try:
        provider.set_shared_state(tree, {"step": 9, "local_step": 9})
        provider.publish_state_provider(expiration=60.0)

        # deflake (the multi-peer test's race, single-provider flavor):
        # the sharded-preference assertion must not race the fire-and-
        # forget catalog announcement
        _wait_for_catalog(dhts[1], "prefer")

        result = joiner.load_state_from_peers(timeout=20.0)
        assert result is not None
        _metadata, restored = result
        np.testing.assert_array_equal(restored["w"], tree["w"])
        assert teles[1].counter("ckpt.restores").value == 1
        assert teles[1].counter("ckpt.shards_fetched").value == 4  # ceil(13/4)
        assert teles[0].counter("ckpt.shards_served").value == 4
        # catalog depth feeds the resume decision (best_advertised_state_step)
        assert joiner.best_advertised_state_step() == 9
    finally:
        _shutdown(dhts, avgs)

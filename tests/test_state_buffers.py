"""The shared state in KEPT host buffers (PR 60): a backup maps and frees
nothing of the state's size after a process's first, the sharded form is
hashed in place and equals the flattened one's, a set under read is not
written, and joiners load whole snapshots while backups run. And ONE state
on the device (PR 62): a backup reads the live state through aliases, makes
no copy of it, leaves no host value on it, and the next apply — which
donates it — waits for the read's end and for nothing behind it."""
import hashlib
import threading
import time

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from dedloc_tpu.averaging import DecentralizedAverager
from dedloc_tpu.averaging import averager as averager_mod
from dedloc_tpu.averaging.partition import (
    SnapshotBuffers,
    TreeLayout,
    tree_spec,
)
from dedloc_tpu.checkpointing import (
    CheckpointManifest,
    assemble_tree,
    build_manifest,
    manifest_of_flat,
    shard_bytes,
    verify_shard,
)
from dedloc_tpu.collaborative import CollaborativeOptimizer
from dedloc_tpu.collaborative import optimizer as optimizer_mod
from dedloc_tpu.core.serialization import deserialize_tree, unpack_obj
from dedloc_tpu.dht import DHT
from dedloc_tpu.optim import lamb
from dedloc_tpu.parallel.train_step import TrainState
from dedloc_tpu.telemetry.registry import Telemetry
from dedloc_tpu.telemetry.steps import StepRecorder
from dedloc_tpu.utils.checkpoint import named_leaves

BF16 = np.dtype(ml_dtypes.bfloat16)

TREES = {
    "float32": lambda rng: {
        "a/w": rng.standard_normal((5, 7)).astype(np.float32),
        "b": rng.standard_normal((11,)).astype(np.float32),
    },
    "bf16_moment": lambda rng: {
        "a/w": rng.standard_normal((5, 7)).astype(np.float32),
        "m/w": rng.standard_normal((5, 7)).astype(BF16),
        "z": rng.standard_normal((3,)).astype(np.float32),
    },
    "int32_counter": lambda rng: {
        "count": np.asarray(123_456, np.int32),
        "w": rng.standard_normal((13,)).astype(np.float32),
    },
}


def _filled(tree) -> SnapshotBuffers:
    buffers = SnapshotBuffers(tree_spec(tree))
    for name, leaf in tree.items():
        buffers.write(name, leaf)
    return buffers


# ------------------------------------------------------------ the buffer set


@pytest.mark.parametrize("kind", sorted(TREES))
def test_buffers_tree_and_flat_are_one_memory(rng, kind):
    tree = TREES[kind](rng)
    buffers = _filled(tree)
    flat = TreeLayout.for_tree(tree).flatten_into(
        tree, np.empty((buffers.layout.total_size,), np.float32)
    )
    np.testing.assert_array_equal(buffers.flat, flat)
    for name, leaf in tree.items():
        kept = buffers.tree[name]
        assert kept.dtype == leaf.dtype and kept.shape == leaf.shape
        np.testing.assert_array_equal(kept, leaf)
        # an fp32 leaf IS its span of the vector; any other keeps its own
        assert np.shares_memory(kept, buffers.flat) == (
            leaf.dtype == np.float32
        )
    own = sum(v.nbytes for v in tree.values() if v.dtype != np.float32)
    assert buffers.nbytes == buffers.flat.nbytes + own
    with pytest.raises(TypeError):
        buffers.write(sorted(tree)[-1], np.zeros(tree[sorted(tree)[-1]].shape,
                                                 np.float64))


def test_tree_spec_reads_device_arrays_without_a_transfer():
    tree = {"w": jnp.ones((3, 2), jnp.bfloat16), "n": jnp.zeros([], jnp.int32)}
    assert tree_spec(tree) == [
        ("n", (), np.dtype(np.int32)), ("w", (3, 2), BF16),
    ]
    assert tree_spec({"x": 3.5}) == [("x", (), np.dtype(np.float64))]


# -------------------------------------------------- (b) the in-place manifest


def _reference_manifest(tree, step, shard_size, metadata):
    """The sharded form as the tree stood before kept buffers, in plain
    numpy: concatenate fp32 casts, hash each shard's bytes."""
    names = sorted(tree)
    flat = np.concatenate(
        [np.asarray(tree[n]).astype(np.float32).reshape(-1) for n in names]
    )
    raws = [
        flat[s : s + shard_size].tobytes()
        for s in range(0, flat.size, shard_size)
    ]
    manifest = CheckpointManifest(
        step=step, shard_size=shard_size, total_size=flat.size,
        spec=tuple(
            (n, tuple(tree[n].shape), np.dtype(tree[n].dtype).str)
            for n in names
        ),
        shard_digests=tuple(hashlib.sha256(r).digest() for r in raws),
        metadata=dict(metadata),
    )
    return manifest, raws


@pytest.mark.parametrize("shard_size", [4, 1 << 20])
@pytest.mark.parametrize("kind", sorted(TREES))
def test_in_place_manifest_equals_the_flattened_one(rng, kind, shard_size):
    tree = TREES[kind](rng)
    metadata = {"step": 9, "local_step": 9}
    reference, raws = _reference_manifest(tree, 9, shard_size, metadata)
    flattened, flat = build_manifest(tree, 9, shard_size, metadata)
    buffers = _filled(tree)
    in_place = manifest_of_flat(
        buffers.layout, buffers.flat, buffers.tree, 9, shard_size, metadata
    )
    for manifest, vector in ((flattened, flat), (in_place, buffers.flat)):
        assert manifest.shard_digests == reference.shard_digests
        assert manifest.to_bytes() == reference.to_bytes()
        assert manifest.digest() == reference.digest()
        for i, raw in enumerate(raws):
            assert shard_bytes(vector, manifest, i) == raw
            verify_shard(manifest, i, raw)


def test_int64_past_2_24_raises_in_place_and_stays_blob_only(rng):
    tree = {"count": np.asarray(2**24 + 1, np.int64),
            "w": rng.standard_normal((6,)).astype(np.float32)}
    with pytest.raises(ValueError, match="roundtrip"):
        build_manifest(tree, 1, 4)
    buffers = _filled(tree)
    with pytest.raises(ValueError, match="roundtrip"):
        manifest_of_flat(buffers.layout, buffers.flat, buffers.tree, 1, 4)
    dht = DHT(start=True, listen_host="127.0.0.1")
    provider = DecentralizedAverager(
        dht, "blobonly", listen_host="127.0.0.1", checkpoint_shard_size=4
    )
    try:
        assert provider.set_shared_state(tree, {"step": 1})
        with pytest.raises(ValueError, match="roundtrip"):
            provider._sharded_state_sync()
        provider.publish_state_provider()  # announces no catalog record
        reply = dht.run_coroutine(lambda node: provider.client.call(
            provider.endpoint, "state.get", {}, timeout=10.0
        ))
        assert hashlib.sha256(reply["state"]).digest() == reply["checksum"]
        restored = deserialize_tree(unpack_obj(reply["state"])["tree"])
        assert restored["count"].dtype == np.int64
        np.testing.assert_array_equal(restored["count"], tree["count"])
    finally:
        provider.shutdown()
        dht.shutdown()


# ------------------------------------------------- (a) two sets, alternating


def _optimizer(prefix, dht, tele=None, **over):
    kwargs = dict(
        target_batch_size=64, averaging_expiration=1.5, averaging_timeout=15.0,
        min_refresh_period=0.1, default_refresh_period=0.3,
        listen_host="127.0.0.1", checkpoint_shard_size=4,
        telemetry_registry=tele,
    )
    kwargs.update(over)
    return CollaborativeOptimizer(lamb(0.05), dht, prefix, **kwargs)


def _backup(opt, state):
    """One whole backup of ``state``, the duty cycle's wait taken out."""
    opt._backup_took = 0.0
    opt.seed_state_sharing(state)
    opt._join_backup()
    done = list(opt._finished_backups)
    opt._note_backups(None)  # counters; the spans need no record here
    return done


def test_four_backups_write_two_sets_in_turn_and_allocate_once():
    dht = DHT(start=True, listen_host="127.0.0.1")
    tele = Telemetry(peer="solo")
    opt = _optimizer("kept", dht, tele)
    try:
        tx = lamb(0.05)
        state = TrainState.create(
            {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((5,))}, tx
        )
        flats, allocated = [], []
        for i in range(4):
            done = _backup(opt, state.replace(
                params={"w": state.params["w"] + i, "b": state.params["b"]}
            ))
            assert [d[0] for d in done] == ["backup_transfer", "backup_publish"]
            assert done[0][2] == done[1][1]  # publish begins where transfer ends
            allocated += [d[3]["opt.backup_host_alloc_bytes"] for d in done]
            buffers, metadata = opt.averager._shared_state
            flats.append(buffers.flat)
            assert metadata["local_step"] == opt.local_step
            np.testing.assert_array_equal(
                buffers.tree["[0]['w']"],
                np.arange(12.0, dtype=np.float32).reshape(3, 4) + i,
            )
            # every published fp32 leaf is a view of the set's one vector
            for name, _shape, dtype in buffers.layout.spec:
                assert np.shares_memory(buffers.tree[name], buffers.flat) == (
                    dtype == np.float32
                )
        assert flats[0] is flats[2] and flats[1] is flats[3]
        assert flats[0] is not flats[1]
        assert len(opt.averager._state_sets) == 2
        size = opt.averager._shared_state[0].nbytes
        # the first backup allocates its own set and, behind its publish,
        # the second one (touched there); no later backup allocates
        assert allocated == [size, size, 0, 0, 0, 0, 0, 0]
        assert tele.counter("opt.backup_host_alloc_bytes").value == 2 * size
        assert tele.counter("opt.backup_bytes").value > 0
    finally:
        opt.shutdown()
        dht.shutdown()


def test_a_changed_layout_takes_fresh_buffers():
    avg = DecentralizedAverager.__new__(DecentralizedAverager)
    avg._state_lock = threading.Lock()
    avg._state_sets, avg._shared_state, avg._state_generation = [], None, 0
    avg._shared_state_blob = avg._sharded_state = None
    avg._sharded_state_error = None
    small = {"w": np.ones((3,), np.float32)}
    large = {"w": np.ones((4,), np.float32)}
    first, n1 = avg.claim_state_buffers(tree_spec(small))
    assert n1 == first.nbytes == 12
    assert avg.claim_state_buffers(tree_spec(small)) == (first, 0)  # unpublished
    avg.publish_shared_state(first, {"step": 0})
    assert avg.reserve_state_buffers() == 12
    assert avg.reserve_state_buffers() == 0
    second, n2 = avg.claim_state_buffers(tree_spec(small))
    assert second is not first and n2 == 0
    third, n3 = avg.claim_state_buffers(tree_spec(large))
    assert n3 == third.nbytes == 16 and third not in (first, second)
    assert avg._state_sets == [first, third]


# --------------------------------------------- (c) a set under read is kept


def _tree_of(k: int):
    """A state whose every element says which backup wrote it."""
    return {
        "a/w": np.full((9, 5), float(k), np.float32),
        "b": np.full((7,), float(k), np.float32),
        "count": np.asarray(k, np.int32),
    }


def _assert_whole(tree, k=None):
    k = int(tree["count"]) if k is None else k
    for name, leaf in _tree_of(k).items():
        assert tree[name].dtype == leaf.dtype
        np.testing.assert_array_equal(tree[name], leaf)
    return k


@pytest.mark.parametrize("reader", ["state.get", "ckpt.manifest", "ckpt.shard"])
def test_reader_held_open_across_two_backups_reads_one_snapshot(
    monkeypatch, reader
):
    """The reader opens on snapshot 1 and stays open while backup 2 lands in
    the OTHER set and backup 3 — which would write under it — is skipped and
    counted; what it gets verifies against the checksum / manifest it was
    given and is snapshot 1, whole."""
    dht = DHT(start=True, listen_host="127.0.0.1")
    peer = DHT(start=True, listen_host="127.0.0.1",
               initial_peers=[dht.get_visible_address()])
    tele = Telemetry(peer="provider")
    opt = _optimizer("leased", dht, tele)
    client = DecentralizedAverager(peer, "leased", listen_host="127.0.0.1")
    entered, release = threading.Event(), threading.Event()

    def held_open(real):
        """``real``, its FIRST call (the reader's) kept open."""
        def wrapper(*args, **kwargs):
            if not entered.is_set():
                entered.set()
                assert release.wait(30.0)
            return real(*args, **kwargs)
        return wrapper

    try:
        tx = lamb(0.05)

        def state_of(k):
            return TrainState.create(
                {"w": jnp.full((6, 4), float(k)), "b": jnp.full((3,), float(k))},
                tx,
            )

        def published_k():
            return float(opt.averager._shared_state[0].tree["[0]['w']"][0, 0])

        both = ["backup_transfer", "backup_publish"]
        assert [d[0] for d in _backup(opt, state_of(1))] == both
        provider = opt.averager
        first_set = provider._shared_state[0]
        reply, call, lease = {}, None, None
        if reader == "ckpt.shard":
            # a shard read is one synchronous section of the DHT loop: held
            # open here as the handler holds it, by its lease
            lease = provider._leased_state()
            held = lease.__enter__()
            manifest, flat = held.sharded
        else:
            if reader == "ckpt.manifest":
                with provider._state_lock:  # a provider that has not hashed yet
                    provider._sharded_state = None
            patched = {"state.get": "serialize_tree",
                       "ckpt.manifest": "manifest_of_flat"}[reader]
            monkeypatch.setattr(
                averager_mod, patched, held_open(getattr(averager_mod, patched))
            )
            call = threading.Thread(target=lambda: reply.update(
                peer.run_coroutine(lambda node: client.client.call(
                    provider.endpoint, reader, {}, timeout=30.0
                ))
            ))
            call.start()
            assert entered.wait(15.0), "the reader never opened"
        assert first_set.readers == 1
        assert [d[0] for d in _backup(opt, state_of(2))] == both  # other set
        assert published_k() == 2.0
        assert _backup(opt, state_of(3)) == []  # under the reader: skipped
        assert published_k() == 2.0
        assert tele.counter("opt.backups_skipped.leased").value == 1
        if reader == "ckpt.shard":
            raws = [shard_bytes(flat, manifest, i)
                    for i in range(manifest.num_shards)]
            lease.__exit__(None, None, None)
        else:
            release.set()
            call.join(30.0)
            assert not call.is_alive() and reply
        if reader == "state.get":
            assert hashlib.sha256(reply["state"]).digest() == reply["checksum"]
            tree = deserialize_tree(unpack_obj(reply["state"])["tree"])
        else:
            if reader == "ckpt.manifest":
                manifest = CheckpointManifest.from_bytes(reply["manifest"])
                raws = [shard_bytes(first_set.flat, manifest, i)
                        for i in range(manifest.num_shards)]
            tree = assemble_tree(manifest, {
                i: verify_shard(manifest, i, raw) for i, raw in enumerate(raws)
            })
        assert first_set.readers == 0
        params = {k: v for k, v in tree.items() if k.startswith("[0]")}
        assert len(params) == 2
        assert all(np.all(v == 1.0) for v in params.values())  # snapshot 1
        # the lease is gone with the reader: the next backup lands
        assert [d[0] for d in _backup(opt, state_of(4))] == both
        assert published_k() == 4.0 and provider._shared_state[0] is first_set
        assert tele.counter("opt.backups_skipped.leased").value == 1
    finally:
        release.set()
        client.shutdown()
        opt.shutdown()
        peer.shutdown()
        dht.shutdown()


def test_duty_cycle_and_busy_skips_are_counted():
    dht = DHT(start=True, listen_host="127.0.0.1")
    tele = Telemetry(peer="solo")
    opt = _optimizer("skips", dht, tele)
    try:
        state = TrainState.create({"w": jnp.ones((4,))}, lamb(0.05))
        _backup(opt, state)
        opt._backup_took = 3600.0  # an hour-long backup: the cap bites
        opt.seed_state_sharing(state)
        assert opt._backup_thread is None
        gate = threading.Event()
        opt._backup_thread = threading.Thread(target=gate.wait, daemon=True)
        opt._backup_thread.start()
        opt.seed_state_sharing(state)
        gate.set()
        opt._join_backup()
        assert tele.counter("opt.backups_skipped.duty_cycle").value == 1
        assert tele.counter("opt.backups_skipped.busy").value == 1
        assert tele.counter("opt.backups_skipped.leased").value == 0
    finally:
        opt.shutdown()
        dht.shutdown()


# ------------------------------------- (d) joiners load while backups run


@pytest.mark.parametrize("shard_size", [0, 4], ids=["blob", "sharded"])
def test_joiner_loads_whole_snapshots_while_backups_run(shard_size):
    root = DHT(start=True, listen_host="127.0.0.1")
    second = DHT(start=True, listen_host="127.0.0.1",
                 initial_peers=[root.get_visible_address()])
    tele = Telemetry(peer="joiner")
    provider = DecentralizedAverager(
        root, "live", listen_host="127.0.0.1",
        checkpoint_shard_size=shard_size,
    )
    joiner = DecentralizedAverager(
        second, "live", listen_host="127.0.0.1",
        checkpoint_shard_size=shard_size, checkpoint_fetch_parallelism=4,
        state_sync_retries=6, state_sync_backoff=0.02,
        telemetry_registry=tele,
    )
    stop = threading.Event()
    written = {"k": 0, "skipped": 0}

    def backups():
        while not stop.is_set():
            k = written["k"] + 1
            if provider.set_shared_state(
                _tree_of(k), {"step": k, "local_step": k}
            ):
                written["k"] = k
                provider.publish_state_provider(expiration=60.0, step=k)
            else:
                written["skipped"] += 1
            time.sleep(0.1)

    writer = threading.Thread(target=backups, daemon=True)
    try:
        assert provider.set_shared_state(_tree_of(0), {"step": 0, "local_step": 0})
        provider.publish_state_provider(expiration=60.0, step=0)
        writer.start()
        loaded = []
        deadline = time.time() + 60.0
        while time.time() < deadline and not (
            len(loaded) >= 6 and loaded[-1] >= loaded[0] + 3
            # a sharded restore that raced a backup falls back to the blob:
            # keep loading until one made it through whole
            and (not shard_size or tele.counter("ckpt.restores").value)
        ):
            result = joiner.load_state_from_peers(timeout=20.0)
            if result is None:
                continue  # every attempt raced a backup: verified, refused
            metadata, tree = result
            assert _assert_whole(tree) == metadata["step"]
            loaded.append(metadata["step"])
            time.sleep(0.02)
        assert len(loaded) >= 6 and loaded[-1] >= loaded[0] + 3, loaded
        assert loaded == sorted(loaded)
        if shard_size:
            assert tele.counter("ckpt.restores").value >= 1
    finally:
        stop.set()
        writer.join(10.0)
        assert not writer.is_alive()
        joiner.shutdown(); provider.shutdown()
        second.shutdown(); root.shutdown()
    assert written["k"] >= 3


# ------------------------------------- (e) one state on the device (PR 62)


def _live_state(k: float = 1.0) -> TrainState:
    """Three parameter leaves under LAMB: ten leaves a snapshot."""
    return TrainState.create(
        {"w": jnp.arange(12.0).reshape(3, 4) + k, "b": jnp.full((5,), k),
         "e": jnp.full((2, 3), -k)},
        lamb(0.05),
    )


def _snapshot_leaves(state):
    return dict(named_leaves((state.params, state.opt_state)))


def _device_buffers():
    """The device buffers under this process's live arrays."""
    out = set()
    for array in jax.live_arrays():
        try:
            out.add(array.unsafe_buffer_pointer())
        except Exception:  # noqa: BLE001 — over several devices: not ours
            pass
    return out


class _Fetches:
    """Every Array the backup thread asks the runtime to fetch, in order;
    the ``hold_at``-th request stays open until ``release``."""

    def __init__(self, monkeypatch, hold_at=None):
        from jax._src.array import ArrayImpl

        self.through, self.hold_at = [], hold_at
        self.entered, self.release = threading.Event(), threading.Event()
        real = ArrayImpl.copy_to_host_async

        def spy(array):
            if threading.current_thread() is not threading.main_thread():
                self.through.append(array)
                if len(self.through) == self.hold_at:
                    self.entered.set()
                    assert self.release.wait(30.0)
            return real(array)

        monkeypatch.setattr(ArrayImpl, "copy_to_host_async", spy)


def test_a_backup_copies_nothing_on_the_device(monkeypatch):
    """Held open at its FIRST request, the reader has made one alias — an
    Array more, not a buffer more; the parent held a copy of every leaf
    here. Behind the read nothing is left of it."""
    dht = DHT(start=True, listen_host="127.0.0.1")
    opt = _optimizer("nocopy", dht)
    fetches = _Fetches(monkeypatch, hold_at=1)
    try:
        state = _live_state()
        # (reading an Array's buffer pointer, or its value, leaves a view
        # of it behind: those first, and the arrays counted first)
        assert int(state.step) == 0
        buffers, arrays = _device_buffers(), len(jax.live_arrays())
        opt._backup_took = 0.0
        opt.seed_state_sharing(state)
        assert fetches.entered.wait(15.0), "the reader never asked"
        assert len(jax.live_arrays()) <= arrays + 1
        assert _device_buffers() == buffers
        fetches.release.set()
        opt._join_backup()
        fetches.through.clear()
        assert len(jax.live_arrays()) == arrays
        assert _device_buffers() == buffers
    finally:
        fetches.release.set()
        opt.shutdown()
        dht.shutdown()


def test_a_backup_leaves_no_host_value_on_the_live_state(monkeypatch):
    """The runtime keeps a fetched value on the Array it was fetched
    through (on a chip: a host copy of it), so no leaf of the live state is
    ever fetched through: each is read through an Array of its own over the
    SAME buffer, which the thread drops."""
    dht = DHT(start=True, listen_host="127.0.0.1")
    opt = _optimizer("nocache", dht)
    fetches = _Fetches(monkeypatch)
    try:
        state = _live_state()
        leaves = _snapshot_leaves(state)
        for _ in range(2):
            assert len(_backup(opt, state)) == 2
        assert len(fetches.through) == 2 * len(leaves)
        live = {id(leaf) for leaf in leaves.values()}
        assert not live & {id(array) for array in fetches.through}
        assert [a.unsafe_buffer_pointer() for a in fetches.through] == 2 * [
            leaves[name].unsafe_buffer_pointer() for name in sorted(leaves)
        ]
        assert all(leaf._npy_value is None for leaf in leaves.values())
        tree = opt.averager._shared_state[0].tree
        for name, leaf in leaves.items():
            np.testing.assert_array_equal(tree[name], np.array(leaf))
    finally:
        opt.shutdown()
        dht.shutdown()


@pytest.mark.parametrize("reader", ["held_open", "done_first"])
def test_an_apply_waits_for_the_reads_end_and_not_for_the_publish(
    monkeypatch, reader
):
    """A backup launched from S, then the apply that donates S. With the
    read held open at its fourth leaf the apply blocks until the read's end
    — and goes on while the PUBLISH is still held; the snapshot that lands
    is S at S's step, bit for bit. With the reader done first nothing
    blocks and nothing is counted."""
    dht = DHT(start=True, listen_host="127.0.0.1")
    tele = Telemetry(peer="solo")
    opt = _optimizer("wait", dht, tele)
    held = reader == "held_open"
    fetches = _Fetches(monkeypatch, hold_at=4 if held else None)
    publish_entered, publish = threading.Event(), threading.Event()
    real_publish = opt.averager.publish_shared_state

    def held_publish(*args, **kwargs):
        publish_entered.set()
        assert publish.wait(30.0)
        return real_publish(*args, **kwargs)

    recorder, out = StepRecorder(), {}
    try:
        state = _live_state()
        before = _snapshot_leaves(state)
        expected = {name: np.array(leaf) for name, leaf in before.items()}
        grads = jax.tree.map(jnp.ones_like, state.params)
        collab = opt.tracker.fetch_collaboration_state()
        opt._backup_took = 0.0
        if held:
            opt.averager.publish_shared_state = held_publish
        opt.seed_state_sharing(state)
        if held:
            assert fetches.entered.wait(15.0), "the reader never got there"
        else:
            opt._join_backup()

        def apply():
            with recorder.step(step=0):
                out["state"] = opt._apply_and_advance(state, grads, collab, 1)[0]

        applying = threading.Thread(target=apply, daemon=True)
        applying.start()
        if held:
            applying.join(0.5)
            assert applying.is_alive(), "the apply did not wait for the read"
            assert not any(leaf.is_deleted() for leaf in before.values())
            fetches.release.set()
        applying.join(30.0)
        assert not applying.is_alive(), "the apply waited for the publish"
        record = recorder.records[-1]
        spans = {s[0]: s for s in record["spans"]}
        assert spans["backup_wait"][1] == "opt_apply"
        waited = spans["backup_wait"][3] - spans["backup_wait"][2]
        if held:
            # the read is over, the publish is not: nothing is shared yet,
            # and the backup this apply would launch is skipped as busy
            assert publish_entered.wait(15.0)
            assert opt._backup_thread.is_alive()
            assert opt.averager._shared_state is None
            assert record["opt.backup_waits"] == 1 and waited > 0.3
            assert record["opt.backups_skipped.busy"] == 1
            publish.set()
            opt._join_backup()
        else:
            # nobody read S any more: the apply took its buffers
            assert all(leaf.is_deleted() for leaf in before.values())
            assert "opt.backup_waits" not in record and waited < 0.2
        assert tele.counter("opt.backup_waits").value == int(held)
        assert int(out["state"].step) == 1
        assert np.all(np.array(out["state"].params["b"]) != expected["[0]['b']"])
        if held:
            buffers, metadata = opt.averager._shared_state
            assert metadata["step"] == 0
            assert sorted(buffers.tree) == sorted(expected)
            for name, leaf in expected.items():
                assert buffers.tree[name].dtype == leaf.dtype
                np.testing.assert_array_equal(buffers.tree[name], leaf)
    finally:
        fetches.release.set()
        publish.set()
        opt.shutdown()
        dht.shutdown()


def test_the_record_that_launches_a_backup_says_what_the_device_holds(
    monkeypatch,
):
    """``opt.hbm_after_launch_bytes``: the device's bytes in use, read once
    inside ``backup_launch`` — on the record that launched a backup, on no
    other, and nowhere the runtime reports nothing (the CPU)."""
    dht = DHT(start=True, listen_host="127.0.0.1")
    opt = _optimizer("hbm", dht)
    recorder = StepRecorder()
    try:
        state = _live_state()
        readings = iter([None, 7_654_321_000])
        monkeypatch.setattr(
            optimizer_mod, "hbm_bytes_in_use", lambda: next(readings)
        )
        for took in (0.0, 0.0, 3600.0):  # launched twice, then skipped
            opt._join_backup()
            opt._backup_took = took
            with recorder.step(step=0):
                opt.seed_state_sharing(state)
        silent, launched, skipped = list(recorder.records)[-3:]
        for record in (silent, launched):
            assert "backup_launch" in {s[0] for s in record["spans"]}
        assert launched["opt.hbm_after_launch_bytes"] == 7_654_321_000
        assert "opt.hbm_after_launch_bytes" not in silent
        assert "opt.hbm_after_launch_bytes" not in skipped
        assert skipped["opt.backups_skipped.duty_cycle"] == 1
    finally:
        opt.shutdown()
        dht.shutdown()

"""The synthetic multicrop source's two schedules (``data/multicrop.py``):
in line for a small batch, AHEAD of the consumer on its own threads for a
large one — one stream a seed either way, held here to the plain generator
the source was until PR 63 (``_plain_batches``, kept as the reference), and
the contract between the ring of kept buffers and whoever uploads from them
(``roles/swav.make_put_crops``)."""
import gc
import sys
import threading
import time

import numpy as np
import pytest

from dedloc_tpu.data import multicrop
from dedloc_tpu.data.multicrop import (
    RING_SLOTS,
    VALID_DRAWS,
    MultiCropSpec,
    synthetic_multicrop_batches,
)

WAIT = 20.0  # seconds a thread or a batch may take before a test fails


def _plain_batches(spec, batch_size, seed=0):
    """The source as it was: every view drawn whole, fresh arrays all the
    way, ``concatenate`` at the end."""
    rng = np.random.default_rng(seed)
    while True:
        means = rng.standard_normal((batch_size, 1, 1, spec.channels)) * 0.5
        groups = []
        for size, count in zip(spec.sizes, spec.counts):
            views = []
            for _ in range(count):
                noise = rng.standard_normal(
                    (batch_size, size, size, spec.channels)
                ).astype(np.float32) * 0.1
                views.append((means + noise).astype(np.float32))
            groups.append(np.concatenate(views, axis=0))
        yield groups


def _source_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith(multicrop._THREAD_PREFIX)]


def _ended(threads):
    deadline = time.monotonic() + WAIT
    for thread in threads:
        thread.join(max(0.0, deadline - time.monotonic()))
    return not any(t.is_alive() for t in threads)


@pytest.fixture
def ahead(monkeypatch):
    """Every batch engages the pipeline, whatever its bytes."""
    monkeypatch.setattr(multicrop, "PIPELINE_MIN_BYTES", 0)


@pytest.fixture(autouse=True)
def no_thread_left_behind():
    yield
    gc.collect()
    assert _ended(_source_threads())


def _assert_same_bytes(ours, plain):
    assert len(ours) == len(plain)
    for a, b in zip(ours, plain):
        assert a.dtype == b.dtype == np.float32 and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


# (schedule, pieces): in line at the tiny spec; ahead with a batch in a few
# pieces; ahead with one image a piece, more pieces than scratch buffers
@pytest.mark.parametrize("seed", [0, 12])
@pytest.mark.parametrize("schedule, piece_elems", [
    ("in_line", 1 << 20), ("ahead", 4096), ("ahead", 1),
])
def test_same_seed_same_bytes_as_the_plain_generator(
    monkeypatch, seed, schedule, piece_elems
):
    spec, rows = MultiCropSpec.tiny(), 5
    monkeypatch.setattr(multicrop, "_PIECE_ELEMS", piece_elems)
    if schedule == "ahead":
        monkeypatch.setattr(multicrop, "PIPELINE_MIN_BYTES", 0)
    source = synthetic_multicrop_batches(spec, rows, seed=seed)
    plain = _plain_batches(spec, rows, seed=seed)
    for _ in range(3):
        _assert_same_bytes(next(source), next(plain))
    assert bool(_source_threads()) == (schedule == "ahead")
    source.close()


def test_the_schedule_is_chosen_by_a_batch_s_bytes(monkeypatch):
    spec = MultiCropSpec.tiny()
    one_batch = 4 * 3 * sum(
        c * s * s * spec.channels for s, c in zip(spec.sizes, spec.counts)
    )
    for threshold, threads in ((one_batch + 1, False), (one_batch, True)):
        monkeypatch.setattr(multicrop, "PIPELINE_MIN_BYTES", threshold)
        source = synthetic_multicrop_batches(spec, 3, seed=1)
        assert not _source_threads()  # nothing starts before the first next
        next(source)
        assert bool(_source_threads()) == threads
        source.close()
    # the role's batch engages it, the reference check's 8 rows do not
    full = MultiCropSpec()
    per_image = 4 * sum(
        c * s * s * full.channels for s, c in zip(full.sizes, full.counts)
    )
    monkeypatch.undo()
    assert 8 * per_image < multicrop.PIPELINE_MIN_BYTES < 128 * per_image


def test_more_finishers_than_cores_lose_no_piece(ahead, monkeypatch):
    """The stress case: sixteen finishers on whatever cores there are, one
    image a piece, the interpreter switching threads every 10 us — a lost
    update of a slot's ``pending`` would publish a batch early (wrong
    bytes) or never (no batch within ``WAIT``)."""
    spec = MultiCropSpec(sizes=(16, 8), counts=(6, 10))
    monkeypatch.setattr(multicrop.os, "cpu_count", lambda: 64)
    monkeypatch.setattr(multicrop, "_PIECE_ELEMS", 1)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        source = synthetic_multicrop_batches(spec, 7, seed=3)
        plain = _plain_batches(spec, 7, seed=3)
        deadline = time.monotonic() + WAIT
        for _ in range(40):
            _assert_same_bytes(next(source), next(plain))
            assert time.monotonic() < deadline
        assert len(_source_threads()) == 1 + 16
        source.close()
    finally:
        sys.setswitchinterval(interval)


def test_batches_come_in_stream_order_whichever_finishes_first(
    ahead, monkeypatch
):
    """One piece a view: a batch is four pieces, and the first batch's first
    piece is held until the batches behind it are finished."""
    spec = MultiCropSpec.tiny()
    monkeypatch.setattr(multicrop.os, "cpu_count", lambda: 64)
    finish, first, later = multicrop._finish_piece, [], threading.Event()

    def slow_first(draw, means, out):
        first.append(out)
        if len(first) == 1:
            assert later.wait(WAIT)
        finish(draw, means, out)
        if len(first) >= 2 * spec.num_crops:
            later.set()  # a whole batch behind the held one is through

    monkeypatch.setattr(multicrop, "_finish_piece", slow_first)
    source = synthetic_multicrop_batches(spec, 3, seed=4)
    plain = _plain_batches(spec, 3, seed=4)
    for _ in range(2 * RING_SLOTS):
        _assert_same_bytes(next(source), next(plain))
    source.close()


def test_a_held_batch_is_valid_for_the_contract_s_draws_and_no_longer(ahead):
    spec = MultiCropSpec.tiny()
    source = synthetic_multicrop_batches(spec, 4, seed=5)
    plain = _plain_batches(spec, 4, seed=5)
    drawn = [next(source)]
    held, was = drawn[0], next(plain)
    for _ in range(3 * RING_SLOTS):
        for _ in range(VALID_DRAWS):
            drawn.append(next(source))
            later = next(plain)
        time.sleep(0.02)  # the source builds ahead: let it
        _assert_same_bytes(held, was)
        held, was = drawn[-1], later
    # ... and the ring is a ring: a batch's arrays come round again
    assert np.shares_memory(drawn[0][0], drawn[RING_SLOTS][0])
    assert not np.shares_memory(drawn[0][0], drawn[VALID_DRAWS + 1][0])
    source.close()


class _LateUpload:
    """An upload that reads its host array only when it is waited for."""

    log = []

    def __init__(self, host):
        self.host, self.was, self.read = host, host.copy(), None
        _LateUpload.log.append(self)

    def block_until_ready(self):
        if self.read is None:
            self.read = self.host.copy()
        return self


def test_put_crops_lets_no_slot_go_before_its_upload_has_read_it(ahead):
    """The loop's own order — draw, put, draw, put — over an upload that
    reads late: every batch is read before the draw that may rewrite it,
    and reads what was drawn."""
    from dedloc_tpu.roles.swav import make_put_crops

    _LateUpload.log = []
    spec = MultiCropSpec.tiny()
    put = make_put_crops(upload=_LateUpload)
    source = synthetic_multicrop_batches(spec, 4, seed=9)
    plain = _plain_batches(spec, 4, seed=9)
    puts = []
    for k in range(4 * RING_SLOTS):
        if k > VALID_DRAWS:
            # batch k may rewrite batch k - VALID_DRAWS - 1: read by now?
            assert all(u.read is not None for u in puts[k - VALID_DRAWS - 1])
        batch, expected = next(source), next(plain)
        puts.append(put(batch))
        assert all(u.host is group for u, group in zip(puts[-1], batch))
        _assert_same_bytes([u.was for u in puts[-1]], expected)
        time.sleep(0.01)
    source.close()
    assert len(_LateUpload.log) == len(puts) * len(spec.sizes)
    for uploads in puts[:-1]:
        for u in uploads:
            assert u.read.tobytes() == u.was.tobytes()
    # the last batch's upload is nobody's to wait for yet
    assert all(u.read is None for u in puts[-1])


def test_put_crops_places_groups_on_a_mesh_and_waits_for_sharded_ones():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from dedloc_tpu.parallel.mesh import make_mesh
    from dedloc_tpu.roles.swav import make_put_crops

    sharding = NamedSharding(make_mesh(2), PartitionSpec("data"))
    put = make_put_crops(sharding)
    source = synthetic_multicrop_batches(MultiCropSpec.tiny(), 4, seed=2)
    for _ in range(3):
        host = next(source)
        device = put(host)
        assert all(isinstance(d, jax.Array) for d in device)
        assert all(d.sharding == sharding for d in device)
        _assert_same_bytes([np.asarray(d) for d in device], host)


def _raise_while_consuming(source):
    for i, _batch in enumerate(source):
        if i == 1:
            raise RuntimeError("the consumer's")


@pytest.mark.parametrize("ending", ["close", "dropped", "consumer_raises"])
def test_every_ending_ends_the_source_s_threads(ahead, ending):
    source = synthetic_multicrop_batches(MultiCropSpec.tiny(), 4, seed=1)
    if ending == "consumer_raises":
        with pytest.raises(RuntimeError, match="the consumer's"):
            _raise_while_consuming(source)
        threads = _source_threads()
        assert threads
        del source
        gc.collect()  # the traceback held the frame that held the source
    else:
        next(source)  # the one-shot caller's
        threads = _source_threads()
        assert len(threads) >= 2
        if ending == "close":
            source.close()
        else:
            del source
    assert _ended(threads) and not _source_threads()


@pytest.mark.parametrize("where", ["draw", "finish"])
def test_an_exception_of_the_source_s_threads_is_raised_at_next(
    ahead, monkeypatch, where
):
    spec = MultiCropSpec.tiny()
    if where == "finish":
        def fail(draw, means, out):
            raise ValueError("the finisher's")

        monkeypatch.setattr(multicrop, "_finish_piece", fail)
    else:
        class Broken:
            def standard_normal(self, *args, **kwargs):
                raise ValueError("the draw's")

        monkeypatch.setattr(
            multicrop.np.random, "default_rng", lambda seed: Broken()
        )
    source = synthetic_multicrop_batches(spec, 4, seed=1)
    with pytest.raises(ValueError, match=f"the {where}"):
        next(source)
    # the generator ended with it, and took its threads along
    with pytest.raises(StopIteration):
        next(source)
    assert not _source_threads()


def test_draws_are_counted_and_those_that_were_ready(ahead, monkeypatch):
    spec = MultiCropSpec.tiny()
    stats = {}
    source = synthetic_multicrop_batches(spec, 4, seed=1, stats=stats)
    next(source)
    # the first batch is ready only if this thread was held up behind it
    first = stats["data.draws_ready"]
    assert stats["data.draws"] == 1 and first in (0, 1)
    for n in range(2, 6):
        # a consumer slower than the source finds its batch waiting
        deadline = time.monotonic() + WAIT
        while source.gi_frame.f_locals["ahead"].ready.empty():
            assert time.monotonic() < deadline
            time.sleep(0.005)
        next(source)
        assert stats == {"data.draws": n, "data.draws_ready": first + n - 1}
    source.close()
    # in line nothing is ever ready ahead of its consumer
    monkeypatch.undo()
    stats = {}
    source = synthetic_multicrop_batches(spec, 4, seed=1, stats=stats)
    for _ in range(3):
        next(source)
    assert stats == {"data.draws": 3, "data.draws_ready": 0}

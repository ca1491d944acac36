"""Rows are counted for the peer that ASKED for the source, not for the
thread that draws: a producer thread that prefetches around the role's
batches (``data/streaming.prefetch``) draws on a thread no peer is bound to,
and its rows still land on — and its stop still follows — the record of the
peer that built the source."""
import threading

import pytest

from benchmark.instrument import InstrumentedSource, Recorder, WindowOver


def _source(recorder, peer, rows=4):
    return InstrumentedSource(iter(range(1000)), recorder, peer, rows,
                              WindowOver)


def test_draws_from_another_thread_land_on_the_builders_record():
    recorder = Recorder(n_peers=2, warmup_steps=1, seconds=1.0)
    built = {}

    def peer_main(index):
        # as run.py's peer threads do: bind, then build the source there
        recorder.bind(index)
        built[index] = _source(recorder, recorder.peer())

    for index in (0, 1):
        thread = threading.Thread(target=peer_main, args=(index,))
        thread.start()
        thread.join(timeout=10)
        assert not thread.is_alive()
    assert recorder.peer() is None  # this thread is bound to no peer

    drawn = []
    producer = threading.Thread(
        target=lambda: drawn.extend(next(built[1]) for _ in range(3))
    )
    producer.start()
    producer.join(timeout=10)
    assert not producer.is_alive() and drawn == [0, 1, 2]
    assert next(built[0]) == 0  # and in line, from an unbound thread
    assert [rows for _t0, _t1, rows in recorder.peers[1].draws] == [4, 4, 4]
    assert len(recorder.peers[0].draws) == 1
    assert all(t1 >= t0 for t0, t1, _rows in recorder.peers[1].draws)


def test_the_stop_follows_the_builders_record_on_any_thread():
    recorder = Recorder(n_peers=2, warmup_steps=1, seconds=1.0)
    sources = [_source(recorder, peer) for peer in recorder.peers]
    recorder.final_step = 5
    recorder.peers[0].last_local_step = 5  # peer 0 closed the window
    recorder.peers[1].last_local_step = 4  # peer 1 has a step to go
    raised = []

    def produce(source):
        try:
            next(source)
        except WindowOver as e:
            raised.append(e)

    for source in sources:
        thread = threading.Thread(target=produce, args=(source,))
        thread.start()
        thread.join(timeout=10)
        assert not thread.is_alive()
    assert len(raised) == 1 and not recorder.peers[0].draws
    assert len(recorder.peers[1].draws) == 1
    recorder.abort = True  # an abort ends every source, whoever draws
    with pytest.raises(WindowOver):
        next(sources[1])

"""Share of the window's wall the peers spent inside ``next(batches)``."""


def reduce(run, params):
    wall = sum(
        end - start for start, end in
        (run.recorder.peer_window(peer) for peer in run.recorder.peers)
    )
    waited = sum(seconds for seconds, _rows in run.draws_in_window())
    return 100.0 * waited / wall if wall > 0 else None

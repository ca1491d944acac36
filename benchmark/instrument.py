"""The yardstick's probes: everything the benchmark learns about a run it
learns here, from OUTSIDE the program.

Three wrappers, installed by ``run.py`` before any peer starts:

- the role's batch source (the role adapter says which function builds it):
  every ``next()`` is timed and its rows counted for the peer that ASKED for
  the source (its record is resolved once, where the source is built, on the
  peer's own thread — a producer thread that draws ahead of the loop counts
  for that peer too), and the source is where a run ends — it stops once
  the window is over;
- ``CollaborativeOptimizer.step``: wall per call and whether it stepped;
- ``CollaborativeOptimizer.report_loss``: the loss each global step
  advertises (both roles call it once per global step).

Plus two passive listeners: a ``logging`` handler on the ``dedloc_tpu``
logger (group size per global step, warnings) and ``jax.monitoring``
listeners (compile events with the jitted function's name, cache hits).

All peers of a cell are threads of one process; a thread is bound to its
peer index with ``Recorder.bind`` before it enters the role.
"""
from __future__ import annotations

import dataclasses
import logging
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

now = time.perf_counter


class WindowOver(Exception):
    """Raised by a wrapped batch source to end a role that has no graceful
    end-of-data path (``run_trainer``'s ``finally`` shuts everything down)."""


@dataclasses.dataclass
class PeerRecord:
    index: int
    draws: List[Tuple[float, float, int]] = dataclasses.field(
        default_factory=list
    )  # (t_before next(), t_after, rows)
    opt_calls: List[Tuple[float, float, bool, int]] = dataclasses.field(
        default_factory=list
    )  # (t_enter, t_exit, stepped, local_step after the call)
    losses: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list
    )  # (t, loss)
    groups: Dict[int, int] = dataclasses.field(default_factory=dict)
    first_state_step: Optional[int] = None
    last_state: Any = None
    last_local_step: int = 0
    full_group_steps: int = 0  # completed global steps with the full group
    error: Optional[BaseException] = None
    finished: bool = False

    def step_time(self, local_step: int) -> Optional[float]:
        """When this peer completed the global step that took it to
        ``local_step``."""
        for _t0, t1, stepped, after in self.opt_calls:
            if stepped and after == local_step:
                return t1
        return None


_GROUP_RE = re.compile(r"global step (\d+) applied \(group=(\d+)")


class Recorder:
    """Shared state of one run: per-peer records, the window, the stop."""

    def __init__(self, n_peers: int, warmup_steps: int, seconds: float):
        self.n_peers = n_peers
        self.warmup_steps = warmup_steps
        self.seconds = seconds
        self.peers = [PeerRecord(i) for i in range(n_peers)]
        self.lock = threading.Lock()
        self._local = threading.local()
        self.start_step: Optional[int] = None  # window opens at this step
        self.window_open_t: Optional[float] = None
        self.window_open_wall: Optional[float] = None
        self.deadline: Optional[float] = None
        self.final_step: Optional[int] = None  # window closes at this step
        self.abort = False
        self.compiles: List[Tuple[float, str, str, float]] = []
        self.cache = {"hits": 0, "misses": 0}
        self.log: List[Tuple[float, int, Optional[int], str, str]] = []

    # ------------------------------------------------------------ threads

    def bind(self, index: int) -> None:
        self._local.index = index

    def peer(self) -> Optional[PeerRecord]:
        index = getattr(self._local, "index", None)
        return None if index is None else self.peers[index]

    # ------------------------------------------------------------- events

    def on_draw(self, peer: PeerRecord, t0: float, t1: float,
                rows: int) -> None:
        peer.draws.append((t0, t1, rows))

    def should_stop(self, peer: PeerRecord) -> bool:
        if self.abort:
            return True
        return (
            self.final_step is not None
            and peer.last_local_step >= self.final_step
        )

    def before_opt_step(self, state_in) -> None:
        peer = self.peer()
        if peer is not None and peer.first_state_step is None:
            # one host read of a device scalar, on the first boundary of the
            # warm-up only (before the call: an apply donates its input)
            peer.first_state_step = int(state_in.step)

    def on_opt_step(self, opt, t0: float, t1: float, out) -> None:
        peer = self.peer()
        if peer is None:
            return
        stepped = bool(out[3])
        local_step = int(opt.local_step)
        peer.opt_calls.append((t0, t1, stepped, local_step))
        peer.last_local_step = local_step
        peer.last_state = out[0]
        if not stepped:
            return
        with self.lock:
            if peer.groups.get(local_step, 0) >= self.n_peers:
                peer.full_group_steps += 1
            if self.start_step is None:
                ready = all(
                    p.full_group_steps >= self.warmup_steps
                    and p.groups.get(p.last_local_step, 0) >= self.n_peers
                    for p in self.peers
                )
                if ready and all(
                    p.last_local_step == local_step for p in self.peers
                ):
                    self.start_step = local_step
                    self.window_open_t = t1
                    self.window_open_wall = time.time()
                    self.deadline = t1 + self.seconds
            elif self.final_step is None and t1 >= self.deadline:
                self.final_step = local_step

    def on_loss(self, loss: float) -> None:
        peer = self.peer()
        if peer is not None:
            peer.losses.append((now(), float(loss)))

    def on_log(self, record: logging.LogRecord) -> None:
        message = record.getMessage()
        peer = self.peer()
        match = _GROUP_RE.search(message)
        if match and peer is not None:
            peer.groups[int(match.group(1))] = int(match.group(2))
        self.log.append((
            now(), record.levelno,
            None if peer is None else peer.index, record.name, message,
        ))

    # ------------------------------------------------------------- window

    def peer_window(self, peer: PeerRecord) -> Optional[Tuple[float, float]]:
        """(start, end) of the window on this peer's own clock of steps: its
        completion of the opening and of the closing global step."""
        if self.start_step is None or self.final_step is None:
            return None
        start = peer.step_time(self.start_step)
        end = peer.step_time(self.final_step)
        if start is None or end is None:
            return None
        return start, end

    def window(self) -> Optional[Tuple[float, float]]:
        spans = [self.peer_window(p) for p in self.peers]
        if any(s is None for s in spans):
            return None
        return max(s[0] for s in spans), max(s[1] for s in spans)


class _LogHandler(logging.Handler):
    def __init__(self, recorder: Recorder) -> None:
        super().__init__(level=logging.INFO)
        self.recorder = recorder

    def emit(self, record: logging.LogRecord) -> None:
        self.recorder.on_log(record)


class InstrumentedSource:
    """Iterator around the role's own batch iterator, counting for ``peer``
    — the record of the peer that built the source, whichever thread
    draws."""

    def __init__(self, inner, recorder: Recorder, peer: PeerRecord,
                 rows: int, stop_exc):
        self.inner = iter(inner)
        self.recorder = recorder
        self.peer = peer
        self.rows = rows
        self.stop_exc = stop_exc

    def __iter__(self):
        return self

    def __next__(self):
        if self.recorder.should_stop(self.peer):
            raise self.stop_exc()
        t0 = now()
        batch = next(self.inner)
        self.recorder.on_draw(self.peer, t0, now(), self.rows)
        return batch


def install(recorder: Recorder):
    """Wrap the optimizer, attach the listeners; returns an ``uninstall``."""
    import jax

    from dedloc_tpu.collaborative.optimizer import CollaborativeOptimizer
    from dedloc_tpu.utils.logging import get_logger

    orig_step = CollaborativeOptimizer.step
    orig_report = CollaborativeOptimizer.report_loss

    def step(self, state, grad_acc, n_acc, samples):
        recorder.before_opt_step(state)
        t0 = now()
        out = orig_step(self, state, grad_acc, n_acc, samples)
        recorder.on_opt_step(self, t0, now(), out)
        return out

    def report_loss(self, loss):
        recorder.on_loss(loss)
        return orig_report(self, loss)

    CollaborativeOptimizer.step = step
    CollaborativeOptimizer.report_loss = report_loss

    def on_duration(event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            recorder.compiles.append((
                now(), event.rsplit("/", 1)[1],
                str(kw.get("fun_name", "")), float(duration),
            ))

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            recorder.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            recorder.cache["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    handler = _LogHandler(recorder)
    get_logger()  # configure the package logger before attaching
    logging.getLogger("dedloc_tpu").addHandler(handler)

    def uninstall() -> None:
        CollaborativeOptimizer.step = orig_step
        CollaborativeOptimizer.report_loss = orig_report
        logging.getLogger("dedloc_tpu").removeHandler(handler)

    return uninstall

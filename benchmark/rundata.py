"""What one finished run hands to the metric reducers."""
from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Dict, List, Optional, Tuple

from benchmark.instrument import Recorder


@dataclasses.dataclass
class RunData:
    recorder: Recorder
    cell: Dict[str, Any]
    config: Dict[str, Any]
    role: Any  # the role adapter module
    args: Any  # peer 0's parsed arguments
    chips: int
    device_kind: str
    process_start: float  # perf_counter at the top of run.py
    memory: Dict[str, Any]
    trace: Optional[Dict] = None  # benchmark.trace.Trace of the traced window
    step_records: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list
    )  # the flight recorder's step.record events inside the window

    def window(self) -> Tuple[float, float]:
        window = self.recorder.window()
        if window is None:
            raise RuntimeError("the run has no complete window")
        return window

    def opt_calls_in_window(self, stepped: bool) -> List[float]:
        """Wall seconds of every ``opt.step`` call inside the window that did
        (or did not) step, all peers together (each peer's calls between ITS
        completion of the opening and of the closing global step)."""
        walls = []
        for peer in self.recorder.peers:
            start, end = self.recorder.peer_window(peer)
            walls += [
                t1 - t0 for t0, t1, did, _after in peer.opt_calls
                if did == stepped and start < t1 <= end
            ]
        return walls

    def draws_in_window(self) -> List[Tuple[float, int]]:
        """(seconds inside ``next(batches)``, rows) of every draw inside the
        window, all peers together."""
        draws = []
        for peer in self.recorder.peers:
            start, end = self.recorder.peer_window(peer)
            draws += [
                (t1 - t0, rows) for t0, t1, rows in peer.draws
                if start < t1 <= end
            ]
        return draws

    def seq_length(self) -> int:
        """The sequence length an ALBERT cell runs at (the role clamps the
        flag to the configuration's positions)."""
        return min(
            self.args.training.seq_length,
            self.config["sizes"]["max_position_embeddings"],
        )

    def program(self, logical: str) -> str:
        return self.role.PROGRAMS[logical]


def median_ms(seconds: List[float]) -> Optional[float]:
    return statistics.median(seconds) * 1e3 if seconds else None

"""One profiler gate, and the reader that joins the host's clock with the
device's.

The flight recorder (``telemetry/steps.py``) and ``Telemetry.span`` enter a
``jax.profiler`` annotation ``dedloc/<name>`` around every span, so a
profiler session that covers the run holds the host's spans on the host
plane of the SAME xplane, on the same time base, as the device's programs
("XLA Modules" on each ``/device:*`` plane). Two things live here:

- ``ProfileGate``: the one way a role opens such a session —
  ``--telemetry.profile_dir`` and ``--telemetry.profile_boundaries
  <first>:<count>`` profile ``count`` accumulation boundaries from the
  ``first``. The recorder tells the gate each boundary's index. A process
  has one profiler session: with several peer threads in one process the
  first to reach its window owns it and the others skip.
- ``attribute_idle``: takes the idle intervals of the idlest device between
  its major programs and charges each to the innermost ``dedloc/*`` host
  span covering it, per host thread — what the HOST was doing while the
  device had nothing to run, by the host's own names and not by the gap's
  neighbours on the device.

    python -m dedloc_tpu.telemetry.profile <profile_dir> [--json] [--save f]

Both work on a small neutral form, ``{"devices": {plane: [(program,
start_ns, duration_ns), ...]}, "hosts": {thread: [(span, start_ns,
duration_ns), ...]}}`` — also how a recorded profile is kept as a test
fixture (``--save``).
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import threading
from typing import Any, Dict, List, Optional, Tuple

from dedloc_tpu.utils.logging import get_logger

logger = get_logger(__name__)

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)
Profile = Dict[str, Dict[str, List[Event]]]

PREFIX = "dedloc/"
ROOT = "boundary"  # the record itself: dedloc/boundary
MODULES = "XLA Modules"

# a process has one jax.profiler session at a time, and a profile directory
# holds one window: the peer threads of one process share both
_SESSION = threading.Lock()
_CLAIMED_DIRS: set = set()


class ProfileGate:
    """Opens a profiler session at boundary ``first`` and closes it
    ``count`` boundaries later (or at ``close()``). The session is stopped
    on a thread of its own: writing the xplane took most of a minute for a
    ten-second window of two ALBERT peers (my chip run, PR 23), and a
    training thread held that long makes its partners' rounds fail."""

    def __init__(self, profile_dir: str, first: int, count: int) -> None:
        self.profile_dir = profile_dir
        self.first = int(first)
        self.count = int(count)
        self._owns = False
        self._done = False
        self._stopper: Optional[threading.Thread] = None

    def at_boundary(self, index: int) -> None:
        """Called by the recorder before boundary ``index`` begins."""
        if self._done:
            return
        if self._owns:
            if index >= self.first + self.count:
                self._stop(wait=False)
        elif index >= self.first:
            if not _SESSION.acquire(blocking=False):
                self._skip()
                return
            if self.profile_dir in _CLAIMED_DIRS:
                _SESSION.release()
                self._skip()
                return
            _CLAIMED_DIRS.add(self.profile_dir)
            self._owns = True
            start_session(self.profile_dir)
            logger.info(
                f"profiling {self.count} boundaries from boundary {index} "
                f"into {self.profile_dir}"
            )

    def _skip(self) -> None:
        self._done = True
        logger.info(
            f"profile window skipped: {self.profile_dir} is another peer "
            "thread's window"
        )

    def _stop(self, wait: bool) -> None:
        if self._owns:
            self._owns = False
            self._done = True
            self._stopper = threading.Thread(
                target=self._stop_session, name="dedloc-profile-stop",
                daemon=True,
            )
            self._stopper.start()
        if wait and self._stopper is not None:
            self._stopper.join()

    def _stop_session(self) -> None:
        import jax

        try:
            jax.profiler.stop_trace()
            logger.info(f"profile written under {self.profile_dir}")
        finally:
            _SESSION.release()

    def close(self) -> None:
        """End of the loop: stop a window still open, and wait until the
        profile is on disk."""
        self._stop(wait=True)


def start_session(profile_dir: str) -> None:
    """Device trace plus the host's TraceMe events (``dedloc/*`` among
    them), with the Python tracer off: it would slow the very threads whose
    gaps the profile is to show."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(profile_dir, profiler_options=options)


def profile_gate(args) -> Optional[ProfileGate]:
    """The gate a ``TelemetryArguments`` block asks for, or None: telemetry
    off, or no ``profile_dir``."""
    if not getattr(args, "enabled", False) or not args.profile_dir:
        return None
    first, _, count = str(args.profile_boundaries).partition(":")
    try:
        first_i, count_i = int(first), int(count)
    except ValueError:
        first_i = count_i = -1
    if first_i < 0 or count_i <= 0:
        raise ValueError(
            "--telemetry.profile_boundaries wants <first>:<count>, got "
            f"{args.profile_boundaries!r}"
        )
    return ProfileGate(args.profile_dir, first_i, count_i)


# ------------------------------------------------------------------ reading


def load_profile(profile_dir: str) -> Profile:
    """The newest xplane under ``profile_dir``: per device plane its
    programs, per host thread its ``dedloc/*`` spans (the prefix taken
    off)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {profile_dir}")
    devices: Dict[str, List[Event]] = {}
    hosts: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == MODULES:
                    devices[plane.name] = [
                        (ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [
                    (ev.name[len(PREFIX):], float(ev.start_ns),
                     float(ev.duration_ns))
                    for ev in line.events if ev.name.startswith(PREFIX)
                ]
                if spans:
                    name, n = line.name, 2
                    while name in hosts:  # threads may share a name
                        name, n = f"{line.name}#{n}", n + 1
                    hosts[name] = spans
    return {"devices": devices, "hosts": hosts}


def save_profile(profile: Profile, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(profile, f)


def load_saved(path: str) -> Profile:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {
        kind: {k: [tuple(ev) for ev in events] for k, events in group.items()}
        for kind, group in raw.items()
    }


def idle_intervals(
    programs: List[Event], major_ns: float = 1e5
) -> List[Tuple[float, float]]:
    """The intervals in which a device ran no MAJOR program (one of at least
    ``major_ns``: the helpers a dispatch scatters between them —
    ``convert_element_type``, ``_threefry_seed`` — are microseconds and stay
    inside the gap), between its first and its last."""
    gaps = []
    frontier = None
    for _n, start, dur in sorted(
        (ev for ev in programs if ev[2] >= major_ns), key=lambda ev: ev[1]
    ):
        if frontier is not None and start > frontier:
            gaps.append((frontier, start))
        if frontier is None or start + dur > frontier:
            frontier = start + dur
    return gaps


def _charge(spans: List[Event], a: float, b: float, into: Dict[str, float]):
    """Split [a, b] at the edges of the spans that overlap it and charge
    each piece to the innermost span covering it (the latest to start)."""
    live = [(s, s + d, n) for n, s, d in spans if s < b and s + d > a]
    cuts = sorted({a, b, *(
        t for s, e, _n in live for t in (s, e) if a < t < b
    )})
    for c, d in zip(cuts, cuts[1:]):
        mid = (c + d) / 2
        covering = [(s, -e, n) for s, e, n in live if s <= mid < e]
        name = max(covering)[2] if covering else "(no span)"
        into[name] = into.get(name, 0.0) + (d - c) / 1e9


def attribute_idle(profile: Any, major_ns: float = 1e5) -> Dict[str, Any]:
    """Charge the idlest device's idle time to the host's spans.

    ``profile``: a directory ``load_profile`` reads, or the neutral form.
    Per host thread that recorded ``dedloc/*`` spans — the peer threads
    (those with ``dedloc/boundary`` records; ``.pair`` has two, and both are
    named) and whatever else carried spans, such as a DHT loop with its
    ``mm.form_group`` / ``allreduce.round`` — the device's idle intervals
    are clipped to what the thread's spans bracket (a boundary begun before
    the session is not in it) and split by innermost span:

        {"device": plane, "idle_s": ..., "window_s": ...,
         "threads": {thread: {"peer": bool, "idle_s": seconds in the
                              thread's range,
                              "spans": {name: seconds, ...},
                              "named_share": share not charged to the root
                              ("boundary") or to no span}}}
    """
    if isinstance(profile, str):
        profile = load_profile(profile)
    best = None
    for plane, programs in profile["devices"].items():
        gaps = idle_intervals(programs, major_ns)
        idle = sum(b - a for a, b in gaps)
        if best is None or idle > best[2]:
            best = (plane, programs, idle, gaps)
    if best is None:
        raise ValueError("the profile holds no device plane with programs")
    plane, programs, idle, gaps = best
    major = [ev for ev in programs if ev[2] >= major_ns]
    window = (
        max(s + d for _n, s, d in major) - min(s for _n, s, _d in major)
        if major else 0.0
    )
    threads: Dict[str, Any] = {}
    for thread, spans in profile["hosts"].items():
        lo = min(s for _n, s, _d in spans)
        hi = max(s + d for _n, s, d in spans)
        charged: Dict[str, float] = {}
        for a, b in gaps:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                _charge(spans, a, b, charged)
        total = sum(charged.values())
        unnamed = charged.get(ROOT, 0.0) + charged.get("(no span)", 0.0)
        threads[thread] = {
            "peer": any(n == ROOT for n, _s, _d in spans),
            "idle_s": total,
            "spans": dict(sorted(charged.items(), key=lambda kv: -kv[1])),
            "named_share": (total - unnamed) / total if total > 0 else 0.0,
        }
    return {
        "device": plane, "idle_s": idle / 1e9, "window_s": window / 1e9,
        "threads": threads,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Device idle time of a profile, by the host span that "
        "covers it."
    )
    parser.add_argument("profile", help="profile directory, or a --save file")
    parser.add_argument("--json", action="store_true")
    parser.add_argument("--save", help="also keep the neutral form here (.json.gz)")
    opts = parser.parse_args(argv)
    profile = (
        load_saved(opts.profile) if os.path.isfile(opts.profile)
        else load_profile(opts.profile)
    )
    if opts.save:
        save_profile(profile, opts.save)
    result = attribute_idle(profile)
    if opts.json:
        print(json.dumps(result))
        return 0
    share = 100 * result["idle_s"] / result["window_s"] if result["window_s"] else 0.0
    print(
        f"{result['device']}: idle {result['idle_s']:.4f} s of "
        f"{result['window_s']:.4f} s between major programs ({share:.1f} %)"
    )
    for thread, row in sorted(
        result["threads"].items(), key=lambda kv: (not kv[1]["peer"], kv[0])
    ):
        kind = "peer thread" if row["peer"] else "other thread"
        print(
            f"\n{kind} {thread}: {row['idle_s']:.4f} s of idle inside its "
            f"spans, {100 * row['named_share']:.1f} % under a named span"
        )
        for name, seconds in row["spans"].items():
            print(
                f"  {name:<20} {seconds:9.4f} s "
                f"{100 * seconds / row['idle_s']:6.1f} %"
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())

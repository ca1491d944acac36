"""Per-step flight recorder: in-situ hot-path attribution for the trainer.

"Where did step N's wall-clock go, on this peer, in this run": every
accumulation boundary is recorded as a TREE of named host spans, and the
breakdown is published through the existing telemetry registry (events +
histograms + gauges), so the coordinator's swarm-health fold and
``runlog_summary --steps`` can rank peers by phase skew without attaching a
profiler to a volunteer's box.

A record runs from the start of one boundary to the start of the next. Its
spans (docs/observability.md "Step-phase flight recorder"):

- ``data_wait``    host input-pipeline stall (``next(batches)``)
- ``h2d``          host→device batch transfer (``put_batch`` on a mesh,
                   SwAV's crop upload)
- ``fwd_bwd``      what the host spends ENQUEUEING the jitted accumulate
                   (XLA runs async; the recorder never blocks on the device)
- ``drain``        where the program really waits for those accumulates:
                   the host read of the micro-batch counter at the top of a
                   global step. A peer's compute is ``fwd_bwd + drain``
- ``round_plan``   the shape of the round about to run: contribution cap,
                   ramp / gate weight and their trace events
- ``grad_flatten`` launching the device-side flatten/quantize program (or,
                   on the legacy path, the per-leaf device_get + host
                   flatten of the mean grads — the jit↔host seam crossing)
- ``ef_norm``      telemetry's own: LAUNCHING the scalar program and its
                   transfer for the ``opt.ef_residual_norm`` gauge (no host
                   sync: ``opt.step`` sets the gauge when it next runs)
- ``avg_wire``     the synchronous averaging round. Children:
                   ``d2h_stream`` (the EXPOSED remainder of the async
                   device→host gradient stream, ~0 when matchmaking hides
                   it) and, measured on the DHT loop's thread by the
                   averager, ``matchmaking`` (entering the round → group
                   formed, the wait for the partner included) and
                   ``allreduce`` (group formed → result). ``allreduce`` is
                   cut two ways (``averaging/allreduce.py``): its STAGES
                   ``ar_resolve`` / ``ar_prepare`` / ``ar_scatter`` /
                   ``ar_gather`` (child ``ar_straggler``) / ``ar_finish``
                   are the round's coroutine end to end and tile it; its
                   KINDS ``ar_encode`` / ``ar_decode`` / ``ar_reduce`` /
                   ``ar_copy`` / ``ar_frame`` are folded sums of what the
                   loop thread did inside it and overlap the stages
                   (``allreduce`` − Σ kinds: the loop thread waiting);
                   ``ar_partner_lag`` is how late the partner's first part
                   landed. ``ar_loop_cpu_s`` on the record is the loop
                   thread's CPU time over ``allreduce``,
                   ``ar_attached_chunks`` the chunk payloads its frames
                   carried by reference
- ``opt_apply``    optimizer apply + NaN guard; children ``backup_wait``
                   (for the end of a backup's read of the state the apply
                   donates) and ``h2d_result`` (the averaged flat buffer)
- ``backup_launch`` the on-thread part of the state backup: one host sync
                   on the apply program + a thread start
- ``acc_reset``    the fresh gradient accumulator after an apply (one small
                   eager program per leaf)
- ``collab``       progress-tracker reads/reports (DHT overhead)
- ``post_step``    the tail of a global step; children ``loss_sync`` (the
                   one host read of the loss), ``publish`` (signed metrics
                   to the DHT), ``log`` (log lines, train log, checkpoint)

Span names are open — instrumented code may record others — but the
canonical ones are what the cross-peer skew views key on. Spans NEST: a
span opened inside another is its child. ``phases`` in the record is each
name's SELF time (its duration minus what its children on this thread
cover), so it is disjoint by construction and Σ phases + ``untimed_s`` =
wall; ``spans`` carries the tree itself, ``[name, parent, t0_s, t1_s]`` with
offsets from the record's start (a run of more than 64 spans is folded:
repeated leaf spans of one name under one parent become ``[name, parent,
first t0_s, last t1_s, count, total_s]``). Spans ATTACHED from another
thread's clock readings (``matchmaking`` / ``allreduce`` / ``ar_*``,
``backup_transfer``; ``attach`` is the one way in, and takes a folded span
whole) are in ``spans`` only: they split their parent, they are not this
thread's time.

HOST COUNTERS ride on every record, read on the record's own thread at its
start and end: ``cpu_s`` (the thread's CPU seconds over the record), ``sys_s``
(those of them in the kernel), ``minflt`` / ``majflt`` (page faults) and
``nivcsw`` (involuntary context switches: the thread was preempted). On a
scripted timeline they read 0.

The HOLD RECORD (docs/observability.md "Hold record"): a span that runs for
longer than ``max(HOLD_MIN_S, HOLD_FACTOR x the ninth decile of its name's
last closed durations)`` is HELD, once a global step has run behind the set-up
record. The rule is applied when a span closes (so a hold is never missed) and, every ~100 ms, by ONE watcher thread a process to
the innermost OPEN span of each live record — which, when the rule holds,
samples the operating system's view of the blocked thread WHILE it is
blocked: scheduler state, kernel wait channel, system call, the Python call
site, which other threads of the process were busy, the machine's pressure.
A hold leaves an entry in the record's ``holds`` and ONE ``held:`` line at
INFO, telemetry on or off.

The SET-UP RECORD (``setup_record`` / ``lap`` / ``close_setup``) is the same
machinery pointed at a peer's start: ONE record a peer, from the role's
entry to the end of its first global step, whose spans are LAPS (each runs
from the end of the one before it, so they tile the start) and whose
``first_call.<program>`` children are made from JAX's own compile events
(docs/observability.md "Set-up record"). It closes with one ``set-up:``
line on the role's logger, telemetry on or off.

Design rules, mirroring ``registry.py``:

- **Timing is always on, publishing is not.** ``StepRecorder.step`` always
  times into its in-memory ring — a handful of clock reads and dict writes
  per boundary against hundreds of milliseconds of work — so an ordinary
  run can say which span of a slow global step stalled (one INFO line).
  Events, histograms, gauges and the JSONL mirror stay behind
  ``--telemetry.enabled``.
- **The recorder syncs nothing.** No ``block_until_ready`` is added for a
  timer's sake: a span measures what the host did, and the device's time is
  read off the profiler's device planes.
- **On the profiler's clock.** Each span also enters a
  ``jax.profiler.TraceAnnotation("dedloc/<name>")`` and each record a
  ``StepTraceAnnotation("dedloc/boundary", step_num=<boundary>)`` (only
  when jax is already imported — the simulator builds recorders without
  it), so any profiler session that covers the run holds the host spans in
  the same xplane as the device's programs (``telemetry/profile.py``).
- **FakeClock-compatible.** All timing uses the registry's monotonic
  clock (``registry.monotonic_clock``), which advances with the FakeClock
  offset — fault-injection tests produce deterministic span durations.
- **One event per phase plus one summary.** With telemetry on, each
  finished record emits a ``step.phase`` event per phase (self time) and one
  ``step.record`` event carrying the full breakdown (wall, samples and
  running totals, per-phase seconds, spans, untimed residual, dominant
  phase, online MFU); each phase also feeds the ``step.phase.<name>``
  histogram so metrics-bus snapshots carry ``step.phase.<name>.mean`` for
  the coordinator's swarm-health fold.
"""
from __future__ import annotations

import contextvars
import os
import re
import resource
import statistics
import sys
import threading
import weakref
from collections import deque
from contextlib import contextmanager
from typing import Any, Deque, Dict, Iterator, List, Optional

from dedloc_tpu.telemetry import registry
from dedloc_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# the canonical span names, in pipeline order — the cross-peer views key on
# these (tools/runlog_summary.py keeps a deliberate copy, _CANONICAL_PHASES,
# because the tool is stdlib-only; keep the two in sync)
PHASES = (
    "data_wait", "h2d", "fwd_bwd", "drain", "round_plan", "grad_flatten",
    "ef_norm", "avg_wire",
    "d2h_stream", "opt_apply", "backup_wait", "h2d_result", "backup_launch",
    "acc_reset", "collab", "post_step", "loss_sync", "publish", "log",
)

# a record holding more spans than this folds its repeated leaf spans
MAX_SPANS = 64

# bf16 peak TFLOP/s per chip by PJRT device_kind substring (Google Cloud
# TPU documentation, per-generation system pages) — THE table: bench.py and
# the tools import it from here.
TPU_PEAK_TFLOPS = (
    ("v5 lite", 197.0),  # v5e
    ("v5e", 197.0),
    ("v5p", 459.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 46.0),
    ("v6 lite", 918.0),  # trillium
)


def chip_peak_tflops() -> float:
    """Peak bf16 TFLOP/s of device 0. A non-TPU backend returns 0.0 (no
    utilization is reported there); a TPU that is not in the table raises —
    a utilization against a guessed peak is worse than none."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        return 0.0
    kind = device.device_kind.lower()
    for sub, peak in TPU_PEAK_TFLOPS:
        if sub in kind:
            return peak
    raise ValueError(
        f"no peak TFLOP/s on record for TPU device_kind "
        f"{device.device_kind!r}; add it to telemetry.steps.TPU_PEAK_TFLOPS "
        "with its source"
    )


def albert_tflops_per_sample(cfg, seq: int, max_pred: int) -> float:
    """Analytic MODEL TFLOPs for one ALBERT fwd+bwd sample — the same
    matmul-only formula as bench.py's ``albert_train_flops_per_sample``
    (remat recompute excluded by convention), so the recorder's in-situ MFU
    gauge is directly comparable to bench.py's ``mfu`` field."""
    h, i, s = cfg.hidden_size, cfg.intermediate_size, seq
    e, v = cfg.embedding_size, cfg.vocab_size
    per_token_layer = 8 * h * h + 4 * h * s + 4 * h * i
    fwd = cfg.num_hidden_layers * per_token_layer * s
    fwd += 2 * e * h * s
    fwd += max_pred * 2 * (h * e + e * v)
    fwd += 2 * h * 2
    return 3.0 * fwd / 1e12


class Span:
    """One timed region: a context manager that, inside a live record, is a
    node of its span tree, and outside one just times (``dur_s`` is always
    there for the call site — ``CollaborativeOptimizer.seam_ms`` reads it)."""

    __slots__ = ("name", "t0", "t1", "children_s", "leaf", "cpu_s",
                 "excused_s", "hold", "_c0", "_ctx", "_clock", "_annotation")

    def __init__(self, name: str, ctx: "Optional[_StepContext]") -> None:
        self.name = name
        self.t0 = self.t1 = 0.0
        self.children_s = 0.0  # what this thread's child spans cover
        self.leaf = True
        # the thread's CPU seconds inside the span (None: never entered)
        self.cpu_s: Optional[float] = None
        self.excused_s = 0.0  # the excess its children's holds reported
        self.hold: "Optional[_Hold]" = None  # the watcher's samples, if held
        self._c0 = 0.0
        self._ctx = ctx
        self._clock = ctx._clock if ctx is not None else registry.monotonic_clock
        self._annotation = None

    @property
    def dur_s(self) -> float:
        return max(0.0, self.t1 - self.t0)

    def elapsed(self) -> float:
        """Seconds since the span was entered, while it is open."""
        return max(0.0, self._clock() - self.t0)

    def __enter__(self) -> "Span":
        self._annotation = registry.trace_annotation(self.name)
        if self._annotation is not None:
            self._annotation.__enter__()
        if self._ctx is None:
            self.t0 = self._clock()
            return self
        self._c0 = registry.thread_cpu_clock()
        # stamped BEFORE it is on the stack: the watcher reads the top's t0
        self.t0 = self._clock()
        self._ctx._stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = self._clock()
        if self._ctx is not None:
            self.cpu_s = registry.thread_cpu_clock() - self._c0
            self._ctx._close(self)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)


class _StepContext:
    """The live record: the stack of open spans, the closed ones, the
    per-name self times, plus free-form attrs (``ctx.attrs["stepped"] =
    True``) merged into the final record."""

    __slots__ = ("phases", "totals", "attached", "spans", "holds", "attrs",
                 "step", "boundary", "samples", "tid", "ident", "_clock",
                 "_start", "_stack", "_folded", "_recorder", "_steady",
                 "_judged", "_counters", "_attached_at", "_watched")

    def __init__(self, step: Optional[int], boundary: int, samples: int,
                 clock, recorder: "Optional[StepRecorder]" = None) -> None:
        self.phases: Dict[str, float] = {}  # name -> SELF seconds
        self.totals: Dict[str, float] = {}  # name -> seconds, children in
        self.holds: List[Dict[str, Any]] = []  # the spans the rule held
        # name -> seconds of the spans ATTACHED from other threads' readings
        self.attached: Dict[str, float] = {}
        # closed spans: [name, parent, t0, t1, leaf] or, folded,
        # [name, parent, first t0, last t1, True, count, total]
        self.spans: List[list] = []
        self.attrs: Dict[str, Any] = {}
        self.step = step
        self.boundary = boundary
        self.samples = int(samples)
        self._clock = clock
        self._start = clock()
        self._stack: List[Span] = []
        self._folded = False  # the record overflowed MAX_SPANS once
        # the record's own thread, for the watcher: /proc's id and Python's
        self.tid = threading.get_native_id()
        self.ident = threading.get_ident()
        self._recorder = recorder  # whose hold rule its spans are put to
        # the rule waits for the set-up record to close: until then the
        # spans hold compilation and say nothing of what is usual; and the
        # first global step behind it is not JUDGED (``StepRecorder.step``)
        self._steady = _SETUP.get() is None
        self._judged = False
        self._counters = _thread_counters()
        self._attached_at: List[tuple] = []  # (name, t0, t1), this clock
        # (entry of ``holds``, what the watcher saw of it): merged when the
        # record ends
        self._watched: List[tuple] = []

    def counters(self) -> Dict[str, Any]:
        """The thread's CPU seconds (``sys_s``: those in the kernel), page
        faults and involuntary context switches since the record began."""
        return {
            key: after - before for key, before, after in zip(
                _COUNTER_KEYS, self._counters, _thread_counters(),
            )
        }

    def elapsed(self) -> float:
        """Seconds since the record began, on the record's clock."""
        return max(0.0, self._clock() - self._start)

    def total(self, name: str) -> float:
        """Seconds the closed spans called ``name`` took so far, their
        children included."""
        return self.totals.get(name, 0.0)

    def phase(self, name: str) -> Span:
        """A span called ``name``, child of whichever span is open now."""
        return Span(name, self)

    def add(self, name: str, seconds: float) -> None:
        """A span of ``seconds`` that ends now, for call sites that hold a
        duration and not a region (the exposed part of the D2H stream)."""
        span = Span(name, self)
        span.t1 = self._clock()
        span.t0 = span.t1 - max(0.0, seconds)
        self._close(span, pop=False)

    def attach(
        self, name: str, t0: float, t1: float, parent: Optional[str] = None,
        count: Optional[int] = None, total_s: Optional[float] = None,
    ) -> None:
        """A span read off this clock by ANOTHER thread (the averager's
        ``matchmaking`` / ``allreduce`` / ``ar_*`` on the DHT loop, the
        backup thread's ``backup_transfer``), as a child of ``parent`` (the
        open span when left out). With ``count`` and ``total_s`` it is a
        FOLDED entry: ``count`` sections between ``t0`` and ``t1`` that took
        ``total_s`` together. It splits its parent for the reader; it is not
        this thread's time, so neither ``phases`` nor the parent's self time
        change."""
        if parent is None and self._stack:
            parent = self._stack[-1].name
        seconds = max(0.0, t1 - t0) if total_s is None else total_s
        self.attached[name] = self.attached.get(name, 0.0) + seconds
        self._attached_at.append((name, t0, t1))
        self._record(
            name, parent, t0, t1, True,
            *(() if count is None else (int(count), float(seconds))),
        )

    # ------------------------------------------------------------- internal

    def _close(self, span: Span, pop: bool = True) -> None:
        if pop:
            self._stack.pop()
        parent = self._stack[-1] if self._stack else None
        dur = span.dur_s
        if self._recorder is not None:
            self._recorder._span_closed(self, span, parent, dur)
        if parent is not None:
            parent.children_s += dur
            parent.excused_s += span.excused_s
            parent.leaf = False
        self.phases[span.name] = (
            self.phases.get(span.name, 0.0) + max(0.0, dur - span.children_s)
        )
        self.totals[span.name] = self.totals.get(span.name, 0.0) + dur
        self._record(
            span.name, parent.name if parent is not None else None,
            span.t0, span.t1, span.leaf,
        )

    def _record(self, name, parent, t0, t1, leaf, *folded) -> None:
        self.spans.append([
            name, parent, t0 - self._start, max(t0, t1) - self._start, leaf,
            *folded,
        ])
        if len(self.spans) > MAX_SPANS:
            self._fold()

    def _fold(self) -> None:
        """Merge the leaf spans of one name under one parent into a single
        entry with a count and a total: a boundary of many micro-batches
        stays a bounded record."""
        self._folded = True
        folded: Dict[tuple, list] = {}
        out: List[list] = []
        for span in self.spans:
            if not span[4]:
                out.append(span)
                continue
            key = (span[0], span[1])
            count, total = (
                (span[5], span[6]) if len(span) > 5 else (1, span[3] - span[2])
            )
            into = folded.get(key)
            if into is None:
                folded[key] = into = [*span[:5], 0, 0.0]
                out.append(into)
            into[2] = min(into[2], span[2])
            into[3] = max(into[3], span[3])
            into[5] += count
            into[6] += total
        self.spans = [
            s[:5] if len(s) > 5 and s[5] == 1 and s[6] == s[3] - s[2] else s
            for s in out
        ]

    def finished_spans(self) -> List[list]:
        """``[name, parent, t0_s, t1_s]`` (``+ [count, total_s]`` when
        folded), microsecond precision, in closing order."""
        if self._folded:
            self._fold()  # a record that folded once ends folded throughout
        return [
            [s[0], s[1], round(s[2], 6), round(s[3], 6)]
            + ([s[5], round(s[6], 6)] if len(s) > 5 else [])
            for s in self.spans
        ]


# the live record (per-thread / per-task): instrumented code that does not
# hold the recorder — the collaborative optimizer, the roles' step
# functions — opens its spans through this
_CURRENT: contextvars.ContextVar[Optional[_StepContext]] = (
    contextvars.ContextVar("dedloc_step", default=None)
)


def current() -> Optional[_StepContext]:
    return _CURRENT.get()


def phase(name: str) -> Span:
    """A span in the live record, or a bare timer when no step is being
    recorded (one contextvar load more than ``Span`` itself)."""
    return Span(name, _CURRENT.get())


def add(name: str, seconds: float) -> None:
    """Credit pre-measured seconds to the live record (no-op when none is
    live) — for call sites that already hold a duration."""
    ctx = _CURRENT.get()
    if ctx is not None:
        ctx.add(name, seconds)


def attach(name: str, t0: float, t1: float, **where) -> None:
    """``_StepContext.attach`` on the live record (no-op when none is)."""
    ctx = _CURRENT.get()
    if ctx is not None:
        ctx.attach(name, t0, t1, **where)


# ------------------------------------------------------------------ set-up
#
# The start of a peer, role entry -> end of its first global step, as ONE
# record on the machinery above. Its spans are LAPS: ``lap(name)`` closes a
# span that began where the lap before it ended, so a role marks the end of
# each phase with one line and the laps tile the start by construction.
# While the record is open two ``jax.monitoring`` listeners file every
# ``/jax/core/compile/*`` event of the record's thread under the lap it fell
# in; they are removed when the record closes.

# /jax/core/compile/<event> -> the key its seconds are summed under
_COMPILE_KINDS = {
    "jaxpr_trace_duration": "trace_s",
    "jaxpr_to_mlir_module_duration": "lower_s",
    "backend_compile_duration": "backend_s",  # a compile, or the cache's load
}
# a program whose first call (start of its trace -> end of its compile or
# cache load) took at least this long is a ``first_call.<program>`` span; a
# shorter one (SwAV's eager init runs hundreds) stays in its lap's sums
FIRST_CALL_MIN_S = 0.05
# JAX emits ``jaxpr_trace_duration`` around a CACHED trace too (microseconds):
# only a longer event is a trace (the benchmark's
# ``reducers/setup_compile.py`` counts by this same constant)
TRACE_MIN_S = 1e-3
# the line names this many programs, the costliest first calls
TRACED_PROGRAMS = 8

_SETUP: contextvars.ContextVar[Optional["_SetupContext"]] = (
    contextvars.ContextVar("dedloc_setup", default=None)
)


def _program_name(fun_name: Any) -> str:
    """``accumulate_step`` from the trace event's ``accumulate_step`` and from
    the lowering's and the backend's ``jit(accumulate_step)``."""
    name = str(fun_name).removeprefix("jit(").removesuffix(")")
    return re.sub(r"\s+", "_", name) or "?"


class _SetupContext(_StepContext):
    """The live set-up record: a ``_StepContext`` whose spans are laps, plus
    what the compile events of its thread add up to."""

    __slots__ = ("compile", "traces", "cache", "complete", "_log", "_lap",
                 "_pending", "_listeners")

    def __init__(self, log, clock) -> None:
        super().__init__(None, 0, 0, clock)
        # lap name -> {"trace_s", "lower_s", "backend_s", "programs"}: the
        # OUTERMOST compile events inside it (a trace nested in a trace is
        # its parent's time), so the sums are wall seconds of the lap
        self.compile: Dict[str, Dict[str, float]] = {}
        self.traces: Dict[str, int] = {}  # program -> outermost traces
        self.cache = {"hits": 0, "misses": 0}  # the persistent cache's
        self.complete = False  # closed at the end of a first global step
        self._log = log
        self._lap = self._start
        self._pending: List[tuple] = []  # (kind, program, t0, t1), by t1
        self._listeners = self._listen()

    def _listen(self) -> tuple:
        import jax

        def on_duration(event: str, duration: float, **kw) -> None:
            kind = _COMPILE_KINDS.get(event.rpartition("/")[2])
            if kind is None or _SETUP.get() is not self:
                return  # another thread's (another peer's) compile
            now = self._clock()
            self._pending.append(
                (kind, _program_name(kw.get("fun_name", "")),
                 now - float(duration), now)
            )

        def on_event(event: str, **kw) -> None:
            if _SETUP.get() is not self:
                return
            if event == "/jax/compilation_cache/cache_hits":
                self.cache["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache["misses"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return on_duration, on_event

    def _unlisten(self) -> None:
        import jax

        on_duration, on_event = self._listeners
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)

    def lap(self, name: str) -> None:
        """Close a span called ``name`` from the end of the lap before it to
        now, with the first calls that fell inside it as its children."""
        span = Span(name, self)
        span.t0, span.t1 = self._lap, self._clock()
        self._stack.append(span)
        self._file_compiles(span)
        self._close(span)
        self._lap = span.t1

    def _file_compiles(self, span: Span) -> None:
        pending, self._pending = self._pending, []
        # events arrive as they END: one that starts before the start of
        # those before it contains them (an inner jit traced inside an outer
        # trace, an eager compile inside a trace) — keep the outermost
        roots: List[tuple] = []
        for item in pending:
            while roots and roots[-1][2] >= item[2]:
                roots.pop()
            roots.append(item)
        if not roots:
            return
        sums = self.compile.setdefault(span.name, {
            "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "programs": 0,
        })
        # one first call: the outermost events of one program in a row, up
        # to the backend's (a trace, its lowering, its compile or load)
        calls: List[list] = []
        call = None
        for kind, program, t0, t1 in roots:
            sums[kind] += t1 - t0
            if kind == "trace_s" and t1 - t0 >= TRACE_MIN_S:
                self.traces[program] = self.traces.get(program, 0) + 1
            if call is None or call[0] != program:
                call = [program, t0, t1]
                calls.append(call)
            call[2] = t1
            if kind == "backend_s":
                sums["programs"] += 1
                call = None
        for program, t0, t1 in calls:
            t0, t1 = max(t0, span.t0), min(t1, span.t1)
            if t1 - t0 >= FIRST_CALL_MIN_S:
                child = Span(f"first_call.{program}", self)
                child.t0, child.t1 = t0, t1
                self._close(child, pop=False)

    # ---------------------------------------------------------- the close

    def first_calls(self) -> Dict[str, float]:
        """program -> seconds inside its ``first_call`` spans."""
        return {
            name.removeprefix("first_call."): seconds
            for name, seconds in self.totals.items()
            if name.startswith("first_call.")
        }

    def traced(self, first_calls: Dict[str, float]) -> Dict[str, int]:
        """program -> times traced, for the costliest first calls."""
        top = sorted(first_calls, key=first_calls.get, reverse=True)
        return {p: self.traces.get(p, 0) for p in top[:TRACED_PROGRAMS]}

    def compiled(self) -> Dict[str, float]:
        """The laps' compile sums, added up."""
        return {
            key: sum(c[key] for c in self.compile.values())
            for key in ("trace_s", "lower_s", "backend_s", "programs")
        }

    def line(self, total: float) -> str:
        """The ``set-up:`` line: ``key=value`` in seconds, the laps in the
        order they first closed, then what the compile events add up to."""
        first_calls = self.first_calls()
        laps = " ".join(
            f"{name}={seconds:.3f}" for name, seconds in self.totals.items()
            if not name.startswith("first_call.")
        )
        compiled = self.compiled()
        return (
            f"set-up: total={total:.3f} complete={int(self.complete)} {laps}"
            f" | first_calls={sum(first_calls.values()):.3f}"
            f" trace={compiled['trace_s']:.3f} lower={compiled['lower_s']:.3f}"
            f" backend={compiled['backend_s']:.3f}"
            f" programs={compiled['programs']}"
            f" hits={self.cache['hits']} misses={self.cache['misses']}"
            + "".join(
                f" traces[{program}]={count}"
                for program, count in self.traced(first_calls).items()
            )
        )

    def close(
        self, telemetry: Optional[registry.Telemetry] = None,
        complete: bool = False,
    ) -> None:
        """Remove the listeners, log the line, publish (telemetry on).
        ``complete``: at the end of a first global step."""
        self.complete = complete
        self._unlisten()
        _SETUP.set(None)
        if self._pending or self._clock() - self._lap >= 1e-3:
            self.lap("rest")  # a record abandoned between two laps
        total = self._lap - self._start
        self._log.info(self.line(total))
        tele = registry.resolve(telemetry)
        if tele is None:
            return
        tele.event(
            "setup.record", dur_s=total, complete=self.complete,
            phases=dict(self.phases),
            untimed_s=max(0.0, total - sum(self.phases.values())),
            spans=self.finished_spans(), compile=self.compile,
            **self.compiled(), traces=self.traced(self.first_calls()),
            cache_hits=self.cache["hits"], cache_misses=self.cache["misses"],
            **self.attrs,
        )


@contextmanager
def setup_record(log) -> Iterator[_SetupContext]:
    """Open this thread's set-up record around a role (``log``: the role's
    logger, which gets the ``set-up:`` line). ``close_setup`` closes it at
    the end of the first global step; a role that ends or raises before one
    closes it here, ``complete=0``."""
    ctx = _SetupContext(log, registry.monotonic_clock)
    _SETUP.set(ctx)
    try:
        yield ctx
    finally:
        if _SETUP.get() is ctx:
            ctx.close()


def current_setup() -> Optional[_SetupContext]:
    return _SETUP.get()


def lap(name: str) -> None:
    """``_SetupContext.lap`` on this thread's set-up record (no-op when none
    is open: every start after the first global step)."""
    ctx = _SETUP.get()
    if ctx is not None:
        ctx.lap(name)


def close_setup(telemetry: Optional[registry.Telemetry] = None) -> None:
    """The end of the first global step: close this thread's set-up record
    (no-op when none is open)."""
    ctx = _SETUP.get()
    if ctx is not None:
        ctx.close(telemetry, complete=True)


def train_log_row(rec: _StepContext) -> Dict[str, Any]:
    """THIS boundary's values for a role's ``--training.train_log_path``
    line and its ``LocalMetrics``, read off the live record at the point of
    publishing (inside ``post_step``): the boundary's wall so far, its data
    stall, the wall of its ``opt.step`` call, every span's total, and the
    running totals ``opt.step`` stamped."""
    row: Dict[str, Any] = {
        "samples": rec.samples,
        "boundary_ms": rec.elapsed() * 1e3,
        "data_wait_ms": rec.total("data_wait") * 1e3,
        "allreduce_ms": rec.attrs.get("opt_step_s", 0.0) * 1e3,
        "spans_ms": {k: round(v * 1e3, 3) for k, v in rec.totals.items()},
    }
    for key in ("samples_total", "boundaries_total", "global_steps_total"):
        if key in rec.attrs:
            row[key] = rec.attrs[key]
    return row


# ------------------------------------------------------------ the hold record
#
# What the operating system says about a thread of THIS process, read from
# /proc by the one watcher thread while a span overruns and once more when
# it has closed: never by the span's own thread. But for ``_thread_counters``
# (a record's start and end) nothing here runs until the hold rule
# (``StepRecorder``) has found a span held.

_TASKS = "/proc/self/task"
_PROC = os.path.isdir(_TASKS)
_TICK_S = 1.0 / os.sysconf("SC_CLK_TCK") if _PROC else 0.01
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)
_HERE = os.sep + "dedloc_tpu" + os.sep
# a hold's entry names this many busy threads / other Python threads / spans
# beside it
BUSY_THREADS = 5
OTHER_THREADS = 8
BESIDE_SPANS = 6
# the line says so when the watcher's reads of /proc took this long
SLOW_READ_S = 0.05
# ... and when the watcher went this long without a look while the span was
# open (its period is a tenth of a second): it was kept out itself
WATCHER_AWAY_S = 0.25


# a record's host counters, in ``_thread_counters``' order
_COUNTER_KEYS = ("cpu_s", "sys_s", "minflt", "majflt", "nivcsw")


def _thread_counters() -> tuple:
    """(CPU seconds, those of them in the kernel, minor faults, major
    faults, involuntary context switches) of the CALLING thread so far;
    zeros on a scripted timeline, as ``registry.thread_cpu_clock`` reads
    there."""
    if _RUSAGE_THREAD is None or registry.scripted_time():
        return (0.0, 0.0, 0, 0, 0)
    usage = resource.getrusage(_RUSAGE_THREAD)
    return (
        registry.thread_cpu_clock(), usage.ru_stime, usage.ru_minflt,
        usage.ru_majflt, usage.ru_nivcsw,
    )


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read()
    except OSError:  # the thread ended, or this kernel lacks the file
        return ""


def _task_stat(tid) -> Optional[tuple]:
    """(comm, scheduler state, CPU seconds, those of them in the kernel) of
    one thread of this process."""
    head, _, tail = _read(f"{_TASKS}/{tid}/stat").rpartition(") ")
    fields = tail.split()
    if len(fields) < 13:
        return None
    user_s, sys_s = int(fields[11]) * _TICK_S, int(fields[12]) * _TICK_S
    return head.partition("(")[2], fields[0], user_s + sys_s, sys_s


def _threads() -> Dict[int, tuple]:
    """tid -> (name, CPU seconds, those in the kernel) of every thread of
    this process; a Python thread goes by its ``threading`` name, another
    by the kernel's comm."""
    names = {t.native_id: t.name for t in threading.enumerate()}
    try:
        tids = os.listdir(_TASKS)
    except OSError:
        return {}
    found = {}
    for tid in tids:
        stat = _task_stat(tid)
        if stat is not None:
            found[int(tid)] = (names.get(int(tid)) or stat[0], *stat[2:])
    return found


def _machine() -> Dict[str, float]:
    """The machine's side at one instant: the ``some`` totals of
    /proc/pressure in seconds (tasks stalled for want of CPU, memory, I/O),
    this process's page faults and — the one that is no running total — the
    threads runnable now."""
    now: Dict[str, float] = {}
    for kind in ("cpu", "memory", "io"):
        match = re.search(r"some .*total=(\d+)", _read(f"/proc/pressure/{kind}"))
        if match:
            now[f"{kind}_s"] = int(match.group(1)) / 1e6
    load = _read("/proc/loadavg").split()
    if len(load) > 3:
        now["runnable"] = float(load[3].partition("/")[0])
    usage = resource.getrusage(resource.RUSAGE_SELF)
    now["minflt"], now["majflt"] = usage.ru_minflt, usage.ru_majflt
    return now


def _where(frame) -> str:
    code = frame.f_code
    return f"{os.path.basename(code.co_filename)}:{frame.f_lineno} {code.co_name}"


def _site(frame):
    """The innermost frame inside this package (the call site: which jitted
    call, which ``device_get``), else the innermost frame."""
    innermost = frame
    while frame is not None:
        if _HERE in frame.f_code.co_filename:
            return frame
        frame = frame.f_back
    return innermost


def _frames(frame) -> List[str]:
    """Where a thread is in Python, from its current frame: its innermost
    three frames and, when it is none of them, its call site."""
    found: List[str] = []
    site = _site(frame)
    while frame is not None and len(found) < 3:
        found.append(_where(frame))
        if frame is site:
            site = None
        frame = frame.f_back
    if site is not None:
        found.append(_where(site))
    return found


class _Hold:
    """What the watcher saw of ONE span while it overran. Every read of /proc
    is the WATCHER's, made outside ``lock`` (which only guards the merge): the
    span's own thread marks the hold closed at the span's close (``close``)
    and wakes the watcher, which reads every thread's clock and the
    machine's counters once more (``finish``). A record that ends before
    that reading has waits for it (``seen``), FINISH_WAIT_S at most."""

    # past this many samples the watcher looks once a COARSE_S
    FINE_SAMPLES = 200
    COARSE_S = 1.0
    FINISH_WAIT_S = 0.1

    __slots__ = ("lock", "closed", "done", "tid", "samples", "state", "wchan",
                 "syscall", "frames", "others", "threads", "machine", "busy",
                 "psi", "read_s", "_watcher", "_since", "_closed_at", "_next")

    def __init__(self, tid: int, ident: int) -> None:
        self.lock = threading.Lock()
        self.closed = False
        self.done = threading.Event()  # the closing reading is in
        self.tid = tid
        self.samples = 0
        self.state: Dict[str, int] = {}
        self.wchan: Dict[str, int] = {}
        self.syscall: Dict[str, int] = {}
        # Python's view, once and first (it asks the kernel nothing): where
        # the held thread is, and where every other Python thread is while
        # it is held (the backup thread in its transfer, the DHT loop in a
        # round)
        frames = sys._current_frames()
        skip = (ident, threading.get_ident())  # made on the watcher's thread
        self.frames = _frames(frames.get(ident))
        self.others = [
            [t.name, _where(_site(frames[t.ident]))]
            for t in threading.enumerate()
            if t.ident not in skip and t.ident in frames
        ][:OTHER_THREADS]
        # every thread's CPU and the machine's counters where the watcher
        # came (read by the first ``sample``, after the held thread itself)
        # and what they had moved by when the span closed (``finish``)
        self.threads: Optional[Dict[int, tuple]] = None
        self.machine: Dict[str, float] = {}
        self.busy: Optional[List[list]] = None
        self.psi: Dict[str, Any] = {}
        self.read_s = 0.0  # the longest one round of /proc reads took
        self._watcher = threading.get_native_id()
        self._since = self._closed_at = self._next = registry.monotonic_clock()

    def sample(self) -> bool:
        """One look at the held thread; False once its span has closed."""
        now = registry.monotonic_clock()
        if self.closed or now < self._next:
            return not self.closed
        task = f"{_TASKS}/{self.tid}"
        stat = _task_stat(self.tid)
        seen = stat is not None and (
            (self.state, stat[1]),
            (self.wchan, _read(f"{task}/wchan").strip()),
            # the call's number; "running" on a CPU, -1 blocked outside a
            # call (a page fault)
            (self.syscall, _read(f"{task}/syscall").partition(" ")[0].strip()),
        )
        took = registry.monotonic_clock() - now
        with self.lock:
            if self.closed:
                return False
            self.read_s = max(self.read_s, took)
            if seen:
                self.samples += 1
                for counts, key in seen:
                    if key and key != "0":
                        counts[key] = counts.get(key, 0) + 1
            self._next = now + (
                0.0 if self.samples < self.FINE_SAMPLES else self.COARSE_S
            )
            if self.threads is not None:
                return True
        threads, machine = _threads(), _machine()
        took = registry.monotonic_clock() - now
        with self.lock:
            if not self.closed:
                self.threads, self.machine = threads, machine
                self.read_s = max(self.read_s, took)
        return not self.closed

    def close(self) -> None:
        """The span's own thread, at the span's close: no read of its own."""
        with self.lock:
            self.closed = True
        self._closed_at = registry.monotonic_clock()
        _WATCHER.closing(self)

    def finish(self) -> None:
        """The watcher's thread, woken by ``close``: the threads that used
        the most CPU since it came, and the machine's deltas."""
        if self.threads is not None:
            now = registry.monotonic_clock()
            busy = []
            for tid, (name, cpu_s, sys_s) in _threads().items():
                if tid not in (self.tid, self._watcher):
                    _, cpu0_s, sys0_s = self.threads.get(tid, (name, 0.0, 0.0))
                    busy.append((cpu_s - cpu0_s, sys_s - sys0_s, name))
            busy.sort(reverse=True)
            after = _machine()
            psi = {
                key: round(after[key] - before, 6)
                for key, before in self.machine.items() if key in after
            }
            if "runnable" in psi:
                psi["runnable"] = [self.machine["runnable"], after["runnable"]]
            took = registry.monotonic_clock() - now
            with self.lock:
                self.read_s = max(self.read_s, took)
                self.psi = psi
                self.busy = [
                    [name, round(cpu_s, 3), round(sys_s, 3)]
                    for cpu_s, sys_s, name in busy[:BUSY_THREADS] if cpu_s > 0
                ]
        self.done.set()

    def seen(self) -> Dict[str, Any]:
        """What was seen, for the record's entry (the span's own thread, when
        the record ends): the samples' counts and the closing reading —
        ``busy_threads`` None where the watcher never got that far."""
        self.done.wait(self.FINISH_WAIT_S)
        return {
            "samples": self.samples,
            "watched_s": round(self._closed_at - self._since, 6),
            "read_s": round(self.read_s, 6),
            "state": self.state, "wchan": self.wchan,
            "syscall": self.syscall, "frames": self.frames,
            "others": self.others, "busy_threads": self.busy, "psi": self.psi,
        }


def hold_line(hold: Dict[str, Any]) -> str:
    """The ``held:`` line of one entry of a record's ``holds``
    (docs/observability.md "Hold record" has its grammar)."""

    def counts(by_key: Dict[str, int], top: int = 2) -> str:
        return " ".join(
            f"{key} {by_key[key]}/{hold['samples']}"
            for key in sorted(by_key, key=by_key.get, reverse=True)[:top]
        )

    parts = [
        f"held: span={hold['span']} {hold['held_s']:.3f} s "
        f"(usual {hold['usual_s']:.3f}) cpu {hold['cpu_s']:.3f}"
    ]
    if hold.get("sampled_in"):
        parts.append(f"sampled in {hold['sampled_in']}")
    if "frames" in hold:  # the watcher came while the span was open
        for key in ("state", "wchan", "syscall"):
            if hold[key]:
                parts.append(f"{key} {counts(hold[key])}")
        if hold["read_s"] >= SLOW_READ_S:
            parts.append(f"/proc answered in {hold['read_s']:.3f}")
        if hold["frames"]:
            parts.append("at " + " < ".join(hold["frames"]))
        if hold["busy_threads"]:
            parts.append("busy: " + ", ".join(
                f"{name} {cpu_s:.2f} s (sys {sys_s:.2f})"
                for name, cpu_s, sys_s in hold["busy_threads"]
            ))
        elif hold["busy_threads"] is None:
            parts.append("busy: unread")  # the watcher never got that far
        if hold["others"]:
            parts.append("others: " + ", ".join(
                f"{name} in {where}" for name, where in hold["others"]
            ))
        psi = hold["psi"]
        pressure = [
            f"{key.removesuffix('_s')} {psi[key]:.3f}"
            for key in ("cpu_s", "memory_s", "io_s") if key in psi
        ]
        if pressure:  # a kernel without /proc/pressure has none to give
            parts.append("psi " + " ".join(pressure) + (
                f" runnable {psi['runnable'][0]:.0f}>{psi['runnable'][1]:.0f}"
                if "runnable" in psi else ""
            ))
    else:
        parts.append(f"unsampled ({hold['note']})")
    if hold.get("watcher_away_s", 0.0) >= WATCHER_AWAY_S:
        parts.append(f"watcher kept out {hold['watcher_away_s']:.3f} s")
    if hold.get("beside"):
        parts.append("beside: " + ", ".join(
            f"{name} {a0:+.3f}..{a1:+.3f}" for name, a0, a1 in hold["beside"]
        ))
    return " ".join(parts)


class _Watcher:
    """ONE daemon thread a process, alive while a ``StepRecorder`` is: every
    ``WATCH_PERIOD_S`` it puts the hold rule to the innermost open span of
    each live record (``StepRecorder._look``) and, while one is held,
    samples it every ``SAMPLE_PERIOD_S``. On a scripted timeline it looks
    at nothing."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._recorders: "weakref.WeakSet[StepRecorder]" = weakref.WeakSet()
        self._thread: Optional[threading.Thread] = None
        self._wake = threading.Event()
        self._closing: Deque[_Hold] = deque()  # closed, not finished yet
        self.looked = 0.0  # when the last visit began (monotonic clock)
        self.gap = (0.0, 0.0)  # the last two looks WATCHER_AWAY_S apart

    def away(self, open_s: float) -> float:
        """The longest the watcher went WITHOUT a look inside the last
        ``open_s`` seconds: a period when all is well. More means it was
        kept out itself — by the interpreter lock (some thread kept it
        through a C call: no Python thread ran), by a read of /proc that did
        not return, or because the process was not run at all."""
        now = registry.monotonic_clock()
        since = now - open_s
        gap0, gap1 = self.gap
        closed = min(gap1, now) - max(gap0, since) if gap1 > since else 0.0
        return max(closed, now - max(self.looked, since), 0.0)

    def add(self, recorder: "StepRecorder") -> None:
        with self._lock:
            self._recorders.add(recorder)
            if self._thread is None and _PROC:
                self._thread = threading.Thread(
                    target=self._run, name="dedloc-hold-watcher", daemon=True,
                )
                self._thread.start()

    def closing(self, hold: _Hold) -> None:
        """A held span has closed (its own thread): the watcher is woken to
        take the hold's closing reading."""
        self._closing.append(hold)
        self._wake.set()

    def remove(self, recorder: "StepRecorder") -> None:
        """After the last recorder's, the thread has ended."""
        with self._lock:
            self._recorders.discard(recorder)
            thread = None if len(self._recorders) else self._thread
        if thread is not None:
            self._wake.set()
            thread.join(timeout=2.0)

    def _run(self) -> None:
        self.looked = registry.monotonic_clock()  # no gap before its start
        period = StepRecorder.WATCH_PERIOD_S
        while period is not None:
            self._wake.wait(period)
            self._wake.clear()
            # the visit's references die with its frame: a recorder nobody
            # closed is still collected, and the thread ends after it
            period = self._visit()

    def _visit(self) -> Optional[float]:
        """Look at every live record once; the seconds to sleep before the
        next look, None when the thread is to end."""
        before, self.looked = self.looked, registry.monotonic_clock()
        if self.looked - before >= WATCHER_AWAY_S:
            self.gap = (before, self.looked)
        with self._lock:
            recorders = list(self._recorders)
            if not recorders:
                self._thread = None
                return None
        period = min(r.WATCH_PERIOD_S for r in recorders)
        if registry.scripted_time():
            return period
        try:
            while self._closing:
                self._closing.popleft().finish()
            for recorder in recorders:
                if recorder._look():
                    period = min(period, recorder.SAMPLE_PERIOD_S)
        except Exception:  # noqa: BLE001 — the thread's boundary
            # the rule still runs at every span's close; the samples are
            # lost until the next recorder starts a watcher (INFO: a
            # WARNING is a failed step to whoever counts them)
            logger.info("hold watcher stopped", exc_info=True)
            with self._lock:
                self._thread = None
            return None
        return period


_WATCHER = _Watcher()


class StepRecorder:
    """Bounded ring of per-boundary records + an online MFU gauge + the
    hold rule + the slow-step notice.

    One recorder per trainer loop. ``model_tflops_per_sample`` and
    ``peak_tflops`` enable the MFU gauge (0 disables it — e.g. CPU smoke
    runs); throughput for the gauge is a ring-window mean (samples over
    recorded wall), so it tracks the same quantity the bench headline
    measures rather than a single noisy step. ``perf`` (a
    ``utils/perf.PerfStats``) receives every finished record's wall as
    ``boundary`` and its phases under their names — the operator's
    ``--training.log_perf_steps`` report, fed from the one timer there is.
    ``profile`` (``telemetry/profile.ProfileGate``) is told each boundary's
    index, and opens and closes the profiler window the operator asked for.
    """

    # a global step is slow when its wall exceeds this many times the
    # running median of the last SLOW_WINDOW global steps (at least
    # SLOW_MIN_STEPS of them: the first ones hold compilation)
    SLOW_FACTOR = 1.5
    SLOW_WINDOW = 32
    SLOW_MIN_STEPS = 4
    # a SPAN is held when it runs for longer than max(HOLD_MIN_S,
    # HOLD_FACTOR x the ninth decile of the last SLOW_WINDOW closed spans of
    # its name), its children's own holds taken out, once HOLD_MIN_SPANS of
    # its name have closed since the set-up record did; the first GLOBAL
    # STEP behind the set-up only shows what is usual
    HOLD_MIN_S = 0.5
    HOLD_FACTOR = 2.0
    HOLD_MIN_SPANS = 3
    # the watcher looks at the open spans this often, and samples a held one
    # this often
    WATCH_PERIOD_S = 0.1
    SAMPLE_PERIOD_S = 0.05

    def __init__(
        self,
        telemetry: Optional[registry.Telemetry] = None,
        model_tflops_per_sample: float = 0.0,
        peak_tflops: float = 0.0,
        ring: int = 256,
        mfu_window: int = 32,
        perf=None,
        profile=None,
    ) -> None:
        self.telemetry = telemetry
        self.model_tflops_per_sample = float(model_tflops_per_sample)
        self.peak_tflops = float(peak_tflops)
        self.records: Deque[Dict[str, Any]] = deque(maxlen=ring)
        self.mfu_window = int(mfu_window)
        self.perf = perf
        self.profile = profile
        self.boundaries = 0  # records begun: the next record's index
        # the global step being assembled (its records' wall, span totals
        # and attached spans' totals), and the last SLOW_WINDOW finished ones
        self._step_wall = 0.0
        self._step_totals: Dict[str, float] = {}
        self._step_attached: Dict[str, float] = {}
        self._step_holds: List[Dict[str, Any]] = []
        self._recent_steps: Deque[tuple] = deque(maxlen=self.SLOW_WINDOW)
        # span name -> its last SLOW_WINDOW closed durations (children in)
        self._durations: Dict[str, Deque[float]] = {}
        self._live: Optional[_StepContext] = None  # the watcher reads it
        # a whole global step has run behind the set-up: what happens once a
        # step (the first enqueue behind an apply that finds the runtime
        # full) has been seen once, and the rule begins
        self._learned = False
        _WATCHER.add(self)

    @contextmanager
    def step(
        self, step: Optional[int] = None, samples: int = 0
    ) -> Iterator[_StepContext]:
        """Record one accumulation boundary; yields the live record. The
        timing always runs; what is published (events, histograms, gauges)
        waits for a telemetry registry."""
        tele = registry.resolve(self.telemetry)
        boundary = self.boundaries
        self.boundaries += 1
        if self.profile is not None:
            self.profile.at_boundary(boundary)
        ctx = _StepContext(
            step, boundary, samples,
            tele.clock if tele is not None else registry.monotonic_clock,
            recorder=self,
        )
        annotation = registry.trace_annotation("boundary", step_num=boundary)
        if annotation is not None:
            annotation.__enter__()
        ctx._judged = self._learned and ctx._steady
        token = _CURRENT.set(ctx)
        self._live = ctx
        try:
            yield ctx
        finally:
            self._live = None
            _CURRENT.reset(token)
            wall = ctx.elapsed()
            if annotation is not None:
                annotation.__exit__(None, None, None)
            self._finish(tele, ctx, wall)

    def close(self) -> None:
        """End of the loop: a profiler window still open is closed, and the
        watcher lets go of this recorder (it ends with the last one)."""
        _WATCHER.remove(self)
        if self.profile is not None:
            self.profile.close()

    # -------------------------------------------------------- the hold rule

    def _usual(self, name: str) -> Optional[float]:
        """What the closed spans called ``name`` usually stay under — the
        ninth decile of their durations, so that BOTH lengths of a span
        that has two are usual (Ouro's ``fwd_bwd`` enqueues in 2 ms when the
        runtime has room and in 540 ms when it has not, turn about: its
        median flips between the two, and behind an apply the short one has
        three in four) — None until HOLD_MIN_SPANS of them have closed."""
        history = self._durations.get(name)
        if history is None or len(history) < self.HOLD_MIN_SPANS:
            return None
        try:
            return statistics.quantiles(history, n=10, method="inclusive")[-1]
        except RuntimeError:  # the watcher's read met the span's thread's append
            return None

    def _held(self, name: str, seconds: float) -> Optional[float]:
        """The usual duration of ``name`` if ``seconds`` of it (its
        children's holds out) is a hold, else None."""
        if seconds <= self.HOLD_MIN_S:
            return None
        usual = self._usual(name)
        if usual is None or seconds <= self.HOLD_FACTOR * usual:
            return None
        return usual

    def _look(self) -> bool:
        """The watcher's visit, on ITS thread: put the rule to the innermost
        open span of the live record and sample it while it is held. Reads
        the top of a list and a span's fields: nothing a span's own thread
        waits for. True while a hold is being sampled."""
        ctx = self._live
        if ctx is None or not ctx._judged:
            return False
        try:
            top = ctx._stack[-1]
        except IndexError:
            return False
        hold = top.hold
        if hold is None:
            open_s = ctx._clock() - top.t0 - top.excused_s
            if self._held(top.name, open_s) is None:
                return False
            hold = top.hold = _Hold(ctx.tid, ctx.ident)
        return hold.sample()

    def _span_closed(
        self, ctx: _StepContext, span: Span, parent: Optional[Span],
        dur: float,
    ) -> None:
        """The rule where no hold is missed: at a span's close, on its own
        thread (an ``add``-ed span too). A held span is an entry of the
        record's ``holds``, with what the watcher sampled of it if it came
        in time; its excess is excused from every span around it, so the
        entries' ``excess_s`` never count a second twice."""
        own = dur - span.excused_s
        usual = self._held(span.name, own) if ctx._judged else None
        if usual is not None:
            entry = {
                "span": span.name,
                "parent": parent.name if parent is not None else None,
                "t0_s": round(span.t0 - ctx._start, 6),
                "held_s": round(dur, 6), "usual_s": round(usual, 6),
                "excess_s": round(own - usual, 6),
                "cpu_s": round(span.cpu_s or 0.0, 6), "samples": 0,
            }
            if registry.scripted_time():
                entry["note"] = "scripted clock"
            elif not _PROC:
                entry["note"] = "no /proc"
            else:
                entry["watcher_away_s"] = round(_WATCHER.away(dur), 3)
                if span.hold is not None:
                    span.hold.close()
                    ctx._watched.append((entry, span.hold))
                else:
                    # the watcher never got to it: an ``add``-ed span, a
                    # close between two looks — or it was kept out itself:
                    # ``watcher_away_s`` says which
                    entry["note"] = "not watched"
            ctx.holds.append(entry)
            span.excused_s += own - usual
        elif span.hold is not None:
            # the watcher held this span for what a CHILD's close has since
            # explained (an ``add``-ed child is never open: ``d2h_stream``
            # inside ``avg_wire``): the samples are that child's
            for entry in reversed(ctx.holds):
                if (
                    entry["parent"] == span.name
                    and entry.get("note") == "not watched"
                ):
                    del entry["note"]
                    entry["sampled_in"] = span.name
                    span.hold.close()
                    ctx._watched.append((entry, span.hold))
                    break
        if not ctx._steady:
            return  # a span of the set-up holds compilation: no usual length
        history = self._durations.get(span.name)
        if history is None:
            history = self._durations[span.name] = deque(
                maxlen=self.SLOW_WINDOW
            )
        history.append(dur)

    # ------------------------------------------------------------- internal

    def _finish(
        self, tele: Optional[registry.Telemetry], ctx: _StepContext,
        wall: float,
    ) -> None:
        phases = dict(ctx.phases)
        untimed = max(0.0, wall - sum(phases.values()))
        for entry, hold in ctx._watched:
            entry.update(hold.seen())
        record: Dict[str, Any] = {
            "step": ctx.step,
            "boundary": ctx.boundary,
            "samples": ctx.samples,
            "wall_s": wall,
            "phases": phases,
            "untimed_s": untimed,
            "spans": ctx.finished_spans(),
            **ctx.counters(),
            "holds": ctx.holds,
            "held_excess_s": sum(h["excess_s"] for h in ctx.holds),
            **ctx.attrs,
        }
        for hold in ctx.holds:
            # the attached spans that overlap it, each with where it began
            # and ended against the hold's START (a ``backup_transfer`` that
            # ends with the hold: the hold is the transfer's last act)
            t0 = ctx._start + hold["t0_s"]
            beside: Dict[str, list] = {}
            for name, a0, a1 in ctx._attached_at:
                if a0 < t0 + hold["held_s"] and a1 > t0:
                    beside.setdefault(
                        name, [name, round(a0 - t0, 3), round(a1 - t0, 3)]
                    )
            hold["beside"] = list(beside.values())[:BESIDE_SPANS]
            logger.info(
                hold_line(hold) + f" | step {ctx.step} boundary {ctx.boundary}"
                f": {wall:.3f} s, cpu {record['cpu_s']:.3f} of them"
                f" {record['sys_s']:.3f} in the kernel, faults"
                f" {record['minflt']}+{record['majflt']}, preempted"
                f" {record['nivcsw']}"
            )
        dominant = max(phases, key=phases.get) if phases else None
        if dominant is not None:
            record["dominant"] = dominant
        mfu = self._update_mfu(tele, record)
        if mfu is not None:
            record["mfu"] = mfu
        self.records.append(record)
        if ctx._steady and record.get("stepped"):
            self._learned = True
        self._step_holds.extend(ctx.holds)
        self._notice_slow_step(record, ctx.totals, ctx.attached)
        if self.perf is not None:
            self.perf.metric("boundary").update(wall)
            for name, dur in phases.items():
                self.perf.metric(name).update(dur)
        if tele is None:
            return
        tele.histogram("step.wall").observe(wall)
        for hold in ctx.holds:
            tele.counter("step.holds").inc()
            tele.counter("step.held_s").inc(hold["excess_s"])
        for name, dur in phases.items():
            tele.histogram(f"step.phase.{name}").observe(dur)
            tele.event("step.phase", phase=name, dur_s=dur, step=ctx.step)
        tele.event("step.record", dur_s=wall, **{
            k: v for k, v in record.items() if k != "wall_s"
        })

    def _notice_slow_step(
        self, record, totals: Dict[str, float], attached: Dict[str, float],
    ) -> None:
        """Fold the record into the global step being assembled; when the
        step completes, compare its wall with the running median and say —
        ONE line, at INFO: a WARNING is a failed step to whoever counts
        them — which spans it spent more in than a median step does, and
        which spans OTHER threads ran beside it (the attached ones: a
        ``backup_transfer``, an averaging stage), against their medians."""
        self._step_wall += record["wall_s"]
        for into, seconds_by_name in (
            (self._step_totals, totals), (self._step_attached, attached),
        ):
            for name, seconds in seconds_by_name.items():
                into[name] = into.get(name, 0.0) + seconds
        if not record.get("stepped"):
            return
        wall, spans, beside, holds = (
            self._step_wall, self._step_totals, self._step_attached,
            self._step_holds,
        )
        self._step_wall, self._step_totals, self._step_attached = 0.0, {}, {}
        self._step_holds = []
        recent = list(self._recent_steps)
        self._recent_steps.append((wall, spans, beside))
        if len(recent) < self.SLOW_MIN_STEPS:
            return
        median = statistics.median(w for w, _s, _b in recent)
        if wall <= self.SLOW_FACTOR * median:
            return

        def over_usual(now: Dict[str, float], column: int, top: int) -> str:
            usual = {
                name: statistics.median(
                    step[column].get(name, 0.0) for step in recent
                )
                for name in now
            }
            over = sorted(now, key=lambda n: usual[n] - now[n])
            return ", ".join(
                f"{n} {now[n]:.3f} ({now[n] - usual[n]:+.3f})"
                for n in over[:top]
            )

        logger.info(
            f"slow global step {record.get('step')}: {wall:.3f} s against a "
            f"median of {median:.3f} s over the last {len(recent)}; spans "
            "(s, against their median): " + over_usual(spans, 1, 6) + (
                "; on other threads beside it: " + over_usual(beside, 2, 4)
                if beside else ""
            ) + (
                "; held: " + ", ".join(
                    f"{h['span']} {h['excess_s']:+.3f}" for h in holds
                ) if holds else ""
            )
        )

    def _update_mfu(self, tele, record) -> Optional[float]:
        if self.model_tflops_per_sample <= 0 or self.peak_tflops <= 0:
            return None
        # ``record`` is not in the ring yet — append before slicing so
        # mfu_window=1 means "this step only", not the whole ring
        recent = (list(self.records) + [record])[-self.mfu_window:]
        samples = sum(r["samples"] for r in recent)
        wall = sum(r["wall_s"] for r in recent)
        if samples <= 0 or wall <= 0:
            return None
        sps = samples / wall
        mfu = sps * self.model_tflops_per_sample / self.peak_tflops
        if tele is not None:
            tele.gauge("step.samples_per_sec").set(sps)
            tele.gauge("step.mfu").set(mfu)
        return mfu

"""Flight recorder: spans, events and counters on the modelled clock,
host regions on the host clock.

The :class:`Tracer` is the single recording surface both runtimes
instrument against.  Design constraints (ISSUE 7 tentpole):

* **zero overhead when disabled** — every call site is guarded by
  ``if tracer is not None``; the runtimes take ``tracer=None`` by
  default, so a disabled run executes the exact pre-instrumentation
  code (no record allocation, no clock reads, no branches beyond the
  None check);
* **deterministic** — the modelled records (spans, events, counters;
  host regions are apart, below) carry only the runtime's *modelled*
  clock (``Sim.loop.now`` / ``VirtualClock.now``; never
  ``time.time()``), are appended in event-execution order, and the
  export sorts with a stable per-record sequence tie-breaker, so the
  same (workload, seed, FaultSchedule) produces a byte-identical JSON
  trace (pinned by tests/test_obs.py);
* **Perfetto-compatible export** — :meth:`Tracer.to_chrome_trace`
  emits the Chrome trace-event format (``ph: X/i/C/M``): one thread
  track per engine/NIC/link/request, counter tracks for queue depths,
  tier occupancy and link congestion.  Load the JSON at
  https://ui.perfetto.dev (docs/observability.md has the walkthrough).

Track names are hierarchical strings (``"snic/node0"``,
``"engine/pe(0, 0)"``, ``"req/12"``): the first path component becomes
the Perfetto process, the full name the thread, both assigned ids in
first-seen order (deterministic given deterministic recording).

Host regions (:meth:`Tracer.region`) are the one record on the host
clock: what the host was doing, timed with ``time.perf_counter_ns``
and entered as a ``jax.profiler.TraceAnnotation`` of the same name, so
a device trace taken meanwhile holds them on its host plane, on the
clock of the device ops.  They are kept apart from the modelled-clock
records: :meth:`to_chrome_trace` and :meth:`export_bytes` never see
them, and :meth:`regions_chrome_trace` writes them out on a ``host/``
track group.  While a region is open, a backend compile is recorded as
a ``compile`` instant on its track, naming the region.
"""
from __future__ import annotations

import json
import time
from typing import Callable, Dict, List, Optional

#: timestamp unit of the Chrome trace format (microseconds)
_US = 1e6
#: the jax.monitoring duration event of one backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# every open region of every tracer, innermost last: module state, as
# the jax.monitoring listener that reads it is one per process
_open: List["Region"] = []
_listening = [False]


def _on_compile(event: str, duration: float, **kw) -> None:
    if event == COMPILE_EVENT and _open:
        r = _open[-1]
        r.tracer.host_events.append(
            (r.track, "compile", time.perf_counter_ns(),
             {"duration_s": duration, "region": r.name}))


def _listen_for_compiles() -> None:
    """Register :func:`_on_compile` with jax.monitoring, once per
    process."""
    if not _listening[0]:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _listening[0] = True


class Region:
    """One host region: ``track``, ``name``, host-clock ``t0``/``t1``
    in ns (``t1`` is -1 while open), ``parent`` (the innermost region
    of the same tracer open on entry, or None) and ``args``, which the
    code inside may add to (counts known only at exit)."""

    __slots__ = ("tracer", "track", "name", "t0", "t1", "parent", "args",
                 "_ann")

    def __init__(self, tracer: "Tracer", track: str, name: str,
                 args: dict):
        self.tracer, self.track, self.name, self.args = (tracer, track,
                                                         name, args)
        self.parent: Optional[Region] = None
        self.t0 = self.t1 = -1

    def __enter__(self) -> "Region":
        import jax
        for r in reversed(_open):
            if r.tracer is self.tracer:
                self.parent = r
                break
        _open.append(self)
        self.tracer.regions.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._ann = None
        _open.remove(self)
        return False


class Tracer:
    """Append-only recorder of spans, instant events and counters.

    ``now_fn`` (bound by the runtime via :meth:`bind_clock`) supplies
    the modelled time for records whose call site does not pass an
    explicit timestamp — the seam components (scheduler, traffic
    manager, tier, controller) have no clock of their own.
    """

    def __init__(self, now_fn: Optional[Callable[[], float]] = None):
        self._now = now_fn
        # (seq, track, name, t0, t1, args) — t1 < 0 marks an instant
        self.spans: List[tuple] = []
        self.counters: List[tuple] = []    # (seq, track, t, values)
        self._seq = 0
        # host-clock records, apart from the modelled ones
        self.regions: List[Region] = []    # in order of entry
        self.host_events: List[tuple] = []  # (track, name, t_ns, args)

    # ------------------------------------------------------------------
    # clock binding
    # ------------------------------------------------------------------
    def bind_clock(self, now_fn: Callable[[], float]) -> "Tracer":
        """Attach the owning runtime's modelled clock (``loop.now`` /
        ``clock.now``).  Never a wall clock: determinism depends on it."""
        self._now = now_fn
        return self

    @property
    def now(self) -> float:
        if self._now is None:
            raise RuntimeError("Tracer has no clock bound; the owning "
                               "runtime must call bind_clock() first")
        return self._now()

    # ------------------------------------------------------------------
    # recording
    # ------------------------------------------------------------------
    def span(self, track: str, name: str, t0: float, t1: float,
             **args) -> None:
        """A complete span [t0, t1] on ``track`` (Chrome ``ph: X``)."""
        self.spans.append((self._seq, track, name, float(t0), float(t1),
                           args))
        self._seq += 1

    def event(self, track: str, name: str, t: Optional[float] = None,
              **args) -> None:
        """An instant event (Chrome ``ph: i``) at ``t`` (default: the
        bound clock's now)."""
        tt = self.now if t is None else float(t)
        self.spans.append((self._seq, track, name, tt, -1.0, args))
        self._seq += 1

    def counter(self, track: str, t: Optional[float] = None,
                **values) -> None:
        """A counter sample (Chrome ``ph: C``): one numeric series per
        keyword, rendered as a stacked counter track in Perfetto."""
        tt = self.now if t is None else float(t)
        self.counters.append((self._seq, track, tt, values))
        self._seq += 1

    def region(self, track: str, name: str, **args) -> Region:
        """A host region, as a context manager: timed on the host clock
        and entered as a ``jax.profiler.TraceAnnotation`` named
        ``name``.  It does not wait for the device; where it syncs, the
        code inside does.  Entering yields the :class:`Region`, whose
        ``args`` the code inside may add to."""
        _listen_for_compiles()
        return Region(self, track, name, args)

    # ------------------------------------------------------------------
    # queries (attribution / audit consume these, not the raw tuples)
    # ------------------------------------------------------------------
    def iter_spans(self, track_prefix: Optional[str] = None,
                   name: Optional[str] = None):
        """Yield ``(track, name, t0, t1, args)`` for complete spans,
        optionally filtered; recording order."""
        for _, track, nm, t0, t1, args in self.spans:
            if t1 < 0:
                continue
            if track_prefix is not None and \
                    not track.startswith(track_prefix):
                continue
            if name is not None and nm != name:
                continue
            yield track, nm, t0, t1, args

    def iter_events(self, name: Optional[str] = None):
        """Yield ``(track, name, t, args)`` for instant events."""
        for _, track, nm, t0, t1, args in self.spans:
            if t1 >= 0:
                continue
            if name is not None and nm != name:
                continue
            yield track, nm, t0, args

    # ------------------------------------------------------------------
    # export
    # ------------------------------------------------------------------
    def _track_ids(self) -> Dict[str, tuple]:
        """track name -> (pid, tid), assigned in first-seen order."""
        pids: Dict[str, int] = {}
        tids: Dict[str, tuple] = {}
        for rec in sorted(self.spans + self.counters,
                          key=lambda r: r[0]):
            track = rec[1]
            if track in tids:
                continue
            group = track.split("/", 1)[0]
            pid = pids.setdefault(group, len(pids) + 1)
            tids[track] = (pid, len(tids) + 1)
        return tids

    def to_chrome_trace(self) -> dict:
        """The Chrome trace-event representation (a JSON-ready dict)."""
        tids = self._track_ids()
        out: List[dict] = []
        for track, (pid, tid) in tids.items():
            out.append({"ph": "M", "name": "process_name", "pid": pid,
                        "tid": 0,
                        "args": {"name": track.split("/", 1)[0]}})
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "args": {"name": track}})
        recs = []
        for seq, track, name, t0, t1, args in self.spans:
            pid, tid = tids[track]
            if t1 >= 0:
                recs.append((t0, seq, {
                    "ph": "X", "name": name, "cat": track,
                    "ts": round(t0 * _US, 3),
                    "dur": round(max(t1 - t0, 0.0) * _US, 3),
                    "pid": pid, "tid": tid, "args": args}))
            else:
                recs.append((t0, seq, {
                    "ph": "i", "name": name, "cat": track, "s": "t",
                    "ts": round(t0 * _US, 3),
                    "pid": pid, "tid": tid, "args": args}))
        for seq, track, t, values in self.counters:
            pid, tid = tids[track]
            recs.append((t, seq, {
                "ph": "C", "name": track, "ts": round(t * _US, 3),
                "pid": pid, "tid": tid, "args": values}))
        recs.sort(key=lambda r: (r[0], r[1]))
        out.extend(r[2] for r in recs)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def export_json(self, path: str) -> str:
        """Write the Perfetto-loadable trace to ``path``.  Sorted keys
        and fixed separators keep the bytes deterministic."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, sort_keys=True,
                      separators=(",", ":"))
            f.write("\n")
        return path

    def export_bytes(self) -> bytes:
        """The exported trace as bytes (what export_json writes) — the
        determinism tests compare these directly."""
        return (json.dumps(self.to_chrome_trace(), sort_keys=True,
                           separators=(",", ":")) + "\n").encode()

    def regions_chrome_trace(self) -> dict:
        """The host regions and host events in the Chrome trace-event
        format, each track under ``host/``, in microseconds from the
        first region's start.  Regions still open are left out."""
        done = [r for r in self.regions if r.t1 >= 0]
        t0 = min((r.t0 for r in done), default=0)
        tracks = list(dict.fromkeys(
            [f"host/{r.track}" for r in done]
            + [f"host/{e[0]}" for e in self.host_events]))
        tids = {t: i + 1 for i, t in enumerate(tracks)}
        out: List[dict] = [{"ph": "M", "name": "process_name", "pid": 1,
                            "tid": 0, "args": {"name": "host"}}]
        out += [{"ph": "M", "name": "thread_name", "pid": 1, "tid": tid,
                 "args": {"name": t}} for t, tid in tids.items()]
        for r in done:
            out.append({"ph": "X", "name": r.name, "cat": r.track,
                        "ts": (r.t0 - t0) / 1e3, "dur": (r.t1 - r.t0) / 1e3,
                        "pid": 1, "tid": tids[f"host/{r.track}"],
                        "args": r.args})
        for track, name, t, args in self.host_events:
            out.append({"ph": "i", "name": name, "cat": track, "s": "t",
                        "ts": (t - t0) / 1e3, "pid": 1,
                        "tid": tids[f"host/{track}"], "args": args})
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    # ------------------------------------------------------------------
    # fault-window annotations (sim/faults.py)
    # ------------------------------------------------------------------
    def annotate_faults(self, faults) -> None:
        """Record a FaultSchedule's slowdown windows as spans on the
        ``faults`` track (one sub-track per resource) and its engine
        deaths as instant events, so every chaos run's injected
        degradations are visible alongside the request lifecycles."""
        if faults is None:
            return
        for w in faults.windows:
            self.span(f"faults/{w.resource}", "fault_window",
                      w.t0, w.t1, factor=w.factor,
                      node=w.node if w.node is not None else "all")
        for d in faults.deaths:
            self.event("faults/deaths", "engine_death_scheduled",
                       t=d.t, engine=list(d.engine))

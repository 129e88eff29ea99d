"""The chip benchmark's harness: one cell, one run.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(``configs/<name>.json``), a traffic mix (``traffic/<mix>.json``) and its
chips.  One run:

1. set-up: weights made on the device from ``--seed`` in one jitted call;
   every program the window can need compiled (or loaded from the
   persistent cache): the prefill and decode programs through a
   ``ServingSystem`` serving one round of each size the mix allows, the
   per-length install and persist programs for every KV block count the
   mix can make;
2. the window: a fresh ``ServingSystem`` (``mode="dualpath"``, split
   reads on, as ``repro.launch.serve`` builds it) driven through
   ``run_online`` for ``--seconds`` on a wall clock (:class:`WallClock`,
   :class:`WindowLoop`) instead of its modelled one.  Every round is
   timed from when it was due; new submissions stop when the window
   closes; for an open-loop mix the rounds due in the window are then
   served until each has its first token, at most ``drain_s``;
3. the check: a sample of the finished rounds, drawn from the seed with
   the longest in it, compared against the configuration's plain float32
   reference (:mod:`check`);
4. the result line.

The harness depends on these parts of the program, and a change to them
has to keep them: ``ServingSystem(cfg, params, n_pe=, n_de=, mode=,
block_tokens=, max_seq=, de_slots=, split_reads=)``, its attributes
``clock``, ``loop``, ``metrics`` (``RoundMetrics`` with ``submit_t``,
``prefill_done_t``, ``done_t``, ``gen_tokens``), ``pes``, ``des``,
``store``, and ``run_online``, ``run_offline`` and ``stats()``; engines'
``step``, ``install_hit_kv``, ``_persist``, ``last_step_items``,
``last_step_ctxs``, ``slots``, ``decode_steps``, ``state``; the store's
``write_block``; ``repro.kernels.ops.kv_layer_gather``;
``repro.engines.kvio`` (``n_attn_layers``, ``kv_row_bytes``,
``deserialize_kv_layer``, ``serialize_kv_layer``) and
``repro.models.init_decode_state``, which set-up calls for every block
count; ``repro.configs.get_config``; ``repro.sim.traces.Round`` and
``Trajectory``.
"""
from __future__ import annotations

import dataclasses
from contextlib import nullcontext
import gc
import heapq
import importlib.util
import itertools
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

import check
import flops
import reduce
import traffic as traffic_mod
from peaks import peaks

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_MARKER = "bench_trace_window"
# host spans, in the order idle device time is attributed to them
COVER_GROUP = 2      # warm-up sessions served together
SPANS = ("idle_sleep", "persist", "write_block", "install", "prefill",
         "decode")


class WindowClosed(Exception):
    """Raised from the event loop when the measured window is over."""


# ---------------------------------------------------------------------------
# the benchmark's description
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if "workloads" not in m
            or cell in m["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(name=name, chips=int(w["chips"]),
                config=json.loads((root / conf["file"]).read_text()),
                traffic=traffic_mod.load(w["traffic"]),
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))


def reference_module(config: dict):
    name = config["reference"]
    return _load_module(HERE / "references" / f"{name}.py", f"ref_{name}")


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry ``arch``, with every published key the file states
    carried over, so the program runs the configuration as stated."""
    from repro.configs import get_config
    cfg = get_config(config["arch"])
    d, heads = config["hidden_size"], config["num_attention_heads"]
    layers = config["num_hidden_layers"]
    return dataclasses.replace(
        cfg, d_model=d, n_layers=layers, n_heads=heads,
        n_kv_heads=config.get("num_key_value_heads", heads),
        head_dim=config.get("head_dim", d // heads),
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config.get("rope_theta", 10000.0)),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        qkv_bias=bool(config.get("qkv_bias", False)),
        embed_scale=float(config.get("scale_emb", 1.0)),
        ffn_mult=(float(config["scale_depth"]) / math.sqrt(layers)
                  if "scale_depth" in config else 1.0),
        param_dtype=config.get("torch_dtype", "bfloat16"),
        kv_cache_dtype=config.get("torch_dtype", "bfloat16"))


# ---------------------------------------------------------------------------
# the wall clock and the window's event loop
# ---------------------------------------------------------------------------


class WallClock:
    """The serving system's clock, read from the host: ``now`` is seconds
    since the window opened, ``advance`` does nothing (work takes the
    time it takes) and ``jump_to`` sleeps until the next event.  While
    the loop fires an event ``now`` reads that event's due time, so a
    round is stamped with when it was due, not when the loop got to
    it."""

    def __init__(self, spans: "Spans"):
        self.t0 = time.perf_counter()
        self.due: Optional[float] = None
        self.spans = spans

    @property
    def now(self) -> float:
        if self.due is not None:
            return self.due
        return time.perf_counter() - self.t0

    def advance(self, dt: float) -> float:
        return self.now

    def jump_to(self, t: float) -> float:
        dt = t - self.now
        if dt > 0:
            with self.spans.span("idle_sleep"):
                time.sleep(dt)
        return self.now


class WindowLoop:
    """Timed events of the window: sessions' arrivals (``at``) and their
    next rounds at the ends of think gaps (``after``).

    Closes the window at ``close_s``: pending submissions are dropped;
    with ``drain_s`` > 0 the loop then runs on until every round due in
    the window has its first token, at most ``drain_s`` more; then it
    raises :class:`WindowClosed`.  ``cap`` bounds the sessions in play,
    a client for each: a session is in play from its arrival until its
    last round is out, its own next rounds are never held back, and a
    new session starts when fewer than ``cap`` rounds are in flight or
    waiting out a think gap."""

    def __init__(self, clock: WallClock, metrics: Dict, close_s: float,
                 drain_s: float, cap: Optional[int]):
        self.clock = clock
        self.metrics = metrics
        self.close_s = close_s
        self.drain_s = drain_s
        self.cap = cap
        self._new: list = []              # arrivals of sessions
        self._cont: list = []             # sessions' next rounds
        self._seq = itertools.count()
        self._open: set = set()
        self._seen = 0
        self.closed = False
        self.late: List[float] = []       # firing time minus due time

    def at(self, t: float, fn) -> None:
        heapq.heappush(self._new, (t, next(self._seq), fn))

    def after(self, dt: float, fn) -> None:
        heapq.heappush(self._cont, (self.clock.now + max(dt, 0.0),
                                    next(self._seq), fn))

    @property
    def pending(self) -> int:
        return len(self._new) + len(self._cont)

    def _admits(self) -> bool:
        if self.cap is None:
            return True
        keys = list(self.metrics)
        self._open.update(keys[self._seen:])
        self._seen = len(keys)
        self._open = {r for r in self._open
                      if r in self.metrics and self.metrics[r].done_t < 0}
        return len(self._open) + len(self._cont) < self.cap

    def next_time(self) -> Optional[float]:
        """The next wake-up: an event that may fire, or the close."""
        if self.closed:
            return self.clock.now
        ts = [self.close_s]
        if self._cont:
            ts.append(self._cont[0][0])
        if self._new and self._admits():
            ts.append(self._new[0][0])
        return min(ts)

    def _drained(self, now: float) -> bool:
        if now >= self.close_s + self.drain_s:
            return True
        return all(m.prefill_done_t >= 0 for m in self.metrics.values())

    def _fire(self, heap, now: float) -> None:
        t, _, fn = heapq.heappop(heap)
        self.late.append(now - t)
        self.clock.due = t
        try:
            fn()
        finally:
            self.clock.due = None

    def fire_due(self) -> int:
        now = self.clock.now
        if not self.closed and now >= self.close_s:
            self.closed = True
            self._new.clear()
            self._cont.clear()
        if self.closed:
            if self._drained(now):
                raise WindowClosed
            return 0
        n = 0
        while self._cont and self._cont[0][0] <= now:
            self._fire(self._cont, now)
            n += 1
        while self._new and self._new[0][0] <= now and self._admits():
            self._fire(self._new, now)
            n += 1
        return n


# ---------------------------------------------------------------------------
# spans and counters recorded around the program's calls
# ---------------------------------------------------------------------------


class Spans:
    """Host spans by name (total seconds, count), written as profiler
    TraceAnnotations so a trace can attribute idle device time."""

    def __init__(self, on: bool):
        self.on = on
        self.total: Dict[str, float] = {}
        self.count: Dict[str, int] = {}

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, rec: Spans, name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        if self.rec.on:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t
        if self.rec.on:
            self.ann.__exit__(*exc)
        r = self.rec
        r.total[self.name] = r.total.get(self.name, 0.0) + dt
        r.count[self.name] = r.count.get(self.name, 0) + 1
        return False


@dataclasses.dataclass
class Record:
    """What the window produced, for the metric readers."""

    config: dict
    peaks: dict
    spans: Spans
    emitted: List = dataclasses.field(default_factory=list)
    finished: List = dataclasses.field(default_factory=list)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    gather_calls: List = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None
    trace_window: Optional[tuple] = None
    trace_span_perf: Optional[tuple] = None     # host perf_counter on, off
    compiles_in_window: int = 0

    def add(self, key: str, v: float) -> None:
        self.counters[key] = self.counters.get(key, 0.0) + v


def instrument(system, rec: Record, clock: WallClock, traced: bool):
    """Wrap the engines' calls.  Always: the output tokens each step
    emits, and the token streams of finished rounds (for the check).
    With ``traced``: spans that end once the device is done, FLOPs of
    the served tokens, and the bytes of each KV gather."""
    import jax
    from repro.kernels import ops

    spans = rec.spans
    for pe in system.pes.values():
        step, install = pe.step, pe.install_hit_kv

        def pe_step(step=step, pe=pe):
            with spans.span("prefill") if traced else nullcontext():
                done = step()
                if traced:
                    jax.block_until_ready([er.state for er in done])
            rec.emitted.append((clock.now, len(done)))
            if traced:
                items = pe.last_step_items
                rec.add("prefill_tokens", sum(b for _, b in items))
                rec.add("flops", sum(flops.prefill_flops(rec.config, c, b)
                                     for c, b in items))
            return done

        def pe_install(er, payload, install=install):
            with spans.span("install"):
                install(er, payload)
                jax.block_until_ready(er.state)
            if payload:
                rec.add("hit_tokens", er.req.cached_tokens)

        pe.step = pe_step
        if traced:
            pe.install_hit_kv = pe_install
    for de in system.des.values():
        step, persist = de.step, de._persist

        def de_step(step=step, de=de):
            active = [(er, len(er.generated)) for er in de.slots
                      if er is not None]
            n0 = de.decode_steps
            with spans.span("decode") if traced else nullcontext():
                done = step()
                if traced:
                    jax.block_until_ready(de.state)
            rec.emitted.append((clock.now, sum(len(er.generated) - k
                                               for er, k in active)))
            for er in done:
                rec.finished.append(dict(
                    rid=er.req.rid, prompt=er.context_tokens
                    + er.append_tokens, generated=list(er.generated),
                    cached=er.req.cached_tokens))
            if traced and de.decode_steps > n0:
                rec.add("decode_steps", de.decode_steps - n0)
                rec.add("flops", sum(flops.decode_flops(rec.config, c)
                                     for c in de.last_step_ctxs))
            return done

        def de_persist(slot, er, persist=persist):
            with spans.span("persist"):
                persist(slot, er)
            rec.add("persisted_rounds", 1)

        de.step = de_step
        if traced:
            de._persist = de_persist
    if traced:
        write = system.store.write_block

        def write_block(ref, block, write=write):
            with spans.span("write_block"):
                return write(ref, block)

        system.store.write_block = write_block
        gather = ops.kv_layer_gather

        def kv_layer_gather(pool, table, *, layer, interpret=None):
            n, _, pt, row = pool.shape
            rec.gather_calls.append(
                (time.perf_counter(), flops.gather_bytes(
                    table.shape[0], pt, row)))
            return gather(pool, table, layer=layer, interpret=interpret)

        ops.kv_layer_gather = kv_layer_gather
        return lambda: setattr(ops, "kv_layer_gather", gather)
    return lambda: None


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def enable_compile_cache():
    """JAX's persistent compilation cache where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR`` when set, else a fixed path inside
    the checkout), keeping every program, however fast it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


_compiles = [0]
_cache = {"hits": 0, "misses": 0}


def _on_duration(event, duration, **kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles[0] += 1


def _on_event(event, **kw):
    if event == "/jax/compilation_cache/cache_hits":
        _cache["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _cache["misses"] += 1


def compile_count() -> int:
    """Backend compiles in this process so far (jax.monitoring; the
    listeners, which also count the persistent cache's hits and misses,
    are registered once per process)."""
    import jax
    if not getattr(_on_duration, "registered", False):
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _on_duration.registered = True
    return _compiles[0]


def cache_files() -> str:
    """The persistent cache's directory, its bytes and files, for the
    log."""
    import jax
    d = jax.config.jax_compilation_cache_dir
    files = [f for f in Path(d).rglob("*") if f.is_file()] if d else []
    return (f"{d} holds {sum(f.stat().st_size for f in files)} bytes in "
            f"{len(files)} files")


def build_system(cfg, params, serve: dict):
    from repro.serving import ServingSystem
    return ServingSystem(
        cfg, params, n_pe=serve["n_pe"], n_de=serve["n_de"], mode="dualpath",
        block_tokens=serve["block_tokens"], max_seq=serve["max_seq"],
        de_slots=serve["de_slots"], split_reads=serve["split_reads"])


def trajectories(sessions):
    from repro.sim.traces import Round, Trajectory
    return [Trajectory(s.key, [Round(a, g, th) for a, g, th in s.rounds])
            for s in sessions]


def block_counts(p: dict, max_seq: int, bt: int):
    """The KV block counts a mix can make the program move: hits (a
    round's prefix read back and installed) and persists (the blocks a
    round adds to the store).  A round persists the whole blocks of its
    context less the last token, which was never fed back; the next
    round of the session hits those blocks.  Contexts are sums of the
    mix's sizes, so multiples of their greatest common divisor."""
    sizes = p["sizes"]
    if p["kind"] == "single":
        return set(), {(a + g - 1) // bt for a in sizes["prompt"]
                       for g in sizes["gen"] if a + g <= max_seq}
    unit = math.gcd(*[s for v in sizes.values() for s in v])
    ctx = range(unit, max_seq + 1, unit)
    stored = {(c - 1) // bt for c in ctx}
    grow = {(c1 - 1) // bt - (c0 - 1) // bt for c0 in ctx for c1 in ctx
            if c1 > c0}
    return stored - {0}, (stored | grow) - {0}


def warm_block_programs(cfg, system, serve: dict, p: dict):
    """Compile the install and persist programs for every KV block count
    the mix can make (:func:`block_counts`): the gather kernel and the
    per-layer install write of a hit of ``n`` blocks, the per-layer read
    of a persist of ``n`` blocks.  Device-resident zeros stand in for
    the bytes."""
    import jax
    import jax.numpy as jnp
    from repro.engines import kvio
    from repro.kernels import ops
    from repro.models import init_decode_state

    bt, max_seq = serve["block_tokens"], serve["max_seq"]
    layers, row = kvio.n_attn_layers(cfg), kvio.kv_row_bytes(cfg)
    one = init_decode_state(cfg, 1, max_seq)
    de = next(iter(system.des.values()))
    hits, persists = block_counts(p, max_seq, bt)
    for n in sorted(hits):
        pool = jnp.zeros((n, layers, bt, row), jnp.uint8)
        out = ops.kv_layer_gather(pool, jnp.arange(n, dtype=jnp.int32),
                                  layer=0)
        st = kvio.deserialize_kv_layer(cfg, one, 0, 0, 0,
                                       np.zeros((n * bt, row), np.uint8))
        jax.block_until_ready((out, st))
        del pool, out, st
    for n in sorted(persists):
        kvio.serialize_kv_layer(cfg, de.state, 0, 0, n * bt, 0)


def clients(p: dict, serve: dict, work) -> Optional[int]:
    """Rounds in flight at most: the mix's count, or its count per
    decode slot of the configuration."""
    per_slot = p["arrival"].get("outstanding_per_slot")
    if per_slot:
        return max(1, round(per_slot * serve["de_slots"]))
    return work.max_outstanding


def coverage_sessions(p: dict, max_len: int) -> List[traffic_mod.Session]:
    """One round of every size the mix allows, each with the shortest
    gen: the prefill programs the window can meet."""
    sizes = p["sizes"]
    g = min(sizes["gen"])
    out = []
    if p["kind"] == "sessions":
        firsts = [s for s in sizes["first"] if s <= max_len // 4]
        for f in firsts:
            out.append([(f, g, 0.0)])
        for a in sizes["append"]:
            if firsts[0] + a + 2 * g <= max_len:
                out.append([(firsts[0], g, 0.0), (a, g, 0.0)])
    else:
        out = [[(a, g, 0.0)] for a in sizes["prompt"] if a + g <= max_len]
    return [traffic_mod.Session(-(i + 1), r) for i, r in enumerate(out)]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device_info(chips: int):
    """The devices the run uses; refuses a run without enough TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's backend is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs


class NoChip(Exception):
    pass


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class Profiler:
    """The device trace of the whole window, from its opening to its
    close: the few installs of a backlog's window fall where they fall.
    Only the traced run has it."""

    def __init__(self):
        self.dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
        self.state = "off"

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.dir.name)
        self.mark = jax.profiler.TraceAnnotation(TRACE_MARKER)
        self.mark.__enter__()
        self.perf_on = time.perf_counter()
        self.state = "on"

    def stop(self) -> None:
        import jax
        if self.state == "on":
            self.perf_off = time.perf_counter()
            self.mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def read(self):
        try:
            files = sorted(Path(self.dir.name).rglob("*.xplane.pb"))
            if not files:
                return None, None
            trace = reduce.compact_xplane(str(files[-1]))
            return trace, reduce.window_of(trace, TRACE_MARKER)
        finally:
            self.dir.cleanup()


def metric_reader(name: str):
    return _load_module(HERE / "metrics" / f"{name}.py",
                        "metric_" + name.replace(".", "_"))


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    seconds: float
    end_s: float
    setup_s: float
    rounds: List[dict]
    ttft: List[float]
    tpot: List[float]
    rec: Record


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        fault: Optional[Callable] = None, control: bool = False) -> dict:
    """One run of ``cell``; returns the result line's object.  ``fault``,
    for tests only, breaks the system under test before the window.
    ``control`` (calibrate.py and the tests) puts the float8 reference's
    choices in the served tokens' place at the same positions and
    decides ``correct`` on them by the same comparison; the program's
    own gap is then reported beside it as ``program_max_logit_gap_sd``."""
    import jax

    devs = device_info(cell.chips) if require_tpu else jax.devices()
    dev = devs[0]
    pk = peaks(dev.device_kind) if require_tpu else None
    enable_compile_cache()
    compile_count()
    config, serve = cell.config, cell.config["serve"]
    ref = reference_module(config)
    cfg = program_config(config)
    work = traffic_mod.generate(cell.traffic, serve["max_seq"], seed,
                                seconds)

    # -- set-up: weights, then every program the window can need -----
    phases = [("start", time.perf_counter())]

    def phase(name):
        phases.append((name, time.perf_counter()))
        log(f"set-up: {name} done after {phases[-1][1] - t_start!r} s")

    params = ref.weights(config, seed)
    jax.block_until_ready(params)
    phase("weights")
    warm = build_system(cfg, params, serve)
    warm_block_programs(cfg, warm, serve, cell.traffic)
    phase("block_programs")
    # a few sessions to a system: all due at once, they would each hold
    # a prefill state together
    cover = coverage_sessions(cell.traffic, serve["max_seq"])
    for i in range(0, len(cover), COVER_GROUP):
        warm = build_system(cfg, params, serve)
        warm.run_offline(trajectories(cover[i:i + COVER_GROUP]))
        del warm
        gc.collect()
    phase("coverage_rounds")
    system = build_system(cfg, params, serve)
    phase("system")
    if fault is not None:
        fault(system)
    spans = Spans(on=trace)
    rec = Record(config=config, peaks=pk, spans=spans)
    clock = WallClock(spans)
    profiler = Profiler() if trace else None
    loop = WindowLoop(clock, system.metrics, seconds, work.drain_s,
                      clients(cell.traffic, serve, work))
    restore = instrument(system, rec, clock, trace)
    system.clock, system.loop = clock, loop
    compiles0 = compile_count()
    setup_s = time.perf_counter() - t_start
    log(f"window opens after {setup_s!r} s of set-up")

    # -- the window --------------------------------------------------
    if profiler is not None:
        profiler.start()
    clock.t0 = time.perf_counter()
    try:
        system.run_online(trajectories(work.sessions), work.arrivals)
    except WindowClosed:
        pass
    end_s = clock.now
    rec.compiles_in_window = compile_count() - compiles0
    if profiler is not None:
        profiler.stop()
        rec.trace, rec.trace_window = profiler.read()
        if profiler.state == "done":
            rec.trace_span_perf = (profiler.perf_on, profiler.perf_off)
    restore()
    stats = system.stats()
    rounds = [dict(due=m.submit_t, first=m.prefill_done_t, done=m.done_t,
                   gen=m.gen_tokens) for m in system.metrics.values()]
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:cell.chips])
    late = loop.late
    system.clock = system.loop = loop = None
    del system, params
    gc.collect()

    # -- the check, once the program's state is freed ----------------
    picked = check.sample(rec.finished, seed)
    params = ref.weights(config, seed)
    got = check.gaps(ref, config, params, picked, serve["max_seq"],
                     max(cell.traffic["sizes"]["gen"]), control=control)
    del params
    ttft, missing = reduce.ttfts(rounds, seconds)
    if not work.drain_s:
        # a backlog: rounds still in flight at the close are attempted,
        # not failed
        missing = 0
    limit = config["correct"]["max_logit_gap_sd"]["limit"]
    gap = got["control_max_logit_gap_sd" if control else "max_logit_gap_sd"]
    compared = {
        "max_logit_gap_sd": {"value": gap, "limit": limit},
        "rounds_without_first_token": {"value": missing, "limit": 0},
        "tokens_compared": {"value": got["tokens"], "limit": 1},
    }
    correct = (limit is not None and gap <= limit
               and missing == 0 and got["tokens"] >= 1)

    # -- metrics -----------------------------------------------------
    res = Run(seconds=seconds, end_s=end_s, setup_s=setup_s, rounds=rounds, ttft=ttft,
              tpot=reduce.tpots(rounds, seconds), rec=rec)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = metric_reader(m["name"]).read(res)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    out = {"correct": bool(correct), "attempted": len(rounds),
           "failed": missing, "metrics": metrics, "device": device}
    busy = None
    if trace and rec.trace is not None and rec.trace_window is not None:
        busy = reduce.device_busy(rec.trace, rec.trace_window)
    if busy is not None:
        device["busy_s"] = busy["busy_s"]
        device["window_s"] = busy["window_s"]
        out["breakdown"] = {
            "device_ops": reduce.top_ops(rec.trace, rec.trace_window),
            "idle_gaps": reduce.idle_by_host(rec.trace, busy["idle"],
                                             SPANS)}

    # -- report ------------------------------------------------------
    finished = sum(1 for r in rounds if 0 <= r["done"] <= seconds)
    log(f"cell {cell.name} seed {seed} seconds {seconds} trace {int(trace)}"
        f" on {dev.device_kind} x{len(devs)}")
    log(f"window closed at {end_s!r} s; rounds due {len(rounds)}, with a "
        f"first token {len(ttft)}, finished in the window {finished}, "
        f"output tokens in the window "
        f"{reduce.tokens_in_window(rec.emitted, seconds)}")
    log(f"generator lateness (fire minus due, s): median "
        f"{reduce.percentile(late, 50)!r} p90 {reduce.percentile(late, 90)!r}"
        f" max {max(late, default=float('nan'))!r} over {len(late)} events")
    log(f"compiles in the window: {rec.compiles_in_window}; set-up "
        f"{setup_s!r} s (" + ", ".join(
            f"{b[0]} {b[1] - a[1]!r} s" for a, b in zip(phases, phases[1:]))
        + f"; {compiles0} compiles); memory_peak_bytes {int(mem)}")
    log(f"persistent cache: {_cache['hits']} hits, {_cache['misses']} "
        f"misses; {cache_files()}")
    log("stats: " + json.dumps({k: stats[k] for k in (
        "store_reads", "store_writes", "read_bytes_pe_side",
        "read_bytes_de_side", "split_reads", "prefill_tokens",
        "decode_steps", "gen_tokens")}))
    if trace:
        log("spans_s: " + json.dumps(rec.spans.total))
        log("counters: " + json.dumps(rec.counters))
    log(f"checked {got['rounds']} rounds ({got['hit_rounds']} with prefix "
        f"hits), {got['tokens']} served tokens")
    for k, v in compared.items():
        log(f"compared {k}: {v['value']!r} limit {v['limit']!r}")
    out["window"] = {"end_s": end_s, "finished": finished,
                     "ttft_max_s": max(ttft, default=None),
                     "generator_late_p90_s": reduce.percentile(late, 90)}
    if control:
        out["program_max_logit_gap_sd"] = got["max_logit_gap_sd"]
        log("control run: compared is the float8 reference's gap; the "
            f"program's is {got['max_logit_gap_sd']!r}")
    out["compared"] = compared
    return out

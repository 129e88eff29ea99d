"""Flight recorder (repro.obs): determinism, schema, attribution,
audit.

Pins the ISSUE-7 tentpole contracts:

* **byte-determinism** — the same (workload, seed, FaultSchedule)
  yields a byte-identical exported trace;
* **zero overhead when disabled** — an untraced run's ``results()`` /
  ``stats()`` are numerically identical to a traced run's;
* **schema two-way closure** — both runtimes emit exactly the
  registered metric keys: no unregistered keys (``conforming``
  raises), no orphaned registrations (``orphans`` is empty);
* **attribution exactness** — the TTFT decomposition is a partition:
  components sum to the window exactly, category priority and the
  queue residual behave as documented;
* **audit** — span/event byte sums equal the runtimes' conservation
  ledgers, and any tampering (dropped or inflated record) raises
  :class:`TraceAuditError`;
* **fault annotation** — a FaultSchedule's windows and deaths appear
  as spans/events with the schedule's exact boundaries.
"""
import math

import numpy as np
import pytest

from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       TraceAuditError, Tracer, attribute_ttft,
                       audit_sim, bottleneck_report, conforming, orphans,
                       registered_keys)
from repro.core.config import ResilienceConfig
from repro.sim import DS_660B, HOPPER_NODE, Sim, SimConfig
from repro.sim.faults import EngineDeath, FaultSchedule, SlowdownWindow
from repro.sim.traces import Round, Trajectory


def _trajs(n=6, rounds=((2048, 16), (256, 16), (256, 16))):
    return [Trajectory(i, [Round(*r) for r in rounds]) for i in range(n)]


def _sim(tracer=None, faults=None, **kw):
    cfg = SimConfig(node=HOPPER_NODE, model=DS_660B, P=1, D=2,
                    mode="dualpath",
                    resilience=ResilienceConfig(faults=faults), **kw)
    return Sim(cfg, _trajs(), tracer=tracer).run()


# ---------------------------------------------------------------------------
# tracer unit behaviour
# ---------------------------------------------------------------------------

def test_tracer_requires_bound_clock_for_default_timestamps():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        tr.event("x", "no-clock")
    tr.event("x", "explicit", t=1.5)     # explicit t needs no clock
    tr.bind_clock(lambda: 2.0)
    tr.event("x", "bound")
    assert [(t, n) for _, n, t, _ in tr.iter_events()] == \
        [(1.5, "explicit"), (2.0, "bound")]


def test_span_event_counter_separation():
    tr = Tracer(now_fn=lambda: 0.0)
    tr.span("a/t", "s", 1.0, 2.0, k=1)
    tr.event("a/t", "e", t=1.5)
    tr.counter("a/q", t=1.0, depth=3)
    assert [n for _, n, *_ in tr.iter_spans()] == ["s"]
    assert [n for _, n, *_ in tr.iter_events()] == ["e"]
    trace = tr.to_chrome_trace()["traceEvents"]
    assert [r["ph"] for r in trace if r["ph"] != "M"] == ["X", "C", "i"]
    # hierarchical tracks: one pid per first path component
    meta = {r["name"]: r for r in trace if r["ph"] == "M"}
    assert meta["process_name"]["args"]["name"] == "a"


def test_export_bytes_deterministic_under_record_content():
    def build():
        tr = Tracer(now_fn=lambda: 0.0)
        tr.span("snic/node0", "nic_xfer", 0.0, 1.0, tag="read",
                nbytes=10)
        tr.event("req/1", "first_token", t=1.0)
        tr.counter("snic/node0/queue", t=1.0, queued_bytes=5)
        return tr.export_bytes()
    assert build() == build()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_primitives():
    c = Counter("gen_tokens")
    c.inc(); c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge("net_congestion")
    assert math.isnan(g.value)
    g.set(0.25)
    assert g.value == 0.25
    h = Histogram("ttft_s")
    assert math.isnan(h.percentile(50))
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.percentile(50) == 2.0          # nearest-rank
    assert h.percentile(100) == 4.0
    s = h.summary()
    assert s["count"] == 4 and s["mean"] == 2.5


def test_registry_get_or_create_and_kind_conflicts():
    r = MetricsRegistry()
    c = r.counter("doorbells")
    assert r.counter("doorbells") is c
    with pytest.raises(TypeError):
        r.gauge("doorbells")
    r.gauge("wall_s").set(1.0)
    c.inc(3)
    snap = r.snapshot()
    assert snap["doorbells"] == 3 and snap["wall_s"] == 1.0
    assert list(snap) == sorted(snap)


# ---------------------------------------------------------------------------
# metric-key schema: two-way closure on both runtimes
# ---------------------------------------------------------------------------

def test_conforming_rejects_unregistered_keys():
    with pytest.raises(KeyError, match="not_a_registered_metric"):
        conforming({"not_a_registered_metric": 1}, "sim")


def test_sim_results_schema_two_way():
    r = _sim().results()
    assert conforming(r, "sim") is r        # no unregistered keys
    assert orphans(r, "sim") == set()       # no registered-but-missing


def test_serving_stats_schema_two_way(serving_run):
    st = serving_run["st"]
    assert conforming(st, "serving") is st
    assert orphans(st, "serving") == set()
    # the shared keys really are shared
    shared = registered_keys("sim") & registered_keys("serving")
    assert {"gen_tokens", "ttft_mean", "finished_rounds"} <= shared


# ---------------------------------------------------------------------------
# determinism + zero overhead (simulator; the serving side of both
# properties is pinned by benchmarks/fig_bottleneck.py --smoke in CI)
# ---------------------------------------------------------------------------

def test_sim_trace_byte_identical_across_runs():
    tr1, tr2 = Tracer(), Tracer()
    _sim(tracer=tr1)
    _sim(tracer=tr2)
    b = tr1.export_bytes()
    assert b == tr2.export_bytes()
    assert b.endswith(b"\n") and len(b) > 1000


def test_sim_results_identical_with_and_without_tracer():
    r0 = _sim().results()
    r1 = _sim(tracer=Tracer()).results()
    for k in r0:
        if isinstance(r0[k], float) and math.isnan(r0[k]):
            assert math.isnan(r1[k]), k
        else:
            assert r0[k] == r1[k], k


# ---------------------------------------------------------------------------
# fault annotation
# ---------------------------------------------------------------------------

def test_fault_schedule_annotation_boundaries():
    fs = FaultSchedule(
        windows=[SlowdownWindow("snic", 2.0, 5.0, 8.0, node=1),
                 SlowdownWindow("net", 1.0, 3.0, 2.0)],
        deaths=[EngineDeath(4.5, (1, 0))])
    tr = Tracer()
    tr.annotate_faults(fs)
    spans = {(trk, t0, t1): args for trk, _, t0, t1, args
             in tr.iter_spans(None, "fault_window")}
    assert spans[("faults/snic", 2.0, 5.0)] == {"factor": 8.0, "node": 1}
    assert spans[("faults/net", 1.0, 3.0)] == {"factor": 2.0,
                                               "node": "all"}
    deaths = [(t, args) for _, _, t, args
              in tr.iter_events("engine_death_scheduled")]
    assert deaths == [(4.5, {"engine": [1, 0]})]


def test_sim_death_and_recovery_events_recorded():
    tr = Tracer()
    sim = _sim(tracer=tr,
               faults=FaultSchedule(deaths=[EngineDeath(1.0, (1, 0))]))
    r = sim.results()
    assert r["engine_deaths"] == 1
    deaths = [args for _, _, _, args in tr.iter_events("engine_death")]
    assert deaths and deaths[0]["engine"] == [1, 0]
    recovered = list(tr.iter_events("recovered"))
    assert len(recovered) == r["recovered_rounds"]
    audit_sim(sim, tr)                      # ledgers still exact


# ---------------------------------------------------------------------------
# attribution: exact partition, priority, residual
# ---------------------------------------------------------------------------

def _synthetic_tracer():
    """One request with hand-built spans:

      window [0, 10]; read_leg [1, 4]; prefill [3, 7] (overlaps the
      read 1 s); pd_transfer [7, 8]; drain [8.5, 9] on the global
      track; first_token at 10.
    Priority storage > compute > net > drain > queue gives
      storage 3, compute 3, net 1, drain 0.5, queue 2.5.
    """
    tr = Tracer(now_fn=lambda: 0.0)
    tr.span("req/5", "scheduled", 0.0, 1.0)
    tr.span("req/5", "read_leg", 1.0, 4.0, side="pe", nbytes=10)
    tr.span("req/5", "prefill", 3.0, 7.0)
    tr.span("req/5", "pd_transfer", 7.0, 8.0)
    tr.span("reconfig", "drain", 8.5, 9.0, engine=[0, 0])
    tr.event("req/5", "first_token", t=10.0)
    return tr


def test_attribution_hand_computed_partition():
    per = attribute_ttft(_synthetic_tracer())
    rec = per[5]
    assert rec["ttft_s"] == pytest.approx(10.0)
    assert rec["storage_s"] == pytest.approx(3.0)
    assert rec["compute_s"] == pytest.approx(3.0)   # overlap -> storage
    assert rec["net_s"] == pytest.approx(1.0)
    assert rec["drain_s"] == pytest.approx(0.5)
    assert rec["queue_s"] == pytest.approx(2.5)
    parts = sum(rec[c] for c in ("storage_s", "compute_s", "net_s",
                                 "drain_s", "queue_s"))
    assert parts == pytest.approx(rec["ttft_s"], abs=1e-12)
    rep = bottleneck_report(per)
    assert rep["n"] == 1
    assert rep["bottleneck"] in ("storage", "compute")
    assert rep["max_decomp_err_s"] < 1e-12


def test_attribution_empty_report_is_nan_not_crash():
    rep = bottleneck_report({})
    assert rep["n"] == 0 and rep["bottleneck"] == "none"
    assert math.isnan(rep["ttft_mean_s"])


def test_sim_attribution_matches_measured_ttft_exactly():
    tr = Tracer()
    sim = _sim(tracer=tr)
    r = sim.results()
    rep = bottleneck_report(attribute_ttft(tr))
    assert rep["n"] == r["finished_rounds"]
    assert rep["max_decomp_err_s"] < 1e-9
    assert rep["ttft_mean_s"] == pytest.approx(r["ttft_mean"], rel=1e-9)


# ---------------------------------------------------------------------------
# audit: exactness + tamper detection
# ---------------------------------------------------------------------------

def test_sim_audit_passes_and_detects_tampering():
    tr = Tracer()
    sim = _sim(tracer=tr)
    out = audit_sim(sim, tr)
    by_node = out["snic_bytes_by_node"]
    assert sum(t.get("read", 0) for t in by_node.values()) > 0
    # inflate one NIC span's byte count -> the ledger check must fail
    for i, (seq, track, name, t0, t1, args) in enumerate(tr.spans):
        if name == "nic_xfer" and args.get("tag") == "read":
            tampered = dict(args, nbytes=args["nbytes"] + 1)
            tr.spans[i] = (seq, track, name, t0, t1, tampered)
            break
    with pytest.raises(TraceAuditError, match="read span bytes"):
        audit_sim(sim, tr)


def test_sim_audit_rejects_unknown_tags():
    tr = Tracer()
    sim = _sim(tracer=tr)
    tr.span("snic/node0", "nic_xfer", 0.0, 1.0, tag="mystery", nbytes=0)
    with pytest.raises(TraceAuditError, match="unknown"):
        audit_sim(sim, tr)


# ---------------------------------------------------------------------------
# serving runtime (one traced online run, shared across tests)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serving_run():
    import jax
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import ServingSystem
    from repro.sim.spec import REDUCED_TEST_NODE

    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))

    def run(tracer):
        s = ServingSystem(cfg, params, n_pe=1, n_de=2, block_tokens=16,
                          max_seq=160, de_slots=2, seed=0,
                          split_reads=True, node=REDUCED_TEST_NODE,
                          tracer=tracer)
        trajs = [Trajectory(i, [Round(24, 6, 0.5), Round(16, 4, 0.0)])
                 for i in range(4)]
        sessions = s.run_online(trajs, [0.0, 0.1, 0.2, 0.3])
        return s, [list(x.context) for x in sessions]

    tr = Tracer()
    sys_, tokens = run(tr)
    sys0, tokens0 = run(None)
    return {"system": sys_, "tracer": tr, "st": sys_.stats(),
            "tokens": tokens, "untraced_st": sys0.stats(),
            "untraced_tokens": tokens0}


def test_serving_untraced_bit_identity(serving_run):
    assert serving_run["tokens"] == serving_run["untraced_tokens"]
    st, st0 = serving_run["st"], serving_run["untraced_st"]
    for k in st0:
        if isinstance(st0[k], float) and math.isnan(st0[k]):
            assert math.isnan(st[k]), k
        else:
            assert st0[k] == st[k], k


def test_serving_lifecycle_spans_cover_the_state_machine(serving_run):
    tr = serving_run["tracer"]
    names = {n for _, n, *_ in tr.iter_spans("req/")}
    # persist/reading can legitimately be zero-width (state entered and
    # left within one tick) and zero-width state spans are elided
    assert {"scheduled", "prefill", "decode"} <= names
    # TTFT endpoints: one first_token per finished round
    firsts = list(tr.iter_events("first_token"))
    assert len(firsts) == serving_run["st"]["finished_rounds"]


def test_serving_audit_and_attribution(serving_run):
    from repro.obs import audit_serving
    st = serving_run["st"]
    out = audit_serving(serving_run["system"], serving_run["tracer"],
                        check_persists=True)
    assert out["persist_bytes"] == st["store_writes"]
    rep = bottleneck_report(attribute_ttft(serving_run["tracer"]))
    assert rep["n"] == st["finished_rounds"]
    assert rep["max_decomp_err_s"] < 1e-9
    assert rep["ttft_mean_s"] == pytest.approx(st["ttft_mean"], rel=1e-9)


def test_serving_audit_detects_missing_read_event(serving_run):
    from repro.obs import audit_serving
    tr = serving_run["tracer"]
    snap = list(tr.spans)
    try:
        for i, (seq, track, name, t0, t1, args) in enumerate(tr.spans):
            if name == "storage_read":
                del tr.spans[i]
                break
        with pytest.raises(TraceAuditError, match="storage_read"):
            audit_serving(serving_run["system"], tr,
                          check_persists=False)
    finally:
        tr.spans[:] = snap


# ---------------------------------------------------------------------------
# host regions (Tracer.region): host clock, device trace, compiles
# ---------------------------------------------------------------------------

#: every region the served path records, with the args each carries
REGION_ARGS = {
    "serve.tick": {"inflight", "decode_steps"},
    "serve.schedule": {"issued"},
    "serve.poll": {"completed"},
    "serve.wait": set(),
    "pe.prefill": {"tokens", "items"},
    "pe.install": {"rid", "hit_tokens"},
    "pe.install.upload": {"bytes"},
    "pe.install.gather": {"layer", "bytes"},
    "pe.install.place": {"layer", "bytes"},
    "de.decode": {"slots"},
    "de.admit": {"slot", "bytes"},
    "de.persist": {"rid", "blocks", "bytes"},
    "de.persist.copy": {"bytes"},
    "store.write": {"bytes"},
}


def _host_plane_events(trace_dir):
    """(name, duration_ns) of every event on the profiler's host
    planes."""
    from pathlib import Path

    from jax.profiler import ProfileData
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    return [(ev.name, ev.duration_ns) for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_region_lands_on_the_device_trace_host_plane(tmp_path):
    import time

    import jax
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tr.region("serve", "test.region.clock") as r:
            time.sleep(0.02)
    durs = [d for n, d in _host_plane_events(tmp_path)
            if n == "test.region.clock"]
    assert len(durs) == 1
    own = r.t1 - r.t0
    assert abs(durs[0] - own) <= max(0.1 * own, 50_000)


def test_nested_regions_record_their_parent():
    tr, other = Tracer(), Tracer()
    with tr.region("a", "outer") as outer:
        with other.region("b", "elsewhere") as elsewhere:
            with tr.region("a", "inner", k=1) as inner:
                inner.args["n"] = 2
        with tr.region("a", "sibling") as sibling:
            pass
    assert outer.parent is None and elsewhere.parent is None
    assert inner.parent is outer and sibling.parent is outer
    assert inner.args == {"k": 1, "n": 2}
    assert [r.name for r in tr.regions] == ["outer", "inner", "sibling"]
    assert all(r.t1 >= r.t0 >= 0 for r in tr.regions)
    assert outer.t0 <= inner.t0 <= inner.t1 <= sibling.t0 <= outer.t1


def test_regions_stay_out_of_the_modelled_export():
    tr = Tracer(now_fn=lambda: 0.0)
    tr.span("req/1", "scheduled", 0.0, 1.0)
    before = tr.export_bytes()
    with tr.region("serve", "serve.tick", inflight=1):
        pass
    assert tr.export_bytes() == before
    host = tr.regions_chrome_trace()["traceEvents"]
    assert [e["name"] for e in host if e["ph"] == "X"] == ["serve.tick"]
    threads = {e["args"]["name"] for e in host
               if e["name"] == "thread_name"}
    assert threads == {"host/serve"}


@pytest.fixture(scope="module")
def region_run(tmp_path_factory):
    """A traced serving run under the profiler: two-round sessions
    (the second rounds hit the first's blocks), the last arriving after
    an idle gap; a ``max_seq`` no other test uses, so its programs
    compile inside the run."""
    import jax
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import ServingSystem
    from repro.sim.spec import REDUCED_TEST_NODE

    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tr = Tracer()
    s = ServingSystem(cfg, params, n_pe=1, n_de=1, block_tokens=16,
                      max_seq=176, de_slots=2, seed=0, split_reads=True,
                      node=REDUCED_TEST_NODE, tracer=tr)
    trajs = [Trajectory(i, [Round(40, 4, 0.0), Round(24, 4, 0.0)])
             for i in range(3)]
    trace_dir = tmp_path_factory.mktemp("xplane")
    with jax.profiler.trace(str(trace_dir)):
        s.run_online(trajs, [0.0, 0.0, 50.0])
    return {"system": s, "tracer": tr,
            "host": _host_plane_events(trace_dir)}


def test_served_path_records_every_region_with_its_args(region_run):
    tr = region_run["tracer"]
    seen = {}
    for r in tr.regions:
        seen.setdefault(r.name, set()).update(r.args)
        assert r.t1 >= r.t0
    assert seen == REGION_ARGS
    hits = [r for r in tr.regions if r.name == "pe.install"
            and r.args["hit_tokens"]]
    assert hits
    for r in tr.regions:
        if r.name.startswith("pe.install."):
            assert r.parent.name == "pe.install"
            assert r.parent.args["hit_tokens"] > 0
    copies = [r for r in tr.regions if r.name == "de.persist.copy"]
    assert copies and all(r.parent.name == "de.persist" for r in copies)
    assert sum(r.args["bytes"] for r in copies) == \
        region_run["system"].store.bytes_written
    steps = sum(r.args["decode_steps"] for r in tr.regions
                if r.name == "serve.tick")
    assert steps == sum(de.decode_steps
                        for de in region_run["system"].des.values())
    assert steps == sum(1 for r in tr.regions if r.name == "de.decode")


def test_admit_region_and_prefill_state_counter(region_run):
    """Each admission is a ``de.admit`` region inside a tick, with the
    slot and the bytes of the request's state it writes; the ``serve``
    counter ``prefill_state_bytes`` follows the per-request states alive
    in whole states, and is 0 once every round is admitted."""
    from repro.engines.runtime import state_bytes
    from repro.models import init_decode_state
    s, tr = region_run["system"], region_run["tracer"]
    one = state_bytes(init_decode_state(s.cfg, 1, 176))
    admits = [r for r in tr.regions if r.name == "de.admit"]
    assert len(admits) == s.stats()["finished_rounds"] == 6
    assert {r.args["slot"] for r in admits} <= {0, 1}
    assert all(r.args["bytes"] == one and r.parent.name == "serve.tick"
               for r in admits)
    held = [v["prefill_state_bytes"] for _, track, _, v in tr.counters
            if track == "serve"]
    assert held and held[-1] == 0 and max(held) >= one
    assert all(v % one == 0 and v <= 3 * one for v in held)
    assert all(a != b for a, b in zip(held, held[1:]))


def test_every_region_is_on_the_device_trace_host_plane(region_run):
    from collections import Counter
    on_plane = Counter(n for n, _ in region_run["host"])
    for name, n in Counter(r.name for r in
                           region_run["tracer"].regions).items():
        assert on_plane[name] == n, name


def test_compile_inside_a_region_names_it(region_run):
    compiles = [(track, args) for track, name, _, args
                in region_run["tracer"].host_events if name == "compile"]
    assert ("engine/pe(0, 0)", "pe.prefill") in {
        (track, a["region"]) for track, a in compiles}
    assert all(a["duration_s"] > 0 for _, a in compiles)

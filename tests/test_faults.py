"""Chaos suite for the fault model (sim/faults.py) and both runtimes.

Three layers, mirroring the module's design rules:

1. **Schedule unit tests** — validation, multiplicative window
   composition, the hash-based (order-free) straggler draw, and
   ``generate`` determinism.  A chaos failure must reproduce from
   ``(seed, rates)`` alone, so the schedule itself has to be pure data.
2. **Simulator fuzz** — randomized schedules over a small dual-path
   operating point, asserting the liveness/conservation invariants that
   must hold under *any* schedule: every round finishes, deaths are
   recovered, a zero-fault schedule is result-identical to
   ``faults=None``.
3. **Serving-runtime chaos** — the real-bytes runtime under pinned and
   seeded schedules.  Faults only perturb *timing*, never computation,
   so greedy decode must emit bit-identical tokens in every arm; engine
   death must re-home rounds with persists firing exactly once
   (``store_writes`` and ``trie_blocks`` equal the fault-free run —
   the dead engine's deferred store writes never execute, the recovery
   round re-persists once).

``CHAOS_SEED`` (CI matrix: 0/1/2) re-seeds every randomized schedule so
the three chaos jobs explore disjoint fault timelines.
"""
import os
from dataclasses import replace

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.config import ElasticConfig, ResilienceConfig
from repro.sim import DS_660B, HOPPER_NODE, Sim, SimConfig
from repro.sim.faults import (EngineDeath, FaultSchedule, SlowdownWindow,
                              StragglerModel)
from repro.sim.traces import Round, Trajectory

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "0"))


# ---------------------------------------------------------------------------
# FaultSchedule: pure data, deterministic queries
# ---------------------------------------------------------------------------


def test_window_validation():
    with pytest.raises(ValueError):
        SlowdownWindow("disk", 0.0, 1.0, 2.0)     # unknown resource
    with pytest.raises(ValueError):
        SlowdownWindow("snic", 1.0, 1.0, 2.0)     # empty interval
    with pytest.raises(ValueError):
        SlowdownWindow("snic", 0.0, 1.0, 0.5)     # speedups forbidden
    with pytest.raises(ValueError):
        StragglerModel(prob=1.5, severity=2.0)
    with pytest.raises(ValueError):
        StragglerModel(prob=0.5, severity=0.9)


def test_windows_compose_multiplicatively():
    fs = FaultSchedule(windows=[
        SlowdownWindow("snic", 0.0, 10.0, 4.0),            # fabric-wide
        SlowdownWindow("snic", 5.0, 15.0, 2.0, node=0),    # node 0 only
        SlowdownWindow("net", 2.0, 3.0, 3.0),
    ])
    assert fs.snic_factor(0, 1.0) == 4.0
    assert fs.snic_factor(0, 7.0) == 8.0          # overlap: 4 * 2
    assert fs.snic_factor(1, 7.0) == 4.0          # node window misses
    assert fs.snic_factor(0, 12.0) == 2.0
    assert fs.snic_factor(0, 15.0) == 1.0         # t1 exclusive
    assert fs.snic_factor(0, 0.0) == 4.0          # t0 inclusive
    assert fs.net_factor(2.5) == 3.0 and fs.net_factor(3.0) == 1.0
    assert fs.boundaries("snic") == [0.0, 5.0, 10.0, 15.0]
    assert fs.boundaries("net") == [2.0, 3.0]


def test_schedule_sorts_regardless_of_construction_order():
    a = SlowdownWindow("snic", 5.0, 6.0, 2.0)
    b = SlowdownWindow("net", 1.0, 2.0, 2.0)
    d1, d2 = EngineDeath(9.0, (1, 0)), EngineDeath(3.0, (0, 0))
    fs = FaultSchedule(windows=[a, b], deaths=[d1, d2])
    assert fs.windows == [b, a]
    assert fs.deaths == [d2, d1]


def test_empty_property():
    assert FaultSchedule().empty
    assert FaultSchedule(straggler=StragglerModel(0.0, 4.0)).empty
    assert not FaultSchedule(
        windows=[SlowdownWindow("snic", 0.0, 1.0, 2.0)]).empty
    assert not FaultSchedule(deaths=[EngineDeath(1.0, (0, 0))]).empty
    assert not FaultSchedule(straggler=StragglerModel(0.1, 4.0)).empty


def test_straggler_draw_deterministic_and_side_independent():
    m = StragglerModel(prob=0.5, severity=6.0, seed=CHAOS_SEED)
    draws = {(rid, side): m.factor(rid, side)
             for rid in range(200) for side in ("pe", "de")}
    # pure function: re-query in any order, same answer
    for (rid, side), f in sorted(draws.items(), reverse=True):
        assert m.factor(rid, side) == f
        assert f in (1.0, 6.0)
    # the md5 draw decorrelates the two sides of one request (a linear
    # hash made them straggle in lockstep); at prob=0.5 over 200 rids
    # some request must straggle on exactly one side
    split = [rid for rid in range(200)
             if draws[(rid, "pe")] != draws[(rid, "de")]]
    assert split, "pe/de draws perfectly correlated"
    # and the empirical rate is near prob (binomial, 400 draws)
    frac = sum(f > 1.0 for f in draws.values()) / len(draws)
    assert 0.3 < frac < 0.7


def test_generate_is_deterministic_in_seed():
    kw = dict(duration_s=100.0, nodes=range(4),
              engines=((2, 0), (3, 0)), snic_fault_rate=0.05,
              link_flap_rate=0.03, straggler_prob=0.2, n_deaths=2,
              death_frac=0.4)
    a = FaultSchedule.generate(seed=7, **kw)
    b = FaultSchedule.generate(seed=7, **kw)
    c = FaultSchedule.generate(seed=8, **kw)
    assert a.windows == b.windows and a.deaths == b.deaths
    assert a.straggler == b.straggler
    assert a.windows != c.windows
    # expected window counts and death placement
    assert len(a.windows) == round(0.05 * 100) + round(0.03 * 100)
    assert len(a.deaths) == 2
    for d in a.deaths:
        assert d.engine in ((2, 0), (3, 0))
        assert 0.9 * 40.0 <= d.t <= 1.1 * 40.0     # death_frac +/- 10%
    assert all(w.factor >= 1.0 for w in a.windows)


# ---------------------------------------------------------------------------
# simulator chaos: liveness + conservation under any schedule
# ---------------------------------------------------------------------------

_NODE = replace(HOPPER_NODE, g=1, snic_bw=4e9)   # storage-bound point
_N_AGENTS, _N_ROUNDS = 4, 2


def _sim_run(faults=None, hedge=False, elastic=False):
    cfg = SimConfig(node=_NODE, model=DS_660B, P=2, D=2, mode="dualpath",
                    nodes_per_pe_group=1, nodes_per_de_group=1,
                    split_reads=True, kv_hbm_frac=0.04,
                    resilience=ResilienceConfig(faults=faults,
                                                hedge_reads=hedge),
                    elastic=ElasticConfig(enabled=elastic,
                                          reconfig_interval_s=4.0,
                                          reconfig_patience=2))
    trajs = [Trajectory(i, [Round(8192, 16), Round(2048, 32)])
             for i in range(_N_AGENTS)]
    return Sim(cfg, trajs).run()


def test_sim_zero_fault_schedule_is_invisible():
    """Design rule 'empty = invisible': an empty schedule with hedging
    armed must produce a bit-identical results() dict to faults=None."""
    r0 = _sim_run().results()
    r1 = _sim_run(faults=FaultSchedule(), hedge=True).results()
    assert r0 == r1
    assert r0["hedged_reads"] == 0 and r0["engine_deaths"] == 0


def test_sim_pinned_death_recovers_all_rounds():
    """One DE dies mid-run: its in-flight rounds are re-homed and every
    agent still finishes on the surviving engines."""
    fs = FaultSchedule(deaths=[EngineDeath(4.0, (3, 0))])
    sim = _sim_run(faults=fs)
    r = sim.results()
    assert r["finished_agents"] == _N_AGENTS
    assert r["finished_rounds"] == _N_AGENTS * _N_ROUNDS
    assert r["engine_deaths"] == 1
    assert r["recovered_rounds"] > 0
    assert r["n_de_final"] == 1
    # the fault-free run is strictly no slower (it lost an engine)
    assert r["sim_time"] > 0


@given(draw=st.integers(0, 1 << 16),
       snic_rate=st.floats(0.0, 0.2),
       strag_prob=st.floats(0.0, 0.5),
       flap_rate=st.floats(0.0, 0.1),
       n_deaths=st.integers(0, 1),
       hedge=st.booleans())
@settings(max_examples=20, deadline=None)
def test_chaos_sim_completes_under_any_schedule(draw, snic_rate,
                                                strag_prob, flap_rate,
                                                n_deaths, hedge):
    """The fuzz core: whatever the schedule, every admitted round
    completes, deaths never exceed the schedule, and recovery counters
    are only non-zero when a death actually fired."""
    fs = FaultSchedule.generate(
        seed=draw ^ (CHAOS_SEED << 17), duration_s=20.0, nodes=range(4),
        engines=((2, 0), (3, 0)),
        snic_fault_rate=snic_rate, snic_factor=6.0,
        straggler_prob=strag_prob, straggler_severity=8.0,
        link_flap_rate=flap_rate, link_factor=3.0,
        n_deaths=n_deaths, death_frac=0.3)
    sim = _sim_run(faults=None if fs.empty else fs, hedge=hedge)
    r = sim.results()
    assert r["finished_agents"] == _N_AGENTS
    assert r["finished_rounds"] == _N_AGENTS * _N_ROUNDS
    assert r["engine_deaths"] <= len(fs.deaths)
    if r["engine_deaths"] == 0:
        assert r["recovered_rounds"] == 0
    else:
        assert r["n_pe_final"] + r["n_de_final"] < 4
    assert r["hedge_moved_tokens"] >= 0
    if not hedge:
        assert r["hedged_reads"] == 0
    if r["hedged_reads"] == 0:
        assert r["hedge_moved_tokens"] == 0
    # every finished round carries complete latency stamps
    assert sim.slo_attainment(ttft_slo_s=1e9, tpot_slo_s=1e9) == 1.0


def test_boundaries_array_pins_window_crossing():
    """Regression pin for :meth:`FaultSchedule.boundaries_array` — both
    engines schedule one re-share per edge off this array, so its exact
    contents (sorted, deduplicated, per-resource, float64) decide where
    a flow crossing a slowdown window switches drain rate."""
    import numpy as np
    fs = FaultSchedule(windows=[
        SlowdownWindow("net", 5.0, 9.0, 2.0),
        SlowdownWindow("net", 7.0, 15.0, 1.5),   # overlaps the first
        SlowdownWindow("net", 9.0, 20.0, 3.0),   # t0 == prior t1: dedup
        SlowdownWindow("snic", 2.0, 20.0, 3.0, node=0),
    ])
    edges = fs.boundaries_array("net")
    assert edges.dtype == np.float64
    assert edges.tolist() == [5.0, 7.0, 9.0, 15.0, 20.0]
    # list form stays a view of the same truth
    assert fs.boundaries("net") == edges.tolist()
    # per-resource isolation: snic edges never leak into net
    assert fs.boundaries_array("snic").tolist() == [2.0, 20.0]
    assert fs.boundaries_array("dram").size == 0
    # the piecewise factor the edges delimit: nested windows multiply
    for t, f in ((4.9, 1.0), (5.0, 2.0), (7.5, 3.0), (9.5, 4.5),
                 (15.5, 3.0), (20.0, 1.0)):
        assert fs.net_factor(t) == f, (t, f)


def test_chaos_sim_death_under_elastic_backfill():
    """Death + elastic controller: the lost DE role is backfillable via
    a compensating flip and the run still completes every round."""
    fs = FaultSchedule(deaths=[EngineDeath(4.0, (3, 0))])
    r = _sim_run(faults=fs, elastic=True).results()
    assert r["finished_agents"] == _N_AGENTS
    assert r["engine_deaths"] == 1


# ---------------------------------------------------------------------------
# serving-runtime chaos: real bytes, real tokens
# ---------------------------------------------------------------------------
# Faults perturb when work happens, never what is computed: greedy
# decode must emit bit-identical tokens under every schedule, and the
# store/trie must end byte-identical to the fault-free run (persists
# fire exactly once even across an engine death).

jax = pytest.importorskip("jax")

from repro.configs import get_config          # noqa: E402
from repro.models import init_params          # noqa: E402
from repro.serving import ServingSystem       # noqa: E402
from repro.sim.spec import REDUCED_TEST_NODE  # noqa: E402


@pytest.fixture(scope="module")
def cfg_params():
    cfg = get_config("qwen1.5-0.5b").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _serve(cfg_params, faults=None, hedge_reads=False):
    cfg, params = cfg_params
    sys_ = ServingSystem(cfg, params, n_pe=2, n_de=2, block_tokens=16,
                         max_seq=160, de_slots=2, seed=0, pipelined=True,
                         split_reads=True, node=REDUCED_TEST_NODE,
                         resilience=ResilienceConfig(
                             faults=faults, hedge_reads=hedge_reads))
    trajs = [Trajectory(i, [Round(24, 4), Round(16, 4), Round(8, 4)])
             for i in range(4)]
    sessions = sys_.run_online(trajs, [0.0, 0.1, 0.2, 0.3])
    return sys_, sessions


@pytest.fixture(scope="module")
def baseline(cfg_params):
    sys_, sessions = _serve(cfg_params)
    return sys_.stats(), [s.context for s in sessions]


def _assert_chaos_invariants(sys_, sessions, base):
    """The invariants every serving chaos arm must satisfy."""
    base_stats, base_tokens = base
    st_ = sys_.stats()
    # 1. every admitted request completes
    assert all(s.done() for s in sessions)
    # 2. timing-only faults: token streams bit-identical
    assert [s.context for s in sessions] == base_tokens
    # 3. persists fire exactly once — a dead engine's deferred store
    #    writes never execute and the recovery round re-persists, so
    #    total bytes written and trie blocks match the fault-free run
    assert st_["store_writes"] == base_stats["store_writes"]
    assert st_["trie_blocks"] == base_stats["trie_blocks"]
    # 4. per-side byte conservation through hedge rebalances: moving a
    #    remainder between sides never creates or destroys read bytes.
    #    Recovery legitimately re-reads a restarted round's KV, so with
    #    recovered rounds the total may only grow, never shrink
    total = st_["read_bytes_pe_side"] + st_["read_bytes_de_side"]
    base_total = (base_stats["read_bytes_pe_side"] +
                  base_stats["read_bytes_de_side"])
    if st_["recovered_rounds"] == 0:
        assert total == base_total
    else:
        assert total >= base_total
    return st_


def test_serving_zero_fault_schedule_is_invisible(cfg_params, baseline):
    """Empty schedule + hedging armed: the whole stats() dict — wall
    clock included — must be identical to faults=None."""
    sys_, sessions = _serve(cfg_params, faults=FaultSchedule(),
                            hedge_reads=True)
    base_stats, base_tokens = baseline
    assert [s.context for s in sessions] == base_tokens
    st_ = sys_.stats()
    assert st_ == base_stats


def test_serving_chaos_straggle_hedged(cfg_params, baseline):
    """A degraded node-0 SNIC plus per-leg stragglers, hedging on: the
    hedge re-water-fills straggling remainders to the healthy side with
    byte-exact accounting and identical tokens."""
    fs = FaultSchedule(
        windows=[SlowdownWindow("snic", 0.0, 1e9, 8.0, node=0)],
        straggler=StragglerModel(0.4, 8.0, seed=7))
    sys_, sessions = _serve(cfg_params, faults=fs, hedge_reads=True)
    st_ = _assert_chaos_invariants(sys_, sessions, baseline)
    assert st_["hedged_reads"] > 0
    assert st_["hedge_moved_tokens"] > 0


def test_serving_chaos_de_death_recovers(cfg_params, baseline):
    """A DE dies mid-run: its in-flight rounds restart on the survivor
    from persisted KV, exactly-once persists, identical tokens."""
    fs = FaultSchedule(deaths=[EngineDeath(0.6, (2, 0))])
    sys_, sessions = _serve(cfg_params, faults=fs)
    st_ = _assert_chaos_invariants(sys_, sessions, baseline)
    assert st_["engine_deaths"] == 1
    assert st_["recovered_rounds"] > 0
    assert st_["n_de_final"] == 1
    # recovery re-reads the restarted rounds' KV: reads grow, never shrink
    assert st_["store_reads"] >= baseline[0]["store_reads"]


def test_serving_chaos_randomized_schedule(cfg_params, baseline):
    """The CI chaos matrix: a generated schedule (windows + stragglers,
    re-seeded per CHAOS_SEED) with hedging must preserve all chaos
    invariants on the real runtime."""
    fs = FaultSchedule.generate(
        seed=CHAOS_SEED, duration_s=2.0, nodes=range(2),
        snic_fault_rate=1.0, snic_factor=4.0, snic_window_s=0.5,
        link_flap_rate=0.5, link_factor=2.0, link_window_s=0.5,
        straggler_prob=0.3, straggler_severity=6.0)
    assert not fs.empty
    sys_, sessions = _serve(cfg_params, faults=fs, hedge_reads=True)
    _assert_chaos_invariants(sys_, sessions, baseline)

"""The traffic generator: determinism per seed, the same work for every
seed, and the Table-2 shape of the agentic mix."""
import numpy as np
import pytest

import traffic


def _work(mix, seed, max_len=4096, seconds=30):
    return traffic.generate(traffic.load(mix), max_len, seed, seconds)


@pytest.mark.parametrize("mix", ["agentic", "agentic.offline", "short",
                                 "short.offline"])
def test_same_seed_same_work(mix):
    a, b = _work(mix, 2**31 + 17), _work(mix, 2**31 + 17)
    assert [s.rounds for s in a.sessions] == [s.rounds for s in b.sessions]
    assert [s.key for s in a.sessions] == [s.key for s in b.sessions]
    assert a.arrivals == b.arrivals


@pytest.mark.parametrize("mix", ["agentic", "short"])
def test_seeds_reorder_the_same_work(mix):
    a, b = _work(mix, 1), _work(mix, 2)
    assert sorted(map(tuple, (tuple(s.rounds) for s in a.sessions))) == \
        sorted(map(tuple, (tuple(s.rounds) for s in b.sessions)))
    # every seed offers the same sessions at the same arrival times, all
    # of them within the window
    assert a.arrivals == b.arrivals
    assert a.arrivals[0] == 0.0 and max(a.arrivals) < 30
    assert [s.rounds for s in a.sessions] != [s.rounds for s in b.sessions]


@pytest.mark.parametrize("mix,max_len", [
    ("agentic", 4096), ("agentic", 2048), ("agentic.offline", 2048),
    ("short", 4096)])
def test_sizes_come_from_the_mix_and_fit_the_cap(mix, max_len):
    p = traffic.load(mix)
    w = traffic.generate(p, max_len, 5, 30)
    allowed = set().union(*map(set, p["sizes"].values()))
    for s in w.sessions:
        assert sum(a + g for a, g, _ in s.rounds) <= max_len
        for a, g, th in s.rounds:
            assert a in allowed and g in p["sizes"]["gen"]
            assert th >= 0


def test_agentic_matches_table2_shape():
    """The copy of the paper's Table-2 generator, capped at 4096 tokens:
    appends and gens keep the calibrated means (500, 160) within the
    cap's truncation, first rounds at most a quarter of the cap, think
    gaps with the mix's mean, and several rounds per session."""
    p = traffic.load("agentic")
    rng = np.random.default_rng(0)
    sessions = [traffic.Session(i, traffic.agent_session(rng, p, 4096))
                for i in range(400)]
    st = traffic.summary(sessions)
    assert 300 <= st["append"] <= 600
    assert 130 <= st["gen"] <= 200
    assert 0.8 <= st["think"] <= 1.2
    assert 2.0 <= st["turns"] <= 8.0
    assert all(s.rounds[0][0] <= 1024 for s in sessions)


def test_backlog_and_cap():
    w = _work("agentic.offline", 9)
    p = traffic.load("agentic.offline")
    assert len(w.sessions) == p["arrival"]["sessions"]
    assert set(w.arrivals) == {0.0}
    assert w.drain_s == 0
    import bench
    serve = {"de_slots": 4}
    assert bench.clients(p, serve, w) == round(
        p["arrival"]["outstanding_per_slot"] * 4)
    # every seed serves the backlog's head in the same order
    assert [s.rounds for s in _work("agentic.offline", 10).sessions] == \
        [s.rounds for s in w.sessions]


def test_snap_is_nearest_on_log_scale():
    assert traffic.snap(100, [64, 128]) == 128
    assert traffic.snap(90, [64, 128]) == 64
    assert traffic.snap(1e6, [64, 128]) == 128


def test_block_counts_by_hand():
    import bench
    single = {"kind": "single", "sizes": {"prompt": [64, 128],
                                          "gen": [64]}}
    assert bench.block_counts(single, 4096, 16) == (set(), {7, 11})
    sessions = {"kind": "sessions", "sizes": {"first": [64], "append": [128],
                                              "gen": [64]}}
    hits, persists = bench.block_counts(sessions, 256, 16)
    # contexts 64..256 in steps of 64: 3, 7, 11, 15 whole blocks stored;
    # a later round adds 4, 8 or 12
    assert hits == {3, 7, 11, 15}
    assert persists == {3, 4, 7, 8, 11, 12, 15}

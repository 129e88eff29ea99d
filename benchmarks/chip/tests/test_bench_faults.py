"""The whole run on the CPU at a small size, the chip check skipped:
sound, it comes out correct; with the timed path broken underneath, not.

Each fault is one the served path can have: a decode step that returns
its state unchanged (the new token's KV never written), a generated
token altered where it is produced, and the hit KV left out of the
install (prefill over a blank prefix).  On one chip there is no exchange
between chips to leave out, and decode takes no mean over its batch."""
import time

import pytest

import bench
from bench_small import small_cell

SECONDS = 3.0


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    monkeypatch.setattr(bench, "enable_compile_cache", lambda: None)


def _run(fault=None, name="qwen05b.agentic.offline", seed=2**31 + 5):
    cell = small_cell(name)
    return bench.run(cell, seed, SECONDS, False, time.perf_counter(),
                     require_tpu=False, fault=fault)


def _each_engine(system, kind, wrap):
    engines = system.des if kind == "de" else system.pes
    for eng in engines.values():
        wrap(eng)


def state_unchanged(system):
    def wrap(de):
        step = de.step

        def stale():
            before = de.state
            out = step()
            de.state = before
            return out
        de.step = stale
    _each_engine(system, "de", wrap)


def token_altered(system):
    vocab = system.cfg.vocab_size

    def wrap(de):
        step = de.step

        def altered():
            out = step()
            for er in [e for e in de.slots if e is not None] + out:
                if len(er.generated) > 1:
                    er.generated[-1] = (er.generated[-1] + 1) % vocab
            return out
        de.step = altered
    _each_engine(system, "de", wrap)


def hit_kv_left_out(system):
    def wrap(pe):
        install = pe.install_hit_kv
        pe.install_hit_kv = lambda er, payload: install(er, None)
    _each_engine(system, "pe", wrap)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"setup_s", "gen_tokens_per_s"}


@pytest.mark.parametrize("fault", [state_unchanged, token_altered,
                                   hit_kv_left_out],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(fault):
    out = _run(fault)
    assert not out["correct"], out["compared"]

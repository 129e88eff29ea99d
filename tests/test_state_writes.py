"""The served path writes its states in place: admit's slot write, the
install's per-layer placement and the prefill step each take the state
they write over (donated), and give what the copying writes gave."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.engines import kvio, runtime
from repro.engines.runtime import DecodeEngine, EngineRequest
from repro.models import forward, init_decode_state, init_params
from repro.models.model import append_step
from repro.serving import ServingSystem
from repro.sim.traces import Round, Trajectory

KEY = jax.random.PRNGKey(0)
# a dense arch and one with several layer groups (moe: dense, pre, moe)
# or a recurrent state beside its KV (hybrid)
ARCHS = ["qwen1.5-0.5b", "granite-moe-3b-a800m", "zamba2-2.7b"]
PAGED = ["qwen1.5-0.5b", "granite-moe-3b-a800m"]


def _random_state(cfg, b, s, seed=1):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    return jax.tree.map(
        lambda a: jax.random.normal(next(keys), a.shape).astype(a.dtype),
        init_decode_state(cfg, b, s))


def _equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _deleted(state):
    return [a.is_deleted() for a in jax.tree.leaves(state)]


@pytest.mark.parametrize("arch", ARCHS)
def test_put_slot_matches_slot_set_and_donates(arch):
    cfg = get_config(arch).reduced()
    axes = kvio.batch_axes_of_state(cfg)
    state = _random_state(cfg, 3, 16)
    sub = kvio.slot_get(_random_state(cfg, 3, 16, seed=2), axes, 0)
    want = kvio.slot_set(state, axes, 2, sub)
    got = kvio.put_slot(state, axes, 2, sub)
    assert all(_deleted(state))
    assert not any(_deleted(sub))
    _equal(got, want)


@pytest.mark.parametrize("arch", PAGED)
def test_put_kv_layer_matches_and_donates(arch):
    """Each layer written in place holds what the copying write gives;
    the group written is deleted, the others are left as they were, and
    ``deserialize_kv_layer`` leaves its input valid."""
    cfg = get_config(arch).reduced()
    b, cap, t0, T = 2, 24, 3, 8
    state = _random_state(cfg, b, cap)
    rows = np.random.default_rng(0).integers(
        0, 64, (kvio.n_attn_layers(cfg), T, kvio.kv_row_bytes(cfg)),
        dtype=np.uint8)
    kept = kvio.deserialize_kv(cfg, state, 1, t0, rows)
    assert not any(_deleted(state))
    np.testing.assert_array_equal(kvio.serialize_kv(cfg, kept, 1, t0,
                                                    t0 + T), rows)
    np.testing.assert_array_equal(kvio.serialize_kv(cfg, kept, 0, 0, cap),
                                  kvio.serialize_kv(cfg, state, 0, 0, cap))
    got = jax.tree.map(jnp.copy, state)
    for li in range(kvio.n_attn_layers(cfg)):
        key = kvio._kv_rows(cfg)[li][0]
        before = got
        got = kvio.put_kv_layer(cfg, got, 1, t0, li, rows[li])
        assert all(_deleted(before[key]))
        assert not any(_deleted({k: v for k, v in before.items()
                                 if k != key}))
    _equal(got, kept)


def test_put_kv_layer_one_program_for_every_layer():
    """Layer, slot and start are traced: two layers of one row count
    share a program, and only a new row count compiles another."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    assert kvio.n_attn_layers(cfg) >= 2
    row = kvio.kv_row_bytes(cfg)
    kvio._place_rows.clear_cache()
    state = init_decode_state(cfg, 1, 64)
    state = kvio.put_kv_layer(cfg, state, 0, 0, 0, np.zeros((32, row),
                                                             np.uint8))
    assert kvio._place_rows._cache_size() == 1
    state = kvio.put_kv_layer(cfg, state, 0, 16, 1, np.ones((32, row),
                                                            np.uint8))
    assert kvio._place_rows._cache_size() == 1
    kvio.put_kv_layer(cfg, state, 0, 0, 1, np.ones((16, row), np.uint8))
    assert kvio._place_rows._cache_size() == 2


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_step_last_logits_and_donates(arch):
    """The engine's prefill step gives the last row of ``forward``'s
    logits (and of ``append_step``'s full form) and deletes the state
    it was given."""
    cfg = get_config(arch).reduced()
    params = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (1, 12), 0, cfg.vocab_size)
    full, _ = forward(params, cfg, toks)
    _, st = append_step(params, cfg, toks[:, :7], init_decode_state(cfg, 1,
                                                                     24),
                        jnp.zeros((1,), jnp.int32))
    want, _ = append_step(params, cfg, toks[:, 7:], st,
                          jnp.full((1,), 7, jnp.int32))
    last, out = runtime._append_step(params, cfg, toks[:, 7:], st,
                                     jnp.full((1,), 7, jnp.int32))
    assert all(_deleted(st))
    assert last.shape == (1, 1, cfg.vocab_size)
    np.testing.assert_array_equal(np.asarray(last[0, 0]),
                                  np.asarray(want[0, -1]))
    tol = 0.25 if cfg.family == "moe" else 0.0
    assert float(jnp.max(jnp.abs(last[0, 0] - full[0, -1]))) <= tol


def test_chunk_ending_at_the_state_end():
    """A chunk that fills the state to its last position is written
    where it belongs: the rows equal those of a longer state, and the
    last logits equal ``forward``'s."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    S = 12
    toks = jax.random.randint(KEY, (1, S), 0, cfg.vocab_size)
    full, _ = forward(params, cfg, toks)
    states = []
    for cap in (S, 2 * S):
        _, st = runtime._append_step(params, cfg, toks[:, :7],
                                     init_decode_state(cfg, 1, cap),
                                     jnp.zeros((1,), jnp.int32))
        last, st = runtime._append_step(params, cfg, toks[:, 7:], st,
                                        jnp.full((1,), 7, jnp.int32))
        np.testing.assert_allclose(np.asarray(last[0, 0]),
                                   np.asarray(full[0, -1]), atol=1e-5)
        states.append(st)
    for a, b in zip(jax.tree.leaves(states[0]), jax.tree.leaves(states[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:, :, :S])


def test_prompt_longer_than_the_state_is_refused():
    cfg = get_config("qwen1.5-0.5b").reduced()
    pe = runtime.PrefillEngine(0, cfg, None, None, None, max_seq=16)
    er = EngineRequest(req=None, context_tokens=[1] * 10,
                       append_tokens=[2] * 7)
    with pytest.raises(ValueError, match="17 tokens"):
        pe.install_hit_kv(er, None)


def _pointers(state):
    return [a.unsafe_buffer_pointer() for a in jax.tree.leaves(state)]


def test_admit_writes_in_place_unless_held_elsewhere():
    """Admit writes the request's state into the decode state's own
    buffers; where something outside the engine holds the decode state,
    into a copy, and the holder keeps what it holds."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    de = DecodeEngine(0, cfg, None, None, None, None, max_seq=16, n_slots=2)

    def request(seed):
        er = EngineRequest(req=None, context_tokens=[], append_tokens=[])
        er.state, er.length, er.first_token = (
            _random_state(cfg, 1, 16, seed), 5, 7)
        return er

    before = _pointers(de.state)
    er = request(1)
    sub = er.state
    de.admit(er)
    assert _pointers(de.state) == before
    _equal(kvio.slot_get(de.state, de.axes, 0), sub)
    held = de.state
    de.admit(request(2))
    assert not any(_deleted(held))
    assert not set(_pointers(de.state)) & set(_pointers(held))
    _equal(kvio.slot_get(de.state, de.axes, 0),
           kvio.slot_get(held, de.axes, 0))
    _equal(kvio.slot_get(de.state, de.axes, 1),
           kvio.slot_get(_random_state(cfg, 1, 16, 2), de.axes, 0))


def _served(arch, mode):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, KEY)
    sys_ = ServingSystem(cfg, params, n_pe=1, n_de=1, mode=mode,
                         block_tokens=16, max_seq=160, de_slots=2, seed=0)
    trajs = [Trajectory(i, [Round(20, 4), Round(13, 3), Round(9, 4)])
             for i in range(3)]
    return [s.context for s in sys_.run_offline(trajs)]


def _copying_writes(monkeypatch):
    """The engines' writes as they were before they took their states
    over: copies of the slot write and of each layer's placement, and a
    prefill step that keeps its input and gives every position's
    logits."""
    put = kvio.put_kv_layer
    monkeypatch.setattr(kvio, "put_slot", kvio.slot_set)
    monkeypatch.setattr(kvio, "put_kv_layer", lambda cfg, state, *a: put(
        cfg, jax.tree.map(jnp.copy, state), *a))
    monkeypatch.setattr(runtime, "_append_step",
                        jax.jit(append_step, static_argnums=1))


@pytest.mark.parametrize("arch", ARCHS)
def test_served_tokens_as_with_copying_writes_and_basic(arch, monkeypatch):
    served = _served(arch, "dualpath")
    assert served == _served(arch, "basic")
    _copying_writes(monkeypatch)
    assert served == _served(arch, "dualpath")


def test_dropped_system_frees_its_decode_state_without_gc():
    """A system built as the chip benchmark builds it holds no reference
    cycle: dropping its last name frees its decode state at once, before
    the next system's is made."""
    import gc
    import weakref
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    gc.disable()
    try:
        sys_ = ServingSystem(cfg, params, n_pe=1, n_de=1, mode="dualpath",
                             block_tokens=16, max_seq=160, de_slots=2,
                             split_reads=True)
        sys_.run_offline([Trajectory(0, [Round(20, 4), Round(13, 3)])])
        engine = weakref.ref(next(iter(sys_.des.values())))
        del sys_
        assert engine() is None
    finally:
        gc.enable()

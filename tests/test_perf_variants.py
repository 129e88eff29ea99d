"""Correctness of the §Perf hillclimb variants (EXPERIMENTS.md §Perf)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.engines import kvio, runtime
from repro.models import forward, init_decode_state, init_params
from repro.models.model import decode_step
from repro.models.moe import moe_ffn
from repro.serving import ServingSystem
from repro.sim.traces import Round, Trajectory

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "gemma2-2b",
                                  "llava-next-34b"])
def test_decode_cache_carry_bitexact(arch):
    """The served decode step (jitted, state donated) gives, bitwise over
    five steps, the state of an eager, undonated decode_step and the
    logits of the same step jitted undonated (gemma2's final softcap
    rounds apart eager and jitted, donated or not); its compiled program
    aliases every KV leaf of the state to its output."""
    cfg = get_config(arch).reduced()
    params = init_params(cfg, KEY)
    kept = jax.jit(decode_step, static_argnums=1)
    b = 2
    st1 = init_decode_state(cfg, b, 16)
    st2 = init_decode_state(cfg, b, 16)
    toks = jnp.array([3, 5], jnp.int32)
    for i in range(5):
        lengths = jnp.array([i, 2 * i], jnp.int32)
        want, _ = kept(params, cfg, toks, st1, lengths)
        l1, st1 = decode_step(params, cfg, toks, st1, lengths)
        l2, st2 = runtime._decode_step(params, cfg, toks, st2, lengths)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(l2))
        for a, c in zip(jax.tree.leaves(st1), jax.tree.leaves(st2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        toks = jnp.argmax(l1, axis=-1).astype(jnp.int32)
    lowered = runtime._decode_step.lower(params, cfg, toks, st2, lengths)
    assert lowered.as_text().count("tf.aliasing_output") == len(
        jax.tree.leaves(st2))
    kv_bytes = sum(a.nbytes for a in jax.tree.leaves(st2["kv"]))
    assert lowered.compile().memory_analysis().alias_size_in_bytes \
        >= kv_bytes


def test_decode_rows_over_a_mesh_match():
    """The dense decode writes its rows by one scatter over a mesh and
    through lane windows off one: both give the same logits and state."""
    from jax.sharding import Mesh
    from repro.models.sharding import use_mesh
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    st1 = init_decode_state(cfg, 2, 16)
    st2 = init_decode_state(cfg, 2, 16)
    toks = jnp.array([3, 5], jnp.int32)
    for i in range(5):
        lengths = jnp.array([i, 2 * i + 1], jnp.int32)
        l1, st1 = decode_step(params, cfg, toks, st1, lengths)
        with use_mesh(mesh):
            l2, st2 = decode_step(params, cfg, toks, st2, lengths)
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        toks = jnp.argmax(l1, axis=-1).astype(jnp.int32)
    for a, c in zip(jax.tree.leaves(st1), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def _spy_on_decode(monkeypatch):
    """Record, per served decode step, whether the state it was given
    was taken over (its buffers deleted), and count the state leaves the
    engine copied first."""
    donated, copied = [], []
    step, copy = runtime._decode_step, jnp.copy

    def spy(params, cfg, toks, state, lengths):
        out = step(params, cfg, toks, state, lengths)
        donated.append(all(a.is_deleted() for a in jax.tree.leaves(state)))
        return out

    def counted_copy(a, *args, **kw):
        copied.append(a.shape)
        return copy(a, *args, **kw)

    monkeypatch.setattr(runtime, "_decode_step", spy)
    monkeypatch.setattr(jnp, "copy", counted_copy)
    return donated, copied


def test_decode_engine_cycles_over_donated_state(monkeypatch):
    """Two slots, three two-round sessions: the decode engine admits,
    decodes, persists and admits again, each step donating its state.
    Every persisted block holds the KV a cache-free forward computes, so
    persist read the state each step left, and no step touched a
    deleted buffer (that raises)."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    donated, copied = _spy_on_decode(monkeypatch)
    sys_ = ServingSystem(cfg, params, block_tokens=8, max_seq=96,
                         de_slots=2, seed=0)
    sessions = sys_.run_offline(
        [Trajectory(i, [Round(12 + 4 * i, 5), Round(9, 6)])
         for i in range(3)])
    assert len(donated) > 10 and all(donated) and not copied
    for sess in sessions:
        hit, refs = sys_.trie.match(sess.context)
        assert hit >= 16
        _, state = forward(params, cfg,
                           jnp.asarray([sess.context], jnp.int32),
                           return_state=True)
        got = np.concatenate([sys_.store.peek(r) for r in refs], axis=1)
        np.testing.assert_array_equal(
            got, kvio.serialize_kv(cfg, state, 0, 0, hit))


def test_decode_state_held_outside_is_kept(monkeypatch):
    """A state that something outside the engine also holds is stepped
    as a copy: the holder can still read it, unchanged, after the step."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    donated, copied = _spy_on_decode(monkeypatch)
    sys_ = ServingSystem(cfg, params, block_tokens=8, max_seq=64,
                         de_slots=2, seed=0)
    de = next(iter(sys_.des.values()))
    step = de.step

    def holding_step():
        before = de.state
        copy = jax.tree.map(np.asarray, before)
        out = step()
        for a, c in zip(jax.tree.leaves(before), jax.tree.leaves(copy)):
            np.testing.assert_array_equal(np.asarray(a), c)
        return out

    de.step = holding_step
    sys_.run_offline([Trajectory(0, [Round(12, 4)])])
    n_leaves = len(jax.tree.leaves(de.state))
    assert len(donated) == 3 and len(copied) == 3 * n_leaves


def _moe_fixture():
    cfg = get_config("granite-moe-3b-a800m").reduced()
    params = init_params(cfg, KEY)
    p = jax.tree.map(lambda a: a[0], params["super_blocks"]["moe"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 16, cfg.d_model),
                          jnp.float32)
    return cfg, p, x


def test_moe_dense_matches_ragged():
    """dense all-experts == dropless ragged (exact routing, no capacity)."""
    cfg, p, x = _moe_fixture()
    y1 = moe_ffn(p, cfg, x, impl="ragged")
    y2 = moe_ffn(p, cfg, x, impl="dense")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)


def test_moe_ep_local_matches_ragged_without_drops():
    cfg, p, x = _moe_fixture()
    y1 = moe_ffn(p, cfg, x, impl="ragged")
    y2 = moe_ffn(p, cfg, x, impl="ep_local", capacity_factor=1000.0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)


def test_remat_policies_equivalent_loss():
    from repro.training import make_train_step
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    batch = jax.random.randint(KEY, (2, 17), 0, cfg.vocab_size)
    losses = []
    for remat in ("full", "dots", False):
        opt_init, ts = make_train_step(cfg, n_microbatches=1, remat=remat)
        _, _, loss = ts(params, opt_init(params), batch)
        losses.append(float(loss))
    assert max(losses) - min(losses) < 1e-3, losses

"""End-to-end serving integration: dual-path loading with real KV bytes.

The decisive test: multi-turn generation through the full system (trie
hits, FullBlock reads on either path, chunked prefill, PD transfer,
slot-batched decode, block persistence) must produce the SAME tokens as
a cache-free reference that re-prefills the whole prompt every round.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import TierConfig
from repro.engines import kvio
from repro.models import decode_step, forward, init_decode_state, init_params
from repro.serving import ServingSystem
from repro.sim.traces import Round, Trajectory

KEY = jax.random.PRNGKey(0)


def reference_generate(cfg, params, rounds, rng):
    """Cache-free oracle: full forward per round, greedy decode."""
    context = []
    all_gen = []
    for rnd in rounds:
        append = list(rng.integers(2, cfg.vocab_size, size=rnd.append))
        prompt = context + append
        toks = jnp.asarray([prompt], jnp.int32)
        logits, _ = forward(params, cfg, toks)
        first = int(jnp.argmax(logits[0, -1]))
        gen = [first]
        st = init_decode_state(cfg, 1, len(prompt) + rnd.gen + 4)
        _, st = __import__("repro.models.model", fromlist=["append_step"]) \
            .append_step(params, cfg, toks, st, jnp.zeros((1,), jnp.int32))
        cur = first
        for i in range(rnd.gen - 1):
            lg, st = decode_step(params, cfg, jnp.asarray([cur], jnp.int32),
                                 st, jnp.asarray([len(prompt) + i], jnp.int32))
            cur = int(jnp.argmax(lg[0]))
            gen.append(cur)
        all_gen.append(gen)
        context = prompt + gen
    return all_gen


@pytest.mark.parametrize("mode", ["dualpath", "basic", "split", "tiered",
                                  "tiered-small"])
def test_generation_with_cache_reuse_matches_reference(mode):
    """tiered: big DRAM tier + think-time prefetch (round-start reads
    served from node DRAM); tiered-small: a tier of a few blocks, so
    eviction churns constantly mid-trajectory.  Generation must stay
    bit-identical to the cache-free reference in every arm."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    rounds = [Round(20, 4), Round(13, 3), Round(9, 4)]
    traj = Trajectory(0, rounds)
    tier_kw = {}
    if mode == "tiered":
        tier_kw = dict(tier=TierConfig(dram_tier_bytes=1 << 30,
                                       prefetch=True))
    elif mode == "tiered-small":
        tier_kw = dict(tier=TierConfig(dram_tier_bytes=32768, prefetch=True,
                                       tier_policy="agentic-ttl"))
    sys_ = ServingSystem(cfg, params, n_pe=1, n_de=1,
                         mode="basic" if mode == "basic" else "dualpath",
                         split_reads=(mode == "split"),
                         block_tokens=16, max_seq=160, de_slots=2, seed=0,
                         **tier_kw)
    sessions = sys_.run_offline([traj])
    assert sessions[0].rounds_done == 3
    ref = reference_generate(cfg, params, rounds,
                             np.random.default_rng(1000))
    ctx = sessions[0].context
    # reconstruct per-round gens from the final context? easier: compare
    # final context suffix — instead regenerate via the recorded sessions
    # by replaying; simplest strong check: final context equality.
    ref_context = []
    rng = np.random.default_rng(1000)
    for rnd, gen in zip(rounds, ref):
        append = list(rng.integers(2, cfg.vocab_size, size=rnd.append))
        ref_context = ref_context + append + gen
    assert ctx == ref_context, (
        f"cache-reuse generation diverged from cache-free reference "
        f"({mode}); first mismatch at "
        f"{next(i for i, (a, b) in enumerate(zip(ctx, ref_context)) if a != b)}")


def test_persisted_blocks_hold_the_forward_kv():
    """Round 1's prompt + gen ends exactly on a block boundary.  The last
    generated token is never fed back through decode, so its KV row does
    not exist yet: that block must wait for round 2, and every block the
    trie serves holds exactly the KV a cache-free forward computes."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    sys_ = ServingSystem(cfg, params, block_tokens=16, max_seq=160,
                         de_slots=2, seed=0)
    sessions = sys_.run_offline([Trajectory(0, [Round(12, 4),
                                                Round(16, 4)])])
    ctx = sessions[0].context
    hit, refs = sys_.trie.match(ctx)
    assert hit == 32
    _, state = forward(params, cfg, jnp.asarray([ctx], jnp.int32),
                       return_state=True)
    want = kvio.serialize_kv(cfg, state, 0, 0, hit)
    got = np.concatenate([sys_.store.peek(r) for r in refs], axis=1)
    np.testing.assert_array_equal(got, want)


def test_multi_agent_multi_engine():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    trajs = [Trajectory(i, [Round(18, 3), Round(12, 3)]) for i in range(5)]
    sys_ = ServingSystem(cfg, params, n_pe=2, n_de=2, mode="dualpath",
                         block_tokens=16, max_seq=128, de_slots=4, seed=0)
    sessions = sys_.run_offline(trajs)
    assert all(s.rounds_done == 2 for s in sessions)
    st = sys_.stats()
    assert st["store_reads"] > 0          # round 2 hit the cache
    assert st["trie_blocks"] > 0
    assert st["decode_steps"] > 0


def test_dualpath_uses_both_sides_under_load():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    trajs = [Trajectory(i, [Round(24, 3), Round(16, 3), Round(8, 3)])
             for i in range(6)]
    sys_ = ServingSystem(cfg, params, n_pe=1, n_de=1, mode="dualpath",
                         block_tokens=16, max_seq=160, de_slots=8, seed=0)
    sys_.run_offline(trajs)
    st = sys_.stats()
    assert st["read_bytes_de_side"] > 0, "storage->DE path never used"
    assert st["read_bytes_pe_side"] > 0


def test_split_reads_use_both_sides_within_one_request():
    """§6.1 future work executed for real: with split_reads the hit
    FullBlocks of a single request are read partly on the PE side and
    partly on the DE side (block-granular partition), and generation
    still matches — asserted via the split arm of the reference test
    above; here we check the split actually happened."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    trajs = [Trajectory(i, [Round(32, 3), Round(16, 3)]) for i in range(3)]
    sys_ = ServingSystem(cfg, params, n_pe=1, n_de=1, mode="dualpath",
                         split_reads=True, block_tokens=16, max_seq=160,
                         de_slots=4, seed=0)
    sys_.run_offline(trajs)
    st = sys_.stats()
    assert st["split_reads"] > 0, "no request was split"
    assert st["read_bytes_pe_side"] > 0
    assert st["read_bytes_de_side"] > 0


def test_basic_mode_never_uses_de_side():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    trajs = [Trajectory(i, [Round(20, 3), Round(12, 3)]) for i in range(4)]
    sys_ = ServingSystem(cfg, params, n_pe=1, n_de=1, mode="basic",
                         block_tokens=16, max_seq=128, de_slots=4, seed=0)
    sys_.run_offline(trajs)
    assert sys_.stats()["read_bytes_de_side"] == 0


def test_tiered_serving_serves_hits_from_dram_and_conserves():
    """With a warm DRAM tier the round-start reads bypass the store (=
    the storage NIC): after round 1 every hit byte is a DRAM hit, and
    dram-served + store-read (SNIC) bytes == total hit bytes."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_params(cfg, KEY)
    trajs = [Trajectory(i, [Round(24, 3), Round(16, 3), Round(8, 3)])
             for i in range(3)]
    sys_ = ServingSystem(cfg, params, n_pe=1, n_de=1, mode="dualpath",
                         block_tokens=16, max_seq=160, de_slots=4, seed=0,
                         tier=TierConfig(dram_tier_bytes=1 << 30,
                                         prefetch=True))
    sys_.run_offline(trajs)
    st = sys_.stats()
    assert st["dram_hit_bytes"] > 0, "tier never served a hit"
    # conservation: every hit byte was served from DRAM or the store,
    # and the per-side counters partition exactly along that line
    # (read_bytes_* is SNIC traffic only, matching the sim's convention)
    assert st["dram_hit_bytes"] == (st["dram_bytes_pe_side"] +
                                    st["dram_bytes_de_side"])
    assert st["tier_miss_bytes"] == (st["read_bytes_pe_side"] +
                                     st["read_bytes_de_side"])
    # with ample capacity nothing is evicted and, past the cold start,
    # nothing needs the SNIC: all store reads come from tier misses
    assert st["tier_evicted_bytes"] == 0
    assert st["store_reads"] == st["tier_miss_bytes"] + \
        st["tier_prefetch_bytes"]
    for tier in sys_.tiers.values():
        assert tier.pinned_bytes() == 0      # all read leases released


def test_ssm_state_blob_reuse():
    cfg = get_config("mamba2-1.3b").reduced()
    params = init_params(cfg, KEY)
    sys_ = ServingSystem(cfg, params, n_pe=1, n_de=1, max_seq=128,
                         de_slots=2, seed=0)
    sessions = sys_.run_offline([Trajectory(0, [Round(16, 3), Round(8, 3)])])
    assert sessions[0].rounds_done == 2
    assert sys_.blob_store.bytes_read > 0, "state blob never reused"


def test_mla_arch_serving():
    cfg = get_config("ds27b").reduced()
    params = init_params(cfg, KEY)
    sys_ = ServingSystem(cfg, params, n_pe=1, n_de=1, max_seq=128,
                         block_tokens=16, de_slots=2, seed=0)
    sessions = sys_.run_offline([Trajectory(0, [Round(18, 3), Round(10, 3)])])
    assert sessions[0].rounds_done == 2
    assert sys_.stats()["store_reads"] > 0

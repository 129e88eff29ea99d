"""Bring-up smoke: serve qwen1.5-0.5b at its published widths on one TPU.

    python chip_smoke.py

One process, no subprocesses.  It serves a few multi-round agent
trajectories through ``repro.launch.serve`` (trie lookup -> dual-path
read -> layerwise KV install -> prefill -> PD transfer -> decode ->
persist) on random weights from a fixed seed, then checks:

  (a) every round completes with its requested tokens, and no logit of
      an engine step or of the reference is a NaN or an inf;
  (b) the store served prefix hits over both read paths (split reads),
      and fewer tokens were prefilled than the cache-free prompt total;
  (c) the same trajectories served with ``mode="basic"`` give identical
      tokens;
  (d) every served token is the argmax of a cache-free reference, or
      within ``BF16_TIE_ULPS`` of it (see there).

It prints what it measured, then as its last line
``{"ok": true, "device": {...}}``.  It exits non-zero, without that
line, when a check fails or when JAX finds no TPU.
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import numpy as np                                  # noqa: E402

from repro.configs import get_config                # noqa: E402
from repro.engines import runtime                   # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import serve                # noqa: E402
from repro.models import forward, init_params       # noqa: E402
from repro.sim.traces import Round, Trajectory      # noqa: E402

ARCH = "qwen1.5-0.5b"
SEED = 0
AGENTS = 4
# round 1 carries the task prompt; later rounds append a tool result
ROUNDS = (Round(320, 24), Round(48, 24), Round(48, 24))
MAX_SEQ = 1024
DE_SLOTS = 8

# (d)'s allowance.  Logits leave the tied-embedding matmul in bf16 (8
# significant bits) before the f32 cast (models/model.py
# logits_from_hidden).  The served path (prefix KV installed from
# storage bytes, append attention over a padded cache, batched decode)
# rounds in another order than one cache-free forward, so the two may
# disagree by a few bf16 ulps at the top logit.  A served token within
# that band of the reference's top logit is a near-tie broken the other
# way; a wider gap means the served path computed on wrong data.
BF16_TIE_ULPS = 4


def trajectories():
    return [Trajectory(i, list(ROUNDS)) for i in range(AGENTS)]


_all_finite = jax.jit(lambda x: jnp.isfinite(x).all())


def watch_logits():
    """Wrap the engines' prefill and decode steps so that each step's
    logits are checked for NaN and inf on the device.  Returns the list
    the per-step flags go to; they reach the host once, at the end.

    ``jax_debug_nans`` would copy every output of every step to the
    host, the whole decode state included (0.8 GB per decode step at
    these widths), and then the smoke measures those copies.
    """
    flags = []

    def wrap(step):
        def checked(*args):
            logits, state = step(*args)
            flags.append(_all_finite(logits))
            return logits, state
        return checked

    runtime._append_step = wrap(runtime._append_step)
    runtime._decode_step = wrap(runtime._decode_step)
    return flags


def generated_positions():
    """Stream positions of the generated tokens, round after round."""
    pos, p = [], 0
    for rnd in ROUNDS:
        p += rnd.append
        pos.extend(range(p, p + rnd.gen))
        p += rnd.gen
    return np.asarray(pos, np.int32)


def cache_free_prompt_tokens():
    total, ctx = 0, 0
    for rnd in ROUNDS:
        total += ctx + rnd.append
        ctx += rnd.append + rnd.gen
    return AGENTS * total


def bf16_ulp(x):
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(x), 1e-30))) - 7)


def reference_gaps(cfg, params, sessions):
    """Cache-free reference, teacher-forced on each served stream.

    One causal forward over a trajectory's whole served context gives,
    at every position, the logits that re-prefilling that round's prompt
    and greedy decoding up to it would give.  Returns, per served
    generated token, the reference's top logit minus its logit for the
    served token (0 where the served token is the argmax), and the
    reference top logit; also whether every scored row was finite.
    """
    @jax.jit
    def score(params, tokens, pred, served):
        rows = forward(params, cfg, tokens)[0][0, pred]       # (n, V)
        top = rows.max(-1)
        chosen = jnp.take_along_axis(rows, served[:, None], -1)[:, 0]
        return top, chosen, jnp.isfinite(rows).all()

    gen_pos = generated_positions()
    gaps, tops, finite = [], [], True
    for s in sessions:
        ctx = np.asarray([int(t) for t in s.context], np.int32)
        padded = np.zeros((1, MAX_SEQ), np.int32)
        padded[0, :len(ctx)] = ctx
        top, chosen, ok = score(params, jnp.asarray(padded),
                                jnp.asarray(gen_pos - 1),
                                jnp.asarray(ctx[gen_pos]))
        top, chosen = np.asarray(top), np.asarray(chosen)
        gaps.append(top - chosen)
        tops.append(top)
        finite &= bool(ok)
    return np.concatenate(gaps), np.concatenate(tops), finite


def run(cfg, counts):
    """Serve, check (a)-(d); returns ({check: passed}, report lines).

    ``counts["compile_requests"]`` is kept current by the caller; each
    phase's host wall time and compile requests are reported.
    """
    phases, mark = [], [time.perf_counter(), counts["compile_requests"]]

    def phase(name):
        now, n = time.perf_counter(), counts["compile_requests"]
        phases.append(f"{name} {now - mark[0]!r} s / {n - mark[1]} compiles")
        mark[:] = [now, n]

    params = jax.block_until_ready(
        init_params(cfg, jax.random.PRNGKey(SEED)))
    phase("init_params")
    step_flags = watch_logits()
    kw = dict(max_seq=MAX_SEQ, de_slots=DE_SLOTS, split_reads=True)
    system, sessions = serve(cfg, params, trajectories(), **kw)
    st = system.stats()
    streams = [[int(t) for t in s.context] for s in sessions]
    del system                      # frees its decode state before basic
    phase("serve_dualpath")
    _, basic = serve(cfg, params, trajectories(), mode="basic", **kw)
    phase("serve_basic")
    gaps, tops, finite = reference_gaps(cfg, params, sessions)
    tol = BF16_TIE_ULPS * bf16_ulp(tops)
    phase("reference")

    want_len = sum(r.append + r.gen for r in ROUNDS)
    prompt_total = cache_free_prompt_tokens()
    basic_streams = [[int(t) for t in s.context] for s in basic]
    steps_finite = bool(jnp.stack(step_flags).all())
    checks = {
        "a_rounds_complete_and_finite":
            all(s.rounds_done == len(ROUNDS) for s in sessions)
            and all(len(c) == want_len for c in streams)
            and st["gen_tokens"] == AGENTS * sum(r.gen for r in ROUNDS)
            and steps_finite and finite,
        "b_both_paths_and_reuse":
            st["store_reads"] > 0 and st["read_bytes_pe_side"] > 0
            and st["read_bytes_de_side"] > 0
            and st["prefill_tokens"] < prompt_total,
        "c_basic_mode_identical": streams == basic_streams,
        "d_matches_cache_free_reference": bool(np.all(gaps <= tol)),
    }
    n_basic_diff = sum(a != b for s, t in zip(streams, basic_streams)
                       for a, b in zip(s, t))
    n_diff = int(np.count_nonzero(gaps > 0))
    worst = int(np.argmax(gaps / tol))
    report = [
        f"served: {sum(s.rounds_done for s in sessions)} rounds, "
        f"{st['gen_tokens']} generated tokens, {AGENTS} agents "
        f"(mode=dualpath, split_reads, n_pe=1, n_de=1)",
        f"logits finite: {steps_finite} over {len(step_flags)} engine "
        f"steps (dualpath and basic), {finite} in the reference",
        f"prefill_tokens: {st['prefill_tokens']} "
        f"(cache-free prompt total {prompt_total})",
        f"store_reads_bytes: {st['store_reads']}  "
        f"read_bytes_pe_side: {st['read_bytes_pe_side']}  "
        f"read_bytes_de_side: {st['read_bytes_de_side']}  "
        f"split_reads: {st['split_reads']}",
        f"basic mode: {n_basic_diff} of {len(streams) * want_len} stream "
        f"tokens differ from dualpath",
        f"reference: {len(gaps) - n_diff}/{len(gaps)} served tokens are "
        f"its argmax; largest gap {float(gaps[worst])!r} "
        f"({float(gaps[worst] / bf16_ulp(tops[worst]))!r} bf16 ulps) at "
        f"top logit {float(tops[worst])!r} (allowed {float(tol[worst])!r})",
        "host_wall_by_phase_incl_compile: " + "; ".join(phases),
    ]
    return checks, report


def main():
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's backend is {backend!r}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    counts = {"compile_requests": 0, "cache_hits": 0}

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compile_requests"] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    checks, report = run(get_config(ARCH), counts)
    dev = jax.devices()[0]
    print(f"device_kind: {dev.device_kind}")
    for line in report:
        print(line)
    for name, passed in checks.items():
        print(f"check {name}: {'pass' if passed else 'FAIL'}")
    print(f"compile_requests: {counts['compile_requests']}  "
          f"persistent_cache_hits: {counts['cache_hits']}  "
          f"(cache dir {cache_dir})")
    print(f"host_wall_s_incl_compile: {time.perf_counter() - T0!r}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    if not all(checks.values()):
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

import os
# 512 fake CPU devices; pinned to the CPU so this never takes an
# attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each runnable cell this lowers the appropriate step —
``train_step`` (train_4k), ``prefill_step`` (prefill_32k) or
``serve_step`` (decode_32k / long_500k) — onto the production mesh
(16x16 single-pod, 2x16x16 multi-pod), compiles it, and extracts:

  * memory_analysis()   — proves the cell fits per-device HBM,
  * cost_analysis()     — HLO FLOPs / bytes for §Roofline,
  * collective bytes    — parsed from the compiled HLO (loop-aware).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun.json
"""
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, SHAPE_ORDER, get_config, shape_supported
from repro.configs.base import ARCH_IDS, ModelConfig
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.models import abstract_params, decode_step, forward, init_decode_state
from repro.models.sharding import param_partition_specs, use_mesh
from repro.roofline.hlo import parse_hlo_metrics, xla_cost_analysis
from repro.training.train import make_train_step

MOE_IMPL = "ep"


def _sds(shape, dtype, mesh, spec):
    from repro.models.sharding import sanitize_spec
    spec = sanitize_spec(shape, spec, mesh)
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _batch_spec(mesh, *rest):
    return P(batch_axes(mesh), *rest)


# ---------------------------------------------------------------------------
# input specs
# ---------------------------------------------------------------------------


def decode_state_specs(cfg: ModelConfig, mesh, batch: int, seq_axis="auto"):
    """PartitionSpec tree matching init_decode_state(cfg, batch, S).

    ``seq_axis``: 'auto' (default) shards KV heads over ``model`` when the
    head count divides the axis, else falls back to sharding the KV
    *sequence* dim (context-parallel cache with distributed softmax).
    §Perf iteration 0: without the fallback, every arch with
    kv_heads ∤ 16 leaves the model axis idle on its decode cache and the
    decode_32k cells exceed 16 GB/chip (see results/dryrun_baseline_v0).
    Pass None to disable (v0 behaviour) or 'model' to force seq sharding.
    """
    b_ax = batch_axes(mesh) if batch % (
        2 * 16 if "pod" in mesh.axis_names else 16) == 0 else None
    if b_ax is None and batch >= 16 and batch % 16 == 0:
        b_ax = ("data",)    # shard over data only

    model_size = mesh.shape["model"]
    if seq_axis == "auto":
        heads_fit = cfg.n_kv_heads and cfg.n_kv_heads % model_size == 0
        seq_axis = None if heads_fit else "model"
        if cfg.attn_variant == "mla":
            seq_axis = "model"      # latent has no head dim to shard

    def kv(n_stack):
        lead = (None,) * len(n_stack)
        head_ax = "model" if seq_axis != "model" else None
        return {"k": P(*lead, b_ax, seq_axis, head_ax, None),
                "v": P(*lead, b_ax, seq_axis, head_ax, None)}

    fam = cfg.family
    if fam in ("dense", "vlm"):
        return {"kv": kv((0,))}
    if fam == "moe":
        m = cfg.moe
        out = {}
        if cfg.attn_variant == "mla":
            def mk(ns):
                lead = (None,) * len(ns)
                return {"c": P(*lead, b_ax, seq_axis, None),
                        "krope": P(*lead, b_ax, seq_axis, None)}
            if m.first_k_dense:
                out["dense"] = mk((0,))
            out["moe"] = mk((0,))
            if m.period > 1:
                out["pre"] = mk((0, 0))
            return out
        if m.first_k_dense:
            out["dense"] = kv((0,))
        out["moe"] = kv((0,))
        if m.period > 1:
            out["pre"] = kv((0, 0))
        return out
    if fam == "ssm":
        return {"mamba": {
            "ssm": P(None, b_ax, "model", None, None),
            "conv_x": P(None, b_ax, None, "model"),
            "conv_B": P(None, b_ax, None, None),
            "conv_C": P(None, b_ax, None, None),
        }}
    if fam == "hybrid":
        return {
            "mamba": {
                "ssm": P(None, None, b_ax, "model", None, None),
                "conv_x": P(None, None, b_ax, None, "model"),
                "conv_B": P(None, None, b_ax, None, None),
                "conv_C": P(None, None, b_ax, None, None),
            },
            # batch=1 long-context: shard the KV sequence over data
            # (context-parallel cache) when batch cannot shard
            "shared": {"k": P(None, b_ax, "data" if b_ax is None else None,
                              "model", None),
                       "v": P(None, b_ax, "data" if b_ax is None else None,
                              "model", None)},
        }
    raise ValueError(fam)


def input_specs(arch: str, shape_name: str, mesh, state_seq_axis=None):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    gb, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        if cfg.frontend_embed_dim:
            return {"batch": {
                "inputs": _sds((gb, s, cfg.frontend_embed_dim), jnp.bfloat16,
                               mesh, _batch_spec(mesh, None, None)),
                "labels": _sds((gb, s), jnp.int32, mesh,
                               _batch_spec(mesh, None)),
            }}
        return {"batch": {"tokens": _sds((gb, s), jnp.int32, mesh,
                                         _batch_spec(mesh, None))}}
    if shape.kind == "prefill":
        if cfg.frontend_embed_dim:
            return {"inputs": _sds((gb, s, cfg.frontend_embed_dim),
                                   jnp.bfloat16, mesh,
                                   _batch_spec(mesh, None, None))}
        return {"inputs": _sds((gb, s), jnp.int32, mesh,
                               _batch_spec(mesh, None))}
    # decode
    state = init_decode_state(cfg, gb, s, abstract=True)
    sspecs = decode_state_specs(cfg, mesh, gb,
                                seq_axis=state_seq_axis or "auto")
    b_ax = None if gb < 16 else batch_axes(mesh)
    state_sds = jax.tree.map(
        lambda sds, spec: _sds(sds.shape, sds.dtype, mesh, spec),
        state, sspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return {
        "tokens": _sds((gb,), jnp.int32, mesh, P(b_ax)),
        "state": state_sds,
        "lengths": _sds((gb,), jnp.int32, mesh, P(b_ax)),
    }


def param_specs(cfg: ModelConfig, mesh, overrides=None, profile=None):
    import dataclasses
    if profile:
        cfg = dataclasses.replace(cfg, sharding_profile=profile)
    pspecs = param_partition_specs(cfg, mesh, overrides)
    return jax.tree.map(
        lambda sds, spec: _sds(sds.shape, sds.dtype, mesh, spec),
        abstract_params(cfg), pspecs)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def opt_state_specs(cfg: ModelConfig, mesh, params_sds, overrides=None):
    from repro.training.optimizer import make_optimizer
    opt_init, _ = make_optimizer(cfg.optimizer, cfg.opt_state_dtype)
    opt_abs = jax.eval_shape(opt_init, params_sds)
    pspecs = param_partition_specs(cfg, mesh, overrides)
    if cfg.optimizer == "adamw":
        specs = {"m": pspecs, "v": pspecs, "step": P()}
    else:  # adafactor: factored state is small — replicate
        specs = jax.tree.map(lambda _: P(), opt_abs["fac"])
        specs = {"fac": specs, "step": P()}
    return jax.tree.map(
        lambda sds, spec: _sds(sds.shape, sds.dtype, mesh, spec),
        opt_abs, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def build_cell(arch: str, shape_name: str, mesh, variant=None):
    """Returns (jitted_fn, arg_sds_tuple).

    ``variant`` (hillclimbing knobs, all optional):
      weight_overrides  — logical-axis -> mesh-axis rule overrides
      profile           — replace the arch's sharding profile entirely
      act_overrides     — activation logical-axis rule overrides
      microbatches      — grad-accum depth for train cells
      remat             — False | 'full' | 'dots' | 'dots_no_batch'
      moe_impl          — 'ep' | 'ragged'
      capacity_factor   — MoE EP capacity factor
      state_seq_axis    — mesh axis to shard decode KV seq dim over
    """
    v = variant or {}
    overrides = v.get("weight_overrides")
    moe_impl = v.get("moe_impl", MOE_IMPL)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    p_sds = param_specs(cfg, mesh, overrides, profile=v.get("profile"))
    ins = input_specs(arch, shape_name, mesh,
                      state_seq_axis=v.get("state_seq_axis"))

    if shape.kind == "train":
        _, train_step = make_train_step(
            cfg, moe_impl=moe_impl,
            n_microbatches=v.get("microbatches"),
            remat=v.get("remat", "full"))
        import dataclasses
        ocfg = dataclasses.replace(cfg, sharding_profile=v["profile"]) \
            if v.get("profile") else cfg
        o_sds = opt_state_specs(ocfg, mesh, p_sds, overrides)
        fn = jax.jit(train_step, donate_argnums=(0, 1))
        return fn, (p_sds, o_sds, ins["batch"])

    if shape.kind == "prefill":
        ret_state = cfg.supports_decode
        pmb = v.get("prefill_microbatch")

        def _fwd(params, inputs):
            return forward(params, cfg, inputs, return_state=ret_state,
                           moe_impl=moe_impl, last_only=True,
                           capacity_factor=v.get("capacity_factor", 1.25))

        if pmb:
            from repro.engines.kvio import batch_axes_of_state

            def prefill_step(params, inputs):
                gb = inputs.shape[0]
                micro = inputs.reshape((pmb, gb // pmb) + inputs.shape[1:])
                outs = jax.lax.map(lambda inp: _fwd(params, inp), micro)
                logits, state = outs
                logits = logits.reshape((gb,) + logits.shape[2:])
                if not ret_state:
                    return logits
                axes = batch_axes_of_state(cfg)
                state = jax.tree.map(
                    lambda a, ax: jnp.moveaxis(a, 0, ax).reshape(
                        a.shape[1:ax + 1] + (gb,) + a.shape[ax + 2:]),
                    state, axes)
                return logits, state
        else:
            def prefill_step(params, inputs):
                out = _fwd(params, inputs)
                return out if ret_state else out[0]

        fn = jax.jit(prefill_step)
        return fn, (p_sds, ins["inputs"])

    def serve_step(params, tokens, state, lengths):
        return decode_step(params, cfg, tokens, state, lengths,
                           moe_impl=moe_impl,
                           capacity_factor=v.get("capacity_factor", 1.25))

    fn = jax.jit(serve_step, donate_argnums=(2,))
    return fn, (p_sds, ins["tokens"], ins["state"], ins["lengths"])


# ---------------------------------------------------------------------------
# run
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             variant=None, verbose: bool = True,
             hlo_dir: str | None = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_supported(cfg, shape)
    if not ok:
        return dict(arch=arch, shape=shape_name,
                    mesh="multi" if multi_pod else "single",
                    status="skipped", reason=why)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    v = variant or {}
    with use_mesh(mesh, v.get("profile", cfg.sharding_profile),
                  act_overrides=v.get("act_overrides")):
        fn, args = build_cell(arch, shape_name, mesh, variant)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = xla_cost_analysis(compiled)   # list-vs-dict across JAX versions
    hlo = compiled.as_text()
    if hlo_dir:
        import gzip
        os.makedirs(hlo_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}"
        with gzip.open(os.path.join(hlo_dir, tag + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    # loop-aware per-device metrics (XLA's cost_analysis counts while
    # bodies once — see repro.roofline.hlo); raw numbers kept for reference
    metrics = parse_hlo_metrics(hlo)
    out = dict(
        arch=arch, shape=shape_name,
        mesh="multi" if multi_pod else "single",
        status="ok",
        n_devices=mesh.size,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        flops=metrics.get("flops", 0.0),
        bytes_accessed=metrics.get("bytes", 0.0),
        collective_bytes=metrics.get("collective_bytes", 0.0),
        collectives={k: v for k, v in metrics.items()
                     if k in ("all-gather", "all-reduce", "reduce-scatter",
                              "all-to-all", "collective-permute") and v},
        xla_cost_flops=cost.get("flops", 0.0) if cost else 0.0,
        xla_cost_bytes=cost.get("bytes accessed", 0.0) if cost else 0.0,
    )
    if mem is not None:
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                out[k] = v
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × "
              f"{'2x16x16' if multi_pod else '16x16'}: OK "
              f"(lower {out['lower_s']}s, compile {out['compile_s']}s, "
              f"GFLOPs {out['flops']/1e9:.1f}, "
              f"coll {out['collective_bytes']/1e9:.3f} GB)")
        print(f"  memory_analysis: "
              f"{ {k: v for k, v in out.items() if k.endswith('bytes')} }")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--hlo-dir", default=None,
                    help="save gzipped compiled HLO per cell (re-analysis)")
    args = ap.parse_args(argv)

    archs = list(ARCH_IDS) if (args.all or args.arch is None) else [args.arch]
    shapes = SHAPE_ORDER if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    results = []
    done = {}
    if args.out and os.path.exists(args.out):
        try:
            for r in json.load(open(args.out)):
                done[(r["arch"], r["shape"], r["mesh"])] = r
        except Exception:
            done = {}

    def flush():
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out + ".tmp", "w") as f:
                json.dump(results, f, indent=1)
            os.replace(args.out + ".tmp", args.out)

    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                key = (arch, shape_name, "multi" if mp else "single")
                if key in done and done[key]["status"] in ("ok", "skipped"):
                    results.append(done[key])
                    continue
                try:
                    results.append(run_cell(arch, shape_name, mp,
                                            hlo_dir=args.hlo_dir))
                except Exception as e:  # noqa: BLE001 — report, don't die
                    results.append(dict(arch=arch, shape=shape_name,
                                        mesh="multi" if mp else "single",
                                        status="error", error=repr(e)[:500]))
                    print(f"[dryrun] {arch} × {shape_name} ERROR: {e}",
                          file=sys.stderr)
                flush()
    flush()
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())

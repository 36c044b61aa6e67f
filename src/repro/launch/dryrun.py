import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (must be set before ANY jax import — jax locks device count on first init;
#  tests may shrink the placeholder count via REPRO_DRYRUN_DEVICES)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

# Multi-pod dry-run: lower + compile every (arch x shape) cell on the
# production mesh, record memory/cost/collective analysis for §Roofline.
#
# Usage:
#   python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh single
#   python -m repro.launch.dryrun --all --mesh both      (subprocess per cell)

import argparse
import functools
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, cells_for
from repro.configs.registry import ARCHS, get_arch
from repro.launch import specs as S
from repro.launch.analysis import analyze_compiled, model_flops
from repro.launch.mesh import make_production_mesh
import contextlib

from repro.models.layers import (abstract_params, activation_sharding,
                                 is_spec, logical_axes, moe_sharding)
from repro.models.transformer import model_spec
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.serve.engine import make_prefill_step, make_serve_step
from repro.sharding.rules import batch_spec, param_rules, param_shardings
from repro.train.step import make_train_step

# per-arch training knobs (activation memory / optimizer-state pressure)
TRAIN_OVERRIDES = {
    "deepseek-v3-671b": dict(num_microbatches=8, moment_dtype="int8",
                             accum_dtype="bfloat16"),
    "deepseek-moe-16b": dict(num_microbatches=2),
    "minitron-8b": dict(num_microbatches=2),
}


def count_params(cfg):
    spec = model_spec(cfg)
    leaves = jax.tree.leaves(spec, is_leaf=is_spec)
    total = active = 0.0
    for s in leaves:
        n = 1.0
        for d in s.shape:
            n *= d
        total += n
        if "experts" in s.axes:
            active += n * cfg.experts_per_token / max(cfg.num_experts, 1)
        else:
            active += n
    return total, active


def _opt_shardings(mesh, rules, log_axes_tree, abs_params, opt_abs):
    """Moments mirror the param shardings exactly; int8-quantized moments
    are shape-preserving, so codes reuse the param sharding and the
    last-dim-blocked scales reuse it minus the last dim."""
    p_sh = param_shardings(log_axes_tree, rules, mesh, abs_params)

    def moments(abs_m):
        def rec(a, ps):
            if isinstance(a, dict):
                return {k: rec(a[k], ps[k] if isinstance(ps, dict) else ps)
                        for k in a}
            if isinstance(a, list):
                return [rec(x, ps[i] if isinstance(ps, list) else ps)
                        for i, x in enumerate(a)]
            if isinstance(a, tuple):   # (codes, scales)
                codes, scales = a
                spec = list(ps.spec)
                cspec = P(*spec[:codes.ndim])
                sspec = P(*spec[:max(codes.ndim - 1, 0)])
                return (NamedSharding(mesh, cspec),
                        NamedSharding(mesh, sspec))
            return ps
        return rec(abs_m, p_sh)

    return {
        "step": NamedSharding(mesh, P()),
        "m": moments(opt_abs["m"]),
        "v": moments(opt_abs["v"]),
    }, p_sh


def _moe_ctx(mesh, cfg, rules, batch_rows: int):
    """moe_sharding context: (B, E, cap, D) expert-buffer template — experts
    over their rule axes, batch groups over whatever data axes remain."""
    if not cfg.num_experts:
        return contextlib.nullcontext()
    from jax.sharding import PartitionSpec as P
    exp_axes = tuple(a for a in rules.get("experts", ())
                     if a in mesh.axis_names)
    esize = 1
    for a in exp_axes:
        esize *= mesh.shape[a]
    if not exp_axes or cfg.num_experts % esize:
        return contextlib.nullcontext()
    dp = tuple(a for a in ("pod", "data")
               if a in mesh.axis_names and a not in exp_axes)
    bsize = 1
    for a in dp:
        bsize *= mesh.shape[a]
    bshard = (dp if len(dp) > 1 else dp[0]) \
        if dp and batch_rows % bsize == 0 and batch_rows >= bsize else None
    espec = exp_axes if len(exp_axes) > 1 else exp_axes[0]
    # scatter layout: batch over ALL data axes (experts local);
    # expert layout: experts over the EP axes, batch over the rest.
    alldp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    asize = 1
    for a in alldp:
        asize *= mesh.shape[a]
    sshard = (alldp if len(alldp) > 1 else alldp[0]) \
        if alldp and batch_rows % asize == 0 and batch_rows >= asize else None
    # transit stage only needed when EP axes overlap the scatter batch axes
    overlap = [a for a in exp_axes if a in alldp]
    transit = None
    if overlap:
        keep_b = tuple(a for a in alldp if a not in exp_axes)
        tb = (keep_b if len(keep_b) > 1 else keep_b[0]) if keep_b else None
        te = overlap if len(overlap) > 1 else overlap[0]
        transit = P(tb, te)
    return moe_sharding(P(sshard), P(bshard, espec), transit)


def build_lowered(arch: str, shape_name: str, multi_pod: bool,
                  overrides: dict | None = None):
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = param_rules(cfg)
    spec = model_spec(cfg)
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    abs_params = abstract_params(spec, dtype)
    log_tree = logical_axes(spec)
    p_sh = param_shardings(log_tree, rules, mesh, abs_params)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        kw = dict(TRAIN_OVERRIDES.get(arch, {}))
        kw.update(overrides or {})
        opt_cfg = AdamWConfig(moment_dtype=kw.pop("moment_dtype", "float32"))
        accum = jnp.bfloat16 if kw.pop("accum_dtype", "float32") == "bfloat16" \
            else jnp.float32
        nmb = kw.pop("num_microbatches", 1)
        opt_abs = jax.eval_shape(
            functools.partial(init_opt_state, cfg=opt_cfg), abs_params)
        o_sh, p_sh = _opt_shardings(mesh, rules, log_tree, abs_params, opt_abs)
        step = make_train_step(cfg, opt_cfg, num_microbatches=nmb,
                               remat=True, accum_dtype=accum)
        batch_abs = S.train_inputs(cfg, shape)
        batch_sh = S.train_input_shardings(mesh, cfg, shape)
        metrics_sh = {"loss": repl, "grad_norm": repl, "lr": repl}
        fn = jax.jit(step,
                     in_shardings=(p_sh, o_sh, batch_sh),
                     out_shardings=(p_sh, o_sh, metrics_sh),
                     donate_argnums=(0, 1))
        act_spec = batch_spec(mesh, shape.global_batch, 3, seq_dim=1,
                              seq_len=shape.seq_len)
        with jax.set_mesh(mesh), activation_sharding(act_spec), \
                _moe_ctx(mesh, cfg, rules, shape.global_batch // nmb):
            lowered = fn.lower(abs_params, opt_abs, batch_abs)
        return lowered, mesh, cfg, shape

    if shape.kind == "prefill":
        fn0 = make_prefill_step(cfg, cache_len=shape.seq_len)
        inputs = S.prefill_inputs(cfg, shape)
        in_sh = S.train_input_shardings(mesh, cfg, shape)
        in_sh = {k: v for k, v in in_sh.items() if k in inputs}
        cache_abs = S.cache_abstract(cfg, shape.global_batch, shape.seq_len)
        c_sh = S.cache_shardings(mesh, cache_abs, shape.global_batch)
        out_sh = (S.logits_sharding(mesh, cfg, shape.global_batch), c_sh)
        fn = jax.jit(fn0, in_shardings=(p_sh, in_sh), out_shardings=out_sh)
        act_spec = batch_spec(mesh, shape.global_batch, 3, seq_dim=1,
                              seq_len=shape.seq_len)
        with jax.set_mesh(mesh), activation_sharding(act_spec), \
                _moe_ctx(mesh, cfg, rules, shape.global_batch):
            lowered = fn.lower(abs_params, inputs)
        return lowered, mesh, cfg, shape

    # decode
    fn0 = make_serve_step(cfg)
    cache_abs = S.cache_abstract(cfg, shape.global_batch, shape.seq_len)
    c_sh = S.cache_shardings(mesh, cache_abs, shape.global_batch)
    inp_abs, pos_abs = S.decode_inputs(cfg, shape)
    inp_sh = NamedSharding(mesh, batch_spec(mesh, shape.global_batch,
                                            inp_abs.ndim))
    out_tok_sh = inp_sh if cfg.input_mode == "tokens" else NamedSharding(
        mesh, batch_spec(mesh, shape.global_batch, 1))
    fn = jax.jit(fn0, in_shardings=(p_sh, c_sh, inp_sh, repl),
                 out_shardings=(out_tok_sh, c_sh), donate_argnums=(1,))
    act_spec = batch_spec(mesh, shape.global_batch, 3)
    with jax.set_mesh(mesh), activation_sharding(act_spec), \
            _moe_ctx(mesh, cfg, rules, shape.global_batch):
        lowered = fn.lower(abs_params, cache_abs, inp_abs, pos_abs)
    return lowered, mesh, cfg, shape


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_path: str | None = None, save_hlo: bool = False) -> dict:
    t0 = time.time()
    lowered, mesh, cfg, shape = build_lowered(arch, shape_name, multi_pod)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    n_dev = mesh.devices.size
    pod_size = n_dev // mesh.shape.get("pod", 1)
    rec = analyze_compiled(compiled, n_dev, pod_size)
    total, active = count_params(cfg)
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind != "decode" else shape.global_batch)
    rec.update({
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "devices": n_dev, "kind": shape.kind,
        "lower_s": t_lower, "compile_s": t_compile,
        "params_total": total, "params_active": active,
        "tokens_per_step": tokens,
        "model_flops_total": model_flops(active, tokens, shape.kind),
    })
    rec["model_flops_per_device"] = rec["model_flops_total"] / n_dev
    if rec["flops_per_device"]:
        rec["useful_flops_fraction"] = (rec["model_flops_per_device"]
                                        / rec["flops_per_device"])
    print(f"[dryrun] {arch} {shape_name} mesh={rec['mesh']} "
          f"compile={t_compile:.1f}s "
          f"flops/dev={rec['flops_per_device']:.3e} "
          f"bytes/dev={rec['bytes_per_device']:.3e} "
          f"peak_mem={rec['memory'].get('peak_bytes', -1)/2**30:.2f}GiB "
          f"bound={rec['roofline']['bound']}")
    print("  memory_analysis:", rec["memory"])
    print("  cost_analysis: flops=%.4e bytes=%.4e" % (
        rec["flops_per_device"], rec["bytes_per_device"]))
    print("  collectives:", json.dumps(rec["collectives"], indent=None))
    print("  roofline:", {k: (round(v, 6) if isinstance(v, float) else v)
                          for k, v in rec["roofline"].items()})
    if save_hlo and out_path:
        with open(out_path.replace(".json", ".hlo.txt"), "w") as f:
            f.write(compiled.as_text())
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()

    os.makedirs(args.out_dir, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        failures = []
        for name, cfg in ARCHS.items():
            if args.arch and name != args.arch:
                continue
            for shape in cells_for(cfg):
                for m in meshes:
                    out = os.path.join(args.out_dir,
                                       f"{name}_{shape.name}_{m}.json")
                    if os.path.exists(out):
                        print(f"[skip cached] {out}")
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", name, "--shape", shape.name,
                           "--mesh", m, "--out-dir", args.out_dir]
                    r = subprocess.run(cmd, timeout=args.timeout,
                                       capture_output=True, text=True)
                    sys.stdout.write(r.stdout[-2000:])
                    if r.returncode != 0:
                        failures.append((name, shape.name, m))
                        print(f"[FAIL] {name} {shape.name} {m}\n"
                              + r.stderr[-2000:])
        print(f"\n[dryrun --all] done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    assert args.arch and args.shape
    out = os.path.join(args.out_dir,
                       f"{args.arch}_{args.shape}_{meshes[0]}.json")
    for m in meshes:
        out = os.path.join(args.out_dir,
                           f"{args.arch}_{args.shape}_{m}.json")
        run_cell(args.arch, args.shape, m == "multi", out,
                 save_hlo=args.save_hlo)


if __name__ == "__main__":
    main()

"""Multi-pod dry-run: lower + compile every (arch x input shape) on the
production meshes and extract memory / cost / collective statistics.

The two os.environ lines below MUST run before any jax import (jax locks the
device count at first init); this module is the only place the 512
placeholder devices exist.

FLOPs accounting: XLA's cost analysis counts a ``while`` body (the layer
scan) once, so the sharded scanned module under-reports FLOPs by ~L x.
The dry-run therefore compiles two cheap single-device *probes* with the
layer loop unrolled at depth k and 2k (k = hybrid group size or 1) and
extrapolates: total = f(k) + (L/k - 1) * (f(2k) - f(k)). Memory and
collective statistics come from the real sharded artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
      --shape train_4k [--multi-pod] [--mode train|serve|hfl] [--out o.jsonl]
  PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes --out o.jsonl
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.configs.base import ModelConfig, active_param_count, param_count
from repro.fed.distributed import (abstract_edge_params, make_hfl_round,
                                   make_serve_step, make_train_step)
from repro.launch.mesh import make_production_mesh, mesh_num_devices
from repro.launch.sharding import (batch_shardings, param_shardings,
                                   serve_state_shardings)
from repro.models import registry as R
from repro.roofline.analysis import (collective_bytes_from_hlo,
                                     model_flops_decode, model_flops_train,
                                     roofline_report)


def _mem_stats(compiled) -> Dict[str, float]:
    m = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        out[k] = float(getattr(m, k, 0) or 0)
    out["total_bytes_per_device"] = (
        out["argument_size_in_bytes"] + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"] - out.get("alias_size_in_bytes", 0.0))
    return out


def _cost_stats(compiled) -> Dict[str, float]:
    c = compiled.cost_analysis()
    if isinstance(c, (list, tuple)):
        c = c[0]
    return {"flops": float(c.get("flops", 0.0)),
            "bytes_accessed": float(c.get("bytes accessed", 0.0)),
            "transcendentals": float(c.get("transcendentals", 0.0))}


def _lower(cfg: ModelConfig, shape, mode: str, mesh=None, n_edge: int = 2,
           unroll: bool = False, microbatch: int = 1):
    """Build + lower the step function. mesh=None -> single-device probe."""
    params_abs = R.abstract_params(cfg)
    if mesh is not None:
        p_shard = param_shardings(params_abs, mesh)

    if mode in ("train",):
        specs = R.input_specs(cfg, shape)
        w_spec = jax.ShapeDtypeStruct((shape.global_batch,), jnp.float32)
        step = make_train_step(cfg, remat=True, unroll=unroll,
                               microbatch=microbatch)
        if mesh is None:
            return jax.jit(step).lower(params_abs, specs, w_spec)
        b_shard = batch_shardings(specs, mesh)
        w_shard = batch_shardings({"w": w_spec}, mesh)["w"]
        return jax.jit(step, in_shardings=(p_shard, b_shard, w_shard),
                       out_shardings=(p_shard, None)
                       ).lower(params_abs, specs, w_spec)
    if mode == "prefill":
        specs = R.input_specs(cfg, shape)
        window = R.serve_window(cfg, shape)
        state_abs = R.abstract_serve_state(cfg, shape.global_batch,
                                           shape.seq_len, window=window)

        def pf(params, batch, state):
            return R.prefill(params, cfg, batch, state, window=window,
                             unroll=unroll)

        if mesh is None:
            return jax.jit(pf).lower(params_abs, specs, state_abs)
        b_shard = batch_shardings(specs, mesh)
        s_shard = serve_state_shardings(state_abs, mesh)
        return jax.jit(pf, in_shardings=(p_shard, b_shard, s_shard),
                       out_shardings=(None, s_shard)
                       ).lower(params_abs, specs, state_abs)
    if mode == "serve":
        window = R.serve_window(cfg, shape)
        state_abs = R.abstract_serve_state(cfg, shape.global_batch,
                                           shape.seq_len, window=window)
        tok_abs = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        step = make_serve_step(cfg, window=window, unroll=unroll)
        if mesh is None:
            return jax.jit(step, donate_argnums=(2,)
                           ).lower(params_abs, tok_abs, state_abs)
        s_shard = serve_state_shardings(state_abs, mesh)
        t_shard = batch_shardings({"t": tok_abs}, mesh)["t"]
        # donate the cache/state: decode updates it in place instead of
        # materializing a second full KV cache every step
        return jax.jit(step, in_shardings=(p_shard, t_shard, s_shard),
                       out_shardings=(None, s_shard), donate_argnums=(2,)
                       ).lower(params_abs, tok_abs, state_abs)
    if mode == "hfl":
        ep_abs = abstract_edge_params(cfg, n_edge)
        b = shape.global_batch
        specs = R.input_specs(cfg, shape)
        st_specs = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (n_edge, b // n_edge) + s.shape[1:], s.dtype), specs)
        w_abs = jax.ShapeDtypeStruct((n_edge, b // n_edge), jnp.float32)
        step_abs = jax.ShapeDtypeStruct((), jnp.int32)
        rnd = make_hfl_round(cfg, n_edge=n_edge, t_es=5, remat=True,
                             unroll=unroll, microbatch=microbatch)
        if mesh is None:
            return jax.jit(rnd).lower(ep_abs, st_specs, w_abs, step_abs)
        ep_shard = param_shardings(ep_abs, mesh, edge_stacked=True)
        sb_shard = batch_shardings(st_specs, mesh, edge_stacked=True)
        w_shard = batch_shardings({"w": w_abs}, mesh, edge_stacked=True)["w"]
        return jax.jit(rnd, in_shardings=(ep_shard, sb_shard, w_shard, None),
                       out_shardings=(ep_shard, None)
                       ).lower(ep_abs, st_specs, w_abs, step_abs)
    raise ValueError(mode)


# grad-accumulation defaults for the train shapes (chosen in the perf pass
# so each config's live activations fit 16 GB v5e HBM; see EXPERIMENTS.md)
TRAIN_MICROBATCH = {
    "kimi-k2-1t-a32b": 16,
    "mixtral-8x22b": 8,
    "granite-20b": 4,
    "qwen2.5-14b": 4,
    "seamless-m4t-large-v2": 16,
    "zamba2-1.2b": 4,
    "granite-8b": 2,
}


def _probe_cfg(cfg: ModelConfig, layers: int) -> ModelConfig:
    kw: Dict[str, Any] = {"num_layers": layers}
    if cfg.encoder_layers:
        kw["encoder_layers"] = layers
    return dataclasses.replace(cfg, **kw)


def flops_probe(cfg: ModelConfig, shape, mode: str) -> Dict[str, float]:
    """Single-device unrolled probes at depth k and 2k -> extrapolated total
    FLOPs/bytes of the full-depth module."""
    k = cfg.hybrid_attn_every if cfg.arch_type == "hybrid" else 1
    c1 = _lower(_probe_cfg(cfg, k), shape, mode, mesh=None,
                unroll=True).compile()
    c2 = _lower(_probe_cfg(cfg, 2 * k), shape, mode, mesh=None,
                unroll=True).compile()
    f1, f2 = _cost_stats(c1), _cost_stats(c2)
    mult = cfg.num_layers / k - 1.0
    out = {}
    for key in ("flops", "bytes_accessed", "transcendentals"):
        out[key] = f1[key] + mult * (f2[key] - f1[key])
    return out


def dryrun_one(arch: str, shape_name: str, multi_pod: bool = False,
               mode: Optional[str] = None, n_edge: int = 2,
               verbose: bool = True, probe: bool = True,
               microbatch: int = 1) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if not R.supports_shape(cfg, shape):
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped",
                "reason": "long_500k not meaningful for this arch "
                          "(see DESIGN.md)"}
    mode = mode or ("serve" if shape.kind == "decode"
                    else ("prefill" if shape.kind == "prefill" else "train"))
    if mode == "hfl" and not multi_pod:
        raise ValueError("hfl mode maps edge servers onto pods (multi-pod)")
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_num_devices(mesh)
    t0 = time.time()
    tokens = shape.global_batch * (shape.seq_len
                                   if mode in ("train", "hfl") else 1)
    n_active = active_param_count(cfg)

    with jax.sharding.set_mesh(mesh):
        compiled = _lower(cfg, shape, mode, mesh=mesh, n_edge=n_edge,
                          microbatch=microbatch).compile()
    mem = _mem_stats(compiled)
    cost_scanned = _cost_stats(compiled)
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    coll_total = float(sum(coll.values()))
    if probe:
        cost_global = flops_probe(cfg, shape, mode)
        flops_per_device = cost_global["flops"] / chips
        bytes_per_device = cost_global["bytes_accessed"] / chips
    else:
        cost_global = None
        flops_per_device = cost_scanned["flops"]
        bytes_per_device = cost_scanned["bytes_accessed"]
    if mode in ("train", "hfl"):
        mf = model_flops_train(n_active, tokens)
    elif mode == "prefill":
        mf = model_flops_decode(n_active, shape.global_batch * shape.seq_len)
    else:
        mf = model_flops_decode(n_active, shape.global_batch)
    roof = roofline_report(flops_per_device, bytes_per_device,
                           coll_total, chips, model_flops=mf)
    rec = {
        "arch": arch, "shape": shape_name, "mode": mode,
        "microbatch": microbatch,
        "multi_pod": multi_pod, "chips": chips, "status": "ok",
        "elapsed_s": round(time.time() - t0, 1),
        "params_total": param_count(cfg), "params_active": n_active,
        "memory": mem, "cost_scanned": cost_scanned,
        "cost_probe_global": cost_global, "collectives": coll,
        "roofline": roof,
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} ({mode}, "
              f"{'multi' if multi_pod else 'single'}-pod, {chips} chips): "
              f"OK in {rec['elapsed_s']}s | "
              f"mem/device {mem['total_bytes_per_device']/2**30:.2f} GiB | "
              f"flops/device {flops_per_device:.3e} | "
              f"coll {coll_total/2**20:.1f} MiB | "
              f"dominant={roof['dominant']}", flush=True)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--mode", choices=["train", "serve", "prefill", "hfl"],
                    default=None)
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the flops extrapolation probes")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="grad-accumulation slices for train shapes "
                         "(0 = per-arch default table)")
    ap.add_argument("--all", action="store_true",
                    help="all (arch x shape) on the chosen mesh(es)")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args(argv)

    jobs = []
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        for arch in ARCH_IDS:
            for shape in INPUT_SHAPES:
                for mp in meshes:
                    jobs.append((arch, shape, mp, None))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mp in meshes:
            jobs.append((args.arch, args.shape, mp, args.mode))

    failures = 0
    for arch, shape, mp, mode in jobs:
        try:
            mb = args.microbatch or TRAIN_MICROBATCH.get(arch, 1)
            rec = dryrun_one(arch, shape, multi_pod=mp, mode=mode,
                             probe=not args.no_probe, microbatch=mb)
        except Exception as e:  # noqa: BLE001 — report and continue
            failures += 1
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[dryrun] {arch} x {shape} "
                  f"({'multi' if mp else 'single'}-pod): FAILED {e}",
                  flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on
placeholder devices, prove memory/sharding coherence, and dump the roofline
raw material (cost_analysis, memory_analysis, collective schedule) to
benchmarks/artifacts/<arch>_<shape>_<mesh>[__tag].json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --he-agg --mesh single
"""
# The first two executable lines: jax locks the device count on first init,
# so the placeholder-device flag must be set before ANY other import.
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_EXTRA", ""))

import argparse
import json
import sys
import time
import traceback

import jax
import numpy as np

from repro import configs
from repro.configs.shapes import SHAPES, input_specs
from repro.launch import fl_step, steps
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.models.sharding import axis_env_from_mesh
from repro.optim import AdamWConfig

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                "..", "..", ".."))  # benchmarks/
from benchmarks import roofline as rf  # noqa: E402

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "benchmarks", "artifacts")


def _mesh_for(name: str):
    return make_production_mesh(multi_pod=(name == "multi"))


def _abstract_opt(params_abs):
    sds = jax.ShapeDtypeStruct
    f32 = jax.numpy.float32
    zeros = lambda p: sds(p.shape, f32)
    return {"m": jax.tree_util.tree_map(zeros, params_abs),
            "v": jax.tree_util.tree_map(zeros, params_abs),
            "step": sds((), jax.numpy.int32)}


def lower_cell(arch: str, shape: str, mesh_name: str, tag: str = "",
               param_mode: str = "train", cfg_overrides: dict | None = None):
    """Lower+compile one cell; returns the artifact dict."""
    mesh = _mesh_for(mesh_name)
    n_dev = mesh.size
    cfg = configs.get_config(arch)
    if cfg_overrides:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **cfg_overrides)
    sp = SHAPES[shape]
    with jax.sharding.set_mesh(mesh):
        ax = axis_env_from_mesh(mesh)
        model = build_model(cfg, ax)
        params_abs = model.init_abstract()

        t0 = time.perf_counter()
        if sp.kind == "train":
            batch = input_specs(cfg, shape)
            step = steps.jit_train_step(model, mesh, AdamWConfig(), batch)
            lowered = step.lower(params_abs, _abstract_opt(params_abs), batch)
            tokens = sp.batch * sp.seq
        elif sp.kind == "prefill":
            batch = input_specs(cfg, shape)
            step = steps.jit_prefill_step(model, mesh, batch)
            lowered = step.lower(params_abs, batch)
            tokens = sp.batch * sp.seq
        else:  # decode
            full = input_specs(cfg, shape, model=model)
            batch = {"tokens": full["tokens"]}
            cache = full["cache"]
            step = steps.jit_decode_step(model, mesh, cache, batch, sp.batch,
                                         param_mode=param_mode)
            lowered = step.lower(params_abs, cache, batch)
            tokens = sp.batch
        t_lower = time.perf_counter() - t0

        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    return _analyze(compiled, cfg, sp.kind, tokens, n_dev, arch, shape,
                    mesh_name, t_lower, t_compile, tag)


def lower_he_agg(mesh_name: str, arch: str = "qwen1.5-0.5b",
                 p_ratio: float = 0.1, n_clients: int = 8, tag: str = ""):
    """The paper-technique cell: distributed CKKS FedAvg aggregation."""
    mesh = _mesh_for(mesh_name)
    cfg = configs.get_config(arch)
    spec = fl_step.HeAggSpec.for_model(
        cfg.param_count(), p_ratio, n_clients, mesh.size)
    ins = spec.input_specs()
    with jax.sharding.set_mesh(mesh):
        t0 = time.perf_counter()
        step = fl_step.jit_he_agg_step(spec, mesh,
                                       [1.0 / n_clients] * n_clients)
        lowered = step.lower(ins["cts"], ins["plain"])
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    class _HECfg:
        name = f"he-agg[{arch}, p={p_ratio}]"

        @staticmethod
        def active_param_count():
            return 0

    art = _analyze(compiled, _HECfg, "he_agg", 0, mesh.size,
                   arch, "he_agg", mesh_name, t_lower, t_compile, tag)
    art["he"] = {
        "n_clients": n_clients, "p_ratio": p_ratio,
        "n_chunks": spec.n_chunks, "n_plain": spec.n_plain,
        "wire_bytes_per_client": spec.wire_bytes_per_client(),
    }
    _write(art)
    return art


def _analyze(compiled, cfg, kind, tokens, n_dev, arch, shape, mesh_name,
             t_lower, t_compile, tag=""):
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    txt = compiled.as_text()
    colls = rf.parse_collectives(txt)
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    fused = rf.parse_memory_traffic(txt)
    roof = rf.build_roofline(cfg, kind, tokens, n_dev, flops, bytes_acc,
                             colls, fused, rf.peaks(rf.DRYRUN_TARGET))
    import gzip
    os.makedirs(ARTIFACTS, exist_ok=True)
    tagsuf = f"__{tag}" if tag else ""
    hlo_fn = os.path.join(ARTIFACTS,
                          f"{arch}_{shape}_{mesh_name}{tagsuf}.hlo.gz")
    with gzip.open(hlo_fn, "wt") as f:
        f.write(txt)
    art = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "tag": tag,
        "n_devices": n_dev, "tokens": tokens, "kind": kind,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "hlo_lines": len(txt.splitlines()),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_hbm_bytes": ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              + ma.temp_size_in_bytes
                              - ma.alias_size_in_bytes,
        },
        "collectives": {"counts": colls.counts,
                        "by_op_bytes": colls.by_op},
        "roofline": roof.to_dict(),
    }
    return art


def _write(art: dict):
    os.makedirs(ARTIFACTS, exist_ok=True)
    tag = f"__{art['tag']}" if art.get("tag") else ""
    fn = f"{art['arch']}_{art['shape']}_{art['mesh']}{tag}.json"
    with open(os.path.join(ARTIFACTS, fn), "w") as f:
        json.dump(art, f, indent=1)
    return fn


def run_cell(arch, shape, mesh_name, force=False, tag="",
             param_mode="train", cfg_overrides=None):
    tagsuf = f"__{tag}" if tag else ""
    fn = os.path.join(ARTIFACTS, f"{arch}_{shape}_{mesh_name}{tagsuf}.json")
    if os.path.exists(fn) and not force:
        print(f"SKIP (cached) {arch} {shape} {mesh_name}")
        return json.load(open(fn))
    t0 = time.perf_counter()
    try:
        art = lower_cell(arch, shape, mesh_name, tag, param_mode=param_mode,
                         cfg_overrides=cfg_overrides)
    except Exception as e:
        print(f"FAIL {arch} {shape} {mesh_name}: {e}")
        traceback.print_exc()
        return None
    _write(art)
    r = art["roofline"]
    peak = art["memory"]["peak_hbm_bytes"] / 1e9
    print(f"OK {arch} {shape} {mesh_name} "
          f"compile={art['compile_s']}s "
          f"comp={r['compute_s']*1e3:.1f}ms mem={r['memory_s']*1e3:.1f}ms "
          f"coll={r['collective_s']*1e3:.1f}ms dom={r['dominant']} "
          f"frac={r['roofline_fraction']:.2f} peakHBM={peak:.1f}GB "
          f"({time.perf_counter()-t0:.0f}s)")
    return art


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--he-agg", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--param-mode", default="train",
                    choices=["train", "serve_tp"])
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.he_agg:
        for m in meshes:
            t0 = time.perf_counter()
            art = lower_he_agg(m, tag=args.tag)
            r = art["roofline"]
            print(f"OK he_agg {m} comp={r['compute_s']*1e3:.2f}ms "
                  f"mem={r['memory_s']*1e3:.2f}ms coll={r['collective_s']*1e3:.2f}ms "
                  f"dom={r['dominant']} ({time.perf_counter()-t0:.0f}s)")
        return
    if args.all:
        cells = configs.all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    ok = fail = 0
    for arch, shape in cells:
        for m in meshes:
            art = run_cell(arch, shape, m, force=args.force, tag=args.tag,
                           param_mode=args.param_mode)
            ok += art is not None
            fail += art is None
    print(f"done: {ok} ok, {fail} failed")
    if fail:
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) and
extract memory / cost / collective statistics.

The production meshes need 512 host devices: ``main`` forces that count
in ``XLA_FLAGS`` before jax's backend first initializes (no import here
initializes it), so importing this module leaves the environment alone.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all   # every pair
"""
import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import get, pairs
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.optim import adamw

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def default_n_micro(arch: str, dp: int, global_batch: int) -> int:
    """1 example per device per microstep for ≥10B-class; fewer microsteps
    for small models (no memory pressure)."""
    small = {"xlstm-125m", "stablelm-3b", "whisper-large-v3",
             "minicpm3-4b", "starcoder2-7b"}
    per_dev = max(1, global_batch // dp)
    if arch in small:
        return max(1, per_dev // 4)
    return per_dev


def decode_window(cfg, shape_name: str) -> int:
    if shape_name == "long_500k":
        return cfg.long_decode_window
    return cfg.sliding_window


def build_step(cfg, shape, mesh, *, n_micro=None, seq_parallel=True,
               loss_chunk=512, mlstm_chunkwise=False, window=None,
               attn_anchor=True):
    """Returns (jitted_fn, abstract_args) ready to .lower(*args)."""
    axis_names = mesh.axis_names
    dp = 1
    for a in ("pod", "data"):
        if a in axis_names:
            dp *= mesh.shape[a]

    def _init_all(k):
        p = M.init_params(cfg, k)
        return p, M.init_adapters(cfg, k, p)

    aparams, aadapters = jax.eval_shape(_init_all, jax.random.PRNGKey(0))
    axis_sizes = dict(mesh.shape)
    pspecs = shd.param_specs(aparams, axis_names, axis_sizes)
    aspecs = shd.param_specs(aadapters, axis_names, axis_sizes)
    psh = shd.named(mesh, pspecs)
    ash = shd.named(mesh, aspecs)

    if shape.kind == "train":
        nm = n_micro or default_n_micro(cfg.name, dp, shape.global_batch)
        opts = M.FwdOptions(
            remat=True, seq_parallel=seq_parallel,
            mlstm_chunkwise=mlstm_chunkwise,
            attn_anchor=attn_anchor,
            window=window if window is not None else
            (cfg.sliding_window or None))
        step = M.make_train_step(cfg, n_microbatches=nm, opts=opts,
                                 loss_chunk=loss_chunk)
        aopt = jax.eval_shape(adamw.init, aadapters)
        osh = adamw.AdamWState(
            step=NamedSharding(mesh, P()),
            mu=shd.named(mesh, shd.param_specs(aadapters, axis_names,
                                               axis_sizes)),
            nu=shd.named(mesh, shd.param_specs(aadapters, axis_names,
                                               axis_sizes)))
        batch = M.input_specs(cfg, shape)
        bsh = shd.named(mesh, shd.batch_specs(batch, axis_names))
        fn = jax.jit(step, in_shardings=(psh, ash, osh, bsh),
                     donate_argnums=(1, 2))
        return fn, (aparams, aadapters, aopt, batch), {"n_micro": nm}

    if shape.kind == "prefill":
        opts = M.FwdOptions(remat=False, collect_cache=True,
                            shard_cache=True, seq_parallel=seq_parallel,
                            attn_anchor=attn_anchor,
                            window=window if window is not None else
                            (cfg.sliding_window or None))
        step = M.make_prefill_step(cfg, opts)
        batch = M.input_specs(cfg, shape)
        bsh = shd.named(mesh, shd.batch_specs(batch, axis_names))
        fn = jax.jit(step, in_shardings=(psh, ash, bsh))
        return fn, (aparams, aadapters, batch), {}

    if shape.kind == "decode":
        w = window if window is not None else decode_window(cfg, shape.name)
        step = M.make_serve_step(cfg, window=w)
        spec = M.input_specs(cfg, shape, window=w)
        cache, token, pos = spec["cache"], spec["token"], spec["pos"]
        csh = shd.named(mesh, shd.cache_specs(cache, axis_names,
                                              shape.global_batch,
                                              axis_sizes))
        tsh = shd.named(mesh, shd.batch_specs(
            {"token": token}, axis_names))["token"]
        fn = jax.jit(step, in_shardings=(psh, ash, csh, tsh,
                                         NamedSharding(mesh, P())),
                     donate_argnums=(2,))
        return fn, (aparams, aadapters, cache, token, pos), {"window": w}

    raise ValueError(shape.kind)


def run_one(arch: str, shape_name: str, mesh_kind: str, *, tag="baseline",
            save=True, qlora=False, **knobs):
    import dataclasses
    cfg = get(arch)
    if qlora:
        cfg = dataclasses.replace(
            cfg, lora=dataclasses.replace(cfg.lora, quantize_base=True))
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
           "knobs": knobs, "status": "ok"}
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            fn, args, extra = build_step(cfg, shape, mesh, **knobs)
            rec.update(extra)
            lowered = fn.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            txt = compiled.as_text()
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (ma.argument_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      + ma.output_size_in_bytes
                                      - ma.alias_size_in_bytes),
        }
        rec["cost"] = {"flops_per_device": ca.get("flops", 0.0),
                       "bytes_per_device": ca.get("bytes accessed", 0.0),
                       "transcendentals": ca.get("transcendentals", 0.0)}
        coll = hlo.collective_stats(txt)
        rec["collectives"] = coll
        cbytes = sum(v["bytes"] for v in coll.values())
        # Collectives: EXACT dynamic traffic via known_trip_count-weighted
        # attribution (each op × product of enclosing while trip counts).
        coll_w = hlo.collective_stats_weighted(txt)
        rec["collectives_weighted"] = coll_w
        cbytes_w = sum(v["bytes"] for v in coll_w.values())
        # FLOPs/bytes: XLA's cost analysis counts a while body ONCE — our
        # step scans layer groups and microbatches, so we compute exact
        # trip-weighted dot FLOPs and a materialized-buffer HBM-traffic
        # proxy straight from the HLO (see hlo_analysis.weighted_hlo_cost).
        trips = cfg.n_groups * max(1, int(extra.get("n_micro", 1)))
        wc = hlo.weighted_hlo_cost(txt, inner_mult_cutoff=trips)
        rec["scan_trips"] = trips
        rec["cost_corrected"] = {
            "flops_per_device": wc["flops"],
            "bytes_per_device": wc["bytes"],
            "bytes_outer_per_device": wc["bytes_outer"],
            "collective_bytes_per_device": cbytes_w,
        }
        rec["roofline_raw"] = hlo.roofline_terms(
            flops_per_chip=ca.get("flops", 0.0),
            hbm_bytes_per_chip=ca.get("bytes accessed", 0.0),
            collective_bytes_per_chip=cbytes)
        # memory term uses bytes_outer — inner attention-chunk tiles are
        # VMEM-resident under the Pallas flash kernel on the TPU target
        # (the all-buffers figure is kept in cost_corrected for reference)
        rec["roofline"] = hlo.roofline_terms(
            flops_per_chip=rec["cost_corrected"]["flops_per_device"],
            hbm_bytes_per_chip=rec["cost_corrected"][
                "bytes_outer_per_device"],
            collective_bytes_per_chip=rec["cost_corrected"][
                "collective_bytes_per_device"])
        rec["model_flops"] = model_flops(cfg, shape)
        hw = (rec["cost_corrected"]["flops_per_device"]
              * total_chips(mesh))
        rec["useful_flops_ratio"] = (rec["model_flops"] / hw) if hw else 0.0
        rec["hlo_bytes"] = len(txt)
    except Exception as e:  # noqa: BLE001 — record failures, don't die
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    if save:
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        name = f"{arch}_{shape_name}_{mesh_kind}_{tag}.json"
        (OUT_DIR / name).write_text(json.dumps(rec, indent=1))
    return rec


def total_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n


def model_flops(cfg, shape) -> float:
    """6·N·D (train) / 2·N·D (inference) with N = active params."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch          # decode: one token


def main():
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--loss-chunk", type=int, default=512)
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--mlstm-chunkwise", action="store_true")
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--no-attn-anchor", action="store_true")
    ap.add_argument("--qlora", action="store_true")
    args = ap.parse_args()

    knobs = dict(n_micro=args.n_micro, loss_chunk=args.loss_chunk,
                 seq_parallel=not args.no_seq_parallel,
                 mlstm_chunkwise=args.mlstm_chunkwise, window=args.window,
                 attn_anchor=not args.no_attn_anchor, qlora=args.qlora)

    if args.all:
        todo = [(a, s, m) for (a, s) in pairs()
                for m in ("single", "multi")]
    else:
        todo = [(args.arch, args.shape, args.mesh)]

    for (a, s, m) in todo:
        t0 = time.time()
        rec = run_one(a, s, m, tag=args.tag, **knobs)
        status = rec["status"]
        extra = ""
        if status == "ok":
            mem = rec["memory"]["peak_bytes_per_device"] / 2**30
            dom = rec["roofline"]["dominant"]
            extra = f"peak={mem:.2f}GiB/dev dominant={dom}"
        else:
            extra = rec["error"][:160]
        print(f"[{time.time()-t0:7.1f}s] {a} × {s} × {m}: {status} {extra}",
              flush=True)


if __name__ == "__main__":
    main()

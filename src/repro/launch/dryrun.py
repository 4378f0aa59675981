import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: AOT-lower + compile every (architecture × input
shape) on the production mesh, prove it fits, and extract roofline terms.

MUST be run as its own process (the XLA_FLAGS line above precedes any jax
import and locks the device count to 512 placeholder host devices).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape prefill_32k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # single-pod sweep
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.configs.base import INPUT_SHAPES, get_config, list_configs
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.launch.roofline_model import (analytic_bytes, analytic_flops,
                                         collective_bytes_corrected,
                                         collective_bytes_nested,
                                         loop_multiplier, trips_for_case)
from repro.launch.steps import build_case, case_supported
from repro.models.sharding import mesh_context

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1,
                "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s*(.{0,400}?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    per_type = {}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        b = _type_bytes(m.group(1))
        per_type[m.group(2)] = per_type.get(m.group(2), 0) + b
    return per_type, sum(per_type.values())


def model_flops(cfg, ishape) -> float:
    n_active = cfg.active_param_count()
    tokens = ishape.global_batch * (ishape.seq_len if ishape.mode != "decode"
                                    else 1)
    mult = 6.0 if ishape.mode == "train" else 2.0
    return mult * n_active * tokens


def run_case(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             q_block: int = 512, opt: str = ""):
    from repro.launch import optflags  # noqa: F811 (module-level import ok)
    optflags.set_flags(opt.split(",") if opt else [])
    cfg = get_config(arch)
    ishape = INPUT_SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}"
    if opt:
        tag += "__opt_" + opt.replace(",", "+").replace("=", "")
    ok, why = case_supported(cfg, ishape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "skipped", "skip_reason": why}
    if not ok:
        print(f"[dryrun] {tag}: SKIP ({why})")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    try:
        with mesh_context(mesh):
            step, args, meta = build_case(cfg, ishape, mesh, q_block=q_block)
            donate = meta.get("donate", ()) if optflags.has("donate") else ()
            lowered = jax.jit(step, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    except Exception as e:  # noqa: BLE001 - record the failure
        rec.update(status="FAILED", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[dryrun] {tag}: FAILED {type(e).__name__}: {e}")
        return rec

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    mem_rec = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_rec[attr] = int(v)

    # RAW HLO numbers (XLA counts while-loop bodies ONCE — see
    # roofline_model.py; kept for transparency)
    per_type_raw, coll_raw = collective_bytes(compiled.as_text())
    flops_raw = float(cost.get("flops", 0.0))
    bytes_raw = float(cost.get("bytes accessed", 0.0))

    # ANALYTIC compute/memory terms + nested-loop-corrected collectives
    hlo = compiled.as_text()
    mult = loop_multiplier(cfg, ishape, meta.get("microbatches", 1))
    trips = trips_for_case(cfg, ishape, meta.get("microbatches", 1),
                           q_block)
    per_type, coll_total = collective_bytes_nested(hlo, trips)
    _, coll_flat = collective_bytes_corrected(hlo, mult)
    flops_dev = analytic_flops(cfg, ishape) / n_dev
    bytes_dev = analytic_bytes(cfg, ishape, n_dev)
    mf = model_flops(cfg, ishape)
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS_BF16,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_total / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    rec.update(
        status="ok", devices=n_dev, meta=meta,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        per_device={"analytic_flops": flops_dev, "analytic_bytes": bytes_dev,
                    "collective_bytes": coll_total,
                    "collectives_by_type": per_type,
                    "loop_trips": trips,
                    "collective_bytes_flat_estimate": coll_flat,
                    "hlo_flops_raw": flops_raw,
                    "hlo_bytes_raw": bytes_raw,
                    "collective_bytes_raw": coll_raw},
        memory_analysis=mem_rec,
        model_flops_global=mf,
        model_flops_per_device=mf / n_dev,
        useful_flops_ratio=(mf / n_dev) / flops_dev if flops_dev else None,
        roofline_terms_s=terms,
        dominant_term=dominant,
    )
    arg_gb = mem_rec.get("argument_size_in_bytes", 0) / 2 ** 30
    tmp_gb = mem_rec.get("temp_size_in_bytes", 0) / 2 ** 30
    print(f"[dryrun] {tag}: OK compile={t_compile:.0f}s "
          f"flops/dev={flops_dev:.3g} bytes/dev={bytes_dev:.3g} "
          f"coll/dev={coll_total:.3g} args={arg_gb:.2f}GiB "
          f"temp={tmp_gb:.2f}GiB dominant={dominant}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--q-block", type=int, default=512)
    ap.add_argument("--opt", default="",
                    help="comma-separated optflags (see launch/optflags.py)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ([args.arch] if args.arch else
             [a for a in list_configs() if not a.startswith("tiny-")])
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    results = []
    for a in archs:
        for s in shapes:
            results.append(run_case(a, s, args.multi_pod, args.out,
                                    args.q_block, args.opt))
    bad = [r for r in results if r["status"] == "FAILED"]
    print(f"[dryrun] done: {len(results)} cases, "
          f"{sum(r['status'] == 'ok' for r in results)} ok, "
          f"{sum(r['status'] == 'skipped' for r in results)} skipped, "
          f"{len(bad)} failed")
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()

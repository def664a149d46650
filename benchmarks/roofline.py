"""Roofline analysis (deliverable g).

For each (arch x shape) cell on the single-pod mesh, derive the three
roofline terms:

    compute    = FLOPs_per_device / 197e12           [bf16 TFLOP/s]
    memory     = bytes_per_device / 819e9            [HBM GB/s]
    collective = wire_bytes_per_device / 50e9        [ICI GB/s/link]

Methodology note (CPU dry-run environment): XLA's cost_analysis counts a
while-loop body ONCE, so a scanned-layers program under-reports by ~L x
n_microbatches. We therefore lower each cell twice at reduced depth
(L0 and 2*L0 layer units) with scans fully UNROLLED and one microbatch,
measure (flops, bytes, collectives) exactly, and extrapolate:

    per_layer = f(2*L0) - f(L0);   outside = f(L0) - L0 * per_layer
    total     = outside + L_full * per_layer, then x n_microbatches

The layer "unit" respects each family's period (zamba2: shared-attn
group of 6; xlstm: slstm_every pair; encdec: enc+dec pair). MODEL_FLOPS
(6*N*D / 6*N_active*D) is computed analytically for the waste ratio.
Memory-fit numbers come from the FULL-depth dry-run compile (scans
rolled), recorded separately in EXPERIMENTS.md §Dry-run.
"""
# No-clobber: a device count already pinned in XLA_FLAGS (or injected
# via REPRO_HOST_DEVICES) wins; only the bare default forces 512.
from repro.launch.xla import ensure_host_platform_device_count
ensure_host_platform_device_count(default=512)

import argparse
import dataclasses
import json
import sys
from typing import Dict, Optional, Tuple

import jax

import repro.configs as C
from repro.configs.base import (AmbdgConfig, ENCDEC, HYBRID, SSM,
                                ModelConfig, RunConfig, SHAPES)
from repro.launch import dryrun as dr
from repro.launch.mesh import make_mesh, mesh_config

PEAK_FLOPS = 197e12     # bf16 per chip
HBM_BW = 819e9          # bytes/s
ICI_BW = 50e9           # bytes/s/link
N_CHIPS = 256


def layer_unit(cfg: ModelConfig) -> int:
    if cfg.family == HYBRID:
        return cfg.shared_attn_every
    if cfg.family == SSM:
        return cfg.xlstm.slstm_every
    return 1


def with_depth(cfg: ModelConfig, units: int) -> ModelConfig:
    u = layer_unit(cfg)
    kw = {"n_layers": units * u, "scan_unroll": True}
    if cfg.family == ENCDEC:
        kw["n_encoder_layers"] = units * u
    return dataclasses.replace(cfg, **kw)


def measure(cfg: ModelConfig, shape_name: str, n_mb: int = 1,
            tau: int = 1) -> Dict:
    """Lower+compile one reduced-depth cell; return raw counters."""
    rc = RunConfig(model=cfg, shape=SHAPES[shape_name],
                   mesh=mesh_config(False),
                   ambdg=AmbdgConfig(tau=tau, n_microbatches=n_mb),
                   remat="none")
    mesh = make_mesh(rc.mesh.shape, rc.mesh.axis_names)
    if rc.shape.kind == "train":
        lowered = dr.lower_train(rc, mesh)
    elif rc.shape.kind == "prefill":
        lowered = dr.lower_prefill(rc, mesh)
    else:
        lowered = dr.lower_serve(rc, mesh)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = dr.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": sum(v for k, v in coll.items() if k != "count"),
        "coll_by_type": coll,
    }


def extrapolate(cfg_full: ModelConfig, shape_name: str,
                n_mb_full: int = 8, u0: int = 1) -> Dict:
    """Two reduced-depth unrolled lowerings -> full-depth estimate."""
    u = layer_unit(cfg_full)
    total_units = cfg_full.n_layers // u
    f1 = measure(with_depth(cfg_full, u0), shape_name)
    f2 = measure(with_depth(cfg_full, 2 * u0), shape_name)
    out = {}
    kind = SHAPES[shape_name].kind
    mb_scale = n_mb_full if kind == "train" else 1
    # a train step at n_mb microbatches does the same total work as one
    # full-batch pass (we measure n_mb=1 at full batch)
    for key in ("flops", "bytes", "coll"):
        per = (f2[key] - f1[key]) / u0
        outside = f1[key] - u0 * per
        total = outside + total_units * per
        if total <= 0 or per < 0:
            # fusion differences between the two depths can make the
            # finite difference noisy; fall back to proportional
            # scaling from the deeper measurement (upper-bounds the
            # fixed part, conservative for the roofline)
            total = f2[key] * total_units / (2 * u0)
            per = f2[key] / (2 * u0)
            outside = 0.0
        out[key] = total
        out[f"{key}_per_unit"] = per
        out[f"{key}_outside"] = outside
    out["coll_by_type_2u"] = f2["coll_by_type"]
    return out


def model_flops(cfg: ModelConfig, shape) -> Tuple[float, float]:
    """(MODEL_FLOPS 6*N*D, active variant) global per step/token batch."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mult = 6.0
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mult = 2.0
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        mult = 2.0
    n_total = cfg.n_params()
    n_active = cfg.n_active_params()
    return mult * n_total * tokens, mult * n_active * tokens


def roofline_terms(est: Dict, cfg: ModelConfig, shape) -> Dict:
    compute_s = est["flops"] / PEAK_FLOPS
    memory_s = est["bytes"] / HBM_BW
    coll_s = est["coll"] / ICI_BW
    dominant = max((compute_s, "compute"), (memory_s, "memory"),
                   (coll_s, "collective"))[1]
    mf_total, mf_active = model_flops(cfg, shape)
    mf_per_device = mf_active / N_CHIPS
    return {
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dominant,
        "model_flops_per_device": mf_per_device,
        "useful_ratio": (mf_per_device / est["flops"]
                         if est["flops"] else float("nan")),
        "bound_s": max(compute_s, memory_s, coll_s),
        "roofline_fraction": (mf_per_device / PEAK_FLOPS) /
                             max(compute_s, memory_s, coll_s)
                             if max(compute_s, memory_s, coll_s) else 0.0,
    }


def run_cell(arch: str, shape_name: str, n_mb: int = 8,
             cfg: Optional[ModelConfig] = None) -> Dict:
    cfg = cfg or C.get_config(arch)
    shape = SHAPES[shape_name]
    if cfg.family in (SSM, HYBRID):
        # time-scan families: unrolling the SSD/mLSTM chunk loops makes
        # the measurement compile impractically slow on one CPU core;
        # use analytic FLOPs (the time-scan is FLOP-dominated by its
        # within-chunk matmuls, captured by 6*N*D) and the rolled
        # compile's bytes/collectives as LOWER BOUNDS (while bodies
        # counted once) — flagged in the row.
        est = measure(dataclasses.replace(cfg, scan_unroll=False),
                      shape_name, n_mb=1)
        mf_total, mf_active = model_flops(cfg, shape)
        remat_mult = 4.0 / 3.0 if (shape.kind == "train" and
                                   cfg.block_remat == "full") else 1.0
        est = {"flops": mf_active / N_CHIPS * remat_mult,
               "bytes": est["bytes"], "coll": est["coll"],
               "coll_by_type_2u": est["coll_by_type"],
               "methodology": "analytic-flops+rolled-lower-bounds"}
    else:
        est = extrapolate(cfg, shape_name, n_mb_full=n_mb)
        est["methodology"] = "unrolled-L-extrapolation"
    terms = roofline_terms(est, cfg, shape)
    row = {"arch": arch, "shape": shape_name,
           "methodology": est["methodology"], **{
        k: (round(v, 6) if isinstance(v, float) else v)
        for k, v in {**est, **terms}.items()
        if k not in ("coll_by_type_2u", "methodology")}}
    row["coll_by_type"] = est["coll_by_type_2u"]
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in C.ARCH_IDS:
            for shape in C.applicable_shapes(arch):
                cells.append((arch, shape.name))
    else:
        cells.append((args.arch, args.shape))

    rows, failures = [], []
    for arch, shape in cells:
        try:
            row = run_cell(arch, shape)
            rows.append(row)
            print(json.dumps(row))
        except Exception as e:  # noqa: BLE001
            failures.append({"arch": arch, "shape": shape,
                             "error": repr(e)[:300]})
            print(f"FAIL {arch} {shape}: {e!r}", file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "failures": failures}, f, indent=1)
    print(f"{len(rows)} ok, {len(failures)} failed")


if __name__ == "__main__":
    main()

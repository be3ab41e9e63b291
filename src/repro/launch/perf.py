import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # simulated devices: never take a chip

"""§Perf hillclimb driver: compile a (arch x shape x mesh) cell under a named
optimization variant and report the roofline-term deltas vs baseline.

  PYTHONPATH=src python -m repro.launch.perf --arch gemma3-1b \
      --shape train_4k --mesh pod1 --variant sp_dots

Variants compose config-level levers (see models/common.py):
  baseline      paper-faithful defaults
  sp            sequence-parallel residual stream (Megatron-SP)
  dots          remat policy saving matmul outputs
  sp_dots       both
  qchunk512/qchunk2048   attention query-block size
  kv_heads      decode KV cache sharded over kv-heads instead of sequence
  cf10          MoE capacity factor 1.0 (tighter dispatch buffer)
  accumN        N-way gradient accumulation (train shapes)
"""
import argparse
import dataclasses
import json
import time

from ..configs import ARCH_IDS, SHAPES, get_config
from ..launch.dryrun import (cell_path, compile_cell, depth_units, model_flops,
                             with_depth)
from ..launch.mesh import HW, make_production_mesh
from ..distributed.hlo_analysis import depth_delta, roofline_terms

PERF_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                        "experiments", "perf")

VARIANTS = {
    "baseline": {},
    "sp": dict(seq_parallel=True),
    "dots": dict(remat_policy="dots"),
    "sp_dots": dict(seq_parallel=True, remat_policy="dots"),
    "qchunk512": dict(attn_q_chunk=512),
    "qchunk2048": dict(attn_q_chunk=2048),
    "kv_heads": dict(decode_shard="heads"),
    "cf10": dict(capacity_factor=1.0),
    "ssmchunk256": dict(ssm_chunk=256),
    "localdisp": dict(moe_local_dispatch=True),
    "localdisp_cf10": dict(moe_local_dispatch=True, capacity_factor=1.0),
}


def run_variant(arch: str, shape_name: str, mesh_kind: str, variant: str,
                accum: int = 1, skip_delta: bool = False):
    overrides = VARIANTS[variant] if variant in VARIANTS else {}
    if variant.startswith("accum"):
        accum = int(variant[5:])
        overrides = {}
    cfg = dataclasses.replace(get_config(arch), **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "pod2"))
    chips = 512 if mesh_kind == "pod2" else 256
    t0 = time.time()
    full = compile_cell(cfg, shape, mesh, accum=accum)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "variant": variant, "accum": accum, "full": full}
    if not skip_delta:
        mk = lambda u: dataclasses.replace(   # noqa: E731
            with_depth(cfg, u), unroll=True, ssm_chunk=-1)
        c1 = compile_cell(mk(1), shape, mesh)
        c2 = compile_cell(mk(2), shape, mesh)
        d = depth_delta(c1["cost"], c2["cost"], c1["collectives"],
                        c2["collectives"], 1, depth_units(cfg))
        terms = roofline_terms(d["flops"], d["bytes"], d["collective_bytes"],
                               chips, HW.PEAK_BF16_FLOPS, HW.HBM_BW,
                               HW.ICI_BW)
        mf = model_flops(cfg, shape)
        terms["model_flops"] = mf
        terms["useful_ratio"] = mf / (d["flops"] * chips) if d["flops"] else 0
        rec["roofline"] = terms
    rec["wall_s"] = round(time.time() - t0, 1)
    os.makedirs(PERF_DIR, exist_ok=True)
    safe = arch.replace(".", "_")
    path = os.path.join(PERF_DIR,
                        f"{safe}__{shape_name}__{mesh_kind}__{variant}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", choices=tuple(SHAPES), required=True)
    ap.add_argument("--mesh", choices=("pod1", "pod2"), default="pod1")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--skip-delta", action="store_true")
    args = ap.parse_args()
    rec = run_variant(args.arch, args.shape, args.mesh, args.variant,
                      args.accum, args.skip_delta)
    m = rec["full"]["memory"]
    line = {
        "variant": args.variant,
        "peak_gb": round(m["peak_per_device_bytes"] / 1e9, 2),
        "fits": m["fits_hbm"],
        "coll_gb_full": round(rec["full"]["collectives"]["total"] / 1e9, 3),
    }
    if "roofline" in rec:
        ro = rec["roofline"]
        line.update(compute_s=round(ro["compute_s"], 4),
                    memory_s=round(ro["memory_s"], 4),
                    collective_s=round(ro["collective_s"], 4),
                    bottleneck=ro["bottleneck"],
                    useful=round(ro["useful_ratio"], 3))
    print(json.dumps(line))


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # simulated devices: never take a chip

"""Multi-pod dry-run (deliverable e): ``.lower().compile()`` every
(architecture x input-shape x mesh) cell on the production meshes —
(data=16, model=16) single pod and (pod=2, data=16, model=16) = 512 chips —
and record memory / cost / collective-schedule evidence for §Dry-run and
§Roofline.

The lines above MUST precede every other import (jax locks the device
count and platform at first init).  This is a simulated-device tool: it is
pinned to the CPU, and so is every per-cell child it starts, so it can
never hold a TPU that another process needs.

Usage:
  python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k --mesh pod1
  python -m repro.launch.dryrun --all          # every cell, subprocess-per-cell
  python -m repro.launch.dryrun --all --filter train_4k
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_platforms", "cpu")   # also when imported after jax

from ..configs import ARCH_IDS, SHAPES, cell_supported, get_config
from ..configs.shapes import ShapeSpec
from ..distributed import hints
from ..distributed.hlo_analysis import (collective_bytes, depth_delta,
                                        flops_and_bytes, roofline_terms)
from ..distributed.sharding import (batch_shardings, cache_shardings,
                                    opt_state_shardings, params_shardings,
                                    replicated)
from ..models import abstract_params, build_model
from ..models.common import ArchConfig
from ..training.optimizer import OptConfig, abstract_opt_state
from ..training.train_step import make_train_step
from .mesh import HW, make_production_mesh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


# ---------------------------------------------------------------------------
def with_depth(cfg: ArchConfig, units: int) -> ArchConfig:
    """Same width, reduced depth (for the depth-delta roofline method)."""
    if cfg.family == "hybrid":
        return dataclasses.replace(cfg, n_layers=units * cfg.attn_every)
    if cfg.family in ("encdec", "audio"):
        return dataclasses.replace(cfg, n_layers=units, n_enc_layers=units)
    return dataclasses.replace(cfg, n_layers=units)


def depth_units(cfg: ArchConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def input_specs(cfg: ArchConfig, shape: ShapeSpec, model) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        n_tok = s - (cfg.n_patches or 0)
        batch = {"tokens": jax.ShapeDtypeStruct((b, n_tok), i32),
                 "labels": jax.ShapeDtypeStruct((b, n_tok), i32)}
        if cfg.family in ("audio", "encdec"):
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.n_frames, cfg.d_model), jnp.float32)
        if cfg.n_patches:
            batch["patches"] = jax.ShapeDtypeStruct(
                (b, cfg.n_patches, cfg.d_model), jnp.float32)
        return {"batch": batch}
    if shape.kind == "prefill":
        n_tok = s - (cfg.n_patches or 0)
        out = {"tokens": jax.ShapeDtypeStruct((b, n_tok), i32),
               "cache": model.cache_specs(b, s)}
        if cfg.family in ("audio", "encdec"):
            out["extra"] = jax.ShapeDtypeStruct(
                (b, cfg.n_frames, cfg.d_model), jnp.float32)
        if cfg.n_patches:
            out["extra"] = jax.ShapeDtypeStruct(
                (b, cfg.n_patches, cfg.d_model), jnp.float32)
        return out
    # decode: one new token against a seq_len KV cache
    return {"token": jax.ShapeDtypeStruct((b, 1), i32),
            "cache": model.cache_specs(b, s),
            "pos": jax.ShapeDtypeStruct((b,), i32)}


# ---------------------------------------------------------------------------
def build_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, accum: int = 1):
    """Returns (fn, arg_specs tuple, in_shardings tuple, donate_argnums)."""
    model = build_model(cfg)
    pspecs = abstract_params(model.param_specs())
    pshard = params_shardings(pspecs, mesh, cfg)
    specs = input_specs(cfg, shape, model)

    if shape.kind == "train":
        opt_cfg = OptConfig(total_steps=1000)
        step = make_train_step(model, opt_cfg, accum_steps=accum)
        state = {"params": pspecs, "opt": abstract_opt_state(pspecs)}
        state_sh = {"params": pshard,
                    "opt": opt_state_shardings(pshard, mesh, pspecs)}
        bsh = batch_shardings(mesh, specs["batch"])
        return step, (state, specs["batch"]), (state_sh, bsh), (0,)

    if shape.kind == "prefill":
        csh = cache_shardings(mesh, specs["cache"], cfg)
        tsh = batch_shardings(mesh, {"t": specs["tokens"]})["t"]
        if "extra" in specs:
            esh = batch_shardings(mesh, {"e": specs["extra"]})["e"]

            def fn(params, tokens, cache, extra):
                return model.prefill(params, tokens, cache, extra)

            return fn, (pspecs, specs["tokens"], specs["cache"],
                        specs["extra"]), (pshard, tsh, csh, esh), (2,)

        def fn(params, tokens, cache):
            return model.prefill(params, tokens, cache)

        return fn, (pspecs, specs["tokens"], specs["cache"]), \
            (pshard, tsh, csh), (2,)

    # decode
    csh = cache_shardings(mesh, specs["cache"], cfg)
    tsh = batch_shardings(mesh, {"t": specs["token"]})["t"]
    psh = batch_shardings(mesh, {"p": specs["pos"]})["p"]

    def fn(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos)

    return fn, (pspecs, specs["token"], specs["cache"], specs["pos"]), \
        (pshard, tsh, csh, psh), (2,)


def compile_cell(cfg: ArchConfig, shape: ShapeSpec, mesh,
                 want_hlo: bool = False, accum: int = 1) -> Dict[str, Any]:
    fn, arg_specs, in_sh, donate = build_cell(cfg, shape, mesh, accum=accum)
    t0 = time.perf_counter()
    with hints.use_mesh_hints(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh,
                          donate_argnums=donate).lower(*arg_specs)
        t1 = time.perf_counter()
        compiled = lowered.compile()
    t2 = time.perf_counter()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    rec = {
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_per_device_bytes": int(ma.argument_size_in_bytes
                                         + ma.temp_size_in_bytes
                                         + ma.output_size_in_bytes
                                         - ma.alias_size_in_bytes),
        },
        "cost": flops_and_bytes(ca),
        "collectives": coll,
    }
    rec["memory"]["fits_hbm"] = rec["memory"]["peak_per_device_bytes"] \
        <= HW.HBM_BYTES
    if want_hlo:
        rec["hlo_head"] = "\n".join(
            l for l in hlo.splitlines()
            if any(c in l for c in ("all-reduce", "all-gather",
                                    "reduce-scatter", "all-to-all",
                                    "collective-permute")))[:20000]
    return rec


def model_flops(cfg: ArchConfig, shape: ShapeSpec) -> float:
    n_act = cfg.n_active_params()
    if shape.kind == "train":
        return 6.0 * n_act * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_act * shape.global_batch * shape.seq_len
    return 2.0 * n_act * shape.global_batch            # decode: one token


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             skip_delta: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    chips = 512 if mesh_kind == "pod2" else 256
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "chips": chips}
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "pod2"))
    dp = 32 if mesh_kind == "pod2" else 16
    try:
        # auto-microbatching: escalate grad-accum until the step fits HBM
        # (production launcher behaviour; per-token costs are unchanged)
        accum_tried = []
        full = None
        accum = 1
        max_accum = 16
        while True:
            full = compile_cell(cfg, shape, mesh, accum=accum)
            accum_tried.append(
                {"accum": accum,
                 "temp_gb": round(full["memory"]["temp_bytes"] / 1e9, 2),
                 "fits": full["memory"]["fits_hbm"]})
            if shape.kind != "train" or full["memory"]["fits_hbm"]:
                break
            # jump straight to the overshoot-implied accumulation level
            over = full["memory"]["peak_per_device_bytes"] / HW.HBM_BYTES
            nxt = accum
            while nxt < over * accum and nxt < max_accum:
                nxt *= 2
            nxt = max(nxt, accum * 2)
            if nxt > max_accum or shape.global_batch % (nxt * dp) != 0:
                break
            accum = nxt
        rec["accum"] = accum_tried
        rec["full"] = full
        if not skip_delta:
            # depth-delta roofline correction: XLA cost_analysis counts scan
            # bodies ONCE regardless of trip count (verified: flops are
            # depth-invariant under scan), so the delta compiles UNROLL the
            # layer loop and collapse ssm chunk scans to one trip so every
            # instance is counted (see distributed/hlo_analysis.py).
            u = 1
            mk = lambda uu: dataclasses.replace(     # noqa: E731
                with_depth(cfg, uu), unroll=True, ssm_chunk=-1)
            c1 = compile_cell(mk(u), shape, mesh)
            c2 = compile_cell(mk(u + 1), shape, mesh)
            d = depth_delta(c1["cost"], c2["cost"], c1["collectives"],
                            c2["collectives"], u, depth_units(cfg))
            rec["delta"] = d
            terms = roofline_terms(d["flops"], d["bytes"],
                                   d["collective_bytes"], chips,
                                   HW.PEAK_BF16_FLOPS, HW.HBM_BW, HW.ICI_BW)
            mf = model_flops(cfg, shape)
            terms["model_flops"] = mf
            terms["hlo_flops_total"] = d["flops"] * chips
            terms["useful_ratio"] = (mf / (d["flops"] * chips)
                                     if d["flops"] else 0.0)
            rec["roofline"] = terms
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record compile failures as data
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:])
    return rec


# ---------------------------------------------------------------------------
def cell_path(arch, shape, mesh_kind):
    os.makedirs(OUT_DIR, exist_ok=True)
    safe = arch.replace("/", "_").replace(".", "_")
    return os.path.join(OUT_DIR, f"{safe}__{shape}__{mesh_kind}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("pod1", "pod2"), default="pod1")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--filter", default="",
                    help="substring filter on '<arch>__<shape>__<mesh>'")
    ap.add_argument("--skip-existing", action="store_true", default=True)
    ap.add_argument("--no-skip-existing", dest="skip_existing",
                    action="store_false")
    ap.add_argument("--skip-delta", action="store_true")
    args = ap.parse_args()

    if args.all:
        cells = [(a, s, m) for a in ARCH_IDS for s in SHAPES
                 for m in ("pod1", "pod2")]
        cells = [c for c in cells
                 if args.filter in f"{c[0]}__{c[1]}__{c[2]}"]
        for arch, shape, mesh_kind in cells:
            path = cell_path(arch, shape, mesh_kind)
            if args.skip_existing and os.path.exists(path):
                print(f"[skip-existing] {path}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_kind]
            if args.skip_delta or mesh_kind == "pod2":
                # §Roofline is single-pod; pod2 cells only need the
                # compile + memory + collective-schedule proof.
                cmd.append("--skip-delta")
            print(">>", " ".join(cmd), flush=True)
            r = subprocess.run(cmd, cwd=os.getcwd())
            if r.returncode != 0:
                print(f"[subprocess failed] {arch} {shape} {mesh_kind}")
        return

    assert args.arch and args.shape
    rec = run_cell(args.arch, args.shape, args.mesh,
                   skip_delta=args.skip_delta)
    path = cell_path(args.arch, args.shape, args.mesh)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("full", "delta")}, indent=1))
    if rec["status"] == "ok":
        m = rec["full"]["memory"]
        print(f"memory/device: args={m['argument_bytes']/1e9:.2f}GB "
              f"temp={m['temp_bytes']/1e9:.2f}GB fits_hbm={m['fits_hbm']}")
        if "roofline" in rec:
            print("roofline:", json.dumps(rec["roofline"]))
    sys.exit(0 if rec["status"] in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()

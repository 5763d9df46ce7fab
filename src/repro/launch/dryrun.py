import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + os.environ.get("DRYRUN_DEVICES", "512")).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and record memory/cost/collective analysis.

MUST be the process entry point (jax locks the device count on first
init — the XLA_FLAGS line above precedes every other import).

  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
      --shape train_4k --mesh single
  DRYRUN_DEVICES=32 ... --devices-override 32   # small-mesh smoke mode

Outputs one JSON per cell under --out (default experiments/dryrun/).
"""

import argparse       # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES, cell_applicable,  # noqa: E402
                           get_config)
from repro.launch import cells as cells_mod                    # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402
from repro.models import transformer as tf                     # noqa: E402
from repro.roofline import analysis as ra                      # noqa: E402


def make_mesh_for(args, multi_pod: bool):
    if args.devices_override:
        n = args.devices_override
        if multi_pod:
            return make_mesh((2, n // 8, 4), ("pod", "data", "model"))
        return make_mesh((n // 4, 4), ("data", "model"))
    return make_production_mesh(multi_pod=multi_pod)


def _compile_variant(cfg, shape, mesh, state_bits, variant="baseline"):
    cell = cells_mod.make_cell(cfg, shape, mesh, state_bits=state_bits,
                               variant=variant)
    lowered = cells_mod.lower_cell(cell, mesh)
    return lowered.compile()


def _per_device_costs(compiled):
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    text = compiled.as_text()
    raw, per_kind = ra.collective_bytes(text)
    weighted = sum(ra._ALGO_FACTOR[k] * v for k, v in per_kind.items())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(weighted), per_kind, text)


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             out_dir: str, state_bits: int = 32,
             cfg_override=None, variant: str = "baseline") -> dict:
    """Compile the production (scan-over-layers) program for the memory
    proof, plus two small *unrolled* calibration programs (1 and 2 pattern
    repeats) whose per-layer costs extrapolate linearly to full depth —
    XLA's cost analysis counts loop bodies once, so the scan program's
    FLOPs/bytes/collectives must be reconstructed this way (verified in
    EXPERIMENTS.md §Methodology)."""
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "mesh_shape": dict(mesh.shape), "status": "skip", "why": why}
    if not ok:
        return rec
    chips = int(len(mesh.devices.flat))
    t0 = time.time()

    # 1) production program: scan over layers — the compile-success proof
    #    and the per-device memory analysis
    full_cfg = cfg.replace(scan_layers=True, attn_impl="xla")
    compiled = _compile_variant(full_cfg, shape, mesh, state_bits, variant)
    t_full = time.time() - t0
    mem = compiled.memory_analysis()
    mem_rec = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
        if mem is not None and hasattr(mem, field):
            mem_rec[field] = int(getattr(mem, field))
    coll_counts = ra.collective_counts(compiled.as_text())

    # 2) calibration: unrolled at 1 and 2 pattern repeats
    p = len(cfg.pattern)

    def cal_cfg(reps):
        kw = dict(n_layers=p * reps, scan_layers=False, attn_impl="xla")
        if cfg.enc_dec:
            kw["n_enc_layers"] = max(1, cfg.n_enc_layers
                                     // cfg.n_repeat * reps)
        return cfg.replace(**kw)

    f1, b1, c1, _, _ = _per_device_costs(
        _compile_variant(cal_cfg(1), shape, mesh, state_bits, variant))
    f2, b2, c2, kinds2, _ = _per_device_costs(
        _compile_variant(cal_cfg(2), shape, mesh, state_bits, variant))
    r = cfg.n_repeat
    flops_dev = f1 + (f2 - f1) * (r - 1)
    bytes_dev = b1 + (b2 - b1) * (r - 1)
    coll_dev = c1 + (c2 - c1) * (r - 1)
    t_cal = time.time() - t0 - t_full

    n_total, n_active = ra.count_active_params(cfg, tf.param_shapes(cfg))
    mf = ra.model_flops(cfg, shape, n_total, n_active)
    roof = ra.Roofline(
        name=f"{arch}/{shape_name}/{mesh_name}", chips=chips,
        hlo_flops=flops_dev * chips, hlo_bytes=bytes_dev * chips,
        coll_bytes=coll_dev * chips, coll_per_kind=kinds2,
        model_flops=mf)
    rec.update(
        status="ok",
        seconds_compile=round(t_full, 2),
        seconds_calibration=round(t_cal, 2),
        memory=mem_rec,
        per_device_bytes=(mem_rec.get("argument_size_in_bytes", 0)
                          + mem_rec.get("temp_size_in_bytes", 0)),
        per_device_flops=flops_dev,
        per_device_coll_bytes=coll_dev,
        n_params_total=n_total, n_params_active=n_active,
        collective_counts=coll_counts,
        roofline=roof.row(),
    )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--state-bits", type=int, default=32)
    ap.add_argument("--devices-override", type=int, default=0,
                    help="small-mesh smoke mode (set DRYRUN_DEVICES too)")
    ap.add_argument("--variant", default="baseline",
                    help="baseline | tiara_decode | remat_layer | "
                         "moe_hints | remat_layer+moe_hints")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for multi in meshes:
        mesh = make_mesh_for(args, multi)
        mesh_name = "pod2x16x16" if multi else "pod16x16"
        if args.devices_override:
            mesh_name += f"_ovr{args.devices_override}"
        for arch in archs:
            for shape_name in shapes:
                tag = f"{arch}__{shape_name}__{mesh_name}"
                if args.variant != "baseline":
                    tag += f"__{args.variant}"
                path = os.path.join(args.out, tag + ".json")
                t0 = time.time()
                try:
                    rec = run_cell(arch, shape_name, mesh, mesh_name,
                                   args.out, state_bits=args.state_bits,
                                   variant=args.variant)
                except Exception as e:      # noqa: BLE001 — record & go on
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "fail",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                rec["wall_s"] = round(time.time() - t0, 2)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1, default=str)
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skip"
                n_fail += st == "fail"
                extra = ""
                if st == "ok":
                    r = rec["roofline"]
                    extra = (f"dom={r['dominant']} "
                             f"comp={r['compute_s']*1e3:.2f}ms "
                             f"mem={r['memory_s']*1e3:.2f}ms "
                             f"coll={r['collective_s']*1e3:.2f}ms "
                             f"dev={rec['per_device_bytes']/2**30:.2f}GiB")
                elif st == "fail":
                    extra = rec["error"][:120]
                print(f"[{st:4s}] {tag} ({rec['wall_s']}s) {extra}",
                      flush=True)
    print(f"dry-run complete: {n_ok} ok, {n_skip} skip, {n_fail} fail")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# a compile-on-host tool: never claim an attached accelerator (rungrid
# starts several of these at once, and a chip takes one process)
os.environ["JAX_PLATFORMS"] = "cpu"

# Multi-pod dry-run: lower + compile every (arch × shape) cell on the
# production mesh and record memory/cost/collective analysis.
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b \
#         --shape decode_32k [--multi-pod] [--out artifacts/dryrun]
#
# The XLA_FLAGS / JAX_PLATFORMS assignments above are the VERY FIRST
# statements — before ANY other import — because jax locks the platform and
# device count on first init; nothing
# else in the repo sets it globally (smoke tests/benches see 1 device).

import argparse
import json
import sys
import time
import traceback

import jax

from repro.analysis.roofline import model_flops_for, roofline
from repro.config import SHAPES
from repro.configs import ASSIGNED_ARCHS, PAPER_ARCHS, get_config
from repro.launch import specs as sp
from repro.launch.mesh import make_mesh_config, make_production_mesh


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             out_dir: str = "artifacts/dryrun", rank_ratio: float = 0.25,
             sals_enabled: bool = True, dist_mode: str = "local",
             seq_parallel: bool = True, microbatches: int = 1,
             remat: str = "block", save_hlo: bool = False,
             k_latent_dtype: str = "bfloat16", strategy: str = "tp_sp",
             tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    record: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "multi_pod": multi_pod, "sals": sals_enabled,
                    "rank_ratio": rank_ratio, "dist_mode": dist_mode,
                    "tag": tag}

    ok, reason = sp.cell_status(cfg, shape)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        _write(out_dir, record, tag)
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_cfg = make_mesh_config(multi_pod=multi_pod, dist_mode=dist_mode,
                                seq_parallel=seq_parallel)
    chips = mesh.devices.size

    kw: dict = {}
    if shape.kind == "train":
        if strategy == "auto":
            # fsdp wins for dense models whose batch covers the mesh
            # (§Perf C2); MoE keeps EP + tp_sp (§Perf B1/B2); multi-pod
            # (batch 256 < 512 chips) keeps tp_sp
            n_dev = 512 if multi_pod else 256
            strategy = "fsdp" if (cfg.family != "moe"
                                  and shape.global_batch % n_dev == 0) \
                else "tp_sp"
        kw = {"microbatches": microbatches, "remat": remat,
              "strategy": strategy}
        record["strategy"] = strategy
    else:
        kw = {"rank_ratio": rank_ratio, "sals_enabled": sals_enabled,
              "k_latent_dtype": k_latent_dtype}
        if shape.kind == "decode":
            kw["dist_mode"] = dist_mode

    t0 = time.time()
    try:
        fn, args, in_sh, out_sh = sp.build_step(shape.kind, cfg, shape, mesh,
                                                mesh_cfg, **kw)
        with mesh:
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
    except Exception as e:  # noqa: BLE001
        record["status"] = "failed"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc(limit=8)
        _write(out_dir, record, tag)
        return record

    record["status"] = "ok"
    record["lower_s"] = round(t_lower, 1)
    record["compile_s"] = round(t_compile, 1)
    record["xla_cost_analysis"] = {
        k: cost.get(k) for k in ("flops", "bytes accessed")
        if cost and k in cost} if cost else {}
    if mem is not None:
        record["memory_analysis"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0) +
                          (getattr(mem, "argument_size_in_bytes", 0) or 0),
        }
    peak = record.get("memory_analysis", {}).get("peak_bytes")

    rep = roofline(arch, cfg, shape, mesh_name, chips, hlo, peak)
    record["roofline"] = rep.to_json()
    record["model_flops"] = model_flops_for(cfg, shape)
    if save_hlo:
        hpath = _path(out_dir, record, tag) + ".hlo.txt"
        with open(hpath, "w") as f:
            f.write(hlo)
        record["hlo_path"] = hpath
    _write(out_dir, record, tag)
    return record


def _path(out_dir: str, record: dict, tag: str) -> str:
    os.makedirs(out_dir, exist_ok=True)
    t = f".{tag}" if tag else ""
    return os.path.join(out_dir, f"{record['arch']}.{record['shape']}."
                                 f"{record['mesh']}{t}")


def _write(out_dir: str, record: dict, tag: str) -> None:
    with open(_path(out_dir, record, tag) + ".json", "w") as f:
        json.dump(record, f, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True,
                    choices=ASSIGNED_ARCHS + PAPER_ARCHS)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--rank-ratio", type=float, default=0.25)
    ap.add_argument("--no-sals", action="store_true",
                    help="baseline: full-attention decode, no compression")
    ap.add_argument("--dist-mode", default="local",
                    choices=("local", "global"))
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="block",
                    choices=("none", "block", "save_dots"))
    ap.add_argument("--strategy", default="auto",
                    choices=("auto", "tp_sp", "fsdp", "ep_dp"),
                    help="train parallelism: Megatron TP+SP or pure ZeRO-3")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--latent-int8", action="store_true",
                    help="beyond-paper: int8-quantized latent key cache")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   out_dir=args.out, rank_ratio=args.rank_ratio,
                   sals_enabled=not args.no_sals, dist_mode=args.dist_mode,
                   seq_parallel=not args.no_seq_parallel,
                   microbatches=args.microbatches, remat=args.remat,
                   save_hlo=args.save_hlo,
                   k_latent_dtype="int8" if args.latent_int8 else "bfloat16",
                   strategy=args.strategy, tag=args.tag)
    status = rec["status"]
    if status == "ok":
        r = rec["roofline"]
        mem = rec.get("memory_analysis", {})
        print(f"[dryrun] {rec['arch']} × {rec['shape']} × {rec['mesh']}: OK "
              f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)")
        print(f"  per-dev: flops={r['hlo_flops']:.3e} bytes={r['hlo_bytes']:.3e} "
              f"coll={r['collective_bytes']:.3e}")
        print(f"  terms(s): compute={r['t_compute']:.4f} "
              f"memory={r['t_memory']:.4f} collective={r['t_collective']:.4f}"
              f"  bound={r['bound']}  useful={r['useful_ratio']:.2f}")
        if mem:
            print(f"  memory_analysis: args={_gb(mem['argument_bytes'])} "
                  f"temps={_gb(mem['temp_bytes'])} "
                  f"peak≈{_gb(mem['peak_bytes'])} per device")
        return 0
    if status == "skipped":
        print(f"[dryrun] {rec['arch']} × {rec['shape']}: SKIPPED — "
              f"{rec['reason']}")
        return 0
    print(f"[dryrun] {rec['arch']} × {rec['shape']} × {rec['mesh']}: FAILED\n"
          f"{rec['error']}\n{rec.get('traceback', '')}")
    return 1


def _gb(x) -> str:
    return f"{x / 2**30:.2f}GiB" if x is not None else "?"


if __name__ == "__main__":
    sys.exit(main())

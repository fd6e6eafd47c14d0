"""Roofline analysis over the dry-run records (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh) cell, three per-device time lower bounds on
TPU v5e:

  compute    = dot_FLOPs_per_device / 197e12           (bf16 peak)
  memory     = bytes_accessed_per_device / 819e9       (HBM bw)
  collective = wire_bytes_per_device / 50e9            (per-link ICI)

Sources: dot FLOPs and collective payloads from the loop-corrected HLO
walker (benchmarks/hlo_analysis — cost_analysis counts while bodies once);
bytes-accessed from compiled.cost_analysis() corrected by the same loop
multiplier implied by the flops ratio.  Wire factors per algorithm:
all-reduce 2(n-1)/n, all-gather/reduce-scatter/all-to-all (n-1)/n,
collective-permute 1 (payloads are already per-device tensor bytes).

MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) for training; 2*N*D per
generated/prefilled token for inference cells.  The ratio
MODEL_FLOPS / HLO_FLOPs shows how much compiled compute is "useful"
(catching remat/dispatch waste).

``--lut`` switches to the LUT-cascade roofline (docs/KERNELS.md §5): it
prints the autotuner's modeled candidate grid — (mode, block_b,
unit_tile) x {compute roof, memory roof, VMEM feasibility} from
``repro.kernels.autotune.roofline_candidates`` — for each paper task, and
writes the per-(task, device) choice table to
``experiments/AUTOTUNE_choices.json`` (the nightly CI uploads it as the
autotuner audit artifact).  Pure model output: no training, no timing.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                           "dryrun")

WIRE_FACTOR = {
    "all-reduce": 2.0,        # ring: 2(n-1)/n ~= 2
    "all-gather": 1.0,        # (n-1)/n ~= 1
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def load_records(results_dir: Optional[str] = None) -> List[dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(results_dir or RESULTS_DIR,
                                              "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def model_flops(cfg, shape) -> float:
    """Analytic useful-FLOPs for the cell (global, forward+backward for
    train; forward for serve)."""
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def cell_roofline(rec: dict, cfg, shape) -> Optional[dict]:
    if rec.get("status") != "ok":
        return None
    ana = rec.get("analysis") or {}
    flops_dev = ana.get("dot_flops_per_device", 0.0)
    coll = ana.get("collective_bytes_per_device", {})
    wire_bytes = sum(WIRE_FACTOR[k] * v for k, v in coll.items()
                     if k in WIRE_FACTOR)
    # bytes accessed: cost_analysis is loop-body-once; scale by the same
    # multiplier the flop walker implies (bounded to >= 1).
    ca = rec.get("cost_analysis", {})
    raw_flops = ca.get("flops", 0.0) or 1.0
    mult = max(1.0, flops_dev / raw_flops) if raw_flops else 1.0
    bytes_dev = (ca.get("bytes accessed", 0.0) or 0.0) * mult

    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll = wire_bytes / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_flops_global = flops_dev * rec["devices"]
    bound = max(terms.values())
    ideal = mf / (rec["devices"] * PEAK_FLOPS)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "devices": rec["devices"],
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant.replace("_s", ""),
        "model_flops": mf,
        "hlo_flops_global": hlo_flops_global,
        "useful_ratio": round(mf / hlo_flops_global, 3)
        if hlo_flops_global else None,
        # fraction of ideal (pure-compute) step time if the dominant
        # bound were achieved:
        "roofline_fraction": round(ideal / bound, 3) if bound else None,
        "loop_mult": round(mult, 1),
    }


def build_table(results_dir: Optional[str] = None) -> List[dict]:
    from repro.configs import lm_archs
    from repro.launch import steps

    rows = []
    for rec in load_records(results_dir):
        cfg = lm_archs.get(rec["arch"])
        shape = steps.SHAPES[rec["shape"]]
        if rec.get("status") == "skipped":
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "mesh": rec["mesh"], "status": "skipped",
                         "reason": rec.get("reason", "")})
            continue
        row = cell_roofline(rec, cfg, shape)
        if row:
            row["status"] = "ok"
            rows.append(row)
        else:
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "mesh": rec["mesh"], "status": rec.get("status")})
    return rows


def print_table(mesh: str = "single",
                results_dir: Optional[str] = None) -> List[dict]:
    rows = [r for r in build_table(results_dir) if r["mesh"] == mesh]
    hdr = (f"{'arch':<15} {'shape':<12} {'comp ms':>8} {'mem ms':>8} "
           f"{'coll ms':>8} {'dominant':>10} {'useful':>7} {'roofl%':>7}")
    print(hdr)
    print("-" * len(hdr))
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        if r.get("status") != "ok":
            print(f"{r['arch']:<15} {r['shape']:<12} "
                  f"{'[' + r.get('status', '?') + ']':>8}")
            continue
        print(f"{r['arch']:<15} {r['shape']:<12} "
              f"{r['compute_s'] * 1e3:8.2f} {r['memory_s'] * 1e3:8.2f} "
              f"{r['collective_s'] * 1e3:8.2f} {r['dominant']:>10} "
              f"{r['useful_ratio'] if r['useful_ratio'] is not None else '-':>7} "
              f"{(r['roofline_fraction'] or 0) * 100:6.1f}%")
    return rows


def markdown_table(results_dir: Optional[str] = None) -> str:
    lines = []
    for mesh in ("single", "multi"):
        rows = [r for r in build_table(results_dir) if r["mesh"] == mesh]
        lines.append(f"\n#### mesh: {mesh}\n")
        lines.append("| arch | shape | compute ms | memory ms | "
                     "collective ms | dominant | useful | roofline |")
        lines.append("|---|---|---|---|---|---|---|---|")
        for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
            if r.get("status") != "ok":
                lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                             f"{r.get('status')} | — | — |")
                continue
            lines.append(
                f"| {r['arch']} | {r['shape']} | "
                f"{r['compute_s'] * 1e3:.1f} | {r['memory_s'] * 1e3:.1f} | "
                f"{r['collective_s'] * 1e3:.1f} | {r['dominant']} | "
                f"{r['useful_ratio']} | "
                f"{(r['roofline_fraction'] or 0) * 100:.1f}% |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# LUT-cascade roofline (--lut): the kernel autotuner's model, printed
# ---------------------------------------------------------------------------

LUT_CHOICES_OUT = os.path.join(os.path.dirname(__file__), "..",
                               "experiments", "AUTOTUNE_choices.json")


def print_lut_candidates(task: str = "nid", device: str = "cpu",
                         batch: int = 4096) -> List[dict]:
    """Print the modeled candidate grid for one task's fused cascade."""
    from repro.configs import paper_tasks
    from repro.kernels import autotune

    layers = autotune.config_layers(paper_tasks.task_config(task))
    rows = autotune.roofline_candidates(layers, batch=batch, device=device)
    pick = autotune.pick_tuning(layers, batch=batch, device=device)
    hdr = (f"{'mode':<9} {'block_b':>7} {'tile':>5} {'comp us':>8} "
           f"{'mem us':>8} {'bound':>7} {'rows/s':>12} {'vmem KiB':>9} "
           f"{'fits':>5}")
    print(f"\n=== {task} @ {device} (batch {batch}) ===")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        star = " *" if (r["mode"] == pick.mode
                        and r["block_b"] == pick.block_b
                        and (r["mode"] == "resident"
                             or r["unit_tile"] == pick.unit_tile)) else ""
        print(f"{r['mode']:<9} {r['block_b']:>7} "
              f"{r['unit_tile'] or '-':>5} {r['t_compute_us']:>8.2f} "
              f"{r['t_memory_us']:>8.2f} {r['bound']:>7} "
              f"{r['rows_per_s']:>12,.0f} {r['vmem_bytes'] / 1024:>9.0f} "
              f"{'y' if r['fits_vmem'] else 'N':>5}{star}")
    print(f"pick: mode={pick.mode} block_b={pick.block_b} "
          f"unit_tile={pick.unit_tile} (source={pick.source})")
    return rows


def write_lut_choices(out: str = LUT_CHOICES_OUT) -> str:
    """Write the per-(task, device) autotuner choice table (nightly CI)."""
    from repro.kernels import autotune

    doc = autotune.choice_table()
    out = os.path.abspath(out)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return out


def lut_main(out: str, tasks=None,
             devices=("cpu", "TPU v5 lite")) -> None:
    from repro.configs import paper_tasks

    for task in tasks or sorted(paper_tasks.TASKS):
        for dev in devices:
            print_lut_candidates(task, dev)
    print(f"\nwrote {write_lut_choices(out)}")


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("results_dir", nargs="?", default=None,
                    help="dry-run records dir (LM roofline mode)")
    ap.add_argument("--lut", action="store_true",
                    help="LUT-cascade autotuner roofline instead of the "
                         "LM dry-run table")
    ap.add_argument("--out", default=LUT_CHOICES_OUT,
                    help="--lut: where to write the choice table JSON")
    args = ap.parse_args()
    if args.lut:
        lut_main(args.out)
        return
    for mesh in ("single", "multi"):
        print(f"\n=== mesh: {mesh} ===")
        print_table(mesh, args.results_dir)


if __name__ == "__main__":
    main()

"""Roofline analysis over the dry-run artifacts.

Per (arch x shape x mesh) cell, from the compiled SPMD module (per-device
quantities):

    compute term    = HLO_FLOPs / peak_FLOP/s          (197 TFLOP/s bf16)
    memory term     = HLO_bytes / HBM_bw               (819 GB/s)
    collective term = collective operand bytes / link_bw  (50 GB/s/link)

plus MODEL_FLOPS (analytic useful compute, 6·N·D train / 2·N·D inference,
active params for MoE) and the useful-compute ratio that catches
remat/redundancy waste. Emits the EXPERIMENTS.md tables.

Usage: PYTHONPATH=src python -m repro.launch.roofline [--dir runs/dryrun]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # bytes/s
LINK_BW = 50e9           # bytes/s per ICI link


def model_flops_per_device(arch: str, shape: str, n_chips: int) -> float | None:
    """Analytic useful FLOPs per device for one step (None = N/A)."""
    from repro.configs import get_arch
    spec = get_arch(arch)
    if spec.family == "lm":
        cfg = spec.model
        n_active = cfg.num_active_params()
        sh = spec.shapes[shape]
        tokens = sh["global_batch"] * (sh["seq_len"] if sh["kind"] != "decode" else 1)
        mult = 6 if sh["kind"] == "train" else 2
        return mult * n_active * tokens / n_chips
    if spec.family == "recsys":
        cfg = spec.model
        f, d = cfg.n_sparse, cfg.embed_dim
        dense = 0
        fk = f
        for h in cfg.cin_layers:
            dense += h * f * fk * d            # CIN einsum per sample
            fk = h
        dims = [f * d] + list(cfg.mlp_layers) + [1]
        dense += sum(dims[i] * dims[i + 1] for i in range(len(dims) - 1))
        sh = spec.shapes[shape]
        b = sh["batch"]
        if sh["kind"] == "retrieval":
            return 2 * b * sh["n_candidates"] * cfg.d_query / n_chips
        mult = 6 if sh["kind"] == "train" else 2
        return mult * dense * b / n_chips
    if spec.family == "gnn":
        sh = spec.shapes[shape]
        cfg = spec.model(sh) if callable(spec.model) else spec.model
        if sh["kind"] == "dist_full":
            n, e = sh["n_nodes"], sh["n_edges"]
        elif sh["kind"] == "minibatch":
            seeds = sh["batch_nodes"]
            f1, f2 = sh["fanouts"]
            n = seeds * (1 + f1 + f1 * f2)
            e = seeds * (f1 + f1 * f2)
        else:
            n = sh["n_nodes"] * sh["batch"]
            e = sh["n_edges"] * sh["batch"]
        name = spec.name
        if name == "gcn-cora":
            h = cfg.d_hidden
            per = 2 * (n * cfg.d_in * h + e * h + n * h * cfg.n_classes + e * cfg.n_classes)
        elif name in ("meshgraphnet", "graphcast"):
            h = cfg.d_hidden
            din = getattr(cfg, "n_vars", getattr(cfg, "d_node_in", h))
            per = 2 * (n * din * h + cfg.n_layers * (e * (3 * h) * h + e * h * h
                                                     + n * (2 * h) * h + n * h * h))
        else:  # mace: A-basis + correlation products
            c = cfg.d_hidden
            per = 2 * cfg.n_layers * (e * 3 * c * 9 + n * c * c * 9 + n * c * 9 * 9 * 2)
        mult = 3 if "train" not in sh.get("kind", "") else 3
        return 3 * per / n_chips     # fwd+bwd ~ 3x fwd
    return None   # bfs: traversal has no useful MXU FLOPs


def load_records(run_dir: str) -> list:
    out = []
    for path in sorted(glob.glob(os.path.join(run_dir, "*.json"))):
        rec = json.load(open(path))
        out.append(rec)
    return out


def _true_depth(arch: str, shape: str) -> int | None:
    from repro.configs import get_arch
    spec = get_arch(arch)
    cfg = spec.model(spec.shapes[shape]) if callable(spec.model) else spec.model
    return getattr(cfg, "n_layers", None)


def _scan_corrected(records: list) -> dict:
    """Exact-flop correction: XLA counts scan bodies once, so scanned stacks
    are lowered unrolled at L=2 and L=4 and extrapolated linearly to the true
    depth (exact for homogeneous layers). Returns {(arch, shape): corrected
    metrics} for the single-pod mesh."""
    by_key = {(r["arch"], r["shape"], r["mesh"]): r for r in records if r.get("ok")}
    out = {}
    for (arch, shape, mesh), r2 in by_key.items():
        if not mesh.endswith("_L2"):
            continue
        r4 = by_key.get((arch, shape, mesh.replace("_L2", "_L4")))
        if not r4:
            continue
        l_true = _true_depth(arch, shape)
        if not l_true:
            continue

        def ext(a, b):
            return a + (b - a) / 2.0 * (l_true - 2)

        f = ext(r2["cost"].get("flops", 0), r4["cost"].get("flops", 0))
        by = ext(r2["cost"].get("bytes accessed", 0), r4["cost"].get("bytes accessed", 0))
        cl = ext(r2["collectives"]["total_bytes"], r4["collectives"]["total_bytes"])
        out[(arch, shape)] = {"flops": f, "bytes": by, "coll": max(cl, 0.0),
                              "method": f"unroll L2/L4 -> L{l_true}"}
    return out


def analyze(rec: dict, corrected: dict | None = None) -> dict | None:
    if not rec.get("ok"):
        return None
    n_chips = 512 if rec["mesh"].startswith("2x16x16") else 256
    flops = rec["cost"].get("flops", 0.0)
    byts = rec["cost"].get("bytes accessed", 0.0)
    coll = rec["collectives"]["total_bytes"]
    method = "direct"
    if corrected and rec["mesh"] == "16x16":
        c = corrected.get((rec["arch"], rec["shape"]))
        if c:
            flops, byts, coll = c["flops"], c["bytes"], c["coll"]
            method = c["method"]
    t_c = flops / PEAK_FLOPS
    t_m = byts / HBM_BW
    t_x = coll / LINK_BW
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x), key=lambda kv: kv[1])[0]
    mf = model_flops_per_device(rec["arch"], rec["shape"], n_chips)
    ratio = (mf / flops) if (mf and flops) else None
    mem = rec.get("memory", {})
    dev_bytes = mem.get("argument_size_in_bytes", 0) + mem.get("temp_size_in_bytes", 0)
    # roofline fraction: useful compute time over the step's bound
    bound = max(t_c, t_m, t_x)
    frac = (mf / PEAK_FLOPS) / bound if (mf and bound > 0) else None
    return {
        **{k: rec[k] for k in ("arch", "shape", "mesh")},
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
        "dominant": dom, "model_flops_ratio": ratio, "roofline_frac": frac,
        "device_bytes": dev_bytes, "fits_16g": dev_bytes <= 16e9,
        "method": method,
        "collective_detail": {k: v["operand_bytes"] for k, v in rec["collectives"].items()
                              if isinstance(v, dict)},
    }


def what_moves_it(row: dict) -> str:
    d = row["dominant"]
    if d == "compute":
        r = row.get("model_flops_ratio") or 0
        if r < 0.4:
            return "compute-dominated with low useful ratio: cut remat/recompute or fuse"
        return "compute-bound: increase arithmetic intensity per chip (larger per-device tiles)"
    if d == "memory":
        return "HBM-bound: fuse ops / lower precision / shrink materialized intermediates"
    return "collective-bound: shrink payloads (bit-packing), overlap, or reshard to cut traffic"


def fmt_s(x):
    if x is None:
        return "-"
    if x == 0:
        return "0"
    return f"{x:.3e}"


def markdown_table(rows: list) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | dominant "
           "| useful/HLO flops | roofline frac | bytes/dev | fits 16G |\n"
           "|---|---|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {fmt_s(r['t_compute_s'])} "
            f"| {fmt_s(r['t_memory_s'])} | {fmt_s(r['t_collective_s'])} | {r['dominant']} "
            f"| {fmt_s(r.get('model_flops_ratio'))} | {fmt_s(r.get('roofline_frac'))} "
            f"| {r['device_bytes']/1e9:.2f}G | {'yes' if r['fits_16g'] else 'NO'} |")
    return hdr + "\n".join(lines) + "\n"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="runs/dryrun")
    ap.add_argument("--mesh", default=None, choices=[None, "16x16", "2x16x16"])
    args = ap.parse_args()
    records = load_records(args.dir)
    corrected = _scan_corrected(records)
    rows = []
    failed = []
    for rec in records:
        if "_L" in rec.get("mesh", ""):
            continue  # unroll probes feed the correction, not the table
        if args.mesh and rec.get("mesh") != args.mesh:
            continue
        row = analyze(rec, corrected)
        if row is None:
            failed.append((rec["arch"], rec["shape"], rec["mesh"], rec.get("error")))
        else:
            rows.append(row)
    print(markdown_table(rows))
    for r in rows:
        print(f"# {r['arch']}/{r['shape']}/{r['mesh']}: {what_moves_it(r)}")
    if failed:
        print("\n# FAILED CELLS:")
        for f in failed:
            print("#  ", f)


if __name__ == "__main__":
    main()

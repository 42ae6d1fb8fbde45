"""Render the roofline table from results/dryrun.json (§Roofline source).

CLI (used by the CI bench-gate to publish the roofline artifact):

  PYTHONPATH=src python -m repro.launch.dryrun --arch whisper_base \
      --shape train_4k --mesh single
  PYTHONPATH=src python benchmarks/roofline_report.py --out ROOFLINE.json

emits the same ``{"name", "value", "unit"}`` row list as the other
benches (value = roofline fraction, -1 for skipped/failed cells), plus
the EXPERIMENTS.md markdown table with ``--markdown``.

LUT-serving cells (``params_mode == "lut"`` dryrun records) additionally
emit a per-row gather-vs-accumulate decomposition: the LUT decode step is
two phases — table-row *gather* (pure HBM traffic: ``planes x chunks``
rows of ``p`` table elements per token) and shift-add *accumulate* (pure
compute: one add per gathered element) — and the analytic split of the
cell's roofline into those phases says which side a tiling or a narrow
table format can still buy time on.
"""
from __future__ import annotations

import json
import os

from repro.launch.hlo_analysis import V5E

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun.json")


def lut_decomposition(arch: str, tokens: int, device_kind: str = V5E) -> dict:
    """Analytic gather-vs-accumulate split of ``tokens`` decode tokens
    through ``arch``'s LUT-converted projections (uniform chunk-1 plan, the
    dryrun's conversion).  Gather is HBM-bound (bytes of table rows
    touched), accumulate is compute-bound (one shift-add per gathered
    element); both in seconds at ``device_kind``'s published peaks
    (``hlo_analysis.PEAKS``)."""
    from repro.configs.base import get_config
    from repro.core.lut import plane_scales
    from repro.core.planner import plan_model
    from repro.launch.hlo_analysis import peaks
    from repro.models.model import model_specs
    from repro.models.params import abstract_params

    cfg = get_config(arch)
    params = abstract_params(model_specs(cfg))
    mplan = plan_model(params, float("inf"), max_chunk=1)
    gather_bytes = accum_ops = 0.0
    for key, plan in mplan.layers.items():
        copies = mplan.copies.get(key, 1)
        rows = len(plane_scales(plan)) * plan.num_chunks  # gathers per token
        elems = rows * plan.out_features
        gather_bytes += copies * elems * max(1, plan.storage_bits // 8)
        accum_ops += copies * elems
    return {
        "gather_bytes": tokens * gather_bytes,
        "accumulate_ops": tokens * accum_ops,
        "gather_s": tokens * gather_bytes / peaks(device_kind)["hbm_bw"],
        "accumulate_s": tokens * accum_ops / peaks(device_kind)["bf16_flops"],
        "device_kind": device_kind,
    }


def _cell_tokens(shape: str) -> int:
    """Decoded/prefilled tokens a dryrun cell pushes through the model."""
    from repro.launch.inputs import shape_case

    case = shape_case(shape)
    if case.kind == "decode":
        return case.global_batch
    return case.global_batch * case.seq_len


def load(path: str = RESULTS) -> list[dict]:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return json.load(f)


def rows(path: str = RESULTS) -> list[tuple[str, float, str]]:
    out = []
    for r in sorted(load(path), key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        key = f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}"
        if r.get("status") == "ok":
            t = r["terms"]
            out.append(
                (
                    key,
                    round(t["roofline_fraction"], 4),
                    f"dom={t['dominant']} c={t['compute_s']:.4f} "
                    f"m={t['memory_s']:.4f} x={t['collective_s']:.4f} "
                    f"useful={r['useful_flops_ratio']:.2f}",
                )
            )
            if r.get("params_mode") == "lut" and r.get("kind") != "train":
                d = lut_decomposition(r["arch"], _cell_tokens(r["shape"]))
                out.append(
                    (
                        f"{key}/gather_s",
                        round(d["gather_s"], 6),
                        f"{d['gather_bytes']:.3e} B of table rows "
                        f"(cell memory_s={t['memory_s']:.4f})",
                    )
                )
                out.append(
                    (
                        f"{key}/accumulate_s",
                        round(d["accumulate_s"], 6),
                        f"{d['accumulate_ops']:.3e} shift-adds "
                        f"(cell compute_s={t['compute_s']:.4f})",
                    )
                )
        else:
            why = r.get("reason", r.get("error", ""))[:60]
            out.append((key, -1.0, f"{r.get('status')}: {why}"))
    return out


def markdown_table(records: list[dict]) -> str:
    """EXPERIMENTS.md §Roofline table."""
    lines = [
        "| arch | shape | mesh | compute_s | memory_s | collective_s | dominant "
        "| roofline frac | useful FLOPs | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in sorted(records, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        if r.get("status") == "ok":
            t = r["terms"]
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} "
                f"| {t['compute_s']:.4f} | {t['memory_s']:.4f} "
                f"| {t['collective_s']:.4f} | {t['dominant']} "
                f"| {t['roofline_fraction']:.3f} | {r['useful_flops_ratio']:.2f} | |"
            )
        else:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | – | – | – | – | – | – "
                f"| {r.get('status')}: {r.get('reason', r.get('error', ''))[:50]} |"
            )
    return "\n".join(lines)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--results", default=RESULTS,
                    help="dryrun records to render (results/dryrun.json)")
    ap.add_argument("--out", default=None, help="write JSON rows to this path")
    ap.add_argument("--markdown", action="store_true",
                    help="print the EXPERIMENTS.md table instead of JSON rows")
    args = ap.parse_args()
    if args.markdown:
        print(markdown_table(load(args.results)))
        return
    payload = [
        {"name": name, "value": value, "unit": unit}
        for name, value, unit in rows(args.results)
    ]
    text = json.dumps(payload, indent=1)
    print(text)
    if args.out:
        parent = os.path.dirname(args.out)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

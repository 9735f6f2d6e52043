"""Emit the §Roofline table from dry-run results (results/dryrun.jsonl).

Not a timing benchmark: it renders the per-(arch x shape x mesh) roofline
terms the dry-run recorded, so EXPERIMENTS.md and CI can diff them."""

from __future__ import annotations

if __package__ in (None, ""):  # direct run: repair sys.path (see _bootstrap)
    import _bootstrap  # noqa: F401

import json
import os

from benchmarks.common import emit

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun.jsonl")


def load_rows(path: str = RESULTS):
    rows = {}
    if not os.path.exists(path):
        return rows
    for line in open(path):
        r = json.loads(line)
        if r.get("ok"):
            rows[(r["arch"], r["shape"], r["mesh"])] = r  # last write wins
    return rows


def run(quick: bool = True):
    rows = load_rows()
    if not rows:
        emit("roofline/missing", 0.0, "run python -m repro.launch.dryrun --all first")
        return
    for (arch, shape, mesh), r in sorted(rows.items()):
        rf = r["roofline"]
        emit(
            f"roofline/{arch}/{shape}/{mesh}", 0.0,
            f"bottleneck={rf['bottleneck']};rf={rf['roofline_fraction']:.4f};"
            f"t_comp={rf['t_compute_s']:.2e};t_mem={rf['t_memory_s']:.2e};"
            f"t_coll={rf['t_collective_s']:.2e};peak_gb={r['memory']['peak_gb']:.2f}",
        )


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    run(quick=False)

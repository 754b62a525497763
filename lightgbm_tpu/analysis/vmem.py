"""Pass 5 — VMEM budget: evaluate the repo's own tile planners on a
CPU-only runner, against the budgets the kernels assume on device.

This is the pass that makes the PR-11 band fix regression-proof without
a TPU: instead of pattern-matching kernel source, it IMPORTS
``ops/pallas_wave._tile_plan`` / ``tile_plan_vmem_report`` and
``ops/pallas_hist.tile_shape`` and sweeps them over the benchmark
shapes and the widths of the ladder (ops/plan.py resolve_wave_width).
Three invariants:

* ``vmem-budget``         — a wave cell whose hist block passes the
  64 MB resident gate (``plan.WAVE_VMEM_GATE``) must plan a TOTAL
  live set (resident + transients) that fits physical VMEM.  In the
  chunked-RMW regime the planner deliberately runs resident blocks up
  to the gate with ~60 MB of transients on top — legal on v5e's 128 MB
  arena, and this rule is what keeps a future budget bump honest.
* ``vmem-serialized-rmw`` — the accumulator-aware live-set rule from
  PR-11: when the resident block leaves less than the chunked-RMW
  window, the planner must clamp the chunk (``pathological`` False in
  ``tile_plan_vmem_report``).  A True here is the 18-30 MB band
  pathology resurrected.
* ``vmem-hist-tile``      — the standalone Pallas histogram kernel's
  (one-hot tile + resident accumulator) must respect its own ~6 MB
  budget at every bin width the binner can produce.

Findings anchor at the planner's ``def`` line in the owning module, so
an inline suppression there covers a deliberately-over-budget regime.

Grid: ncols from the bucketization tests/benches (epsilon 2000, bosch
968, higgs 28, airline 8, synthetic 40/136/700), bin_pad from
ops/wave._bin_pad's two products (64, 128) plus 256 for deep-bin runs,
the widths of the ladder and their neighbours.  ~200 cells, < 1 s on
CPU.
"""
from __future__ import annotations

import ast
from typing import List

from .core import Finding, SourceModule

PASS_NAME = "vmem"

RULES = {
    "vmem-budget":
        "wave tile plan's total live set exceeds physical VMEM for a "
        "cell the VMEM gate admits",
    "vmem-serialized-rmw":
        "tile planner re-creates the serialized chunked-RMW pathology "
        "(PR-11 accumulator-aware clamp regressed)",
    "vmem-hist-tile":
        "pallas_hist tile_shape oversubscribes its VMEM budget at some "
        "bin width",
}

N_ROWS = 1 << 20
NCOLS_GRID = (8, 28, 40, 136, 700, 968, 2000)
BIN_PAD_GRID = (64, 128, 256)
WIDTH_GRID = (1, 8, 16, 32, 64)
NUM_BINS_GRID = (16, 63, 64, 255, 256, 1024, 4096)

# v5e VMEM arena per core (the ceiling every budget constant in
# ops/pallas_wave.py is derived from)
TOTAL_VMEM_BYTES = 128 << 20



def _def_line(modules: List[SourceModule], path_suffix: str,
              func_name: str) -> int:
    for mod in modules:
        if not mod.path.endswith(path_suffix):
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.FunctionDef) \
                    and node.name == func_name:
                return node.lineno
    return 0


def _check_wave(modules: List[SourceModule],
                findings: List[Finding]) -> None:
    from ..ops.plan import WAVE_VMEM_GATE
    from ..ops.pallas_wave import tile_plan_vmem_report
    from ..ops.wave import hist_block_bytes

    path = "lightgbm_tpu/ops/pallas_wave.py"
    line = _def_line(modules, "ops/pallas_wave.py", "_tile_plan")
    for fc in NCOLS_GRID:
        for bp in BIN_PAD_GRID:
            for w in WIDTH_GRID:
                if hist_block_bytes(fc, bp, w) > WAVE_VMEM_GATE:
                    continue        # the VMEM gate keeps this cell out
                rep = tile_plan_vmem_report(N_ROWS, fc, bp, w)
                live = rep["live_new"]     # resident + transients
                if live > TOTAL_VMEM_BYTES:
                    findings.append(Finding(
                        "vmem-budget", PASS_NAME, path, line,
                        "live set %.1f MB > %.0f MB physical VMEM at "
                        "ncols=%d bin_pad=%d W=%d"
                        % (live / 2**20, TOTAL_VMEM_BYTES / 2**20,
                           fc, bp, w),
                        "shrink the chunk/bsub plan for this regime "
                        "in _tile_plan"))
                if rep["pathological_new"]:
                    findings.append(Finding(
                        "vmem-serialized-rmw", PASS_NAME, path, line,
                        "serialized chunked-RMW plan at ncols=%d "
                        "bin_pad=%d W=%d (resident %.1f MB)"
                        % (fc, bp, w,
                           rep["resident_bytes"] / 2**20),
                        "restore the accumulator-aware chunk clamp "
                        "(PR-11, docs/FusedIteration.md)"))


def _check_hist(modules: List[SourceModule],
                findings: List[Finding]) -> None:
    from ..ops.pallas_hist import TILE_BUDGET, supports_bins, tile_shape

    path = "lightgbm_tpu/ops/pallas_hist.py"
    line = _def_line(modules, "ops/pallas_hist.py", "tile_shape")
    for num_bins in NUM_BINS_GRID:
        if not supports_bins(num_bins):
            # the kernel refuses this width at runtime
            # (leaf_histogram_pallas falls back to onehot) — the budget
            # invariant only binds widths the kernel claims
            continue
        f_blk, row_chunk = tile_shape(num_bins)
        resident = f_blk * num_bins * 3 * 4
        onehot = f_blk * num_bins * row_chunk * 4
        if resident + onehot > TILE_BUDGET:
            findings.append(Finding(
                "vmem-hist-tile", PASS_NAME, path, line,
                "tile (F_BLK=%d, C=%d) at B=%d holds %.1f MB "
                "(one-hot %.1f + resident %.1f) > %.0f MB budget"
                % (f_blk, row_chunk, num_bins,
                   (resident + onehot) / 2**20, onehot / 2**20,
                   resident / 2**20, TILE_BUDGET / 2**20),
                "let the row-chunk floor drop further (lanes stay "
                "%%128) or block the bin axis"))


def run(modules: List[SourceModule], repo_root: str) -> List[Finding]:
    findings: List[Finding] = []
    _check_wave(modules, findings)
    _check_hist(modules, findings)
    return list(dict.fromkeys(findings))

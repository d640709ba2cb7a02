"""Dry-run deliverable integrity: the 80-cell grid is complete and coherent.

Validates the committed experiment artifacts (experiments/dryrun/) rather
than recompiling — the grid itself is produced by `python -m
repro.launch.grid --mesh both` (minutes of compile time; see EXPERIMENTS.md).
Skips cleanly if the artifacts have not been generated in this checkout.
"""
import glob
import json
import os

import pytest

from repro.configs import SHAPES, get_config, list_archs

DRYRUN_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "experiments", "dryrun")

pytestmark = pytest.mark.skipif(
    not glob.glob(os.path.join(DRYRUN_DIR, "*.json")),
    reason="dry-run grid artifacts not generated")


def _load_all():
    out = {}
    for f in glob.glob(os.path.join(DRYRUN_DIR, "*.json")):
        d = json.load(open(f))
        out[(d["arch"], d["shape"], d["mesh"])] = d
    return out


def test_grid_complete_and_green():
    cells = _load_all()
    archs = list_archs()
    assert len(archs) == 10
    for arch in archs:
        for shape in SHAPES:
            for mesh in ("single", "multi"):
                assert (arch, shape, mesh) in cells, (arch, shape, mesh)
                st = cells[(arch, shape, mesh)]["status"]
                assert st in ("ok", "skipped"), (arch, shape, mesh, st)


def test_skips_match_policy():
    cells = _load_all()
    for (arch, shape, mesh), d in cells.items():
        cfg = get_config(arch)
        if shape == "long_500k" and not cfg.subquadratic:
            assert d["status"] == "skipped", (arch, shape)
        else:
            assert d["status"] == "ok", (arch, shape, mesh)


def test_roofline_fields_present():
    cells = _load_all()
    for key, d in cells.items():
        if d["status"] != "ok":
            continue
        r = d["roofline"]
        for field in ("compute_s", "memory_s", "collective_s", "dominant",
                      "hlo_flops_per_device", "collective_bytes_per_device"):
            assert field in r, (key, field)
        assert r["dominant"] in ("compute", "memory", "collective")
        assert r["hlo_flops_per_device"] > 0, key
        assert d["chips"] == (512 if key[2] == "multi" else 256)


def test_train_cells_fit_reasonably():
    """Dense training cells fit v5e HBM (16 GiB/dev, small margin for the
    32B flagship).  MoE cells exceed it (their dispatch is the GSPMD
    scatter, with no explicit all-to-all), so they get the wider bound
    here."""
    cells = _load_all()
    for (arch, shape, mesh), d in cells.items():
        if d["status"] != "ok" or shape != "train_4k":
            continue
        cfg = get_config(arch)
        mem = d.get("memory_analysis", {})
        peak = mem.get("argument_size_in_bytes", 0) \
            + mem.get("temp_size_in_bytes", 0)
        bound = 32 if cfg.n_experts else 18
        assert peak < bound * 2 ** 30, (arch, mesh, peak / 2 ** 30)
    # the optimized MoE artifact, when present, must actually fit
    opt = os.path.join(os.path.dirname(DRYRUN_DIR), "perf",
                       "moonshot-v1-16b-a3b__train_4k__single__a2a.json")
    if os.path.exists(opt):
        d = json.load(open(opt))
        mem = d["memory_analysis"]
        peak = mem.get("argument_size_in_bytes", 0) \
            + mem.get("temp_size_in_bytes", 0)
        assert peak < 8 * 2 ** 30

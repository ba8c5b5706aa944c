"""Launch-layer tests: mesh construction, cell registry, analytic model
consistency, dry-run artifact schema."""

import glob
import json
import os

import pytest

from repro.configs import ARCH_IDS, SHAPES, all_cells, cell_status, \
    get_config
from repro.launch.analytic import analyse_cell, cache_bytes
from repro.launch.mesh import TPU_V5E, make_host_mesh

ART = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")


def test_cell_matrix_is_complete():
    cells = all_cells()
    assert len(cells) == 44
    skips = [c for c in cells if c[2] == "skipped_full_attention"]
    assert len(skips) == 8
    # the skips are exactly long_500k on the pure full-attention archs
    assert all(s[1] == "long_500k" for s in skips)
    runs = {(a, s) for a, s, st in cells if st == "run"}
    assert ("rwkv6-1.6b", "long_500k") in runs
    assert ("zamba2-1.2b", "long_500k") in runs
    assert ("h2o-danube-1.8b", "long_500k") in runs


def test_host_mesh():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["model"] == 1


def test_hw_constants():
    assert TPU_V5E["peak_bf16_flops"] == 197e12
    assert TPU_V5E["hbm_bytes_per_s"] == 819e9
    assert TPU_V5E["ici_bytes_per_s"] == 5.0e10


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_cache_bytes_bounded_for_long_context(arch):
    """Sub-quadratic archs must have (near-)constant cache vs seq len."""
    cfg = get_config(arch)
    c32 = cache_bytes(cfg, 1, 32768)
    c500 = cache_bytes(cfg, 1, 524288)
    if cfg.sub_quadratic:
        assert c500 <= c32 * 1.01, (arch, c32, c500)
    else:
        assert c500 > c32 * 4


def test_dryrun_artifacts_schema():
    """If the sweep has run, every compiled artifact has the fields the
    roofline reads."""
    files = glob.glob(os.path.join(ART, "single_pod_16x16", "*.json"))
    files = [f for f in files if "__hc" not in f]
    if not files:
        pytest.skip("dry-run artifacts not generated")
    assert len(files) == len(all_cells())
    for f in files:
        with open(f) as fh:
            rec = json.load(fh)
        assert rec["status"] in ("run", "skipped_full_attention"), f
        if rec["status"] == "run":
            for key in ("cost", "collectives_weighted", "roofline",
                        "params", "devices"):
                assert key in rec, (f, key)
            assert rec["devices"] == 256

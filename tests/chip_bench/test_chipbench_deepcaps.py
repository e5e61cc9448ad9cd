"""The DeepCaps model module (`models/deepcaps.py`): a tiny geometry runs
through the harness on the CPU and agrees with its plain reference on
every answer, and the published geometry's work counts are pinned to the
ones counted from DeepCaps' equations."""
import json
import pathlib

import numpy as np

from benchmarks.chip import bench, harness

from test_chipbench_correct import CLOSED

ROOT = pathlib.Path(__file__).resolve().parents[2]
PUBLISHED = json.loads((ROOT / "benchmarks/chip/configs/deepcaps_cifar10.json")
                       .read_text())
TINY = {**PUBLISHED, "name": "deepcaps_tiny",
        "geometry": {**PUBLISHED["geometry"], "input_shape": [32, 32, 3],
                     "stem_filters": 8,
                     "cells": [[4, 2], [4, 4], [4, 4], [4, 4]],
                     "caps_dim": 6},
        "calib_n": 16}


def test_a_tiny_deepcaps_runs_through_the_harness():
    """build_cell -> drive -> check, Pallas in interpret mode: every
    answer equals the reference's, the class capsules are mostly
    non-zero and the predictions spread, and the reference one
    precision lower does not pass."""
    cell = harness.build_cell(TINY, CLOSED, 2**31 + 33)
    assert cell.model_id == "deepcaps_tiny@pallas"
    win = harness.drive(cell, 0.3)
    served = ~np.isnan(win.done_s)
    assert win.v_q.shape[1:] == (10, 6)
    assert np.count_nonzero(win.v_q[served]) > win.v_q[served].size // 2
    assert len(np.unique(win.pred[served])) > 1
    chk = harness.check(cell, win)
    assert chk["checked"] > 0 and chk["failed"] == 0, chk
    assert all(chk["numbers"][k] <= harness.LIMITS[k]
               for k in harness.LIMITS)
    v4, _ = cell.model.reference(TINY["geometry"], cell.params, cell.calib,
                                 cell.pool[win.pool_idx[served]], 4)
    assert np.sum(v4 != win.v_q[served]) > 0


def test_published_work_counts():
    """1,346,666,496 MACs an image, 13,362,176 weights and biases (the
    local routing's bias included), I = 2,560 class-layer capsules."""
    model = bench.model(PUBLISHED)
    g = PUBLISHED["geometry"]
    layers = model.layers(g)
    assert sum(l["macs"] for l in layers) == 1_346_666_496
    assert sum(l["weight_bytes"] for l in layers) == 13_362_176
    assert model.input_caps(g) == 2560
    assert model.out_shape(g) == (10, 32)
    by_name = {l["name"]: l for l in layers}
    assert by_name["cell4.skip3d.routing"] == {
        "name": "cell4.skip3d.routing", "kind": "routing",
        "macs": 16 * 5 * 32 * 32 * 8, "act_bytes": 16 * (32 * 32 * 8 + 256),
        "weight_bytes": 256}
    assert by_name["cell4.skip3d.votes"]["kind"] == "conv"
    assert by_name["caps.routing"]["macs"] == 5 * 10 * 2560 * 32
    assert by_name["caps.uhat"]["kind"] == "uhat"
    assert sum(l["kind"] == "conv" for l in layers) == 17

"""The four per-layer metrics fed by ``train_epoch``'s own step rows
(``place.ready_ms``, ``place.mib``, ``loop.device_wait_ms``,
``loop.self_ms``): the committed entries and data files, and a tiny traced
run on the CPU seam (``require_tpu=False``) that reports them — all four
where the cell has a ``place_batch``, and no arrival where it has none."""

from __future__ import annotations

import time

import pytest

from benchmark import harness
from test_benchmark_manifest import (  # noqa: F401 — fixtures
    TINY_DATA,
    _assert_correct_but_for_the_trend,
    _load,
    manifest,
    tiny_root,
)

CELLS = ["vgg11_part3_w1", "sc2_3b_dp_s4096", "sc2_3b_dp_w4"]
ROW_FIELDS = {
    "place.ready_ms": ("batch_ready_s", 1000.0, "placement"),
    "place.mib": ("h2d_bytes", 2.0 ** -20, "placement"),
    "loop.device_wait_ms": ("block_s", 1000.0, "step loop"),
    "loop.self_ms": ("loop_self_s", 1000.0, "step loop"),
}


@pytest.mark.parametrize("name", list(ROW_FIELDS))
def test_metric_is_a_data_file_over_the_row_field(manifest, name):
    field, scale, layer = ROW_FIELDS[name]
    assert _load(f"benchmark/metrics/{name}.json") == {
        "reader": "step_row_median", "args": {"field": field, "scale": scale}}
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry["workloads"] == CELLS and entry["moves"] == "mfu_pct"
    assert entry["layer"] == layer
    # appended: the accepted metrics keep their places
    assert manifest["per_layer"].index(entry) >= 8


def test_reader_leaves_out_a_field_no_row_has():
    from benchmark.readers import step_row_median

    rows = [{"batch": 3, "block_s": 0.25, "h2d_bytes": 2 ** 20},
            {"batch": 4, "block_s": 0.75, "h2d_bytes": 2 ** 20,
             "loop_self_s": 0.001}]
    context = {"step_rows": rows}
    assert step_row_median.read(context, "batch_ready_s", 1000.0) is None
    assert step_row_median.read(context, "h2d_bytes", 2.0 ** -20) == 1.0
    assert step_row_median.read(context, "block_s", 1000.0) == 500.0
    assert step_row_median.read(context, "loop_self_s", 1000.0) == 1.0


def _traced(tiny_root, cell, capsys):
    # Three seconds: starting and stopping the profiler takes one on a
    # loaded host, and rows of the traced stretch are not read.
    out = harness.run_cell(tiny_root, cell, seed=2**31 + 11, seconds=3.0,
                           trace=True, t0=time.perf_counter(),
                           require_tpu=False)
    _assert_correct_but_for_the_trend(out, capsys.readouterr().out)
    return {name: m["value"] for name, m in out["metrics"].items()}


@pytest.mark.parametrize("cell, mib", [
    # 8 devices x 4 images of 32x32x3 uint8 + int32 labels
    ("t_part3", (32 * 32 * 32 * 3 + 32 * 4) / 2**20),
    # 8 devices x 1 sequence of 128 int32 tokens, and as many targets
    ("t_lm", 2 * 8 * 128 * 4 / 2**20),
])
def test_traced_run_with_a_placement_reports_all_four(tiny_root, cell, mib,
                                                      capsys):
    values = _traced(tiny_root, cell, capsys)
    assert set(ROW_FIELDS) <= set(values)
    assert values["place.mib"] == mib  # a count: exact
    # placement call -> resident cannot end before the call returns
    assert values["place.ready_ms"] >= values["place.ms"] > 0
    assert values["loop.device_wait_ms"] > 0 and values["loop.self_ms"] > 0
    # the older row-fed metrics are still read beside them
    assert {"data.wait_ms", "place.ms", "loop.dispatch_ms"} <= set(values)


def test_traced_run_without_a_placement_has_no_arrival(tiny_root, capsys):
    values = _traced(tiny_root, "t_part1", capsys)
    assert "place.ready_ms" not in values
    # jit moves the same bytes: 16 images and their labels
    labels = values["place.mib"] * 2**20 - 16 * TINY_DATA["height"] \
        * TINY_DATA["width"] * TINY_DATA["channels"]
    assert labels in (16 * 4, 16 * 8)
    assert values["loop.device_wait_ms"] > 0 and values["loop.self_ms"] > 0

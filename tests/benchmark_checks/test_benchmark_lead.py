"""``place.lead_ms``, the per-layer metric that says ``train_epoch`` holds one
placed batch ahead of the step (row field ``batch_lead_s``): the committed
entry and data file, what the reader makes of rows with and without the
field, and a tiny traced run on the CPU seam (``require_tpu=False``) that
reports it where the cell places its batches and leaves it out where it has
no placement call."""

from __future__ import annotations

import math

import pytest

from test_benchmark_loop_spans import CELLS, _traced
from test_benchmark_manifest import (  # noqa: F401 — fixtures
    _load,
    manifest,
    tiny_root,
)

NAME = "place.lead_ms"


def test_metric_is_a_data_file_over_the_row_field(manifest):
    assert _load(f"benchmark/metrics/{NAME}.json") == {
        "reader": "step_row_median",
        "args": {"field": "batch_lead_s", "scale": 1000.0}}
    entry = manifest["per_layer"][-1]  # appended: nothing before it moved
    assert entry == {
        "name": NAME, "unit": "ms", "better": "higher",
        "source": "program_span", "layer": "placement", "moves": "mfu_pct",
        "workloads": CELLS}
    ready = next(m for m in manifest["per_layer"]
                 if m["name"] == "place.ready_ms")
    assert (entry["layer"], entry["workloads"]) \
        == (ready["layer"], ready["workloads"])


def test_reader_takes_the_median_lead_and_leaves_out_what_no_row_has():
    from benchmark.readers import step_row_median

    # an epoch of three steps: hidden, exposed (negative), and the last
    # iteration, which has no batch ahead and no field
    rows = [{"batch": 3, "batch_lead_s": 0.040},
            {"batch": 4, "batch_lead_s": -0.020},
            {"batch": 5},
            {"batch": 6, "batch_lead_s": 0.044}]
    assert step_row_median.read({"step_rows": rows}, "batch_lead_s",
                                1000.0) == 40.0
    # the parent commit's rows: nothing to read, no metric, no error
    assert step_row_median.read(
        {"step_rows": [{"batch": 3, "batch_ready_s": 0.02}]},
        "batch_lead_s", 1000.0) is None


@pytest.mark.parametrize("cell, placed", [("t_part3", True),
                                          ("t_part1", False)])
def test_traced_run_reports_the_lead_where_batches_are_placed(
        tiny_root, cell, placed, capsys):
    values = _traced(tiny_root, cell, capsys)
    assert (NAME in values) is placed
    if placed:
        # its sign is the host's and the CPU's business here; on the chip
        # it says whether the input beat the step
        assert math.isfinite(values[NAME]) and values[NAME] != 0
        assert values["place.ready_ms"] >= values["place.ms"] > 0

"""The device-trace reduction on hand-made event lists with known answers."""

from __future__ import annotations

import pytest

from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Record

MS = 1e6  # nanoseconds
FLASH = ('%attn.12 = (bf16[48,4096,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[48,1,4096]) '
         'custom-call(bf16[48,4096,128]{2,1,0} %x), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
ADAMW = ('%fusion.7 = (f32[3072,49152]{1,0:T(8,128)}, f32[3072,49152]{1,0}) '
         'fusion(f32[3072,49152]{1,0:T(8,128)} %p), kind=kLoop, calls=%fc.18')


def op(device, name, start_ms, dur_ms, line="XLA Ops"):
    return Record(f"/device:TPU:{device}", line, name, start_ms * MS,
                  dur_ms * MS)


def host(name, start_ms, dur_ms):
    return Record("/host:CPU", "python", name, start_ms * MS, dur_ms * MS)


@pytest.mark.parametrize("intervals, merged, seconds", [
    ([(0, 2), (1, 3)], [(0, 3)], 3),                  # overlapping
    ([(0, 1), (1, 2)], [(0, 2)], 2),                  # touching
    ([(5, 6), (0, 1)], [(0, 1), (5, 6)], 2),          # unsorted, disjoint
    ([(0, 10), (2, 3), (4, 5)], [(0, 10)], 10),       # nested
    ([(1, 1), (2, 2)], [], 0),                        # empty intervals
])
def test_union(intervals, merged, seconds):
    assert tr.union(intervals) == merged
    assert tr.total(tr.union(intervals)) == seconds


@pytest.mark.parametrize("a, b, rest", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 4), (6, 8)], [(3, 7)], [(0, 3), (7, 8)]),
    ([(0, 4)], [], [(0, 4)]),
    ([(0, 4)], [(0, 4)], []),
    ([(2, 3)], [(0, 10)], []),
])
def test_subtract(a, b, rest):
    assert tr.subtract(a, b) == rest


def test_busy_idle_and_step_time_on_one_device():
    # 2 steps in a 10 ms stretch: ops cover [1,4) and [5,9) with an overlap.
    records = [
        host(tr.STRETCH, 0, 10),
        op(0, "fusion.1", 1, 2), op(0, "convolution.2", 2, 2),   # [1,4)
        op(0, "fusion.1", 5, 4),                                 # [5,9)
        op(0, "jit_step", 0, 10, line="XLA Modules"),            # not an op
    ]
    out = tr.reduce(records, steps=2)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.010)
    assert out["busy_s"] == pytest.approx(0.007)
    assert out["idle_pct"] == pytest.approx(30.0)
    assert out["device_ms"] == pytest.approx(3.5)
    assert out["has_collectives"] is False
    assert out["exposed_collective_ms"] == 0
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.006)]


def test_exposed_collective_time_with_async_pairs():
    # all-reduce-start is hidden under a fusion that runs beside it on a
    # second op line; all-reduce-done waits alone for 3 ms; a synchronous
    # collective-permute is half covered.
    records = [
        host(tr.STRETCH, 0, 20),
        op(0, "all-reduce-start.1", 1, 1),
        op(0, "fusion.7", 1, 4, line="XLA Ops 2"),
        op(0, "all-reduce-done.1", 6, 3),
        op(0, "%collective-permute.3 = f32[8]", 10, 2),
        op(0, "fusion.8", 11, 3, line="XLA Ops 2"),
    ]
    out = tr.reduce(records, steps=1, op_lines=("XLA Ops", "XLA Ops 2"))
    assert out["has_collectives"] is True
    # exposed: done [6,9) = 3 ms, permute [10,11) = 1 ms; start is hidden
    assert out["exposed_collective_ms"] == pytest.approx(4.0)
    assert out["busy_s"] == pytest.approx(0.011)  # [1,5) [6,9) [10,14)


PSUM = ('%psum.492 = f32[3072,49152]{1,0:T(8,128)} all-reduce(f32[3072,49152]{1,0} '
        '%fusion.356), channel_id=5, replica_groups={{0,1,2,3}}, to_apply=%add')


@pytest.mark.parametrize("name, collective", [
    (PSUM, True),                                   # named by jax, not by opcode
    ("%all-reduce-done.1 = f32[8] all-reduce-done(f32[8] %s)", True),
    ("collective-permute-start.3", True),
    ("%all-reduce_fusion.2 = f32[8] fusion(f32[8] %x), kind=kLoop", False),
    (ADAMW, False),
    (FLASH, False),
])
def test_collectives_are_told_by_opcode(name, collective):
    assert tr.is_collective(name) is collective


def test_four_devices_mean_busy_slowest_step_and_exposure():
    records = [host(tr.STRETCH, 0, 10)]
    for dev, compute_ms in enumerate((4, 5, 6, 7)):
        records.append(op(dev, "fusion.1", 0, compute_ms))
        # every device then waits in the all-reduce until 8 ms
        records.append(op(dev, "all-reduce.2", compute_ms, 8 - compute_ms))
    out = tr.reduce(records, steps=2)
    assert out["devices"] == 4
    assert out["busy_s"] == pytest.approx(0.008)       # each busy 8 of 10 ms
    assert out["idle_pct"] == pytest.approx(20.0)
    assert out["device_ms"] == pytest.approx(4.0)      # 8 ms / 2 steps
    # the fastest device waits longest: 4 ms over 2 steps
    assert out["exposed_collective_ms"] == pytest.approx(2.0)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    records = [
        host(tr.STRETCH, 0, 10),
        host("bench.data_next", 0, 2),
        host("bench.place_batch", 2, 1),
        host("bench.step_dispatch", 3, 2),
        op(0, "fusion.1", 4, 4),                      # busy [4,8)
    ]
    out = tr.reduce(records, steps=1)
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.data_next"] == pytest.approx(0.002)
    assert gaps["bench.place_batch"] == pytest.approx(0.001)
    assert gaps["bench.step_dispatch"] == pytest.approx(0.001)   # [3,4)
    assert gaps["unannotated"] == pytest.approx(0.002)           # [8,10)
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_mosaic_kernels_are_told_by_their_custom_call_target():
    records = [host(tr.STRETCH, 0, 10), op(0, FLASH, 0, 3), op(0, ADAMW, 3, 1),
               op(0, FLASH, 5, 3)]
    out = tr.reduce(records, steps=2)
    assert out["has_pallas"] is True
    assert out["pallas_ms"] == pytest.approx(3.0)
    names = dict(out["device_ops"])
    assert names["attn.12 pallas (bf16[48,4096,128]{2,1,0:T(8,128)(2,1)S(1)}, "
                 "f32[48,1,4096])"] == pytest.approx(0.006)
    assert tr.label(ADAMW).startswith("fusion.7 fusion (f32[3072,49152]")
    assert tr.label("not an instruction") == "not an instruction"
    plain = tr.reduce([op(0, ADAMW, 0, 1)], steps=1)
    assert plain["has_pallas"] is False and plain["pallas_ms"] == 0


def test_nothing_on_a_device_reduces_to_nothing():
    assert tr.reduce([host("bench.data_next", 0, 1)], steps=3) is None
    assert tr.reduce([op(0, "fusion", 0, 1)], steps=0) is None


def test_inventory_and_xplane_adapter(tmp_path):
    """The adapter reads a real (CPU) profile into records; the device
    planes of a chip trace are covered by the hand-made lists above."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.data_next"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    records = tr.load_xplane(path)
    assert any(r.name == "bench.data_next" for r in tr.host_spans(records))
    inv = tr.inventory(records)
    assert any("python" in lines for lines in inv.values())
    assert tr.reduce(records, steps=1) is None  # no TPU plane on the CPU

"""The trace reduction on a small recorded trace of a real chip: the last two
``accumulate_step`` executions (ALBERT-large, 12 rows, S=512) and the solo
boundary (``_fused_mean_clip`` + ``guarded_apply_step``) of PR 22's probe run
on a TPU v5e, kept in the reduction's own neutral form. Expected numbers were
read off the same run by the probe's own independent aggregation (per-name
totals over the profiler's events) and by hand from the module line."""
import os

import pytest

from benchmark import trace as T

FIXTURE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "fixtures",
    "albert_b12_two_accumulates_and_solo_boundary.json.gz",
)


@pytest.fixture(scope="module")
def trace():
    return T.load_fixture(FIXTURE)


def test_names():
    assert T.module_name("jit_accumulate_step(18408891744454614805)") == "accumulate_step"
    assert T.module_name("jit__fused_mean_clip(66840)") == "_fused_mean_clip"
    assert T.op_name("%flash_fwd.4 = (bf16[96,512,128]{2,1,0:T(8,128)") == "flash_fwd"
    assert T.op_name("%flash_bwd_fused.10 = (bf16[192") == "flash_bwd_fused"
    assert T.op_name("%while.7 = (s32[]") == "while"
    assert T.op_name("%all-reduce-start.3 = f32[8]") == "all-reduce-start"
    assert T.op_name("%copy = f32[2]") == "copy"


def test_union_of_intervals():
    assert T.union_ns([]) == 0.0
    assert T.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25.0
    assert T.union_ns([(5, 6), (0, 10)]) == 10.0  # nested


def test_programs(trace):
    """Module line by hand: two accumulates of 106.707260 and 106.704433 ms,
    a 264.363 us mean+clip, a 1,336.031 us guarded apply."""
    acc = T.module_durations(trace, ["accumulate_step"])["/device:TPU:0"]
    assert acc == pytest.approx([0.106707260, 0.106704433], rel=1e-9)
    boundary = T.module_durations(
        trace, ["_fused_mean_clip", "guarded_apply_step"]
    )["/device:TPU:0"]
    assert boundary == pytest.approx([0.000264363, 0.001336031], rel=1e-9)


def test_busy_and_idle(trace):
    """Ops nest (a ``%while`` spans its body), so busy is a UNION: it can
    never exceed the window, and here the device is busy for all but the
    dispatch gaps — 0.06 % idle."""
    busy, window = T.device_busy(trace)["/device:TPU:0"]
    assert 0 < busy <= window
    assert window == pytest.approx(0.21504328, rel=1e-6)
    assert 100 * (1 - busy / window) == pytest.approx(0.0604, abs=0.001)
    # a plain sum of the nested events would overcount past the window
    plain = sum(d for _n, _s, d in trace["/device:TPU:0"][T.OPS]) / 1e9
    assert plain > window


def test_kernels(trace):
    """24 layers x 2 steps = 48 calls of each attention kernel, 96 of each
    LayerNorm kernel (two per layer). Per-call medians from the probe's own
    per-name totals over four steps: 41.04 ms / 96 and 38.274 ms / 96."""
    fwd = T.op_durations(trace, "flash_fwd")
    bwd = T.op_durations(trace, "flash_bwd_fused")
    assert len(fwd) == len(bwd) == 48
    assert sum(fwd) / 48 == pytest.approx(41.04e-3 / 96, rel=0.005)
    assert sum(bwd) / 48 == pytest.approx(38.274e-3 / 96, rel=0.005)
    assert len(T.op_durations(trace, "ln_residual_fwd")) == 96
    assert len(T.op_durations(trace, "ln_residual_bwd")) == 96
    assert T.op_durations(trace, "flash_bwd_dq") == []


def test_kernel_time_is_the_median_call(trace):
    """The LayerNorm kernels report their time, not a roofline share. By
    hand from the fixture's 96 ``ln_residual_fwd`` events: sorted, the 48th
    and 49th are both 21.039 us (least 19.838, greatest 22.352)."""
    from types import SimpleNamespace

    from benchmark.reducers import kernel_time

    run = SimpleNamespace(trace=trace)
    assert kernel_time.reduce(run, {"kernel": "ln_residual_fwd"}) == pytest.approx(21.039, abs=1e-3)
    assert kernel_time.reduce(run, {"kernel": "ln_residual_bwd"}) == pytest.approx(32.707, abs=1e-3)
    assert kernel_time.reduce(run, {"kernel": "no_such_kernel"}) is None
    assert kernel_time.reduce(SimpleNamespace(trace=None), {"kernel": "flash_fwd"}) is None


def test_top_ops_leave_containers_out(trace):
    top = T.top_ops(trace, 10)
    names = [n for n, _s in top]
    assert "while" not in names and len(top) == 10
    assert {"flash_fwd", "flash_bwd_fused"} <= set(names)
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def test_collective_share_is_zero_on_one_chip(trace):
    assert T.collective_seconds(trace) == {"/device:TPU:0": 0.0}
    synthetic = {"/device:TPU:0": {T.OPS: [
        ("%fusion.1 = f32[8]", 0.0, 100.0),
        ("%all-reduce-start.1 = f32[8]", 100.0, 50.0),
        ("%all-reduce-done.1 = f32[8]", 120.0, 80.0),
    ]}}
    assert T.collective_seconds(synthetic)["/device:TPU:0"] == pytest.approx(100e-9)


def test_idle_gaps_are_classified_by_their_neighbours(trace):
    gaps = dict(T.idle_gaps(trace, "accumulate_step"))
    between = "accumulate_step->accumulate_step (host dispatch/data)"
    boundary = "accumulate_step->_fused_mean_clip (boundary)"
    assert between in gaps and boundary in gaps
    # by hand from the module line: 369,024,112 - (262,304,842 + 106,707,260)
    assert gaps[between] == pytest.approx(12010e-9, rel=1e-6)

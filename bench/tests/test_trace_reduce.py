"""The trace reduction on a small recorded trace: three decode steps of
phi3-mini-3.8b through EngineCore on one TPU v5 lite (the device's
``XLA Modules`` and ``XLA Ops`` lines, trimmed to the kernel calls, the
loop events and the instructions of 20 us or more, and the host's step
spans)."""
import json
import os

import numpy as np
import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_v5e_phi3_3steps.json")


@pytest.fixture(scope="module")
def trace():
    with open(DATA) as f:
        return tr.Trace.from_dict(json.load(f))


def _timeline_busy_s(spans, lo, hi):
    """Busy seconds by marking every microsecond an event covers."""
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for s, e, _ in spans:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) // 1000):int((b - lo) // 1000)] = True
    return grid.sum() * 1e-6


def test_busy_idle_and_kernels(trace):
    r = tr.reduce_trace(trace, ["paged_flash_decode_fwd",
                                "paged_prefill_fwd"])
    lo, hi = tr.window_of(trace)
    dev = trace.devices["/device:TPU:0"]
    assert r.window_s == pytest.approx((hi - lo) / 1e9)
    assert r.busy_mean_s == pytest.approx(
        _timeline_busy_s(dev["modules"], lo, hi), abs=2e-4)
    assert 0.0 < r.idle_share < 0.2
    # 32 layers x 3 decode steps, one kernel call each; no prefill ran
    assert r.kernel_calls == {"paged_flash_decode_fwd": 96}
    assert "paged_prefill_fwd" not in r.kernel_s
    assert 0.0 < r.kernel_s["paged_flash_decode_fwd"] < r.busy_mean_s
    assert r.steps == 3 and r.step_s <= r.window_s + 1e-9
    # loops are not leaf instructions; the kernel leads the breakdown
    assert not any("/while" in name for name, _ in r.top_ops)
    assert r.top_ops[0][0] == "jit_dec/paged_flash_decode_fwd.9"
    # idle gaps fall inside step spans, and add up to the idle time
    assert {lab for lab, _ in r.idle_gaps} <= {"step", "none"}
    assert sum(r.idle_by_host.values()) == pytest.approx(
        r.window_s - r.busy_mean_s, rel=1e-6)
    assert r.collective_exposed_s == {"/device:TPU:0": 0.0}


def test_interval_arithmetic():
    u = tr.union([(0, 5, "a"), (3, 8, "b"), (10, 12, "c"), (20, 30, "d")],
                 1, 25)
    assert u == [(1, 8), (10, 12), (20, 25)]
    assert tr.length(u) == 14
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) \
        == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]


def test_collective_only_time():
    t = tr.Trace(
        {"/device:TPU:0": {
            "modules": [(0, 100, "jit_step(1)")],
            "ops": [(0, 40, "%fusion.1 = bf16[8] fusion()"),
                    (30, 70, "%all-reduce.2 = bf16[8] all-reduce()"),
                    (60, 80, "%fusion.3 = bf16[8] fusion()")]}},
        [(0, 100, "window"), (0, 100, "step")])
    r = tr.reduce_trace(t)
    # the all-reduce runs alone from 40 to 60
    assert r.collective_exposed_s["/device:TPU:0"] == pytest.approx(20e-9)
    assert r.idle_by_host == {}


def test_collective_exposed_pct_two_devices():
    """Device 0's all-reduce runs alone 20 ns of a 100 ns window, device
    1's 40 ns: the reader gives their mean share, 30%, and nothing for a
    run on one chip or without a trace."""
    import types

    from conftest import ROOT
    from manifest import Manifest
    ar = "%all-reduce.2 = bf16[8] all-reduce()"
    t = tr.Trace(
        {"/device:TPU:0": {
            "modules": [(0, 100, "jit_step(1)")],
            "ops": [(0, 40, "%fusion.1 = bf16[8] fusion()"), (30, 70, ar),
                    (60, 80, "%fusion.3 = bf16[8] fusion()")]},
         "/device:TPU:1": {
            "modules": [(0, 100, "jit_step(1)")],
            "ops": [(0, 50, ar), (40, 100, "%fusion.4 = bf16[8] fusion()")]}},
        [(0, 100, "window"), (0, 100, "step")])
    r = tr.reduce_trace(t)
    assert r.collective_exposed_s == pytest.approx(
        {"/device:TPU:0": 20e-9, "/device:TPU:1": 40e-9})
    read = Manifest(ROOT).reader("collective_exposed_pct")
    assert read(types.SimpleNamespace(trace=r, chips=2)) \
        == pytest.approx(30.0)
    assert read(types.SimpleNamespace(trace=r, chips=1)) is None
    assert read(types.SimpleNamespace(trace=None, chips=2)) is None


def test_names():
    op = "%paged_flash_decode_fwd.9 = bf16[8,32,8,96] custom-call(s32[8,16])"
    assert tr.short_name(op) == "paged_flash_decode_fwd.9"
    assert tr.op_kind(op) == "paged_flash_decode_fwd"
    assert tr.short_name("jit_dec(11798637317343654533)") == "jit_dec"

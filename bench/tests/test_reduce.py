"""Trace reduction and byte counts, on hand-built planes with
hand-computed answers and on a small trace recorded on a TPU v5e."""
import glob
import os
from collections import namedtuple

import pytest

from metrics.lib import hlo, roofline, xplane

Ev = namedtuple("Ev", "name start_ns duration_ns")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def _planes():
    # host: the window [100, 1100); a batch_step span [200, 600) holding
    # a fused_step call [210, 260); a gather_rows span [800, 900)
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 100, 1000), Ev("bench.batch_step", 200, 400),
        Ev("bench.fused_step", 210, 50), Ev("bench.gather_rows", 800, 100),
        Ev("PjitFunction(fused_step)", 210, 40)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_fused_step(1)", 300, 200),
                             Ev("jit_gather_rows(2)", 850, 20)]),
        Line("XLA Ops", [Ev("probe", 300, 80), Ev("gather", 380, 40),
                         Ev("fusion", 400, 100),   # overlaps gather
                         Ev("gather", 850, 20),
                         Ev("late", 1090, 50)]),   # clipped at 1100
        Line("Steps", [Ev("step", 0, 5000)])])
    return [host, dev]


def test_union_gaps_and_innermost_by_hand():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert xplane.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]
    assert xplane.clip([(0, 5), (6, 9), (9, 12)], 2, 10) == \
        [(2, 5), (6, 9), (9, 10)]
    spans = [(0, 10, "outer"), (2, 4, "inner")]
    assert xplane.innermost(spans, 3) == "inner"
    assert xplane.innermost(spans, 5) == "outer"
    assert xplane.innermost(spans, 11) == xplane.NO_SPAN


def test_reduce_by_hand():
    s = xplane.reduce(_planes())
    assert s.window == (100, 1100) and s.window_s == pytest.approx(1e-6)
    # busy: [300, 500) + [850, 870) + [1090, 1100) = 230 ns
    assert s.busy_ns == 230
    assert s.busy_s == pytest.approx(230e-9)
    assert s.device_planes == ["/device:TPU:0"]
    assert s.ops["gather"] == [2, pytest.approx(60e-9)]
    assert s.modules["jit_fused_step(1)"] == [1, pytest.approx(200e-9)]
    n, t = s.op_in_module_time(lambda m: "fused_step" in m,
                               lambda o: o == "gather")
    assert (n, t) == (1, pytest.approx(40e-9))
    n, t = s.op_in_module_time(lambda m: "gather_rows" in m,
                               lambda o: o == "gather")
    assert (n, t) == (1, pytest.approx(20e-9))
    # gaps, named at their midpoints: [100, 300) mid 200 -> batch_step;
    # [500, 850) mid 675 and [870, 1090) mid 980 -> no span open
    assert s.gap_count == 3
    assert s.idle[xplane.NO_SPAN] == pytest.approx((350 + 220) * 1e-9)
    assert s.idle["bench.batch_step"] == pytest.approx(200e-9)
    bd = s.breakdown()
    assert bd["device_ops"][0] == ["fusion", pytest.approx(100e-9)]
    assert bd["idle_gaps"][0][0] == xplane.NO_SPAN
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_no_device_plane_reduces_to_window_without_busy_time():
    s = xplane.reduce(_planes()[:1])
    assert s.device_planes == [] and s.busy_ns == 0


PROBE = ('%branch_0_fun.4 = s32[256,1]{1,0:T(8,128)S(1)} custom-call('
         's32[256,1]{1,0:T(8,128)S(1)} %copy.24, s32[1,32768]{1,0:T(1,128)} '
         '%state_keys.1), custom_call_target="tpu_custom_call", '
         'operand_layout_constraints={s32[256,1]{1,0}, s32[1,32768]{1,0}}')
GATHER = ('%branch_0_fun.5 = f32[256,1,2]{2,1,0:T(1,128)S(1)} custom-call('
          's32[256]{0:T(256)S(1)} %get-tuple-element.68, '
          'f32[32769,1,2]{2,1,0:T(1,128)S(1)} %copy.25), '
          'custom_call_target="tpu_custom_call"')
SCATTER = ('%branch_0_fun.3 = f32[32769,1,2]{2,1,0:T(1,128)S(1)} custom-call('
           's32[256]{0:T(256)S(1)} %get-tuple-element.65, '
           'f32[256,1,2]{2,1,0:T(1,128)S(1)} %bitcast.3, '
           'f32[32769,1,2]{2,1,0:T(1,128)S(1)} %copy.26), '
           'custom_call_target="tpu_custom_call"')
COPY = ('%copy.25 = f32[32769,1,2]{2,1,0:T(1,128)S(1)} copy('
        'f32[32769,1,2]{0,2,1:T(2,128)} %pages.1)')


def test_kernels_told_apart_by_signature():
    assert hlo.kernel_of(PROBE) == "tac_probe"
    assert hlo.kernel_of(GATHER) == "page_gather"
    assert hlo.kernel_of(SCATTER) == "page_scatter"
    assert hlo.kernel_of(COPY) is None


def test_byte_counts_by_hand():
    # 256 query keys, a directory row of 2^15 int32 keys, 256 ways out
    assert roofline.probe_bytes(256, 2 ** 15) == 1_024 + 131_072 + 1_024
    assert roofline.op_bytes(PROBE) == 133_120
    # 256 slot ids, 256 rows of (1 + 1) f32 read, the same written
    assert roofline.page_bytes(256, 8) == 1_024 + 2 * 256 * 8
    assert roofline.op_bytes(GATHER) == 5_120
    # the scatter touches 256 rows of the 32,769-row pool, not all of it
    assert roofline.op_bytes(SCATTER) == 5_120
    assert roofline.op_bytes(COPY) is None
    # 819 GB/s moves 819 bytes in a nanosecond
    assert roofline.share_pct(819, 2e-9, 819e9) == pytest.approx(50.0)


def _fixture():
    paths = glob.glob(os.path.join(FIXTURES, "*.xplane.pb"))
    if not paths:
        pytest.fail("no recorded trace in bench/tests/fixtures")
    from jax.profiler import ProfileData
    return ProfileData.from_file(paths[0])


def test_recorded_trace_reduces_as_a_plain_sweep_says():
    """A fused-plane trace recorded on a TPU v5e (a short q5 window):
    the busy time equals a plain sweep over the device ops in the window,
    every ``fused_step`` holds one probe, and the readers' shares stay
    below 100%."""
    s = xplane.reduce(_fixture().planes)
    assert s.device_planes == ["/device:TPU:0"]
    ops = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns))
                 for p in _fixture().planes if p.name == "/device:TPU:0"
                 for ln in p.lines if ln.name == xplane.OPS_LINE
                 for e in ln.events)
    lo, hi = s.window
    busy, reach = 0, lo
    for a, b in ops:
        a, b = max(a, reach), min(b, hi)
        if b > a:
            busy += b - a
            reach = b
    assert s.busy_ns == busy > 0
    assert 0 < s.busy_s < s.window_s
    n_steps, _ = s.module_time(lambda m: "fused_step" in m)
    probes = sum(c for (m, op), (c, _) in s.op_in_module.items()
                 if "fused_step" in m and hlo.kernel_of(op) == "tac_probe")
    assert n_steps > 0 and probes == n_steps
    assert set(s.idle) <= {xplane.NO_SPAN, "bench.batch_step",
                           "bench.fused_step", "bench.fused_admit",
                           "bench.gather_rows", "bench.drop_slots"}
    assert sum(s.idle.values()) == pytest.approx(
        s.window_s - s.busy_ns * 1e-9)
    import importlib
    run = {"trace": s, "peak": {"hbm_bytes_per_s": 819e9}}
    for name in ("tac_probe_roofline", "page_gather_roofline",
                 "fused_step_us", "device_idle_share"):
        v = importlib.import_module(f"metrics.{name}").read(run)
        assert v is not None and 0 < v, name
        if name != "fused_step_us":
            assert v < 100, (name, v)

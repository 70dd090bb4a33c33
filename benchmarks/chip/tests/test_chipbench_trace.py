"""The trace reduction, checked by hand on a made-up trace."""
import pytest

import tiny  # noqa: F401  (puts the benchmark's package on the path)
from chipbench.readers import idle_pct
from chipbench.trace import Ev, WINDOW, module_base, op_base, reduce

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _trace():
    return [
        Ev(HOST, "python3", WINDOW, 1000, 10000),                 # window [1000, 11000]
        Ev(DEV, "XLA Modules", "jit_query_probed(11)", 2000, 1000),
        Ev(DEV, "XLA Modules", "jit__insert(12)", 5000, 2000),
        Ev(DEV, "XLA Modules", "jit_rebuild(13)", 10000, 2000),   # clipped at 11000
        Ev(DEV, "XLA Ops", "early.0", 0, 1500),                   # clipped to [1000, 1500]
        Ev(DEV, "XLA Ops", "fusion.1", 2000, 500),
        Ev(DEV, "XLA Ops", "scan_scores.2", 2500, 500),
        Ev(DEV, "XLA Ops", "copy.3", 5000, 2000),
        Ev(DEV, "XLA Ops", "while.4", 10000, 2000),
        Ev(HOST, "ame-latency-0", "ReadSyncFlag", 0, 20000),      # overlaps every gap
        Ev(HOST, "chipbench-open-loop", "dispatch", 2900, 2200),  # covers gap [3000, 5000]
        Ev(HOST, "chipbench-wait-query", "np.asarray", 7100, 2800),
    ]


def test_busy_idle_and_window():
    s = reduce(_trace())
    assert s.devices == 1
    assert s.window_s == pytest.approx(10000e-9)
    # busy union: [1000,1500] + [2000,3000] + [5000,7000] + [10000,11000]
    assert s.busy_s == pytest.approx(4500e-9)

    class R:
        trace = s
    assert idle_pct(R) == pytest.approx(55.0)


def test_gaps_longest_first_named_by_host_event():
    s = reduce(_trace())
    # gaps: [7000,10000] 3000 ns, [3000,5000] 2000 ns, [1500,2000] 500 ns
    assert [round(g * 1e9) for _, g in s.gaps] == [3000, 2000, 500]
    # [7000,10000]: ReadSyncFlag overlaps 3000 ns, more than np.asarray's 2800
    assert s.gaps[0][0] == "ame-latency-0:ReadSyncFlag"
    # [3000,5000]: dispatch and ReadSyncFlag both cover it; the shorter wins
    assert s.gaps[1][0] == "chipbench-open-loop:dispatch"


def test_module_and_op_time():
    s = reduce(_trace())
    assert s.module("jit_query_probed") == (1, pytest.approx(1000e-9))
    assert s.module("jit__insert") == (1, pytest.approx(2000e-9))
    assert s.module("jit_rebuild") == (1, pytest.approx(1000e-9))
    assert s.ops["jit_query_probed/scan_scores.2"] == pytest.approx(500e-9)
    assert s.ops["?/early.0"] == pytest.approx(500e-9)
    assert s.op_seconds("jit_query_probed", "scan_scores") == pytest.approx(500e-9)
    top = s.breakdown()["device_ops"]
    assert top[0] == ["jit__insert/copy.3", pytest.approx(2000e-9)]
    assert module_base("jit_query_probed(11)") == "jit_query_probed"


def test_busy_is_the_mean_over_devices():
    ev = _trace() + [Ev("/device:TPU:1", "XLA Ops", "all", 0, 20000)]
    s = reduce(ev)
    assert s.devices == 2
    assert s.busy_s == pytest.approx((4500e-9 + 10000e-9) / 2)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        reduce([Ev(DEV, "XLA Ops", "x", 0, 10)])


def test_op_names_from_hlo_text():
    hlo = ("%scan_scores.1 = f32[128,1052672]{1,0:T(8,128)} custom-call("
           "f32[128,1024]{1,0:T(8,128)S(1)} %copy-done.1)")
    assert op_base(hlo) == "scan_scores.1 f32[128,1052672]"
    assert op_base("%while.3 = (s32[]{:T(128)}, f32[8]{0}) while(%t)") == "while.3 (tuple)"
    assert op_base("fusion.1") == "fusion.1"
    ev = _trace() + [Ev(DEV, "XLA Ops", hlo, 2000, 100)]
    assert reduce(ev).op_seconds("jit_query_probed", "scan_scores") == pytest.approx(600e-9)

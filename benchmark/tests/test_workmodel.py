import pytest

from benchmark import workmodel as wm

I, O, F, N = wm.INVOKE, wm.OK, wm.FAIL, wm.INFO
R, W, C = 0, 1, 2


def test_closure_and_completion_counts_by_hand():
    # w=1, V=2: one slot, one mask lacking it, 4 ops per state + 1 merge
    assert wm.closure_ops(1, 2) == 1 * 1 * (4 * 2 + 1)
    # w=3, V=6: 3 slots x 4 masks x (24 + 1)
    assert wm.closure_ops(3, 6) == 3 * 4 * 25
    assert wm.complete_ops(3, 6) == 4 + 8
    assert wm.closure_ops(0, 6) == wm.complete_ops(0, 6) == 0
    # 33 states take two words
    assert wm.closure_ops(2, 33) == 2 * 2 * (4 * 33 + 2)
    assert wm.complete_ops(2, 33) == (2 + 4) * 2


def test_key_work_sequential_history():
    # write 1, then read 1: two closures at width 1, two completions.
    typ = [I, O, I, O]
    proc = [0, 0, 0, 0]
    f = [W, W, R, R]
    a = [1, 1, -1, 1]
    b = [-1, -1, -1, -1]
    w = wm.key_work(typ, proc, f, a, b)
    V = 2                                   # initial value and 1
    assert w["states"] == V and w["events"] == 2 and w["peak"] == 1
    assert w["ops"] == 2 * (wm.closure_ops(1, V) + wm.complete_ops(1, V))


def test_key_work_concurrent_fail_and_info():
    # p0 write 2 | p1 cas(0,1) fails | p2 write 3 :info (pinned) | p0 ok
    # then p1 read 2 ok. Widths at the oks: 2 (write + pinned info),
    # then 2 again (read + pinned info); the failed cas never counts.
    typ = [I, I, I, F, N, O, I, O]
    proc = [0, 1, 2, 1, 2, 0, 1, 1]
    f = [W, C, W, C, W, W, R, R]
    a = [2, 0, 3, 0, 3, 2, -1, 2]
    b = [-1, 1, -1, 1, -1, -1, -1, -1]
    w = wm.key_work(typ, proc, f, a, b)
    V = 3                                   # initial, 2, 3
    assert w["states"] == V and w["events"] == 2 and w["peak"] == 2
    assert w["ops"] == 2 * (wm.closure_ops(2, V) + wm.complete_ops(2, V))


def test_fused_completions_pay_one_closure():
    # two invokes, two oks in a row: the second ok needs no closure.
    typ = [I, I, O, O]
    proc = [0, 1, 0, 1]
    f = [W, W, W, W]
    a = [1, 2, 1, 2]
    b = [-1] * 4
    w = wm.key_work(typ, proc, f, a, b)
    V = 3
    assert w["ops"] == (wm.closure_ops(2, V) + wm.complete_ops(2, V)
                        + wm.complete_ops(1, V))


def test_least_time_names_its_bound():
    peaks = {"vpu_int32_ops_per_s": {"value": 1e12},
             "hbm_bytes_per_s": {"value": 1e11}}
    assert wm.least_time(1e12, 1e9, peaks) == (1.0, "vpu")
    t, bound = wm.least_time(1e9, 1e12, peaks)
    assert bound == "hbm" and t == pytest.approx(10.0)

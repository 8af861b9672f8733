"""The trace reduction: busy union, idle share, gaps and op time by name,
on hand-made events and on a trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

import tracereduce
from conftest import BENCH
from tracereduce import Trace

RECORDED = Path(__file__).resolve().parent / "data" / "splitme-small.xplane.pb"


def _trace():
    # device 0: ops at [0,10) [5,20) [30,40); device 1: [0,40)
    ops = {"/device:TPU:0": [("fusion.1", 0, 10),
                             ("vmap_jit_kl_loss__.2", 5, 15),
                             ("all-reduce.2", 30, 10)],
           "/device:TPU:1": [("fusion.1", 0, 40)]}
    host = [("campaign", 0, 50), ("plan", 18, 15), ("rounds", 0, 50)]
    return Trace(window=(0.0, 50.0), ops=ops, host=host)


def test_union_and_idle():
    t = _trace()
    assert tracereduce.merge(t.ops["/device:TPU:0"]) == [(0, 20), (30, 40)]
    # device 0 busy 30 ns, device 1 busy 40 ns: averaged 35 ns
    assert t.busy_s() == pytest.approx(35e-9)
    assert t.window_s == pytest.approx(50e-9)


def test_op_time_by_name():
    t = _trace()
    sec, n = t.op_seconds(lambda name: "kl_loss" in name)
    assert sec == pytest.approx(15e-9 / 2) and n == 0   # one of two devices
    sec, n = t.op_seconds(lambda name: name.startswith("fusion"))
    assert sec == pytest.approx(50e-9 / 2) and n == 1


def test_op_name_from_hlo_text():
    text = ("%jvp_jit_kl_loss__.11 = f32[2,32,32,1]{3,2,1,0} custom-call("
            "f32[2,32,32,256]{3,2,1,0} %gram_fusion.4)")
    assert tracereduce.op_name(text) == "jvp_jit_kl_loss__.11"
    assert tracereduce.op_name("fusion.1") == "fusion.1"


def test_gaps_labelled_by_innermost_host_span():
    gaps = _trace().idle_gaps()
    assert gaps[0][0] == "plan" and gaps[0][1] == pytest.approx(10e-9)
    assert gaps[1][0] == "rounds" and gaps[1][1] == pytest.approx(10e-9)
    top = _trace().top_ops(2)
    assert top[0][0] == "fusion.1"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    t = tracereduce.read(str(RECORDED), 1)
    assert t.ops and t.window_s > 0
    busy = t.busy_s()
    assert 0 < busy <= t.window_s
    kl, n_kl = t.op_seconds(lambda name: "kl_loss" in name)
    gram, n_gram = t.op_seconds(lambda name: "gram" in name)
    assert n_kl > 0 and n_gram > 0 and 0 < kl + gram < busy
    gaps = t.idle_gaps()
    assert gaps and all(g[1] > 0 for g in gaps)


def test_allreduce_reader_on_hand_made_events():
    import run as harness
    read = harness.load_reader(BENCH.parent, "allreduce_ms_per_round")
    ops = {"/device:TPU:0": [("fusion.1", 0, 10), ("psum.3", 10, 4e6),
                             ("all-reduce-start.1", 20, 1e6),
                             ("all-reduce-done.1", 30, 3e6),
                             ("psum.3", 60, 1e6)],
           "/device:TPU:1": [("psum.3", 10, 6e6),
                             ("reduce_sum.2", 40, 9e6),
                             ("multiply_reduce_fusion.4", 41, 9e6)]}
    trace = Trace(window=(0.0, 50.0), ops=ops)
    ctx = {"trace": trace, "mix": {"rounds": 2}}
    # in the window: device 0 4 + 1 + 3 ms, device 1 6 ms; the psum at 60
    # lies outside it, and a reduction on one chip is no collective
    assert read(ctx) == pytest.approx((8 + 6) / 2 / 2)
    # no collective, or no trace: silent, never 0
    one = Trace(window=(0.0, 50.0), ops={"/device:TPU:0": [("fusion.1", 0,
                                                            10)]})
    assert read({"trace": one, "mix": {"rounds": 2}}) is None
    assert read({"trace": None, "mix": {"rounds": 2}}) is None

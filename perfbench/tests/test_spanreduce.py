"""The span and scope reduction (``spanreduce``) and its two readers, on
hand-made events and a hand-encoded XSpace."""
import importlib.util
import struct
from pathlib import Path

import pytest

import spanreduce
from tracereduce import Trace

METRICS = Path(__file__).resolve().parents[1] / "metrics"
NAMES = ("run_campaign", "plan_schedule", "segment", "host_fetch")
ROOTS = ("run_campaign",)


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _trace(host):
    # device 0 busy [10,20) [60,70) in a window [0,100): idle 80 ns
    ops = {"/device:TPU:0": [("fusion.1", 10, 10), ("fusion.2", 60, 10)]}
    return Trace(window=(0.0, 100.0), ops=ops, host=host)


def test_self_time_under_a_while():
    # a while [0,100) holds two body ops and a cond [60,90) with one op
    ops = [("while.1", 0, 100, None), ("fusion.1", 10, 20, "a"),
           ("fusion.2", 30, 10, "a"), ("cond.1", 60, 30, None),
           ("fusion.3", 65, 20, "a"), ("fusion.4", 120, 5, "a")]
    assert spanreduce.self_ns(ops) == [40, 20, 10, 10, 20, 5]
    # the self times add up to the busy union
    assert sum(spanreduce.self_ns(ops)) == 105


@pytest.mark.parametrize("path, scope", [
    ("jit(seg)/jit(main)/while/body/vmap(phase_client)/dot_general:",
     "phase_client"),
    ("jit(seg)/while/body/jvp(vmap(phase_server))/jit(kl_loss)/x:",
     "phase_server"),
    ("jit(seg)/while/body/vmap()/aggregate/reduce_sum:", "aggregate"),
    ("jit(seg)/while/body/cond/branch_1_fun/vmap(eval)/dot:", "eval"),
    ("jit(seg)/while/body/select_n:", None),
    ("", None),
    (None, None),
])
def test_scope_of_an_op_path(path, scope):
    assert spanreduce.scope_of(path) == scope


def test_scope_seconds_by_stat():
    w = "jit(seg)/while"
    ops = {"/device:TPU:0": [
        ("while.1", 0, 100, w + ":"),
        ("fusion.1", 10, 30, w + "/body/vmap(phase_local)/dot:"),
        ("fusion.2", 50, 20, w + "/body/aggregate/add:"),
        ("fusion.3", 80, 10, w + "/body/cond/eval/dot:"),
        ("fusion.9", 500, 10, w + "/body/eval/dot:")]}       # past window
    got = spanreduce.scope_seconds(ops, (0.0, 200.0))
    assert got == pytest.approx({"unscoped": 40e-9, "phase_local": 30e-9,
                                 "aggregate": 20e-9, "eval": 10e-9})


def test_a_trace_without_op_paths_reads_none():
    ops = {"/device:TPU:0": [("fusion.1", 0, 10, None)]}
    assert spanreduce.scope_seconds(ops, (0.0, 100.0)) is None
    assert spanreduce.scope_seconds({}, (0.0, 100.0)) is None


def test_idle_by_program_span_with_the_root_unattributed():
    host = [("campaign", 0, 100),          # the harness's, not a program span
            ("run_campaign", 0, 95), ("plan_schedule", 0, 10),
            ("segment", 20, 40), ("trace_to_jaxpr_dynamic", 25, 20),
            ("host_fetch", 70, 25)]
    t = _trace(host)
    idle = spanreduce.idle_by_span(t, NAMES)
    assert idle == pytest.approx({"plan_schedule": 10e-9, "segment": 40e-9,
                                  "host_fetch": 25e-9, None: 5e-9})
    # the root alone covers nothing idle here; none covers [95, 100)
    assert spanreduce.idle_unattributed_share(t, NAMES, ROOTS) == \
        pytest.approx(100 * 5 / 80)
    t = _trace([("run_campaign", 0, 100), ("segment", 20, 40)])
    assert spanreduce.idle_unattributed_share(t, NAMES, ROOTS) == \
        pytest.approx(100 * 40 / 80)


def test_idle_share_reads_none_without_program_spans():
    t = _trace([("campaign", 0, 100), ("PjitFunction(f)", 20, 40)])
    assert spanreduce.idle_unattributed_share(t, NAMES, ROOTS) is None


def test_readers():
    from repro.launch import spans
    t = _trace([("run_campaign", 0, 101), ("segment", 0, 100)])
    assert _reader("idle_unattributed_share")({"trace": t}) == 0.0
    assert _reader("idle_unattributed_share")({"trace": None}) is None
    want = spans.counts["segment_builds"] / spans.counts["campaigns"] \
        if spans.counts["campaigns"] else None
    assert _reader("segment_builds_per_campaign")({}) == want


# ---------------------------------------------------------------------------
# A hand-encoded XSpace: one TPU plane, an op line, an op path held by
# value and one by reference to a stat name
# ---------------------------------------------------------------------------

def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields):
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        elif isinstance(value, float):
            out += _varint(number << 3 | 1) + struct.pack("<d", value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def test_read_ops_decodes_paths(tmp_path):
    stat_meta = [_msg((1, 7), (2, "tf_op")), _msg((1, 8), (2, "flops")),
                 _msg((1, 9), (2, "jit(seg)/aggregate/add:"))]
    event_meta = [
        _msg((1, 1), (2, "%fusion.1 = f32[2] fusion(f32[2] %p)"),
             (5, _msg((1, 8), (2, 3.0))),
             (5, _msg((1, 7), (5, "jit(seg)/vmap(phase_client)/dot:")))),
        _msg((1, 2), (2, "%add.2 = f32[2] add(f32[2] %a)"),
             (5, _msg((1, 7), (7, 9)))),
        _msg((1, 3), (2, "%while.3 = f32[2] while(f32[2] %b)"))]
    ops_line = _msg((2, "XLA Ops"), (3, 1000),
                    (4, _msg((1, 3), (2, 0), (3, 9000))),
                    (4, _msg((1, 1), (2, 1000), (3, 2000))),
                    (4, _msg((1, 2), (2, 4000), (3, 3000))))
    other = _msg((2, "XLA Modules"), (4, _msg((1, 1), (2, 0), (3, 1))))
    plane = _msg((1, 5), (2, "/device:TPU:0"), (3, other), (3, ops_line),
                 *[(4, _msg((1, i + 1), (2, m)))
                   for i, m in enumerate(event_meta)],
                 *[(5, _msg((1, 7 + i), (2, m)))
                   for i, m in enumerate(stat_meta)])
    host = _msg((2, "/host:CPU"), (3, _msg((2, "python"))))
    skipped = _msg((2, "/device:TPU:1"), (3, ops_line))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, plane), (1, skipped)))
    ops = spanreduce.read_ops(str(path), 1)
    assert ops == {"/device:TPU:0": [
        ("while.3", 1000.0, 9.0, None),
        ("fusion.1", 1001.0, 2.0, "jit(seg)/vmap(phase_client)/dot:"),
        ("add.2", 1004.0, 3.0, "jit(seg)/aggregate/add:")]}
    assert spanreduce.scope_seconds(ops, (0.0, 2000.0)) == pytest.approx(
        {"unscoped": 4e-9, "phase_client": 2e-9, "aggregate": 3e-9})

"""Program spans, counters and named scopes (``repro.launch.spans``).

A tiny scanned campaign under ``spans.record()`` must give a span tree
rooted at ``run_campaign``; its counters must count with recording off;
the spans must reach a profiler trace with their attrs; and the lowered
segment must carry the round's named scopes.  Every campaign test starts
from a cleared segment cache (``campaign.clear_segment_cache``), so its
first campaign builds its segments.
"""
import glob

import jax
import pytest

from repro.configs.splitme_dnn import DNN10
from repro.core.cost import SystemParams
from repro.launch import campaign, spans

M, ROUNDS, SEEDS = 12, 4, (0, 1)


@pytest.fixture(scope="module")
def small_data():
    from repro.data import oran
    X, y = oran.generate(n_per_class=300, seed=0)
    (Xtr, ytr), (Xte, yte) = oran.train_test_split(X, y)
    cd = oran.partition_non_iid(Xtr, ytr, M, samples_per_client=32, seed=0)
    return cd, (Xte, yte)


@pytest.fixture(autouse=True)
def cold_segment_cache():
    campaign.clear_segment_cache()


def _run(small_data, framework="splitme", **kw):
    cd, test = small_data
    return campaign.run_campaign(framework, DNN10, SystemParams(M=M, seed=0),
                                 cd, rounds=ROUNDS, seeds=SEEDS,
                                 test_data=test, eval_every=2, **kw)


def _children(recorded, parent):
    return [s for s in recorded if s.parent == parent.id]


@pytest.mark.parametrize("framework", ["splitme", "fedavg"])
def test_span_tree_of_a_campaign(small_data, framework):
    with spans.record() as recorded:
        _run(small_data, framework)
    root = recorded[0]
    assert root.name == "run_campaign" and root.parent is None
    assert root.attrs == {"framework": framework, "rounds": ROUNDS,
                          "seeds": len(SEEDS)}
    kids = _children(recorded, root)
    names = [s.name for s in kids]
    n_seg = names.count("segment")
    assert n_seg >= 1
    assert names == (["plan_schedule", "init_state"] + ["segment"] * n_seg
                     + ["host_fetch"])
    # every span is the root or a child of it, and closed inside it
    assert len(recorded) == 1 + len(kids)
    assert all(root.start_ns <= s.start_ns <= s.end_ns <= root.end_ns
               for s in kids)
    # the segments tile the campaign's rounds in order
    segs = [s.attrs for s in kids if s.name == "segment"]
    assert [a["start"] for a in segs] == [
        sum(b["length"] for b in segs[:i]) for i in range(n_seg)]
    assert sum(a["length"] for a in segs) == ROUNDS


def test_counters_attribute_builds_and_transfers(small_data):
    before = spans.counts.copy()
    with spans.record() as recorded:
        for _ in range(2):
            _run(small_data)
    counted = spans.counts - before
    roots = [s for s in recorded if s.name == "run_campaign"]
    segs = [s for s in recorded if s.name == "segment"]
    # one build per segment shape over both campaigns: the second hits
    keys = {(s.attrs["kb"], s.attrs["eb"], s.attrs["lb"]) for s in segs}
    assert counted["segment_builds"] == len(keys)
    assert counted["segment_builds"] == sum(s.attrs["built"] for s in segs)
    assert counted["segment_hits"] == len(segs) - len(keys)
    second = [s for s in segs if s.parent == roots[1].id]
    assert second and not any(s.attrs["built"] for s in second)
    assert [s.counts for s in second] == [{"segment_hits": 1}] * len(second)
    assert counted["host_transfers"] == counted["campaigns"] == 2
    for s in segs:
        assert s.counts.get("segment_builds", 0) == int(s.attrs["built"])
        assert s.counts.get("segment_hits", 0) == int(not s.attrs["built"])
        if s.attrs["built"]:
            # the build traces, lowers and compiles inside the segment
            assert s.counts["trace_s"] > 0 and s.counts["rebuild_s"] > 0
            assert (s.counts.get("executables_compiled", 0)
                    + s.counts.get("executables_loaded", 0)) >= 1
    fetches = [s for s in recorded if s.name == "host_fetch"]
    assert [s.counts for s in fetches] == [{"host_transfers": 1}] * 2


def test_counters_count_with_recording_off(small_data):
    with spans.record() as recorded:
        pass
    before = spans.counts.copy()
    _run(small_data, "fedavg")
    counted = spans.counts - before
    assert recorded == []
    assert counted["host_transfers"] == 1
    assert counted["segment_builds"] >= 1
    assert counted["executables_compiled"] >= counted["segment_builds"]


def test_spans_reach_the_profiler_trace(small_data, tmp_path):
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)), spans.record() as recorded:
        _run(small_data, "fedavg")
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    traced = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                traced += [(e.name, dict(e.stats)) for e in line.events
                           if e.name in spans.NAMES]
    want = [(s.name, {k: int(v) if isinstance(v, bool) else v
                      for k, v in s.attrs.items()}) for s in recorded]
    assert sorted(traced, key=repr) == sorted(want, key=repr)


def test_lowered_segment_carries_the_round_scopes(small_data, monkeypatch):
    """The segment's HLO metadata names each phase, the aggregation and
    the fused eval."""
    real_jit, texts = jax.jit, []

    def spy(fun, *a, **k):
        jitted = real_jit(fun, *a, **k)
        if getattr(fun, "__name__", None) != "seg":
            return jitted

        def call(*args):
            texts.append(jitted.lower(*args).as_text(debug_info=True))
            return jitted(*args)
        return call

    monkeypatch.setattr(jax, "jit", spy)
    _run(small_data)
    assert texts
    for scope in ("phase_client", "phase_server", "aggregate", "eval"):
        assert all(scope in t for t in texts), scope


def test_span_names_and_nesting():
    with pytest.raises(ValueError, match="not a program span"):
        with spans.span("campaign"):
            pass
    for harness_name in ("campaign", "plan", "fetch", "rounds"):
        assert harness_name not in spans.NAMES
    before = spans.counts["host_transfers"]
    with spans.record() as recorded:
        with spans.span("run_campaign", framework="x"):
            with spans.span("host_fetch"):
                spans.count("host_transfers", 2)
            spans.count("segment_builds")
    root, fetch = recorded
    assert fetch.parent == root.id and root.parent is None
    assert fetch.counts == {"host_transfers": 2}
    assert root.counts == {"segment_builds": 1}
    assert spans.counts["host_transfers"] == before + 2


@pytest.mark.parametrize("intervals, want", [
    ([], 0.0),
    ([(0.0, 1.0), (2.0, 3.0)], 2.0),
    ([(0.0, 4.0), (1.0, 2.0), (3.0, 5.0)], 5.0),     # nested and overlapping
])
def test_union_seconds(intervals, want):
    assert spans.union_seconds(intervals) == pytest.approx(want)

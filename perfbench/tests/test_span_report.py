"""``span_report`` on the tiny SplitMe cell, on the CPU: the recorded
spans account for the harness's rebuild reading and the counters."""
import argparse

import pytest

import span_report


def test_span_report_on_a_tiny_cell(tiny_root, tmp_path):
    args = argparse.Namespace(workload="splitme-tiny.mini", seed=5,
                              campaigns=1, out=str(tmp_path))
    rep = span_report.report(args, root=tiny_root, require_chip=False)
    assert len(rep["campaign_s"]["off"]) == len(rep["campaign_s"]["on"]) == 1
    cam, = rep["campaigns"]
    rows = cam["spans"]
    assert set(rows) == {"run_campaign", "plan_schedule", "init_state",
                         "segment", "host_fetch"}
    assert rows["run_campaign"]["n"] == 1
    assert cam["counted"]["host_transfers"] == rows["host_fetch"][
        "host_transfers"] == 1
    # a window campaign finds its segment scans in the driver's cache (a
    # hit) or builds them (a build): one or the other per segment span
    seg = rows["segment"]
    assert (cam["counted"].get("segment_builds", 0)
            + cam["counted"].get("segment_hits", 0)
            == seg.get("segment_builds", 0) + seg.get("segment_hits", 0)
            == seg["n"])
    # every JAX compile event of the campaign falls in some program span
    assert sum(r.get("rebuild_s", 0.0) for r in rows.values()) == \
        pytest.approx(cam["harness_rebuild_s"], rel=1e-6)
    assert cam["harness_built"] == (
        cam["counted"].get("executables_compiled", 0)
        + cam["counted"].get("executables_loaded", 0))
    traced = rep["traced"]
    assert traced["spans"]["run_campaign"]["n"] == 1
    assert traced["scope_s"] is None          # the CPU has no TPU op lines
    assert (tmp_path / "splitme-tiny.mini.xplane.pb").exists()
    assert "idle_unattributed_share" in span_report.summary(rep)

"""The mesh configuration, cut to a size a test run holds, on four CPU
devices: the clients shard over the data axis, the round's psum and the
Step-4 Gram psums cross the devices, and the run comes out correct."""
import json

from conftest import BENCH, add_mesh_cell, run_on_devices


def test_tiny_mesh_cell_is_correct_on_four_devices(tiny_root):
    name = add_mesh_cell(tiny_root)
    cfg = json.loads((tiny_root / "perfbench" / "configs"
                      / "mesh-tiny.json").read_text())
    committed = json.loads((BENCH / "configs"
                            / "splitme-dnn10-m48-mesh4.json").read_text())
    assert cfg["mesh"] == committed["mesh"] == {"data": 4}
    assert cfg["limits"] == committed["limits"]
    result = run_on_devices(tiny_root, name, devices=4)
    assert result["correct"] is True, result["checks"]
    assert result["device"]["count"] == 4
    assert result["checks"]["schedule_mismatch"]["value"] == 0

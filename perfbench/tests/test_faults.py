"""The harness's run with the timed path broken underneath comes out
``correct: false``, once for each fault these cells can have
(``perfbench/faults.py``); the mesh cell's, the left-out exchange among
them, on four CPU devices in a child process."""
import pytest

from conftest import add_mesh_cell, run_on_devices, run_tiny
from faults import FAULTS, MESH_FAULTS, planted


@pytest.mark.parametrize("cell", ["splitme-tiny.mini", "fedavg-tiny.mini"])
def test_sound_run_is_correct(tiny_root, cell):
    assert run_tiny(tiny_root, cell)["correct"] is True


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["splitme-tiny.mini", "fedavg-tiny.mini"])
def test_fault_is_incorrect(tiny_root, cell, fault):
    with planted(fault):
        result = run_tiny(tiny_root, cell)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("fault", sorted(MESH_FAULTS))
def test_mesh_fault_is_incorrect(tiny_root, fault):
    """``exchange_left_out``: every device averages only its own
    clients."""
    name = add_mesh_cell(tiny_root)
    result = run_on_devices(tiny_root, name, devices=4, fault=fault)
    assert result["correct"] is False, result["checks"]

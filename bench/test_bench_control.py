"""The correctness check fails what it must, at a tiny size on the CPU:
the control (the plain reference computed in bfloat16, put in the
program's place) and each fault a training cell can have, planted in the
program underneath the harness. The sound program passes the same
limits. The limits are the real cells' (``workloads/*.json``)."""
import pytest

from bench import harness
from bench.faults import FAULTS
from bench.readings import collect

SEEDS = (3, 2 ** 32 + 11)


def _fails(cell, numbers):
    return any(numbers[k] > limit for k, limit in cell.limits.items())


def readings(root, cell_name, fault_names, seeds):
    """The readings ``bench/readings.py`` takes on the chip, at the tiny
    size: {"sound": [numbers per seed], "control": [...], fault: [...]}."""
    import jax
    cell = harness.load_cell(cell_name, root)
    got = collect(cell, jax.devices(), seeds, seeds, fault_names, seeds)
    out = {k: list(got[k].values()) for k in ("sound", "control")}
    out.update({f: list(v.values()) for f, v in got["faults"].items()})
    return out


@pytest.fixture(scope="module")
def one_chip(tiny_root):
    cell = harness.load_cell("tiny.t4", tiny_root)
    return cell, readings(tiny_root, "tiny.t4",
                          FAULTS, SEEDS)


def test_sound_program_passes(one_chip):
    cell, got = one_chip
    for numbers in got["sound"]:
        assert not _fails(cell, numbers), numbers


def test_control_fails(one_chip):
    cell, got = one_chip
    for numbers in got["control"]:
        assert _fails(cell, numbers), numbers


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_fails(one_chip, fault):
    cell, got = one_chip
    for numbers in got[fault]:
        assert _fails(cell, numbers), numbers


def test_state_unchanged_reads_one_on_update(one_chip):
    _, got = one_chip
    for numbers in got["state_unchanged"]:
        assert numbers["update"] == pytest.approx(1.0)

"""TPUDetector on hosts it must not guess about (PR 23): the vfio
control node is not a chip, a chip of unknown generation is an error
that names what was looked at, and the device nodes — what an engine can
open — decide the count."""

import pytest

from gpustack_tpu.detectors import detector as det
from gpustack_tpu.schemas.workers import WorkerStatus


def _detect(monkeypatch, nodes, pci, env=None):
    for var in (
        "TPU_ACCELERATOR_TYPE", "TPU_TOPOLOGY", "TPU_WORKER_COUNT",
        "TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES", "TPU_SLICE_NAME",
    ):
        monkeypatch.delenv(var, raising=False)
    for k, v in (env or {}).items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(
        det.glob, "glob",
        lambda pat: [n for n in nodes if n.startswith(pat.rstrip("*"))],
    )
    monkeypatch.setattr(det, "pci_tpu_generations", lambda: list(pci))
    status = WorkerStatus()
    det.TPUDetector()._fill_tpu(status)
    return status


def test_vfio_control_node_is_not_a_chip(monkeypatch):
    monkeypatch.setattr(
        det.glob, "glob",
        lambda pat: (
            ["/dev/vfio/0", "/dev/vfio/vfio"] if "vfio" in pat else []
        ),
    )
    assert det.chip_device_nodes() == ["/dev/vfio/0"]


def test_one_chip_machine_as_seen_on_the_v5e(monkeypatch):
    """The chip tool's one-chip machine: one vfio group, four TPU
    functions on the PCI bus, TPU_ACCELERATOR_TYPE naming four chips."""
    status = _detect(
        monkeypatch,
        nodes=["/dev/vfio/0", "/dev/vfio/vfio"],
        pci=["v5e"] * 4,
        env={"TPU_ACCELERATOR_TYPE": "v5litepod-4", "TPU_TOPOLOGY": "2x2"},
    )
    assert [c.index for c in status.chips] == [0]
    assert status.chips[0].chip_type == "v5e"
    assert status.chips[0].hbm_bytes == 16 * 2**30
    assert status.slice.chips_per_host == 1


def test_generation_from_the_pci_id_without_the_env(monkeypatch):
    status = _detect(
        monkeypatch, nodes=[f"/dev/accel{i}" for i in range(4)],
        pci=["v6e"] * 4,
    )
    assert len(status.chips) == 4
    assert {c.chip_type for c in status.chips} == {"v6e"}
    assert status.chips[0].hbm_bytes == 32 * 2**30


def test_chips_of_unknown_generation_are_an_error(monkeypatch):
    with pytest.raises(RuntimeError) as e:
        _detect(monkeypatch, nodes=["/dev/accel0"], pci=["0x0099"])
    # the error names what was looked at
    assert "0x0099" in str(e.value) and "/dev/accel0" in str(e.value)
    with pytest.raises(RuntimeError, match="generation"):
        _detect(monkeypatch, nodes=["/dev/vfio/0"], pci=[])


def test_generation_without_an_hbm_size_is_an_error(monkeypatch):
    monkeypatch.setitem(det._ACCEL_ALIASES, "v9x", "v9x")
    with pytest.raises(RuntimeError, match="no HBM size"):
        _detect(
            monkeypatch, nodes=["/dev/accel0"], pci=[],
            env={"TPU_ACCELERATOR_TYPE": "v9x-8"},
        )


def test_no_chips_is_said_not_guessed(monkeypatch, caplog):
    with caplog.at_level("INFO"):
        status = _detect(monkeypatch, nodes=[], pci=[])
    assert status.chips == []
    assert "no TPU chips on this host" in caplog.text

"""TPU + system detection.

Replaces the reference's detector stack (fastfetch binary wrapper +
gpustack-runtime NVML probing, reference detectors/detector_factory.py).
Three sources, none of which opens a chip or a JAX backend:

- the chips this host can open are its device nodes: ``/dev/accel*``,
  else the numbered vfio groups ``/dev/vfio/<n>`` (``/dev/vfio/vfio`` is
  the control node). Seen on a one-chip v5e machine (PR 23): no
  ``/dev/accel*``, ``/dev/vfio/0`` + ``/dev/vfio/vfio``, four TPU
  functions on the PCI bus and ``TPU_ACCELERATOR_TYPE=v5litepod-4`` —
  only the device nodes say "one chip";
- the generation comes from ``TPU_ACCELERATOR_TYPE`` ("v5litepod-8"),
  else from the PCI device id of Google's functions in sysfs;
- slice topology from ``TPU_TOPOLOGY`` / ``TPU_WORKER_ID`` /
  ``TPU_WORKER_HOSTNAMES``.

System info comes straight from /proc (the C++ ``sysinfo`` tool in
native/ provides the same JSON contract for non-Python consumers).
"""

from __future__ import annotations

import glob
import json
import logging
import os
import platform
from typing import Dict, List, Optional

from gpustack_tpu.schemas.workers import SliceTopology, TPUChip, WorkerStatus

logger = logging.getLogger(__name__)

# HBM per chip by generation (GiB)
CHIP_HBM_GIB: Dict[str, int] = {
    "v4": 32,
    "v5e": 16,
    "v5p": 95,
    "v6e": 32,
}

_ACCEL_ALIASES = {
    "v5litepod": "v5e",
    "v5lite": "v5e",
    "v5p": "v5p",
    "v6e": "v6e",
    "v4": "v4",
}


# A TPU chip is one PCI function of Google's vendor id; its device id
# names the generation (the table the `tpu-info` tool reads chips by).
GOOGLE_PCI_VENDOR = "0x1ae0"
PCI_DEVICES_GLOB = "/sys/bus/pci/devices/*"
_PCI_DEVICE_GENERATION = {
    "0x005e": "v4",
    "0x0063": "v5e",
    "0x0062": "v5p",
    "0x006f": "v6e",
}


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""


def pci_tpu_generations() -> List[str]:
    """One entry per TPU chip on the PCI bus: its generation, or the raw
    device id where the table above does not know it. Reads sysfs only —
    the worker process never opens the chips or a JAX backend."""
    out = []
    for dev in sorted(glob.glob(PCI_DEVICES_GLOB)):
        if _read(os.path.join(dev, "vendor")).lower() != GOOGLE_PCI_VENDOR:
            continue
        device_id = _read(os.path.join(dev, "device")).lower()
        out.append(_PCI_DEVICE_GENERATION.get(device_id, device_id))
    return out


def chip_device_nodes() -> List[str]:
    """The chips' device nodes: ``/dev/accel*``, else the numbered vfio
    groups — ``/dev/vfio/vfio`` is the control node, not a chip."""
    return sorted(glob.glob("/dev/accel*")) or sorted(
        p for p in glob.glob("/dev/vfio/*")
        if os.path.basename(p).isdigit()
    )


def parse_accelerator_type(accel: str):
    """'v5litepod-8' -> ('v5e', 8); 'v4-32' -> ('v4', 32)."""
    if not accel or "-" not in accel:
        return None
    gen_raw, _, count = accel.rpartition("-")
    gen = _ACCEL_ALIASES.get(gen_raw.strip().lower())
    try:
        return (gen, int(count)) if gen else None
    except ValueError:
        return None


class TPUDetector:
    """Detect TPU chips + slice topology on this host."""

    def detect(self) -> WorkerStatus:
        status = WorkerStatus(
            cpu_count=os.cpu_count() or 0,
            os=platform.system(),
            kernel=platform.release(),
            arch=platform.machine(),
        )
        self._fill_memory(status)
        self._fill_tpu(status)
        self._fill_versions(status)
        return status

    def _fill_memory(self, status: WorkerStatus) -> None:
        try:
            info = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    key, _, rest = line.partition(":")
                    info[key.strip()] = rest.strip()
            total = int(info.get("MemTotal", "0 kB").split()[0]) * 1024
            avail = int(info.get("MemAvailable", "0 kB").split()[0]) * 1024
            status.memory_total_bytes = total
            status.memory_used_bytes = max(0, total - avail)
        except (OSError, ValueError, IndexError):
            pass

    def _fill_tpu(self, status: WorkerStatus) -> None:
        accel = os.environ.get("TPU_ACCELERATOR_TYPE", "")
        parsed = parse_accelerator_type(accel)
        pci_gens = pci_tpu_generations()
        devices = chip_device_nodes()
        looked_at = (
            f"TPU_ACCELERATOR_TYPE={accel!r}, "
            f"{len(pci_gens)} PCI function(s) of vendor {GOOGLE_PCI_VENDOR} "
            f"under {PCI_DEVICES_GLOB} (device ids {sorted(set(pci_gens))}), "
            f"device nodes {devices}"
        )
        if parsed is None and not devices and not pci_gens:
            logger.info("no TPU chips on this host: %s", looked_at)
            return
        if parsed:
            gen, total_chips = parsed
        else:
            known = {g for g in pci_gens if g in CHIP_HBM_GIB}
            if len(known) != 1:
                raise RuntimeError(
                    "TPU chips found but their generation could not be "
                    f"established: {looked_at}"
                )
            gen, total_chips = known.pop(), len(devices) or len(pci_gens)
        if gen not in CHIP_HBM_GIB:
            raise RuntimeError(
                f"no HBM size known for TPU generation {gen!r} "
                f"(known: {sorted(CHIP_HBM_GIB)}): {looked_at}"
            )
        topology = os.environ.get("TPU_TOPOLOGY", "")
        num_hosts = max(
            1, int(os.environ.get("TPU_WORKER_COUNT", "0") or 0)
        )
        host_index = int(os.environ.get("TPU_WORKER_ID", "0") or 0)
        hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
        if num_hosts == 1 and hostnames:
            num_hosts = max(1, len(hostnames.split(",")))
        # the device nodes are what an engine process can open: a host
        # may show more TPU functions on its PCI bus (and name a bigger
        # slice in TPU_ACCELERATOR_TYPE) than it was given chips
        chips_here = (
            len(devices) or len(pci_gens) or total_chips // num_hosts or 1
        )
        hbm = CHIP_HBM_GIB[gen] * 2**30
        status.chips = [
            TPUChip(index=i, chip_type=gen, hbm_bytes=hbm)
            for i in range(chips_here)
        ]
        status.slice = SliceTopology(
            topology=topology,
            chips_per_host=chips_here,
            num_hosts=num_hosts,
            host_index=host_index,
            ici_domain=os.environ.get("TPU_SLICE_NAME", "")
            or (accel if num_hosts > 1 else ""),
        )

    def _fill_versions(self, status: WorkerStatus) -> None:
        # version strings only: importing jax opens no backend, and the
        # worker must never hold the chips its engines need
        try:
            import jax

            status.jax_version = jax.__version__
        except Exception:
            pass
        try:
            import importlib.metadata as md

            status.libtpu_version = md.version("libtpu")
        except Exception:
            pass


class FakeDetector:
    """Fixture-driven detector (tests / simulated fleets)."""

    def __init__(self, fixture_path: str):
        self.fixture_path = fixture_path

    def detect(self) -> WorkerStatus:
        with open(self.fixture_path) as f:
            return WorkerStatus.model_validate(json.load(f))


def create_detector(fake_fixture: Optional[str] = None):
    if fake_fixture:
        return FakeDetector(fake_fixture)
    return TPUDetector()

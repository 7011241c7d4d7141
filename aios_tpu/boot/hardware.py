"""Hardware detection at boot.

Reference parity (initd/src/hardware.rs:37+): CPU/memory/disk discovery from
/proc and /sys. TPU-specific addition: lists the accelerator device nodes
WITHOUT opening them — a chip belongs to one process, and that process is
the runtime service the supervisor is about to spawn, so nothing on the
boot path may import JAX (the reference's GPU detection has no TPU notion
at all).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List

import psutil


@dataclass
class HardwareInfo:
    cpu_model: str = ""
    cpu_cores: int = 0
    cpu_threads: int = 0
    memory_total_mb: int = 0
    disks: List[Dict] = field(default_factory=list)
    tpu_devices: List[str] = field(default_factory=list)


def tpu_device_nodes() -> List[str]:
    """Accelerator device nodes a TPU VM exposes (/dev/accel* on the
    older driver, numbered /dev/vfio groups on the newer one). Listing
    them opens nothing."""
    nodes = sorted(str(p) for p in Path("/dev").glob("accel*"))
    nodes += sorted(
        str(p) for p in Path("/dev/vfio").glob("[0-9]*")
    )
    return nodes


def detect() -> HardwareInfo:
    info = HardwareInfo()
    try:
        for line in Path("/proc/cpuinfo").read_text().splitlines():
            if line.startswith("model name"):
                info.cpu_model = line.split(":", 1)[1].strip()
                break
    except OSError:
        pass
    info.cpu_cores = psutil.cpu_count(logical=False) or 0
    info.cpu_threads = psutil.cpu_count() or 0
    info.memory_total_mb = int(psutil.virtual_memory().total / 1e6)
    for part in psutil.disk_partitions(all=False):
        try:
            usage = psutil.disk_usage(part.mountpoint)
        except OSError:
            continue
        info.disks.append(
            {"mount": part.mountpoint, "total_gb": round(usage.total / 1e9, 1)}
        )
    info.tpu_devices = tpu_device_nodes()
    return info

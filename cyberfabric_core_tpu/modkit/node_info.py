"""Host/accelerator inventory collectors — the modkit-node-info library.

Reference: libs/modkit-node-info/src/model.rs:13-95 (NodeSysInfo = os + cpu +
memory + host + gpus + battery), sysinfo_collector.rs, gpu_collector_linux.rs,
syscap_collector.rs, hardware_uuid.rs. The reference shells out to OS APIs per
platform; this rendition reads Linux's /proc and /sys directly (the TPU fleet
is Linux) with graceful degradation elsewhere — every collector returns what it
can and omits what it can't, never raises.

The GPU collector analogue is JAX device enumeration: on a TPU host the
accelerator inventory IS jax.devices() (+ HBM stats where the platform exposes
them); NVML has no role here.
"""

from __future__ import annotations

import os
import platform
import socket
import time
from typing import Any, Optional

# ------------------------------------------------------------------ os / cpu


def collect_os() -> dict[str, Any]:
    """OsInfo: name / version / arch."""
    name = platform.system().lower() or "unknown"
    version = platform.release()
    try:  # prefer the distro pretty-name when present
        with open("/etc/os-release") as f:
            for line in f:
                if line.startswith("PRETTY_NAME="):
                    name = line.split("=", 1)[1].strip().strip('"')
                    break
    except OSError:
        pass
    return {"name": name, "version": version, "arch": platform.machine()}


def collect_cpu() -> dict[str, Any]:
    """CpuInfo: model / num_cpus / cores / frequency_mhz."""
    info: dict[str, Any] = {"model": platform.processor() or "unknown",
                            "num_cpus": os.cpu_count() or 0, "cores": 0,
                            "frequency_mhz": 0.0}
    try:
        # physical cores = distinct (package, core) pairs — core ids repeat
        # per socket on multi-socket hosts
        cores: set[tuple[str, str]] = set()
        phys = "0"
        model_name = None
        with open("/proc/cpuinfo") as f:
            for line in f:
                if ":" not in line:
                    continue
                key, val = (s.strip() for s in line.split(":", 1))
                if key == "model name" and model_name is None:
                    model_name = val
                elif key == "cpu MHz" and not info["frequency_mhz"]:
                    info["frequency_mhz"] = float(val)
                elif key == "physical id":
                    phys = val
                elif key == "core id":
                    cores.add((phys, val))
        if model_name:  # always prefer it: platform.processor() is often just
            info["model"] = model_name  # the arch string ("x86_64")
        info["cores"] = len(cores) or info["num_cpus"]
    except (OSError, ValueError):
        info["cores"] = info["cores"] or info["num_cpus"]
    return info


def collect_memory() -> dict[str, Any]:
    """MemoryInfo: total / available / used bytes + used_percent."""
    total = available = None
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    total = int(line.split()[1]) * 1024
                elif line.startswith("MemAvailable:"):
                    available = int(line.split()[1]) * 1024
                if total is not None and available is not None:
                    break
    except (OSError, ValueError):
        pass
    if total is None:
        return {"total_bytes": 0, "available_bytes": 0, "used_bytes": 0,
                "used_percent": 0}
    available = available if available is not None else 0
    used = total - available
    return {"total_bytes": total, "available_bytes": available,
            "used_bytes": used, "used_percent": round(100 * used / total)}


# ------------------------------------------------------------------ host


def _primary_ip() -> Optional[str]:
    """Default-route source address via a connected UDP socket (no packet is
    sent) — the reference's "first address = primary (default route)" rule."""
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("10.255.255.255", 1))
            return s.getsockname()[0]
    except OSError:
        return None


def collect_host(resolve_dns: bool = False) -> dict[str, Any]:
    """HostInfo: hostname / uptime_seconds / ip_addresses (primary first).

    ``resolve_dns`` gates the getaddrinfo lookup for secondary addresses: it
    can block for the resolver timeout, so the default path (called from async
    module init) sticks to the non-blocking UDP-connect probe."""
    hostname = platform.node() or "localhost"
    uptime = 0
    try:
        with open("/proc/uptime") as f:
            uptime = int(float(f.read().split()[0]))
    except (OSError, ValueError):
        pass
    ips: list[str] = []
    primary = _primary_ip()
    if primary:
        ips.append(primary)
    if resolve_dns:
        try:
            for entry in socket.getaddrinfo(hostname, None, socket.AF_INET):
                addr = entry[4][0]
                if addr not in ips and not addr.startswith("127."):
                    ips.append(addr)
        except OSError:
            pass
    return {"hostname": hostname, "uptime_seconds": uptime, "ip_addresses": ips}


def collect_battery() -> Optional[dict[str, Any]]:
    """BatteryInfo: on_battery / percentage — None on battery-less hosts
    (servers, the normal TPU case)."""
    base = "/sys/class/power_supply"
    try:
        supplies = os.listdir(base)
    except OSError:
        return None
    for name in supplies:
        try:
            with open(f"{base}/{name}/type") as f:
                if f.read().strip() != "Battery":
                    continue
            with open(f"{base}/{name}/capacity") as f:
                pct = int(f.read().strip())
            status = ""
            try:
                with open(f"{base}/{name}/status") as f:
                    status = f.read().strip().lower()
            except OSError:
                pass
            return {"on_battery": status == "discharging", "percentage": pct}
        except (OSError, ValueError):
            continue
    return None


def hardware_uuid() -> Optional[str]:
    """Stable machine identity (hardware_uuid.rs analogue): machine-id first,
    DMI product UUID as fallback."""
    for path in ("/etc/machine-id", "/var/lib/dbus/machine-id",
                 "/sys/class/dmi/id/product_uuid"):
        try:
            with open(path) as f:
                v = f.read().strip()
            if v:
                return v
        except OSError:
            continue
    return None


# ------------------------------------------------------------------ accelerators


def collect_accelerators() -> list[dict[str, Any]]:
    """Accelerator inventory via JAX (gpu_collector analogue for the TPU
    fleet): platform/kind per device + HBM totals where exposed."""
    try:
        from .telemetry import jax_devices

        out = []
        for d in jax_devices():
            dev: dict[str, Any] = {
                "id": d.id, "platform": d.platform,
                "model": getattr(d, "device_kind", "?"),
            }
            try:
                stats = d.memory_stats()
                if stats:
                    dev["total_memory_mb"] = round(
                        stats.get("bytes_limit", 0) / 1e6, 1)
                    dev["used_memory_mb"] = round(
                        stats.get("bytes_in_use", 0) / 1e6, 1)
            except Exception:  # noqa: BLE001 — platform-dependent surface
                pass
            out.append(dev)
        return out
    except Exception:  # noqa: BLE001 — no backend at all
        return []


# ------------------------------------------------------------------ syscaps


def collect_syscaps() -> list[dict[str, Any]]:
    """SysCap list (syscap_collector.rs analogue): concrete host capabilities
    with key/category/present/version/amount fields."""
    import shutil

    caps: list[dict[str, Any]] = [{
        "key": "runtime.python", "category": "runtime", "name": "python",
        "display_name": "Python", "present": True,
        "version": platform.python_version(), "amount": None,
        "amount_dimension": None,
    }]
    try:
        import jax

        caps.append({
            "key": "runtime.jax", "category": "runtime", "name": "jax",
            "display_name": "JAX", "present": True, "version": jax.__version__,
            "amount": float(len(jax.devices())), "amount_dimension": "devices",
        })
    except Exception:  # noqa: BLE001
        caps.append({"key": "runtime.jax", "category": "runtime", "name": "jax",
                     "display_name": "JAX", "present": False, "version": None,
                     "amount": None, "amount_dimension": None})
    for tool in ("g++", "cmake", "ninja", "protoc"):
        caps.append({
            "key": f"toolchain.{tool}", "category": "toolchain", "name": tool,
            "display_name": tool, "present": shutil.which(tool) is not None,
            "version": None, "amount": None, "amount_dimension": None,
        })
    mem = collect_memory()
    caps.append({
        "key": "hw.memory", "category": "hardware", "name": "memory",
        "display_name": "Memory", "present": mem["total_bytes"] > 0,
        "version": None, "amount": float(mem["total_bytes"]),
        "amount_dimension": "bytes",
    })
    return caps


def collect_node_sys_info() -> dict[str, Any]:
    """The full NodeSysInfo document (model.rs:13-22)."""
    return {
        "os": collect_os(),
        "cpu": collect_cpu(),
        "memory": collect_memory(),
        "host": collect_host(),
        "accelerators": collect_accelerators(),
        "battery": collect_battery(),
        "hardware_uuid": hardware_uuid(),
        "collected_at": time.time(),
    }

"""Machine-readable benchmark output: BENCH_<table>.json files.

Every benchmark table is persisted as ``BENCH_<table>.json`` with the raw
rows plus enough host info to compare runs across machines/commits — the
perf trajectory of the repo is tracked from these artifacts.
"""
from __future__ import annotations

import json
import os
import platform
import sys
import time


def host_info() -> dict:
    """Host and device of the run. A JAX that cannot name its device
    raises here: an artifact without its device is not comparable."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": platform.platform(),
        "machine": platform.machine(),
        "python": sys.version.split()[0],
        "cpu_count": os.cpu_count(),
        "jax": jax.__version__,
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
    }


def write_bench_json(table: str, rows: list[dict], out_dir: str = ".",
                     extra: dict | None = None) -> str:
    """Write BENCH_<table>.json; returns the path."""
    os.makedirs(out_dir, exist_ok=True)
    safe = table.replace("/", "_").replace("-", "_")
    path = os.path.join(out_dir, f"BENCH_{safe}.json")
    doc = {
        "table": table,
        "created_unix": time.time(),
        "host": host_info(),
        "rows": rows,
    }
    if extra:
        doc.update(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return path

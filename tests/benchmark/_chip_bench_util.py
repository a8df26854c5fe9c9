"""Tiny cells of the chip benchmark, for the CPU.

:func:`tiny_root` copies the benchmark's files and manifest into a
directory and adds, as files and manifest entries alone, a 64x64 uLBM
configuration on one chip and on four, and an 8-step traffic mix. The
harness then runs them on JAX's CPU backend with the Pallas kernels in
interpret mode.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY = "tiny.c8"
TINY_MESH = "tinyx4.c8"


def tiny_config(chips: int) -> dict:
    """The one-chip or the four-chip configuration, cut to 64x64."""
    name = "ulbm-d2q9-16384-mesh4" if chips == 4 else "ulbm-d2q9-8192"
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert cfg["chips"] == chips
    cfg.update(name=f"tiny-{chips}", grid=[64, 64],
               walls={"discs": 3, "radius": [2, 5], "lid_rows": 2})
    return cfg


def add_cell(root: Path, name: str, cfg: dict, traffic: str) -> None:
    """Add a configuration file and a cell to the manifest under
    ``root``; every per-layer metric limited to some cells gains it."""
    bench = root / "benchmarks" / "chip"
    (bench / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": cfg["name"], "source": "tiny", "reduced": ["grid"],
        "file": f"benchmarks/chip/configs/{cfg['name']}.json",
        "why": "tiny"})
    manifest["workloads"].append({
        "name": name, "config": cfg["name"], "traffic": traffic,
        "chips": cfg["chips"], "why": "tiny"})
    for m in manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))


def tiny_root(root: Path) -> Path:
    root = Path(root)
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "benchmarks" / "chip" / "traffic" / "tiny8.json").write_text(
        json.dumps({"steps_per_call": 8, "readback": "mass"}))
    add_cell(root, TINY, tiny_config(1), "tiny8")
    add_cell(root, TINY_MESH, tiny_config(4), "tiny8")
    return root

"""What every cell shares: loading a cell by name, the compile cache, the
peak table, the per-layer metric readers, the checks and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found here by name, so a new cell
needs new files and new ``BENCHMARK.json`` entries, not edits.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Optional

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    bench: dict

    def end_to_end(self) -> list:
        return _metrics_of(self.bench["end_to_end"], self.name)

    def per_layer(self) -> list:
        return _metrics_of(self.bench["per_layer"], self.name)


def _metrics_of(entries, cell):
    return [m for m in entries if cell in m.get("workloads", [cell])]


@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    peak: Optional[dict]
    t0: float
    root: Path
    control: bool = False


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, name: str, overrides=None) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                         f"choose from {sorted(cells)}")
    w = cells[name]
    files = root / "bench"
    over = overrides or {}
    config = over.get("config") or _load_json(
        files / "configs" / f"{w['config']}.json")
    mix = over.get("mix") or _load_json(
        files / "mixes" / f"{w['traffic']}.json")
    limits = over.get("limits") or _load_json(
        files / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                limits=limits, bench=bench)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at one fixed place inside the
    checkout, for every program of the run however small."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peaks(root: Path, device_kind: str) -> dict:
    table = _load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peak row for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(record, trace)``."""
    path = Path(__file__).resolve().parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(trace_red=None) -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if trace_red is not None:
        out["busy_s"] = trace_red["busy_s"]
        out["window_s"] = trace_red["window_s"]
    return out


# ------------------------------------------------------------------ checks
def check(checks: dict, name: str, value: float, limits: dict) -> None:
    """Record one compared number beside its limit (a number over its
    limit, or one that is not finite, is not correct)."""
    checks[name] = {"value": float(value),
                    "limit": float(limits[name])}


def all_within(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()


def result_line(run: Run, out: dict) -> dict:
    """The line printed last.  ``out`` is what the driver returned:
    ``end_to_end`` values, the ``record`` the per-layer readers read, the
    reduced ``trace`` (traced runs), ``attempted``/``failed`` and
    ``checks``."""
    cell = run.cell
    metrics = {}
    if run.trace:
        red = out["trace"]
        for m in cell.per_layer():
            v = metric_reader(m["name"])(out["record"], red)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": bool(out["correct"]),
            "attempted": int(out["attempted"]),
            "failed": int(out["failed"]),
            "metrics": metrics,
            "device": out["device"]}
    if run.trace:
        from bench import trace as T
        line["breakdown"] = T.breakdown(out["trace"])
    line["checks"] = out["checks"]
    return line

#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's root:
the cell names a configuration (``configs/<config>.json`` and the plain
reference it names, ``configs/<reference>.py``) and a traffic file
(``traffic/<traffic>.json``), whose ``kind`` picks the driver
(``drivers/<kind>.py``); each per-layer metric has a reader
(``metrics/<metric>.py``, where a metric split by cells, ``<metric>.<part>``,
shares its reader and its driver value); the cell's limits for ``correct`` are in
``limits/<cell>.json``.  A new cell, configuration, job or metric is new
files and new ``BENCHMARK.json`` entries.

The run fails, with no result line, when JAX finds no TPU or fewer chips
than the cell asks for.  With ``--trace 0`` it reports the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiled window.  Standard error ends with each number compared for
``correct`` beside its limit; the last line of standard output is the
result object.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from collections import defaultdict
from pathlib import Path

_T0 = time.perf_counter()

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def process_age() -> float:
    """Seconds since this process started (from /proc, so the
    interpreter's start and the imports count), else since this module
    was imported."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T0


class CompileClock:
    """Seconds XLA spends compiling or reading the persistent cache, and
    the cache's hits, counted from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.secs = defaultdict(float)
        self.events = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == _COMPILE_EVENT:
            self.secs[kw.get("fun_name", "?")] += secs
            self.events += 1

    def _event(self, event, **kw):
        if event == _CACHE_HIT_EVENT:
            self.hits += 1

    def total(self) -> float:
        return sum(self.secs.values())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def base_name(metric: str) -> str:
    """``step_mfu.train`` -> ``step_mfu``: a quantity
    split by the cells that report it keeps one driver value and one
    reader (``metrics/<base>.py``) under every split name."""
    return metric.split(".", 1)[0]


def reader(metric: str):
    return load_module(HERE / "metrics" / f"{base_name(metric)}.py")


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """A cell of ``BENCHMARK.json`` with everything found by its names."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"[bench] no workload {name!r} in "
                             f"BENCHMARK.json; have {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = self.entry["chips"]
        cfg = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = load_json(ROOT / cfg["file"])
        self.reference = load_module(
            HERE / "configs" / f"{self.config['reference']}.py")
        # what runs: the published sizes but for the cuts that ``reduced``
        # names, in the reference's (and the program's) names
        self.sizes = self.reference.sizes(self.config)
        self.cut = [self.reference.KEYS[k] for k in self.config["reduced"]]
        self.job = load_json(HERE / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = load_json(HERE / "limits" / f"{name}.json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in moved]


def find_devices(chips: int):
    """The chips this run uses; exits non-zero where JAX finds no TPU or
    fewer than ``chips``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"[bench] no TPU: JAX finds {devices[0].platform} "
                         f"devices only")
    if len(devices) < chips:
        raise SystemExit(f"[bench] the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    path = os.environ.get(CACHE_ENV) or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             wrap_step=None) -> dict:
    """Set up, measure and check one run of ``cell``; returns the result
    object.  ``wrap_step`` (tests only) replaces the program's step with
    a broken one."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    clock = CompileClock()
    driver = load_module(HERE / "drivers" / f"{cell.job['kind']}.py")
    out = driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                     devices=devices, clock=clock, process_age=process_age,
                     trace_dir=TRACE_DIR / f"{cell.name}-{seed}",
                     wrap_step=wrap_step, log=log)
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        ctx = out["layer_ctx"]
        metrics = {}
        for m in cell.per_layer:
            value = reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            elif "workloads" in m:
                # the metric names this cell: its reader must find what it
                # reads (a kernel renamed or re-laid-out must not leave the
                # metric silent)
                raise SystemExit(f"[bench] {m['name']} found nothing to read "
                                 f"in the trace of {cell.name}, which it "
                                 f"lists")
        result["metrics"] = metrics
    else:
        e2e = out["end_to_end"]
        result["metrics"] = {m["name"]: {"value": e2e[base_name(m["name"])],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    d0 = devices[0]
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": out["memory_peak_bytes"]}
    if trace:
        red = out["layer_ctx"]["trace"]
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": [[n, s] for n, s in
                                             red.idle_gaps[:10]]}
    for name, value, limit, where in out["checks"]:
        log(f"check {name} = {value!r} (limit {limit!r}; worst at {where})")
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit, _ in out["checks"]}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(load_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = find_devices(cell.chips)
    log(f"{cell.name}: {devices[0].device_kind} x {len(devices)}, seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}, compile cache "
        f"{enable_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

"""The harness: loads a cell by name, checks the device, runs the driver its
configuration names, reads the per-layer metrics, and prints one result line.

The last line of standard output is one JSON object::

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
     "checks"}

``checks`` comes last: each number compared for ``correct``, with its limit.
The same numbers are the last lines of standard error. Nothing is printed on
standard output when the device is not a TPU, when there are fewer chips than
the cell asks for, when the device is not in ``peaks.json``, or when the
program under test is not in the checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

from bench import loader
from bench import trace as trace_mod

PEAKS = os.path.join(loader.BENCH, "peaks.json")
TRACE_DIR = "bench_traces"       # under the checkout; listed in .gitignore


class Refused(RuntimeError):
    """The run cannot measure here: no result is printed."""


def peaks_for(kind: str, path: str = PEAKS) -> dict:
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise Refused(f"device kind {kind!r} is not in {path}; add its "
                      "published peaks there")
    return table[kind]


def check_devices(chips: int, require_tpu: bool = True) -> list:
    """The attached devices, refused unless they are TPUs and there are at
    least ``chips`` of them. There is no CPU path."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's devices are {devs[0].platform!r}; the "
                      "benchmark measures the chip only")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, {len(devs)} attached")
    return devs


def memory_peak_bytes(devs) -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


class Ctx:
    """What a driver gets: the cell, its configuration and traffic, the
    seed, the window length, the trace switch, the devices, the process's
    start time, host spans and the profiler."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 devices: list, t_start: float, base: str, trace_dir: str):
        self.cell = cell
        self.config = cell["config_data"]
        self.traffic = cell["traffic_data"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices[: cell["chips"]]
        self.t_start = t_start
        self.base = base
        self.trace_dir = trace_dir
        self.trace_path: str | None = None
        self.trace_run_dir: str | None = None
        self.reference = loader.reference(self.config["reference"], base)

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def profile(self):
        """Trace the device and the host inside the block; Python function
        tracing stays off, so that host spans are the harness's and JAX's."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        os.makedirs(self.trace_dir, exist_ok=True)
        run_dir = tempfile.mkdtemp(prefix="run-", dir=self.trace_dir)
        jax.profiler.start_trace(run_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
            self.trace_path = trace_mod.find(run_dir)
            self.trace_run_dir = run_dir


class MetricCtx:
    """What a per-layer metric reader gets."""

    def __init__(self, ctx: Ctx, outcome: dict, reduced: dict | None,
                 peaks: dict):
        self.cell = ctx.cell
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.chips = len(ctx.devices)
        self.outcome = outcome
        self.trace = reduced
        self.peaks = peaks


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def execute(name: str, seed: int, seconds: float, trace: bool, *,
            t_start: float | None = None, require_tpu: bool = True,
            base: str = loader.BENCH, root: str = loader.ROOT,
            device_kind: str | None = None,
            trace_device: tuple[str, str] | None = None) -> dict:
    """One run of cell ``name``; returns the result object. Raises
    ``Refused`` where nothing may be printed. ``require_tpu``,
    ``device_kind``, ``trace_device`` (where a trace holds the device's
    operations, when that is not a TPU) and the paths exist for the
    harness's own tests."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = loader.cell(name, base)
    bench = loader.benchmark(root)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise Refused(f"no src/repro under {root}: the program under test "
                      "is not in this checkout")
    if os.path.join(root, "src") not in sys.path:
        sys.path.insert(0, os.path.join(root, "src"))
    devs = check_devices(cell["chips"], require_tpu)
    kind = device_kind or devs[0].device_kind
    peaks = peaks_for(kind)
    ctx = Ctx(cell, seed, seconds, trace, devs, t_start, base,
              os.path.join(root, TRACE_DIR))
    driver = loader.driver(ctx.config["driver"], base)
    out = driver.run(ctx)

    checks = [(c, v, cell["limits"].get(c)) for c, v in out["checks"]]
    correct = bool(checks) and all(
        lim is not None and _finite(v) and v <= lim for _, v, lim in checks)

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics: dict = {}
    breakdown: dict = {}
    if trace:
        reduced = trace_mod.reduce(
            trace_mod.load(ctx.trace_path, *(trace_device or ())), out["unit"])
        shutil.rmtree(ctx.trace_run_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        mctx = MetricCtx(ctx, out, reduced, peaks)
        for m in loader.per_layer_for(bench, name):
            v = loader.metric(m["name"], base).read(mctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = {"breakdown": {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}}
    else:
        for m in loader.end_to_end_for(bench, name):
            v = out["setup_s"] if m["name"] == "setup_s" else out["e2e"].get(m["name"])
            if v is None:
                raise RuntimeError(f"driver {ctx.config['driver']!r} measured no "
                                   f"{m['name']!r} for {name}")
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    head = {"correct": correct, "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics, "device": device}
    return {**head, **breakdown, "checks": {
        c: {"value": v, "limit": lim} for c, v, lim in checks}}


def main(argv=None, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: a traced run that reports the per-layer metrics")
    args = ap.parse_args(argv)
    try:
        res = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start)
    except (Refused, loader.NotFound) as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        return 2
    except Exception:
        traceback.print_exc()
        print("bench: the run failed; no result", file=sys.stderr, flush=True)
        return 1
    print(f"correct = {res['correct']}; the numbers compared:", file=sys.stderr)
    for c, v in res["checks"].items():
        print(f"check {c} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0

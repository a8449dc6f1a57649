"""Finds every piece of the benchmark by name, as files under ``bench/``:

  configs/<config>.json      a configuration: its sizes, its driver, its reference
  traffic/<mix>.json         a traffic mix: the parameters one driver reads
  cells/<cell>.json          a cell: config, traffic, chips and the check limits
  metrics/<metric>.py        the reader of one per-layer metric: ``read(ctx)``
  drivers/<driver>.py        the loop a cell's window runs, chosen by the config
  references/<name>.py       the plain reference a config names

Adding a config, a mix, a cell or a metric is adding a file; no code here
or in ``run.py`` changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from types import ModuleType

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class NotFound(LookupError):
    """No file of that kind holds that name."""


def _path(kind: str, name: str, ext: str, base: str = BENCH) -> str:
    if not NAME.match(name):
        raise NotFound(f"{kind[:-1] if kind.endswith('s') else kind} name "
                       f"{name!r} is not a valid name")
    path = os.path.join(base, kind, name + ext)
    if not os.path.isfile(path):
        raise NotFound(f"no {kind}/{name}{ext} under {base}")
    return path


def _json(kind: str, name: str, base: str = BENCH) -> dict:
    with open(_path(kind, name, ".json", base)) as f:
        return json.load(f)


def config(name: str, base: str = BENCH) -> dict:
    return _json("configs", name, base)


def traffic(name: str, base: str = BENCH) -> dict:
    return _json("traffic", name, base)


def cell(name: str, base: str = BENCH) -> dict:
    """The cell with its config and traffic loaded: ``{"name", "config",
    "traffic", "chips", "limits", "config_data", "traffic_data"}``."""
    c = _json("cells", name, base)
    c["name"] = name
    c["config_data"] = config(c["config"], base)
    c["traffic_data"] = traffic(c["traffic"], base)
    c.setdefault("limits", {})
    return c


def names(kind: str, base: str = BENCH) -> list[str]:
    """Every name of one kind (``configs``, ``traffic``, ``cells``,
    ``metrics``) found under ``base``."""
    ext = ".py" if kind == "metrics" else ".json"
    d = os.path.join(base, kind)
    return sorted(f[: -len(ext)] for f in os.listdir(d)
                  if f.endswith(ext) and NAME.match(f[: -len(ext)]))


def _module(path: str, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric(name: str, base: str = BENCH) -> ModuleType:
    """The reader of one per-layer metric; it has ``read(ctx) -> float | None``."""
    mod = _module(_path("metrics", name, ".py", base),
                  "bench_metric_" + re.sub(r"\W", "_", name))
    if not callable(getattr(mod, "read", None)):
        raise NotFound(f"metrics/{name}.py has no read(ctx)")
    return mod


def driver(name: str, base: str = BENCH) -> ModuleType:
    return _module(_path("drivers", name, ".py", base), "bench_driver_" + name)


def reference(name: str, base: str = BENCH) -> ModuleType:
    return _module(_path("references", name, ".py", base), "bench_ref_" + name)


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def end_to_end_for(bench: dict, cell_name: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer_for(bench: dict, cell_name: str) -> list[dict]:
    """Per-layer metrics this cell reports: those that list it, and those
    with no list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell_name)}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]

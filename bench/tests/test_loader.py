"""The loader finds every piece by name, and a piece added as a file is
picked up with no edit to code."""
import json
import os
import shutil

from bench import harness, loader
from bench.tests.conftest import ROOT


def test_finds_every_config_mix_cell_and_metric():
    bench = loader.benchmark(ROOT)
    for c in bench["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = loader.config(c["name"])
        loader.driver(cfg["driver"])
        loader.reference(cfg["reference"])
    for w in bench["workloads"]:
        cell = loader.cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert cell["limits"], f"{w['name']} has no check limits"
    for m in bench["per_layer"]:
        assert callable(loader.metric(m["name"]).read)
    for kind in ("configs", "traffic", "cells", "metrics"):
        for name in loader.names(kind):
            {"configs": loader.config, "traffic": loader.traffic,
             "cells": loader.cell, "metrics": loader.metric}[kind](name)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    bench = loader.benchmark(ROOT)
    for w in bench["workloads"]:
        e2e = {m["name"] for m in loader.end_to_end_for(bench, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert loader.per_layer_for(bench, w["name"])


def test_unknown_names_are_not_found():
    for fn in (loader.config, loader.traffic, loader.cell, loader.metric):
        try:
            fn("no-such-piece")
        except loader.NotFound:
            continue
        raise AssertionError(f"{fn.__name__} found no-such-piece")
    try:
        loader.cell("../cells/x")
    except loader.NotFound:
        pass
    else:
        raise AssertionError("a path is not a name")


def test_pieces_dropped_into_a_copy_are_picked_up(tmp_path):
    """A new config, mix, cell and per-layer metric, added as files to a
    copy, run through the harness with no edit to any code."""
    from bench.tests.conftest import make_tiny_copy, write_best_known

    root = make_tiny_copy(str(tmp_path))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "metrics", "solve.rounds_per_solve.py"), "w") as f:
        f.write('"""Solves traced, per solve: a reader added as a file."""\n\n\n'
                "def read(ctx):\n"
                "    return float(ctx.trace['units']) if ctx.trace else None\n")
    with open(os.path.join(b, "traffic", "two.json"), "w") as f:
        json.dump({"kind": "solve", "request_pool": 3,
                   "pool_seed": 5, "warmup_solves": 1, "trace_solves": 2}, f)
    shutil.copy(os.path.join(b, "cells", "solve.homo-n8.full.json"),
                os.path.join(b, "cells", "solve.homo-n8.two.json"))
    with open(os.path.join(b, "cells", "solve.homo-n8.two.json")) as f:
        cell = json.load(f)
    cell["traffic"] = "two"
    del cell["best_known_r_asym"]
    with open(os.path.join(b, "cells", "solve.homo-n8.two.json"), "w") as f:
        json.dump(cell, f)
    write_best_known(root, "solve.homo-n8.two")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "solve.homo-n8.two", "config": "homo-n8",
                               "traffic": "two", "chips": 1, "why": "added"})
    bench["per_layer"].append({"name": "solve.rounds_per_solve", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "topology pipeline", "moves": "solve_s",
                               "workloads": ["solve.homo-n8.two"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "solve.homo-n8.full" in m["workloads"]:
            m["workloads"].append("solve.homo-n8.two")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    assert "solve.homo-n8.two" in loader.names("cells", b)
    res = harness.execute("solve.homo-n8.two", 2**40 + 7, 0.5, True,
                          require_tpu=False, device_kind="TPU v5 lite",
                          base=b, root=root, trace_device=("/host:CPU", "tf_XLA"))
    assert res["correct"], res["checks"]
    assert res["metrics"]["solve.rounds_per_solve"]["value"] == 2.0
    assert list(res)[-1] == "checks"

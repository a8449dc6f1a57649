"""Fixtures of the harness's own tests: a copy of ``bench/`` in a temporary
directory, with tiny configurations, mixes and cells added as files, beside
the program's ``src/``. The tests run on the CPU; the harness's look for a
chip is skipped there through ``harness.execute(require_tpu=False)``, never
in the command."""
import json
import os
import shutil

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: tiny cells and the limits their checks are held to; the numbers are the
#: tiny model's, read on the CPU: sound runs read loss 3e-5, gradient 4e-3
#: and change 2e-4; the fp8 control reads 3e-4 and 4e-2
TINY_LIMITS = {"loss_gap": 1.5e-4, "grad_gap": 0.015, "change_gap": 0.01}
with open(os.path.join(ROOT, "bench", "cells", "solve.homo-n64.full.json")) as _f:
    SOLVE_LIMITS = json.load(_f)["limits"]


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def write_best_known(root: str, name: str) -> None:
    """Add ``best_known_r_asym`` to solve cell ``name`` of the copy at
    ``root``, read from the sound program as for a cell on the chip."""
    from bench import calibrate, loader

    b = os.path.join(root, "bench")
    path = os.path.join(b, "cells", name + ".json")
    with open(path) as f:
        cell = json.load(f)
    cell["best_known_r_asym"] = calibrate.best_known(loader.cell(name, b), root)
    _write(path, cell)


def make_tiny_copy(dst: str) -> str:
    """``dst`` with ``bench/``, ``BENCHMARK.json`` and a link to ``src/``,
    and the tiny pieces added as files."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dst, "src"))
    b = os.path.join(dst, "bench")
    with open(os.path.join(b, "configs", "smollm-135m.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=256)
    _write(os.path.join(b, "configs", "tiny-llama.json"), cfg)
    _write(os.path.join(b, "configs", "homo-n8.json"),
           {"driver": "solve", "reference": "topology_f64", "n": 8, "r": 10,
            "scenario": "homo", "restarts": 1})
    for name, layout in (("tiny4", "stacked"), ("tinysh4", "sharded")):
        _write(os.path.join(b, "traffic", name + ".json"),
               {"kind": "train", "layout": layout, "workers": 4,
                "batch_per_worker": 1, "seq_len": 64, "pool_batches": 4,
                "topology": {"kind": "ba", "r": 4}, "check_rounds": 3,
                "trace_rounds": 3})
    cells = {"train.tiny.stacked4": ("tiny-llama", "tiny4", 1, TINY_LIMITS),
             "train.tiny.sharded4": ("tiny-llama", "tinysh4", 4, TINY_LIMITS),
             "solve.homo-n8.full": ("homo-n8", "full", 1, SOLVE_LIMITS)}
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, (config, traffic, chips, limits) in cells.items():
        _write(os.path.join(b, "cells", name + ".json"),
               {"config": config, "traffic": traffic, "chips": chips,
                "limits": limits})
        if config == "homo-n8":
            write_best_known(dst, name)
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "tiny test cell"})
        kind = name.split(".")[0]
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m and any(w.startswith(kind + ".")
                                        for w in m["workloads"]):
                m["workloads"].append(name)
    _write(os.path.join(dst, "BENCHMARK.json"), bench)
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_copy(str(tmp_path_factory.mktemp("tiny")))

"""Per-layer metric readers that read the driver's outcome."""
from types import SimpleNamespace

from bench import loader


def _ctx(phases):
    return SimpleNamespace(outcome={} if phases is None else {"phases": phases})


def test_host_phase_seconds_sum_prep_round_eval_per_solve():
    read = loader.metric("solve.host_phase_s").read
    phases = [{"prep": 0.001, "warm": 0.9, "admm": 3.4, "round": 0.01,
               "polish": 0.8, "eval": 0.009},
              {"prep": 0.002, "round": 0.012, "eval": 0.006}]
    assert abs(read(_ctx(phases)) - 0.02) < 1e-15


def test_host_phase_seconds_absent_read_none():
    read = loader.metric("solve.host_phase_s").read
    assert read(_ctx(None)) is None
    assert read(_ctx([])) is None
    assert read(_ctx([{"admm": 3.4, "warm": 0.9}])) is None

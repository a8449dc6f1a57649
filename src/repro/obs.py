"""Program spans and counters, on the profiler's clock.

One mechanism times the program's own work:

- :class:`PhaseProfile` — host seconds per named phase, plus integer
  counters, for one solve or one training round. ``phase(name, **ids)``
  times a block on the host clock and opens a
  ``jax.profiler.TraceAnnotation`` named ``repro.<area>.<name>`` over it,
  so that a traced run shows the phase on the same clock as the device's
  operations. With no profiler running the annotation does nothing.
- :func:`span` — one ``repro.<name>`` host span with no seconds kept (a
  whole request, so that its phases share the request's identifiers).
- :func:`scope` — ``jax.named_scope("repro.<name>")`` for device code. The
  compiled operations inside carry the name in their ``op_name`` metadata
  (through ``vmap``, ``value_and_grad``, ``scan`` and ``checkpoint``), so
  that a trace's device time can be attributed by layer. A scope changes
  metadata only, never the computation. Call sites reach it as
  ``obs.scope(...)``, through the module.

Names in use: areas ``solve`` (``prep``, ``classic``, ``warm``, ``admm``,
``round``, ``polish``, ``eval``) and ``round`` (``plan``, ``dispatch``,
``sync``, ``reopt``); counters ``admm_iters`` and ``cg_iters``; scopes
``attention``, ``mlp``, ``logits``, ``optimizer`` and ``gossip``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

import jax

__all__ = ["PhaseProfile", "span", "scope"]


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` carrying ``ids`` as its arguments."""
    return jax.profiler.TraceAnnotation(f"repro.{name}", **ids)


def scope(name: str):
    """A named scope ``repro.<name>`` for the operations traced inside."""
    return jax.named_scope(f"repro.{name}")


class _Phase:
    """One timed block of a :class:`PhaseProfile`. ``seconds`` holds the
    block's host seconds once it has ended; they are added to the profile
    only when the block ends without an exception."""

    __slots__ = ("_profile", "_name", "_ann", "_t0", "seconds")

    def __init__(self, profile: "PhaseProfile", name: str, ids: dict):
        self._profile = profile
        self._name = name
        self._ann = span(f"{profile.area}.{name}", **ids)
        self.seconds = 0.0

    def __enter__(self) -> "_Phase":
        self._ann.__enter__()
        self._t0 = self._profile.clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = self._profile.clock() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        if exc_type is None:
            self._profile.add(self._name, self.seconds)
        return False


@dataclass
class PhaseProfile:
    """Per-phase wall time (phase name → SECONDS) and counters of one solve
    or one round.

    Solve phases: ``prep`` (validation + scenario resolution), ``warm``
    (greedy init + SA), ``admm``, ``round`` (support extraction + repair),
    ``polish``, ``eval`` (invariants + spectral), ``classic`` (fallback
    construction); ``queue``/``solve`` are the service's. Round phases:
    ``plan``, ``dispatch``, ``sync``, ``reopt``. ``area`` names the spans
    (``repro.<area>.<phase>``); ``clock`` is the host clock the phases are
    timed on.
    """

    phases: dict[str, float] = field(default_factory=dict)
    area: str = "solve"
    counts: dict[str, int] = field(default_factory=dict)
    clock: Callable[[], float] = field(default=time.perf_counter,
                                       repr=False, compare=False)

    def phase(self, name: str, **ids) -> _Phase:
        """Time the block as phase ``name`` under a ``repro.<area>.<name>``
        span carrying ``ids``; ``as`` gives its seconds once it ends."""
        return _Phase(self, name, ids)

    def add(self, phase: str, seconds: float) -> None:
        self.phases[phase] = self.phases.get(phase, 0.0) + float(seconds)

    def count(self, name: str, k: int) -> None:
        self.counts[name] = self.counts.get(name, 0) + int(k)

    def merge(self, other: "PhaseProfile | dict") -> "PhaseProfile":
        """New profile with the phase times and the counters of both
        operands summed (a legacy dict carries phases only)."""
        out = PhaseProfile(dict(self.phases), self.area, dict(self.counts))
        src = other if isinstance(other, PhaseProfile) else \
            PhaseProfile.from_dict(other)
        for k, v in src.phases.items():
            out.add(k, v)
        for k, v in src.counts.items():
            out.count(k, v)
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "PhaseProfile":
        """Parse a legacy profile dict: ``<phase>_s`` values are seconds,
        ``<phase>_ms`` milliseconds, bare numeric keys seconds."""
        out = cls()
        for k, v in d.items():
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                continue
            if k.endswith("_ms"):
                out.add(k[:-3], v / 1e3)
            elif k.endswith("_s"):
                out.add(k[:-2], v)
            else:
                out.add(k, v)
        return out

    def to_dict(self) -> dict:
        """Legacy ``<phase>_s`` dict view (seconds), for consumers of the
        pre-§17 profile plumbing."""
        return {f"{k}_s": v for k, v in self.phases.items()}

    def add_to(self, d: dict) -> dict:
        """Add this profile's ``<phase>_s`` seconds into the legacy dict
        ``d`` (summing where a key is there already); returns ``d``."""
        for k, v in self.to_dict().items():
            d[k] = d.get(k, 0.0) + v
        return d

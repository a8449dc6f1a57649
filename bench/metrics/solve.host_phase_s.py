"""Host seconds per solve outside the device's programs: the program's
``PhaseProfile`` phases ``prep`` (validation, scenario), ``round`` (support
extraction and repair) and ``eval`` (invariants and the spectral check),
summed per solve and averaged over the traced window's solves. Each phase is
also a ``repro.solve.<phase>`` span over the same interval."""

HOST_PHASES = ("prep", "round", "eval")


def read(ctx):
    per_solve = [sum(p[k] for k in HOST_PHASES if k in p)
                 for p in ctx.outcome.get("phases", [])
                 if any(k in p for k in HOST_PHASES)]
    return sum(per_solve) / len(per_solve) if per_solve else None

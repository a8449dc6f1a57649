"""ADMM seconds per solve: the program's ``PhaseProfile`` phase ``admm``
(host clock around the solver call, which ends in a host read of the
solution), averaged over the traced window's solves."""


def read(ctx):
    phases = [p.get("admm") for p in ctx.outcome.get("phases", [])]
    phases = [p for p in phases if p is not None]
    return sum(phases) / len(phases) if phases else None

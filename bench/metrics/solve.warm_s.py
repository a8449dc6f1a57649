"""SA warm-start seconds per solve: the program's ``PhaseProfile`` phase
``warm`` (greedy initial graph and simulated annealing), averaged over the
traced window's solves."""


def read(ctx):
    phases = [p.get("warm") for p in ctx.outcome.get("phases", [])]
    phases = [p for p in phases if p is not None]
    return sum(phases) / len(phases) if phases else None

"""host_prep_share: the share of each call's wall time spent outside the
engine's device loop (``History.wall_seconds``): the host work that
``engine.run`` does before its loop (schedule, eval subset, ledger) and
the return of results, over the untraced window's calls."""


def read(ctx):
    total = sum(c for c, _ in ctx["calls"])
    loop = sum(min(w, c) for c, w in ctx["calls"])
    return 100.0 * (total - loop) / total

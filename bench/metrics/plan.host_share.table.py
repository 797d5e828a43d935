"""Percent of the window inside the program's ``plan.optimize`` and
``plan.jit`` spans: the planner's whole host cost per query (rewrite and
lower, then trace, compile or cache load and enqueue), on the host
clock."""

SPANS = ("plan.optimize", "plan.jit")


def read(run):
    spans = [(s, e) for name, s, e in run.spans if name in SPANS]
    return run.share_of_window(spans) if spans else None

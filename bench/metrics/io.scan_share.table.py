"""Percent of the window inside the program's scan spans
(``io.scan.read`` and ``io.scan.materialize``), on the host clock."""

SPANS = ("io.scan.read", "io.scan.materialize")


def read(run):
    spans = [(s, e) for name, s, e in run.spans if name in SPANS]
    return run.share_of_window(spans) if spans else None

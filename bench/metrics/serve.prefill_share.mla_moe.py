"""Percent of the window inside the engine's ``serve.prefill`` spans: each
``generate`` call from its start to its first token on the host, on the
host clock."""

SPANS = ("serve.prefill",)


def read(run):
    spans = [(s, e) for name, s, e in run.spans if name in SPANS]
    return run.share_of_window(spans) if spans else None

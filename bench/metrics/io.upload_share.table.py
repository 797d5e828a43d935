"""Percent of the window inside the program's ``io.scan.upload`` spans:
placing the scanned columns on the device until they are there, on the
host clock."""

SPANS = ("io.scan.upload",)


def read(run):
    spans = [(s, e) for name, s, e in run.spans if name in SPANS]
    return run.share_of_window(spans) if spans else None

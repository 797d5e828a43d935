"""Percent of the traced window in which no operation ran on the device
(1 - union of the device-op intervals / window), from the profiler trace."""


def read(run):
    return run.idle_share()

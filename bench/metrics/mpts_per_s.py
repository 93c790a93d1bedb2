"""Millions of interior grid points advanced one model step per second of
the window: points x steps completed / window seconds / 1e6."""


def read(rec):
    return rec.counters["points"] * rec.counters["steps"] / rec.window_s / 1e6

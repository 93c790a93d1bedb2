"""Host milliseconds in the program's ``ensemble.to_host`` spans (device to
host copies) that start in the window, per answered request."""

from bench import spans


def read(rec):
    return spans.ms_per_request(rec, ["ensemble.to_host"])

"""Host milliseconds in the program's ``ensemble.wait`` spans (the host
waiting for a gathered member's device result) that start in the window,
per answered request."""

from bench import spans


def read(rec):
    return spans.ms_per_request(rec, ["ensemble.wait"])

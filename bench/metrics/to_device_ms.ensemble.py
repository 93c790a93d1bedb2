"""Host milliseconds in the program's ``ensemble.stack`` and
``ensemble.to_device`` spans (numpy stacking, then the put of the batched
storage) that start in the window, per answered request."""

from bench import spans


def read(rec):
    return spans.ms_per_request(rec, ["ensemble.stack", "ensemble.to_device"])

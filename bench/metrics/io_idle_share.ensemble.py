"""% of the first device's idle time in the window during which some host
thread was inside the program's ``ensemble.stack``, ``ensemble.to_device``
or ``ensemble.to_host`` spans."""

from bench import spans


def read(rec):
    return spans.idle_share_under(rec, ["ensemble.stack", "ensemble.to_device", "ensemble.to_host"])

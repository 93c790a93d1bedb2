"""Share of the window in which the first device ran no operation, in %."""

from bench import roofline


def read(rec):
    return roofline.idle_share(rec)

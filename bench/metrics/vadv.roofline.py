"""vadv's share of its HBM roofline: the algorithm's bytes of one call over
the HBM peak, divided by the call's device time inside ``bench.vadv``."""

from bench import roofline


def read(rec):
    return roofline.share(rec, "vadv")

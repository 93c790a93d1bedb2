"""The whole model step's share of the chip's peak, in %: the algorithmic
bytes of the step's 22 calls over the HBM peak, divided by the step's time
in the window.  HBM is the bound of every call here (section 3 of PERF.md),
so this is the step's roofline share: at most the best kernel's, and still
read when a kernel leaves the path."""

from bench import roofline


def read(rec):
    return roofline.step_share(rec)

"""% of each chip's busy time in the window spent in collective operations
(the halo exchanges' permutes, with their start and done halves), mean over
the run's chips."""

from bench import collectives


def read(rec):
    chips = collectives.collective_chips(rec)
    return 100.0 * sum(c / b for _, b, c in chips) / len(chips) if chips else None

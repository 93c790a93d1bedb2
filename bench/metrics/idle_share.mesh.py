"""Share of the window in which a chip ran no operation, in %, mean over
the run's chips."""

from bench import collectives


def read(rec):
    chips = collectives.per_chip(rec)
    return 100.0 * sum(1.0 - b / w for w, b, _ in chips) / len(chips) if chips else None

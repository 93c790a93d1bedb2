"""GB/s a chip ships in its collectives: the exchange plan's bytes a chip
sends per step (the program's report) times the window's steps, over that
chip's collective time in the window, mean over the run's chips."""

from bench import collectives


def read(rec):
    chips = [c for _, _, c in collectives.collective_chips(rec)]
    sent = rec.counters.get("exchange_bytes_per_step", 0) * rec.counters.get("steps", 0)
    if not sent or not chips or not all(chips):
        return None
    return sum(sent / c for c in chips) / len(chips)  # bytes per ns is GB/s

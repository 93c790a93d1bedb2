"""Live members over padded members of every batch dispatched in the
window, from the engine's counters, in %."""


def read(rec):
    padded = rec.counters["padded_members"]
    return 100.0 * rec.counters["live_members"] / padded if padded else None

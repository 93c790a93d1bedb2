"""The whole step's share of a chip's HBM peak, in %: the algorithmic bytes
of one step on a chip's tile for its members (``bench/work/forecast.py``),
over the HBM peak, divided by the step's time in the window.  The program
has no kernel of its own, so this is the cell's roofline share."""

from bench import harness
from bench.work import forecast


def read(rec):
    c = rec.counters
    if "tile" not in c:
        return None
    nbytes = c["members_per_chip"] * forecast.bytes_moved(c["tile"], c["itemsize"])
    floor_s = nbytes / harness.peaks(rec.device_kind)["hbm_bytes_per_s"]
    return 100.0 * floor_s / (rec.window_s / c["steps"])

"""Requests answered per second: every request sent in the window, over the
time from the first send until the last of them is answered."""


def read(rec):
    return rec.counters["completed"] / rec.window_s

"""Host milliseconds per completed request in the program's
``serving.scatter`` and ``serving.gather`` spans within the window."""


def read(rec):
    io, done = rec.counters.get("host_io_s"), rec.counters.get("completed")
    return io / done * 1e3 if io is not None and done else None

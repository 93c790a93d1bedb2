"""Set-up seconds: process start to the first timed operation, compilation included."""


def read(rec):
    return rec.setup_s

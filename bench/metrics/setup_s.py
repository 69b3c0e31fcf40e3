"""Set-up: process start to the measured window (host clock)."""


def read(rec):
    return rec["setup_s"]

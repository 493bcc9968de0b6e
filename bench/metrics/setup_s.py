"""Set-up: process start to the first measured update (host clock)."""


def read(rec: dict):
    return rec["setup_s"]

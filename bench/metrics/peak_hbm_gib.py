"""``memory_stats()["peak_bytes_in_use"]`` after the window, in GiB."""


def read(rec: dict):
    if not rec["memory_peak_bytes"]:
        return None
    return rec["memory_peak_bytes"] / 2 ** 30

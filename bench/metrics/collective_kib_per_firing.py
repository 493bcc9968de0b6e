"""Bytes a row-sharded firing moves between chips, on average, in KiB:
the change of ``EngineStats.collective_bytes`` (the collective operand
bytes of each committed firing's compiled program) over the change of
``triggers_fired`` in the window."""


def read(rec: dict):
    b, a = rec["counters"]["before"], rec["counters"]["after"]
    if "collective_bytes" not in a:
        return None
    fired = a["triggers_fired"] - b["triggers_fired"]
    if fired <= 0:
        return None
    return (a["collective_bytes"] - b["collective_bytes"]) / fired / 1024

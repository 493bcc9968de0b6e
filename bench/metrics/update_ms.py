"""The paper's per-update refresh time: the whole window over the
updates fully applied in it (views ready on the device)."""


def read(rec: dict):
    w = rec["window"]
    return 1000.0 * w["window_s"] / w["updates"] if w.get("updates") else None

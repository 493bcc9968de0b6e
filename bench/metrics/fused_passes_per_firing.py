"""Views applied in one pass with their factor products, per firing: the
change of ``EngineStats.fused_view_passes`` over the change of
``triggers_fired`` in the window.  Each such view is read from HBM once
instead of once for its apply and once for each product."""


def read(rec: dict):
    b, a = rec["counters"]["before"], rec["counters"]["after"]
    if "fused_view_passes" not in a:
        return None
    fired = a["triggers_fired"] - b["triggers_fired"]
    if fired <= 0:
        return None
    return (a["fused_view_passes"] - b["fused_view_passes"]) / fired

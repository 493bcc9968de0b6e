"""Trigger programs the engine built inside the window: the change of
``EngineStats.trigger_builds``.  Beside ``info.compiles_in_window``, it
says whether a compile there came from an engine step."""


def read(rec: dict):
    b, a = rec["counters"]["before"], rec["counters"]["after"]
    if "trigger_builds" not in a:
        return None
    return a["trigger_builds"] - b["trigger_builds"]

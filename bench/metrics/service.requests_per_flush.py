"""Requests per coalesced flush over the window (``SchedulerStats``)."""


def read(ctx):
    st = ctx["window"].get("scheduler")
    if not st or not st["flushes"]:
        return None
    return st["requests"] / st["flushes"]

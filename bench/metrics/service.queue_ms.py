"""Median wait of a request in the scheduler's queue: the end of its
``scheduler.submit`` span to the start of the ``scheduler.flush`` that served
it, matched by trace id (the flush carries its first request's id and the
others' in ``coalesced_trace_ids``)."""

import statistics


def read(ctx):
    submit = {s["trace_id"]: s["t_end"] for s in ctx["spans"]
              if s["name"] == "scheduler.submit"}
    waits = []
    for s in ctx["spans"]:
        if s["name"] != "scheduler.flush":
            continue
        for tid in (s["trace_id"], *s["attrs"].get("coalesced_trace_ids", ())):
            if tid in submit:
                waits.append(1e3 * (s["t_start"] - submit[tid]))
    return statistics.median(waits) if waits else None

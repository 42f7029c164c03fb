"""Wall time of the ``construction.round`` spans in the window, per request
sent (host clock)."""


def read(ctx):
    reqs = ctx["window"].get("requests")
    if not reqs:
        return None
    wall = sum(s["wall_s"] for s in ctx["spans"]
               if s["name"] == "construction.round")
    return 1e3 * wall / len(reqs)

"""XLA compilations plus persistent-cache loads inside the window (JAX's
monitoring events), per request sent."""


def read(ctx):
    reqs = ctx["window"].get("requests")
    if not reqs:
        return None
    ev = ctx["compile_events"]
    return (ev["compiles"] + ev["cache_loads"]) / len(reqs)

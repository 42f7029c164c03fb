"""Device time of the programs that ran in the window (the trace's
``XLA Modules``; in a corpus job they are the scan executors), per million
residues scanned."""


def read(ctx):
    tr, w = ctx["trace"], ctx["window"]
    if tr is None or not w.get("residues") or not tr["programs"]:
        return None
    return 1e3 * sum(tr["programs"].values()) / (w["residues"] / 1e6)

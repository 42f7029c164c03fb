"""Share of the traced window in which the device was idle and no host span
was open at the gap's middle: the ``no span`` entry of the trace's idle-gap
breakdown over the window (0 where the breakdown lists none)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["window_s"]:
        return None
    gaps = dict(tr["breakdown"]["idle_gaps"])
    return 100.0 * gaps.get("no span", 0.0) / tr["window_s"]

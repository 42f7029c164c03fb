"""Residues of every shard that finished in the window, over the window
(host clock; the window closes at the end of the last such shard)."""


def read(ctx):
    w = ctx["window"]
    if "shards" not in w or not w["shards"]:
        return None
    return w["residues"] / (w["t_end"] - w["t0"])

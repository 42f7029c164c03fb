"""The corpus job's own host work around each scan, per shard finished in
the window: the wall of its ``jobs.checkpoint`` (the shard's npz write and
rename) and ``flight.record`` (the flight trail) spans (host clock). The
``jobs.pending`` checkpoint probes are left out: a job's ``run()`` probes
once, and the benchmark's loop calls it, and probes twice more, per shard."""

from bench.spans import named


def read(ctx):
    shards = ctx["window"].get("shards")
    spans = named(ctx, "jobs.checkpoint", "flight.record")
    if not shards or not spans:
        return None
    return 1e3 * sum(s["wall_s"] for s in spans) / len(shards)

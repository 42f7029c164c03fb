"""The general closed-loop generator: a corpus job's documents, cycle by
cycle, in shards of ``shard_docs``, from a traffic file's parameters.

* ``cycle_shards``: shards per cycle; the window runs cycle after cycle.
* ``order``: ``"classes"`` (the default): every shard holds one length
  class, shards allocated to classes by entry share and interleaved, so
  the pipeline groups its corpus by length; ``"release"``: documents in
  draw order, lengths from ``lengths`` (see
  :func:`bench.data.draw_lengths`), so a shard mixes lengths as a release
  file does.

Lengths and their order come from the mix's ``template_seed`` and the
cycle; ``--seed`` draws the residues (and permutes a release-order cycle).
"""

from __future__ import annotations

import numpy as np

from bench import data


def class_plan(cfg: dict, traffic: dict) -> dict:
    cls = data.length_classes(cfg["proteins"])
    counts = data.allocate(cls["shares"], traffic["cycle_shards"])
    return {"classes": cls, "counts": counts, "order": data.interleave(counts)}


def cycle_lengths(cfg: dict, traffic: dict, shard_docs: int,
                  seed: int, cycle: int) -> np.ndarray:
    """Every document's length in cycle ``cycle``, in shard order."""
    if traffic.get("order", "classes") == "classes":
        plan = class_plan(cfg, traffic)
        return np.repeat([plan["classes"]["lengths"][c] for c in plan["order"]],
                         shard_docs)
    tpl = np.random.default_rng([traffic["template_seed"], cycle])
    n = traffic["cycle_shards"] * shard_docs
    lengths = data.draw_lengths(cfg["proteins"], tpl, n,
                                traffic.get("lengths", {"mode": "raw"}))
    return lengths[np.random.default_rng([seed, cycle, 1]).permutation(n)]


def docs_of(cfg: dict, lengths, rng: np.random.Generator) -> list:
    """Residues for each length, drawn in bulk by run of equal lengths."""
    probs = data.composition(cfg)
    lengths = np.asarray(lengths)
    cut = np.flatnonzero(np.diff(lengths)) + 1
    out = []
    for run in np.split(lengths, cut):
        if len(run):
            out += data.residues(rng, len(run), int(run[0]), probs)
    return out


def cycle_docs(cfg: dict, traffic: dict, shard_docs: int, seed: int,
               cycle: int) -> list:
    lengths = cycle_lengths(cfg, traffic, shard_docs, seed, cycle)
    return docs_of(cfg, lengths, np.random.default_rng([seed, cycle]))


def warmup_docs(cfg: dict, traffic: dict, shard_docs: int,
                seed: int) -> list:
    """Documents of every shape a cycle holds, on the warm-up ``seed``: one
    shard per length class, or in release order the first cycle's lengths,
    grouped."""
    if traffic.get("order", "classes") == "classes":
        lengths = sorted(set(cycle_lengths(cfg, traffic, shard_docs, seed, 0)
                             .tolist()))
        lengths = np.repeat(lengths, shard_docs)
    else:
        lengths = np.sort(cycle_lengths(cfg, traffic, shard_docs, seed, 0))
    return docs_of(cfg, lengths, np.random.default_rng(seed))

"""The general open-loop generator: due times and contents of one request
stream, from a traffic file's parameters.

* ``rate_per_s``: Poisson arrivals at this rate; with ``bursts``
  (``{"every_s", "length_s", "factor"}``) the rate is ``factor`` times higher
  for the first ``length_s`` of every ``every_s``.
* ``sequences_per_request``: ``[lo, hi]``, uniform.
* ``lengths``: see :func:`bench.data.draw_lengths` (default: length classes).
* ``patterns``: ``{"kind": "bank"}`` every signature of the configuration;
  ``{"kind": "subset", "sizes": [lo, hi], "zipf_s": s}`` that many distinct
  signatures, by Zipf popularity over the bank; ``{"kind": "variant",
  "sources": [ids]}`` one never-seen variant of a source signature
  (:func:`bench.data.variant`).

Sizes, arrivals and signature choices come from the mix's
``template_seed``, so every seed offers the same work; ``--seed`` permutes
them and draws residues and pattern variants.
"""

from __future__ import annotations

import math

import numpy as np

from bench import data


def _arrival_times(units: np.ndarray, traffic: dict) -> np.ndarray:
    """Unit-rate arrival times -> seconds, through the inverse of the
    cumulative intensity (time rescaling)."""
    rate = traffic["rate_per_s"]
    b = traffic.get("bursts")
    if not b:
        return units / rate
    horizon = units[-1] / rate + b["every_s"]
    t = np.arange(0.0, horizon, 1e-3)
    inside = (t % b["every_s"]) < b["length_s"]
    lam = np.concatenate([[0.0], np.cumsum(
        np.where(inside, rate * b["factor"], rate)[:-1] * 1e-3)])
    return np.interp(units, lam, t)


def _expected_count(traffic: dict, seconds: float) -> float:
    b = traffic.get("bursts")
    boost = 1.0 + (b["factor"] - 1.0) * b["length_s"] / b["every_s"] if b \
        else 1.0
    return traffic["rate_per_s"] * boost * seconds


def requests(cfg: dict, traffic: dict, seed: int, seconds: float,
             seen: set) -> list:
    """-> [{"due", "docs", "patterns", "reference"}] in due order, enough
    for ``seconds``. ``seen`` holds every pattern met so far in the run."""
    n = int(math.ceil(_expected_count(traffic, seconds) * 1.5)) + 8
    tpl = np.random.default_rng(traffic["template_seed"])
    gaps = tpl.exponential(1.0, size=n)
    lo, hi = traffic["sequences_per_request"]
    n_seqs = tpl.integers(lo, hi + 1, size=n)
    lengths = np.split(
        data.draw_lengths(cfg["proteins"], tpl, int(n_seqs.sum()),
                          traffic.get("lengths")),
        np.cumsum(n_seqs)[:-1])
    pats = traffic.get("patterns", {"kind": "bank"})
    bank = cfg["bank"]
    ids = list(bank)
    if pats["kind"] == "subset":
        popularity = tpl.permutation(len(ids))
        weight = 1.0 / (1.0 + np.argsort(popularity)) ** pats["zipf_s"]
        weight /= weight.sum()
        k = tpl.integers(pats["sizes"][0], pats["sizes"][1] + 1, size=n)
        subsets = [tpl.choice(len(ids), size=int(j), replace=False, p=weight)
                   for j in k]
    elif pats["kind"] == "variant":
        sources = [bank[x] for x in pats["sources"]]
        kinds = tpl.choice(["spacer", "residue"], size=n)
        first = tpl.integers(len(sources), size=n)
    elif pats["kind"] != "bank":
        raise ValueError(f"unknown pattern kind {pats['kind']!r}")

    rng = np.random.default_rng(seed)
    units = np.cumsum(gaps[rng.permutation(n)])
    due = _arrival_times(units - units[0], traffic)
    order = rng.permutation(n)
    probs = data.composition(cfg)
    out = []
    for j, i in enumerate(order):
        docs = []
        for length in lengths[i]:
            docs += data.residues(rng, 1, int(length), probs)
        if pats["kind"] == "variant":
            p = [fresh_variant(sources, int(first[i]), kinds[i], rng, seen)]
        elif pats["kind"] == "subset":
            p = [bank[ids[x]] for x in sorted(subsets[i])]
        else:
            p = list(bank.values())
        out.append({"due": float(due[j]), "docs": docs, "patterns": p,
                    "reference": p})
    return out


def fresh_variant(sources: list, first: int, kind: str, rng,
                  seen: set) -> str:
    """A variant never seen in this run: of ``sources[first]`` when it still
    has one, else of the next source that does."""
    other = "residue" if kind == "spacer" else "spacer"
    for j in range(len(sources)):
        source = sources[(first + j) % len(sources)]
        for attempt in range(32):
            v = data.variant(source, other if attempt % 2 else kind, rng)
            if v is not None and v not in seen:
                seen.add(v)
                return v
    raise RuntimeError("every variant source is exhausted")

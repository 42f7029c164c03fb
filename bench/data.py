"""Seeded inputs for every cell: sequence lengths, symbols and user patterns.

Nothing here imports the program. Every size is a function of the
configuration alone, so all seeds share one set of shapes (and one set of
compiled programs); ``--seed`` changes symbols, order and pattern variants.

* Lengths: log-normal (configuration ``sequences``), truncated to ``clip``,
  quantised to length classes with geometric edges. A class's length is the
  truncated distribution's mean inside the class, and its share the
  probability mass there, both integrated on a fixed grid.
* Symbols: i.i.d. from the configuration's ``alphabet`` with its
  ``composition``, drawn in bulk as bytes (no per-character Python).
* Cold patterns: variants of real PROSITE signatures (see :func:`variant`).
"""

from __future__ import annotations

import math
import re

import numpy as np

#: The letters a cold pattern's new residue class draws from: PROSITE
#: signatures are over the 20 amino acids.
VARIANT_AMINO_ACIDS = "ACDEFGHIKLMNPQRSTVWY"


def _grid(seqs: dict):
    mu, sigma = math.log(seqs["median"]), seqs["sigma"]
    xs = np.exp(np.linspace(0.0, math.log(1e6), 1_000_001))
    pdf = np.exp(-(np.log(xs) - mu) ** 2 / (2 * sigma * sigma)) / (
        xs * sigma * math.sqrt(2 * math.pi))
    return xs, np.gradient(xs) * pdf


def length_classes(seqs: dict) -> dict:
    """-> {"lengths", "shares", "removed_entries", "removed_residues",
    "mean"}: class lengths (ints), entry shares per class (sum 1 after the
    clip), and the shares of entries and residues that the clip removes."""
    xs, w = _grid(seqs)
    lo, hi = seqs["clip"]
    edges = seqs["class_edges"]
    keep = (xs >= lo) & (xs <= hi)
    lengths, shares = [], []
    for i, (a, b) in enumerate(zip(edges[:-1], edges[1:])):
        last = i == len(edges) - 2
        m = keep & (xs >= a) & ((xs <= b) if last else (xs < b))
        lengths.append(int(round(float((w[m] * xs[m]).sum() / w[m].sum()))))
        shares.append(float(w[m].sum() / w[keep].sum()))
    return {
        "lengths": lengths,
        "shares": shares,
        "removed_entries": float(1 - w[keep].sum() / w.sum()),
        "removed_residues": float(1 - (w * xs)[keep].sum() / (w * xs).sum()),
        "mean": float((w * xs)[keep].sum() / w[keep].sum()),
    }


def clip_info(seqs: dict) -> str:
    """The info line that says what the clip of ``seqs`` cuts away."""
    cls = length_classes(seqs)
    return (f"reduced: the clip to {seqs['clip']} removes "
            f"{cls['removed_entries']:.6%} of entries and "
            f"{cls['removed_residues']:.6%} of residues; class lengths "
            f"{cls['lengths']}")


def allocate(shares, total: int) -> list:
    """Largest-remainder split of ``total`` items over ``shares``."""
    raw = np.asarray(shares, dtype=np.float64) * total
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[: total - out.sum()]:
        out[i] += 1
    return out.tolist()


def interleave(counts) -> list:
    """Smooth weighted round robin: class ids, each ``counts[c]`` times,
    spread evenly (deterministic)."""
    total = int(sum(counts))
    cur = [0] * len(counts)
    order = []
    for _ in range(total):
        for c, n in enumerate(counts):
            cur[c] += n
        c = max(range(len(counts)), key=lambda j: (cur[j], -j))
        cur[c] -= total
        order.append(c)
    return order


def composition(cfg: dict) -> np.ndarray:
    """Probabilities of the configuration's symbols, in ``alphabet`` order."""
    comp = cfg["sequences"]["composition"]
    p = np.asarray([comp[a] for a in cfg["alphabet"]], dtype=np.float64)
    return p / p.sum()


def residues(rng: np.random.Generator, n_docs: int, length: int,
             probs: np.ndarray, alphabet: str) -> list:
    """``n_docs`` strings of ``length`` symbols of ``alphabet`` (``probs``
    in its order), drawn in bulk."""
    codes = np.frombuffer(alphabet.encode("ascii"), dtype=np.uint8)
    cdf = np.cumsum(probs)
    cdf[-1] = 1.0
    idx = np.searchsorted(cdf, rng.random(n_docs * length), side="right")
    text = codes[idx].tobytes().decode("ascii")
    return [text[i * length:(i + 1) * length] for i in range(n_docs)]


# --------------------------------------------------------------------------
# User patterns: seeded variants of real signatures
# --------------------------------------------------------------------------

_SPACER = re.compile(r"^x(?:\((\d+)\))?$")
_RESIDUE = re.compile(r"^([A-Z])(\(\d+(?:,\d+)?\))?$")


def _split(pattern: str):
    body = pattern
    head = "<" if body.startswith("<") else ""
    body = body[len(head):]
    tail = ">" if body.endswith(">") else ""
    body = body[: len(body) - len(tail)]
    return head, body.split("-"), tail


def variant(pattern: str, kind: str, rng: np.random.Generator):
    """One seeded variant of a PROSITE signature, or None if ``kind`` has no
    site in it. ``kind="spacer"``: one ``x`` / ``x(n)`` moves by +-1 (n >= 1
    afterwards). ``kind="residue"``: one literal residue becomes a sorted
    class of itself and 1-4 other residues. Anchors are kept."""
    head, elems, tail = _split(pattern)
    if kind == "spacer":
        sites = [i for i, e in enumerate(elems) if _SPACER.match(e)]
        if not sites:
            return None
        i = sites[int(rng.integers(len(sites)))]
        n = int(_SPACER.match(elems[i]).group(1) or 1)
        n = n + 1 if n == 1 else n + int(rng.choice((-1, 1)))
        elems[i] = "x" if n == 1 else f"x({n})"
    elif kind == "residue":
        sites = [i for i, e in enumerate(elems) if _RESIDUE.match(e)]
        if not sites:
            return None
        i = sites[int(rng.integers(len(sites)))]
        aa, rep = _RESIDUE.match(elems[i]).groups()
        others = [a for a in VARIANT_AMINO_ACIDS if a != aa]
        extra = rng.choice(others, size=int(rng.integers(1, 5)), replace=False)
        elems[i] = "[" + "".join(sorted([aa, *extra])) + "]" + (rep or "")
    else:
        raise ValueError(f"unknown variant kind {kind!r}")
    return head + "-".join(elems) + tail


def draw_lengths(seqs: dict, rng: np.random.Generator, n: int,
                 spec: dict | None = None) -> np.ndarray:
    """``n`` lengths as a traffic mix asks for them (``spec``):

    * ``{"mode": "classes"}`` (the default): a length class drawn by entry
      share, at the class's length;
    * ``{"mode": "raw", "range": [lo, hi]}``: the log-normal itself,
      truncated to ``range`` (default: the configuration's clip), unquantised.
    """
    spec = spec or {"mode": "classes"}
    if spec["mode"] == "classes":
        cls = length_classes(seqs)
        pick = rng.choice(len(cls["shares"]), size=n, p=cls["shares"])
        return np.asarray(cls["lengths"])[pick]
    if spec["mode"] != "raw":
        raise ValueError(f"unknown length mode {spec['mode']!r}")
    lo, hi = spec.get("range", seqs["clip"])
    xs, w = _grid(seqs)
    keep = (xs >= lo) & (xs <= hi)
    cdf = np.cumsum(w[keep])
    idx = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    return np.rint(xs[keep][np.minimum(idx, keep.sum() - 1)]).astype(int)

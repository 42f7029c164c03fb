"""The plain reference: PROSITE signatures as Python regular expressions.

Independent of the program: it shares no code, tables or automata with it,
only the PROSITE syntax (https://prosite.expasy.org/scanprosite/scanprosite_doc.html):
``x`` any symbol, ``[..]`` one of, ``{..}`` none of, ``e(n)`` / ``e(n,m)``
repeats, ``<`` / ``>`` anchors at the N / C terminus, hit = some match
anywhere in the sequence (ScanProsite's search semantics). The letters are
whatever the configuration's alphabet holds.

Documents are joined by newlines and each pattern runs once over the whole
text with ``re.finditer``; no element matches a newline, so no match spans
two documents, and a document has a hit iff some match starts in it.
"""

from __future__ import annotations

import re

import numpy as np


def to_regex(pattern: str) -> str:
    body = pattern.strip().rstrip(".")
    head = "^" if body.startswith("<") else ""
    body = body[1:] if head else body
    tail = "$" if body.endswith(">") else ""
    body = body[:-1] if tail else body
    out = []
    for elem in body.split("-"):
        rep = ""
        if elem.endswith(")"):
            elem, _, spec = elem[:-1].partition("(")
            rep = "{" + spec + "}"
        if elem == "x":
            atom = "[^\\n]"
        elif elem.startswith("["):
            atom = elem
        elif elem.startswith("{"):
            atom = "[^\\n" + elem[1:-1] + "]"
        elif len(elem) == 1 and elem.isalpha():
            atom = elem
        else:
            raise ValueError(f"bad element {elem!r} in {pattern!r}")
        out.append(atom + rep)
    return head + "".join(out) + tail


def hits(patterns, docs) -> np.ndarray:
    """(P, D) bool: ``hits[p, d]`` iff signature ``p`` occurs in doc ``d``."""
    docs = list(docs)
    out = np.zeros((len(patterns), len(docs)), dtype=bool)
    if not docs:
        return out
    text = "\n".join(docs)
    starts = np.cumsum([0] + [len(d) + 1 for d in docs[:-1]])
    for p, pat in enumerate(patterns):
        rx = re.compile(to_regex(pat), re.MULTILINE)
        pos = [m.start() for m in rx.finditer(text)]
        if pos:
            out[p, np.searchsorted(starts, pos, side="right") - 1] = True
    return out


def control(cfg: dict, doc: str) -> str:
    """``doc`` as the configuration's control reads it: ``control.fold`` is
    ``[from, to]``, each symbol of ``from`` replaced by the one at its place
    in ``to`` (a lowered precision: several symbols share one code)."""
    src, dst = cfg["control"]["fold"]
    return doc.translate(str.maketrans(src, dst))

"""Reading compiled HLO text in tests: the lines of a ``while`` loop's body
and of every computation it calls."""

import re


def computations(text: str) -> dict:
    """Computation name -> its instruction lines."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line.strip())
    return comps


def loop_body(text: str, marker: str) -> list:
    """Lines of the body of the one ``while`` whose line holds ``marker``
    (a carry shape, a trip count), with the computations it calls."""
    comps = computations(text)
    loops = [ln for c in comps.values() for ln in c
             if " while(" in ln and marker in ln]
    assert len(loops) == 1, (marker, loops)
    seen, todo, lines = set(), [re.search(r"body=%?([\w.\-]+)", loops[0])
                                .group(1)], []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            lines.append(line)
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
    return lines

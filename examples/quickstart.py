"""Quickstart: the paper's pipeline through the Scanner engine.

    PYTHONPATH=src python examples/quickstart.py

One entry point covers every configuration: compile a PROSITE pattern under
an execution plan (``auto`` mode constructs the SFA — Rabin fingerprints +
bulk dedup — when it fits the state budget, and falls back to enumeration
otherwise), then match a protein string in parallel chunks and stream it in
bounded-memory blocks, verifying both against the sequential matcher.
"""

import sys
sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.core import compile_prosite, synthetic_protein
from repro.engine import ChunkPolicy, ScanPlan, Scanner

enable_compile_cache()

# The P-loop NTP-binding motif: [AG]-x(4)-G-K-[ST]
PATTERN = "[AG]-x(4)-G-K-[ST]"
dfa = compile_prosite(PATTERN)
print(f"DFA: {dfa.n_states} states over {dfa.n_symbols} symbols")

scanner = Scanner.compile(
    PATTERN,
    ScanPlan(mode="auto", chunking=ChunkPolicy(n_chunks=16, block_len=4096)),
)
print(scanner.describe())
print(f"auto mode chose: {scanner.pattern_modes[PATTERN]}")

protein = synthetic_protein(100_000, seed=42)
protein = protein[:50_000] + "AGGGGGKT" + protein[50_008:]  # plant a P-loop

par = scanner.accepts(protein)
seq = dfa.accepts(protein)
print(f"parallel match: {par}   sequential match: {seq}")
assert par == seq == True

# The same scan, streamed in 10k-char pieces: memory stays one block wide and
# the running function-monoid prefix carries across calls.
streamed = scanner.stream(
    protein[i: i + 10_000] for i in range(0, len(protein), 10_000)
)
assert streamed.accepts == par
hit = scanner.locate(protein).argmax()
print(f"streamed match: {streamed.accepts} ({streamed.n_symbols} symbols); "
      f"first match ends at position {hit}")
print("OK — chunk-parallel and streamed SFA matching agree with the "
      "sequential DFA.")

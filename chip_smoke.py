#!/usr/bin/env python3
"""Drive the scan service's main path once on a TPU and check it bit for bit.

    python chip_smoke.py              # one chip: the whole main path
    python chip_smoke.py --chips 4    # four chips: the shard_map path only

One chip, in one process, at ScanProsite scale:

1. cold compile of all 23 bundled PROSITE signatures through
   ``Scanner.service(store_dir)`` with the default ``ScanPlan()`` and a
   fresh artifact store, so SFA construction runs on the chip (on a TPU the
   ``auto`` construction stages resolve to the Pallas fingerprint and
   expansion kernels, which must be compiled, not interpreted);
2. a bulk scan of 65,536 seeded ``synthetic_protein`` documents of 368
   residues (24.1 M residues, about 1/8 of UniProtKB/Swiss-Prot);
3. a burst of 16 coalesced requests (3 bundled ids and 1,024 documents
   each, lengths 240 / 368 / 1,024) through ``submit`` → ``flush`` →
   ``Ticket.result``, cold and then warm;
4. exactness, bit for bit, on 2,048 seeded documents of each length: hits
   against ``backend="reference"`` and ``census_sequential``, SFAs built
   with the Pallas stages against the XLA stages (``cache="off"``), and
   ``backend="pallas"`` scans against ``backend="xla"``.

``--chips 4`` builds the bank with ``ConstructionPolicy(distribution=
"shard_map")`` and scans with ``ScanPlan(distribution="shard_map")`` on
meshes over all four devices, and compares SFAs and hits with the local
one-device result computed in the same process.

Timings, compile seconds and peak device memory go to stdout and to
``chiprun_out/chip_smoke/summary*.json``. The last stdout line is
``{"ok": true, "device": {...}}``; any failed check exits non-zero before
it. Without a TPU the script exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

BULK_DOCS, BULK_LEN = 65_536, 368
#: UniProtKB/Swiss-Prot release 2024_01: ~571k entries, ~205 M residues.
SWISSPROT_RESIDUES = 205_000_000
LENGTHS = (240, 368, 1024)
SAMPLE_DOCS = 2048
BURST_REQUESTS, BURST_IDS, BURST_DOCS = 16, 3, 1024

#: Seed bases keep every document set disjoint from the others.
SEED_BULK, SEED_SAMPLE, SEED_BURST = 0, 10_000_000, 20_000_000


class SmokeFailure(RuntimeError):
    """A phase of the smoke did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    """-> jax.devices() when they are ``n_chips`` TPU devices; exits 2
    otherwise, before any result is printed."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) != n_chips:
        print(f"chip_smoke: needs {n_chips} TPU device(s); JAX found "
              f"{len(devices)} {platform!r} device(s)", file=sys.stderr)
        sys.exit(2)
    return devices


# --------------------------------------------------------------------------
# Data
# --------------------------------------------------------------------------


def make_docs(n: int, length: int, seed: int) -> list:
    from repro.core import synthetic_protein

    return [synthetic_protein(length, seed=seed + i) for i in range(n)]


def encode(docs: list, alphabet: str) -> np.ndarray:
    """Equal-length protein strings -> (D, L) int32 symbol ids, in bulk."""
    lut = np.full(256, -1, dtype=np.int32)
    lut[np.frombuffer(alphabet.encode("ascii"), dtype=np.uint8)] = np.arange(
        len(alphabet), dtype=np.int32)
    raw = np.frombuffer("".join(docs).encode("ascii"), dtype=np.uint8)
    out = lut[raw].reshape(len(docs), -1)
    check(bool((out >= 0).all()), "document outside the alphabet")
    return out


# --------------------------------------------------------------------------
# Checks shared by both modes
# --------------------------------------------------------------------------


def same_sfas(a, b, what: str) -> None:
    """Two compiled scanners hold bit-identical modes and SFA stacks."""
    check(a.pattern_modes == b.pattern_modes, f"{what}: pattern modes differ")
    check(len(a.groups) == len(b.groups), f"{what}: group counts differ")
    for ga, gb in zip(a.groups, b.groups):
        check(ga.mode == gb.mode
              and np.array_equal(ga.indices, gb.indices), f"{what}: groups")
        if ga.mode == "sfa":
            check(np.array_equal(ga.sfa_states, gb.sfa_states)
                  and np.array_equal(np.asarray(ga.deltas),
                                     np.asarray(gb.deltas))
                  and np.array_equal(np.asarray(ga.sfa_maps),
                                     np.asarray(gb.sfa_maps)),
                  f"{what}: SFA tables differ")


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# --------------------------------------------------------------------------
# One chip
# --------------------------------------------------------------------------


def check_pallas_compiled() -> int:
    """The cold compile's construction rounds ran the Pallas stages as
    Mosaic kernels: every compiled round step was built with both stages
    on ``"pallas"``, ``interpret=False``, and holds a TPU custom call."""
    from repro.construction.cache import round_compile_cache
    from repro.kernels import ops

    check(ops._default_interpret() is False, "Pallas kernels interpreted")
    steps = [(k, exe) for k, exe in round_compile_cache().items()
             if k[0] == "local-step"]
    check(bool(steps), "no compiled construction round")
    for key, exe in steps:
        check(key[7:10] == ("pallas", "pallas", False),
              f"round {key} did not select compiled Pallas stages")
        check("tpu_custom_call" in exe.as_text(),
              f"round {key} holds no Mosaic kernel")
    return len(steps)


def run_one_chip(device, summary: dict) -> None:
    from repro.construction.cache import round_compile_cache
    from repro.core import PROSITE_EXTRA, PROSITE_SAMPLES, load_bank
    from repro.core.multipattern import census_sequential
    from repro.engine import ConstructionPolicy, ScanPlan, Scanner

    bank = load_bank()
    ids = list(bank.ids)
    check(len(ids) == len(PROSITE_SAMPLES) + len(PROSITE_EXTRA) == 23,
          "bundled bank is not the 23 signatures")
    store = OUT_DIR / "store"
    shutil.rmtree(store, ignore_errors=True)

    svc = Scanner.service(store, plan=ScanPlan())
    try:
        # -- 1. cold compile through the service -----------------------------
        sc, t_compile = timed(lambda: svc.scanner(bank))
        rep = sc.construction_report
        modes = list(sc.pattern_modes.values())
        log(f"cold compile: {t_compile:.3f} s, {rep.rounds} construction "
            f"rounds via {rep.method}, {rep.constructed} SFAs built, "
            f"{rep.blown} blown (modes: {modes.count('sfa')} sfa, "
            f"{modes.count('enumeration')} enumeration, "
            f"{modes.count('speculative')} speculative)")
        check(rep.rounds > 0, "cold compile ran no construction round")
        n_steps = check_pallas_compiled()
        log(f"pallas stages: compiled (Mosaic) in all {n_steps} round "
            "programs")
        summary["cold_compile"] = {
            "seconds": t_compile, "rounds": rep.rounds,
            "constructed": rep.constructed, "blown": rep.blown,
            "round_programs": n_steps,
        }

        # -- 2. bulk scan ----------------------------------------------------
        bulk_docs, t_gen = timed(
            lambda: make_docs(BULK_DOCS, BULK_LEN, SEED_BULK))
        bulk = encode(bulk_docs, sc.alphabet)
        residues = bulk.size
        log(f"bulk corpus: {BULK_DOCS} docs x {BULK_LEN} residues = "
            f"{residues} residues ({residues / SWISSPROT_RESIDUES:.4f} of "
            f"Swiss-Prot's ~{SWISSPROT_RESIDUES} residues), made in "
            f"{t_gen:.3f} s")
        # One cold pass: a warm repeat costs as much device time again and
        # the scan's compile is a few seconds of it.
        res, t_scan = timed(lambda: sc.scan(bulk))
        check(res.hits.shape == (len(ids), BULK_DOCS), "bulk hit shape")
        log(f"bulk scan: {t_scan:.3f} s (compiles included) = "
            f"{residues / t_scan:.6e} residues/s, "
            f"{residues * len(ids) / t_scan:.6e} residue-patterns/s")
        summary["bulk_scan"] = {
            "docs": BULK_DOCS, "length": BULK_LEN, "cold_s": t_scan,
            "residues_per_s": residues / t_scan,
        }

        # -- 3. service burst, cold then warm (same traffic again) ----------
        rng = np.random.default_rng(SEED_BURST)
        reqs = [
            (list(rng.choice(ids, BURST_IDS, replace=False)),
             make_docs(BURST_DOCS, LENGTHS[r % len(LENGTHS)],
                       SEED_BURST + r * BURST_DOCS))
            for r in range(BURST_REQUESTS)
        ]
        burst, answers = {}, []
        for label in ("cold", "warm"):
            t0 = time.perf_counter()
            tickets = [svc.submit(p, d) for p, d in reqs]
            served = svc.flush()
            answers.append([t.result().hits for t in tickets])
            dt = time.perf_counter() - t0
            check(served == BURST_REQUESTS, f"burst served {served} requests")
            burst[label] = dt
        bank_hits = sc.scan([row for _, d in reqs
                             for row in encode(d, sc.alphabet)]).hits
        for r, (p, _) in enumerate(reqs):
            want = bank_hits[[ids.index(i) for i in p],
                             r * BURST_DOCS:(r + 1) * BURST_DOCS]
            for hits in (answers[0][r], answers[1][r]):
                check(hits.shape == (BURST_IDS, BURST_DOCS)
                      and np.array_equal(hits, want),
                      f"burst request {r} differs from the bank scan")
        log(f"service burst: {BURST_REQUESTS} requests x {BURST_DOCS} docs, "
            f"cold {burst['cold']:.3f} s, warm {burst['warm']:.3f} s; every "
            "request equal to the bank scan")
        summary["burst_s"] = burst

        # -- 4. exactness on the seeded sample ------------------------------
        samples = {
            L: encode(make_docs(SAMPLE_DOCS, L, SEED_SAMPLE + L * 10_000),
                      sc.alphabet)
            for L in LENGTHS
        }
        ref = Scanner.compile(bank, sc.plan.with_(backend="reference"))
        pal = Scanner.compile(bank, sc.plan.with_(backend="pallas"))
        check(ref.construction_report.rounds == 0
              and pal.construction_report.rounds == 0,
              "re-compiles missed the service cache")
        exact = {}
        for L, corpus in samples.items():
            got, t_xla = timed(lambda: sc.scan(corpus).hits)
            (want, t_ref) = timed(lambda: ref.scan(corpus).hits)
            check(np.array_equal(got, want),
                  f"L={L}: xla hits differ from backend='reference'")
            counts, t_seq = timed(lambda: census_sequential(bank, corpus))
            check(np.array_equal(got.sum(axis=1, dtype=np.int32), counts),
                  f"L={L}: census differs from census_sequential")
            pal_hits, t_pal = timed(lambda: pal.scan(corpus).hits)
            check(np.array_equal(pal_hits, got),
                  f"L={L}: backend='pallas' hits differ from 'xla'")
            log(f"exact L={L}: {SAMPLE_DOCS} docs, {int(got.sum())} hits "
                "bit-identical to backend='reference' and "
                "census_sequential; backend='pallas' bit-identical to "
                f"'xla' (compiles included: xla scan {t_xla:.3f} s, pallas "
                f"scan {t_pal:.3f} s; reference {t_ref:.3f} s, "
                f"census_sequential {t_seq:.3f} s on the host)")
            exact[L] = {"hits": int(got.sum()), "xla_scan_s": t_xla,
                        "pallas_scan_s": t_pal, "reference_s": t_ref,
                        "census_sequential_s": t_seq}
        summary["exact"] = exact

        built = {}
        for fb in ("pallas", "xla"):
            plan = ScanPlan(construction=ConstructionPolicy(
                cache="off", method="batched",
                fingerprint_backend=fb, expand_backend=fb))
            built[fb], dt = timed(lambda: Scanner.compile(bank, plan))
            log(f"construction {fb}/{fb} (cache off): {dt:.3f} s, "
                f"{built[fb].construction_report.rounds} rounds")
            summary[f"construction_{fb}_s"] = dt
        same_sfas(built["pallas"], built["xla"], "pallas vs xla stages")
        same_sfas(built["pallas"], sc, "cache-off vs service compile")
        log("exact SFAs: Pallas-stage construction bit-identical to the "
            "XLA stages")
        summary["round_programs"] = len(round_compile_cache())
    finally:
        svc.close()


# --------------------------------------------------------------------------
# Four chips
# --------------------------------------------------------------------------


def run_shard_map(devices, summary: dict, docs: int = SAMPLE_DOCS,
                  lengths=LENGTHS) -> None:
    """shard_map construction + scan over every device vs the local
    one-device result, bit for bit."""
    from repro.core import load_bank
    from repro.engine import ConstructionPolicy, ScanPlan, Scanner

    bank = load_bank()
    local_plan = ScanPlan(construction=ConstructionPolicy(
        cache="off", method="batched"))
    dist_plan = ScanPlan(distribution="shard_map",
                         construction=ConstructionPolicy(
                             cache="off", method="batched",
                             distribution="shard_map"))
    local, t_local = timed(lambda: Scanner.compile(bank, local_plan))
    dist, t_dist = timed(lambda: Scanner.compile(bank, dist_plan))
    check(dist.mesh is not None and dist.mesh.size == len(devices),
          "scan mesh does not span every device")
    log(f"construction: local {t_local:.3f} s "
        f"({local.construction_report.rounds} rounds), shard_map over "
        f"{dist.mesh.size} devices {t_dist:.3f} s "
        f"({dist.construction_report.rounds} rounds)")
    same_sfas(dist, local, "shard_map vs local construction")
    log("exact SFAs: shard_map construction bit-identical to local")
    summary["construction_s"] = {"local": t_local, "shard_map": t_dist}
    scans = {}
    for L in lengths:
        corpus = encode(make_docs(docs, L, SEED_SAMPLE + L * 10_000),
                        local.alphabet)
        want = local.scan(corpus).hits
        got = dist.scan(corpus).hits
        check(np.array_equal(got, want), f"L={L}: shard_map hits differ")
        _, t_l = timed(lambda: local.scan(corpus))
        _, t_d = timed(lambda: dist.scan(corpus))
        log(f"exact L={L}: {docs} docs, {int(want.sum())} hits, shard_map "
            f"scan bit-identical to local (warm: local {t_l:.3f} s, "
            f"shard_map {t_d:.3f} s)")
        scans[L] = {"hits": int(want.sum()), "local_s": t_l,
                    "shard_map_s": t_d}
    summary["scans"] = scans


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the whole main path; 4: the shard_map path "
                         "and its local comparison only")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compile cache {cache_dir}")
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    summary = {"device_kind": dev.device_kind, "chips": len(devices)}
    t0 = time.perf_counter()
    if args.chips == 4:
        run_shard_map(devices, summary)
    else:
        run_one_chip(dev, summary)
    summary["wall_s"] = time.perf_counter() - t0
    summary["peak_bytes_in_use"] = [peak_bytes(d) for d in devices]
    log(f"peak device memory: {summary['peak_bytes_in_use']} bytes; "
        f"wall {summary['wall_s']:.3f} s")
    name = "summary.json" if args.chips == 1 else "summary_4chips.json"
    (OUT_DIR / name).write_text(json.dumps(summary, indent=1, default=str))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

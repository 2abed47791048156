"""The comparison that decides ``correct``.

Every round a run made (warm-up included, since verdict state carries
over) is put in one normal form — flagged hosts, per-host scores, the
RCA'd hosts with their onsets, confidences, ranked and co-causes, deferred
hosts, mitigations, quarantine — for the program and for the plain
reference (``bench/configs/monitor_reference.py``), and the two are
compared.  Readings:

* ``flag_diff``: hosts flagged by one side only, summed over rounds;
* ``onset_diff``: RCA'd hosts whose onset differs;
* ``verdict_diff``: hosts RCA'd or deferred against the reference's
  order, mitigations off the strike lifecycle, any quarantine, top causes
  and co-cause lists that differ;
* ``score_err``: largest |score - reference| / max(1, |reference|);
* ``conf_err``: largest |confidence - reference| over RCA'd hosts' causes;
* ``staging_diff`` (live entry): staged cells unequal to the pushed window.

Where the reference itself puts two hosts (or two causes, or a co-cause
test) within ``TIE`` of each other, float32 rounding may order them either
way; such a pair is not counted.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from bench.configs import monitor_reference as ref

#: relative band inside which the reference's order of two scores (or
#: confidences, or a co-cause margin) is left to float32 rounding
TIE = 1e-5

EXACT = ("flag_diff", "onset_diff", "verdict_diff", "staging_diff")


def normalize(fd, tick_end: int, wn: int, rate_hz: float) -> dict:
    """A program ``FleetDiagnosis`` in normal form; onsets relative to
    the detection window (the last ``wn`` ticks before ``tick_end``)."""
    t0 = int(tick_end) - int(wn)
    rca = {}
    for h, d in fd.diagnoses.items():
        rca[int(h)] = {
            "onset": int(round(d.event.t_onset * rate_hz)) - t0,
            "conf": {rc.cause.value: float(rc.confidence) for rc in d.ranked},
            "ranked": [rc.cause.value for rc in d.ranked],
            "causes": [c.value for c in fd.causes.get(h, [])],
        }
    return {
        "flagged": [int(h) for h in fd.flagged_hosts],
        "scores": np.asarray(fd.per_host_scores, np.float64).copy(),
        "rca": rca,
        "deferred": [int(h) for h in fd.deferred_hosts],
        "mitig": {int(h): m.value for h, m in fd.mitigations.items()},
        "quar": [int(h) for h in fd.quarantined],
    }


def reference_rounds(entry, rounds: Sequence[int], g: ref.Geometry,
                     top_k: Optional[int], precision: str,
                     extra_rca: Optional[Dict[int, Sequence[int]]] = None,
                     ) -> Dict[int, dict]:
    """The reference (or, at "bfloat16", the control) at each round of
    ``rounds``, in normal form.  Strikes count consecutive flagged rounds
    and only whether they reach the exclusion threshold matters, so a
    flagged host's strikes come from detecting it again in the
    ``threshold - 1`` rounds before.  Layer 3 runs for the round's RCA'd
    hosts and those in ``extra_rca[k]`` that the reference flags too."""
    out = {}
    for k in rounds:
        fire, score, onset = ref.detect(entry.ref_tail(k), g, precision)
        cand = np.flatnonzero(fire)
        order = np.argsort(-score[cand], kind="stable")
        flagged = [int(h) for h in cand[order]]
        strikes = {h: 1 for h in flagged}
        alive = np.array(flagged, np.int64)
        for j in range(1, g.strikes_to_exclude):
            if k - j < 0 or not alive.size:
                break
            f, _, _ = ref.detect(entry.ref_tail(k - j, alive), g, precision)
            alive = alive[f]
            for h in alive:
                strikes[int(h)] += 1
        n = len(flagged) if top_k is None else min(int(top_k), len(flagged))
        rca_ids, deferred = flagged[:n], flagged[n:]
        hosts = sorted(set(rca_ids) | set(
            int(h) for h in (extra_rca or {}).get(k, ()) if fire[h]))
        info = {}
        if hosts:
            res = ref.rca(entry.ref_block(k, hosts, g), g, precision)
            info = dict(zip(hosts, res))
        rca = {h: {"onset": int(onset[h]), "conf": info[h]["conf"],
                   "ranked": info[h]["ranked"], "causes": info[h]["causes"]}
               for h in rca_ids}
        dset = set(deferred)
        mitig = {h: ref.action(rca[h]["ranked"][0] if h in rca else None,
                               strikes[h], h in dset, g) for h in flagged}
        out[k] = {"flagged": flagged, "scores": score, "onsets": onset,
                  "rca": rca, "info": info, "deferred": deferred,
                  "mitig": mitig, "quar": [], "strikes": strikes}
    return out


def check_sample(seed: int, n_rounds: int, n_check: int) -> List[int]:
    """Rounds compared with the reference: ``n_check`` drawn from the
    seed, and the last round."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    pick = rng.choice(n_rounds, size=min(n_check, n_rounds), replace=False)
    return sorted(set(pick.tolist()) | {n_rounds - 1})


def control_readings(entry, g: ref.Geometry, top_k: Optional[int],
                     rounds: Sequence[int], precision: str = "bfloat16",
                     ) -> Dict[str, float]:
    """Readings of the control — the reference at ``precision`` put in
    the program's place — against the reference, at the same rounds."""
    C = reference_rounds(entry, rounds, g, top_k, precision)
    R = reference_rounds(entry, rounds, g, top_k, "float64",
                         extra_rca={k: list(C[k]["rca"]) for k in rounds})
    return fold([compare(C[k], R[k], g) for k in rounds])


def _boundary_band(R: dict) -> set:
    """Hosts whose place at the RCA/deferral boundary the reference leaves
    to rounding (their scores within TIE of the boundary's)."""
    fl, k = R["flagged"], len(R["rca"])
    if k == 0 or k >= len(fl):
        return set()
    s = R["scores"]
    hi, lo = s[fl[k - 1]], s[fl[k]]
    tol = TIE * max(1.0, abs(hi))
    if hi - lo > tol:
        return set()
    return {h for h in fl if lo - tol <= s[h] <= hi + tol}


def compare(P: dict, R: dict, g: ref.Geometry) -> Dict[str, float]:
    """Readings of one round: program (or control) ``P`` against the
    reference ``R``."""
    r = {"flag_diff": 0, "onset_diff": 0, "verdict_diff": 0,
         "score_err": 0.0, "conf_err": 0.0}
    pf, rf = set(P["flagged"]), set(R["flagged"])
    r["flag_diff"] = len(pf ^ rf)
    rs = R["scores"]
    r["score_err"] = float(np.max(np.abs(P["scores"] - rs)
                                  / np.maximum(1.0, np.abs(rs))))
    band = _boundary_band(R)
    prca = set(P["rca"])
    must_in = set(R["rca"]) - band
    must_out = set(R["deferred"]) - band
    r["verdict_diff"] += len(must_in - prca) + len(must_out & prca)
    r["verdict_diff"] += len(P["quar"])
    r["verdict_diff"] += len(set(P["mitig"]) - pf)
    for h in pf & rf:
        top = P["rca"][h]["ranked"][0] if h in P["rca"] and \
            P["rca"][h]["ranked"] else None
        want = ref.action(top, R["strikes"][h], h not in prca, g)
        if P["mitig"].get(h) != want:
            r["verdict_diff"] += 1
    for h in prca & rf:
        if P["rca"][h]["onset"] != int(R["onsets"][h]):
            r["onset_diff"] += 1
        ri = R["info"].get(h)
        pc = P["rca"][h]["conf"]
        if ri is None or pc is None:
            continue
        r["conf_err"] = max([r["conf_err"]] + [
            abs(pc[c] - ri["conf"][c]) for c in ri["conf"] if c in pc])
        rk = ri["ranked"]
        gap = ri["conf"][rk[0]] - ri["conf"][rk[1]] if len(rk) > 1 else 1.0
        if gap <= TIE:
            continue
        if P["rca"][h]["ranked"][:1] != rk[:1]:
            r["verdict_diff"] += 1
            continue
        if min(list(ri["margins"].values()) + [1.0]) > TIE \
                and P["rca"][h]["causes"] != ri["causes"]:
            r["verdict_diff"] += 1
    return r


def fold(readings: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """Counts summed, errors maxed over rounds."""
    out: Dict[str, float] = {}
    for rd in readings:
        for k, v in rd.items():
            out[k] = out.get(k, 0) + v if k in EXACT else max(
                out.get(k, 0.0), v)
    return out


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every reading is within its limit (missing = fail)."""
    return all(k in readings and readings[k] <= lim
               for k, lim in limits.items())

"""Monitor survivability: crash-safe checkpoint/restore + deterministic
ring replay for the live fleet-diagnosis loop.

The paper's operational claim (detect <= 5 s, RCA <= 8 s) only holds while
the monitor itself stays up: its engine cooldowns, per-host strikes,
quarantine hysteresis and rolling baselines are all mutable state that
evaporates on a crash, turning every in-flight incident into a duplicate
verdict or a miss.  This module makes that state durable:

* **Checkpoint file format** — a fixed binary envelope (magic, version,
  payload length, CRC32) around a JSON payload.  Writes are atomic
  (``tmp + fsync + os.replace``), so a crash mid-write leaves the previous
  checkpoint intact.  Loads are *all-or-nothing*: a truncated file, a
  flipped byte, or a version skew raises :class:`CheckpointError` — a
  half-restored hybrid is worse than a cold start, so nothing is applied
  until the whole payload has parsed.

* **MonitorSession** — the warm-restartable round loop above
  :class:`~repro.monitor.fleet.FleetMonitor`.  It owns the cross-round
  state ``diagnose_fleet`` cannot: the verdict cooldown map that turns a
  per-round diagnosis stream into *events* (one verdict per ``(host,
  cause)`` incident, the engine's cooldown discipline at fleet level —
  concurrent causes on one host dedup independently), and per-host streaming
  baseline moments (Welford chunk merges over each round's newly-seen
  ticks).  ``save``/``restore`` snapshot it together with the monitor's
  strike/quarantine/degraded state.

* **Deterministic replay** — after a restore, re-driving the trailing
  ring contents through ``tick(..., replay=True)`` re-converges to the
  verdict stream of an uninterrupted run byte-for-byte: every round's
  diagnosis is a pure function of (window, restored state), and the
  restored cooldown map suppresses re-emission of any verdict already
  delivered before the crash — zero duplicates by construction, gated as
  ``restart/fleet_replay_parity`` in the benchmarks.
"""
from __future__ import annotations

import binascii
import dataclasses
import json
import os
import struct
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.monitor.fleet import FleetDiagnosis, FleetMonitor

#: checkpoint envelope magic — 8 bytes, never reused across formats
MAGIC = b"RPROCKPT"

#: envelope version; a reader only accepts exactly its own version
#: (state schemas are not forward/backward compatible across PRs).
#: v2: the verdict cooldown map is keyed per (host, cause) — a v1
#: checkpoint's per-host map cannot express concurrent-cause dedup, so
#: v1 loads are rejected loudly into a cold start.
VERSION = 2

_HEADER = struct.Struct("<8sIQI")   # magic, version, payload len, crc32


class CheckpointError(Exception):
    """A checkpoint failed validation — corrupt, truncated, or wrong
    version.  The caller must fall back to a cold start."""


def save_checkpoint(path: str, payload: Dict[str, object]) -> int:
    """Atomically write ``payload`` under the versioned CRC envelope.

    Returns the byte size written.  The temp file lives in the target
    directory so ``os.replace`` stays a same-filesystem atomic rename; a
    crash at any point leaves either the old checkpoint or none — never a
    torn file that a later restore could half-trust.
    """
    body = json.dumps(payload, sort_keys=True).encode("utf-8")
    blob = _HEADER.pack(MAGIC, VERSION, len(body),
                        binascii.crc32(body) & 0xFFFFFFFF) + body
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f".{os.path.basename(path)}.tmp.{os.getpid()}")
    try:
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return len(blob)


def load_checkpoint(path: str) -> Dict[str, object]:
    """Read and fully validate a checkpoint; raise :class:`CheckpointError`
    on ANY defect.  Validation order matters: magic before version before
    length before CRC, so the error names the outermost failure."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError as e:
        raise CheckpointError(f"cannot read checkpoint {path!r}: {e}")
    if len(blob) < _HEADER.size:
        raise CheckpointError(
            f"truncated checkpoint {path!r}: {len(blob)} bytes < "
            f"{_HEADER.size}-byte header")
    magic, version, body_len, crc = _HEADER.unpack_from(blob)
    if magic != MAGIC:
        raise CheckpointError(f"bad magic {magic!r} in {path!r}")
    if version != VERSION:
        raise CheckpointError(
            f"checkpoint version {version} != supported {VERSION} "
            f"({path!r}) — refusing a cross-version restore")
    body = blob[_HEADER.size:]
    if len(body) != body_len:
        raise CheckpointError(
            f"truncated checkpoint {path!r}: payload {len(body)} bytes, "
            f"header promises {body_len}")
    if binascii.crc32(body) & 0xFFFFFFFF != crc:
        raise CheckpointError(f"CRC mismatch in {path!r} — corrupt payload")
    try:
        payload = json.loads(body.decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise CheckpointError(f"unparseable checkpoint payload: {e}")
    if not isinstance(payload, dict):
        raise CheckpointError("checkpoint payload is not an object")
    return payload


@dataclasses.dataclass
class SessionStats:
    """Survivability counters, mirrored into benchmarks and tests."""

    rounds: int = 0                 # diagnosis rounds executed
    restarts: int = 0               # warm restarts (successful restores)
    checkpoints_written: int = 0
    checkpoints_rejected: int = 0   # corrupt/truncated/version-skewed loads
    replay_ticks: int = 0           # samples re-driven during replay rounds
    duplicates_suppressed: int = 0  # verdicts deduped by the cooldown map


@dataclasses.dataclass(frozen=True)
class FleetVerdict:
    """One deduplicated fleet verdict — the session's event-level output
    (the per-round ``FleetDiagnosis`` re-reports an incident every round
    its spike is still inside the trailing window)."""

    host: int
    pred: str            # top cause, CauseClass.value
    t_onset: float
    t_detect: float
    t_ready: float

    def sig(self) -> Tuple[int, str, float, float, float]:
        """The deterministic replay-parity signature (same discipline as
        the scorecard's ``_diag_sig``: virtual-time fields only)."""
        return (self.host, self.pred, self.t_onset, self.t_detect,
                self.t_ready)


class MonitorSession:
    """A crash-restartable fleet-diagnosis loop.

    Drives a :class:`FleetMonitor` one trailing window per ``tick``, and
    owns every piece of cross-round mutable state: the monitor's
    strike/quarantine/degraded machinery (checkpointed via its
    ``state_dict``), the verdict cooldown map, and per-host streaming
    baseline moments.  ``save``/``restore`` make the whole bundle durable;
    after a restore, re-presenting the trailing windows (ring replay)
    yields byte-identical verdicts to an uninterrupted session with zero
    duplicates.
    """

    def __init__(self, monitor: FleetMonitor, channels: Sequence[str],
                 cooldown_s: Optional[float] = None):
        self.monitor = monitor
        self.channels = list(channels)
        #: verdict dedup horizon; defaults to the engine's cooldown
        self.cooldown_s = (float(cooldown_s) if cooldown_s is not None
                           else float(monitor.cfg.cooldown_s))
        self.stats = SessionStats()
        # verdict dedup per (host, cause): with concurrent hypotheses a
        # host may carry several true causes at once, and a second cause
        # surfacing mid-incident must not be swallowed by the first
        # cause's cooldown
        self._cooldown_until: Dict[Tuple[int, str], float] = {}
        self._t_seen = -np.inf        # newest sample time already processed
        # per-host streaming baseline moments (Welford chunk merge over
        # newly-seen ticks): host -> (n, mean, M2), each (C,) float64
        self._base_n: Dict[int, np.ndarray] = {}
        self._base_mean: Dict[int, np.ndarray] = {}
        self._base_m2: Dict[int, np.ndarray] = {}

    # -------------------------------------------------------------- moments
    def baseline_moments(self, host: int,
                         ) -> Optional[Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]]:
        """(n, mean, variance) per channel for one host, or None."""
        h = int(host)
        if h not in self._base_n:
            return None
        n, mean, m2 = self._base_n[h], self._base_mean[h], self._base_m2[h]
        var = np.where(n > 1, m2 / np.maximum(n, 1), 0.0)
        return n.copy(), mean.copy(), var

    def _update_moments(self, ts: np.ndarray, slab: np.ndarray,
                        valid: Optional[np.ndarray], new_lo: int) -> None:
        """Merge each host's newly-seen columns into its running moments.

        Chunk-merge Welford (Chan et al.'s parallel combine rule) over the
        same chunk sequence is deterministic, so an uninterrupted run and
        a restore+replay — which see identical chunk boundaries — converge
        to bit-identical moments.  Invalid cells are excluded per channel.
        """
        if new_lo >= ts.shape[0]:
            return
        H, C, _ = slab.shape
        chunk = np.asarray(slab[:, :, new_lo:], np.float64)
        if valid is not None:
            ok = np.asarray(valid[:, :, new_lo:], bool)
        else:
            ok = np.isfinite(chunk)
        w = np.where(ok, chunk, 0.0)
        cn = ok.sum(axis=2).astype(np.float64)              # (H, C)
        cmean = np.divide(w.sum(axis=2), np.maximum(cn, 1.0))
        cm2 = np.where(ok, (chunk - cmean[:, :, None]) ** 2, 0.0).sum(axis=2)
        for h in range(H):
            if h not in self._base_n:
                self._base_n[h] = np.zeros(C)
                self._base_mean[h] = np.zeros(C)
                self._base_m2[h] = np.zeros(C)
            n0, mu0, m20 = (self._base_n[h], self._base_mean[h],
                            self._base_m2[h])
            n1, mu1, m21 = cn[h], cmean[h], cm2[h]
            n = n0 + n1
            safe = np.maximum(n, 1.0)
            delta = mu1 - mu0
            self._base_mean[h] = mu0 + delta * (n1 / safe)
            self._base_m2[h] = m20 + m21 + delta * delta * (n0 * n1 / safe)
            self._base_n[h] = n

    # ----------------------------------------------------------------- tick
    def tick(self, ts: np.ndarray, slab: np.ndarray,
             valid: Optional[np.ndarray] = None,
             extra_cost_s: float = 0.0, replay: bool = False,
             ) -> Tuple[FleetDiagnosis, List[FleetVerdict]]:
        """One diagnosis round over a trailing (hosts, C, T) window.

        Returns the raw per-round :class:`FleetDiagnosis` plus the
        *deduplicated* verdicts: one per ``(host, cause)`` in the round's
        verdict-cause lists (primary first, then any corroborated
        co-causes when the engine runs concurrent hypotheses), emitted
        only when its detection time has cleared that pair's cooldown —
        the same incident re-reported by later rounds (or re-derived by a
        post-restore replay) is suppressed and counted, while a *new*
        cause surfacing on an already-diagnosed host is not.
        """
        fd = self.monitor.diagnose_fleet(ts, slab, self.channels,
                                         valid=valid,
                                         extra_cost_s=extra_cost_s)
        self.stats.rounds += 1
        new_lo = int(np.searchsorted(ts, self._t_seen, side="right"))
        if replay:
            self.stats.replay_ticks += ts.shape[0] - new_lo
        self._update_moments(ts, slab, valid, new_lo)
        verdicts: List[FleetVerdict] = []
        for h in sorted(fd.diagnoses):
            d = fd.diagnoses[h]
            td = float(d.event.t_detect)
            for cause in fd.causes.get(h, [d.top_cause]):
                key = (int(h), cause.value)
                if td < self._cooldown_until.get(key, -np.inf):
                    self.stats.duplicates_suppressed += 1
                    continue
                self._cooldown_until[key] = td + self.cooldown_s
                verdicts.append(FleetVerdict(
                    host=int(h), pred=cause.value,
                    t_onset=float(d.event.t_onset), t_detect=td,
                    t_ready=float(d.t_ready if d.t_ready is not None
                                  else d.t_rca)))
        if ts.shape[0]:
            self._t_seen = max(self._t_seen, float(ts[-1]))
        return fd, verdicts

    # ---------------------------------------------------------- persistence
    def state_dict(self) -> Dict[str, object]:
        """JSON-serializable session state: the monitor's state (strikes,
        quarantine, shard plan when sharded), per-(host, cause) verdict
        cooldowns, streaming baseline moments, and counters."""
        return {
            "monitor": self.monitor.state_dict(),
            "cooldown_until": {f"{h}|{cause}": float(v)
                               for (h, cause), v
                               in self._cooldown_until.items()},
            "t_seen": float(self._t_seen),
            "baseline": {
                str(h): {"n": self._base_n[h].tolist(),
                         "mean": self._base_mean[h].tolist(),
                         "m2": self._base_m2[h].tolist()}
                for h in sorted(self._base_n)
            },
            "stats": dataclasses.asdict(self.stats),
        }

    def save(self, path: str) -> int:
        """Atomically checkpoint the session; returns bytes written."""
        n = save_checkpoint(path, self.state_dict())
        self.stats.checkpoints_written += 1
        return n

    def restore(self, path: str) -> bool:
        """Warm-restore from ``path``; cold start on any rejection.

        All-or-nothing: the payload is parsed into locals completely
        before any session/monitor field is touched, so a malformed
        payload can never leave a half-restored hybrid.  Returns True on
        a warm restore; False (with a loud warning and a counted
        rejection) means the session keeps its cold-start state.

        Shard-plan skew lands here too: a
        :class:`~repro.monitor.shard.ShardedFleetMonitor` whose plan
        does not match the checkpoint's recorded ``shard_plan`` raises
        ``ValueError`` from ``load_state_dict``, which this catch turns
        into a counted cold start — resharding the fleet between runs
        deliberately invalidates prior strike/quarantine state rather
        than misattributing it across the new shard boundaries.
        """
        try:
            payload = load_checkpoint(path)
            mon_state = payload["monitor"]
            cooldown: Dict[Tuple[int, str], float] = {}
            for k, v in payload["cooldown_until"].items():
                h, _, cause = k.partition("|")
                if not cause:
                    raise CheckpointError(
                        f"cooldown key {k!r} is not host|cause")
                cooldown[(int(h), cause)] = float(v)
            t_seen = float(payload["t_seen"])
            base_n: Dict[int, np.ndarray] = {}
            base_mean: Dict[int, np.ndarray] = {}
            base_m2: Dict[int, np.ndarray] = {}
            for k, blk in payload["baseline"].items():
                h = int(k)
                base_n[h] = np.asarray(blk["n"], np.float64)
                base_mean[h] = np.asarray(blk["mean"], np.float64)
                base_m2[h] = np.asarray(blk["m2"], np.float64)
            # monitor state parses/applies atomically inside
            # load_state_dict (full replacement)
            self.monitor.load_state_dict(mon_state)
        except (CheckpointError, KeyError, TypeError, ValueError) as e:
            self.stats.checkpoints_rejected += 1
            warnings.warn(f"monitor checkpoint rejected, cold start: {e}",
                          RuntimeWarning, stacklevel=2)
            return False
        self._cooldown_until = cooldown
        self._t_seen = t_seen
        self._base_n, self._base_mean, self._base_m2 = (base_n, base_mean,
                                                        base_m2)
        self.stats.restarts += 1
        return True

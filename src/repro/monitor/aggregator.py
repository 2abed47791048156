"""Live fleet assembly: N per-host agents -> one (hosts, C, T) slab.

This is the missing live path of the paper's §5.1 fleet extension: the
benchmarks drive ``FleetMonitor.diagnose_fleet`` with pre-stacked slabs,
but a deployment has N :class:`TelemetryAgent` s — each sampling from its
own background thread (or the virtual clock in trials) — and the monitor
must read *while they write*.  :class:`FleetAggregator` owns the agents
and assembles the monitor's (hosts, C, T) f32 slab from each host's ring
via the seqlock reader (:meth:`MultiChannelRing.read_window`):

  * **a mirrored window frame** — the staging buffers are (hosts, C, 2T):
    tick ``j`` of the fleet's tick grid lives at column ``j mod T`` and
    again at ``j mod T + T``, so the round's window is always the
    contiguous column range ``[s, s + T)`` and the snapshot hands out
    views of it.  A steady-state round reads each host's new ticks out
    of its ring straight into the frame's moving edge; the validity of
    those columns and their mirror copy are then written once for the
    whole fleet.  No row is ever shifted.  A row that does not fit the
    frame (ragged, torn, restarted, off the grid) takes one bounded
    full-window copy instead; only a torn read (writer collided
    mid-copy) repeats a copy,
  * **clock alignment** — hosts are right-aligned on the newest timestamp
    every live host has reached (``t_common``); hosts that have sampled
    past it contribute their window *ending at* ``t_common``,
  * **ragged tolerance** — late joiners with short rings are backfilled
    with their oldest sample (a flat, quiet baseline) and their true
    length reported in ``valid``; hosts whose newest sample is older than
    ``dead_after_s`` (agent died mid-run) are zeroed out of the slab and
    listed in ``skipped`` so a stale spike cannot masquerade as live.

``diagnose`` feeds the staged slab directly to a
:class:`~repro.monitor.fleet.FleetMonitor` — the training loop's
per-diagnosis defensive full-window copy is gone — and, since the stager
keeps each row's count of invalid cells, passes no validity mask at all
when the window holds none.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from repro.core.spans import span
from repro.monitor.fleet import FleetDiagnosis, FleetMonitor
from repro.telemetry.agent import TelemetryAgent


@dataclasses.dataclass
class AggregatorStats:
    """Cumulative aggregator health counters (one snapshot per fleet)."""
    assemblies: int = 0
    torn_retries: int = 0       # seqlock validate-retry loops across hosts
    torn_giveups: int = 0       # reads that exhausted retries (host skipped)
    ragged_hosts: int = 0       # short (late-joiner) rows staged
    dead_hosts: int = 0         # stale rows zeroed out of the slab
    masked_hosts: int = 0       # young rows masked out of a diagnosis
    hung_agents: int = 0        # agent threads that outlived stop()'s join
    agent_restarts: int = 0     # agents re-armed or replaced in place
    host_resets: int = 0        # monitor reset_host calls delivered
    unchanged_skips: int = 0    # rows reused untouched (seqlock watermark)
    delta_reads: int = 0        # rows advanced by a delta read, not T ticks
    full_restages: int = 0      # live rows that took the full T-tick copy
    #: bytes written into the staging buffers (slab, timestamps, validity
    #: and their scratch rows), summed at each write: ring reads, validity
    #: masks, mirror copies, and the zeroing of dead and short rows
    staged_bytes: int = 0


@dataclasses.dataclass
class FleetSnapshot:
    """One staged (hosts, C, T) assembly: slab, clock, validity, skips."""
    ts: np.ndarray              # (T,) reference clock, newest at T-1
    slab: np.ndarray            # (hosts, C, T) f32 — view of the frame
    valid: np.ndarray           # (hosts,) true sample count per row
    skipped: List[int]          # dead/stale hosts (rows zeroed)
    retries: int                # torn-read retries during this assembly
    #: (hosts, C, T) bool — per-cell validity of the staged slab.  False
    #: marks cells a collector failed to deliver (the agent writes NaN for
    #: crashed/backoff-skipped collectors); zeroed dead/skipped rows stay
    #: all-True — their zeros are deliberate quiet, not corruption.
    valid_mask: Optional[np.ndarray] = None
    #: live hosts too young to fill the diagnosed span — rows zeroed by
    #: ``diagnose`` for that round (NOT flagged-eligible; an operator must
    #: not read their zero spike score as "monitored and healthy")
    masked: List[int] = dataclasses.field(default_factory=list)
    #: (hosts, T) f64 — each row's own staged timestamps (``ts`` is one
    #: of them, the reference row's)
    ts_rows: Optional[np.ndarray] = None



def _write(dst: np.ndarray, src) -> int:
    """``dst[...] = src``; returns the bytes written."""
    dst[...] = src
    return dst.nbytes


class FleetAggregator:
    """Owns per-host agents and stages their windows for fleet RCA."""

    def __init__(self, agents: Sequence[TelemetryAgent], window_s: float,
                 dead_after_s: Optional[float] = None, min_samples: int = 2):
        """Preallocate the staging slab for ``agents`` (which must agree
        on channel layout and sampling rate); ``window_s`` fixes the
        staged span T and ``dead_after_s`` the staleness horizon past
        which a host's row is zeroed and skipped."""
        if not agents:
            raise ValueError("need at least one agent")
        self.agents: List[TelemetryAgent] = list(agents)
        self.channels: List[str] = list(agents[0].channels)
        self.rate_hz = float(agents[0].rate_hz)
        for a in self.agents[1:]:
            if list(a.channels) != self.channels:
                raise ValueError("agents disagree on channel layout")
            if float(a.rate_hz) != self.rate_hz:
                raise ValueError("agents disagree on sampling rate")
        self.window_s = float(window_s)
        self.window_n = int(self.window_s * self.rate_hz)
        if self.window_n <= 0:
            raise ValueError("window shorter than one sample period")
        period = 1.0 / self.rate_hz
        #: a host whose newest sample lags the fleet by more than this is
        #: considered dead (agent thread gone) and masked from the slab
        self.dead_after_s = (float(dead_after_s) if dead_after_s is not None
                             else max(10.0 * period, 0.5))
        self.min_samples = int(min_samples)
        H, C, T = len(self.agents), len(self.channels), self.window_n
        # preallocated mirrored frame: every assembly reuses these buffers
        # and zero allocs; columns c and c + T always hold the same tick,
        # so the window [s, s + T) is contiguous wherever s falls
        self._slab = np.zeros((H, C, 2 * T), np.float32)
        self._ts_rows = np.zeros((H, 2 * T), np.float64)
        self._scratch = np.empty((C, T), np.float32)
        self._ts_scratch = np.empty(T, np.float64)
        self._valid = np.ones((H, C, 2 * T), bool)
        #: invalid cells in each row's staged window (``~valid`` count)
        self._invalid = np.zeros(H, np.int64)
        #: tick (``round(t * rate)``) of the last staged ``t_common``; the
        #: window is the columns ``[s, s + T)``, ``s = (tick - T + 1) mod T``
        self._frame_tick = 0
        # delta-staging bookkeeping: a row whose last stage was a full
        # clean T-tick window (no trim, no backfill, no masking since)
        # records the seqlock sequence + newest staged tick; the next
        # assembly then reuses the row untouched (sequence unchanged) or
        # reads only the delta ticks out of the ring into the frame
        self._staged_seq = np.full(H, -1, np.int64)
        self._staged_last = np.full(H, -np.inf)
        self._staged_full = np.zeros(H, bool)
        #: ``_staged_full`` as the monitor last diagnosed it: a row that
        #: was not a full clean window then or now was rewritten in place
        #: since, and ``diagnose`` drops the monitor's carried state of it
        self._carried = np.zeros(H, bool)
        self.stats = AggregatorStats()
        self.last_snapshot: Optional[FleetSnapshot] = None
        self._stopped = False
        # hosts whose agent was restarted/replaced since the last
        # diagnosis: the next diagnose() delivers monitor.reset_host for
        # them (fresh probe != relapsing probe — quarantine backoff and
        # strikes re-base)
        self._pending_resets: set = set()

    # ------------------------------------------------------------ lifecycle
    def start_background(self) -> None:
        """Start every agent's sampling thread (live deployment mode)."""
        self._stopped = False
        for a in self.agents:
            a.run_background()

    def stop(self, timeout: float = 5.0) -> None:
        """Stop every agent; idempotent and bounded.

        Each agent's join waits at most ``timeout`` seconds — a collector
        wedged in a syscall cannot hang fleet shutdown; such threads are
        counted in ``stats.hung_agents`` and left daemonized.  A second
        ``stop`` is a no-op.
        """
        if self._stopped:
            return
        self._stopped = True
        for a in self.agents:
            a.stop(timeout=timeout)
            if a.hung:
                self.stats.hung_agents += 1

    def run_virtual(self, t_start: float, t_end: float) -> None:
        """Drive every agent over the span on the shared virtual clock."""
        for a in self.agents:
            a.run_virtual(t_start, t_end)

    # --------------------------------------------------------- agent restart
    def restart_agent(self, host: int, timeout: float = 5.0) -> None:
        """Re-arm host's agent in place (the RESTART_TELEMETRY action).

        Stops the sampling thread (bounded), clears the agent's crash
        state via :meth:`TelemetryAgent.restart`, and — if the fleet is
        running in background mode — starts it again.  Marks the host for
        a monitor-side :meth:`~repro.monitor.fleet.FleetMonitor.reset_host`
        at the next diagnosis: a freshly-restarted probe must not inherit
        the dead probe's quarantine backoff or strike history."""
        a = self.agents[int(host)]
        was_live = a._thread is not None
        a.stop(timeout=timeout)
        if a.hung:
            self.stats.hung_agents += 1
        a.restart()
        if was_live and not self._stopped:
            a.run_background()
        self.stats.agent_restarts += 1
        self._pending_resets.add(int(host))
        self._staged_full[int(host)] = False  # fresh probe, fresh stage

    def replace_agent(self, host: int, agent: TelemetryAgent,
                      timeout: float = 5.0) -> TelemetryAgent:
        """Swap in a brand-new agent for ``host``; returns the old one.

        The replacement must agree on channel layout and rate (the staging
        slab is preallocated on both).  Like :meth:`restart_agent`, the
        host's monitor-side strike/quarantine history is scheduled for
        reset at the next diagnosis."""
        h = int(host)
        if list(agent.channels) != self.channels:
            raise ValueError("replacement agent disagrees on channel layout")
        if float(agent.rate_hz) != self.rate_hz:
            raise ValueError("replacement agent disagrees on sampling rate")
        old = self.agents[h]
        was_live = old._thread is not None
        old.stop(timeout=timeout)
        if old.hung:
            self.stats.hung_agents += 1
        self.agents[h] = agent
        if was_live and not self._stopped:
            agent.run_background()
        self.stats.agent_restarts += 1
        self._pending_resets.add(h)
        self._staged_full[h] = False  # new ring: staged row is orphaned
        return old

    # ------------------------------------------------------------- assembly
    def _stage_delta(self, h: int, agent: TelemetryAgent, skip: int,
                     count: int, seq: int, t_common: float, period: float,
                     adv: int, cols: slice) -> tuple:
        """O(delta) staging attempt for one live host row.

        Preconditions for even trying: the row's previous stage was a
        full clean T-tick window (``_staged_full``, so it sits at the
        previous round's frame offset), this round wants the un-skipped
        steady-state alignment (``skip == 0``), and the ring holds a full
        window.  Then either the seqlock sequence is unchanged and the
        frame did not move (``adv == 0``) — nothing was pushed, the
        staged row *is* this round's window, zero ring reads — or the new
        right edge sits exactly the frame's advance ``adv`` ticks ahead:
        only those new ticks are read out of the ring, straight into the
        frame columns ``cols`` (the last ``adv`` columns of the new
        window), whose validity and mirror copy
        :meth:`_stage_new_columns` then writes for the whole fleet.  Both
        outcomes are bitwise-identical to the full restage they replace
        (ring history is append-only, so the overlapping columns could
        not have changed).  Any gap, torn read, off-grid timestamp, or a
        shift other than the frame's voids the attempt — the caller falls
        back to the full restage.  Returns ``(staged, moved, retries,
        nbytes)``: ``moved`` marks a delta read, ``nbytes`` the bytes
        written into the staging buffers.
        """
        T = self.window_n
        if not self._staged_full[h] or skip != 0 or count < T:
            return False, False, 0, 0
        if seq >= 0 and seq == self._staged_seq[h] and adv == 0 \
                and abs(self._staged_last[h] - t_common) <= 0.5 * period:
            self.stats.unchanged_skips += 1
            return True, False, 0, 0
        gap = t_common - self._staged_last[h]
        di = int(round(gap / period))
        if not (0 < di < T and di == adv
                and abs(gap - di * period) <= 0.25 * period):
            return False, False, 0, 0
        ts_n, d_n, r = agent.ring.read_window(
            di, out_ts=self._ts_rows[h, cols], out=self._slab[h, :, cols])
        nbytes = ts_n.nbytes + d_n.nbytes
        if (ts_n.size != di
                or abs(float(ts_n[0]) - (self._staged_last[h] + period))
                > 0.25 * period
                or abs(float(ts_n[-1]) - t_common) > 0.5 * period):
            # writer raced past the watermark or ticks were dropped: the
            # new columns no longer line up — void the row, restage fully
            self._staged_full[h] = False
            return False, False, r, nbytes
        self._staged_seq[h] = seq
        self._staged_last[h] = float(ts_n[-1])
        self.stats.delta_reads += 1
        return True, True, r, nbytes

    def _mirror(self, rows, a: int, b: int) -> int:
        """Copy frame columns ``[a, b)`` of ``rows`` (slab, timestamps,
        validity) onto their twins, column ``c``'s being ``c + T`` below
        ``T`` and ``c - T`` from it; returns the bytes written."""
        T = self.window_n
        nbytes = 0
        for lo, hi, d in ((a, min(b, T), T), (max(a, T), b, -T)):
            if lo < hi:
                for x in (self._slab[rows], self._ts_rows[rows],
                          self._valid[rows]):
                    nbytes += _write(x[..., lo + d:hi + d], x[..., lo:hi])
        return nbytes

    def _stage_new_columns(self, cols: slice, moved: np.ndarray) -> int:
        """The fleet-wide half of this round's delta reads, over the new
        frame columns ``cols`` of every row: the validity mask, the
        invalid-cell counts of the ``moved`` rows (up by the new ticks',
        down by the leaving ticks', which the mirrored validity still
        holds there), and the mirror copy.  Every other row already has
        ``valid == isfinite(slab)`` and mirrored twins, so rewriting its
        columns changes nothing.  Returns the bytes written."""
        v = self._valid[:, :, cols]
        if not v.all():
            self._invalid[moved] -= (~v).sum(axis=(1, 2))[moved]
        nbytes = np.isfinite(self._slab[:, :, cols], out=v).nbytes
        if not v.all():
            self._invalid[moved] += (~v).sum(axis=(1, 2))[moved]
        return nbytes + self._mirror(slice(None), cols.start, cols.stop)

    def assemble(self) -> FleetSnapshot:
        """Stage every host's trailing window into the mirrored frame.

        Safe against concurrent background writers: each host row is a
        seqlock-validated consistent snapshot.  Returns the snapshot whose
        ``slab``, ``ts`` and ``valid_mask`` are (hosts, C, T) views of
        the internal frame — consume them before the next ``assemble``
        call.
        """
        H, T = len(self.agents), self.window_n
        with span("aggregator.assemble"):
            period = 1.0 / self.rate_hz
            giveups0 = sum(a.ring.torn_giveups for a in self.agents)

            # phase 1: consistent (seq, count, newest-ts) probe per host to
            # pick the common right edge of the fleet window; the seqlock
            # sequence doubles as the delta-staging change detector
            counts = np.zeros(H, np.int64)
            lasts = np.full(H, -np.inf)
            seqs = np.full(H, -1, np.int64)
            with span("assemble.probe", hosts=H):
                for h, a in enumerate(self.agents):
                    seqs[h], counts[h], lasts[h] = a.ring.watermark()
            have = counts >= max(self.min_samples, 1)
            if not have.any():
                snap = FleetSnapshot(ts=np.zeros(0),
                                     slab=self._slab[:0, :, :T],
                                     valid=np.zeros(H, np.int64),
                                     skipped=list(range(H)), retries=0)
                self.last_snapshot = snap
                return snap
            t_latest = float(lasts[have].max())
            alive = have & (lasts >= t_latest - self.dead_after_s)
            t_common = float(lasts[alive].min())
            tick = int(round(t_common * self.rate_hz))
            s = (tick - T + 1) % T

            # phase 2: every live host's window, right-aligned at
            # t_common, into the frame columns [s, s + T)
            st = self.stats
            n0 = (st.delta_reads, st.full_restages, st.unchanged_skips)
            with span("assemble.copy") as sp:
                valid, skipped, ref_host, retries, nbytes = self._stage_rows(
                    alive, have, counts, lasts, seqs, t_common, period, s,
                    tick - self._frame_tick)
                delta, full, unchanged = (
                    st.delta_reads - n0[0], st.full_restages - n0[1],
                    st.unchanged_skips - n0[2])
                sp.set_metadata(delta_reads=delta, full_restages=full,
                                unchanged=unchanged, bytes=nbytes)
            self._frame_tick = tick
            st.staged_bytes += nbytes
            st.assemblies += 1
            st.torn_retries += retries
            st.torn_giveups += (
                sum(a.ring.torn_giveups for a in self.agents) - giveups0)
            snap = FleetSnapshot(ts=self._ts_rows[ref_host, s:s + T],
                                 slab=self._slab[:, :, s:s + T],
                                 valid=valid, skipped=skipped,
                                 retries=retries,
                                 valid_mask=self._valid[:, :, s:s + T],
                                 ts_rows=self._ts_rows[:, s:s + T])
            self.last_snapshot = snap
            return snap

    def _stage_rows(self, alive: np.ndarray, have: np.ndarray,
                    counts: np.ndarray, lasts: np.ndarray, seqs: np.ndarray,
                    t_common: float, period: float, s: int,
                    adv: int) -> tuple:
        """Phase 2 of :meth:`assemble`: every live host's window,
        right-aligned at ``t_common``, into the frame columns ``[s, s +
        T)``; ``adv`` is the frame's advance in ticks since the last
        assembly.  Returns ``(valid, skipped, ref_host, retries,
        nbytes)``, ``nbytes`` the bytes written into the staging
        buffers."""
        H, T = len(self.agents), self.window_n
        retries = nbytes = 0
        valid = np.zeros(H, np.int64)
        moved = np.zeros(H, bool)
        # a delta read's new ticks: the last ``adv`` columns of the window
        new_cols = slice(s + T - adv, s + T) if 0 < adv < T else None
        win = slice(s, s + T)
        skipped: List[int] = []
        ref_host = -1
        for h, a in enumerate(self.agents):
            if not alive[h]:
                # dead or empty: a stale window must not be diagnosed as
                # live telemetry — zero the row (flat => never flagged)
                self._slab[h] = 0.0
                self._ts_rows[h] = 0.0
                self._valid[h] = True
                nbytes += (self._slab[h].nbytes + self._ts_rows[h].nbytes
                           + self._valid[h].nbytes)
                self._invalid[h] = 0
                self._staged_full[h] = False
                skipped.append(h)
                self.stats.dead_hosts += int(have[h])
                continue
            skip = max(0, int(round((lasts[h] - t_common) / period)))
            # O(delta) staging first: a row whose previous stage was a
            # full clean window is reused untouched (seqlock sequence
            # unchanged) or topped up with only the new ticks at the
            # frame's edge — byte-identical to the full restage it
            # replaces, falling back to it on any raggedness, race, or gap
            staged, moved[h], r0, b0 = self._stage_delta(
                h, a, skip, int(counts[h]), int(seqs[h]), t_common, period,
                adv, new_cols)
            retries += r0
            nbytes += b0
            if staged:
                valid[h] = T
                if ref_host < 0 or T > valid[ref_host]:
                    ref_host = h
                continue
            # full-window hosts stage straight into their frame window —
            # ONE bounded copy out of the ring, then its mirror; the
            # scratch detour only happens for ragged/trimmed rows
            row, ts_row = self._slab[h, :, win], self._ts_rows[h, win]
            direct = counts[h] - skip >= T
            out_ts = ts_row if direct else self._ts_scratch
            out_d = row if direct else self._scratch
            ts_h, d_h, r = a.ring.read_window(T, out_ts=out_ts, out=out_d,
                                              skip_newest=skip)
            retries += r
            nbytes += ts_h.nbytes + d_h.nbytes
            # a live writer may have pushed between peek() and the read,
            # making the stale `skip` land past t_common — re-derive the
            # common-edge trim from the timestamps actually returned
            k = int(np.searchsorted(ts_h, t_common + 0.5 * period,
                                    side="right"))
            ts_h, d_h = ts_h[:k], d_h[:, :k]
            if k < self.min_samples:
                self._slab[h] = 0.0
                self._ts_rows[h] = 0.0
                self._valid[h] = True
                nbytes += (self._slab[h].nbytes + self._ts_rows[h].nbytes
                           + self._valid[h].nbytes)
                self._invalid[h] = 0
                self._staged_full[h] = False
                skipped.append(h)
                continue
            if not (direct and k == T):
                if direct:
                    # short/trimmed read landed left-aligned in the frame
                    # window itself: move it through scratch to right-align
                    nbytes += (_write(self._scratch[:, :k], d_h)
                               + _write(self._ts_scratch[:k], ts_h))
                    d_h = self._scratch[:, :k]
                    ts_h = self._ts_scratch[:k]
                nbytes += (_write(row[:, T - k:], d_h)
                           + _write(ts_row[T - k:], ts_h))
            if k < T:
                # late joiner: backfill the missing head with its oldest
                # sample — a flat stretch that reads as a quiet baseline
                nbytes += (_write(row[:, :T - k], d_h[:, :1])
                           + _write(ts_row[:T - k],
                                    ts_h[0] - period
                                    * np.arange(T - k, 0, -1)))
                self.stats.ragged_hosts += 1
            valid[h] = k
            # per-cell validity: the agent marks failed/backoff-skipped
            # collectors' channels NaN, so finiteness IS the delivery mask
            vrow = self._valid[h, :, win]
            nbytes += np.isfinite(row, out=vrow).nbytes
            self._invalid[h] = vrow.size - np.count_nonzero(vrow)
            nbytes += self._mirror(h, s, s + T)
            # only a full clean direct window seeds the next round's
            # delta path — trimmed/backfilled rows must restage
            full = bool(direct and k == T)
            self._staged_full[h] = full
            if full:
                self._staged_seq[h] = int(seqs[h])
                self._staged_last[h] = float(ts_row[-1])
            self.stats.full_restages += 1
            if ref_host < 0 or k > valid[ref_host]:
                ref_host = h
        if moved.any():
            nbytes += self._stage_new_columns(new_cols, moved)
        return valid, skipped, ref_host, retries, nbytes

    # ------------------------------------------------------------- sharding
    def shard_plan(self, shard_hosts: Optional[int] = None,
                   rack_shards: Optional[int] = None):
        """A :class:`~repro.monitor.shard.ShardPlan` covering this fleet.

        Convenience for building the matching
        :class:`~repro.monitor.shard.ShardedFleetMonitor`: the plan's
        host count is the aggregator's agent count, cut into
        ``shard_hosts``-sized contiguous shards (``REPRO_SHARD_HOSTS``
        default) grouped ``rack_shards`` per rack (``REPRO_RACK_SHARDS``
        default).  :meth:`diagnose` then works unchanged — a sharded
        monitor's ``diagnose_fleet`` processes the staged slab shard by
        shard through per-shard views, no extra copies."""
        from repro.monitor.shard import ShardPlan
        return ShardPlan.for_fleet(len(self.agents), shard_hosts,
                                   rack_shards)

    # ------------------------------------------------------------ diagnosis
    def diagnose(self, monitor: FleetMonitor, min_valid_s: float = 0.0,
                 ) -> Optional[FleetDiagnosis]:
        """Assemble and run fleet RCA on the staged slab (no extra copy).

        Returns None when no host has accumulated ``min_valid_s`` seconds
        of telemetry yet (startup / all agents dead).  The diagnosed span
        is the one the most-established host genuinely supports
        (``valid.max()``, capped by the window); live hosts too young to
        fill it are masked out of THIS round — rows zeroed, like
        ``assemble``'s dead-host masking, and reported via
        ``last_snapshot.masked`` / ``stats.masked_hosts``.  That closes
        two failure modes at once: a backfilled flat head never enters
        the diagnosed slab (the constant would hit the sigma floor and
        flag a perfectly healthy late joiner as a straggler — max-valid
        clamping *without* masking had exactly that hole), and a single
        restarting agent can neither narrow every established host's
        baseline nor collapse the span into ``diagnose_fleet``'s
        short-baseline quiet verdict (which would wipe a real straggler's
        strike history fleet-wide while the newcomer refills).

        The per-cell validity mask goes to the monitor only while some
        staged window holds an invalid cell (the stager counts them);
        otherwise ``valid=None``, which ``diagnose_fleet`` treats exactly
        like an all-true mask, without scanning one.  Rows rewritten in
        place since the monitor's last round (zeroed, masked, backfilled,
        or restaged over such a row) go to
        :meth:`~repro.monitor.fleet.FleetMonitor.invalidate_rows` first:
        the monitor's carried moments and device windows hold what those
        rows said before."""
        with span("aggregator.diagnose", hosts=len(self.agents)) as sp:
            # agent-restart wiring: a host whose probe was restarted/replaced
            # since the last round gets its monitor-side strike/quarantine
            # history re-based BEFORE this diagnosis — delivered exactly once
            for h in sorted(self._pending_resets):
                monitor.reset_host(h)
                self.stats.host_resets += 1
            self._pending_resets.clear()
            snap = self.assemble()
            if snap.slab.shape[0] == 0 or not snap.valid.size:
                return None
            k = int(snap.valid.max())
            if k < max(int(min_valid_s * self.rate_hz), 1):
                return None
            for h in np.flatnonzero((snap.valid > 0) & (snap.valid < k)):
                # cannot fill the span: quiet this round, over the whole
                # frame row so its mirror stays whole; zeros are
                # deliberate quiet, so the row is all valid
                self._slab[h] = 0.0
                self._valid[h] = True
                self._invalid[h] = 0
                snap.masked.append(int(h))
                # the staged row was just overwritten in place — it can no
                # longer seed a delta read; force a full restage next round
                self._staged_full[h] = False
            self.stats.masked_hosts += len(snap.masked)
            sp.set_metadata(masked=len(snap.masked))
            # zeroed, masked and backfilled rows, and rows restaged over
            # one, hold other values than the monitor saw for the same
            # ticks: their carried moments and device windows must go
            monitor.invalidate_rows(
                np.flatnonzero(~(self._carried & self._staged_full)))
            self._carried[:] = self._staged_full
            T = self.window_n
            # the invalid-cell counts say whether the mask has a False
            # cell: without one the monitor's clean path needs no mask
            vm = snap.valid_mask if self._invalid.any() else None
            if k < T:
                return monitor.diagnose_fleet(
                    snap.ts[T - k:], snap.slab[:, :, T - k:], self.channels,
                    valid=None if vm is None else vm[:, :, T - k:])
            return monitor.diagnose_fleet(snap.ts, snap.slab, self.channels,
                                          valid=vm)

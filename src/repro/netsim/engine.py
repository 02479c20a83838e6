"""Discrete-time packet-level fat-tree simulator (the htsim analogue).

One jitted ``tick`` stepped under ``lax.scan``.  Within a tick (order is
part of the model, DESIGN.md §3):

  1. feedback  — ACK/NACK events due now update transport (inflight, rtx),
                 CC and the load balancer;
  2. RTO       — sender-side per-packet timeouts → retransmit marks,
                 timeout events (REPS freezing), window reduction;
  3. service   — every queue dequeues ≤1 packet (degraded links serve every
                 other tick; failed links blackhole); final-hop dequeues
                 deliver to the receiver, which dedupes via a SACK bitmap,
                 coalesces ACKs, and schedules the ACK return;
  4. arrivals  — in-flight packets due now are enqueued at their next hop
                 (ECMP hash or adaptive least-queue choice), with RED/ECN
                 marking and tail-drop (→ trim NACK or silent loss);
  5. injection — each host injects ≤1 packet (round-robin over its eligible
                 connections, window-limited); the load balancer stamps the
                 EV (REPS Algorithm 2 lives here).

Invariants the engine maintains (tested):
  * a connection sees at most one delivery per tick (host downlink serves
    1 pkt/tick), so per-connection LB/CC updates are exact with
    ``feedback_rounds=2``;
  * packet slots are conserved (ring free-list; alloc failures counted);
  * ``inflight`` accounting is exact (ACK count / NACK / RTO each decrement
    exactly once; orphans never double-decrement).

Hot-path layout (this file's perf model — see README "Performance &
execution model"):

  * The per-packet table is ONE packed ``(PF, NP)`` int32 array.  Each
    pipeline stage gathers the rows it touches once, rewrites whole packet
    columns densely, and scatters back once — on the CPU/TPU backends the
    per-tick cost is dominated by the number of non-fusable gather/scatter/
    sort kernels, not FLOPs, so stages budget one gather + one scatter each
    instead of ~10 per-field ops.
  * FIFO ranking of same-target arrivals (and of same-connection ACK
    events for the exact ``feedback_rounds`` replay) and every
    per-connection event aggregation (inflight / NACK / delivery /
    injection accounting) go through two backend-switchable segment
    primitives — ``_seg_rank_b`` and ``_seg_sum_b``
    (``SimConfig.kernels_backend``): the jnp formulations are a pairwise
    compare+reduce rank and stacked scatter-adds (one narrow scatter per
    stage, replacing the dense one-hot masked reductions that used to
    dominate the tick); the pallas formulations are the tiled
    histogram-scan kernels in ``repro.kernels.seg_rank``/``seg_sum``,
    which batch across the vmapped sweep/fleet row axis via the
    ``pallas_call`` vmap rule.  The ACK feedback rounds scatter once into a
    ``(round, conn)`` table instead of building a ``(K, NC)`` selection
    mask per round.
  * Scalar stat counters live in a single ``(N_STATS,)`` vector updated
    once per tick with a stacked delta.
  * ``_step`` is a pure function of (state, tick, base_key); the
    ``FleetRunner`` vmaps it over per-seed keys to batch whole sweeps
    (repro.netsim.fleet).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.load_balancers import LoadBalancer
from repro.netsim.config import INT32_MAX, SimConfig, checked_auto_pkt_slots
from repro.netsim.topology import Topology

# packet states
FREE, FLYING, QUEUED, IN_ACK, IN_NACK, LOST_WAIT = 0, 1, 2, 3, 4, 5

BIG = 2**30  # python int: usable both as jnp operand and as static fill_value

# Packed packet-table rows: pkt[field, slot].  Everything int32 (bools 0/1).
PS, PCONN, PEV, PSEQ, PHOP, PCURQ, PSEND, PEVT, PECN, PORPH, PACK = range(11)
PF = 11

# Fused stats vector indices.
(
    ST_DROPS_CONG, ST_DROPS_FAIL, ST_TIMEOUTS, ST_DELIVERED, ST_ECN,
    ST_INJECTED, ST_UNPROC, ST_ALLOC_FAIL,
) = range(8)
N_STATS = 8


@dataclasses.dataclass(frozen=True)
class Workload:
    """Static connection table (built by repro.netsim.workloads)."""

    src: np.ndarray  # (NC,) int32 source host
    dst: np.ndarray  # (NC,) int32 destination host
    msg_pkts: np.ndarray  # (NC,) int32 message size in packets
    start: np.ndarray  # (NC,) int32 start tick
    dep: np.ndarray  # (NC,) int32 index of prerequisite conn or -1
    name: str = "custom"

    @property
    def n_conns(self) -> int:
        return len(self.src)


# failure kind codes (FailureSchedule.kind); names for error messages/docs
K_DOWN, K_DEGRADED, K_GRAY = 0, 1, 2
KNOWN_KINDS = {
    K_DOWN: "down",
    K_DEGRADED: "degraded",
    K_GRAY: "gray_loss",
}
# gray-loss drop probability is fixed-point: param / GRAY_SCALE
GRAY_SCALE = 65536


@dataclasses.dataclass(frozen=True)
class FailureSchedule:
    """Link events: kind 0 = down (blackhole), 1 = degraded to half rate,
    2 = gray loss (silent per-packet drop with probability
    ``param / GRAY_SCALE``, drawn through the engine's threefry key so
    runs stay bit-reproducible; invisible to adaptive switch routing —
    that is the defining "gray" property).

    A row is *active* at tick ``t`` iff ``start <= t < end``.  Two row
    shapes are legal (``validate``): real windows (``end > start``) and
    inert pads (``start == end == 0``).  Padding/truncation must preserve
    the active-set at every tick — in particular a permanent failure
    (``end = failures.FOREVER``) may never have its ``end`` clipped to a
    pad/bucket boundary, which would silently resurrect the link there.
    ``pad_to`` only ever appends inert rows; dropping rows is the job of
    ``failures.truncate_dead`` (which refuses to drop live events).

    ``param`` is the per-row kind parameter (gray-loss drop rate); it is
    optional at construction (defaults to zeros) so the long-standing
    4-array call sites stay valid.
    """

    queue: np.ndarray  # (F,) int32 queue id
    start: np.ndarray  # (F,) int32 tick
    end: np.ndarray  # (F,) int32 tick
    kind: np.ndarray  # (F,) int32
    param: np.ndarray | None = None  # (F,) int32 kind parameter

    def __post_init__(self) -> None:
        if self.param is None:
            object.__setattr__(
                self, "param", np.zeros((len(self.queue),), np.int32)
            )

    def __len__(self) -> int:
        return len(self.queue)

    @staticmethod
    def none() -> "FailureSchedule":
        z = np.zeros((0,), np.int32)
        return FailureSchedule(z, z, z, z, z)

    @staticmethod
    def concat(*scheds: "FailureSchedule") -> "FailureSchedule":
        return FailureSchedule(
            np.concatenate([s.queue for s in scheds]).astype(np.int32),
            np.concatenate([s.start for s in scheds]).astype(np.int32),
            np.concatenate([s.end for s in scheds]).astype(np.int32),
            np.concatenate([s.kind for s in scheds]).astype(np.int32),
            np.concatenate([s.param for s in scheds]).astype(np.int32),
        )

    def pad_to(self, f: int) -> "FailureSchedule":
        """Append inert rows (start == end == 0: never active for any
        ``now >= 0``) up to ``f`` total.  Existing rows are bit-unchanged —
        padding can therefore never alter the active-set of any tick."""
        extra = f - len(self.queue)
        assert extra >= 0, (
            f"cannot pad a {len(self.queue)}-event schedule down to {f} "
            "rows; drop provably-dead events first (failures.truncate_dead)"
        )
        if extra == 0:
            return self
        z = np.zeros((extra,), np.int32)
        return FailureSchedule(
            queue=np.concatenate([self.queue.astype(np.int32), z]),
            start=np.concatenate([self.start.astype(np.int32), z]),
            end=np.concatenate([self.end.astype(np.int32), z]),
            kind=np.concatenate([self.kind.astype(np.int32), z]),
            param=np.concatenate([self.param.astype(np.int32), z]),
        )

    def validate(self, n_queues: int | None = None) -> None:
        """Reject rows that are neither real windows nor inert pads — each
        violation raises ``ValueError`` naming the offending rows.  The
        dangerous in-between (``end <= start`` but not all-zero) is what a
        buggy pad/truncate produces when it clips ``end`` instead of
        keeping the original window — at the clip boundary the link would
        come back up even though the builder scheduled it down forever.
        Unknown ``kind`` codes are rejected too: an out-of-range kind
        would silently fall through the engine's active-set arithmetic
        (matching none of the per-kind masks) and the row would be a
        no-op instead of the fault the caller asked for."""
        s = np.asarray(self.start)
        e = np.asarray(self.end)
        q = np.asarray(self.queue)
        k = np.asarray(self.kind)
        p = np.asarray(self.param)
        live = e > s
        inert = (s == 0) & (e == 0) & (q == 0) & (k == 0) & (p == 0)
        bad = ~(live | inert)
        if bad.any():
            i = int(np.nonzero(bad)[0][0])
            raise ValueError(
                "failure rows must be real windows (end > start) or inert "
                "pads (queue == start == end == kind == param == 0); "
                f"offending rows {np.nonzero(bad)[0].tolist()} (first: row "
                f"{i} queue={int(q[i])} start={int(s[i])} end={int(e[i])} "
                f"kind={int(k[i])}) look like a clipped/truncated schedule, "
                "which would resurrect the link at the clip boundary"
            )
        if (s < 0).any():
            i = int(np.nonzero(s < 0)[0][0])
            raise ValueError(
                f"failure row {i} (queue {int(q[i])}) starts at tick "
                f"{int(s[i])}: windows cannot start before tick 0"
            )
        unknown = live & ~np.isin(k, list(KNOWN_KINDS))
        if unknown.any():
            i = int(np.nonzero(unknown)[0][0])
            raise ValueError(
                f"failure row {i} (queue {int(q[i])}, "
                f"[{int(s[i])}, {int(e[i])})) has unknown kind "
                f"{int(k[i])}; known kinds: "
                + ", ".join(f"{c}={n}" for c, n in sorted(KNOWN_KINDS.items()))
            )
        bad_p = live & (
            ((k == K_GRAY) & ((p <= 0) | (p > GRAY_SCALE)))
            | ((k != K_GRAY) & (p != 0))
        )
        if bad_p.any():
            i = int(np.nonzero(bad_p)[0][0])
            raise ValueError(
                f"failure row {i} (queue {int(q[i])}, kind {int(k[i])}) has "
                f"param {int(p[i])}: gray-loss rows need 0 < param <= "
                f"{GRAY_SCALE} (drop probability = param/{GRAY_SCALE}); "
                "other kinds take param == 0"
            )
        if n_queues is not None:
            bad_q = live & ((q < 0) | (q >= n_queues))
            if bad_q.any():
                i = int(np.nonzero(bad_q)[0][0])
                raise ValueError(
                    f"failure row {i} targets queue {int(q[i])}, outside "
                    f"the topology's [0, {n_queues}) queue range"
                )

    def merge(
        self,
        delta: "FailureSchedule",
        at_tick: int = 0,
        n_queues: int | None = None,
    ) -> "FailureSchedule":
        """Merge an event ``delta`` into this schedule — the ONE code path
        shared by statically declared composites and the soak runtime's
        live mid-run injection (``SoakRunner.inject`` calls this with
        ``at_tick`` = the current tick cursor).

        Validation (each violation raises ``ValueError``):

        * every delta row must be a real window starting at or after
          ``at_tick`` — an event injected into the already-simulated past
          could never equal the statically-scheduled run it claims to be;
        * a delta row may not overlap an existing *down* window on the
          same queue: the link is already dead there, and the delta's own
          ``end`` would imply a resurrection that pad/truncate semantics
          forbid (the no-resurrect invariant of ``validate``);
        * a delta row may not overlap an existing same-kind window on the
          same queue (a double-scheduled event is a bug, not a request) —
          a *down* delta over an existing *degraded* window stays legal,
          exactly like the statically-declared down+degraded composites.

        Rows of ``self`` (including inert pads) are kept bit-unchanged and
        the delta's live rows are appended, so for any valid delta
        ``base.merge(delta)`` is ``concat(base, delta_live)`` — an injected
        run and the equivalent pre-declared schedule produce identical
        active-sets at every tick.
        """
        delta.validate(n_queues)
        self.validate(n_queues)
        d_s = np.asarray(delta.start, np.int64)
        d_e = np.asarray(delta.end, np.int64)
        d_live = d_e > d_s
        if not np.all(d_s[d_live] >= at_tick):
            bad = np.nonzero(d_live & (d_s < at_tick))[0].tolist()
            raise ValueError(
                f"delta rows {bad} start before tick {at_tick}: events "
                "cannot be injected into the already-simulated past"
            )
        b_q = np.asarray(self.queue, np.int64)
        b_s = np.asarray(self.start, np.int64)
        b_e = np.asarray(self.end, np.int64)
        b_k = np.asarray(self.kind, np.int64)
        b_live = b_e > b_s
        d_q = np.asarray(delta.queue, np.int64)
        d_k = np.asarray(delta.kind, np.int64)
        for i in np.nonzero(d_live)[0]:
            same_q = b_live & (b_q == d_q[i])
            overlap = same_q & (b_s < d_e[i]) & (d_s[i] < b_e)
            if np.any(overlap & (b_k == 0)):
                j = np.nonzero(overlap & (b_k == 0))[0].tolist()
                raise ValueError(
                    f"delta row {int(i)} (queue {int(d_q[i])}, "
                    f"[{int(d_s[i])}, {int(d_e[i])})) overlaps existing "
                    f"down window(s) {j}: the link is already dead there, "
                    "and the delta's end tick would resurrect it"
                )
            if np.any(overlap & (b_k == d_k[i])):
                j = np.nonzero(overlap & (b_k == d_k[i]))[0].tolist()
                raise ValueError(
                    f"delta row {int(i)} (queue {int(d_q[i])}) overlaps "
                    f"same-kind window(s) {j}: double-scheduled event"
                )
            # accepted rows join the base for subsequent delta-row checks,
            # so a delta overlapping itself is rejected the same way
            b_q = np.append(b_q, d_q[i])
            b_s = np.append(b_s, d_s[i])
            b_e = np.append(b_e, d_e[i])
            b_k = np.append(b_k, d_k[i])
            b_live = np.append(b_live, True)
        live_delta = FailureSchedule(
            queue=np.asarray(delta.queue, np.int32)[d_live],
            start=np.asarray(delta.start, np.int32)[d_live],
            end=np.asarray(delta.end, np.int32)[d_live],
            kind=np.asarray(delta.kind, np.int32)[d_live],
            param=np.asarray(delta.param, np.int32)[d_live],
        )
        merged = FailureSchedule.concat(self, live_delta)
        merged.validate(n_queues)
        return merged


class ScenarioArrays(NamedTuple):
    """Per-scenario dynamic arrays, split out of the Simulator so the sweep
    engine can batch *heterogeneous* scenarios: ``step_scenario`` is pure in
    (state, tick, key, scenario), and scenarios sharing static shapes vmap
    together on a leading row axis (repro.netsim.sweep)."""

    conn_src: jax.Array  # (NC,) int32
    conn_dst: jax.Array  # (NC,) int32
    conn_msg: jax.Array  # (NC,) int32
    conn_start: jax.Array  # (NC,) int32
    conn_dep: jax.Array  # (NC,) int32
    host_conns: jax.Array  # (NH, CPH) int32, -1 padded
    watch: jax.Array  # (W,) int32 queue ids traced per tick
    f_queue: jax.Array  # (F,) int32
    f_start: jax.Array  # (F,) int32
    f_end: jax.Array  # (F,) int32
    f_kind: jax.Array  # (F,) int32
    f_param: jax.Array  # (F,) int32


class SimState(NamedTuple):
    # packed packet table (PF, NP) int32 — see field constants above
    pkt: jax.Array
    # queues
    qbuf: jax.Array  # (NQ, QCAP)
    q_head: jax.Array
    q_len: jax.Array
    q_served: jax.Array  # cumulative serve count per queue
    # connections
    c_inflight: jax.Array
    c_next_new: jax.Array
    c_delivered: jax.Array
    c_rx_pending: jax.Array
    c_done: jax.Array
    c_done_tick: jax.Array
    c_rtx_count: jax.Array
    c_rtx: jax.Array  # (NC, MSG) bool
    c_rcv: jax.Array  # (NC, MSG) bool
    c_cwnd: jax.Array  # float32
    c_alpha: jax.Array  # float32
    # hosts
    h_rr: jax.Array
    # LB state
    lb_state: Any
    # free list
    fl: jax.Array
    fl_head: jax.Array
    fl_count: jax.Array
    # cumulative stats, fused into one vector (N_STATS,)
    s_stats: jax.Array
    # sparse active-slot set (conn-scale mode, ARCHITECTURE.md §10):
    # as_idx is the ascending, NP-padded list of currently allocated packet
    # slots and as_count the number of real entries.  Dense mode carries
    # the empty placeholder ((0,) / scalar 0) so the pytree structure —
    # and therefore every compiled sweep shape — is mode-independent.
    as_idx: jax.Array  # (A,) int32, sorted, NP-padded (dense: (0,))
    as_count: jax.Array  # () int32

    # ---- unpacked views (read-only compat accessors) ---------------------
    @property
    def p_state(self):
        return self.pkt[PS]

    @property
    def p_conn(self):
        return self.pkt[PCONN]

    @property
    def p_ev(self):
        return self.pkt[PEV]

    @property
    def p_seq(self):
        return self.pkt[PSEQ]

    @property
    def p_hop(self):
        return self.pkt[PHOP]

    @property
    def p_cur_queue(self):
        return self.pkt[PCURQ]

    @property
    def p_send_tick(self):
        return self.pkt[PSEND]

    @property
    def p_event_tick(self):
        return self.pkt[PEVT]

    @property
    def p_ecn(self):
        return self.pkt[PECN].astype(jnp.bool_)

    @property
    def p_orphan(self):
        return self.pkt[PORPH].astype(jnp.bool_)

    @property
    def p_ack_count(self):
        return self.pkt[PACK]

    @property
    def s_drops_cong(self):
        return self.s_stats[ST_DROPS_CONG]

    @property
    def s_drops_fail(self):
        return self.s_stats[ST_DROPS_FAIL]

    @property
    def s_timeouts(self):
        return self.s_stats[ST_TIMEOUTS]

    @property
    def s_delivered(self):
        return self.s_stats[ST_DELIVERED]

    @property
    def s_ecn_marks(self):
        return self.s_stats[ST_ECN]

    @property
    def s_injected(self):
        return self.s_stats[ST_INJECTED]

    @property
    def s_unprocessed(self):
        return self.s_stats[ST_UNPROC]

    @property
    def s_alloc_fail(self):
        return self.s_stats[ST_ALLOC_FAIL]


class TickTrace(NamedTuple):
    max_qlen: jax.Array
    sum_qlen: jax.Array
    drops: jax.Array
    timeouts: jax.Array
    delivered: jax.Array
    injected: jax.Array
    watch_qlen: jax.Array  # (W,)
    watch_served: jax.Array  # (W,) int32 0/1


class Probe(NamedTuple):
    """Per-tick observables the telemetry channels reduce over
    (repro.netsim.telemetry).

    Unlike ``TickTrace`` (a raw stream destined for the host), a ``Probe``
    never leaves the device: it is consumed on the spot by the pure
    ``(carry, probe) -> carry`` channel reducers folded inside the scanned
    tick loop.  Every field is a *delta or instantaneous* view of the tick,
    so a quiescent tick (no packets, no startable work) produces an
    all-zero probe and channel updates become no-ops — which is what makes
    summary collection compatible with quiescence early exit.
    """

    now: jax.Array  # () int32 — the tick just executed
    q_len: jax.Array  # (NQ,) int32 occupancy after the tick
    served: jax.Array  # (NQ,) int32 0/1 — dequeued this tick
    watch_qlen: jax.Array  # (W,) int32 occupancy of watched queues
    watch_served: jax.Array  # (W,) int32 0/1 for watched queues
    stats_delta: jax.Array  # (N_STATS,) int32 counter increments this tick
    done_now: jax.Array  # (NC,) bool — conns that completed this tick
    fct: jax.Array  # (NC,) int32 — done tick - start where done_now, else 0


class TickEvents(NamedTuple):
    """Per-tick decision-event counts for the flight recorder
    (repro.netsim.tracer).

    Observation-only companions to ``Probe``: derived from state diffs
    around the LB call sites (the optional ``LoadBalancer.trace`` port) and
    the scenario's failure windows, never fed back into the simulation.
    Same quiescence contract as ``Probe`` — all-zero on a quiescent tick —
    so the tracer carry stays compatible with early exit and per-row
    horizon freezing.
    """

    lb: jax.Array  # (N_TRACE_KINDS,) int32 LB decision counts this tick
    fail_start: jax.Array  # () int32 — queues whose failure window opens now


class Simulator:
    """Builds and runs one simulation scenario.

    Static scenario structure (cfg / topo / workload tables / failures /
    watch list) lives on the instance; per-run dynamic state is the
    ``SimState`` pytree plus the PRNG base key, both explicit arguments of
    the pure ``_step`` — which is what lets ``FleetRunner`` vmap one
    compiled scenario over many seeds.
    """

    def __init__(
        self,
        cfg: SimConfig,
        workload: Workload,
        lb: LoadBalancer,
        failures: FailureSchedule | None = None,
        watch_queues: np.ndarray | None = None,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.topo = Topology.build(cfg)
        self.wl = workload
        self.lb = lb
        self.failures = failures or FailureSchedule.none()
        if cfg.failure_slots:
            # shape pin (sweep bucketing): pad with inert rows so a serial
            # reference built from the raw schedule shares the sweep row's
            # (F,) shape — semantics of pad rows are FailureSchedule's.
            self.failures = self.failures.pad_to(cfg.failure_slots)
        self.failures.validate(self.topo.n_queues)
        self.seed = seed

        NC = workload.n_conns
        msg_max = int(workload.msg_pkts.max()) if NC else 1
        assert msg_max <= cfg.max_msg_pkts, (
            f"message of {msg_max} pkts exceeds max_msg_pkts={cfg.max_msg_pkts}"
        )
        auto_msg = int(min(cfg.max_msg_pkts, max(int(2 ** np.ceil(np.log2(max(msg_max, 2)))), 2)))
        if cfg.msg_slots:
            assert cfg.msg_slots >= auto_msg, (
                f"msg_slots={cfg.msg_slots} < required bitmap width {auto_msg}"
            )
            self.MSG = int(cfg.msg_slots)
        else:
            self.MSG = auto_msg
        self.NQ = self.topo.n_queues
        self.NH = cfg.n_hosts
        if cfg.conn_sharding:
            # Scale mode: live packet slots are bounded by slot *lifetime*
            # (injection admits ≤ NH/tick and every slot frees within
            # rto + drain + feedback latency of its send), not by
            # NC * max_cwnd — so the auto size caps at the lifetime bound
            # and a million-conn run no longer allocates a 2^28-slot table.
            # At figure scales the conn-based size is the smaller of the
            # two, so the auto rule (and every result) is unchanged there.
            bound = self._active_bound()
            conn_auto = int(
                2 ** np.ceil(np.log2(NC * cfg.max_cwnd_pkts + 4 * self.NH + 64))
            )
            self.NP = int(cfg.pkt_slots) if cfg.pkt_slots else min(conn_auto, bound)
            if self.NP > INT32_MAX:
                raise ValueError(
                    f"pkt_slots={self.NP} exceeds the int32 slot namespace "
                    f"(max {INT32_MAX})"
                )
            self.A = min(int(cfg.active_slots) if cfg.active_slots else bound, self.NP)
        else:
            # dense mode: THE auto rule, python-int checked against int32
            # (near 10**6 conns the raw product wraps silently otherwise)
            self.NP = checked_auto_pkt_slots(
                NC, cfg.max_cwnd_pkts, self.NH, pin=cfg.pkt_slots
            )
            self.A = 0
        # MAX_ARR is RNG-visible (the per-arrival RED uniform draw has
        # shape (MAX_ARR,), and jax threefry draws are not prefix-stable),
        # so it keeps the seed engine's generous bound for bit-parity.
        self.MAX_ARR = self.NQ + self.NH
        # MAX_EV / MAX_FREE are pure compaction sizes — no RNG shape
        # derives from them — so they use tight per-tick bounds (every K
        # beyond a bound is provably unreachable, making the shrink
        # bit-invisible while directly narrowing the hot-path rank /
        # segment-sum / scatter widths):
        #  * feedback: ACKs are emitted only by final-hop dequeues (the NH
        #    host downlinks, queues ≥ t0_down_base) with a fixed ack delay
        #    → ≤ NH due per tick; trim NACKs (≤ MAX_ARR, fixed nack delay)
        #    exist only when cfg.trimming;
        #  * frees: feedback slots (≤ MAX_EV) + RTO LOST_WAIT expiries
        #    (≤ NH) + service frees (≤ NQ serves) + arrival drops
        #    (≤ MAX_ARR).
        self.MAX_EV = self.NH + (self.MAX_ARR if cfg.trimming else 0)
        self.MAX_FREE = self.MAX_EV + self.NQ + self.MAX_ARR + self.NH

        # int32 audit: the widest flattened segment-id / sort-key spaces the
        # tick builds (feedback (round, conn) table; seg-rank's
        # seg * K + iota sort keys).  Computed in python ints — near 10**6
        # conns these cross 2**31 long before any array exists, and a
        # wrapped id would scatter into the wrong connection silently.
        widest = max(
            (cfg.feedback_rounds + 1) * (NC + 1),
            (NC + 1) * (self.MAX_EV + 1),
            (self.NQ + 1) * (self.MAX_ARR + 1),
        )
        if widest > INT32_MAX:
            raise ValueError(
                f"per-tick segment-id space overflows int32: n_conns={NC}, "
                f"n_queues={self.NQ}, max events/tick {self.MAX_EV}, "
                f"max arrivals/tick {self.MAX_ARR} -> widest id {widest} > "
                f"{INT32_MAX}. Reduce the topology/connection count."
            )

        # host -> local conn table (vectorized — the per-conn python loop
        # this replaces dominated build time near 10**6 conns)
        src = np.asarray(workload.src, np.int64)
        counts = (
            np.bincount(src, minlength=self.NH)
            if NC
            else np.zeros(self.NH, np.int64)
        )
        auto_cph = int(max(1, counts.max())) if NC else 1
        if cfg.conns_per_host:
            assert cfg.conns_per_host >= auto_cph, (
                f"conns_per_host={cfg.conns_per_host} < required {auto_cph}"
            )
            self.CPH = int(cfg.conns_per_host)
        else:
            self.CPH = auto_cph
        hc = np.full((self.NH, self.CPH), -1, np.int32)
        if NC:
            # stable sort by host keeps conn-id order within each host —
            # identical fill to the per-host append loop it replaces
            order = np.argsort(src, kind="stable")
            starts = np.zeros(self.NH, np.int64)
            starts[1:] = np.cumsum(counts)[:-1]
            rank = np.arange(NC, dtype=np.int64) - starts[src[order]]
            hc[src[order], rank] = order
        self.host_conns = jnp.asarray(hc)

        self.conn_src = jnp.asarray(workload.src.astype(np.int32))
        self.conn_dst = jnp.asarray(workload.dst.astype(np.int32))
        self.conn_msg = jnp.asarray(workload.msg_pkts.astype(np.int32))
        self.conn_start = jnp.asarray(workload.start.astype(np.int32))
        self.conn_dep = jnp.asarray(workload.dep.astype(np.int32))

        if watch_queues is None:
            watch_queues = self.topo.t0_up_queues(0)[: cfg.n_watch_queues]
        self.watch = jnp.asarray(np.asarray(watch_queues, np.int32))

        self.f_queue = jnp.asarray(self.failures.queue)
        self.f_start = jnp.asarray(self.failures.start)
        self.f_end = jnp.asarray(self.failures.end)
        self.f_kind = jnp.asarray(self.failures.kind)
        self.f_param = jnp.asarray(self.failures.param)

        # the pure-step view of this scenario's dynamic arrays
        self.scn = ScenarioArrays(
            conn_src=self.conn_src,
            conn_dst=self.conn_dst,
            conn_msg=self.conn_msg,
            conn_start=self.conn_start,
            conn_dep=self.conn_dep,
            host_conns=self.host_conns,
            watch=self.watch,
            f_queue=self.f_queue,
            f_start=self.f_start,
            f_end=self.f_end,
            f_kind=self.f_kind,
            f_param=self.f_param,
        )

        self.base_key = jax.random.PRNGKey(seed)

    # ------------------------------------------------------------------
    def _active_bound(self) -> int:
        """Pow2 bound on simultaneously-allocated packet slots (conn-scale
        mode): injection admits ≤ NH packets per tick and every slot frees
        within one lifetime of its send — worst-case path drain
        (diameter hops, each ≤ hop latency + a full queue at degraded
        half-rate) plus the feedback return delay, with RTO as the hard
        backstop for silent losses.  LOST_WAIT slots of already-completed
        connections leak past this bound (their RTO never fires — same as
        dense mode, where NP slack absorbs them); if a long lossy soak
        fills the cap, injection alloc-fails *visibly* (s_alloc_fail)
        rather than corrupting state.
        """
        cfg = self.cfg
        lifetime = (
            cfg.rto_ticks
            + cfg.ack_delay_ticks
            + cfg.nack_delay_ticks
            + self.topo.diameter * (cfg.hop_latency_ticks + 2 * cfg.queue_capacity)
        )
        raw = self.NH * lifetime + 4 * self.NH + 64
        return int(2 ** np.ceil(np.log2(max(raw, 2))))

    # ------------------------------------------------------------------
    def init_state(self, key: jax.Array | None = None) -> SimState:
        NP, NQ, NC, NH = self.NP, self.NQ, self.wl.n_conns, self.NH
        cfg = self.cfg
        i32 = jnp.int32
        if key is None:
            key = self.base_key
        return SimState(
            pkt=jnp.zeros((PF, NP), i32),
            qbuf=jnp.zeros((NQ, cfg.queue_capacity), i32),
            q_head=jnp.zeros((NQ,), i32),
            q_len=jnp.zeros((NQ,), i32),
            q_served=jnp.zeros((NQ,), i32),
            c_inflight=jnp.zeros((NC,), i32),
            c_next_new=jnp.zeros((NC,), i32),
            c_delivered=jnp.zeros((NC,), i32),
            c_rx_pending=jnp.zeros((NC,), i32),
            c_done=jnp.zeros((NC,), jnp.bool_),
            c_done_tick=jnp.full((NC,), -1, i32),
            c_rtx_count=jnp.zeros((NC,), i32),
            c_rtx=jnp.zeros((NC, self.MSG), jnp.bool_),
            c_rcv=jnp.zeros((NC, self.MSG), jnp.bool_),
            c_cwnd=jnp.full((NC,), float(cfg.init_cwnd_pkts), jnp.float32),
            c_alpha=jnp.zeros((NC,), jnp.float32),
            h_rr=jnp.zeros((NH,), i32),
            lb_state=self.lb.init_state(NC, jax.random.fold_in(key, 777)),
            fl=jnp.arange(NP, dtype=i32),
            fl_head=jnp.zeros((), i32),
            fl_count=jnp.asarray(NP, i32),
            s_stats=jnp.zeros((N_STATS,), i32),
            as_idx=jnp.full((self.A,), NP, i32),
            as_count=jnp.zeros((), i32),
        )

    # ------------------------------------------------------------------
    def _cc_on_ack(self, cwnd, alpha, mask, ecn, rtt):
        """Per-ACK CC update (DCTCP-variant per §4.1 / MPRDMA)."""
        cfg = self.cfg
        if cfg.cc == "dctcp":
            g = cfg.dctcp_g
            alpha = jnp.where(
                mask, (1 - g) * alpha + g * ecn.astype(jnp.float32), alpha
            )
            up = cwnd + 1.0 / jnp.maximum(cwnd, 1.0)
            down = cwnd - alpha / 2.0
            cwnd = jnp.where(mask, jnp.where(ecn, down, up), cwnd)
        elif cfg.cc == "eqds":
            # receiver-credit approximation: fast additive increase toward a
            # hard BDP cap; ECN halves toward the cap floor.
            up = cwnd + 4.0 / jnp.maximum(cwnd, 1.0)
            down = cwnd - 0.5
            cwnd = jnp.where(mask, jnp.where(ecn, down, up), cwnd)
            cwnd = jnp.minimum(cwnd, float(self.cfg.init_cwnd_pkts))
        elif cfg.cc == "delay":
            t = float(cfg.delay_target_ticks)
            over = (rtt.astype(jnp.float32) - t) / t
            up = cwnd + 1.0 / jnp.maximum(cwnd, 1.0)
            down = cwnd - cfg.delay_beta * jnp.clip(over, 0.0, 1.0)
            cwnd = jnp.where(mask, jnp.where(over > 0, down, up), cwnd)
        else:
            raise ValueError(cfg.cc)
        cwnd = jnp.clip(cwnd, 1.0, float(cfg.max_cwnd_pkts))
        return cwnd, alpha

    # ------------------------------------------------------------------
    @staticmethod
    def _compact(mask: jax.Array, size: int) -> jax.Array:
        """Indices of set bits in ascending order, padded with len(mask).

        Bit-equivalent to ``jnp.nonzero(mask, size=size, fill_value=N)[0]``
        but ~15x cheaper on the CPU backend: the j-th set bit is found by a
        vectorized binary search over the running popcount instead of the
        full-width scatter nonzero lowers to.
        """
        cs = jnp.cumsum(mask.astype(jnp.int32))
        targets = jnp.arange(1, size + 1, dtype=jnp.int32)
        return jnp.searchsorted(cs, targets, side="left").astype(jnp.int32)

    @staticmethod
    def _seg_rank(seg: jax.Array) -> jax.Array:
        """FIFO rank of each element within its segment (stable in input
        order): rank_i = #{j < i : seg_j == seg_i}.

        For the K used at CI scale (a few hundred) the O(K^2) pairwise
        comparison is a single fused compare+reduce — cheaper than both
        argsort and a segment-cumsum over the one-hot histogram, whose
        K x n_segs scan dominates the arrivals step on CPU/TPU.  Past ~1k
        elements the quadratic mask loses to the O(K log K) sort, so large
        fleets fall back to the sort-based run-length rank.
        """
        K = seg.shape[0]
        if K <= 1024:
            earlier = jnp.tril(jnp.ones((K, K), jnp.bool_), k=-1)  # j < i
            same = seg[None, :] == seg[:, None]
            return jnp.sum(same & earlier, axis=1, dtype=jnp.int32)
        iota = jnp.arange(K, dtype=jnp.int32)
        order = jnp.argsort(seg * jnp.int32(K) + iota)  # stable in input order
        ts = seg[order]
        run_start = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), ts[1:] != ts[:-1]]
        )
        pos_in_run = iota - jax.lax.cummax(jnp.where(run_start, iota, 0))
        return jnp.zeros((K,), jnp.int32).at[order].set(pos_in_run)

    # ------------------------------------------------------------------
    # Backend-switchable segment primitives (SimConfig.kernels_backend).
    # "auto" resolves at trace time: the tiled Pallas kernels on TPU, the
    # jnp formulations elsewhere.  Both are bit-identical (int32 adds are
    # order-free; ranks are exact), so flipping the backend never changes
    # simulation results — tests/test_kernel_parity.py locks this across
    # multi-bucket sweeps.
    def _kb(self) -> str:
        from repro.distrib.sharding import resolve_kernels_backend

        return resolve_kernels_backend(self.cfg.kernels_backend)

    def _seg_rank_b(self, seg: jax.Array, n_segments: int) -> jax.Array:
        """FIFO rank within segment; ids >= n_segments are sentinels whose
        ranks are never consumed (the pallas kernel returns 0 for them)."""
        if self._kb() == "pallas":
            from repro.kernels import ops as kernel_ops

            return kernel_ops.seg_rank(seg, n_segments)
        return self._seg_rank(seg)

    def _seg_sum_b(
        self, seg: jax.Array, vals: jax.Array, n_segments: int
    ) -> jax.Array:
        """Stacked (F, K) int32 fields segment-summed to (F, n_segments);
        ids >= n_segments drop.  One narrow scatter-add on the jnp path —
        the replacement for the dense per-field one-hot reductions."""
        if self._kb() == "pallas":
            from repro.kernels import ops as kernel_ops

            return kernel_ops.seg_sum(seg, vals, n_segments)
        return jnp.zeros((vals.shape[0], n_segments), jnp.int32).at[
            :, seg
        ].add(vals, mode="drop")

    # ------------------------------------------------------------------
    # Conn-sharded bitmap indirection (scale mode).  Under a conn-axis mesh
    # the (NC, MSG) rtx/rcv bitmaps are the only per-conn state too large
    # to replicate, so they stay device-local and every access goes through
    # these four helpers: each device answers for the conn rows it owns and
    # a psum-OR reconstructs the full-shape value the tick body expects
    # (scatters simply drop on non-owners).  With conn_axis=None each
    # helper IS the dense expression it replaces, byte-for-byte.
    def _bm_local(self, bmap, conns, conn_axis):
        NCd = bmap.shape[0]
        off = jax.lax.axis_index(conn_axis) * NCd
        loc = conns - off
        inr = (loc >= 0) & (loc < NCd)
        return jnp.where(inr, loc, NCd), inr

    def _bm_get(self, bmap, conns, seqs, conn_axis):
        """``bmap.at[conns, seqs].get(mode="fill", fill_value=True)``."""
        if conn_axis is None:
            return bmap.at[conns, seqs].get(mode="fill", fill_value=True)
        loc, inr = self._bm_local(bmap, conns, conn_axis)
        got = bmap.at[loc, seqs].get(mode="fill", fill_value=False)
        hit = jax.lax.psum((inr & got).astype(jnp.int32), conn_axis) > 0
        return hit | (conns >= self.wl.n_conns) | (conns < 0)

    def _bm_max(self, bmap, conns, seqs, vals, conn_axis):
        """``bmap.at[conns, seqs].max(vals, mode="drop")``."""
        if conn_axis is None:
            return bmap.at[conns, seqs].max(vals, mode="drop")
        loc, _ = self._bm_local(bmap, conns, conn_axis)
        return bmap.at[loc, seqs].max(vals, mode="drop")

    def _bm_set_false(self, bmap, conns, seqs, conn_axis):
        """``bmap.at[conns, seqs].set(False, mode="drop")``."""
        if conn_axis is None:
            return bmap.at[conns, seqs].set(False, mode="drop")
        loc, _ = self._bm_local(bmap, conns, conn_axis)
        return bmap.at[loc, seqs].set(False, mode="drop")

    def _bm_rows(self, bmap, conns, conn_axis):
        """``bmap[conns]`` — full (K, MSG) bool rows; callers pass in-range
        conn ids only."""
        if conn_axis is None:
            return bmap[conns]
        loc, inr = self._bm_local(bmap, conns, conn_axis)
        rows = bmap.at[loc].get(mode="fill", fill_value=False)
        rows = jnp.where(inr[:, None], rows, False)
        return jax.lax.psum(rows.astype(jnp.int32), conn_axis) > 0

    # ------------------------------------------------------------------
    def tick_fn(self, state: SimState, tick: jax.Array) -> tuple[SimState, TickTrace]:
        return self._step(state, tick, self.base_key)

    def _step(
        self, state: SimState, tick: jax.Array, base_key: jax.Array
    ) -> tuple[SimState, TickTrace]:
        return self.step_scenario(state, tick, base_key, self.scn)

    def step_scenario(
        self,
        state: SimState,
        tick: jax.Array,
        base_key: jax.Array,
        scn: ScenarioArrays,
        emit_events: bool = False,
        conn_axis: str | None = None,
    ) -> tuple:
        """One tick, pure in (state, tick, key, scenario arrays).

        Static structure (cfg, topology, shapes, LB object) still lives on
        the instance; everything a scenario can vary *without changing
        shapes* arrives via ``scn`` — which is what the sweep engine vmaps
        over to batch heterogeneous (workload, lb, failures) cells into one
        compiled scan (repro.netsim.sweep).

        ``emit_events`` is a *static* flag: when False (the default) the
        compiled computation is byte-for-byte today's — no trace-port calls
        are staged at all.  When True the return grows a third element, a
        ``TickEvents`` of observation-only decision counts gathered from
        LB-state diffs around the three LB call sites (``fold_in`` key
        derivation consumes no randomness and the trace port draws none, so
        the (state, trace) pair is bit-identical either way).

        ``conn_axis`` (static) names the mesh axis the *connection* state
        axis is sharded over (scale mode, inside ``shard_map``): small
        (NC,) per-conn vectors and the scn conn tables arrive as local
        shards, are all_gathered to full shape at entry and sliced back at
        exit — so every RNG draw keeps its full, shard-count-independent
        shape and results stay bit-identical to the unsharded run — while
        the (NC, MSG) rtx/rcv bitmaps (the dominant per-conn storage) stay
        device-local behind the ``_bm_*`` psum indirection.  ``lb_state``
        is replicated: LBs draw (NC,)-shaped randoms internally, so
        sharding it would change draw shapes and break parity.
        """
        cfg, topo = self.cfg, self.topo
        NP, NQ, NH = self.NP, self.NQ, self.NH
        NC = self.wl.n_conns
        QCAP = cfg.queue_capacity
        now = tick.astype(jnp.int32)
        key = jax.random.fold_in(base_key, tick)

        pkt = state.pkt
        (
            qbuf, q_head, q_len, q_served,
            c_inflight, c_next_new, c_delivered, c_rx_pending, c_done,
            c_done_tick, c_rtx_count, c_rtx, c_rcv, c_cwnd, c_alpha,
            h_rr, lb_state, fl, fl_head, fl_count, s_stats,
            as_idx, as_count,
        ) = state[1:]

        if conn_axis is not None:
            with jax.named_scope("tick.conn_exchange"):
                # conn-sharded entry: gather the small per-conn leaves to full
                # shape (collective cost O(NC) scalars/tick; the (NC, MSG)
                # bitmaps stay local).  NCd/coff identify this device's block.
                NCd = c_inflight.shape[0]
                coff = jax.lax.axis_index(conn_axis) * NCd

                def cgather(x):
                    return jax.lax.all_gather(x, conn_axis, axis=0, tiled=True)

                (c_inflight, c_next_new, c_delivered, c_rx_pending, c_done,
                 c_done_tick, c_rtx_count, c_cwnd, c_alpha) = (
                    cgather(c_inflight), cgather(c_next_new),
                    cgather(c_delivered), cgather(c_rx_pending),
                    cgather(c_done), cgather(c_done_tick),
                    cgather(c_rtx_count), cgather(c_cwnd), cgather(c_alpha),
                )
                scn = scn._replace(
                    conn_src=cgather(scn.conn_src),
                    conn_dst=cgather(scn.conn_dst),
                    conn_msg=cgather(scn.conn_msg),
                    conn_start=cgather(scn.conn_start),
                    conn_dep=cgather(scn.conn_dep),
                )

        sparse = bool(cfg.conn_sharding)
        if sparse:
            # scale mode: stages 1/2/4/6 iterate the packet table through
            # the sorted active-slot set (A entries) instead of dense (NP,)
            # masks — per-tick cost tracks live traffic, not table width.
            # Compaction works on positions-within-as_idx, then maps back
            # through as_idx; because as_idx is kept ascending, the
            # compacted slot sequences are identical to the dense path's,
            # and with A == NP the whole mode is bit-identical to dense.
            with jax.named_scope("tick.active_set"):
                asx = jnp.minimum(as_idx, NP - 1)
                as_valid = as_idx < NP
                entry_ps_a = jnp.where(as_valid, pkt[PS, asx], FREE)
        else:
            state_at_entry = pkt[PS]

        if emit_events:
            from repro.core.load_balancers import N_TRACE_KINDS

            lb_counts = jnp.zeros((N_TRACE_KINDS,), jnp.int32)

        # =============== 1. feedback (ACK / NACK) =====================
        with jax.named_scope("tick.feedback"):
            if sparse:
                ps_a = entry_ps_a
                evt_a = pkt[PEVT, asx]
                due_a = as_valid & ((ps_a == IN_ACK) | (ps_a == IN_NACK)) & (evt_a == now)
                e_pos = self._compact(due_a, self.MAX_EV)
                e_idx = jnp.where(
                    e_pos < self.A, as_idx[jnp.minimum(e_pos, self.A - 1)], NP
                )
            else:
                p_state = pkt[PS]
                due = ((p_state == IN_ACK) | (p_state == IN_NACK)) & (pkt[PEVT] == now)
                e_idx = self._compact(due, self.MAX_EV)
            e_valid = e_idx < NP
            E = pkt[:, jnp.minimum(e_idx, NP - 1)]  # (PF, MAX_EV) one gather
            e_conn = jnp.where(e_valid, E[PCONN], NC)  # NC = sentinel segment
            e_is_nack = e_valid & (E[PS] == IN_NACK)
            e_is_ack = e_valid & ~e_is_nack
            e_ev = jnp.where(e_valid, E[PEV], 0)
            e_ecn = e_valid & (E[PECN] == 1)
            e_cnt = jnp.where(e_valid, E[PACK], 0)
            e_seq = jnp.where(e_valid, E[PSEQ], 0)
            e_rtt = jnp.where(e_valid, now - E[PSEND], 0)

            # ONE stacked segment-sum covers the whole feedback stage.  Index =
            # (ACK round, conn): an ACK's round is its FIFO rank among
            # same-connection ACKs (slot order, unique per conn — computed once
            # by the segment-rank primitive, no per-round scatter-min
            # selection); non-ACK/pad events land via their real conn (rank
            # within the NC sentinel segment picks an arbitrary row, summed
            # out) so the round-summed leading fields still aggregate ALL
            # events, while the ACK-masked trailing fields keep the per-round
            # table clean.  Without trimming no packet can ever be IN_NACK
            # (only the arrivals trim branch creates them), so the NACK
            # bookkeeping — rtx marking, the cwnd decrement, two table fields
            # and a bitmap scatter — is statically compiled out.
            R_fb = cfg.feedback_rounds
            ack_seg = jnp.where(e_is_ack, e_conn, NC)
            e_rank = self._seg_rank_b(ack_seg, NC + 1)
            ridx = jnp.minimum(e_rank, R_fb) * (NC + 1) + e_conn
            fields = [
                jnp.where(e_is_nack, 1, e_cnt) if cfg.trimming else e_cnt,  # dec
                e_is_ack.astype(jnp.int32),
                jnp.where(e_is_ack, e_ev, 0),
                (e_ecn & e_is_ack).astype(jnp.int32),
                jnp.where(e_is_ack, e_rtt, 0),
            ]
            if cfg.trimming:
                already = self._bm_get(c_rcv, e_conn, e_seq, conn_axis)
                need_rtx = e_is_nack & ~already
                prev_rtx = self._bm_get(c_rtx, e_conn, e_seq, conn_axis)
                c_rtx = self._bm_max(c_rtx, e_conn, e_seq, need_rtx, conn_axis)
                fields += [
                    (need_rtx & ~prev_rtx).astype(jnp.int32),
                    e_is_nack.astype(jnp.int32),
                ]
            tbl = self._seg_sum_b(
                ridx, jnp.stack(fields), (R_fb + 1) * (NC + 1)
            ).reshape(len(fields), R_fb + 1, NC + 1)
            fb = jnp.sum(tbl, axis=1)  # rank-independent totals per conn
            c_inflight = c_inflight - fb[0, :NC]
            if cfg.trimming:
                c_rtx_count = c_rtx_count + fb[5, :NC]
                nacks_per_conn = fb[6, :NC]
                c_cwnd = jnp.clip(
                    c_cwnd - nacks_per_conn.astype(jnp.float32),
                    1.0,
                    float(cfg.max_cwnd_pkts),
                )

            # LB + CC updates: up to `feedback_rounds` exact rounds of one ACK
            # event per connection — round r's per-conn event is table row r.
            # Each round gets its own key off the tick stream (fold 4) so
            # repath draws differ per seed / row / tick / round; key-ignoring
            # LBs are bit-identical (fold_in consumes no randomness).
            k_ack = jax.random.fold_in(key, 4)
            for r in range(R_fb):
                conn_mask = tbl[1, r, :NC] > 0
                conn_ev = tbl[2, r, :NC]
                conn_ecn = tbl[3, r, :NC] > 0
                conn_rtt = tbl[4, r, :NC]
                c_cwnd, c_alpha = self._cc_on_ack(c_cwnd, c_alpha, conn_mask, conn_ecn, conn_rtt)
                with jax.named_scope("tick.lb"):
                    prev_lb = lb_state
                    lb_state = self.lb.on_ack(
                        lb_state, conn_mask, conn_ev, conn_ecn, now,
                        jax.random.fold_in(k_ack, r),
                    )
                    if emit_events:
                        lb_counts = lb_counts + self.lb.trace(
                            "ack", prev_lb, lb_state, conn_mask
                        )
            unprocessed = jnp.sum(
                (e_is_ack & (e_rank >= R_fb)).astype(jnp.int32)
            )

        # =============== 2. RTO ========================================
        with jax.named_scope("tick.rto"):
            # A packet's RTO can fire only at send_tick + rto_ticks: PSEND is
            # written once, at injection; orphaning and the live states
            # (FLYING, QUEUED, LOST_WAIT) never come back once left; c_done
            # never clears; and a row's ticks run contiguously.  A live,
            # unorphaned packet older than that was checked at that tick and
            # kept only because its connection was done, which it still is.
            # So the candidates are the packets sent exactly rto_ticks ago
            # (the first check after injection when rto_ticks < 1): at most
            # NH, since injection admits <= 1 packet per host per tick.  The
            # done-check and every write then stay NH rows wide.
            rto_age = max(int(cfg.rto_ticks), 1)
            if sparse:
                cand_a = (
                    as_valid
                    & ((ps_a == FLYING) | (ps_a == QUEUED) | (ps_a == LOST_WAIT))
                    & (pkt[PORPH, asx] != 1)
                    & ((now - pkt[PSEND, asx]) == rto_age)
                )
                c_pos = self._compact(cand_a, NH)
                c_idx = jnp.where(
                    c_pos < self.A, as_idx[jnp.minimum(c_pos, self.A - 1)], NP
                )
            else:
                cand = (
                    ((p_state == FLYING) | (p_state == QUEUED) | (p_state == LOST_WAIT))
                    & (pkt[PORPH] != 1)
                    & ((now - pkt[PSEND]) == rto_age)
                )
                c_idx = self._compact(cand, NH)
            Rp = pkt[:, jnp.minimum(c_idx, NP - 1)]  # (PF, NH)
            r_valid = (c_idx < NP) & ~c_done[jnp.clip(Rp[PCONN], 0, NC - 1)]
            timeouts_d = jnp.sum(r_valid.astype(jnp.int32))
            r_conn = jnp.where(r_valid, Rp[PCONN], NC)
            r_seq = jnp.where(r_valid, Rp[PSEQ], 0)
            rcv_already = self._bm_get(c_rcv, r_conn, r_seq, conn_axis)
            rto_need = r_valid & ~rcv_already
            prev_rtx_p = self._bm_get(c_rtx, r_conn, r_seq, conn_axis)
            c_rtx = self._bm_max(
                c_rtx, jnp.where(rto_need, r_conn, NC), r_seq, rto_need, conn_axis
            )
            rsum_rto = self._seg_sum_b(
                r_conn,
                jnp.stack([
                    (rto_need & ~prev_rtx_p).astype(jnp.int32),
                    r_valid.astype(jnp.int32),
                ]),
                NC + 1,
            )
            c_rtx_count = c_rtx_count + rsum_rto[0, :NC]
            rto_per_conn = rsum_rto[1, :NC]
            c_inflight = c_inflight - rto_per_conn
            c_cwnd = jnp.clip(
                c_cwnd - rto_per_conn.astype(jnp.float32), 1.0, float(cfg.max_cwnd_pkts)
            )
            with jax.named_scope("tick.lb"):
                prev_lb = lb_state
                lb_state = self.lb.on_timeout(
                    lb_state, rto_per_conn > 0, now, jax.random.fold_in(key, 5)
                )
                if emit_events:
                    lb_counts = lb_counts + self.lb.trace(
                        "timeout", prev_lb, lb_state, rto_per_conn > 0
                    )
            # free the feedback slots; orphan the fired packets and free the
            # LOST_WAIT ones: one whole-row scatter at <= MAX_EV + NH slots
            Rn = Rp.at[PS].set(jnp.where(Rp[PS] == LOST_WAIT, FREE, Rp[PS]))
            Rn = Rn.at[PORPH].set(1)
            pkt = pkt.at[:, jnp.concatenate([e_idx, jnp.where(r_valid, c_idx, NP)])].set(
                jnp.concatenate([E.at[PS].set(FREE), Rn], axis=1), mode="drop"
            )

        # =============== 3. service / dequeue ===========================
        with jax.named_scope("tick.service"):
            f_active = (now >= scn.f_start) & (now < scn.f_end)
            failed_q = (
                jnp.zeros((NQ + 1,), jnp.bool_)
                .at[jnp.where(f_active & (scn.f_kind == K_DOWN), scn.f_queue, NQ)]
                .max(True, mode="drop")[:NQ]
            )
            degraded_q = (
                jnp.zeros((NQ + 1,), jnp.bool_)
                .at[jnp.where(f_active & (scn.f_kind == K_DEGRADED), scn.f_queue, NQ)]
                .max(True, mode="drop")[:NQ]
            )
            # gray loss: per-queue fixed-point drop probability (param/GRAY_SCALE)
            # scatter-maxed from active kind-2 rows, compared against a uniform
            # draw on its own fold (3) of the tick key — independent of the RED
            # (1) and LB (2) streams, so schedules with no gray rows stay
            # bit-identical to runs predating the gray fault model.
            gray_p = (
                jnp.zeros((NQ + 1,), jnp.int32)
                .at[jnp.where(f_active & (scn.f_kind == K_GRAY), scn.f_queue, NQ)]
                .max(scn.f_param, mode="drop")[:NQ]
            )
            u_gray = jax.random.uniform(jax.random.fold_in(key, 3), (NQ,))
            gray_hit = (u_gray * GRAY_SCALE).astype(jnp.int32) < gray_p
            service_ok = ~(degraded_q & (now % 2 == 1))
            serve = (q_len > 0) & service_ok
            head_pid = qbuf[jnp.arange(NQ), q_head % QCAP]
            q_head = jnp.where(serve, q_head + 1, q_head)
            q_len = jnp.where(serve, q_len - 1, q_len)
            q_served = q_served + serve.astype(jnp.int32)

            pid = jnp.where(serve, head_pid, NP)  # NP = drop sentinel
            qid = jnp.arange(NQ, dtype=jnp.int32)
            # gray-dropped serves share the blackhole path (silent loss →
            # ST_DROPS_FAIL, LOST_WAIT awaiting RTO) but NOT the q_len_eff
            # routing penalty below: gray loss is invisible to the switches.
            blackhole = serve & (failed_q | gray_hit)
            is_final = serve & ~blackhole & (qid >= topo.t0_down_base)
            mid = serve & ~blackhole & ~is_final

            D = pkt[:, jnp.minimum(pid, NP - 1)]  # (PF, NQ) served-packet rows
            d_orph = serve & (D[PORPH] == 1)

            # blackholed: silent loss (failure — no trim); orphans are freed
            drops_fail_d = jnp.sum((blackhole & ~d_orph).astype(jnp.int32))

            # deliveries (≤ 1 per connection per tick — host downlink serves 1)
            dconn = jnp.where(is_final, D[PCONN], NC)
            dseq = jnp.where(is_final, D[PSEQ], 0)
            # deliveries only happen at the final-hop queues — the STATIC tail
            # [t0_down_base, NQ) of the queue axis (NH host downlinks) — so the
            # delivery-side scatters restrict to that slice: the dropped rows
            # are all sentinel/False no-ops, and scatter cost is rows × K
            fin = slice(topo.t0_down_base, NQ)
            was_done = c_done.at[dconn].get(mode="fill", fill_value=True)
            newly = is_final & ~self._bm_get(c_rcv, dconn, dseq, conn_axis)
            c_rcv = self._bm_max(
                c_rcv, dconn[fin], dseq[fin], is_final[fin], conn_axis
            )
            delivered_d = jnp.sum(newly.astype(jnp.int32))
            deliver_ackable = is_final & ~d_orph & ~was_done
            msg_of = scn.conn_msg.at[dconn].get(mode="fill", fill_value=BIG)
            # ≤1 delivery per conn per tick ⇒ the post-update per-conn counters
            # equal the pre-update gathers plus this queue's own contribution —
            # so `emit`/`first_done` are computable BEFORE the scatter and the
            # whole stage needs ONE stacked segment-sum.
            del_of = (
                c_delivered.at[dconn].get(mode="fill", fill_value=0)
                + newly.astype(jnp.int32)
            )
            now_done = del_of >= msg_of
            rxp = (
                c_rx_pending.at[dconn].get(mode="fill", fill_value=0)
                + deliver_ackable.astype(jnp.int32)
            )
            emit = deliver_ackable & ((rxp >= cfg.ack_coalesce) | now_done)
            first_done = is_final & now_done & ~was_done
            dsum = self._seg_sum_b(
                dconn[fin],
                jnp.stack([
                    newly.astype(jnp.int32)[fin],
                    deliver_ackable.astype(jnp.int32)[fin],
                    emit.astype(jnp.int32)[fin],
                    first_done.astype(jnp.int32)[fin],
                ]),
                NC + 1,
            )
            c_delivered = c_delivered + dsum[0, :NC]
            c_rx_pending = jnp.where(
                dsum[2, :NC] > 0, 0, c_rx_pending + dsum[1, :NC]
            )
            # completion bookkeeping
            first_done_c = dsum[3, :NC] > 0
            c_done = c_done | first_done_c
            c_done_tick = jnp.where(first_done_c, now, c_done_tick)

            # served-packet row rewrite (one scatter): blackhole / mid / final
            d_state = jnp.where(
                blackhole,
                jnp.where(d_orph, FREE, LOST_WAIT),
                jnp.where(
                    mid,
                    FLYING,
                    jnp.where(emit, IN_ACK, FREE),  # final hop: emitted ACK reuses slot
                ),
            )
            d_evt = jnp.where(
                mid,
                now + cfg.hop_latency_ticks,
                jnp.where(emit, now + cfg.ack_delay_ticks, D[PEVT]),
            )
            Dn = D.at[PS].set(d_state)
            Dn = Dn.at[PEVT].set(d_evt)
            Dn = Dn.at[PHOP].set(jnp.where(mid, D[PHOP] + 1, D[PHOP]))
            Dn = Dn.at[PCURQ].set(jnp.where(mid, qid, D[PCURQ]))
            Dn = Dn.at[PACK].set(jnp.where(emit, rxp, D[PACK]))
            pkt = pkt.at[:, pid].set(Dn, mode="drop")

        # =============== 4. arrivals / enqueue ==========================
        with jax.named_scope("tick.arrivals"):
            if sparse:
                arr_a = (
                    as_valid
                    & (pkt[PS, asx] == FLYING)
                    & (pkt[PEVT, asx] == now)
                )
                a_pos = self._compact(arr_a, self.MAX_ARR)
                a_idx = jnp.where(
                    a_pos < self.A, as_idx[jnp.minimum(a_pos, self.A - 1)], NP
                )
            else:
                p_state = pkt[PS]
                arr = (p_state == FLYING) & (pkt[PEVT] == now)
                a_idx = self._compact(arr, self.MAX_ARR)
            a_valid = a_idx < NP
            A = pkt[:, jnp.minimum(a_idx, NP - 1)]  # (PF, MAX_ARR)
            a_conn = jnp.where(a_valid, A[PCONN], 0)
            a_ev = jnp.where(a_valid, A[PEV], 0)
            a_inj = jnp.where(a_valid, A[PHOP], 1) == 0
            a_cur = jnp.where(a_valid, A[PCURQ], 0)
            a_src = scn.conn_src[jnp.clip(a_conn, 0, NC - 1)]
            a_dst = scn.conn_dst[jnp.clip(a_conn, 0, NC - 1)]
            # adaptive switches exclude locally-known failed ports (link down is
            # visible at the switch); hashing LBs ignore q_len entirely.
            q_len_eff = q_len + failed_q.astype(jnp.int32) * jnp.int32(4 * QCAP)
            with jax.named_scope("route.next_hop"):
                target = topo.next_queue(
                    a_inj, a_cur, a_conn, a_ev, a_src, a_dst, q_len_eff,
                    adaptive=self.lb.switch_adaptive,
                )
                target = jnp.where(a_valid, target, NQ)
            u_red = jax.random.uniform(jax.random.fold_in(key, 1), (self.MAX_ARR,))

            arrivals_backend = cfg.arrivals_backend
            if arrivals_backend == "auto":
                arrivals_backend = (
                    "pallas" if jax.default_backend() == "tpu" else "jnp"
                )
            if arrivals_backend == "pallas":
                # fused serve+rank+accept kernel (repro.kernels.queue_tick);
                # service already happened, so serve mask is all-zero here.
                from repro.kernels import ops as kernel_ops

                new_qlen, k_accept, _, pos = kernel_ops.queue_tick(
                    target, u_red, q_len, jnp.zeros((NQ,), jnp.int32),
                    QCAP, cfg.kmin, cfg.kmax,
                )
                accept = a_valid & k_accept
                q_len = new_qlen
            else:
                # FIFO rank among same-target arrivals (stable in slot order)
                rank = self._seg_rank_b(target, NQ + 1)
                qlen_t = q_len.at[target].get(mode="fill", fill_value=0)
                accept = a_valid & (rank < QCAP - qlen_t)
                pos = qlen_t + rank
                q_len = q_len.at[jnp.where(accept, target, NQ)].add(1, mode="drop")
            dropd = a_valid & ~accept
            mark_p = (
                jnp.clip(
                    (pos.astype(jnp.float32) - cfg.kmin) / float(cfg.kmax - cfg.kmin),
                    0.0,
                    1.0,
                )
                * cfg.pmax
            )
            mark = accept & (u_red < mark_p)
            ecn_marks_d = jnp.sum(mark.astype(jnp.int32))
            slot = (q_head.at[target].get(mode="fill", fill_value=0) + pos) % QCAP
            qbuf = qbuf.at[jnp.where(accept, target, NQ), slot].set(
                a_idx, mode="drop"
            )
            # congestion drops: trim → NACK; else silent (await RTO); orphans free
            a_orph = a_valid & (A[PORPH] == 1)
            drops_cong_d = jnp.sum((dropd & ~a_orph).astype(jnp.int32))
            if cfg.trimming:
                dstate = jnp.where(a_orph, FREE, IN_NACK)
            else:
                dstate = jnp.where(a_orph, FREE, LOST_WAIT)
            An = A.at[PS].set(jnp.where(accept, QUEUED, dstate))
            An = An.at[PCURQ].set(jnp.where(accept, target, A[PCURQ]))
            An = An.at[PECN].set(A[PECN] | mark.astype(jnp.int32))
            if cfg.trimming:
                An = An.at[PEVT].set(
                    jnp.where(dropd & ~a_orph, now + cfg.nack_delay_ticks, A[PEVT])
                )
            pkt = pkt.at[:, a_idx].set(An, mode="drop")

        # =============== 5. injection ===================================
        with jax.named_scope("tick.injection"):
            started = (now >= scn.conn_start) & (
                (scn.conn_dep < 0) | c_done[jnp.clip(scn.conn_dep, 0, NC - 1)]
            )
            has_work = (c_rtx_count > 0) | (c_next_new < scn.conn_msg)
            can = (
                started
                & ~c_done
                & has_work
                & (c_inflight < jnp.floor(c_cwnd).astype(jnp.int32))
            )
            hc = scn.host_conns  # (NH, CPH)
            elig = can[jnp.clip(hc, 0, NC - 1)] & (hc >= 0)
            ordr = (jnp.arange(self.CPH)[None, :] - h_rr[:, None]) % self.CPH
            score = jnp.where(elig, ordr, BIG)
            pick_local = jnp.argmin(score, axis=1).astype(jnp.int32)
            any_pick = jnp.min(score, axis=1) < BIG
            # free-slot allocation (ring pop)
            srank = jnp.cumsum(any_pick.astype(jnp.int32)) - 1
            can_alloc = srank < fl_count
            if sparse:
                # active-set capacity gate.  Since every non-FREE slot is
                # tracked, as_count + fl_count == NP always — so with A == NP
                # this conjunct is exactly `srank < fl_count` again and the
                # sparse path stays bit-identical to dense; when A binds, the
                # overflow surfaces as counted alloc-fails, never lost slots.
                can_alloc = can_alloc & (as_count + srank < self.A)
            sendh = any_pick & can_alloc
            alloc_fail_d = jnp.sum((any_pick & ~can_alloc).astype(jnp.int32))
            n_alloc = jnp.sum(sendh.astype(jnp.int32))
            slot_p = fl[(fl_head + srank) % NP]
            fl_head = (fl_head + n_alloc) % NP
            fl_count = fl_count - n_alloc

            pick_conn = jnp.where(
                sendh, hc[jnp.arange(NH), pick_local], NC
            )  # NC sentinel
            h_rr = jnp.where(sendh, (pick_local + 1) % self.CPH, h_rr)
            # seq selection: retransmissions first
            pick_cc = jnp.clip(pick_conn, 0, NC - 1)
            use_rtx = c_rtx_count[pick_cc] > 0
            rtx_rows = self._bm_rows(c_rtx, pick_cc, conn_axis)  # (NH, MSG)
            rtx_seq = jnp.argmax(rtx_rows, axis=1).astype(jnp.int32)
            new_seq = c_next_new[pick_cc]
            seq = jnp.where(use_rtx, rtx_seq, new_seq)
            c_rtx = self._bm_set_false(
                c_rtx, jnp.where(sendh & use_rtx, pick_conn, NC), rtx_seq, conn_axis
            )
            # each host picks <= 1 conn and a conn lives on one host, so
            # per-conn injection counts are 0/1: one stacked segment-sum covers
            # the send mask, rtx/new splits and the inflight increment
            isum = self._seg_sum_b(
                pick_conn,
                jnp.stack([
                    sendh.astype(jnp.int32),
                    (sendh & use_rtx).astype(jnp.int32),
                ]),
                NC + 1,
            )
            send_mask = isum[0, :NC] > 0
            c_rtx_count = c_rtx_count - isum[1, :NC]
            c_next_new = c_next_new + (isum[0] - isum[1])[:NC]
            c_inflight = c_inflight + isum[0, :NC]
            injected_d = n_alloc

            with jax.named_scope("tick.lb"):
                # the load balancer stamps the EV (REPS Algorithm 2)
                prev_lb = lb_state
                evs, lb_state = self.lb.choose_ev(
                    lb_state, send_mask, jax.random.fold_in(key, 2), now
                )
                if emit_events:
                    lb_counts = lb_counts + self.lb.trace(
                        "choose", prev_lb, lb_state, send_mask
                    )
            pkt_ev = evs[pick_cc]

            wslot = jnp.where(sendh, slot_p, NP)
            W = jnp.stack([
                jnp.full((NH,), FLYING, jnp.int32),  # PS
                pick_conn,  # PCONN
                pkt_ev,  # PEV
                seq,  # PSEQ
                jnp.zeros((NH,), jnp.int32),  # PHOP
                jnp.full((NH,), -1, jnp.int32),  # PCURQ
                jnp.full((NH,), now, jnp.int32),  # PSEND
                jnp.full((NH,), now + cfg.hop_latency_ticks, jnp.int32),  # PEVT
                jnp.zeros((NH,), jnp.int32),  # PECN
                jnp.zeros((NH,), jnp.int32),  # PORPH
                jnp.zeros((NH,), jnp.int32),  # PACK
            ])
            # one (PF, NH) block scatter writes the whole new-packet rows
            pkt = pkt.at[:, wslot].set(W, mode="drop")

        # =============== 6. free-list push ==============================
        with jax.named_scope("tick.freelist"):
            # slots popped and re-used this tick are FLYING now, not FREE — no
            # conflict with the push below.
            if sparse:
                fs_a = jnp.where(as_valid, pkt[PS, asx], FREE)  # post-tick states
                freed_a = as_valid & (fs_a == FREE) & (entry_ps_a != FREE)
                f_pos = self._compact(freed_a, self.MAX_FREE)
                f_idx2 = jnp.where(
                    f_pos < self.A, as_idx[jnp.minimum(f_pos, self.A - 1)], NP
                )
            else:
                freed = (pkt[PS] == FREE) & (state_at_entry != FREE)
                f_idx2 = self._compact(freed, self.MAX_FREE)
            f_val = f_idx2 < NP
            n_freed = jnp.sum(f_val.astype(jnp.int32))
            if self.MAX_FREE <= NP and not sparse:
                # the push targets a contiguous (mod NP) ring segment, so it is
                # a rotate + static-slice blend + rotate back — a scatter here
                # would serialize over MAX_FREE rows per sweep lane on CPU/TPU
                start = (fl_head + fl_count) % NP
                rot = jnp.roll(fl, -start)
                head = jnp.where(
                    jnp.arange(self.MAX_FREE, dtype=jnp.int32) < n_freed,
                    f_idx2,
                    rot[: self.MAX_FREE],
                )
                fl = jnp.roll(rot.at[: self.MAX_FREE].set(head), start)
            else:
                # positional scatter: O(MAX_FREE) instead of the O(NP) roll —
                # always in sparse mode (that roll is exactly the dense cost
                # the active set removes), or under a tiny pkt_slots pin.
                # Both branches write identical fl contents.
                frank = jnp.cumsum(f_val.astype(jnp.int32)) - 1
                fpos = (fl_head + fl_count + frank) % NP
                fl = fl.at[jnp.where(f_val, fpos, NP)].set(f_idx2, mode="drop")
            fl_count = fl_count + n_freed

            if sparse:
                # active-set maintenance: drop freed slots, add this tick's
                # allocations (wslot), re-sort ascending.  Real entries ≤ A by
                # the injection gate; NP sentinels sort to the tail.
                with jax.named_scope("tick.active_set"):
                    alive = as_valid & (fs_a != FREE)
                    cand = jnp.concatenate([jnp.where(alive, as_idx, NP), wslot])
                    as_idx = jnp.sort(cand)[: self.A]
                    as_count = jnp.sum(alive.astype(jnp.int32)) + n_alloc

            # =============== 7. fused stats update ==========================
            s_stats = s_stats + jnp.stack([
                drops_cong_d, drops_fail_d, timeouts_d, delivered_d,
                ecn_marks_d, injected_d, unprocessed, alloc_fail_d,
            ])

        if conn_axis is not None:
            # conn-sharded exit: hand back only this device's block of the
            # gathered per-conn vectors (inverse of the entry all_gather —
            # every device computed the identical full-shape values).
            with jax.named_scope("tick.conn_exchange"):
                def cslice(x):
                    return jax.lax.dynamic_slice_in_dim(x, coff, NCd, axis=0)

                (c_inflight, c_next_new, c_delivered, c_rx_pending, c_done,
                 c_done_tick, c_rtx_count, c_cwnd, c_alpha) = (
                    cslice(c_inflight), cslice(c_next_new),
                    cslice(c_delivered), cslice(c_rx_pending),
                    cslice(c_done), cslice(c_done_tick),
                    cslice(c_rtx_count), cslice(c_cwnd), cslice(c_alpha),
                )

        new_state = SimState(
            pkt,
            qbuf, q_head, q_len, q_served,
            c_inflight, c_next_new, c_delivered, c_rx_pending, c_done,
            c_done_tick, c_rtx_count, c_rtx, c_rcv, c_cwnd, c_alpha,
            h_rr, lb_state, fl, fl_head, fl_count, s_stats,
            as_idx, as_count,
        )
        trace = TickTrace(
            max_qlen=jnp.max(q_len),
            sum_qlen=jnp.sum(q_len),
            drops=s_stats[ST_DROPS_CONG] + s_stats[ST_DROPS_FAIL],
            timeouts=s_stats[ST_TIMEOUTS],
            delivered=s_stats[ST_DELIVERED],
            injected=s_stats[ST_INJECTED],
            watch_qlen=q_len[scn.watch],
            watch_served=serve[scn.watch].astype(jnp.int32),
        )
        if emit_events:
            # failure-window activation edge, deduped per queue exactly like
            # the service stage's scatter-max (pad rows repeat row 0 and
            # union away, so counts match the declared schedule).
            f_on = (scn.f_start == now) & (now < scn.f_end)
            fail_q = (
                jnp.zeros((NQ + 1,), jnp.bool_)
                .at[jnp.where(f_on, scn.f_queue, NQ)]
                .max(True, mode="drop")[:NQ]
            )
            events = TickEvents(
                lb=lb_counts,
                fail_start=jnp.sum(fail_q.astype(jnp.int32)),
            )
            return new_state, trace, events
        return new_state, trace

    # ------------------------------------------------------------------
    def probe(
        self,
        prev: SimState,
        new: SimState,
        tick: jax.Array,
        scn: ScenarioArrays,
    ) -> Probe:
        """Derive the tick's ``Probe`` from the states around it.

        Pure in (prev, new, tick, scn) like ``step_scenario`` itself, so the
        sweep engine can vmap it over heterogeneous rows.  Deltas telescope:
        summing ``stats_delta`` over any tick range reproduces the final
        ``s_stats`` of that range bit-exactly.
        """
        now = tick.astype(jnp.int32)
        done_now = new.c_done & ~prev.c_done
        served = new.q_served - prev.q_served
        return Probe(
            now=now,
            q_len=new.q_len,
            served=served,
            watch_qlen=new.q_len[scn.watch],
            watch_served=served[scn.watch],
            stats_delta=new.s_stats - prev.s_stats,
            done_now=done_now,
            fct=jnp.where(done_now, now - scn.conn_start, 0).astype(jnp.int32),
        )

    def step_probe(
        self,
        state: SimState,
        tick: jax.Array,
        base_key: jax.Array,
        scn: ScenarioArrays,
        conn_axis: str | None = None,
    ) -> tuple[SimState, Probe]:
        """One tick that emits a ``Probe`` instead of a host-bound trace —
        the summary-collection analogue of ``step_scenario`` (the unused
        ``TickTrace`` is dead code XLA eliminates).  Under a conn mesh the
        probe's (NC,) fields (done_now / fct) are per-device conn shards,
        consistent with the sharded carry."""
        new, _ = self.step_scenario(state, tick, base_key, scn, conn_axis=conn_axis)
        with jax.named_scope("tick.telemetry"):
            return new, self.probe(state, new, tick, scn)

    def step_events(
        self,
        state: SimState,
        tick: jax.Array,
        base_key: jax.Array,
        scn: ScenarioArrays,
        conn_axis: str | None = None,
    ) -> tuple[SimState, Probe, "TickEvents"]:
        """``step_probe`` plus the flight recorder's ``TickEvents`` — the
        tick body the sweep engine scans when a ``TraceSpec`` is active."""
        new, _, events = self.step_scenario(
            state, tick, base_key, scn, emit_events=True, conn_axis=conn_axis
        )
        with jax.named_scope("tick.telemetry"):
            return new, self.probe(state, new, tick, scn), events

    # ------------------------------------------------------------------
    @functools.partial(jax.jit, static_argnums=(0, 1))
    def _run(self, n_ticks: int, state: SimState):
        ticks = jnp.arange(n_ticks, dtype=jnp.int32)
        return jax.lax.scan(self.tick_fn, state, ticks)

    def run(self, n_ticks: int, state: SimState | None = None):
        """Run the simulation for n_ticks; returns (final_state, trace)."""
        if state is None:
            state = self.init_state()
        return self._run(n_ticks, state)

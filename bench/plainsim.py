"""A plain packet-level simulator of one sweep row: the reference of ``correct``.

It is written from the model's rules (queues, ECN marking, DCTCP, RTO,
ECMP / OPS / REPS entropy choice, the summary sketch), not from the
program's code, and imports nothing of the program: it takes the cell's
numbers (a ``SimConfig``-like object read by attribute), the generated
workload and failure arrays, the row's seed and its load balancer's name
and arguments.  Straightforward formulations throughout: one row, no
``vmap``, no ``lax.switch``, no bucket padding, no kernels; per-connection
sums are scatter-adds and FIFO ranks a pairwise count.

The model, one tick ``t`` (the order is part of the model):

1. feedback: ACKs due now (slot order) lower the sender's in-flight count
   by the packets they acknowledge; the first ``feedback_rounds`` ACKs of
   each connection, in order, update DCTCP and the load balancer; later
   ones count as unprocessed;
2. RTO: a live packet of an unfinished connection sent ``rto`` ticks ago
   times out (at most one per host per tick): its sequence number is
   marked for retransmission unless received, the window drops by one, the
   packet is orphaned, and one waiting in ``LOST_WAIT`` is freed;
3. service: every non-empty queue dequeues its head; a link that is down
   drops it silently (``LOST_WAIT``, or freed if orphaned); a host
   downlink delivers it (SACK bitmap, completion, one ACK per
   ``ack_coalesce`` packets, ``ack_delay`` ticks later); any other queue
   forwards it ``hop_latency`` ticks later;
4. arrivals: packets due at their next hop are ranked FIFO in slot order
   per target queue, tail-dropped past the capacity, RED-marked on a
   uniform draw per arrival;
5. injection: each host sends at most one packet, round-robin over its
   eligible connections (window-limited), retransmissions first, into a
   slot popped from the free-list ring; the load balancer stamps its EV;
6. the slots freed this tick are pushed on the ring in ascending order.

Random draws use JAX's threefry keys as the model defines them: the row's
key is ``PRNGKey(seed)``, tick ``t`` folds in ``t``, then 1 for the RED
uniforms and 2 for the load balancer's EVs; ECMP's static EVs fold 777
into the row key.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# packet states
FREE, FLYING, QUEUED, IN_ACK, LOST_WAIT = 0, 1, 2, 3, 5
BIG = 2**30
# counters, in the order of the program's stats vector and sketch
STATS = ("drops_cong", "drops_fail", "timeouts", "delivered", "ecn_marks",
         "injected", "unprocessed", "alloc_fails")
PACKET_FIELDS = ("state", "conn", "ev", "seq", "hop", "curq", "send", "evt",
                 "ecn", "orph", "ack")
SUM_SHIFT = 20  # the sketch's wide sums carry 2**20 units into a high word


def _pow2_at_least(x: int) -> int:
    return 1 << max(1, int(np.ceil(np.log2(max(x, 2)))))


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The row's sizes, derived from the cell's numbers and workload."""

    NH: int  # hosts
    NC: int  # connections
    NQ: int  # queues: ToR uplinks, spine downlinks, host downlinks
    NP: int  # packet slots
    A: int  # slots that may be allocated at once (scale mode's cap)
    MSG: int  # SACK / retransmit bitmap width
    CPH: int  # connections per host
    T: int  # ToRs
    H: int  # hosts per ToR
    U: int  # uplinks per ToR (= spines)

    @property
    def max_arrivals(self) -> int:
        return self.NQ + self.NH

    @property
    def down_base(self) -> int:
        return 2 * self.T * self.U


def shapes_of(cfg, src: np.ndarray, msg: np.ndarray) -> Shapes:
    if cfg.tiers != 2 or cfg.fabric:
        raise NotImplementedError("the reference models the 2-tier fat tree")
    NH, H, U = cfg.n_hosts, cfg.hosts_per_tor, cfg.uplinks_per_tor
    T = NH // H
    NC = len(src)
    NP = int(cfg.pkt_slots) or _pow2_at_least(
        NC * cfg.max_cwnd_pkts + 4 * NH + 64)
    A = NP
    if cfg.conn_sharding:
        # scale mode budgets slots by lifetime, not by window: a slot is
        # freed within an RTO, the ACK and NACK returns and a full drain
        # of the 3-hop path (a full queue at half rate per hop); hosts
        # admit one packet a tick each
        life = (cfg.rto_ticks + cfg.ack_delay_ticks + cfg.nack_delay_ticks
                + 3 * (cfg.hop_latency_ticks + 2 * cfg.queue_capacity))
        bound = _pow2_at_least(NH * life + 4 * NH + 64)
        NP = int(cfg.pkt_slots) or min(NP, bound)
        A = min(int(cfg.active_slots) or bound, NP)
    msg_auto = min(cfg.max_msg_pkts, _pow2_at_least(int(msg.max())))
    cph = int(np.bincount(src, minlength=NH).max())
    return Shapes(
        NH=NH, NC=NC, NQ=2 * T * U + NH,
        NP=NP, A=A,
        MSG=int(cfg.msg_slots) or msg_auto,
        CPH=max(int(cfg.conns_per_host), cph), T=T, H=H, U=U,
    )


def first_set(mask, k: int):
    """The first ``k`` set positions of ``mask`` in ascending order, padded
    with ``len(mask)``: where the running count of set bits reaches 1, 2,
    ..., k."""
    count = jnp.cumsum(mask.astype(jnp.int32))
    return jnp.searchsorted(count, jnp.arange(1, k + 1, dtype=jnp.int32),
                            side="left").astype(jnp.int32)


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def path_hash(conn, ev, tor, nports: int):
    """The switches' EV hash: which of ``nports`` uplinks a packet takes."""
    u = lambda v: v.astype(jnp.uint32)  # noqa: E731
    h = _mix32(u(conn) * jnp.uint32(0x9E3779B1)
               ^ u(ev) * jnp.uint32(0x85EBCA77)
               ^ u(tor) * jnp.uint32(0xC2B2AE3D))
    return (h % jnp.uint32(nports)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Load balancers
# ---------------------------------------------------------------------------
class Ecmp:
    """One EV per connection, drawn once."""

    def __init__(self, evs_size: int):
        self.evs = evs_size

    def init(self, nc, row_key):
        return jax.random.randint(jax.random.fold_in(row_key, 777), (nc,), 0,
                                  self.evs, jnp.int32)

    def on_ack(self, st, mask, ev, ecn, now):
        return st

    def on_timeout(self, st, mask, now):
        return st

    def choose(self, st, mask, key):
        return st, st


class Ops(Ecmp):
    """A fresh uniform EV for every packet."""

    def init(self, nc, row_key):
        return jnp.zeros((nc,), jnp.int32)

    def choose(self, st, mask, key):
        return jax.random.randint(key, st.shape, 0, self.evs, jnp.int32), st


class Reps(Ecmp):
    """REPS (the paper's Algorithms 1 and 2): a ring of ``buffer_size``
    EVs echoed by clean ACKs, consumed oldest-valid first; explore fresh EVs
    while the buffer is empty, drained (unless freezing) or for the first
    ``num_pkts_bdp`` sends; a timeout outside exploration freezes the ring
    for ``freezing_timeout`` ticks, recycling EVs even when invalid."""

    def __init__(self, evs_size: int, buffer_size: int = 8,
                 num_pkts_bdp: int = 32, freezing_timeout: int = 1024):
        super().__init__(evs_size)
        self.B, self.bdp = buffer_size, num_pkts_bdp
        self.freeze_for = freezing_timeout

    def init(self, nc, row_key):
        z = jnp.zeros((nc,), jnp.int32)
        return dict(buf_ev=jnp.zeros((nc, self.B), jnp.int32),
                    buf_valid=jnp.zeros((nc, self.B), bool),
                    head=z, num_valid=z,
                    explore_counter=jnp.full((nc,), self.bdp, jnp.int32),
                    is_freezing=jnp.zeros((nc,), bool), exit_freezing=z,
                    n_cached=z)

    def on_ack(self, st, mask, ev, ecn, now):
        keep = mask & ~ecn  # an ECN-marked ACK's EV is discarded
        rows = jnp.arange(st["head"].shape[0])
        was_valid = st["buf_valid"][rows, st["head"]]
        at_head = keep[:, None] & (jnp.arange(self.B)[None, :]
                                   == st["head"][:, None])
        thaw = keep & st["is_freezing"] & (now > st["exit_freezing"])
        return dict(
            buf_ev=jnp.where(at_head, ev[:, None], st["buf_ev"]),
            buf_valid=st["buf_valid"] | at_head,
            head=jnp.where(keep, (st["head"] + 1) % self.B, st["head"]),
            num_valid=st["num_valid"] + (keep & ~was_valid),
            explore_counter=jnp.where(thaw, self.bdp, st["explore_counter"]),
            is_freezing=st["is_freezing"] & ~thaw,
            exit_freezing=st["exit_freezing"],
            n_cached=st["n_cached"] + keep,
        )

    def on_timeout(self, st, mask, now):
        enter = mask & ~st["is_freezing"] & (st["explore_counter"] == 0)
        return {**st,
                "is_freezing": st["is_freezing"] | enter,
                "exit_freezing": jnp.where(enter, now + self.freeze_for,
                                           st["exit_freezing"])}

    def choose(self, st, mask, key):
        fresh = jax.random.randint(key, st["head"].shape, 0, self.evs,
                                   jnp.int32)
        explore = mask & ((st["n_cached"] == 0)
                          | ((st["num_valid"] == 0) & ~st["is_freezing"])
                          | (st["explore_counter"] > 0))
        take = mask & ~explore
        pop = take & (st["num_valid"] > 0)  # oldest valid entry
        recycle = take & (st["num_valid"] == 0)  # frozen: entry at head
        slot = jnp.where(pop, (st["head"] - st["num_valid"]) % self.B,
                         st["head"])
        rows = jnp.arange(slot.shape[0])
        evs = jnp.where(take, st["buf_ev"][rows, slot], fresh)
        at_slot = pop[:, None] & (jnp.arange(self.B)[None, :]
                                  == slot[:, None])
        return evs, {
            **st,
            "buf_valid": st["buf_valid"] & ~at_slot,
            "num_valid": st["num_valid"] - pop,
            "head": jnp.where(recycle, (st["head"] + 1) % self.B, st["head"]),
            "explore_counter": jnp.where(
                explore, jnp.maximum(st["explore_counter"] - 1, 0),
                st["explore_counter"]),
        }


LBS = {"ecmp": Ecmp, "ops": Ops, "reps": Reps}


def make_lb(name: str, kwargs: dict):
    if name not in LBS:
        raise NotImplementedError(f"the reference has no load balancer {name!r}")
    return LBS[name](**kwargs)


# ---------------------------------------------------------------------------
# The summary sketch
# ---------------------------------------------------------------------------
def _wide_add(hi, lo, delta):
    lo = lo + delta
    return hi + (lo >> SUM_SHIFT), lo & ((1 << SUM_SHIFT) - 1)


class Sketch:
    """The summary telemetry of a row: counter totals; FCT count, sum, min
    and max, last completion tick, queue-length max and sum; a 64-bin log
    histogram of FCTs and a 32-bin one of non-zero queue lengths; 24
    windows of watched-queue service and occupancy and of the counters;
    the first failure drop, and the first timeout and delivery after it.
    ``flat`` lays it out word for word as the program's sketch does."""

    def __init__(self, horizon: int, qcap: int, n_watch: int,
                 fct_bins: int = 64, qlen_bins: int = 32, windows: int = 24):
        def edges(hi, n):
            return np.geomspace(1.0, float(max(hi, 2)), n + 1).astype(np.float32)

        self.fct_edges = edges(horizon, fct_bins)
        self.qlen_edges = edges(qcap, qlen_bins)
        self.stride = max(1, -(-horizon // windows))
        self.nw = -(-horizon // self.stride)
        self.nwatch = n_watch

    def init(self):
        z = jnp.zeros((), jnp.int32)
        nb_f, nb_q = len(self.fct_edges) - 1, len(self.qlen_edges) - 1
        zs = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
        return dict(
            totals=zs(len(STATS)),
            fct_count=z, fct_sum_hi=z, fct_sum_lo=z,
            fct_min=jnp.int32(BIG), fct_max=jnp.int32(-1),
            done_tick_max=jnp.int32(-1), qlen_max=z, qlen_sum_hi=z,
            qlen_sum_lo=z,
            fct_hi=zs(nb_f), fct_lo=zs(nb_f), qlen_hi=zs(nb_q),
            qlen_lo=zs(nb_q),
            util=zs(self.nw, self.nwatch), wqlen=zs(self.nw, self.nwatch),
            wstats=zs(self.nw, len(STATS)),
            first_drop=jnp.int32(BIG), first_timeout=jnp.int32(BIG),
            first_redeliver=jnp.int32(BIG),
        )

    @staticmethod
    def _hist(hi, lo, edges, vals, mask):
        n = len(edges) - 1
        b = jnp.clip(jnp.searchsorted(jnp.asarray(edges),
                                      vals.astype(jnp.float32),
                                      side="right") - 1, 0, n - 1)
        counts = jnp.zeros((n,), jnp.int32).at[b].add(mask.astype(jnp.int32))
        return _wide_add(hi, lo, counts)

    def update(self, s, now, q_len, served, watch, delta, done_now, fct):
        fct_hi, fct_lo = _wide_add(s["fct_sum_hi"], s["fct_sum_lo"],
                                   jnp.sum(fct))
        q_hi, q_lo = _wide_add(s["qlen_sum_hi"], s["qlen_sum_lo"],
                               jnp.sum(q_len))
        h_f = self._hist(s["fct_hi"], s["fct_lo"], self.fct_edges, fct,
                         done_now)
        h_q = self._hist(s["qlen_hi"], s["qlen_lo"], self.qlen_edges, q_len,
                         q_len > 0)
        w = jnp.minimum(now // self.stride, self.nw - 1)
        first_drop = jnp.minimum(s["first_drop"],
                                 jnp.where(delta[1] > 0, now, BIG))
        after = now > first_drop
        return dict(
            totals=s["totals"] + delta,
            fct_count=s["fct_count"] + jnp.sum(done_now.astype(jnp.int32)),
            fct_sum_hi=fct_hi, fct_sum_lo=fct_lo,
            fct_min=jnp.minimum(s["fct_min"],
                                jnp.min(jnp.where(done_now, fct, BIG))),
            fct_max=jnp.maximum(s["fct_max"],
                                jnp.max(jnp.where(done_now, fct, -1))),
            done_tick_max=jnp.maximum(
                s["done_tick_max"], jnp.max(jnp.where(done_now, now, -1))),
            qlen_max=jnp.maximum(s["qlen_max"], jnp.max(q_len)),
            qlen_sum_hi=q_hi, qlen_sum_lo=q_lo,
            fct_hi=h_f[0], fct_lo=h_f[1], qlen_hi=h_q[0], qlen_lo=h_q[1],
            util=s["util"].at[w].add(served[watch]),
            wqlen=s["wqlen"].at[w].add(q_len[watch]),
            wstats=s["wstats"].at[w].add(delta),
            first_drop=first_drop,
            first_timeout=jnp.minimum(
                s["first_timeout"],
                jnp.where((delta[2] > 0) & after, now, BIG)),
            first_redeliver=jnp.minimum(
                s["first_redeliver"],
                jnp.where((delta[3] > 0) & after, now, BIG)),
        )

    ORDER = ("totals", "fct_count", "fct_sum_hi", "fct_sum_lo", "fct_min",
             "fct_max", "done_tick_max", "qlen_max", "qlen_sum_hi",
             "qlen_sum_lo", "fct_hi", "fct_lo", "qlen_hi", "qlen_lo", "util",
             "wqlen", "wstats", "first_drop", "first_timeout",
             "first_redeliver")

    @classmethod
    def flat(cls, s) -> np.ndarray:
        return np.concatenate([np.asarray(s[k], np.int32).reshape(-1)
                               for k in cls.ORDER])


# ---------------------------------------------------------------------------
# The row
# ---------------------------------------------------------------------------
class Row:
    """One row: its sizes, scenario and load balancer; ``tick`` is pure."""

    def __init__(self, cfg, workload, failures, seed: int, lb: str,
                 lb_kwargs: dict, horizon: int, collect: str,
                 control: bool = False):
        if cfg.trimming or cfg.cc != "dctcp":
            raise NotImplementedError("the reference models DCTCP without "
                                      "trimming")
        src = np.asarray(workload.src, np.int64)
        self.sh = sh = shapes_of(cfg, src, np.asarray(workload.msg_pkts))
        self.c = cfg
        hc = np.full((sh.NH, sh.CPH), -1, np.int32)
        fill = np.zeros(sh.NH, np.int64)
        for c, h in enumerate(src):  # each host's connections, in id order
            hc[h, fill[h]] = c
            fill[h] += 1
        f = failures
        if f is not None and len(f.queue):
            if np.any(np.asarray(f.kind) != 0):
                raise NotImplementedError("the reference models links down")
            fq, fs, fe = f.queue, f.start, f.end
        else:
            fq = fs = fe = np.zeros((0,), np.int32)
        i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
        # the row's scenario: arguments of the compiled tick, so that one
        # program serves every seed
        self.scn = dict(
            src=i32(workload.src), dst=i32(workload.dst),
            msg=i32(workload.msg_pkts), start=i32(workload.start),
            dep=i32(workload.dep), hc=i32(hc), f_q=i32(fq), f_s=i32(fs),
            f_e=i32(fe), key=jax.random.PRNGKey(seed),
        )
        self.watch = np.arange(min(cfg.n_watch_queues, sh.U), dtype=np.int32)
        self.lb = make_lb(lb, {"evs_size": cfg.evs_size, **lb_kwargs})
        self.sketch = (Sketch(horizon, cfg.queue_capacity, len(self.watch))
                       if collect == "summary" else None)
        self.control = control

    # -- state ---------------------------------------------------------
    def init(self):
        sh, c = self.sh, self.c
        zi = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
        st = dict(
            pkt={k: zi(sh.NP) for k in PACKET_FIELDS},
            qbuf=zi(sh.NQ, c.queue_capacity), q_head=zi(sh.NQ),
            q_len=zi(sh.NQ), q_served=zi(sh.NQ),
            inflight=zi(sh.NC), next_new=zi(sh.NC), delivered=zi(sh.NC),
            rx_pending=zi(sh.NC), done=jnp.zeros((sh.NC,), bool),
            done_tick=jnp.full((sh.NC,), -1, jnp.int32), rtx_count=zi(sh.NC),
            rtx=jnp.zeros((sh.NC, sh.MSG), bool),
            rcv=jnp.zeros((sh.NC, sh.MSG), bool),
            cwnd=jnp.full((sh.NC,), float(c.init_cwnd_pkts), jnp.float32),
            alpha=jnp.zeros((sh.NC,), jnp.float32),
            rr=zi(sh.NH), lb=self.lb.init(sh.NC, self.scn["key"]),
            fl=jnp.arange(sh.NP, dtype=jnp.int32), fl_head=jnp.int32(0),
            fl_count=jnp.int32(sh.NP), stats=zi(len(STATS)),
        )
        return st, (self.sketch.init() if self.sketch else None)

    # -- one tick -------------------------------------------------------
    def _dctcp(self, cwnd, alpha, mask, ecn):
        g = self.c.dctcp_g
        alpha = jnp.where(mask, (1 - g) * alpha + g * ecn.astype(jnp.float32),
                          alpha)
        up = cwnd + 1.0 / jnp.maximum(cwnd, 1.0)
        down = cwnd - alpha / 2.0
        cwnd = jnp.where(mask, jnp.where(ecn, down, up), cwnd)
        return jnp.clip(cwnd, 1.0, float(self.c.max_cwnd_pkts)), alpha

    def _next_queue(self, scn, first_hop, cur, conn, ev):
        sh = self.sh
        s, d = scn["src"][conn], scn["dst"][conn]
        s_tor, d_tor = s // sh.H, d // sh.H
        host_down = sh.down_base + d
        uplink = s_tor * sh.U + path_hash(conn, ev, s_tor, sh.U)
        on_uplink = cur < sh.T * sh.U
        spine_down = sh.T * sh.U + (cur % sh.U) * sh.T + d_tor
        return jnp.where(first_hop,
                         jnp.where(s_tor == d_tor, host_down, uplink),
                         jnp.where(on_uplink, spine_down, host_down))

    def tick(self, st, t, scn):
        sh, c = self.sh, self.c
        NP, NC, NQ, NH = sh.NP, sh.NC, sh.NQ, sh.NH
        QCAP = c.queue_capacity
        now = t.astype(jnp.int32)
        key = jax.random.fold_in(scn["key"], t)
        p = dict(st["pkt"])
        entry_state = p["state"]
        stats = dict.fromkeys(STATS, jnp.int32(0))
        cwnd, alpha, lb = st["cwnd"], st["alpha"], st["lb"]
        inflight, rtx, rcv = st["inflight"], st["rtx"], st["rcv"]
        rtx_count = st["rtx_count"]
        # 1. feedback
        due = (p["state"] == IN_ACK) & (p["evt"] == now)
        ev_slot = first_set(due, NH)
        real = ev_slot < NP
        e = {k: v[jnp.minimum(ev_slot, NP - 1)] for k, v in p.items()}
        e_conn = jnp.where(real, e["conn"], NC)
        earlier = jnp.arange(NH)[None, :] < jnp.arange(NH)[:, None]
        rank = jnp.sum(earlier & (e_conn[None, :] == e_conn[:, None])
                       & real[None, :], axis=1)
        inflight = inflight - jnp.zeros((NC,), jnp.int32).at[e_conn].add(
            jnp.where(real, e["ack"], 0), mode="drop")
        for r in range(c.feedback_rounds):
            this = real & (rank == r)
            at = jnp.where(this, e_conn, NC)
            mask = jnp.zeros((NC,), bool).at[at].set(True, mode="drop")
            ev = jnp.zeros((NC,), jnp.int32).at[at].set(e["ev"], mode="drop")
            ecn = jnp.zeros((NC,), bool).at[at].set(e["ecn"] == 1,
                                                    mode="drop")
            cwnd, alpha = self._dctcp(cwnd, alpha, mask, ecn)
            lb = self.lb.on_ack(lb, mask, ev, ecn, now)
        stats["unprocessed"] = jnp.sum(real & (rank >= c.feedback_rounds))
        p["state"] = jnp.where(due, FREE, p["state"])

        # 2. RTO
        live = ((p["state"] == FLYING) | (p["state"] == QUEUED)
                | (p["state"] == LOST_WAIT))
        fire = (live & (p["orph"] == 0) & (now - p["send"] >= c.rto_ticks)
                & ~st["done"][jnp.clip(p["conn"], 0, NC - 1)])
        stats["timeouts"] = jnp.sum(fire)
        f_slot = first_set(fire, NH)
        f_real = f_slot < NP
        f_conn = jnp.where(f_real, p["conn"][jnp.minimum(f_slot, NP - 1)], NC)
        f_seq = jnp.where(f_real, p["seq"][jnp.minimum(f_slot, NP - 1)], 0)
        in_range = f_conn < NC
        fc = jnp.minimum(f_conn, NC - 1)
        resend = f_real & ~jnp.where(in_range, rcv[fc, f_seq], True)
        newly_marked = resend & ~jnp.where(in_range, rtx[fc, f_seq], True)
        rtx = rtx.at[jnp.where(resend, f_conn, NC), f_seq].set(True,
                                                               mode="drop")
        rtx_count = rtx_count + jnp.zeros((NC,), jnp.int32).at[f_conn].add(
            newly_marked.astype(jnp.int32), mode="drop")
        fired = jnp.zeros((NC,), jnp.int32).at[f_conn].add(1, mode="drop")
        inflight = inflight - fired
        cwnd = jnp.clip(cwnd - fired.astype(jnp.float32), 1.0,
                        float(c.max_cwnd_pkts))
        lb = self.lb.on_timeout(lb, fired > 0, now)
        p["orph"] = jnp.where(fire, 1, p["orph"])
        p["state"] = jnp.where(fire & (p["state"] == LOST_WAIT), FREE,
                               p["state"])

        # 3. service
        up = (now >= scn["f_s"]) & (now < scn["f_e"])
        down = jnp.zeros((NQ,), bool).at[jnp.where(up, scn["f_q"], NQ)].set(
            True, mode="drop")
        q_len, q_head = st["q_len"], st["q_head"]
        serve = q_len > 0
        qid = jnp.arange(NQ)
        head = st["qbuf"][qid, q_head % QCAP]
        q_head = q_head + serve
        q_len = q_len - serve
        q_served = st["q_served"] + serve
        lost = serve & down
        final = serve & ~lost & (qid >= sh.down_base)
        fwd = serve & ~lost & ~final
        hs = jnp.where(serve, head, 0)
        d = {k: v[hs] for k, v in p.items()}
        orph = serve & (d["orph"] == 1)
        stats["drops_fail"] = jnp.sum(lost & ~orph)
        dconn = jnp.where(final, d["conn"], NC)
        dc = jnp.minimum(dconn, NC - 1)
        dseq = jnp.where(final, d["seq"], 0)
        was_done = jnp.where(final, st["done"][dc], True)
        new_pkt = final & ~rcv[dc, dseq]
        rcv = rcv.at[dconn, dseq].set(True, mode="drop")
        stats["delivered"] = jnp.sum(new_pkt)
        ackable = final & ~orph & ~was_done
        count = jnp.where(final, st["delivered"][dc], 0) + new_pkt
        complete = count >= jnp.where(final, scn["msg"][dc], BIG)
        pending = jnp.where(final, st["rx_pending"][dc], 0) + ackable
        emit = ackable & ((pending >= c.ack_coalesce) | complete)
        finished = final & complete & ~was_done

        def per_conn(x):
            return jnp.zeros((NC,), jnp.int32).at[dconn].add(
                x.astype(jnp.int32), mode="drop")

        delivered = st["delivered"] + per_conn(new_pkt)
        rx_pending = jnp.where(per_conn(emit) > 0, 0,
                               st["rx_pending"] + per_conn(ackable))
        finished_c = per_conn(finished) > 0
        done = st["done"] | finished_c
        done_tick = jnp.where(finished_c, now, st["done_tick"])
        out = {
            "state": jnp.where(lost, jnp.where(orph, FREE, LOST_WAIT),
                               jnp.where(fwd, FLYING,
                                         jnp.where(emit, IN_ACK, FREE))),
            "evt": jnp.where(fwd, now + c.hop_latency_ticks,
                             jnp.where(emit, now + c.ack_delay_ticks,
                                       d["evt"])),
            "hop": d["hop"] + fwd,
            "curq": jnp.where(fwd, qid, d["curq"]),
            "ack": jnp.where(emit, pending, d["ack"]),
        }
        at = jnp.where(serve, head, NP)
        for k, v in out.items():
            p[k] = p[k].at[at].set(v, mode="drop")

        # 4. arrivals
        arr = (p["state"] == FLYING) & (p["evt"] == now)
        a_slot = first_set(arr, sh.max_arrivals)
        a_real = a_slot < NP
        a = {k: v[jnp.minimum(a_slot, NP - 1)] for k, v in p.items()}
        a_conn = jnp.where(a_real, a["conn"], 0)
        target = jnp.where(
            a_real, self._next_queue(scn, a["hop"] == 0, a["curq"], a_conn,
                                     a["ev"]), NQ)
        u_red = jax.random.uniform(jax.random.fold_in(key, 1),
                                   (sh.max_arrivals,))
        K = sh.max_arrivals
        earlier = jnp.arange(K)[None, :] < jnp.arange(K)[:, None]
        rank = jnp.sum(earlier & (target[None, :] == target[:, None]), axis=1)
        qlen_t = jnp.where(a_real, q_len[jnp.minimum(target, NQ - 1)], 0)
        accept = a_real & (rank < QCAP - qlen_t)
        pos = qlen_t + rank
        q_len = q_len.at[jnp.where(accept, target, NQ)].add(1, mode="drop")
        mark_p = jnp.clip((pos.astype(jnp.float32) - c.kmin)
                          / float(c.kmax - c.kmin), 0.0, 1.0) * c.pmax
        mark = accept & (u_red < mark_p)
        stats["ecn_marks"] = jnp.sum(mark)
        where_q = (q_head[jnp.minimum(target, NQ - 1)] + pos) % QCAP
        qbuf = st["qbuf"].at[jnp.where(accept, target, NQ), where_q].set(
            a_slot, mode="drop")
        a_orph = a_real & (a["orph"] == 1)
        dropped = a_real & ~accept
        stats["drops_cong"] = jnp.sum(dropped & ~a_orph)
        at = jnp.where(a_real, a_slot, NP)
        p["state"] = p["state"].at[at].set(
            jnp.where(accept, QUEUED, jnp.where(a_orph, FREE, LOST_WAIT)),
            mode="drop")
        p["curq"] = p["curq"].at[at].set(jnp.where(accept, target, a["curq"]),
                                         mode="drop")
        p["ecn"] = p["ecn"].at[at].set(a["ecn"] | mark, mode="drop")

        # 5. injection
        dep = scn["dep"]
        started = (now >= scn["start"]) & (
            (dep < 0) | done[jnp.clip(dep, 0, NC - 1)])
        work = (rtx_count > 0) | (st["next_new"] < scn["msg"])
        can = (started & ~done & work
               & (inflight < jnp.floor(cwnd).astype(jnp.int32)))
        hc = scn["hc"]
        elig = (hc >= 0) & can[jnp.clip(hc, 0, NC - 1)]
        turn = (jnp.arange(sh.CPH)[None, :] - st["rr"][:, None]) % sh.CPH
        order = jnp.where(elig, turn, BIG)
        pick = jnp.argmin(order, axis=1)
        wants = jnp.min(order, axis=1) < BIG
        nth = jnp.cumsum(wants) - 1
        fl, fl_head, fl_count = st["fl"], st["fl_head"], st["fl_count"]
        sends = wants & (nth < fl_count)
        if sh.A < NP:  # the cap on live slots binds before the free list
            sends = sends & (jnp.sum(entry_state != FREE) + nth < sh.A)
        stats["alloc_fails"] = jnp.sum(wants & ~sends)
        n_sent = jnp.sum(sends).astype(jnp.int32)
        stats["injected"] = n_sent
        slot = fl[(fl_head + nth) % NP]
        fl_head = (fl_head + n_sent) % NP
        fl_count = fl_count - n_sent
        conn = jnp.where(sends, hc[jnp.arange(NH), pick], NC)
        rr = jnp.where(sends, (pick + 1) % sh.CPH, st["rr"])
        cc = jnp.minimum(conn, NC - 1)
        resend = sends & (rtx_count[cc] > 0)
        rtx_seq = jnp.argmax(rtx[cc], axis=1).astype(jnp.int32)
        seq = jnp.where(resend, rtx_seq, st["next_new"][cc])
        rtx = rtx.at[jnp.where(resend, conn, NC), rtx_seq].set(False,
                                                              mode="drop")
        sent = jnp.zeros((NC,), jnp.int32).at[conn].add(1, mode="drop")
        resent = jnp.zeros((NC,), jnp.int32).at[conn].add(
            resend.astype(jnp.int32), mode="drop")
        rtx_count = rtx_count - resent
        next_new = st["next_new"] + sent - resent
        inflight = inflight + sent
        evs, lb = self.lb.choose(lb, sent > 0, jax.random.fold_in(key, 2))
        new = {"state": FLYING, "conn": conn, "ev": evs[cc], "seq": seq,
               "hop": 0, "curq": -1, "send": now,
               "evt": now + c.hop_latency_ticks, "ecn": 0, "orph": 0,
               "ack": 0}
        at = jnp.where(sends, slot, NP)
        for k, v in new.items():
            p[k] = p[k].at[at].set(jnp.broadcast_to(v, (NH,)).astype(
                jnp.int32), mode="drop")

        # 6. free-list push, in ascending slot order.  A tick frees at most
        # one slot per ACK, RTO, served packet and arrival.
        freed = (p["state"] == FREE) & (entry_state != FREE)
        most = NH + NH + NQ + sh.max_arrivals
        fr_slot = first_set(freed, most)
        n_freed = jnp.sum(fr_slot < NP).astype(jnp.int32)
        ring = (fl_head + fl_count + jnp.arange(most)) % NP
        fl = fl.at[jnp.where(fr_slot < NP, ring, NP)].set(fr_slot, mode="drop")
        fl_count = fl_count + n_freed

        delta = jnp.stack([stats[k].astype(jnp.int32) for k in STATS])
        if self.control:  # float state held in bfloat16
            cwnd, alpha = (jax.lax.reduce_precision(x, exponent_bits=8,
                                                    mantissa_bits=7)
                           for x in (cwnd, alpha))
        new_st = dict(
            pkt=p, qbuf=qbuf, q_head=q_head, q_len=q_len, q_served=q_served,
            inflight=inflight, next_new=next_new, delivered=delivered,
            rx_pending=rx_pending, done=done, done_tick=done_tick,
            rtx_count=rtx_count, rtx=rtx, rcv=rcv, cwnd=cwnd, alpha=alpha,
            rr=rr, lb=lb, fl=fl, fl_head=fl_head, fl_count=fl_count,
            stats=st["stats"] + delta,
        )
        done_now = done & ~st["done"]
        probe = dict(now=now, q_len=q_len, served=serve.astype(jnp.int32),
                     delta=delta, done_now=done_now,
                     fct=jnp.where(done_now, now - scn["start"], 0))
        return new_st, probe

    def chunk_fn(self):
        """``(state, sketch, t0, n) -> (state, sketch)``: ``n`` ticks from
        ``t0``, compiled once for every seed (``n`` static)."""
        sk, watch = self.sketch, self.watch

        def body(carry, t, scn):
            st, s = carry
            st, pr = self.tick(st, t, scn)
            if sk is not None:
                s = sk.update(s, pr["now"], pr["q_len"], pr["served"],
                              watch, pr["delta"], pr["done_now"], pr["fct"])
            return (st, s), None

        def run(st, s, scn, t0, n):
            ticks = t0 + jnp.arange(n, dtype=jnp.int32)
            return jax.lax.scan(lambda c, t: body(c, t, scn), (st, s),
                                ticks)[0]

        fn = jax.jit(run, static_argnums=4)
        return lambda st, s, t0, n: fn(st, s, self.scn, t0, n)


def view(st) -> dict:
    """What the comparison reads of a state: every connection's transport
    state, the queues (lengths, serve counts and their packets in FIFO
    order), the live packets as a multiset, the hosts' round-robin turn,
    the free-slot count, the counters and the load balancer's state."""
    st = jax.device_get(st)
    return view_of({k: np.asarray(v) for k, v in st["pkt"].items()}, st)


def view_of(p: dict, st: dict) -> dict:
    """``view`` of packet fields ``p`` and the rest of a state ``st``."""
    live = p["state"] != FREE
    rows = np.stack([p[k][live] for k in PACKET_FIELDS], axis=1)
    rows = rows[np.lexsort(rows.T[::-1])]
    qbuf, q_head, q_len = (np.asarray(st[k]) for k in ("qbuf", "q_head",
                                                       "q_len"))
    nq, cap = qbuf.shape
    i = np.arange(cap)[None, :]
    slots = qbuf[np.arange(nq)[:, None], (q_head[:, None] + i) % cap]
    inq = i < q_len[:, None]
    queued = np.stack([np.where(inq, p[k][slots], -1)
                       for k in ("conn", "seq", "send")], axis=-1)
    out = {k: np.asarray(st[k]) for k in (
        "inflight", "next_new", "delivered", "rx_pending", "done",
        "done_tick", "rtx_count", "rtx", "rcv", "cwnd", "alpha", "q_len",
        "q_head", "q_served", "rr", "fl_count", "stats")}
    lb = st["lb"]
    lb = ({f"lb.{k}": np.asarray(v) for k, v in lb.items()}
          if isinstance(lb, dict) else {"lb": np.asarray(lb)})
    return {**out, "queued": queued, "packets": rows, **lb}

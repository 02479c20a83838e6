"""The reference of ``correct`` on the 3-tier k-ary fat tree (``plainsim``
with this fabric's sizes and next-hop rule).

The fabric (Al-Fares et al., SIGCOMM 2008, Sec. 3): ``P`` pods, each of
``TP`` ToRs and ``U`` aggs; every ToR has one uplink to each agg of its
pod; agg ``a`` of every pod has ``U2`` uplinks, to cores ``a*U2 .. a*U2 +
U2 - 1``; each core has one downlink to agg ``c // U2`` of every pod.  One
FIFO queue per directed link, at its source, numbered region by region as
the simulator lays them out:

    ToR t -> agg a              t*U + a
    agg (p, a) -> core a*U2+u   T*U + (p*U + a)*U2 + u
    core c -> agg (p, c//U2)    T*U + P*U*U2 + c*P + p
    agg (p, a) -> ToR p*TP+l    ... + C*P + (p*U + a)*TP + l
    ToR t -> host t*H + h       ... + P*U*TP + t*H + h

A packet goes straight down where it can: to its host from its own ToR,
to the destination ToR from an agg of its pod, to the destination pod from
a core.  Up is a choice, hashed from the packet's connection and EV at both
choice hops: a ToR salts the hash with its index ``t``, an agg with its
global index ``p*U + a`` plus 7919, so the two choices of one EV are
independent.  The first hop (ToR to agg) keeps the ``U`` of ``plainsim``:
the ToR's uplinks, whose first ones are the watched queues.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np

from bench import plainsim

AGG_SALT = 7919  # added to an agg's global index in its hash


@dataclasses.dataclass(frozen=True)
class Shapes(plainsim.Shapes):
    """``plainsim.Shapes`` on the 3-tier tree: ``U`` is the aggs of a pod
    (a ToR's uplinks), and the fabric adds pods, ToRs per pod and the
    aggs' core uplinks."""

    P: int  # pods
    TP: int  # ToRs per pod
    U2: int  # core uplinks per agg

    @property
    def agg_up_base(self) -> int:
        return self.T * self.U

    @property
    def core_down_base(self) -> int:
        return self.agg_up_base + self.P * self.U * self.U2

    @property
    def agg_down_base(self) -> int:
        return self.core_down_base + self.U * self.U2 * self.P

    @property
    def down_base(self) -> int:
        return self.agg_down_base + self.P * self.U * self.TP


class Row(plainsim.Row):
    """A ``plainsim`` row on the 3-tier fat tree (not in scale mode)."""

    def shapes_of(self, cfg, src: np.ndarray, msg: np.ndarray) -> Shapes:
        if cfg.tiers != 3 or cfg.fabric or cfg.conn_sharding:
            raise NotImplementedError("fat_tree3 models the 3-tier fat tree "
                                      "without scale mode")
        NH, H = cfg.n_hosts, cfg.hosts_per_tor
        TP, U, U2 = cfg.tors_per_pod, cfg.aggs_per_pod, cfg.agg_uplinks
        T = NH // H
        P = T // TP
        nq = T * U + 2 * P * U * U2 + P * U * TP + NH
        NC = len(src)
        NP = int(cfg.pkt_slots) or plainsim._pow2_at_least(
            NC * cfg.max_cwnd_pkts + 4 * NH + 64)
        msg_auto = min(cfg.max_msg_pkts,
                       plainsim._pow2_at_least(int(msg.max())))
        cph = int(np.bincount(src, minlength=NH).max())
        return Shapes(
            NH=NH, NC=NC, NQ=nq, NP=NP, A=NP,
            MSG=int(cfg.msg_slots) or msg_auto,
            CPH=max(int(cfg.conns_per_host), cph), T=T, H=H, U=U,
            P=P, TP=TP, U2=U2,
        )

    def _next_queue(self, scn, first_hop, cur, conn, ev):
        sh = self.sh
        d = scn["dst"][conn]
        s_tor, d_tor = scn["src"][conn] // sh.H, d // sh.H
        d_pod, d_local = d_tor // sh.TP, d_tor % sh.TP
        # leaving the source ToR: down to the host, or up to a hashed agg
        at_src_tor = jnp.where(
            s_tor == d_tor, sh.down_base + d,
            s_tor * sh.U + plainsim.path_hash(conn, ev, s_tor, sh.U))
        # at an agg, reached from ToR cur // U: down to the destination
        # ToR in its own pod, else up to a hashed core
        agg = (cur // sh.U // sh.TP) * sh.U + cur % sh.U  # p*U + a
        at_agg = jnp.where(
            cur // sh.U // sh.TP == d_pod,
            sh.agg_down_base + agg * sh.TP + d_local,
            sh.agg_up_base + agg * sh.U2
            + plainsim.path_hash(conn, ev, agg + AGG_SALT, sh.U2))
        # at a core, reached from agg (p, a) by uplink u: down to the
        # destination pod
        up = cur - sh.agg_up_base
        core = (up // sh.U2 % sh.U) * sh.U2 + up % sh.U2
        at_core = sh.core_down_base + core * sh.P + d_pod
        # at an agg of the destination pod, reached from core c: down to
        # the destination ToR
        from_core = (cur - sh.core_down_base) // sh.P
        at_dst_agg = (sh.agg_down_base
                      + (d_pod * sh.U + from_core // sh.U2) * sh.TP + d_local)
        # at the destination ToR: down to the host
        return jnp.where(
            first_hop, at_src_tor,
            jnp.where(cur < sh.agg_up_base, at_agg,
                      jnp.where(cur < sh.core_down_base, at_core,
                                jnp.where(cur < sh.agg_down_base, at_dst_agg,
                                          sh.down_base + d))))


view = plainsim.view

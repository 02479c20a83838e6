"""Fat-tree topology: queue-id layout, ECMP hashing, and the hop-transition
function (DESIGN.md §3 "Simulator time model").

Every directed link has one FIFO queue at its source.  Queue-id regions:

2-tier (T tors × H hosts each, U uplinks == U spines):
    t0_up[t, u]   = t*U + u                         [0,            T*U)
    sp_down[s, t] = T*U + s*T + t                   [T*U,          T*U+U*T)
    t0_down[t, h] = T*U + U*T + t*H + h             [...,          +T*H)

3-tier (P pods × Tp tors × H hosts; A aggs/pod; U2 core-uplinks/agg;
        C = A*U2 cores; core c attaches to agg c//U2 of every pod):
    t0_up[t, a]        = t*A + a
    agg_up[p, a, u]    = T*A + (p*A + a)*U2 + u
    core_down[c, p]    = T*A + P*A*U2 + c*P + p
    agg_down[p, a, tl] = ... + C*P + (p*A + a)*Tp + tl
    t0_down[t, h]      = ... + P*A*Tp + t*H + h

The packet's EV selects the up-direction "choice" ports via a mixing hash
of (flow_id, EV, switch salt); down-direction ports are determined by the
destination (standard Clos routing).  This mirrors §2.2: the sender does
not know the EV→path mapping, only that distinct EVs hash independently.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.netsim.config import SimConfig


def mix32(x: jax.Array) -> jax.Array:
    """Murmur3-style 32-bit finalizer (good avalanche; used as ECMP hash)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def ecmp_hash(flow_id: jax.Array, ev: jax.Array, salt: jax.Array, nports) -> jax.Array:
    h = mix32(
        flow_id.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        ^ ev.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        ^ salt.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
    )
    return (h % jnp.asarray(nports, jnp.uint32)).astype(jnp.int32)


def _mix32_np(x: int) -> int:
    x &= 0xFFFFFFFF
    x ^= x >> 16
    x = (x * 0x7FEB352D) & 0xFFFFFFFF
    x ^= x >> 15
    x = (x * 0x846CA68B) & 0xFFFFFFFF
    x ^= x >> 16
    return x


def ecmp_hash_np(flow_id: int, ev: int, salt: int, nports: int) -> int:
    """Bit-exact numpy/python mirror of ``ecmp_hash`` — the reference the
    topogen property tests and ``TopologySpec.walk`` use off-device."""
    h = _mix32_np(
        ((flow_id * 0x9E3779B1) ^ (ev * 0x85EBCA77) ^ (salt * 0xC2B2AE3D))
        & 0xFFFFFFFF
    )
    return int(h % max(int(nports), 1))


@dataclasses.dataclass(frozen=True)
class Topology:
    cfg: SimConfig
    n_queues: int
    # region bases (python ints — static under jit)
    t0_up_base: int
    agg_up_base: int  # 3-tier only (== -1 for 2-tier)
    core_down_base: int
    agg_down_base: int
    t0_down_base: int

    @staticmethod
    def build(cfg: SimConfig) -> "Topology":
        if cfg.fabric:
            # generated fabric (netsim/topogen.py): same interface, ONE
            # table-driven router for every fabric kind — the engine never
            # branches on what kind of fabric it is running.
            return TableTopology.build(cfg)
        T, H = cfg.n_tors, cfg.hosts_per_tor
        if cfg.tiers == 2:
            U = cfg.uplinks_per_tor
            t0_up = 0
            sp_down = T * U
            t0_down = sp_down + U * T
            n_queues = t0_down + T * H
            return Topology(
                cfg=cfg,
                n_queues=n_queues,
                t0_up_base=t0_up,
                agg_up_base=-1,
                core_down_base=sp_down,  # reuse for spine-down region
                agg_down_base=-1,
                t0_down_base=t0_down,
            )
        A, U2, P, Tp = cfg.aggs_per_pod, cfg.agg_uplinks, cfg.n_pods, cfg.tors_per_pod
        C = cfg.n_cores
        t0_up = 0
        agg_up = T * A
        core_down = agg_up + P * A * U2
        agg_down = core_down + C * P
        t0_down = agg_down + P * A * Tp
        n_queues = t0_down + T * H
        return Topology(
            cfg=cfg,
            n_queues=n_queues,
            t0_up_base=t0_up,
            agg_up_base=agg_up,
            core_down_base=core_down,
            agg_down_base=agg_down,
            t0_down_base=t0_down,
        )

    @property
    def diameter(self) -> int:
        """Max queue hops on any src->dst path (host downlink included)."""
        return 3 if self.cfg.tiers == 2 else 5

    # -- helpers for benchmarks / tests (numpy, not jitted) ----------------
    def t0_up_queues(self, tor: int) -> np.ndarray:
        cfg = self.cfg
        n_up = cfg.uplinks_per_tor if cfg.tiers == 2 else cfg.aggs_per_pod
        return np.arange(n_up) + self.t0_up_base + tor * n_up

    def t0_down_queue(self, host: int) -> int:
        cfg = self.cfg
        t, hl = host // cfg.hosts_per_tor, host % cfg.hosts_per_tor
        return self.t0_down_base + t * cfg.hosts_per_tor + hl

    def is_final_hop(self, q: jax.Array) -> jax.Array:
        return q >= self.t0_down_base

    # -- the hop-transition function (jit-traceable) ------------------------
    def next_queue(
        self,
        at_injection: jax.Array,  # bool (K,): packet leaving the source host
        cur_queue: jax.Array,  # int32 (K,): queue just dequeued from
        flow_id: jax.Array,  # int32 (K,)
        ev: jax.Array,  # int32 (K,)
        src: jax.Array,  # int32 (K,) source host id
        dst: jax.Array,  # int32 (K,) destination host id
        q_len: jax.Array,  # int32 (n_queues,): current lengths (adaptive)
        adaptive: bool,  # static: in-network least-queue choice
    ) -> jax.Array:
        cfg = self.cfg
        T, H = cfg.n_tors, cfg.hosts_per_tor
        src_tor, dst_tor = src // H, dst // H
        dst_local = dst % H
        same_tor = src_tor == dst_tor
        t0_down = self.t0_down_base + dst_tor * H + dst_local

        if cfg.tiers == 2:
            U = cfg.uplinks_per_tor
            up_choice = ecmp_hash(flow_id, ev, src_tor, U)
            if adaptive:
                # switch-local least-queue pick among this TOR's uplinks
                cand = self.t0_up_base + src_tor[:, None] * U + jnp.arange(U)
                lens = q_len[cand]
                up_choice = jnp.argmin(lens, axis=1).astype(jnp.int32)
            t0_up = self.t0_up_base + src_tor * U + up_choice
            # cur_queue regions
            at_t0_up = cur_queue < self.core_down_base  # t0_up region
            spine = jnp.where(at_t0_up, cur_queue - self.t0_up_base, 0) % U
            sp_down = self.core_down_base + spine * T + dst_tor

            nxt = jnp.where(
                at_injection,
                jnp.where(same_tor, t0_down, t0_up),
                jnp.where(at_t0_up, sp_down, t0_down),
            )
            return nxt.astype(jnp.int32)

        # ---- 3-tier ----
        A, U2, Tp = cfg.aggs_per_pod, cfg.agg_uplinks, cfg.tors_per_pod
        src_pod, dst_pod = src_tor // Tp, dst_tor // Tp
        dst_tor_local = dst_tor % Tp
        same_pod = src_pod == dst_pod

        up1 = ecmp_hash(flow_id, ev, src_tor, A)
        if adaptive:
            cand = self.t0_up_base + src_tor[:, None] * A + jnp.arange(A)
            up1 = jnp.argmin(q_len[cand], axis=1).astype(jnp.int32)
        t0_up = self.t0_up_base + src_tor * A + up1

        in_t0_up = cur_queue < self.agg_up_base
        agg_id = jnp.where(in_t0_up, cur_queue - self.t0_up_base, 0)
        agg_a = agg_id % A  # agg index within the pod
        agg_global = src_pod * A + agg_a
        up2 = ecmp_hash(flow_id, ev, agg_global + 7919, U2)
        if adaptive:
            cand = self.agg_up_base + agg_global[:, None] * U2 + jnp.arange(U2)
            up2 = jnp.argmin(q_len[cand], axis=1).astype(jnp.int32)
        agg_up = self.agg_up_base + agg_global * U2 + up2
        agg_down_same = self.agg_down_base + agg_global * Tp + dst_tor_local

        in_agg_up = (cur_queue >= self.agg_up_base) & (
            cur_queue < self.core_down_base
        )
        # (p*A+a)*U2+u -> c = a*U2+u
        core = (jnp.where(in_agg_up, cur_queue - self.agg_up_base, 0) // U2 % A) * U2 + (
            jnp.where(in_agg_up, cur_queue - self.agg_up_base, 0) % U2
        )
        core_down = self.core_down_base + core * cfg.n_pods + dst_pod

        in_core_down = (cur_queue >= self.core_down_base) & (
            cur_queue < self.agg_down_base
        )
        core_at = jnp.where(in_core_down, cur_queue - self.core_down_base, 0) // cfg.n_pods
        dst_agg = core_at // U2
        agg_down_x = (
            self.agg_down_base + (dst_pod * A + dst_agg) * Tp + dst_tor_local
        )

        nxt = jnp.where(
            at_injection,
            jnp.where(same_tor, t0_down, t0_up),
            jnp.where(
                in_t0_up,
                jnp.where(same_pod, agg_down_same, agg_up),
                jnp.where(
                    in_agg_up,
                    core_down,
                    jnp.where(in_core_down, agg_down_x, t0_down),
                ),
            ),
        )
        return nxt.astype(jnp.int32)


class TableTopology:
    """Table-driven topology built from a generated ``TopologySpec``
    (netsim/topogen.py) — the SAME consumer interface as the arithmetic
    ``Topology`` (``n_queues`` / ``t0_down_base`` / ``next_queue`` /
    ``t0_up_queues`` / ``t0_down_queue`` / ``is_final_hop``), so the
    engine and sweep run generated fabrics with zero special-casing.

    Routing is one uniform up/down rule over the spec's tables: route down
    via ``down_next[sw, dst]`` when defined, else spray over the
    ``up_deg[sw]``-wide candidate block ``up_base[sw, dst] + choice`` with
    the choice hashed from (flow, EV, per-switch salt plane) — or picked
    adaptively by least queue length when the LB is switch-adaptive.
    """

    def __init__(self, cfg: SimConfig, spec):
        if spec.n_hosts != cfg.n_hosts:
            raise ValueError(
                f"fabric {cfg.fabric!r} has {spec.n_hosts} hosts but "
                f"SimConfig.n_hosts={cfg.n_hosts}; they must agree"
            )
        self.cfg = cfg
        self.spec = spec
        self.n_queues = spec.n_queues
        self.t0_down_base = spec.t0_down_base
        # region bases kept for interface parity (unused by the router)
        self.t0_up_base = 0
        self.agg_up_base = -1
        self.core_down_base = -1
        self.agg_down_base = -1
        self._host_sw = jnp.asarray(spec.host_sw)
        self._q_sw = jnp.asarray(spec.q_sw)
        self._up_base = jnp.asarray(spec.up_base)
        self._up_deg = jnp.asarray(spec.up_deg)
        self._down_next = jnp.asarray(spec.down_next)
        self._salt = jnp.asarray(spec.salt)

    @staticmethod
    def build(cfg: SimConfig) -> "TableTopology":
        from repro.netsim.topogen import build_spec

        return TableTopology(cfg, build_spec(cfg.fabric))

    @property
    def diameter(self) -> int:
        """Max queue hops on any src->dst path (host downlink included)."""
        return self.spec.diameter

    # -- helpers for benchmarks / tests (numpy, not jitted) ----------------
    def t0_up_queues(self, tor: int) -> np.ndarray:
        base, size = (int(v) for v in self.spec.sw_up_span[tor])
        return np.arange(size) + base

    def t0_down_queue(self, host: int) -> int:
        return self.t0_down_base + host

    def is_final_hop(self, q: jax.Array) -> jax.Array:
        return q >= self.t0_down_base

    # -- the hop-transition function (jit-traceable) ------------------------
    def next_queue(
        self,
        at_injection: jax.Array,
        cur_queue: jax.Array,
        flow_id: jax.Array,
        ev: jax.Array,
        src: jax.Array,
        dst: jax.Array,
        q_len: jax.Array,
        adaptive: bool,
    ) -> jax.Array:
        NH, NQ, NS = self.cfg.n_hosts, self.n_queues, self.spec.n_switches
        sw = jnp.where(
            at_injection,
            self._host_sw[jnp.clip(src, 0, NH - 1)],
            self._q_sw[jnp.clip(cur_queue, 0, NQ - 1)],
        )
        # garbage lanes (padded arrivals, final-hop queues) clip to a real
        # switch; their outputs are masked off by the caller's a_valid
        sw = jnp.clip(sw, 0, NS - 1)
        dstc = jnp.clip(dst, 0, NH - 1)
        down_q = self._down_next[sw, dstc]
        base = self._up_base[sw, dstc]
        deg = self._up_deg[sw]
        choice = ecmp_hash(
            flow_id, ev, self._salt[sw], jnp.maximum(deg, 1)
        )
        if adaptive:
            maxd = max(self.spec.max_up_deg, 1)
            cand = base[:, None] + jnp.arange(maxd, dtype=jnp.int32)
            lens = q_len[jnp.clip(cand, 0, NQ - 1)]
            lens = jnp.where(
                jnp.arange(maxd, dtype=jnp.int32)[None, :] < deg[:, None],
                lens,
                jnp.int32(2**30),
            )
            choice = jnp.argmin(lens, axis=1).astype(jnp.int32)
        nxt = jnp.where(down_q >= 0, down_q, base + choice)
        return nxt.astype(jnp.int32)

"""Load-balancer zoo behind one interface (paper §4.1 baselines + REPS).

Every load balancer is a *static* object holding configuration; its mutable
per-connection state is a pytree threaded through the netsim engine's jitted
tick.  Interface:

    init_state(n_conns, key)                        -> state pytree
    choose_ev(state, mask, key, now)                -> (evs (N,), state)
    on_ack(state, mask, ev, ecn, now, key)          -> state
    on_timeout(state, mask, now, key)               -> state

``mask`` selects the connections that send / got an ACK / timed out this
tick (the netsim guarantees at most one such event per connection per tick,
see DESIGN.md §5).  ``switch_adaptive`` marks in-network approaches
(adaptive RoCE): the sender still stamps an EV but switches override the
port choice with a local least-queue decision.

Key-threading contract: every callback that may re-path receives a key
derived from the engine's per-tick threefry stream (``fold_in(tick_key,
2)`` for ``choose_ev``, ``fold_in(fold_in(tick_key, 4), round)`` per
feedback round for ``on_ack``, ``fold_in(tick_key, 5)`` for
``on_timeout``), so draws differ per seed, per sweep row, per tick and per
feedback round.  ``fold_in`` derives keys without consuming randomness,
so LBs that ignore the key are bit-identical to runs before the key was
threaded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import reps as reps_core
from repro.utils import pytree_dataclass, static_field

# Trace-event kinds reported by the optional LoadBalancer.trace port (one
# int32 count per kind, see trace() below).  The netsim tracer maps these to
# ring-buffer event codes; keep the numbering stable — it is serialized into
# flight-recorder part files.
TR_EV_HIT = 0  # REPS: popped the oldest *valid* cached EV
TR_EV_MISS = 1  # REPS: explored a fresh uniform EV
TR_EV_RECYCLE = 2  # REPS: freezing-mode reuse of a (possibly invalid) slot
TR_EV_FREEZE = 3  # REPS: entered freezing mode (failure detected)
TR_REPATH_ACK_ECN = 4  # re-path decided from ECN feedback on ACKs
TR_REPATH_RTO = 5  # re-path decided from a retransmission timeout
TR_REPATH_FLOWLET = 6  # re-path decided from a flowlet gap expiry
TR_REPATH_EPOCH = 7  # re-path decided at an epoch / message boundary
N_TRACE_KINDS = 8


def _trace_counts(*pairs):
    """Build a (N_TRACE_KINDS,) int32 count vector from (kind, mask) pairs.

    Every mask MUST already be gated on the site's event mask so the result
    is all-zero on quiescent ticks (the tracer carry must be a bitwise no-op
    when nothing happens, same contract as the telemetry channels).
    """
    out = jnp.zeros((N_TRACE_KINDS,), jnp.int32)
    for kind, m in pairs:
        out = out.at[kind].set(jnp.sum(m.astype(jnp.int32)))
    return out


def _rand_evs(key, n, evs_size):
    return jax.random.randint(key, (n,), 0, evs_size, jnp.int32)


def _mix32(x):
    """Cheap int32 -> uint32 avalanche hash (xorshift-multiply finalizer)."""
    u = x.astype(jnp.uint32)
    u = u ^ (u >> jnp.uint32(16))
    u = u * jnp.uint32(0x7FEB352D)
    u = u ^ (u >> jnp.uint32(15))
    u = u * jnp.uint32(0x846CA68B)
    u = u ^ (u >> jnp.uint32(16))
    return u


class LoadBalancer:
    name: str = "abstract"
    switch_adaptive: bool = False

    def __init__(self, evs_size: int = 65536):
        self.evs_size = evs_size

    def init_state(self, n_conns: int, key: jax.Array):
        raise NotImplementedError

    def choose_ev(self, state, mask, key, now):
        raise NotImplementedError

    def on_ack(self, state, mask, ev, ecn, now, key):
        return state

    def on_timeout(self, state, mask, now, key):
        return state

    def trace(self, site, prev, new, mask):
        """Optional observation-only trace port (flight recorder).

        ``site`` is a *static* string naming the engine call site just
        executed ("choose" | "ack" | "timeout"); ``prev``/``new`` are the LB
        state before/after that call and ``mask`` is the event mask the call
        received.  Returns (N_TRACE_KINDS,) int32 per-kind decision counts
        summed over connections.  Contract: pure state-diff observation (no
        RNG, no state change) and every count gated on ``mask`` so the
        result is all-zero whenever ``mask`` is — LBs whose state drifts on
        idle ticks (e.g. PLB epoch rollover) must not emit events the
        quiescence early-exit would skip.
        """
        del site, prev, new, mask
        return jnp.zeros((N_TRACE_KINDS,), jnp.int32)


# ---------------------------------------------------------------------------
# ECMP: one static EV per connection (per-flow hashing).  §2.2
# ---------------------------------------------------------------------------
class EcmpLB(LoadBalancer):
    name = "ecmp"

    def init_state(self, n_conns, key):
        return _rand_evs(key, n_conns, self.evs_size)

    def choose_ev(self, state, mask, key, now):
        return state, state


# ---------------------------------------------------------------------------
# OPS: uniform random EV per packet.  §2.2
# ---------------------------------------------------------------------------
class OpsLB(LoadBalancer):
    name = "ops"

    def init_state(self, n_conns, key):
        return jnp.zeros((n_conns,), jnp.int32)  # dummy (keeps pytree nonempty)

    def choose_ev(self, state, mask, key, now):
        return _rand_evs(key, state.shape[0], self.evs_size), state


# ---------------------------------------------------------------------------
# REPS (the paper).  §3
# ---------------------------------------------------------------------------
class RepsLB(LoadBalancer):
    """REPS with a switchable compute backend.

    backend="jnp"    — the vectorized repro.core.reps implementation;
    backend="pallas" — the fused repro.kernels.reps_update kernel drives
                       Algorithms 1+2 (Mosaic on TPU, interpret elsewhere);
    backend="auto"   — pallas on TPU, jnp otherwise.

    Both backends share the REPSState pytree and are bit-identical (the
    kernel is pinned to the same scalar oracle; tests assert parity), so
    flipping the backend never changes simulation results.
    """

    name = "reps"

    def __init__(
        self,
        evs_size: int = 65536,
        buffer_size: int = 8,
        num_pkts_bdp: int = 32,
        freezing_timeout: int = 1024,
        enable_freezing: bool = True,
        backend: str = "auto",
    ):
        super().__init__(evs_size)
        self.cfg = reps_core.REPSConfig(
            buffer_size=buffer_size,
            evs_size=evs_size,
            num_pkts_bdp=num_pkts_bdp,
            freezing_timeout=freezing_timeout,
        )
        self.enable_freezing = enable_freezing
        assert backend in ("auto", "jnp", "pallas"), backend
        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
        if backend == "pallas":
            from repro.kernels import reps_update

            assert buffer_size == reps_update.BUF, (
                f"pallas backend is compiled for buffer depth "
                f"{reps_update.BUF}, got {buffer_size}"
            )
        self.backend = backend

    def init_state(self, n_conns, key):
        return reps_core.init_state(self.cfg, n_conns)

    def _kernel_tick(self, state, ack_mask, ack_ev, ack_ecn, timeout_mask,
                     send_mask, rand_ev, now):
        """One fused Algorithm 1+2 pass through the Pallas kernel.

        Unused event classes are passed as all-zero masks, which makes the
        corresponding algorithm a no-op — so the engine's split pipeline
        stages (feedback / RTO / injection) each map onto one kernel call.
        """
        from repro.kernels import ops as kernel_ops

        n = state.head.shape[0]
        z = jnp.zeros((n,), jnp.int32)
        i = lambda x: x.astype(jnp.int32)
        out = kernel_ops.reps_tick(
            state.buf_ev, i(state.buf_valid), state.head, state.num_valid,
            state.explore_counter, i(state.is_freezing), state.exit_freezing,
            state.n_cached,
            i(ack_mask) if ack_mask is not None else z,
            ack_ev if ack_ev is not None else z,
            i(ack_ecn) if ack_ecn is not None else z,
            i(timeout_mask) if timeout_mask is not None else z,
            i(send_mask) if send_mask is not None else z,
            rand_ev if rand_ev is not None else z,
            jnp.asarray(now, jnp.int32),
            self.cfg.num_pkts_bdp,
            self.cfg.freezing_timeout,
        )
        (buf_ev, buf_valid, head, num_valid, explore, freezing, exit_freeze,
         n_cached, evs) = out
        new_state = reps_core.REPSState(
            buf_ev=buf_ev,
            buf_valid=buf_valid.astype(jnp.bool_),
            head=head,
            num_valid=num_valid,
            explore_counter=explore,
            is_freezing=freezing.astype(jnp.bool_),
            exit_freezing=exit_freeze,
            n_cached=n_cached,
        )
        return new_state, evs

    def choose_ev(self, state, mask, key, now):
        if self.backend == "pallas":
            n = state.head.shape[0]
            rand_ev = jax.random.randint(key, (n,), 0, self.cfg.evs_size, jnp.int32)
            state, evs = self._kernel_tick(
                state, None, None, None, None, mask, rand_ev, now
            )
            return evs, state
        return reps_core.choose_ev(self.cfg, state, mask, key)

    def on_ack(self, state, mask, ev, ecn, now, key):
        if self.backend == "pallas":
            state, _ = self._kernel_tick(
                state, mask, ev, ecn, None, None, None, now
            )
            return state
        return reps_core.on_ack(self.cfg, state, mask, ev, ecn, now)

    def on_timeout(self, state, mask, now, key):
        if not self.enable_freezing:
            return state
        if self.backend == "pallas":
            state, _ = self._kernel_tick(
                state, None, None, None, mask, None, None, now
            )
            return state
        return reps_core.on_failure_detection(self.cfg, state, mask, now)

    def trace(self, site, prev, new, mask):
        # Pure REPSState diffs, so both backends (jnp / pallas, bit-equal
        # states) report identical events.  choose_ev mutates num_valid only
        # via pop-oldest-valid (hit) and head only via freezing-mode reuse
        # (recycle); everything else under the mask explored fresh entropy.
        if site == "choose":
            hit = mask & (new.num_valid < prev.num_valid)
            recycle = mask & (new.head != prev.head)
            miss = mask & ~hit & ~recycle
            return _trace_counts(
                (TR_EV_HIT, hit), (TR_EV_RECYCLE, recycle), (TR_EV_MISS, miss)
            )
        if site == "timeout":
            freeze = mask & new.is_freezing & ~prev.is_freezing
            return _trace_counts((TR_EV_FREEZE, freeze))
        return jnp.zeros((N_TRACE_KINDS,), jnp.int32)


# ---------------------------------------------------------------------------
# PLB / FlowBender-style: per-connection EV, re-path when an epoch sees a
# high ECN fraction or on RTO.  Configured aggressively per the paper §4.1.
# ---------------------------------------------------------------------------
@pytree_dataclass
class PlbState:
    ev: jax.Array  # (N,) int32 current EV
    acks: jax.Array  # (N,) int32 ACKs this epoch
    marked: jax.Array  # (N,) int32 ECN-marked ACKs this epoch
    epoch_end: jax.Array  # (N,) int32 tick
    bad_epochs: jax.Array  # (N,) int32 consecutive congested epochs


class PlbLB(LoadBalancer):
    name = "plb"

    def __init__(
        self,
        evs_size: int = 65536,
        epoch_ticks: int = 64,
        ecn_frac_threshold: float = 0.5,
        repath_after_epochs: int = 1,  # aggressive (FlowBender-like)
    ):
        super().__init__(evs_size)
        self.epoch_ticks = epoch_ticks
        self.ecn_frac_threshold = ecn_frac_threshold
        self.repath_after_epochs = repath_after_epochs

    def init_state(self, n_conns, key):
        return PlbState(
            ev=_rand_evs(key, n_conns, self.evs_size),
            acks=jnp.zeros((n_conns,), jnp.int32),
            marked=jnp.zeros((n_conns,), jnp.int32),
            epoch_end=jnp.full((n_conns,), self.epoch_ticks, jnp.int32),
            bad_epochs=jnp.zeros((n_conns,), jnp.int32),
        )

    def choose_ev(self, state, mask, key, now):
        return state.ev, state

    def on_ack(self, state, mask, ev, ecn, now, key):
        # Reset-then-count: close out an epoch that has already ended
        # before counting this tick's ACKs.  `epoch_over` depends only on
        # `now`, so an idle gap spanning the boundary rolls the epoch over
        # on the next ACK too — the completed epoch is judged on its own
        # counters, never with the next burst's first ACK mixed in.
        epoch_over = now >= state.epoch_end
        frac_bad = state.marked > (
            jnp.ceil(state.acks.astype(jnp.float32) * self.ecn_frac_threshold)
        ).astype(jnp.int32)
        bad_epochs = jnp.where(
            epoch_over,
            jnp.where(frac_bad & (state.acks > 0), state.bad_epochs + 1, 0),
            state.bad_epochs,
        )
        acks = jnp.where(epoch_over, 0, state.acks)
        marked = jnp.where(epoch_over, 0, state.marked)
        epoch_end = jnp.where(
            epoch_over, now + self.epoch_ticks, state.epoch_end
        )
        acks = jnp.where(mask, acks + 1, acks)
        marked = jnp.where(mask & ecn, marked + 1, marked)
        repath = bad_epochs >= self.repath_after_epochs
        new_ev = jax.random.randint(
            key, state.ev.shape, 0, self.evs_size, jnp.int32
        )
        ev_out = jnp.where(repath, new_ev, state.ev)
        bad_epochs = jnp.where(repath, 0, bad_epochs)
        return PlbState(
            ev=ev_out,
            acks=acks,
            marked=marked,
            epoch_end=epoch_end,
            bad_epochs=bad_epochs,
        )

    def on_timeout(self, state, mask, now, key):
        new_ev = jax.random.randint(
            key, state.ev.shape, 0, self.evs_size, jnp.int32
        )
        return state.replace(ev=jnp.where(mask, new_ev, state.ev))

    def trace(self, site, prev, new, mask):
        # A PLB repath can technically land on a feedback round where the
        # repathing connection's own ACK mask is false (bad_epochs carried
        # from earlier rounds); the mask gate drops those so idle-tick epoch
        # rollovers never emit events — tracing is best-effort observation.
        if site == "ack":
            return _trace_counts((TR_REPATH_ACK_ECN, mask & (new.ev != prev.ev)))
        if site == "timeout":
            return _trace_counts((TR_REPATH_RTO, mask))
        return jnp.zeros((N_TRACE_KINDS,), jnp.int32)


# ---------------------------------------------------------------------------
# Flowlet switching: new random EV whenever the inter-send gap exceeds the
# flowlet timeout (paper sets it aggressively to RTT/2).  §4.1
# ---------------------------------------------------------------------------
@pytree_dataclass
class FlowletState:
    ev: jax.Array  # (N,) int32
    last_send: jax.Array  # (N,) int32 tick of previous send


class FlowletLB(LoadBalancer):
    name = "flowlet"

    def __init__(self, evs_size: int = 65536, gap_ticks: int = 32):
        super().__init__(evs_size)
        self.gap_ticks = gap_ticks

    def init_state(self, n_conns, key):
        return FlowletState(
            ev=_rand_evs(key, n_conns, self.evs_size),
            last_send=jnp.full((n_conns,), -(10**6), jnp.int32),
        )

    def choose_ev(self, state, mask, key, now):
        n = state.ev.shape[0]
        new_flowlet = mask & ((now - state.last_send) > self.gap_ticks)
        ev = jnp.where(new_flowlet, _rand_evs(key, n, self.evs_size), state.ev)
        return ev, FlowletState(
            ev=ev, last_send=jnp.where(mask, now, state.last_send)
        )

    def trace(self, site, prev, new, mask):
        if site == "choose":
            return _trace_counts((TR_REPATH_FLOWLET, mask & (new.ev != prev.ev)))
        return jnp.zeros((N_TRACE_KINDS,), jnp.int32)


# ---------------------------------------------------------------------------
# MPTCP-like: K static subflow EVs per connection, packets round-robin over
# subflows; a timeout re-hashes one subflow.  Coarse model of running K QPs
# (paper §4.1 uses K=8).  CC remains shared (documented simplification).
# ---------------------------------------------------------------------------
@pytree_dataclass
class MptcpState:
    sub_evs: jax.Array  # (N, K) int32
    rr: jax.Array  # (N,) int32 round-robin cursor


class MptcpLB(LoadBalancer):
    name = "mptcp"

    def __init__(self, evs_size: int = 65536, n_subflows: int = 8):
        super().__init__(evs_size)
        self.n_subflows = n_subflows

    def init_state(self, n_conns, key):
        return MptcpState(
            sub_evs=jax.random.randint(
                key, (n_conns, self.n_subflows), 0, self.evs_size, jnp.int32
            ),
            rr=jnp.zeros((n_conns,), jnp.int32),
        )

    def choose_ev(self, state, mask, key, now):
        idx = state.rr % self.n_subflows
        ev = jnp.take_along_axis(state.sub_evs, idx[:, None], axis=1)[:, 0]
        rr = jnp.where(mask, state.rr + 1, state.rr)
        return ev, state.replace(rr=rr)

    def on_timeout(self, state, mask, now, key):
        # Re-hash the subflow at the cursor for timed-out connections.
        idx = state.rr % self.n_subflows
        onehot = jax.nn.one_hot(idx, self.n_subflows, dtype=jnp.bool_)
        new_evs = jax.random.randint(
            key, state.sub_evs.shape, 0, self.evs_size, jnp.int32
        )
        sub_evs = jnp.where(mask[:, None] & onehot, new_evs, state.sub_evs)
        return state.replace(sub_evs=sub_evs)

    def trace(self, site, prev, new, mask):
        if site == "timeout":
            return _trace_counts((TR_REPATH_RTO, mask))
        return jnp.zeros((N_TRACE_KINDS,), jnp.int32)


# ---------------------------------------------------------------------------
# MPRDMA-like: per-packet spraying that avoids recently ECN-marked EVs via a
# small ring of "bad" EVs (no caching of good paths — the paper's contrast).
# ---------------------------------------------------------------------------
@pytree_dataclass
class MprdmaState:
    bad_evs: jax.Array  # (N, L) int32 recently marked EVs
    bad_ptr: jax.Array  # (N,) int32


class MprdmaLB(LoadBalancer):
    name = "mprdma"

    def __init__(self, evs_size: int = 65536, blacklist: int = 16):
        super().__init__(evs_size)
        self.blacklist = blacklist

    def init_state(self, n_conns, key):
        return MprdmaState(
            bad_evs=jnp.full((n_conns, self.blacklist), -1, jnp.int32),
            bad_ptr=jnp.zeros((n_conns,), jnp.int32),
        )

    def choose_ev(self, state, mask, key, now):
        n = state.bad_evs.shape[0]
        k1, k2 = jax.random.split(key)
        cand1 = _rand_evs(k1, n, self.evs_size)
        cand2 = _rand_evs(k2, n, self.evs_size)
        bad1 = jnp.any(state.bad_evs == cand1[:, None], axis=1)
        ev = jnp.where(bad1, cand2, cand1)  # one resample on blacklist hit
        return ev, state

    def on_ack(self, state, mask, ev, ecn, now, key):
        add = mask & ecn
        L = self.blacklist
        onehot = jax.nn.one_hot(state.bad_ptr % L, L, dtype=jnp.bool_)
        bad_evs = jnp.where(add[:, None] & onehot, ev[:, None], state.bad_evs)
        return MprdmaState(
            bad_evs=bad_evs,
            bad_ptr=jnp.where(add, state.bad_ptr + 1, state.bad_ptr),
        )


# ---------------------------------------------------------------------------
# BitMap (STrack-like): 1 bit of congestion state per EV in the whole EVS —
# the memory-expensive strawman of paper §3.3.  Marked EVs are avoided by
# resampling up to R candidates.
# ---------------------------------------------------------------------------
@pytree_dataclass
class BitmapState:
    bad: jax.Array  # (N, EVS) bool


class BitmapLB(LoadBalancer):
    name = "bitmap"

    def __init__(self, evs_size: int = 256, resamples: int = 4):
        super().__init__(evs_size)
        self.resamples = resamples

    def init_state(self, n_conns, key):
        return BitmapState(bad=jnp.zeros((n_conns, self.evs_size), jnp.bool_))

    def choose_ev(self, state, mask, key, now):
        n = state.bad.shape[0]
        keys = jax.random.split(key, self.resamples)
        ev = _rand_evs(keys[0], n, self.evs_size)
        for i in range(1, self.resamples):
            is_bad = jnp.take_along_axis(state.bad, ev[:, None], axis=1)[:, 0]
            cand = _rand_evs(keys[i], n, self.evs_size)
            ev = jnp.where(is_bad, cand, ev)
        return ev, state

    def on_ack(self, state, mask, ev, ecn, now, key):
        onehot = jax.nn.one_hot(ev, self.evs_size, dtype=jnp.bool_)
        bad = jnp.where(mask[:, None] & onehot, ecn[:, None], state.bad)
        return BitmapState(bad=bad)


# ---------------------------------------------------------------------------
# PRIME-like: multi-part entropy header (PAPERS.md).  The EV splits into a
# per-flow part hashed at connection setup and a sub-entropy field of
# ``sub_bits`` bits that rotates per packet through a hashed sequence —
# per-packet path diversity over a bounded window of EVs, so the reorder
# span stays bounded too.  An RTO re-hashes the flow part (the whole window
# moves off the failed path, via the threaded engine key); an ECN-marked
# ACK skips the rotation forward to leave the congested sub-path sooner.
# ---------------------------------------------------------------------------
@pytree_dataclass
class PrimeState:
    base: jax.Array  # (N,) int32 hashed per-flow part of the header
    ctr: jax.Array  # (N,) int32 per-packet rotation counter


class PrimeLB(LoadBalancer):
    name = "prime"

    def __init__(self, evs_size: int = 65536, sub_bits: int = 4):
        super().__init__(evs_size)
        assert 0 < (1 << sub_bits) <= evs_size, (sub_bits, evs_size)
        self.sub_bits = sub_bits

    def init_state(self, n_conns, key):
        return PrimeState(
            base=_rand_evs(key, n_conns, self.evs_size),
            ctr=jnp.zeros((n_conns,), jnp.int32),
        )

    def choose_ev(self, state, mask, key, now):
        sub = (
            _mix32(state.ctr) & jnp.uint32((1 << self.sub_bits) - 1)
        ).astype(jnp.int32)
        ev = (state.base + sub) % self.evs_size
        return ev, state.replace(
            ctr=jnp.where(mask, state.ctr + 1, state.ctr)
        )

    def on_ack(self, state, mask, ev, ecn, now, key):
        return state.replace(
            ctr=jnp.where(mask & ecn, state.ctr + 1, state.ctr)
        )

    def on_timeout(self, state, mask, now, key):
        new_base = _rand_evs(key, state.base.shape[0], self.evs_size)
        return state.replace(base=jnp.where(mask, new_base, state.base))

    def trace(self, site, prev, new, mask):
        if site == "ack":  # ECN-skip advances the sub-entropy rotation
            return _trace_counts((TR_REPATH_ACK_ECN, mask & (new.ctr != prev.ctr)))
        if site == "timeout":  # flow-part re-hash moves the whole window
            return _trace_counts((TR_REPATH_RTO, mask))
        return jnp.zeros((N_TRACE_KINDS,), jnp.int32)


# ---------------------------------------------------------------------------
# SeqBalance-like: reorder-free congestion-aware re-pathing (PAPERS.md).
# One EV per connection, re-drawn only at message boundaries (every
# ``msg_pkts`` sends) when the window since the last boundary saw a high
# ECN fraction — packets inside a message never straddle two paths.  An RTO
# means the message is stalled anyway (nothing left to reorder), so it
# re-paths immediately with the threaded engine key.
# ---------------------------------------------------------------------------
@pytree_dataclass
class SeqBalanceState:
    ev: jax.Array  # (N,) int32 current path
    sent: jax.Array  # (N,) int32 sends since the last boundary
    acks: jax.Array  # (N,) int32 ACKs since the last boundary
    marked: jax.Array  # (N,) int32 ECN-marked ACKs since the last boundary


class SeqBalanceLB(LoadBalancer):
    name = "seqbalance"

    def __init__(
        self,
        evs_size: int = 65536,
        msg_pkts: int = 16,
        ecn_frac_threshold: float = 0.25,
    ):
        super().__init__(evs_size)
        self.msg_pkts = msg_pkts
        self.ecn_frac_threshold = ecn_frac_threshold

    def init_state(self, n_conns, key):
        z = jnp.zeros((n_conns,), jnp.int32)
        return SeqBalanceState(
            ev=_rand_evs(key, n_conns, self.evs_size), sent=z, acks=z, marked=z
        )

    def choose_ev(self, state, mask, key, now):
        n = state.ev.shape[0]
        boundary = mask & (state.sent >= self.msg_pkts)
        congested = state.marked.astype(jnp.float32) > (
            state.acks.astype(jnp.float32) * self.ecn_frac_threshold
        )
        repath = boundary & congested
        ev = jnp.where(repath, _rand_evs(key, n, self.evs_size), state.ev)
        sent = jnp.where(
            mask, jnp.where(boundary, 1, state.sent + 1), state.sent
        )
        return ev, SeqBalanceState(
            ev=ev,
            sent=sent,
            acks=jnp.where(boundary, 0, state.acks),
            marked=jnp.where(boundary, 0, state.marked),
        )

    def on_ack(self, state, mask, ev, ecn, now, key):
        return state.replace(
            acks=jnp.where(mask, state.acks + 1, state.acks),
            marked=jnp.where(mask & ecn, state.marked + 1, state.marked),
        )

    def on_timeout(self, state, mask, now, key):
        new_ev = _rand_evs(key, state.ev.shape[0], self.evs_size)
        return state.replace(
            ev=jnp.where(mask, new_ev, state.ev),
            acks=jnp.where(mask, 0, state.acks),
            marked=jnp.where(mask, 0, state.marked),
        )

    def trace(self, site, prev, new, mask):
        if site == "choose":  # congestion-triggered message-boundary repath
            return _trace_counts((TR_REPATH_EPOCH, mask & (new.ev != prev.ev)))
        if site == "timeout":
            return _trace_counts((TR_REPATH_RTO, mask))
        return jnp.zeros((N_TRACE_KINDS,), jnp.int32)


# ---------------------------------------------------------------------------
# CONGA-style flowlet table: a small per-connection table of candidate EVs
# with a cached congestion score fed by ECN marks (integer EWMA).  A flowlet
# gap switches to the least-congested cached candidate instead of a uniform
# redraw; an RTO re-hashes the active candidate (threaded engine key) and
# clears its score so the fresh path starts unprejudiced.
# ---------------------------------------------------------------------------
@pytree_dataclass
class FlowletTableState:
    cand: jax.Array  # (N, T) int32 candidate EVs
    score: jax.Array  # (N, T) int32 cached congestion score
    cur: jax.Array  # (N,) int32 active candidate index
    last_send: jax.Array  # (N,) int32 tick of previous send


class FlowletTableLB(LoadBalancer):
    name = "flowlet_table"
    SCORE_MARK = 64  # score bump per ECN-marked ACK (decay is 1/4 per ACK)

    def __init__(
        self, evs_size: int = 65536, table: int = 4, gap_ticks: int = 32
    ):
        super().__init__(evs_size)
        self.table = table
        self.gap_ticks = gap_ticks

    def init_state(self, n_conns, key):
        return FlowletTableState(
            cand=jax.random.randint(
                key, (n_conns, self.table), 0, self.evs_size, jnp.int32
            ),
            score=jnp.zeros((n_conns, self.table), jnp.int32),
            cur=jnp.zeros((n_conns,), jnp.int32),
            last_send=jnp.full((n_conns,), -(10**6), jnp.int32),
        )

    def choose_ev(self, state, mask, key, now):
        new_flowlet = mask & ((now - state.last_send) > self.gap_ticks)
        best = jnp.argmin(state.score, axis=1).astype(jnp.int32)
        cur = jnp.where(new_flowlet, best, state.cur)
        ev = jnp.take_along_axis(state.cand, cur[:, None], axis=1)[:, 0]
        return ev, state.replace(
            cur=cur, last_send=jnp.where(mask, now, state.last_send)
        )

    def on_ack(self, state, mask, ev, ecn, now, key):
        hit = mask[:, None] & (state.cand == ev[:, None])
        decayed = (
            state.score
            - state.score // 4
            + jnp.where(ecn, self.SCORE_MARK, 0)[:, None]
        )
        return state.replace(score=jnp.where(hit, decayed, state.score))

    def on_timeout(self, state, mask, now, key):
        onehot = jax.nn.one_hot(state.cur, self.table, dtype=jnp.bool_)
        sel = mask[:, None] & onehot
        new_cand = jax.random.randint(
            key, state.cand.shape, 0, self.evs_size, jnp.int32
        )
        return state.replace(
            cand=jnp.where(sel, new_cand, state.cand),
            score=jnp.where(sel, 0, state.score),
        )

    def trace(self, site, prev, new, mask):
        if site == "choose":  # flowlet gap switched to another candidate
            return _trace_counts((TR_REPATH_FLOWLET, mask & (new.cur != prev.cur)))
        if site == "timeout":  # active candidate re-hashed + score cleared
            return _trace_counts((TR_REPATH_RTO, mask))
        return jnp.zeros((N_TRACE_KINDS,), jnp.int32)


# ---------------------------------------------------------------------------
# SwitchLB: N variants behind one lax.switch branch index, so scenarios that
# differ only in their load balancer share a single compilation (the sweep
# engine's LB dispatch, repro.netsim.sweep).  State is (branch_idx, tuple of
# every variant's state); each callback switches into the active variant,
# passing the *same* key/mask the variant would see serially and rewriting
# only its own state slot — so the active branch's stream is bit-identical
# to a serial run with the plain variant.  Under vmap the switch lowers to
# run-all-branches + select, which is the price of one compilation for the
# whole LB column.
# ---------------------------------------------------------------------------
class SwitchLB(LoadBalancer):
    name = "switch"

    def __init__(self, variants):
        variants = tuple(variants)
        assert variants, "need at least one variant"
        flags = {v.switch_adaptive for v in variants}
        assert len(flags) == 1, (
            "SwitchLB variants must agree on switch_adaptive (in-network "
            "adaptive LBs change the routing function, a static property); "
            "bucket them separately"
        )
        sizes = {int(v.evs_size) for v in variants}
        if len(sizes) != 1:
            raise ValueError(
                "SwitchLB variants must share one evs_size (every branch "
                "samples the same entropy space; a smaller variant would "
                "silently draw out-of-range EVs): got "
                + ", ".join(f"{v.name}={v.evs_size}" for v in variants)
                + ".  Pass evs_size explicitly to each variant — note "
                "BitmapLB defaults to 256 while the rest of the zoo "
                "defaults to 65536."
            )
        super().__init__(sizes.pop())
        self.variants = variants
        self.switch_adaptive = flags.pop()
        self.name = "switch(" + "+".join(v.name for v in variants) + ")"

    def _dispatch(self, bidx, states, fn, out_proto=None):
        """lax.switch over per-variant callbacks; branch i rewrites state
        slot i only.  fn(i, state_i) -> (aux_i, new_state_i)."""

        def mk(i):
            def br(sts):
                # one profiler scope per variant, so a trace splits the
                # switch's device time by load balancer
                with jax.named_scope(f"lb.{self.variants[i].name}"):
                    aux, si = fn(i, sts[i])
                return aux, tuple(
                    si if j == i else sts[j] for j in range(len(sts))
                )

            return br

        return jax.lax.switch(bidx, [mk(i) for i in range(len(self.variants))], states)

    def init_state(self, n_conns, key):
        # every variant is seeded with the same key it would get serially
        return (
            jnp.zeros((), jnp.int32),
            tuple(v.init_state(n_conns, key) for v in self.variants),
        )

    def with_branch(self, state, branch_idx):
        """Rebind the branch index (the sweep sets it per scenario row)."""
        return (jnp.asarray(branch_idx, jnp.int32), state[1])

    def choose_ev(self, state, mask, key, now):
        bidx, states = state
        evs, states = self._dispatch(
            bidx, states,
            lambda i, s: self.variants[i].choose_ev(s, mask, key, now),
        )
        return evs, (bidx, states)

    def on_ack(self, state, mask, ev, ecn, now, key):
        bidx, states = state
        _, states = self._dispatch(
            bidx, states,
            lambda i, s: (
                jnp.zeros((), jnp.int32),
                self.variants[i].on_ack(s, mask, ev, ecn, now, key),
            ),
        )
        return (bidx, states)

    def on_timeout(self, state, mask, now, key):
        bidx, states = state
        _, states = self._dispatch(
            bidx, states,
            lambda i, s: (
                jnp.zeros((), jnp.int32),
                self.variants[i].on_timeout(s, mask, now, key),
            ),
        )
        return (bidx, states)

    def trace(self, site, prev, new, mask):
        # Only the active branch mutated its state slot, so only its trace
        # port sees a diff — the switch picks exactly that variant's counts.
        bidx = new[0]

        def mk(i):
            def br(_):
                return self.variants[i].trace(site, prev[1][i], new[1][i], mask)

            return br

        return jax.lax.switch(
            bidx,
            [mk(i) for i in range(len(self.variants))],
            jnp.zeros((), jnp.int32),
        )


# ---------------------------------------------------------------------------
# Adaptive RoCE (NVIDIA Spectrum-X style): in-network per-packet adaptive
# routing — switches pick the least-loaded valid uplink.  The sender sprays
# (EV is ignored by adaptive switches).
# ---------------------------------------------------------------------------
class AdaptiveRoceLB(OpsLB):
    name = "adaptive_roce"
    switch_adaptive = True


REGISTRY = {
    cls.name: cls
    for cls in [
        EcmpLB,
        OpsLB,
        RepsLB,
        PlbLB,
        FlowletLB,
        MptcpLB,
        MprdmaLB,
        BitmapLB,
        AdaptiveRoceLB,
        PrimeLB,
        SeqBalanceLB,
        FlowletTableLB,
    ]
}


def make_lb(name: str, **kwargs) -> LoadBalancer:
    return REGISTRY[name](**kwargs)

"""Pallas kernel parity on randomized multi-tick event streams.

Feeds randomized ACK/timeout/send streams through the fused kernels in
interpret mode, threading state tick-to-tick, and asserts bit-identity
against both the pure-jnp refs and the scalar REPSOracle — including the
freezing-mode recycle branch (getNextEV with no valid entries).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import reps as reps_core
from repro.core.load_balancers import RepsLB
from repro.kernels import ops, ref
from repro.kernels.reps_update import BUF


def _stream_inputs(key, N, evs, p_ack=0.5, p_to=0.2, p_send=0.7):
    ks = [jax.random.fold_in(key, i) for i in range(6)]
    return dict(
        ack_mask=jax.random.bernoulli(ks[0], p_ack, (N,)).astype(jnp.int32),
        ack_ev=jax.random.randint(ks[1], (N,), 0, evs, jnp.int32),
        ack_ecn=jax.random.bernoulli(ks[2], 0.3, (N,)).astype(jnp.int32),
        timeout_mask=jax.random.bernoulli(ks[3], p_to, (N,)).astype(jnp.int32),
        send_mask=jax.random.bernoulli(ks[4], p_send, (N,)).astype(jnp.int32),
        rand_ev=jax.random.randint(ks[5], (N,), 0, evs, jnp.int32),
    )


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reps_tick_stream_matches_ref(seed):
    """40 ticks of chained kernel state == chained ref state, bit for bit."""
    N, evs, bdp, freeze = 70, 128, 3, 12
    key = jax.random.PRNGKey(seed)
    cfg = reps_core.REPSConfig(
        buffer_size=BUF, evs_size=evs, num_pkts_bdp=bdp, freezing_timeout=freeze
    )
    st = reps_core.init_state(cfg, N)
    kstate = rstate = (
        st.buf_ev, st.buf_valid.astype(jnp.int32), st.head, st.num_valid,
        st.explore_counter, st.is_freezing.astype(jnp.int32),
        st.exit_freezing, st.n_cached,
    )
    for t in range(40):
        inp = _stream_inputs(jax.random.fold_in(key, t), N, evs)
        args = tuple(inp.values()) + (t, bdp, freeze)
        kout = ops.reps_tick(*kstate, *args)
        rout = ref.reps_tick_ref(*rstate, *args)
        for i, (g, w) in enumerate(zip(kout, rout)):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w), err_msg=f"tick {t} field {i}"
            )
        kstate, rstate = kout[:8], rout[:8]


def test_reps_tick_stream_matches_scalar_oracle():
    """Chained kernel ticks == the paper-pseudocode oracle, per connection,
    on a stream that drives connections into freezing mode and back out."""
    N, evs, bdp, freeze = 13, 64, 2, 6
    key = jax.random.PRNGKey(7)
    cfg = reps_core.REPSConfig(
        buffer_size=BUF, evs_size=evs, num_pkts_bdp=bdp, freezing_timeout=freeze
    )
    oracles = [reps_core.REPSOracle(cfg) for _ in range(N)]
    st = reps_core.init_state(cfg, N)
    kstate = (
        st.buf_ev, st.buf_valid.astype(jnp.int32), st.head, st.num_valid,
        st.explore_counter, st.is_freezing.astype(jnp.int32),
        st.exit_freezing, st.n_cached,
    )
    saw_freezing_recycle = False
    for t in range(80):
        # heavy timeouts + sparse acks exercise the recycle-at-head branch
        inp = _stream_inputs(
            jax.random.fold_in(key, t), N, evs, p_ack=0.3, p_to=0.5, p_send=0.8
        )
        am, ev, ecn, tm, sm, rnd = (np.asarray(v) for v in inp.values())
        args = tuple(inp.values()) + (t, bdp, freeze)
        kout = ops.reps_tick(*kstate, *args)
        for i, o in enumerate(oracles):
            if am[i]:
                o.on_ack(int(ev[i]), bool(ecn[i]), t)
            if tm[i]:
                o.on_failure_detection(t)
            if sm[i]:
                if o.is_freezing and o.num_valid == 0 and o.n_cached > 0:
                    saw_freezing_recycle = True
                got_ev = o.on_send(int(rnd[i]))
                assert int(kout[8][i]) == got_ev, (t, i)
            assert int(kout[2][i]) == o.head, (t, i)
            assert int(kout[3][i]) == o.num_valid, (t, i)
            assert bool(kout[5][i]) == o.is_freezing, (t, i)
            assert list(np.asarray(kout[0][i])) == o.buf_ev, (t, i)
        kstate = kout[:8]
    assert saw_freezing_recycle, "stream never hit the freezing recycle branch"


@pytest.mark.parametrize("seed", [0, 1])
def test_queue_tick_stream_matches_ref(seed):
    """Chained queue ticks (serve + enqueue) stay bit-identical to the ref."""
    Q, K, cap = 48, 160, 24
    key = jax.random.PRNGKey(seed + 100)
    qlen = jnp.zeros((Q,), jnp.int32)
    qlen_ref = jnp.zeros((Q,), jnp.int32)
    for t in range(30):
        k = jax.random.fold_in(key, t)
        serve = jax.random.bernoulli(jax.random.fold_in(k, 1), 0.8, (Q,)).astype(jnp.int32)
        target = jax.random.randint(jax.random.fold_in(k, 2), (K,), 0, Q + 6, jnp.int32)
        u = jax.random.uniform(jax.random.fold_in(k, 3), (K,))
        got = ops.queue_tick(target, u, qlen, serve, cap, 5, 19)
        want = ref.queue_tick_ref(
            np.asarray(target), np.asarray(u), qlen_ref, serve, cap, 5, 19
        )
        for name, g, w in zip(["qlen", "accept", "mark"], got[:3], want[:3]):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w), err_msg=f"tick {t} {name}"
            )
        acc = np.asarray(got[1])
        np.testing.assert_array_equal(
            np.asarray(got[3])[acc], np.asarray(want[3])[acc], err_msg=f"tick {t} pos"
        )
        qlen, qlen_ref = got[0], want[0]


def test_repslb_backends_bit_identical():
    """RepsLB(backend=pallas) == RepsLB(backend=jnp) through the LB API,
    state and chosen EVs, over a random stream."""
    kwargs = dict(evs_size=512, num_pkts_bdp=4, freezing_timeout=16)
    lbj = RepsLB(backend="jnp", **kwargs)
    lbp = RepsLB(backend="pallas", **kwargs)
    key = jax.random.PRNGKey(3)
    N = 29
    sj, sp = lbj.init_state(N, key), lbp.init_state(N, key)
    for t in range(50):
        k = jax.random.fold_in(key, t)
        am = jax.random.bernoulli(jax.random.fold_in(k, 1), 0.4, (N,))
        ev = jax.random.randint(jax.random.fold_in(k, 2), (N,), 0, 512, jnp.int32)
        ecn = jax.random.bernoulli(jax.random.fold_in(k, 3), 0.25, (N,))
        tm = jax.random.bernoulli(jax.random.fold_in(k, 4), 0.3, (N,))
        sm = jax.random.bernoulli(jax.random.fold_in(k, 5), 0.7, (N,))
        now = jnp.int32(t)
        sj = lbj.on_ack(sj, am, ev, ecn, now, jax.random.fold_in(k, 7))
        sp = lbp.on_ack(sp, am, ev, ecn, now, jax.random.fold_in(k, 7))
        sj = lbj.on_timeout(sj, tm, now, jax.random.fold_in(k, 8))
        sp = lbp.on_timeout(sp, tm, now, jax.random.fold_in(k, 8))
        ej, sj = lbj.choose_ev(sj, sm, jax.random.fold_in(k, 6), now)
        ep, sp = lbp.choose_ev(sp, sm, jax.random.fold_in(k, 6), now)
        m = np.asarray(sm)
        np.testing.assert_array_equal(np.asarray(ej)[m], np.asarray(ep)[m])
        for a, b in zip(jax.tree_util.tree_leaves(sj), jax.tree_util.tree_leaves(sp)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Batched tick hot-spot kernels (seg_rank / seg_sum) — unit parity plus the
# sweep-path contract: kernels_backend="pallas" (interpret off-TPU) must be
# bit-identical to the jnp scatter formulations across multi-bucket grids,
# including horizon-frozen rows and failure schedules.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "K,S", [(7, 4), (64, 33), (130, 12), (320, 195), (300, 4500)]
)
def test_seg_primitives_match_refs(K, S):
    """seg_rank / seg_sum kernels == the pure-jnp oracles, per element and
    under vmap (the sweep row axis adds a grid dimension); S = 4500 spans
    three segment tiles, the last one partial."""
    key = jax.random.PRNGKey(K * 1000 + S)
    seg = jax.random.randint(key, (3, K), 0, S + 2, jnp.int32)  # incl. >= S
    vals = jax.random.randint(jax.random.fold_in(key, 1), (3, 5, K), -4, 9,
                              jnp.int32)
    rk = jax.vmap(lambda s: ops.seg_rank(s, S))(seg)
    rr = jax.vmap(lambda s: ref.seg_rank_ref(s, S))(seg)
    in_range = np.asarray(seg) < S  # kernel ranks out-of-range ids as 0
    np.testing.assert_array_equal(
        np.asarray(rk)[in_range], np.asarray(rr)[in_range]
    )
    sk = jax.vmap(lambda s, v: ops.seg_sum(s, v, S))(seg, vals)
    sr = jax.vmap(lambda s, v: ref.seg_sum_ref(s, v, S))(seg, vals)
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))


def test_sweep_kernels_backend_pallas_bit_identical():
    """A ≥2-bucket sweep grid under kernels_backend="pallas" (interpret
    mode) bit-matches the jnp path cell by cell — including a frozen-horizon
    row (two horizons merged into one bucket) and a failure schedule."""
    from repro.configs.arcane_paper import FATTREE_32_CI
    from repro.netsim import (
        SweepCase, SweepEngine, Topology, failures, workloads,
    )

    cfg = FATTREE_32_CI
    topo = Topology.build(cfg)
    fs = failures.link_down(list(topo.t0_up_queues(0)[:2]), 20, 90)
    wl_p = workloads.permutation(32, 12, seed=1)
    wl_i = workloads.incast(32, 5, 12)

    def cases():
        return [
            # same shapes, different horizons -> one bucket, the 90-tick
            # row freezes at its own horizon while the bucket scans to 140
            SweepCase("p/reps", wl_p, "reps", 140,
                      lb_kwargs=dict(evs_size=cfg.evs_size)),
            SweepCase("p/ops/frozen", wl_p, "ops", 90,
                      lb_kwargs=dict(evs_size=cfg.evs_size)),
            # distinct shape bucket (NC 5 -> padded 8) with failures
            SweepCase("i/reps/fail", wl_i, "reps", 140, failures=fs,
                      lb_kwargs=dict(evs_size=cfg.evs_size)),
        ]

    engines = {
        kb: SweepEngine(cfg, cases(), devices=1, kernels_backend=kb)
        for kb in ("jnp", "pallas")
    }
    assert len(engines["jnp"].buckets) >= 2
    assert engines["jnp"].plan == engines["pallas"].plan
    results = {kb: e.run(collect="none") for kb, e in engines.items()}
    for c in cases():
        a = results["jnp"].state_for(c.name)
        b = results["pallas"].state_for(c.name)
        for name in ("c_done_tick", "s_stats", "q_served", "c_delivered",
                     "pkt", "q_len"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
                err_msg=f"{c.name}: {name}",
            )
    # and the jnp sweep equals its serial reference (the existing sweep
    # contract holds with the backend switch threaded through)
    ref_sim = engines["jnp"].serial_sim("i/reps/fail")
    st, _ = ref_sim.run(140)
    jax.block_until_ready(st.c_done)
    sw = results["jnp"].state_for("i/reps/fail")
    np.testing.assert_array_equal(np.asarray(st.c_done_tick), sw.c_done_tick)
    np.testing.assert_array_equal(np.asarray(st.s_stats), sw.s_stats)

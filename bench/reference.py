"""The comparison that decides ``correct``, and its control.

The reference for a sweep row is ``bench/plainsim.py``: a plain packet
simulator of the same model, built from the generated inputs alone (the
cell's numbers, workload, failures, the row's seed, the load balancer's
name and arguments) and importing nothing of the program.  It runs after
the window, one row at a time, in chunks of the cell's chunk length, on
the chip (the model's float state is float32, and the chip's arithmetic is
what both sides must agree on).

Compared exactly, for every sampled row: each connection's transport state
(in-flight count, next sequence, deliveries, ACK debt, completion and its
tick, retransmit marks and bitmap, SACK bitmap, window, DCTCP alpha), the
queues (length, head, serve count and their packets in FIFO order), the
live packets as a multiset, each host's round-robin turn, the free-slot
count, the counters and the load balancer's state (``state_mismatch``);
and in summary mode every word of the row's telemetry sketch
(``sketch_mismatch``).  Both limits are 0.

The control is the reference with the only float state, the window and
DCTCP alpha, held in bfloat16 (rounded after every tick): the nearest
precision below the one the configuration states.  Put in the program's
place it must fail.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import plainsim

LIMITS = {"state_mismatch": 0, "sketch_mismatch": 0}


def program_view(state, lb_state) -> dict:
    """``plainsim.view`` of one row of the program's ``SimState`` (host
    arrays), read by field name; ``lb_state`` is the row's own load
    balancer state."""
    fields = ("p_state", "p_conn", "p_ev", "p_seq", "p_hop", "p_cur_queue",
              "p_send_tick", "p_event_tick", "p_ecn", "p_orphan",
              "p_ack_count")
    p = {k: np.asarray(getattr(state, f), np.int32)
         for k, f in zip(plainsim.PACKET_FIELDS, fields)}
    st = {k: getattr(state, "c_" + k) for k in (
        "inflight", "next_new", "delivered", "rx_pending", "done",
        "done_tick", "rtx_count", "rtx", "rcv", "cwnd", "alpha")}
    st.update(qbuf=state.qbuf, q_head=state.q_head, q_len=state.q_len,
              q_served=state.q_served, rr=state.h_rr,
              fl_count=state.fl_count, stats=state.s_stats)
    if hasattr(lb_state, "buf_ev"):  # REPS: the paper's Table 1 fields
        st["lb"] = {k: getattr(lb_state, k) for k in (
            "buf_ev", "buf_valid", "head", "num_valid", "explore_counter",
            "is_freezing", "exit_freezing", "n_cached")}
    else:
        st["lb"] = lb_state
    return plainsim.view_of(p, st)


def mismatches(a: dict, b: dict) -> int:
    """Elements that differ between two views (a field of another shape,
    or one that only one side has, counts whole)."""
    n = 0
    for k in set(a) | set(b):
        if k not in a or k not in b:
            n += np.size(a.get(k, b.get(k)))
            continue
        x, y = np.asarray(a[k]), np.asarray(b[k])
        n += (int(np.count_nonzero(x != y)) if x.shape == y.shape
              else max(x.size, y.size))
    return n


def sketch_mismatches(a, b) -> int:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.size, b.size)
    return int(np.count_nonzero(a != b))


def row_of(state, row: int, branch: int):
    """One row of a host ``SimState`` batch and its own LB state, taken out
    of the ``SwitchLB`` carry ``(branch index, variant states)``."""
    st = jax.tree_util.tree_map(lambda x: x[row], state)
    return st, st.lb_state[1][branch]


def run_reference(cfg, inputs, case, si: int, ticks: int, collect: str,
                  chunk: int, horizon: int, control: bool = False):
    """The reference row of ``case`` for seed index ``si``, run to
    ``ticks`` (never past the horizon: a frozen row stopped there).
    Returns ``(view, flat sketch or None)``."""
    row = plainsim.Row(cfg, inputs.workload, inputs.failures,
                       inputs.row_seeds[si], case.lb, dict(case.lb_kwargs),
                       horizon, collect, control=control)
    step = row.chunk_fn()
    st, sk = row.init()
    t, ticks = 0, min(ticks, horizon)
    while t < ticks:
        n = min(chunk, ticks - t)
        st, sk = step(st, sk, np.int32(t), n)
        t += n
    sk = plainsim.Sketch.flat(jax.device_get(sk)) if sk is not None else None
    return plainsim.view(st), sk


def _numbers(pairs, collect: str):
    """Tally ``[((view, sketch), (ref_view, ref_sketch)), ...]`` into the
    numbers compared, each beside its limit."""
    numbers = {"state_mismatch": sum(mismatches(p[0], r[0])
                                     for p, r in pairs)}
    if collect == "summary":
        numbers["sketch_mismatch"] = sum(sketch_mismatches(p[1], r[1])
                                         for p, r in pairs)
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in numbers.items()}


def _reference(memo, cfg, inputs, case, si, ticks, collect, chunk, horizon,
               control=False):
    key = (case.name, si, ticks, control)
    if key not in memo:
        memo[key] = run_reference(cfg, inputs, case, si, ticks, collect,
                                  chunk, horizon, control)
    return memo[key]


def check(cfg, inputs, index: dict, snapshots: dict, collect: str,
          chunk: int, horizon: int, memo: dict | None = None):
    """Compare the sampled rows of every snapshot with the reference.

    ``index``: bucket -> [(row, case, seed index, branch)], ``snapshots``:
    bucket -> ``harness.Snapshot``.  ``memo``, when given, keeps reference
    results between calls on the same inputs.  Returns ``(numbers,
    rows_compared)``, numbers as ``{name: {"value", "limit"}}``."""
    memo = {} if memo is None else memo
    pairs = []
    for bi, snap in sorted(snapshots.items()):
        for row, case, si, branch in index[bi]:
            tel = snap.telemetry[row] if collect == "summary" else None
            pairs.append((
                (program_view(*row_of(snap.state, row, branch)), tel),
                _reference(memo, cfg, inputs, case, si, snap.ticks, collect,
                           chunk, horizon),
            ))
    return _numbers(pairs, collect), len(pairs)


def control(cfg, inputs, index: dict, ticks: int, collect: str, chunk: int,
            horizon: int, memo: dict | None = None):
    """The control in the program's place: the sampled rows of every bucket
    computed by the reference with its float state in bfloat16, compared
    with the reference at ``ticks``.  Same return as ``check``."""
    memo = {} if memo is None else memo
    pairs = [
        (_reference(memo, cfg, inputs, case, si, ticks, collect, chunk,
                    horizon, control=True),
         _reference(memo, cfg, inputs, case, si, ticks, collect, chunk,
                    horizon))
        for bi in sorted(index) for _row, case, si, _branch in index[bi]
    ]
    return _numbers(pairs, collect), len(pairs)

"""The comparison that decides ``correct``, and its control.

The reference for a sweep row is ``bench/plainsim.py``: a plain packet
simulator of the same model, built from the generated inputs alone (the
cell's numbers, workload, failures, the row's seed, the load balancer's
name and arguments) and importing nothing of the program.  A configuration
that names a ``reference`` runs ``bench/references/<name>.py`` in its place
(``model_of``): its ``Row`` (a subclass of ``plainsim.Row``),
its ``view`` of a reference state and, where it models more of the
program's state, ``program_view(state, lb_state)``, the entries it reads
from the program's row in addition to those read here.  It runs after
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

Where the program finalized a bucket before its horizon (early exit), it
claims that no later tick would change a row.  Every row of that bucket,
as the program left it, is then held to the reference model's own rule
(``Row.quiescent``): each row that the rule does not find quiescent counts
one into ``state_mismatch``.  For the sampled rows, whose state equals the
reference's or fails already, this is the reference row's own quiescence.

The control is the reference with the only float state, the window and
DCTCP alpha, held in bfloat16 (rounded after every tick): the nearest
precision below the one the configuration states.  Put in the program's
place it must fail.
"""
from __future__ import annotations

import pathlib

import jax
import numpy as np

from bench import extensions, plainsim

ROOT = pathlib.Path(__file__).resolve().parents[1]
LIMITS = {"state_mismatch": 0, "sketch_mismatch": 0}


def model_of(config: dict, root: pathlib.Path = ROOT):
    """The reference model of a configuration: ``bench/references/<name>.py``
    where it names a ``reference``, else ``plainsim``."""
    if "reference" in config:
        return extensions.find(root, "references", config["reference"])
    return plainsim


def program_view(state, lb_state, model=plainsim) -> dict:
    """``plainsim.view`` of one row of the program's ``SimState`` (host
    arrays), read by field name; ``lb_state`` is the row's own load
    balancer state.  A reference ``model`` that reads more adds entries of
    its own (``model.program_view``); it replaces none of these."""
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
    view = plainsim.view_of(p, st)
    more = getattr(model, "program_view", None)
    if more is None:
        return view
    added = more(state, lb_state)
    if set(added) & set(view):
        raise ValueError(f"a reference extension may only add to the "
                         f"program's view: {sorted(set(added) & set(view))}")
    return {**view, **added}


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


def reference_row(cfg, inputs, case, si: int, collect: str, horizon: int,
                  control: bool = False, model=plainsim):
    """The ``model``'s row of ``case`` for seed index ``si``."""
    return model.Row(cfg, inputs.workload, inputs.failures,
                     inputs.row_seeds[si], case.lb, dict(case.lb_kwargs),
                     horizon, collect, control=control)


def run_reference(cfg, inputs, case, si: int, ticks: int, collect: str,
                  chunk: int, horizon: int, control: bool = False,
                  model=plainsim):
    """The reference row of ``case`` for seed index ``si``, run to
    ``ticks`` (never past the horizon: a frozen row stopped there).
    Returns ``(view, flat sketch or None)``."""
    row = reference_row(cfg, inputs, case, si, collect, horizon, control,
                        model)
    step = row.chunk_fn()
    st, sk = row.init()
    t, ticks = 0, min(ticks, horizon)
    while t < ticks:
        n = min(chunk, ticks - t)
        st, sk = step(st, sk, np.int32(t), n)
        t += n
    sk = row.sketch.flat(jax.device_get(sk)) if sk is not None else None
    return model.view(st), sk


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
               control=False, model=plainsim):
    key = (case.name, si, ticks, control, id(model))
    if key not in memo:
        memo[key] = run_reference(cfg, inputs, case, si, ticks, collect,
                                  chunk, horizon, control, model)
    return memo[key]


def unquiet_rows(rule, state, ticks: int, model=plainsim) -> int:
    """Rows of a finalized bucket (host ``SimState``, real rows only) that
    the reference ``rule`` (a ``Row``) does not find quiescent at
    ``ticks``.  Each row's load balancer state is its own ``SwitchLB``
    branch's."""
    branches = np.asarray(state.lb_state[0])
    return sum(
        not rule.quiescent(program_view(*row_of(state, r, int(b)), model),
                           ticks)
        for r, b in enumerate(branches)
    )


def check(cfg, inputs, index: dict, snapshots: list, collect: str,
          chunk: int, horizon: int, memo: dict | None = None,
          model=plainsim):
    """Compare the sampled rows of every snapshot with the reference
    ``model`` (``model_of``).

    ``index``: bucket -> [(row, case, seed index, branch)], ``snapshots``:
    [(bucket, ``harness.Snapshot``)]: each bucket's first finalize in the
    window and the bucket in flight at its close.  A finalized snapshot's
    rows must be quiescent by the model's rule (``unquiet_rows``).
    ``memo``, when given, keeps reference results between calls on the same
    inputs.  Returns ``(numbers, rows_compared)``, numbers as
    ``{name: {"value", "limit"}}``."""
    memo = {} if memo is None else memo
    pairs, unquiet = [], 0
    for bi, snap in snapshots:
        for row, case, si, branch in index[bi]:
            tel = snap.telemetry[row] if collect == "summary" else None
            pairs.append((
                (program_view(*row_of(snap.state, row, branch), model), tel),
                _reference(memo, cfg, inputs, case, si, snap.ticks, collect,
                           chunk, horizon, model=model),
            ))
        if snap.finalized:
            _, case, si, _ = index[bi][0]
            rule = reference_row(cfg, inputs, case, si, collect, horizon,
                                 model=model)
            unquiet += unquiet_rows(rule, snap.state, snap.ticks, model)
    numbers = _numbers(pairs, collect)
    numbers["state_mismatch"]["value"] += unquiet
    return numbers, len(pairs)


def control(cfg, inputs, index: dict, ticks: int, collect: str, chunk: int,
            horizon: int, memo: dict | None = None, model=plainsim):
    """The control in the program's place: the sampled rows of every bucket
    computed by the reference ``model`` with its float state in bfloat16,
    compared with the reference at ``ticks``.  Same return as ``check``."""
    memo = {} if memo is None else memo
    pairs = [
        (_reference(memo, cfg, inputs, case, si, ticks, collect, chunk,
                    horizon, control=True, model=model),
         _reference(memo, cfg, inputs, case, si, ticks, collect, chunk,
                    horizon, model=model))
        for bi in sorted(index) for _row, case, si, _branch in index[bi]
    ]
    return _numbers(pairs, collect), len(pairs)

"""The algorithmic work of the tick kernels, counted from a cell's shapes.

Four kernels sit on the tick's path: ``seg_rank`` (the FIFO rank of a
connection's ACKs), ``seg_sum`` (per-connection sums of the feedback, RTO,
delivery and injection events), ``queue_tick`` (arrivals: FIFO rank per
queue, tail drop, RED marks) and ``reps_tick`` (REPS's Algorithms 1 and 2).
What each is asked per row and tick follows from the model and the cell's
sizes alone, whatever implements it:

* feedback: one rank over the tick's ACK slots (at most one per host
  downlink, ``NH``) and one sum of 5 fields over them into
  ``(feedback_rounds + 1) x (NC + 1)`` segments (in-flight debit, ACK flag,
  EV, ECN, RTT per round);
* RTO, delivery, injection: sums of 2, 4 and 2 fields over ``NH`` events
  into ``NC + 1`` segments;
* arrivals: ``NQ + NH`` arrival slots onto ``NQ`` queues;
* REPS rows only: ``feedback_rounds`` ACK steps, one timeout step and one
  send step over the row's ``NC`` connections.

Bytes: each input read once and each output written once at its dtype,
counting only what the step reads or changes (one buffer slot per REPS
step, not the whole ring).  Ops: the integer operations of the least
algorithm, per element, as listed by each function.  The least time on a
chip is ``max(ops / peak ops, bytes / HBM bandwidth)``; with well under
one op per byte the bytes bound at any peak in ``peaks.json``.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
KERNELS = ("seg_rank", "seg_sum", "queue_tick", "reps_tick")
I32, BOOL = 4, 1


@dataclasses.dataclass(frozen=True)
class Work:
    kernel: str
    bytes: int
    ops: int

    def __mul__(self, n: int) -> "Work":
        return Work(self.kernel, self.bytes * n, self.ops * n)


def seg_rank(k: int) -> Work:
    """Rank of each of ``k`` ids among the earlier equal ones: ids in, ranks
    out; one counter increment per element."""
    return Work("seg_rank", I32 * k + I32 * k, k)


def seg_sum(k: int, fields: int, segments: int) -> Work:
    """``fields`` rows of ``k`` values summed into ``segments`` by ``k``
    ids: one add per value."""
    return Work("seg_sum", I32 * (k + fields * k + fields * segments),
                fields * k)


def queue_tick(k: int, queues: int) -> Work:
    """``k`` arrivals (target, RED uniform) onto ``queues`` lengths: per
    arrival its rank (1), the room test (2), its position (1) and the RED
    mark (sub, div, 2 clips, compare: 5); one length update per queue.
    Out: lengths, accept and mark flags, positions."""
    return Work("queue_tick",
                (I32 + I32) * k + I32 * queues                  # in
                + I32 * queues + (BOOL + BOOL + I32) * k,       # out
                9 * k + queues)


# REPS per connection and step: (bytes in, bytes out, ops), read off the
# paper's pseudocode.  ACK: mask, EV, ECN flag, head, valid count, the
# head slot's valid bit, cached count, freezing flag and deadline in; the
# head slot's EV and valid bit, head, valid count, cached count, freezing
# flag, explore counter out.  Timeout: mask, freezing flag, explore counter
# in; freezing flag and deadline out.  Send: mask, fresh EV, cached count,
# valid count, freezing flag, explore counter, head, the chosen slot's EV
# in; the EV, the slot's valid bit, valid count, head, explore counter out.
REPS_ACK = (3 * BOOL + 5 * I32 + BOOL, 3 * I32 + 2 * BOOL + 2 * I32, 8)
REPS_TIMEOUT = (2 * BOOL + I32, BOOL + I32, 4)
REPS_SEND = (2 * BOOL + 6 * I32, 4 * I32 + BOOL, 12)


def reps_step(n: int, step: tuple) -> Work:
    b_in, b_out, ops = step
    return Work("reps_tick", n * (b_in + b_out) + I32, n * ops)


def row_tick(n_hosts: int, n_conns: int, n_queues: int, rounds: int,
             lb: str) -> list[Work]:
    """Every kernel's work in one tick of one row with load balancer
    ``lb``."""
    work = [
        seg_rank(n_hosts),
        seg_sum(n_hosts, 5, (rounds + 1) * (n_conns + 1)),
        seg_sum(n_hosts, 2, n_conns + 1),
        seg_sum(n_hosts, 4, n_conns + 1),
        queue_tick(n_queues + n_hosts, n_queues),
        seg_sum(n_hosts, 2, n_conns + 1),
    ]
    if lb == "reps":
        work += [reps_step(n_conns, REPS_ACK)] * rounds
        work += [reps_step(n_conns, REPS_TIMEOUT), reps_step(n_conns,
                                                             REPS_SEND)]
    return work


def n_queues(cfg) -> int:
    """Queues of the 2-tier fat tree: ToR uplinks, spine downlinks and host
    downlinks."""
    if cfg.tiers != 2 or cfg.fabric:
        raise NotImplementedError("kernel work is counted for 2-tier trees")
    return 2 * cfg.n_hosts // cfg.hosts_per_tor * cfg.uplinks_per_tor \
        + cfg.n_hosts


def bucket_tick(cfg, n_conns: int, lb_rows: dict) -> dict:
    """Each kernel's work in one tick of a bucket: ``{kernel: Work}`` summed
    over its real rows (``lb_rows``: load balancer -> rows)."""
    out = {}
    for lb, rows in lb_rows.items():
        for w in row_tick(cfg.n_hosts, n_conns, n_queues(cfg),
                          cfg.feedback_rounds, lb):
            prev = out.get(w.kernel, Work(w.kernel, 0, 0))
            out[w.kernel] = Work(w.kernel, prev.bytes + w.bytes * rows,
                                 prev.ops + w.ops * rows)
    return out


def peaks(device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def least_time_s(work: Work, device_kind: str) -> float:
    pk = peaks(device_kind)
    return max(work.ops / pk["ops_per_s"], work.bytes / pk["hbm_bytes_per_s"])


def roofline(ctx: dict, kernel: str):
    """``kernel``'s share of its roofline in the traced chunk (%): the
    least time of its work there over its device time, summed over the
    chips used, so work that every chip repeats counts once.  ``None``
    where the kernel did no counted work or did not run."""
    work = ctx["kernel_work"]().get(kernel)
    ns = sum(d.kernel_by.get(kernel, 0.0)
             for d in ctx["trace"].devices.values())
    if work is None or work.bytes == 0 or ns <= 0:
        return None
    ticks = ctx["window"].traced["ticks"]
    return 100.0 * least_time_s(work * ticks, ctx["device_kind"]) / (ns / 1e9)

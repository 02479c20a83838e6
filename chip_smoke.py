"""Chip smoke: the simulator's main path, end to end, on TPU.

One chip (the default) runs the fig07 permutation block at the full width
of the paper's main fabric through ``SweepEngine`` — the path every
``benchmarks/common.figure_grid`` figure takes, with ``collect="summary"``
and quiescence early exit:

* fabric ``FATTREE_128`` (128 hosts, 16 hosts/ToR, 16 uplinks/ToR) with
  the paper-scale constants (``benchmarks/common.ci_cfg`` under
  ``BENCH_FULL=1``: 65,536 EVs, 85-packet queues, 4,096-packet bitmaps);
* a permutation of 2,048-packet messages, 5% of the ToR uplinks down from
  tick 150 on, ECMP / OPS / REPS x ``SEEDS`` seeds in one bucket, over
  fig07's ``TICKS``-tick horizon.

The grid runs twice, once with every tick kernel in Pallas (seg_rank,
seg_sum, queue_tick, reps_tick) and once with every one in jnp; the
Pallas program must hold Mosaic kernels (``tpu_custom_call``), both grids
must give identical summaries, sketches and final states, one REPS row
must equal its serial ``Simulator.run`` reference bit for bit, and REPS
must complete every connection.

``--chips 4`` runs only the conn-sharded scale row instead (the
``benchmarks/scale_smoke.py`` row: 10^5 connections, 300 ticks, REPS)
with ``conn_devices=4``, and compares it bit for bit with the same row at
``conn_devices=1`` on one of the four chips, under the Pallas kernels and
under jnp.  The row's 10^5-segment axes span many segment tiles, so this
is the chip check of the tiled Mosaic kernels against the jnp reference.

Every check is a hard failure (non-zero exit).  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Run from the repository root: ``python chip_smoke.py [--chips 4]``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.arcane_paper import FATTREE_128  # noqa: E402
from repro.netsim import (  # noqa: E402
    SimConfig, SweepCase, SweepEngine, failures, workloads,
)
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

LBS = ("ecmp", "ops", "reps")
TICKS = 8000  # fig07's horizon
SEEDS = 2  # rows per LB cell of the fig07 grid
REPS_CELL = "fig07/permutation/reps"


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def fig07_cases(cfg: SimConfig, msg_pkts: int, ticks: int, seeds: int,
                backend: str) -> list[SweepCase]:
    """The fig07 permutation block: one cell per LB, ``seeds`` rows each."""
    fs = failures.random_down_uplinks(cfg, 0.05, 150, failures.FOREVER, seed=7)
    wl = workloads.permutation(cfg.n_hosts, msg_pkts, seed=1)
    out = []
    for lb in LBS:
        kw = {"evs_size": cfg.evs_size}
        if lb == "reps":
            kw.update(freezing_timeout=800, backend=backend)
        out.append(SweepCase(
            f"fig07/permutation/{lb}", wl, lb, ticks, lb_kwargs=kw,
            failures=fs, seeds=tuple(range(seeds)),
        ))
    return out


def program_text(eng: SweepEngine) -> str:
    return "\n".join(
        fn.as_text()
        for prog in eng.programs.values()
        for fn in prog.chunk_fns.values()
    )


def run_grid(cfg: SimConfig, msg_pkts: int, ticks: int, seeds: int,
             backend: str):
    """One grid with every tick kernel on ``backend`` ("pallas" or "jnp")."""
    cfg = cfg.replace(kernels_backend=backend, arrivals_backend=backend)
    cases = fig07_cases(cfg, msg_pkts, ticks, seeds, backend)
    eng = SweepEngine(cfg, cases, devices=1, kernels_backend=backend)
    res = eng.run(collect="summary", early_exit=True)
    rows = sum(b.n_rows for b in res.buckets)
    row_ticks = sum(b.ticks_run * b.n_rows for b in res.buckets)
    ticks_run = max(b.ticks_run for b in res.buckets)
    log(
        f"grid[{backend}]: buckets={len(res.buckets)} rows={rows} "
        f"ticks_run={ticks_run} compile_s={res.compile_wall_s:.3f} "
        f"exec_s={res.exec_wall_s:.3f} "
        f"ticks_per_s={ticks_run / max(res.exec_wall_s, 1e-9):.1f} "
        f"row_ticks_per_s={row_ticks / max(res.exec_wall_s, 1e-9):.1f}"
    )
    return eng, res


def same_tree(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def check_backends_agree(pal, ref) -> int:
    """Per cell and seed: summary, sketch bytes and final state equal."""
    (eng_p, res_p), (eng_j, res_j) = pal, ref
    check(eng_p.plan == eng_j.plan, "pallas and jnp grids planned differently")
    sums_p, sums_j = res_p.summaries(), res_j.summaries()
    n = 0
    for bp, bj in zip(res_p.buckets, res_j.buckets):
        check(bp.ticks_run == bj.ticks_run, "grids stopped at different ticks")
        for cp, cj in zip(bp.cells, bj.cells):
            name = cp.case.name
            for i, (rp, rj) in enumerate(zip(cp.rows, cj.rows)):
                check(repr(sums_p[name][i]) == repr(sums_j[name][i]),
                      f"{name} seed {i}: summaries differ")
                check(np.array_equal(bp.telemetry[rp], bj.telemetry[rj]),
                      f"{name} seed {i}: sketch bytes differ")
                check(same_tree(res_p.state_for(name, i),
                                res_j.state_for(name, i)),
                      f"{name} seed {i}: final states differ")
                n += 1
    return n


def check_serial(eng: SweepEngine, res, name: str) -> None:
    """Seed 0 of ``name`` against its serial ``Simulator.run`` reference."""
    bucket = next(b for b in res.buckets
                  for c in b.cells if c.case.name == name)
    cell = next(c for c in bucket.cells if c.case.name == name)
    sim = eng.serial_sim(name)
    t0 = time.time()
    st, _ = sim.run(bucket.ticks_run)
    jax.block_until_ready(st)
    log(f"serial[{name} seed 0]: ticks={bucket.ticks_run} "
        f"compile+exec_s={time.time() - t0:.3f}")
    row = res.state_for(name)
    check(same_tree(row.lb_state[1][cell.branch], st.lb_state),
          f"{name}: LB state differs from the serial reference")
    check(same_tree(row._replace(lb_state=()), st._replace(lb_state=())),
          f"{name}: state differs from the serial reference")


def one_chip(cfg: SimConfig, msg_pkts: int, ticks: int, seeds: int) -> None:
    log(f"fabric: {cfg.n_hosts} hosts, {cfg.hosts_per_tor} hosts/ToR, "
        f"{cfg.uplinks_per_tor} uplinks/ToR, evs={cfg.evs_size}, "
        f"queue={cfg.queue_capacity}, max_msg={cfg.max_msg_pkts}")
    log(f"traffic: permutation of {msg_pkts}-packet messages, 5% uplinks "
        f"down from tick 150, {'/'.join(LBS)} x {seeds} seeds, horizon "
        f"{ticks} ticks (fig07: 8000)")
    pal = run_grid(cfg, msg_pkts, ticks, seeds, "pallas")
    ref = run_grid(cfg, msg_pkts, ticks, seeds, "jnp")
    n_pal = program_text(pal[0]).count("tpu_custom_call")
    n_ref = program_text(ref[0]).count("tpu_custom_call")
    log(f"kernels: pallas program holds {n_pal} tpu_custom_call, jnp "
        f"program {n_ref}")
    check(n_pal > 0, "the pallas program holds no Mosaic kernel")
    check(n_ref == 0, "the jnp reference program holds a Mosaic kernel")
    n = check_backends_agree(pal, ref)
    log(f"pallas == jnp: summaries, sketch bytes and final states of "
        f"{n} rows")
    check_serial(*pal, REPS_CELL)
    log("sweep row == serial Simulator.run reference, bit for bit")
    for i, s in enumerate(pal[1].summaries()[REPS_CELL]):
        check(s.completed == s.n_conns,
              f"REPS seed {i} completed {s.completed}/{s.n_conns}")
        log(f"REPS seed {i}: completed {s.completed}/{s.n_conns}, "
            f"runtime {s.runtime_ticks} ticks, timeouts {s.timeouts}")


def scale_row(n_conns: int, ticks: int, conn_devices: int, backend: str):
    """The ``benchmarks/scale_smoke.py`` row on ``conn_devices`` devices,
    every tick kernel on ``backend`` ("pallas" or "jnp")."""
    from benchmarks.scale_smoke import scale_workload

    cfg = SimConfig(n_hosts=128, hosts_per_tor=16, uplinks_per_tor=16,
                    conn_sharding=True, kernels_backend=backend,
                    arrivals_backend=backend)
    case = SweepCase(f"scale/row{n_conns}", scale_workload(n_conns, 128),
                     "reps", ticks=ticks, lb_kwargs={"backend": backend},
                     seeds=(0,))
    eng = SweepEngine(cfg, [case], devices=conn_devices,
                      conn_devices=conn_devices, kernels_backend=backend)
    res = eng.run(collect="none")
    st = res.state_for(case.name)
    devs = {
        d for prog in eng.programs.values() for fn in prog.chunk_fns.values()
        for s in jax.tree_util.tree_leaves(fn.input_shardings)
        for d in s.device_set
    }
    n_mosaic = program_text(eng).count("tpu_custom_call")
    log(f"scale[{backend}, conn_devices={conn_devices}]: conns={n_conns} "
        f"ticks={ticks} done={int(np.asarray(st.c_done).sum())} "
        f"devices_used={len(devs)} tpu_custom_call={n_mosaic} "
        f"compile_s={res.compile_wall_s:.3f} exec_s={res.exec_wall_s:.3f} "
        f"ticks_per_s={ticks / max(res.exec_wall_s, 1e-9):.2f}")
    check(len(devs) == conn_devices,
          f"conn_devices={conn_devices} program placed on {len(devs)} devices")
    check((n_mosaic > 0) == (backend == "pallas"),
          f"{backend} scale program holds {n_mosaic} Mosaic kernels")
    return st


def four_chips(n_conns: int, ticks: int) -> None:
    sharded = scale_row(n_conns, ticks, 4, "pallas")
    check(int(np.asarray(sharded.c_done).sum()) > 0,
          "scale row made no progress")
    single = scale_row(n_conns, ticks, 1, "pallas")
    check(same_tree(sharded, single),
          "conn_devices=4 row differs from the conn_devices=1 row")
    log("conn_devices=4 == conn_devices=1, bit for bit")
    ref = scale_row(n_conns, ticks, 1, "jnp")
    check(same_tree(single, ref),
          "pallas scale row differs from the jnp scale row")
    log("pallas == jnp at scale (multi-tile segment kernels), bit for bit")


def cache_entries(path: str) -> int:
    """Compiled programs in the cache (JAX names each ``<key>-cache``)."""
    if not os.path.isdir(path):
        return 0
    return sum(name.endswith("-cache") for name in os.listdir(path))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the fig07 grid on one chip (default); 4: only "
                    "the conn-sharded scale row and its 1-chip reference")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"JAX found no TPU (platform {dev.platform!r})")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} chips, found "
          f"{len(devices)}")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    cache = enable_compile_cache()
    log(f"compile cache: {cache} ({cache_entries(cache)} entries before)")

    t0 = time.time()
    if args.chips == 4:
        four_chips(n_conns=100_000, ticks=300)
    else:
        one_chip(FATTREE_128, msg_pkts=2048, ticks=TICKS, seeds=SEEDS)
    log(f"wall_s={time.time() - t0:.1f}; compile cache now holds "
        f"{cache_entries(cache)} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()

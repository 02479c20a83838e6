"""The comparison that decides ``correct`` fails where it must.

At a tiny size on the CPU, one warm grid of the fig07 cell is driven by the
harness's own window with the timed path broken underneath, once for each
fault a one-chip cell can have; each run must come out not correct.  The
control (the reference with its float state in bfloat16) must fail too,
and the unbroken run must pass.  The four-chip cell's fault (the exchange
between chips left out) is in ``test_bench_rehearsal.py``.
"""

import jax
import jax.numpy as jnp
import pytest

from bench import harness, reference
from bench.tiny import shrink

SEED = 2**31 + 12345  # a seed past 32 signed bits


@pytest.fixture(scope="module")
def warm():
    spec = shrink(harness.load_cell("fig07_perm.mixed_lb"))
    grid = harness.build_grid(spec, SEED)
    harness.warm_up(grid)
    return grid, {}


def numbers_of(grid, memo, hook=None):
    eng = grid.engine
    carry = eng.bucket_carry(eng.buckets[0], grid.collect, grid.tel_spec)
    window = harness.run_window(grid, carry, 0.0, chunk_hook=hook)
    numbers, rows = reference.check(
        grid.cfg, grid.inputs, harness.row_index(grid), window.snapshots,
        grid.collect, grid.chunk, grid.spec.traffic["ticks"], memo=memo,
    )
    assert rows == 3  # one sampled row per LB cell
    return numbers, window


def passes(numbers):
    return all(v["value"] <= v["limit"] for v in numbers.values())


def advance(grid):
    eng = grid.engine
    return lambda c, b, t0, n: eng.run_chunk(b, c, t0, n, grid.collect,
                                             grid.tel_spec)[0]


def test_sound_run_is_correct(warm):
    numbers, window = numbers_of(*warm)
    assert passes(numbers)
    assert window.compiles == 0  # nothing compiles inside the window


def test_state_left_unchanged_fails(warm):
    numbers, _ = numbers_of(*warm, hook=lambda c, b, t0, n: c)
    assert not passes(numbers)


def test_half_the_rows_left_out_fails(warm):
    grid, memo = warm
    step = advance(grid)

    def half(c, b, t0, n):
        old = jax.tree_util.tree_map(jnp.copy, c)
        new = step(c, b, t0, n)
        keep = b.plan.n_padded_rows // 2
        return jax.tree_util.tree_map(
            lambda x, o: x.at[keep:].set(o[keep:]), new, old)

    assert not passes(numbers_of(grid, memo, hook=half)[0])


def test_answer_altered_where_produced_fails(warm):
    grid, memo = warm
    step = advance(grid)

    def altered(c, b, t0, n):
        st, tel = step(c, b, t0, n)
        return st._replace(c_delivered=st.c_delivered.at[0, 0].add(1)), tel

    assert not passes(numbers_of(grid, memo, hook=altered)[0])


def test_control_fails(warm):
    grid, memo = warm
    numbers, rows = reference.control(
        grid.cfg, grid.inputs, harness.row_index(grid), grid.chunk,
        grid.collect, grid.chunk, grid.spec.traffic["ticks"], memo=memo,
    )
    assert rows == 3 and not passes(numbers)
    assert numbers["state_mismatch"]["value"] > 0

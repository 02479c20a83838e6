"""The check of the cell whose window re-grids (``fig07_perm.reps_only``).

At a tiny size on the CPU, the bucket of six REPS rows runs to its horizon
(``finalize_bucket``) and again from its initial state (the re-grid), and
the window closes with that second pass in flight; a clock that reads one
second a chunk fixes where it closes.  The check compares the first
finalize and the bucket in flight with the reference.  The sound window is
correct; each fault the cell can have makes it not correct: the state left
unchanged, the half of the rows that holds the sampled row left out, an
answer altered in every row where it is produced, the re-grid started from
the finished carry instead of the initial state, and early exit finalizing
the bucket one poll before it is quiescent.  The control (the reference
with its float state in bfloat16) fails too.
"""
import jax
import jax.numpy as jnp
import pytest

from bench import harness, reference
from bench.tiny import shrink

SEED = 2**31 + 4242  # a seed past 32 signed bits
TICKS, CHUNK = 150, 50  # a pass of three chunks
LONG = 1000  # a horizon the tiny bucket quiesces well before


class Chunks:
    """Runs chunks through ``step`` and is the window's clock: one second
    for each chunk run."""

    def __init__(self, step):
        self.step, self.n = step, 0

    def __call__(self, c, b, t0, n):
        self.n += 1
        return self.step(c, b, t0, n)

    def clock(self):
        return self.n


def advance(grid):
    eng = grid.engine
    return lambda c, b, t0, n: eng.run_chunk(b, c, t0, n, grid.collect,
                                             grid.tel_spec)[0]


def window_of(grid, hook=None, seconds=4, trace_dir=None):
    """From the initial carry for ``seconds`` chunks: at ``TICKS``, three
    chunks, the finalize and one chunk of the re-grid."""
    eng = grid.engine
    carry = eng.bucket_carry(eng.buckets[0], grid.collect, grid.tel_spec)
    chunks = Chunks(hook or advance(grid))
    return harness.run_window(grid, carry, seconds, trace_dir=trace_dir,
                              chunk_hook=chunks, clock=chunks.clock)


def grid_of(ticks):
    spec = shrink(harness.load_cell("fig07_perm.reps_only"))
    spec.traffic.update(ticks=ticks, chunk_ticks=CHUNK)
    grid = harness.build_grid(spec, SEED)
    harness.warm_up(grid)
    return grid


@pytest.fixture(scope="module")
def warm():
    return grid_of(TICKS), {}


def numbers_of(grid, memo, window):
    numbers, rows = reference.check(
        grid.cfg, grid.inputs, harness.row_index(grid), window.snapshots,
        grid.collect, grid.chunk, grid.spec.traffic["ticks"], memo=memo,
        model=grid.model,
    )
    assert rows == 2  # the sampled row, finalized and in flight
    return numbers


def passes(numbers):
    return all(v["value"] <= v["limit"] for v in numbers.values())


def test_sound_window_finalizes_regrids_and_is_correct(warm):
    grid, memo = warm
    window = window_of(grid)
    assert [(b, t) for b, t, _ in window.regrids] == [(0, TICKS)]
    assert [(b, s.ticks, s.finalized) for b, s in window.snapshots] == [
        (0, TICKS, True), (0, CHUNK, False)]
    assert window.row_ticks == 6 * TICKS + 6 * CHUNK
    assert window.compiles == 0
    assert len(window.regrid_s) == 1 and len(window.chunk_s) == 4
    assert passes(numbers_of(grid, memo, window))


def test_profiler_time_does_not_shorten_a_traced_window(warm, tmp_path,
                                                        monkeypatch):
    grid, _ = warm
    sound = window_of(grid)
    chunks = Chunks(advance(grid))
    slow = lambda *a: setattr(chunks, "n", chunks.n + 100)  # noqa: E731
    monkeypatch.setattr(jax.profiler, "start_trace", slow)
    monkeypatch.setattr(jax.profiler, "stop_trace", slow)
    eng = grid.engine
    carry = eng.bucket_carry(eng.buckets[0], grid.collect, grid.tel_spec)
    traced = harness.run_window(grid, carry, 4, trace_dir=str(tmp_path),
                                chunk_hook=chunks, clock=chunks.clock)
    assert traced.chunks == sound.chunks == 4
    assert [(b, t) for b, t, _ in traced.regrids] == [(0, TICKS)]
    assert traced.traced == {"ticks": CHUNK, "rows": 6,
                             "lb_rows": {"reps": 6}}


def test_state_left_unchanged_fails(warm):
    grid, memo = warm
    window = window_of(grid, hook=lambda c, b, t0, n: c)
    assert not passes(numbers_of(grid, memo, window))


def test_the_sampled_rows_half_left_out_fails(warm):
    grid, memo = warm
    step = advance(grid)
    sampled = harness.row_index(grid)[0][0][0]
    half = grid.engine.buckets[0].plan.n_padded_rows // 2
    out = slice(half, None) if sampled >= half else slice(0, half)

    def left_out(c, b, t0, n):
        old = jax.tree_util.tree_map(jnp.copy, c)
        new = step(c, b, t0, n)
        return jax.tree_util.tree_map(lambda x, o: x.at[out].set(o[out]),
                                      new, old)

    assert not passes(numbers_of(grid, memo, window_of(grid, left_out)))


def test_answer_altered_where_produced_fails(warm):
    grid, memo = warm
    step = advance(grid)

    def altered(c, b, t0, n):
        st, tel = step(c, b, t0, n)
        return st._replace(c_delivered=st.c_delivered.at[:, 0].add(1)), tel

    assert not passes(numbers_of(grid, memo, window_of(grid, altered)))


def test_regrid_from_the_finished_carry_fails(warm):
    grid, memo = warm
    step = advance(grid)
    finished = {}

    def stale(c, b, t0, n):
        if t0 == 0 and "carry" in finished:  # the re-grid's first chunk
            c = jax.device_put(finished["carry"])
        c = step(c, b, t0, n)
        if t0 + n == TICKS:
            finished["carry"] = jax.device_get(c)
        return c

    window = window_of(grid, stale)
    numbers = numbers_of(grid, memo, window)
    assert not passes(numbers)


def test_control_fails(warm):
    grid, memo = warm
    numbers, rows = reference.control(
        grid.cfg, grid.inputs, harness.row_index(grid), TICKS, grid.collect,
        grid.chunk, TICKS, memo=memo, model=grid.model,
    )
    assert rows == 1 and not passes(numbers)
    assert numbers["state_mismatch"]["value"] > 0


@pytest.fixture(scope="module")
def early():
    """The bucket at a horizon it quiesces well before, and the sound
    window's quiescence tick."""
    grid = grid_of(LONG)
    q = window_of(grid, seconds=LONG // CHUNK).regrids[0][1]
    # two passes to quiescence, and one chunk of the third
    return grid, window_of(grid, seconds=2 * (q // CHUNK) + 1), {}


def test_early_exit_credits_the_row_ticks_run_and_is_correct(early):
    """A bucket that quiesces before its horizon is credited the ticks its
    rows ran, not the rest of the horizon that early exit skips; every row
    of the finalized bucket is quiescent by the reference's rule."""
    grid, window, memo = early
    quiet = {t for _, t, _ in window.regrids}
    assert len(window.regrids) == 2 and len(quiet) == 1
    (q,) = quiet
    assert 2 * CHUNK <= q < LONG
    (_, first), (_, last) = window.snapshots
    assert first.finalized and first.ticks == q and not last.finalized
    assert window.row_ticks == 6 * (2 * q + last.ticks)
    assert passes(numbers_of(grid, memo, window))


def test_finalize_one_poll_early_fails(early):
    """Early exit that finalizes the bucket one poll before all its rows
    are quiescent cuts the user's result short, though every row's state
    still equals the reference's at that tick."""
    grid, sound, memo = early
    q = sound.regrids[0][1]
    group = grid.engine.buckets[0].program.group
    detector = grid.quiescent[group]
    grid.quiescent[group] = lambda st, scn, horizon, offset: (
        int(offset) == q - CHUNK or detector(st, scn, horizon, offset))
    try:
        window = window_of(grid, seconds=q // CHUNK + 1)
    finally:
        grid.quiescent[group] = detector
    assert [t for _, t, _ in window.regrids] == [q - CHUNK]
    numbers = numbers_of(grid, memo, window)
    assert not passes(numbers)

"""Host spans (``repro.utils.spans``): a span times itself and lands on the
profiler's host plane, and the sweep engine's bucket wall times are its
``sweep.bucket.*`` spans."""
import time

import jax
from jax.profiler import ProfileData

from repro.configs.arcane_paper import FATTREE_32_CI
from repro.netsim import SweepCase, SweepEngine, sweep, workloads
from repro.utils import spans


def test_span_times_itself_on_the_profilers_host_plane(tmp_path):
    with spans.span("sweep.untraced") as s:
        time.sleep(0.01)
    assert s.seconds >= 0.01 and s.end_ns > s.start_ns
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("sweep.traced") as traced:
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [e for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    (event,) = [e for e in events if e.name == "sweep.traced"]
    # the clock reads sit inside the annotation
    assert event.duration_ns >= traced.end_ns - traced.start_ns >= 0.01e9
    assert not [e for e in events if e.name == "sweep.untraced"]


def test_bucket_wall_times_are_their_spans(monkeypatch):
    """``compile_wall_s`` / ``exec_wall_s`` of every bucket are the seconds
    of its ``sweep.bucket.compile`` / ``sweep.bucket.exec`` spans."""
    closed = []

    class kept(spans.span):
        __slots__ = ()

        def __exit__(self, *exc):
            super().__exit__(*exc)
            closed.append((self.name, self.seconds))

    monkeypatch.setattr(sweep, "span", kept)
    wl = workloads.permutation(32, 8, seed=1)
    cases = [
        SweepCase("p/reps", wl, "reps", 60, seeds=(0,),
                  lb_kwargs={"evs_size": FATTREE_32_CI.evs_size}),
        SweepCase("i/ecmp", workloads.incast(32, 3, 8), "ecmp", 60,
                  seeds=(1,), lb_kwargs={"evs_size": FATTREE_32_CI.evs_size}),
    ]
    eng = SweepEngine(FATTREE_32_CI, cases, devices=1)
    res = eng.run(collect="none", chunk=20, early_exit=True)

    def seconds(name):
        return [s for n, s in closed if n == name]

    assert len(res.buckets) == len(seconds("sweep.bucket.exec")) >= 1
    assert seconds("sweep.bucket.compile") == [
        b.compile_wall_s for b in res.buckets
    ]
    assert seconds("sweep.bucket.exec") == [b.exec_wall_s for b in res.buckets]

"""Roofline machinery: trip-count-aware HLO cost analysis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze_hlo
from repro.launch.roofline import PEAK_FLOPS, Roofline, model_flops_for
from repro.configs import SHAPES, get_config


def _cost(f, *args):
    c = jax.jit(f).lower(*args).compile()
    return analyze_hlo(c.as_text())


def test_scan_flops_match_unrolled():
    def body(x, w):
        return jnp.tanh(x @ w), None

    W = jnp.ones((8, 128, 128), jnp.float32)
    x = jnp.ones((4, 128), jnp.float32)

    def scanned(w, x):
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    def unrolled(w, x):
        for i in range(8):
            x, _ = body(x, w[i])
        return x.sum()

    cs, cu = _cost(scanned, W, x), _cost(unrolled, W, x)
    assert cs.flops == pytest.approx(cu.flops, rel=0.01)
    assert cs.flops == pytest.approx(8 * 2 * 4 * 128 * 128, rel=0.05)


def test_nested_scan_trip_counts():
    def inner(x, w):
        return x @ w, None

    def outer(x, ws):
        def step(x, w3):
            y, _ = jax.lax.scan(inner, x, w3)
            return y, None

        y, _ = jax.lax.scan(step, x, ws)
        return y.sum()

    ws = jnp.ones((5, 3, 64, 64), jnp.float32)
    x = jnp.ones((2, 64), jnp.float32)
    c = _cost(outer, x, ws)
    assert c.flops == pytest.approx(5 * 3 * 2 * 2 * 64 * 64, rel=0.05)


def test_model_flops_for():
    cfg = get_config("mistral-nemo-12b")
    tf = model_flops_for(cfg, SHAPES["train_4k"])
    # 6 * ~12B * 1M tokens ~ 7.6e16within 2x of the closed form
    assert 3e16 < tf < 2e17
    moe = get_config("qwen3-moe-235b-a22b")
    assert moe.active_param_count() < 0.2 * moe.param_count()


def test_roofline_terms():
    r = Roofline(
        flops=197e12, hbm_bytes=819e9, coll_bytes=0.0, coll_breakdown={},
        n_devices=256, model_flops=197e12 * 256,
    )
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(1.0)
    assert r.bottleneck in ("compute", "memory")
    assert r.roofline_fraction == pytest.approx(1.0)


def test_collective_parse():
    mesh = jax.make_mesh((len(jax.devices()),), ("d",))

    def f(x):
        return jax.lax.psum(x, "d")

    from jax.sharding import NamedSharding, PartitionSpec as P

    sf = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    c = jax.jit(sf).lower(jnp.ones((128, 128), jnp.float32)).compile()
    cost = analyze_hlo(c.as_text())
    if len(jax.devices()) > 1:
        assert cost.coll_bytes > 0

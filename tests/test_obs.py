"""``repro.obs`` host spans: totals add up across nesting, each span is a
profiler ``TraceAnnotation`` on the host plane, and ``make_fleet`` holds
its two set-up stages."""
import glob
import os
import time

import jax
import jax.numpy as jnp

from repro import obs


def _delta(before, name):
    return obs.host_seconds().get(name, 0.0) - before.get(name, 0.0)


def test_nested_spans_add_up():
    before = obs.host_seconds()
    with obs.span("test_outer"):
        for _ in range(2):
            with obs.span("test_inner"):
                time.sleep(0.01)
    inner, outer = _delta(before, "test_inner"), _delta(before, "test_outer")
    assert inner >= 0.02
    assert outer >= inner
    # a copy: callers cannot change the totals
    obs.host_seconds()["test_outer"] = -1.0
    assert obs.host_seconds()["test_outer"] >= outer


def test_span_total_survives_an_exception():
    before = obs.host_seconds()
    try:
        with obs.span("test_raises"):
            raise KeyError("x")
    except KeyError:
        pass
    assert _delta(before, "test_raises") >= 0.0
    assert "test_raises" in obs.host_seconds()


def test_span_is_a_trace_annotation_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("test_traced"):
            f(jnp.ones(8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert obs.PREFIX + "test_traced" in names


def test_make_fleet_span_holds_its_stages():
    from repro.sim import simulator as S
    before = obs.host_seconds()
    S.make_fleet(S.FleetScenarioConfig(
        n_leaves=128, n_training=4, n_inference=4, n_batch=4,
        duration_s=600.0, tick_s=60.0, b_max=128))
    fleet = _delta(before, "make_fleet")
    tenants = _delta(before, "make_tenants")
    build = _delta(before, "market_build")
    assert tenants > 0 and build > 0
    assert fleet >= tenants + build

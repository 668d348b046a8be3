"""bench/trace.py on a small trace recorded on the CPU."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

import cells

trace = cells.load_module(os.path.join(cells.HERE, "trace.py"),
                          "bench_trace")


def test_reduce_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.round"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    s = trace.reduce(str(tmp_path))
    assert s.ops, "no device operations found"
    assert s.devices
    # host spans come from the host tracer, with the Python tracer off
    names = {nm for _, _, _, nm in s.host}
    assert "bench.round" in names
    assert any(nm.startswith("PjitFunction") for nm in names)
    busy, window = s.busy_seconds(), s.window_seconds()
    assert 0 < busy <= window
    # the three calls' operations: the dot ran three times
    dots = [o for o in s.ops if "dot" in o.name]
    assert len(dots) == 3
    assert abs(s.op_seconds("dot") - sum(o.dur for o in dots) * 1e-9) < 1e-12
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and v > 0 for n, v in b["device_ops"])
    # idle time is what the window leaves over, gaps can not exceed it
    assert sum(v for _, v in b["idle_gaps"]) <= window - busy + 1e-9


def test_union_merges_overlaps():
    assert trace._union([(0, 5), (3, 8), (10, 12)]) == [[0, 8], [10, 12]]

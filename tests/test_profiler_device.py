"""Device-trace (xprof) profiler coverage.

The §5.1 profiler row delegates device timelines to jax.profiler; the
hardware proof (real TPU kernel events in the artifact) is every
`--trace 1` run of benchmark/run.py on the chip. Here: the summary against a real CPU
capture (host-only -> zero device lanes, exercising the same code
path), and a chip test that skips off-TPU; the summary over a trace cut
from a chip run is in tests/test_tracing_spans.py. Reference analog:
/root/reference/paddle/fluid/platform/profiler/cuda_tracer.h.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler

requires_tpu = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="device-lane capture needs the real chip")


def test_device_trace_summary_on_host_capture(tmp_path):
    """jax.profiler runs fine on CPU but yields host-only lanes; the
    summary must parse the capture and report zero device events."""
    d = str(tmp_path / "xprof")
    f = jax.jit(lambda a: jnp.sum(a * 2.0))
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(d)
    np.asarray(f(x))
    jax.profiler.stop_trace()
    s = profiler.device_trace_summary(d)
    assert s["device_events"] == 0
    assert s["device_lanes"] == []
    assert s["by_scope"] == [] and s["by_kernel"] == []
    # missing dir -> empty summary, no crash
    assert profiler.device_trace_summary(str(tmp_path / "nope")) == {
        "device_lanes": [], "device_events": 0, "top_kernels": [],
        "by_scope": [], "by_kernel": [], "top_ops": [], "host_spans": []}


def test_profiler_exposes_device_trace_dir():
    p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    assert p.device_trace_dir is None     # CPU-only: no device capture


@requires_tpu
def test_device_trace_captures_tpu_kernels():
    p = profiler.Profiler(
        targets=[profiler.ProfilerTarget.CPU, profiler.ProfilerTarget.TPU])
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    np.asarray(f(x))
    p.start()
    np.asarray(f(x))
    p.stop()
    assert p.device_trace_dir is not None
    s = profiler.device_trace_summary(p.device_trace_dir)
    assert s["device_events"] > 0
    assert any("TPU" in lane for lane in s["device_lanes"])

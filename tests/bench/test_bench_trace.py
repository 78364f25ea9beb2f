"""The trace reduction: busy union, idle share and gaps, time per
program name, and reading a trace recorded on the CPU."""
import time

import pytest

import bench_tiny  # noqa: F401  (puts the repository root on the path)
from bench import trace as T


def test_union_busy_and_gaps_by_hand():
    iv = [(0, 10), (5, 20), (30, 40), (38, 45), (50, 50)]
    assert T.merge(iv) == [(0, 20), (30, 45)]
    # window [10, 42]: covered 10..20 and 30..42
    assert T.busy_ns(iv, 10, 42) == 10 + 12
    assert T.gaps(iv, 10, 42) == [(20, 30)]
    assert T.gaps(iv, -5, 60) == [(-5, 0), (20, 30), (45, 60)]
    red = T.Reduced(window=(10.0, 42.0),
                    modules={"/device:TPU:0": [("a", s, e) for s, e in iv]})
    assert red.busy_s() == pytest.approx(22e-9)
    assert red.idle_share() == pytest.approx(1 - 22 / 32)


def test_time_by_name_top_names_and_gap_labels():
    ev = [("jit__mips_topk", 0, 4), ("fusion.1", 4, 5),
          ("jit__mips_topk", 10, 16), ("copy", 16, 17)]
    assert T.time_by_name(ev, lambda n: "mips_topk" in n) == 10
    assert T.top_names(ev, 2) == [("jit__mips_topk", 10e-9),
                                  ("fusion.1", 1e-9)]
    spans = [("bench.window", 0, 100), ("bench.query_batch", 5, 9)]
    gaps = [(6, 8), (20, 30), (40, 41)]
    assert T.top_gaps(gaps, spans) == [("bench.window", 11e-9),
                                       ("bench.query_batch", 2e-9)]
    assert T.label(200, spans) == "untraced"


def test_reads_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    step(x).block_until_ready()
    prof = T.Profiler(tmp_path / "trace")
    prof.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                step(x).block_until_ready()
            time.sleep(0.02)
    # on the CPU the programs run on the host plane's client threads
    red = prof.stop(device_prefix="/host:CPU",
                    module_line="tf_XLAPjRtCpuClient",
                    op_line="tf_XLAEigen")
    assert not (tmp_path / "trace").exists()
    assert red.window_s >= 0.06
    assert [n for n, _, _ in red.host].count("bench.step") == 3
    busy = red.busy_s()
    assert 0 < busy < red.window_s
    assert 0 < red.idle_share() < 1
    dots = T.time_by_name(red.all_modules(), lambda n: "dot" in n)
    assert 0 < dots <= busy * 1e9
    bd = red.breakdown()
    assert len(bd["idle_gaps"]) <= 10 and bd["idle_gaps"]
    assert {k for k, _ in bd["idle_gaps"]} <= {"bench.step", "bench.window"}

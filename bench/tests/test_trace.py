"""The trace reducer: interval arithmetic and kernel matching by hand."""

import pytest

from bench.lib import trace
from bench.lib import kernels


def test_union_and_gaps():
    busy, gaps = trace.union_gaps([(2, 4), (3, 6), (8, 9), (12, 20)], 0, 15)
    assert busy == 2 + 2 + 1 + 3
    assert gaps == [(0, 2), (6, 8), (9, 12)]


def test_union_of_nothing_is_one_gap():
    assert trace.union_gaps([], 5, 9) == (0.0, [(5, 9)])


def test_gaps_named_by_the_host_event_covering_most():
    host = [trace.Interval(0, 100, "window"), trace.Interval(10, 30, "a"),
            trace.Interval(25, 60, "b"), trace.Interval(70, 75, "c")]
    named = trace.name_gaps([(20, 50), (80, 90)], host, ["window"])
    assert [n for n, _ in named] == ["b", "none"]
    assert [s for _, s in named] == pytest.approx([30e-9, 10e-9])


def test_top_keeps_largest_first():
    assert trace.top([("a", 1.0), ("b", 3.0), ("c", 2.0)], 2) == [
        ["b", 3.0], ["c", 2.0]]


def test_kernels_found_by_name_or_stats():
    dev = trace.DeviceTrace(
        1.0, {"custom-call.3": 0.25, "mithril_record.1": 0.5, "copy.7": 0.125,
              "custom-call.9": 0.0625},
        {"custom-call.3": 2, "mithril_record.1": 4, "copy.7": 4,
         "custom-call.9": 1}, [],
        {"custom-call.3": 'custom-call.3 kernel_name="mithril_mine_batched"',
         "mithril_record.1": "mithril_record.1",
         "copy.7": "copy.7 s32[16,16384,4,2]",
         "custom-call.9": "custom-call.9 mithril_mine"})
    s = trace.Summary(2.0, [dev], [])
    assert kernels.kernel_seconds(s, "record") == 0.5
    assert kernels.kernel_seconds(s, "mine") == 0.25 + 0.0625
    assert kernels.kernel_launches(s, "mine") == 3
    assert kernels.all_kernel_ops(s) == {"custom-call.3", "mithril_record.1",
                                         "custom-call.9"}


def test_self_times_take_nested_ops_out_and_clip_to_the_window():
    I = trace.Interval
    ops = [I(0, 100, "while"), I(10, 30, "copy"), I(40, 50, "kernel"),
           I(60, 90, "cond"), I(70, 80, "copy"), I(100, 130, "fusion"),
           I(150, 160, "copy")]
    own, launches = trace.self_times(ops, 5, 120)
    assert own == pytest.approx({"while": 95 - 20 - 10 - 30,
                                 "copy": 20 + 10, "kernel": 10,
                                 "cond": 30 - 10, "fusion": 20})
    assert sum(own.values()) == trace.union_gaps(
        [(o.start, o.end) for o in ops], 5, 120)[0]
    assert launches == {"copy": 2, "kernel": 1, "cond": 1, "fusion": 1}


def test_describe_shortens_hlo_text():
    assert trace.describe(
        "%copy.455 = s32[16,16384,4,2]{3,2,1,0:T(4,128)} copy(s32[16,16384,"
        "4,2]{1,3,2,0:T(2,128)} %get-tuple-element.2736)") == \
        "copy.455 copy s32[16,16384,4,2]"
    assert trace.describe(
        "%while.55 = (s32[]{:T(128)}, s32[16,1,512]{2,0,1:T(8,128)}) while("
        "(s32[]{:T(128)}, s32[16,1,512]{2,0,1:T(8,128)}) %tuple.395), "
        "condition=%c, body=%b") == "while.55 while (...)"
    assert trace.describe("fusion.2") == "fusion.2"


WHILE = ("%while.1 = (s32[]{:T(128)}, s32[4]{0}) while((s32[]{:T(128)}, "
         "s32[4]{0}) %tuple.2), condition=%c.1, body=%b.1")
COPY = ("%copy.3 = s32[16,16384,4,2]{3,2,1,0:T(4,128)} copy(s32[16,16384,4,"
        "2]{1,3,2,0:T(2,128)} %get-tuple-element.9)")

TRACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 500000
             stats { metadata_id: 1
                     str_value: "kernel_name=mithril_mine_batched" } }
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 5500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%(copy)s" } }
  event_metadata { key: 2 value { id: 2 name: "mithril_record.7" } }
  event_metadata { key: 3 value { id: 3 name: "custom-call.9" } }
  event_metadata { key: 4 value { id: 4 name: "jit_run_chunk" } }
  event_metadata { key: 5 value { id: 5 name: "%(while)s" } }
  stat_metadata { key: 1 value { id: 1 name: "long_name" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 3
    name: "python3"
    timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 9800000 }
    events { metadata_id: 3 offset_ps: 4300000 duration_ps: 1000000 }
  }
  lines {
    id: 4
    name: "sweep-producer"
    timestamp_ns: 500
    events { metadata_id: 4 offset_ps: 5700000 duration_ps: 800000 }
  }
  lines {
    id: 5
    name: "sweep-drain"
    timestamp_ns: 500
    events { metadata_id: 5 offset_ps: 0 duration_ps: 10400000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "job" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(run_chunk)" } }
  event_metadata { key: 4 value { id: 4 name: "device_put" } }
  event_metadata { key: 5 value { id: 5 name: "np.asarray(jax.Array)" } }
}
''' % {"copy": COPY, "while": WHILE}


def test_reduce_a_trace_file(tmp_path):
    # A hand-built trace in the profiler's own format, reduced end to
    # end: ops on the device's ``XLA Ops`` line, nested in a ``while``,
    # clipped to the window span; kernels found by name or by their
    # stats; idle gaps named by what the host was doing.
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    s = trace.reduce(str(path), "bench_window", 1, skip_names=("job",))
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(4.5e-6)
    assert s.op_seconds() == pytest.approx(
        {WHILE: 1e-6, COPY: 2e-6, "mithril_record.7": 1e-6,
         "custom-call.9": 0.5e-6})
    assert s.launches(COPY) == 1        # the second starts after the span
    assert kernels.kernel_seconds(s, "record") == pytest.approx(1e-6)
    assert kernels.kernel_launches(s, "mine") == 1
    # a drain thread blocked all through frames the gaps: it names the
    # long one, and the short ones only where nothing shorter overlaps;
    # the window and the job never name a gap
    assert s.gap_names == [
        ("np.asarray(jax.Array)", pytest.approx(0.5e-6)),
        ("PjitFunction(run_chunk)", pytest.approx(1e-6)),
        ("np.asarray(jax.Array)", pytest.approx(4e-6))]
    bd = trace.breakdown(s)
    assert bd["device_ops"][0] == ["copy.3 copy s32[16,16384,4,2]",
                                   pytest.approx(2e-6)]
    assert ["while.1 while (...)", pytest.approx(1e-6)] in bd["device_ops"]
    assert bd["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    with pytest.raises(ValueError):
        trace.reduce(str(path), "bench_window", 2)


@pytest.mark.parametrize("metric", ["record_kernel_roofline",
                                    "runner_other_ms_per_step"])
def test_kernel_metrics_refuse_a_trace_without_kernels(metric):
    """Kernel names that no longer match the trace stop the run: the
    time outside the kernels would otherwise swallow theirs."""
    import types
    from pathlib import Path

    from bench import run

    dev = trace.DeviceTrace(1.0, {"copy.7": 0.5, "fusion.2": 0.25},
                            {"copy.7": 4, "fusion.2": 4}, [],
                            {"copy.7": "copy.7", "fusion.2": "fusion.2"})
    r = types.SimpleNamespace(
        trace=trace.Summary(2.0, [dev], []), device_kind="TPU v5 lite",
        jobs=[], cell=types.SimpleNamespace(config={}, shape={"chunk": 8}))
    r.jobs = [types.SimpleNamespace(n_slabs=2)]
    with pytest.raises(LookupError):
        run.read_metric(Path(run.ROOT), {"name": metric}, r)

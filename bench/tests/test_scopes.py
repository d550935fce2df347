"""The chunk runner's time by named scope, read by hand from a trace built
in the profiler's own format and a compiled HLO text written by hand."""

import types

import pytest

from bench.lib import scopes, trace

BODY = "jit(scan_chunk)/while/body/closed_call"

# A compiled runner's HLO text: each instruction with its op_name, as
# ``Compiled.as_text()`` prints them (operands without shapes).
HLO = f'''
HloModule jit_scan_chunk

ENTRY %main {{
  %while.1 = (s32[]{{:T(128)}}, s32[4]{{0}}) while((s32[]{{:T(128)}}, s32[4]{{0}}) %tuple.2), condition=%c.1, body=%b.1, metadata={{op_name="jit(scan_chunk)/while"}}
  %fusion.2 = s32[16,512]{{1,0:T(8,128)}} fusion(%p.1, %p.2), kind=kLoop, calls=%f.2, metadata={{op_name="{BODY}/access/vmap()/add"}}
  %mithril_record.3 = (s32[16]{{0}}, s32[16]{{0}}) custom-call(%p.3), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/record/pallas_call"}}
  %cond.4 = (s32[16]{{0}}) conditional(%p.4, %p.5, %p.6), branch_computations={{%b1, %b2}}, metadata={{op_name="{BODY}/barrier/cond"}}
  %copy.5 = s32[16,16384,4,2]{{3,2,1,0:T(4,128)}} copy(%get-tuple-element.9), metadata={{op_name="{BODY}/barrier/cond"}}
  %gather.6 = s32[16,2]{{1,0}} gather(%copy.5, %p.7), metadata={{op_name="{BODY}/prefetch/vmap()/gather"}}
  %fusion.7 = s32[16]{{0}} fusion(%p.8), kind=kLoop, calls=%f.7, metadata={{op_name="{BODY}/prefetch/vmap(amp)/add"}}
  %copy-done.8 = s32[16,4,32768]{{2,1,0:T(4,128)}} copy-done(%copy-start.8)
  ROOT %tuple.9 = (s32[4]{{0}}) tuple(%p.9), metadata={{op_name="{BODY}"}}
}}
'''

# Trace names are the whole instruction text, operands with their shapes.
OPS = [
    # (name, start ns, end ns): a while holding one scan step
    ("%while.1 = (s32[]{:T(128)}, s32[4]{0}) while((s32[]{:T(128)}, "
     "s32[4]{0}) %tuple.2), condition=%c.1, body=%b.1", 0, 100),
    ("%fusion.2 = s32[16,512]{1,0:T(8,128)} fusion(s32[16] %p.1, s32[16] "
     "%p.2), kind=kLoop, calls=%f.2", 0, 10),
    ("%mithril_record.3 = (s32[16]{0}, s32[16]{0}) custom-call(s32[16] "
     "%p.3), custom_call_target=\"tpu_custom_call\"", 10, 14),
    ("%cond.4 = (s32[16]{0}) conditional(pred[] %p.4, s32[16] %p.5, "
     "s32[16] %p.6)", 14, 20),
    ("%copy.5 = s32[16,16384,4,2]{3,2,1,0:T(4,128)} copy(s32[16,16384,4,2]"
     "{1,3,2,0:T(2,128)} %get-tuple-element.9)", 20, 70),
    ("%gather.6 = s32[16,2]{1,0} gather(s32[16,16384,4,2] %copy.5, s32[16] "
     "%p.7)", 70, 75),
    ("%fusion.7 = s32[16]{0} fusion(s32[16] %p.8), kind=kLoop", 75, 80),
    ("%copy-done.8 = s32[16,4,32768]{2,1,0:T(4,128)} copy-done((s32[16,4,"
     "32768]{2,1,0:T(4,128)}, s32[16,4,32768]{2,1,0:T(4,128)S(1)}, u32[]"
     "{:S(2)}) %copy-start.8)", 80, 90),
    # another module's fusion.2: same name, another shape; and an eager op
    ("%fusion.2 = pred[16]{0} fusion(pred[16] %p.1), kind=kLoop", 110, 112),
    ("%broadcast.1 = s32[16,3]{1,0} broadcast(s32[] %c.1)", 112, 116),
]


def _trace_file(tmp_path):
    """A device trace as the profiler writes it: the op_name sits in the
    ``tf_op`` stat of each event's metadata, where ``ProfileData`` does
    not show it, so the benchmark joins the runner's compiled text."""
    events, metadata = [], []
    for i, (name, start, end) in enumerate(OPS, 1):
        events.append(f"events {{ metadata_id: {i} offset_ps: {start * 1000}"
                      f" duration_ps: {(end - start) * 1000} }}")
        metadata.append(
            f"event_metadata {{ key: {i} value {{ id: {i} name: "
            f"{trace_quote(name)} stats {{ metadata_id: 1 str_value: "
            f"\"{BODY}:\" }} }} }}")
    text = f'''
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000 {" ".join(events)} }}
  {" ".join(metadata)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 2 name: "python3" timestamp_ns: 1000
           events {{ metadata_id: 1 offset_ps: 0 duration_ps: 120000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench_window" }} }}
}}
'''
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace.reduce(str(path), "bench_window", 1)


def trace_quote(s):
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _run(summary, name="t-cell"):
    cell = types.SimpleNamespace(name=name, config={}, shape={})
    return types.SimpleNamespace(trace=summary, cell=cell)


@pytest.fixture
def summary(tmp_path):
    return _trace_file(tmp_path)


def test_scope_of_takes_the_innermost():
    assert scopes.scope_of(f"{BODY}/barrier/cond/branch_1_fun/mul") == \
        "barrier"
    assert scopes.scope_of(f"{BODY}/prefetch/vmap(amp)/add") == "amp"
    assert scopes.scope_of(f"{BODY}/amp/vmap()/prefetch/add") == "prefetch"
    assert scopes.scope_of("jit(_masked_reset)/select_n") is None
    assert scopes.scope_of("") is None


def test_each_op_under_its_scope(summary):
    split = scopes.scope_seconds(summary, scopes.op_names(HLO))
    assert split == pytest.approx({
        "access": 10e-9, "record": 4e-9,
        # the cond's own time and the layout copy it hands its output on
        # with: the copy carries its producer's op_name
        "barrier": 6e-9 + 50e-9,
        "prefetch": 5e-9, "amp": 5e-9,
        # the loop's own time, a copy-done with no op_name, and the
        # other module's fusion.2 and the eager broadcast
        "unscoped": (100 - 90) * 1e-9 + 10e-9 + 2e-9 + 4e-9})
    assert sum(split.values()) == pytest.approx(summary.busy_s)


def test_scope_metrics_add_up_to_busy_per_step(summary, monkeypatch):
    from bench import run

    monkeypatch.setitem(scopes._HLO, "t-cell", HLO)
    r = _run(summary)
    names = ("access", "barrier", "prefetch", "amp", "unscoped")
    got = {n: run.read_metric(run.ROOT, {"name": f"{n}_ms_per_step"}, r)
           for n in names}
    # one record-kernel launch in the span: one scan step
    assert got["barrier"] == pytest.approx(56e-6)
    record = scopes.ms_per_step(r, "record")
    assert sum(got.values()) + record == pytest.approx(1e3 * summary.busy_s)


def test_a_program_without_scopes_reads_nothing(summary, monkeypatch):
    """The same trace against a runner whose op_names name no scope, as
    before the program had them: every scope metric reads nothing, and
    the run goes on."""
    from bench import run

    plain = HLO
    for s in scopes.SCOPES:
        plain = plain.replace(f"/{s}/", "/").replace(f"({s})", "()")
    monkeypatch.setitem(scopes._HLO, "t-cell", plain)
    for n in ("access", "barrier", "prefetch", "amp", "unscoped"):
        assert run.read_metric(run.ROOT, {"name": f"{n}_ms_per_step"},
                               _run(summary)) is None
    assert run.read_metric(run.ROOT, {"name": "barrier_ms_per_step"},
                           _run(None)) is None

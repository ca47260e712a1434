"""The trace reduction (bench/trace.py) on hand-made events and on a slice
of a trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import trace as T

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def test_union_clip_subtract():
    assert T.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert T.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert T.subtract([(0, 10)], [(2, 3), (2.5, 4), (8, 12)]) == [(0, 2), (4, 8)]
    assert T.length([(0, 2), (4, 8)]) == 6


def test_self_time_takes_nested_ops_out_of_their_loop():
    events = [("while.1", 0, 10), ("fusion.a", 1, 3), ("fusion.b", 5, 2), ("fusion.a", 12, 1)]
    got = T.self_times(events, 0, 20)
    assert got == {"while.1": 5, "fusion.a": 4, "fusion.b": 2}
    assert sum(got.values()) == T.length(T.union([(s, s + d) for _, s, d in events]))


def test_reduce_busy_gaps_and_collectives():
    host = [("bench.window", 0, 100), ("feeder.next", 20, 15), ("loss.wait", 60, 30)]
    dev0 = [("fusion.1", 0, 20), ("all-reduce.3", 40, 20), ("fusion.2", 45, 5), ("fusion.4", 90, 10)]
    dev1 = [("fusion.1", 0, 50)]
    r = T.reduce({"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, "host": host})
    ns = 1e-9
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["busy_s"] == pytest.approx((50 + 50) / 2 * ns)  # device 0: 20 + 20 + 10
    # device 0 idles 20–40 (feeder.next overlaps 15 of it) and 60–90 (loss.wait)
    assert dict(r["idle_gaps"]) == pytest.approx({"feeder.next": 20 * ns, "loss.wait": 30 * ns})
    assert r["collective_s"] == pytest.approx(20 * ns)
    assert r["collective_exposed_s"] == pytest.approx(15 * ns)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(35 * ns)]


def test_reduce_recorded_v5e_slice():
    events = json.load(open(FIXTURES / "trace_lm_v5e.json"))
    r = T.reduce(events)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(2.007257e-3)
    assert r["busy_s"] == pytest.approx(1.999991e-3)
    # the one gap is the host waiting on the previous step's loss
    assert r["idle_gaps"] == [["loss.wait", pytest.approx(7.266e-6)]]
    assert sum(t for _, t in r["device_ops"]) == pytest.approx(r["busy_s"])
    assert r["collective_s"] == 0.0


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        T.reduce({"devices": {"/device:TPU:0": [("f", 0, 1)]}, "host": []})


def test_op_name():
    assert T.op_name("%fusion.570.remat = (f32[4096]{0}) fusion(f32[2]{0} %x), kind=kOutput") == \
        "fusion.570.remat"


HLO = """
%fused_computation (param_0: f32[256,256]) -> f32[256,256] {
  %cos.2 = f32[256,256]{1,0} cosine(%param_2.1), metadata={op_name="jit(f)/jvp(jit(f))/moe/cos" stack_frame_id=2}
  ROOT %add_any.2 = f32[256,256]{1,0} add(%param_0, %mul.2), metadata={op_name="jit(f)/transpose(jvp(jit(f)))/moe/add_any" stack_frame_id=2}
}

ENTRY %main.3 (x.1: f32[256,256]) -> f32[256,256] {
  %x.1 = f32[256,256]{1,0} parameter(0), metadata={op_name="x"}
  %constant.1 = f32[] constant(1)
  %wrapped_sine = f32[256,256]{1,0} fusion(%x.1), kind=kLoop, calls=%wrapped_sine_computation, metadata={op_name="jit(f)/jvp(jit(f))/moe/sin" stack_frame_id=2}, backend_config={"outer_dimension_partitions":["2"]}
  %dot_general.1 = f32[256,256]{1,0} dot(%wrapped_broadcast, %x.1), lhs_contracting_dims={1}, rhs_contracting_dims={1}, metadata={op_name="jit(f)/transpose(jvp(jit(f)))/moe/dot_general" stack_frame_id=2}
  ROOT %multiply_add_fusion = f32[256,256]{1,0} fusion(%dot, %dot_general.1, %x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/transpose(jvp(checkpoint))/attn/add_any" stack_frame_id=2}
}
"""


def test_hlo_stacks_maps_each_instruction_to_its_op_name():
    got = T.hlo_stacks(HLO)
    assert got["wrapped_sine"] == "jit(f)/jvp(jit(f))/moe/sin"
    assert got["dot_general.1"] == "jit(f)/transpose(jvp(jit(f)))/moe/dot_general"
    assert got["multiply_add_fusion"] == "jit(f)/transpose(jvp(checkpoint))/attn/add_any"
    assert got["cos.2"].endswith("/moe/cos")
    assert "constant.1" not in got  # no metadata


# Shaped as a v5e step's HLO: the compiler makes an async collective's start
# fusion, a loop's prefetches and the entry's parameter prefetches with no
# metadata of their own.
HLO_NO_METADATA = """
%fused_computation.758 (param_0.1: bf16[768,3072]) -> (bf16[768,3072], bf16[3072,3072]) {
  %all-gather.100 = bf16[3072,3072]{1,0} all-gather(%param_0.1), dimensions={0}, metadata={op_name="jit(step)/transpose(jvp(moe))/dot_general"}
  ROOT %custom-call.90 = (bf16[768,3072]{1,0}, bf16[3072,3072]{1,0}) custom-call(%all-gather.100), custom_call_target="AsyncCollectiveStart"
}

%wide.body (p: (s32[], f32[4096])) -> (s32[], f32[4096]) {
  %copy-start.3 = (f32[4096]{0}, f32[4096]{0:S(1)}, u32[]) copy-start(%get-tuple-element.7)
  %copy-done.3 = f32[4096]{0:S(1)} copy-done(%copy-start.3)
  ROOT %tuple.2 = (s32[], f32[4096]{0}) tuple(%add.1, %copy-done.3)
}

ENTRY %main.52_spmd (param.40: f32[3072]) -> f32[3072] {
  %async-collective-start.2 = (bf16[768,3072]{1,0}, bf16[3072,3072]{1,0}) fusion(%bitcast.9), kind=kCustom, calls=%fused_computation.758, backend_config={"flag_configs":[]}
  %async-collective-done.2 = bf16[3072,3072]{1,0} fusion(%get-tuple-element.4562), kind=kCustom, calls=%fused_computation.761, metadata={op_name="jit(step)/transpose(jvp(moe))/dot_general"}
  %all-reduce-start.4 = f32[3072]{0} all-reduce-start(%param.40), to_apply=%add
  %all-reduce-done.4 = f32[3072]{0} all-reduce-done(%all-reduce-start.4), metadata={op_name="jit(step)/transpose(jvp(attn))/reduce_sum"}
  %while.5 = (s32[], f32[4096]{0}) while(%tuple.1), condition=%wide.cond, body=%wide.body, metadata={op_name="jit(step)/jvp(attn)/while"}
  %copy-start.120 = (f32[3072]{0}, f32[3072]{0:S(1)}, u32[]) copy-start(%param.40)
  ROOT %copy-done.120 = f32[3072]{0:S(1)} copy-done(%copy-start.120)
}
"""


def test_hlo_stacks_names_the_ops_the_compiler_made_without_metadata():
    got = T.hlo_stacks(HLO_NO_METADATA)
    # a fusion: the computation it calls
    assert got["async-collective-start.2"] == "jit(step)/transpose(jvp(moe))/dot_general"
    # an async start: its done
    assert got["all-reduce-start.4"] == "jit(step)/transpose(jvp(attn))/reduce_sum"
    # inside a loop's body: the loop
    assert got["copy-start.3"] == got["copy-done.3"] == "jit(step)/jvp(attn)/while"
    # the entry's prefetches have nothing to name them
    assert "copy-start.120" not in got and "copy-done.120" not in got


@pytest.mark.parametrize("stack, scopes", [
    ("jit(step)/jit(main)/moe/dot_general", ["jit(step)", "jit(main)", "moe", "dot_general"]),
    ("jit(f)/jvp(moe)/mul", ["jit(f)", "moe", "mul"]),
    ("jit(f)/transpose(jvp(moe))/mul", ["jit(f)", "moe", "mul"]),
    ("jit(f)/transpose(jvp(checkpoint))/moe/add_any", ["jit(f)", "moe", "add_any"]),
    ("jit(f)/remat/moe/mul", ["jit(f)", "moe", "mul"]),
    ("jit(f)/vmap(jvp(attn))/moe/mul", ["jit(f)", "attn", "moe", "mul"]),
    ("jit(f)/jvp(jit(g/h))/x", ["jit(f)", "jit(g/h)", "x"]),
])
def test_scopes_of_strips_autodiff_and_remat_wrappers(stack, scopes):
    assert T.scopes_of(stack) == scopes


def test_reduce_puts_self_time_under_each_named_scope_forward_and_backward():
    host = [("bench.window", 0, 100)]
    dev = [("while.1", 0, 60), ("fusion.fwd", 5, 10), ("fusion.bwd", 20, 30), ("copy-done.1", 70, 5)]
    stacks = {"fusion.fwd": "jit(step)/jvp(moe)/dot_general",
              "fusion.bwd": "jit(step)/transpose(jvp(moe))/dot_general",
              "while.1": "jit(step)/while"}
    events = {"devices": {"/device:TPU:0": dev, "/device:TPU:1": dev}, "host": host}
    r = T.reduce(events, stacks=stacks)
    ns = 1e-9
    assert r["scopes"]["moe"] == pytest.approx(2 * 40 * ns)  # both chips: device-time
    assert r["scopes"]["jit(step)"] == pytest.approx(2 * 60 * ns)
    assert r["scopes"]["while"] == pytest.approx(2 * 20 * ns)
    assert r["coverage"] == pytest.approx(60 / 65)
    # without the HLO text's stacks nothing is known
    r = T.reduce(events)
    assert r["scopes"] == {} and r["coverage"] == 0.0


class _Event:
    def __init__(self, name, start, dur, **stats):
        self.name, self.start_ns, self.duration_ns, self.stats = name, start, dur, stats


def _line(name, *events):
    return type("Line", (), {"name": name, "events": list(events)})()


def _plane(name, *lines):
    return type("Plane", (), {"name": name, "lines": list(lines)})()


def _trace(*planes):
    return type("Trace", (), {"planes": list(planes)})()


_HOST = _plane("/host:CPU", _line("python", _Event("bench.window", 0, 100)),
               _line("tf_pjrt_cpu", _Event("fusion.3", 10, 5, hlo_op="fusion.3"),
                     _Event("end: fusion.3", 15, 0, hlo_op="fusion.3")))


def test_load_reads_each_device_ops_line_and_the_benchmark_spans():
    ops = _line("XLA Ops", _Event("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 20, 30))
    got = T.load(_trace(_plane("/device:TPU:0", _line("XLA Modules"), ops), _HOST))
    assert got == {"devices": {"/device:TPU:0": [("fusion.1", 20.0, 30.0)]},
                   "host": [("bench.window", 0.0, 100.0)]}
    # host threads stand for devices on a chip only where the caller says so
    assert T.load(_trace(_plane("/device:TPU:0", ops), _HOST), cpu_stand_in=False) == got


def test_a_chip_trace_without_an_ops_line_is_refused_not_read_from_host_threads():
    data = _trace(_plane("/device:TPU:0", _line("XLA Modules"), _line("Steps")), _HOST)
    events = T.load(data)
    assert events["devices"] == {}
    with pytest.raises(ValueError, match="no device with an XLA Ops line"):
        T.reduce(events)


def test_the_cpu_backend_reads_its_op_threads_as_devices():
    events = T.load(_trace(_HOST), cpu_stand_in=True)
    assert events["devices"] == {"/host:CPU#1": [("fusion.3", 10.0, 5.0)]}
    assert T.reduce(events)["busy_s"] == pytest.approx(5e-9)

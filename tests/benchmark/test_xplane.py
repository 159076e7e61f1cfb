"""The trace reduction, on hand-made traces whose answers are known, and on
a small trace recorded on a v5e (``data/v5e_small.xplane.pb``) where present."""

import os

import pytest

from perfbench import xplane

US = 1_000_000  # picoseconds in a microsecond


def _space(*planes):
    from jax.profiler import ProfileData

    return list(ProfileData.from_text_proto("\n".join(planes)).planes)


def _plane(name, lines, names, stats=None):
    """``lines``: {line name: [(metadata id, start us, duration us, category)]}."""
    out = [f'planes {{ name: "{name}"']
    for i, (line, events) in enumerate(lines.items()):
        out.append(f'lines {{ id: {i + 1} name: "{line}" timestamp_ns: 0')
        for meta, start, dur, *cat in events:
            stat = f' stats {{ metadata_id: 1 str_value: "{cat[0]}" }}' if cat else ""
            out.append(f"events {{ metadata_id: {meta} offset_ps: {start * US} duration_ps: {dur * US}{stat} }}")
        out.append("}")
    for key, value in names.items():
        out.append(f'event_metadata {{ key: {key} value {{ id: {key} name: "{value}" }} }}')
    out.append('stat_metadata { key: 1 value { id: 1 name: "hlo_category" } }')
    out.append("}")
    return "\n".join(out)


# names as the chip writes them: the whole HLO instruction
KERNEL = ('%attn._cached_attention.7 = bf16[8,16,1,256]{3,2,1,0} custom-call(s32[8,160]{1,0} %p), '
          'custom_call_target=\\"tpu_custom_call\\"')
NAMES = {1: "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", 2: "%fusion.22 = f32[] fusion()",
         3: "%while.4 = (s32[]) while((s32[]) %t)", 4: KERNEL, 5: "%all-reduce.3 = f32[8] all-reduce(f32[8] %x)",
         6: "%all-gather-done.1 = f32[8] all-gather-done(%s)", 7: "%copy.9 = bf16[8] copy(bf16[8] %y)",
         8: '%custom-call.4 = bf16[8] custom-call(bf16[8] %z), custom_call_target=\\"ConcatBitcast\\"'}


def _device(n, ops, modules=()):
    return _plane(f"/device:TPU:{n}", {"XLA Ops": ops, "XLA Modules": list(modules)},
                  {**NAMES, 20: "jit_chunk_fn(123)", 21: "jit_fn(77)"})


def _host(spans, window=(0, 100)):
    names = {1: xplane.WINDOW_SPAN, 2: "engine.step", 3: "generator.wait"}
    events = [(1, window[0], window[1] - window[0])] + spans
    return _plane("/host:CPU", {"main": events}, names)


def test_busy_is_the_union_and_idle_the_rest():
    # ops at [10,30) and [20,50) overlap; [70,80) stands alone: busy 50 of 100 us
    planes = _space(_device(0, [(1, 10, 20), (2, 20, 30), (7, 70, 10)]), _host([]))
    r = xplane.reduce_planes(planes)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(50e-6)
    assert r["devices"] == 1


def test_self_time_does_not_count_a_loop_body_twice():
    # a while of 60 us encloses two fusions of 20 us and a kernel of 10 us
    ops = [(3, 10, 60), (1, 10, 20), (2, 30, 20), (4, 55, 10), (8, 52, 2)]
    r = xplane.reduce_planes(_space(_device(0, ops, [(20, 5, 70)]), _host([])))
    assert r["busy_s"] == pytest.approx(60e-6)
    assert r["op_s"]["fusion"] == pytest.approx(40e-6)      # fusion.1 + fusion.22 under one name
    assert r["op_s"]["while"] == pytest.approx(8e-6)        # 60 - 20 - 20 - 10 - 2
    # a Pallas kernel is a tpu_custom_call; XLA's own custom calls are not kernels
    assert r["kernel_s"] == {"attn._cached_attention": pytest.approx(10e-6)}
    assert r["kernel_s_by_module"] == {"jit_chunk_fn": {"attn._cached_attention": pytest.approx(10e-6)}}
    assert r["device_ops"][0][0] == "fusion"


def test_ops_are_clipped_to_the_window_span():
    planes = _space(_device(0, [(1, 0, 40), (2, 90, 40)]), _host([], window=(20, 100)))
    r = xplane.reduce_planes(planes)
    assert r["window_s"] == pytest.approx(80e-6)
    assert r["busy_s"] == pytest.approx(30e-6)              # [20,40) and [90,100)


def test_exposed_collective_time_and_the_mean_over_chips():
    # chip 0: all-reduce 10 us and an all-gather-done 5 us hold the op line; chip 1: none
    a = _device(0, [(1, 0, 50), (5, 50, 10), (6, 60, 5)])
    b = _device(1, [(1, 0, 50)])
    r = xplane.reduce_planes(_space(a, b, _host([])))
    assert r["devices"] == 2
    assert r["collective_exposed_s"] == pytest.approx(7.5e-6)   # (15 + 0) / 2
    assert r["busy_s"] == pytest.approx((65e-6 + 50e-6) / 2)


def test_modules_add_up_by_jit_name():
    mods = [(20, 0, 30), (20, 40, 30), (21, 75, 10)]
    r = xplane.reduce_planes(_space(_device(0, [(1, 0, 90)], mods), _host([])))
    assert r["module_s"] == {"jit_chunk_fn": pytest.approx(60e-6), "jit_fn": pytest.approx(10e-6)}
    assert r["module_calls"]["jit_chunk_fn"] == 2


def test_idle_gaps_are_named_after_the_innermost_host_span():
    # device idle in [30,60) while engine.step runs, and in [70,100) while the generator waits
    ops = [(1, 0, 30), (2, 60, 10)]
    host = _host([(2, 25, 40), (3, 68, 32)])
    r = xplane.reduce_planes(_space(_device(0, ops), host), ("engine.step", "generator.wait"))
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    assert gaps["engine.step"] == pytest.approx(30e-6)
    assert gaps["generator.wait"] == pytest.approx(30e-6)


def test_a_trace_without_a_device_plane_is_refused_unless_rehearsing():
    planes = _space(_host([]))
    with pytest.raises(RuntimeError):
        xplane.reduce_planes(planes)
    assert xplane.reduce_planes(planes, require_device=False)["devices"] == 0


def test_interval_helpers():
    assert xplane.union_length([(0, 10), (5, 15), (20, 25)]) == 20
    assert xplane.gaps_between([(5, 10), (20, 30)], 0, 40) == [(0, 5), (10, 20), (30, 40)]
    assert xplane.base_name("%fusion.12.3 = f32[]") == "fusion"
    assert xplane.is_collective("reduce-scatter.5") and not xplane.is_collective("fusion.1")


RECORDED = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded v5e trace in the tree")
def test_recorded_v5e_trace_reduces():
    r = xplane.reduce_planes(xplane.read_planes(RECORDED))
    assert r["devices"] >= 1
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and r["module_s"]
    assert sum(r["kernel_s"].values()) > 0.0


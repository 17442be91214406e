"""The trace reduction on a small synthetic trace: busy and idle time of
the device, per-program and per-op time, and idle gaps attributed to
the host span open at the time."""
import pytest

from bench import xplane


def _plane(pid, name, lines, stat_names=()):
    """Text proto of one XPlane; ``lines`` maps a line name to
    ``(event name, start ns, duration ns, {stat: str})`` tuples."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    smeta = {n: i + 1 for i, n in enumerate(stat_names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        out.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for ev, start, dur, *stats in evs:
            st = "".join(
                f' stats {{ metadata_id: {smeta[k]} str_value: "{v}" }}'
                for k, v in (stats[0] if stats else {}).items())
            out.append(f'    events {{ metadata_id: {meta[ev]} '
                       f'offset_ps: {start * 1000} '
                       f'duration_ps: {dur * 1000}{st} }}')
        out.append('  }')
    for n, i in meta.items():
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
    for n, i in smeta.items():
        out.append(f'  stat_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
    out.append('}')
    return "\n".join(out)


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    gmm = "%gmm.7 = f32[64,40,2048] custom-call(bf16[64,40,2048] %x)"
    device = _plane(1, "/device:TPU:0", {
        "XLA Modules": [("jit__step(31)", 100, 300),
                        ("jit_prefill_chunk(42)", 600, 200),
                        ("jit__step(31)", 1100, 300)],
        "XLA Ops": [("%while.3 = (s32[]) while(%t)", 100, 300),
                    ("%fusion.1 = bf16[8] fusion(%gmm.7)", 100, 100),
                    (gmm, 200, 200),
                    ("%fusion.2 = bf16[8] fusion(%p)", 600, 200),
                    ("%fusion.1 = bf16[8] fusion(%gmm.7)", 1100, 100),
                    (gmm, 1200, 200),
                    # outside the window: clipped away
                    ("%fusion.1 = bf16[8] fusion(%gmm.7)", 5000, 100)],
    })
    host = _plane(2, "/host:CPU", {"python": [
        ("bench.window", 0, 2000),
        ("FlowServeEngine.step", 0, 900),
        ("DPGroup.decode_launch", 30, 60),
        ("DPGroup.run_prefill_chunk", 400, 150),
        ("FlowServeEngine.step", 950, 500),
        ("not.a.span", 500, 10),
    ]})
    return xplane.load(ProfileData.from_text_proto(device + "\n" + host),
                       ["FlowServeEngine.step", "DPGroup.decode_launch",
                        "DPGroup.run_prefill_chunk"])


def test_window_and_busy(trace):
    assert trace.window == (0, 2000)
    assert trace.window_s == pytest.approx(2e-6)
    # ops cover [100, 400) + [600, 800) + [1100, 1400) = 800 ns
    assert xplane.busy_s(trace) == pytest.approx(800e-9)


def test_modules_and_ops(trace):
    dec = xplane.modules(trace, "jit__step")
    assert len(dec) == 2 and dec.total_s() == pytest.approx(600e-9)
    assert xplane.modules(trace, "jit_prefill_chunk").total_s() == \
        pytest.approx(200e-9)
    # a consumer that names %gmm.7 as its operand is not the kernel
    gmm = xplane.ops(trace, "gmm")
    assert len(gmm) == 2 and gmm.total_s() == pytest.approx(400e-9)
    assert len(xplane.ops(trace, "fusion", module="jit_prefill_chunk")) == 1
    assert len(xplane.ops(trace, "fusion", module="jit__step")) == 2
    top = xplane.top_ops(trace)
    assert top[0][0] == "jit__step/gmm"
    assert top[0][1] == pytest.approx(400e-9)


def test_short_names():
    assert xplane.short_name("%gmm.7 = f32[8] custom-call(%a)") == "gmm"
    assert xplane.short_name("jit__step(9519399922792469619)") == \
        "jit__step"
    assert xplane.short_name(
        "%dynamic-slice_bitcast_fusion.8 = bf16[64] fusion()") == \
        "dynamic-slice_bitcast_fusion"


def test_idle_gaps_go_to_the_open_host_span(trace):
    idle = dict(xplane.attribute_idle(trace))
    # [0,100) mid 50: decode_launch open inside the first step;
    # [400,600) mid 500: run_prefill_chunk; [800,1100) mid 950: the
    # second step opens at 950; [1400,2000) mid 1700: no span
    assert idle["DPGroup.decode_launch"] == pytest.approx(100e-9)
    assert idle["DPGroup.run_prefill_chunk"] == pytest.approx(200e-9)
    assert idle["FlowServeEngine.step"] == pytest.approx(300e-9)
    assert idle["client"] == pytest.approx(600e-9)
    assert sum(idle.values()) == pytest.approx(
        trace.window_s - xplane.busy_s(trace))


def test_a_trace_without_the_window_event_is_refused():
    from jax.profiler import ProfileData

    host = _plane(2, "/host:CPU", {"python": [("x", 0, 10)]})
    with pytest.raises(ValueError):
        xplane.load(ProfileData.from_text_proto(host))

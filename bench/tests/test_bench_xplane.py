"""The trace reduction: on a small trace built by hand, where every number
is known (device busy and idle in the window, device time per call of the
decode-step program, the kernel's time, idle gaps by host span), and on a
small trace recorded on the chip."""
import gzip
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import xplane

MS = 1_000_000_000  # picoseconds per millisecond


def _events(evs):
    return "\n".join(
        f"events {{ metadata_id: {m} offset_ps: {int(s * MS)} "
        f"duration_ps: {int(d * MS)} }}" for m, s, d in evs)


def _trace(tmp_path):
    # host: window 0-100 ms; idle wait 0-10; serve 10-100 with prefill
    # 10-30 and decode 30-100
    host = _events([(1, 0, 100), (2, 0, 10), (3, 10, 90), (4, 10, 20),
                    (5, 30, 70)])
    # device ops (ms): prefill fusion 12-20, decode steps 40-42, 60-62,
    # 80-82 with the kernel inside each (40.5-41.5, ...), and one op
    # before the window (-5 to -1, not counted)
    ops = _events([(11, 12, 8), (12, 40, 2), (13, 40.5, 1), (12, 60, 2),
                   (13, 60.5, 1), (12, 80, 2), (13, 80.5, 1), (11, -5, 4)])
    mods = _events([(21, 12, 8), (22, 40, 2), (22, 60, 2), (22, 80, 2)])
    text = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000000 {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.idle" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "bench.serve" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "prefill" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "decode" }} }}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000 {ops} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000000 {mods} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "%fusion.1 = f32[8]{{0}} fusion(f32[8]{{0}} %p)" }} }}
  event_metadata {{ key: 12 value {{ id: 12 name: "%while.3 = (s32[]) while((s32[]) %t)" }} }}
  event_metadata {{ key: 13 value {{ id: 13 name: "%paged_attention.11 = bf16[1,32,1,128]{{3,2,1,0}} custom-call(s32[1,64]{{1,0}} %x)" }} }}
  event_metadata {{ key: 21 value {{ id: 21 name: "jit_prefill(7)" }} }}
  event_metadata {{ key: 22 value {{ id: 22 name: "jit__paged_decode_step(9)" }} }}
}}
planes {{ id: 3 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000 {_events([(11, 0, 100)])} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "fusion.1" }} }}
}}
"""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return tmp_path


def test_reduction_of_a_known_trace(tmp_path):
    r = xplane.reduce_dir(_trace(tmp_path))  # chip 0 only
    assert r.window_s == pytest.approx(0.100)
    # busy: 12-20 and three 2 ms steps (the kernel lies inside them)
    assert r.busy_s == pytest.approx(0.014)
    assert r.per_call_ms("_paged_decode_step") == pytest.approx(2.0)
    assert r.programs["_paged_decode_step"][0] == 3
    assert r.per_call_ms("no_such_program") is None
    assert r.kernel_calls == 3
    assert r.kernel_s == pytest.approx(0.003)
    # idle 0-12 (bench.idle 0-10, prefill 10-12 by the midpoint: 6 ms lies
    # in bench.idle), 20-40 in decode (midpoint 30 is decode's start),
    # 42-60, 62-80, 82-100 in decode
    idle = r.idle_by_host
    assert sum(idle.values()) == pytest.approx(0.100 - 0.014)
    assert idle["bench.idle"] == pytest.approx(0.012)
    assert idle["decode"] == pytest.approx(0.074)
    b = r.breakdown()
    # ops by program and HLO name; the while loop's body is what counts
    assert b["device_ops"] == [
        ["prefill/fusion", pytest.approx(0.008)],
        ["_paged_decode_step/paged_attention", pytest.approx(0.003)]]
    assert len(b["idle_gaps"]) <= 10


@pytest.mark.parametrize("name, op", [
    ("%paged_attention.11 = bf16[1,32,1,128]{3,2,1,0} custom-call(s32[1] %a)",
     "paged_attention"),
    ("%multiply_reduce_fusion.6 = f32[] fusion(bf16[1,1,4096] %g)",
     "multiply_reduce_fusion"),
    ("%dynamic-update-slice.26 = bf16[64] dynamic-update-slice(bf16[64] %c)",
     "dynamic-update-slice"),
    ("%fusion = f32[] fusion(f32[] %x)", "fusion"),
    ("copy-start", "copy-start"),
])
def test_op_name(name, op):
    assert xplane.op_name(name) == op


def test_busy_averages_the_chips_asked_for(tmp_path):
    r = xplane.reduce_dir(_trace(tmp_path), chips=[0, 1])
    assert r.busy_s == pytest.approx((0.014 + 0.100) / 2)


def test_no_window_span_is_an_error(tmp_path):
    d = tmp_path / "t"
    d.mkdir()
    (d / "x.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 2 name: "/device:TPU:0" }'))
    with pytest.raises(ValueError):
        xplane.reduce_dir(d)


# ------------------------------------------------ a trace recorded on the chip
# deepseek-7b-l8, three cold requests of 128 + 16 tokens served back to back
# on one TPU v5e, with the harness's spans (bench.window, layer calls)
RECORDED = Path(__file__).with_name("data") / "cold-3req.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("recorded")
    (d / "run.xplane.pb").write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return d


def test_recorded_trace(recorded):
    r = xplane.reduce_dir(recorded)
    pd = ProfileData.from_file(str(next(recorded.glob("*.xplane.pb"))))
    host = [e for p in pd.planes if p.name == "/host:CPU" for ln in p.lines
            for e in ln.events if e.name == "bench.window"]
    w0, w1 = host[0].start_ns, host[0].end_ns
    dev = pd.find_plane_with_name("/device:TPU:0")
    steps = [e for ln in dev.lines if ln.name == "XLA Modules"
             for e in ln.events if e.name.startswith("jit__paged_decode_step(")
             and w0 <= e.start_ns and e.end_ns <= w1]
    assert r.window_s == pytest.approx((w1 - w0) * 1e-9)
    # 3 requests x 16 decode steps, 8 layers each with one kernel call
    assert r.programs["_paged_decode_step"][0] == len(steps) == 48
    assert r.kernel_calls == 48 * 8
    assert r.per_call_ms("_paged_decode_step") == pytest.approx(
        sum(e.duration_ns for e in steps) * 1e-6 / 48)
    assert 0 < r.kernel_s < r.programs["_paged_decode_step"][1] < r.busy_s
    assert r.busy_s < r.window_s
    # the device waits on the host's prefill (an eager forward) the most
    assert r.breakdown()["idle_gaps"][0][0] == "prefill"
    assert sum(r.idle_by_host.values()) == pytest.approx(r.window_s - r.busy_s)

"""The reduction from the profiler's xplane to busy/idle, kernel time,
exposed collectives and the breakdown, on a synthetic trace that goes
through the real loader (`ProfileData`, from a text proto)."""
import pytest
from bench_testlib import REPO  # noqa: F401  (puts the repo on sys.path)

from benchmarks.harness import xplane

# One chip, two runs of the step's program (0..1000 us and 1100..2100 us):
#   a `while` 100..900 us that holds a flash kernel 100..400, a fusion
#   400..600 and an all-reduce 700..900 of which 800..900 runs beside
#   nothing; a gap 600..700; then fusion.9 900..1000.
# The second run holds one fusion 1100..2100.  Host spans on the same clock.
US = 1_000_000  # ps


def ev(meta, lo_us, hi_us, stat=None):
    stats = f' stats {{ metadata_id: 1 str_value: "{stat}" }}' if stat else ""
    return (f"events {{ metadata_id: {meta} offset_ps: {lo_us * US} "
            f"duration_ps: {(hi_us - lo_us) * US}{stats} }}")


TRACE = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {ev(10, 0, 1000)} {ev(10, 1100, 2100)} {ev(11, 2200, 2300)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {ev(1, 100, 900)}
    {ev(2, 100, 400, "jit(train_step)/while/body/flash_packed_fwd/pallas_call")}
    {ev(3, 400, 600)} {ev(4, 700, 900)} {ev(5, 900, 1000)}
    {ev(3, 1100, 2100)} {ev(5, 2200, 2300)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "while.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "custom-call.7" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.3 = bf16[8]{{0}} fusion(bf16[8]{{0}} %flash_packed_fwd.3), kind=kLoop" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "all-reduce.1" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "fusion.9" }} }}
  event_metadata {{ key: 10 value {{ id: 10 name: "jit_train_step(123)" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "jit_leaf_norms(5)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
planes {{ id: 2 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {ev(10, 0, 1000)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {ev(3, 0, 500)} {ev(4, 500, 1000)} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "fusion.3" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "all-reduce.1" }} }}
  event_metadata {{ key: 10 value {{ id: 10 name: "jit_train_step(123)" }} }}
}}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {ev(20, 550, 720)} {ev(21, 0, 50)} {ev(22, 10, 20)} }}
  event_metadata {{ key: 20 value {{ id: 20 name: "bench.device_put" }} }}
  event_metadata {{ key: 21 value {{ id: 21 name: "bench.dispatch" }} }}
  event_metadata {{ key: 22 value {{ id: 22 name: "something.else" }} }}
}}
"""


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    return xplane.from_profile_data(ProfileData.from_text_proto(TRACE))


@pytest.fixture(scope="module")
def ws(trace):
    return xplane.windows(trace, "jit_train_step")


def test_loader_finds_devices_ops_modules_and_the_benchmarks_spans(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert len(trace.devices[0].ops) == 7 and len(trace.devices[0].modules) == 3
    assert sorted(s.name for s in trace.host_spans) == \
        ["bench.device_put", "bench.dispatch"]
    flash = [e for e in trace.devices[0].ops if e.name == "custom-call.7"][0]
    assert "flash_packed_fwd" in flash.text and flash.dur == 300_000


def test_window_is_the_whole_runs_of_the_steps_program(ws):
    w = ws[0]
    assert (w.lo, w.hi, w.steps) == (0.0, 2_100_000.0, 2)
    assert all(e.end <= w.hi for e in w.ops)      # fusion.9 at 2200 is out


def test_busy_union_and_idle_share(ws):
    # chip 0: busy 100..600, 700..1000 and 1100..2100 = 1800 of 2100 us (the
    # gap inside the while counts as idle); chip 1: all of its 1000 us
    assert xplane.total(ws[0].busy) == 1_800_000
    busy_s, window_s = xplane.busy_and_window_s(ws)
    assert busy_s == pytest.approx((1800e-6 + 1000e-6) / 2)
    assert window_s == pytest.approx((2100e-6 + 1000e-6) / 2)
    assert xplane.idle_share(ws) == pytest.approx(300 / 2100)  # the worst chip


def test_kernel_time_is_found_by_event_name_or_by_its_op_name_stat(ws):
    assert xplane.kernel_ms_per_step(ws, ["flash_packed_fwd"]) == \
        pytest.approx(0.300 / 2)
    assert xplane.kernel_ms_per_step(ws, ["fusion.9"]) == pytest.approx(0.05)
    assert xplane.kernel_ms_per_step(ws, ["renamed_kernel"]) is None


def test_an_operand_named_like_the_kernel_is_not_the_kernel(trace):
    # fusion.3 reads %flash_packed_fwd.3; its event keeps only its own name
    assert {e.name for e in trace.devices[0].ops} == {
        "while.1", "custom-call.7", "fusion.3", "all-reduce.1", "fusion.9"}
    assert xplane.own_name("%flash_packed_dq.9 = bf16[64,512,768]{2,1,0} "
                           "custom-call(s32[1]{0} %g)") == "flash_packed_dq.9"


def test_exposed_collective_is_what_no_other_operation_covers(ws):
    # chip 0: all-reduce 700..900, nothing else runs beside it (the while
    # that holds it is no operation of its own): 200 us over 2 steps.
    # chip 1: 500 us over 1 step: the worst chip counts.
    assert xplane.exposed_collective_ms_per_step(ws) == pytest.approx(0.5)
    assert xplane.exposed_collective_ms_per_step(ws[:1]) == pytest.approx(0.1)


def test_own_time_takes_nested_operations_out_of_the_while(ws):
    top = dict(xplane.top_ops(ws[:1]))
    assert top["fusion.3"] == pytest.approx(1200e-6)
    assert top["while.1"] == pytest.approx(100e-6)   # 800 - 300 - 200 - 200
    assert list(top)[0] == "fusion.3"


def test_idle_gaps_are_named_by_the_host_span_that_covers_them(trace, ws):
    gaps = xplane.idle_gaps(ws[0], trace.host_spans)
    assert sorted(g[0] for g in gaps) == ["device_put", "dispatch", "no_span"]
    by = {g[0]: g[1] for g in gaps}
    assert by["device_put"] == pytest.approx(100e-6)   # 600..700
    assert by["dispatch"] == pytest.approx(100e-6)     # 0..100
    assert by["no_span"] == pytest.approx(100e-6)      # 1000..1100


def test_interval_arithmetic():
    assert xplane.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4)], [(0, 4)]) == []
    assert xplane.clip([(0, 10)], 2, 5) == [(2, 5)]


def test_a_trace_without_the_steps_program_gives_no_window(trace):
    assert xplane.windows(trace, "jit_other_program") == []

"""The reduction from a profiler trace to busy, idle and per-program time."""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import device_trace

FIXTURES = Path(__file__).resolve().parent / "fixtures"
US = 1_000_000  # picoseconds per microsecond


def event(meta, start_us, end_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * US} "
            f"duration_ps: {(end_us - start_us) * US} }}")


def plane(pid, name, line, events, names):
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                    for i, n in names.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" '
            f'timestamp_ns: 0 {" ".join(events)} }} {meta} }}')


def write_trace(tmp_path, text):
    out = tmp_path / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_known_busy_idle_and_programs(tmp_path):
    # device: fold 10-20 us, partition 15-40 us, fold 60-70 us, and one fold
    # outside the traced stretch; host: the stretch 0-100 us, two rounds,
    # an observe call and a measurement
    device = plane(1, "/device:TPU:0", "XLA Modules", [
        event(1, 10, 20), event(2, 15, 40), event(1, 60, 70), event(1, 120, 130),
    ], {1: "jit__fold_in_impl(7)", 2: "jit__partition_units_impl(9)"})
    host = plane(2, "/host:CPU", "main", [
        event(1, 0, 100), event(2, 0, 50), event(3, 2, 45), event(2, 50, 100),
        event(4, 75, 95), event(5, 0, 200),
    ], {1: "bench.traced", 2: "bench.round", 3: "bench.observe", 4: "bench.measure",
        5: "python main"})
    devices, annotations = device_trace.load(write_trace(tmp_path, device + host))
    assert [a[0] for a in annotations].count("bench.round") == 2
    red = device_trace.reduce(devices, annotations)
    assert red["window_s"] == pytest.approx(100e-6)
    assert red["busy_s"] == pytest.approx(40e-6)  # [10, 40] and [60, 70]
    assert red["programs_s"] == pytest.approx(
        {"jit__fold_in_impl": 20e-6, "jit__partition_units_impl": 25e-6})
    # idle [0, 10] under observe, [40, 60] mid-point 50 in the second round,
    # [70, 100] mid-point 85 in the measurement
    assert red["idle_s"] == pytest.approx(
        {"bench.observe": 10e-6, "bench.round": 20e-6, "bench.measure": 30e-6})
    top = device_trace.breakdown(red)
    assert top["device_ops"][0][0] == "jit__partition_units_impl"
    assert top["idle_gaps"][0] == ["bench.measure", pytest.approx(30e-6)]


def test_a_recorded_chip_trace(tmp_path):
    # fixtures/steady-small.xplane.pb: two rounds of the steady mix at p=200,
    # profiled on a TPU v5e by the Tracer of bench/traffic/generator.py
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "chip.xplane.pb").write_bytes((FIXTURES / "steady-small.xplane.pb").read_bytes())
    devices, annotations = device_trace.load(str(tmp_path))
    red = device_trace.reduce(devices, annotations)
    assert red["window_s"] == pytest.approx(5.01886e-3)
    assert red["busy_s"] == pytest.approx(3.61e-5)
    assert red["rounds"] == 2
    assert red["programs_s"] == pytest.approx(
        {"jit__fold_in_impl": 2.7236e-5, "jit_convert_element_type": 8.864e-6})
    assert red["idle_s"] == pytest.approx(
        {"bench.observe": 4.227646e-3, "bench.round": 4.16668e-4, "bench.measure": 3.38446e-4})
    # idle and busy fill the stretch; the programs ran one at a time
    assert red["busy_s"] + sum(red["idle_s"].values()) == pytest.approx(red["window_s"])
    assert sum(red["programs_s"].values()) == pytest.approx(red["busy_s"])


def test_no_stretch_or_no_device_event_reads_nothing(tmp_path):
    device = plane(1, "/device:TPU:0", "XLA Modules", [event(1, 500, 600)],
                   {1: "jit__fold_in_impl(7)"})
    host = plane(2, "/host:CPU", "main", [event(1, 0, 100)], {1: "bench.traced"})
    devices, annotations = device_trace.load(write_trace(tmp_path, device + host))
    assert device_trace.reduce(devices, annotations) is None
    assert device_trace.reduce({}, annotations) is None

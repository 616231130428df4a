"""The trace reduction on a trace written here, event by event, in the
profiler's own format (``XSpace`` protobuf), so every number it must give
can be worked out by hand."""
import pytest

from _bench_tiny import ROOT  # noqa: F401
from bench import trace


def _varint(x):
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(pid, name, lines):
    """``lines``: {line name: [(event name, start_us, duration_us)]}."""
    names = sorted({ev for evs in lines.values() for ev, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = _field(1, pid) + _field(2, name)
    for li, (lname, evs) in enumerate(lines.items()):
        line = _field(1, li + 1) + _field(2, lname) + _field(3, 0)
        for ev, start, dur in evs:
            line += _field(4, _field(1, ids[ev]) + _field(2, int(start * 1e6))
                           + _field(3, int(dur * 1e6)))
        body += _field(3, line)
    for n, i in ids.items():
        body += _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
    return _field(1, body)


HOST = {"python": [("bench.window", 0, 1000), ("bench.flush", 100, 500),
                   ("bench.place", 100, 450), ("bench.greedy_window", 300, 220),
                   ("other", 10, 5)]}
DEVICE = {"XLA Modules": [("jit__greedy_scan(1)", 350, 150),
                          ("jit_convert_element_type(2)", 320, 2)],
          "XLA Ops": [("%while.1 = (f64[4]) while(...)", 350, 150),
                      ("%add.2 = f64[4] add(...)", 360, 10),
                      ("%add.2 = f64[4] add(...)", 400, 10)]}


def _write(tmp_path, device=DEVICE):
    raw = (_plane(1, "/host:CPU", HOST) + _plane(2, "/device:TPU:0", device)
           + _plane(3, "/device:CUSTOM:Megascale Trace", {}))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    return str(path)


def test_busy_time_is_the_union_of_program_runs_on_the_chip(tmp_path):
    r = trace.reduce(_write(tmp_path))
    assert r.devices == 1          # the custom plane is not a chip
    assert r.window_s == pytest.approx(1000e-6)
    assert r.busy_s == pytest.approx(152e-6)
    assert r.module_n == {"jit__greedy_scan(1)": 1, "jit_convert_element_type(2)": 1}
    assert r.module_s["jit__greedy_scan(1)"] == pytest.approx(150e-6)
    assert r.ops_s == pytest.approx({"%while.1": 150e-6, "%add.2": 20e-6})
    assert r.ops_read == 3 and not r.dropped


def test_idle_gaps_are_named_by_the_innermost_host_span(tmp_path):
    r = trace.reduce(_write(tmp_path))
    # idle: [0, 320) mid 160 in place; [322, 350) mid 336 in greedy_window;
    # [500, 1000) mid 750 outside every span
    assert dict(r.idle_by_span) == pytest.approx(
        {"bench.place": 320e-6, "bench.greedy_window": 28e-6, "harness": 500e-6})
    assert r.gaps[0] == ("harness", pytest.approx(500e-6))


def test_a_dropped_trace_buffer_ends_the_window(tmp_path):
    device = dict(DEVICE, **{"XLA TraceMe": [("Trace Buffers Dropped", 420, 500)]})
    r = trace.reduce(_write(tmp_path, device))
    assert r.dropped
    assert r.window_s == pytest.approx(420e-6)
    assert r.busy_s == pytest.approx(2e-6 + 70e-6)
    assert r.ops_read == 3

"""The program's own spans (``gf.*``) read back from a profiler trace:
on a trace written here event by event, where every number can be worked
out by hand, and on a traced run of a tiny cell on the host CPU."""
import json
import struct

import jax
import jax.numpy as jnp
import pytest

from _bench_tiny import ROOT, on_cpu  # noqa: F401
from test_bench_trace_synthetic import DEVICE, HOST, _field, _varint
from bench import harness, program_spans, trace


def _stat(num, value):
    if isinstance(value, float):   # double_value, a fixed 64-bit field
        return _varint(2 << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, int):     # int64_value
        return _varint(4 << 3) + _varint(value)
    return _field(5, value)        # str_value


def _plane(pid, name, lines):
    """``lines``: {line name: [(event name, start_us, duration_us[, stats])]}."""
    evs_all = [ev for evs in lines.values() for ev in evs]
    ids = {n: i + 1 for i, n in enumerate(sorted({ev[0] for ev in evs_all}))}
    keys = sorted({k for ev in evs_all if len(ev) > 3 for k in ev[3]})
    sids = {k: i + 1 for i, k in enumerate(keys)}
    body = _field(1, pid) + _field(2, name)
    for li, (lname, evs) in enumerate(lines.items()):
        line = _field(1, li + 1) + _field(2, lname) + _field(3, 0)
        for ev in evs:
            raw = (_field(1, ids[ev[0]]) + _field(2, int(ev[1] * 1e6))
                   + _field(3, int(ev[2] * 1e6)))
            for k, v in (ev[3] if len(ev) > 3 else {}).items():
                raw += _field(4, _field(1, sids[k]) + _stat(sids[k], v))
            line += _field(4, raw)
        body += _field(3, line)
    for n, i in ids.items():
        body += _field(4, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
    for k, i in sids.items():
        body += _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, k)))
    return _field(1, body)


W = {"window": 3}
GF = [("gf.flush", 105, 490, dict(W, tasks=256, trigger="full", wait_ms=42.5)),
      ("gf.prepare", 106, 3, W), ("gf.place", 110, 435, W),
      ("gf.predict", 110, 20, W), ("gf.normalizers", 130, 70, W),
      ("gf.pack", 200, 100, W),
      ("gf.h2d", 305, 25, dict(W, bytes=1000, arrays=53)),
      ("gf.scan", 330, 175, W),
      ("gf.d2h", 505, 10, dict(W, bytes=500, arrays=21)),
      ("gf.winner", 515, 15, W), ("gf.commit", 530, 10, dict(W, timeline_len=77)),
      ("gf.release", 541, 3, W),
      ("gf.complete", 546, 44, W), ("gf.gc", 700, 20),
      ("gf.flush", 1100, 50, {"window": 4})]    # after the traced window


def _write(tmp_path, name, host):
    raw = _plane(1, "/host:CPU", host) + _plane(2, "/device:TPU:0", DEVICE)
    path = tmp_path / name
    path.write_bytes(raw)
    return str(path)


def test_program_spans_name_the_idle_by_hand(tmp_path):
    ps = program_spans.reduce(
        _write(tmp_path, "p.xplane.pb", {"python": HOST["python"] + GF}))
    assert ps.window_s == pytest.approx(1000e-6)
    assert [sp[0] for sp in ps.spans] == [ev[0] for ev in GF[:-1]]
    flush = ps.spans[0]
    assert flush[1:3] == (105_000, 595_000)
    assert flush[3] == {"window": 3, "tasks": 256, "trigger": "full", "wait_ms": 42.5}
    # idle: [0, 320) mid 160 in normalizers; [322, 350) mid 336 in the scan;
    # [500, 1000) mid 750 outside every gf span (gc ends at 720)
    assert ps.idle_s == pytest.approx(
        {"gf.normalizers": 320e-6, "gf.scan": 28e-6, "outside": 500e-6})
    s = program_spans.summary(ps)
    assert (s["device_windows"], s["flushes"], s["triggers"]) == (1, 1, {"full": 1})
    assert s["pack_ms"] == pytest.approx(0.190)
    assert s["commit_ms"] == pytest.approx(0.028)
    assert s["host_ms"] == pytest.approx(0.218)
    assert s["transfer_ms"] == pytest.approx(0.035)
    assert s["scan_ms"] == pytest.approx(0.175)
    assert s["engine_ms"] == pytest.approx(0.055)
    assert (s["transfer_bytes"], s["h2d_arrays"], s["d2h_arrays"]) == (1500, 53, 21)
    assert s["queue_wait_ms"] == 42.5
    assert s["queue_wait_ms_by_trigger"] == {"full": 42.5}
    assert s["timeline_len"] == [77]
    assert s["gc"] == {"collections": 1, "seconds": pytest.approx(20e-6)}
    assert s["program_idle_pct"] == pytest.approx(34.8)


def test_program_spans_leave_the_harness_reduction_as_it_was(tmp_path):
    """Every field of ``bench.trace.reduce`` reads the same with the
    program's spans in the trace; a gap under both ``bench.place`` and
    ``gf.normalizers`` stays ``bench.place``'s."""
    before = trace.reduce(_write(tmp_path, "a.xplane.pb", HOST))
    after = trace.reduce(
        _write(tmp_path, "b.xplane.pb", {"python": HOST["python"] + GF}))
    assert after == before
    assert dict(after.idle_by_span)["bench.place"] == pytest.approx(320e-6)


def test_a_traced_cell_nests_the_program_spans_by_window(on_cpu, capsys):
    from repro.kernels.placement import ops

    out = on_cpu / "trace"
    rc = harness.main(["--workload", "sebs256-stream", "--seed", str(2**32 + 9),
                       "--seconds", "1", "--trace", "1", "--trace-dir", str(out)],
                      root=on_cpu)
    res = capsys.readouterr()
    assert rc == 0, res.err[-2000:]
    metrics = json.loads(res.out.strip().splitlines()[-1])["metrics"]
    stats = dict(ops.WINDOW_STATS)
    ps = program_spans.reduce(trace.find(str(out)))
    wins = program_spans.by_window(ps.spans)
    assert wins and len(program_spans.device_windows(ps.spans)) == stats["device"]
    parent = {"gf.prepare": "gf.flush", "gf.place": "gf.flush",
              "gf.complete": "gf.flush", "gf.predict": "gf.place",
              "gf.normalizers": "gf.place", "gf.pack": "gf.place",
              "gf.h2d": "gf.place", "gf.scan": "gf.place", "gf.d2h": "gf.place",
              "gf.winner": "gf.place", "gf.commit": "gf.place",
              "gf.release": "gf.place"}
    for w, sps in wins.items():
        names = [sp[0] for sp in sps]
        assert sorted(names) == sorted(["gf.flush", *parent]), (w, names)
        by = {sp[0]: sp for sp in sps}
        for child, up in parent.items():
            assert by[up][1] <= by[child][1] and by[child][2] <= by[up][2]
        assert by["gf.flush"][3]["trigger"] in ("full", "timer")
        assert by["gf.h2d"][3]["arrays"] == 53 and by["gf.d2h"][3]["arrays"] == 21
    # the spans' bytes are the program's counters, window for window
    for side in ("h2d", "d2h"):
        assert sum(sp[3]["bytes"] for sp in ps.spans
                   if sp[0] == f"gf.{side}") == stats[f"{side}_bytes"]
    s = program_spans.summary(ps)
    assert all(s[k] > 0 for k in ("pack_ms", "commit_ms", "transfer_ms",
                                  "transfer_bytes", "queue_wait_ms"))
    assert metrics["transfer_bytes_per_window.stream"]["value"] == pytest.approx(
        (stats["h2d_bytes"] + stats["d2h_bytes"]) / stats["device"])
    assert metrics["scan_load_s"]["value"] == ops.COMPILE_STATS["seconds"] > 0


def test_the_scan_program_keeps_the_name_the_readers_match():
    """A rename of the scan would turn its device time and roofline to
    nothing in silence; here it fails."""
    from repro.kernels.placement import ops

    reader = harness.load_module(ROOT, "metrics", "scan_device_ms_per_window")
    assert reader.SCAN == ops.SCAN_PROGRAM
    E, C, H, T = 8, 4, 2, 4
    f = jnp.float64
    spec = jax.ShapeDtypeStruct
    with jax.enable_x64(True):
        consts = {k: spec((E,), f) for k in ("idle_bt", "su_bt", "qd", "rates", "wt")}
        consts.update(alive=spec((E,), jnp.bool_), rt_tab=spec((2, E), f),
                      en_tab=spec((2, E), f), fen_tab=spec((2,), f),
                      frt_tab=spec((2,), f), add_tab=spec((1, E), f),
                      hv_tab=spec((1, E), f),
                      scalars={k: spec((), f) for k in (
                          "a1", "b1", "g1", "idle_on_sum", "w_idle_on", "lam_b1",
                          "lam_a1", "alpha", "sf1", "sf2", "f_beta", "f_mu")})
        init = {k: spec((H, E), f) for k in (
            "mins", "first", "last", "dyn", "const", "const_g", "e_base", "nl_r",
            "g_base_r", "lk_r", "fw_r")}
        init.update(slots=spec((H, E, C), f), staged=spec((H, 1, E), jnp.bool_),
                    **{k: spec((H,), f) for k in ("c_cur", "tj", "c_sum_b",
                                                  "tj_b", "cg_sum_b")})
        xs = {k: spec((H, T), jnp.int32) for k in ("ti", "hv_id", "sig")}
        xs.update({k: spec((H, T), f) for k in ("ready_s", "nb", "u_tw", "u_oj",
                                                "u_fd")})
        xs.update({k: spec((H, T), jnp.bool_) for k in ("shared_s", "new_run",
                                                        "valid")})
        lowered = ops._greedy_scan.lower(consts, ops._as_tuple_carry(init), xs,
                                         n_ep=5, use_kernel=False)
    name = lowered.compiler_ir().operation.attributes["sym_name"].value
    assert ops.SCAN_PROGRAM in name

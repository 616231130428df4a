"""The benchmark's parts by themselves: traffic, work count, metric
arithmetic, and a cell added by files alone."""
import json
import shutil

import numpy as np
import pytest

from _bench_tiny import ROOT, on_cpu  # noqa: F401
from bench import deployment, harness, traffic, work
from bench.trace import TraceSummary


def test_fixed_mix_gives_every_seed_the_same_counts():
    p = np.array([0.5, 0.3, 0.2])
    a = traffic.fixed_mix(101, p, np.random.default_rng(1))
    b = traffic.fixed_mix(101, p, np.random.default_rng(2**33 + 5))
    assert np.array_equal(np.bincount(a), [51, 30, 20])
    assert np.array_equal(np.bincount(a), np.bincount(b))
    assert not np.array_equal(a, b)


def test_zipf_popularity_follows_the_rank_order():
    fns = ["a", "b", "c"]
    p = traffic.popularity({"kind": "zipf", "s": 1.0, "rank_order": ["c", "a", "b"]}, fns)
    assert p == pytest.approx(np.array([1 / 2, 1 / 3, 1.0]) / (11 / 6))
    assert traffic.popularity({"kind": "uniform"}, fns) == pytest.approx([1 / 3] * 3)


def test_arrivals_are_a_fixed_count_on_the_window():
    a = traffic.arrivals(250.0, 4.0, np.random.default_rng(3))
    b = traffic.arrivals(250.0, 4.0, np.random.default_rng(4))
    assert len(a) == len(b) == 1000
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 4.0


def test_deployments_replicate_the_table1_testbed():
    cfg = deployment.load(ROOT / "bench/configs/table1x64-sebs.json")
    fleet = deployment.machines(cfg)
    assert len(fleet) == 256
    assert sum(m.cores for m in fleet) == 12288
    assert fleet[0].name == "desktop_0" and fleet[-1].name == "faster_63"
    assert fleet[-1].perf_scale == pytest.approx(1.6 * 2.26)
    profs = deployment.profiles(cfg, fleet)
    rt, e = profs["graph_pagerank"]["faster_63"]
    assert rt == pytest.approx(0.1 / 2.26) and e == pytest.approx(rt * 1.33)
    small = deployment.load(ROOT / "bench/configs/table1x8-sebs.json")
    assert [m.name for m in deployment.machines(small)][:5] == [
        "desktop_0", "theta_0", "ic_0", "faster_0", "desktop_1"]


def test_scan_work_counted_by_hand():
    # 4 heuristics x 10 tasks x 3 endpoints = 120 score cells
    flops, nbytes = work.scan_work(4, 10, 3)
    assert flops == 120 * 21 and nbytes == 120 * 88
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.roofline_s(flops, nbytes, peak)
    assert bound == "bytes" and t == pytest.approx(120 * 88 / 819e9)
    t, bound = work.roofline_s(1e15, 1.0, peak)
    assert bound == "operations" and t == pytest.approx(1e15 / 197e12)


def _run_record(**kw):
    base = dict(workload="w", kind="closed_batch", seconds=10.0, setup_s=12.5,
                heuristics=4, endpoints=32)
    base.update(kw)
    return harness.Run(**base)


def _metric(name):
    return harness.load_module(ROOT, "metrics", name).read


def test_end_to_end_metric_arithmetic():
    run = _run_record(placed_in_window=81920, window_s=10.24)
    assert _metric("batch_tasks_per_s")(run) == pytest.approx(8000.0)
    assert _metric("setup_s")(run) == 12.5
    assert _metric("stream_p95_ms")(run) is None
    stream = _run_record(kind="open_poisson", seconds=20.0, placed_in_window=29000,
                         latencies_s=np.arange(1, 101) / 1000.0)
    assert _metric("stream_tasks_per_s")(stream) == pytest.approx(1450.0)
    assert _metric("stream_p95_ms")(stream) == pytest.approx(95.05)
    assert _metric("batch_tasks_per_s")(stream) is None


def test_per_layer_metric_arithmetic():
    spans = [harness.WindowSpan(flush_s=1.0, sched_s=0.9, device_s=0.6,
                                device_calls=1, tasks=8192),
             harness.WindowSpan(flush_s=0.8, sched_s=0.7, device_s=0.0,
                                device_calls=0, tasks=100)]
    tr = TraceSummary(window_s=2.0, busy_s=0.5,
                      module_s={"jit__greedy_scan(1)": 0.5, "jit_convert(2)": 0.001},
                      module_n={"jit__greedy_scan(1)": 1, "jit_convert(2)": 12},
                      ops_s={}, ops_read=0, dropped=False, gaps=[], idle_by_span=[],
                      devices=1)
    run = _run_record(traced=True, spans=spans, trace=tr, compiles_in_window=0,
                      peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert _metric("engine_ms_per_window")(run) == pytest.approx(100.0)
    assert _metric("host_prep_ms_per_window")(run) == pytest.approx(500.0)
    assert _metric("device_call_ms_per_window")(run) == pytest.approx(600.0)
    assert _metric("compiles_in_window")(run) == 0
    assert _metric("scan_device_ms_per_window")(run) == pytest.approx(500.0)
    assert _metric("device_idle_pct")(run) == pytest.approx(75.0)
    # 4 x 8192 x 32 cells at 88 B over 819 GB/s, against 0.5 s on the device
    assert _metric("scan_roofline")(run) == pytest.approx(
        100 * 4 * 8192 * 32 * 88 / 819e9 / 0.5)
    # nothing traced: the device readers find nothing, never a zero share
    bare = _run_record()
    for name in ("engine_ms_per_window", "scan_device_ms_per_window",
                 "scan_roofline", "device_idle_pct", "compiles_in_window"):
        assert _metric(name)(bare) is None


def test_a_cell_traffic_and_metric_added_by_files_alone(on_cpu, capsys):
    """A new deployment, traffic mix, limits and per-layer metric, each a
    file of its own, and entries in BENCHMARK.json: no code edited."""
    root = on_cpu
    cfg = json.loads((root / "bench/configs/table1x8-sebs.json").read_text())
    cfg["replicas"] = 1
    (root / "bench/configs/table1-sebs.json").write_text(json.dumps(cfg))
    tr = json.loads((root / "bench/traffic/batch8k-uniform.json").read_text())
    tr.update(window_tasks=32, max_batch=32)
    tr["classes"][0]["popularity"] = {"kind": "zipf", "s": 2.0,
                                      "rank_order": cfg["functions"][::-1]}
    (root / "bench/traffic/batch32-zipf.json").write_text(json.dumps(tr))
    shutil.copy(root / "bench/limits/sebs32-batch8k.json",
                root / "bench/limits/sebs4-batch32.json")
    (root / "bench/metrics/windows_per_s.py").write_text(
        "def read(run):\n"
        "    return len(run.spans) / run.window_s if run.spans else None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "table1-sebs", "source": "https://arxiv.org/abs/2406.17710",
                            "file": "bench/configs/table1-sebs.json", "reduced": [],
                            "why": "the paper's own four machines"})
    spec["workloads"].append({"name": "sebs4-batch32", "config": "table1-sebs",
                              "traffic": "batch32-zipf", "chips": 1, "why": "small"})
    next(m for m in spec["end_to_end"]
         if m["name"] == "batch_tasks_per_s")["workloads"].append("sebs4-batch32")
    spec["per_layer"].append({"name": "windows_per_s.batch", "unit": "1/s",
                              "better": "higher", "source": "program_span",
                              "layer": "engine", "moves": "batch_tasks_per_s",
                              "workloads": ["sebs4-batch32"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for trace, names in ((0, {"batch_tasks_per_s", "setup_s"}),
                         (1, {"windows_per_s.batch"})):
        rc = harness.main(["--workload", "sebs4-batch32", "--seed", "5",
                           "--seconds", "0.5", "--trace", str(trace)], root=root)
        out = capsys.readouterr()
        assert rc == 0, out.err[-2000:]
        res = json.loads(out.out.strip().splitlines()[-1])
        assert res["correct"] is True
        assert set(res["metrics"]) == names

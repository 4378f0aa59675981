"""The benchmark harness on the CPU: cells resolve from their names,
traffic is fixed by the seed, the readers compute what they say, and the
measurement path refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from harness import readers, spec, traffic  # noqa: E402
from harness.load import QueryRecord, Window  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = spec.resolve(cell)
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert c.config_name == w["config"] and c.traffic_name == w["traffic"]
    assert spec.traffic_path(w["traffic"]).is_file()
    assert c.arch.dims(c.config)["layers"] >= 1
    e2e = {m.name for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m.moves in e2e, f"{m.name} moves a metric {cell} lacks"


def test_every_metric_has_a_reader_file():
    for name, path in spec.metric_files(SPEC).items():
        assert path.is_file(), name
        assert callable(spec.load_module(path, "m_" + name.replace(".", "_")
                                         ).read)


def test_benchmark_json_keeps_to_its_shape():
    import re
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [c["name"] for c in SPEC["configs"]] + CELLS + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert re.match(NAME, n), n
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_a_new_mix_resolves_without_an_edit(tmp_path):
    mix = dict(json.loads(spec.traffic_path("rag_poisson").read_text()),
               rate_qps=0.5)
    (tmp_path / "rag_slow.json").write_text(json.dumps(mix))
    s = json.loads(json.dumps(SPEC))
    s["workloads"].append({"name": "chatglm3-6b.rag_slow",
                           "config": "chatglm3-6b", "traffic": "rag_slow",
                           "chips": 1, "why": "a test mix"})
    c = spec.resolve("chatglm3-6b.rag_slow", s, traffic_dir=tmp_path)
    assert c.traffic["rate_qps"] == 0.5
    assert c.config_name == "chatglm3-6b"
    assert {m.name for m in c.end_to_end} == {"setup_s"}


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.resolve("no-such.cell")


MIX = {"app": "advanced_rag", "loop": "open", "rate_qps": 1.2,
       "ramp_s": 15, "tail_s": 60, "docs_per_query": 10,
       "question_words": {"mean": 17.61, "sigma": 0.4},
       "doc_words": {"mean": 46.3, "sigma": 0.5}, "clients": 16}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_open_schedule_is_fixed_by_the_seed(seed):
    a, sa, wa = traffic.open_schedule(MIX, seed, 51)
    b, sb, wb = traffic.open_schedule(MIX, seed, 51)
    assert a == b and sa == sb and wa == wb == (15.0, 66.0)
    due = [t for t in a if wa[0] <= t < wa[1]]
    assert len(due) == round(1.2 * 51) and len(sa) == len(a)
    assert all(x <= y for x, y in zip(a, a[1:]))


def _window_sizes(sizes, times, window):
    return [s for s, t in zip(sizes, times) if window[0] <= t < window[1]]


def test_seeds_share_sizes_and_gaps_in_another_order():
    a, sa, wa = traffic.open_schedule(MIX, 1, 51)
    b, sb, _ = traffic.open_schedule(MIX, 2, 51)
    assert a != b and len(a) == len(b)
    ga, gb = np.diff(a[15:76]), np.diff(b[15:76])
    assert not np.allclose(ga, gb)
    wa_, wb_ = _window_sizes(sa, a, wa), _window_sizes(sb, b, wa)
    assert wa_ != wb_
    # the same question lengths and document lengths in another order
    assert sorted(q for q, _ in wa_) == sorted(q for q, _ in wb_)
    assert sorted(d for _, ds in wa_ for d in ds) == \
        sorted(d for _, ds in wb_ for d in ds)
    qa = traffic.make_query(1, 3, sa[3])
    qb = traffic.make_query(2, 3, sb[3])
    assert qa != qb
    for q, (nq, nd) in ((qa, sa[3]), (qb, sb[3])):
        assert len(q["docs"]) == 10
        assert [len(d["text"].split()) for d in q["docs"]] == list(nd)
        assert len(q["question"].split()) == nq
    assert traffic.make_query(2**33 + 5, 9, sa[9]) == \
        traffic.make_query(2**33 + 5, 9, sa[9])


def test_sizes_follow_the_mix_distribution():
    _, sizes, _ = traffic.open_schedule(MIX, 3, 51)
    window = sizes[18:79]
    q = [n for n, _ in window]
    d = [x for _, ds in window for x in ds]
    assert np.mean(q) == pytest.approx(17.61, rel=0.05)
    assert np.mean(d) == pytest.approx(46.3, rel=0.05)
    assert max(d) > 2.5 * np.median(d)        # a heavy tail, not one size


def test_closed_loop_queries_are_fixed_by_client_and_turn():
    seen = {traffic.closed_queries(MIX, c, k) for c in range(16)
            for k in range(4)}
    assert len(seen) == 64
    assert traffic.closed_queries(MIX, 3, 2) == 3 + 16 * 2
    group = [traffic.closed_sizes(MIX, 5, i) for i in range(64)]
    assert group == [traffic.closed_sizes(MIX, 5, i) for i in range(64)]
    other = [traffic.closed_sizes(MIX, 6, i) for i in range(64)]
    assert group != other
    assert sorted(q for q, _ in group) == sorted(q for q, _ in other)


class _Ctx:
    def __init__(self, t_done, err=None):
        from threading import Event
        self.t_done = t_done
        self.error = err
        self.done = Event()
        self.done.set()


def _run(lat, window=(0.0, 10.0), stats=None):
    qs = []
    for i, x in enumerate(lat):
        r = QueryRecord(i, t_sched=float(i), t_submit=float(i),
                        ctx=_Ctx(float(i) + x), in_window=True)
        qs.append(r)
    w = Window(t_open=window[0], t_close=window[1], queries=qs)
    if stats:
        w.open_stats, w.close_stats = stats
    return SimpleNamespace(window=w, window_queries=lambda: qs)


def test_percentiles_are_over_all_requests():
    lat = [1.0] * 50 + [10.0] * 10
    run = _run(lat)
    p90 = readers.percentile(readers.latencies(run), 90)
    # over all 60 requests; medians of six chunks of ten would read 1 or 10
    chunks = [np.median(lat[i:i + 10]) for i in range(0, 60, 10)]
    assert p90 == pytest.approx(float(np.percentile(lat, 90)))
    assert p90 != pytest.approx(float(np.percentile(chunks, 90)))
    assert readers.percentile(readers.latencies(run), 50) == 1.0


def test_failed_queries_have_no_latency():
    run = _run([1.0, 2.0, 3.0])
    run.window.queries[1].ctx.error = RuntimeError("x")
    assert readers.latencies(run) == [1.0, 3.0]


def test_gen_tokens_per_s_is_a_counter_delta():
    run = _run([1.0], window=(100.0, 150.0),
               stats=({"decode_tokens": 1000, "decode_iters": 100},
                      {"decode_tokens": 6000, "decode_iters": 450}))
    assert readers.gen_tokens_per_s(run) == pytest.approx(5000 / 50)
    assert readers.decode_batch(run) == pytest.approx(5000 / 350)


def _bench(cmd, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_measurement_exits_with_an_error_without_a_tpu():
    p = _bench([sys.executable, "bench/run.py", "--workload", CELLS[0],
                "--seed", "3", "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench([sys.executable, "bench/run.py", "--workload", CELLS[0],
                "--seed", "3", "--seconds", "1", "--trace", "0"], tmp_path,
               {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""

"""The harness end to end on the CPU at a test-only size: it picks up a
new configuration, traffic mix and per-layer metric from new files alone,
refuses to run without a TPU, and reports ``correct`` false when the
served path is broken underneath it."""

import json
import shutil
from pathlib import Path

import pytest

import run

BENCH = Path(__file__).resolve().parents[1]
TINY = Path(__file__).resolve().parent / "tiny"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose benchmark is the tiny configuration, its three
    mixes and one extra metric, each in a file of its own."""
    root = tmp_path_factory.mktemp("checkout")
    for sub in ("layer_metrics", "kinds"):
        shutil.copytree(BENCH / sub, root / "bench" / sub)
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(TINY / sub, root / "bench" / sub, dirs_exist_ok=True)
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    rename = {"hek293.oms.offline": "tiny.backlog",
              "iprg2012.oms.poisson.best_order": "tiny.poisson",
              "iprg2012.oms.random": "tiny.random"}
    bench["configs"] = [{"name": "tiny", "source": "test-only",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": n, "config": "tiny", "traffic": n, "chips": 1, "why": "t"}
        for n in ("tiny.backlog", "tiny.poisson", "tiny.random")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    bench["per_layer"].append(
        {"name": "batches_per_s", "unit": "1/s", "better": "higher",
         "source": "program_counter", "layer": "queue and scheduler",
         "moves": "spectra_per_s", "workloads": ["tiny.backlog"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def result(capsys, root, workload, trace=0, seed=2**31 + 11, **kw):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1.5", "--trace", str(trace)],
                  root=root, require_tpu=False, **kw)
    out = capsys.readouterr()
    assert rc == 0, out.err
    return json.loads(out.out.strip().splitlines()[-1]), out


def test_open_loop_cell_is_correct_and_reports_its_metrics(capsys, root):
    res, out = result(capsys, root, "tiny.poisson")
    assert res["correct"] and res["failed"] == 0
    # 40 requests a second over the 1.5 s window; the pre-roll's are not
    # counted, and its time is set-up
    assert res["attempted"] == 60
    assert "set-up pre-roll: 0.5" in out.out
    assert set(res["metrics"]) == {"p50_ms", "p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    # the warm-up reached every program the window runs
    assert "0 programs compiled or loaded inside it" in out.out
    assert out.err.strip().splitlines()[-1].startswith("check ")


def test_open_loop_cell_reports_its_layers_traced(capsys, root):
    res, _ = result(capsys, root, "tiny.poisson", trace=1)
    assert res["correct"]
    m = res["metrics"]
    assert m["queue_wait_p95_ms.open"]["value"] >= 0
    assert 0 < m["batch_fill.open"]["value"] <= 1
    assert "oms_overscan.open" in m
    # device metrics read nothing on the CPU and are left out, never 0
    assert "search_roofline.open" not in m and "device_idle.open" not in m
    assert not any(k.endswith(".offline") for k in m)


def test_random_order_cell_is_correct_and_reports_its_metrics(capsys,
                                                              root):
    res, out = result(capsys, root, "tiny.random")
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"spectra_per_s.random", "setup_s"}
    assert res["metrics"]["spectra_per_s.random"]["value"] > 0
    assert "0 programs compiled or loaded inside it" in out.out
    assert "window batches by tile budget" in out.out
    res, _ = result(capsys, root, "tiny.random", trace=1)
    assert res["correct"]
    assert {"oms_overscan.random", "executor_ms.random"} <= set(
        res["metrics"])
    assert not any(k.endswith((".offline", ".open")) for k in res["metrics"])


def test_new_config_mix_and_metric_come_from_files(capsys, root):
    res, _ = result(capsys, root, "tiny.backlog", trace=1)
    assert res["correct"]
    # the extra metric's reader was found by its name alone; the device
    # metrics read nothing on the CPU and are left out, never 0
    assert "batches_per_s" in res["metrics"]
    assert "executor_ms.offline" in res["metrics"]
    assert "search_roofline.offline" not in res["metrics"]
    assert "device_idle.offline" not in res["metrics"]


@pytest.mark.parametrize("workload",
                         ["tiny.backlog", "tiny.poisson", "tiny.random"])
def test_control_fails_the_comparison(capsys, root, workload):
    """The control's answers, put where the served ones were, go through
    the run's own checks and come out as not correct."""
    res, out = result(capsys, root, workload, control=True)
    assert not res["correct"]
    top = res["checks"]["topk_mismatch"]
    assert top["value"] > top["limit"]
    program = [x for x in out.out.splitlines() if x.startswith("program:")]
    assert program == [f"program: topk_mismatch 0 fdr_mismatch 0 over "
                       f"{res['checks']['checked_requests']['value']} "
                       f"requests"]


def test_no_tpu_no_result(capsys, root):
    rc = run.main(["--workload", "tiny.poisson", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], root=root)
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "needs a TPU" in out.err


@pytest.mark.parametrize("workload",
                         ["tiny.backlog", "tiny.poisson", "tiny.random"])
def test_an_altered_answer_is_caught(capsys, root, monkeypatch, workload):
    """The first answer of every batch points one row off, as the
    executor hands it back."""
    from repro.serve import db_search
    real = db_search.SearchExecutor.finalize

    def altered(self, handle):
        done = real(self, handle)
        if done:
            res = done[0].result
            res.indices = res.indices.copy()
            res.indices[0] += 1
        return done

    monkeypatch.setattr(db_search.SearchExecutor, "finalize", altered)
    res, _ = result(capsys, root, workload)
    assert not res["correct"]
    assert res["checks"]["topk_mismatch"]["value"] > 0


def test_half_the_batch_left_out_is_caught(capsys, root, monkeypatch):
    from repro.serve import db_search
    real = db_search.SearchExecutor.finalize

    def half(self, handle):
        done = real(self, handle)
        return done[: len(done) // 2]

    monkeypatch.setattr(db_search.SearchExecutor, "finalize", half)
    res, _ = result(capsys, root, "tiny.poisson")
    assert not res["correct"]
    assert res["failed"] > 0
    assert res["checks"]["missing"]["value"] == res["failed"]


def test_knee_sweep_reports_each_rate(capsys, root):
    import sweep
    rc = sweep.main(["--workload", "tiny.poisson", "--seed", str(2**31 + 3),
                     "--seconds", "1", "--rates", "20,40"],
                    root=root, require_tpu=False)
    out = capsys.readouterr()
    assert rc == 0, out.err
    rows = [json.loads(x) for x in out.out.splitlines() if x.startswith("{")]
    assert [r["rate_per_s"] for r in rows] == [20, 40]
    for r in rows:
        assert r["answered"] == r["due"] == r["rate_per_s"]
        assert r["programs_in_window"] == 0
        assert 0 < r["batch_fill"] <= 1 and r["p95_ms"] >= r["p50_ms"]
        assert sum(r["tiles"].values()) == r["batches"]

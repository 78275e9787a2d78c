"""A deployment kind is a file of its own. A checkout that gains a toy kind
(``toy_kind/kinds/echo.py``: every spectrum answered with its own
precursor), its configuration, traffic and reader as new files runs end
to end through ``run.main``, with no edit to the harness; a configuration
with no kind, or with a kind that has no file, stops the run before any
result."""

import json
import shutil
from pathlib import Path

import pytest

import run

TOY = Path(__file__).resolve().parent / "toy_kind"


def metric(name, unit, better, cells, source="host_clock", **kw):
    m = {"name": name, "unit": unit, "better": better, "source": source}
    return dict(m, **kw, workloads=cells) if cells else dict(m, **kw)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose benchmark is the toy kind alone, beside two
    configurations that name no kind and an unknown one."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(TOY, root / "bench")
    cfg = json.loads((TOY / "configs" / "echo.json").read_text())
    del cfg["kind"]
    (root / "bench" / "configs" / "nokind.json").write_text(json.dumps(cfg))
    cfg["kind"] = "no_such_kind"
    (root / "bench" / "configs" / "unknown.json").write_text(json.dumps(cfg))
    configs = ("echo", "nokind", "unknown")
    cells = {"echo.backlog": ("echo", "echo.backlog"),
             "echo.poisson": ("echo", "echo.poisson"),
             "nokind.backlog": ("nokind", "echo.backlog"),
             "unknown.backlog": ("unknown", "echo.backlog")}
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 10,
        "configs": [{"name": n, "source": "test-only",
                     "file": f"bench/configs/{n}.json", "reduced": [],
                     "why": "test"} for n in configs],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "test"} for n, (c, t) in cells.items()],
        "end_to_end": [
            metric("spectra_per_s", "spectra/s", "higher", ["echo.backlog"],
                   bound=0.25),
            metric("answered_per_batch", "spectra", "higher",
                   ["echo.backlog"], bound=0.25),
            metric("p95_ms", "ms", "lower", ["echo.poisson"], bound=0.25),
            metric("setup_s", "s", "lower", None, bound=0.25)],
        "per_layer": [metric("batch_fill.echo", "fraction", "higher",
                             ["echo.backlog", "echo.poisson"],
                             source="program_counter", layer="echo",
                             moves="spectra_per_s")],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_cell(root, workload, trace=0, **kw):
    return run.main(["--workload", workload, "--seed", str(2**31 + 23),
                     "--seconds", "1.5", "--trace", str(trace)],
                    root=root, require_tpu=False, **kw)


@pytest.mark.parametrize("workload,trace,metrics", [
    ("echo.backlog", 0, {"spectra_per_s", "answered_per_batch", "setup_s"}),
    ("echo.poisson", 0, {"p95_ms", "setup_s"}),
    ("echo.poisson", 1, {"batch_fill.echo"}),
])
def test_a_new_kind_runs_from_new_files(capsys, root, workload, trace,
                                        metrics):
    rc = run_cell(root, workload, trace)
    out = capsys.readouterr()
    assert rc == 0, out.err
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == metrics
    assert all(m["value"] > 0 for m in res["metrics"].values())
    # the generic checks frame the kind's own, in this order
    assert list(res["checks"]) == ["missing", "precursor_mismatch",
                                   "checked_requests"]
    assert res["checks"]["checked_requests"]["value"] >= 16
    assert "0 programs compiled or loaded inside it" in out.out
    assert "echo: " in out.out and "window batches" in out.out


def test_the_new_kinds_control_fails(capsys, root):
    assert run_cell(root, "echo.backlog", control=True) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not res["correct"]
    bad = res["checks"]["precursor_mismatch"]
    assert bad["value"] > bad["limit"]


def test_the_knee_sweep_runs_a_new_kind(capsys, root):
    import sweep
    rc = sweep.main(["--workload", "echo.poisson", "--seed", str(2**31 + 5),
                     "--seconds", "1", "--rates", "20,40"],
                    root=root, require_tpu=False)
    out = capsys.readouterr()
    assert rc == 0, out.err
    rows = [json.loads(x) for x in out.out.splitlines() if x.startswith("{")]
    assert [r["rate_per_s"] for r in rows] == [20, 40]
    for r in rows:
        assert r["answered"] == r["due"] == r["rate_per_s"]
        assert r["programs_in_window"] == 0 and r["batches"] > 0
        # the echo kind adds no fields of its own
        assert "tiles" not in r and "oms_overscan" not in r


@pytest.mark.parametrize("workload,says", [
    ("nokind.backlog", "bench/configs/nokind.json names no deployment kind"),
    ("unknown.backlog", "there is no file bench/kinds/no_such_kind.py"),
])
def test_a_config_without_a_known_kind_stops_the_run(capsys, root, workload,
                                                     says):
    with pytest.raises(SystemExit) as stop:
        run_cell(root, workload)
    # a message, which Python prints to stderr and exits 1 on
    assert isinstance(stop.value.code, str) and says in stop.value.code
    assert capsys.readouterr().out == ""

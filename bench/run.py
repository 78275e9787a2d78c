"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``: the
library, its encoding, the search window and the guarantees) and a
traffic mix (``bench/traffic/<mix>.json``), both listed in
``BENCHMARK.json``. The run makes the library and the queries on the
device from the seed, brings up the server, warms every program the
traffic reaches, then measures for ``--seconds``. With ``--trace 1`` the
window is profiled and the cell's per-layer metrics are read by the
readers in ``bench/layer_metrics/<metric>.py``; otherwise the end-to-end
metrics are reported. After the window the served answers of whole
dispatched batches, drawn from the seed, are compared with the plain
reference (``bench/reference.py``); every number compared is printed with
its limit, last on stderr and last in the result line.

It needs a TPU: on any other platform, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import numpy as np  # noqa: E402
import readers  # noqa: E402

QUERY_TAG, WARM_TAG = 10, 11  # generator streams of the seed
_COMPILES: list[int] = []


def log(msg: str) -> None:
    print(msg, flush=True)


def load_cell(root: Path, name: str):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / config["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return (cell, cfg, mix, mine(bench["end_to_end"]),
            mine(bench["per_layer"]))


def load_reader(root: Path, metric: str):
    path = root / "bench" / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def end_to_end(name: str, rec) -> float:
    # a metric held to one cell's bound (``spectra_per_s.random``) is
    # computed as the metric its name starts with
    name = name.partition(".")[0]
    if name == "setup_s":
        return rec.setup_s
    if name in ("p50_ms", "p95_ms"):
        # an answer that never came counts as infinitely late
        lat = [(rec.served[r].t_done - rec.due[r]) * 1e3
               if r in rec.served else math.inf for r in rec.attempted]
        return readers.percentile(lat, 50 if name == "p50_ms" else 95)
    if name == "spectra_per_s":
        # every dispatched spectrum, over the time until the last came back
        done = sum(1 for r in rec.attempted if r in rec.served)
        return done / (rec.t_close - rec.t0)
    raise ValueError(f"no end-to-end metric {name!r} in this harness")


def sample_batches(rec, rng, want: int) -> list[list[int]]:
    """Whole batches dispatched in the window, drawn from the seed until
    they hold ``want`` requests; the largest batch always among them."""
    whole = [b.rids for b in rec.window_batches
             if all(r in rec.served for r in b.rids)]
    if not whole:
        return []
    first = max(range(len(whole)), key=lambda i: len(whole[i]))
    order = [first] + [int(i) for i in rng.permutation(len(whole))
                       if i != first]
    out, n = [], 0
    for i in order:
        if n >= want:
            break
        out.append(whole[i])
        n += len(whole[i])
    return out


def load_peaks(kind: str) -> dict:
    """The chip's published peaks from ``bench/peaks.json``; a device kind
    that is not in the table is an error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


def compile_cache(root: Path) -> list[int]:
    """Keep every compiled program in the checkout's ``.jax_cache`` (a
    fixed path, so the next run finds it) and count the programs compiled
    or loaded from now on, in the returned one-element list."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _COMPILES:  # one listener per process, however many runs
        _COMPILES.append(0)

        def count(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES[0] += 1

        jax.monitoring.register_event_duration_secs_listener(count)
    return _COMPILES


def bring_up(cfg: dict, mix: dict, seed: int, pools, compiles):
    """A cell's set-up: the library from ``seed`` (its precursors from a
    random order's ``set_seed``), a query pool of ``count`` spectra for
    each (pool seed, count) of ``pools``, the bank and the server, then the
    warm-up of every program the mix reaches over those pools' precursors.
    Returns (spec, library, pools, deployment)."""
    import jax

    import adapter
    import gen
    import loadgen

    spec = gen.Spec.from_config(cfg)
    serving = cfg["serving"]
    max_batch = serving["max_batch_size"]
    device = jax.devices()[0]

    def phase(label, t0):
        log(f"set-up {label}: {time.monotonic() - t0:.3f} s")

    t = time.monotonic()
    # a random order's set fixes every precursor: the same work each seed
    random_order = mix.get("precursor_order") == "random"
    lib, table = gen.build_library(
        spec, seed, precursor_seed=mix["set_seed"] if random_order else None)
    phase(f"library ({spec.library_rows} targets + as many decoys, "
          f"{2 * spec.library_rows * spec.dim // 8} packed bytes)", t)
    t = time.monotonic()
    made = [gen.query_pool(spec, s, QUERY_TAG, count, table, max_batch,
                           mix.get("precursor_order", "stratified"),
                           mix.get("set_batches", 0), mix.get("set_seed", 0))
            for s, count in pools]
    warm = gen.query_pool(spec, seed, WARM_TAG,
                          loadgen.warm_pool_size(mix, max_batch), table,
                          max_batch)
    del table
    phase(f"query pool ({sum(map(len, made))} spectra, {len(warm)} for "
          f"warm-up)", t)
    t = time.monotonic()
    dep = adapter.Deployment(lib, serving, cfg["windows"][mix["window"]])
    phase("bank and server", t)
    log(f"device 0 peak memory after the bank build: "
        f"{(device.memory_stats() or {}).get('peak_bytes_in_use', 0)} bytes")
    t = time.monotonic()
    batches = loadgen.warm_batches(
        mix, max_batch, dep.buckets, warm,
        min(float(p.precursor.min()) for p in made),
        max(float(p.precursor.max()) for p in made))
    sweeps = loadgen.warm_up(dep, batches, lambda: compiles[0])
    phase(f"warm-up ({sweeps} sweeps of {len(batches[0])} batches, "
          f"{compiles[0]} programs so far)", t)
    return spec, lib, made, dep


def measure(dep, mix: dict, pool, seconds: float, seed: int, compiles,
            cfg: dict):
    """One measured window. Returns it and its record: the requests due,
    those answered so far (the answers still in flight are collected by
    ``loadgen.drain`` into the same dict), the batches dispatched inside
    it, the planner's counters as it opened and closed, and the programs
    compiled or loaded inside it."""
    import loadgen

    opened = {}

    def on_open():
        opened.update(counters=dep.oms_counters(), compiles=compiles[0],
                      batches=len(dep.batches))

    w = loadgen.run(dep, mix, pool, seconds, seed,
                    cfg["serving"]["max_batch_size"], dep.annotate, on_open)
    rec = types.SimpleNamespace(
        t0=w.t0, t_end=w.t_end, due=w.due, attempted=w.attempted,
        served=w.served,
        window_batches=[b for b in dep.batches[opened["batches"]:]
                        if b.t_dispatch < w.t_end],
        counters=(opened["counters"], dep.oms_counters()),
        programs=compiles[0] - opened["compiles"], config=cfg, mix=mix)
    return w, rec


def log_batches(rec) -> None:
    """Log the window's batches by OMS tile budget, and each batch's
    requests and budget in dispatch order."""
    import program_spans

    tiles = program_spans.batch_tiles(rec)
    if tiles is None:
        return
    log(f"window batches by tile budget: "
        f"{dict(sorted(collections.Counter(t for _, t in tiles).items()))}")
    log("window batches (requests x tiles): "
        + " ".join(f"{n}x{t}" for n, t in tiles))


def main(argv=None, *, root: Path | None = None,
         require_tpu: bool = True, control: bool = False) -> int:
    """One run of one cell. ``root`` (the checkout holding BENCHMARK.json)
    and ``require_tpu`` are for the tests; ``control`` logs the program's
    comparison, then puts the control's answers in place of the served
    ones, so that the result line judges the control
    (``bench/calibrate.py``)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(root or HERE.parent)
    cell, cfg, mix, e2e, per_layer = load_cell(root, args.workload)

    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, but JAX found "
              f"{devices[0].platform!r} devices", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run.py: the cell needs {cell['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    compiles = compile_cache(root)
    peaks = None
    if require_tpu:
        try:
            peaks = load_peaks(devices[0].device_kind)
        except KeyError as e:
            print(f"run.py: {e.args[0]}", file=sys.stderr)
            return 2

    import adapter
    import loadgen
    import reference
    import tracereduce

    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; cell {args.workload}, seed {args.seed}")
    spec, lib, (pool,), dep = bring_up(
        cfg, mix, args.seed,
        [(args.seed, loadgen.pool_size(mix, args.seconds))], compiles)
    win = cfg["windows"][mix["window"]]
    serving = cfg["serving"]
    setup_s = time.monotonic() - T_START

    trace_dir = tempfile.mkdtemp() if args.trace else None
    dep.annotate = adapter.Annotate(bool(args.trace))
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    w, rec = measure(dep, mix, pool, args.seconds, args.seed, compiles, cfg)
    if trace_dir:
        jax.profiler.stop_trace()
    if w.t0 > w.t_start:
        # the pre-roll fills the queue before the window: set-up
        setup_s += w.t0 - w.t_start
        log(f"set-up pre-roll: {w.t0 - w.t_start:.3f} s")
    log(f"set-up total: {setup_s:.3f} s")
    loadgen.drain(dep, w)
    stats = devices[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"device 0 peak memory: {peak} bytes")
    failed = sum(1 for r in w.attempted if r not in w.served)
    log(f"window: {len(w.attempted)} requests due, "
        f"{len(w.attempted) - failed} answered, "
        f"{len(rec.window_batches)} batches dispatched, "
        f"{rec.programs} programs compiled or loaded inside it"
        + (f", closed {w.t_close - w.t0:.3f} s after it opened"
           if w.t_close else ""))
    log_batches(rec)
    dep.close()
    del dep
    gc.collect()

    rec.__dict__.update(
        setup_s=setup_s, t_close=w.t_close, window_s=args.seconds,
        precursor_of={r: float(pool.precursor[i])
                      for r, i in w.pool_row.items()},
        window=win, peaks=peaks, sorted_prec=np.sort(lib.precursor),
        trace=None, notes=[])
    breakdown = None
    if trace_dir:
        loaded = tracereduce.load(trace_dir)
        log(f"trace: {len(loaded.device_ops)} device planes, "
            f"{sum(map(len, loaded.device_ops.values()))} device ops, "
            f"{len(loaded.host_spans)} harness spans")
        rec.trace = tracereduce.reduce(loaded)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if rec.trace:
            breakdown = {"device_ops": rec.trace["device_ops"],
                         "idle_gaps": rec.trace["idle_gaps"]}

    metrics = {}
    for m in (per_layer if args.trace else e2e):
        value = (load_reader(root, m["name"])(rec) if args.trace
                 else end_to_end(m["name"], rec))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    for note in rec.notes:
        log(note)

    t = time.monotonic()
    batches = sample_batches(rec, np.random.default_rng(args.seed),
                             mix["check_requests"])
    got = {r: vars(s) for r, s in w.served.items()}

    def query(rid):
        i = w.pool_row[rid]
        return pool.levels[i].astype(np.int64), pool.precursor[i]

    def answers(half: bool) -> dict:
        ref = reference.Reference(lib, tol=win["tol"],
                                  open_tol=win["open_tol"], k=serving["k"],
                                  dim=spec.dim, half=half)
        return reference.answer_batches(ref, batches, query, serving["fdr"])

    want = answers(half=False)
    found = reference.compare(want, got)
    log(f"reference: {found['checked_requests']} requests in "
        f"{len(batches)} batches and {2 * len(lib.checked_rows)} bank rows "
        f"in {time.monotonic() - t:.3f} s")
    if control:
        log(f"program: topk_mismatch {found['topk_mismatch']} fdr_mismatch "
            f"{found['fdr_mismatch']} over {found['checked_requests']} "
            f"requests")
        # the control's answers go where the served ones were
        got.update(answers(half=True))
        found = reference.compare(want, got)
        log(f"control (half the dimensions): topk_mismatch "
            f"{found['topk_mismatch']} fdr_mismatch {found['fdr_mismatch']} "
            f"over {found['checked_requests']} requests")
    checks = {
        "missing": {"value": failed, "limit": 0},
        "topk_mismatch": {"value": found["topk_mismatch"], "limit": 0},
        "fdr_mismatch": {"value": found["fdr_mismatch"], "limit": 0},
        "bank_rows_mismatch": {"value": reference.check_bank(lib),
                               "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    # too few whole batches to check is not a pass
    checks["checked_requests"] = {"value": found["checked_requests"],
                                  "limit": mix["check_requests"]}
    correct &= found["checked_requests"] >= mix["check_requests"]

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if args.trace:
        device["busy_s"] = rec.trace["busy_s"] if rec.trace else 0.0
        device["window_s"] = (rec.trace["window_s"] if rec.trace
                              else args.seconds)
    out = {"correct": bool(correct), "attempted": len(w.attempted),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        bound = "at least" if name == "checked_requests" else "limit"
        print(f"check {name}: {c['value']} ({bound} {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

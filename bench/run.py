"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``: the
deployment's sizes, settings and guarantees, and its ``kind``) and a
traffic mix (``bench/traffic/<mix>.json``), both listed in
``BENCHMARK.json``. The configuration's kind is a file of its own,
``bench/kinds/<kind>.py`` (see :func:`load_kind`): it makes the
deployment's data and inputs from the seed, brings it up and warms every
program the traffic reaches. This file then measures for ``--seconds``.
With ``--trace 1`` the window is profiled and the cell's per-layer
metrics are read by the readers in ``bench/layer_metrics/<metric>.py``;
otherwise the end-to-end metrics are reported. After the window the
served answers of whole dispatched batches, drawn from the seed, are
compared by the kind with its plain reference; every number compared is
printed with its limit, last on stderr and last in the result line.

It needs a TPU: on any other platform, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
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

_COMPILES: list[int] = []


def log(msg: str) -> None:
    print(msg, flush=True)


def load_cell(root: Path, name: str):
    """The cell, its configuration, traffic mix, end-to-end and per-layer
    metrics, and its configuration's kind module."""
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
            mine(bench["per_layer"]), load_kind(root, config["file"], cfg))


def load_kind(root: Path, config_file: str, cfg: dict):
    """The module ``bench/kinds/<kind>.py`` that the configuration's
    ``kind`` names: everything of a run that belongs to one kind of
    deployment. It has these functions:

    * ``bring_up(cfg, mix, seed, pools, compiles, log)``: the data from
      ``seed``, one input pool for each (pool seed, count) of ``pools``,
      the deployment and its warm-up; returns (deployment, pools). A pool
      has ``levels`` and ``precursor`` rows and a length; the deployment
      serves ``loadgen``: ``submit(levels, precursor)``, ``step``,
      ``cancel``, ``pending``, ``queued``, ``buckets``, ``batches``,
      ``annotate``, ``close``.
    * ``counters(dep)``: the program's counters, read as the window opens
      and closes (``rec.counters``).
    * ``describe(rec, dep, pool, w, log)``: the record's fields that only
      this kind's readers use, and the kind's log of the window.
    * ``checks(rec, dep, pool, w, batches, control, log)``: the served
      answers of ``batches`` against the kind's reference (with
      ``control``, the control's answers in their place); returns
      ({name: {"value", "limit"}}, requests compared).
    * optionally ``end_to_end(name, rec)``, for a metric of this kind
      alone, and ``sweep_fields(rec)``, what ``bench/sweep.py`` reports of
      a window beside its generic fields.

    No kind, or no such file, stops the run before it starts."""
    kind = cfg.get("kind")
    if kind is None:
        raise SystemExit(f"run.py: {config_file} names no deployment kind "
                         f"(its \"kind\" picks bench/kinds/<kind>.py)")
    path = root / "bench" / "kinds" / f"{kind}.py"
    if not (isinstance(kind, str) and kind.isidentifier()
            and path.is_file()):
        raise SystemExit(f"run.py: {config_file} names the kind {kind!r}, "
                         f"but there is no file bench/kinds/{kind}.py")
    spec = importlib.util.spec_from_file_location(f"kind_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # a kind's dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: Path, metric: str):
    path = root / "bench" / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def end_to_end(name: str, rec, kind) -> float:
    # a metric held to one cell's bound (``spectra_per_s.random``) is
    # computed as the metric its name starts with
    base = name.partition(".")[0]
    if base == "setup_s":
        return rec.setup_s
    if base in ("p50_ms", "p95_ms"):
        # an answer that never came counts as infinitely late
        lat = [(rec.served[r].t_done - rec.due[r]) * 1e3
               if r in rec.served else math.inf for r in rec.attempted]
        return readers.percentile(lat, 50 if base == "p50_ms" else 95)
    if base == "spectra_per_s":
        # every dispatched spectrum, over the time until the last came back
        done = sum(1 for r in rec.attempted if r in rec.served)
        return done / (rec.t_close - rec.t0)
    if hasattr(kind, "end_to_end"):
        return kind.end_to_end(name, rec)
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


def measure(kind, dep, mix: dict, pool, seconds: float, seed: int,
            compiles, cfg: dict):
    """One measured window. Returns it and its record: the requests due,
    those answered so far (the answers still in flight are collected by
    ``loadgen.drain`` into the same dict), the batches dispatched inside
    it, the kind's counters as it opened and closed, and the programs
    compiled or loaded inside it."""
    import loadgen

    opened = {}

    def on_open():
        opened.update(counters=kind.counters(dep), compiles=compiles[0],
                      batches=len(dep.batches))

    w = loadgen.run(dep, mix, pool, seconds, seed,
                    cfg["serving"]["max_batch_size"], dep.annotate, on_open)
    rec = types.SimpleNamespace(
        t0=w.t0, t_end=w.t_end, due=w.due, attempted=w.attempted,
        served=w.served,
        window_batches=[b for b in dep.batches[opened["batches"]:]
                        if b.t_dispatch < w.t_end],
        counters=(opened["counters"], kind.counters(dep)),
        programs=compiles[0] - opened["compiles"], config=cfg, mix=mix)
    return w, rec


def main(argv=None, *, root: Path | None = None,
         require_tpu: bool = True, control: bool = False) -> int:
    """One run of one cell. ``root`` (the checkout holding BENCHMARK.json)
    and ``require_tpu`` are for the tests; ``control`` has the kind log
    the program's comparison, then put the control's answers in place of
    the served ones, so that the result line judges the control
    (``bench/calibrate.py``)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(root or HERE.parent)
    cell, cfg, mix, e2e, per_layer, kind = load_cell(root, args.workload)

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

    import loadgen
    import tracereduce

    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; cell {args.workload}, seed {args.seed}")
    dep, (pool,) = kind.bring_up(
        cfg, mix, args.seed,
        [(args.seed, loadgen.pool_size(mix, args.seconds))], compiles, log)
    setup_s = time.monotonic() - T_START

    trace_dir = tempfile.mkdtemp() if args.trace else None
    dep.annotate = tracereduce.Annotate(bool(args.trace))
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    w, rec = measure(kind, dep, mix, pool, args.seconds, args.seed, compiles,
                     cfg)
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
    kind.describe(rec, dep, pool, w, log)
    dep.close()
    gc.collect()

    rec.__dict__.update(
        setup_s=setup_s, t_close=w.t_close, window_s=args.seconds,
        peaks=peaks, trace=None, notes=[])
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
                 else end_to_end(m["name"], rec, kind))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    for note in rec.notes:
        log(note)

    batches = sample_batches(rec, np.random.default_rng(args.seed),
                             mix["check_requests"])
    found, checked = kind.checks(rec, dep, pool, w, batches, control, log)
    checks = {"missing": {"value": failed, "limit": 0}, **found}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    # too few whole batches to check is not a pass
    checks["checked_requests"] = {"value": checked,
                                  "limit": mix["check_requests"]}
    correct &= checked >= mix["check_requests"]

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

"""One run of one cell: set-up, the measured window, the records, the
metrics, and the comparison that decides ``correct``."""
from __future__ import annotations

import gc
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

from gfbench import check, serve, spec, trace, traffic, window


class Refused(Exception):
    """The run cannot measure what the cell asks for."""


def device_check(devices, peaks: dict, chips: int) -> dict:
    """The peak table's entry for these devices; refuses a backend other
    than TPU, a ``device_kind`` the table lacks, and too few chips."""
    if not devices or devices[0].platform != "tpu":
        raise Refused("JAX found no TPU (platform "
                      f"{devices[0].platform if devices else None!r})")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise Refused(f"device_kind {kind!r} is not in bench/peaks.json")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return peaks[kind]


def _log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def measure(cell: dict, seed: int, seconds: float, traced: bool,
            out_dir: Path, peak: dict, t_start: float, compiles: list, *,
            control: bool = False,
            root: Path = spec.ROOT) -> dict:
    """Run the cell once and return the result line's fields, with the
    numbers compared under ``checks`` (and the control's beside them
    under ``control`` when asked)."""
    import jax
    from repro.core.event_loop import EventLoop, WallClock
    from repro.core.telemetry import Telemetry
    conf, mix, arch = cell["config"], cell["mix"], cell["arch"]
    model, text_len = conf["model"], conf["text_encoder"]["prompt_len"]
    chips = cell["chips"]

    tel = Telemetry() if traced else None
    t_build = time.monotonic()
    eng = serve.build(conf, seed, chips, telemetry=tel)
    _log(f"set-up: imports {t_build - t_start:.2f} s, engine and weights "
         f"{time.monotonic() - t_build:.2f} s")
    clock = WallClock()
    eng.backend.t0 = clock.t0
    if tel is not None:
        tel.t0 = clock.t0
    rec = serve.Recorder(arch)
    rec.install(eng, clock)
    loop = EventLoop(eng.cp, clock)
    serve.warm_up(eng, loop, clock, traffic.warm_set(mix, seed),
                  mix["model"])
    _log(f"set-up: warm-up {clock.now():.2f} s, {len(compiles)} compiles "
         f"so far")
    planned = traffic.generate(mix, model, arch, peak, seconds, seed,
                               text_len)
    trace_dir = out_dir / "traces" / f"{cell['name']}-{seed}"
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
    times = serve.run_window(
        eng, rec, clock, planned, mix["model"], seconds,
        drain_s=mix["drain_s"],
        trace_dir=str(trace_dir) if traced else None)
    t0 = clock.t0
    setup_s = t0 + times["w0"] - t_start
    in_window = [d for at, d in compiles
                 if t0 + times["w0"] <= at <= t0 + times["stop"]]
    devices = jax.devices()[:chips]
    mem_peak = serve.peak_bytes(devices)
    _log(f"device memory: peak {mem_peak} of "
         f"{(devices[0].memory_stats() or {}).get('bytes_limit')} bytes")

    ids = [p.id for p in planned]
    idset = set(ids)
    run = {
        "window": {"w0": times["w0"], "w1": times["w1"],
                   "seconds": times["w1"] - times["w0"]},
        "stop": times["stop"],
        "steps": [c for c in rec.completions if c["req"] in idset],
        "requests": serve.request_records(eng.cp, ids),
        "spans": [(n, a - t0, b - t0) for n, a, b in rec.spans],
        "worker_errors": len(eng.backend.errors),
        "collective_timeouts": len(eng.backend.timeouts),
        "setup_s": setup_s, "model": model, "text_len": text_len,
        "arch": conf["architecture"], "root": str(root),
        "peak": peak, "trace": None,
    }
    items = check.collect(eng.cp, window.denoise_steps(run), seed,
                          mix["check"]["steps"], arch)
    for err in eng.backend.errors[:2]:
        _log(f"worker error: {err}")
    eng.shutdown()
    del eng, loop, clock, tel, rec
    gc.collect()

    if traced:
        tr = trace.load(str(trace_dir))
        run["trace"] = {"events": tr, "span": trace.window(tr)}
        shutil.rmtree(trace_dir, ignore_errors=True)
        with open(out_dir / "traces" / f"{cell['name']}-{seed}.json",
                  "w") as f:
            json.dump(tr, f)

    steps = window.denoise_steps(run)
    _log(f"window: {len(in_window)} compiles inside it "
         f"({sum(in_window):.3f} s), {window.unfinished(run)} judged "
         f"requests unfinished when waiting stopped "
         f"{run['stop'] - run['window']['w1']:.3f} s after the close, "
         f"{len(steps)} denoise steps in it (longest "
         f"{max((x['duration'] for x in steps), default=0.0):.3f} s)")

    entries = cell["per_layer"] if traced else cell["end_to_end"]
    metrics = {}
    for m in entries:
        value = spec.reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    result = {"correct": False, "attempted": window.attempted(run),
              "failed": window.failed(run), "metrics": metrics,
              "device": device}
    if traced:
        tr, (a, b) = run["trace"]["events"], run["trace"]["span"]
        device["busy_s"] = trace.busy_ns(tr, a, b) * 1e-9
        device["window_s"] = (b - a) * 1e-9
        result["breakdown"] = {"device_ops": trace.top_ops(tr, a, b),
                               "idle_gaps": trace.idle_gaps(tr, a, b)}

    t_ref = time.monotonic()
    ref = check.Reference(conf, seed, arch)
    found = check.gaps(ref, items)
    _log(f"reference: {time.monotonic() - t_ref:.2f} s for "
         f"{len(items)} sampled outputs")
    ok, table = check.verdict(found, cell["limits"])
    result["correct"] = ok
    if control:
        result["control"] = check.gaps(ref, items, control=True)
        result["control_correct"], control_table = check.verdict(
            result["control"], cell["limits"])
        for name, (value, limit) in control_table.items():
            _log(f"control {name} {value!r} limit {limit!r}")
        _log(f"control correct: {result['control_correct']}")
        result["faults"] = check.fault_readings(ref, items)
    result["checks"] = table
    _save(out_dir, cell["name"], seed, traced, run, result)
    return result


def _save(out_dir: Path, name: str, seed: int, traced: bool, run: dict,
          result: dict):
    """The run's records, for reading later (traces are summarized)."""
    keep = dict(run)
    keep.pop("trace")
    path = out_dir / "runs" / f"{name}-{seed}-{int(traced)}.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump({"run": keep, "result": result}, f, default=str)


def report(result: dict, out=sys.stdout, err=sys.stderr):
    """Each number compared beside its limit as the last lines of
    standard error, and the result as the last line of standard output,
    ``checks`` last in it."""
    for name, (value, limit) in result["checks"].items():
        print(f"check {name} {value!r} limit {limit!r}", file=err)
    err.flush()
    line = {k: v for k, v in result.items() if k != "checks"}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in result["checks"].items()}
    print(json.dumps(line), file=out, flush=True)


def run_seconds(bench: dict, requested: Optional[float]) -> float:
    return float(bench["run_seconds"] if requested is None else requested)

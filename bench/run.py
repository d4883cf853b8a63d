#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process: it makes the weights on the device from the seed, starts
the cell's gateways on tcp engines, warms the cell's executables, runs
the cell's traffic for its warm-up, measures for ``--seconds``, then
checks what was served against the plain reference.  ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` its per-layer
metrics, from spans, counters and a profiler trace of part of the
window.  The last line of standard output is one JSON object; the
numbers compared for ``correct`` are the last lines of standard error
too.  Without a TPU, or with fewer chips than the cell needs, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"
TRACE_SECONDS = 4.0          # profiled part of a traced window
SAMPLE_TOKENS = 384          # served tokens the reference checks, at least
SAMPLE_MIN = 3               # requests the reference checks, at least
SAMPLE_MAX = 16              # requests the reference checks, at most


class NoChip(RuntimeError):
    pass


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def enable_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout;
    the program's own helper is pointed at the same directory."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_for(n: int, require_tpu: bool = True):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices


class CompileCounter:
    """Counts compilations (or cache fetches) after ``arm()``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT and self.armed:
            self.count += 1

    def arm(self) -> None:
        self.armed = True


def sample_requests(records, seed: int):
    """Requests the reference checks: the longest, the longest of each
    replica, then others drawn from the seed, until at least
    SAMPLE_MIN requests and SAMPLE_TOKENS served tokens, or SAMPLE_MAX
    requests."""
    import numpy as np
    ok = [r for r in records if r["ok"]]
    if not ok:
        return []
    by_len = sorted(ok, key=lambda r: -(len(r["prompt"]) + r["n_out"]))
    picked = {id(by_len[0]): by_len[0]}
    for rep in sorted({r["replica"] for r in ok}, key=str):
        first = next(r for r in by_len if r["replica"] == rep)
        picked.setdefault(id(first), first)
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 7])
    for i in rng.permutation(len(ok)):
        enough = len(picked) >= SAMPLE_MIN and \
            sum(r["n_out"] for r in picked.values()) >= SAMPLE_TOKENS
        if enough or len(picked) >= SAMPLE_MAX:
            break
        picked.setdefault(id(ok[i]), ok[i])
    return list(picked.values())


def gap_numbers(cell, weights, sample, ref, control: bool = False) -> dict:
    """The widest and the mean gap of the sample's served tokens below
    the reference's best logit (``control``: of the float8 control's
    first tokens instead)."""
    import numpy as np
    gaps = []
    for r in sample:
        t0 = time.monotonic()
        g = ref.gaps(cell.config, weights, r["prompt"], r["out"],
                     control=control)
        gaps.append(g)
        log(f"reference{' control' if control else ''}: "
            f"{len(r['prompt'])}+{len(g)} tokens "
            f"{time.monotonic() - t0:.2f} s, widest gap {np.max(g):.4f}")
    allg = np.concatenate(gaps) if gaps else np.zeros(1)
    return {"max_logit_gap": float(np.max(allg)),
            "mean_logit_gap": float(np.mean(allg))}


def compare(cell, weights, records, seed: int, ref) -> dict:
    """The numbers that decide ``correct``, each beside its limit: those
    of ``gap_numbers`` that the configuration gives a limit."""
    unanswered = sum(1 for r in records if not r["ok"])
    sample = sample_requests(records, seed)
    gaps = gap_numbers(cell, weights, sample, ref)
    answered = len(records) - unanswered
    out = {"requests_checked": {"value": len(sample), "at_least": True,
                                "limit": max(1, min(SAMPLE_MIN, answered))},
           "unanswered": {"value": unanswered, "limit": 0}}
    for name, limit in cell.config["limits"].items():
        out[name] = {"value": gaps[name], "limit": limit}
    return out


def passed(compared: dict) -> bool:
    for v in compared.values():
        if v.get("at_least"):
            if v["value"] < v["limit"]:
                return False
        elif v["value"] > v["limit"]:
            return False
    return True


def _serve(cell, model, params, devices, *, seed, seconds, traced,
           require_tpu) -> dict:
    """Warm, run the traffic and measure; returns plain data only, with
    every engine closed and released."""
    from bench import trace_reduce
    from bench.instrument import StepTimer
    from bench.loadgen import LoadRun
    from bench.stack import Stack
    from bench.traffic_gen import make_plan

    dep = cell.config["deployment"]
    vocab = model.cfg.vocab
    stack = Stack(model, params, devices, dep)
    out = {"steps": None, "spans": [], "reduced": None, "traced": None}
    try:
        _warm_requests(stack, dep, vocab)
        if traced:
            from repro.telemetry import trace as rtrace
            rtrace.configure(sample=1.0, ring=1 << 18)
            rtrace.clear()
            out["steps"] = StepTimer()
            for rep in stack.replicas:
                out["steps"].install(rep.serve)
        plan = make_plan(cell.traffic, find=cell.find, seed=seed,
                         seconds=seconds,
                         max_len=dep["max_len"], n_slots=dep["n_slots"],
                         replicas=len(devices))
        load = LoadRun(plan, stack.send, seed=seed, vocab=vocab)
        compiles = CompileCounter()
        snap = out["snap"] = {}
        profiler = _Profiler(OUT_DIR / "trace" / cell.name) if traced \
            else None

        def at_open():
            snap["stats0"] = stack.gateway_stats()
            snap["aff0"] = stack.affinity_stats()
            snap["wall0"] = time.time()
            compiles.arm()

        def at_close():
            snap["stats1"] = stack.gateway_stats()
            snap["aff1"] = stack.affinity_stats()
            snap["wall1"] = time.time()
            snap["compiles"] = compiles.count

        timers = _window_timers(load, plan, at_open, at_close, profiler,
                                seconds)
        out["records"] = load.run()
        for t in timers:
            t.join()
        out["window"] = load.window
        out["memory"] = [d.memory_stats()["peak_bytes_in_use"]
                         for d in devices] if require_tpu else [0]
        if traced:
            from repro.telemetry import trace as rtrace
            out["spans"] = [s for s in rtrace.export()["spans"]
                            if s["name"] == "gen.serve"
                            and snap["wall0"] <= s["wall"] < snap["wall1"]]
            if profiler.path is not None:
                out["reduced"] = trace_reduce.reduce(profiler.path)
                out["traced"] = profiler.window
            shutil.rmtree(profiler.dir, ignore_errors=True)
    finally:
        stack.close()
    return out


def run_cell(cell, *, seed: int, seconds: float, traced: bool,
             require_tpu: bool = True) -> dict:
    """Everything but the argument parsing and the printing."""
    import jax

    from bench import measure, spec, trace_reduce
    from bench.peaks import peaks_for
    from repro.models import Model

    devices = chips_for(cell.chips, require_tpu)[:cell.chips]
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if require_tpu else None
    family = cell.family
    model = Model(family.program_config(cell.config))
    w0 = family.make_weights(model, seed, cell.config, devices[0])
    params = [w0] + [jax.device_put(w0, d) for d in devices[1:]]
    got = _serve(cell, model, params, devices, seed=seed, seconds=seconds,
                 traced=traced, require_tpu=require_tpu)
    del params                 # the replicas' copies; w0 stays for the check
    gc.collect()
    records, snap = got["records"], got["snap"]
    t_open, t_close = got["window"]
    setup_s = t_open - T_START
    due = measure.in_window(records, t_open, t_close)
    e2e = measure.end_to_end(records, t_open, t_close)
    mid = (t_open + t_close) / 2
    halves = [measure.percentile([measure.ttft_ms(r) for r in due
                                  if r["ok"] and a <= r["due"] < b], 50)
              for a, b in ((t_open, mid), (mid, t_close))]
    log(f"window {seconds} s: {e2e['attempted']} due, {e2e['failed']} "
        f"failed, {e2e['n_ttft']} ttft, {e2e['n_tpot']} tpot samples; "
        f"{len(records)} requests in all; compiles in window "
        f"{snap['compiles']}; setup_s {setup_s:.3f}; ttft p50 by half "
        f"of the window {halves}")
    # what a per-layer reader is given
    run = SimpleNamespace(
        cell=cell, seed=seed, window=(t_open, t_close), records=records,
        due=due, e2e=e2e, snap=snap, spans=got["spans"], steps=got["steps"],
        reduced=got["reduced"], traced=got["traced"], peaks=peaks,
        chips=len(devices), memory=got["memory"],
        shape=getattr(family, "cost_shape", lambda c: None)(cell.config),
        chunk=cell.config["deployment"].get("chunk_tokens"))
    metrics = {}
    if traced:
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"], cell.find)(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    t_ref = time.monotonic()
    compared = compare(cell, w0, due, seed, cell.reference)
    log(f"reference check {time.monotonic() - t_ref:.1f} s")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(got["memory"])}
    out = {"correct": passed(compared), "attempted": e2e["attempted"],
           "failed": e2e["failed"], "metrics": metrics, "device": device}
    reduced = got["reduced"]
    if reduced and reduced["chips"]:
        chips = list(reduced["chips"].values())
        device["busy_s"] = sum(c["busy_s"] for c in chips) / len(chips)
        device["window_s"] = got["traced"][1] - got["traced"][0]
        out["breakdown"] = {
            "device_ops": trace_reduce.top(
                {k: v / len(chips) for k, v in reduced["ops"].items()}),
            "idle_gaps": trace_reduce.top(
                {k: v / len(chips) for k, v in reduced["gaps"].items()})}
    out["compared"] = compared
    return out


def _warm_requests(stack, dep, vocab: int) -> None:
    """Through each replica's ``gen.generate``: two prompt chunks and a
    decode, then a session follow-up where the deployment pins sessions,
    so that the chunk, decode, slot scatter and gather executables are
    compiled (or fetched from the cache) before the cell's own warm-up
    traffic fills every slot."""
    import numpy as np
    C = dep["chunk_tokens"]
    prompt = np.arange(1, C + 2) % (vocab - 1) + 1
    for i, _ in enumerate(stack.replicas):
        arg = {"tokens": prompt.tolist(), "max_new": 3}
        if dep["session_cap"]:
            arg["session_id"] = f"warm{i}"
        out = stack.generate(arg, replica=i)
        if dep["session_cap"]:
            stack.generate(dict(arg, tokens=arg["tokens"] + out["tokens"]
                                + [1, 2]), replica=i)


class _Profiler:
    """Profiles TRACE_SECONDS of the window from a helper thread."""

    def __init__(self, log_dir: Path):
        self.dir = log_dir
        self.path = None
        self.window = None

    def run(self, seconds: float) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        t0 = time.monotonic()
        time.sleep(seconds)
        t1 = time.monotonic()
        jax.profiler.stop_trace()
        self.window = (t0, t1)
        from bench.trace_reduce import find_xplane
        self.path = find_xplane(self.dir)


def _window_timers(load, plan, at_open, at_close, profiler, seconds):
    """Threads that fire at the window's open and close (and run the
    profiler inside it), on the schedule's clock."""
    def wait_until(rel):
        while load.t0 == 0.0:
            time.sleep(0.001)
        delay = load.t0 + rel - time.monotonic()
        if delay > 0:
            time.sleep(delay)

    w_open, w_close = plan.window

    def opener():
        wait_until(w_open)
        at_open()

    def closer():
        wait_until(w_close)
        at_close()

    def prof():
        trace_s = min(TRACE_SECONDS, 0.5 * seconds)
        wait_until(w_open + 0.25 * seconds)
        profiler.run(trace_s)

    fns = [opener, closer] + ([prof] if profiler else [])
    threads = [threading.Thread(target=f, daemon=True) for f in fns]
    for t in threads:
        t.start()
    return threads


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import spec
    cell = spec.load_cell(args.workload)
    enable_cache()
    try:
        out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       traced=bool(args.trace))
    except NoChip as e:
        log(str(e))
        return 2
    for name, v in out["compared"].items():
        log(f"compared {name} {v['value']} limit {v['limit']}"
            + (" (at least)" if v.get("at_least") else ""))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

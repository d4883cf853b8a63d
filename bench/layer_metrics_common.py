"""Shared arithmetic of the per-layer readers: the needed work of the
window's calls (``bench/work.py``) against the device time the trace
gives each executable."""
from __future__ import annotations

from bench import cost, trace_reduce, work


def _ready(run) -> bool:
    return run.shape is not None and run.peaks is not None


def hit_share(run) -> float:
    return work.follow_up_hit_share(run.records, run.snap["stats0"],
                                    run.snap["stats1"], *run.window)


def decode_roofline(run):
    """Least time of the decode steps the profiler saw (their weights,
    and the K/V and operations of the tokens produced while it ran) over
    the device time of executable ``jit_decode_step``, in %."""
    if not _ready(run) or not run.reduced:
        return None
    calls, secs = trace_reduce.module_seconds(run.reduced, "decode_step")
    if not calls or secs <= 0:
        return None
    _, flops, kv = work.decode_work(run.records, run.shape, *run.traced)
    weights = cost.decode_cost(run.shape, [])[1]
    need = cost.roofline_seconds(flops, calls * weights + kv, run.peaks)
    return 100.0 * need / secs


def chunk_roofline(run):
    """Mean least time of a prefill chunk of the window's requests, times
    the chunks the profiler saw, over the device time of executable
    ``jit_prefill_chunk``, in %."""
    if not _ready(run) or not run.reduced or not run.chunk:
        return None
    calls, secs = trace_reduce.module_seconds(run.reduced, "prefill_chunk")
    chunks = work.prefill_chunks(run.records, run.shape, run.chunk,
                                 hit_share(run), *run.window)
    weight = sum(w for w, _, _ in chunks)
    if not calls or secs <= 0 or weight <= 0:
        return None
    need = sum(w * cost.roofline_seconds(f, b, run.peaks)
               for w, f, b in chunks) / weight
    return 100.0 * need * calls / secs


def step_mfu(run):
    """Operations of every token decoded and every prompt token
    prefilled in the window over the window times the chips times the
    bfloat16 peak, in %."""
    if not _ready(run):
        return None
    _, dec, _ = work.decode_work(run.records, run.shape, *run.window)
    pre = sum(w * f for w, f, _ in work.prefill_chunks(
        run.records, run.shape, run.chunk or 1 << 30, hit_share(run),
        *run.window))
    if not dec + pre:
        return None
    t0, t1 = run.window
    return 100.0 * (dec + pre) / ((t1 - t0) * run.chips
                                  * run.peaks["bf16_flops"])

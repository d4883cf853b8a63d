#!/usr/bin/env python3
"""Chip smoke test: serve qwen1.5-0.5b at its published widths on a TPU.

    python chip_smoke.py            # one chip: client -> gateway -> engine
    python chip_smoke.py --chips 4  # four chips: one replica per chip
                                    # behind a routed, session-affine pool

One chip: a ``ServeEngine`` (8 slots, 1024-token cache, 8 pinned
sessions, ``impl="auto"``) behind a ``ServingGateway`` on a tcp
``Engine`` answers 8 fresh greedy requests (prompts of 128 and 384
tokens) and one follow-up per session, all over ``gen.generate`` from a
second client ``Engine``.  The compiled prefill must hold the Pallas
flash kernel; its last-position logits are compared with a float32
reference of the same parameters on the host CPU; the gateway's first
token must be their argmax.

Four chips: four device-pinned replicas in this one process, registered
with an in-process registry, serve 8 conversations x 3 turns through
``ServicePool(balancer="rr")`` + ``SessionAffinity``; every turn must
give the same greedy tokens as one replica on device 0 alone.

Earlier lines are diagnostics (device kind, compile seconds per
executable, the logits difference beside its bound, peak device
bytes); none of them is a speed.  The last line is one JSON object,
``{"ok": true, "device": {...}}``.  Without a TPU, or when any check
fails, the script exits non-zero and prints no ``"ok": true``.
Weights are random, made from a fixed seed.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
SEED = 0
N_SLOTS, MAX_LEN, SESSION_CAP = 8, 1024, 8
PROMPT_LENS = (128, 384)          # two prompt lengths: two prefill shapes
MAX_NEW = 32
FOLLOW_UP = 16                    # new user tokens appended per turn
CONVERSATIONS, TURNS = 8, 3       # four-chip phase
RPC_TIMEOUT = 900.0               # first calls include compilation
# Bound on max |chip - reference| over the last-position logits, as a
# share of the reference's largest |logit|.  The chip computes in
# bfloat16 (8-bit mantissa, relative rounding 2^-9 per operation) with
# float32 accumulation; the reference runs the same float32 parameters
# in float32 at "highest" matmul precision on the host CPU.
LOGITS_BOUND = 0.05


class CheckFailed(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def log(*parts) -> None:
    print(*parts, flush=True)


# --------------------------------------------------------------- building
def build(cfg, seed: int = SEED):
    """Model and random parameters from ``seed`` on the default device."""
    import jax
    from repro.models import Model, unzip

    model = Model(cfg)
    params, _ = unzip(jax.jit(model.init)(jax.random.PRNGKey(seed)))
    return model, params


class Replica:
    """One gateway: a ``ServeEngine`` behind ``ServingGateway`` on its own
    tcp ``Engine``, optionally pinned to a device and registered."""

    def __init__(self, model, params, *, device=None, registry=None,
                 n_slots=N_SLOTS, max_len=MAX_LEN, session_cap=SESSION_CAP):
        from repro.core.executor import Engine
        from repro.serve.engine import ServeEngine
        from repro.services import ServingGateway

        self.serve = ServeEngine(model, params, max_len=max_len,
                                 n_slots=n_slots, session_cap=session_cap,
                                 impl="auto", device=device)
        self.rpc = Engine("tcp://127.0.0.1:0")
        self.gateway = ServingGateway(self.rpc, self.serve,
                                      registry=registry,
                                      report_interval=0.2)
        self.uri = self.rpc.uri

    def close(self) -> None:
        self.gateway.close()
        self.rpc.shutdown()


def _check_result(res: dict, max_new: int, what: str) -> list:
    check(res.get("done"), f"{what}: not done ({res})")
    check(len(res["tokens"]) == max_new,
          f"{what}: {len(res['tokens'])} tokens, expected {max_new}")
    return list(res["tokens"])


def _fan_out(fn, n: int) -> list:
    with cf.ThreadPoolExecutor(n) as tp:
        return list(tp.map(fn, range(n)))


# ----------------------------------------------------------- gateway phase
def gateway_phase(model, params, *, prompt_lens=PROMPT_LENS,
                  max_new=MAX_NEW, follow_up=FOLLOW_UP, n_slots=N_SLOTS,
                  max_len=MAX_LEN, session_cap=SESSION_CAP,
                  seed=SEED) -> dict:
    """Fresh requests, then one follow-up per session, through the
    gateway from a client engine.  Returns what the caller checks
    against the chip: the first prompt, the chip's prefill logits for
    it, the compiled prefill's text and the gateway's stats."""
    from repro.core.executor import Engine

    rng = np.random.default_rng(seed)
    vocab = model.cfg.vocab
    prompts = [rng.integers(1, vocab, prompt_lens[i % len(prompt_lens)],
                            dtype=np.int32) for i in range(n_slots)]
    rep = Replica(model, params, n_slots=n_slots, max_len=max_len,
                  session_cap=session_cap)
    try:
        with Engine("tcp://127.0.0.1:0") as client:
            def generate(tokens, sid):
                return client.call(rep.uri, "gen.generate",
                                   {"tokens": list(map(int, tokens)),
                                    "max_new": max_new, "session_id": sid},
                                   timeout=RPC_TIMEOUT)

            first = _fan_out(lambda i: _check_result(
                generate(prompts[i], f"s{i}"), max_new, f"request {i}"),
                n_slots)
            extra = [rng.integers(1, vocab, follow_up, dtype=np.int32)
                     for _ in range(n_slots)]
            _fan_out(lambda i: _check_result(
                generate(np.concatenate([prompts[i], first[i], extra[i]]),
                         f"s{i}"), max_new, f"follow-up {i}"), n_slots)
            stats = client.call(rep.uri, "gen.stats", {})
        check(stats["faults"] == 0, f"gateway step faults: {stats}")
        check(stats["prefix_hits"] >= n_slots // 2,
              f"prefix hits {stats['prefix_hits']} < {n_slots // 2}")

        serve = rep.serve
        # the engine's own prefill executable, on the engine's device
        batch = {"tokens": serve._put(prompts[0][None, :])}
        logits, _ = serve._prefill_jit(serve.params, batch)
        logits = np.asarray(logits[0], np.float32)
        hlo = serve._prefill_jit.lower(serve.params, batch).compile() \
            .as_text()
        check(first[0][0] == int(np.argmax(logits)),
              f"gateway's first token {first[0][0]} != argmax of the "
              f"prefill logits {int(np.argmax(logits))}")
        return {"prompt": prompts[0], "logits": logits, "hlo": hlo,
                "stats": stats}
    finally:
        rep.close()


def reference_logits(model, params, prompt) -> np.ndarray:
    """Last-position prefill logits of the same parameters on the host
    CPU: ``impl="ref"`` kernels, float32 compute, highest precision."""
    import jax
    from repro.models import Model

    cpu = jax.devices("cpu")[0]
    ref = Model(model.cfg.replace(compute_dtype="float32"))
    p_cpu = jax.device_put(params, cpu)
    tokens = jax.device_put(np.asarray(prompt, np.int32)[None, :], cpu)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: ref.prefill(
            p, {"tokens": t}, cache_len=t.shape[1], impl="ref"))(p_cpu,
                                                                 tokens)
    return np.asarray(logits[0], np.float32)


def logits_error(chip: np.ndarray, ref: np.ndarray) -> float:
    """max |chip - ref| as a share of max |ref|."""
    return float(np.max(np.abs(chip - ref)) / np.max(np.abs(ref)))


# ----------------------------------------------------------- replica phase
def run_conversations(call, vocab: int, *, conversations=CONVERSATIONS,
                      turns=TURNS, prompt_lens=PROMPT_LENS, max_new=MAX_NEW,
                      follow_up=FOLLOW_UP, seed=SEED) -> list:
    """``conversations`` sessions x ``turns`` turns, each turn's prompt
    the whole history plus new tokens drawn from ``seed``; ``call(sid,
    arg)`` answers one ``gen.generate``.  Returns the tokens per turn."""
    rng = np.random.default_rng(seed + 1)
    hist = [rng.integers(1, vocab, prompt_lens[c % len(prompt_lens)]
                         ).tolist() for c in range(conversations)]
    news = [[rng.integers(1, vocab, follow_up).tolist()
             for _ in range(conversations)] for _ in range(turns)]
    out = []
    for t in range(turns):
        def one(c):
            arg = {"tokens": hist[c], "max_new": max_new,
                   "session_id": f"conv{c}"}
            return _check_result(call(f"conv{c}", arg), max_new,
                                 f"turn {t} conversation {c}")
        toks = _fan_out(one, conversations)
        for c in range(conversations):
            hist[c] = hist[c] + toks[c] + news[t][c]
        out.append(toks)
    return out


def replica_phase(model, params, devices, **conv_kw) -> dict:
    """One replica alone on ``devices[0]``, then one replica per device
    behind a registry-resolved pool with session affinity; both serve
    the same conversations, and every turn must match.  Returns the
    tokens of both runs, the replicas' stats and their (closed) engines,
    whose arrays the caller may inspect."""
    from repro.core.executor import Engine
    from repro.fabric import (RegistryService, RetryPolicy, ServicePool,
                              SessionAffinity)

    vocab = model.cfg.vocab
    out = {}
    with Engine("tcp://127.0.0.1:0") as client:
        alone = Replica(model, params, device=devices[0])
        try:
            out["alone"] = run_conversations(
                lambda sid, arg: client.call(alone.uri, "gen.generate", arg,
                                             timeout=RPC_TIMEOUT),
                vocab, **conv_kw)
        finally:
            alone.close()

        with Engine("tcp://127.0.0.1:0") as reg_engine:
            registry = RegistryService(reg_engine, instance_ttl=30.0)
            reps = [Replica(model, params, device=d,
                            registry=reg_engine.uri) for d in devices]
            try:
                # fixed credits: gen.generate holds a call open for a
                # whole generation, which adaptive credits would read as
                # congestion
                pool = ServicePool(client, reg_engine.uri, "gen",
                                   balancer="rr", credits_per_target=8,
                                   adaptive_credits=False,
                                   policy=RetryPolicy(
                                       attempts=1, rpc_timeout=RPC_TIMEOUT))
                deadline = time.monotonic() + 60.0
                while True:
                    pool.refresh(force=True)
                    if len(pool.replicas()) == len(devices):
                        break
                    check(time.monotonic() < deadline,
                          "replicas never all registered")
                    time.sleep(0.1)
                affinity = SessionAffinity(pool)
                out["pool"] = run_conversations(
                    lambda sid, arg: affinity.call_routed(
                        sid, "gen.generate", arg, timeout=RPC_TIMEOUT)[0],
                    vocab, **conv_kw)
                out["stats"] = [client.call(r.uri, "gen.stats", {})
                                for r in reps]
                out["affinity"] = affinity.stats()
                out["engines"] = [r.serve for r in reps]
                pool.close()
            finally:
                for r in reps:
                    r.close()
                registry.close()
    for i, s in enumerate(out["stats"]):
        check(s["faults"] == 0, f"replica {i} step faults: {s}")
        check(s["steps"] > 0, f"replica {i} served nothing: {s}")
    for t, (a, b) in enumerate(zip(out["alone"], out["pool"])):
        check(a == b, f"turn {t}: pool tokens differ from the single "
                      f"replica's")
    return out


# -------------------------------------------------------------------- main
class CompileClock:
    """Seconds JAX spent compiling (or fetching from the persistent
    cache) each executable, by function name."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds: dict = {}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == self.EVENT:
            name = kw.get("fun_name", "?")
            with self._lock:
                self.seconds[name] = self.seconds.get(name, 0.0) + duration

    def report(self) -> None:
        with self._lock:
            items = sorted(self.seconds.items())
        for name, s in items:
            log(f"compile_s {name} {s:.3f}")
        log(f"compile_s total {sum(s for _, s in items):.3f}")


def one_chip(cfg, dev) -> None:
    model, params = build(cfg)
    t0 = time.monotonic()
    res = gateway_phase(model, params)
    log(f"gateway phase {time.monotonic() - t0:.1f} s (compiles included); "
        f"stats: prefix_hits={res['stats']['prefix_hits']} "
        f"steps={res['stats']['steps']} faults={res['stats']['faults']}")
    check("tpu_custom_call" in res["hlo"],
          "the compiled prefill holds no Pallas kernel")
    log("compiled prefill holds the Pallas kernel (tpu_custom_call)")
    err = logits_error(res["logits"],
                       reference_logits(model, params, res["prompt"]))
    log(f"logits max|chip - f32 cpu ref| / max|ref| = {err:.6f} "
        f"(bound {LOGITS_BOUND})")
    check(err <= LOGITS_BOUND, f"logits error {err} over {LOGITS_BOUND}")
    log(f"peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}")


def four_chips(cfg, devices) -> None:
    model, params = build(cfg)
    res = replica_phase(model, params, devices)
    pbytes = sum(x.nbytes for x in jax_leaves(params))
    for i, (d, eng) in enumerate(zip(devices, res["engines"])):
        used = d.memory_stats()["bytes_in_use"]
        check(used >= pbytes, f"device {i} holds {used} bytes < params "
                              f"{pbytes}")
        check(all(x.devices() == {d} for x in jax_leaves(
            eng.params, eng.cache, eng._cache1_zero)),
              f"replica {i} has arrays off device {d}")
        log(f"device {i} {d}: bytes_in_use {used} >= param bytes {pbytes}; "
            f"replica steps={res['stats'][i]['steps']} "
            f"prefix_hits={res['stats'][i]['prefix_hits']}")
    log(f"affinity {res['affinity']}")
    log(f"{len(res['pool'])} turns x {len(res['pool'][0])} conversations x "
        f"{MAX_NEW} tokens: pool tokens == single-replica tokens")


def jax_leaves(*trees) -> list:
    import jax
    return [x for t in trees for x in jax.tree_util.tree_leaves(t)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the replicas-behind-the-router phase")
    args = ap.parse_args(argv)

    import jax
    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()      # before anything compiles
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    log(f"cache_dir {cache_dir}")
    log(f"device_kind {dev.device_kind} platform {dev.platform} "
        f"count {len(devices)}")
    clock = CompileClock()
    cfg = configs.get(ARCH)
    try:
        if args.chips == 4:
            four_chips(cfg, devices[:4])
        else:
            one_chip(cfg, dev)
    except CheckFailed as e:
        clock.report()
        print(f"CHECK FAILED: {e}", file=sys.stderr)
        return 1
    clock.report()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

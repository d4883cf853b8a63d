"""Serving driver: model server behind the Mercury gateway + demo client.

Starts a ServeEngine for the chosen arch (reduced config by default),
exposes it through the ServingGateway over the tcp NA plugin, and — in
--demo mode — runs a client engine that submits a few batched prompts and
prints the completions.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced --demo
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --listen tcp://0.0.0.0:7777        # stay up as a server
  PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-3b-a800m \
      --param-dtype bfloat16 --slots 24 --max-len 2048   # one v5e chip
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.core.executor import Engine
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model, unzip
from repro.serve.engine import ServeEngine
from repro.services import ServingGateway
from repro.telemetry import trace


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--listen", default="tcp://127.0.0.1:0")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--param-dtype", default=None,
                    choices=("float32", "bfloat16"),
                    help="dtype the weights are held in (default: the "
                         "preset's; bfloat16 halves them, as a full-size "
                         "granite-moe-3b-a800m needs on one chip)")
    ap.add_argument("--demo", action="store_true")
    ap.add_argument("--registry", default=None, metavar="URI[,URI...]",
                    help="fabric registry to self-register with (service "
                         "'gen'): replicas started this way are routable "
                         "through a ServicePool.  For a replicated "
                         "registry pass the whole comma-separated quorum "
                         "address set; registration and heartbeats fail "
                         "over between the replicas (DESIGN.md §8)")
    ap.add_argument("--service", default="gen",
                    help="service name to register under (with --registry)")
    ap.add_argument("--member-id", default=None,
                    help="join the control plane's membership service "
                         "(mem.*, served by the same registry quorum) "
                         "under this id and bind the registration to "
                         "it: if this node dies, member expiry reaps "
                         "the instance without waiting for the "
                         "instance TTL (requires the registry to run "
                         "with its membership plane on — the default)")
    ap.add_argument("--trace-sample", type=float, default=None,
                    metavar="P",
                    help="head-sampling probability for distributed "
                         "traces rooted here (0..1; default honors "
                         "REPRO_TRACE_SAMPLE, falling back to 0.01). "
                         "Sampled spans are served via dbg.trace")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.trace_sample is not None:
        trace.configure(sample=args.trace_sample)

    cfg = configs.reduced(args.arch) if args.reduced else configs.get(args.arch)
    if args.param_dtype:
        cfg = cfg.replace(param_dtype=args.param_dtype)
    model = Model(cfg)
    params, _ = unzip(model.init(jax.random.PRNGKey(0)))
    serve = ServeEngine(model, params, max_len=args.max_len,
                        n_slots=args.slots)

    server = Engine(args.listen)
    gw = ServingGateway(server, serve, registry=args.registry,
                        service=args.service, member_id=args.member_id)
    print(f"serving {cfg.name} at {server.uri} "
          f"({args.slots} slots, max_len {args.max_len})"
          + (f", registered with {args.registry} as {args.service!r}"
             if args.registry else "")
          + (f", member {args.member_id!r}" if args.member_id else ""))

    if args.demo:
        rng = np.random.default_rng(0)
        with Engine("tcp://127.0.0.1:0") as client:
            t0 = time.monotonic()
            rids = []
            for i in range(6):
                prompt = rng.integers(1, cfg.vocab, size=5 + i).tolist()
                rids.append(client.call(server.uri, "gen.submit",
                                        {"tokens": prompt, "max_new": 12,
                                         "temperature": 0.7}))
            for r in rids:
                out = client.call(server.uri, "gen.result",
                                  {"rid": r["rid"], "wait": True},
                                  timeout=120.0)
                print(f"rid {r['rid']}: {out['tokens']}")
            print("stats:", client.call(server.uri, "gen.stats", {}),
                  f"({time.monotonic() - t0:.1f}s)")
        gw.stop()
        server.shutdown()
    else:
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            gw.stop()
            server.shutdown()


if __name__ == "__main__":
    main()

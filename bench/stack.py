"""The system under test, as a deployment runs it: per chip one
``ServeEngine`` behind a ``ServingGateway`` on its own tcp ``Engine``.

One chip: the client calls the gateway's ``gen.generate`` directly.
Several chips: the replicas register with an in-process registry and
the client routes through ``ServicePool(balancer="least")`` with
``SessionAffinity``, with fixed credits (a ``gen.generate`` call stays
open for a whole generation, which adaptive credits read as
congestion).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

RPC_TIMEOUT = 300.0


class Replica:
    def __init__(self, model, params, device, dep: Dict,
                 registry: Optional[str] = None):
        from repro.core.executor import Engine
        from repro.serve.engine import ServeEngine
        from repro.services import ServingGateway

        self.device = device
        self.serve = ServeEngine(
            model, params, max_len=dep["max_len"], n_slots=dep["n_slots"],
            impl=dep["impl"], chunk_tokens=dep["chunk_tokens"],
            session_cap=dep["session_cap"], device=device)
        self.rpc = Engine("tcp://127.0.0.1:0",
                          handler_threads=dep["handler_threads"])
        self.gateway = ServingGateway(self.rpc, self.serve,
                                      registry=registry,
                                      report_interval=0.2)
        self.uri = self.rpc.uri

    def close(self) -> None:
        """Stop the step loop, fail what the engine still holds (requests
        due after the window), then stop the RPC engine."""
        self.gateway.close()
        self.serve.fail_all("benchmark run over")
        self.rpc.shutdown()


class Stack:
    """Replicas, the client engine and the send path of one cell."""

    def __init__(self, model, params_per_device: List, devices: List,
                 dep: Dict):
        from repro.core.executor import Engine

        self.dep = dep
        self.client = Engine("tcp://127.0.0.1:0", handler_threads=4)
        self.registry_engine = self.registry = None
        self.pool = self.affinity = self.executor = None
        routed = len(devices) > 1
        reg_uri = None
        if routed:
            from repro.fabric import RegistryService
            self.registry_engine = Engine("tcp://127.0.0.1:0")
            self.registry = RegistryService(self.registry_engine,
                                            instance_ttl=30.0)
            reg_uri = self.registry_engine.uri
        self.replicas = [Replica(model, p, d, dep, registry=reg_uri)
                         for p, d in zip(params_per_device, devices)]
        if routed:
            self._connect_pool(reg_uri, len(devices))

    def _connect_pool(self, reg_uri: str, n: int) -> None:
        import concurrent.futures as cf
        from repro.fabric import (RetryPolicy, ServicePool,
                                  SessionAffinity)
        self.pool = ServicePool(
            self.client, reg_uri, "gen", balancer="least",
            credits_per_target=self.dep["handler_threads"],
            adaptive_credits=False,
            policy=RetryPolicy(attempts=1, rpc_timeout=RPC_TIMEOUT))
        deadline = time.monotonic() + 60.0
        while len(self.pool.replicas()) < n:
            if time.monotonic() > deadline:
                raise RuntimeError("replicas never all registered")
            time.sleep(0.05)
            self.pool.refresh(force=True)
        self.affinity = SessionAffinity(self.pool)
        self.executor = cf.ThreadPoolExecutor(
            max_workers=self.dep["handler_threads"],
            thread_name_prefix="bench-client")

    # ------------------------------------------------------------- sending
    def send(self, arg: Dict, on_done: Callable) -> None:
        """Send one ``gen.generate``; ``on_done(value, error, replica)``
        runs when the answer arrives."""
        arg = dict(arg, timeout=RPC_TIMEOUT)
        if self.pool is None:
            # a root span per call, sampled as the pool samples its own
            from repro.telemetry import trace
            root = trace.start_trace("bench.gen.generate")
            with trace.use(root.ctx):
                fut = self.client.call_async(self.replicas[0].uri,
                                             "gen.generate", arg,
                                             timeout=RPC_TIMEOUT)

            def done(f):
                err = f.exception()
                root.finish("OK" if err is None else "FAULT")
                on_done(None if err else f.result(), err, 0)
            fut.add_done_callback(done)
            return

        def routed():
            sid = arg.get("session_id")
            try:
                if sid is not None:
                    value, iid = self.affinity.call_routed(
                        sid, "gen.generate", arg, timeout=RPC_TIMEOUT)
                else:
                    value, iid = self.pool.call_routed(
                        "gen.generate", arg, timeout=RPC_TIMEOUT)
            except Exception as e:            # reported as a failure
                on_done(None, e, None)
                return
            on_done(value, None, iid)
        self.executor.submit(routed)

    def generate(self, arg: Dict, replica: int = 0) -> Dict:
        """One blocking call to one replica (warm-up)."""
        return self.client.call(self.replicas[replica].uri, "gen.generate",
                                dict(arg, timeout=RPC_TIMEOUT),
                                timeout=RPC_TIMEOUT)

    # --------------------------------------------------------------- stats
    def gateway_stats(self) -> List[Dict]:
        return [self.client.call(r.uri, "gen.stats", {}, timeout=30.0)
                for r in self.replicas]

    def affinity_stats(self) -> Optional[Dict]:
        return None if self.affinity is None else self.affinity.stats()

    def close(self) -> None:
        if self.executor is not None:
            self.executor.shutdown(wait=True)
        if self.pool is not None:
            self.pool.close()
        for r in self.replicas:
            r.close()
        if self.registry is not None:
            self.registry.close()
            self.registry_engine.shutdown()
        self.client.shutdown()

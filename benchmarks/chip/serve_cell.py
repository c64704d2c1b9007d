"""Serving cells: closest-concepts over HTTP, open loop, on the chip.

Set-up makes the table on the device from the seed, publishes it through
``EmbeddingRegistry.publish`` and stands up the deployment that
``launch/serve.py`` runs: ``ServingEngine`` (sharded over
``make_serving_mesh()`` where the host has several chips) ->
``Gateway`` -> ``serve_http``.  It compiles every scheduler bucket shape
and warms the result cache with the mix's own law.  The window is sent
by the client fleet; afterwards a seeded sample of the answers the
clients received is compared with the float64 reference.
"""
from __future__ import annotations

import contextlib
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import client, hoststats, traffic
from .spec import subseed


class GatewaySpans:
    """The benchmark's own span around ``Gateway.handle``: the HTTP layer
    calls ``server.gateway.handle``, so this stands in for the gateway
    and times each call (traced runs only)."""

    def __init__(self, gateway, annotate):
        self._gw = gateway
        self._annotate = annotate
        self.durations: List[float] = []

    def handle(self, route, payload=None, *, match=None):
        t = time.perf_counter()
        try:
            with self._annotate("gateway.handle"):
                return self._gw.handle(route, payload, match=match)
        finally:
            self.durations.append(time.perf_counter() - t)

    def __getattr__(self, name):
        return getattr(self._gw, name)


def make_table(n: int, dim: int, seed: int) -> np.ndarray:
    """The served float32 table, made on the device in one jitted call
    from the Xavier-uniform initial law U(-6/sqrt(d), 6/sqrt(d)), whatever
    the configuration's ``model``."""
    import jax
    import jax.numpy as jnp

    bound = 6.0 / np.sqrt(dim)
    make = jax.jit(lambda key: jax.random.uniform(
        key, (n, dim), jnp.float32, -bound, bound))
    return np.asarray(jax.device_get(make(jax.random.key(seed))))


def buckets(max_batch: int) -> List[int]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b <<= 1
    return out + [max_batch]


class Deployment:
    """One published table behind engine -> gateway -> HTTP."""

    def __init__(self, cfg: Dict[str, Any], seed: int, workdir: str,
                 spans: bool):
        import jax

        from repro.api import Gateway, serve_http
        from repro.core.registry import EmbeddingRegistry
        from repro.core.serving import ServingEngine
        from repro.launch.mesh import make_serving_mesh
        from repro.ontology import synthetic

        srv = cfg["serving"]
        self.ontology, self.model = cfg["ontology"], cfg["model"]
        self.k = int(srv["k"])
        kg = synthetic.generate(getattr(synthetic, cfg["profile"]),
                                seed=subseed(seed, "ontology"),
                                n_terms=int(cfg["n_terms"]))
        self.ids = list(kg.entities)
        self.labels = [kg.label_of(e) for e in self.ids]
        self.row_of = {e: i for i, e in enumerate(self.ids)}
        self.table = make_table(len(self.ids), int(cfg["dim"]),
                                subseed(seed, "weights"))
        self.registry = EmbeddingRegistry(workdir)
        version = "bench-1"
        self.registry.publish(self.ontology, version, self.model, self.ids,
                              self.labels, self.table, f"seed-{seed}",
                              {"dim": int(cfg["dim"])})
        self.registry.seal(self.ontology, version)
        self.engine = ServingEngine(self.registry, mesh=make_serving_mesh())
        self.gateway = Gateway(
            self.engine, max_batch=int(srv["max_batch"]),
            flush_after_ms=float(srv["flush_after_ms"]),
            max_pending=srv.get("max_pending"),
            result_cache_entries=int(srv["result_cache_entries"]),
            result_cache_bytes=int(srv["result_cache_bytes"]),
            max_jobs_queued=int(srv["max_jobs_queued"]))
        self.spans = (GatewaySpans(self.gateway, jax.profiler.TraceAnnotation)
                      if spans else None)
        self.server = serve_http(self.spans or self.gateway, port=0)
        self.path = (f"/closest-concepts/{self.ontology}/{self.model}"
                     f"?k={self.k}&query=")

    def warm(self, rows: np.ndarray) -> None:
        """Compile every bucket shape of the top-k path, then fill the
        result cache with ``rows`` in pages of one batch."""
        from repro.api.schema import ClosestConceptsRequest

        index = self.engine._index(self.ontology, self.model)
        for b in buckets(self.gateway.scheduler.max_batch):
            index.top_k_rows(list(range(b)), self.k)
        page = self.gateway.scheduler.max_batch
        for start in range(0, len(rows), page):
            self.gateway.closest_concepts_batch(
                [ClosestConceptsRequest(self.ontology, self.model,
                                        self.ids[int(r)], self.k)
                 for r in rows[start:start + page]])

    def counters(self) -> Dict[str, Dict[str, float]]:
        from repro.kernels import ops as kops

        cache = self.gateway.result_cache
        return {"scheduler": dict(self.gateway.scheduler.stats),
                "result_cache": dict(cache.stats()) if cache else {},
                "stream": dict(kops.stream_stats)}

    def close(self) -> None:
        self.server.close()
        self.gateway.close()


def _delta(after: Dict, before: Dict) -> Dict:
    return {g: {k: after[g][k] - before[g].get(k, 0)
                for k, v in after[g].items() if isinstance(v, (int, float))}
            for g in after}


def _answers(bodies: Dict[int, bytes], keep: np.ndarray):
    import json

    out = []
    for i in keep:
        try:
            wire = json.loads(bodies.get(int(i), b""))
            out.append([(h["identifier"], h["label"], float(h["score"]))
                        for h in wire["results"]])
        except (ValueError, KeyError, TypeError):
            out.append([])
    return out


def run_window(dep: Deployment, fleet: client.Fleet, mix: Dict[str, Any],
               rate: float, seconds: float, seed: int,
               trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """One open-loop window; returns the client record, counter deltas,
    the traced sub-window's counters and the checked sample."""
    import jax

    offsets, rows = traffic.schedule(mix, len(dep.ids), rate, seconds,
                                     subseed(seed, "traffic"))
    keep = traffic.check_sample(len(rows), int(mix["check_sample"]),
                                subseed(seed, "sample"))
    paths = [dep.path + dep.ids[int(r)] for r in rows]
    if dep.spans is not None:
        dep.spans.durations.clear()
    before = dep.counters()
    lead = 2.0 if trace_dir else 0.5
    t0 = time.monotonic() + lead
    traced: Dict[str, Any] = {}
    tracer = None
    if trace_dir:
        trace_s = min(float(mix["trace_seconds"]), seconds)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0

        def trace():
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                time.sleep(max(0.0, t0 - time.monotonic()))
                traced["before"] = dep.counters()
                with jax.profiler.TraceAnnotation("chipbench.window"):
                    time.sleep(trace_s)
                traced["after"] = dep.counters()
            finally:
                jax.profiler.stop_trace()

        tracer = threading.Thread(target=trace, name="chipbench-trace")
        tracer.start()
    host0 = hoststats.cpu_counters()
    with hoststats.GcPauses() as gcs, hoststats.Heartbeat() as beat:
        win = fleet.run(dep.server.port, t0, paths, offsets, keep,
                        wait_s=lead + seconds + 120.0)
    host = hoststats.counter_delta(hoststats.cpu_counters(), host0)
    stalls = win["stalls"] + [("parent", t, g) for t, g in beat.stalls]
    if tracer is not None:
        tracer.join()
    after = dep.counters()
    ok = win["status"] == 200
    rec = {"client": client.latency_summary(win),
           "latency_ms": (win["done"] - win["due"])[ok] * 1e3,
           "drain_s": float(np.max(win["done"]) - np.max(win["due"])),
           "counters": _delta(after, before),
           "requests": len(rows), "keep": keep, "rows": rows,
           "answers": _answers(win["bodies"], keep),
           "host": hoststats.describe(host, gcs.summary(), stalls, t0)}
    if traced:
        rec["trace_counters"] = _delta(traced["after"], traced["before"])
    if dep.spans is not None and dep.spans.durations:
        rec["spans"] = {"gateway_handle_p50_ms": client.percentile(
            np.asarray(dep.spans.durations) * 1e3, 50)}
    return rec


@contextlib.contextmanager
def deployment(cfg: Dict[str, Any], seed: int, spans: bool):
    with tempfile.TemporaryDirectory(prefix="chipbench-registry-") as root:
        dep = Deployment(cfg, seed, root, spans)
        try:
            yield dep
        finally:
            dep.close()

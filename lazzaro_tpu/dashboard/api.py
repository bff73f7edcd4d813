"""Dashboard HTTP API + live force-graph UI.

Parity target: reference ``dashboard/api.py`` (FastAPI, 142 LoC) — same route
surface:
  GET  /                 → HTML dashboard
  GET  /api/stats        → get_stats + user_id (after check_for_updates)
  GET  /api/users        → all user ids
  POST /api/users/switch → switch_user
  GET  /api/insights     → LLM insights
  GET  /api/export?format= → observations export
  GET  /api/graph        → {nodes, links} for the force graph
  GET  /api/profile      → profile domains
  POST /api/consolidate  → run_consolidation

Observability additions (ISSUE 6, no reference counterpart):
  GET  /metrics          → Prometheus text exposition of the system's
                           Telemetry registry (serving spans, device-side
                           readback counters, pad-waste, peak-HBM gauges)
  GET  /api/metrics      → the same registry as JSON
                           (``MemorySystem.metrics_summary()``)
  GET  /api/reliability  → the reliability layer's derived view (ISSUE 10:
                           circuit-breaker state, dispatch-retry / shed /
                           worker-restart counters, ingest-journal depth,
                           poisoned flag — ``reliability_summary()``)

Differences by design: built on stdlib ``http.server`` (zero extra deps in
this image; FastAPI optional elsewhere), and the UI is fully self-contained
vanilla JS + canvas (the reference pulls Vue/Tailwind/force-graph from CDNs,
which fails in offline deployments).
"""

from __future__ import annotations

import json
import os
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlparse

_ms = None
_ms_lock = threading.Lock()


def set_memory_system(ms) -> None:
    global _ms
    _ms = ms


def _template_path() -> str:
    return os.path.join(os.path.dirname(__file__), "templates", "index.html")


def _graph_payload(ms) -> dict:
    nodes, links = [], []
    for shard_key, shard in ms.shards.items():
        for node_id, node in shard.nodes.items():
            nodes.append({
                "id": node_id,
                "content": node.content,
                "type": node.type,
                "salience": node.salience,
                "shard": shard_key,
                "access_count": node.access_count,
                "is_super_node": node.is_super_node,
            })
        for (src, tgt), edge in shard.edges.items():
            links.append({
                "source": src,
                "target": tgt,
                "weight": edge.weight,
                "type": edge.edge_type,
            })
    for node_id, node in ms.super_nodes.items():
        nodes.append({
            "id": node_id,
            "content": node.content,
            "type": "super_node",
            "salience": node.salience,
            "shard": "global",
            "is_super_node": True,
        })
    return {"nodes": nodes, "links": links}


class DashboardHandler(BaseHTTPRequestHandler):
    def log_message(self, fmt, *args):  # quiet by default
        pass

    def _send(self, payload, status=200, content_type="application/json"):
        if isinstance(payload, (dict, list)):
            body = json.dumps(payload).encode()
        elif isinstance(payload, str):
            body = payload.encode()
        else:
            body = payload
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        url = urlparse(self.path)
        ms = _ms
        if url.path == "/":
            try:
                with open(_template_path()) as f:
                    self._send(f.read(), content_type="text/html")
            except FileNotFoundError:
                self._send("dashboard template missing", 500, "text/plain")
            return
        if ms is None:
            self._send({"error": "Memory system not initialized"}, 503)
            return
        with _ms_lock:
            if url.path == "/metrics":
                # Prometheus scrape surface: the SAME registry
                # metrics_summary() reads, rendered as text exposition —
                # plus the derived headline gauges so a scrape alone
                # carries the pad-waste/queue-wait numbers CI checks.
                summary = ms.metrics_summary()
                extra = []
                for key in ("pad_waste_fraction", "queue_wait_ms_p50",
                            "queue_wait_ms_p95", "serve_dispatches",
                            "ingest_dispatches", "link_pool_overflows"):
                    val = summary.get(key)
                    if val is not None:
                        extra.append(f"lazzaro_{key} {val}")
                # Paged arena (ISSUE 17): page occupancy headline — the
                # arena.pages_* gauges also ride the registry exposition
                # above; these derived rows carry the free-list totals.
                paged = summary.get("paged_arena")
                if paged:
                    for key in ("pages_total", "pages_free",
                                "fragmentation", "pops_total",
                                "pushes_total"):
                        extra.append(
                            f"lazzaro_arena_{key} {paged[key]}")
                body = ms.telemetry.prometheus()
                if extra:
                    body += "\n".join(extra) + "\n"
                self._send(body,
                           content_type="text/plain; version=0.0.4; "
                                        "charset=utf-8")
            elif url.path == "/api/metrics":
                self._send(ms.metrics_summary())
            elif url.path == "/api/reliability":
                self._send(ms.reliability_summary())
            elif url.path == "/api/stats":
                ms.check_for_updates()
                stats = ms.get_stats()
                stats["user_id"] = ms.user_id
                self._send(stats)
            elif url.path == "/api/users":
                self._send(ms.get_all_users())
            elif url.path == "/api/insights":
                self._send({"insights": ms.get_insights()})
            elif url.path == "/api/export":
                fmt = parse_qs(url.query).get("format", ["markdown"])[0]
                self._send({"content": ms.export_observations(format=fmt)})
            elif url.path == "/api/graph":
                ms.check_for_updates()
                self._send(_graph_payload(ms))
            elif url.path == "/api/profile":
                self._send({"profile": ms.profile.data,
                            "last_updated": ms.profile.last_updated})
            else:
                self._send({"error": "not found"}, 404)

    def do_POST(self):
        url = urlparse(self.path)
        ms = _ms
        if ms is None:
            self._send({"error": "Memory system not initialized"}, 503)
            return
        length = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(length) if length else b"{}"
        try:
            data = json.loads(raw or b"{}")
        except json.JSONDecodeError:
            self._send({"error": "invalid JSON body"}, 400)
            return
        with _ms_lock:
            if url.path == "/api/users/switch":
                new_user = data.get("user_id")
                if not new_user:
                    self._send({"error": "User ID required"}, 400)
                    return
                ms.switch_user(new_user)
                self._send({"status": "success", "user_id": ms.user_id})
            elif url.path == "/api/consolidate":
                result = ms.run_consolidation()
                self._send({"status": "success", "result": result})
            else:
                self._send({"error": "not found"}, 404)


def make_server(ms, host: str = "0.0.0.0", port: int = 5299) -> ThreadingHTTPServer:
    set_memory_system(ms)
    return ThreadingHTTPServer((host, port), DashboardHandler)


def entry_point(host: str = "0.0.0.0", port: int = 5299,
                db_dir: str = "db") -> None:
    # Runs on the default backend like any other MemorySystem process. A
    # chip has one owner: beside a serving process that holds the chip,
    # start the dashboard with JAX_PLATFORMS=cpu.
    from lazzaro_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()

    from lazzaro_tpu.core.memory_system import MemorySystem

    ms = MemorySystem(load_from_disk=True, db_dir=db_dir)
    server = make_server(ms, host, port)
    print(f"📊 lazzaro-tpu dashboard on http://{host}:{port}")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.shutdown()
        ms.close()


if __name__ == "__main__":
    entry_point()

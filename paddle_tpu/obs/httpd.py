"""Zero-dependency HTTP exposition for the health plane.

A stdlib :class:`http.server.ThreadingHTTPServer` on a daemon thread,
serving three read-only endpoints off the live obs bundle:

- ``/metrics``  — Prometheus text exposition from the metric registry
- ``/healthz``  — liveness + last-step staleness (200 ok / 503 stale)
- ``/statusz``  — JSON: build info, SLO table, pool/occupancy
  providers, heartbeats, event-log position

Gated by ``PT_OBS_HTTP=<port>`` (auto-started when the telemetry
bundle is built with that set); tests start one explicitly on an
ephemeral port via :func:`start` / ``port=0``.  The handler resolves
``obs.handle()`` lazily per request, so a scrape while telemetry is
off gets a clean 503 instead of a crash, and ``obs.configure`` swaps
under a running server without a restart.

Every request is bracketed by the ``obs.http`` fault point; an armed
``raise`` surfaces as a 500 response and the NEXT request succeeds —
the serving process must never die because monitoring hiccuped.
"""
from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


class _Handler(BaseHTTPRequestHandler):
    # quiet: a scrape per second must not spam stderr
    def log_message(self, fmt, *args):
        pass

    def _send(self, code, body, content_type="application/json"):
        data = body.encode() if isinstance(body, str) else body
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def _send_json(self, code, payload):
        self._send(code, json.dumps(payload, default=str, indent=1))

    def do_GET(self):
        from ..testing.faults import fire

        try:
            fire("obs.http", "before", path=self.path)
            self._route()
            fire("obs.http", "after", path=self.path)
        except Exception as e:
            # one bad request (injected or organic) must not take the
            # server down; report and keep listening
            try:
                self._send_json(500, {"error": repr(e)})
            except Exception:
                pass

    def _route(self):
        from .. import obs
        from . import health

        path = self.path.split("?", 1)[0]
        h = obs.handle()
        if h is None:
            self._send_json(503, {"error": "telemetry off (PT_OBS)"})
            return
        if path == "/metrics":
            self._send(200, h.registry.prometheus_text(),
                       content_type=PROMETHEUS_CONTENT_TYPE)
        elif path == "/healthz":
            ok, payload = health.healthz_payload(h)
            self._send_json(200 if ok else 503, payload)
        elif path == "/statusz":
            self._send_json(200, health.statusz_payload(h))
        else:
            self._send_json(404, {
                "error": f"no route {path!r}",
                "routes": ["/metrics", "/healthz", "/statusz"]})


class ObsHTTPServer:
    """The background exposition server; one per obs bundle."""

    def __init__(self, port=0, host="127.0.0.1"):
        self._server = ThreadingHTTPServer((host, port), _Handler)
        self._server.daemon_threads = True
        self.host = host
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name=f"pt-obs-httpd:{self.port}", daemon=True)
        self._thread.start()

    @property
    def url(self):
        return f"http://{self.host}:{self.port}"

    def stop(self):
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5)


def start(port=0, host="127.0.0.1"):
    """Start (or return the already-running) exposition server for the
    live bundle.  ``port=0`` binds an ephemeral port (tests).  Returns
    the :class:`ObsHTTPServer`, or ``None`` when telemetry is off."""
    from .. import obs

    h = obs.handle()
    if h is None:
        return None
    if h.httpd is None:
        h.httpd = ObsHTTPServer(port=port, host=host)
    return h.httpd


def stop():
    """Stop the live bundle's server, if any."""
    from .. import obs

    h = obs.handle()
    if h is not None and h.httpd is not None:
        h.httpd.stop()
        h.httpd = None

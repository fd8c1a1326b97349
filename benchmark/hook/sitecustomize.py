"""Benchmark-side hook inside the dbnode child (the only process that holds
the chip, hence the only one that can trace it or read its memory).

Active only when ``M3BENCH_HOOK_DIR`` is set: the benchmark puts this
directory on the child's ``PYTHONPATH`` and Python imports
``sitecustomize`` at start-up. The hook listens on a localhost TCP port
(written to ``<dir>/hook.port``) for one-line JSON commands:

- ``{"cmd": "stat"}``         compiles seen so far (jax.monitoring's
                              backend-compile events: every program the
                              process lowered and compiled or fetched from
                              the persistent cache), seconds in them, cache
                              hits, and the fullest device's peak bytes
- ``{"cmd": "trace_start", "dir": ...}`` / ``{"cmd": "trace_stop"}``
                              jax.profiler around a window

The program has no profiler op of its own yet (PERF.md section 7, for the
``tracing`` issue); this file is benchmark code and changes nothing the
program computes.
"""

import os

_DIR = os.environ.get("M3BENCH_HOOK_DIR")

if _DIR:
    import json
    import socketserver
    import threading

    # imported HERE, on the main thread, before the program starts: a
    # first `import jax` racing on another thread leaves jax half made
    import jax
    from jax import monitoring

    _STATE = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0, "tracing": False}
    _NAMES = {}  # program name -> backend compiles
    _LOCK = threading.Lock()

    def _on_duration(event, duration, fun_name="?", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            with _LOCK:
                _STATE["compiles"] += 1
                _STATE["compile_s"] += float(duration)
                _NAMES[fun_name] = _NAMES.get(fun_name, 0) + 1

    def _on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            with _LOCK:
                _STATE["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)

    def _stat():
        peak = in_use = None
        for d in jax.local_devices():
            ms = d.memory_stats() or {}
            if "peak_bytes_in_use" in ms:
                peak = max(peak or 0, int(ms["peak_bytes_in_use"]))
                in_use = max(in_use or 0, int(ms.get("bytes_in_use", 0)))
        with _LOCK:
            out = dict(_STATE, names=dict(_NAMES))
        out.update(peak_bytes=peak, bytes_in_use=in_use, pid=os.getpid())
        return out

    def _handle(req):
        cmd = req.get("cmd")
        if cmd == "stat":
            return _stat()
        if cmd == "trace_start":
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the host's Python stays untraced
            opts.host_tracer_level = 1
            jax.profiler.start_trace(req["dir"], profiler_options=opts)
            _STATE["tracing"] = True
            return {"ok": True}
        if cmd == "trace_stop":
            if _STATE["tracing"]:
                jax.profiler.stop_trace()
                _STATE["tracing"] = False
            return {"ok": True}
        return {"error": f"unknown cmd {cmd!r}"}

    class _Handler(socketserver.StreamRequestHandler):
        def handle(self):
            for line in self.rfile:
                try:
                    resp = _handle(json.loads(line))
                except Exception as exc:  # reported to the benchmark, which fails the run
                    resp = {"error": f"{type(exc).__name__}: {exc}"}
                self.wfile.write((json.dumps(resp) + "\n").encode())
                self.wfile.flush()

    class _Server(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    _srv = _Server(("127.0.0.1", 0), _Handler)
    threading.Thread(target=_srv.serve_forever, daemon=True,
                     name="m3bench-hook").start()
    _tmp = os.path.join(_DIR, "hook.port.tmp")
    with open(_tmp, "w") as _f:
        _f.write(str(_srv.server_address[1]))
    os.replace(_tmp, os.path.join(_DIR, "hook.port"))

"""JAX backend discovery, the compile cache, and CPU-only child processes.

``probe_backend()`` resolves ``jax.default_backend()`` once per process in a
daemon thread with a timeout: the registry contract is that a codec returns
-errno, it never hangs (the reference even ships a hanging-plugin test
fixture, TestErasureCodePlugin.cc:31-76), and PJRT client creation is
native code that cannot be cancelled.  A probe that FAILS or TIMES OUT pins
"unavailable" for the life of the process — callers then take their CPU
path — and says so in the log with the traceback, so a chip that was
expected and did not come up is never a silent CPU run.

``enable_compile_cache()`` places JAX's persistent compilation cache;
``cpu_child_env()`` is the environment for a child that must stay off the
accelerator (a chip belongs to one process at a time).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

log = logging.getLogger("ceph_tpu.jaxdev")

_lock = threading.Lock()
_result: Optional[str] = None
_error: Optional[BaseException] = None

UNAVAILABLE = "unavailable"


def probe_error() -> Optional[BaseException]:
    """The exception that made probe_backend() return UNAVAILABLE, if the
    probe failed with an error rather than a timeout."""
    return _error


def probe_backend(timeout: Optional[float] = None) -> str:
    """Return jax's default backend name ("tpu", "cpu", ...) or
    "unavailable" if backend init fails or does not finish in time."""
    global _result
    with _lock:
        if _result is not None:
            return _result
        if timeout is None:
            timeout = float(os.environ.get("CEPH_TPU_PROBE_TIMEOUT", "30"))
        box = {}

        def _probe() -> None:
            try:
                import jax

                box["backend"] = jax.default_backend()
            except Exception as e:  # import or init failure
                box["error"] = e

        th = threading.Thread(target=_probe, daemon=True, name="jax-probe")
        th.start()
        th.join(timeout)
        global _error
        _error = box.get("error")
        _result = box.get("backend", UNAVAILABLE)
        if _error is not None:
            log.error("jax backend init failed; device paths are off for "
                      "this process", exc_info=_error)
        elif _result == UNAVAILABLE:
            log.error("jax backend init did not finish in %.0fs; device "
                      "paths are off for this process "
                      "(CEPH_TPU_PROBE_TIMEOUT)", timeout)
        return _result


def backend_available() -> bool:
    return probe_backend() != UNAVAILABLE


def accelerator_live() -> bool:
    """Whether this process serves from an accelerator: the one question
    the queue, the resident store, the slab kernels and the mesh ask
    before they engage.  JAX_PLATFORMS=cpu (tests, CPU-only deployments)
    answers without importing jax; otherwise the probe does, and a live
    device switches the persistent compile cache on before anything is
    compiled for it."""
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return False
    if probe_backend() in ("cpu", UNAVAILABLE):
        return False
    enable_compile_cache()
    return True


_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_cache_dir: Optional[str] = None


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, nothing is set
    in code.  Otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
    (the path is part of the cache's key: a temp name, pid or time would
    never hit).  Called by accelerator_live() — where the served path
    first decides a device is live — and by the chip entry points;
    CPU-forced test runs never reach it.  Idempotent."""
    global _cache_dir
    if _cache_dir is None:
        env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if env:
            _cache_dir = env
        else:
            import jax

            _cache_dir = os.path.join(_REPO, ".jax_cache")
            jax.config.update("jax_compilation_cache_dir", _cache_dir)
    return _cache_dir


class CompileMeter:
    """Process-wide tally of XLA backend compiles, fed by jax.monitoring:
    how many programs were asked for, the seconds that took (a persistent-
    cache hit is asked for too, and takes milliseconds), and how many were
    such hits.  ``thread_seconds()`` is the calling thread's share —
    what the batching queue's watchdog subtracts, so a dispatch that
    contained its first compile is not read as a sick lane."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._tls = threading.local()

    def _on_duration(self, name: str, secs: float, **_kw) -> None:
        if name == self._COMPILE:
            with _meter_lock:
                self.count += 1
                self.seconds += secs
            self._tls.seconds = self.thread_seconds() + secs

    def _on_event(self, name: str, **_kw) -> None:
        if name == self._CACHE_HIT:
            with _meter_lock:
                self.cache_hits += 1

    def thread_seconds(self) -> float:
        return getattr(self._tls, "seconds", 0.0)

    def snapshot(self) -> dict:
        return {"compiles": self.count,
                "compile_s": round(self.seconds, 3),
                "cache_hits": self.cache_hits}


_meter: Optional[CompileMeter] = None
_meter_lock = threading.Lock()


def compile_meter() -> CompileMeter:
    """The process CompileMeter, registered with jax.monitoring on first
    use (imports jax: only device paths call this)."""
    global _meter
    if _meter is None:
        from jax import monitoring

        with _meter_lock:
            if _meter is None:
                meter = CompileMeter()
                monitoring.register_event_duration_secs_listener(
                    meter._on_duration)
                monitoring.register_event_listener(meter._on_event)
                _meter = meter
    return _meter


def cpu_child_env(n_cpu_devices: Optional[int] = None) -> dict:
    """Copy of os.environ for a CPU-only child process: JAX_PLATFORMS=cpu
    and, optionally, a forced virtual host device count."""
    out = dict(os.environ)
    out["JAX_PLATFORMS"] = "cpu"
    if n_cpu_devices is not None:
        kept = [
            f
            for f in out.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        out["XLA_FLAGS"] = " ".join(
            kept + [f"--xla_force_host_platform_device_count={n_cpu_devices}"]
        )
    return out

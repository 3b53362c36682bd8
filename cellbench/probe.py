"""What the harness observes by itself: the process's age, the device JAX
reports, compilations (``jax.monitoring``), peak device memory."""

from __future__ import annotations

import os
import time

from cellbench.trace import measure, union

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def process_age_s() -> float:
    """Seconds since this process was created (interpreter start-up and
    imports included), from the kernel's own record."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class CompileLog:
    """Every trace, lowering and backend compile (a persistent-cache hit is
    one too: JAX reports the span around ``compile_or_get_cached``) with its
    wall-clock span and function name — the ``chip_smoke.py`` listener, kept
    as spans so that a window can ask what compiled inside it."""

    def __init__(self):
        import jax.monitoring

        self.spans = []   # (event, fun_name, start, end), time.time() seconds
        self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, name, start, end, **kw):
        if name in (TRACE, LOWER, BACKEND):
            self.spans.append((name, str(kw.get("fun_name", "?")), start, end))

    def _event(self, name, **_kw):
        if name == CACHE_HIT:
            self.cache_hits += 1
        elif name == CACHE_MISS:
            self.cache_misses += 1

    def inside(self, t0: float, t1: float) -> list:
        """Function names that were traced, lowered or compiled in [t0, t1]."""
        return sorted({fn for _, fn, a, b in self.spans if b > t0 and a < t1})

    def counters(self) -> dict:
        """Seconds spent so far: the measure of the union of spans (a jit
        traced inside another's trace reports both)."""
        pick = lambda *ev: measure(union(  # noqa: E731
            (a, b) for e, _, a, b in self.spans if e in ev))
        return {
            "trace_lower_s": pick(TRACE, LOWER),
            "backend_compile_s": pick(BACKEND),
            "programs": len({fn for e, fn, _, _ in self.spans if e == BACKEND}),
            "cache_hits": self.cache_hits, "cache_misses": self.cache_misses,
        }


def require_devices(chips: int, rehearsal: bool):
    """The devices the cell runs on, or ``SystemExit``: a TPU with at least
    ``chips`` chips. Only ``--rehearsal`` accepts the CPU, and then only the
    CPU: a rehearsal on a chip would print no metrics for a real run."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearsal:
        if platform != "cpu":
            raise SystemExit(f"--rehearsal is for the CPU; JAX found {platform!r}")
    elif platform != "tpu":
        raise SystemExit(
            f"cellbench needs 'tpu'; JAX found {platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). There is no "
            "CPU fallback; a tiny-size CPU run needs --rehearsal.")
    if len(devices) < chips:
        raise SystemExit(f"cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest of ``devices`` since the process
    began (a lifetime maximum), where the backend reports one."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def device_line(devices, all_devices: int, memory_peak_bytes: int | None) -> dict:
    """``device`` of the last line, as JAX reports it."""
    return {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": all_devices, "memory_peak_bytes": memory_peak_bytes,
    }


class Clock:
    """One reading of both clocks: ``perf`` for durations, ``wall`` to place
    a window among the compile spans."""

    def __init__(self):
        self.perf, self.wall = time.perf_counter(), time.time()

"""Seconds JAX spends compiling, from its own monitoring events.

The listener is copied from ``chip_smoke.py``'s ``run_phase``: JAX
reports the duration of each trace, lowering and backend compile
through ``jax.monitoring``, and the sum is the compile part of a run.
The harness reads it around the measured window to show that nothing
compiles there.
"""

from __future__ import annotations

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Running total of compile seconds and backend compiles."""

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0

    def on_event(self, event: str, seconds: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += seconds
            if event == COMPILE_EVENTS[-1]:
                self.backend_compiles += 1

    def snapshot(self):
        return self.seconds, self.backend_compiles


_SHARED: list = []


def shared() -> CompileClock:
    """The process's one clock, registered with JAX on first use (a
    listener cannot be unregistered, so runs in one process share it)."""
    if not _SHARED:
        import jax

        clock = CompileClock()
        jax.monitoring.register_event_duration_secs_listener(
            clock.on_event)
        _SHARED.append(clock)
    return _SHARED[0]
